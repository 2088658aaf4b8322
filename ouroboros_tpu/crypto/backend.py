"""CryptoBackend — the batched-verification seam of the whole framework.

Reference seam being generalised: the `StandardCrypto` associated-type bundle
(Shelley/Protocol/Crypto.hs:15-23) reached through typeclass indirection from
`updateChainDepState` (VRF+KES per header) and `applyLedgerBlock` (Ed25519
witness multi-verify per body) — SURVEY.md §2 "The TPU-relevant gap": the
reference verifies strictly sequentially; nothing batches independent proofs.

This trait makes batching first-class.  All three request kinds are *batch*
APIs returning a boolean vector; consensus code collects independent proofs
from a window of headers/blocks and calls one of these once per window
(consensus/batch_validation.py drives it).

Backends:
- CpuRefBackend     — pure-Python (edwards.py); ground truth, slow.
- OpensslBackend    — `cryptography` Ed25519 (libsodium-class C speed) for
                      the signature leaves; VRF still pure-Python.
- JaxBackend        — batched device kernels (ed25519_jax.py), host does
                      hashing/decompression, device does the group math;
                      shards across a mesh via parallel/sharded_verify.py.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from typing import Optional, Sequence

from ..observe import metrics as _metrics
from . import ed25519_ref, kes as kes_mod, vrf_ref

# betas the sequential pass had to compute itself, on the host: a proof
# neither a prefetch nor a drained window's carried rows had delivered
# (in a pipelined replay: 0, unless the producer ran ahead of the carry)
_BETA_HOST_COMPUTES = _metrics.counter("beta_cache.host_computes")


@dataclass(frozen=True)
class Ed25519Req:
    vk: bytes        # 32B verification key
    msg: bytes
    sig: bytes       # 64B


class Ed25519Cols:
    """A run of Ed25519 requests as three parallel columns: `vks[j]`,
    `msgs[j]` and `sigs[j]` are lane j's key, message and signature, and
    the lane stands for `Ed25519Req(vks[j], msgs[j], sigs[j])`.

    The form a block body's witnesses cross from the ledger pass to the
    packers in: a ledger's `extract_proofs` makes ONE a block, of the
    bytes the decoded transactions already hold, whatever their length,
    and no object a witness.  In a request stream it is one ITEM that
    counts for `len` requests, in the block's order, where a request
    object counts for one (`lane_count`, `request_at`); a splitter joins
    its columns to the Ed25519 group whole, and the group a splitter
    returns is one of these too.  Iterated or indexed it gives the
    requests it stands for, so a reader that knows only request objects
    is served."""

    __slots__ = ("vks", "msgs", "sigs")

    def __init__(self, vks, msgs, sigs):
        self.vks = vks
        self.msgs = msgs
        self.sigs = sigs

    @classmethod
    def of_witnesses(cls, txs) -> "Ed25519Cols":
        """Every `(vk, sig)` of each transaction's `witnesses` over that
        transaction's `txid`, in transaction-then-witness order: a block
        body's lanes.  One walk, each row touched once; a transaction
        with one witness (most of a chain) takes no inner loop."""
        vks: list = []
        msgs: list = []
        sigs: list = []
        for tx in txs:
            wits = tx.witnesses
            if len(wits) == 1:
                vk, sig = wits[0]
                vks.append(vk)
                msgs.append(tx.txid)
                sigs.append(sig)
            elif wits:
                txid = tx.txid
                for vk, sig in wits:
                    vks.append(vk)
                    msgs.append(txid)
                    sigs.append(sig)
        return cls(vks, msgs, sigs)

    def append(self, vk: bytes, msg: bytes, sig: bytes) -> None:
        """One more lane (a splitter building its group)."""
        self.vks.append(vk)
        self.msgs.append(msg)
        self.sigs.append(sig)

    def extend(self, cols: "Ed25519Cols") -> None:
        """Another run's lanes after this one's, column by column."""
        self.vks.extend(cols.vks)
        self.msgs.extend(cols.msgs)
        self.sigs.extend(cols.sigs)

    def __len__(self) -> int:
        return len(self.vks)

    def __getitem__(self, j: int) -> Ed25519Req:
        return Ed25519Req(self.vks[j], self.msgs[j], self.sigs[j])

    def __iter__(self):
        return map(Ed25519Req, self.vks, self.msgs, self.sigs)

    def __eq__(self, other) -> bool:
        """Equal to any sequence that holds the same requests."""
        try:
            return len(self) == len(other) and all(
                a == b for a, b in zip(self, other))
        except TypeError:
            return NotImplemented

    __hash__ = None


def ed25519_columns(reqs) -> tuple:
    """(vks, msgs, sigs) of an Ed25519 group: the columns themselves of
    an `Ed25519Cols` (not to be changed), three new lists of a sequence
    of `Ed25519Req`."""
    if isinstance(reqs, Ed25519Cols):
        return reqs.vks, reqs.msgs, reqs.sigs
    return ([r.vk for r in reqs], [r.msg for r in reqs],
            [r.sig for r in reqs])


def lane_count(items) -> int:
    """The requests a stream of items stands for: `len` of a columns
    item, one of a request object."""
    return sum(len(it) if isinstance(it, Ed25519Cols) else 1
               for it in items)


def iter_requests(items):
    """The request objects a stream of items stands for, in order (a
    columns item's are made here)."""
    for it in items:
        if isinstance(it, Ed25519Cols):
            yield from it
        else:
            yield it


def request_at(items, j: int):
    """Request `j` of a stream of items, as a request object."""
    for it in items:
        n = len(it) if isinstance(it, Ed25519Cols) else 1
        if j < n:
            return it[j] if isinstance(it, Ed25519Cols) else it
        j -= n
    raise IndexError("request index out of range")


@dataclass(frozen=True)
class VrfReq:
    vk: bytes        # 32B
    alpha: bytes     # VRF input
    proof: bytes     # 80B


@dataclass(frozen=True)
class KesReq:
    depth: int
    vk: bytes        # 32B root hash
    period: int
    msg: bytes
    sig_bytes: bytes


@dataclass(frozen=True)
class WindowVerdict:
    """Folded window verdict: what finish_window returns when the window
    was submitted with `fold=True` (device-side verdict reduction).

    Instead of a per-proof boolean vector crossing the host<->device
    link, the fused window program folds ok-flags on device and returns
    only the FIRST failing request's index (None = every proof held).
    `first_bad` indexes the requests the submitted items stand for (a
    columns item counts for `len` of them), so a replay driver looks it
    up in its per-block lane counts: requests lie block after block,
    making the first bad request also the first bad block."""
    n: int
    first_bad: Optional[int] = None

    @property
    def all_ok(self) -> bool:
        return self.first_bad is None

    def as_bools(self) -> list:
        """Degraded vector view: True everywhere except first_bad.  Only
        exact when at most one request failed — callers needing the full
        vector must submit with fold=False."""
        out = [True] * self.n
        if self.first_bad is not None:
            out[self.first_bad] = False
        return out


class CryptoBackend:
    """Batch verification interface. Implementations must be bit-exact."""

    name = "abstract"
    # True on backends whose submit_window/pack_window accept fold=True
    # (device-side verdict reduction — consensus/pipeline.py asks)
    supports_window_fold = False

    def verify_ed25519_batch(self, reqs: Sequence[Ed25519Req]) -> list[bool]:
        raise NotImplementedError

    def verify_vrf_batch(self, reqs: Sequence[VrfReq]) -> list[bool]:
        raise NotImplementedError

    def verify_kes_batch(self, reqs: Sequence[KesReq]) -> list[bool]:
        """Default: host hash-path check + ed25519 batch on the leaves
        (the reduction lives in split_mixed)."""
        ed_reqs, ed_owner, _v, _vo, n = self.split_mixed(reqs)
        out = [False] * n
        if ed_reqs:
            for i, ok in zip(ed_owner, self.verify_ed25519_batch(ed_reqs)):
                out[i] = bool(ok)
        return out

    # -- mixed batches --------------------------------------------------------
    def _split_mixed_loop(self, reqs: Sequence, kes_leaf):
        """Shared dispatch skeleton of the host split variants: group
        Ed25519/VRF requests, reduce each KES request through
        `kes_leaf(req) -> (leaf_vk, leaf_sig) | None` (None = the hash
        path is invalid / known-bad, request stays False).

        `reqs` is a stream of items: a columns item joins the Ed25519
        group's three columns whole and answers for the run of request
        indices it stands for; a request object goes into the same
        columns, one lane."""
        ed_reqs = Ed25519Cols([], [], [])
        ed_owner: list[int] = []
        vrf_reqs: list = []
        vrf_owner: list[int] = []
        n = 0                  # requests the items walked so far stand for
        for r in reqs:
            i = n              # the item's first request index
            if isinstance(r, Ed25519Cols):
                n += len(r)
                ed_reqs.extend(r)
                ed_owner.extend(range(i, n))
                continue
            n += 1
            if isinstance(r, Ed25519Req):
                ed_reqs.append(r.vk, r.msg, r.sig)
                ed_owner.append(i)
            elif isinstance(r, VrfReq):
                vrf_reqs.append(r)
                vrf_owner.append(i)
            elif isinstance(r, KesReq):
                leaf = kes_leaf(r)
                if leaf is None:
                    continue          # stays False
                leaf_vk, leaf_sig = leaf
                ed_reqs.append(leaf_vk, r.msg, leaf_sig)
                ed_owner.append(i)
            else:
                raise TypeError(f"unknown proof request type {type(r)}")
        return ed_reqs, ed_owner, vrf_reqs, vrf_owner, n

    def split_mixed(self, reqs: Sequence):
        """Host-side split of a mixed stream of items: KES requests are
        reduced to their Ed25519 leaf checks (hash-path verification
        happens here) and merged into the Ed25519 group, so a mixed window
        costs ONE Ed25519 batch + ONE VRF batch instead of three calls.

        Returns (ed_reqs, ed_owner, vrf_reqs, vrf_owner, n): the Ed25519
        group as one `Ed25519Cols`, owner mapping each grouped lane back
        to the index of the request it answers for, n the requests the
        items stand for."""
        def kes_leaf(r):
            try:
                sig = kes_mod.KesSig.from_bytes(r.depth, r.sig_bytes)
            except ValueError:
                return None
            return kes_mod.verify_prepare(r.depth, r.vk, r.period, sig)
        return self._split_mixed_loop(reqs, kes_leaf)

    def split_mixed_cached(self, reqs: Sequence, cache=None):
        """split_mixed with cross-window KES hash-path memoisation.

        Same return shape as split_mixed, but each KES request's Blake2b
        Merkle walk is looked up in the precomputation cache first
        (keyed by kes.hash_path_key — message-independent): warm paths
        skip the host hashing entirely, cold paths hash once and record
        the outcome.  The sharded mesh backend threads its windows
        through this (the single-chip JaxBackend goes further and runs
        cold paths as device Blake2b jobs — jax_backend.py)."""
        from .precompute import GLOBAL_PRECOMPUTE_CACHE, KES_HOST_WALKS
        cache = cache if cache is not None else GLOBAL_PRECOMPUTE_CACHE

        def kes_leaf(r):
            key = kes_mod.hash_path_key(r.depth, r.vk, r.period,
                                        r.sig_bytes)
            if key is None:
                return None           # structurally invalid
            ent = cache.kes_get(key)
            if ent is None:
                KES_HOST_WALKS.inc()
                sig = kes_mod.KesSig.from_bytes(r.depth, r.sig_bytes)
                prep = kes_mod.verify_prepare(r.depth, r.vk, r.period,
                                              sig)
                ent = ((prep[0], True) if prep is not None
                       else (None, False))
                cache.kes_put(key, *ent)
            leaf_vk, path_ok = ent
            if not path_ok:
                return None           # known-bad hash path
            return leaf_vk, r.sig_bytes[:64]
        return self._split_mixed_loop(reqs, kes_leaf)

    def verify_mixed(self, reqs: Sequence) -> list[bool]:
        """Verify a mixed stream of Ed25519/VRF/KES items; one verdict a
        request the items stand for, in order."""
        ed_reqs, ed_owner, vrf_reqs, vrf_owner, n = self.split_mixed(reqs)
        out = [False] * n
        for i, ok in zip(ed_owner, self.verify_ed25519_batch(ed_reqs)):
            out[i] = bool(ok)
        for i, ok in zip(vrf_owner, self.verify_vrf_batch(vrf_reqs)):
            out[i] = bool(ok)
        return out

    # VRF outputs (beta) for leader election — host-side, cheap
    def vrf_proof_to_hash(self, proof: bytes) -> bytes:
        return vrf_ref.proof_to_hash(proof)

    def vrf_betas_batch(self, proofs: Sequence[bytes]) -> list:
        """Batched proof_to_hash; None where the proof does not decode.
        Device backends override with one kernel call (the seq-pass beta
        prefetch of consensus/batch.py rides on this)."""
        out = []
        for pi in proofs:
            try:
                out.append(vrf_ref.proof_to_hash(pi))
            except ValueError:
                out.append(None)
        return out


_MISSING = object()


class VrfBetaCache:
    """proof bytes -> beta (proof_to_hash) memo with batched prefetch.

    The sequential pass of window validation needs the VRF output of every
    header (leader-threshold check, nonce evolution) — per-proof host EC
    math there costs more than the whole device batch.  Protocols own one
    of these; the batch driver prefetches a window's proofs in one
    backend.vrf_betas_batch call before the sequential fold."""

    def __init__(self, max_entries: int = 200_000):
        self._cache: dict = {}
        self.max_entries = max_entries

    def __contains__(self, proof: bytes) -> bool:
        return proof in self._cache

    def get(self, proof: bytes) -> bytes:
        """Beta for the proof; raises ValueError exactly where
        vrf_ref.proof_to_hash does."""
        v = self._cache.get(proof, _MISSING)
        if v is _MISSING:
            _BETA_HOST_COMPUTES.inc()
            try:
                v = vrf_ref.proof_to_hash(proof)
            except ValueError:
                v = None
            self._store(proof, v)
        if v is None:
            raise ValueError("invalid proof")
        return v

    def prefetch(self, proofs: Sequence[bytes],
                 backend: "CryptoBackend") -> None:
        todo = [p for p in dict.fromkeys(proofs) if p not in self._cache]
        if not todo:
            return
        for p, b in zip(todo, backend.vrf_betas_batch(todo)):
            self._store(p, b)

    def _store(self, proof: bytes, beta) -> None:
        if len(self._cache) >= self.max_entries:
            # evict the oldest half (insertion order), never the entries
            # just prefetched for the in-flight window; pop-with-default
            # because the pipelined replay's producer (miss-path get) and
            # consumer (store_many at drain) may both evict concurrently
            # over stale key snapshots
            drop = len(self._cache) // 2
            for k in list(self._cache)[:drop]:
                self._cache.pop(k, None)
        self._cache[proof] = beta

    def clear(self) -> None:
        self._cache.clear()

    def store_many(self, proofs: Sequence[bytes], betas: Sequence) -> None:
        for p, b in zip(proofs, betas):
            self._store(p, b)


# beta = proof_to_hash(proof) is a pure function of the proof bytes, so one
# process-wide cache serves every protocol instance (TPraos, mock Praos,
# and the HFC combinator all read it)
GLOBAL_BETA_CACHE = VrfBetaCache()


class CpuRefBackend(CryptoBackend):
    """Pure-Python ground truth."""

    name = "cpu-ref"

    def verify_ed25519_batch(self, reqs):
        return list(map(ed25519_ref.verify, *ed25519_columns(reqs)))

    def verify_vrf_batch(self, reqs):
        return [vrf_ref.verify(r.vk, r.alpha, r.proof) for r in reqs]


class OpensslBackend(CpuRefBackend):
    """Ed25519 via OpenSSL (`cryptography`) — the fast-CPU fallback path
    (the role libsodium plays in the reference deployment).  Without the
    binding it degrades to the pure-Python parent (identical verdicts,
    RFC 8032 is deterministic) so `--backend openssl` stays usable on
    minimal installs."""

    name = "cpu-openssl"

    def verify_ed25519_batch(self, reqs):
        try:
            from cryptography.exceptions import InvalidSignature
            from cryptography.hazmat.primitives.asymmetric.ed25519 import (
                Ed25519PublicKey,
            )
        except ImportError:     # absent OR broken binding: degrade
            return super().verify_ed25519_batch(reqs)
        out = []
        for vk, msg, sig in zip(*ed25519_columns(reqs)):
            try:
                Ed25519PublicKey.from_public_bytes(vk).verify(sig, msg)
                out.append(True)
            except (InvalidSignature, ValueError):
                out.append(False)
        return out


_default: Optional[CryptoBackend] = None


def default_backend() -> CryptoBackend:
    """Best available backend: JAX on a REAL accelerator, else OpenSSL CPU.

    On the cpu platform (tests / machines without a chip) the JAX kernels
    still work but run the 256-iteration ladders through XLA:CPU at
    seconds per batch — the C-speed OpenSSL path is the right default
    there, exactly the libsodium-fallback role from BASELINE.json.
    Without the `cryptography` binding the pure-Python ground truth is
    the last resort, so the framework stays functional (just slower).

    That choice is made on what JAX reports, never on a failure: when
    JAX reports an accelerator and `JaxBackend()` cannot be built, the
    error propagates — a node that quietly verifies on the CPU beside an
    idle chip is the fault this function must not hide."""
    global _default
    if _default is None:
        try:
            import jax
        except ImportError:     # host-only install: no device to hide
            jax = None
        if jax is not None and jax.devices()[0].platform != "cpu":
            from .jax_backend import JaxBackend
            _default = JaxBackend()
        elif importlib.util.find_spec("cryptography") is not None:
            _default = OpensslBackend()
        else:
            _default = CpuRefBackend()
    return _default


def set_default_backend(b: Optional[CryptoBackend]) -> None:
    global _default
    _default = b
