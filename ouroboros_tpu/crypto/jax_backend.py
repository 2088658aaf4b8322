"""JaxBackend — the TPU-batched CryptoBackend instance.

Routes Ed25519 batches through the split-128 ladder kernels (half the
doubling chain via the per-key [2^128]A cache, ed25519_jax split-ladder
notes) and VRF batches through the packed vrf kernels (decompression,
Elligator2 and both Strauss ladders fused into one device call).  KES
hash paths run as one batched Blake2b-256 device check (blake2b_jax)
instead of per-item host hashing.

ALL device inputs travel as packed uint32 words, 32x smaller than the
(256, N) int32 bit rows of earlier rounds.  Unpacking is a tiny
on-device XLA prologue fused ahead of the Mosaic kernels.  The link is
not the bottleneck it was on the device this form was designed for (a
whole 4096-lane Ed25519 dispatch + compute + drain took 14 ms on a TPU
v5 lite — smoke reading, PR 22, ROADMAP A2); the packed form is kept
because it is never the larger transfer.

Batch sizes up to ED_TILE lanes are padded to power-of-two buckets (min
128) so repeated calls hit the jit cache instead of recompiling per
shape; a wider batch pads to a multiple of ED_TILE, and the window
composite walks its Ed25519 lanes as ED_TILE-wide tiles (`ed_lanes_core`).

Kernel selection is MEASURED, not assumed: on a TPU the fused pallas
(Mosaic) kernels and the op-by-op XLA kernels are timed head-to-head
the first time each batch shape appears on this machine (persistent,
fenced, min-of-k — crypto/autotune.py), and the winner stays pinned per
(kernel, bucket, device kind) — a hardcoded choice was repeatedly wrong
(VERDICT r3 "weak" #3), and an UNFENCED re-measure mid-run was the prime
suspect for a recorded VRF regression.  Whether either form wins beyond
the spread on the present chip is ROADMAP C3's question; the tuner's
price is that a new window shape compiles BOTH forms of every part
(749 s on the chip machine, PR 22 — ROADMAP A9).

Repeated verification keys cost nothing past their first window: the
cross-window precomputation cache (crypto/precompute.py) memoises the
per-key device work (Ed25519/VRF point decompression + split tables, KES
hash-path outcomes), so a cache-warm window dispatches only the ladders.
"""
from __future__ import annotations

import numpy as np

from ..observe import metrics as _metrics
from ..observe import spans as _spans
from . import autotune as autotune_mod
from . import blake2b_jax as B2
from . import ed25519_jax as EJ
from . import edwards as ed
from . import kes as kes_mod
from .backend import CryptoBackend, Ed25519Req, KesReq, VrfReq
from .precompute import GLOBAL_PRECOMPUTE_CACHE

# observational (gated) counters: window/dispatch volume on the hot path
_WINDOWS = _metrics.counter("jax_backend.windows_submitted")
_COMPOSITE_BUILDS = _metrics.counter("jax_backend.composite_builds")
_FOLD_WINDOWS = _metrics.counter("jax_backend.fold_windows")
# lane occupancy: real requests vs padded bucket lanes per window — the
# mesh backend's padding additionally rounds to a mesh multiple, so the
# waste fraction (1 - used/padded) is the per-shard occupancy cost the
# MULTICHIP_OBS line and the benchmark's `lane_pad_share` report
_LANES_USED = _metrics.counter("jax_backend.lanes_used")
_LANES_PADDED = _metrics.counter("jax_backend.lanes_padded")
# ED_TILE-wide tiles ONE device walks for a window's Ed25519 lanes; a
# window on the single-bucket path adds 0 (the "does it engage" reading)
_ED_TILES = _metrics.counter("jax_backend.ed_tiles")
# what a window's two occasional parts held (real work, not padding):
# windows whose composite carried betas for the window two ahead and the
# beta rows they carried; windows that scheduled no KES hash-path job
_BETA_WINDOWS = _metrics.counter("jax_backend.beta_windows")
_BETA_ROWS = _metrics.counter("jax_backend.beta_rows_carried")
_KES_EMPTY = _metrics.counter("jax_backend.kes_empty_windows")

# device-side verdict-fold sentinel: "no failing request".  int32 max so
# jnp.min over any real request index beats it; request lists are bounded
# far below it (a window is ~thousands of proofs).
FOLD_SENT = 0x7FFFFFFF


def _compile_span_on_first_call(fn, name: str):
    """Wrap a jitted program so its FIRST invocation — the one paying
    XLA trace+compile — runs inside a `compile` span.  Later calls go
    straight through: steady-state dispatch must not be attributed to
    compile (and costs one list lookup when observation is off)."""
    pending = [True]

    def run(*a):
        if pending:
            pending.clear()
            with _spans.span(name, cat="compile"):
                return fn(*a)
        return fn(*a)
    return run


def _bucket(n: int, lo: int = 128) -> int:
    """Smallest lo * 2^k that holds n: the shapes of every batch of at
    most ED_TILE lanes (`JaxBackend._pad`)."""
    m = lo
    while m < n:
        m *= 2
    return m


# Width of one Ed25519 tile of the XLA window composite, in lanes (a
# multiple of pallas_kernels.TILE = 512, so the Pallas grid divides every
# padded count too).  What a lane costs the XLA ladder is not flat in the
# width of the program: 3.5 us at 2,048 and 4,096 lanes, 3.9 at 8,192,
# 5.8 at 16,384, 7.8 at 32,768, 19.0 at 65,536 and 29.1 at 131,072 (a TPU
# v5e; PERF.md section 6, PR 30, has the sweep and the device operations
# that grow), and a loop of tiles costs what its tile does alone.  So a
# device handed more than ED_TILE lanes walks them ED_TILE at a time
# inside the one program.  Tests reach a small tile by monkeypatching
# this name.
ED_TILE = 4096


def ed_tiles(lanes: int) -> int:
    """Tiles `ed_lanes_core` walks for `lanes` lanes on one device: 0 at
    or under ED_TILE (the single-bucket program)."""
    return lanes // ED_TILE if lanes > ED_TILE else 0


def ed_lanes_core(Aw, xa, xw, yw, Rw, signR2, sw, kw):
    """The XLA Ed25519 verification of the lanes ONE device is handed, as
    both window composites trace it (the one-chip program over the whole
    window, the mesh's over a shard): `verify_full_split_words_core` on
    all of them at or under ED_TILE lanes — exactly the program of the
    power-of-two buckets — and above it one ED_TILE-wide tile at a time
    under `lax.map`, so the body is traced and compiled once whatever
    the tile count and a tile's working set stays near the core.  Lanes
    are independent, so the (lanes,) int32 verdicts are the flat
    program's, lane for lane."""
    lanes = Aw.shape[-1]
    tiles = ed_tiles(lanes)
    if not tiles:
        return EJ.verify_full_split_words_core(
            Aw, xa, xw, yw, Rw, signR2[0], sw, kw)
    assert lanes == tiles * ED_TILE, (lanes, ED_TILE)
    from jax import lax

    def tile_major(a):            # (rows, lanes) -> (tiles, rows, ED_TILE)
        return a.reshape(a.shape[0], tiles, ED_TILE).transpose(1, 0, 2)

    def one_tile(t):
        tAw, txa, txw, tyw, tRw, tsR2, tsw, tkw = t
        return EJ.verify_full_split_words_core(
            tAw, txa, txw, tyw, tRw, tsR2[0], tsw, tkw)

    return lax.map(one_tile, tuple(
        tile_major(a) for a in (Aw, xa, xw, yw, Rw, signR2, sw, kw))
    ).reshape(-1)


def batch_inverse(vals: list[int]) -> list[int]:
    """Montgomery trick: invert N field elements with one pow."""
    n = len(vals)
    out = [0] * n
    prefix = [1] * (n + 1)
    for i, v in enumerate(vals):
        prefix[i + 1] = prefix[i] * (v if v else 1) % ed.P
    inv_all = pow(prefix[n], ed.P - 2, ed.P)
    for i in range(n - 1, -1, -1):
        v = vals[i] if vals[i] else 1
        out[i] = prefix[i] * inv_all % ed.P
        inv_all = inv_all * v % ed.P
    return out


def _pad_words(w: np.ndarray, m: int) -> np.ndarray:
    """Pad the lane axis of a words/sign array out to m columns."""
    n = w.shape[-1]
    if n == m:
        return w
    pad = [(0, 0)] * (w.ndim - 1) + [(0, m - n)]
    return np.pad(w, pad)


class JaxBackend(CryptoBackend):
    # instances carry "jax-<platform>" of the devices they took
    # (`platform`, `device_kind`, `device_count` beside it); the class
    # attribute only names the family
    name = "jax"
    # submit_window(fold=True) folds verdicts on device into one
    # WindowVerdict scalar instead of a per-proof vector (the
    # producer/consumer replay driver asks — consensus/pipeline.py)
    supports_window_fold = True

    def __init__(self, min_bucket: int = 128, use_pallas: bool | None = None,
                 autotune: bool | None = None):
        import jax
        self._devices = jax.devices()
        self.platform = self._devices[0].platform
        self.device_kind = self._devices[0].device_kind
        self.device_count = len(self._devices)
        self.name = f"jax-{self.platform}"
        on_tpu = self.platform == "tpu"
        if autotune is None:
            # measure pallas-vs-XLA per shape on a real chip UNLESS the
            # caller pinned the path explicitly; off-TPU pallas interpret
            # mode just re-runs the same jnp ops with extra overhead, so
            # XLA is always right there and measuring would waste compiles
            autotune = on_tpu and use_pallas is None
        if use_pallas is None:
            use_pallas = on_tpu
        self.use_pallas = use_pallas      # static fallback when not tuning
        self.autotune = autotune
        if use_pallas or autotune:
            from . import pallas_kernels as PK
            self._pk = PK
            min_bucket = max(min_bucket, PK.TILE)
        self.min_bucket = min_bucket
        self._composites: dict = {}   # (ne, nv, nb, nk, pallas) -> program
        self._folds: dict = {}        # (ne, nv, nb, nk) -> fold program
        self._pk_vrf_folds: dict = {} # m -> jitted pallas verify+fold
        # donate the window inputs to the composite so a warm-path window
        # reuses the previous window's device buffers instead of
        # reallocating (XLA:CPU ignores donation with a warning -> gate)
        self._donate = self.platform in ("tpu", "gpu")
        # persistent fenced tuner shared process-wide per device kind —
        # only consulted when this instance is itself autotuning, so an
        # explicitly pinned use_pallas/autotune setting is never
        # overridden by a stale measurement file (crypto/autotune.py)
        self._tuner = (autotune_mod.tuner_for(self.device_kind)
                       if autotune else None)
        # static-path choices recorded for kernel_choices() reporting
        self._static_choice: dict = {}
        # per-instance lane occupancy accumulators (padding_stats());
        # written only on the submit path, which has a single writer
        # thread in the pipelined replay (the producer)
        self._lanes_used = 0
        self._lanes_padded = 0
        self._windows_padded = 0

    # -- subclass seams (ShardedJaxBackend overrides both) -------------------
    def _pad(self, n: int) -> int:
        """Batch padding: power-of-two buckets from `min_bucket` up to
        ED_TILE lanes, above that the next multiple of ED_TILE (a
        window's 90,624 Ed25519 lanes pad to 94,208, not 131,072).  The
        mesh backend pads to a mesh multiple, and to whole tiles a shard
        once a shard is wider than ED_TILE."""
        m = _bucket(n, self.min_bucket)
        return m if m <= ED_TILE else -(-n // ED_TILE) * ED_TILE

    def _dev(self, a):
        """Host array -> device array for a lane-axis-last batch input;
        the mesh backend device_puts with the window-axis sharding."""
        import jax.numpy as jnp
        return jnp.asarray(a)

    # -- lane occupancy ------------------------------------------------------
    def _note_padding(self, used: int, padded: int) -> None:
        """Record one window's lane occupancy (real requests vs padded
        bucket lanes across every component batch).  Runs on the submit
        path — the producer thread in the pipelined replay."""
        self._lanes_used += used
        self._lanes_padded += padded
        self._windows_padded += 1
        _LANES_USED.inc(used)
        _LANES_PADDED.inc(padded)

    @property
    def n_shards(self) -> int:
        """Devices the window batch is split over (1 off-mesh; the mesh
        backend overrides via its mesh size)."""
        return 1

    def padding_stats(self, since: Optional[dict] = None) -> dict:
        """Lane occupancy over every window this instance submitted:
        ``waste_frac`` is the fraction of padded lanes that carried no
        real request — on the mesh backend the same fraction per shard,
        since sharding splits the padded batch evenly.  The MULTICHIP
        dryrun and chip_smoke.py embed this dict.  Pass a previously
        returned dict as `since` to get the delta (one replay's windows
        instead of the instance lifetime)."""
        used, padded = self._lanes_used, self._lanes_padded
        windows = self._windows_padded
        if since is not None:
            used -= since["lanes_used"]
            padded -= since["lanes_padded"]
            windows -= since["windows"]
        per_shard = padded // (self.n_shards * max(windows, 1))
        return {
            "windows": windows,
            "lanes_used": used,
            "lanes_padded": padded,
            "waste_frac": round(1.0 - used / padded, 4) if padded
            else 0.0,
            "shards": self.n_shards,
            "lanes_per_shard_per_window": per_shard,
        }

    def prewarm_window(self, reqs, next_beta_proofs=(),
                       fold: bool = False):
        """Run one full window for `reqs` NOW — compiling its composite
        (and, with fold=True, the verdict-fold program) outside any
        timed/timeout-budgeted region — returning ``(wall_seconds, ok)``:
        the seconds (dominated by XLA compile on a cold cache) plus the
        window's verdicts — the per-request bool vector, or with
        fold=True the WindowVerdict scalar (gate on ``ok.all_ok``) — so
        callers assert correctness on THIS run instead of paying a
        duplicate window for it.  Shared by the single-device and mesh
        paths (MULTICHIP_r05 follow-up: a silent 4m25s compile inside
        the timed region turned into rc=124 with zero attribution; the
        dryrun pre-warms and reports this number instead)."""
        import time as _time
        t0 = _time.perf_counter()
        with _spans.span("window.prewarm", cat="compile"):
            ok, _ = self.finish_window(
                self.submit_window(reqs, next_beta_proofs, fold=fold))
        return _time.perf_counter() - t0, ok

    # -- measured kernel selection ------------------------------------------
    @property
    def kernel_choices(self) -> dict:
        """Stable {shape key tuple: use_pallas} of every pinned choice
        this backend can run with (chip_smoke.py prints it)."""
        if self._tuner is not None:
            return self._tuner.choices_snapshot()
        return {k: self._static_choice[k]
                for k in sorted(self._static_choice)}

    def _pick(self, key, run_pallas, run_xla):
        """Return (use_pallas, cached_result) for this shape key.

        Pinned choices (persisted by an earlier process, or measured
        earlier in this one) return instantly.  First sighting of a
        shape under autotune measures both paths through the fenced
        min-of-k tuner and pins the winner — loudly failing if a timed
        region froze the tuner first.  cached_result is the winner's
        last measured output (simple batch callers reuse it to skip one
        dispatch); None whenever no measurement ran."""
        if not self.autotune:
            self._static_choice[key] = self.use_pallas
            return self.use_pallas, None
        use = self._tuner.get(key)
        if use is not None:
            return use, None
        return self._tuner.measure(key, run_pallas, run_xla)

    # -- host prep ----------------------------------------------------------
    def _prep_ed(self, reqs, m: int):
        """Packed-words prep + A128 assembly for an Ed25519 batch padded
        to m.  Returns (dev_args, parse_ok); keys the cache could not
        decompress are masked out of parse_ok (the kernels trust the
        cached affine x and skip the A square root)."""
        pad = m - len(reqs)
        vks = [r.vk for r in reqs] + [b"\x00" * 32] * pad
        arrays, parse_ok = EJ.prepare_words_batch(
            vks,
            [r.msg for r in reqs] + [b""] * pad,
            [r.sig for r in reqs] + [b"\x00" * 64] * pad)
        Aw, _signA, Rw, signR, sw, kw = arrays
        xa, xw, yw, known = EJ.GLOBAL_A128_CACHE.assemble(vks)
        args = (self._dev(Aw), self._dev(xa),
                self._dev(xw), self._dev(yw),
                self._dev(Rw), self._dev(signR.reshape(1, -1)),
                self._dev(sw), self._dev(kw))
        return args, parse_ok & known

    def _ed_dispatch(self, args, m: int, use_pallas: bool):
        """Async-dispatch one prepared Ed25519 batch; (m,) int32 handle."""
        if use_pallas:
            return self._pk._ed25519_split_jit(*args, m).reshape(-1)
        Aw, xa, xw, yw, Rw, signR2, sw, kw = args
        return EJ.verify_full_split_words_kernel(
            Aw, xa, xw, yw, Rw, signR2[0], sw, kw)

    def verify_ed25519_batch(self, reqs):
        if not reqs:
            return []
        n = len(reqs)
        m = self._pad(n)
        args, parse_ok = self._prep_ed(reqs, m)
        use, ok = self._pick(
            ("ed", m),
            lambda: np.asarray(self._ed_dispatch(args, m, True)),
            lambda: np.asarray(self._ed_dispatch(args, m, False)))
        if ok is None:
            ok = np.asarray(self._ed_dispatch(args, m, use))
        return [bool(o) and bool(p)
                for o, p in zip(ok[:n], parse_ok[:n])]

    def _prep_vrf(self, reqs, m: int):
        from . import vrf_jax
        pad = m - len(reqs)
        vks = [r.vk for r in reqs] + [b"\x00" * 32] * pad
        args, parse_ok, gamma_ok, s_ok, pf_arr = vrf_jax._prepare_words(
            vks,
            [r.alpha for r in reqs] + [b""] * pad,
            [r.proof for r in reqs] + [b"\x00" * 80] * pad)
        Yw, _signY, Gw, signG, rw, cw, sw = args
        xa, _x128, _y128, known = EJ.GLOBAL_A128_CACHE.assemble(vks)
        dev = (self._dev(Yw), self._dev(xa),
               self._dev(Gw), self._dev(signG.reshape(1, -1)),
               self._dev(rw), self._dev(cw), self._dev(sw))
        return dev, (parse_ok & known, gamma_ok, s_ok, pf_arr)

    def _vrf_dispatch(self, dev, m: int, use_pallas: bool):
        from . import vrf_jax
        if use_pallas:
            return self._pk._vrf_verify_jit(*dev, m)
        Yw, xa, Gw, signG2, rw, cw, sw = dev
        return vrf_jax.vrf_verify_words_kernel(Yw, xa, Gw,
                                               signG2[0], rw, cw, sw)

    def _vrf_fold_dispatch(self, dev, gamma_b, c_b, valid, m: int,
                           use_pallas: bool):
        """Verify + on-device challenge fold: (m,) uint8 verdicts.  The
        (m, 130) point rows never leave the device — 1 B/proof crosses
        the link instead of 130 B."""
        from . import vrf_jax
        if use_pallas:
            fn = self._pk_vrf_folds.get(m)
            if fn is None:
                import jax
                import jax.numpy as jnp
                PK = self._pk

                def call(Yw, xa, Gw, signG2, rw, cw, sw, gb, cb, va,
                         _m=m):
                    rows = PK._vrf_verify_call(Yw, xa, Gw, signG2, rw,
                                               cw, sw, _m)
                    ok = vrf_jax.challenge_ok_device(rows, gb, cb)
                    return (ok & (va != 0)).astype(jnp.uint8)
                fn = self._pk_vrf_folds[m] = jax.jit(call)
            return fn(*dev, gamma_b, c_b, valid)
        Yw, xa, Gw, signG2, rw, cw, sw = dev
        return vrf_jax.vrf_verify_fold_words_kernel(
            Yw, xa, Gw, signG2[0], rw, cw, sw, gamma_b, c_b, valid)

    def verify_vrf_batch(self, reqs):
        if not reqs:
            return []
        n = len(reqs)
        m = self._pad(n)
        dev, (parse_ok, gamma_ok, s_ok, pf_arr) = self._prep_vrf(reqs, m)
        gamma_b = self._dev(np.ascontiguousarray(pf_arr[:, :32]))
        c_b = self._dev(np.ascontiguousarray(pf_arr[:, 32:48]))
        valid = self._dev(parse_ok.astype(np.uint8))
        # own key: this measures the verify+challenge-fold program pair,
        # a different program than the ("vrf", m) rows form the window
        # composite fuses — sharing the key would pin a choice measured
        # on the wrong program for whichever path ran second
        use, ok = self._pick(
            ("vrff", m),
            lambda: np.asarray(self._vrf_fold_dispatch(
                dev, gamma_b, c_b, valid, m, True)),
            lambda: np.asarray(self._vrf_fold_dispatch(
                dev, gamma_b, c_b, valid, m, False)))
        if ok is None:
            ok = np.asarray(self._vrf_fold_dispatch(dev, gamma_b, c_b,
                                                    valid, m, use))
        return [bool(o) for o in ok[:n]]

    # largest single gamma8 dispatch: bounds the set of compiled shapes
    # (a fresh pallas shape costs minutes through the AOT helper)
    BETA_CHUNK = 2048

    def _beta_dispatch(self, Gw, signG2, m: int, use_pallas: bool):
        from . import vrf_jax
        if use_pallas:
            return self._pk._gamma8_jit(Gw, signG2, m)
        return vrf_jax.gamma8_words_kernel(Gw, signG2[0])

    def vrf_betas_batch(self, proofs):
        from . import vrf_jax
        n = len(proofs)
        if n == 0:
            return []
        if n > self.BETA_CHUNK:
            out = []
            for off in range(0, n, self.BETA_CHUNK):
                out.extend(self.vrf_betas_batch(
                    proofs[off:off + self.BETA_CHUNK]))
            return out
        m = self._pad(n)
        padded = list(proofs) + [b"\x00" * 80] * (m - n)
        (Gw, signG), decode_ok = vrf_jax._prepare_betas_words(padded)
        Gwd = self._dev(Gw)
        signG2 = self._dev(signG.reshape(1, -1))
        use, rows = self._pick(
            ("beta", m),
            lambda: np.asarray(self._beta_dispatch(Gwd, signG2, m, True)),
            lambda: np.asarray(self._beta_dispatch(Gwd, signG2, m, False)))
        if rows is None:
            rows = np.asarray(self._beta_dispatch(Gwd, signG2, m, use))
        return vrf_jax._finish_betas(np.asarray(rows), decode_ok, n)

    # -- mixed windows -------------------------------------------------------
    def _split_mixed_device(self, reqs):
        """Like CryptoBackend.split_mixed but hash-free: KES hash paths
        become device Blake2b jobs instead of host hashing (VERDICT r4
        missing #2), and the jobs themselves are memoised cross-window —
        a hash path depends only on (depth, period, vk, merkle bytes),
        so a pool's per-period subtree is checked on device ONCE and its
        outcome served from the precomputation cache ever after (warm
        windows schedule zero Blake2b jobs).  Identical paths within one
        cold window collapse to one job slice too.

        Returns (ed_reqs, ed_owner, vrf_reqs, vrf_owner, kes_msgs,
        kes_expects, kes_checks, n); kes_checks lists the pending cache
        stores as (key, job_start, n_jobs, owners, leaf_vk) —
        finish_window folds the per-job verdicts into one outcome per
        path and records it."""
        cache = GLOBAL_PRECOMPUTE_CACHE
        ed_reqs: list = []
        ed_owner: list[int] = []
        vrf_reqs: list = []
        vrf_owner: list[int] = []
        kes_msgs: list[bytes] = []
        kes_expects: list[bytes] = []
        pending: dict = {}     # key -> [start, n_jobs, owners, leaf_vk]
        for i, r in enumerate(reqs):
            if isinstance(r, Ed25519Req):
                ed_reqs.append(r)
                ed_owner.append(i)
            elif isinstance(r, VrfReq):
                vrf_reqs.append(r)
                vrf_owner.append(i)
            elif isinstance(r, KesReq):
                key = kes_mod.hash_path_key(r.depth, r.vk, r.period,
                                            r.sig_bytes)
                if key is None:
                    continue          # structurally invalid: stays False
                ent = cache.kes_get(key)
                if ent is not None:                     # warm path
                    leaf_vk, path_ok = ent
                    if not path_ok:
                        continue      # known-bad hash path: stays False
                elif key in pending:  # cold, but already scheduled here
                    pend = pending[key]
                    pend[2].append(i)
                    leaf_vk = pend[3]
                else:                                   # cold path
                    sig = kes_mod.KesSig.from_bytes(r.depth, r.sig_bytes)
                    walk = kes_mod.verify_walk(r.depth, r.vk, r.period,
                                               sig)
                    leaf_vk, _leaf_sig, jobs = walk
                    start = len(kes_msgs)
                    for msg, expect in jobs:
                        kes_msgs.append(msg)
                        kes_expects.append(expect)
                    pending[key] = [start, len(jobs), [i], leaf_vk]
                ed_reqs.append(Ed25519Req(leaf_vk, r.msg,
                                          r.sig_bytes[:64]))
                ed_owner.append(i)
            else:
                raise TypeError(f"unknown proof request type {type(r)}")
        kes_checks = [(key, start, nj, owners, leaf_vk)
                      for key, (start, nj, owners, leaf_vk)
                      in pending.items()]
        return (ed_reqs, ed_owner, vrf_reqs, vrf_owner,
                kes_msgs, kes_expects, kes_checks, len(reqs))

    def _prep_kes_hash(self, kes_msgs, kes_expects, m: int):
        msgs = np.frombuffer(b"".join(kes_msgs), dtype=np.uint8)
        msgs = msgs.reshape(-1, 64)
        exps = np.frombuffer(b"".join(kes_expects), dtype=np.uint8)
        exps = exps.reshape(-1, 32)
        mw = _pad_words(B2.msg_words(msgs), m)
        ew = _pad_words(B2.digest_words(exps), m)
        return self._dev(mw), self._dev(ew)

    def _kes_dispatch(self, mw, ew, m: int, use_pallas: bool):
        if use_pallas:
            return self._pk._kes_hash_jit(mw, ew, m).reshape(-1)
        return B2.check_block64_jit(mw, ew)

    def _window_composite(self, ne: int, nv: int, nb: int, nk: int,
                          pallas: bool):
        """One jitted device program for a whole window: Ed25519 verify +
        VRF verify + next-window gamma8 betas + KES hash checks, results
        concatenated into the packed flat uint8 buffer on device.  ONE
        launch per window instead of one per part.  (On a TPU v5 lite a
        part's whole dispatch + compute + drain took 1-19 ms — smoke
        reading, PR 22, ROADMAP A2 — so a launch is cheap there, and
        the price of the fusion is that every window SHAPE is its own
        multi-minute compile — ROADMAP A9/C5.)

        The program is HOMOGENEOUS (all ladder parts pallas or all XLA):
        mixing an op-by-op XLA ladder into a pallas composite made XLA's
        compile of the combined program pathological (>1h at replay
        shapes, vs minutes for either pure form), and only the chosen
        form is ever compiled.

        The XLA form walks more than ED_TILE Ed25519 lanes as ED_TILE-wide
        tiles inside the one program (`ed_lanes_core`: same verdicts at
        the same offsets of the packed buffer); the Pallas form keeps
        its own 512-lane grid over the tile-multiple `ne`."""
        key = (ne, nv, nb, nk, pallas)
        fn = self._composites.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        from . import vrf_jax
        PK = getattr(self, "_pk", None)

        def call(ed_args, vrf_args, beta_args, kes_args):
            parts = []
            if ed_args is not None:
                if pallas:
                    ok = PK._ed25519_split_call(*ed_args, ne)
                else:
                    ok = ed_lanes_core(*ed_args)
                parts.append(ok.reshape(-1).astype(jnp.uint8))
            if vrf_args is not None:
                if pallas:
                    rows = PK._vrf_verify_call(*vrf_args, nv)
                else:
                    Yw, xa, Gw, sG2, rw, cw, sw = vrf_args
                    rows = vrf_jax.vrf_verify_words_core(
                        Yw, xa, Gw, sG2[0], rw, cw, sw)
                parts.append(rows.reshape(-1))
            if beta_args is not None:
                if pallas:
                    rows = PK._gamma8_call(*beta_args, nb)
                else:
                    bGw, bsG2 = beta_args
                    rows = vrf_jax.gamma8_words_core(bGw, bsG2[0])
                parts.append(rows.reshape(-1))
            if kes_args is not None:
                if pallas:
                    ok = PK._kes_hash_call(*kes_args, nk)
                else:
                    ok = B2.check_block64(*kes_args)
                parts.append(ok.reshape(-1).astype(jnp.uint8))
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

        # donate the window's input buffers: they are built fresh per
        # window and never read after the call, so XLA may overwrite
        # them in place — the double-buffered replay (two windows in
        # flight, consensus/batch.py) stops reallocating device memory
        # every window.  CPU ignores donation (warns), hence the gate.
        fn = jax.jit(call, donate_argnums=(0, 1, 2, 3)) if self._donate \
            else jax.jit(call)
        _COMPOSITE_BUILDS.inc()
        fn = _compile_span_on_first_call(
            fn, f"window.composite({ne},{nv},{nb},{nk})")
        self._composites[key] = fn
        return fn

    def _occasional_widths(self, ne: int, nv: int, nb: int,
                           nk: int) -> tuple:
        """(nb, nk) for a window whose beta and KES parts need `nb` and
        `nk` lanes: those of the narrowest composite this backend has
        ALREADY built for the same Ed25519 and VRF widths that holds
        both, else their own.

        A sync meets these two parts at several widths: betas ride in
        every window but a chain's last two, KES hash jobs only in the
        windows that first meet a pool's hash path (and whether window
        1 does is a race with window 0's drain).  Every (ne, nv, nb, nk)
        is its own program, a minute or more to trace, lower and build
        or load, where the empty lanes of a wider part cost the device
        microseconds (PERF.md section 6, PR 33).  So a window rides a
        built program that covers it rather than building its own, and
        a chain's first window, the widest in both parts, fixes the
        program for the rest."""
        best = None
        for e, v, b, k, _pallas in self._composites:
            if e == ne and v == nv and b >= nb and k >= nk and (
                    best is None or b + k < best[0] + best[1]):
                best = (b, k)
        return best or (nb, nk)

    def submit_window(self, reqs, next_beta_proofs=(), fold: bool = False):
        """Dispatch one replay window's whole device workload — the mixed
        Ed25519/VRF/KES verification of `reqs` AND the VRF betas the NEXT
        window's sequential pass will need — as ONE fused device program
        whose results are packed into ONE flat uint8 array: the
        latency-bound host<->device link is crossed once per window, and
        the launch overhead is paid once instead of per kernel.  Returns
        an opaque state for finish_window.

        With fold=True the per-proof verdicts never cross the link: a
        second tiny device program reduces the composite's packed output
        to the FIRST failing request index (on-device SHA-512 challenge
        fold for VRF — sha512_jax), and finish_window returns a
        WindowVerdict scalar pair instead of the boolean vector.  The
        big ladder composite is SHARED between both modes (same program,
        same autotuned choice, same compile), so a fold caller costs one
        extra small compile, not a second composite.

        `window.submit` holds one span a stage: submit.split,
        submit.pack_ed (key tables included), submit.pack_vrf (beta
        words included), submit.pack_kes, submit.dispatch (the choice
        and the composite call) and, folding, submit.fold."""
        with _spans.span("window.submit", cat="dispatch", cpu=True):
            return self._submit_window(reqs, next_beta_proofs, fold)

    def _submit_window(self, reqs, next_beta_proofs=(),
                       fold: bool = False):
        from . import vrf_jax
        _WINDOWS.inc()
        with _spans.span("submit.split", cat="dispatch"):
            (ed_reqs, ed_owner, vrf_reqs, vrf_owner,
             kes_msgs, kes_expects, kes_checks, n) = \
                self._split_mixed_device(reqs)
            beta_proofs = list(dict.fromkeys(next_beta_proofs))
            ne, nv, nb, nk = (self._pad(len(part)) if part else 0
                              for part in (ed_reqs, vrf_reqs, beta_proofs,
                                           kes_msgs))
            nb, nk = self._occasional_widths(ne, nv, nb, nk)
        if beta_proofs:
            _BETA_WINDOWS.inc()
            _BETA_ROWS.inc(len(beta_proofs))
        if not kes_msgs:
            _KES_EMPTY.inc()
        ed_state = vrf_state = beta_state = None
        ed_args = vrf_args = beta_args = kes_args = None
        with _spans.span("submit.pack_ed", cat="dispatch"):
            if ne:
                ed_args, parse_ok = self._prep_ed(ed_reqs, ne)
                ed_state = (None, parse_ok)
        with _spans.span("submit.pack_vrf", cat="dispatch"):
            if nv:
                vrf_args, masks = self._prep_vrf(vrf_reqs, nv)
                vrf_state = (None,) + masks
            if nb:
                padded = beta_proofs + [b"\x00" * 80] * (
                    nb - len(beta_proofs))
                (Gw, signG), decode_ok = vrf_jax._prepare_betas_words(
                    padded)
                beta_state = (decode_ok,)
                beta_args = (self._dev(Gw),
                             self._dev(signG.reshape(1, -1)))
        with _spans.span("submit.pack_kes", cat="dispatch"):
            if nk:
                kes_args = self._prep_kes_hash(kes_msgs, kes_expects, nk)
        self._note_padding(
            len(ed_reqs) + len(vrf_reqs) + len(beta_proofs) + len(kes_msgs),
            ne + nv + nb + nk)
        with _spans.span("submit.dispatch", cat="dispatch"):
            if (ed_args is None and vrf_args is None and beta_args is None
                    and kes_args is None):
                packed = None
            else:
                allp = self._window_choice(ne, nv, nb, nk, ed_args,
                                           vrf_args, beta_args, kes_args)
                packed = self._window_composite(ne, nv, nb, nk, allp)(
                    ed_args, vrf_args, beta_args, kes_args)
                if not allp:
                    _ED_TILES.inc(ed_tiles(ne // self.n_shards))
        state = {"packed": packed, "n": n,
                 "ed": ed_state, "ed_owner": ed_owner, "ne": ne,
                 "vrf": vrf_state, "vrf_owner": vrf_owner,
                 "vrf_n": len(vrf_reqs), "nv": nv,
                 "beta": beta_state, "beta_proofs": beta_proofs, "nb": nb,
                 "kes_checks": kes_checks, "nk": nk,
                 "kes_n": len(kes_msgs)}
        if fold:
            with _spans.span("submit.fold", cat="dispatch"):
                self._attach_fold(state, reqs)
        return state

    def _attach_fold(self, state, reqs) -> None:
        """Reduce the window's packed verdict buffer on device to [first
        failing request index (4 B LE) | KES job flags | beta rows].

        Host-known failures (undecodable keys/sigs, structurally invalid
        KES, known-bad cached hash paths) never reach the device fold:
        their lanes carry the sentinel owner and their minimum index is
        kept in `host_first_bad` for finish_window to merge.  The KES
        job flags still cross the link raw — they exist only on COLD
        hash paths and the precompute cache must see each path's
        outcome; warm windows ship zero of them."""
        import jax.numpy as jnp
        _FOLD_WINDOWS.inc()
        n = state["n"]
        ne, nv = state["ne"], state["nv"]
        covered = np.zeros(max(n, 1), dtype=bool)
        host_bad = FOLD_SENT
        ed_own = np.full(ne, FOLD_SENT, np.int32)
        if state["ed"] is not None:
            po = np.asarray(state["ed"][1], dtype=bool)
            for k, i in enumerate(state["ed_owner"]):
                covered[i] = True
                if po[k]:
                    ed_own[k] = i
                elif i < host_bad:
                    host_bad = i
        vrf_own = np.full(nv, FOLD_SENT, np.int32)
        gamma_b = np.zeros((nv, 32), np.uint8)
        c_b = np.zeros((nv, 16), np.uint8)
        if state["vrf"] is not None:
            _h, parse_ok, _gok, _sok, pf_arr = state["vrf"]
            pv = np.asarray(parse_ok, dtype=bool)
            gamma_b = np.ascontiguousarray(pf_arr[:, :32])
            c_b = np.ascontiguousarray(pf_arr[:, 32:48])
            for k, i in enumerate(state["vrf_owner"]):
                covered[i] = True
                if pv[k]:
                    vrf_own[k] = i
                elif i < host_bad:
                    host_bad = i
        uncovered = np.flatnonzero(~covered[:n])
        if uncovered.size and uncovered[0] < host_bad:
            host_bad = int(uncovered[0])
        state["fold"] = True
        state["host_first_bad"] = host_bad
        if state["packed"] is not None:
            state["packed"] = self._fold_program(
                ne, nv, state["nb"], state["nk"])(
                    state["packed"], jnp.asarray(ed_own),
                    jnp.asarray(vrf_own), jnp.asarray(gamma_b),
                    jnp.asarray(c_b))

    def _fold_program(self, ne: int, nv: int, nb: int, nk: int):
        """Jitted verdict reduction over one window's packed buffer.
        Output layout: [first-bad index, uint32 LE (FOLD_SENT = none)
        | nk KES job flags | nb*33 beta rows] — the transfer shrinks
        from ne + 130*nv + ... to 4 + nk + 33*nb bytes."""
        key = (ne, nv, nb, nk)
        fn = self._folds.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        from . import vrf_jax

        def fold(flat, ed_own, vrf_own, gamma_b, c_b):
            off = 0
            m = jnp.int32(FOLD_SENT)
            if ne:
                ed_ok = flat[:ne]
                m = jnp.minimum(m, jnp.min(
                    jnp.where(ed_ok != 0, FOLD_SENT, ed_own)))
                off += ne
            if nv:
                rows = flat[off:off + nv * 130].reshape(nv, 130)
                ok = vrf_jax.challenge_ok_device(rows, gamma_b, c_b)
                m = jnp.minimum(m, jnp.min(
                    jnp.where(ok, FOLD_SENT, vrf_own)))
                off += nv * 130
            beta_part = flat[off:off + nb * 33]
            off += nb * 33
            kes_part = flat[off:off + nk]
            idx = m.astype(jnp.uint32)
            idx4 = jnp.stack([idx & 0xFF, (idx >> 8) & 0xFF,
                              (idx >> 16) & 0xFF,
                              (idx >> 24) & 0xFF]).astype(jnp.uint8)
            return jnp.concatenate([idx4, kes_part, beta_part])

        # the composite's packed output is consumed here and never read
        # again — donate it so the fold reuses its buffer
        fn = jax.jit(fold, donate_argnums=(0,)) if self._donate \
            else jax.jit(fold)
        fn = _compile_span_on_first_call(
            fn, f"window.fold({ne},{nv},{nb},{nk})")
        self._folds[key] = fn
        return fn

    def _window_choice(self, ne, nv, nb, nk, ed_args, vrf_args,
                       beta_args, kes_args) -> bool:
        """Homogeneous pallas-vs-XLA choice for one window shape.

        A pinned ("win", ...) choice (persisted by an earlier run, or
        voted earlier in this one) returns with ZERO extra dispatches —
        the warm path never re-measures, so once a benchmark's warmup
        phase has seen every window shape, its timed reps cannot retune.
        First sighting under autotune measures each present component
        through the fenced tuner (keys shared with the simple-batch
        paths), votes, and pins the vote persistently."""
        win_key = ("win", ne, nv, nb, nk)
        if not self.autotune:
            self._static_choice[win_key] = self.use_pallas
            return self.use_pallas
        allp = self._tuner.get(win_key)
        if allp is not None:
            return allp
        use_ed = use_vrf = use_beta = use_kes = False
        if ed_args is not None:
            use_ed, _ = self._pick(
                ("ed", ne),
                lambda: np.asarray(self._ed_dispatch(ed_args, ne, True)),
                lambda: np.asarray(self._ed_dispatch(ed_args, ne, False)))
        if vrf_args is not None:
            use_vrf, _ = self._pick(
                ("vrf", nv),
                lambda: np.asarray(self._vrf_dispatch(vrf_args, nv,
                                                      True)),
                lambda: np.asarray(self._vrf_dispatch(vrf_args, nv,
                                                      False)))
        if beta_args is not None:
            use_beta, _ = self._pick(
                ("beta", nb),
                lambda: np.asarray(self._beta_dispatch(*beta_args, nb,
                                                       True)),
                lambda: np.asarray(self._beta_dispatch(*beta_args, nb,
                                                       False)))
        if kes_args is not None:
            use_kes, _ = self._pick(
                ("kesh", nk),
                lambda: np.asarray(self._kes_dispatch(*kes_args, nk,
                                                      True)),
                lambda: np.asarray(self._kes_dispatch(*kes_args, nk,
                                                      False)))
        # all-pallas unless every present LADDER component measured XLA
        # faster (see _window_composite on why no mixing); the kes hash
        # kernel is too small to swing the vote
        pallas_votes = [v for v, present in
                        ((use_ed, ed_args is not None),
                         (use_vrf, vrf_args is not None),
                         (use_beta, beta_args is not None)) if present]
        allp = any(pallas_votes) if pallas_votes else use_kes
        self._tuner.put_derived(win_key, allp)
        return allp

    def finish_window(self, state):
        """Block on a submit_window dispatch (one transfer); returns
        (ok list aligned with the submitted reqs, {proof: beta} for the
        requested next-window proofs).  For a fold=True submission the
        first element is a WindowVerdict instead of the boolean list."""
        if state.get("fold"):
            return self._finish_window_fold(state)
        out = [False] * state["n"]
        betas: dict = {}
        if state["packed"] is None:
            return out, betas
        with _spans.span("window.drain", cat="device"):
            flat = np.asarray(state["packed"])      # THE round trip
        off = 0
        if state["ed"] is not None:
            ed_ok = flat[off:off + state["ne"]]
            off += state["ne"]
            _handle, parse_ok = state["ed"]
            for k, i in enumerate(state["ed_owner"]):
                out[i] = bool(ed_ok[k]) and bool(parse_ok[k])
        if state["vrf"] is not None:
            rows = flat[off:off + state["nv"] * 130].reshape(-1, 130)
            off += state["nv"] * 130
            from . import vrf_jax
            _h, parse_ok, gamma_ok, s_ok, pf_arr = state["vrf"]
            oks, _b = vrf_jax._finish(rows, parse_ok, gamma_ok, s_ok,
                                      pf_arr, state["vrf_n"])
            for i, ok in zip(state["vrf_owner"], oks):
                out[i] = ok
        if state["beta"] is not None:
            rows = flat[off:off + state["nb"] * 33].reshape(-1, 33)
            off += state["nb"] * 33
            from . import vrf_jax
            bs = vrf_jax._finish_betas(rows, state["beta"][0],
                                       len(state["beta_proofs"]))
            betas = dict(zip(state["beta_proofs"], bs))
        # a KES request is valid only if its leaf Ed25519 check passed
        # (handled via ed_owner above) AND its hash path checked out.
        # Each pending path's per-job verdicts fold into ONE outcome that
        # the precomputation cache remembers — warm windows carry no
        # kes_checks (and schedule no jobs) at all.
        kes_ok = (flat[off:off + state["nk"]] if state["nk"] else
                  np.zeros(0, dtype=np.uint8))
        for key, start, n_jobs, owners, leaf_vk in state["kes_checks"]:
            path_ok = bool(np.all(kes_ok[start:start + n_jobs])) \
                if n_jobs else True
            GLOBAL_PRECOMPUTE_CACHE.kes_put(key, leaf_vk, path_ok)
            if not path_ok:
                for i in owners:
                    out[i] = False
        return out, betas

    def _finish_window_fold(self, state):
        """Fold-mode drain: one tiny transfer — [first-bad idx | KES job
        flags | beta rows] — merged with the host-known failures into a
        WindowVerdict."""
        from . import vrf_jax
        from .backend import WindowVerdict
        n = state["n"]
        betas: dict = {}
        bad = state["host_first_bad"]
        if state["packed"] is None:
            return WindowVerdict(
                n, None if bad >= FOLD_SENT else bad), betas
        with _spans.span("window.drain", cat="device"):
            flat = np.asarray(state["packed"])      # THE round trip
        dev_bad = (int(flat[0]) | int(flat[1]) << 8
                   | int(flat[2]) << 16 | int(flat[3]) << 24)
        bad = min(bad, dev_bad)
        off = 4
        kes_ok = flat[off:off + state["nk"]]
        off += state["nk"]
        for key, start, n_jobs, owners, leaf_vk in state["kes_checks"]:
            path_ok = bool(np.all(kes_ok[start:start + n_jobs])) \
                if n_jobs else True
            GLOBAL_PRECOMPUTE_CACHE.kes_put(key, leaf_vk, path_ok)
            if not path_ok:
                bad = min(bad, min(owners))
        if state["beta"] is not None:
            rows = flat[off:off + state["nb"] * 33].reshape(-1, 33)
            bs = vrf_jax._finish_betas(rows, state["beta"][0],
                                       len(state["beta_proofs"]))
            betas = dict(zip(state["beta_proofs"], bs))
        return WindowVerdict(n, None if bad >= FOLD_SENT else bad), betas

    def verify_kes_batch(self, reqs):
        """KES batch: leaf Ed25519 on the curve kernels + hash path on the
        Blake2b device kernel — no host hashing (VERDICT r4 missing #2)."""
        return self.verify_mixed(reqs)

    def verify_mixed(self, reqs):
        """Fused mixed batch: one packed device transfer for the whole
        window (see submit_window)."""
        ok, _betas = self.finish_window(self.submit_window(reqs))
        return ok
