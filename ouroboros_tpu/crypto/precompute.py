"""Cross-window per-key precomputation cache — the generalized A128Cache.

A chain has few stake pools, so the same verification keys recur in
every replay window and their expensive per-key precomputation is PURE:

- Ed25519 cold/payment keys: the decompressed affine x of A plus the
  affine coordinates of [2^128]A (the split-ladder table half), computed
  on device by ed25519_jax.a128_kernel at first sighting;
- VRF pool keys: the decompressed affine x of Y that feeds the cached-Y
  packed kernel (vrf_jax.vrf_verify_words_kernel) — the [c](-Y) half of
  the on-device triple table is derived from it per batch, so the cached
  x is the whole host-visible per-key cost;
- KES hash paths: the Blake2b-256 Merkle walk of a (depth, period, vk,
  merkle-path) tuple is independent of the signed message, so a pool's
  per-period subtree check has ONE answer for the thousands of headers
  it signs in that period.

This module holds all three behind one LRU-bounded cache keyed by vk
bytes (points) or the KES hash-path identity (kes.hash_path_key), with
counters (`device_fills`, `filled_keys`, `hits`, `misses`, `evictions`)
so the warm-path guarantee — a cache-warm window does ZERO per-key
decompression/table-build device calls — is assertable in tests and
readable in a run's counters.

Unlike the r5 A128Cache, undecodable keys are cached too (as negative
entries): a bad key repeated across windows used to re-dispatch the fill
kernel every window just to re-discover it cannot be decompressed.

Import discipline: this module must import WITHOUT jax (backend.py and
host-only tooling read the KES namespace); the device fill imports
ed25519_jax lazily inside `_fill`.

Counters live in the observability registry (ISSUE 7): the process-wide
cache registers its hit/miss/device_fill/eviction counters under the
`precompute.*` namespace so metrics snapshots, the Prometheus
exposition and the benchmark's counters all read ONE source of truth —
while the original attribute names (`cache.hits`,
`cache.device_fills += 1`, ...) keep working as read/write property
aliases, so every existing assertion and call site is untouched.  Per-instance caches (tests)
carry private unregistered counters with the same API.
"""
from __future__ import annotations

import threading
from collections import OrderedDict

import numpy as np

from ..observe import metrics as _metrics
from ..observe import spans as _spans

# sentinel stored for keys whose decompression failed: assemble() keeps
# reporting known=False for them without re-dispatching the fill kernel
_BAD = object()
_MISSING = object()

# Sum-KES hash paths walked on the HOST (CryptoBackend.split_mixed_cached,
# a cache miss there; the mesh backend's windows).  The one-chip path runs
# cold paths as device Blake2b jobs and never counts here.
KES_HOST_WALKS = _metrics.counter("precompute.kes_host_walks")


class _Stripe:
    """One namespace's lock with contention accounting.

    Pre-service, the cache relied on GIL-atomic dict ops plus
    best-effort LRU bookkeeping for exactly TWO concurrent threads (the
    pipelined replay's producer/consumer).  The adaptive batching
    service multiplies the submitter count, so the LRU bookkeeping now
    runs under a real lock — ONE PER NAMESPACE (points / KES hash
    paths), so Ed25519-key traffic never waits behind a KES walk.  The
    device fill itself stays OUTSIDE the stripe: a multi-second kernel
    dispatch must not serialize every other submitter's lookups.

    Contention is measured, not guessed: a non-immediate acquire bumps
    the owner's `lock_wait` counter (`precompute.lock_wait` in the
    registry) before blocking."""

    __slots__ = ("_lock", "_owner")

    def __init__(self, owner: "PrecomputeCache"):
        self._lock = threading.Lock()
        self._owner = owner

    def __enter__(self) -> "_Stripe":
        if not self._lock.acquire(blocking=False):
            self._owner.lock_wait += 1
            self._lock.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self._lock.release()


class PrecomputeCache:
    """vk bytes -> per-key precomputation, LRU-bounded, with batched
    device fill and a separate KES hash-path outcome namespace.

    assemble() returns ((8, N) uint32 xA-words, x128-words, y128-words,
    known (N,) bool) for a batch of keys, computing every missing unique
    key in one a128_kernel call (padded to a power-of-two bucket so
    repeats hit the jit cache).  `known` is False for keys that failed
    decompression (not on the curve / bad length) — callers must mask
    those invalid, since the verify kernels trust the cached x and skip
    the square-root check entirely.

    Eviction is exact LRU per namespace: every hit refreshes the entry,
    and inserts past `max_entries` drop the least-recently-used entry
    (the r5 ancestor dropped the oldest half in insertion order, which
    could evict keys touched every window)."""

    # counter names in the registry namespace (ISSUE 7); the attribute
    # aliases below expose each as plain read/write ints
    _COUNTERS = ("hits", "misses", "device_fills", "filled_keys",
                 "evictions")

    def __init__(self, max_entries: int = 200_000, register: bool = False):
        self._c: OrderedDict = OrderedDict()    # vk -> (xa, x128, y128)|_BAD
        self._kes: OrderedDict = OrderedDict()  # hash_path_key -> (leaf_vk, ok)
        self.max_entries = max_entries
        # counters: the warm-path contract is `device_fills`/`filled_keys`
        # flat across a warm window (zero per-key device work).  They are
        # `always` instruments — load-bearing program state asserted by
        # tests, counted whether or not observation is enabled —
        # and only the process-wide cache binds them into the global
        # registry (per-instance caches in tests stay private).
        mk = ((lambda n, **kw: _metrics.counter(n, always=True, **kw))
              if register
              else (lambda n, **kw: _metrics.Counter(n, always=True, **kw)))
        self._counters = {name: mk(f"precompute.{name}")
                          for name in self._COUNTERS}
        # lock contention is timing-shaped (how often two submitters
        # collide), so unlike the functional counters it is excluded
        # from the deterministic snapshot (stable=False)
        self._counters["lock_wait"] = mk("precompute.lock_wait",
                                         stable=False)
        # per-namespace lock striping: point entries and KES hash-path
        # outcomes contend independently
        self._lock_c = _Stripe(self)
        self._lock_kes = _Stripe(self)

    # -- counter aliases (the pre-registry accessor names, kept) ------------
    def _alias(name):  # noqa: N805 — descriptor factory, not a method
        def _get(self):
            return self._counters[name].value

        def _set(self, v):
            self._counters[name].value = v
        return property(_get, _set)

    hits = _alias("hits")
    misses = _alias("misses")
    device_fills = _alias("device_fills")
    filled_keys = _alias("filled_keys")
    evictions = _alias("evictions")
    lock_wait = _alias("lock_wait")
    del _alias

    def __len__(self):
        return len(self._c)

    def __contains__(self, vk: bytes) -> bool:
        return vk in self._c

    # -- point entries (Ed25519 A / VRF Y) ----------------------------------
    def assemble(self, vks):
        # snapshot this batch's entries while scanning: a fill larger than
        # max_entries may evict keys this very batch hit, and the read
        # below must still see them (results stay correct under ANY bound)
        local: dict = {}
        missing = []
        with self._lock_c:
            for vk in vks:
                if vk in local:
                    continue
                ent = self._c.get(vk, _MISSING)
                if ent is not _MISSING:
                    try:                # recency touch stays best-effort
                        self._c.move_to_end(vk)   # (eviction-tolerant:
                    except KeyError:    # an unlocked legacy caller may
                        pass            # still race the bookkeeping)
                    self.hits += 1
                    local[vk] = ent
                else:
                    missing.append(vk)
                    local[vk] = _BAD   # overwritten by the fill below
        self.misses += len(missing)
        if missing:
            local.update(self._fill(missing))
        from . import ed25519_jax as EJ
        n = len(vks)
        xa = np.empty((8, n), dtype=np.uint32)
        xs = np.empty((8, n), dtype=np.uint32)
        ys = np.empty((8, n), dtype=np.uint32)
        known = np.zeros(n, dtype=bool)
        for j, vk in enumerate(vks):
            ent = local[vk]
            if ent is _BAD:
                # any valid point works: the lane is masked via `known`
                xa[:, j] = EJ._GX_W
                xs[:, j] = EJ._B128X_W
                ys[:, j] = EJ._B128Y_W
            else:
                xa[:, j], xs[:, j], ys[:, j] = ent
                known[j] = True
        return xa, xs, ys, known

    def _fill(self, missing) -> dict:
        """Batched device fill of every missing key (ONE a128_kernel
        dispatch, padded to a power-of-two bucket).  Undecodable keys are
        stored as negative entries so they never refill.  Returns the
        fresh {vk: entry} map (assemble reads it directly so LRU eviction
        during the insert loop can never lose this batch's entries)."""
        import jax.numpy as jnp

        from . import ed25519_jax as EJ
        from . import field_jax as F
        m = 128
        while m < len(missing):
            m *= 2
        arr, len_ok = EJ._bytes_rows(missing + [b"\x00" * 32] *
                                     (m - len(missing)), 32)
        yA, signA, y_ok = EJ._decode_compressed(arr)
        self.device_fills += 1
        self.filled_keys += len(missing)
        with _spans.span("precompute.fill", cat="device"):
            xa, x, y, ok = EJ.a128_kernel(jnp.asarray(yA),
                                          jnp.asarray(signA))
            xai = F.unpack(np.asarray(xa))
            xi = F.unpack(np.asarray(x))
            yi = F.unpack(np.asarray(y))
        ok = np.asarray(ok) & len_ok & y_ok
        fresh: dict = {}
        for j, vk in enumerate(missing):
            if ok[j]:
                fresh[vk] = (EJ._words_of_int(xai[j]),
                             EJ._words_of_int(xi[j]),
                             EJ._words_of_int(yi[j]))
            else:
                fresh[vk] = _BAD
            self._insert(self._c, vk, fresh[vk])
        return fresh

    # -- KES hash-path outcomes ---------------------------------------------
    def kes_get(self, key):
        """(leaf_vk, path_ok) for a hash-path identity (kes.hash_path_key),
        or None on first sighting."""
        with self._lock_kes:
            ent = self._kes.get(key)
            if ent is None:
                self.misses += 1
                return None
            try:                    # best-effort recency touch kept
                self._kes.move_to_end(key)   # (eviction-tolerant under
            except KeyError:        # any unlocked legacy caller)
                pass
            self.hits += 1
            return ent

    def kes_put(self, key, leaf_vk, path_ok: bool) -> None:
        self._insert(self._kes, key, (leaf_vk, bool(path_ok)))

    def kes_len(self) -> int:
        return len(self._kes)

    # -- plumbing ------------------------------------------------------------
    def _insert(self, od: OrderedDict, key, value) -> None:
        # under the namespace stripe; every step STILL tolerates a
        # concurrent mutation (the eviction-tolerant semantics from the
        # pipelined-replay era are kept — dict ops are GIL-atomic and a
        # legacy unlocked caller must not corrupt the LRU bookkeeping)
        with (self._lock_c if od is self._c else self._lock_kes):
            od[key] = value
            try:
                od.move_to_end(key)
            except KeyError:
                pass
            while len(od) > self.max_entries:
                try:
                    od.popitem(last=False)
                except KeyError:
                    break
                self.evictions += 1

    def clear(self) -> None:
        self._c.clear()
        self._kes.clear()

    def stats(self) -> dict:
        return {"entries": len(self._c), "kes_entries": len(self._kes),
                "hits": self.hits, "misses": self.misses,
                "device_fills": self.device_fills,
                "filled_keys": self.filled_keys,
                "evictions": self.evictions,
                "lock_wait": self.lock_wait}


# one process-wide cache: every backend instance (single-chip, sharded)
# and both primitives' host preps share it, so a key warmed by any path
# stays warm for all of them.  Its counters are the registry's
# `precompute.*` metrics.
GLOBAL_PRECOMPUTE_CACHE = PrecomputeCache(register=True)
