"""Cross-window per-key precomputation cache — the generalized A128Cache.

Which keys recur is a property of the chain, not of the protocol.  A
block's four HEADER proofs are a stake pool's (cold, VRF and KES leaf
keys), and a chain has few pools beside its blocks, so those keys recur
for thousands of blocks.  A block's hundreds of WITNESS keys are payment
keys: on a chain whose wallets derive a fresh address for every
transaction (CIP-1852) a syncing node meets nearly every one of them
once, and on a chain that pays back to two owners' addresses (db_synth's
default) it meets two.  The per-key precomputation is PURE either way,
so it is made once a key and kept while the bound allows:

- Ed25519 cold/payment keys: the decompressed affine x of A plus the
  affine coordinates of [2^128]A (the split-ladder table half), computed
  on device by ed25519_jax.a128_words_kernel at first sighting;
- VRF pool keys: the decompressed affine x of Y that feeds the cached-Y
  packed kernel (vrf_jax.vrf_verify_words_kernel) — the [c](-Y) half of
  the on-device triple table is derived from it per batch, so the cached
  x is the whole host-visible per-key cost;
- KES hash paths: the Blake2b-256 Merkle walk of a (depth, period, vk,
  merkle-path) tuple is independent of the signed message, so a pool's
  per-period subtree check has ONE answer for the thousands of headers
  it signs in that period.

The point tables live in ONE slot table of packed words ((24, slots)
uint32: xA, x([2^128]A), y([2^128]A)), indexed through a dict from vk
bytes to slot; a window in which every lane's key is new and a window in
which every key hits run the same array code, with no per-key Python
integers, entry objects or locked inserts.  The fill programs have two
fixed widths (FILL_NARROW lanes for a handful of new keys, tiles of
jax_backend.ED_TILE lanes for more), so no program is keyed on the count
of new keys.  KES outcomes keep an OrderedDict of their own.  Both
namespaces are LRU-bounded, with counters (`device_fills`,
`filled_keys`, `fill_lanes_padded`, `hits`, `misses`, `evictions`) so
the warm-path guarantee — a cache-warm window does ZERO per-key
decompression/table-build device calls — is assertable in tests and
readable in a run's counters.  `hits` counts LANES served from the
table (and KES lookups that hit); `misses` counts distinct keys that had
to be filled (and KES lookups that missed).

A batch's tables come in TWO PHASES (ISSUE 46).  `begin_assemble(vks)`
looks the keys up, copies the hits out and DISPATCHES the fill of the
distinct new keys, asynchronously; it returns a handle (`_Fill`) and
waits for nothing.  `finish_assemble(handle)` waits for what is left of
the device's work, copies the tables back, stores them and returns the
batch's lanes.  Between the two the caller does the host work that
needs no table: the window path (`JaxBackend._pack_ed`, `_finish_ed`)
hashes and packs the window's lanes there, so the producer no longer
sits out the device's 0.2 s a window of new keys.  `assemble(vks)` is
the one right after the other.  A handle belongs to the thread that
began it and is finished at most once; several threads may each hold
one at a time (the window's producer, `VerifyService`'s submitters).
Nothing is stored before `finish_assemble`: a handle dropped by an
exception leaves the table as if its keys had never been seen (their
fill was counted, and is made again when they are next met); a key two
open handles both began is filled twice and stored once; `clear()`
between the phases loses nothing (the hits were copied out at the
begin, the new keys go into the cleared table).  In BOTH phases the
device's part stays outside the stripe, which is held for the lookup
and for the store only.  `early_fill_keys` counts the keys whose fill
was begun ahead like that (of `filled_keys`, all of them), and
`fill_wait_us` the whole microseconds a finish stood blocked on the
device.

Unlike the r5 A128Cache, undecodable keys are cached too (as negative
entries): a bad key repeated across windows used to re-dispatch the fill
kernel every window just to re-discover it cannot be decompressed.

Import discipline: this module must import WITHOUT jax (backend.py and
host-only tooling read the KES namespace); the device fill imports
ed25519_jax lazily inside `_dispatch_tables`.

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
import time
from collections import OrderedDict
from itertools import repeat

import numpy as np

from ..observe import metrics as _metrics
from ..observe import spans as _spans

# Width of the narrow fill program: a window that meets a handful of new
# keys (a pool's next KES leaf key) fills them in one FILL_NARROW-lane
# call; a window that meets more walks them as whole tiles of
# jax_backend.ED_TILE lanes, the width the Ed25519 ladder measured
# cheapest a lane (PERF.md section 6, PR 30).  Two programs, whatever the
# count of new keys.
FILL_NARROW = 128
# words a key's table takes: xA, x([2^128]A), y([2^128]A), eight each
_TAB_ROWS = 24

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


class _Fill:
    """A batch's tables between `begin_assemble` and `finish_assemble`:
    the output buffer with the hits already in it, the lanes that
    missed (`miss`) and the key each carries (`missed`), the distinct
    new keys in first-seen order (`keys`; empty = nothing in flight,
    the finish does nothing) and what `_dispatch_tables` handed back
    for them (`pending`).  Held by the thread that began it, finished
    at most once; dropping it unfinished costs the cache nothing."""

    __slots__ = ("out", "known", "miss", "missed", "keys", "pending")

    def __init__(self, n: int):
        self.out = np.empty((_TAB_ROWS, n), dtype=np.uint32)
        self.known = np.empty(n, dtype=bool)
        self.miss = self.missed = self.pending = None
        self.keys: list = []


class PrecomputeCache:
    """vk bytes -> per-key precomputation, LRU-bounded, with batched
    device fill and a separate KES hash-path outcome namespace.

    assemble() returns ((8, N) uint32 xA-words, x128-words, y128-words,
    known (N,) bool) for a batch of keys, computing every missing unique
    key on the device in programs of two fixed widths
    (`_dispatch_tables`).  `known` is False for keys that failed
    decompression (not on the curve / bad length) — callers must mask
    those invalid, since the verify kernels trust the cached x and skip
    the square-root check entirely.

    assemble() is begin_assemble() then finish_assemble(): the first
    dispatches the new keys' fill and returns a handle without waiting,
    the second waits, stores and returns the lanes; a caller with host
    work that needs no table does it in between (the module's text says
    who may hold a handle and what a dropped one costs).  The device's
    part of a fill stays outside the stripe in both.

    Eviction is exact LRU per namespace: every hit refreshes the entry,
    and inserts past `max_entries` drop the least-recently-used entry
    (the r5 ancestor dropped the oldest half in insertion order, which
    could evict keys touched every window)."""

    # counter names in the registry namespace (ISSUE 7); the attribute
    # aliases below expose each as plain read/write ints
    _COUNTERS = ("hits", "misses", "device_fills", "filled_keys",
                 "fill_lanes_padded", "evictions", "early_fill_keys")
    # timing-shaped ones: how often two submitters collide, how long a
    # finish stood blocked on the device.  Unlike the functional
    # counters they are excluded from the deterministic snapshot
    _TIMING_COUNTERS = ("lock_wait", "fill_wait_us")

    def __init__(self, max_entries: int = 200_000, register: bool = False):
        # point entries: vk -> slot of the word table.  A slot holds the
        # key's table column, whether the key decoded (False = a negative
        # entry, its column the filler point), its recency stamp (0 = a
        # free slot) and the key itself, for eviction
        self._slot: dict = {}
        self._tab = np.empty((_TAB_ROWS, 0), dtype=np.uint32)
        self._known = np.empty(0, dtype=bool)
        self._stamp = np.empty(0, dtype=np.int64)
        self._key_at = np.empty(0, dtype=object)
        self._free: list = []     # evicted slots, reused first
        self._used = 0            # slots ever handed out
        self._clock = 0           # last recency stamp given
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
        for name in self._TIMING_COUNTERS:
            self._counters[name] = mk(f"precompute.{name}", stable=False)
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
    fill_lanes_padded = _alias("fill_lanes_padded")
    evictions = _alias("evictions")
    early_fill_keys = _alias("early_fill_keys")
    lock_wait = _alias("lock_wait")
    fill_wait_us = _alias("fill_wait_us")
    del _alias

    def __len__(self):
        return len(self._slot)

    def __contains__(self, vk: bytes) -> bool:
        return vk in self._slot

    # -- point entries (Ed25519 A / VRF Y) ----------------------------------
    def assemble(self, vks):
        return self.finish_assemble(self._begin(vks))

    def begin_assemble(self, vks):
        """First phase of `assemble`, for a caller with host work to do
        before it needs the tables: hits copied out, the new keys' fill
        dispatched, nothing waited for.  Returns the handle
        `finish_assemble` takes."""
        fill = self._begin(vks)
        self.early_fill_keys += len(fill.keys)
        return fill

    def _begin(self, vks) -> _Fill:
        n = len(vks)
        fill = _Fill(n)
        # this batch's hits are copied out while the stripe is held: a
        # fill larger than max_entries may evict keys this very batch
        # hit, and the lanes must still carry them (results stay correct
        # under ANY bound)
        with self._lock_c:
            slots = self._slots_of(vks)
            hit = np.flatnonzero(slots >= 0)
            if hit.size:
                at = slots[hit]
                fill.out[:, hit] = self._tab[:, at]
                fill.known[hit] = self._known[at]
                self._stamp[at] = self._stamps(hit.size)
        self.hits += int(hit.size)
        if hit.size < n:
            fill.miss = np.flatnonzero(slots < 0)
            fill.missed = [vks[j] for j in fill.miss.tolist()]
            # the distinct new keys in first-seen order
            fill.keys = list(dict.fromkeys(fill.missed))
            self.misses += len(fill.keys)
            self.device_fills += 1
            self.filled_keys += len(fill.keys)
            with _spans.span("precompute.fill", cat="device"):
                fill.pending = self._dispatch_tables(fill.keys)
        return fill

    def finish_assemble(self, fill: _Fill):
        """Second phase: the new keys' tables fetched (the wait that is
        left of the device's work), stored, and written to the lanes
        that missed.  Undecodable keys are stored as negative entries
        so they never refill.  The lanes read this fill's own columns,
        so LRU eviction during the store can never lose them."""
        keys = fill.keys
        if keys:
            with _spans.span("precompute.fill", cat="device"):
                tab, ok = self._fetch_tables(fill.pending)
                with _spans.span("fill.store", cat="device"):
                    self._store(keys, tab, ok)
            if len(keys) < len(fill.missed):      # a new key met twice
                place = {vk: i for i, vk in enumerate(keys)}
                at = np.array(list(map(place.__getitem__, fill.missed)))
                tab, ok = tab[:, at], ok[at]
            fill.out[:, fill.miss] = tab
            fill.known[fill.miss] = ok
            fill.keys, fill.pending = [], None    # finished: once only
        out = fill.out
        return out[0:8], out[8:16], out[16:24], fill.known

    def _dispatch_tables(self, keys):
        """The device's part of a fill, first half, at fixed program
        widths: up to FILL_NARROW keys in one narrow call, more as whole
        ED_TILE-lane tiles of one program called a tile at a time, all
        dispatched and none waited for.  Spans: fill.pack (key bytes to
        words, on the host), fill.dispatch.  Returns what
        `_fetch_tables` takes."""
        import jax.numpy as jnp

        from . import ed25519_jax as EJ
        from . import jax_backend as JB
        k = len(keys)
        width = FILL_NARROW if k <= FILL_NARROW else JB.ED_TILE
        lanes = -(-k // width) * width
        self.fill_lanes_padded += lanes
        with _spans.span("fill.pack", cat="device"):
            arr, len_ok = EJ._bytes_rows(keys, 32)
            rows = np.zeros((lanes, 32), dtype=np.uint8)
            rows[:k] = arr
            Aw, sign, y_ok = EJ._point_words(rows)
        with _spans.span("fill.dispatch", cat="device"):
            parts = [EJ.a128_words_kernel(jnp.asarray(Aw[:, o:o + width]),
                                          jnp.asarray(sign[o:o + width]))
                     for o in range(0, lanes, width)]
        return parts, len_ok & y_ok[:k]

    def _fetch_tables(self, pending):
        """Second half: ((24, k) uint32 columns, ok (k,) bool) of a
        dispatched fill.  Span fill.fetch: the wait for the device (the
        calls run in order, so for the last one; `fill_wait_us`) and the
        copy back."""
        import jax

        from . import ed25519_jax as EJ
        parts, host_ok = pending
        k = host_ok.size
        with _spans.span("fill.fetch", cat="device"):
            t0 = time.perf_counter()
            jax.block_until_ready(parts[-1])
            self.fill_wait_us += int((time.perf_counter() - t0) * 1e6)
            parts = jax.device_get(parts)     # every copy asked for at once
            tab = np.concatenate([t for t, _ok in parts], axis=1)[:, :k]
            ok = np.concatenate([o for _t, o in parts])[:k]
        ok = ok & host_ok
        # any valid point works for a key that does not decode: its
        # lanes are masked via `known`
        tab[:, ~ok] = EJ._FILLER_COL[:, None]
        return tab, ok

    def _slots_of(self, vks) -> np.ndarray:
        """Each key's slot, -1 where it has none (under the stripe)."""
        return np.array(list(map(self._slot.get, vks, repeat(-1))),
                        dtype=np.int64)

    def _stamps(self, n: int) -> np.ndarray:
        """The next n recency stamps, oldest first (under the stripe)."""
        self._clock += n
        return np.arange(self._clock - n + 1, self._clock + 1)

    def _store(self, keys, tab, ok) -> None:
        """Insert a fill's entries in order, evicting least-recently-used
        ones past `max_entries` — as if one by one, so of a fill larger
        than the bound the LAST `max_entries` keys stay."""
        with self._lock_c:
            over = len(keys) - self.max_entries
            if over > 0:
                self.evictions += over
                keys, tab, ok = keys[over:], tab[:, over:], ok[over:]
            at = self._slots_of(keys)
            new = np.flatnonzero(at < 0)   # not filled by a racing thread
            self._evict(len(self._slot) + new.size - self.max_entries,
                        keep=at[at >= 0])
            at[new] = self._take_slots(new.size)
            self._tab[:, at] = tab
            self._known[at] = ok
            self._stamp[at] = self._stamps(at.size)
            self._key_at[at] = keys
            self._slot.update(zip(keys, at.tolist()))

    def _evict(self, n: int, keep) -> None:
        """Free the n least recently used slots (never one of `keep`)."""
        if n <= 0:
            return
        stamp = self._stamp[:self._used].copy()
        stamp[keep] = 0
        live = np.flatnonzero(stamp > 0)
        n = min(n, live.size)
        if not n:
            return
        gone = live[np.argpartition(stamp[live], n - 1)[:n]]
        for vk in self._key_at[gone]:
            del self._slot[vk]
        self._stamp[gone] = 0
        self._key_at[gone] = None
        self._free.extend(gone.tolist())
        self.evictions += n

    def _take_slots(self, n: int) -> np.ndarray:
        """n free slots: evicted ones first, then fresh ones (the arrays
        double until they hold them, up to the bound)."""
        reuse = min(n, len(self._free))
        out = self._free[len(self._free) - reuse:]
        del self._free[len(self._free) - reuse:]
        fresh = n - reuse
        out.extend(range(self._used, self._used + fresh))
        self._used += fresh
        room = self._stamp.size
        if self._used > room:
            grow = max(self._used, min(max(2 * room, 1024),
                                       self.max_entries)) - room
            self._tab = np.concatenate(
                [self._tab, np.empty((_TAB_ROWS, grow), np.uint32)], axis=1)
            self._known = np.concatenate([self._known, np.zeros(grow, bool)])
            self._stamp = np.concatenate(
                [self._stamp, np.zeros(grow, np.int64)])
            self._key_at = np.concatenate(
                [self._key_at, np.empty(grow, object)])
        return np.asarray(out, dtype=np.int64)

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
        # under the namespace stripe; every step STILL tolerates a
        # concurrent mutation (the eviction-tolerant semantics from the
        # pipelined-replay era are kept — dict ops are GIL-atomic and a
        # legacy unlocked caller must not corrupt the LRU bookkeeping)
        od = self._kes
        with self._lock_kes:
            od[key] = (leaf_vk, bool(path_ok))
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

    def kes_len(self) -> int:
        return len(self._kes)

    # -- plumbing ------------------------------------------------------------
    def clear(self) -> None:
        with self._lock_c:
            self._slot.clear()
            self._stamp[:] = 0
            self._key_at[:] = None
            self._free.clear()
            self._used = 0
        self._kes.clear()

    def stats(self) -> dict:
        return {"entries": len(self._slot), "kes_entries": len(self._kes),
                "hits": self.hits, "misses": self.misses,
                "device_fills": self.device_fills,
                "filled_keys": self.filled_keys,
                "fill_lanes_padded": self.fill_lanes_padded,
                "evictions": self.evictions,
                "early_fill_keys": self.early_fill_keys,
                "lock_wait": self.lock_wait,
                "fill_wait_us": self.fill_wait_us}


# one process-wide cache: every backend instance (single-chip, sharded)
# and both primitives' host preps share it, so a key warmed by any path
# stays warm for all of them.  Its counters are the registry's
# `precompute.*` metrics.
GLOBAL_PRECOMPUTE_CACHE = PrecomputeCache(register=True)
