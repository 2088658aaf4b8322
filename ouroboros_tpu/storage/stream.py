"""Streaming replay engine — disk → decode → verify, restartable.

Reference: the db-analyser replay path (SURVEY.md §3.5): the node opens
LedgerDB from the newest on-disk snapshot (LedgerDB/OnDisk.hs:277) and
streams ImmutableDB chunks through iterators (Impl/Iterator.hs) instead
of materialising the chain; DiskPolicy decides when replay checkpoints
(DiskPolicy.hs).  Our replay so far loaded every block into memory and
started from genesis — fine for a test chain, not for a million-block
mainnet DB.

This module closes that gap with a third pipeline stage in front of the
producer/consumer replay (consensus/pipeline.py):

    prefetcher (thread)          producer (thread)      consumer (caller)
    --------------------------   --------------------   -----------------
    chunk n+k: ONE whole-file    window w+1: seq pass   window w: drain
      read through the FsApi       packing, prefetch,     install betas
      seam, CBOR decode into       async submit           on_window hook:
      window-sized batches                                  DiskPolicy
      (bounded read-ahead;                                  take_snapshot
       blocks when `depth`
       batches are waiting)

Where the decoding runs: in decode worker PROCESSES where the decoder
can be shipped to one (storage/decode_pool.py: the prefetcher sends a
chunk's bytes out and unpickles the built blocks, so the decode leaves
the interpreter lock the three threads share), else on the prefetch
thread itself.  The blocks are the same either way.

Disk + decode seconds hide behind device verify exactly the way the
host sequential pass does: the prefetcher feeds a third on/off signal
into the shared ProgressTracker ({prefetch busy} ∩ {≥1 window in
flight} accumulates O(1) into ``disk_hidden_secs``), and its work is
span-recorded under the ``disk`` phase, beside host-seq/device.

Era discipline: the engine is protocol-agnostic — a Cardano-composed
DB (eras/cardano.py) replays Byron EBBs through the Shelley translation
in ONE stream because era crossing lives in the hard-fork rules the
sequential pass already drives; the engine merely counts the crossings
it decodes (``replay.stream.era_crossings``).

Restartability: `on_window` fires on the consumer thread only after a
window's proofs all held, so the state it hands over is fully verified
— the engine snapshots it crash-consistently (storage/ledgerdb.py:
temp file + checksum + atomic rename; a corrupt/partial newest snapshot
falls back to the previous one) every `snapshot_interval_slots`.  At
open, `resume=True` restores the newest snapshot whose point is still
on the immutable chain and streams strictly AFTER it: a killed replay
resumes in seconds and reaches a byte-identical final state hash.

The cyclic collector: the prefetcher runs ahead of the replay, so whole
windows of decoded blocks (frozen dataclasses, tuples, bytes, ints: no
cycles) are alive at once, and every full pass of CPython's collector
would walk all of them again.  For the length of a replay the engine
owns the collector's permanent generation (`_ReplayCollector`): each
decoded chunk is moved into it, where no pass looks, and reference
counting still frees a block the moment the pipeline drops it.

The snapshot codec defaults to Python-native serialisation behind the
same ``encode_state``/``decode_state`` seam LedgerDB always had (the
reference CBOR-encodes its ledger state; our era states are plain
frozen dataclasses, so the native codec round-trips them exactly — a
custom CBOR codec plugs into the same two arguments).
"""
from __future__ import annotations

import contextlib
import gc
import pickle
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator, Optional

from ..consensus.pipeline import ProgressTracker
from ..observe import flight as _flight
from ..observe import metrics as _metrics
from ..observe import spans as _spans
from .decode_pool import POOL, Lease, decode_blocks
from .ledgerdb import DiskPolicy, LedgerDB

#: header field carrying the hard-fork era tag (combinator.ERA_FIELD —
#: re-declared here so the storage layer stays import-light; the
#: combinator's tests pin the two equal)
ERA_FIELD = "hfc_era"

# observational stream instruments (live scrape/obsreport); the engine's
# own stats come from per-instance fields so they stay exact even with
# observation disabled.  Counts of chunks/blocks/bytes/eras are pure
# functions of the workload (stable); stall/depth/seconds are
# scheduling- and wall-clock-dependent (unstable).
_CHUNKS = _metrics.counter("replay.stream.chunks_read")
_BLOCKS = _metrics.counter("replay.stream.blocks_decoded")
_BYTES = _metrics.counter("replay.stream.bytes_read")
_ERAS = _metrics.counter("replay.stream.era_crossings")
# transactions of the blocks the prefetcher took in, wherever they were
# decoded, and those of them that came back from a decode worker with
# their id already hashed there (`txid_hashed`: ProtocolBlock.from_bytes
# handed it over, and nothing is left for the host pass to encode)
_TXS = _metrics.counter("replay.decode.txs")
_SHIPPED_TXIDS = _metrics.counter("replay.decode.shipped_txids")
_SNAPS = _metrics.counter("replay.stream.snapshots_written")
_STALLS = _metrics.counter("replay.stream.prefetch_stalls", stable=False)
# whole microseconds the prefetch thread spent in those stalls: blocked at
# the read-ahead bound with a decoded window in hand
_BACKPRESSURE_US = _metrics.counter("replay.stream.backpressure_wait_us",
                                    stable=False)
_DEPTH = _metrics.gauge("replay.stream.prefetch_depth", stable=False)
_DISK_SECS = _metrics.gauge("replay.stream.disk_secs", stable=False)
_DISK_HIDDEN = _metrics.gauge("replay.stream.disk_hidden_secs",
                              stable=False)
_SNAP_SECS = _metrics.gauge("replay.stream.snapshot_write_secs",
                            stable=False)
_RESTORE_SECS = _metrics.gauge("replay.stream.restore_secs", stable=False)
_RESUME_SLOT = _metrics.gauge("replay.stream.resumed_from_slot")

# the cyclic collector while a replay runs (`_ReplayCollector`): whole
# microseconds inside collections of any generation, how many of them
# were full (generation 2), freezes, and the objects they moved.  All
# four depend on when the collector happens to run: unstable.
_GC_PAUSE_US = _metrics.counter("replay.gc.pause_us", stable=False)
_GC_FULL = _metrics.counter("replay.gc.full_passes", stable=False)
_GC_FREEZES = _metrics.counter("replay.gc.freezes", stable=False)
_GC_FROZEN = _metrics.counter("replay.gc.frozen_objects", stable=False)

# what the prefetch thread and the caller's thread used, start to end of
# their part of a replay (observe/spans.py `thread_usage`): CPU time in
# whole microseconds, and the times the kernel took the core from them
_PREFETCH_CPU_US = _metrics.counter("replay.thread_cpu_us.prefetch",
                                    stable=False)
_PREFETCH_PREEMPTS = _metrics.counter("replay.thread_preempts.prefetch",
                                      stable=False)
_CALLER_CPU_US = _metrics.counter("replay.thread_cpu_us.caller",
                                  stable=False)
_CALLER_PREEMPTS = _metrics.counter("replay.thread_preempts.caller",
                                    stable=False)

# load-bearing thread accounting, like the pipeline's producer pair: a
# replay that returns with started != finished leaked its prefetcher
_P_STARTED = _metrics.counter("stream.prefetchers_started", always=True)
_P_FINISHED = _metrics.counter("stream.prefetchers_finished", always=True)

THREAD_NAME = "ouro-stream-prefetch"


def pickle_encode(state: Any) -> bytes:
    return pickle.dumps(state, protocol=pickle.HIGHEST_PROTOCOL)


def pickle_decode(raw: Any) -> Any:
    return pickle.loads(bytes(raw))


@dataclass(frozen=True)
class StreamResumed:
    """Typed flight-recorder event: a replay restored from a snapshot
    (arm FLIGHT around a replay to make resume part of any post-mortem,
    e.g. a kill/resume parity mismatch)."""
    slot: int
    point_slot: int
    snapshots_seen: int


class _ReplayCollector:
    """The cyclic collector's permanent generation, lent to the replays
    of this process.

    Inside the `with`, `freeze()` moves every object the collector
    tracks into the permanent generation (`gc.freeze()`, a splice of
    three lists), so no pass of any generation walks the decoded chain
    the engine is holding.  Decoded blocks hold no cycles: reference
    counting frees a frozen block when the pipeline drops it, as before.

    What a freeze also catches is whatever cyclic garbage other threads
    held at that instant.  `lift()` bounds it (the engine calls it at
    every cleanly drained window): the permanent generation goes back
    to the collector, and `freeze()` does nothing until the collector's
    own next full pass has seen the heap; the first freeze after that
    pass takes the whole heap back.  The pass is the collector's, not
    forced: one costs 0.3 s in a process that has traced its device
    programs, whatever the chain, and a light-bodied window lasts
    little longer.  So a replay never sees more full passes than it
    would without this class, and a heavy one sees one a window.

    On leaving, `gc.unfreeze()` hands the permanent generation back,
    and the enabled flag, the thresholds (neither ever touched) and
    `gc.callbacks` are as they were on entry.  `gc.unfreeze()` empties
    the WHOLE permanent generation: a process that had frozen its own
    start-up heap loses that optimisation, never correctness, when a
    replay ends.  The freeze count cannot tell such a process from any
    other (CPython 3.12 parks its immortal objects there at every full
    pass: a fresh interpreter reports 375), so nothing branches on it.

    One instance a process, because the collector is one a process: two
    replays at once share the depth count under the lock, and only the
    outermost exit unfreezes.  Outside any replay `freeze()` and
    `lift()` do nothing, so a bare `BlockPrefetcher` leaves the
    collector alone.

    While entered, a `gc.callbacks` pair times every collection
    (`replay.gc.pause_us`, `replay.gc.full_passes`); each freeze of a
    decoded chunk counts `replay.gc.freezes` and, in
    `replay.gc.frozen_objects`, the objects it moved: those tracked
    outside the permanent generation just before it, which is the
    growth of `gc.get_freeze_count()` without that call's walk of the
    whole permanent generation (19 ms at 337,000 objects)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._depth = 0            # replays running in this process
        self._lifted = False       # lift() was called, and since then
        self._full_pass = False    # ... a full pass has seen the heap
        self._t0_ns = 0            # start of the collection under way
        self._carry_ns = 0         # what whole microseconds left over

    def __enter__(self) -> "_ReplayCollector":
        with self._lock:
            self._depth += 1
            if self._depth == 1:
                gc.callbacks.append(self._on_collection)
                gc.freeze()        # the heap as found: not the chain's
                self._lifted = False
        return self

    def __exit__(self, *exc) -> bool:
        with self._lock:
            self._depth -= 1
            if self._depth == 0:
                gc.unfreeze()
                gc.callbacks.remove(self._on_collection)
        return False

    def _on_collection(self, phase: str, info: dict) -> None:
        # takes no lock: a collection can start inside freeze().
        # Collections never overlap (the interpreter lock, and the
        # collector's own flag), so one start reading is enough
        if phase == "start":
            self._t0_ns = time.perf_counter_ns()
            return
        ns = time.perf_counter_ns() - self._t0_ns + self._carry_ns
        _GC_PAUSE_US.inc(ns // 1000)
        self._carry_ns = ns % 1000
        if info["generation"] == 2:
            _GC_FULL.inc()
            self._full_pass = True

    def freeze(self) -> None:
        """Move what has been allocated since the last freeze (the
        chunk just decoded, mostly) out of the collector's sight."""
        with self._lock:
            if not self._depth:
                return
            if self._lifted:
                if self._full_pass:
                    gc.freeze()    # the whole heap again: not counted
                    self._lifted = False
                return
            _GC_FROZEN.inc(len(gc.get_objects()))
            gc.freeze()
            _GC_FREEZES.inc()

    def lift(self) -> None:
        """Hand the permanent generation back until a full pass has
        seen the heap: cyclic garbage frozen by accident is the
        collector's again from the next call on."""
        with self._lock:
            if self._depth and not self._lifted:
                gc.unfreeze()
                self._full_pass = False
                self._lifted = True


#: the one lender of the collector's permanent generation (see the class)
_COLLECTOR = _ReplayCollector()


class BlockPrefetcher:
    """Bounded read-ahead: a background thread streams (and decodes)
    ImmutableDB chunks into window-sized batches; iterating the
    prefetcher yields decoded blocks, blocking only when the reader is
    genuinely behind the replay.

    Reads are chunk-granular through the FsApi seam (`db.chunk_blocks`:
    one whole-file read per chunk) so a spinning disk sees sequential
    I/O; DBs without the chunk API (the reference-format read view)
    fall back to the per-block iterator, same thread, same bounds.

    Where decoding runs is read off the decoder, by no option: one that
    pickles here and loads in a decode worker (`decode_pool.POOL.lease`:
    `db_analyser.load_db`'s do) has its chunks decoded in the worker
    processes, dispatched ahead and collected in chain order, and this
    thread only unpickles the built blocks; any other (a closure, a
    lambda, a stateful decoder), a DB without the chunk API, and a
    second replay while one holds the pool, decode on this thread.
    Both run `decode_pool.decode_blocks` with the same decoder, so the
    blocks, what they keep of their bytes and a decode error's type are
    the same.
    Blocks in flight at the workers count as read ahead: no chunk goes
    out while decoded-but-unconsumed blocks plus those in flight would
    pass `depth * window` (one chunk always may, so the stream moves).

    Coordination: one Condition guards {batches, stop, eof, error}.
    The thread blocks while `depth` batches are queued (back-pressure),
    the consumer blocks while none are; `close()` wakes and joins the
    thread — the engine calls it in a finally, so an aborted replay
    (first-error-wins, a snapshot-hook kill) never leaks it.  A read or
    decode failure (a worker's too: it arrives as the exception the
    worker raised) parks on `error` and re-raises on the consumer after
    the already-queued batches drain.  `close()` mid-stream drops what
    is in flight and gives the workers back idle; a worker that dies
    fails the replay with `DecodeWorkerDied`.

    `on_decoded(blocks)`, if given, sees every chunk's blocks on this
    thread as they arrive (db_analyser counts blocks and proofs there:
    a count inside the decoder would stay in a worker).

    The cyclic collector: after each decoded chunk, on this thread and
    outside the Condition, the prefetcher offers the chunk to the
    replay's `_ReplayCollector`, which moves it out of the collector's
    sight.  The engine owns that context; a prefetcher used alone (no
    replay running in the process) leaves the collector as it is."""

    def __init__(self, db, decode: Callable[[bytes], Any],
                 window: int = 512, depth: int = 4,
                 tracker: Optional[ProgressTracker] = None,
                 after_hash: Optional[bytes] = None,
                 on_decoded: Optional[Callable[[list], None]] = None):
        self.db = db
        self.decode = decode
        self.window = max(1, window)
        self.depth = max(1, depth)
        self.tracker = tracker
        self.after_hash = after_hash
        self.on_decoded = on_decoded
        # exact per-instance accounting (engine stats read these; the
        # registry instruments mirror them for live observers)
        self.chunks_read = 0
        self.blocks_decoded = 0
        self.bytes_read = 0
        self.era_crossings = 0
        self.stalls = 0
        self._last_era: Optional[int] = None
        self._taken = 0            # blocks the consumer has popped
        self._cond = threading.Condition()
        self._batches: deque = deque()
        self._stop = False
        self._eof = False
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run,
                                        name=THREAD_NAME, daemon=True)

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "BlockPrefetcher":
        _P_STARTED.inc()
        self._thread.start()
        return self

    def close(self) -> None:
        """Stop and join the prefetch thread (idempotent)."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread.ident is not None:
            self._thread.join()

    # -- the reading thread --------------------------------------------------
    def _account(self, blocks: list, shipped: bool = False) -> list:
        """A chunk's decoded blocks, wherever they were decoded
        (`shipped`: in a decode worker): era crossings, counts, the
        caller's hook."""
        txs = hashed = 0
        for b in blocks:
            hdr = getattr(b, "header", b)
            era = hdr.get(ERA_FIELD) if hasattr(hdr, "get") else None
            if era is not None:
                if self._last_era is not None and era != self._last_era:
                    self.era_crossings += 1
                    _ERAS.inc()
                self._last_era = era
            body = getattr(b, "body", ())
            txs += len(body)
            if shipped:    # a class that does not say carries none
                hashed += sum([getattr(tx, "txid_hashed", False)
                               for tx in body])
        _TXS.inc(txs)
        _SHIPPED_TXIDS.inc(hashed)
        self.blocks_decoded += len(blocks)
        _BLOCKS.inc(len(blocks))
        if self.on_decoded is not None:
            self.on_decoded(blocks)
        return blocks

    @contextlib.contextmanager
    def _disk(self, span_name: str, cpu: bool = False):
        """The disk signal (tracker + a `disk`-phase span) around work
        of this thread: a read, a decode, the wait for a worker's
        reply; never the queue wait."""
        tracker = self.tracker
        if tracker is not None:
            tracker.disk_begin()
        try:
            with _spans.span(span_name, cat="disk", cpu=cpu) as sp:
                yield sp
        finally:
            if tracker is not None:
                tracker.disk_end()

    def _note_read(self, pairs) -> None:
        nbytes = sum(len(raw) for _e, raw in pairs)
        self.chunks_read += 1
        self.bytes_read += nbytes
        _CHUNKS.inc()
        _BYTES.inc(nbytes)

    def _decode_batch(self, pairs) -> list:
        """Decode on this thread (a decoder that does not ship)."""
        with self._disk("stream.decode"):
            return self._account(decode_blocks(
                self.decode, [raw for _entry, raw in pairs]))

    def _collect(self, lease: Lease) -> Optional[list]:
        """The oldest chunk out at the workers, as blocks; None when the
        consumer closed the stream meanwhile."""
        with self._disk("stream.decode") as sp:
            blocks = lease.collect(lambda: self._stop, into=sp)
            return None if blocks is None \
                else self._account(blocks, shipped=True)

    def _read_chunks(self) -> Iterator[list]:
        """(entry, raw) pairs a chunk, from the resume cursor on."""
        cursor = self.db.start_after(self.after_hash)
        if cursor is None:
            return
        n0, i0 = cursor
        for n in self.db.chunk_numbers():
            if n < n0:
                continue
            with self._disk("stream.read", cpu=True):
                pairs = self.db.chunk_blocks(
                    n, from_index=i0 if n == n0 else 0)
            self._note_read(pairs)
            yield pairs

    def _read_decoded(self) -> Iterator[list]:
        """Decoded blocks in chain order, one chunk's worth per step."""
        if not hasattr(self.db, "chunk_blocks"):
            yield from self._read_decoded_per_block()
            return
        lease = POOL.lease(self.decode)
        if lease is None:              # the decoder stays in this process
            for pairs in self._read_chunks():
                yield self._decode_batch(pairs)
            return
        made = 0                       # blocks this generator has yielded
        bound = self.depth * self.window
        try:
            for pairs in self._read_chunks():
                # the oldest chunks come back first, until a worker is
                # free and the read-ahead bound has room for this one
                while lease.blocks_in_flight and (
                        not lease.idle
                        or made - self._taken + lease.blocks_in_flight
                        + len(pairs) > bound):
                    blocks = self._collect(lease)
                    if blocks is None:
                        return
                    made += len(blocks)
                    yield blocks
                if pairs:
                    lease.dispatch([raw for _entry, raw in pairs])
            while lease.blocks_in_flight:
                blocks = self._collect(lease)
                if blocks is None:
                    return
                yield blocks
        finally:
            lease.release()

    def _read_decoded_per_block(self) -> Iterator[list]:
        """Generic fallback: the per-block iterator (reference-format
        views), decoded on this thread a window's worth at a time;
        `after_hash` skips the already-replayed prefix."""
        skipping = self.after_hash is not None
        buf_pairs: list = []
        for entry, raw in self.db.stream():
            if skipping:
                if getattr(entry, "hash", None) == self.after_hash \
                        or getattr(entry, "header_hash",
                                   None) == self.after_hash:
                    skipping = False
                continue
            buf_pairs.append((entry, raw))
            if len(buf_pairs) >= self.window:
                self._note_read(buf_pairs)     # one read burst ≈ one chunk
                yield self._decode_batch(buf_pairs)
                buf_pairs = []
        if skipping:
            # the resume point never appeared: yielding nothing would
            # silently report the stale snapshot as the final state
            raise ValueError(
                "resume point is not on the streamed chain (snapshot "
                "outlived the DB?)")
        if buf_pairs:
            self._note_read(buf_pairs)
            yield self._decode_batch(buf_pairs)

    @_spans.thread_usage(_PREFETCH_CPU_US, _PREFETCH_PREEMPTS)
    def _run(self) -> None:
        decoded = self._read_decoded()
        try:
            buf: list = []
            for blocks in decoded:
                # the chunk just decoded leaves the cyclic collector's
                # sight (inside an engine's replay; alone, nothing)
                _COLLECTOR.freeze()
                buf.extend(blocks)
                while len(buf) >= self.window:
                    if not self._put(buf[:self.window]):
                        return
                    buf = buf[self.window:]
            if buf:
                self._put(buf)
        except BaseException as e:   # surfaced on the consumer
            with self._cond:
                self._error = e
                self._cond.notify_all()
        finally:
            decoded.close()        # the workers go back before the join
            _P_FINISHED.inc()
            with self._cond:
                self._eof = True
                self._cond.notify_all()

    def _put(self, batch: list) -> bool:
        """Queue one batch, blocking at the read-ahead bound; False when
        the consumer asked us to stop."""
        with self._cond:
            if len(self._batches) >= self.depth and not self._stop:
                self.stalls += 1
                _STALLS.inc()
                t0 = _spans.monotonic_now()
                self._cond.wait_for(
                    lambda: self._stop
                    or len(self._batches) < self.depth)
                _BACKPRESSURE_US.inc(
                    int((_spans.monotonic_now() - t0) * 1e6))
            if self._stop:
                return False
            self._batches.append(batch)
            _DEPTH.set(len(self._batches))
            self._cond.notify_all()
            return True

    # -- the consuming side --------------------------------------------------
    def __iter__(self) -> Iterator[Any]:
        while True:
            with self._cond:
                self._cond.wait_for(
                    lambda: self._batches or self._eof
                    or self._error is not None or self._stop)
                if self._batches:
                    batch = self._batches.popleft()
                    self._taken += len(batch)
                    _DEPTH.set(len(self._batches))
                    self._cond.notify_all()
                elif self._error is not None:
                    err, self._error = self._error, None
                    raise err
                else:
                    return                 # eof (or stopped)
            yield from batch               # lock NOT held


@dataclass(frozen=True)
class StreamConfig:
    """Engine knobs.  `read_ahead` is the prefetch bound in windows —
    together with the pipeline's DEPTH it fixes the peak number of
    decoded blocks alive at once to (read_ahead + ~3) * window,
    independent of chain length: chunks out at the decode workers
    count toward it (`BlockPrefetcher`), as do the batches queued.
    Nothing here says where blocks are decoded: the prefetcher reads
    that off the decoder it is given.  `policy` drives both the snapshot
    cadence during replay and the trim count
    (storage/ledgerdb.DiskPolicy); `take_snapshots=False` makes the
    run read-only on the DB directory (plain validation)."""
    window: int = 512
    read_ahead: int = 4
    policy: DiskPolicy = DiskPolicy()
    resume: bool = True
    take_snapshots: bool = True


@dataclass
class StreamReplayResult:
    """ReplayResult + the stream's own accounting."""
    final_state: Any
    n_valid: int
    error: Optional[Exception]
    stats: dict = field(default_factory=dict)

    @property
    def all_valid(self) -> bool:
        return self.error is None


class StreamingReplayEngine:
    """One replay of one on-disk chain DB: restore, stream, verify,
    checkpoint.  Construct per run (`db_analyser --resume`, the
    benchmark's replays, the kill/resume tests); the heavyweight state — key
    caches, compiled programs — lives in the backend and survives
    across engines, and so do the decode worker processes
    (storage/decode_pool.py), which belong to the process.  `decode` is
    shipped to them where it can be (`BlockPrefetcher`); `on_decoded`
    is the prefetcher's hook of that name.

    For the length of `replay()` the engine owns the cyclic collector's
    permanent generation (`_ReplayCollector`): it is entered before the
    prefetcher starts and left after the prefetcher is joined, on every
    path out, so the process's collector is then as it was found.  Why:
    the prefetcher keeps windows of decoded blocks alive, and every full
    pass would walk them all.  What it cannot give back: a permanent
    generation the process had filled itself before the replay
    (`gc.unfreeze()` empties all of it; an optimisation lost, never
    correctness)."""

    def __init__(self, fs, db, rules, decode: Callable[[bytes], Any],
                 backend=None, config: Optional[StreamConfig] = None,
                 encode_state: Callable[[Any], Any] = pickle_encode,
                 decode_state: Callable[[Any], Any] = pickle_decode,
                 on_decoded: Optional[Callable[[list], None]] = None):
        self.fs = fs
        self.db = db
        self.rules = rules
        self.decode = decode
        self.on_decoded = on_decoded
        self.backend = backend
        self.cfg = config if config is not None else StreamConfig()
        self._enc = encode_state
        self._dec = decode_state
        self.snapshots_written = 0
        self.snapshot_write_secs = 0.0
        self.restore_secs = 0.0

    # -- restore -------------------------------------------------------------
    def restore(self) -> Optional[tuple]:
        """(slot, point, state) of the newest USABLE snapshot: readable
        (checksum holds — ledgerdb skips torn/corrupt ones) AND whose
        point is still on the immutable chain (a snapshot can outlive
        its blocks when startup validation truncated a corrupt tail —
        resuming from it would strand the replay off-chain)."""
        t0 = _spans.monotonic_now()
        seen = 0
        try:
            for slot, point, state in LedgerDB.iter_snapshots(self.fs,
                                                              self._dec):
                seen += 1
                if point.is_genesis or point.hash in self.db:
                    _RESUME_SLOT.set(slot)
                    _flight.FLIGHT.note(
                        StreamResumed(slot, point.slot, seen))
                    return slot, point, state
            return None
        finally:
            self.restore_secs = _spans.monotonic_now() - t0
            _RESTORE_SECS.set(round(self.restore_secs, 6))

    # -- snapshotting ---------------------------------------------------------
    def _take_snapshot(self, point, state) -> None:
        t0 = _spans.monotonic_now()
        with _spans.span("stream.snapshot", cat="disk", cpu=True):
            LedgerDB.take_snapshot(self.fs, point.slot, point, state,
                                   self._enc, self.cfg.policy)
        self.snapshots_written += 1
        self.snapshot_write_secs += _spans.monotonic_now() - t0
        _SNAPS.inc()
        _SNAP_SECS.set(round(self.snapshot_write_secs, 6))

    # -- the replay ------------------------------------------------------------
    @_spans.thread_usage(_CALLER_CPU_US, _CALLER_PREEMPTS)
    def replay(self) -> StreamReplayResult:
        from ..consensus.batch import replay_blocks_pipelined

        cfg = self.cfg
        restored = self.restore() if cfg.resume else None
        after_hash: Optional[bytes] = None
        state = self.rules.initial_state()
        resumed_from: Optional[int] = None
        if restored is not None:
            resumed_from, point, state = restored
            if not point.is_genesis:
                after_hash = point.hash
        # ETA denominator: O(1) on the native chunk-indexed DB; a
        # reference-format view would pay a full extra read pass for
        # __len__, so it streams without a total
        total = len(self.db) if hasattr(self.db, "chunk_numbers") \
            and after_hash is None else None
        tracker = ProgressTracker(total)
        interval = cfg.policy.snapshot_interval_slots
        # the interval counts from the stream's START (the resume slot,
        # or the initial state's tip for a fresh run) — the first window
        # must not trigger an unconditional full-state serialisation the
        # policy never asked for
        last_snap = {"slot": resumed_from if resumed_from is not None
                     else self.rules.tip(state).slot}

        def on_window(st, _n_done, point):
            # a window has drained clean: what the freezes caught by
            # accident goes back to the collector (see _ReplayCollector)
            _COLLECTOR.lift()
            if cfg.take_snapshots \
                    and point.slot - last_snap["slot"] >= interval:
                self._take_snapshot(point, st)
                last_snap["slot"] = point.slot

        pre = BlockPrefetcher(self.db, self.decode, window=cfg.window,
                              depth=cfg.read_ahead, tracker=tracker,
                              after_hash=after_hash,
                              on_decoded=self.on_decoded)
        with _COLLECTOR:
            pre.start()
            t0 = _spans.monotonic_now()
            try:
                res = replay_blocks_pipelined(
                    self.rules, pre, state, backend=self.backend,
                    window=cfg.window, total_blocks=total, tracker=tracker,
                    on_window=on_window)
            finally:
                pre.close()
        replay_secs = _spans.monotonic_now() - t0
        if cfg.take_snapshots and res.error is None \
                and res.final_state is not None:
            # tip checkpoint: the next open restores in O(snapshot), no
            # replay at all (skipped when the tip snapshot already
            # exists — a fully-resumed rerun writes nothing)
            tip = self.rules.tip(res.final_state)
            if not tip.is_genesis and last_snap["slot"] != tip.slot:
                self._take_snapshot(tip, res.final_state)
                last_snap["slot"] = tip.slot
        _DISK_SECS.set(round(tracker.disk_secs, 6))
        _DISK_HIDDEN.set(round(tracker.disk_hidden_secs, 6))
        stats = {
            "blocks": res.n_valid,
            "replay_secs": round(replay_secs, 4),
            "chunks_read": pre.chunks_read,
            "blocks_decoded": pre.blocks_decoded,
            "bytes_read": pre.bytes_read,
            "era_crossings": pre.era_crossings,
            "prefetch_stalls": pre.stalls,
            "read_ahead": cfg.read_ahead,
            "disk_secs": round(tracker.disk_secs, 4),
            "disk_hidden_secs": round(tracker.disk_hidden_secs, 4),
            "disk_hidden_frac": round(
                tracker.disk_hidden_secs / tracker.disk_secs, 3)
            if tracker.disk_secs > 0 else 0.0,
            "host_seq_secs": round(tracker.host_secs, 4),
            "host_hidden_secs": round(tracker.hidden_secs, 4),
            "snapshots_written": self.snapshots_written,
            "snapshot_write_secs": round(self.snapshot_write_secs, 4),
            "restore_secs": round(self.restore_secs, 4),
            "resumed_from_slot": resumed_from,
        }
        return StreamReplayResult(res.final_state, res.n_valid,
                                  res.error, stats)


def prefetcher_threads_alive() -> int:
    """Live prefetch threads (leak gates share this with the
    started/finished counter pair, like the pipeline's producer)."""
    return sum(t.name == THREAD_NAME and t.is_alive()
               for t in threading.enumerate())
