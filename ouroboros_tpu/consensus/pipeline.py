"""Threaded producer/consumer replay — true host/device overlap.

The r5 software pipeline (consensus/batch.py) kept two windows in
flight, but the sequential pass, request packing, and dispatch all ran
on ONE Python thread: while that thread sat inside a blocking drain
(the packed result transfer plus result folding), no host-sequential
work advanced, so host-seq and device time simply ADDED.  SURVEY.md
hard parts #3 says the split is legal — nonce evolution is sequential,
but proofs are state-independent once seeds are derived — so this
module puts the host half on its own thread:

    producer (background thread)      consumer (caller thread)
    ------------------------------    ------------------------------
    window w+1: seq pass              window w: blocking drain
               (nonce evolution,        (ONE packed transfer; with
                envelope checks,         fold=True just a verdict
                proof extraction)        scalar + betas)
               request packing          install carried betas
               key-cache prefetch       release one permit
               async submit  ───────►   first error wins, oldest-first

Coordination protocol (mirrored 1:1 by the sim model explored under
ouro-race in tests/test_replay_pipeline.py):

  * one Condition guards {pending, submitted, drained, stop, done};
  * the producer acquires a PERMIT before each window's sequential
    pass: it waits until ``submitted - drained < DEPTH`` — exactly the
    beta-carry distance.  Window w's submit ships window w+2's betas,
    which the consumer installs when draining w, immediately before the
    producer's sequential pass for w+2 reads them.  Running further
    ahead would silently fall back to per-proof host EC math;
  * the consumer drains oldest-first outside the lock (the blocking
    device wait must not hold it), installs betas, then releases the
    permit;
  * on a drain error the consumer sets ``stop``; the producer observes
    it at the next permit check, so at most one more window is ever
    submitted, and the consumer discards the leftovers with
    finish_window so no device work is leaked;
  * the producer NEVER touches the result: seq counts, the final state
    and any sequential error hand over through the shared state after
    ``done``, and an unexpected producer exception re-raises on the
    caller thread (``crash``).

Scheduling cannot change the outcome: drains are processed in
submission order and the first error wins, so ReplayResult is
byte-identical to the synchronous driver on any chain, valid or not —
tests/test_replay_pipeline.py pins this.

Shared-cache discipline: the producer owns all point-cache fills and
beta-cache reads; the consumer owns beta-cache writes and KES hash-path
outcome writes.  Individual dict operations are GIL-atomic and every
value is a pure function of its key, so a racing read at worst
recomputes; the caches' LRU bookkeeping (recency touches, capacity
eviction) additionally tolerates a concurrent eviction from the other
thread — see precompute._insert / VrfBetaCache._store.  Span trees are per-thread (observe/spans.py): the producer's
``window.host_seq``/``window.submit`` roots and the consumer's
``window.drain`` roots overlap in wall time — which is the point.
``window.host_seq`` and ``pipeline.drain`` carry the window's index in
the replay (``window=k``), so one window can be followed across both
threads.

The hand-offs are timed by four counters of whole microseconds, never
by spans (observe/spans.py says why): ``producer_wait_blocks_us`` (the
producer inside ``next_window()``, waiting for decoded blocks),
``producer_stall_us`` (the producer waiting for a permit: DEPTH windows
submitted and not drained), ``consumer_wait_us`` (the caller's thread
with no submitted window to drain) and ``first_submit_us`` (producer
start to the first submit, once a replay: the head during which the
device has had nothing).  What the producer thread itself used, start to
end, goes to ``replay.thread_cpu_us.producer`` and
``replay.thread_preempts.producer`` (observe/spans.py says what the three
threads' readings are for), and ``window.host_seq`` is a ``cpu=True``
span.
"""
from __future__ import annotations

import itertools
import threading
from collections import deque
from typing import Any, Optional

from ..chain.block import Point
from ..crypto.backend import (
    GLOBAL_BETA_CACHE, WindowVerdict, lane_count, request_at,
)
from ..observe import flight as _flight
from ..observe import metrics as _metrics
from ..observe import spans as _spans
from .header_validation import HeaderError
from .ledger import LedgerError, OutsideForecastRange

#: max windows submitted-but-not-drained while a sequential pass runs —
#: the beta-carry distance (window w's device call computes w+2's betas)
DEPTH = 2

# load-bearing thread accounting (always on): a replay that returns with
# started != finished leaked its producer
# (tests/test_served_replay.py::test_producer_ran_and_is_gone)
_STARTED = _metrics.counter("pipeline.producers_started", always=True)
_FINISHED = _metrics.counter("pipeline.producers_finished", always=True)
# queue-latency instrumentation (ISSUE 9): submit→drain covers the full
# async residence of a window — dispatch queue + device + transfer —
# the quantity the adaptive batching service will trade off against
# coalescing gain.  Handles pre-bound here (OBS002): observe() is two
# hot-loop calls per window.
_SUBMIT_DRAIN = _metrics.latency_histogram("pipeline.submit_drain_secs")
_WINDOW_BLOCKS = _metrics.histogram("pipeline.window_blocks")
# hand-off waits, whole microseconds on monotonic_now() (measured, so
# unstable): what each thread of the replay spent with nothing to do
_WAIT_BLOCKS_US = _metrics.counter("pipeline.producer_wait_blocks_us",
                                   stable=False)
_CONSUMER_WAIT_US = _metrics.counter("pipeline.consumer_wait_us",
                                     stable=False)
_FIRST_SUBMIT_US = _metrics.counter("pipeline.first_submit_us",
                                    stable=False)
_STALL_US = _metrics.counter("pipeline.producer_stall_us", stable=False)
# what the producer thread used, start to end of its part of a replay
# (observe/spans.py `thread_usage`): CPU time in whole microseconds, and
# the times the kernel took the core from it
_CPU_US = _metrics.counter("replay.thread_cpu_us.producer", stable=False)
_PREEMPTS = _metrics.counter("replay.thread_preempts.producer",
                             stable=False)

# replay progress gauges (rendered live by tools/obsreport.py --live via
# the scrape endpoint).  blocks_done / windows_in_flight / total are
# deterministic end-state for a fixed workload (stable); rate/ETA/
# hidden-fraction are measured seconds (unstable).
_P_BLOCKS = _metrics.gauge("replay.progress.blocks_done")
_P_TOTAL = _metrics.gauge("replay.progress.total_blocks")
_P_INFLIGHT = _metrics.gauge("replay.progress.windows_in_flight")
_P_RATE = _metrics.gauge("replay.progress.blocks_per_sec", stable=False)
_P_ETA = _metrics.gauge("replay.progress.eta_secs", stable=False)
_P_HIDDEN = _metrics.gauge("replay.progress.hidden_frac", stable=False)
# mesh attribution (ISSUE 11): devices the in-flight windows shard over
# (1 off-mesh) and the lane padding waste the per-shard bucket rounding
# cost this replay — both read straight off the backend, published so a
# live scrape of a sharded replay names its mesh
_P_DEVICES = _metrics.gauge("replay.progress.devices")
_P_PAD_WASTE = _metrics.gauge("replay.progress.padding_waste_frac")


def _us_since(t0: float) -> int:
    """Whole microseconds on `monotonic_now()` since the reading `t0`."""
    return int((_spans.monotonic_now() - t0) * 1e6)


class ProgressTracker:
    """Online progress/overlap accounting for one streaming replay,
    published through the registry after every drained window.

    Exactness without history: hidden host-seq time is the measure of
    {host sequential pass active} ∩ {≥1 window in flight}.  Both are
    on/off signals with O(1) transitions (host edges from the producer,
    in-flight edges from submit/drain), so the intersection accumulates
    in a scalar — no interval lists to keep, which matters at
    million-block scale.  The streaming replay (storage/stream.py) adds
    a third on/off signal with the same discipline: {prefetch thread
    reading/decoding} ∩ {≥1 window in flight} accumulates into
    disk_hidden_secs, so the engine can report how many storage seconds
    the read-ahead hid behind device verify.  ETA uses the blocks/sec
    observed so far; total_blocks is optional (an unbounded stream has
    progress but no ETA)."""

    __slots__ = ("t0", "total", "blocks", "host_secs", "hidden_secs",
                 "disk_secs", "disk_hidden_secs", "_lock", "_inflight",
                 "_host_since", "_both_since", "_disk_since",
                 "_disk_both_since")

    def __init__(self, total_blocks: Optional[int] = None):
        self.t0 = _spans.monotonic_now()
        self.total = total_blocks
        self.blocks = 0
        self.host_secs = 0.0
        self.hidden_secs = 0.0
        self.disk_secs = 0.0
        self.disk_hidden_secs = 0.0
        self._lock = threading.Lock()
        self._inflight = 0
        self._host_since: Optional[float] = None
        self._both_since: Optional[float] = None
        self._disk_since: Optional[float] = None
        self._disk_both_since: Optional[float] = None
        _P_TOTAL.set(total_blocks if total_blocks is not None else 0)
        _P_BLOCKS.set(0)
        _P_INFLIGHT.set(0)

    # -- producer edges ------------------------------------------------------
    def host_begin(self) -> None:
        now = _spans.monotonic_now()
        with self._lock:
            self._host_since = now
            if self._inflight:
                self._both_since = now

    def host_end(self) -> None:
        now = _spans.monotonic_now()
        with self._lock:
            if self._host_since is not None:
                self.host_secs += now - self._host_since
                self._host_since = None
            if self._both_since is not None:
                self.hidden_secs += now - self._both_since
                self._both_since = None

    # -- prefetch-thread edges (streaming replay) ----------------------------
    def disk_begin(self) -> None:
        now = _spans.monotonic_now()
        with self._lock:
            self._disk_since = now
            if self._inflight:
                self._disk_both_since = now

    def disk_end(self) -> None:
        now = _spans.monotonic_now()
        with self._lock:
            if self._disk_since is not None:
                self.disk_secs += now - self._disk_since
                self._disk_since = None
            if self._disk_both_since is not None:
                self.disk_hidden_secs += now - self._disk_both_since
                self._disk_both_since = None

    # -- consumer edges ------------------------------------------------------
    def window_submitted(self) -> None:
        now = _spans.monotonic_now()
        with self._lock:
            self._inflight += 1
            if self._inflight == 1:
                if self._host_since is not None:
                    self._both_since = now
                if self._disk_since is not None:
                    self._disk_both_since = now

    def window_drained(self, n_blocks: int) -> None:
        now = _spans.monotonic_now()
        with self._lock:
            self._inflight -= 1
            if self._inflight == 0:
                if self._both_since is not None:
                    self.hidden_secs += now - self._both_since
                    self._both_since = None
                if self._disk_both_since is not None:
                    self.disk_hidden_secs += now - self._disk_both_since
                    self._disk_both_since = None
            self.blocks += n_blocks
            blocks, inflight = self.blocks, self._inflight
            host, hidden = self.host_secs, self.hidden_secs
        elapsed = now - self.t0
        rate = blocks / elapsed if elapsed > 0 else 0.0
        _P_BLOCKS.set(blocks)
        _P_INFLIGHT.set(inflight)
        _P_RATE.set(round(rate, 3))
        if self.total and rate > 0:
            _P_ETA.set(round(max(0, self.total - blocks) / rate, 3))
        _P_HIDDEN.set(round(hidden / host, 4) if host > 0 else 0.0)


class _Shared:
    """Producer/consumer handoff state; every field below is guarded by
    ``cond`` except the producer-private ones it publishes only before
    setting ``done``."""

    __slots__ = ("cond", "pending", "submitted", "drained", "stop",
                 "done", "crash", "seq_error", "seq_done", "final_state",
                 "progress")

    def __init__(self):
        self.cond = threading.Condition()
        # (start, sub, reqs, ends, n_seq, t_submit, state_after, point,
        #  window index): reqs the window's stream of items, ends the
        #  requests up to each block's last (batch.block_of)
        self.pending: deque = deque()
        self.progress: Optional[ProgressTracker] = None
        self.submitted = 0
        self.drained = 0
        self.stop = False               # consumer: error seen, stop producing
        self.done = False               # producer: no more submissions
        self.crash: Optional[BaseException] = None
        self.seq_error: Optional[Exception] = None
        self.seq_done = 0               # blocks past the sequential pass
        self.final_state: Any = None


def _produce(shared: _Shared, ext_rules, block_iter, ext_state, backend,
             window: int, fold: bool) -> None:
    """Producer body: sequential pass + packing + async submit per
    window, permit-gated to the beta-carry depth."""
    protocol, ledger = ext_rules.protocol, ext_rules.ledger
    submit = backend.submit_window
    begin = getattr(backend, "begin_replay", None)
    if begin is not None:
        begin()                 # what it counts window to window restarts
    # a backend that shapes its programs by what is coming is told, a
    # submit, what the windows in sight hold (JaxBackend.expect_lanes)
    expect = getattr(backend, "expect_lanes", None)
    # rules that tell their eras apart keep the host pass's blocks and
    # seconds by era, a window at a time (HardForkLedger.host_pass_tally)
    new_tally = getattr(ledger, "host_pass_tally", None)
    # producer start; None once the first submit is made
    t_first: Optional[float] = _spans.monotonic_now()

    def next_window():
        """The next window as (headers, blocks, the VRF proofs whose
        betas its host pass will read), None at the chain's end."""
        t = _spans.monotonic_now()
        w = list(itertools.islice(block_iter, window))
        _WAIT_BLOCKS_US.inc(_us_since(t))
        if not w:
            return None
        headers = [getattr(b, "header", b) for b in w]
        return headers, w, protocol.vrf_proofs_of(headers)

    try:
        # bounded look-ahead: ahead[0] = current window, ahead[1:] = the
        # two windows whose beta proofs may already be in flight
        ahead: deque = deque()
        for _ in range(3):
            w = next_window()
            if w is None:
                break
            ahead.append(w)
        if ahead:
            # windows 0 and 1 ride a plain prefetch; window w's device
            # call then carries window w+2's betas
            with _spans.span("pipeline.beta_prefetch", cat="device"):
                protocol.prefetch_window(
                    [h for hs, _w, _ps in list(ahead)[:2] for h in hs],
                    backend)

        st = ext_state
        k = -1                          # index of the window in the replay
        while ahead:
            with shared.cond:
                if not (shared.stop
                        or shared.submitted - shared.drained < DEPTH):
                    t_stall = _spans.monotonic_now()
                    shared.cond.wait_for(
                        lambda: shared.stop or
                        shared.submitted - shared.drained < DEPTH)
                    _STALL_US.inc(_us_since(t_stall))
                if shared.stop:
                    return
            k += 1
            headers_w, blk_window, _proofs = ahead.popleft()
            nxt = next_window()
            if nxt is not None:
                ahead.append(nxt)
            tally = new_tally() if new_tally is not None else None
            reqs: list = []
            ends: list[int] = []
            n_reqs = 0
            seq_error: Optional[Exception] = None
            n_seq_w = 0
            progress = shared.progress
            if progress is not None:
                progress.host_begin()
            with _spans.span("window.host_seq", cat="host-seq", cpu=True,
                             window=k):
                for b in blk_window:
                    t_block = _spans.monotonic_now()
                    try:
                        rs, st = _seq_block_step(protocol, ledger, st, b)
                    except OutsideForecastRange as e:
                        # retry-later, never invalid (see
                        # validate_blocks_batched)
                        seq_error = e
                        break
                    except Exception as e:
                        seq_error = (e if isinstance(e, (HeaderError,
                                                         LedgerError))
                                     else LedgerError(str(e)))
                        break
                    reqs.extend(rs)
                    n_reqs += lane_count(rs)
                    ends.append(n_reqs)
                    n_seq_w += 1
                    if tally is not None:
                        tally.block(st.ledger,
                                    _spans.monotonic_now() - t_block)
            if tally is not None:
                tally.close()
            if progress is not None:
                progress.host_end()
            # carry betas for the window TWO ahead (ahead[1] after the
            # pop), whatever era this window is of: the consumer
            # installs them at drain time, which the permit above orders
            # before that window's sequential pass
            next_proofs = (ahead[1][2]
                           if len(ahead) > 1 and seq_error is None else ())
            next_proofs = [p for p in next_proofs
                           if p not in GLOBAL_BETA_CACHE]
            if expect is not None:
                # in sight: windows k+1..k+3; this submit carries k+2's
                # betas and the next one k+3's
                sight = [len(ps) for _hs, _w, ps in ahead]
                expect(max(sight, default=0), max(sight[1:], default=0))
            if t_first is not None:
                _FIRST_SUBMIT_US.inc(_us_since(t_first))
                t_first = None
            sub = (submit(reqs, next_proofs, fold=True) if fold
                   else submit(reqs, next_proofs))
            _WINDOW_BLOCKS.observe(n_seq_w)
            if progress is not None:
                progress.window_submitted()
            # the window's post-prefix state + tip point ride the entry:
            # once this window DRAINS clean, `st` is fully verified up to
            # `pt` — the consumer hands the pair to on_window (the
            # streaming engine's snapshot seam).  A window that died on
            # a genuine sequential validation failure carries NO point:
            # its prefix precedes an invalid block and both drivers
            # refuse to checkpoint it (retry-later horizon waits DO
            # checkpoint — their prefix is on the canonical chain)
            pt = (Point(headers_w[n_seq_w - 1].slot,
                        headers_w[n_seq_w - 1].hash)
                  if n_seq_w and (seq_error is None
                                  or isinstance(seq_error,
                                                OutsideForecastRange))
                  else None)
            with shared.cond:
                shared.pending.append(
                    (shared.seq_done, sub, reqs, ends, n_seq_w,
                     _spans.monotonic_now(), st, pt, k))
                shared.submitted += 1
                shared.seq_done += n_seq_w
                shared.cond.notify_all()
            if seq_error is not None:
                shared.seq_error = seq_error
                break
        shared.final_state = st
    except BaseException as e:      # submit/seq machinery broke: hand the
        shared.crash = e            # exception to the caller thread
    finally:
        with shared.cond:
            shared.done = True
            shared.cond.notify_all()


def _drain(backend, entry) -> tuple:
    """Finish one window's device call; install its carried betas.
    Returns (error, n_valid): error None when every proof held, else
    n_valid is the global index of the first bad block."""
    start, sub, reqs, ends, n_seq_w, t_submit, _st, _pt, k = entry
    # named distinctly from jax_backend's inner "window.drain" span,
    # so a reader that pairs submits with drains by name sees one
    # interval a drain.  This outer span exists for EVERY async backend
    # (the flight recorder must show drains even on stub/CPU backends).
    with _spans.span("pipeline.drain", cat="device", window=k):
        ok, betas = backend.finish_window(sub)
    _SUBMIT_DRAIN.observe(_spans.monotonic_now() - t_submit)
    if betas:
        GLOBAL_BETA_CACHE.store_many(betas.keys(), betas.values())
    # the first failing request index, device-folded or read off the
    # vector: requests lie block after block, so it is of the first bad
    # block
    bad = ok.first_bad if isinstance(ok, WindowVerdict) else first_false(ok)
    if bad is not None:
        first_bad = block_of(ends, bad)
        return LedgerError(
            f"proof {type(request_at(reqs, bad)).__name__} failed for "
            f"block {start + first_bad}"), start + first_bad
    return None, start + n_seq_w


def replay_threaded(ext_rules, blocks, ext_state, backend,
                    window: int = 512,
                    total_blocks: Optional[int] = None,
                    tracker: Optional[ProgressTracker] = None,
                    on_window=None):
    """Run the producer/consumer pipeline to completion; returns the
    same ReplayResult the synchronous driver would (batch.py re-exports
    this as the submit_window path of replay_blocks_pipelined).

    `total_blocks` (len(blocks) when the caller knows it) feeds the
    progress tracker's ETA; a streaming replay without it still reports
    blocks/sec, windows in flight and the hidden fraction.  `tracker`
    lets a caller share one ProgressTracker with other pipeline stages
    (the streaming engine's prefetch thread feeds its disk signal into
    the same tracker).  `on_window(state, n_done, point)` runs on the
    consumer thread after each window drains CLEAN: `state` is the
    fully verified state after that window's prefix and `point` its tip
    — the snapshot seam.  An exception it raises stops the replay
    through the normal first-error-wins teardown (producer joined,
    in-flight windows discarded via finish_window) and re-raises on the
    caller."""
    from .batch import ReplayResult

    if total_blocks is None and hasattr(blocks, "__len__"):
        total_blocks = len(blocks)
    fold = bool(getattr(backend, "supports_window_fold", False))
    # the sharded backend (parallel/sharded_verify.py) drives this SAME
    # driver: the producer's packing pads window w+1 to the per-shard
    # bucket shape (backend._pad rounds to a mesh multiple) while window
    # w's sharded composite drains, and the fold verdict is already the
    # cross-shard minimum — nothing here branches on mesh size, but the
    # mesh is attributed for live observers
    _P_DEVICES.set(int(getattr(backend, "n_shards", 1)))
    stats_fn = getattr(backend, "padding_stats", None)
    pad0 = stats_fn() if stats_fn is not None else None
    shared = _Shared()
    shared.progress = (tracker if tracker is not None
                       else ProgressTracker(total_blocks))
    t = threading.Thread(
        target=_run_producer,
        args=(shared, ext_rules, iter(blocks), ext_state, backend,
              window, fold),
        name="ouro-replay-producer", daemon=True)
    _STARTED.inc()
    t.start()
    error: Optional[Exception] = None
    n_ok = 0
    try:
        while True:
            with shared.cond:
                t_wait = _spans.monotonic_now()
                shared.cond.wait_for(
                    lambda: shared.pending or shared.done)
                _CONSUMER_WAIT_US.inc(_us_since(t_wait))
                if not shared.pending:
                    break               # done and fully drained
                entry = shared.pending.popleft()
            err, n = _drain(backend, entry)      # blocking, lock NOT held
            with shared.cond:
                shared.drained += 1
                shared.cond.notify_all()
            shared.progress.window_drained(entry[4])
            if err is not None:
                error, n_ok = err, n
                break
            if on_window is not None and entry[7] is not None:
                # every proof up to entry's tip point has now held —
                # entry[6] is a durable resume point.  A hook failure
                # (snapshot write error, a test's injected kill) rides
                # the consumer-exception path below: producer joined,
                # leftovers discarded, exception re-raised
                on_window(entry[6], n, entry[7])
    finally:
        # wake a permit-blocked producer and wait it out — the pipeline
        # must never leak its thread, least of all on an error path
        with shared.cond:
            shared.stop = True
            shared.cond.notify_all()
        t.join()
        # discard anything submitted after the first error (or after a
        # consumer-side exception): the async device work must complete
        for entry in shared.pending:
            backend.finish_window(entry[1])
        shared.pending.clear()
        if stats_fn is not None:
            # THIS replay's windows only (since=): a long-lived backend
            # must not smear earlier replays' padding into the gauge
            _P_PAD_WASTE.set(
                stats_fn(since=pad0).get("waste_frac", 0.0))
    if shared.crash is not None:
        # unhandled producer error: the flight ring holds the last
        # spans/metric deltas before the crash — dump before re-raising
        _flight.FLIGHT.dump_on_failure(
            f"replay producer crash: {shared.crash!r}")
        raise shared.crash
    if error is not None:
        # ReplayResult failure (first error wins): a crash-proof record
        # of the moments before the bad window, for offline triage
        _flight.FLIGHT.dump_on_failure(
            f"replay failed at block {n_ok}: {error}")
        return ReplayResult(None, n_ok, error)
    if shared.seq_error is not None:
        # the valid prefix (incl. the drained proofs) is fully verified:
        # resumable when the error is retry-later
        resume = (shared.final_state
                  if isinstance(shared.seq_error, OutsideForecastRange)
                  else None)
        if resume is None:
            # genuine sequential validation failure (retry-later horizon
            # waits are normal operation, not flight-dump material)
            _flight.FLIGHT.dump_on_failure(
                f"replay failed at block {shared.seq_done}: "
                f"{shared.seq_error}")
        return ReplayResult(resume, shared.seq_done, shared.seq_error)
    return ReplayResult(shared.final_state, shared.seq_done, None)


@_spans.thread_usage(_CPU_US, _PREEMPTS)
def _run_producer(*args) -> None:
    try:
        _produce(*args)
    finally:
        _FINISHED.inc()


# placed at the bottom to avoid a circular import at module load
# (batch.py imports replay_threaded; we only need its seq step)
from .batch import _seq_block_step, block_of, first_false  # noqa: E402
