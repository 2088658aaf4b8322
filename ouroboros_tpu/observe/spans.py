"""Hierarchical timing spans with explicit device fencing.

A span is one named, categorised interval on the host timeline; spans
nest, forming one tree per top-level region (a replay window, a
compile).  Categories are the replay's phase vocabulary:

    host-seq   the sequential host pass (nonce evolution, envelope
               checks, proof extraction)
    dispatch   host-side prep + async kernel dispatch (submit_window)
    device     blocking on device results (the finish_window drain, a
               precompute fill)
    compile    XLA trace+compile (first call of a fused composite, the
               sharded-mesh build)
    sync       explicit block_until_ready fences draining the async
               dispatch queue before a timed region
    disk       storage-layer reads + CBOR decode on the streaming
               replay's prefetch thread (storage/stream.py) — the
               seconds the read-ahead hides under device verify

Clock discipline: **monotonic only** — `time.perf_counter()` on the
host, the active runtime's virtual clock under simharness (Sim time in
tests, the IO runtime's monotonic offset in production).  No wall-clock
(`time.time()`-style) reads anywhere: span math must be immune to NTP
steps, and sim tests must see exact virtual durations.

Fencing: a span created with `fence=True` drains the async dispatch
queue (`jax.block_until_ready` on a dummy transfer) at BOTH edges, so
the measured interval covers exactly the work dispatched inside it and inherits nothing in flight.
The fence is skipped when jax was never imported — host-only flows must
not pull in the device stack just by timing themselves.

Disabled recording is near-free: `span()` returns one shared null
context manager (no allocation, no clock read).

Thread discipline: the pipelined replay runs its host-sequential pass on
a background producer thread (consensus/pipeline.py), so the recorder
keeps one open-span stack PER THREAD (a producer's `window.host_seq`
must never adopt the consumer's `window.drain` as a child just because
they overlap in wall time).  Completed roots land in one shared,
lock-guarded list so a drain sees both threads' trees.

Who and which: every span carries `thread`, the name of the thread that
opened it (the streamed replay's three are `ouro-stream-prefetch`,
`ouro-replay-producer` and the caller's), and `span(..., window=k)`
puts keyword arguments into `meta`.  `window.host_seq` (producer) and
`pipeline.drain` (consumer) carry the window's index in the replay, so
one window's work can be followed across both threads;
`window.submit` and `stream.snapshot` follow theirs on the same
thread.  `export.chrome_trace` draws one row per thread and shows
`meta` as the event's `args`; the flight recorder's dump keeps both.

The streamed replay's stage spans (each nests under the pipeline-stage
span named first; cat in brackets):

    stream.decode   decode.parse, decode.build, decode.slices [disk],
                    one each a block (consensus/headers.py): the one
                    walk of the bytes that keeps the list offsets, the
                    block built from the parsed object, the header's
                    slices cut and the transactions' ids hashed at
                    those offsets.  Where a decode worker
                    process did them (storage/decode_pool.py) they are
                    its readings of the same clock, adopted (`adopt`)
                    with `thread` the worker's name, and lie before the
                    `stream.decode` they hang under: that span is then
                    the prefetch thread's wait for the chunk's reply and
                    decode.unpack [disk], the reply read from its pipe
                    and turned into blocks (one where it was waiting
                    whole; a look that found it not there yet is one
                    more)
    window.host_seq seq.header, seq.body [host-seq], the header rules
                    and the ledger pass of one block, interleaved as
                    `_seq_block_step` runs them (consensus/batch.py)
    seq.body        body.tick, body.checks, body.extract, body.reapply
                    [host-seq], one each a block: the four calls into
                    the `LedgerRules` of that pass (`tick`,
                    `sequential_checks`, `extract_proofs`,
                    `reapply_block`), whatever the era.  None a
                    transaction: a span costs about what a light
                    transaction's share of the pass does.
                    body.extract ends with the block's ITEMS in hand:
                    the header's request objects and ONE columns item
                    for the body's witnesses (crypto/backend.py
                    `Ed25519Cols`), no object a witness
    window.submit   submit.split, submit.pack_ed, submit.pack_vrf,
                    submit.pack_kes, submit.dispatch, submit.fold
                    [dispatch], one each a window but submit.pack_ed,
                    which is two (crypto/jax_backend.py: the lanes
                    packed beside the new keys' fill, then, behind the
                    VRF and KES packers, the key tables collected and
                    the tiles copied to the device).
                    What crosses into it is the window's stream of
                    items: submit.split walks the items (a handful a
                    block) and joins each columns item to the Ed25519
                    lanes whole, submit.pack_ed reads the columns
    submit.pack_ed  pack_ed.challenge [dispatch], the Ed25519 challenge
                    scalars of the window's lanes (crypto/ed25519_jax.py
                    `challenge_rows`); a root in `verify_ed25519_batch`
    submit.dispatch submit.ed_tiles [dispatch], the T asynchronous
                    calls of the one Ed25519 tile program a window of T
                    tiles makes (`JaxBackend._ed_tile_program`): what
                    the launches cost the producer, not device time
    (a root)        pipeline.beta_prefetch [device], the beta round
                    trip before window 0 (consensus/pipeline.py)

The cyclic collector has counters, not spans (storage/stream.py):
`replay.gc.pause_us` (whole microseconds inside collections of any
generation, on whichever thread tripped one: a span there would land
inside whatever stage span happened to be open), `replay.gc.full_passes`,
`replay.gc.freezes` and `replay.gc.frozen_objects`, all counted only
while a replay runs.

Waits are counters, not spans: `pipeline.producer_wait_blocks_us`,
`pipeline.producer_stall_us` (the permit wait),
`pipeline.consumer_wait_us` and
`pipeline.first_submit_us` (consensus/pipeline.py), and the prefetch
thread's `replay.stream.backpressure_wait_us` (storage/stream.py
`_put`), hold whole microseconds.  A consumer of spans
that gives a piece of device idle time to the open span that started
last would hand it to a wait span opened after the work it waits on,
and take it from that work; as gated registry counters the waits also
reach the scrape endpoint with span recording off.

On the CPU and off it: `span(..., cpu=True)` reads the opening thread's
own CPU clock (`time.thread_time()`) beside the monotonic clock at both
edges.  The closed span carries `cpu`, the seconds the thread ran
inside it (None where not asked, where a sim or IO runtime supplies the
clock, and on adopted rows), and the rest of its length (`off_cpu`,
clipped to 0 and to the length) goes in whole microseconds to the
counter `span.off_cpu_us.<name>` (gated, `stable=False`, bound once a
name).  In a stage that makes no
blocking call (`window.host_seq`; `decode.unpack`, whose one read of a
waiting reply does not block but gives the lock up)
time off the CPU is the wait for the interpreter lock plus whatever the
kernel took the core away for; in `window.submit`, `stream.read` and
`stream.snapshot` it also holds the copies to the device and the file
system.  Asked for at those five, none of them a block: two clock reads
more a span.

A thread's CPU seconds a replay: `thread_usage(cpu_us, preempts)` around
a thread's part of a streamed replay adds the thread's CPU time
(`time.thread_time_ns()`, whole microseconds) and the times the kernel
took the core from it while it wanted to run
(`getrusage(RUSAGE_THREAD).ru_nivcsw`; a wait for the interpreter lock
is a voluntary switch and is not among them) to the two counters it is
given: `replay.thread_cpu_us.<thread>` and
`replay.thread_preempts.<thread>` for `prefetch` (storage/stream.py
`BlockPrefetcher._run`), `producer` (consensus/pipeline.py
`_run_producer`) and `caller` (`StreamingReplayEngine.replay`).  Always
read, span recording on or off: the three threads' CPU seconds over the
replay's length say how many cores' worth of work the host chain did,
which is 1 where one interpreter lock is taken in turn.
"""
from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import List, Optional

try:
    import resource
    _RUSAGE_THREAD = resource.RUSAGE_THREAD
except (ImportError, AttributeError):      # not Linux: no preempt count
    _RUSAGE_THREAD = None

from ..simharness import runtime as _runtime
from . import metrics as _metrics

def monotonic_now() -> float:
    """Virtual monotonic time under an active sim/IO runtime, host
    perf_counter otherwise."""
    rt = _runtime.current_or_none()
    if rt is not None:
        return rt.now()
    return time.perf_counter()


def device_fence() -> None:
    """Drain the async dispatch queue, so a timed interval never
    inherits the previous dispatch's in-flight device work.  No-op
    unless jax is already imported (a fenced span in a host-only process
    must not load it)."""
    if "jax" not in sys.modules:
        return
    import jax
    jax.block_until_ready(jax.device_put(0.0))


class Span:
    """One completed (or in-flight) interval.  `t0`/`t1` are clock
    readings from `monotonic_now`; `children` are spans closed while
    this one was the innermost open span; `thread` is the name of the
    thread that opened it; `meta` holds the keyword arguments `span()`
    was given (`window=k`), or None; `cpu` is the seconds the opening
    thread ran inside a closed `span(..., cpu=True)`, else None."""

    __slots__ = ("name", "cat", "t0", "t1", "children", "meta", "thread",
                 "cpu")

    def __init__(self, name: str, cat: str, t0: float,
                 thread: Optional[str] = None,
                 meta: Optional[dict] = None):
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.t1: Optional[float] = None
        self.children: List["Span"] = []
        self.meta = meta
        self.thread = thread
        self.cpu: Optional[float] = None

    @property
    def duration(self) -> float:
        return (self.t1 - self.t0) if self.t1 is not None else 0.0

    @property
    def off_cpu(self) -> Optional[float]:
        """Seconds of a `cpu=True` span in which its thread was not on
        a CPU: its length less `cpu`, clipped to 0 and to the length
        (the two clocks are not read at one instant)."""
        if self.cpu is None:
            return None
        return min(self.duration, max(0.0, self.duration - self.cpu))

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()

    def __repr__(self):
        return (f"Span({self.name!r}, cat={self.cat!r}, "
                f"dur={self.duration:.6f}, "
                f"children={len(self.children)})")


class _NullSpan:
    """Shared no-op context manager for the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _LiveSpan:
    __slots__ = ("_rec", "_name", "_cat", "_fence", "_meta", "_cpu",
                 "_cpu0", "_span")

    def __init__(self, rec: "SpanRecorder", name: str, cat: str,
                 fence: bool, meta: Optional[dict] = None,
                 cpu: bool = False):
        self._rec = rec
        self._name = name
        self._cat = cat
        self._fence = fence
        self._meta = meta
        self._cpu = cpu
        self._cpu0: Optional[float] = None   # thread_time() at the open
        self._span: Optional[Span] = None

    def __enter__(self) -> Span:
        if self._fence:
            device_fence()
        self._span = self._rec._open(self._name, self._cat, self._meta)
        # the CPU clock is read inside the monotonic readings at both
        # edges; a runtime's virtual clock has no CPU clock beside it
        if self._cpu and _runtime.current_or_none() is None:
            self._cpu0 = time.thread_time()
        return self._span

    def __exit__(self, *exc):
        if self._fence:
            device_fence()
        if self._cpu0 is not None and self._span.t1 is None:
            self._span.cpu = time.thread_time() - self._cpu0
        self._rec._close(self._span)
        return False


class SpanRecorder:
    """Process-wide span collector: an open-span stack plus the list of
    completed root trees.  Bounded — a forgotten enabled recorder in a
    long-lived node must not grow without limit; overflow drops new
    roots and counts them."""

    def __init__(self, enabled: bool = False, max_roots: int = 100_000):
        self.enabled = enabled
        self.max_roots = max_roots
        self.roots: List[Span] = []
        self._tls = threading.local()      # per-thread open-span stack
        self._lock = threading.Lock()      # guards roots/dropped
        self.dropped = 0
        self.flight = None                 # armed FlightRecorder
        self._drop_counter = _metrics.counter("observe.spans_dropped",
                                              always=True)
        # per-phase duration histograms, bound lazily ONCE per category
        # (a span close must not pay a registry lookup): every close
        # feeds `latency.phase.<cat>`, so phase p50/p95/p99 are live on
        # the scrape endpoint while a replay runs
        self._phase_hist: dict = {}
        # `span.off_cpu_us.<name>` counters of the `cpu=True` spans,
        # bound the same way, once a name
        self._off_cpu: dict = {}

    def _off_cpu_for(self, name: str):
        c = self._off_cpu.get(name)
        if c is None:
            c = _metrics.counter(f"span.off_cpu_us.{name}", stable=False)
            self._off_cpu[name] = c
        return c

    def _hist_for(self, cat: str):
        h = self._phase_hist.get(cat)
        if h is None:
            h = _metrics.latency_histogram(f"latency.phase.{cat}")
            self._phase_hist[cat] = h
        return h

    @property
    def _stack(self) -> List[Span]:
        """Open-span stack of the CALLING thread: nesting is a per-thread
        notion — a producer-thread span overlapping a consumer-thread
        span in wall time is concurrency, not containment."""
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    # -- the public surface ------------------------------------------------
    def span(self, name: str, cat: str = "host-seq", fence: bool = False,
             cpu: bool = False, **meta):
        """Context manager timing one interval; keyword arguments land
        in the span's `meta`; `cpu=True` also reads the thread's CPU
        clock (the module text says what for).  Near-free when the
        recorder is disabled (returns a shared null CM)."""
        if not self.enabled:
            return _NULL
        return _LiveSpan(self, name, cat, fence, meta or None, cpu)

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def drain(self) -> List[Span]:
        """Completed root spans since the last drain (open spans stay on
        the stack and attach to a later drain's roots when closed)."""
        with self._lock:
            out, self.roots = self.roots, []
        return out

    def clear(self) -> None:
        with self._lock:
            self.roots = []
            self._tls = threading.local()
            self.dropped = 0

    # -- recording ---------------------------------------------------------
    def _open(self, name: str, cat: str,
              meta: Optional[dict] = None) -> Span:
        thread = threading.current_thread().name
        sp = Span(name, cat, monotonic_now(), thread, meta)
        self._stack.append(sp)
        return sp

    def _close(self, sp: Span) -> None:
        if sp.t1 is not None:
            # already stamped: this span was adopted as a child by an
            # earlier out-of-order close (or its CM exited twice);
            # recording it again would attach it under a second
            # parent/root and count it twice
            return
        sp.t1 = monotonic_now()
        if sp.cpu is not None:
            self._off_cpu_for(sp.name).inc(int(sp.off_cpu * 1e6))
        fl = self.flight
        if fl is not None:
            fl.span(sp)
        # tolerate out-of-order closes (a generator-held span closed
        # late): pop up to and including sp, re-parenting survivors
        stack = self._stack
        if sp in stack:
            while stack:
                top = stack.pop()
                if top is sp:
                    break
                if top.t1 is None:
                    top.t1 = sp.t1
                sp.children.append(top)
        parent = stack[-1] if stack else None
        # phase-latency feed: one sample per contiguous same-category
        # episode — a span nested under a SAME-cat parent (JaxBackend's
        # "window.drain" inside the pipeline's "pipeline.drain", both
        # device) is the same wait seen twice, and observing both would
        # double the histogram count and skew the quantiles
        if parent is None or parent.cat != sp.cat:
            self._hist_for(sp.cat).observe(sp.t1 - sp.t0)
        if parent is not None:
            parent.children.append(sp)
        else:
            with self._lock:
                if len(self.roots) < self.max_roots:
                    self.roots.append(sp)
                else:
                    self.dropped += 1
                    self._drop_counter.inc()


RECORDER = SpanRecorder()


def recorder() -> SpanRecorder:
    return RECORDER


def span(name: str, cat: str = "host-seq", fence: bool = False,
         cpu: bool = False, **meta):
    """observe.spans.span("window.drain", cat="device") — module-level
    convenience over the process-wide recorder."""
    rec = RECORDER
    if not rec.enabled:
        return _NULL
    return _LiveSpan(rec, name, cat, fence, meta or None, cpu)


def enabled() -> bool:
    return RECORDER.enabled


def _preempts() -> int:
    """Involuntary context switches of the calling thread so far."""
    if _RUSAGE_THREAD is None:
        return 0
    return resource.getrusage(_RUSAGE_THREAD).ru_nivcsw


@contextlib.contextmanager
def thread_usage(cpu_us, preempts):
    """`with thread_usage(cpu_us, preempts):`, or `@thread_usage(...)`
    on a function (a fresh pair of start readings a call), adds what the
    calling thread used inside to two counters: its CPU time in whole
    microseconds, and the times the kernel took the core from it while
    it wanted to run.  Two clock reads and two `getrusage` calls, span
    recording on or off (the module text names the three threads of a
    streamed replay that are read so)."""
    sw0 = _preempts()
    ns0 = time.thread_time_ns()
    try:
        yield
    finally:
        cpu_us.inc((time.thread_time_ns() - ns0) // 1000)
        preempts.inc(_preempts() - sw0)


def adopt(parent: Span, rows, thread: str) -> None:
    """Spans timed in another process become closed children of
    `parent`: `rows` of `(name, cat, t0, t1)` read there from
    `time.perf_counter()` (on Linux the system-wide monotonic clock, so
    the readings are on this recorder's clock), `thread` the name that
    process goes by.  They lie where the other process did the work,
    which can be before `parent` opened (storage/decode_pool.py: a
    worker decodes a chunk ahead of the prefetch thread asking for it)."""
    for name, cat, t0, t1 in rows:
        sp = Span(name, cat, t0, thread)
        sp.t1 = t1
        parent.children.append(sp)
