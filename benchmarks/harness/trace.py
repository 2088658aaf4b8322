"""From the profiler's `.xplane.pb` and the program's spans to device
busy and idle time, the idle gaps by what the host was doing, and the
device operations that took most time.

The functions below the loader work on plain lists of
`(start_ns, end_ns, name)`, so `tests/test_trace.py` checks them on
synthetic intervals as well as on the recorded trace beside it.

Clocks: the profiler stamps every event in nanoseconds from the start
of its session; the program's spans are `time.perf_counter()` seconds.
`run.py` writes a `CLOCK_MARK` annotation into the trace next to a
`perf_counter_ns()` reading, and `clock_offset_ns` turns that pair into
the shift that puts spans on the trace's clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Tuple

Interval = Tuple[float, float, str]

CLOCK_MARK = "bench.clock_mark"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
SHORT_GAP_NS = 10_000
SHORT_GAP = "between_ops_lt10us"
NO_SPAN = "no_span"


# -- reading the file ------------------------------------------------------
def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def describe(pd) -> list:
    """Planes and lines with their event counts: what to look at by hand
    before trusting a reduction on a new device."""
    return [{"plane": p.name,
             "lines": [{"line": ln.name, "events": sum(1 for _ in ln.events)}
                       for ln in p.lines]}
            for p in pd.planes]


def device_ops(pd, rehearse: bool = False) -> Dict[str, List[Interval]]:
    """`{plane name: [(start_ns, end_ns, op name), ...]}` for every TPU
    plane: the events of its operations line, sorted by start.  A CPU
    rehearsal has no device plane; it reads XLA:CPU's own threads on the
    host plane in its place, only to drive the code below."""
    out: Dict[str, List[Interval]] = {}
    for plane in pd.planes:
        if not (DEVICE_PLANE.match(plane.name)
                or rehearse and plane.name == "/host:CPU"):
            continue
        evs: List[Interval] = []
        for line in plane.lines:
            if line.name != OP_LINE and not (
                    rehearse and line.name.startswith("tf_XLA")):
                continue
            evs.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events)
        evs.sort()
        out[plane.name] = evs
    return out


def marks(pd, name: str = CLOCK_MARK) -> List[float]:
    """Start times (trace clock, ns) of the host annotations `name`."""
    found = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            found.extend(e.start_ns for e in line.events if e.name == name)
    return sorted(found)


def clock_offset_ns(mark_trace_ns: float, mark_perf_ns: float) -> float:
    """Add this to a `perf_counter` reading (in ns) to put it on the
    trace's clock."""
    return mark_trace_ns - mark_perf_ns


# -- arithmetic on intervals -------------------------------------------------
def union(intervals: List[Interval], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """The union of the intervals, clipped to [lo, hi], as a sorted
    disjoint list.  Nested and overlapping events count once."""
    merged: List[Tuple[float, float]] = []
    for t0, t1, _name in sorted(intervals):
        t0, t1 = max(t0, lo), min(t1, hi)
        if t1 <= t0:
            continue
        if merged and t0 <= merged[-1][1]:
            if t1 > merged[-1][1]:
                merged[-1] = (merged[-1][0], t1)
        else:
            merged.append((t0, t1))
    return merged


def busy_ns(merged: List[Tuple[float, float]]) -> float:
    return sum(t1 - t0 for t0, t1 in merged)


def gaps(merged: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The complement of a disjoint sorted union within [lo, hi]."""
    out = []
    at = lo
    for t0, t1 in merged:
        if t0 > at:
            out.append((at, t0))
        at = max(at, t1)
    if hi > at:
        out.append((at, hi))
    return out


def attribute_gaps(gap_list: List[Tuple[float, float]],
                   spans: List[Interval],
                   short_ns: float = SHORT_GAP_NS) -> Dict[str, float]:
    """Idle nanoseconds by what the host was doing.  A gap shorter than
    `short_ns` is the device between two operations.  Any other gap is
    cut at every span edge inside it, and each piece goes to the span
    open then that STARTED LAST (the work a thread has just begun, not
    the wait another thread has been in since before it), or to
    `no_span`.  The classes sum to the idle time."""
    spans = sorted(spans)
    starts = [s[0] for s in spans]
    longest = max((s[1] - s[0] for s in spans), default=0.0)
    out: Dict[str, float] = {}
    for g0, g1 in gap_list:
        if g1 - g0 < short_ns:
            out[SHORT_GAP] = out.get(SHORT_GAP, 0.0) + (g1 - g0)
            continue
        first = bisect.bisect_left(starts, g0 - longest)
        last = bisect.bisect_left(starts, g1)
        near = [s for s in spans[first:last] if s[1] > g0]
        cuts = sorted({g0, g1, *(t for s in near for t in s[:2]
                                 if g0 < t < g1)})
        for a, b in zip(cuts, cuts[1:]):
            live = [s for s in near if s[0] <= a and s[1] >= b]
            name = max(live)[2] if live else NO_SPAN
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def op_group(name: str) -> str:
    """The operation's name without its result type and instance number:
    `%fusion.1742 = s32[20,2048]{1,0} fusion(...)` and `fusion.8` both
    read `fusion`, so one kernel's instances add up."""
    name = name.split(" = ")[0].strip().lstrip("%")
    return re.sub(r"[.:]\d+$", "", name) or name


def self_times(ops: List[Interval], lo: float, hi: float
               ) -> Dict[str, float]:
    """Nanoseconds by operation group inside [lo, hi], each event counted
    without the events nested in it (a loop is not charged its body)."""
    out: Dict[str, float] = {}
    stack: List[list] = []        # [end, group, self_ns]

    def close(until: float) -> None:
        while stack and stack[-1][0] <= until:
            _end, group, ns = stack.pop()
            out[group] = out.get(group, 0.0) + max(ns, 0.0)

    for t0, t1, name in sorted(ops):
        t0, t1 = max(t0, lo), min(t1, hi)
        if t1 <= t0:
            continue
        close(t0)
        if stack:
            stack[-1][2] -= min(t1, stack[-1][0]) - t0
        stack.append([t1, op_group(name), t1 - t0])
    close(float("inf"))
    return out


def top(d: Dict[str, float], k: int = 10, scale: float = 1e-9) -> list:
    return [[n, v * scale] for n, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:k]]


# -- the whole reduction -------------------------------------------------------
def reduce_windows(ops_by_device: Dict[str, List[Interval]],
                   spans: List[Interval],
                   windows: List[Tuple[float, float]]) -> dict:
    """Everything the traced run reports from the trace, over the given
    windows (trace clock, ns; the traced replays' own intervals).  Times
    in the result are seconds, summed over the windows and averaged over
    the devices where there are several."""
    n = len(ops_by_device)
    window_ns = sum(hi - lo for lo, hi in windows)
    if n == 0 or window_ns <= 0:
        return {}
    busy = 0.0
    gap_classes: Dict[str, float] = {}
    op_ns: Dict[str, float] = {}
    for ops in ops_by_device.values():
        for lo, hi in windows:
            merged = union(ops, lo, hi)
            busy += busy_ns(merged)
            for k, v in attribute_gaps(gaps(merged, lo, hi),
                                       spans).items():
                gap_classes[k] = gap_classes.get(k, 0.0) + v
            for k, v in self_times(ops, lo, hi).items():
                op_ns[k] = op_ns.get(k, 0.0) + v
    busy_s = busy / n * 1e-9
    window_s = window_ns * 1e-9
    return {
        "devices": n,
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_s": window_s - busy_s,
        "device_ops": top({k: v / n for k, v in op_ns.items()}),
        "idle_gaps": top({k: v / n for k, v in gap_classes.items()}),
    }
