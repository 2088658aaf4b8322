"""The measured window and its two rates.  No JAX, no program import:
pure arithmetic on replay times, so `tests/test_window.py` can drive it
with synthetic clocks.

The window: replays run back to back; it ends at the first replay
boundary at or after `seconds`; a partial replay is never counted.
What the harness does between replays (clearing key caches, a garbage
collection) happens in `before_each`: outside each replay's own
interval, inside the window's wall time, and so inside the end-to-end
rate, which is all the work over all the time.
"""
from __future__ import annotations

import statistics
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional


@dataclass
class Replay:
    """One whole replay: wall seconds, what it returned (the program's
    JSON line as a dict) or the error that ended it."""
    seconds: float
    t0: float = 0.0
    result: Optional[dict] = None
    error: Optional[str] = None


@dataclass
class Window:
    replays: List[Replay] = field(default_factory=list)
    t_start: float = 0.0
    t_end: float = 0.0

    @property
    def attempted(self) -> int:
        return len(self.replays)


def run_window(replay_once: Callable[[], dict], seconds: float,
               before_each: Callable[[], None] = lambda: None,
               after_each: Callable[[int], None] = lambda i: None,
               clock: Callable[[], float] = time.perf_counter) -> Window:
    """Drive `replay_once` until the first replay boundary at or after
    `seconds`.  `before_each` and `after_each(i)` run outside the timed
    interval of every replay (but inside the window's wall time)."""
    w = Window(t_start=clock())
    while True:
        before_each()
        t0 = clock()
        try:
            rep = Replay(0.0, t0, result=replay_once())
        except (Exception, SystemExit) as e:   # a failed replay is counted
            rep = Replay(0.0, t0, error=f"{type(e).__name__}: {e}")
        t1 = clock()
        rep.seconds = t1 - t0
        w.replays.append(rep)
        after_each(len(w.replays) - 1)
        if t1 - w.t_start >= seconds:
            w.t_end = t1
            return w


def window_rate(blocks_per_replay: int, whole_replays: int,
                w: Window) -> float:
    """The end-to-end rate: all the blocks of the whole replays over all
    the time of the window, what happens between replays and any stall
    included."""
    return blocks_per_replay * whole_replays / (w.t_end - w.t_start)


def median_rate(blocks_per_replay: int, replay_seconds: List[float]) -> float:
    """blocks of ONE replay / the median wall time of the whole replays:
    a steadier number that one slow replay in thirty does not move, and
    for that reason a per-layer metric beside the end-to-end rate."""
    return blocks_per_replay / statistics.median(replay_seconds)


def iqr_share(values: List[float]) -> float:
    """The contract's spread: distance between the first and third
    quartile (statistics.quantiles, n=4) as a share of the median."""
    if len(values) < 2:
        return 0.0
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
