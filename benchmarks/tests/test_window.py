"""The window loop and its two rates, on synthetic replay times.

Run by hand: `python -m pytest benchmarks/tests -q` (not part of the
repo's tests/).
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import window  # noqa: E402


class FakeClock:
    """A clock the replays advance: no sleeping, exact arithmetic."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def drive(durations, seconds, housekeeping=0.0):
    clock = FakeClock()
    todo = iter(durations)

    def once():
        clock.now += next(todo)
        return {"ok": True}

    def before():
        clock.now += housekeeping

    return window.run_window(once, seconds, before_each=before, clock=clock)


def test_window_ends_at_the_first_replay_boundary_after_seconds():
    w = drive([1.0] * 50, 10.0)
    assert w.attempted == 10
    assert w.t_end - w.t_start == pytest.approx(10.0)


def test_partial_replay_is_never_counted():
    # 4 s replays, 10 s window: the third replay crosses 10 s and is
    # finished (a whole replay); no fourth, partial one is started
    w = drive([4.0] * 50, 10.0)
    assert [r.seconds for r in w.replays] == [4.0, 4.0, 4.0]
    assert w.t_end - w.t_start == pytest.approx(12.0)


def test_housekeeping_is_outside_each_replay_and_inside_the_rate():
    w = drive([1.0] * 50, 10.0, housekeeping=0.25)
    assert all(r.seconds == pytest.approx(1.0) for r in w.replays)
    # ... but inside the window's wall time, so fewer replays fit
    assert w.attempted == 8
    assert w.t_end - w.t_start == pytest.approx(10.0)
    # the end-to-end rate is all the work over all the time ...
    assert window.window_rate(2048, 8, w) == pytest.approx(2048 * 8 / 10.0)
    # ... and the per-layer median rate sees the replays alone
    assert window.median_rate(2048, [r.seconds for r in w.replays]) == \
        pytest.approx(2048.0)


def test_one_3x_outlier_moves_the_median_little_and_the_rate_by_arithmetic():
    clean = [1.0 + 0.001 * (i % 7) for i in range(30)]
    dirty = list(clean)
    dirty[11] *= 3.0
    m0, m1 = (window.median_rate(2048, s) for s in (clean, dirty))
    assert abs(m1 - m0) / m0 < 0.01
    w0, w1 = (drive(s, sum(s) - 0.5) for s in (clean, dirty))
    assert w0.attempted == w1.attempted == 30
    a0, a1 = (window.window_rate(2048, 30, w) for w in (w0, w1))
    # the end-to-end rate falls by exactly the added time's share: a
    # stall inside the window shows
    assert a1 == pytest.approx(a0 * sum(clean) / sum(dirty))
    assert (a0 - a1) / a0 == pytest.approx(
        2 * clean[11] / sum(dirty), rel=1e-9)
    assert (a0 - a1) / a0 > 0.06


def test_only_whole_replays_count_in_the_rate():
    # three 4 s replays, the second failed: two replays' blocks over all
    # 12 s of the window
    w = drive([4.0] * 3, 10.0)
    assert window.window_rate(512, 2, w) == pytest.approx(1024 / 12.0)


def test_a_failed_replay_is_counted_and_carries_its_error():
    clock = FakeClock()
    n = [0]

    def once():
        clock.now += 1.0
        n[0] += 1
        if n[0] == 2:
            raise SystemExit("validation FAILED at block 7")
        return {"ok": True}

    w = window.run_window(once, 3.0, clock=clock)
    assert w.attempted == 3
    assert [r.error is None for r in w.replays] == [True, False, True]
    assert "block 7" in w.replays[1].error


def test_iqr_share_is_the_contracts_spread():
    import statistics
    vals = [10.0, 10.2, 9.9, 10.1, 10.4, 9.7]
    q = statistics.quantiles(vals, n=4)
    assert window.iqr_share(vals) == pytest.approx(
        (q[2] - q[0]) / statistics.median(vals))
