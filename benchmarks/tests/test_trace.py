"""The trace reduction: busy union, idle share, gap attribution and the
operation table, on synthetic intervals and on the small trace recorded
on the chip beside this file (`small.xplane.pb`, made by
`tests/record_small_trace.py`: three bursts of a small jitted program
under the host spans work.a, work.b, work.a, then 3 ms under no span).
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import trace as T  # noqa: E402


# -- synthetic ---------------------------------------------------------------
OPS = [(0, 10, "%fusion.1 = s32[4]{0} fusion(...)"),
       (2, 4, "%copy.7 = s32[4]{0} copy(...)"),      # nested in fusion.1
       (20, 30, "%fusion.2 = s32[4]{0} fusion(...)"),
       (30.005, 40, "custom-call.3")]


def test_busy_union_counts_nested_and_overlapping_once():
    merged = T.union(OPS, 0, 50)
    assert merged == [(0, 10), (20, 30), (30.005, 40)]
    assert T.busy_ns(merged) == pytest.approx(29.995)
    # clipping to the window
    assert T.busy_ns(T.union(OPS, 5, 25)) == pytest.approx(10.0)


def test_gaps_are_the_complement_inside_the_window():
    merged = T.union(OPS, 0, 50)
    assert T.gaps(merged, 0, 50) == [(10, 20), (30, 30.005), (40, 50)]
    assert T.gaps([], 3, 9) == [(3, 9)]


def test_gap_attribution_latest_started_span_wins_and_sums_to_idle():
    spans = [(5, 15, "stream.decode"), (12, 45, "window.host_seq"),
             (14, 16, "window.submit")]
    gap_list = T.gaps(T.union(OPS, 0, 50), 0, 50)
    out = T.attribute_gaps(gap_list, spans, short_ns=1)
    assert out == pytest.approx({
        "stream.decode": 2.0,          # 10-12
        "window.host_seq": 2 + 4 + 5,  # 12-14, 16-20, 40-45
        "window.submit": 2.0,          # 14-16
        T.NO_SPAN: 5.0,                # 45-50
        T.SHORT_GAP: 0.005})
    assert sum(out.values()) == pytest.approx(
        sum(b - a for a, b in gap_list))


def test_operations_group_by_name_and_exclude_what_is_nested():
    st = T.self_times(OPS, 0, 50)
    assert st == pytest.approx({"fusion": 8 + 10, "copy": 2.0,
                                "custom-call": 9.995})
    assert T.op_group("%fusion.1742 = s32[20,2048]{1,0} fusion(%p)") == \
        "fusion"
    assert T.op_group("tpu_custom_call.12") == "tpu_custom_call"


def test_reduction_over_two_windows_and_two_devices():
    ops = {"/device:TPU:0": OPS, "/device:TPU:1": [(0, 50, "fusion.9")]}
    r = T.reduce_windows(ops, [], [(0, 25), (25, 50)])
    # device 0 busy 29.995, device 1 busy 50: the mean, in seconds
    assert r["busy_s"] == pytest.approx((29.995 + 50) / 2 * 1e-9)
    assert r["window_s"] == pytest.approx(50e-9)
    assert r["idle_s"] / r["window_s"] == pytest.approx(
        1 - (29.995 + 50) / 100)
    assert r["devices"] == 2
    assert T.reduce_windows({}, [], [(0, 1)]) == {}


# -- the recorded trace ------------------------------------------------------
PB = os.path.join(HERE, "small.xplane.pb")
SPANS = os.path.join(HERE, "small.spans.json")
recorded = pytest.mark.skipif(not os.path.exists(PB),
                              reason="no recorded trace beside the test")


@pytest.fixture(scope="module")
def small():
    pd = T.load(PB)
    with open(SPANS) as fh:
        meta = json.load(fh)
    return pd, meta


@recorded
def test_recorded_trace_has_device_ops_and_both_clock_marks(small):
    pd, meta = small
    ops = T.device_ops(pd)
    assert list(ops) == ["/device:TPU:0"]
    assert len(ops["/device:TPU:0"]) > 10
    marks = T.marks(pd)
    assert len(marks) == len(meta["marks_perf_ns"]) == 2
    # the two marks give the same clock offset to within 100 us
    offs = [T.clock_offset_ns(m, p)
            for m, p in zip(marks, meta["marks_perf_ns"])]
    assert abs(offs[1] - offs[0]) < 100_000


@recorded
def test_recorded_trace_reduces_to_what_a_brute_force_count_gives(small):
    pd, meta = small
    ops = T.device_ops(pd)["/device:TPU:0"]
    marks = T.marks(pd)
    off = T.clock_offset_ns(marks[0], meta["marks_perf_ns"][0])
    spans = [(t0 * 1e9 + off, t1 * 1e9 + off, n)
             for t0, t1, n in meta["spans"]]
    lo, hi = marks
    r = T.reduce_windows({"/device:TPU:0": ops}, spans, [(lo, hi)])
    # an independent count: sweep the edges, time with >= 1 op running
    edges = sorted([(max(t0, lo), 1) for t0, t1, _n in ops if t1 > lo
                    and t0 < hi] +
                   [(min(t1, hi), -1) for t0, t1, _n in ops if t1 > lo
                    and t0 < hi])
    running, since, brute = 0, None, 0.0
    for t, step in edges:
        if running == 0 and step == 1:
            since = t
        running += step
        if running == 0:
            brute += t - since
    assert r["busy_s"] == pytest.approx(brute * 1e-9, rel=1e-9)
    assert 0.0 < r["busy_s"] < r["window_s"]
    # sleeps of 2 + 4 + 1 ms under the spans and 3 ms under none: the
    # device is idle at least that long, and the classes say where.
    # (The device's events sit ~1 ms before the host annotations that
    # launched them in this trace: a skew the xplane carries, so a gap
    # of a few ms can lean into the neighbouring span.)
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    assert gaps["work.b"] >= 0.004
    assert gaps["work.a"] >= 0.003
    assert gaps[T.NO_SPAN] >= 0.003
    assert sum(gaps.values()) == pytest.approx(r["idle_s"], rel=1e-6)
    assert r["idle_s"] / r["window_s"] > 0.5
    assert r["device_ops"] and r["device_ops"][0][1] > 0
