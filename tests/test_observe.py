"""ouroboros_tpu/observe test surface (ISSUE 7 satellite):

- registry determinism: snapshots sorted by name and byte-identical for
  identical workloads regardless of instrument creation order;
- span nesting + fencing under both the wall clock and the sim virtual
  clock (exact virtual durations — the same API works under simharness);
- golden files for the three exporters (Prometheus text exposition,
  chrome://tracing trace_event JSON, typed-events JSONL) built from
  hand-constructed fixtures with pinned timestamps, so the golden bytes
  are fully deterministic.  Regenerate after an INTENTIONAL format
  change with:  OURO_REGEN_GOLDEN=1 pytest tests/test_observe.py
- the zero-overhead probe: with observation disabled, gated instruments
  perform no writes at all, `span()` returns one shared null context
  manager, and `always` (load-bearing) counters keep counting without
  charging `data_writes`.
"""
import io
import json
import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ouroboros_tpu import simharness as sim
from ouroboros_tpu.observe import adapter, export, metrics, spans
from ouroboros_tpu.observe.metrics import MetricsRegistry
from ouroboros_tpu.observe.spans import Span, SpanRecorder
from ouroboros_tpu.utils.tracer import (
    TraceAddBlock, TraceChainSyncEvent, TraceFetchDecision,
    TraceForgeEvent, collecting,
)

GOLDEN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "golden", "observe")


# ---------------------------------------------------------------------------
# registry determinism
# ---------------------------------------------------------------------------

def _workload(reg: MetricsRegistry, order: int = 0):
    """The same instrument writes, issued under two creation orders."""
    names = ["b.window", "a.hits", "c.depth"]
    if order:
        names.reverse()
    for n in names:
        if n == "c.depth":
            reg.gauge(n)
        else:
            reg.counter(n)
    reg.counter("a.hits").inc(3)
    reg.counter("b.window").inc()
    reg.gauge("c.depth").set(7)
    h = reg.histogram("d.sizes", buckets=(1, 2, 4))
    for v in (1, 2, 3, 9):
        h.observe(v)


def test_snapshot_sorted_and_byte_identical_across_creation_order():
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    _workload(r1, order=0)
    _workload(r2, order=1)
    snap = r1.snapshot()
    assert list(snap) == sorted(snap)
    assert r1.snapshot_json() == r2.snapshot_json()
    # and across repeated renders of the same registry
    assert r1.snapshot_json() == r1.snapshot_json()


def test_snapshot_values_and_histogram_shape():
    reg = MetricsRegistry()
    _workload(reg)
    snap = reg.snapshot()
    assert snap["a.hits"] == 3
    assert snap["c.depth"] == 7
    assert snap["d.sizes"]["count"] == 4
    assert snap["d.sizes"]["sum"] == 15
    assert snap["d.sizes"]["buckets"] == {"1": 1, "2": 1, "4": 1}
    assert snap["d.sizes"]["overflow"] == 1


def test_unstable_instruments_excluded_from_snapshot_not_prometheus():
    reg = MetricsRegistry()
    reg.counter("stable.count").inc()
    reg.gauge("measured.secs", stable=False).set(1.234)
    snap = reg.snapshot()
    assert "stable.count" in snap and "measured.secs" not in snap
    assert "measured.secs" in reg.snapshot(include_unstable=True)
    prom = export.prometheus_text(reg)
    assert "ouro_measured_secs" in prom and "ouro_stable_count" in prom


def test_instrument_creation_idempotent_and_kind_checked():
    reg = MetricsRegistry()
    c = reg.counter("x")
    assert reg.counter("x") is c
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_reset_zeroes_values_but_keeps_registration():
    reg = MetricsRegistry()
    _workload(reg)
    writes = reg.data_writes
    assert writes > 0
    reg.reset()
    assert reg.data_writes == 0
    assert reg.counter("a.hits").value == 0
    assert reg.histogram("d.sizes", buckets=(1, 2, 4)).count == 0
    assert set(reg.snapshot()) == {"a.hits", "b.window", "c.depth",
                                   "d.sizes"}


# ---------------------------------------------------------------------------
# the zero-overhead probe (disabled observation)
# ---------------------------------------------------------------------------

def test_disabled_registry_performs_zero_writes():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c")
    g = reg.gauge("g")
    h = reg.histogram("h")
    c.inc(5)
    g.set(9)
    h.observe(3)
    assert c.value == 0 and g.value == 0 and h.count == 0
    assert reg.data_writes == 0


def test_always_counters_count_when_disabled_without_data_writes():
    """Migrated load-bearing counters (precompute fills, frozen-tuner
    writes) are program state: they count regardless of the flag and
    are never charged to the disabled-observation probe."""
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("precompute.like", always=True)
    c.inc(2)
    assert c.value == 2
    assert reg.data_writes == 0


def test_disabled_recorder_returns_one_shared_null_cm():
    rec = SpanRecorder(enabled=False)
    cm1 = rec.span("a", cat="device")
    cm2 = rec.span("b", cat="compile", fence=True)
    assert cm1 is cm2                      # no per-call allocation
    with cm1:
        pass
    assert rec.roots == [] and rec._stack == []


def test_global_enable_disable_flip_both_layers():
    from ouroboros_tpu import observe
    was_reg, was_rec = metrics.REGISTRY.enabled, spans.RECORDER.enabled
    try:
        observe.disable()
        assert not metrics.REGISTRY.enabled
        assert not spans.RECORDER.enabled
        assert not observe.enabled()
        observe.enable()
        assert observe.enabled()
    finally:
        metrics.REGISTRY.enabled, spans.RECORDER.enabled = was_reg, was_rec


# ---------------------------------------------------------------------------
# span nesting + fencing, wall clock and sim clock
# ---------------------------------------------------------------------------

def test_span_nesting_wall_clock():
    rec = SpanRecorder(enabled=True)
    with rec.span("outer", cat="dispatch"):
        with rec.span("inner", cat="device"):
            pass
    roots = rec.drain()
    assert len(roots) == 1
    outer = roots[0]
    assert outer.name == "outer" and outer.cat == "dispatch"
    (inner,) = outer.children
    assert inner.name == "inner" and inner.cat == "device"
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert rec.drain() == []               # drain is consuming


def test_span_sim_clock_exact_virtual_durations():
    """Under an active Sim runtime the span clock is virtual time, so
    durations are EXACT — the sim-time-aware half of the spans API."""
    rec = SpanRecorder(enabled=True)

    async def main():
        with rec.span("rep", cat="host-seq"):
            await sim.sleep(2.5)
            with rec.span("drain", cat="device"):
                await sim.sleep(1.25)

    sim.run(main())
    (rep,) = rec.drain()
    assert rep.duration == 3.75
    (drain,) = rep.children
    assert drain.duration == 1.25


def test_fenced_span_fences_both_edges(monkeypatch):
    fences = []
    monkeypatch.setattr(spans, "device_fence",
                        lambda: fences.append(len(fences)))
    rec = SpanRecorder(enabled=True)
    with rec.span("r", cat="sync", fence=True):
        assert fences == [0]               # entry edge fenced
    assert len(fences) == 2                # exit edge fenced too
    with rec.span("n", cat="sync"):        # fence=False: no fence calls
        pass
    assert len(fences) == 2


def test_device_fence_never_imports_jax(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    spans.device_fence()                   # must be a pure no-op
    assert "jax" not in sys.modules


def test_out_of_order_close_reparents_and_closes_survivors():
    """A generator-held span closed late must not corrupt the stack:
    the still-open inner span is adopted and closed at the same stamp."""
    rec = SpanRecorder(enabled=True)
    a = rec._open("a", "host-seq")
    b = rec._open("b", "device")
    rec._close(a)                          # closes a while b still open
    (root,) = rec.drain()
    assert root is a
    assert [c.name for c in a.children] == ["b"]
    assert b.t1 == a.t1
    assert rec._stack == []


def test_adopted_span_late_close_is_not_recorded_twice():
    """The survivor's OWN context-manager exit still fires after it was
    adopted by the out-of-order close; that second _close must be a
    no-op — re-recording it would add it as a second root (duplicated
    in the chrome trace) and overwrite its t1 past its parent's."""
    rec = SpanRecorder(enabled=True)
    a = rec._open("a", "host-seq")
    b = rec._open("b", "device")
    rec._close(a)                          # adopts + stamps b
    stamped = b.t1
    rec._close(b)                          # b's CM exits late
    (root,) = rec.drain()                  # a only — b is not a root
    assert root is a and a.children == [b]
    assert b.t1 == stamped                 # stamp not overwritten
    assert rec.drain() == []


def test_root_overflow_drops_and_counts():
    rec = SpanRecorder(enabled=True, max_roots=2)
    for i in range(4):
        with rec.span(f"s{i}"):
            pass
    assert len(rec.roots) == 2
    assert rec.dropped == 2


# ---------------------------------------------------------------------------
# exporter golden files
# ---------------------------------------------------------------------------

def _golden_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    reg.counter("precompute.hits", always=True).inc(5)
    reg.counter("window.count").inc(3)
    reg.gauge("queue.depth").set(4)
    reg.gauge("autotune.last_secs", stable=False).set(0.125)
    h = reg.histogram("batch.size", buckets=(1, 2, 4))
    for v in (1, 1, 3, 9):
        h.observe(v)
    return reg


def _golden_spans():
    """A consumer-thread root with a drain that carries its window's
    index, and a producer-thread root with a compile inside."""
    rep = Span("rep", "host-seq", 0.0, "MainThread")
    rep.t1 = 10.0
    sub = Span("window.submit", "dispatch", 1.0, "ouro-replay-producer")
    sub.t1 = 3.0
    comp = Span("window.composite(8,8,2,0)", "compile", 1.5,
                "ouro-replay-producer")
    comp.t1 = 2.5
    comp.meta = {"ne": 8}
    drain = Span("pipeline.drain", "device", 3.0, "MainThread",
                 {"window": 0})
    drain.t1 = 6.0
    sub.children.append(comp)
    rep.children.append(drain)
    return [rep, sub]


def _golden_events():
    return [
        TraceChainSyncEvent(peer_id="p1", event="roll-forward", slot=3,
                            n=4),
        TraceForgeEvent(slot=9, outcome="forged"),
        TraceAddBlock(kind="extended", slot=1, block_no=1,
                      hash=b"\x01\x02"),
        ("raw", 7),                        # non-dataclass payload
    ]


def _check_golden(name: str, text: str):
    path = os.path.join(GOLDEN_DIR, name)
    if os.environ.get("OURO_REGEN_GOLDEN"):
        os.makedirs(GOLDEN_DIR, exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
    with open(path) as f:
        golden = f.read()
    assert text == golden, (
        f"{name} drifted from its golden bytes; if the format change is "
        f"intentional: OURO_REGEN_GOLDEN=1 pytest tests/test_observe.py")


def test_prometheus_exposition_golden_and_roundtrip():
    text = export.prometheus_text(_golden_registry())
    _check_golden("metrics.prom", text)
    parsed = export.parse_prometheus_text(text)
    assert parsed["ouro_precompute_hits"] == 5.0
    assert parsed["ouro_window_count"] == 3.0
    assert parsed["ouro_autotune_last_secs"] == 0.125
    assert parsed['ouro_batch_size_bucket{le="+Inf"}'] == 4.0
    assert parsed["ouro_batch_size_sum"] == 14.0
    assert parsed["ouro_batch_size_count"] == 4.0
    # cumulative bucket counts, per the Prometheus convention
    assert parsed['ouro_batch_size_bucket{le="1"}'] == 2.0
    assert parsed['ouro_batch_size_bucket{le="4"}'] == 3.0


def _rows(doc) -> dict:
    """tid -> row name of a chrome-trace document."""
    return {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
            if e["ph"] == "M"}


def test_chrome_trace_golden_and_structure():
    doc = export.chrome_trace(_golden_spans())
    _check_golden("spans.trace.json",
                  json.dumps(doc, sort_keys=True) + "\n")
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in events}
    assert names == {"rep", "window.submit", "window.composite(8,8,2,0)",
                     "pipeline.drain"}
    # one tid row per THREAD, so the replay's threads render as parallel
    # tracks; the category is the event's own `cat`
    rows = _rows(doc)
    assert sorted(rows.values()) == ["MainThread", "ouro-replay-producer"]
    by_name = {e["name"]: e for e in events}
    assert {n: rows[e["tid"]] for n, e in by_name.items()} == {
        "rep": "MainThread", "pipeline.drain": "MainThread",
        "window.submit": "ouro-replay-producer",
        "window.composite(8,8,2,0)": "ouro-replay-producer"}
    assert {n: e["cat"] for n, e in by_name.items()} == {
        "rep": "host-seq", "pipeline.drain": "device",
        "window.submit": "dispatch",
        "window.composite(8,8,2,0)": "compile"}
    comp = by_name["window.composite(8,8,2,0)"]
    assert comp["ts"] == 1.5e6 and comp["dur"] == 1e6
    assert comp["args"] == {"ne": 8}
    assert by_name["pipeline.drain"]["args"] == {"window": 0}
    assert "args" not in by_name["rep"]


@pytest.mark.parametrize("threads, want_rows", [
    # every span names its thread: a row each, in order of first sight
    (("a", "b", "c"), {"x": "a", "y": "b", "z": "c"}),
    # two categories on one thread share its row (rows were by category)
    (("a", "a", "b"), {"x": "a", "y": "a", "z": "b"}),
    # spans built by hand name none: they share the row of the unnamed
    ((None, None, "b"), {"x": export.NO_THREAD, "y": export.NO_THREAD,
                         "z": "b"}),
])
def test_chrome_trace_rows_by_thread(threads, want_rows):
    x = Span("x", "host-seq", 0.0, threads[0])
    y = Span("y", "dispatch", 1.0, threads[1])
    z = Span("z", "device", 2.0, threads[2])
    for sp in (x, y, z):
        sp.t1 = sp.t0 + 1.0
    x.children.append(y)
    doc = export.chrome_trace([x, z])
    rows = _rows(doc)
    got = {e["name"]: rows[e["tid"]] for e in doc["traceEvents"]
           if e["ph"] == "X"}
    assert got == want_rows
    assert len(set(rows.values())) == len(rows) == len(
        set(want_rows.values()))
    assert {e["name"]: e["cat"] for e in doc["traceEvents"]
            if e["ph"] == "X"} == {"x": "host-seq", "y": "dispatch",
                                   "z": "device"}


def test_recorder_stamps_thread_and_meta_only_when_recording():
    rec = SpanRecorder(enabled=True)
    seen = {}

    def work():
        with rec.span("w", cat="disk", window=3) as sp:
            seen["sp"] = sp

    t = threading.Thread(target=work, name="ouro-test-thread")
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    with rec.span("m") as main_sp:
        pass
    assert seen["sp"].thread == "ouro-test-thread"
    assert seen["sp"].meta == {"window": 3}
    assert main_sp.thread == threading.current_thread().name
    assert main_sp.meta is None
    rec.disable()
    with rec.span("off", window=1) as nothing:
        pass
    assert nothing is None                 # the shared null span
    assert [r.name for r in rec.drain()] == ["w", "m"]


def test_events_jsonl_golden_and_typed_schema():
    text = export.events_jsonl(_golden_events())
    _check_golden("events.jsonl", text)
    lines = [json.loads(ln) for ln in text.splitlines()]
    assert [ln["type"] for ln in lines] == [
        "TraceChainSyncEvent", "TraceForgeEvent", "TraceAddBlock",
        "tuple"]
    assert lines[0]["event"] == "roll-forward"   # field kept alongside
    assert lines[0]["n"] == 4
    assert lines[2]["hash"] == "0102"      # bytes hex-encoded
    assert lines[3]["payload"] == ["raw", 7]


def test_jsonl_tracer_is_a_live_bridge():
    fh = io.StringIO()
    tr = export.jsonl_tracer(fh)
    assert tr.active
    tr.trace(TraceForgeEvent(slot=1, outcome="not-leader"))
    tr.trace(TraceForgeEvent(slot=2, outcome="forged"))
    lines = [json.loads(ln) for ln in fh.getvalue().splitlines()]
    assert [(ln["slot"], ln["outcome"]) for ln in lines] == [
        (1, "not-leader"), (2, "forged")]


# ---------------------------------------------------------------------------
# NodeTracers -> metrics adapter
# ---------------------------------------------------------------------------

def test_adapter_counts_by_event_class_not_string():
    reg = MetricsRegistry()
    nt = adapter.metrics_node_tracers(reg)
    nt.chain_sync.trace(TraceChainSyncEvent("p", "roll-forward", 1, n=3))
    nt.chain_sync.trace(TraceChainSyncEvent("p", "validated", 2))
    nt.forge.trace(TraceForgeEvent(5, "forged"))
    snap = reg.snapshot()
    assert snap["node.chainsync.TraceChainSyncEvent"] == 4   # n-weighted
    assert snap["node.forge.TraceForgeEvent"] == 1
    assert "node.fetch.TraceFetchDecision" not in snap


def test_adapter_counting_tee_forwards_and_counts():
    reg = MetricsRegistry()
    inner, evs = collecting()
    t = adapter.counting("fetch", inner, reg)
    ev = TraceFetchDecision("p", 2, 0, "request")
    t.trace(ev)
    assert evs == [ev]                     # event still reaches its sink
    assert reg.snapshot()["node.fetch.TraceFetchDecision"] == 1


def test_precompute_counters_live_in_global_registry():
    """The migrated cache counters are registry instruments AND the old
    attribute names — one source of truth, aliases kept (satellite)."""
    from ouroboros_tpu.crypto.precompute import GLOBAL_PRECOMPUTE_CACHE
    inst = metrics.REGISTRY.get("precompute.hits")
    assert inst is not None
    assert inst is GLOBAL_PRECOMPUTE_CACHE._counters["hits"]
    before = GLOBAL_PRECOMPUTE_CACHE.hits
    GLOBAL_PRECOMPUTE_CACHE.hits += 1      # writeable alias
    try:
        assert inst.value == before + 1
    finally:
        GLOBAL_PRECOMPUTE_CACHE.hits = before


# ---------------------------------------------------------------------------
# ISSUE 36: a span's seconds on the CPU and off it, a thread's CPU time
# ---------------------------------------------------------------------------

def _spin(seconds: float) -> int:
    """Pure Python for `seconds` on the monotonic clock: holds the
    interpreter lock whenever it runs."""
    n, end = 0, time.perf_counter() + seconds
    while time.perf_counter() < end:
        n += 1
    return n


def _off_cpu_counter(name: str) -> int:
    inst = metrics.REGISTRY.get("span.off_cpu_us." + name)
    return inst.value if inst is not None else 0


def _attempts(measure, good, tries: int = 4):
    """`measure()` until `good(reading)`, a few times: the readings are
    of a CPU this process shares with the other test workers."""
    for _ in range(tries):
        got = measure()
        if good(got):
            break
    return got


def test_cpu_span_busy_is_on_the_cpu_and_asleep_is_off_it():
    rec = SpanRecorder(enabled=True)
    assert metrics.REGISTRY.enabled

    def measure():
        c0 = _off_cpu_counter("t36.busy"), _off_cpu_counter("t36.asleep")
        with rec.span("t36.busy", cat="host-seq", cpu=True) as busy:
            _spin(0.05)
        with rec.span("t36.asleep", cat="disk", cpu=True) as asleep:
            time.sleep(0.05)
        return (busy, asleep, _off_cpu_counter("t36.busy") - c0[0],
                _off_cpu_counter("t36.asleep") - c0[1])

    busy, asleep, busy_off_us, asleep_off_us = _attempts(
        measure, lambda r: r[0].cpu > 0.75 * r[0].duration)
    # ratios of the span's own length, never milliseconds
    assert busy.cpu > 0.75 * busy.duration
    assert busy_off_us < 0.25 * busy.duration * 1e6
    assert 0 <= asleep.cpu < 0.25 * asleep.duration
    assert 0.75 * asleep.duration * 1e6 < asleep_off_us \
        <= asleep.duration * 1e6
    inst = metrics.REGISTRY.get("span.off_cpu_us.t36.asleep")
    assert inst.kind == "counter" and not inst.stable and not inst.always
    # bound once a name: the recorder keeps the handle
    assert rec._off_cpu_for("t36.asleep") is inst
    # a span that did not ask reads no CPU clock and feeds no counter
    with rec.span("t36.plain") as plain:
        pass
    assert plain.cpu is None
    assert metrics.REGISTRY.get("span.off_cpu_us.t36.plain") is None


def test_two_spinning_threads_share_one_interpreter_lock():
    """Two threads of pure Python take the lock in turn: each is off the
    CPU for about half of its span, and their CPU seconds add up to
    about the wall time, not to twice it.  This is the reading
    `host_busy_cores` and `host_seq_off_cpu_share` make of a replay."""
    rec = SpanRecorder(enabled=True)

    def measure():
        start = threading.Barrier(2)
        got: dict = {}

        def work(i: int):
            start.wait(10)
            with rec.span(f"t36.spin{i}", cat="host-seq", cpu=True) as sp:
                _spin(0.4)
            got[i] = sp

        c0 = [_off_cpu_counter(f"t36.spin{i}") for i in (0, 1)]
        t0 = time.perf_counter()
        threads = [threading.Thread(target=work, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        wall = time.perf_counter() - t0
        assert not any(t.is_alive() for t in threads)
        off = [(_off_cpu_counter(f"t36.spin{i}") - c0[i]) / 1e6
               for i in (0, 1)]
        return got, off, wall

    def good(r):
        got, off, wall = r
        return all(0.25 < off[i] / got[i].duration < 0.75 for i in (0, 1)) \
            and 0.6 < (got[0].cpu + got[1].cpu) / wall < 1.25

    got, off, wall = _attempts(measure, good)
    for i in (0, 1):
        assert 0.25 < off[i] / got[i].duration < 0.75
        assert 0.25 < got[i].cpu / got[i].duration < 0.75
    assert 0.6 < (got[0].cpu + got[1].cpu) / wall < 1.25


def test_cpu_is_none_under_a_sim_clock_and_on_adopted_rows():
    """A runtime's virtual clock has no CPU clock beside it, and a row
    timed in another process brings none."""
    rec = SpanRecorder(enabled=True)
    c0 = _off_cpu_counter("t36.sim")

    async def main():
        with rec.span("t36.sim", cat="host-seq", cpu=True):
            await sim.sleep(2.5)

    sim.run(main())
    (sp,) = rec.drain()
    assert sp.duration == 2.5 and sp.cpu is None
    assert _off_cpu_counter("t36.sim") == c0 == 0
    parent = Span("stream.decode", "disk", 1.0, "ouro-stream-prefetch")
    parent.t1 = 2.0
    spans.adopt(parent, [("decode.parse", "disk", 0.25, 0.75)],
                thread="ouro-decode-0")
    (row,) = parent.children
    assert row.cpu is None and row.duration == 0.5
    # neither shows CPU fields in the chrome trace; a span that has them
    # shows both beside its meta, and they add up to its length
    doc = export.chrome_trace([sp, parent])
    assert not [e for e in doc["traceEvents"]
                if e["ph"] == "X" and "cpu_ms" in e.get("args", {})]
    timed = Span("window.host_seq", "host-seq", 10.0, "p", {"window": 3})
    timed.t1, timed.cpu = 10.5, 0.125
    (ev,) = [e for e in export.chrome_trace([timed])["traceEvents"]
             if e["ph"] == "X"]
    assert ev["args"] == {"window": 3, "cpu_ms": 125.0, "off_cpu_ms": 375.0}


def test_recording_off_cpu_span_is_the_null_cm_and_no_counter_moves():
    rec = SpanRecorder(enabled=False)
    before = {i.name: i.value for i in metrics.REGISTRY.instruments()
              if i.name.startswith("span.off_cpu_us.")}
    cm = rec.span("t36.off", cat="disk", cpu=True, window=1)
    assert cm is rec.span("other") is spans._NULL
    with cm as nothing:
        time.sleep(0.001)
    assert nothing is None
    was = spans.RECORDER.enabled
    spans.RECORDER.disable()
    try:
        assert spans.span("t36.off", cpu=True) is spans._NULL
    finally:
        spans.RECORDER.enabled = was
    assert metrics.REGISTRY.get("span.off_cpu_us.t36.off") is None
    assert {i.name: i.value for i in metrics.REGISTRY.instruments()
            if i.name.startswith("span.off_cpu_us.")} == before


def test_cpu_span_with_the_registry_off_times_but_counts_nothing():
    """The span still carries `cpu` (recording is the recorder's flag);
    the counter is a gated instrument and the registry's flag drops it."""
    rec = SpanRecorder(enabled=True)
    c0 = _off_cpu_counter("t36.gated")
    was = metrics.REGISTRY.enabled
    metrics.REGISTRY.disable()
    try:
        writes = metrics.REGISTRY.data_writes
        with rec.span("t36.gated", cat="disk", cpu=True) as sp:
            time.sleep(0.002)
        assert metrics.REGISTRY.data_writes == writes
    finally:
        metrics.REGISTRY.enabled = was
    assert sp.cpu is not None and sp.cpu < sp.duration
    assert _off_cpu_counter("t36.gated") == c0


def test_thread_usage_adds_the_calling_threads_cpu_time_once():
    reg = MetricsRegistry()
    cpu_us = reg.counter("t36.cpu_us", stable=False)
    preempts = reg.counter("t36.preempts", stable=False)
    seen: dict = {}

    def work():
        with spans.thread_usage(cpu_us, preempts):
            t0 = time.perf_counter()
            _spin(0.05)
            time.sleep(0.05)
            seen["wall"] = time.perf_counter() - t0

    writes = reg.data_writes
    t = threading.Thread(target=work)
    t.start()
    t.join(30)
    assert not t.is_alive()
    assert reg.data_writes == writes + 2          # one inc each
    assert isinstance(cpu_us.value, int) and isinstance(preempts.value, int)
    # the sleep is not CPU time and the main thread's work is not this
    # thread's: more than nothing, less than the block's length
    assert 0 < cpu_us.value < 0.8 * seen["wall"] * 1e6
    assert preempts.value >= 0
    # an exception passes through and the reading is still made
    with pytest.raises(KeyError):
        with spans.thread_usage(cpu_us, preempts):
            raise KeyError("x")
    assert reg.data_writes == writes + 4


# ---------------------------------------------------------------------------
# ISSUE 8: per-thread span stacks (the pipelined replay's producer and
# consumer record concurrently)
# ---------------------------------------------------------------------------

def test_spans_per_thread_stacks_never_cross_adopt():
    """A producer-thread span overlapping a consumer-thread span in wall
    time is concurrency, not containment: each thread keeps its own open
    stack, completed roots land in the shared list."""
    import threading

    rec = SpanRecorder(enabled=True)
    gate_a = threading.Event()
    gate_b = threading.Event()

    def producer():
        with rec.span("host_seq", cat="host-seq"):
            with rec.span("pack", cat="host-seq"):
                gate_a.set()            # overlap with the consumer span
                gate_b.wait(5)

    t = threading.Thread(target=producer)
    t.start()
    gate_a.wait(5)
    with rec.span("drain", cat="device"):
        pass
    gate_b.set()
    t.join()
    roots = rec.drain()
    by_name = {r.name: r for r in roots}
    assert set(by_name) == {"host_seq", "drain"}
    assert [c.name for c in by_name["host_seq"].children] == ["pack"]
    assert by_name["drain"].children == []      # no cross-thread adoption


def test_spans_concurrent_closes_are_recorded_without_loss():
    """Many threads closing spans concurrently: every root is recorded
    exactly once (the shared roots list is lock-guarded)."""
    import threading

    rec = SpanRecorder(enabled=True, max_roots=10_000)

    def worker(k):
        for i in range(50):
            with rec.span(f"w{k}.{i}", cat="host-seq"):
                pass

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    roots = rec.drain()
    assert len(roots) == 200
    assert len({r.name for r in roots}) == 200
    assert rec.dropped == 0


# ---------------------------------------------------------------------------
# ISSUE 9: log-bucket latency histograms with deterministic quantiles
# ---------------------------------------------------------------------------

def test_latency_buckets_are_log_spaced_and_shared():
    b = metrics.LATENCY_BUCKETS
    assert b[0] == 1e-6
    assert all(b[i + 1] == b[i] * 2 for i in range(len(b) - 1))
    h = metrics.latency_histogram("lat.vocab.probe")
    assert h.buckets == b
    assert not h.stable                    # measured seconds: unstable


def test_histogram_quantiles_exact_and_deterministic():
    reg = MetricsRegistry()
    h = reg.histogram("q", buckets=(1, 2, 4))
    for v in (1, 1, 3, 9):
        h.observe(v)
    # counts [2, 0, 1, overflow 1]; p50 rank=2 -> top of [0,1];
    # p95/p99 fall into the overflow bucket -> the top edge
    assert h.quantile(0.50) == 1.0
    assert h.quantile(0.95) == 4.0
    assert h.quantiles() == {"p50": 1.0, "p95": 4.0, "p99": 4.0}
    # interpolation inside a mid bucket: rank lands in (2,4]
    h2 = reg.histogram("q2", buckets=(1, 2, 4))
    for v in (1, 3, 3, 3):
        h2.observe(v)
    assert h2.quantile(0.5) == pytest.approx(2.0 + 2.0 * (1.0 / 3.0))
    assert reg.histogram("qe", buckets=(1, 2)).quantile(0.5) == 0.0


def test_histogram_quantiles_creation_order_byte_identical():
    """The registry-level determinism contract extends to quantiles:
    same observations, different creation order -> identical snapshot
    bytes AND identical p50/p95/p99 (they are pure functions of the
    counts)."""
    import json as _json

    def build(order):
        reg = MetricsRegistry()
        names = ["lat.a", "lat.b"]
        if order:
            names.reverse()
        for n in names:
            reg.histogram(n, buckets=metrics.LATENCY_BUCKETS)
        for i in range(20):
            reg.histogram("lat.a",
                          buckets=metrics.LATENCY_BUCKETS).observe(
                              0.001 * (i + 1))
            reg.histogram("lat.b",
                          buckets=metrics.LATENCY_BUCKETS).observe(
                              0.01 * (i + 1))
        return reg
    r1, r2 = build(0), build(1)
    assert r1.snapshot_json() == r2.snapshot_json()
    q1 = {n: r1.get(n).quantiles() for n in ("lat.a", "lat.b")}
    q2 = {n: r2.get(n).quantiles() for n in ("lat.a", "lat.b")}
    assert _json.dumps(q1, sort_keys=True) == _json.dumps(q2,
                                                          sort_keys=True)
    assert 0 < q1["lat.a"]["p50"] <= q1["lat.a"]["p95"] \
        <= q1["lat.a"]["p99"]


def test_span_close_feeds_phase_latency_histograms():
    """Every span close records its duration into latency.phase.<cat>
    on the GLOBAL registry — live per-phase quantiles for the scrape
    endpoint without a second instrumentation pass."""
    h = metrics.REGISTRY.get("latency.phase.device")
    before = h.count if h is not None else 0
    rec = SpanRecorder(enabled=True)
    with rec.span("drain", cat="device"):
        pass
    h = metrics.REGISTRY.get("latency.phase.device")
    assert h is not None and h.count == before + 1
    rec.drain()


def test_prom_quantiles_match_local_quantiles():
    """A scraper recomputes the SAME p50/p95/p99 from the cumulative
    exposition buckets that the process reports locally — the
    obsreport --live contract."""
    reg = MetricsRegistry()
    h = reg.histogram("pipe.lat", buckets=metrics.LATENCY_BUCKETS,
                      stable=False)
    for i in range(50):
        h.observe(0.0001 * (i + 1) ** 2)
    parsed = export.parse_prometheus_text(export.prometheus_text(reg))
    got = export.prom_histogram_quantiles(parsed, "ouro_pipe_lat")
    assert got == h.quantiles()
    assert export.prom_histograms(parsed) == {"ouro_pipe_lat": 50.0}


# ---------------------------------------------------------------------------
# ISSUE 9: flight recorder
# ---------------------------------------------------------------------------

def _private_flight(capacity=64):
    from ouroboros_tpu.observe.flight import FlightRecorder
    reg = MetricsRegistry()
    rec = SpanRecorder(enabled=False)
    return FlightRecorder(capacity, registry=reg, recorder=rec), reg, rec


def test_flight_recorder_arm_captures_spans_metrics_events():
    fl, reg, rec = _private_flight()
    c = reg.counter("f.count")
    c.inc()                                # before arming: not recorded
    fl.arm()
    assert rec.enabled                     # arming forces spans on
    with rec.span("w", cat="device"):
        pass
    c.inc(2)
    fl.note(TraceForgeEvent(slot=3, outcome="forged"))
    kinds = [e[1] for e in fl.entries()]
    assert kinds.count("span") == 1
    assert kinds.count("event") == 1
    assert ("f.count" in {e[2] for e in fl.entries()
                          if e[1] == "metric"})
    fl.disarm()
    n = len(fl)
    c.inc()
    assert len(fl) == n                    # disarmed: hook detached
    assert not rec.enabled                 # prior recorder state restored


def test_same_cat_nested_span_records_one_phase_sample():
    """The pipeline's outer "pipeline.drain" wraps JaxBackend's inner
    "window.drain" (both cat=device): ONE wait, ONE histogram sample —
    a same-cat child must not double the latency.phase.device count."""
    h = metrics.REGISTRY.histogram("latency.phase.device",
                                   buckets=metrics.LATENCY_BUCKETS,
                                   stable=False)
    before = h.count
    rec = SpanRecorder(enabled=True)
    with rec.span("pipeline.drain", cat="device"):
        with rec.span("window.drain", cat="device"):
            pass
    assert h.count == before + 1
    # a different-cat child still records under its own phase
    hc = metrics.REGISTRY.get("latency.phase.compile")
    before_c = hc.count if hc is not None else 0
    with rec.span("window.submit", cat="dispatch"):
        with rec.span("composite", cat="compile"):
            pass
    assert metrics.REGISTRY.get("latency.phase.compile").count \
        == before_c + 1
    rec.drain()


def test_flight_arm_is_reentrant_and_note_takes_explicit_time():
    """Nested arm()s must not clobber the saved recorder state (the
    outer disarm restores the TRUE pre-arm state), and note(t=...)
    keeps an event's own clock reading — the post-mortem sim-trace-tail
    path stamps virtual time, not the wall clock of the dump."""
    fl, _reg, rec = _private_flight()
    assert not rec.enabled
    fl.arm()
    fl.arm()                               # reentrant arm
    fl.disarm()
    assert not rec.enabled                 # original state restored
    fl.arm()
    fl.note(("late", 1), t=3.5)
    (entry,) = fl.entries()
    assert entry[0] == 3.5 and entry[1] == "event"
    assert fl._record(entry)["t"] == 3.5
    fl.disarm()


def test_flight_ring_is_bounded():
    fl, reg, rec = _private_flight(capacity=8)
    fl.arm()
    c = reg.counter("f.many")
    for _ in range(50):
        c.inc()
    assert len(fl) == 8
    fl.disarm()


def test_flight_dump_golden_and_byte_identical_replay(tmp_path,
                                                      monkeypatch):
    """A seeded sim failure dumps byte-identical flight files on every
    replay — virtual timestamps only.  Golden regen:
    OURO_REGEN_GOLDEN=1 pytest tests/test_observe.py"""
    # a span line names its thread: whatever runs this test is the main
    # thread of the golden bytes
    monkeypatch.setattr(threading.current_thread(), "name", "MainThread")

    def one_run(d):
        fl, reg, rec = _private_flight()
        fl.arm()

        async def main():
            with rec.span("window.host_seq", cat="host-seq"):
                await sim.sleep(1.5)
            reg.counter("replay.windows").inc()
            with rec.span("pipeline.drain", cat="device", window=0):
                await sim.sleep(0.25)
            fl.note(TraceForgeEvent(slot=7, outcome="error"))

        sim.run(main())
        out = fl.dump(str(d), reason="forced failure (test)")
        fl.disarm()
        return out

    out1 = one_run(tmp_path / "a")
    out2 = one_run(tmp_path / "b")
    with open(out1["jsonl"]) as f:
        text1 = f.read()
    with open(out2["jsonl"]) as f:
        assert f.read() == text1           # byte-identical replay
    with open(out1["trace"]) as f:
        assert f.read() == open(out2["trace"]).read()
    _check_golden("flight.jsonl", text1)
    # the chrome dump loads as a trace_event document
    doc = json.load(open(out1["trace"]))
    events = {e["name"]: e for e in doc["traceEvents"]
              if e.get("ph") == "X"}
    assert set(events) == {"window.host_seq", "pipeline.drain"}
    # the dump keeps who opened a span and which window it was
    assert _rows(doc) == {1: "MainThread"}
    assert events["pipeline.drain"]["args"] == {"window": 0}
    spans_l = [json.loads(ln) for ln in text1.splitlines()[1:]
               if json.loads(ln)["kind"] == "span"]
    assert [(ln["name"], ln["thread"], ln.get("args")) for ln in spans_l] \
        == [("window.host_seq", "MainThread", None),
            ("pipeline.drain", "MainThread", {"window": 0})]
    # header line carries the reason + count
    head = json.loads(text1.splitlines()[0])
    assert head["kind"] == "flight" and "forced failure" in head["reason"]
    assert head["entries"] == len(text1.splitlines()) - 1


def test_flight_dump_on_failure_noop_unless_armed(tmp_path, monkeypatch):
    fl, _reg, _rec = _private_flight()
    monkeypatch.setenv("OURO_FLIGHT_DIR", str(tmp_path / "fr"))
    assert fl.dump_on_failure("boom") is None
    fl.arm()
    out = fl.dump_on_failure("boom")
    assert out is not None and os.path.exists(out["jsonl"])
    assert out["dir"] == str(tmp_path / "fr")
    fl.disarm()
