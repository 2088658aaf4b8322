"""Network-layer observability (ISSUE 14): bounded labels, per-peer mux
accounting, DeltaQ gauges, block-propagation timelines, and the
fleet-telemetry report of a seeded chaos threadnet.

Acceptance gates covered here:

- a seeded 10-node chaos run emits a fleet report with
  time-to-95%-adoption quantiles and per-peer mux byte accounting,
  byte-identical across two replays of the same seed;
- mux byte accounting matches the traffic a test injects exactly on a
  fault-free link;
- with observation disabled the mux hot path performs zero per-peer
  instrument writes and zero label formats;
- the scrape endpoint sheds fault-injected connections without leaking
  handlers or stalling the PeriodicEmitter.
"""
import json

import pytest

from ouroboros_tpu import simharness as sim
from ouroboros_tpu.network.deltaq import PeerGSVTracker
from ouroboros_tpu.network.mux import INITIATOR, Mux, RESPONDER, \
    bearer_pair
from ouroboros_tpu.observe import export, metrics as om
from ouroboros_tpu.observe import netmetrics as net
from ouroboros_tpu.observe.propagation import (
    FleetTelemetry, PropagationTracker,
)
from ouroboros_tpu.simharness import FaultPlan, FaultSpec, Partition
from ouroboros_tpu.testing import (
    ChaosConfig, ThreadNetConfig, run_chaos_threadnet,
)


@pytest.fixture(autouse=True)
def _observation_on():
    """These tests are about what ENABLED observation records; restore
    whatever state the suite was in afterwards."""
    was = om.REGISTRY.enabled
    om.REGISTRY.enable()
    yield
    om.REGISTRY.enabled = was


# ---------------------------------------------------------------------------
# bounded labels
# ---------------------------------------------------------------------------

def test_bounded_labels_cap_and_overflow():
    dom = net.BoundedLabels(cap=3)
    labels = [dom.get(f"peer{i}") for i in range(3)]
    assert labels == ["peer0", "peer1", "peer2"]
    # at capacity a NEW value collapses into the overflow bucket...
    assert dom.get("peer3") == net.OVERFLOW_LABEL
    assert dom.overflows == 1
    # ...while admitted values keep their own label forever (no
    # eviction: an evicted-then-readmitted value would mint a second
    # registry series)
    assert dom.get("peer0") == "peer0"
    assert len(dom) == 3


def test_label_values_sanitised():
    dom = net.BoundedLabels(cap=4)
    assert dom.get('a"b\\c d{e}') == "a_b_c_d_e_"


def test_labeled_series_render_as_prometheus_labels():
    reg = om.MetricsRegistry()
    c = net.labeled_counter("net.mux.ingress_bytes", reg=reg,
                            peer="node0->node1", proto="2")
    c.inc(100)
    net.labeled_counter("net.mux.ingress_bytes", reg=reg,
                        peer="node0->node2", proto="2").inc(7)
    net.labeled_gauge("net.deltaq.g_secs", reg=reg,
                      peer="node0->node1").set(0.05)
    text = export.prometheus_text(reg)
    parsed = export.parse_prometheus_text(text)
    assert parsed[
        'ouro_net_mux_ingress_bytes{peer="node0->node1",proto="2"}'] \
        == 100
    assert parsed[
        'ouro_net_mux_ingress_bytes{peer="node0->node2",proto="2"}'] == 7
    assert parsed['ouro_net_deltaq_g_secs{peer="node0->node1"}'] == 0.05
    # ONE TYPE line per base metric: a real Prometheus parser rejects a
    # duplicate TYPE line, so labeled series of one base must share it
    assert text.count(
        "# TYPE ouro_net_mux_ingress_bytes counter") == 1
    # labeled series are live-exposition data, never the deterministic
    # snapshot
    assert reg.snapshot() == {}


# ---------------------------------------------------------------------------
# mux per-peer accounting
# ---------------------------------------------------------------------------

def _pump(n_bytes=4096, sdu_size=1024, num=2):
    """One mux pair moving `n_bytes` a->b on protocol `num`; returns
    (mux_a, mux_b)."""
    out = {}

    async def main():
        ba, bb = bearer_pair(sdu_size=sdu_size)
        ma, mb = Mux(ba, "A->B.mux-i"), Mux(bb, "A->B.mux-r")
        ma.start()
        mb.start()
        cha = ma.channel(num, INITIATOR)
        chb = mb.channel(num, RESPONDER)
        await cha.send(b"x" * n_bytes)
        got = b""
        while len(got) < n_bytes:
            got += await chb.recv()
        out["muxes"] = (ma, mb)
        ma.stop()
        mb.stop()
        return len(got)

    assert sim.run(main(), seed=1) == n_bytes
    return out["muxes"]


def test_mux_accounting_matches_injected_traffic():
    """On a fault-free link the accounting is EXACT: egress payload
    bytes on the sender equal the bytes the test injected, ingress on
    the receiver equals delivery, SDU counts match the sdu_size split."""
    net.reset_run_scope()
    ma, mb = _pump(n_bytes=4096, sdu_size=1024, num=2)
    assert ma._io is not None and mb._io is not None
    assert ma._io.egress_bytes == {2: 4096}
    assert ma._io.egress_sdus == {2: 4}
    assert mb._io.ingress_bytes == {2: 4096}
    assert mb._io.ingress_sdus == {2: 4}
    # the fleet aggregation view folds the same numbers per (edge, side)
    acct = net.mux_accounting()
    assert acct["A->B|i"]["egress_bytes"] == 4096
    assert acct["A->B|r"]["ingress_bytes"] == 4096
    assert acct["A->B|r"]["by_proto"]["2"]["in_sdus"] == 4
    # and the registry carries the labeled series
    c = om.REGISTRY.get(
        'net.mux.egress_bytes{peer="A->B",proto="2",side="i"}')
    assert c is not None and c.value >= 4096


def test_mux_disabled_observation_is_free():
    """With the registry disabled the mux hot path performs zero gated
    writes, zero label formats, and never builds the accounting object
    (the served replay's counterpart is test_served_replay.py::
    test_observation_off_writes_nothing)."""
    om.REGISTRY.disable()
    writes0 = om.REGISTRY.data_writes
    formats0 = net.LABEL_FORMATS.value
    ma, mb = _pump()
    assert ma._io is None and mb._io is None
    assert om.REGISTRY.data_writes == writes0
    assert net.LABEL_FORMATS.value == formats0


def test_redials_of_one_edge_aggregate():
    """Connection tags carry a #seq per redial; the accounting folds
    them into ONE edge (bounded series under churn)."""
    net.reset_run_scope()
    io1 = net.MuxIO("node0->node1#1.mux-i")
    io2 = net.MuxIO("node0->node1#2.mux-i")
    io1.egress(2, 100)
    io2.egress(2, 50)
    acct = net.mux_accounting()
    assert list(acct) == ["node0->node1|i"]
    assert acct["node0->node1|i"]["egress_bytes"] == 150


# ---------------------------------------------------------------------------
# DeltaQ gauges + RTT histogram
# ---------------------------------------------------------------------------

def test_gsv_tracker_publishes_labeled_gauges():
    tr = PeerGSVTracker(label="gsvtest->peer")
    tr.observe_rtt(0.1)
    g = om.REGISTRY.get('net.deltaq.g_secs{peer="gsvtest->peer"}')
    assert g is not None and g.value == 0.05
    tr.observe_owd(0.02, 8192)
    assert g.value == 0.02            # min-tracked inbound G updated
    v = om.REGISTRY.get('net.deltaq.v_secs{peer="gsvtest->peer"}')
    assert v is not None
    # the keepalive RTT histogram saw the probe
    h = om.REGISTRY.get("net.rtt.keepalive_secs")
    assert h is not None and h.count >= 1


def test_gsv_tracker_unlabelled_publishes_nothing():
    before = len(om.REGISTRY._instruments)
    tr = PeerGSVTracker()
    tr.observe_rtt(0.1)
    gauges = [n for n in om.REGISTRY._instruments
              if n.startswith("net.deltaq.") and "{" in n
              and "unlabelled" in n]
    assert gauges == []
    assert tr._gauges is None
    assert len(om.REGISTRY._instruments) == before


# ---------------------------------------------------------------------------
# propagation timelines
# ---------------------------------------------------------------------------

def test_propagation_tracker_records_first_stage_times():
    from ouroboros_tpu.utils.tracer import collecting
    tracer, events = collecting()

    async def main():
        tr = PropagationTracker(node="n0", cap=8, tracer=tracer)
        h = b"\x01" * 32
        assert tr.mark("header_seen", h, peer="n0->n1")
        await sim.sleep(0.5)
        assert tr.mark("fetch_decided", h, peer="n0->n1")
        await sim.sleep(0.25)
        assert tr.mark("body_arrived", h, peer="n0->n1")
        await sim.sleep(0.25)
        assert tr.mark("adopted", h)
        # duplicates are ignored: header_seen is FIRST-header-seen
        assert not tr.mark("header_seen", h, peer="n0->n2")
        return tr

    tr = sim.run(main(), seed=1)
    h = b"\x01" * 32
    assert tr.stage_time(h, "header_seen") == 0.0
    assert tr.stage_time(h, "fetch_decided") == 0.5
    assert tr.stage_time(h, "adopted") == 1.0
    assert tr.stage_peer(h, "header_seen") == "n0->n1"
    hist = om.REGISTRY.get("net.propagation.header_to_adopted_secs")
    assert hist is not None and hist.count >= 1
    # every mark emitted one TYPED event (duplicates emitted none), at
    # the exact virtual time, rendering through the JSONL schema
    assert [(e.stage, e.t) for e in events] == [
        ("header_seen", 0.0), ("fetch_decided", 0.5),
        ("body_arrived", 0.75), ("adopted", 1.0)]
    line = export.events_jsonl(events[:1])
    assert line.startswith('{"type":"TraceBlockPropagation"')
    assert '"node":"n0"' in line


def test_propagation_tracker_is_bounded():
    tr = PropagationTracker(node="n0", cap=2)
    for i in range(4):
        tr.mark("header_seen", bytes([i]) * 32, t=float(i))
    assert len(tr.timeline) == 2
    assert bytes([3]) * 32 in tr.timeline      # newest kept


def test_fleet_edge_latency_and_partition_healing():
    """Synthetic two-node fleet: delivery latency is the receiver's
    first-header-seen minus the sender's adoption, and a partition
    heals at the first cross-group delivery after its window."""
    fleet = FleetTelemetry(partitions=(
        Partition(1.2, 1.4, (("A",), ("B",))),))
    h = b"\x07" * 32
    ta = fleet.tracker("A")
    tb = fleet.tracker("B")
    ta.mark("adopted", h, t=1.0)
    tb.mark("header_seen", h, peer="B->A", t=1.5)   # receiver->sender
    tb.mark("adopted", h, t=1.6)
    rep = fleet.report()
    assert rep["per_edge_delivery"]["A->B"]["p50"] == 0.5
    assert rep["partitions"][0]["healed_after_secs"] == \
        pytest.approx(0.1)
    # both nodes adopted: time_to_95 over 2 nodes = second adoption
    assert rep["adoption"]["per_block"][0]["to_95"] == \
        pytest.approx(0.6)


# ---------------------------------------------------------------------------
# the acceptance gate: a seeded 10-node chaos fleet
# ---------------------------------------------------------------------------

def _fleet_config(seed: int = 7) -> ChaosConfig:
    half = tuple(f"node{i}" for i in range(5))
    other = tuple(f"node{i}" for i in range(5, 10))
    return ChaosConfig(
        net=ThreadNetConfig(n_nodes=10, n_slots=8, k=10, f=0.5,
                            seed=seed, topology="ring"),
        spec=FaultSpec(jitter=0.04, drop_prob=0.01),
        partitions=(Partition(3.0, 5.0, (half, other)),),
        settle_slots=6, error_scale=0.5)


def test_ten_node_chaos_fleet_report_and_replay_identity():
    cfg = _fleet_config()
    r1 = run_chaos_threadnet(cfg)
    assert not r1.failures, r1.failures
    fleet = r1.fleet
    assert fleet is not None and fleet["nodes"] == \
        [f"node{i}" for i in range(10)]

    # time-to-adoption quantiles are present and sane
    ad = fleet["adoption"]
    assert ad["blocks"] > 0
    assert ad["time_to_50"]["n"] > 0
    assert ad["time_to_95"]["n"] > 0
    assert 0 < ad["time_to_95"]["p50"]
    assert ad["time_to_50"]["p50"] <= ad["time_to_95"]["p50"]

    # per-peer mux accounting exists for the ring's edges, and drops
    # can only LOSE bytes: fleet-wide ingress never exceeds egress
    mux = fleet["mux"]
    assert mux
    assert sum(m["ingress_bytes"] for m in mux.values()) <= \
        sum(m["egress_bytes"] for m in mux.values())
    assert any(m["egress_bytes"] > 0 for m in mux.values())

    # headers crossed real edges
    assert fleet["per_edge_delivery"]

    # byte-identical across a replay of the same seed
    r2 = run_chaos_threadnet(cfg)
    assert json.dumps(r1.fleet, sort_keys=True) == \
        json.dumps(r2.fleet, sort_keys=True)


# ---------------------------------------------------------------------------
# scrape endpoint under fault injection (ISSUE 14 satellite)
# ---------------------------------------------------------------------------

def test_scrape_sheds_faulted_connections_without_leaks():
    """Fault-injected scrapers (drops/stalls/disconnects on the request
    direction) must not leak connection handlers or stall the
    PeriodicEmitter: stop() cancel-joins every handler parked on a
    request that never arrived, and the emitter keeps its cadence
    throughout."""
    from ouroboros_tpu.network.mux import SDU
    from ouroboros_tpu.network.snocket import SimSnocket
    from ouroboros_tpu.observe.scrape import (
        PeriodicEmitter, SCRAPE_PROTOCOL_NUM, SCRAPE_REQUEST,
        ScrapeServer,
    )

    plan = FaultPlan(seed=3, spec=FaultSpec(
        drop_prob=0.4, stall_prob=0.2, stall_for=0.5,
        disconnect_prob=0.2))
    emitted = []

    async def scrape_over(bearer):
        await bearer.write(SDU(0, 0, SCRAPE_PROTOCOL_NUM,
                               SCRAPE_REQUEST))
        chunks = []
        while True:
            sdu = await bearer.read()
            if not sdu.payload:
                break
            chunks.append(sdu.payload)
        return b"".join(chunks).decode()

    async def main():
        sn = SimSnocket()
        srv = await ScrapeServer(sn, "metrics").start()
        em = await PeriodicEmitter(0.5, emitted.append).start()
        outcomes = []
        for i in range(6):
            bearer = await sn.connect("metrics")
            faulty = plan.wrap_bearer(bearer, f"scraper{i}", "server")
            try:
                done, text = await sim.timeout(2.0, scrape_over(faulty))
                outcomes.append(bool(done and text))
            except ConnectionError:
                outcomes.append(False)
        await sim.sleep(1.0)
        await srv.stop()
        await em.stop()
        return outcomes

    outcomes, trace = sim.run_trace(main(), seed=3)
    # the hostile run injected real faults AND the server survived them
    assert plan.events, "fault plan injected nothing"
    assert len(outcomes) == 6
    # no leaked sim threads: every connection handler the server forked
    # for a silent/dead scraper was cancel-joined by stop()
    leaked = sim.leaked_threads(trace)
    assert not leaked, f"leaked sim threads: {leaked}"
    # the emitter never stalled: >= 6 sim-seconds of hostile scraping
    # at 0.5s cadence
    assert len(emitted) >= 6
