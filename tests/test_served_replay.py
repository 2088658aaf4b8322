"""The served replay's tier-1 guard: one small Shelley chain through the
one-chip device path end to end, held to the `cpp` reference.

`JaxBackend(min_bucket=16, use_pallas=False, autotune=False)` replays an
8-block empty-body depth-4-KES chain through `replay_blocks_pipelined`
(threaded producer, `fold=True` device verdict), verifies an
eleven-request mixed batch with one corruption of every primitive, runs the same batch
with observation off, is scraped over the sim transport, and replays the
chain again FROM DISK through `StreamingReplayEngine` with a resumed
reopen.  One module fixture does all of it once, in that order, and
records what happened; each test reads one property, so a failure names
what broke.  Run it before a chip session: a parity loss costs nothing
here.

Every step stays on the ONE window-composite shape the first replay
compiles (minutes of XLA:CPU cold, seconds from the compile cache).
The fixture starts from cold key caches and clears the KES hash-path
cache before each later step, so every step packs real KES jobs (a
batch whose paths are warm would ride the same program with an empty
KES part since PR 33: `JaxBackend._occasional_widths`).
"""
import hashlib
import os
import shutil
import subprocess
import sys
import threading
import types

import pytest

pytest.importorskip("jax")

from ouroboros_tpu import observe                               # noqa: E402
from ouroboros_tpu import simharness as sim                     # noqa: E402
from ouroboros_tpu.consensus.batch import (                     # noqa: E402
    replay_blocks_pipelined,
)
from ouroboros_tpu.crypto import ed25519_ref, kes, vrf_ref      # noqa: E402
from ouroboros_tpu.crypto.backend import (                      # noqa: E402
    GLOBAL_BETA_CACHE, CpuRefBackend, Ed25519Req, KesReq, VrfReq,
    WindowVerdict,
)
from ouroboros_tpu.crypto.jax_backend import JaxBackend         # noqa: E402
from ouroboros_tpu.crypto.precompute import (                   # noqa: E402
    GLOBAL_PRECOMPUTE_CACHE,
)
from ouroboros_tpu.network.snocket import SimSnocket            # noqa: E402
from ouroboros_tpu.observe import export                        # noqa: E402
from ouroboros_tpu.observe.scrape import (                      # noqa: E402
    PeriodicEmitter, ScrapeServer, scrape,
)
from ouroboros_tpu.storage import (                             # noqa: E402
    DiskPolicy, IoFS, StreamConfig, StreamingReplayEngine,
)
from ouroboros_tpu.storage.stream import (                      # noqa: E402
    prefetcher_threads_alive,
)
from tools import db_analyser as dba                            # noqa: E402

pytestmark = pytest.mark.device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# empty bodies, depth-4 KES and one window of 8 keep every device bucket
# at min_bucket 16: one composite shape for the whole file
BLOCKS = WINDOW = 8
KES_DEPTH = 4
HEADER_PROOFS = 4            # 2 VRF proofs, 1 KES signature, 1 OCert
SUBMIT_DRAIN = "ouro_pipeline_submit_drain_secs"

# the mixed batch, in request order: what each request is
MIXED = ("ed25519-good", "ed25519-bad-signature", "vrf-good",
         "vrf-wrong-alpha", "kes-good", "kes-tampered-merkle-node",
         "kes-wrong-period", "kes-truncated-bytes", "kes-evolved-period-1",
         "kes-evolved-period-2", "kes-evolved-period-3")


def _mixed_requests() -> list:
    sk = hashlib.sha256(b"smoke-ed").digest()
    vk = ed25519_ref.public_key(sk)
    vsk = hashlib.sha256(b"smoke-vrf").digest()
    vvk = vrf_ref.public_key(vsk)
    ksk = kes.KesSignKey(KES_DEPTH, hashlib.sha256(b"smoke-kes").digest())
    kvk = ksk.verification_key
    good = ksk.sign(b"kmsg")
    tampered = kes.KesSig(
        good.leaf_sig, ((good.merkle[0][0], bytes(32)),) + good.merkle[1:])
    reqs = [Ed25519Req(vk, b"m0", ed25519_ref.sign(sk, b"m0")),
            Ed25519Req(vk, b"bad", ed25519_ref.sign(sk, b"good")),
            VrfReq(vvk, b"a0", vrf_ref.prove(vsk, b"a0")),
            VrfReq(vvk, b"bad-alpha", vrf_ref.prove(vsk, b"a1")),
            KesReq(KES_DEPTH, kvk, 0, b"kmsg", good.to_bytes()),
            KesReq(KES_DEPTH, kvk, 0, b"kmsg", tampered.to_bytes()),
            KesReq(KES_DEPTH, kvk, 1, b"kmsg", good.to_bytes()),
            KesReq(KES_DEPTH, kvk, 0, b"kmsg", b"\x00" * 7)]
    # three evolved periods: with the good and the tampered path, 5
    # distinct depth-4 hash paths = 20 Blake2b jobs, the KES bucket (32)
    # of the replay's window (7 paths).  Two periods make 16 jobs, a
    # bucket of 16: a second composite shape
    for period in (1, 2, 3):
        ksk.evolve()
        msg = b"p%d" % period
        reqs.append(KesReq(KES_DEPTH, kvk, period, msg,
                           ksk.sign(msg).to_bytes()))
    assert len(reqs) == len(MIXED)
    return reqs


def _producers() -> tuple:
    """(started, finished, alive) of the replay's producer thread."""
    started = observe.metrics.counter("pipeline.producers_started",
                                      always=True).value
    finished = observe.metrics.counter("pipeline.producers_finished",
                                       always=True).value
    alive = sum(t.name == "ouro-replay-producer" and t.is_alive()
                for t in threading.enumerate())
    return started, finished, alive


def _scrape_in_sim():
    """Serve the process registry over the sim transport, scrape it
    once, let the emitter tick twice, stop both; (text, emitted,
    leaked sim threads)."""
    emitted = []

    async def main():
        sn = SimSnocket()
        srv = await ScrapeServer(sn, "metrics").start()
        em = await PeriodicEmitter(1.0, emitted.append).start()
        text = await scrape(sn, "metrics")
        await sim.sleep(2.5)
        await srv.stop()
        await em.stop()
        return text

    text, trace = sim.run_trace(main())
    return text, emitted, sim.leaked_threads(trace)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Everything the file asserts on, gathered in ONE pass whose order
    is part of the contract (see the module docstring).  It records and
    does not judge: the tests do."""
    out = types.SimpleNamespace()
    d = str(tmp_path_factory.mktemp("served") / "chain")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", d, "--protocol", "shelley", "--blocks", str(BLOCKS),
         "--txs-per-block", "0", "--epoch-length", "500", "--pools", "2",
         "--f", "4/5", "--kes-depth", str(KES_DEPTH)],
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    db, rules, decode, _cfg = dba.load_db(d)
    blocks = [decode(raw) for _entry, raw in db.stream()]
    out.blocks = len(blocks)
    GLOBAL_PRECOMPUTE_CACHE.clear()

    # 1. the reference, then the device through the threaded driver with
    #    the fold verdict, spans recording
    reference = dba.make_backend("cpp" if shutil.which("g++") else "openssl")
    GLOBAL_BETA_CACHE.clear()
    out.ref = replay_blocks_pipelined(rules, blocks, rules.initial_state(),
                                      backend=reference, window=WINDOW)
    jb = JaxBackend(min_bucket=16, use_pallas=False, autotune=False)
    fills0 = GLOBAL_PRECOMPUTE_CACHE.device_fills
    started0 = _producers()[0]
    GLOBAL_BETA_CACHE.clear()
    rec = observe.spans.RECORDER
    rec.enable()
    try:
        rec.drain()
        out.dev = replay_blocks_pipelined(rules, blocks,
                                          rules.initial_state(),
                                          backend=jb, window=WINDOW)
        out.roots = rec.drain()
    finally:
        rec.disable()
    out.padding = jb.padding_stats()
    started, finished, alive = _producers()
    out.producers_run = started - started0
    out.producers_unfinished = started - finished
    out.producers_alive = alive
    out.replay_fills = GLOBAL_PRECOMPUTE_CACHE.device_fills - fills0

    # 2. the mixed batch.  Fold mode FIRST, while the KES paths are
    #    cold: it then has the replay's (nv, nb, nk) shape, as the
    #    cold vector batch after it has once the paths are cold again
    reqs = _mixed_requests()
    out.want = CpuRefBackend().verify_mixed(reqs)
    out.fold, _betas = jb.finish_window(jb.submit_window(reqs, fold=True))
    GLOBAL_PRECOMPUTE_CACHE._kes.clear()
    out.got = jb.verify_mixed(reqs)
    # warm, without another composite dispatch: the host split and the
    # table assembly must serve everything from the cache
    fills = GLOBAL_PRECOMPUTE_CACHE.device_fills
    (eds, _eo, _vrfs, _vo, kes_msgs, _ex, checks, _n) = \
        jb._split_mixed_device(reqs)
    GLOBAL_PRECOMPUTE_CACHE.assemble(
        [q.vk for q in reqs if not isinstance(q, KesReq)]
        + [e.vk for e in eds])
    out.warm_fills = GLOBAL_PRECOMPUTE_CACHE.device_fills - fills
    out.warm_kes_jobs, out.warm_kes_checks = len(kes_msgs), len(checks)

    # 3. observation off: the same batch, cold again (the compiled shape,
    #    and more instrumented seams than a warm one crosses)
    GLOBAL_PRECOMPUTE_CACHE._kes.clear()
    reg = observe.metrics.registry()
    was_enabled = reg.enabled
    reg.disable()                 # the recorder is off since step 1
    try:
        writes0, roots0 = reg.data_writes, len(rec.roots)
        out.unobserved = jb.verify_mixed(reqs)
        with observe.span("probe", cat="sync"):
            pass
        out.disabled_writes = reg.data_writes - writes0
        out.disabled_spans = len(rec.roots) - roots0
    finally:
        reg.enabled = was_enabled

    # 4. the simple-batch VRF path (the fold-form program, not the
    #    window composite): eight proofs, one with a wrong alpha
    vsk = hashlib.sha256(b"smoke-spread").digest()
    vvk = vrf_ref.public_key(vsk)
    vrf_reqs = [VrfReq(vvk, b"s%d" % i, vrf_ref.prove(vsk, b"s%d" % i))
                for i in range(8)]
    vrf_reqs[5] = VrfReq(vvk, b"not-s5", vrf_reqs[5].proof)
    out.vrf_want = CpuRefBackend().verify_vrf_batch(vrf_reqs)
    out.vrf_got = jb.verify_vrf_batch(vrf_reqs)

    # 5. the scrape endpoint after a real replay
    out.scrape_text, out.emitted, out.sim_leaked = _scrape_in_sim()

    # 6. the chain from disk, then a resumed reopen; window and cold KES
    #    paths as in step 1, so the compiled shape serves
    policy = DiskPolicy(num_snapshots=2, snapshot_interval_slots=4)
    fs = IoFS(d)

    def from_disk(resume: bool):
        GLOBAL_BETA_CACHE.clear()
        return StreamingReplayEngine(
            fs, db, rules, decode, backend=jb,
            config=StreamConfig(window=WINDOW, read_ahead=2, policy=policy,
                                resume=resume)).replay()

    GLOBAL_PRECOMPUTE_CACHE._kes.clear()
    out.streamed = from_disk(resume=False)
    out.resumed = from_disk(resume=True)
    started, finished, alive = _producers()
    out.stream_threads = (prefetcher_threads_alive() + alive
                          + (started - finished))
    out.composites = sorted(jb._composites)
    return out


def _state_hash(res):
    assert res.all_valid, res.error
    return res.final_state.ledger.state_hash()


# -- the replay ---------------------------------------------------------------

def test_device_replay_state_hash_equals_reference(served):
    assert _state_hash(served.dev) == _state_hash(served.ref)


def test_device_replay_counts_the_reference_blocks_and_proofs(served):
    assert served.dev.n_valid == served.ref.n_valid == served.blocks == BLOCKS
    # one window; its lanes carry the header's four proofs a block (the
    # OCert and the KES leaf signature, two VRF proofs) and the Blake2b
    # jobs of the cold KES hash paths
    assert served.padding["windows"] == 1
    assert served.padding["lanes_used"] >= HEADER_PROOFS * BLOCKS


def test_every_step_ran_the_one_composite_shape(served):
    """The replay, both mixed batches, the unobserved batch and the
    streamed replay: 16 VRF lanes, no betas, 32 KES jobs (the 16
    Ed25519 lanes are one call of the tile program and no part of the
    key).  A second composite is minutes of XLA:CPU compile."""
    assert served.composites == [(16, 0, 32)]


def test_producer_ran_and_is_gone(served):
    assert served.producers_run >= 1
    assert served.producers_unfinished == 0
    assert served.producers_alive == 0


def test_replay_needs_at_most_three_fill_dispatches(served):
    """Two pools, so a handful of keys: one fill dispatch per prep path
    (Ed25519 window, VRF window, betas); more means a key was filled
    twice."""
    assert served.replay_fills <= 3


def test_host_pass_recorded_on_the_producer_thread(served):
    host = [sp for root in served.roots for sp in root.walk()
            if sp.name == "window.host_seq"]
    assert host and sum(sp.duration for sp in host) > 0
    assert {sp.thread for sp in host} == {"ouro-replay-producer"}
    drains = [sp for root in served.roots for sp in root.walk()
              if sp.name == "pipeline.drain"]
    assert drains and drains[0].thread != "ouro-replay-producer"
    assert host[0].t1 <= drains[0].t1


# -- the mixed batch ----------------------------------------------------------

@pytest.mark.parametrize("ix,what", list(enumerate(MIXED)),
                         ids=list(MIXED))
def test_mixed_verdict_equals_reference(served, ix, what):
    assert served.want[ix] is ("good" in what or "evolved" in what), \
        "the reference itself misjudges the fixture"
    assert served.got[ix] == served.want[ix]


def test_fold_verdict_names_the_first_bad_request(served):
    assert isinstance(served.fold, WindowVerdict)
    assert served.fold.first_bad == served.want.index(False) == 1


def test_warm_batch_needs_no_fill_and_no_kes_hashing(served):
    assert served.warm_fills == 0
    assert served.warm_kes_jobs == 0
    assert served.warm_kes_checks == 0


def test_observation_off_writes_nothing(served):
    assert served.unobserved == served.want
    assert served.disabled_writes == 0
    assert served.disabled_spans == 0


def test_vrf_batch_fold_form_equals_reference(served):
    assert served.vrf_want == [i != 5 for i in range(8)]
    assert served.vrf_got == served.vrf_want


# -- scrape -------------------------------------------------------------------

def test_scrape_returns_the_replays_submit_drain_quantiles(served):
    parsed = export.parse_prometheus_text(served.scrape_text)
    assert parsed.get(SUBMIT_DRAIN + "_count", 0) > 0
    q = export.prom_histogram_quantiles(parsed, SUBMIT_DRAIN)
    assert 0 < q["p50"] <= q["p95"] <= q["p99"]
    assert len(served.emitted) >= 2
    assert not served.sim_leaked


# -- from disk ----------------------------------------------------------------

def test_streamed_replay_state_hash_equals_reference(served):
    assert _state_hash(served.streamed) == _state_hash(served.ref)
    assert served.streamed.n_valid == BLOCKS


def test_streamed_replay_read_chunks_and_wrote_a_snapshot(served):
    assert served.streamed.stats["chunks_read"] >= 1
    assert served.streamed.stats["snapshots_written"] >= 1


def test_resumed_reopen_replays_nothing_to_the_same_hash(served):
    assert served.resumed.n_valid == 0
    assert served.resumed.stats["resumed_from_slot"] is not None
    assert _state_hash(served.resumed) == _state_hash(served.ref)


def test_streamed_replay_leaves_no_thread(served):
    assert served.stream_threads == 0
