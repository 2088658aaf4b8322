"""Pipelined replay driver (consensus/batch.py replay_blocks_pipelined):
window-async verification with beta carry, vs the synchronous driver.

Reference semantics being preserved: the LgrDB/db-analyser replay fold
(OnDisk.hs:277) — any invalid block aborts with its index.
"""
from fractions import Fraction

import pytest

from ouroboros_tpu.consensus.batch import (
    replay_blocks_pipelined, validate_blocks_batched,
)
from ouroboros_tpu.consensus.headers import ProtocolBlock, make_header
from ouroboros_tpu.consensus.ledger import ExtLedgerRules
from ouroboros_tpu.crypto.backend import OpensslBackend
from ouroboros_tpu.eras.shelley import (
    KES_FIELD, TPraosConfig, forge_tpraos_fields, shelley_genesis_setup,
)

CFG = TPraosConfig(k=3, f=Fraction(1, 2), epoch_length=20,
                   slots_per_kes_period=5, kes_depth=4,
                   max_kes_evolutions=14)

BACKEND = OpensslBackend()


@pytest.fixture(scope="module")
def chain():
    protocol, ledger, pools = shelley_genesis_setup(2, CFG, seed=b"rp")
    ext = ExtLedgerRules(protocol, ledger)
    state = ext.initial_state()
    blocks, prev = [], None
    slot = 0
    while len(blocks) < 24:
        view = ledger.forecast_view(state.ledger, slot)
        ticked = protocol.tick_chain_dep_state(
            state.header.chain_dep_state, view, slot)
        for p in pools:
            lead = protocol.check_is_leader(p["can_be_leader"], slot,
                                            ticked, view)
            if lead is None:
                continue
            h = make_header(prev, slot, (), issuer=0)
            h = forge_tpraos_fields(protocol, p["hot_key"],
                                    p["can_be_leader"], lead, h)
            blk = ProtocolBlock(h, ())
            state = ext.tick_then_apply(state, blk, backend=BACKEND)
            blocks.append(blk)
            prev = h
            break
        slot += 1
    return ext, blocks, state


def test_pipelined_matches_sync(chain):
    ext, blocks, final = chain
    res = replay_blocks_pipelined(ext, blocks, ext.initial_state(),
                                  backend=BACKEND, window=8)
    assert res.all_valid
    assert res.n_valid == len(blocks)
    assert (res.final_state.ledger.state_hash()
            == final.ledger.state_hash())


def test_pipelined_reports_bad_proof_index(chain):
    ext, blocks, _final = chain
    bad_ix = 13
    blk = blocks[bad_ix]
    sig = bytearray(blk.header.get(KES_FIELD))
    sig[8] ^= 1
    bad_hdr = blk.header.with_fields(**{KES_FIELD: bytes(sig)})
    tampered = list(blocks)
    tampered[bad_ix] = ProtocolBlock(bad_hdr, blk.body)
    # hash changes -> envelope breaks at the NEXT block; with the original
    # successor chain we see either the proof failure at 13 or the
    # envelope break at 14, and the proof failure must win (13 < 14)
    res = replay_blocks_pipelined(ext, tampered, ext.initial_state(),
                                  backend=BACKEND, window=8)
    assert not res.all_valid
    assert res.n_valid == bad_ix
    assert "13" in str(res.error) or "proof" in str(res.error)


def test_pipelined_seq_error_index(chain):
    ext, blocks, _final = chain
    # drop a block: the successor's envelope check fails in the seq pass
    cut = list(blocks[:10]) + list(blocks[11:])
    res = replay_blocks_pipelined(ext, cut, ext.initial_state(),
                                  backend=BACKEND, window=8)
    assert not res.all_valid
    assert res.n_valid == 10


def test_pipelined_resume_from_final_state(chain):
    """ReplayResult resumability end-to-end (VERDICT r4 next-step 9): a
    replay interrupted by OutsideForecastRange returns the state after
    its fully-verified prefix; resuming from final_state over the
    remaining blocks reaches the same state hash as the uninterrupted
    run."""
    from ouroboros_tpu.consensus.ledger import (
        ExtLedgerRules as _ELR, OutsideForecastRange,
    )
    ext, blocks, final = chain
    stop_ix = 15
    stop_slot = blocks[stop_ix].slot

    class HorizonOnce:
        """Ledger proxy whose forecast fails ONCE at stop_slot — the
        replay-time shape of a ChainSync forecast-horizon wait."""

        def __init__(self, inner):
            self._inner = inner
            self.armed = True

        def forecast_view(self, state, slot):
            if self.armed and slot == stop_slot:
                self.armed = False
                raise OutsideForecastRange(f"horizon at {slot}")
            return self._inner.forecast_view(state, slot)

        def __getattr__(self, name):
            return getattr(self._inner, name)

    proxy = _ELR(ext.protocol, HorizonOnce(ext.ledger))
    res = replay_blocks_pipelined(proxy, blocks, ext.initial_state(),
                                  backend=BACKEND, window=4)
    assert not res.all_valid
    assert isinstance(res.error, OutsideForecastRange)
    assert res.n_valid == stop_ix
    assert res.final_state is not None       # resumable
    # "the chain advanced": resume over the remainder from final_state
    res2 = replay_blocks_pipelined(proxy, blocks[res.n_valid:],
                                   res.final_state, backend=BACKEND,
                                   window=4)
    assert res2.all_valid
    assert res2.n_valid == len(blocks) - stop_ix
    assert (res2.final_state.ledger.state_hash()
            == final.ledger.state_hash())


class AsyncStubBackend(OpensslBackend):
    """submit/finish-capable CPU backend: exercises the two-deep in-flight
    window pipeline (drain ordering, beta carry, failure indices) without
    a device.  Verification is deferred to finish_window, like the real
    async path."""

    def __init__(self):
        self.submitted = 0
        self.finished = 0
        self.max_in_flight = 0

    def submit_window(self, reqs, next_beta_proofs=()):
        self.submitted += 1
        self.max_in_flight = max(self.max_in_flight,
                                 self.submitted - self.finished)
        return {"reqs": list(reqs),
                "beta_proofs": list(dict.fromkeys(next_beta_proofs))}

    def finish_window(self, state):
        ok = self.verify_mixed(state["reqs"])
        betas = dict(zip(state["beta_proofs"],
                         self.vrf_betas_batch(state["beta_proofs"])))
        # a window counts as finished when its drain COMPLETES: the
        # producer overlaps with the whole (slow, CPU-bound) verify, so
        # max_in_flight == 2 reflects the pipeline design rather than
        # winning a GIL-slice race against the consumer's first bytecode
        self.finished += 1
        return ok, betas


def test_pipelined_two_deep_stub_backend(chain):
    from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE
    ext, blocks, final = chain
    sb = AsyncStubBackend()
    GLOBAL_BETA_CACHE.clear()
    res = replay_blocks_pipelined(ext, blocks, ext.initial_state(),
                                  backend=sb, window=4)
    assert res.all_valid, res.error
    assert res.n_valid == len(blocks)
    assert (res.final_state.ledger.state_hash()
            == final.ledger.state_hash())
    # the pipeline really kept two windows in flight
    assert sb.max_in_flight == 2
    assert sb.submitted == sb.finished == (len(blocks) + 3) // 4


def test_pipelined_two_deep_failure_index(chain):
    """A bad proof two windows back must still report the EARLIEST bad
    block index even though later windows were submitted optimistically."""
    from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE
    ext, blocks, _final = chain
    bad_ix = 5
    blk = blocks[bad_ix]
    sig = bytearray(blk.header.get(KES_FIELD))
    sig[3] ^= 1
    tampered = list(blocks)
    tampered[bad_ix] = ProtocolBlock(
        blk.header.with_fields(**{KES_FIELD: bytes(sig)}), blk.body)
    GLOBAL_BETA_CACHE.clear()
    res = replay_blocks_pipelined(ext, tampered, ext.initial_state(),
                                  backend=AsyncStubBackend(), window=4)
    assert not res.all_valid
    assert res.n_valid <= bad_ix + 1


@pytest.mark.device
@pytest.mark.slow
def test_pipelined_jax_backend_matches(chain):
    """JaxBackend through the threaded+fold pipeline on a longer chain.
    slow: tracing this chain's window-composite/fold shapes costs ~3
    CPU-minutes per process (the persistent cache only skips the XLA
    compile, not the trace) — tier-1 gates the same path end-to-end in
    test_served_replay.py::test_device_replay_state_hash_equals_reference."""
    jax = pytest.importorskip("jax")
    from ouroboros_tpu.crypto.jax_backend import JaxBackend
    ext, blocks, final = chain
    # XLA-only, no autotune (like test_served_replay.py): the autotuner would
    # MEASURE pallas+XLA candidates for every window/fold shape here —
    # minutes of AOT pallas compile with no extra coverage (kernel
    # selection has its own tests)
    jb = JaxBackend(min_bucket=16, use_pallas=False, autotune=False)
    res = replay_blocks_pipelined(ext, blocks, ext.initial_state(),
                                  backend=jb, window=8)
    assert res.all_valid, res.error
    assert (res.final_state.ledger.state_hash()
            == final.ledger.state_hash())


# ---------------------------------------------------------------------------
# Threaded producer/consumer pipeline (ISSUE 8): the submit_window path of
# replay_blocks_pipelined now runs the host-sequential pass on a background
# producer thread (consensus/pipeline.py).  Scheduling must not change the
# outcome, errors must drain oldest-first, and the producer thread must
# never leak — least of all on error paths where it runs ahead.
# ---------------------------------------------------------------------------

import threading

from ouroboros_tpu.crypto.backend import WindowVerdict
from ouroboros_tpu.observe import metrics as _metrics


def _producer_threads_alive():
    return [t for t in threading.enumerate()
            if t.name == "ouro-replay-producer" and t.is_alive()]


def _producer_counters():
    started = _metrics.counter("pipeline.producers_started",
                               always=True).value
    finished = _metrics.counter("pipeline.producers_finished",
                                always=True).value
    return started, finished


def _tamper(blocks, ix, byte=3):
    blk = blocks[ix]
    sig = bytearray(blk.header.get(KES_FIELD))
    sig[byte] ^= 1
    out = list(blocks)
    out[ix] = ProtocolBlock(blk.header.with_fields(**{KES_FIELD:
                                                      bytes(sig)}),
                            blk.body)
    return out


class FoldStubBackend(AsyncStubBackend):
    """AsyncStubBackend speaking the fold=True protocol: finish_window
    returns a WindowVerdict (first failing request index) instead of the
    per-proof vector — the CPU model of the device-side verdict fold."""

    supports_window_fold = True

    def __init__(self):
        super().__init__()
        self.fold_submissions = 0

    def submit_window(self, reqs, next_beta_proofs=(), fold=False):
        st = super().submit_window(reqs, next_beta_proofs)
        st["fold"] = fold
        if fold:
            self.fold_submissions += 1
        return st

    def finish_window(self, state):
        ok, betas = super().finish_window(state)
        if not state.get("fold"):
            return ok, betas
        first_bad = ok.index(False) if False in ok else None
        return WindowVerdict(len(ok), first_bad), betas


def test_threaded_result_identical_to_sync_driver(chain):
    """ReplayResult parity, threaded (AsyncStubBackend) vs the
    synchronous fallback driver (OpensslBackend has no submit_window),
    over the valid chain, a mid-chain proof tamper, and a truncation —
    same n_valid, same error presence, same final state hash."""
    from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE
    ext, blocks, _final = chain
    variants = [list(blocks), _tamper(blocks, 9),
                list(blocks[:7]) + list(blocks[8:])]
    for blks in variants:
        GLOBAL_BETA_CACHE.clear()
        sync = replay_blocks_pipelined(ext, blks, ext.initial_state(),
                                       backend=BACKEND, window=4)
        GLOBAL_BETA_CACHE.clear()
        thr = replay_blocks_pipelined(ext, blks, ext.initial_state(),
                                      backend=AsyncStubBackend(),
                                      window=4)
        assert thr.n_valid == sync.n_valid
        assert (thr.error is None) == (sync.error is None)
        if sync.final_state is None:
            assert thr.final_state is None
        else:
            assert (thr.final_state.ledger.state_hash()
                    == sync.final_state.ledger.state_hash())


def test_fold_verdict_path_matches_vector_path(chain):
    """The fold=True drain (WindowVerdict scalar) must reproduce the
    vector drain's ReplayResult exactly — valid and tampered."""
    from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE
    ext, blocks, _final = chain
    for blks in (list(blocks), _tamper(blocks, 13), _tamper(blocks, 0)):
        GLOBAL_BETA_CACHE.clear()
        vec = replay_blocks_pipelined(ext, blks, ext.initial_state(),
                                      backend=AsyncStubBackend(),
                                      window=4)
        GLOBAL_BETA_CACHE.clear()
        fb = FoldStubBackend()
        fold = replay_blocks_pipelined(ext, blks, ext.initial_state(),
                                       backend=fb, window=4)
        assert fb.fold_submissions == fb.submitted > 0
        assert fold.n_valid == vec.n_valid
        assert (fold.error is None) == (vec.error is None)
        if vec.final_state is not None:
            assert (fold.final_state.ledger.state_hash()
                    == vec.final_state.ledger.state_hash())


def test_on_window_hook_identical_on_both_drivers(chain):
    """The on_window snapshot seam (ISSUE 15): fires once per FULLY
    verified window with the post-window state and tip point, on the
    threaded driver and the synchronous fallback alike — same windows,
    same points, same state hashes (the streaming engine's checkpoints
    cannot depend on which driver ran)."""
    from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE
    ext, blocks, final = chain

    def run(backend):
        calls = []
        GLOBAL_BETA_CACHE.clear()
        res = replay_blocks_pipelined(
            ext, blocks, ext.initial_state(), backend=backend, window=4,
            on_window=lambda st, n, pt: calls.append(
                (n, pt.slot, st.ledger.state_hash())))
        assert res.all_valid
        return calls, res

    threaded, rt = run(AsyncStubBackend())
    sync, rs = run(BACKEND)                 # no submit_window: fallback
    assert threaded == sync
    assert [n for n, _s, _h in threaded] == [4, 8, 12, 16, 20, 24]
    # the last hook state IS the final state
    assert threaded[-1][2] == rt.final_state.ledger.state_hash()
    assert threaded[-1][1] == blocks[-1].slot


def test_on_window_hook_not_called_past_first_error(chain):
    """A tampered window: the hook fires for windows before the bad
    block only — a checkpoint of unverified state would poison resume."""
    ext, blocks, _final = chain
    tampered = _tamper(blocks, 9)           # window 3 at window=4
    calls = []
    res = replay_blocks_pipelined(
        ext, tampered, ext.initial_state(), backend=AsyncStubBackend(),
        window=4, on_window=lambda st, n, pt: calls.append(n))
    assert not res.all_valid
    assert calls == [4, 8]

    # inspect what the synchronous driver does with the same chain
    calls2 = []
    res2 = replay_blocks_pipelined(
        ext, tampered, ext.initial_state(), backend=BACKEND, window=4,
        on_window=lambda st, n, pt: calls2.append(n))
    assert not res2.all_valid
    assert calls2 == [4, 8]

    # a SEQUENTIAL failure (envelope break from a dropped block, inside
    # window 3) is equally checkpoint-free past the last clean window,
    # on both drivers — the verified prefix precedes an invalid block
    cut = list(blocks[:10]) + list(blocks[11:])
    for backend in (AsyncStubBackend(), BACKEND):
        calls3 = []
        res3 = replay_blocks_pipelined(
            ext, cut, ext.initial_state(), backend=backend, window=4,
            on_window=lambda st, n, pt: calls3.append(n))
        assert not res3.all_valid
        assert calls3 == [4, 8]


def test_on_window_hook_exception_is_clean_stop(chain):
    """A hook failure (snapshot write error, the kill/resume test's
    hard stop) re-raises on the caller through the normal teardown:
    producer joined, every optimistic submission finished."""
    ext, blocks, _final = chain

    class SnapshotDied(Exception):
        pass

    def hook(st, n, pt):
        if n >= 8:
            raise SnapshotDied(f"disk full at block {n}")

    sb = AsyncStubBackend()
    s0, f0 = _producer_counters()
    with pytest.raises(SnapshotDied):
        replay_blocks_pipelined(ext, blocks, ext.initial_state(),
                                backend=sb, window=4, on_window=hook)
    assert sb.submitted == sb.finished > 0   # no leaked device work
    s1, f1 = _producer_counters()
    assert (s1 - s0, f1 - f0) == (1, 1)
    assert not _producer_threads_alive()


def test_error_with_producer_ahead_no_leaks(chain):
    """A proof failure in an early window while the producer has run
    ahead: the earliest bad block index wins, every optimistically
    submitted window is still drained (no leaked device work), and the
    producer thread is joined."""
    from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE
    ext, blocks, _final = chain
    bad_ix = 1                       # first window at window=4
    tampered = _tamper(blocks, bad_ix)
    for mk in (AsyncStubBackend, FoldStubBackend):
        GLOBAL_BETA_CACHE.clear()
        sb = mk()
        s0, f0 = _producer_counters()
        res = replay_blocks_pipelined(ext, tampered, ext.initial_state(),
                                      backend=sb, window=4)
        assert not res.all_valid
        assert res.n_valid == bad_ix
        assert res.final_state is None
        # every submitted window was finished — ahead-of-error windows
        # are discarded via finish_window, not dropped
        assert sb.submitted == sb.finished > 0
        s1, f1 = _producer_counters()
        assert (s1 - s0, f1 - f0) == (1, 1)
        assert not _producer_threads_alive()


def test_forced_failure_dumps_flight_record(chain, tmp_path, monkeypatch):
    """ISSUE 9 acceptance: a forced mid-replay failure with the flight
    recorder armed produces a dump whose chrome-trace file loads (valid
    trace_event JSON with the replay spans) and whose JSONL names the
    failing block in the header reason."""
    import json

    from ouroboros_tpu.observe.flight import FLIGHT

    ext, blocks, _final = chain
    bad_ix = 9
    tampered = _tamper(blocks, bad_ix)
    monkeypatch.setenv("OURO_FLIGHT_DIR", str(tmp_path / "flight"))
    FLIGHT.arm()
    try:
        res = replay_blocks_pipelined(ext, tampered, ext.initial_state(),
                                      backend=AsyncStubBackend(),
                                      window=4)
    finally:
        FLIGHT.disarm()
        FLIGHT.clear()
    assert not res.all_valid and res.n_valid == bad_ix
    trace_path = tmp_path / "flight" / "flight.trace.json"
    jsonl_path = tmp_path / "flight" / "flight.jsonl"
    assert trace_path.exists() and jsonl_path.exists()
    doc = json.loads(trace_path.read_text())
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in events}
    assert {"window.host_seq", "pipeline.drain"} <= names
    assert all(e["dur"] >= 0 for e in events)
    lines = jsonl_path.read_text().splitlines()
    head = json.loads(lines[0])
    assert head["kind"] == "flight"
    assert f"block {bad_ix}" in head["reason"]
    assert head["entries"] == len(lines) - 1
    kinds = {json.loads(ln)["kind"] for ln in lines[1:]}
    assert {"span", "metric"} <= kinds
    # no dump without arming: the error path stays free in normal runs
    res2 = replay_blocks_pipelined(ext, tampered, ext.initial_state(),
                                   backend=AsyncStubBackend(), window=4)
    assert not res2.all_valid
    assert json.loads((tmp_path / "flight" /
                       "flight.jsonl").read_text().splitlines()[0]) \
        == head                            # unchanged by the second run


def test_producer_crash_reraises_on_caller(chain):
    """An unexpected exception in the producer (submit machinery broke)
    re-raises on the caller thread and never leaks the producer."""
    ext, blocks, _final = chain

    class ExplodingBackend(AsyncStubBackend):
        def submit_window(self, reqs, next_beta_proofs=()):
            if self.submitted >= 2:
                raise RuntimeError("submit machinery broke")
            return super().submit_window(reqs, next_beta_proofs)

    s0, f0 = _producer_counters()
    with pytest.raises(RuntimeError, match="submit machinery broke"):
        replay_blocks_pipelined(ext, blocks, ext.initial_state(),
                                backend=ExplodingBackend(), window=4)
    s1, f1 = _producer_counters()
    assert (s1 - s0, f1 - f0) == (1, 1)
    assert not _producer_threads_alive()


def test_pipeline_sim_model_race_free_at_k16():
    """The coordination protocol of consensus/pipeline.py — permit gate
    at the beta-carry depth, oldest-first drain, stop-on-error — modeled
    1:1 on the simharness and explored under ouro-race with K=16 seeded
    schedules: no unordered access pair in any schedule (every shared
    access is transactional), no model failure, and the report is
    deterministic.  A mid-stream failure variant exercises the stop
    path, where the producer may be ahead."""
    from ouroboros_tpu import simharness as sim
    from ouroboros_tpu.consensus.pipeline import DEPTH

    def make_model(n_windows=6, fail_at=None):
        async def main():
            pending = sim.TVar((), label="pipe.pending")
            submitted = sim.TVar(0, label="pipe.submitted")
            drained = sim.TVar(0, label="pipe.drained")
            stop = sim.TVar(False, label="pipe.stop")
            done = sim.TVar(False, label="pipe.done")
            order = sim.TVar((), label="pipe.drain-order")

            async def producer():
                for w in range(n_windows):
                    def gate(tx):
                        if not tx.read(stop):
                            tx.check(tx.read(submitted)
                                     - tx.read(drained) < DEPTH)
                        return tx.read(stop)
                    if await sim.atomically(gate):
                        break
                    await sim.yield_()          # the sequential pass
                    await sim.atomically(lambda tx, w=w: (
                        tx.write(pending, tx.read(pending) + (w,)),
                        tx.write(submitted, tx.read(submitted) + 1)))
                await sim.atomically(lambda tx: tx.write(done, True))

            async def consumer():
                while True:
                    def pop(tx):
                        p = tx.read(pending)
                        if p:
                            tx.write(pending, p[1:])
                            return p[0]
                        tx.check(tx.read(done))
                        return None
                    w = await sim.atomically(pop)
                    if w is None:
                        break
                    await sim.yield_()          # the blocking drain
                    err = fail_at is not None and w == fail_at
                    await sim.atomically(lambda tx, w=w, err=err: (
                        tx.write(order, tx.read(order) + (w,)),
                        tx.write(drained, tx.read(drained) + 1),
                        err and tx.write(stop, True)))
                    if err:
                        break

            p = sim.spawn(producer(), label="pipe-producer")
            c = sim.spawn(consumer(), label="pipe-consumer")
            await p.wait()
            await c.wait()
            got = order.value
            want = tuple(range(len(got)))
            assert got == want, f"drain order broke: {got}"
            if fail_at is not None and len(got):
                assert got[-1] <= fail_at + (DEPTH - 1)
        return main

    for fail_at in (None, 2):
        rep = sim.explore_races(make_model(fail_at=fail_at), k=16, seed=0)
        assert not rep.failures, rep.render()
        assert not rep.found, rep.render()
        rep2 = sim.explore_races(make_model(fail_at=fail_at), k=16,
                                 seed=0)
        assert rep.render() == rep2.render()    # deterministic


# ---------------------------------------------------------------------------
# Sharded pipelined replay (ISSUE 11): ShardedJaxBackend through the SAME
# threaded driver — per-shard padded windows, cross-shard fold verdicts.
# The cheap accounting tests run in tier-1; the full mesh parity sweep is
# slow-marked (one sharded composite costs minutes of XLA:CPU on this
# container's experimental-shard_map jax) and tier-1 gates the same path
# in tests/test_sharded_replay.py.
# ---------------------------------------------------------------------------


@pytest.mark.device
def test_padding_stats_accounting():
    """padding_stats: lane occupancy accumulates per submitted window
    and waste_frac is the padded-lane fraction carrying no request."""
    pytest.importorskip("jax")
    from ouroboros_tpu.crypto.jax_backend import JaxBackend
    jb = JaxBackend(min_bucket=16, use_pallas=False, autotune=False)
    assert jb.padding_stats()["windows"] == 0
    jb._note_padding(24, 32)
    jb._note_padding(8, 16)
    st = jb.padding_stats()
    assert st == {"windows": 2, "lanes_used": 32, "lanes_padded": 48,
                  "waste_frac": round(1 - 32 / 48, 4), "shards": 1,
                  "lanes_per_shard_per_window": 24}
    jb._note_padding(4, 16)
    delta = jb.padding_stats(since=st)
    assert (delta["windows"], delta["lanes_used"],
            delta["lanes_padded"]) == (1, 4, 16)
    assert delta["waste_frac"] == 0.75


@pytest.mark.device
def test_sharded_backend_pads_to_per_shard_buckets():
    """The mesh backend's padding seam: batches round up to a mesh
    multiple past the bucket floor, and padding_stats attributes lanes
    per shard."""
    jax = pytest.importorskip("jax")
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 XLA devices (conftest forces 8)")
    from ouroboros_tpu.parallel import ShardedJaxBackend, make_mesh
    sb = ShardedJaxBackend(make_mesh(2), min_bucket=16)
    assert sb.n_shards == 2
    assert sb._pad(5) == 16       # bucket floor
    assert sb._pad(17) == 18      # mesh-multiple rounding past the floor
    sb._note_padding(17, 18)
    st = sb.padding_stats()
    assert st["shards"] == 2
    assert st["lanes_per_shard_per_window"] == 9


@pytest.mark.device
@pytest.mark.slow
def test_sharded_threaded_result_identical_to_sync_driver(chain):
    """ISSUE 11 acceptance: under the forced-host-device mesh, the
    sharded threaded ReplayResult is byte-identical to the synchronous
    single-device driver on a valid, a tampered, and a truncated chain,
    with zero leaked producer threads and per-shard padding accounted.
    slow: compiles two sharded window composites (~minutes of XLA:CPU
    each on experimental-shard_map jax); tier-1 gates the same path in
    tests/test_sharded_replay.py."""
    jax = pytest.importorskip("jax")
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 XLA devices (conftest forces 8)")
    from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE
    from ouroboros_tpu.parallel import ShardedJaxBackend, make_mesh
    ext, blocks, _final = chain
    sb = ShardedJaxBackend(make_mesh(2), min_bucket=16)
    s0, f0 = _producer_counters()
    variants = [list(blocks), _tamper(blocks, 9),
                list(blocks[:7]) + list(blocks[8:])]
    for blks in variants:
        GLOBAL_BETA_CACHE.clear()
        sync = replay_blocks_pipelined(ext, blks, ext.initial_state(),
                                       backend=BACKEND, window=8)
        GLOBAL_BETA_CACHE.clear()
        thr = replay_blocks_pipelined(ext, blks, ext.initial_state(),
                                      backend=sb, window=8)
        assert thr.n_valid == sync.n_valid
        assert (thr.error is None) == (sync.error is None)
        if sync.final_state is None:
            assert thr.final_state is None
        else:
            assert (thr.final_state.ledger.state_hash()
                    == sync.final_state.ledger.state_hash())
    # the sync driver spawns no producer (no submit_window); each of the
    # three sharded replays spawned and joined exactly one
    s1, f1 = _producer_counters()
    assert (s1 - s0, f1 - f0) == (3, 3)
    assert not _producer_threads_alive()
    st = sb.padding_stats()
    assert st["shards"] == 2 and st["windows"] >= 3
    assert 0.0 <= st["waste_frac"] < 1.0
