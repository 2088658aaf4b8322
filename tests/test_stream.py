"""Streaming replay engine (storage/stream.py): disk→decode→verify with
restartable snapshots.

The db-analyser-analog scenarios of ROADMAP item 4 / SURVEY.md §3.5:
replay a multi-era on-disk DB through the bounded read-ahead prefetcher
and the producer/consumer pipeline, cross Byron EBBs → Shelley in ONE
stream, checkpoint crash-consistently, kill mid-stream and resume to a
byte-identical final state hash.
"""
import importlib.util
import os
import shutil
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE, OpensslBackend
from ouroboros_tpu.observe.flight import FLIGHT
from ouroboros_tpu.storage import (
    DiskPolicy, ImmutableDB, IoFS, LedgerDB, MockFS, StreamConfig,
    StreamingReplayEngine,
)
from ouroboros_tpu.storage.stream import (
    BlockPrefetcher, prefetcher_threads_alive,
)


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _synth_cardano(out, blocks=60, epoch_length=10, chunk_size=10,
                   eras="byron-shelley"):
    dbs = _tool("db_synth")
    args = types.SimpleNamespace(
        out=out, protocol="cardano", blocks=blocks, txs_per_block=1,
        nodes=2, pools=2, f="4/5", epoch_length=epoch_length,
        kes_depth=5, chunk_size=chunk_size, format="native",
        seed="stream-test", eras=eras)
    return dbs.synth_cardano(args)


class AsyncStubBackend:
    """submit/finish CPU backend: drives the THREADED pipeline (windows
    in flight, producer ahead) without a device — the shape the
    kill-mid-stream scenario needs.  Verification delegates to `inner`
    (pure-Python by default; the 10k-block slow e2e passes the native
    C++ backend so full crypto at scale stays minutes, not hours)."""

    def __init__(self, inner=None):
        self._inner = inner if inner is not None else OpensslBackend()
        self.finished = 0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def submit_window(self, reqs, next_beta_proofs=()):
        return {"reqs": list(reqs),
                "bp": list(dict.fromkeys(next_beta_proofs))}

    def finish_window(self, st):
        self.finished += 1
        return (self._inner.verify_mixed(st["reqs"]),
                dict(zip(st["bp"],
                         self._inner.vrf_betas_batch(st["bp"]))))


class HardStop(BaseException):
    """The kill: not an Exception subclass, so nothing between the
    drain and the caller can accidentally swallow it."""


class KillBackend(AsyncStubBackend):
    """Hard-stops the replay at the Nth drain — producer alive, windows
    in flight — through the pipeline's first-error-wins seam.  Later
    finish_window calls (the discard-leftovers path) must succeed, so
    the kill fires exactly once."""

    def __init__(self, kill_at_window, inner=None):
        super().__init__(inner)
        self.kill_at = kill_at_window

    def finish_window(self, st):
        if self.kill_at is not None and self.finished + 1 >= self.kill_at:
            self.kill_at = None
            raise HardStop(f"hard stop at drain {self.finished + 1}")
        return super().finish_window(st)


@pytest.fixture(scope="module")
def chain_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("streamdb"))
    info = _synth_cardano(d)
    assert info["blocks"] == 60
    return d


@pytest.fixture(scope="module")
def loaded(chain_dir):
    dba = _tool("db_analyser")
    db, rules, decode, cfg = dba.load_db(chain_dir)
    return db, rules, decode


@pytest.fixture(scope="module")
def reference_hash(loaded):
    """CPU-reference fold over the whole on-disk chain (the OnDisk.hs
    replay semantics, no streaming machinery involved)."""
    db, rules, decode = loaded
    st = rules.initial_state()
    for _e, raw in db.stream():
        st = rules.tick_then_reapply(st, decode(raw))
    return st.ledger.state_hash()


def _fresh_db_dir(chain_dir, tmp_path):
    """Per-test copy: engines write snapshots into the DB dir."""
    d = str(tmp_path / "db")
    shutil.copytree(chain_dir, d)
    return d


def _engine(db_dir, backend, window=8, resume=False, interval=16,
            num_snapshots=2, read_ahead=2):
    dba = _tool("db_analyser")
    db, rules, decode, _cfg = dba.load_db(db_dir)
    return StreamingReplayEngine(
        IoFS(db_dir), db, rules, decode, backend=backend,
        config=StreamConfig(
            window=window, read_ahead=read_ahead,
            policy=DiskPolicy(num_snapshots=num_snapshots,
                              snapshot_interval_slots=interval),
            resume=resume))


# ---------------------------------------------------------------------------
# Parity + era crossing + accounting
# ---------------------------------------------------------------------------

def test_stream_engine_matches_cpu_reference(chain_dir, tmp_path,
                                             reference_hash):
    d = _fresh_db_dir(chain_dir, tmp_path)
    GLOBAL_BETA_CACHE.clear()
    res = _engine(d, AsyncStubBackend()).replay()
    assert res.all_valid and res.n_valid == 60
    assert res.final_state.ledger.state_hash() == reference_hash
    st = res.stats
    assert st["blocks_decoded"] == 60
    assert st["chunks_read"] >= 2          # chunk-granular, not one slurp
    assert st["bytes_read"] > 0
    assert st["era_crossings"] == 1        # Byron -> Shelley, in-stream
    assert st["host_seq_secs"] > 0         # the threaded pipeline ran
    assert st["disk_secs"] > 0
    assert 0.0 <= st["disk_hidden_frac"] <= 1.0
    # DiskPolicy: periodic snapshots were taken and trimmed to policy
    assert st["snapshots_written"] >= 2
    assert len(LedgerDB.snapshot_names(IoFS(d))) == 2
    assert prefetcher_threads_alive() == 0


@pytest.mark.parametrize("killed", [False, True], ids=["clean", "killed"])
def test_each_thread_reads_its_cpu_time_once_a_replay(chain_dir, tmp_path,
                                                      killed):
    """ISSUE 36: prefetcher, producer and caller each add their CPU
    microseconds (and preempt count) to the registry when their part of
    a replay ends, with span recording off and on every path out."""
    from ouroboros_tpu.observe import metrics, spans
    threads = ("prefetch", "producer", "caller")

    def readings():
        return {f"{kind}.{t}": metrics.REGISTRY.get(
                    f"replay.thread_{kind}.{t}").value
                for t in threads for kind in ("cpu_us", "preempts")}

    assert not spans.RECORDER.enabled and metrics.REGISTRY.enabled
    d = _fresh_db_dir(chain_dir, tmp_path)
    GLOBAL_BETA_CACHE.clear()
    c0 = readings()
    if killed:
        with pytest.raises(HardStop):
            _engine(d, KillBackend(kill_at_window=3)).replay()
    else:
        assert _engine(d, AsyncStubBackend()).replay().all_valid
    c1 = readings()
    assert prefetcher_threads_alive() == 0
    for t in threads:
        assert c1[f"cpu_us.{t}"] > c0[f"cpu_us.{t}"], t
        assert c1[f"preempts.{t}"] >= c0[f"preempts.{t}"], t


def test_stream_crosses_fork_to_shelley(chain_dir, tmp_path,
                                        reference_hash):
    """The final state sits in the Shelley era — the hard-fork
    translation genuinely happened inside the stream (SURVEY.md hard
    parts #2), not via a driver swap."""
    from ouroboros_tpu.eras.cardano import SHELLEY
    d = _fresh_db_dir(chain_dir, tmp_path)
    GLOBAL_BETA_CACHE.clear()
    res = _engine(d, AsyncStubBackend()).replay()
    assert res.all_valid
    assert res.final_state.ledger.era == SHELLEY
    assert res.final_state.header.chain_dep_state.era == SHELLEY


def test_era_field_matches_combinator():
    from ouroboros_tpu.consensus.hardfork.combinator import ERA_FIELD
    from ouroboros_tpu.storage import stream
    assert stream.ERA_FIELD == ERA_FIELD


def test_resumed_reopen_restores_tip_instantly(chain_dir, tmp_path,
                                               reference_hash):
    d = _fresh_db_dir(chain_dir, tmp_path)
    GLOBAL_BETA_CACHE.clear()
    first = _engine(d, AsyncStubBackend()).replay()
    assert first.all_valid
    GLOBAL_BETA_CACHE.clear()
    again = _engine(d, AsyncStubBackend(), resume=True).replay()
    assert again.all_valid and again.n_valid == 0     # nothing re-replayed
    assert again.stats["resumed_from_slot"] is not None
    assert again.final_state.ledger.state_hash() == reference_hash
    # a fully-resumed rerun writes no new snapshot (tip unchanged)
    assert again.stats["snapshots_written"] == 0


# ---------------------------------------------------------------------------
# Kill mid-stream + resume (ISSUE 15 satellite)
# ---------------------------------------------------------------------------

def test_kill_and_resume_byte_identical(chain_dir, tmp_path,
                                        reference_hash):
    """Hard-stop mid-stream through the pipeline's first-error-wins
    seam — producer alive, windows in flight — then reopen from the
    newest snapshot: the resumed run replays only the suffix and ends
    on a byte-identical state hash.  On a parity mismatch the armed
    flight recorder dumps the ring (incl. the StreamResumed event) for
    post-mortem before the assertion fires."""
    d = _fresh_db_dir(chain_dir, tmp_path)
    GLOBAL_BETA_CACHE.clear()
    # interval 8: the two windows drained before the kill are enough to
    # cross the snapshot cadence (the interval counts from the stream's
    # start — there is no unconditional first-window checkpoint)
    eng = _engine(d, KillBackend(kill_at_window=3), interval=8)
    with pytest.raises(HardStop):
        eng.replay()
    # the kill left windows in flight and snapshots behind
    assert eng.snapshots_written >= 1
    assert prefetcher_threads_alive() == 0            # joined, not leaked
    snaps = LedgerDB.snapshot_names(IoFS(d))
    assert snaps, "no snapshot survived the kill"

    GLOBAL_BETA_CACHE.clear()
    FLIGHT.arm()
    try:
        res = _engine(d, AsyncStubBackend(), resume=True).replay()
        assert res.all_valid
        assert res.stats["resumed_from_slot"] is not None
        assert 0 < res.n_valid < 60                   # only the suffix
        got = res.final_state.ledger.state_hash()
        if got != reference_hash:                     # pragma: no cover
            paths = FLIGHT.dump_on_failure(
                f"kill/resume parity mismatch: {got.hex()} != "
                f"{reference_hash.hex()}")
            pytest.fail(f"resume state hash diverged; flight dump at "
                        f"{paths}")
    finally:
        FLIGHT.disarm()
        FLIGHT.clear()
    assert prefetcher_threads_alive() == 0


def test_kill_during_snapshot_write_keeps_previous(chain_dir, tmp_path,
                                                   reference_hash):
    """A crash INSIDE a snapshot write (torn bytes on disk) must not
    poison resume: the checksum rejects the torn file and the engine
    falls back to the previous snapshot."""
    d = _fresh_db_dir(chain_dir, tmp_path)
    GLOBAL_BETA_CACHE.clear()
    first = _engine(d, AsyncStubBackend(), num_snapshots=3).replay()
    assert first.all_valid and first.stats["snapshots_written"] >= 2
    fs = IoFS(d)
    snaps = LedgerDB.snapshot_names(fs)
    # tear the newest snapshot in place (crash mid-write)
    path = os.path.join(d, "ledger", snaps[-1])
    raw = open(path, "rb").read()
    open(path, "wb").write(raw[:len(raw) // 2])
    GLOBAL_BETA_CACHE.clear()
    res = _engine(d, AsyncStubBackend(), resume=True).replay()
    assert res.all_valid
    assert res.stats["resumed_from_slot"] == int(snaps[-2].split("-")[1])
    assert res.final_state.ledger.state_hash() == reference_hash


def test_snapshot_past_truncated_db_falls_back(chain_dir, tmp_path):
    """Startup validation truncated a corrupt tail: the newest snapshot
    now points past the chain.  Restore must skip it (its point is no
    longer in the ImmutableDB) and resume from one still on-chain."""
    d = _fresh_db_dir(chain_dir, tmp_path)
    GLOBAL_BETA_CACHE.clear()
    first = _engine(d, AsyncStubBackend(), num_snapshots=4,
                    interval=12).replay()
    assert first.all_valid and first.stats["snapshots_written"] >= 3
    # corrupt the LAST chunk's data: reopen truncates the chain there
    fs = IoFS(d)
    chunks = sorted(n for n in fs.list_dir(("immutable",))
                    if n.endswith(".chunk"))
    path = os.path.join(d, "immutable", chunks[-1])
    raw = bytearray(open(path, "rb").read())
    raw[3] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    dba = _tool("db_analyser")
    db, rules, decode, _cfg = dba.load_db(d)       # validate_all=False
    db2 = ImmutableDB.open(IoFS(d), chunk_size=10)  # validating open
    assert db2.tip.slot < first.final_state.header.tip.slot
    GLOBAL_BETA_CACHE.clear()
    res = StreamingReplayEngine(
        fs, db2, rules, decode, backend=AsyncStubBackend(),
        config=StreamConfig(window=8, read_ahead=2,
                            policy=DiskPolicy(num_snapshots=4,
                                              snapshot_interval_slots=12),
                            resume=True)).replay()
    assert res.all_valid
    assert res.stats["resumed_from_slot"] is not None
    assert res.stats["resumed_from_slot"] <= db2.tip.slot
    # the resumed replay ends exactly at the truncated chain's tip
    assert res.final_state.header.tip.slot == db2.tip.slot


def test_reference_format_db_streams_and_resumes(tmp_path):
    """The engine's generic per-block fallback path: a REFERENCE-format
    DB (no chunk_blocks API) streams through the same prefetch thread,
    snapshots, and resumes — membership for the snapshot point scans
    only the index files (refformat.RefImmutableView.__contains__)."""
    d = str(tmp_path / "refdb")
    # reference format with EBBs requires chunk_size == epoch_length
    info = _synth_cardano(d, blocks=40, epoch_length=10, chunk_size=10)
    # rewrite as reference format: re-synth directly
    import shutil as _sh
    _sh.rmtree(d)
    dbs = _tool("db_synth")
    args = types.SimpleNamespace(
        out=d, protocol="cardano", blocks=40, txs_per_block=1, nodes=2,
        pools=2, f="4/5", epoch_length=10, kes_depth=5, chunk_size=10,
        format="reference", seed="stream-test", eras="byron-shelley")
    info = dbs.synth_cardano(args)
    assert info["blocks"] == 40
    dba = _tool("db_analyser")
    db, rules, decode, _cfg = dba.load_db(d)
    assert not hasattr(db, "chunk_blocks")        # the fallback path
    fs = IoFS(d)
    GLOBAL_BETA_CACHE.clear()
    first = StreamingReplayEngine(
        fs, db, rules, decode, backend=AsyncStubBackend(),
        config=StreamConfig(window=8, read_ahead=2,
                            policy=DiskPolicy(num_snapshots=2,
                                              snapshot_interval_slots=16),
                            resume=False)).replay()
    assert first.all_valid and first.n_valid == 40
    assert first.stats["era_crossings"] == 1
    GLOBAL_BETA_CACHE.clear()
    again = StreamingReplayEngine(
        fs, db, rules, decode, backend=AsyncStubBackend(),
        config=StreamConfig(window=8, read_ahead=2,
                            resume=True)).replay()
    assert again.all_valid and again.n_valid == 0
    assert again.stats["resumed_from_slot"] is not None
    assert (again.final_state.ledger.state_hash()
            == first.final_state.ledger.state_hash())
    assert prefetcher_threads_alive() == 0


def test_snapshot_interval_counts_from_stream_start(chain_dir, tmp_path):
    """No unconditional first-window checkpoint: with an interval wider
    than the chain, a run writes ONLY the tip checkpoint — the
    `--resume`-without-`--snapshot-every` contract (one full-state
    serialisation, at the end, not after window 1 of a long replay)."""
    d = _fresh_db_dir(chain_dir, tmp_path)
    GLOBAL_BETA_CACHE.clear()
    res = _engine(d, AsyncStubBackend(), interval=1 << 62).replay()
    assert res.all_valid
    assert res.stats["snapshots_written"] == 1        # tip only
    snaps = LedgerDB.snapshot_names(IoFS(d))
    assert len(snaps) == 1
    assert int(snaps[0].split("-")[1]) \
        == res.final_state.header.tip.slot


# ---------------------------------------------------------------------------
# Prefetcher unit behaviour
# ---------------------------------------------------------------------------

def _mock_db(n=20, chunk_size=4):
    fs = MockFS()
    db = ImmutableDB.open(fs, chunk_size=chunk_size)
    prev = b"\x00" * 32
    for i in range(n):
        h = bytes([i, 0]) + bytes(30)
        data = b"raw-%04d" % i
        db.append_block(i, i, h, prev, data)
        prev = h
    return db


def test_prefetcher_yields_all_blocks_in_order():
    db = _mock_db()
    pre = BlockPrefetcher(db, lambda raw: raw, window=3, depth=2).start()
    try:
        got = list(pre)
    finally:
        pre.close()
    assert got == [b"raw-%04d" % i for i in range(20)]
    assert pre.chunks_read == 5
    assert pre.blocks_decoded == 20
    assert prefetcher_threads_alive() == 0


def test_prefetcher_early_close_joins_thread():
    db = _mock_db(n=40)
    pre = BlockPrefetcher(db, lambda raw: raw, window=2, depth=1).start()
    it = iter(pre)
    assert next(it) == b"raw-0000"
    pre.close()                      # consumer abandons mid-stream
    assert prefetcher_threads_alive() == 0
    # the bound really applied: a depth-1 queue behind a stopped
    # consumer cannot have read everything ahead
    assert pre.blocks_decoded < 40


def test_prefetcher_decode_error_surfaces_on_consumer():
    db = _mock_db()

    def decode(raw):
        if raw.endswith(b"0007"):
            raise ValueError("decode broke")
        return raw

    pre = BlockPrefetcher(db, decode, window=3, depth=2).start()
    got = []
    try:
        with pytest.raises(ValueError, match="decode broke"):
            for b in pre:
                got.append(b)
    finally:
        pre.close()
    # whatever was queued before the failure is a clean prefix; the
    # failing block (index 7) never reaches the consumer
    assert got == [b"raw-%04d" % i for i in range(len(got))]
    assert len(got) < 8
    assert prefetcher_threads_alive() == 0


def test_engine_decode_error_aborts_without_leaks(chain_dir, tmp_path):
    d = _fresh_db_dir(chain_dir, tmp_path)
    dba = _tool("db_analyser")
    db, rules, decode, _cfg = dba.load_db(d)
    calls = {"n": 0}

    def exploding(raw):
        calls["n"] += 1
        if calls["n"] == 30:
            raise ValueError("mid-stream decode failure")
        return decode(raw)

    GLOBAL_BETA_CACHE.clear()
    eng = StreamingReplayEngine(
        IoFS(d), db, rules, exploding, backend=AsyncStubBackend(),
        config=StreamConfig(window=8, read_ahead=2, resume=False))
    with pytest.raises(ValueError, match="mid-stream decode failure"):
        eng.replay()
    assert prefetcher_threads_alive() == 0


# ---------------------------------------------------------------------------
# ouro-race: the prefetcher/producer/consumer trio, modeled 1:1
# ---------------------------------------------------------------------------

def test_stream_trio_sim_model_race_free_at_k16():
    """The three-stage coordination protocol — bounded prefetch queue in
    front of the pipeline's permit-gated producer and oldest-first
    consumer — modeled on the simharness and explored under ouro-race
    with K=16 seeded schedules: no unordered access pair, no deadlock,
    deterministic report, and on an early stop (mid-stream failure) all
    three threads reach a terminal state (zero leaked sim threads)."""
    from ouroboros_tpu import simharness as sim
    from ouroboros_tpu.consensus.pipeline import DEPTH
    READ_AHEAD = 2

    def make_model(n_batches=6, fail_at=None):
        async def main():
            batches = sim.TVar((), label="stream.batches")
            eof = sim.TVar(False, label="stream.eof")
            pending = sim.TVar((), label="pipe.pending")
            submitted = sim.TVar(0, label="pipe.submitted")
            drained = sim.TVar(0, label="pipe.drained")
            stop = sim.TVar(False, label="pipe.stop")
            done = sim.TVar(False, label="pipe.done")
            order = sim.TVar((), label="pipe.drain-order")

            async def prefetcher():
                for b in range(n_batches):
                    def put(tx, b=b):
                        if tx.read(stop):
                            return True
                        tx.check(len(tx.read(batches)) < READ_AHEAD)
                        tx.write(batches, tx.read(batches) + (b,))
                        return False
                    await sim.yield_()          # the read+decode
                    if await sim.atomically(put):
                        break
                await sim.atomically(lambda tx: tx.write(eof, True))

            async def producer():
                while True:
                    def take(tx):
                        if tx.read(stop):
                            return ("stop", None)
                        bs = tx.read(batches)
                        if bs:
                            if not (tx.read(submitted) - tx.read(drained)
                                    < DEPTH):
                                tx.check(False)
                            tx.write(batches, bs[1:])
                            return ("batch", bs[0])
                        tx.check(tx.read(eof))
                        return ("eof", None)
                    kind, w = await sim.atomically(take)
                    if kind != "batch":
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

            pf = sim.spawn(prefetcher(), label="stream-prefetch")
            p = sim.spawn(producer(), label="pipe-producer")
            c = sim.spawn(consumer(), label="pipe-consumer")
            await p.wait()
            await c.wait()
            # the engine's finally: close() the prefetcher (it observes
            # stop at its next put) and join it
            await sim.atomically(lambda tx: tx.write(stop, True))
            await pf.wait()
            got = order.value
            assert got == tuple(range(len(got))), f"order broke: {got}"
            if fail_at is None:
                assert len(got) == n_batches
        return main

    for fail_at in (None, 2):
        rep = sim.explore_races(make_model(fail_at=fail_at), k=16, seed=0)
        assert not rep.failures, rep.render()
        assert not rep.found, rep.render()
        rep2 = sim.explore_races(make_model(fail_at=fail_at), k=16,
                                 seed=0)
        assert rep.render() == rep2.render()

    # zero leaked sim threads on the early-stop schedule
    from ouroboros_tpu.simharness import leaked_threads, run_trace
    _res, trace = run_trace(make_model(fail_at=2)())
    assert not leaked_threads(trace)


# ---------------------------------------------------------------------------
# ≥10k-block multi-era end-to-end (slow lane)
# ---------------------------------------------------------------------------

def _fast_cpu_inner():
    """Native C++ verification when the extension is built (full crypto
    over ~50k proofs in minutes), pure-Python otherwise."""
    try:
        from ouroboros_tpu.crypto.cpp_backend import CppBackend
        return CppBackend()
    except Exception:
        return OpensslBackend()


@pytest.mark.slow
def test_stream_10k_block_multi_era_end_to_end(tmp_path):
    """ISSUE 15 acceptance, at scale: a >=10k-block Byron->Shelley DB
    streamed through the engine — full proof verification on the
    threaded pipeline, era boundary crossed in-stream, periodic
    snapshots — then killed mid-stream and resumed from the newest
    snapshot to a byte-identical final state hash.  slow: the 10k-block
    synth plus three large replays cost minutes of CPU even on the
    native backend; the tier-1 lane gates the same engine path via
    test_served_replay.py's from-disk tests and the 60-block tests
    above."""
    d = str(tmp_path / "bigdb")
    info = _synth_cardano(d, blocks=10_000, epoch_length=500,
                          chunk_size=100)
    assert info["blocks"] >= 10_000
    dba = _tool("db_analyser")
    db, rules, decode, _cfg = dba.load_db(d)
    fs = IoFS(d)
    cfg = StreamConfig(window=256, read_ahead=4,
                       policy=DiskPolicy(num_snapshots=2,
                                         snapshot_interval_slots=2000),
                       resume=False)

    GLOBAL_BETA_CACHE.clear()
    full = StreamingReplayEngine(
        fs, db, rules, decode,
        backend=AsyncStubBackend(_fast_cpu_inner()), config=cfg).replay()
    assert full.all_valid and full.n_valid >= 10_000
    assert full.stats["era_crossings"] == 1
    assert full.stats["chunks_read"] >= 50
    want = full.final_state.ledger.state_hash()

    # wipe the checkpoints, kill mid-stream, resume
    for name in LedgerDB.snapshot_names(fs):
        fs.remove(("ledger", name))
    GLOBAL_BETA_CACHE.clear()
    eng = StreamingReplayEngine(
        fs, db, rules, decode,
        backend=KillBackend(20, _fast_cpu_inner()), config=cfg)
    with pytest.raises(HardStop):
        eng.replay()
    assert eng.snapshots_written >= 1
    GLOBAL_BETA_CACHE.clear()
    res = StreamingReplayEngine(
        fs, db, rules, decode,
        backend=AsyncStubBackend(_fast_cpu_inner()),
        config=StreamConfig(window=256, read_ahead=4,
                            policy=cfg.policy, resume=True)).replay()
    assert res.all_valid
    assert res.stats["resumed_from_slot"] is not None
    assert res.n_valid < 10_000              # only the suffix replayed
    assert res.final_state.ledger.state_hash() == want
    assert prefetcher_threads_alive() == 0
