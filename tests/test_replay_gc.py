"""The cyclic collector during a streamed replay (ISSUE 28): the engine
lends the collector's permanent generation to its replay
(`storage/stream.py _ReplayCollector`), each decoded chunk is frozen
into it, the freeze is lifted at every cleanly drained window, and on
every exit path the process's collector is as it was found.

Host backends only.  Two chains forged once a module: a light one (one
transaction a block, the rehearsal's shape) and one of 64 blocks filled
with 352 transactions each, the benchmark's body.
"""
import gc
import importlib.util
import io
import json
import os
import shutil
import subprocess
import sys
import threading
import weakref

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from ouroboros_tpu import observe                                 # noqa: E402
from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE        # noqa: E402
from ouroboros_tpu.crypto.precompute import (                     # noqa: E402
    GLOBAL_PRECOMPUTE_CACHE,
)
from ouroboros_tpu.storage import (                               # noqa: E402
    DiskPolicy, IoFS, StreamConfig, StreamingReplayEngine,
)
from ouroboros_tpu.storage import stream                          # noqa: E402
from ouroboros_tpu.storage.stream import (                        # noqa: E402
    BlockPrefetcher, prefetcher_threads_alive,
)

LIGHT_BLOCKS, LIGHT_WINDOW = 96, 8         # twelve windows
FULL_BLOCKS, FULL_TXS, FULL_WINDOW = 64, 352, 32
# CPython 3.12 parks immortal objects (PEP 683: a few hundred, 375 in a
# fresh interpreter) in the permanent generation whenever a full pass
# meets them, so "nothing left frozen" cannot mean a count of zero; what
# a replay freezes is the whole heap, hundreds of thousands
IMMORTALS = 5000
COUNTERS = ("replay.gc.pause_us", "replay.gc.full_passes",
            "replay.gc.freezes", "replay.gc.frozen_objects")


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _forge(out: str, blocks: int, txs: int) -> str:
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", out, "--protocol", "shelley", "--blocks", str(blocks),
         "--txs-per-block", str(txs), "--pools", "2", "--f", "4/5",
         "--epoch-length", "500", "--kes-depth", "4", "--chunk-size", "4"],
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    return out


@pytest.fixture(scope="module")
def dba():
    return _tool("db_analyser")


@pytest.fixture(scope="module")
def light_chain(tmp_path_factory):
    return _forge(str(tmp_path_factory.mktemp("gc-light")), LIGHT_BLOCKS, 1)


@pytest.fixture(scope="module")
def full_chain(tmp_path_factory):
    return _forge(str(tmp_path_factory.mktemp("gc-full")), FULL_BLOCKS,
                  FULL_TXS)


class Threaded:
    """A host backend behind submit/finish, so the replay runs its three
    threads (prefetcher, producer, caller) as it does on a device."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def submit_window(self, reqs, next_beta_proofs=()):
        return list(reqs), list(dict.fromkeys(next_beta_proofs))

    def finish_window(self, st):
        reqs, proofs = st
        return (self._inner.verify_mixed(reqs),
                dict(zip(proofs, self._inner.vrf_betas_batch(proofs))))


class AllHold(Threaded):
    """Every proof "holds": for the replays that measure the collector
    and not the crypto.  Betas are the real ones (the state needs them)."""

    def finish_window(self, st):
        reqs, proofs = st
        return ([True] * len(reqs),
                dict(zip(proofs, self._inner.vrf_betas_batch(proofs))))


class NoCollector:
    """The replay's collector context patched out: the parent's
    behaviour."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def freeze(self):
        pass

    def lift(self):
        pass


def _engine(dba, chain_dir, tmp_path, backend, window, decode=None,
            interval=1, name="db"):
    d = str(tmp_path / name)
    shutil.copytree(chain_dir, d)
    db, rules, db_decode, _cfg = dba.load_db(d)
    GLOBAL_BETA_CACHE.clear()
    GLOBAL_PRECOMPUTE_CACHE.clear()
    return StreamingReplayEngine(
        IoFS(d), db, rules,
        decode(db_decode) if decode is not None else db_decode,
        backend=backend,
        config=StreamConfig(window=window, read_ahead=4, resume=False,
                            policy=DiskPolicy(
                                snapshot_interval_slots=interval)))


def _collector_state() -> tuple:
    return gc.isenabled(), gc.get_threshold(), list(gc.callbacks)


def _counters() -> dict:
    return {n: observe.REGISTRY.get(n).value for n in COUNTERS}


def _delta(c0: dict) -> dict:
    c1 = _counters()
    return {k: c1[k] - c0[k] for k in c1}


@pytest.fixture()
def found():
    """The collector as the test found it, compared after the test; a
    test that fails half way must not leave the suite's heap frozen."""
    state, frozen = _collector_state(), gc.get_freeze_count()
    try:
        yield state, frozen
    finally:
        gc.unfreeze()
    assert _collector_state() == state
    assert prefetcher_threads_alive() == 0


# -- (a) every exit path leaves the collector as found -----------------------

def _flip_a_witness(at_block: int):
    """A decoder whose block `at_block` carries a witness signature with
    one bit flipped: the backend's verdict stops the replay there."""
    import dataclasses

    from ouroboros_tpu.consensus.headers import ProtocolBlock

    def wrap(decode):
        seen = [0]

        def dec(raw):
            blk = decode(raw)
            if seen[0] == at_block:
                body = list(blk.body)
                (vk, sig), *rest = body[0].witnesses
                sig = bytearray(sig)
                sig[3] ^= 1
                body[0] = dataclasses.replace(
                    body[0], witnesses=((vk, bytes(sig)), *rest))
                blk = ProtocolBlock(blk.header, type(blk.body)(body))
            seen[0] += 1
            return blk
        return dec
    return wrap


def _decode_breaks(at_block: int):
    def wrap(decode):
        seen = [0]

        def dec(raw):
            seen[0] += 1
            if seen[0] == at_block:
                raise ValueError("decode broke")
            return decode(raw)
        return dec
    return wrap


class SnapshotKilled(Exception):
    pass


@pytest.mark.parametrize("path", ["clean", "invalid_block",
                                  "snapshot_hook_raises", "decode_error"])
def test_collector_is_as_found_on_every_exit_path(dba, light_chain, tmp_path,
                                                  found, path):
    state, frozen = found
    fault = {"invalid_block": _flip_a_witness(LIGHT_WINDOW + 3),
             "decode_error": _decode_breaks(LIGHT_WINDOW + 3)}.get(
                 path, lambda decode: decode)
    inside = []

    def probed(decode):
        dec = fault(decode)

        def probe(raw):
            inside.append((len(gc.callbacks), stream._COLLECTOR._depth))
            return dec(raw)
        return probe

    eng = _engine(dba, light_chain, tmp_path,
                  Threaded(dba.make_backend("cpp")), LIGHT_WINDOW, probed)
    if path == "snapshot_hook_raises":
        def killed(point, st):
            raise SnapshotKilled("killed in the snapshot hook")
        eng._take_snapshot = killed
    c0 = _counters()
    if path == "clean":
        res = eng.replay()
        assert res.all_valid and res.n_valid == LIGHT_BLOCKS
    elif path == "invalid_block":
        res = eng.replay()
        assert not res.all_valid and res.n_valid < LIGHT_BLOCKS
    elif path == "snapshot_hook_raises":
        with pytest.raises(SnapshotKilled):
            eng.replay()
    else:
        with pytest.raises(ValueError, match="decode broke"):
            eng.replay()
    assert _collector_state() == state
    assert gc.get_freeze_count() <= max(frozen, IMMORTALS)
    assert stream._COLLECTOR._depth == 0
    # and it was engaged while the replay ran: its callback installed,
    # chunks frozen
    assert inside and all(n == len(state[2]) + 1 and depth == 1
                          for n, depth in inside)
    assert _delta(c0)["replay.gc.freezes"] > 0


# -- (b) garbage made meanwhile by another thread dies after the replay --------

class Node:
    pass


def _make_cycle() -> weakref.ref:
    a, b = Node(), Node()
    a.other, b.other = b, a
    return weakref.ref(a)


def _cycle_on_another_thread(at_block: int, refs: list):
    """A decoder that, at block `at_block`, has another thread make a
    reference cycle and drop it (the prefetcher's next freeze then takes
    the garbage into the permanent generation)."""
    def wrap(decode):
        seen = [0]

        def dec(raw):
            seen[0] += 1
            if seen[0] == at_block:
                t = threading.Thread(
                    target=lambda: refs.append(_make_cycle()))
                t.start()
                t.join(timeout=30)
                assert not t.is_alive()
            return decode(raw)
        return dec
    return wrap


def test_a_cycle_made_on_another_thread_is_dead_after_the_replay(
        dba, light_chain, tmp_path, found):
    refs: list = []
    eng = _engine(dba, light_chain, tmp_path,
                  AllHold(dba.make_backend("cpp")), LIGHT_WINDOW,
                  _cycle_on_another_thread(LIGHT_BLOCKS - 2, refs))
    res = eng.replay()
    assert res.all_valid
    assert len(refs) == 1
    gc.collect()
    assert refs[0]() is None


# -- (c) the lift at each drained window bounds what a freeze caught -----------

def test_the_lift_at_a_drained_window_hands_frozen_garbage_back(
        dba, light_chain, tmp_path, found, monkeypatch):
    """A cycle made during window 0 is frozen with the chunk after it:
    no collection can free it.  The lift at window 0's drain empties the
    permanent generation, and the next full pass (the collector's own in
    a replay; the test's here) frees it before the replay ends."""
    refs: list = []
    eng = _engine(dba, light_chain, tmp_path,
                  AllHold(dba.make_backend("cpp")), LIGHT_WINDOW,
                  _cycle_on_another_thread(2, refs))
    seen = []
    real_lift = stream._COLLECTOR.lift

    def lift():
        gc.collect()
        alive = refs[0]() is not None
        before = gc.get_freeze_count()
        real_lift()
        seen.append((alive, before, gc.get_freeze_count()))

    monkeypatch.setattr(stream._COLLECTOR, "lift", lift)
    snapshots = []
    real_snapshot = eng._take_snapshot
    eng._take_snapshot = lambda point, st: (snapshots.append(point.slot),
                                            real_snapshot(point, st))
    res = eng.replay()
    assert res.all_valid
    n_windows = LIGHT_BLOCKS // LIGHT_WINDOW
    assert len(seen) == n_windows and len(snapshots) == n_windows
    alive, before, after = seen[0]
    # frozen garbage survives a full collection ...
    assert alive
    # ... the decoded read-ahead and the heap as found were frozen, and
    # the lift leaves nothing frozen
    assert before > 1000 and after == 0
    # ... so the collection at the next window's drain frees it
    assert not seen[1][0]


def test_freezing_resumes_after_the_collectors_next_full_pass(found):
    """The lift's other half, without threads: once lifted, `freeze()`
    does nothing until a full pass has seen the heap; the first freeze
    after that pass takes the whole heap back and is not counted."""
    collector = stream._COLLECTOR
    c0 = _counters()
    with collector:
        chunk = [[i] for i in range(3000)]
        collector.freeze()
        assert gc.get_freeze_count() > len(chunk)
        d = _delta(c0)
        assert d["replay.gc.freezes"] == 1
        assert len(chunk) <= d["replay.gc.frozen_objects"] < 2 * len(chunk)
        collector.lift()
        assert gc.get_freeze_count() == 0
        more = [[i] for i in range(3000)]
        collector.freeze()               # lifted, no full pass yet
        collector.lift()                 # already lifted
        assert gc.get_freeze_count() == 0
        gc.collect()                     # the collector's own, in a replay
        collector.freeze()
        assert gc.get_freeze_count() > len(chunk) + len(more)
        d = _delta(c0)
        assert d["replay.gc.freezes"] == 1
        assert d["replay.gc.frozen_objects"] < 2 * len(chunk)
        assert d["replay.gc.full_passes"] == 1
        del more[:]
        collector.freeze()               # counted again from here on
        assert _delta(c0)["replay.gc.freezes"] == 2
    assert gc.get_freeze_count() == 0


# -- (d) two replays at once -------------------------------------------------

def test_two_engines_replaying_at_once_leave_the_collector_as_found(
        dba, light_chain, tmp_path, found):
    state, frozen = found
    depths, results, errors = [], {}, []
    both_inside = threading.Barrier(2, timeout=60)

    def meet(decode):
        first = [True]

        def dec(raw):
            if first[0]:
                first[0] = False
                both_inside.wait()
                depths.append(stream._COLLECTOR._depth)
            return decode(raw)
        return dec

    def run(name):
        try:
            eng = _engine(dba, light_chain, tmp_path,
                          AllHold(dba.make_backend("cpp")), LIGHT_WINDOW,
                          meet, name=name)
            results[name] = eng.replay()
        except BaseException as e:     # read back below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(n,)) for n in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive()
    assert not errors, errors
    assert depths == [2, 2]
    hashes = {r.final_state.ledger.state_hash() for r in results.values()}
    assert len(results) == 2 and len(hashes) == 1
    assert all(r.all_valid and r.n_valid == LIGHT_BLOCKS
               for r in results.values())
    assert _collector_state() == state
    assert gc.get_freeze_count() <= max(frozen, IMMORTALS)
    assert stream._COLLECTOR._depth == 0


def test_depth_count_holds_under_contention(found):
    """More threads than cores entering, freezing, lifting and leaving
    with a short switch interval: a lost update of the depth count would
    leave the callback installed or the heap frozen."""
    state, frozen = found
    collector = stream._COLLECTOR
    errors = []

    def worker():
        try:
            for _ in range(200):
                with collector:
                    assert collector._depth >= 1
                    collector.freeze()
                    collector.lift()
                    collector.freeze()
        except BaseException as e:     # read back below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker)
                   for _ in range(2 * (os.cpu_count() or 4))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors
    assert collector._depth == 0
    assert _collector_state() == state
    assert gc.get_freeze_count() <= max(frozen, IMMORTALS)


# -- (e) nothing is skipped ---------------------------------------------------

def _validate(dba, chain_dir, tmp_path, name) -> dict:
    d = str(tmp_path / name)
    shutil.copytree(chain_dir, d)
    db, rules, decode, cfg = dba.load_db(d)
    GLOBAL_BETA_CACHE.clear()
    GLOBAL_PRECOMPUTE_CACHE.clear()
    out = io.StringIO()
    dba.analysis_validate(db, rules, decode, "cpp", "full", LIGHT_WINDOW,
                          out, hdr_proofs=dba.HEADER_PROOFS[cfg["protocol"]],
                          db_dir=d, snapshot_every=1)
    return json.loads(out.getvalue())


def test_same_verdict_as_with_the_context_patched_out(
        dba, light_chain, tmp_path, found, monkeypatch):
    c0 = _counters()
    with_it = _validate(dba, light_chain, tmp_path, "with")
    assert _delta(c0)["replay.gc.freezes"] > 0
    monkeypatch.setattr(stream, "_COLLECTOR", NoCollector())
    c0 = _counters()
    without = _validate(dba, light_chain, tmp_path, "without")
    assert _delta(c0)["replay.gc.freezes"] == 0
    for key in ("state_hash", "blocks", "proofs", "tip_slot"):
        assert with_it[key] == without[key], key
    assert with_it["blocks"] == LIGHT_BLOCKS
    assert with_it["stream"]["snapshots_written"] \
        == without["stream"]["snapshots_written"] > 0


# -- (f) what it is for: full passes over a chain of full blocks ---------------

class FullPasses:
    """The test's own count of generation-2 collections (the program's
    counter only runs inside its context, which one side patches out)."""

    def __init__(self):
        self.n = 0

    def __call__(self, phase, info):
        if phase == "stop" and info["generation"] == 2:
            self.n += 1


def _in_thread(db_decode):
    """The DB's decoder as a closure: one that no decode worker can be
    sent, so the prefetch thread runs it (storage/decode_pool.py)."""
    return lambda raw: db_decode(raw)


def _full_chain_replay(dba, full_chain, tmp_path, name, decode) -> tuple:
    """(full passes, counter deltas) of one replay of the full-bodied
    chain.  The heap as found is frozen first and one collection run, so
    both sides start from the same collector: nothing long-lived on its
    books, every threshold at zero."""
    eng = _engine(dba, full_chain, tmp_path,
                  AllHold(dba.make_backend("cpp")), FULL_WINDOW,
                  decode=decode, interval=1 << 40, name=name)
    passes = FullPasses()
    gc.freeze()
    gc.collect()
    gc.callbacks.append(passes)
    c0 = _counters()
    try:
        res = eng.replay()
    finally:
        gc.callbacks.remove(passes)
        gc.unfreeze()
    assert res.all_valid and res.n_valid == FULL_BLOCKS
    return passes.n, _delta(c0)


@pytest.mark.parametrize("decoded", ["in-thread", "in-workers"])
def test_fewer_full_passes_and_a_frozen_chain_on_full_blocks(
        dba, full_chain, tmp_path, found, monkeypatch, decoded):
    """Decoded on the prefetch thread, the walk's temporaries drive the
    collector into full passes over the chain, and the freeze takes
    them away.  Decoded in worker processes, the unpickled blocks alone
    allocate too little to reach a full pass on this chain with or
    without it; the chain is frozen all the same."""
    decode = _in_thread if decoded == "in-thread" else None
    n_with, d_with = _full_chain_replay(dba, full_chain, tmp_path, "with",
                                        decode)
    monkeypatch.setattr(stream, "_COLLECTOR", NoCollector())
    n_without, d_without = _full_chain_replay(dba, full_chain, tmp_path,
                                              "without", decode)
    assert d_without == dict.fromkeys(COUNTERS, 0)
    if decoded == "in-thread":
        assert n_with < n_without
    else:
        assert n_with <= n_without
    assert d_with["replay.gc.full_passes"] == n_with
    assert d_with["replay.gc.pause_us"] > 0
    # at least one object a transaction went into the permanent generation
    assert d_with["replay.gc.frozen_objects"] >= FULL_BLOCKS * FULL_TXS
    assert 0 < d_with["replay.gc.freezes"] <= FULL_BLOCKS


def test_little_to_freeze_on_one_transaction_blocks(
        dba, light_chain, tmp_path, found):
    eng = _engine(dba, light_chain, tmp_path,
                  AllHold(dba.make_backend("cpp")), LIGHT_WINDOW)
    c0 = _counters()
    res = eng.replay()
    assert res.all_valid
    d = _delta(c0)
    assert d["replay.gc.freezes"] > 0
    # the bypass: a light block brings far less than a full block's 352
    # transactions would (the full chain freezes over 600 a block)
    assert 0 < d["replay.gc.frozen_objects"] < FULL_TXS * LIGHT_BLOCKS


# -- (g) a prefetcher alone -----------------------------------------------------

def test_a_bare_prefetcher_never_freezes(dba, full_chain, found,
                                         monkeypatch):
    state, _frozen = found
    db, _rules, decode, _cfg = dba.load_db(full_chain)
    freezes = []
    monkeypatch.setattr(gc, "freeze", lambda: freezes.append(1))
    c0 = _counters()
    pre = BlockPrefetcher(db, decode, window=FULL_WINDOW, depth=2).start()
    try:
        n = sum(1 for _b in pre)
    finally:
        pre.close()
    assert n == FULL_BLOCKS
    assert not freezes
    assert _delta(c0) == dict.fromkeys(COUNTERS, 0)
    assert _collector_state() == state
