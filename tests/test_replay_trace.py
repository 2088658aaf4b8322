"""The streamed replay measured from inside (ISSUE 25): the stage spans
of decode, host pass and submit, the wait counters at the hand-offs, the
thread and window fields of a span, and the benchmark's readers of them.

One tiny Shelley chain on disk, streamed through the real three threads
(storage/stream.py prefetcher -> consensus/pipeline.py producer ->
caller) and the real `JaxBackend.submit_window` host path.  Only the two
device programs are stand-ins computed on the host (a window composite
costs minutes of XLA:CPU tracing, see test_replay_pipeline.py), so every
span of the submit path opens where the chip run opens it.
"""
import glob
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import threading

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytest.importorskip("jax")

from ouroboros_tpu import observe                                # noqa: E402
from ouroboros_tpu.consensus import pipeline                      # noqa: E402
from ouroboros_tpu.crypto import edwards, vrf_ref                 # noqa: E402
from ouroboros_tpu.crypto.backend import (                        # noqa: E402
    GLOBAL_BETA_CACHE, OpensslBackend,
)
from ouroboros_tpu.crypto.jax_backend import FOLD_SENT, JaxBackend  # noqa: E402
from ouroboros_tpu.crypto.precompute import (                     # noqa: E402
    GLOBAL_PRECOMPUTE_CACHE,
)
from ouroboros_tpu.observe import spans as spans_mod              # noqa: E402
from ouroboros_tpu.storage import (                               # noqa: E402
    DiskPolicy, IoFS, StreamConfig, StreamingReplayEngine,
)
from ouroboros_tpu.storage.decode_pool import WORKER_NAME          # noqa: E402
from ouroboros_tpu.storage.stream import THREAD_NAME as PREFETCH   # noqa: E402

PRODUCER = "ouro-replay-producer"
BLOCKS, WINDOW = 24, 8
N_WINDOWS = BLOCKS // WINDOW

# where a replay's blocks are decoded (ISSUE 32): `load_db`'s decoder
# ships to the decode worker processes, the same decoder as a closure
# stays on the prefetch thread
BOTH = pytest.mark.parametrize("traced", ["in-workers", "in-thread"],
                               indirect=True)
DECODE_STAGES = ("decode.parse", "decode.build", "decode.slices")

# table 1 of the issue: span -> (cat, parent, thread); the three decode
# stages are on a worker's row where a worker timed them
STAGES = {
    "decode.parse": ("disk", "stream.decode", PREFETCH),
    "decode.build": ("disk", "stream.decode", PREFETCH),
    "decode.slices": ("disk", "stream.decode", PREFETCH),
    "seq.header": ("host-seq", "window.host_seq", PRODUCER),
    "seq.body": ("host-seq", "window.host_seq", PRODUCER),
    "body.tick": ("host-seq", "seq.body", PRODUCER),
    "body.checks": ("host-seq", "seq.body", PRODUCER),
    "body.extract": ("host-seq", "seq.body", PRODUCER),
    "body.reapply": ("host-seq", "seq.body", PRODUCER),
    "submit.split": ("dispatch", "window.submit", PRODUCER),
    "submit.pack_ed": ("dispatch", "window.submit", PRODUCER),
    "pack_ed.challenge": ("dispatch", "submit.pack_ed", PRODUCER),
    "submit.pack_vrf": ("dispatch", "window.submit", PRODUCER),
    "submit.pack_kes": ("dispatch", "window.submit", PRODUCER),
    "submit.dispatch": ("dispatch", "window.submit", PRODUCER),
    "submit.ed_tiles": ("dispatch", "submit.dispatch", PRODUCER),
    "submit.fold": ("dispatch", "window.submit", PRODUCER),
    "pipeline.beta_prefetch": ("device", None, PRODUCER),
}
BODY_STAGES = ("body.tick", "body.checks", "body.extract", "body.reapply")
WAITS = ("pipeline.producer_wait_blocks_us", "pipeline.consumer_wait_us",
         "pipeline.first_submit_us")
# the spans that read their thread's CPU clock too (ISSUE 36), and the
# thread each is on
CPU_SPANS = {"window.host_seq": PRODUCER, "window.submit": PRODUCER,
             "decode.unpack": PREFETCH, "stream.read": PREFETCH,
             "stream.snapshot": None}           # None: the caller's
THREADS = ("prefetch", "producer", "caller")


class HostProgramsBackend(JaxBackend):
    """JaxBackend with its three device programs (Ed25519 tile, window
    composite, fold) computed on the host by the OpenSSL reference.
    `submit_window`, `_submit_window`, the split, the three packers, the
    tiles' copy, `_fold_owners` and `_attach_fold` are the
    real ones; so is `finish_window`."""

    def __init__(self):
        super().__init__(min_bucket=16, use_pallas=False, autotune=False)
        self._cpu = OpensslBackend()
        self._asked = ([], [])
        self.vrf_betas_batch = self._cpu.vrf_betas_batch

    def submit_window(self, reqs, next_beta_proofs=(), fold=False):
        self._asked = (list(reqs), list(dict.fromkeys(next_beta_proofs)))
        return super().submit_window(reqs, next_beta_proofs, fold)

    def _ed_tile_program(self, fold):
        return lambda bad, _own, *_lanes: bad

    def _window_composite(self, nv, nb, nk):
        return lambda *_args: self._asked

    def _fold_program(self, nv, nb, nk):
        def fold(asked, _ed_bad, _vrf_own, _gamma_b, _c_b):
            reqs, proofs = asked
            ok = self._cpu.verify_mixed(reqs)
            bad = ok.index(False) if False in ok else FOLD_SENT
            rows = np.zeros((nb, 33), np.uint8)
            for j, pi in enumerate(proofs):
                gamma = vrf_ref.decode_proof(pi)[0]
                rows[j, :32] = np.frombuffer(edwards.compress(
                    edwards.scalar_mult(8, gamma)), np.uint8)
                rows[j, 32] = 1
            return np.concatenate([
                np.frombuffer(bad.to_bytes(4, "little"), np.uint8),
                np.ones(nk, np.uint8), rows.reshape(-1)])
        return fold


@pytest.fixture(scope="module")
def chain_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tracedb"))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", d, "--protocol", "shelley", "--blocks", str(BLOCKS),
         "--txs-per-block", "3", "--pools", "2", "--f", "4/5",
         "--epoch-length", "500", "--kes-depth", "4", "--chunk-size", "6"],
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    return d


@pytest.fixture()
def host_tables(monkeypatch):
    """The per-key tables are a device fill too (`a128_words_kernel`):
    a stand-in marks every key known, and the global caches this replay
    fills with it are cleared after (the KES outcomes put back)."""
    from ouroboros_tpu.crypto import ed25519_jax as EJ

    def a128(Aw, _signA):
        n = np.asarray(Aw).shape[1]
        return np.zeros((24, n), np.uint32), np.ones(n, bool)

    monkeypatch.setattr(EJ, "a128_words_kernel", a128)
    cache = GLOBAL_PRECOMPUTE_CACHE
    saved = cache._kes.copy()   # point tables refill on demand
    try:
        yield
    finally:
        cache.clear()
        cache._kes.update(saved)


def _counters() -> dict:
    return {i.name: i.value for i in observe.REGISTRY.instruments()
            if i.kind == "counter"}


def _replay(chain_dir, db_dir, backend, decoded="in-workers"):
    """One streamed replay as `db_analyser --analysis validate` makes it
    (cold caches, snapshots on); (stats, state hash, counter deltas)."""
    from tools import db_analyser
    shutil.copytree(chain_dir, db_dir)
    db, rules, db_decode, _cfg = db_analyser.load_db(db_dir)
    decode = db_decode if decoded == "in-workers" \
        else (lambda raw: db_decode(raw))
    GLOBAL_BETA_CACHE.clear()
    GLOBAL_PRECOMPUTE_CACHE.clear()
    engine = StreamingReplayEngine(
        IoFS(db_dir), db, rules, decode, backend=backend,
        config=StreamConfig(window=WINDOW, read_ahead=4, resume=False,
                            policy=DiskPolicy(snapshot_interval_slots=10)))
    c0 = _counters()
    res = engine.replay()
    c1 = _counters()
    assert res.all_valid, res.error
    assert res.n_valid == BLOCKS
    return (res.stats, res.final_state.ledger.state_hash(),
            {k: c1[k] - c0.get(k, 0) for k in c1})


@pytest.fixture()
def traced(request, chain_dir, tmp_path, host_tables):
    """(roots, stats, state hash, counter deltas) of one replay with
    span recording on; decoded in the workers unless a test asks
    (`BOTH`) for the prefetch thread too."""
    rec = spans_mod.RECORDER
    assert not rec.enabled
    rec.drain()
    rec.enable()
    try:
        stats, state_hash, delta = _replay(
            chain_dir, str(tmp_path / "on"), HostProgramsBackend(),
            getattr(request, "param", "in-workers"))
    finally:
        rec.disable()
    return rec.drain(), stats, state_hash, delta


def _adopted(sp) -> bool:
    """Timed by a decode worker process, on this process's clock."""
    return sp.thread.startswith(WORKER_NAME)


def _with_parents(roots):
    """(span, parent or None) over a forest."""
    def walk(sp, parent):
        yield sp, parent
        for c in sp.children:
            yield from walk(c, sp)
    for r in roots:
        yield from walk(r, None)


@BOTH
def test_every_stage_span_under_its_parent_on_its_thread(traced):
    roots, _stats, _hash, delta = traced
    in_workers = delta["replay.decode.worker_blocks"] > 0
    assert delta["replay.decode.worker_blocks"] in (0, BLOCKS)
    pairs = list(_with_parents(roots))
    by_name: dict = {}
    for sp, parent in pairs:
        by_name.setdefault(sp.name, []).append((sp, parent))
    for name, (cat, parent_name, thread) in STAGES.items():
        assert name in by_name, f"no span {name}"
        for sp, parent in by_name[name]:
            assert sp.cat == cat
            if in_workers and name in DECODE_STAGES:
                assert _adopted(sp)
            else:
                assert sp.thread == thread
            assert (parent.name if parent else None) == parent_name
    # the reply read and turned into blocks, on the prefetch thread:
    # once a chunk whose reply was waiting whole, and once more for a
    # look that found it not there yet (`Lease.collect` reads first)
    unpacks = by_name.get("decode.unpack", [])
    chunks = len(by_name["stream.decode"]) if in_workers else 0
    assert chunks <= len(unpacks) <= 2 * chunks
    for sp, parent in unpacks:
        assert (sp.cat, sp.thread, parent.name) \
            == ("disk", PREFETCH, "stream.decode")
    # how many: one a block, one a window, one a replay (seq.* open more
    # than once a block: the statements keep their order)
    for name in DECODE_STAGES + BODY_STAGES:
        assert len(by_name[name]) == BLOCKS, name
    assert len(by_name["seq.header"]) == 3 * BLOCKS
    assert len(by_name["seq.body"]) == 2 * BLOCKS
    for name in ("submit.split", "pack_ed.challenge",
                 "submit.pack_vrf",
                 "submit.pack_kes", "submit.dispatch", "submit.ed_tiles",
                 "submit.fold",
                 "window.submit", "window.host_seq", "pipeline.drain"):
        assert len(by_name[name]) == N_WINDOWS, name
    # the Ed25519 packer in two pieces a window: the lanes packed beside
    # the new keys' fill, then the key tables collected (PR 46)
    assert len(by_name["submit.pack_ed"]) == 2 * N_WINDOWS
    assert len(by_name["pipeline.beta_prefetch"]) == 1
    # the spans the benchmark already read keep name, cat and thread
    caller = threading.current_thread().name
    for name, cat, thread in (
            ("stream.read", "disk", PREFETCH),
            ("stream.decode", "disk", PREFETCH),
            ("window.host_seq", "host-seq", PRODUCER),
            ("window.submit", "dispatch", PRODUCER),
            ("pipeline.drain", "device", caller),
            ("stream.snapshot", "disk", caller)):
        assert {(sp.cat, sp.thread) for sp, _p in by_name[name]} \
            == {(cat, thread)}, name
    # no span of cat compile: the benchmark counts those in its window
    assert not [sp for sp, _p in pairs if sp.cat == "compile"]


@BOTH
def test_children_never_longer_than_their_parent(traced):
    """But for the spans a worker timed: they hang under the
    `stream.decode` that collected their chunk and lie where the worker
    did the work, ahead of it, on the same clock: inside the replay."""
    roots, _stats, _hash, _delta = traced
    own = [sp for r in roots for sp in r.walk() if not _adopted(sp)]
    start, end = min(sp.t0 for sp in own), max(sp.t1 for sp in own)
    for sp, parent in _with_parents(roots):
        assert sp.t1 is not None and sp.t1 >= sp.t0
        if _adopted(sp):
            assert sp.name in DECODE_STAGES and not sp.children
            assert start <= sp.t0 and sp.t1 <= parent.t1 <= end
        elif parent is not None:
            assert parent.t0 <= sp.t0 and sp.t1 <= parent.t1
    for root in roots:
        for sp in root.walk():
            assert sum(c.duration for c in sp.children
                       if not _adopted(c)) <= sp.duration + 1e-9


def test_a_fill_holds_its_four_stages_in_order(traced):
    """`precompute.fill` is making tables for missed keys, whoever asks
    (a packer of `window.submit`, the beta prefetch): packing the keys
    and storing the tables on the host, dispatch and fetch the device's
    side, in the two phases of a fill, and the counters beside it count
    keys and lanes."""
    roots, _stats, _hash, delta = traced
    fills = [sp for r in roots for sp in r.walk()
             if sp.name == "precompute.fill"]
    # two spans a fill since PR 46, one a phase: the begin packs and
    # dispatches, the finish fetches and stores, and the caller's own
    # work may lie between them
    assert fills and len(fills) == 2 * delta["precompute.device_fills"]
    halves = {(("fill.pack", "device"), ("fill.dispatch", "device")): 0,
              (("fill.fetch", "device"), ("fill.store", "device")): 0}
    for sp in fills:
        assert sp.cat == "device" and sp.thread == PRODUCER
        halves[tuple((c.name, c.cat) for c in sp.children)] += 1
    assert set(halves.values()) == {delta["precompute.device_fills"]}
    # the window's Ed25519 keys are begun ahead of the lanes' hashing
    assert 0 < delta["precompute.early_fill_keys"] \
        <= delta["precompute.filled_keys"]
    # `misses` counts the KES hash paths that missed too
    assert 0 < delta["precompute.filled_keys"] <= delta["precompute.misses"]
    assert delta["precompute.filled_keys"] \
        <= delta["precompute.fill_lanes_padded"]
    assert delta["precompute.fill_lanes_padded"] % 128 == 0


def test_window_index_on_both_threads(traced):
    roots, _stats, _hash, _delta = traced
    spans = [sp for r in roots for sp in r.walk()]
    for name in ("window.host_seq", "pipeline.drain"):
        got = [sp.meta["window"] for sp in spans if sp.name == name]
        assert got == list(range(N_WINDOWS)), name
    # only those two carry it
    assert {sp.name for sp in spans if sp.meta} \
        == {"window.host_seq", "pipeline.drain"}


def test_wait_counters_count_and_first_submit_rises_once(
        chain_dir, tmp_path, host_tables, monkeypatch):
    """Each counter gets what its hand-off measured; `first_submit_us`
    is written once a replay, the other two once a `next_window()` and
    once a wait of the caller's thread."""
    class Spy:
        def __init__(self, inst, seen):
            self.inst, self.seen = inst, seen

        def inc(self, n=1):
            self.seen.append(n)
            self.inst.inc(n)

    incs: dict = {n: [] for n in WAITS}
    for attr, name in (("_WAIT_BLOCKS_US", WAITS[0]),
                       ("_CONSUMER_WAIT_US", WAITS[1]),
                       ("_FIRST_SUBMIT_US", WAITS[2])):
        inst = getattr(pipeline, attr)
        assert inst.name == name and inst.kind == "counter"
        assert not inst.always and not inst.stable
        monkeypatch.setattr(pipeline, attr, Spy(inst, incs[name]))
    _stats, _hash, delta = _replay(chain_dir, str(tmp_path / "db"),
                                   HostProgramsBackend())
    for name in WAITS:
        assert delta[name] == sum(incs[name]) > 0, name
        assert all(isinstance(n, int) and n >= 0 for n in incs[name])
    assert len(incs["pipeline.first_submit_us"]) == 1
    # three windows read ahead, then one more pull a window
    assert len(incs["pipeline.producer_wait_blocks_us"]) == 3 + N_WINDOWS
    assert len(incs["pipeline.consumer_wait_us"]) == N_WINDOWS + 1
    # the head of the replay holds the whole wait for the first blocks
    assert incs["pipeline.first_submit_us"][0] \
        >= incs["pipeline.producer_wait_blocks_us"][0]


def test_recording_off_allocates_no_span_and_counters_still_count(
        chain_dir, tmp_path, host_tables, traced, monkeypatch):
    _roots, _stats, hash_on, _delta = traced
    made = []
    real_init = spans_mod.Span.__init__

    def counting_init(self, *a, **kw):
        made.append(a[0])
        real_init(self, *a, **kw)

    monkeypatch.setattr(spans_mod.Span, "__init__", counting_init)
    # nor does a worker time anything, or ship a row of it
    monkeypatch.setattr(spans_mod, "adopt",
                        lambda *a, **kw: made.append("adopt"))
    assert not spans_mod.RECORDER.enabled
    _stats, hash_off, delta = _replay(chain_dir, str(tmp_path / "off"),
                                      HostProgramsBackend())
    assert delta["replay.decode.worker_blocks"] == BLOCKS
    assert made == []
    assert spans_mod.RECORDER.drain() == []
    for name in WAITS:
        assert delta[name] > 0, name
    # recording changes nothing the replay computes
    assert hash_off == hash_on


def test_chrome_trace_of_a_replay_has_a_row_per_thread(traced):
    roots, _stats, _hash, _delta = traced
    doc = observe.export.chrome_trace(roots)
    rows = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
            if e["ph"] == "M"}
    workers = {n for n in rows.values() if n.startswith(WORKER_NAME)}
    assert workers and set(rows.values()) - workers == {
        PREFETCH, PRODUCER, threading.current_thread().name}
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {rows[e["tid"]] for e in events
            if e["name"] in DECODE_STAGES} == workers
    assert {rows[e["tid"]] for e in events
            if e["name"] == "decode.unpack"} == {PREFETCH}
    assert {rows[e["tid"]] for e in events
            if e["name"].startswith(("seq.", "submit."))} == {PRODUCER}
    drains = [e for e in events if e["name"] == "pipeline.drain"]
    assert [e["args"]["window"] for e in drains] == list(range(N_WINDOWS))
    assert {e["cat"] for e in drains} == {"device"}
    # the operator's view of a `cpu=True` span: its milliseconds on the
    # CPU and off it, which add up to its length; no other span has them
    with_cpu = [e for e in events if "cpu_ms" in e.get("args", {})]
    assert {e["name"] for e in with_cpu} == set(CPU_SPANS)
    for e in with_cpu:
        a = e["args"]
        assert a["cpu_ms"] >= 0 and 0 <= a["off_cpu_ms"] <= e["dur"] / 1e3
        if a["off_cpu_ms"]:
            assert a["cpu_ms"] + a["off_cpu_ms"] \
                == pytest.approx(e["dur"] / 1e3, abs=2e-3)
    hosts = [e for e in with_cpu if e["name"] == "window.host_seq"]
    assert [e["args"]["window"] for e in hosts] == list(range(N_WINDOWS))


@BOTH
def test_cpu_spans_carry_cpu_seconds_and_feed_their_counter(traced):
    """The five `cpu=True` spans of a replay (ISSUE 36) and no other
    carry `cpu`; what each name's spans were off the CPU is what its
    counter holds, to the microsecond a span."""
    roots, _stats, _hash, delta = traced
    in_workers = delta["replay.decode.worker_blocks"] > 0
    spans = [sp for r in roots for sp in r.walk()]
    caller = threading.current_thread().name
    want = {n: t or caller for n, t in CPU_SPANS.items()
            if in_workers or n != "decode.unpack"}
    assert {sp.name for sp in spans if sp.cpu is not None} == set(want)
    for name, thread in want.items():
        mine = [sp for sp in spans if sp.name == name]
        assert all(sp.cpu is not None and sp.cpu >= 0
                   and sp.thread == thread for sp in mine), name
        assert all(0 <= sp.off_cpu <= sp.duration for sp in mine)
        off_us = sum(sp.off_cpu for sp in mine) * 1e6
        got = delta["span.off_cpu_us." + name]
        assert off_us - len(mine) <= got <= off_us + 1e-3, name
        assert got <= sum(sp.duration for sp in mine) * 1e6
    assert not any(sp.cpu is not None for sp in spans if _adopted(sp))


def test_thread_readings_rise_once_a_replay_with_recording_off(
        chain_dir, tmp_path, host_tables, monkeypatch):
    """Each of the three threads adds its CPU microseconds and its
    preempt count once a replay, span recording off, and no thread's
    CPU time is longer than the replay."""
    names = [f"replay.thread_{kind}.{t}" for t in THREADS
             for kind in ("cpu_us", "preempts")]
    for name in names:
        inst = observe.REGISTRY.get(name)
        assert inst.kind == "counter" and not inst.always \
            and not inst.stable, name
    read = []
    real_preempts = spans_mod._preempts

    def counting_preempts():
        read.append(threading.current_thread().name)
        return real_preempts()

    monkeypatch.setattr(spans_mod, "_preempts", counting_preempts)
    assert not spans_mod.RECORDER.enabled
    stats, _hash, delta = _replay(chain_dir, str(tmp_path / "db"),
                                  HostProgramsBackend())
    # two readings a thread: where its part of the replay starts and ends
    assert sorted(read) == sorted(
        2 * [PREFETCH, PRODUCER, threading.current_thread().name])
    for name in names:
        assert isinstance(delta[name], int) and delta[name] >= 0, name
    for thread in THREADS:
        # `replay_secs` starts after the restore and ends before the
        # tip checkpoint, both of them the caller's: a second of room
        assert 0 < delta["replay.thread_cpu_us." + thread] \
            <= (stats["replay_secs"] + 1.0) * 1e6, thread
    assert not [k for k in delta if k.startswith("span.off_cpu_us.")
                and delta[k]]


# -- the benchmark's readers of these spans and counters ----------------------

BENCH = os.path.join(REPO, "benchmarks")
NEW_METRICS = (
    "decode_parse_us_per_block", "decode_build_us_per_block",
    "decode_slices_us_per_block", "decode_mb_per_s", "read_ms_per_chunk",
    "host_header_us_per_block", "host_body_us_per_block",
    "producer_wait_blocks_ms_per_window", "submit_pack_ms_per_window",
    "submit_dispatch_ms_per_window", "consumer_wait_ms_per_window",
    "first_submit_share", "decode_one_walk_share")
# the cyclic collector during a replay (ISSUE 28), listed after PR 27's;
# a tiny replay may see no collection at all, so these may read 0
GC_METRICS = ("gc_pause_us_per_block", "gc_full_passes_per_replay",
              "gc_frozen_objects_per_block")
# the per-key table fill (ISSUE 31), listed after PR 30's
KEY_METRICS = ("key_fill_ms_per_window", "key_fill_us_per_key",
               "key_fill_host_share", "key_fill_pad_share",
               "key_cache_hit_share")
# the decode worker processes (ISSUE 32), listed after PR 31's; the
# wait may read 0 (every reply there before it was asked for)
WORKER_METRICS = ("decode_worker_share", "decode_unpack_us_per_block",
                  "decode_wait_us_per_block")
# the Ed25519 packer's challenge stage (ISSUE 35), listed after PR 33's
CHALLENGE_METRICS = ("ed_challenge_ms_per_window",
                     "ed_challenge_native_share")
# the host chain from inside (ISSUE 36), listed after PR 35's: the ledger
# pass in four stages, five spans' time off the CPU, the three threads'
# time on it.  A tiny replay may read 0 off the CPU in a span and no
# preempt at all
HOSTCHAIN_METRICS = (
    "body_tick_us_per_block", "body_checks_us_per_block",
    "body_extract_us_per_block", "body_reapply_us_per_block",
    "host_seq_off_cpu_share", "submit_off_cpu_share",
    "decode_unpack_off_cpu_share", "read_off_cpu_share",
    "snapshot_off_cpu_share", "producer_on_cpu_share",
    "prefetch_on_cpu_share", "caller_on_cpu_share", "host_busy_cores",
    "host_preempts_per_replay")
# the ledger walk's transaction counters (ISSUE 37), listed after PR 36's
LEDGER_TX_METRICS = ("body_light_tx_share", "host_body_us_per_tx")
MAY_READ_ZERO = GC_METRICS[:2] + WORKER_METRICS[2:] + tuple(
    m for m in HOSTCHAIN_METRICS
    if m.endswith("_off_cpu_share") or m == "host_preempts_per_replay")


def _facts(roots, stats, delta) -> dict:
    """Facts as `gather_facts` in benchmarks/run.py gathers them from
    one replay: span seconds and counts by name, counter deltas, the
    window's totals."""
    span_seconds: dict = {}
    span_count: dict = {}
    for root in roots:
        for sp in root.walk():
            span_seconds[sp.name] = span_seconds.get(sp.name, 0.0) \
                + sp.duration
            span_count[sp.name] = span_count.get(sp.name, 0) + 1
    return {
        "window": {"replays": 1, "blocks": BLOCKS,
                   "windows": delta["jax_backend.windows_submitted"],
                   "replay_seconds": stats["replay_secs"]},
        "span_seconds": span_seconds, "span_count": span_count,
        "counter": delta,
        "stream": {k: v for k, v in stats.items()
                   if isinstance(v, (int, float))},
    }


def _reader_module():
    spec = importlib.util.spec_from_file_location(
        "bench_readers", os.path.join(BENCH, "harness", "readers.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_new_metric_files_are_these():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        listed = [m["name"] for m in json.load(fh)["per_layer"]]
    # in this order and together; later PRs' metrics follow them
    for group in (NEW_METRICS, GC_METRICS, KEY_METRICS, WORKER_METRICS,
                  CHALLENGE_METRICS, HOSTCHAIN_METRICS, LEDGER_TX_METRICS):
        at = listed.index(group[0])
        assert listed[at:at + len(group)] == list(group)
    files = {os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(BENCH, "layer_metrics", "*.json"))}
    assert files == set(listed)


@pytest.mark.parametrize("metric", NEW_METRICS + GC_METRICS + KEY_METRICS
                         + WORKER_METRICS + CHALLENGE_METRICS
                         + HOSTCHAIN_METRICS + LEDGER_TX_METRICS)
def test_layer_metric_reader_resolves_on_a_tiny_replay(traced, metric):
    """A renamed span or counter fails here, not in a chip run."""
    roots, stats, _hash, delta = traced
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as fh:
        doc = json.load(fh)
    facts = _facts(roots, stats, delta)
    for ns, key in doc["reader"]["num"] + doc["reader"].get("den", []):
        assert key in facts[ns], f"{metric}: no fact {ns}/{key}"
    value = _reader_module().read(doc["reader"], facts)
    assert value is not None and value >= 0
    assert value > 0 or metric in MAY_READ_ZERO
    if metric.endswith("_share"):
        assert value <= 100.0
    if metric in ("decode_worker_share", "ed_challenge_native_share",
                  "body_light_tx_share"):
        assert value == 100.0
    source = {"span_seconds": "program_span",
              "counter": "program_counter"}[doc["reader"]["num"][0][0]]
    assert doc["source"] == source


@pytest.mark.parametrize("traced", ["in-thread"], indirect=True)
@pytest.mark.parametrize("metric", HOSTCHAIN_METRICS)
def test_hostchain_reader_with_the_decode_on_the_prefetch_thread(traced,
                                                                 metric):
    """The same fourteen where no decode worker is used: no reply is
    unpickled, so that one share reads nothing, never a made-up number;
    the prefetch thread's CPU time now holds the decode."""
    roots, stats, _hash, delta = traced
    assert delta["replay.decode.worker_blocks"] == 0
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as fh:
        reader = json.load(fh)["reader"]
    value = _reader_module().read(reader, _facts(roots, stats, delta))
    if metric == "decode_unpack_off_cpu_share":
        assert value is None
        return
    assert value is not None and value >= 0
    assert value > 0 or metric in MAY_READ_ZERO
    if metric.endswith("_share"):
        assert value <= 100.0


@BOTH
def test_stages_never_exceed_their_outer_span(traced):
    """The reconciliation PERF.md makes on the chip, as far as a tiny
    chain on a shared CPU can hold it: the stages of decode, of the host
    pass and of submit are all inside the span around them, so they add
    up to no more than it.  Decoded in the workers, `stream.decode`
    holds the unpacking and the wait, and the three stages are the
    workers' own seconds, side by side."""
    roots, stats, _hash, delta = traced
    facts = _facts(roots, stats, delta)
    sec = facts["span_seconds"]
    in_workers = delta["replay.decode.worker_blocks"] > 0
    if not in_workers:
        for m in WORKER_METRICS[1:]:
            with open(os.path.join(BENCH, "layer_metrics",
                                   m + ".json")) as fh:
                reader = json.load(fh)["reader"]
            # nothing to read, or nothing read: never a made-up number
            assert not _reader_module().read(reader, facts)
    for outer, stages in (
            ("stream.decode", ("decode.unpack",) if in_workers
             else DECODE_STAGES),
            ("window.host_seq", ("seq.header", "seq.body")),
            ("seq.body", BODY_STAGES),
            ("window.submit", ("submit.split", "submit.pack_ed",
                               "submit.pack_vrf", "submit.pack_kes",
                               "submit.dispatch", "submit.fold"))):
        assert 0 < sum(sec[s] for s in stages) <= sec[outer], outer
