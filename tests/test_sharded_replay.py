"""The served replay over a four-device mesh, held to the plain
reference (ISSUE 27): `analysis_validate` over
`ShardedJaxBackend(make_mesh(4))` on a small forged Shelley chain, at
the sizes of `benchmarks/configs/shelley-sync-4chip.json`'s `rehearse`
block (16 blocks, windows of 8, one transaction a block, `min_bucket`
16), on four of the conftest's virtual CPU devices.

Three things: the mesh replay's verdict equals the `cpp` reference's
and the one-chip `JaxBackend`'s; a bad lane in any shard (or in two)
stops the replay where the reference stops, so the folded first-bad
index is the minimum over the shards; and the span and counters the
mesh path adds close and count what they should.

The first test of the file pays the XLA:CPU compile of the sharded
composite and of the one-chip composite (minutes cold, seconds from
the compile cache); every other test reuses the two backends.
"""
import dataclasses
import importlib.util
import io
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

# the mesh's and the one-chip backend's window programs: minutes of
# XLA:CPU compile, so conftest.py starts this file first
pytestmark = pytest.mark.device

from ouroboros_tpu.consensus.headers import ProtocolBlock      # noqa: E402
from ouroboros_tpu.crypto.backend import (                     # noqa: E402
    GLOBAL_BETA_CACHE, Ed25519Req,
)
from ouroboros_tpu.crypto.jax_backend import JaxBackend        # noqa: E402
from ouroboros_tpu.crypto.precompute import (                  # noqa: E402
    GLOBAL_PRECOMPUTE_CACHE,
)
from ouroboros_tpu.observe import metrics as metrics_mod       # noqa: E402
from ouroboros_tpu.observe import spans as spans_mod           # noqa: E402
from ouroboros_tpu.parallel import ShardedJaxBackend, make_mesh  # noqa: E402
from tools import db_analyser as dba                           # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCKS, WINDOW, SHARDS, SEED = 16, 8, 4, 2741
N_WINDOWS = BLOCKS // WINDOW
# a block here is 2 VRF proofs, 1 KES signature (its Ed25519 leaf on the
# device, its hash path on the host), 1 OCert signature and 1 witness
ED_LANES, VRF_LANES = 3 * WINDOW, 2 * WINDOW
# a shard's tile is min_bucket // SHARDS = 4 lanes off an accelerator, so
# a window's 24 Ed25519 lanes go as two tiles of 16 lanes over the mesh
ED_TILE_LANES = 16
ED_WALKED = 2 * ED_TILE_LANES
MESH_COUNTERS = ("jax_backend.shard_put_bytes",
                 "jax_backend.shard_lanes_padded",
                 "precompute.kes_host_walks")


def _counters() -> dict:
    return {i.name: i.value for i in metrics_mod.REGISTRY.instruments()
            if getattr(i, "kind", "") == "counter"}


def _clear_caches() -> None:
    GLOBAL_BETA_CACHE.clear()
    GLOBAL_PRECOMPUTE_CACHE.clear()


def _forge(tmp_path_factory, name: str, *extra: str) -> tuple:
    d = str(tmp_path_factory.mktemp(name) / "chain")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", d, "--blocks", str(BLOCKS), "--seed", str(SEED),
         "--protocol", "shelley", "--pools", "2", "--f", "1/20",
         "--epoch-length", "432000", "--kes-depth", "6",
         "--txs-per-block", "1", *extra],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    return (d, *dba.load_db(d))


@pytest.fixture(scope="module")
def chain(tmp_path_factory):
    """(directory, db, rules, decode, cfg) of the seeded chain, forged
    with the rehearse block's `synth` arguments."""
    return _forge(tmp_path_factory, "mesh-chain")


@pytest.fixture(scope="module")
def fresh_chain(tmp_path_factory):
    """The same chain with `--witness-keys fresh` (the rehearse block of
    `shelley-sync-1chip-freshkeys.json`): the same counts of blocks,
    transactions and proofs, so the same window shapes and programs."""
    return _forge(tmp_path_factory, "fresh-chain",
                  "--witness-keys", "fresh")


def _validate(chain, backend, decode=None, cold: bool = True) -> dict:
    """One replay as `db_analyser --analysis validate --validate full`
    makes it, from cold key caches unless told otherwise; the program's
    line.  SystemExit where it rejects a block."""
    d, db, rules, db_decode, cfg = chain
    if cold:
        _clear_caches()
    out = io.StringIO()
    dba.analysis_validate(
        db, rules, decode or db_decode, backend, "full", WINDOW, out,
        hdr_proofs=dba.HEADER_PROOFS[cfg["protocol"]], db_dir=d,
        snapshot_every=100)
    return json.loads(out.getvalue())


@pytest.fixture(scope="module")
def mesh_backend():
    if len(jax.devices()) < SHARDS:
        pytest.skip(f"needs {SHARDS} XLA devices (conftest forces 8)")
    return ShardedJaxBackend(make_mesh(SHARDS), min_bucket=16)


@pytest.fixture(scope="module")
def one_chip_backend():
    return JaxBackend(min_bucket=16, use_pallas=False, autotune=False)


@pytest.fixture(scope="module")
def reference_backend():
    return dba.make_backend("cpp" if shutil.which("g++") else "openssl")


@pytest.fixture(scope="module")
def lines(chain, mesh_backend, reference_backend, one_chip_backend):
    """The replay's line by backend, and what the mesh's counters read
    while the OTHER backends replayed (nothing, or the path leaked)."""
    out = {"mesh": _validate(chain, mesh_backend)}
    c0 = _counters()
    out["cpp"] = _validate(chain, reference_backend)
    out["one-chip"] = _validate(chain, one_chip_backend)
    c1 = _counters()
    out["leak"] = {k: c1[k] - c0.get(k, 0) for k in MESH_COUNTERS}
    return out


# -- the mesh replay against the reference and the one-chip backend ---------

@pytest.mark.parametrize("what", ["state_hash", "blocks", "proofs"])
@pytest.mark.parametrize("other", ["cpp", "one-chip"])
def test_mesh_replay_equals(lines, other, what):
    assert lines["mesh"][what] == lines[other][what]


def test_mesh_replay_line_names_the_mesh(lines):
    line = lines["mesh"]
    assert line["blocks"] == BLOCKS and line["proofs"] == 5 * BLOCKS
    assert line["backend_name"] == "jax-mesh-4"
    assert line["device_count"] == SHARDS
    assert line["stream"]["snapshots_written"] >= 1


@pytest.mark.parametrize("name", MESH_COUNTERS)
def test_other_backends_never_count_on_the_mesh_counters(lines, name):
    assert lines["leak"][name] == 0


# -- the share tied to the whole: a bad lane in each shard ------------------

def _flip_witness(blk):
    """The block with one bit of its first transaction's witness
    signature flipped (in the decoded block: a byte flipped on disk may
    be caught by the decoder, which proves nothing about the backend)."""
    body = list(blk.body)
    (vk, sig), *rest = body[0].witnesses
    bad = bytearray(sig)
    bad[3] ^= 1
    body[0] = dataclasses.replace(
        body[0], witnesses=((vk, bytes(bad)), *rest))
    return ProtocolBlock(blk.header, type(blk.body)(body)), bytes(bad)


def _tampering_decode(decode, at_blocks, flipped: list):
    seen = [0]

    def dec(raw: bytes):
        blk = decode(raw)
        if seen[0] in at_blocks:
            blk, sig = _flip_witness(blk)
            flipped.append(sig)
        seen[0] += 1
        return blk
    return dec


def _stop(chain, backend, at_blocks, flipped=None) -> tuple:
    """Where and why the replay of the tampered chain stopped: (blocks
    accepted, kind of proof that failed), from the program's own words
    (the threaded and the synchronous driver name the block's slot
    differently after that)."""
    decode = chain[3]
    with pytest.raises(SystemExit) as e:
        _validate(chain, backend, _tampering_decode(
            decode, at_blocks, flipped if flipped is not None else []))
    m = re.match(r"validation FAILED at block (\d+): proof (\w+) failed "
                 r"for block ", str(e.value))
    assert m, str(e.value)
    return int(m.group(1)), m.group(2)


@pytest.mark.parametrize("at_blocks,shards", [
    ((5,), {0}),                   # in the window's second tile
    ((1,), {1}), ((2,), {2}), ((4,), {3}),
    ((6, 3), {1, 2}),              # two shards: the minimum wins
    ((WINDOW + 4,), {3}),          # a later window
], ids=["shard0-tile1", "shard1", "shard2", "shard3", "shards1and2",
        "window1-shard3"])
def test_a_bad_lane_in_any_shard_stops_where_the_reference_stops(
        chain, mesh_backend, reference_backend, monkeypatch,
        at_blocks, shards):
    packed = []                    # (requests, padded lanes) a window
    real = mesh_backend._pack_ed

    def spy(reqs, m):
        packed.append((list(reqs), m))
        return real(reqs, m)

    monkeypatch.setattr(mesh_backend, "_pack_ed", spy)
    flipped: list = []
    builds = metrics_mod.counter("jax_backend.composite_builds").value
    got = _stop(chain, mesh_backend, at_blocks, flipped)
    want = _stop(chain, reference_backend, at_blocks)
    assert got == want == (min(at_blocks), "Ed25519Req")
    # the flipped signatures really sat in the shards the case names
    hit = set()
    for reqs, m in packed:
        assert m == ED_WALKED and len(reqs) == ED_LANES
        for lane, r in enumerate(reqs):
            if isinstance(r, Ed25519Req) and r.sig in flipped:
                # a tile's lanes are split four ways, tile after tile
                hit.add(lane % ED_TILE_LANES // (ED_TILE_LANES // SHARDS))
    assert hit == shards
    # and the tampered window ran the clean chain's composite
    assert metrics_mod.counter(
        "jax_backend.composite_builds").value == builds


# -- a chain on which every witness key is new (ISSUE 31) --------------------

_COMPILES: list = []      # backend compiles of this process, as they end
_LISTENING: list = []     # set once the listener is registered


def _listen_for_compiles() -> None:
    """Start counting backend compiles, once a process."""
    if not _LISTENING:
        _LISTENING.append(True)
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _secs, **_kw: _COMPILES.append(event)
            if event.endswith("backend_compile_duration") else None)


def _witness_keys(chain) -> list:
    _d, db, _rules, decode, _cfg = chain
    return [vk for _e, raw in db.stream() for tx in decode(raw).body
            for vk, _sig in tx.witnesses]


@pytest.fixture(scope="module")
def fresh_lines(fresh_chain, lines, mesh_backend, one_chip_backend,
                reference_backend):
    """The fresh chain's replay by backend, AFTER `lines` compiled every
    program on the pool-key chain; beside each device replay what the
    per-key cache was asked (`assemble` spied on) and what it counted."""
    _listen_for_compiles()
    cache = GLOBAL_PRECOMPUTE_CACHE
    out = {"cpp": _validate(fresh_chain, reference_backend)}
    for name, backend in (("mesh", mesh_backend),
                          ("one-chip", one_chip_backend)):
        asked = []     # (keys, which were cached, lanes `hits` rose by)

        def spied(real):
            def spy(vks):
                cached = [vk in cache for vk in vks]
                h0 = cache.hits
                res = real(vks)
                asked.append((list(vks), cached, cache.hits - h0))
                return res
            return spy

        compiles = len(_COMPILES)
        builds = metrics_mod.counter("jax_backend.composite_builds").value
        # the cache is asked in one go (the VRF packer) or in two phases
        # (the window's Ed25519 lanes): the lookup is in the first
        cache.assemble = spied(cache.assemble)
        cache.begin_assemble = spied(cache.begin_assemble)
        try:
            c0 = _counters()
            out[name] = _validate(fresh_chain, backend)
            c1 = _counters()
        finally:
            del cache.assemble, cache.begin_assemble
        out[name + "-facts"] = {
            "asked": asked,
            "delta": {k: c1[k] - c0.get(k, 0) for k in c1},
            "compiles": len(_COMPILES) - compiles,
            "builds": metrics_mod.counter(
                "jax_backend.composite_builds").value - builds}
    return out


def test_no_witness_key_signs_twice_on_the_fresh_chain(chain, fresh_chain):
    fresh = _witness_keys(fresh_chain)
    assert len(fresh) == len(set(fresh)) == BLOCKS
    assert len(set(_witness_keys(chain))) == 2      # the pools' two


@pytest.mark.parametrize("what", ["state_hash", "blocks", "proofs"])
@pytest.mark.parametrize("backend", ["mesh", "one-chip"])
def test_fresh_chain_replay_equals_the_reference(fresh_lines, backend,
                                                 what):
    assert fresh_lines[backend][what] == fresh_lines["cpp"][what]
    assert fresh_lines["cpp"]["blocks"] == BLOCKS


@pytest.mark.parametrize("backend", ["mesh", "one-chip"])
def test_fresh_chain_fills_each_witness_key_once_and_hits_header_keys(
        fresh_chain, fresh_lines, backend):
    facts = fresh_lines[backend + "-facts"]
    witness = set(_witness_keys(fresh_chain))
    seen: set = set()
    lanes_hit = 0
    for vks, cached, rose in facts["asked"]:
        # a lane hits exactly when its key was in the cache, and no
        # witness key ever was: each is met once, as a miss
        assert rose == sum(cached)
        assert not any(c for vk, c in zip(vks, cached) if vk in witness)
        lanes_hit += rose
        seen.update(vks)
    assert witness <= seen
    delta = facts["delta"]
    # every distinct key filled once: the witness keys and the header
    # keys (cold, VRF and KES leaf keys; the pad lanes' zero key)
    assert delta["precompute.filled_keys"] == len(seen)
    assert len(witness) < len(seen) <= len(witness) + 4 + BLOCKS + 1
    assert lanes_hit > 0           # window 1 meets window 0's pool keys
    assert delta["precompute.fill_lanes_padded"] \
        == 128 * delta["precompute.device_fills"]


@pytest.mark.parametrize("backend", ["mesh", "one-chip"])
def test_fresh_chain_compiles_nothing_the_pool_chain_did_not(
        fresh_lines, backend):
    """The fill's programs are keyed on no count of new keys: the chain
    of new keys runs what the chain of two keys compiled."""
    facts = fresh_lines[backend + "-facts"]
    assert facts["compiles"] == 0 and facts["builds"] == 0
    assert facts["delta"]["jax_backend.windows_submitted"] == N_WINDOWS


@pytest.mark.parametrize("backend", ["mesh", "one-chip"])
def test_a_flipped_witness_on_the_fresh_chain_stops_both_at_its_block(
        fresh_chain, fresh_lines, mesh_backend, one_chip_backend,
        reference_backend, backend):
    device = mesh_backend if backend == "mesh" else one_chip_backend
    at = (WINDOW + 2,)
    assert _stop(fresh_chain, device, at) \
        == _stop(fresh_chain, reference_backend, at) \
        == (WINDOW + 2, "Ed25519Req")


# -- a chain of unequal windows (ISSUE 38) -------------------------------------

@pytest.fixture(scope="module")
def mixed_chain(tmp_path_factory):
    """The chain under arrivals a slot (the rehearse block of
    `traffic/bodies-mempool-diurnal.json` in small): window 0 holds some
    tens of transactions, window 1 a few, so the two windows walk
    different numbers of tiles."""
    return _forge(tmp_path_factory, "mixed-chain",
                  "--tx-arrivals-per-slot", "0.6,0.05",
                  "--tx-arrival-phase-slots", "160")


@pytest.fixture(scope="module")
def mixed_lines(mixed_chain, lines, mesh_backend, one_chip_backend,
                reference_backend):
    """The mixed chain's replay by backend, AFTER `lines` compiled every
    program on the chain of equal windows."""
    _listen_for_compiles()
    out = {"cpp": _validate(mixed_chain, reference_backend)}
    for name, backend in (("mesh", mesh_backend),
                          ("one-chip", one_chip_backend)):
        compiles = len(_COMPILES)
        c0 = _counters()
        out[name] = _validate(mixed_chain, backend)
        c1 = _counters()
        out[name + "-facts"] = {
            "delta": {k: c1[k] - c0.get(k, 0) for k in c1},
            "compiles": len(_COMPILES) - compiles}
    return out


@pytest.mark.parametrize("what", ["state_hash", "blocks", "proofs"])
@pytest.mark.parametrize("backend", ["mesh", "one-chip"])
def test_mixed_chain_replay_equals_the_reference(mixed_lines, backend,
                                                 what):
    assert mixed_lines[backend][what] == mixed_lines["cpp"][what]
    assert mixed_lines["cpp"]["blocks"] == BLOCKS
    assert mixed_lines["cpp"]["proofs"] > 5 * BLOCKS


@pytest.mark.parametrize("backend", ["mesh", "one-chip"])
def test_mixed_chain_compiles_nothing_the_equal_chain_did_not(
        mixed_lines, backend):
    """A window's tile count is no program's shape, on the mesh as on
    one chip: windows of other widths run what `lines` compiled."""
    facts = mixed_lines[backend + "-facts"]
    delta = facts["delta"]
    assert facts["compiles"] == 0
    assert delta["jax_backend.composite_builds"] == 0
    assert delta["jax_backend.windows_submitted"] == N_WINDOWS
    assert delta["jax_backend.ed_width_changes"] == 1
    real = delta["jax_backend.ed_lanes_real"]
    assert real == mixed_lines["cpp"]["proofs"] - 2 * BLOCKS
    # whole tiles of 16 lanes (4 a shard on the mesh), none without a
    # real lane
    assert delta["jax_backend.ed_lanes_walked"] % ED_TILE_LANES == 0
    assert real <= delta["jax_backend.ed_lanes_walked"] \
        < real + N_WINDOWS * ED_TILE_LANES
    assert delta["jax_backend.ed_tiles"] * ED_TILE_LANES \
        == delta["jax_backend.ed_lanes_walked"]


# -- what the mesh adds: one span, three counters ---------------------------

@pytest.fixture(scope="module")
def traced(chain, mesh_backend, lines):
    """One replay with span recording on, `_dev` and `_dev_tiles` spied
    on; then a second with every cache left warm."""
    put = []                       # bytes handed to each sharded put
    real, real_tiles = mesh_backend._dev, mesh_backend._dev_tiles

    def spy(a):
        put.append(np.asarray(a).nbytes)
        return real(a)

    def spy_tiles(arrays, ne):
        put.append(sum(a.nbytes for a in arrays))
        return real_tiles(arrays, ne)

    rec = spans_mod.RECORDER
    assert not rec.enabled
    rec.drain()
    mesh_backend._dev, mesh_backend._dev_tiles = spy, spy_tiles
    rec.enable()
    try:
        c0 = _counters()
        line = _validate(chain, mesh_backend)
        c1 = _counters()
    finally:
        rec.disable()
        del mesh_backend._dev, mesh_backend._dev_tiles
    roots = rec.drain()
    kes_paths = GLOBAL_PRECOMPUTE_CACHE.kes_len()
    _validate(chain, mesh_backend, cold=False)
    c2 = _counters()
    return {"line": line, "roots": roots, "put": put,
            "kes_paths": kes_paths,
            "delta": {k: c1[k] - c0.get(k, 0) for k in c1},
            "warm_delta": {k: c2[k] - c1.get(k, 0) for k in c2}}


def test_traced_replay_is_the_same_replay(traced, lines):
    assert traced["line"]["state_hash"] == lines["cpp"]["state_hash"]


def test_shard_put_spans_close_inside_the_packing_stages(traced):
    spans = [sp for root in traced["roots"] for sp in root.walk()]
    puts = [sp for sp in spans if sp.name == "submit.shard_put"]
    # a window's Ed25519 tiles in one put, their owner rows in another,
    # and 7 VRF arrays; no beta lanes on a two-window chain (both
    # windows' betas ride the plain prefetch)
    assert len(puts) == len(traced["put"]) == 9 * N_WINDOWS
    for sp in puts:
        assert sp.t1 is not None and sp.t1 >= sp.t0
        assert sp.cat == "dispatch"
        assert sp.thread == "ouro-replay-producer"
    by_stage = {
        stage: sum(c.name == "submit.shard_put" for sp in spans
                   if sp.name == stage for c in sp.children)
        for stage in ("submit.pack_ed", "submit.pack_vrf", "submit.fold")}
    assert by_stage == {"submit.pack_ed": N_WINDOWS,
                        "submit.pack_vrf": 7 * N_WINDOWS,
                        "submit.fold": N_WINDOWS}


def test_shard_put_bytes_counts_what_was_handed_over(traced):
    assert traced["delta"]["jax_backend.shard_put_bytes"] \
        == sum(traced["put"]) > 0


def test_shard_lanes_padded_is_one_shards_share(traced, mesh_backend):
    per_window = (ED_WALKED + VRF_LANES) // SHARDS
    assert traced["delta"]["jax_backend.shard_lanes_padded"] \
        == per_window * N_WINDOWS
    assert traced["delta"]["jax_backend.lanes_padded"] \
        == SHARDS * per_window * N_WINDOWS
    assert mesh_backend.padding_stats()["lanes_per_shard_per_window"] \
        == per_window


def test_kes_host_walks_counts_misses_and_not_hits(traced):
    walks = traced["delta"]["precompute.kes_host_walks"]
    assert 1 <= walks <= BLOCKS
    assert walks == traced["kes_paths"]       # one walk a cached path
    assert traced["warm_delta"]["precompute.kes_host_walks"] == 0
    assert traced["warm_delta"]["jax_backend.windows_submitted"] \
        == N_WINDOWS


@pytest.mark.parametrize("metric,source", [
    ("shard_put_ms_per_window", "program_span"),
    ("shard_put_mb_per_window", "program_counter"),
    ("shard_lanes_per_window", "program_counter"),
    ("kes_host_walks_per_block", "program_counter")])
def test_layer_metric_reader_resolves_on_the_mesh_replay(traced, metric,
                                                         source):
    """The benchmark's data files of these metrics against the facts of
    this replay, gathered as `benchmarks/run.py` gathers them: a renamed
    span or counter fails here, not in a chip run."""
    bench = os.path.join(REPO, "benchmarks")
    spec = importlib.util.spec_from_file_location(
        "bench_readers", os.path.join(bench, "harness", "readers.py"))
    readers = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(readers)
    with open(os.path.join(bench, "layer_metrics", metric + ".json")) as fh:
        doc = json.load(fh)
    span_seconds: dict = {}
    for root in traced["roots"]:
        for sp in root.walk():
            span_seconds[sp.name] = span_seconds.get(sp.name, 0.0) \
                + sp.duration
    facts = {"window": {"blocks": BLOCKS, "windows": N_WINDOWS},
             "span_seconds": span_seconds, "counter": traced["delta"]}
    value = readers.read(doc["reader"], facts)
    assert value is not None and value > 0
    assert doc["source"] == source
    if metric == "shard_lanes_per_window":
        assert value == (ED_WALKED + VRF_LANES) // SHARDS


def test_no_span_when_recording_is_off(chain, mesh_backend, lines):
    assert not spans_mod.RECORDER.enabled
    spans_mod.RECORDER.drain()
    _validate(chain, mesh_backend)
    assert spans_mod.RECORDER.drain() == []


# -- no replay reaches the standalone batch forms (ISSUE 43) -------------------

def test_no_replay_reaches_the_standalone_forms(chain, lines, mesh_backend,
                                                monkeypatch):
    """What lets the standalone forms change (tests/test_mesh_batch.py
    holds them to the reference) without a replay, and so a cell of the
    benchmark, feeling it."""
    def reached(reqs):
        raise AssertionError("a replay called a standalone batch form")

    monkeypatch.setattr(mesh_backend, "verify_ed25519_batch", reached)
    monkeypatch.setattr(mesh_backend, "verify_vrf_batch", reached)
    line = _validate(chain, mesh_backend)
    assert line["blocks"] == BLOCKS
    assert line["state_hash"] == lines["cpp"]["state_hash"]
