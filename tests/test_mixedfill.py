"""A chain whose blocks hold what a mempool held (ISSUE 38): the forge's
arrival arguments, and the device path on windows of unequal Ed25519
width, on the CPU.

The benchmark's `sync-mixedfill` at rehearsal size: a 64-block Shelley
chain under a scaled arrival profile (`db_synth --tx-arrivals-per-slot
... --tx-arrival-phase-slots 160`) through the one-chip device path in
windows of 8.  Off an accelerator a tile is the backend's `min_bucket`,
here 16 lanes, and a window holds 16 header lanes and one a transaction,
so the chain's windows walk 6, 2, 2, 1, 7, 3, 2 and 3 tiles.

What is held: (a) the forge is a function of its arguments, a block
never passes the body limit, what does not fit waits, empty blocks
occur, and without the arguments the chain is the parent's byte for
byte; (b) the replay equals the `cpp` reference's twice over, on the
programs a chain of EQUAL windows built before it, building none
itself; (c) the window path gives the flat program's and the pure-Python
reference's verdicts lane for lane at widths around the tile
boundaries, folded first-bad index included; (d) a flipped witness in
the widest and in the narrowest window that holds one, and a flipped
KES signature in a block with no transaction, stop both paths at the
same block with the same error; (e) a chain whose length is no multiple
of the window and a replay cut at an invalid header build no program.
One module fixture makes every replay; each test reads one property.
The composite is `tests/test_longchain.py`'s (same sizes, same program).
"""
import dataclasses
import functools
import hashlib
import io
import json
import os
import subprocess
import sys
from fractions import Fraction

import pytest

pytest.importorskip("jax")

from ouroboros_tpu import observe                               # noqa: E402
from ouroboros_tpu.consensus.batch import (                     # noqa: E402
    replay_blocks_pipelined,
)
from ouroboros_tpu.consensus.headers import ProtocolBlock       # noqa: E402
from ouroboros_tpu.crypto import ed25519_ref                    # noqa: E402
from ouroboros_tpu.crypto import jax_backend as JB              # noqa: E402
from ouroboros_tpu.crypto.backend import (                      # noqa: E402
    GLOBAL_BETA_CACHE, CpuRefBackend, Ed25519Req,
)
from ouroboros_tpu.crypto.jax_backend import JaxBackend         # noqa: E402
from ouroboros_tpu.crypto.precompute import (                   # noqa: E402
    GLOBAL_PRECOMPUTE_CACHE,
)
from ouroboros_tpu.eras.shelley import KES_FIELD                # noqa: E402
from tools import db_analyser as dba                            # noqa: E402
from tools import db_synth                                      # noqa: E402

from test_cpp_backend import _PARENT_DB, _SYNTH, _db_digest    # noqa: E402

pytestmark = pytest.mark.device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCKS, WINDOW, TILE = 64, 8, 16
WINDOWS = BLOCKS // WINDOW
PROFILE, PHASE = "0.4,0.15,0,0,0,0.6,0.05,0.1", 160
SHELLEY = ["--protocol", "shelley", "--pools", "2", "--f", "1/20",
           "--epoch-length", "432000", "--kes-depth", "6",
           "--slots-per-kes-period", "129600"]
ARRIVALS = ["--tx-arrivals-per-slot", PROFILE,
            "--tx-arrival-phase-slots", str(PHASE)]

COUNTERS = ("jax_backend.composite_builds", "jax_backend.windows_submitted",
            "jax_backend.ed_tiles", "jax_backend.ed_lanes_real",
            "jax_backend.ed_lanes_walked", "jax_backend.ed_width_changes")


def _forge(out: str, blocks: int, seed: str, *more: str) -> dict:
    """db_synth in a child; the chain's config.json."""
    subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", out, "--blocks", str(blocks), "--seed", seed, *SHELLEY,
         *more], check=True, capture_output=True)
    with open(os.path.join(out, "config.json")) as fh:
        return json.load(fh)


def _body_sizes(d: str) -> list:
    db, _rules, decode, _cfg = dba.load_db(d)
    return [len(decode(raw).body) for _entry, raw in db.stream()]


# -- (a) the forge -----------------------------------------------------------------

def test_mempool_takes_what_arrived_up_to_the_cap_and_keeps_the_rest():
    """3.5 a slot: 7 after two slots, a block of 5 leaves 2; the next
    slot's 3.5 make 5.5, a block of 5 leaves a half."""
    pool = db_synth._Mempool("3.5,0", 4, cap=5)
    assert pool.take(1) == 5 and pool.waiting == 2
    assert pool.take(2) == 5 and pool.waiting == Fraction(1, 2)
    assert pool.take(3) == 4 and pool.waiting == 0   # 0.5 + 3.5, phase 0
    assert pool.take(9) == 0                         # phase 1 and after: 0
    assert pool.summary() == {"min": 0, "mean": 3.5, "max": 5,
                              "empty_blocks": 1, "cap": 5,
                              "left_waiting": 0}


@pytest.mark.parametrize("rates,phase_slots", [
    ("4.4,17.6,8.8,2.0,0.6,0.2,0.4,1.2", 5120), ("0.3", 7), ("5,0,1", 3)])
def test_mempool_loses_no_arrival_whatever_the_gaps(rates, phase_slots):
    pool = db_synth._Mempool(rates, phase_slots, cap=352)
    rs = [Fraction(r) for r in rates.split(",")]
    slot, arrived = -1, Fraction(0)
    for gap in (1, 40, 3, 1, 1, 97, 12, 5, 260, 1, 33) * 40:
        for s in range(slot + 1, slot + gap + 1):
            arrived += rs[min(s // phase_slots, len(rs) - 1)]
        slot += gap
        assert 0 <= pool.take(slot) <= 352
    assert sum(pool.taken) + pool.waiting == arrived


@pytest.mark.parametrize("bad", [
    ["--tx-arrivals-per-slot", "1,2"],              # no phase length
    ["--tx-arrival-phase-slots", "100"],            # no rates
    ["--tx-arrivals-per-slot", "1,-2", "--tx-arrival-phase-slots", "10"],
    ["--tx-arrivals-per-slot", "1", "--tx-arrival-phase-slots", "0"]])
def test_forge_refuses_half_an_argument_pair(tmp_path, bad):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", str(tmp_path / "db"), "--blocks", "4", *SHELLEY, *bad],
        capture_output=True)
    assert r.returncode != 0


def test_forge_refuses_arrivals_on_a_chain_that_is_not_shelley(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", str(tmp_path / "db"), "--blocks", "4", *ARRIVALS],
        capture_output=True)
    assert r.returncode != 0


@pytest.fixture(scope="module")
def backlog_chain(tmp_path_factory):
    """24 blocks, 40 arrivals a slot for 60 slots and none after: more
    than the first blocks can carry."""
    d = str(tmp_path_factory.mktemp("backlog") / "chain")
    cfg = _forge(d, 24, "backlog", "--tx-arrivals-per-slot", "40,0",
                 "--tx-arrival-phase-slots", "60")
    return d, cfg, _body_sizes(d)


def test_no_block_passes_the_body_limit(backlog_chain):
    _d, cfg, sizes = backlog_chain
    cap = cfg["tx_arrivals"]["txs_per_block"]["cap"]
    assert cap == db_synth.MAX_BLOCK_BODY_SIZE // cfg["tx_arrivals"][
        "tx_bytes"] == 352
    assert max(sizes) == cap
    assert (cap + 1) * cfg["tx_arrivals"]["tx_bytes"] \
        > db_synth.MAX_BLOCK_BODY_SIZE


def test_what_did_not_fit_waits_for_the_next_blocks(backlog_chain):
    """2,400 arrive in the first 60 slots; the blocks forged in them
    cannot carry that, so full blocks go on after the arrivals stop
    until the backlog is gone, and nothing is lost."""
    _d, cfg, sizes = backlog_chain
    stats = cfg["tx_arrivals"]["txs_per_block"]
    assert sum(sizes) + stats["left_waiting"] == 40 * 60
    full = [i for i, n in enumerate(sizes) if n == stats["cap"]]
    assert len(full) >= 40 * 60 // stats["cap"] - 1
    drained = full[-1] + 1          # one part-full block, then nothing
    assert all(n == 0 for n in sizes[drained + 1:])


def test_config_says_what_the_chain_came_to_hold(backlog_chain):
    _d, cfg, sizes = backlog_chain
    assert cfg["tx_arrivals"]["per_slot"] == "40,0"
    assert cfg["tx_arrivals"]["phase_slots"] == 60
    stats = cfg["tx_arrivals"]["txs_per_block"]
    assert (stats["min"], stats["max"], stats["empty_blocks"]) \
        == (min(sizes), max(sizes), sizes.count(0))
    assert stats["mean"] == round(sum(sizes) / len(sizes), 3)
    assert stats["empty_blocks"] > 0


def test_same_arguments_same_chain_other_seed_another(tmp_path):
    a, b, c = (str(tmp_path / n) for n in "abc")
    for d, seed in ((a, "s1"), (b, "s1"), (c, "s2")):
        _forge(d, 16, seed, *ARRIVALS)
    assert _db_digest(a) == _db_digest(b) != _db_digest(c)


def test_without_the_arguments_the_chain_is_the_parents(tmp_path):
    """The digest `tests/test_cpp_backend.py` pins for the parent's
    db_synth, from this tree's, and no `tx_arrivals` in its config."""
    out = str(tmp_path / "db")
    subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", out, *_SYNTH], check=True, capture_output=True)
    assert _db_digest(out) == _PARENT_DB
    with open(os.path.join(out, "config.json")) as fh:
        assert "tx_arrivals" not in json.load(fh)


# -- the replays ---------------------------------------------------------------------

def _counters() -> dict:
    return {n: observe.metrics.counter(n).value for n in COUNTERS}


def _clear_caches() -> None:
    GLOBAL_BETA_CACHE.clear()
    GLOBAL_PRECOMPUTE_CACHE.clear()


def _drain_spans() -> dict:
    """Compile spans closed since the last drain, and the seconds
    inside `submit.ed_tiles`."""
    spans = [sp for root in observe.spans.RECORDER.drain()
             for sp in root.walk() if sp.t1 is not None]
    return {"compile_spans": sum(sp.cat == "compile" for sp in spans),
            "ed_tiles_s": sum(sp.t1 - sp.t0 for sp in spans
                              if sp.name == "submit.ed_tiles")}


def _validate(ctx, backend) -> dict:
    db, rules, decode, cfg, chain = ctx
    _clear_caches()
    _drain_spans()
    c0 = _counters()
    out = io.StringIO()
    dba.analysis_validate(db, rules, decode, backend, "full", WINDOW, out,
                          hdr_proofs=dba.HEADER_PROOFS[cfg["protocol"]],
                          db_dir=chain, snapshot_every=100)
    c1 = _counters()
    return {**json.loads(out.getvalue()),
            "moved": {n: c1[n] - c0[n] for n in COUNTERS}, **_drain_spans()}


def _programs(dev: JaxBackend) -> tuple:
    return (sorted(dev._ed_tile_programs), sorted(dev._composites),
            sorted(dev._folds))


def _flip(sig: bytes) -> bytes:
    return sig[:3] + bytes([sig[3] ^ 1]) + sig[4:]


def _flip_witness(blk):
    body = list(blk.body)
    (vk, sig), *rest = body[0].witnesses
    body[0] = dataclasses.replace(body[0],
                                  witnesses=((vk, _flip(sig)), *rest))
    return ProtocolBlock(blk.header, type(blk.body)(body))


def _flip_kes(blk):
    return ProtocolBlock(blk.header.with_fields(
        **{KES_FIELD: _flip(blk.header.get(KES_FIELD))}), blk.body)


def _stop(rules, blocks, backend) -> dict:
    _clear_caches()
    c0 = _counters()
    res = replay_blocks_pipelined(rules, blocks, rules.initial_state(),
                                  backend=backend, window=WINDOW)
    return {"n_valid": res.n_valid, "error": type(res.error).__name__,
            "builds": _counters()["jax_backend.composite_builds"]
            - c0["jax_backend.composite_builds"]}


def _load(chain: str) -> tuple:
    db, rules, decode, cfg = dba.load_db(chain)
    return db, rules, decode, cfg, chain


@pytest.fixture(scope="module")
def mixedfill(tmp_path_factory):
    root = tmp_path_factory.mktemp("mixedfill")
    mixed, equal = str(root / "mixed"), str(root / "equal")
    _forge(mixed, BLOCKS, "38", *ARRIVALS)
    _forge(equal, BLOCKS, "38", "--txs-per-block", "1")
    ctx = _load(mixed)
    db, rules, decode = ctx[:3]
    was_recording = observe.spans.RECORDER.enabled
    observe.enable()
    try:
        cpu = dba.make_backend("cpp")
        dev = JaxBackend(min_bucket=16, use_pallas=False, autotune=False)
        blocks = [decode(raw) for _entry, raw in db.stream()]
        sizes = [len(b.body) for b in blocks]
        lanes = [sum(sizes[w:w + WINDOW]) + 2 * WINDOW
                 for w in range(0, BLOCKS, WINDOW)]
        # the chain of equal windows first: it builds every program
        rec = {"sizes": sizes, "lanes": lanes, "tile": dev.ed_tile,
               "reference": _validate(ctx, cpu),
               "equal_chain": _validate(_load(equal), dev),
               "equal_chain_programs": _programs(dev),
               "first": _validate(ctx, dev),
               "second": _validate(ctx, dev),
               "programs": _programs(dev)}

        # (d) the widest window, the narrowest that holds a witness, and
        # an empty block of the window with no transaction at all
        wide = max(range(WINDOWS), key=lanes.__getitem__)
        narrow = min((w for w in range(WINDOWS) if lanes[w] > 2 * WINDOW),
                     key=lanes.__getitem__)
        empty = min(range(WINDOWS), key=lanes.__getitem__)
        rec["windows"] = {"wide": wide, "narrow": narrow, "empty": empty}
        rec["stops"] = {}
        for name, w, flip in (("wide", wide, _flip_witness),
                              ("narrow", narrow, _flip_witness),
                              ("empty", empty, _flip_kes)):
            at = next(i for i in range(w * WINDOW, (w + 1) * WINDOW)
                      if bool(sizes[i]) == (flip is _flip_witness))
            bad = list(blocks)
            bad[at] = flip(bad[at])
            rec["stops"][name] = {"at": at, "device": _stop(rules, bad, dev),
                                  "reference": _stop(rules, bad, cpu)}
        # (e) a last window of 5 blocks; a window cut after 3 blocks by a
        # header whose predecessor is not the block before it
        rec["short"] = {"device": _stop(rules, blocks[:BLOCKS - 3], dev),
                        "reference": _stop(rules, blocks[:BLOCKS - 3], cpu)}
        cut = 2 * WINDOW + 3
        broken = blocks[:cut] + blocks[cut + 1:]
        rec["cut"] = {"at": cut, "device": _stop(rules, broken, dev),
                      "reference": _stop(rules, broken, cpu)}
        rec["programs_at_the_end"] = _programs(dev)
    finally:
        if not was_recording:
            observe.spans.RECORDER.disable()
    return rec


# -- (b) the replay against the reference, on one set of programs --------------------

def test_windows_span_four_or_more_tile_counts(mixedfill):
    assert mixedfill["tile"] == TILE
    tiles = [-(-n // TILE) for n in mixedfill["lanes"]]
    assert len(set(tiles)) >= 4 and min(tiles) == 1 and max(tiles) >= 6
    assert 0 in mixedfill["sizes"]


@pytest.mark.parametrize("key", ["state_hash", "blocks", "proofs"])
@pytest.mark.parametrize("replay", ["first", "second"])
def test_device_path_equals_the_reference(mixedfill, replay, key):
    assert mixedfill[replay][key] == mixedfill["reference"][key]
    assert mixedfill["reference"]["blocks"] == BLOCKS
    assert mixedfill["reference"]["proofs"] \
        == 4 * BLOCKS + sum(mixedfill["sizes"])


@pytest.mark.parametrize("replay", ["first", "second"])
def test_the_device_walked_only_tiles_with_a_real_lane(mixedfill, replay):
    moved, lanes = mixedfill[replay]["moved"], mixedfill["lanes"]
    tiles = [-(-n // TILE) for n in lanes]
    assert moved["jax_backend.windows_submitted"] == WINDOWS
    assert moved["jax_backend.ed_lanes_real"] == sum(lanes)
    assert moved["jax_backend.ed_tiles"] == sum(tiles)
    assert moved["jax_backend.ed_lanes_walked"] == TILE * sum(tiles)
    assert moved["jax_backend.ed_width_changes"] \
        == sum(a != b for a, b in zip(tiles, tiles[1:])) >= WINDOWS // 2


def test_programs_are_those_of_a_chain_of_equal_windows(mixedfill):
    """The chain of one transaction a block (every window two tiles)
    was replayed first and built the tile program, one composite and
    one fold; the windows of 1 to 7 tiles ran on them."""
    equal = mixedfill["equal_chain"]
    assert equal["moved"]["jax_backend.composite_builds"] == 1
    assert equal["compile_spans"] == 3
    assert equal["moved"]["jax_backend.ed_width_changes"] == 0
    tile_programs, composites, folds = mixedfill["equal_chain_programs"]
    assert tile_programs == [True]
    assert len(composites) == len(folds) == 1
    assert mixedfill["programs"] == mixedfill["equal_chain_programs"]


@pytest.mark.parametrize("replay", ["first", "second"])
def test_a_replay_of_unequal_windows_builds_nothing(mixedfill, replay):
    assert mixedfill[replay]["moved"]["jax_backend.composite_builds"] == 0
    assert mixedfill[replay]["compile_spans"] == 0


@pytest.mark.parametrize("metric,source", [
    ("ed_width_change_share", "program_counter"),
    ("ed_walk_overhead_share", "program_counter"),
    ("ed_tile_dispatch_ms_per_window", "program_span")])
def test_layer_metric_reader_reads_this_replay(mixedfill, metric, source):
    """The benchmark's data files of the three metrics against the facts
    of the second replay, gathered as `benchmarks/run.py` gathers them:
    a renamed span or counter fails here, not in a chip run."""
    import importlib.util
    bench = os.path.join(REPO, "benchmarks")
    spec = importlib.util.spec_from_file_location(
        "bench_readers", os.path.join(bench, "harness", "readers.py"))
    readers = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(readers)
    with open(os.path.join(bench, "layer_metrics", metric + ".json")) as fh:
        doc = json.load(fh)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        entry, = (m for m in json.load(fh)["per_layer"]
                  if m["name"] == metric)
    assert entry["workloads"] == ["sync-mixedfill"]
    assert doc["source"] == entry["source"] == source
    replay = mixedfill["second"]
    tiles = [-(-n // TILE) for n in mixedfill["lanes"]]
    facts = {"counter": replay["moved"], "window": {"windows": WINDOWS},
             "span_seconds": {"submit.ed_tiles": replay["ed_tiles_s"]}}
    value = readers.read(doc["reader"], facts)
    want = {"ed_width_change_share": 100 * sum(
                a != b for a, b in zip(tiles, tiles[1:])) / WINDOWS,
            "ed_walk_overhead_share": 100 * (
                1 - sum(mixedfill["lanes"]) / (TILE * sum(tiles))),
            "ed_tile_dispatch_ms_per_window":
                1e3 * replay["ed_tiles_s"] / WINDOWS}[metric]
    assert value == pytest.approx(want) and value > 0
    # a program without the counters (the parent's) leaves the metric out
    assert readers.read(doc["reader"], {"window": {"windows": WINDOWS},
                                        "counter": {},
                                        "span_seconds": {}}) is None


# -- (d) stops ---------------------------------------------------------------------------

@pytest.mark.parametrize("where,error", [
    ("wide", "LedgerError"), ("narrow", "LedgerError"),
    ("empty", "LedgerError")])
def test_a_flip_stops_both_paths_at_its_block(mixedfill, where, error):
    stop = mixedfill["stops"][where]
    assert stop["device"]["n_valid"] == stop["reference"]["n_valid"] \
        == stop["at"]
    assert stop["device"]["error"] == stop["reference"]["error"] == error
    assert stop["device"]["builds"] == 0


def test_the_three_flips_sat_where_they_were_meant_to(mixedfill):
    w, lanes = mixedfill["windows"], mixedfill["lanes"]
    assert lanes[w["wide"]] == max(lanes) > 6 * TILE
    assert lanes[w["empty"]] == 2 * WINDOW         # no transaction at all
    assert 2 * WINDOW < lanes[w["narrow"]] <= 2 * TILE
    assert mixedfill["sizes"][mixedfill["stops"]["empty"]["at"]] == 0


# -- (e) short and cut windows -----------------------------------------------------------

def test_a_short_last_window_builds_no_program(mixedfill):
    short = mixedfill["short"]
    assert short["device"] == short["reference"] \
        == {"n_valid": BLOCKS - 3, "error": "NoneType", "builds": 0}


def test_a_replay_cut_at_an_invalid_header_builds_no_program(mixedfill):
    cut = mixedfill["cut"]
    assert cut["device"]["n_valid"] == cut["reference"]["n_valid"] \
        == cut["at"]
    assert cut["device"]["error"] == cut["reference"]["error"] != "NoneType"
    assert cut["device"]["builds"] == 0
    assert mixedfill["programs_at_the_end"] == mixedfill["programs"]


@pytest.mark.parametrize("need,rides", [
    ((16, 16, 16), (16, 16, 16)),
    ((16, 0, 0), (16, 16, 16)),       # a chain's last two windows
    ((8, 0, 0), (16, 16, 16)),        # a short or a cut window
    ((8, 0, 8), (16, 16, 16)),
    ((16, 256, 0), (16, 256, 0)),     # wider than any built: its own
    ((32, 0, 0), (64, 0, 0)),         # the narrowest that holds it
    ((0, 0, 0), (0, 0, 0)),           # nothing for the composite
    ((0, 16, 0), (16, 16, 16))])      # no VRF lane of its own: it rides too
def test_a_window_rides_the_narrowest_built_composite_that_holds_it(
        need, rides):
    jb = JaxBackend(min_bucket=16, use_pallas=False, autotune=False)
    jb._composites = {(16, 16, 16): None, (64, 0, 0): None,
                      (128, 128, 128): None}
    assert jb._occasional_widths(*need) == rides


# -- (c) the window path lane for lane ---------------------------------------------------

WIDTHS = {"one": 1, "tile-1": TILE - 1, "tile": TILE, "tile+1": TILE + 1,
          "3tiles": 3 * TILE, "5tiles+7": 5 * TILE + 7}


def _bad_lanes(n: int) -> tuple:
    """In the first tile, at the start of the last tile, and in the last
    real lane (beside the padding, where there is any)."""
    return tuple(sorted({min(2, n - 1), (n - 1) // TILE * TILE, n - 1}))


@functools.lru_cache(maxsize=None)
def _signed(i: int) -> Ed25519Req:
    """Lane i's request, signed once for every width that holds it."""
    sk = hashlib.sha256(b"mixedfill-%d" % (i & 1)).digest()
    msg = b"lane-%03d" % i
    return Ed25519Req(ed25519_ref.public_key(sk), msg,
                      ed25519_ref.sign(sk, msg))


def _requests(n: int, bad=()) -> list:
    reqs = [_signed(i) for i in range(n)]
    for i in bad:
        sig = reqs[i].sig
        reqs[i] = dataclasses.replace(
            reqs[i], sig=sig[:40] + bytes([sig[40] ^ 1]) + sig[41:])
    return reqs


@pytest.fixture(scope="module")
def lanes():
    """Every width by three paths: the window path (tile calls, with and
    without the fold), ONE flat `verify_full_split_words_core` program
    over the whole batch (the simple batch entry point's 128-lane
    bucket) and the pure-Python reference."""
    tiled = JaxBackend(min_bucket=TILE, use_pallas=False, autotune=False)
    flat = JaxBackend(use_pallas=False, autotune=False)
    ref = CpuRefBackend()
    out = {}
    for name, n in WIDTHS.items():
        for planted in (_bad_lanes(n), ()):
            reqs = _requests(n, planted)
            st = tiled.submit_window(reqs, fold=True)
            out[name, bool(planted)] = {
                "walked": st["ne"],
                "tiled": tiled.verify_mixed(reqs),
                "fold": tiled.finish_window(st)[0],
                "flat": flat.verify_ed25519_batch(reqs),
                "ref": ref.verify_mixed(reqs)}
    out["programs"] = _programs(tiled)
    assert flat._pad(max(WIDTHS.values())) == 128
    return out


@pytest.mark.parametrize("planted", [True, False], ids=["bad", "clean"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_window_path_equals_flat_program_and_reference(lanes, width,
                                                       planted):
    n = WIDTHS[width]
    got = lanes[width, planted]
    bad = _bad_lanes(n) if planted else ()
    assert got["tiled"] == got["flat"] == got["ref"] \
        == [i not in bad for i in range(n)]
    assert got["walked"] == -(-n // TILE) * TILE


@pytest.mark.parametrize("planted", [True, False], ids=["bad", "clean"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_folded_first_bad_index_is_the_references(lanes, width, planted):
    got = lanes[width, planted]
    want = got["ref"].index(False) if planted else None
    assert got["fold"].n == WIDTHS[width]
    assert got["fold"].first_bad == want
    assert got["fold"].all_ok == (want is None)


def test_six_widths_ran_one_tile_program_in_its_two_forms(lanes):
    assert lanes["programs"] == ([False, True], [], [])
