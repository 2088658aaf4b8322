"""The 8-window replay's tier-1 guard (the benchmark's `sync-longchain`
at rehearsal size): a 64-block Shelley chain with the mainnet genesis's
KES period (`db_synth --slots-per-kes-period 129600`) through the
one-chip device path in windows of 8, held to the plain reference.

What eight windows have that two do not: windows 0-5 carry the VRF betas
of the window two ahead in their composite and the host pass of windows
2-7 reads them from the cache the drain filled; with the genesis's KES
period a pool's hash path is walked once, so later windows hold no KES
job; and all of them ride the ONE composite the first window builds
(`JaxBackend._occasional_widths`) and the one Ed25519 tile program.  One module fixture replays the chain
three times (clean, clean again, one witness flipped in window 3) and
records what happened; each test reads one property, so a failure names
what broke.  The composite is one XLA:CPU compile (minutes cold, seconds
from the compile cache); `benchmarks/run.py --workload sync-longchain
--rehearse` uses the same sizes, hence the same program.
"""
import dataclasses
import io
import json
import os
import subprocess
import sys
import threading

import pytest

pytest.importorskip("jax")

from ouroboros_tpu import observe                               # noqa: E402
from ouroboros_tpu.consensus.batch import (                     # noqa: E402
    replay_blocks_pipelined,
)
from ouroboros_tpu.consensus.headers import ProtocolBlock       # noqa: E402
from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE      # noqa: E402
from ouroboros_tpu.crypto.jax_backend import JaxBackend         # noqa: E402
from ouroboros_tpu.crypto.precompute import (                   # noqa: E402
    GLOBAL_PRECOMPUTE_CACHE,
)
from tools import db_analyser as dba                            # noqa: E402

pytestmark = pytest.mark.device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCKS, WINDOW = 64, 8
WINDOWS = BLOCKS // WINDOW
KES_PERIOD = 129600                   # the mainnet genesis's slotsPerKESPeriod
PROOFS_A_BLOCK = 1 + 4                # one witness; 2 VRF, KES, OCert
FLIPPED = 3 * WINDOW + 4              # a block of window 3

COUNTERS = ("jax_backend.composite_builds", "jax_backend.windows_submitted",
            "jax_backend.beta_windows", "jax_backend.beta_rows_carried",
            "jax_backend.kes_empty_windows", "beta_cache.host_computes",
            "pipeline.producer_stall_us",
            "replay.stream.backpressure_wait_us")


def _counters() -> dict:
    return {n: observe.metrics.counter(n).value for n in COUNTERS}


def _clear_caches() -> None:
    GLOBAL_BETA_CACHE.clear()
    GLOBAL_PRECOMPUTE_CACHE.clear()


def _validate(ctx, backend) -> dict:
    """One replay as `db_analyser --analysis validate --validate full`
    makes it, key caches cold; the program's JSON line and the counters
    it moved."""
    db, rules, decode, cfg, chain = ctx
    _clear_caches()
    c0 = _counters()
    out = io.StringIO()
    dba.analysis_validate(db, rules, decode, backend, "full", WINDOW, out,
                          hdr_proofs=dba.HEADER_PROOFS[cfg["protocol"]],
                          db_dir=chain, snapshot_every=100)
    c1 = _counters()
    return {**json.loads(out.getvalue()),
            "moved": {n: c1[n] - c0[n] for n in COUNTERS}}


def _flip_witness(blk):
    body = list(blk.body)
    (vk, sig), *rest = body[0].witnesses
    sig = bytearray(sig)
    sig[3] ^= 1
    body[0] = dataclasses.replace(body[0],
                                  witnesses=((vk, bytes(sig)), *rest))
    return ProtocolBlock(blk.header, type(blk.body)(body))


def _stop(rules, blocks, backend) -> dict:
    _clear_caches()
    res = replay_blocks_pipelined(rules, blocks, rules.initial_state(),
                                  backend=backend, window=WINDOW)
    return {"n_valid": res.n_valid, "error": type(res.error).__name__}


@pytest.fixture(scope="module")
def longchain(tmp_path_factory):
    chain = str(tmp_path_factory.mktemp("longchain") / "chain")
    subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", chain, "--protocol", "shelley", "--blocks", str(BLOCKS),
         "--txs-per-block", "1", "--pools", "2", "--f", "1/20",
         "--epoch-length", "432000", "--kes-depth", "6",
         "--slots-per-kes-period", str(KES_PERIOD), "--seed", "33"],
        check=True, capture_output=True)
    db, rules, decode, cfg = dba.load_db(chain)
    ctx = (db, rules, decode, cfg, chain)
    was_recording = observe.spans.RECORDER.enabled
    observe.enable()
    try:
        cpu = dba.make_backend("cpp")
        dev = JaxBackend(min_bucket=16, use_pallas=False, autotune=False)
        rec = {"cfg": cfg,
               "reference": _validate(ctx, cpu),
               "first": _validate(ctx, dev),
               "second": _validate(ctx, dev),
               "shapes": sorted(k[:3] for k in dev._composites),
               "tile_programs": sorted(dev._ed_tile_programs),
               "folds": sorted(dev._folds)}
        blocks = [decode(raw) for _entry, raw in db.stream()]
        blocks[FLIPPED] = _flip_witness(blocks[FLIPPED])
        c0 = _counters()
        rec["device_stop"] = _stop(rules, blocks, dev)
        rec["stop_moved"] = {n: v - c0[n] for n, v in _counters().items()}
        rec["reference_stop"] = _stop(rules, blocks, cpu)
        rec["shapes_after_stop"] = sorted(k[:3] for k in dev._composites)
        rec["programs_after_stop"] = (sorted(dev._ed_tile_programs),
                                      sorted(dev._folds))
        rec["producers_alive"] = sum(
            t.name == "ouro-replay-producer" and t.is_alive()
            for t in threading.enumerate())
    finally:
        if not was_recording:
            observe.spans.RECORDER.disable()
    return rec


def test_chain_stays_in_the_first_kes_period(longchain):
    assert longchain["cfg"]["slots_per_kes_period"] == KES_PERIOD


@pytest.mark.parametrize("key", ["state_hash", "blocks", "proofs"])
@pytest.mark.parametrize("replay", ["first", "second"])
def test_device_path_equals_the_reference(longchain, replay, key):
    assert longchain[replay][key] == longchain["reference"][key]
    assert longchain["reference"]["blocks"] == BLOCKS
    assert longchain["reference"]["proofs"] == BLOCKS * PROOFS_A_BLOCK


@pytest.mark.parametrize("replay", ["first", "second"])
def test_every_window_went_to_the_device(longchain, replay):
    moved = longchain[replay]["moved"]
    assert moved["jax_backend.windows_submitted"] == WINDOWS


def test_one_composite_serves_all_eight_windows(longchain):
    """Window 0 holds every part at its widest (betas for window 2, both
    pools' KES hash paths); windows with a narrower or an empty part
    ride its program."""
    assert longchain["first"]["moved"]["jax_backend.composite_builds"] == 1
    (nv, nb, nk), = longchain["shapes"]
    assert nb >= 2 * WINDOW and nk >= 2 * 6 and nv
    # and the one folding tile program, and one fold
    assert longchain["tile_programs"] == [True]
    assert longchain["folds"] == [(nv, nb, nk)]


def test_second_replay_builds_no_composite(longchain):
    assert longchain["second"]["moved"]["jax_backend.composite_builds"] == 0


@pytest.mark.parametrize("replay", ["first", "second"])
def test_windows_0_to_5_carried_betas(longchain, replay):
    moved = longchain[replay]["moved"]
    assert moved["jax_backend.beta_windows"] == WINDOWS - 2
    assert moved["jax_backend.beta_rows_carried"] == (WINDOWS - 2) \
        * 2 * WINDOW


@pytest.mark.parametrize("replay", ["first", "second"])
def test_host_pass_used_the_carried_betas(longchain, replay):
    """Windows 0-1 ride the prefetch, 2-7 the rows windows 0-5 carried:
    the sequential pass computed no beta itself."""
    assert longchain[replay]["moved"]["beta_cache.host_computes"] == 0


@pytest.mark.parametrize("replay", ["first", "second"])
def test_later_windows_hold_no_kes_job(longchain, replay):
    """Both pools' paths are walked in window 0 (window 1 packs before
    or after window 0's drain stores them: 6 or 7 of 8)."""
    empty = longchain[replay]["moved"]["jax_backend.kes_empty_windows"]
    assert empty in (WINDOWS - 2, WINDOWS - 1)


def test_reference_counts_no_device_window(longchain):
    moved = longchain["reference"]["moved"]
    assert moved["jax_backend.windows_submitted"] == 0
    assert moved["jax_backend.beta_windows"] == 0


@pytest.mark.parametrize("key", ["n_valid", "error"])
def test_flipped_witness_stops_both_at_the_same_block(longchain, key):
    assert longchain["device_stop"][key] == longchain["reference_stop"][key]
    assert longchain["reference_stop"]["n_valid"] == FLIPPED
    assert longchain["reference_stop"]["error"] == "LedgerError"


def test_stop_came_with_windows_in_flight(longchain):
    """The bad window was submitted with the one before it still in
    flight, and the producer may have submitted one or two more before
    it saw the stop: none built a program, and the producer is gone
    when the replay returns."""
    moved = longchain["stop_moved"]
    assert 4 <= moved["jax_backend.windows_submitted"] <= 6
    assert moved["jax_backend.composite_builds"] == 0
    assert longchain["shapes_after_stop"] == longchain["shapes"]
    assert longchain["programs_after_stop"] == (longchain["tile_programs"],
                                                longchain["folds"])
    assert longchain["producers_alive"] == 0


def test_wait_counters_read_a_number(longchain):
    for replay in ("first", "second"):
        moved = longchain[replay]["moved"]
        assert moved["pipeline.producer_stall_us"] >= 0
        assert moved["replay.stream.backpressure_wait_us"] >= 0
