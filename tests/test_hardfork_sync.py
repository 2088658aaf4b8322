"""The hard-fork replay's tier-1 guard (the benchmark's `sync-hardfork`
at rehearsal size): a chain forged on each era's OWN parameters in
miniature (a 60-slot Byron epoch under Shelley's 432,000, seven genesis
keys signing in turn under a 0.22 threshold, Sum6 KES with the genesis's
period), a Byron tail, the fork at the epoch boundary, a Shelley head,
through the streamed, pipelined one-chip device path in windows of 8.

Held to a PLAIN reference that shares none of that machinery: a fold of
`rules.tick_then_apply` over the decoded blocks, one block at a time,
on the pure-Python crypto (`CpuRefBackend`): no pipeline, no windows, no
decode workers.

What the crossing has that a Shelley chain has not: Byron windows that
call the tile program and nothing else, Byron windows that carry the
betas of the Shelley windows two ahead, a window that holds blocks of
both eras with the translation in its middle, and all of them on the ONE
composite a Shelley chain of the same window builds, whichever era the
backend meets first.  One module fixture forges and replays; each test
reads one property, so a failure names what broke.  The composite is the
one `tests/test_longchain.py` and the `--rehearse` runs of the
eight-window cells build (16, 16, 16): seconds from the compile cache.
"""
import dataclasses
import io
import json
import os
import subprocess
import sys

import pytest

pytest.importorskip("jax")

import jax                                                      # noqa: E402

from ouroboros_tpu import observe                               # noqa: E402
from ouroboros_tpu.consensus.batch import (                     # noqa: E402
    replay_blocks_pipelined,
)
from ouroboros_tpu.consensus.hardfork.combinator import ERA_FIELD  # noqa: E402
from ouroboros_tpu.consensus.header_validation import HeaderError  # noqa: E402
from ouroboros_tpu.consensus.headers import ProtocolBlock       # noqa: E402
from ouroboros_tpu.consensus.ledger import LedgerError          # noqa: E402
from ouroboros_tpu.crypto.backend import (                      # noqa: E402
    GLOBAL_BETA_CACHE, CpuRefBackend,
)
from ouroboros_tpu.crypto.jax_backend import JaxBackend         # noqa: E402
from ouroboros_tpu.crypto.precompute import (                   # noqa: E402
    GLOBAL_PRECOMPUTE_CACHE,
)
from ouroboros_tpu.eras.byron import SIG_FIELD                  # noqa: E402
from ouroboros_tpu.eras.shelley import KES_FIELD                # noqa: E402
from tools import db_analyser as dba                            # noqa: E402

pytestmark = pytest.mark.device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW, BLOCKS = 8, 64
BYRON_EPOCH, SHELLEY_EPOCH = 60, 432000
BYRON_TXS, SHELLEY_TXS = 2, 1
# Byron blocks of the two chains: the fork inside window 5 (4 + 4), and
# on the edge between windows 4 and 5
CHAINS = {"mid": 44, "edge": 40}
PER_ERA = {"byron": {"genesis_keys": 7, "epoch_length": BYRON_EPOCH,
                     "k": 2160, "threshold": 0.22, "window": 2160,
                     "slot_length": 20.0},
           "shelley": {"k": 2160, "f": "1/20", "epoch_length": SHELLEY_EPOCH,
                       "slots_per_kes_period": 129600, "kes_depth": 6,
                       "max_kes_evolutions": 62, "slot_length": 1.0}}

COUNTERS = ("hfc.era_blocks.byron", "hfc.era_blocks.shelley",
            "hfc.era_host_us.byron", "hfc.era_host_us.shelley",
            "hfc.mixed_windows", "jax_backend.composite_free_windows",
            "jax_backend.composite_builds", "jax_backend.windows_submitted",
            "jax_backend.beta_windows", "beta_cache.host_computes",
            "ledger.byron.txs", "ledger.shelley.txs",
            "replay.decode.worker_blocks", "replay.decode.one_walk_blocks",
            "replay.decode.shipped_txids", "replay.decode.txs")

_PROGRAMS: list = []       # backend compiles (a cache load counts)
jax.monitoring.register_event_duration_secs_listener(
    lambda event, _secs, **_kw: _PROGRAMS.append(event)
    if event.endswith("backend_compile_duration") else None)


def _counters() -> dict:
    return {n: observe.metrics.counter(n).value for n in COUNTERS}


def _clear_caches() -> None:
    GLOBAL_BETA_CACHE.clear()
    GLOBAL_PRECOMPUTE_CACHE.clear()


def forge(out: str, byron_blocks: int, seed: str, blocks: int = BLOCKS,
          protocol: str = "cardano") -> None:
    era = ["--byron-epoch-length", str(BYRON_EPOCH), "--byron-blocks",
           str(byron_blocks), "--byron-keys", "7", "--byron-txs-per-block",
           str(BYRON_TXS), "--pbft-threshold", "0.22", "--pbft-window",
           "2160", "--k", "2160"] if protocol == "cardano" else []
    subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", out, "--protocol", protocol, "--blocks", str(blocks),
         "--txs-per-block", str(SHELLEY_TXS), "--pools", "2", "--f", "1/20",
         "--epoch-length", str(SHELLEY_EPOCH), "--kes-depth", "6",
         "--slots-per-kes-period", "129600", "--seed", seed, *era],
        check=True, capture_output=True)


def _streamed(ctx, backend) -> dict:
    """One replay as `db_analyser --analysis validate --validate full`
    makes it (prefetcher, decode workers, pipelined windows), key caches
    cold; the program's JSON line, the counters it moved, the programs
    it built and its `hfc.translate` spans."""
    db, rules, decode, cfg, chain = ctx
    _clear_caches()
    observe.spans.RECORDER.drain()
    c0, p0 = _counters(), len(_PROGRAMS)
    out = io.StringIO()
    dba.analysis_validate(db, rules, decode, backend, "full", WINDOW, out,
                          hdr_proofs=dba.HEADER_PROOFS[cfg["protocol"]],
                          db_dir=chain, snapshot_every=100)
    c1 = _counters()
    translations = sum(sp.name == "hfc.translate"
                       for root in observe.spans.RECORDER.drain()
                       for sp in root.walk())
    return {**json.loads(out.getvalue()),
            "moved": {n: c1[n] - c0[n] for n in COUNTERS},
            "programs_built": len(_PROGRAMS) - p0,
            "translations": translations}


def _plain_fold(rules, blocks) -> dict:
    """The plain reference: `tick_then_apply`, a block at a time, on the
    pure-Python crypto; every state kept, so a tampered block can be
    handed to the state before it."""
    cpu = CpuRefBackend()
    states = [rules.initial_state()]
    proofs = 0
    for b in blocks:
        states.append(rules.tick_then_apply(states[-1], b, backend=cpu))
        header = 0 if b.header.get("ebb") else (
            1 if b.header.get(ERA_FIELD) == 0 else 4)
        proofs += header + sum(len(tx.witnesses) for tx in b.body)
    return {"states": states, "blocks": len(blocks), "proofs": proofs,
            "state_hash": states[-1].ledger.state_hash().hex()}


def _flip(data: bytes) -> bytes:
    out = bytearray(data)
    out[3] ^= 1
    return bytes(out)


def _flip_field(blk, name: str):
    return ProtocolBlock(
        blk.header.with_fields(**{name: _flip(blk.header.get(name))}),
        blk.body)


def _flip_witness(blk):
    body = list(blk.body)
    (vk, sig), *rest = body[0].witnesses
    body[0] = dataclasses.replace(body[0],
                                  witnesses=((vk, _flip(sig)), *rest))
    return ProtocolBlock(blk.header, type(blk.body)(body))


# what is flipped, in the `mid` chain (44 Byron blocks, the fork inside
# window 5 = blocks 40-47): (block, how, the proof the device names, the
# rule the plain fold names)
TAMPERED = {
    "byron-delegate-signature":
        (10, lambda b: _flip_field(b, SIG_FIELD), "Ed25519Req", HeaderError),
    "byron-witness": (21, _flip_witness, "Ed25519Req", LedgerError),
    "first-shelley-kes-signature":
        (44, lambda b: _flip_field(b, KES_FIELD), "KesReq", HeaderError),
    "shelley-witness-in-the-mixed-window":
        (46, _flip_witness, "Ed25519Req", LedgerError),
}


def _stops(rules, blocks, fold, backend, at: int, tamper) -> dict:
    """The tampered chain through the pipelined device replay and through
    the plain fold: where each stopped, and on what."""
    bad = list(blocks)
    bad[at] = tamper(bad[at])
    _clear_caches()
    res = replay_blocks_pipelined(rules, bad, rules.initial_state(),
                                  backend=backend, window=WINDOW)
    try:
        rules.tick_then_apply(fold["states"][at], bad[at],
                              backend=CpuRefBackend())
        plain = None
    except (HeaderError, LedgerError) as e:
        plain = e
    return {"device_n_valid": res.n_valid, "device_error": res.error,
            "plain_error": plain}


def _lend_programs(built_by: JaxBackend, to: JaxBackend) -> None:
    """`to` asks for programs by its own rule, and where it asks for a
    key `built_by` has built it is lent that program instead of building
    its own (a second backend's programs are new closures: minutes of
    tracing and cache loads for the same code).  A key `built_by` lacks
    is built, and then shows in `to`'s set."""
    for cache, maker in (("_composites", "_window_composite"),
                         ("_folds", "_fold_program"),
                         ("_ed_tile_programs", "_ed_tile_program")):
        def lent(*args, _built=getattr(built_by, cache),
                 _own=getattr(to, cache), _make=getattr(to, maker)):
            key = args if len(args) > 1 else args[0]   # `fold` alone
            if key in _built:
                _own.setdefault(key, _built[key])
            return _make(*args)
        setattr(to, maker, lent)


def _open(chain: str):
    db, rules, decode, cfg = dba.load_db(chain)
    return (db, rules, decode, cfg, chain), \
        [decode(raw) for _entry, raw in db.stream()]


@pytest.fixture(scope="module")
def hardfork(tmp_path_factory):
    root = tmp_path_factory.mktemp("hardfork")
    was_recording = observe.spans.RECORDER.enabled
    observe.enable()
    rec: dict = {}
    try:
        dev = JaxBackend(min_bucket=16, use_pallas=False, autotune=False)
        for name, byron_blocks in CHAINS.items():
            chain = str(root / name)
            forge(chain, byron_blocks, seed="40-" + name)
            ctx, blocks = _open(chain)
            fold = _plain_fold(ctx[1], blocks)
            rec[name] = {"cfg": ctx[3], "fold": fold, "blocks": blocks,
                         "first": _streamed(ctx, dev)}
            if name == "mid":
                rec[name]["second"] = _streamed(ctx, dev)
                rec["stops"] = {
                    what: _stops(ctx[1], blocks, fold, dev, at, tamper)
                    for what, (at, tamper, _p, _r) in TAMPERED.items()}
        rec["composites"] = sorted(k[:3] for k in dev._composites)
        rec["folds"] = sorted(dev._folds)
        rec["tile_programs"] = sorted(dev._ed_tile_programs)
        # a Shelley chain of the same Shelley width, on a backend that
        # has met nothing else
        shelley = str(root / "shelley")
        forge(shelley, 0, seed="40-shelley", blocks=3 * WINDOW,
              protocol="shelley")
        other = JaxBackend(min_bucket=16, use_pallas=False, autotune=False)
        _lend_programs(dev, other)
        rec["shelley"] = _streamed(_open(shelley)[0], other)
        rec["shelley_composites"] = sorted(k[:3] for k in other._composites)
        rec["shelley_folds"] = sorted(other._folds)
    finally:
        if not was_recording:
            observe.spans.RECORDER.disable()
    return rec


# -- the forge ---------------------------------------------------------------
@pytest.mark.parametrize("seed", ["1", "3000000007"])
def test_the_forge_gives_the_cells_shape(tmp_path, seed):
    """A Byron tail in consecutive slots ending at the epoch's last, the
    first Shelley slot at or after the boundary, 2 and N transactions a
    block, `config.json` holding every per-era parameter, and `load_db`
    building the same rules from it."""
    chain = str(tmp_path / "chain")
    forge(chain, 12, seed, blocks=20)
    (_db, rules, _decode, cfg, _dir), blocks = _open(chain)
    byron, shelley = blocks[:12], blocks[12:]
    assert [b.slot for b in byron] == list(range(BYRON_EPOCH - 12,
                                                 BYRON_EPOCH))
    assert all(b.header.get(ERA_FIELD) == 0 for b in byron)
    assert all(b.header.get(ERA_FIELD) == 1 for b in shelley)
    assert shelley[0].slot >= BYRON_EPOCH and not any(
        b.header.get("ebb") for b in blocks)
    assert [b.header.issuer for b in byron] == [b.slot % 7 for b in byron]
    assert {len(b.body) for b in byron} == {BYRON_TXS}
    assert {len(b.body) for b in shelley} == {SHELLEY_TXS}
    assert byron[0].body[0].certs and not byron[1].body[0].certs
    assert cfg["byron"] == PER_ERA["byron"]
    assert cfg["shelley"] == PER_ERA["shelley"]
    assert cfg["fork_epoch"] == 1 and cfg["chain"] == {
        "byron_blocks": 12, "first_slot": BYRON_EPOCH - 12,
        "byron_txs_per_block": BYRON_TXS, "txs_per_block": SHELLEY_TXS}
    b_era, s_era = rules.ledger.eras
    assert (b_era.protocol.n, b_era.protocol.threshold,
            b_era.protocol.window, b_era.protocol.epoch_length,
            b_era.protocol.security_param) == (7, 0.22, 2160, BYRON_EPOCH,
                                               2160)
    assert (b_era.params.epoch_size, b_era.params.slot_length) == (
        BYRON_EPOCH, 20.0)
    s = s_era.protocol.config
    assert (s.k, str(s.f), s.epoch_length, s.slots_per_kes_period,
            s.kes_depth, s.max_kes_evolutions) == (2160, "1/20",
                                                   SHELLEY_EPOCH, 129600,
                                                   6, 62)
    assert (s_era.params.epoch_size, s_era.params.slot_length) == (
        SHELLEY_EPOCH, 1.0)
    # the same rules: a second load replays the chain to the same state
    st = rules.initial_state()
    for b in blocks:
        st = rules.tick_then_reapply(st, b)
    again = dba.load_db(chain)[1]
    st2 = again.initial_state()
    for b in blocks:
        st2 = again.tick_then_reapply(st2, b)
    assert st.ledger.state_hash() == st2.ledger.state_hash()
    assert st.ledger.era == 1 and st.ledger.transitions == (1,)


# -- the streamed replay against the plain fold --------------------------------
@pytest.mark.parametrize("key", ["state_hash", "blocks", "proofs"])
@pytest.mark.parametrize("replay", ["mid/first", "mid/second", "edge/first"])
def test_streamed_replay_equals_the_plain_fold(hardfork, replay, key):
    chain, which = replay.split("/")
    assert hardfork[chain][which][key] == hardfork[chain]["fold"][key]
    byron = CHAINS[chain]
    assert hardfork[chain]["fold"]["blocks"] == BLOCKS
    assert hardfork[chain]["fold"]["proofs"] == \
        byron * (1 + BYRON_TXS) + (BLOCKS - byron) * (4 + SHELLEY_TXS)


def test_the_final_state_is_a_shelley_state_holding_the_byron_outputs(
        hardfork):
    """Translated once; every Byron output the first Shelley block did
    not spend is in the Shelley UTxO after it."""
    states, blocks = hardfork["mid"]["fold"]["states"], \
        hardfork["mid"]["blocks"]
    n = CHAINS["mid"]
    before, after = states[n].ledger, states[n + 1].ledger
    assert (before.era, after.era, states[-1].ledger.era) == (0, 1, 1)
    assert after.transitions == (1,)
    spent = {i for tx in blocks[n].body for i in tx.inputs}
    crossed = {(t, i) for t, i, _a, _m in before.inner.utxo} - spent
    assert crossed and crossed <= {(u[0], u[1]) for u in after.inner.utxo}


@pytest.mark.parametrize("chain,mixed", [("mid", 1), ("edge", 0)])
def test_the_host_pass_is_told_apart_by_era(hardfork, chain, mixed):
    moved = hardfork[chain]["first"]["moved"]
    byron = CHAINS[chain]
    assert moved["hfc.era_blocks.byron"] == byron
    assert moved["hfc.era_blocks.shelley"] == BLOCKS - byron
    assert moved["hfc.era_host_us.byron"] > 0
    assert moved["hfc.era_host_us.shelley"] > 0
    assert moved["hfc.mixed_windows"] == mixed
    assert moved["ledger.byron.txs"] == byron * BYRON_TXS
    assert moved["ledger.shelley.txs"] == (BLOCKS - byron) * SHELLEY_TXS
    assert hardfork[chain]["first"]["stream"]["era_crossings"] == 1
    # one ledger and one chain-dep translation a replay
    assert hardfork[chain]["first"]["translations"] == 2


@pytest.mark.parametrize("chain", ["mid", "edge"])
def test_byron_windows_without_betas_call_no_composite(hardfork, chain):
    """Windows 0-2 hold Ed25519 lanes alone; windows 3 and 4 carry the
    betas of windows 5 and 6 and ride the composite."""
    moved = hardfork[chain]["first"]["moved"]
    assert moved["jax_backend.windows_submitted"] == BLOCKS // WINDOW
    assert moved["jax_backend.composite_free_windows"] == 3
    assert moved["jax_backend.beta_windows"] == 3          # windows 3-5


@pytest.mark.parametrize("replay", ["mid/first", "mid/second", "edge/first"])
def test_no_beta_is_computed_on_the_host(hardfork, replay):
    """The hand-off two windows ahead starts at the first window that
    holds a Shelley block, across the era boundary."""
    chain, which = replay.split("/")
    assert hardfork[chain][which]["moved"]["beta_cache.host_computes"] == 0


@pytest.mark.parametrize("replay", ["mid/first", "edge/first"])
def test_both_eras_decode_in_the_workers_with_their_ids(hardfork, replay):
    chain, which = replay.split("/")
    moved = hardfork[chain][which]["moved"]
    assert moved["replay.decode.worker_blocks"] == BLOCKS
    assert moved["replay.decode.one_walk_blocks"] == BLOCKS
    assert moved["replay.decode.txs"] == moved["replay.decode.shipped_txids"] \
        == moved["ledger.byron.txs"] + moved["ledger.shelley.txs"]


def test_one_composite_serves_both_chains(hardfork):
    assert hardfork["mid"]["first"]["moved"][
        "jax_backend.composite_builds"] == 1
    assert hardfork["edge"]["first"]["moved"][
        "jax_backend.composite_builds"] == 0
    assert len(hardfork["composites"]) == 1
    assert hardfork["folds"] == hardfork["composites"]
    assert hardfork["tile_programs"] == [True]


def test_a_second_replay_builds_no_program(hardfork):
    assert hardfork["mid"]["first"]["programs_built"] > 0
    assert hardfork["mid"]["second"]["programs_built"] == 0
    assert hardfork["edge"]["first"]["programs_built"] == 0


def test_byron_first_and_shelley_only_leave_the_same_composites(hardfork):
    """The program set does not depend on the order in which a chain's
    eras meet the backend."""
    assert hardfork["shelley"]["blocks"] == 3 * WINDOW
    assert hardfork["shelley_composites"] == hardfork["composites"]
    assert hardfork["shelley_folds"] == hardfork["folds"]


@pytest.mark.parametrize("what", sorted(TAMPERED))
def test_a_flipped_bit_stops_both_at_the_same_block(hardfork, what):
    """The device replay names the block and the proof, the plain fold
    the rule that refused the same block."""
    at, _tamper, proof, rule = TAMPERED[what]
    got = hardfork["stops"][what]
    assert got["device_n_valid"] == at
    assert isinstance(got["device_error"], LedgerError)
    assert f"proof {proof} failed for block {at}" in str(got["device_error"])
    assert type(got["plain_error"]) is rule
