"""A chain whose holders delegate (ISSUE 45): the forge's certificate
mix, the ledger's persistent delegation map against a plain reference,
and the device path on transactions with two witnesses, on the CPU.

The benchmark's `sync-delegrush` at rehearsal size: a 64-block Shelley
chain of 6 transactions a block (3 plain spends, 2 first delegations by
stake keys no earlier block has seen, 1 re-delegation by a key that
delegated at least a window earlier; a pool registered in blocks 15, 31,
47 and 63) through the one-chip device path in windows of 8.  A window
holds 16 header lanes and 9 witness lanes a block, 88 Ed25519 lanes = 6
tiles of 16, 30% of them keys the cache has not seen.

What is held: (a) the forge makes the counts its arguments give, a
re-delegating key delegated a window or more earlier, the same
arguments give the same chain, and arguments that do not go together are
refused; (b) the chain replayed block by block in lockstep with
`testing/dual.py`'s `ShelleySpec` (plain dicts, shares no rule with the
ledger) observes equal after every block; (c) the streamed replay ends
in the state hash of a plain fold of `tick_then_apply` on
`CpuRefBackend` and in the `cpp` reference's, twice over, on ONE
composite, and the walk's counters say what the bodies held; (d) a
delegation whose SECOND witness is flipped, and a delegation to a pool
nobody registered, stop the device path and the reference at the same
block with the same error.  One module fixture makes every replay; each
test reads one property.  The composite is `tests/test_longchain.py`'s
(same sizes, same program).
"""
import dataclasses
import io
import json
import os
import subprocess
import sys

import pytest

pytest.importorskip("jax")

from ouroboros_tpu import observe                               # noqa: E402
from ouroboros_tpu.consensus.batch import (                     # noqa: E402
    replay_blocks_pipelined,
)
from ouroboros_tpu.consensus.headers import (                   # noqa: E402
    ProtocolBlock, make_header,
)
from ouroboros_tpu.crypto.backend import (                      # noqa: E402
    GLOBAL_BETA_CACHE, CpuRefBackend,
)
from ouroboros_tpu.crypto.jax_backend import JaxBackend         # noqa: E402
from ouroboros_tpu.crypto.precompute import (                   # noqa: E402
    GLOBAL_PRECOMPUTE_CACHE,
)
from ouroboros_tpu.eras.shelley import (                        # noqa: E402
    CERT_DELEG, CERT_POOL, forge_tpraos_fields,
    make_shelley_tx, shelley_genesis_setup,
)
from ouroboros_tpu.testing.dual import dual_shelley             # noqa: E402
from tools import db_analyser as dba                            # noqa: E402

from test_cpp_backend import _db_digest                         # noqa: E402

pytestmark = pytest.mark.device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCKS, WINDOW, TILE = 64, 8, 16
WINDOWS = BLOCKS // WINDOW
TXS, DELEGS, REDELEGS, AFTER, POOL_EVERY = 6, 2, 1, 8, 16
SEED = "45"
SHELLEY = ["--protocol", "shelley", "--pools", "2", "--f", "1/20",
           "--epoch-length", "432000", "--kes-depth", "6",
           "--slots-per-kes-period", "129600"]
MIX = ["--txs-per-block", str(TXS), "--deleg-txs-per-block", str(DELEGS),
       "--redeleg-txs-per-block", str(REDELEGS),
       "--redeleg-after-blocks", str(AFTER),
       "--pool-reg-every-blocks", str(POOL_EVERY)]
# what the arguments come to over the chain
N_POOL = BLOCKS // POOL_EVERY
N_REDELEG = (BLOCKS - AFTER) * REDELEGS
N_DELEG = BLOCKS * DELEGS + AFTER * REDELEGS - N_POOL
N_CERT = N_DELEG + N_REDELEG + N_POOL
WITNESSES = BLOCKS * TXS + N_CERT
FLIPPED = 3 * WINDOW + 4              # a block of window 3

COUNTERS = ("jax_backend.composite_builds", "jax_backend.windows_submitted",
            "jax_backend.ed_tiles", "jax_backend.ed_lanes_real",
            "precompute.hits", "precompute.misses",
            "ledger.shelley.txs", "ledger.shelley.light_txs",
            "ledger.shelley.cert_txs", "ledger.shelley.certs.deleg",
            "ledger.shelley.certs.pool", "ledger.shelley.witnesses",
            "ledger.shelley.deleg_new_entries", "ledger.shelley.cert_us")


def _synth(out: str, blocks: int, seed: str, *more: str):
    """db_synth in a child, however it ends."""
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", out, "--blocks", str(blocks), "--seed", seed, *SHELLEY,
         *more], capture_output=True)


def _forge(out: str, blocks: int, seed: str, *more: str) -> dict:
    """A chain forged; its config.json."""
    _synth(out, blocks, seed, *more).check_returncode()
    with open(os.path.join(out, "config.json")) as fh:
        return json.load(fh)


def _counters() -> dict:
    return {n: observe.metrics.counter(n).value for n in COUNTERS}


def _clear_caches() -> None:
    GLOBAL_BETA_CACHE.clear()
    GLOBAL_PRECOMPUTE_CACHE.clear()


def _validate(ctx, backend) -> dict:
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


def _stop(rules, blocks, backend) -> dict:
    _clear_caches()
    c0 = _counters()
    res = replay_blocks_pipelined(rules, blocks, rules.initial_state(),
                                  backend=backend, window=WINDOW)
    return {"n_valid": res.n_valid, "error": type(res.error).__name__,
            "text": str(res.error),
            "builds": _counters()["jax_backend.composite_builds"]
            - c0["jax_backend.composite_builds"]}


def _flip_second_witness(blk, k: int):
    """Transaction k's SECOND witness (the certificate's authorising
    key's) with one bit of its signature flipped."""
    body = list(blk.body)
    first, (vk, sig), *rest = body[k].witnesses
    sig = sig[:3] + bytes([sig[3] ^ 1]) + sig[4:]
    body[k] = dataclasses.replace(body[k],
                                  witnesses=(first, (vk, sig), *rest))
    return ProtocolBlock(blk.header, type(blk.body)(body))


def _block_to_an_unregistered_pool(rules, blocks):
    """One more block on top of the chain, forged as `db_synth` forges
    (the same seed gives the same pools' keys): a delegation, witnessed
    by spender and stake key, to a pool nobody registered."""
    cfg = rules.protocol.config
    protocol, ledger, pools = shelley_genesis_setup(
        2, cfg, stake_per_pool=100_000, seed=SEED.encode())
    ext = rules.initial_state()
    for b in blocks:
        ext = rules.tick_then_reapply(ext, b)
    slot = blocks[-1].slot + 1
    while True:
        view = ledger.forecast_view(ext.ledger, slot)
        ticked = protocol.tick_chain_dep_state(
            ext.header.chain_dep_state, view, slot)
        leaders = [(p, protocol.check_is_leader(p["can_be_leader"], slot,
                                                ticked, view))
                   for p in pools]
        lead = next(((p, pi) for p, pi in leaders if pi is not None), None)
        if lead is not None:
            break
        slot += 1
    owner = pools[0]
    t, i, _a, amount, _assets = next(
        u for u in ext.ledger.utxo if u[2] == owner["addr"])
    stake_sk = b"\x45" * 32
    from ouroboros_tpu.crypto import ed25519_ref
    tx = make_shelley_tx(
        inputs=[(t, i)], outputs=[(owner["addr"], amount)],
        certs=[(CERT_DELEG, ed25519_ref.public_key(stake_sk), b"\x99" * 28)],
        signing_keys=[owner["keys"].addr_sk, stake_sk])
    pool, pi = lead
    hdr = forge_tpraos_fields(protocol, pool["hot_key"],
                              pool["can_be_leader"], pi,
                              make_header(blocks[-1].header, slot, [tx],
                                          issuer=0))
    return ProtocolBlock(hdr, (tx,))


# -- (a) the forge -----------------------------------------------------------------

@pytest.mark.parametrize("bad", [
    ["--deleg-txs-per-block", "2", "--txs-per-block", "1"],   # more than fit
    ["--deleg-txs-per-block", "300", "--txs-per-block", "300"],  # > 65536 B
    ["--redeleg-txs-per-block", "1", "--redeleg-after-blocks", "0"],
    ["--deleg-txs-per-block", "-1", "--redeleg-txs-per-block", "2"],
    ["--deleg-txs-per-block", "1", "--tx-arrivals-per-slot", "1",
     "--tx-arrival-phase-slots", "10"]])
def test_forge_refuses_a_mix_that_does_not_fit(tmp_path, bad):
    assert _synth(str(tmp_path / "db"), 4, "s", *bad).returncode != 0


def test_forge_refuses_a_mix_on_a_chain_that_is_not_shelley(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", str(tmp_path / "db"), "--blocks", "4",
         "--deleg-txs-per-block", "1"], capture_output=True)
    assert r.returncode != 0


def test_same_arguments_same_chain_other_seed_another(tmp_path):
    a, b, c = (str(tmp_path / n) for n in "abc")
    for d, seed in ((a, "s1"), (b, "s1"), (c, "s2")):
        _forge(d, 12, seed, *MIX)
    assert _db_digest(a) == _db_digest(b) != _db_digest(c)


@pytest.fixture(scope="module")
def delegrush(tmp_path_factory):
    chain = str(tmp_path_factory.mktemp("delegrush") / "chain")
    synth_cfg = _forge(chain, BLOCKS, SEED, *MIX)
    db, rules, decode, cfg = dba.load_db(chain)
    ctx = (db, rules, decode, cfg, chain)
    blocks = [decode(raw) for _entry, raw in db.stream()]
    was_recording = observe.spans.RECORDER.enabled
    observe.enable()
    try:
        cpu = dba.make_backend("cpp")
        dev = JaxBackend(min_bucket=TILE)
        rec = {"synth": synth_cfg, "blocks": blocks, "rules": rules,
               "tile": dev.ed_tile,
               "reference": _validate(ctx, cpu),
               "first": _validate(ctx, dev),
               "second": _validate(ctx, dev),
               "shapes": sorted(k[:3] for k in dev._composites)}
        # (c) the plain fold: every proof by the pure-Python backend
        ref = CpuRefBackend()
        ext = rules.initial_state()
        for b in blocks:
            ext = rules.tick_then_apply(ext, b, backend=ref)
        rec["plain_fold"] = ext.ledger
        # (d) the second witness of a delegation in window 3; one more
        # block that delegates to a pool nobody registered
        k = next(i for i, tx in enumerate(blocks[FLIPPED].body)
                 if tx.certs and tx.certs[0][0] == CERT_DELEG)
        bad = list(blocks)
        bad[FLIPPED] = _flip_second_witness(bad[FLIPPED], k)
        nowhere = blocks + [_block_to_an_unregistered_pool(rules, blocks)]
        rec["stops"] = {
            "second-witness": {"at": FLIPPED,
                               "device": _stop(rules, bad, dev),
                               "reference": _stop(rules, bad, cpu)},
            "unregistered-pool": {"at": BLOCKS,
                                  "device": _stop(rules, nowhere, dev),
                                  "reference": _stop(rules, nowhere, cpu)}}
        rec["shapes_at_the_end"] = sorted(k[:3] for k in dev._composites)
    finally:
        if not was_recording:
            observe.spans.RECORDER.disable()
    return rec


def _kinds(blk) -> list:
    return [tx.certs[0][0] if tx.certs else "plain" for tx in blk.body]


def test_every_block_holds_the_mix_its_arguments_give(delegrush):
    seen: dict = {}                 # stake key -> block it first delegated in
    for n, blk in enumerate(delegrush["blocks"]):
        kinds = _kinds(blk)
        assert len(kinds) == TXS and kinds.count("plain") == TXS - 3
        assert kinds.count(CERT_POOL) == (n % POOL_EVERY == POOL_EVERY - 1)
        redelegs = 0
        for tx in blk.body:
            assert len(tx.witnesses) == 1 + len(tx.certs) and \
                len(tx.certs) <= 1
            for kind, key, _pool in tx.certs:
                assert tx.witnesses[1][0] == key     # the authorising key
                if kind == CERT_DELEG and key in seen:
                    redelegs += 1
                    assert n - seen[key] >= AFTER
                elif kind == CERT_DELEG:
                    seen[key] = n
        assert redelegs == (REDELEGS if n >= AFTER else 0)
    assert len(seen) == N_DELEG


def test_the_order_inside_a_block_is_drawn_from_the_seed(delegrush):
    orders = {tuple(_kinds(b)) for b in delegrush["blocks"]}
    assert len(orders) > 8


def test_config_records_the_mix_and_what_was_forged(delegrush):
    mix = delegrush["synth"]["tx_mix"]
    assert mix["made"] == {"plain": BLOCKS * (TXS - 3), "deleg": N_DELEG,
                           "redeleg": N_REDELEG, "pool": N_POOL}
    assert mix["stake_keys"] == N_DELEG
    assert mix["pools_registered"] == 2 + N_POOL
    assert mix["tx_bytes"] == {"plain": 186, "deleg": 358, "pool": 361,
                               "body_max": 3 * 186 + 2 * 358 + 361}
    assert (mix["txs_per_block"], mix["deleg_txs_per_block"],
            mix["redeleg_txs_per_block"], mix["redeleg_after_blocks"],
            mix["pool_reg_every_blocks"]) == (TXS, DELEGS, REDELEGS, AFTER,
                                              POOL_EVERY)


# -- (b) lockstep with the plain spec ------------------------------------------------

def test_ledger_and_plain_spec_observe_equal_after_every_block(delegrush):
    """`DualLedger.apply_block` raises on the first difference in any
    observation (UTxO, pools, delegations, snapshots, pots): the
    persistent delegation map and pool registry against plain dicts."""
    led = delegrush["rules"].ledger
    dual = dual_shelley(led.genesis, led.config, led.initial_pools,
                        led.initial_delegs,
                        initial_reserves=led.initial_reserves)
    sizes = []
    for blk in delegrush["blocks"]:
        res = dual.apply_block(blk)
        assert res.impl_error is None and res.spec_error is None
        sizes.append(len(dual.state.delegs))
        assert sizes[-1] == len(dual.spec.delegs)
    assert sizes[-1] == 2 + N_DELEG and sizes == sorted(sizes)
    assert len(dual.state.pools) == len(dual.spec.pools) == 2 + N_POOL
    assert dual.state.state_hash() == delegrush["plain_fold"].state_hash()


# -- (c) the streamed replay ---------------------------------------------------------

@pytest.mark.parametrize("replay", ["first", "second", "reference"])
def test_streamed_replay_ends_in_the_plain_folds_state(delegrush, replay):
    got = delegrush[replay]
    assert got["state_hash"] == delegrush["plain_fold"].state_hash().hex()
    assert got["blocks"] == BLOCKS
    assert got["proofs"] == 4 * BLOCKS + WITNESSES


def test_final_maps_are_the_plain_folds_entry_for_entry(delegrush):
    final = delegrush["plain_fold"]
    assert len(final.delegs) == 2 + N_DELEG
    assert len(final.pools) == 2 + N_POOL
    assert set(final.delegs.to_dict().values()) <= set(final.pools.to_dict())


def test_one_composite_serves_the_chain(delegrush):
    assert delegrush["first"]["moved"]["jax_backend.composite_builds"] == 1
    assert delegrush["second"]["moved"]["jax_backend.composite_builds"] == 0
    assert len(delegrush["shapes"]) == 1
    assert delegrush["shapes_at_the_end"] == delegrush["shapes"]


@pytest.mark.parametrize("replay", ["first", "second"])
def test_a_window_is_six_tiles_of_hits_and_misses(delegrush, replay):
    moved = delegrush[replay]["moved"]
    assert delegrush["tile"] == TILE
    assert moved["jax_backend.windows_submitted"] == WINDOWS
    assert moved["jax_backend.ed_lanes_real"] == WITNESSES + 2 * BLOCKS
    assert moved["jax_backend.ed_tiles"] == WINDOWS * 6
    # every stake key and cold key misses once, the window it is first
    # met in; the two owners' keys and re-delegating keys hit
    assert moved["precompute.misses"] >= N_DELEG + N_POOL
    assert moved["precompute.hits"] >= BLOCKS * TXS + N_REDELEG


@pytest.mark.parametrize("replay", ["first", "second"])
def test_the_walks_counters_say_what_the_bodies_held(delegrush, replay):
    moved = delegrush[replay]["moved"]
    assert moved["ledger.shelley.txs"] == BLOCKS * TXS
    assert moved["ledger.shelley.light_txs"] == BLOCKS * TXS - N_CERT
    assert moved["ledger.shelley.cert_txs"] == N_CERT
    assert moved["ledger.shelley.certs.deleg"] == N_DELEG + N_REDELEG
    assert moved["ledger.shelley.certs.pool"] == N_POOL
    assert moved["ledger.shelley.witnesses"] == WITNESSES
    assert moved["ledger.shelley.deleg_new_entries"] == N_DELEG
    assert moved["ledger.shelley.cert_us"] > 0


# -- (d) stops -------------------------------------------------------------------------

@pytest.mark.parametrize("where,text", [
    ("second-witness", "proof Ed25519Req failed for block"),
    ("unregistered-pool", "delegation to unregistered pool 999999999999")])
def test_both_paths_stop_at_the_same_block_with_the_same_error(
        delegrush, where, text):
    stop = delegrush["stops"][where]
    assert stop["device"]["n_valid"] == stop["reference"]["n_valid"] \
        == stop["at"]
    assert stop["device"]["error"] == stop["reference"]["error"] \
        == "LedgerError"
    assert text in stop["device"]["text"] and text in stop["reference"]["text"]
    assert stop["device"]["builds"] == 0
