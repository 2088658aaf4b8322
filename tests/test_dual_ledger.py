"""Dual-ledger conformance: production era ledgers vs naive executable
specs over random tx streams (valid and invalid), lockstep after every
block.

Reference: Ledger/Dual.hs + ouroboros-consensus-byronspec (SURVEY.md §2).
"""
import hashlib
import random
from dataclasses import replace
from fractions import Fraction

import pytest

from ouroboros_tpu.crypto import ed25519_ref
from ouroboros_tpu.eras.byron import CERT_DLG, make_byron_tx
from ouroboros_tpu.eras.shelley import (
    CERT_DELEG, CERT_POOL, CERT_RETIRE, SHELLEY_FAMILY, TPraosConfig,
    make_shelley_tx, pool_id_of,
)
from ouroboros_tpu.testing.dual import (
    DualLedgerMismatch, dual_byron, dual_shelley,
)

GEN = b"\x00" * 32


class FakeBlock:
    """Body + slot + hash carrier (the ledger rules' HasHeader surface)."""

    def __init__(self, body, slot):
        self.body = tuple(body)
        self.slot = slot
        self.hash = hashlib.blake2b(
            b"%d" % slot + b"".join(tx.txid for tx in body),
            digest_size=32).digest()
        self.header = self


def _keys(n, tag):
    sks = [hashlib.blake2b(b"dual-%s-%d" % (tag, i),
                           digest_size=32).digest() for i in range(n)]
    return sks, [ed25519_ref.public_key(sk) for sk in sks]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_byron_dual_random_streams(seed):
    rng = random.Random(seed)
    sks, vks = _keys(4, b"by")
    gsks, gvks = _keys(2, b"bygen")
    genesis = {vks[i]: 1000 for i in range(4)}
    dual = dual_byron(genesis, gvks, gvks)
    # spendable outputs per owner index
    owned = {i: [(GEN, sorted(vks).index(vks[i]), 1000)] for i in range(4)}
    slot = 1
    for step in range(60):
        kind = rng.random()
        body = []
        if kind < 0.6:
            # valid transfer
            o = rng.randrange(4)
            if owned[o]:
                txid, ix, amt = owned[o].pop(0)
                dest = rng.randrange(4)
                give = rng.randrange(amt + 1)
                tx = make_byron_tx(
                    [(txid, ix)],
                    [(vks[dest], give), (vks[o], amt - give)],
                    [], [sks[o]])
                owned[dest].append((tx.txid, 0, give))
                owned[o].append((tx.txid, 1, amt - give))
                body = [tx]
        elif kind < 0.75:
            # delegation cert
            gix = rng.randrange(2)
            tx = make_byron_tx(
                [], [], [(CERT_DLG, gix.to_bytes(8, "big"),
                          vks[rng.randrange(4)])], [gsks[gix]])
            body = [tx]
        elif kind < 0.9:
            # invalid: overspend — both sides must reject identically
            o = rng.randrange(4)
            if owned[o]:
                txid, ix, amt = owned[o][0]
                body = [make_byron_tx([(txid, ix)],
                                      [(vks[o], amt + 1)], [], [sks[o]])]
        else:
            # invalid: duplicate inputs
            o = rng.randrange(4)
            if owned[o]:
                txid, ix, amt = owned[o][0]
                body = [make_byron_tx([(txid, ix), (txid, ix)],
                                      [(vks[o], amt)], [], [sks[o]])]
        res = dual.apply_block(FakeBlock(body, slot))    # raises on skew
        if res.impl_error is not None and body:
            # rejected tx: restore generator bookkeeping is unnecessary
            # (owned was only mutated on the valid paths)
            pass
        slot += 1


@pytest.mark.parametrize("seed", [21, 22])
def test_shelley_dual_random_streams(seed):
    rng = random.Random(seed)
    cfg = TPraosConfig(k=3, f=Fraction(1, 2), epoch_length=15,
                       slots_per_kes_period=5, kes_depth=3)
    sks, vks = _keys(4, b"sh")
    cold_sks, cold_vks = _keys(2, b"shcold")
    pool_ids = [pool_id_of(v) for v in cold_vks]
    genesis = {vks[i]: 1000 for i in range(4)}
    dual = dual_shelley(genesis, cfg,
                        {pool_ids[0]: b"\x01" * 32},
                        {vks[0]: pool_ids[0]})
    owned = {i: [(GEN, sorted(vks).index(vks[i]), 1000)] for i in range(4)}
    slot = 1
    for step in range(80):
        kind = rng.random()
        body = []
        if kind < 0.55:
            o = rng.randrange(4)
            if owned[o]:
                txid, ix, amt = owned[o].pop(0)
                dest = rng.randrange(4)
                give = rng.randrange(amt + 1)
                tx = make_shelley_tx(
                    [(txid, ix)],
                    [(vks[dest], give), (vks[o], amt - give)],
                    [], [sks[o]])
                owned[dest].append((tx.txid, 0, give))
                owned[o].append((tx.txid, 1, amt - give))
                body = [tx]
        elif kind < 0.7:
            # register the second pool / re-delegate someone
            which = rng.random()
            o = rng.randrange(4)
            if which < 0.5:
                body = [make_shelley_tx(
                    [], [], [(CERT_POOL, cold_vks[1], b"\x02" * 32)],
                    [cold_sks[1]])]
            else:
                pid = pool_ids[rng.randrange(2)]
                tx = make_shelley_tx(
                    [], [], [(CERT_DELEG, vks[o], pid)], [sks[o]])
                body = [tx]
        elif kind < 0.85:
            o = rng.randrange(4)
            if owned[o]:
                txid, ix, amt = owned[o][0]
                body = [make_shelley_tx([(txid, ix)],
                                        [(vks[o], amt + 5)], [], [sks[o]])]
        else:
            o = rng.randrange(4)
            if owned[o]:
                txid, ix, amt = owned[o][0]
                body = [make_shelley_tx([(txid, ix), (txid, ix)],
                                        [(vks[o], amt)], [], [sks[o]])]
        res = dual.apply_block(FakeBlock(body, slot))
        # delegation to the unregistered pool must fail on BOTH sides —
        # apply_block already asserts error agreement
        slot += rng.randrange(1, 4)     # cross epoch boundaries sometimes


def test_bad_witness_rejected_by_both_sides():
    """A structurally-fine tx with an INVALID signature: the impl rejects
    via the crypto backend, the spec via ed25519_ref — agreement holds."""
    sks, vks = _keys(2, b"bw")
    gsks, gvks = _keys(1, b"bwgen")
    dual = dual_byron({vks[0]: 100}, gvks, gvks)
    tx = make_byron_tx([(GEN, 0)], [(vks[1], 100)], [], [sks[0]])
    bad_sig = bytes(64)
    from dataclasses import replace as _rep
    tx = _rep(tx, witnesses=((vks[0], bad_sig),))
    res = dual.apply_block(FakeBlock([tx], 1))
    assert res.impl_error is not None and res.spec_error is not None
    # and the states stayed in lockstep: a clean spend still works
    good = make_byron_tx([(GEN, 0)], [(vks[1], 100)], [], [sks[0]])
    res2 = dual.apply_block(FakeBlock([good], 2))
    assert res2.impl_error is None


def test_dual_catches_injected_divergence():
    """Sanity: a deliberate impl/spec divergence trips the oracle."""
    sks, vks = _keys(2, b"dv")
    gsks, gvks = _keys(1, b"dvgen")
    dual = dual_byron({vks[0]: 100}, gvks, gvks)
    # corrupt the spec state directly
    dual.spec.utxo[(b"\xff" * 32, 0)] = (vks[1], 5)
    tx = make_byron_tx([(GEN, 0)], [(vks[0], 100)], [], [sks[0]])
    with pytest.raises(DualLedgerMismatch):
        dual.apply_block(FakeBlock([tx], 1))


# ---------------------------------------------------------------------------
# Lockstep at the benchmark's shapes (ISSUE 37): the ledger pass walks a
# block over one UTxO overlay, and the spec knows nothing of overlays
# ---------------------------------------------------------------------------

WALK_CFG = TPraosConfig(k=3, f=Fraction(1, 2), epoch_length=1000,
                        slots_per_kes_period=5, kes_depth=3)


def _gen_in(vks, i):
    return (GEN, sorted(vks).index(vks[i]))


def _genesis_ends(sks, vks, owners):
    """The open end of a chain of spends not yet begun, one an owner:
    (outpoint, amount, the key that may spend it)."""
    return [(_gen_in(vks, o), 1000, sks[o]) for o in owners]


def _chains_of_spends(n, ends, fresh_tag=None):
    """n one-input one-output one-witness transactions going on from the
    open `ends` of len(ends) chains of spends: transaction t spends what
    transaction t - chains made, so all but the first of each chain
    spend an input made earlier in the same block.  With `fresh_tag`
    every transaction pays to a key nothing has seen, which signs the
    chain's next transaction only.  Returns (txs, the new open ends)."""
    ends = list(ends)
    txs = []
    for t in range(n):
        c = t % len(ends)
        txin, amount, sk = ends[c]
        if fresh_tag is None:
            next_sk = sk
        else:
            next_sk = hashlib.blake2b(b"fresh-%s-%d" % (fresh_tag, t),
                                      digest_size=32).digest()
        tx = make_shelley_tx([txin],
                             [(ed25519_ref.public_key(next_sk), amount)],
                             [], [sk])
        ends[c] = ((tx.txid, 0), amount, next_sk)
        txs.append(tx)
    return txs, ends


def _full_blocks(fresh):
    """352 transactions (a body filled to maxBlockBodySize by db_synth's
    shape) in two chains of spends, then 8 more that go on from them."""
    sks, vks = _keys(2, b"walk")
    first, ends = _chains_of_spends(352, _genesis_ends(sks, vks, (0, 1)),
                                    b"a" if fresh else None)
    second, _ = _chains_of_spends(8, ends, b"b" if fresh else None)
    return {vk: 1000 for vk in vks}, {}, {}, "shelley", {}, [first, second]


def _mixed_blocks(era):
    """Light transactions around one of each thing a transaction can
    carry in `era`: certificates (pool, delegation, retirement), a
    withdrawal, two inputs, two witnesses, a validity interval
    (allegra+), a mint, a multi-asset output spent on and a burn
    (mary)."""
    sks, vks = _keys(8, b"mix")
    cold_sks, cold_vks = _keys(2, b"mixcold")
    pids = [pool_id_of(v) for v in cold_vks]
    policy_sk, = _keys(1, b"mixpolicy")[0]
    aid = pool_id_of(ed25519_ref.public_key(policy_sk))
    ix = SHELLEY_FAMILY.index(era)
    light, ends = _chains_of_spends(6, _genesis_ends(sks, vks, (0, 1)))
    body = list(light[:4])
    body.append(make_shelley_tx(          # pool registration + delegation
        [_gen_in(vks, 2)], [(vks[2], 1000)],
        [(CERT_POOL, cold_vks[1], b"\x02" * 32),
         (CERT_DELEG, vks[2], pids[1])], [sks[2], cold_sks[1]]))
    body.append(make_shelley_tx(          # retirement of the first pool
        [], [], [(CERT_RETIRE, cold_vks[0], (3).to_bytes(8, "big"))],
        [cold_sks[0]]))
    body.append(make_shelley_tx(          # withdrawal of the whole balance
        [_gen_in(vks, 3)], [(vks[3], 1007)], [], [sks[3], cold_sks[0]],
        withdrawals=[(pids[0], 7)]))
    two_in = make_shelley_tx(             # two inputs, two witnesses
        [_gen_in(vks, 4), _gen_in(vks, 5)],
        [(vks[4], 1500), (vks[5], 400)], [], [sks[4], sks[5]])
    body.append(two_in)
    body.append(make_shelley_tx(          # ... both spent in this block
        [(two_in.txid, 1), (two_in.txid, 0)], [(vks[5], 1900)], [],
        [sks[5], sks[4]]))
    if ix >= SHELLEY_FAMILY.index("allegra"):
        body.append(make_shelley_tx(
            [_gen_in(vks, 6)], [(vks[6], 1000)], [], [sks[6]],
            validity=(-1, 50)))
    if ix >= SHELLEY_FAMILY.index("mary"):
        mint = make_shelley_tx(
            [_gen_in(vks, 7)],
            [(vks[7], 600, [(aid, 9)]), (vks[6], 400)], [],
            [sks[7], policy_sk], mint=[(aid, 9)])
        move = make_shelley_tx(           # an input whose entry has assets
            [(mint.txid, 0)],
            [(vks[6], 300, [(aid, 4)]), (vks[7], 300, [(aid, 5)])], [],
            [sks[7]])
        burn = make_shelley_tx(
            [(move.txid, 0)], [(vks[6], 300, [(aid, 1)])], [],
            [sks[6], policy_sk], mint=[(aid, -3)])
        body += [mint, move, burn]
    body += light[4:]
    more, _ = _chains_of_spends(4, ends)
    return ({vk: 1000 for vk in vks}, {pids[0]: b"\x01" * 32},
            {vks[0]: pids[0]}, era, {pids[0]: 7}, [body, more])


def _flatten_blocks():
    """Forty-four owners (a base the overlay's deletes have to hide) and ten
    blocks of 24 transactions: the overlay passes its flatten bound
    (64 entries) several times, at block ends, and every block spends
    base entries, earlier blocks' outputs and its own."""
    sks, vks = _keys(44, b"flat")
    blocks, ends = [], _genesis_ends(sks, vks, range(4))
    for b in range(10):
        # four chains go on all the way; four new owners join a block
        ends = ends[:4] + _genesis_ends(sks, vks, range(4 + 4 * b, 8 + 4 * b))
        txs, ends = _chains_of_spends(24, ends,
                                      b"flat%d" % b if b % 2 else None)
        blocks.append(txs)
    return {vk: 1000 for vk in vks}, {}, {}, "shelley", {}, blocks


WALK_CASES = {
    "full-body-two-chains": lambda: _full_blocks(fresh=False),
    "full-body-fresh-keys": lambda: _full_blocks(fresh=True),
    "mixed-shelley": lambda: _mixed_blocks("shelley"),
    "mixed-allegra": lambda: _mixed_blocks("allegra"),
    "mixed-mary": lambda: _mixed_blocks("mary"),
    "flatten-rule": _flatten_blocks,
}


def _walk_dual(case):
    genesis, pools, delegs, era, rewards, blocks = WALK_CASES[case]()
    dual = dual_shelley(genesis, WALK_CFG, pools, delegs, era=era)
    if rewards:
        dual.state = replace(dual.state,
                             rewards=tuple(sorted(rewards.items())))
        dual.spec.rewards = dict(rewards)
    return dual, blocks


@pytest.mark.parametrize("case", list(WALK_CASES))
def test_shelley_block_walk_in_lockstep(case):
    dual, blocks = _walk_dual(case)
    bases = set()
    for slot, body in enumerate(blocks, 1):
        res = dual.apply_block(FakeBlock(body, slot))   # raises on skew
        assert res.impl_error is None, res.impl_error
        assert len(dual.state.utxo) == len(dual.spec.utxo)
        bases.add(id(dual.state.utxo._base))
    if case == "flatten-rule":
        assert len(bases) > 2, "the overlay never passed its bound"


@pytest.mark.parametrize("case", list(WALK_CASES))
def test_mempool_apply_tx_agrees_with_the_block_walk(case):
    """The same transactions one by one through the mempool's entry
    point end where the blocks end (and the spec with them)."""
    dual, blocks = _walk_dual(case)
    st = dual.state
    for body in blocks:
        for tx in body:
            st = dual.impl.apply_tx(st, tx)
    for slot, body in enumerate(blocks, 1):
        dual.apply_block(FakeBlock(body, slot))
    got = dual.observe_impl(st)
    want = dual.spec.observe()
    for part in ("utxo", "pools", "delegs", "rewards", "retiring"):
        assert got[part] == want[part], part
    assert st.utxo == dual.state.utxo
    assert st.tip == dual.impl.initial_state().tip


def test_an_outpoint_deleted_made_again_and_spent_again_stays_spent():
    """A transaction with no inputs and a zero output has the same txid
    every time it is applied, so its outpoint can be made, flattened
    into the base, spent, made again and spent again: the second spend
    has to hide the base's stale entry too, or it comes back."""
    sks, vks = _keys(2, b"again")
    dual = dual_shelley({vk: 1000 for vk in vks}, WALK_CFG, {}, {})
    make = make_shelley_tx([], [(vks[0], 0)], [], [])
    spend = make_shelley_tx([(make.txid, 0)], [(vks[0], 0)], [], [sks[0]])
    fill, _ = _chains_of_spends(70, _genesis_ends(sks, vks, (0, 1)))
    bodies = [[make] + fill, [spend], [make], [spend]]
    for slot, body in enumerate(bodies, 1):
        res = dual.apply_block(FakeBlock(body, slot))
        assert res.impl_error is None, res.impl_error
    assert (make.txid, 0) in dual.state.utxo._base      # it was flattened
    assert (make.txid, 0) not in dual.state.utxo
    res = dual.apply_block(FakeBlock([spend], 5))
    assert "missing input" in str(res.impl_error)
    assert res.spec_error is not None
