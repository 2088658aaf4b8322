"""The Shelley ledger pass walks a block over ONE UTxO overlay (ISSUE 37):
what that must not change, pinned.

- Every error `sequential_checks` and `_apply_txs` can raise, by its
  text: the literals below were recorded from the commit before the walk
  changed.  A fault at transaction i wins over a different fault at
  transaction j > i; inside one transaction the rules keep their order;
  across the `LedgerRules` seam of `_seq_block_step` a late witness
  fault wins over an early ledger fault (checks run over the whole block
  before `reapply_block` starts).
- The final state hash of seeded `db_synth` chains at the benchmark's
  full-body shape (`pool` and `fresh` witness keys), as literals from
  that commit.
- Earlier states stay what they were: every `ExtLedgerState` of a chain
  is kept, the chain applied, and each kept state read again; a
  snapshot written mid-chain restores to a state that replays to the
  same final hash.  Only `UtxoMap.to_dict()` and `state_hash()` are
  held, not the overlay's `_base` / `_adds` / `_dels` split.
- The delegation map and the pool registry are the same persistent map
  (ISSUE 45): `PersistentMap` against a dict over random streams of sets,
  overwrites and deletes with a freeze every few steps, and the state
  hash of a chain that carries certificates as the sorted-tuple form
  made it.
"""
import hashlib
import os
import pickle
import random
import subprocess
import sys
from dataclasses import replace
from fractions import Fraction

import pytest

from ouroboros_tpu.consensus import batch
from ouroboros_tpu.consensus.headers import ProtocolBlock, make_header
from ouroboros_tpu.consensus.ledger import ExtLedgerRules, LedgerError
from ouroboros_tpu.crypto import ed25519_ref
from ouroboros_tpu.crypto.backend import OpensslBackend
from ouroboros_tpu.eras.shelley import (
    CERT_DELEG, CERT_POOL, CERT_RETIRE, PersistentMap, ShelleyLedger,
    TPraosConfig, UtxoMap, forge_tpraos_fields, make_shelley_tx,
    pool_id_of, shelley_genesis_setup, thawed_holds, thawed_set,
)
from ouroboros_tpu.storage.fs import MockFS
from ouroboros_tpu.storage.ledgerdb import LedgerDB
from ouroboros_tpu.storage.stream import pickle_decode, pickle_encode
from tools import db_analyser as dba

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BACKEND = OpensslBackend()
GEN = b"\x00" * 32
CFG = TPraosConfig(k=3, f=Fraction(1, 2), epoch_length=100,
                   slots_per_kes_period=20, kes_depth=6,
                   max_kes_evolutions=62)
SLOT = 5
N_OWNERS = 6


class FakeBlock:
    """Body + slot + hash carrier (the ledger rules' HasHeader surface)."""

    def __init__(self, body, slot=SLOT):
        self.body = tuple(body)
        self.slot = slot
        self.hash = hashlib.blake2b(
            b"%d" % slot + b"".join(tx.txid for tx in body),
            digest_size=32).digest()
        self.header = self


def _sk(tag: bytes) -> bytes:
    return hashlib.blake2b(b"walk-" + tag, digest_size=32).digest()


class Ctx:
    """A ledger of one era over six owners of 1000 each, one registered
    pool (cold key 0) with a claimable reward of 7, and the ticked state
    a block at SLOT is applied to."""

    def __init__(self, era: str):
        self.sks = [_sk(b"owner-%d" % i) for i in range(N_OWNERS)]
        self.vks = [ed25519_ref.public_key(sk) for sk in self.sks]
        self.cold_sks = [_sk(b"cold-%d" % i) for i in range(2)]
        self.cold_vks = [ed25519_ref.public_key(sk) for sk in self.cold_sks]
        self.pids = [pool_id_of(vk) for vk in self.cold_vks]
        self.policy_sk = _sk(b"policy")
        self.aid = pool_id_of(ed25519_ref.public_key(self.policy_sk))
        self.ledger = ShelleyLedger(
            {vk: 1000 for vk in self.vks}, CFG,
            {self.pids[0]: b"\x01" * 32}, {self.vks[0]: self.pids[0]},
            era=era)
        st = replace(self.ledger.initial_state(),
                     rewards=((self.pids[0], 7),))
        self.ticked = self.ledger.tick(st, SLOT)
        order = sorted(self.vks)
        self.genesis_in = [(GEN, order.index(vk)) for vk in self.vks]

    def tx(self, o: int, *, inputs=None, outputs=None, certs=(),
           signers=None, **kw):
        """Owner o's genesis output moved to owner o, unless told
        otherwise; signed by owner o unless told otherwise."""
        return make_shelley_tx(
            [self.genesis_in[o]] if inputs is None else inputs,
            [(self.vks[o], 1000)] if outputs is None else outputs,
            certs,
            [self.sks[o]] if signers is None else signers, **kw)


# -- the faults, one transaction each ---------------------------------------
# name -> (earliest era it can be built in, builder(ctx, owner) -> tx)

CHECKS_FAULTS = {
    "validity-needs-allegra": ("shelley", lambda c, o: c.tx(
        o, validity=(0, 100))),
    "outside-validity": ("allegra", lambda c, o: c.tx(
        o, validity=(50, 100))),
    "mint-needs-mary": ("allegra", lambda c, o: c.tx(
        o, mint=[(c.aid, 1)], signers=[c.sks[o], c.policy_sk])),
    "asset-output-needs-mary": ("shelley", lambda c, o: c.tx(
        o, outputs=[(c.vks[o], 1000, [(c.aid, 1)])])),
    "spend-unwitnessed": ("shelley", lambda c, o: c.tx(
        o, signers=[c.sks[(o + 1) % N_OWNERS]])),
    "pool-reg-unwitnessed": ("shelley", lambda c, o: c.tx(
        o, certs=[(CERT_POOL, c.cold_vks[1], b"\x02" * 32)])),
    "deleg-unwitnessed": ("shelley", lambda c, o: c.tx(
        o, certs=[(CERT_DELEG, c.vks[(o + 1) % N_OWNERS], c.pids[0])])),
    "retire-unwitnessed": ("shelley", lambda c, o: c.tx(
        o, certs=[(CERT_RETIRE, c.cold_vks[0], (5).to_bytes(8, "big"))])),
    "withdrawal-unwitnessed": ("shelley", lambda c, o: c.tx(
        o, outputs=[(c.vks[o], 1007)], withdrawals=[(c.pids[0], 7)])),
    "mint-unwitnessed": ("mary", lambda c, o: c.tx(
        o, outputs=[(c.vks[o], 1000, [(c.aid, 1)])], mint=[(c.aid, 1)])),
}

APPLY_FAULTS = {
    "duplicate-inputs": ("shelley", lambda c, o: c.tx(
        o, inputs=[c.genesis_in[o], c.genesis_in[o]])),
    "missing-input": ("shelley", lambda c, o: c.tx(
        o, inputs=[c.genesis_in[o], (b"\xee" * 32, 3)])),
    "withdrawal-mismatch": ("shelley", lambda c, o: c.tx(
        o, outputs=[(c.vks[o], 1006)], withdrawals=[(c.pids[0], 6)],
        signers=[c.sks[o], c.cold_sks[0]])),
    "negative-output": ("shelley", lambda c, o: c.tx(
        o, outputs=[(c.vks[o], 1000), (c.vks[o], -1)])),
    "asset-quantity": ("mary", lambda c, o: c.tx(
        o, outputs=[(c.vks[o], 1000, [(c.aid, 0)])])),
    "overspend": ("shelley", lambda c, o: c.tx(
        o, outputs=[(c.vks[o], 1001)])),
    "asset-imbalance": ("mary", lambda c, o: c.tx(
        o, outputs=[(c.vks[o], 1000, [(c.aid, 5)])])),
    "deleg-unregistered": ("shelley", lambda c, o: c.tx(
        o, certs=[(CERT_DELEG, c.vks[o], c.pids[1])])),
    "retire-unregistered": ("shelley", lambda c, o: c.tx(
        o, certs=[(CERT_RETIRE, c.cold_vks[1], (5).to_bytes(8, "big"))],
        signers=[c.sks[o], c.cold_sks[1]])),
    "retire-epoch-past": ("shelley", lambda c, o: c.tx(
        o, certs=[(CERT_RETIRE, c.cold_vks[0], (0).to_bytes(8, "big"))],
        signers=[c.sks[o], c.cold_sks[0]])),
    "unknown-cert": ("shelley", lambda c, o: c.tx(
        o, certs=[("bogus", c.vks[o], b"")])),
}

# the texts, recorded from the commit before the walk changed
EXPECTED = {
    "validity-needs-allegra":
        "validity intervals need allegra+, era is shelley",
    "outside-validity":
        "tx 3b414ba3f2bf outside validity interval [50, 100] at slot 5",
    "mint-needs-mary": "multi-asset values need mary, era is allegra",
    "asset-output-needs-mary": "multi-asset values need mary, era is shelley",
    "spend-unwitnessed":
        "tx f396aead26f8 spends from a737f634b571 without a witness",
    "pool-reg-unwitnessed": "pool registration without the cold-key witness",
    "deleg-unwitnessed": "delegation without the staking-key witness",
    "retire-unwitnessed": "pool retirement without the cold-key witness",
    "withdrawal-unwitnessed":
        "withdrawal from ff3a99829cd8 without the pool cold-key witness",
    "mint-unwitnessed":
        "minting asset 1d7cd03a5bab without its policy-key witness",
    "duplicate-inputs": "tx 52b97801fc36 has duplicate inputs",
    "missing-input": "missing input eeeeeeeeeeee#3",
    "withdrawal-mismatch":
        "tx ad60351f45f5: withdrawal 6 != reward balance 7 of ff3a99829cd8",
    "negative-output": "tx fcc36aff2bd8 has a negative output",
    "asset-quantity": "output asset quantity must be positive",
    "overspend": "tx 15c3f786e78e produces 1001 > spends 1000",
    "asset-imbalance":
        "tx 692f22cf8a94: asset balance mismatch "
        "(consumed+minted != produced)",
    "deleg-unregistered": "delegation to unregistered pool 98fe7a8dfb47",
    "retire-unregistered": "retirement of unregistered pool 98fe7a8dfb47",
    "retire-epoch-past": "retirement epoch 0 not after the current epoch 0",
    "unknown-cert": "unknown certificate kind 'bogus'",
}


def _partner(group: dict, name: str) -> str:
    """A DIFFERENT fault of the same call that every era can carry."""
    first, second = (("spend-unwitnessed", "deleg-unwitnessed")
                     if group is CHECKS_FAULTS
                     else ("overspend", "missing-input"))
    return second if name == first else first


def _raised(fn, *args, **kw) -> str:
    with pytest.raises(LedgerError) as e:
        fn(*args, **kw)
    return str(e.value)


@pytest.mark.parametrize("name", list(CHECKS_FAULTS) + list(APPLY_FAULTS))
def test_first_faulty_transaction_raises_its_own_error(name):
    """Light, FAULT, light, another fault of the same call: the block
    raises the first fault's text, to the letter."""
    group = CHECKS_FAULTS if name in CHECKS_FAULTS else APPLY_FAULTS
    era, fault = group[name]
    ctx = Ctx(era)
    other = group[_partner(group, name)][1]
    body = [ctx.tx(0), fault(ctx, 1), ctx.tx(2), other(ctx, 3)]
    got = _raised(ctx.ledger.apply_block, ctx.ticked, FakeBlock(body),
                  backend=BACKEND)
    assert got == EXPECTED[name]
    # and the other fault is one: in the first's place it raises its own
    alone = [ctx.tx(0), other(ctx, 1), ctx.tx(2)]
    assert _raised(ctx.ledger.apply_block, ctx.ticked, FakeBlock(alone),
                   backend=BACKEND) == EXPECTED[_partner(group, name)]


FEATURE_FAULTS = ["validity-needs-allegra", "outside-validity",
                  "mint-needs-mary", "asset-output-needs-mary"]


# what the mempool's pseudo-block raises: its slot is the tip's
MEMPOOL_TEXT = {
    "validity-needs-allegra":
        "validity intervals need allegra+, era is shelley",
    "outside-validity":
        "tx 3b414ba3f2bf outside validity interval [50, 100] at slot -1",
    "mint-needs-mary": "multi-asset values need mary, era is allegra",
    "asset-output-needs-mary": "multi-asset values need mary, era is shelley",
}


@pytest.mark.parametrize("name", FEATURE_FAULTS)
def test_reapply_and_mempool_gate_features_themselves(name):
    """`reapply_block` and the mempool's `apply_tx` run no
    `sequential_checks`: `_apply_txs` raises the era's gate itself."""
    era, fault = CHECKS_FAULTS[name]
    ctx = Ctx(era)
    tx = fault(ctx, 1)
    assert _raised(ctx.ledger.reapply_block, ctx.ticked,
                   FakeBlock([ctx.tx(0), tx])) == EXPECTED[name]
    assert _raised(ctx.ledger.apply_tx, ctx.ticked, tx,
                   backend=BACKEND) == MEMPOOL_TEXT[name]


# one transaction that carries every `_apply_txs` fault at once, in the
# order the walk raises them; case k leaves out the first k
APPLY_ORDER = ["outside-validity", "duplicate-inputs", "missing-input",
               "withdrawal-mismatch", "negative-output", "asset-quantity",
               "overspend", "asset-imbalance", "deleg-unregistered",
               "unknown-cert"]
APPLY_ORDER_TEXT = {
    "outside-validity":
        "tx 0bac3c70aaa3 outside validity interval [50, 100] at slot 5",
    "duplicate-inputs": "tx 2f0f25adee16 has duplicate inputs",
    "missing-input": "missing input eeeeeeeeeeee#3",
    "withdrawal-mismatch":
        "tx 5593743e4192: withdrawal 6 != reward balance 7 of ff3a99829cd8",
    "negative-output": "tx 7d292310eb40 has a negative output",
    "asset-quantity": "output asset quantity must be positive",
    "overspend": "tx bc2db1aa313e produces 1002 > spends 1000",
    "asset-imbalance":
        "tx 840cb9fa782d: asset balance mismatch "
        "(consumed+minted != produced)",
    "deleg-unregistered": "delegation to unregistered pool 98fe7a8dfb47",
    "unknown-cert": "unknown certificate kind 'bogus'",
}


def _many_faults(ctx: Ctx, faults: list):
    f = set(faults)
    inputs = [ctx.genesis_in[1]]
    if "duplicate-inputs" in f:
        inputs.append(ctx.genesis_in[1])
    if "missing-input" in f:
        inputs.append((b"\xee" * 32, 3))
    outputs = [(ctx.vks[1], 1001 if "overspend" in f else 900)]
    if "negative-output" in f:
        outputs.append((ctx.vks[1], -1))
    if "asset-quantity" in f:
        outputs.append((ctx.vks[1], 1, [(ctx.aid, 0)]))
    if "asset-imbalance" in f:
        outputs.append((ctx.vks[1], 1, [(ctx.aid, 5)]))
    certs = []
    if "deleg-unregistered" in f:
        certs.append((CERT_DELEG, ctx.vks[1], ctx.pids[1]))
    if "unknown-cert" in f:
        certs.append(("bogus", ctx.vks[1], b""))
    return make_shelley_tx(
        inputs, outputs, certs, [ctx.sks[1]],
        validity=(50, 100) if "outside-validity" in f else (),
        withdrawals=[(ctx.pids[0], 6)] if "withdrawal-mismatch" in f
        else [])


@pytest.mark.parametrize("k", range(len(APPLY_ORDER)))
def test_apply_rules_keep_their_order_inside_a_transaction(k):
    ctx = Ctx("mary")
    tx = _many_faults(ctx, APPLY_ORDER[k:])
    got = _raised(ctx.ledger.reapply_block, ctx.ticked,
                  FakeBlock([ctx.tx(0), tx]))
    assert got == APPLY_ORDER_TEXT[APPLY_ORDER[k]]


# the same for `sequential_checks`: features, spender, certificates in
# their order, withdrawal, mint
CHECKS_ORDER = ["outside-validity", "spend-unwitnessed",
                "pool-reg-unwitnessed", "deleg-unwitnessed",
                "retire-unwitnessed", "withdrawal-unwitnessed",
                "mint-unwitnessed"]
CHECKS_ORDER_TEXT = {
    "outside-validity":
        "tx 6811430249c7 outside validity interval [50, 100] at slot 5",
    "spend-unwitnessed":
        "tx 802dae24c8e2 spends from a737f634b571 without a witness",
    "pool-reg-unwitnessed": "pool registration without the cold-key witness",
    "deleg-unwitnessed": "delegation without the staking-key witness",
    "retire-unwitnessed": "pool retirement without the cold-key witness",
    "withdrawal-unwitnessed":
        "withdrawal from ff3a99829cd8 without the pool cold-key witness",
    "mint-unwitnessed":
        "minting asset 1d7cd03a5bab without its policy-key witness",
}


def _many_unwitnessed(ctx: Ctx, faults: list):
    f = set(faults)
    certs = []
    if "pool-reg-unwitnessed" in f:
        certs.append((CERT_POOL, ctx.cold_vks[1], b"\x02" * 32))
    if "deleg-unwitnessed" in f:
        certs.append((CERT_DELEG, ctx.vks[2], ctx.pids[0]))
    if "retire-unwitnessed" in f:
        certs.append((CERT_RETIRE, ctx.cold_vks[0],
                      (5).to_bytes(8, "big")))
    return make_shelley_tx(
        [ctx.genesis_in[1]], [(ctx.vks[1], 1000)], certs,
        [ctx.sks[3] if "spend-unwitnessed" in f else ctx.sks[1]],
        validity=(50, 100) if "outside-validity" in f else (),
        withdrawals=[(ctx.pids[0], 7)] if "withdrawal-unwitnessed" in f
        else [],
        mint=[(ctx.aid, 1)] if "mint-unwitnessed" in f else [])


@pytest.mark.parametrize("k", range(len(CHECKS_ORDER)))
def test_check_rules_keep_their_order_inside_a_transaction(k):
    ctx = Ctx("mary")
    tx = _many_unwitnessed(ctx, CHECKS_ORDER[k:])
    got = _raised(ctx.ledger.sequential_checks, ctx.ticked,
                  FakeBlock([ctx.tx(0), tx]))
    assert got == CHECKS_ORDER_TEXT[CHECKS_ORDER[k]]


# -- the seam of _seq_block_step ---------------------------------------------

SEAM_TEXT = {
    "late witness fault wins":
        "tx a918cd770004 spends from 7875b27c6b39 without a witness",
    "ledger fault alone": "tx 67f18d0178f6 produces 1000000000 > spends 1000",
}


def _first_leader():
    """A two-pool TPraos network at its first slot with a leader."""
    protocol, ledger, pools = shelley_genesis_setup(2, CFG, seed=b"seam")
    ext = ExtLedgerRules(protocol, ledger)
    state = ext.initial_state()
    slot = 0
    while True:
        view = ledger.forecast_view(state.ledger, slot)
        ticked = protocol.tick_chain_dep_state(
            state.header.chain_dep_state, view, slot)
        for pool in pools:
            lead = protocol.check_is_leader(pool["can_be_leader"], slot,
                                            ticked, view)
            if lead is not None:
                return protocol, ledger, pools, state, slot, pool, lead
        slot += 1


@pytest.fixture(scope="module")
def tpraos_net():
    return _first_leader()


def _forged(net, body):
    protocol, _ledger, _pools, _state, slot, pool, lead = net
    body = tuple(body)
    h = forge_tpraos_fields(protocol, pool["hot_key"],
                            pool["can_be_leader"], lead,
                            make_header(None, slot, body, issuer=0))
    return ProtocolBlock(h, body)


@pytest.mark.parametrize("case", list(SEAM_TEXT))
def test_seam_runs_checks_over_the_block_before_reapply(tpraos_net, case):
    """An overspend at transaction 0 and an unwitnessed spend at
    transaction 1: `_seq_block_step` raises the witness fault, because
    `sequential_checks` walks the whole block before `reapply_block`
    starts.  Without the late fault the early one is raised."""
    assert _seam_error(tpraos_net, case) == SEAM_TEXT[case]


def _seam_error(net, case: str) -> str:
    protocol, ledger, pools, state, _slot, _pool, _lead = net
    order = sorted(p["addr"] for p in pools)
    a, b = pools
    overspend = make_shelley_tx(
        [(GEN, order.index(a["addr"]))], [(a["addr"], 10 ** 9)], [],
        [a["keys"].addr_sk])
    late = make_shelley_tx(
        [(GEN, order.index(b["addr"]))], [(b["addr"], 1)], [],
        [a["keys"].addr_sk if case.startswith("late")
         else b["keys"].addr_sk])
    return _raised(batch._seq_block_step, protocol, ledger, state,
                   _forged(net, [overspend, late]))


# -- golden state hashes of db_synth chains ---------------------------------

GOLDEN = {
    # (witness keys, blocks, txs a block) -> final ledger state hash
    ("pool", 6, 352):
        "a02e9aaf8d471f8a328240ccaf67a1c8ae7d8f0f16491e7240681c62cc2c7e2a",
    ("fresh", 6, 352):
        "0f0bbc29906bcb1f0158f2e0be06e075b4162d008b17aa63c293c4686b48ab8c",
    ("pool", 20, 40):
        "f3f329175b7cb0340ab11648f47a0ac3d5dbd2b735970db4572e7e91c4099184",
}


# 40 blocks of 3 plain spends, 2 first delegations and 1 re-delegation, a
# pool registered in blocks 15 and 31: the hash the ledger of the commit
# before ISSUE 45 (delegation map and pool registry as sorted tuples)
# replayed this chain to
CERT_MIX = ("--deleg-txs-per-block", "2", "--redeleg-txs-per-block", "1",
            "--redeleg-after-blocks", "8", "--pool-reg-every-blocks", "16")
GOLDEN_CERT_MIX = \
    "bb91185392f72d56a27daf1155e3cecfedc36722e6c5ad3c246a79060351b6f1"


def _synth(root, keys: str, blocks: int, txs: int, *more: str):
    chain = os.path.join(str(root), f"{keys}-{blocks}-{txs}")
    subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", chain, "--protocol", "shelley", "--blocks", str(blocks),
         "--txs-per-block", str(txs), "--pools", "2", "--f", "1/20",
         "--epoch-length", "432000", "--kes-depth", "6",
         "--witness-keys", keys, "--seed", "37", *more],
        check=True, capture_output=True)
    db, rules, decode, _cfg = dba.load_db(chain)
    return rules, [decode(raw) for _entry, raw in db.stream()]


def _walk(rules, blocks, state=None):
    """The replay's sequential pass, block by block: every state."""
    st = rules.initial_state() if state is None else state
    states = []
    for b in blocks:
        _reqs, st = batch._seq_block_step(rules.protocol, rules.ledger,
                                          st, b)
        states.append(st)
    return states


@pytest.mark.parametrize("keys,blocks,txs", list(GOLDEN))
def test_db_synth_chain_ends_in_the_recorded_state(tmp_path_factory, keys,
                                                   blocks, txs):
    rules, chain = _synth(tmp_path_factory.mktemp("walk"), keys, blocks, txs)
    assert sum(len(b.body) for b in chain) == blocks * txs
    final = _walk(rules, chain)[-1]
    assert final.ledger.state_hash().hex() == GOLDEN[(keys, blocks, txs)]


def test_a_chain_with_certificates_ends_in_the_tuple_forms_state(
        tmp_path_factory):
    rules, chain = _synth(tmp_path_factory.mktemp("walk"), "pool", 40, 6,
                          *CERT_MIX)
    final = _walk(rules, chain)[-1].ledger
    assert final.state_hash().hex() == GOLDEN_CERT_MIX
    assert (len(final.delegs), len(final.pools)) == (2 + 88 - 2, 2 + 2)
    # the pairs a hash, a snapshot or an epoch boundary reads, in key order
    assert list(final.delegs) == sorted(final.delegs.to_dict().items())


# -- the one persistent map, against a dict ------------------------------------

def _random_walk(seed: int, cls=PersistentMap):
    """Blocks of sets, overwrites and deletes over a small key space,
    one thaw and one freeze a block; every map made, beside the dict it
    has to equal."""
    rng = random.Random(seed)
    keys = [b"%04d" % k for k in range((12, 90, 400, 1500)[seed % 4])]
    model = {k: b"v0" for k in rng.sample(keys, len(keys) // 3)}
    m = cls.from_dict(model)
    made = [(m, dict(model))]
    for block in range(60):
        thawed = base, adds, dels = m.thaw()
        for step in range(rng.randrange(0, 2 + len(keys) // 6)):
            k = rng.choice(keys)
            assert thawed_holds(thawed, k) == (k in model)
            if rng.random() < 0.35 and k in model:
                adds.pop(k, None)
                dels.add(k)
                del model[k]
            else:
                v = b"v%d.%d" % (block, step)
                thawed_set(thawed, k, v)
                model[k] = v
        m = cls.freeze(base, adds, dels)
        made.append((m, dict(model)))
    return made


@pytest.mark.parametrize("seed", range(8))
def test_persistent_map_reads_as_the_dict_it_stands_for(seed):
    made = _random_walk(seed)
    if len(made[-1][1]) > 100:
        assert len({id(m._base) for m, _d in made}) > 2, "never flattened"
    # every map ever made, read after all the later ones were: none was
    # touched by a later block's walk
    for m, model in made:
        assert m.to_dict() == model and len(m) == len(model)
        assert list(m) == sorted(model.items())
        assert dict(m) == model
        get = m.getter()
        for k in [*model, b"\xff" * 4]:
            assert (k in m) == (k in model)
            assert m.get(k) == get(k) == model.get(k)
        assert m.get(b"\xff" * 4, 7) == 7


@pytest.mark.parametrize("seed", range(4))
def test_persistent_map_equality_and_pickling(seed):
    made = _random_walk(seed)
    for (m, model), (later, later_model) in zip(made, made[1:]):
        assert m == PersistentMap.from_dict(model)
        assert (m == later) == (model == later_model)
        back = pickle.loads(pickle.dumps(m, pickle.HIGHEST_PROTOCOL))
        assert type(back) is PersistentMap and back == m
        assert list(back) == list(m)
    with pytest.raises(TypeError):
        hash(made[0][0])


def test_the_utxo_map_is_the_same_map_with_rows_for_entries():
    rows = [(b"t" * 32, ix, b"a" * 32, 10 + ix, ()) for ix in (2, 0, 1)]
    utxo = UtxoMap.from_items(rows)
    assert isinstance(utxo, PersistentMap)
    assert list(utxo) == sorted(rows)
    base, adds, dels = utxo.thaw()
    adds.pop((b"t" * 32, 1), None)
    dels.add((b"t" * 32, 1))
    after = UtxoMap.freeze(base, adds, dels)
    assert type(after) is UtxoMap and len(after) == 2 and len(utxo) == 3
    assert list(after) == [r for r in sorted(rows) if r[1] != 1]
    assert pickle.loads(pickle.dumps(after)) == after


# -- earlier states stay what they were --------------------------------------

@pytest.fixture(scope="module")
def chain20(tmp_path_factory):
    return _synth(tmp_path_factory.mktemp("walk"), "fresh", 20, 40)


def test_every_kept_state_reads_as_it_did_when_made(chain20):
    rules, chain = chain20
    states, seen = [], []
    st = rules.initial_state()
    for b in chain:
        _reqs, st = batch._seq_block_step(rules.protocol, rules.ledger,
                                          st, b)
        states.append(st)
        seen.append((st.ledger.utxo.to_dict(), st.ledger.state_hash(),
                     len(st.ledger.utxo)))
    assert len({h for _d, h, _n in seen}) == len(chain)
    for st, (utxo, state_hash, n) in zip(states, seen):
        assert st.ledger.utxo.to_dict() == utxo
        assert st.ledger.state_hash() == state_hash
        assert len(st.ledger.utxo) == n == len(utxo)
    # a fork from a state in the middle leaves the states after it alone
    mid = len(chain) // 2
    again = _walk(rules, chain[mid + 1:], states[mid])
    assert again[-1].ledger.state_hash() == seen[-1][1]
    assert states[mid].ledger.state_hash() == seen[mid][1]


def test_snapshot_mid_chain_restores_and_replays_to_the_same_hash(chain20):
    rules, chain = chain20
    mid = len(chain) // 2
    states = _walk(rules, chain)
    fs = MockFS()
    LedgerDB.take_snapshot(fs, chain[mid].slot, rules.tip(states[mid]),
                           states[mid], pickle_encode)
    slot, point, restored = LedgerDB.read_latest_snapshot(fs, pickle_decode)
    assert (slot, point) == (chain[mid].slot, rules.tip(states[mid]))
    assert restored.ledger.utxo.to_dict() == \
        states[mid].ledger.utxo.to_dict()
    assert restored.ledger.state_hash() == states[mid].ledger.state_hash()
    final = _walk(rules, chain[mid + 1:], restored)[-1]
    assert final.ledger.state_hash() == states[-1].ledger.state_hash()
