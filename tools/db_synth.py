#!/usr/bin/env python
"""db-synth — forge an on-disk chain to replay with db-analyser.

The role the reference's `db-converter` plays for its validate-mainnet CI
gate (ouroboros-consensus-byron `db-converter`,
ouroboros-consensus-byron/ouroboros-consensus-byron.cabal:82 +
.buildkite/validate-mainnet.sh): produce an ImmutableDB the analyser can
replay.

Two chain flavours:
  --protocol mock-praos   mock ledger + mock-Praos (1 VRF + 1 KES/header)
  --protocol shelley      TPraos + Shelley ledger — the BASELINE workload:
                          2 ECVRF proofs + 1 KES sig + 1 OCert Ed25519 sig
                          per header, Ed25519 tx witnesses per body
                          (BASELINE.md configs #2-#4).

Usage: python tools/db_synth.py --out DIR [--protocol shelley] [--blocks N]
       [--txs-per-block M] [--pools P] [--f NUM/DEN]
       [--witness-keys pool|fresh] [--slots-per-kes-period N]
       [--tx-arrivals-per-slot R0,R1,... --tx-arrival-phase-slots S]
       [--deleg-txs-per-block D --redeleg-txs-per-block R
        --redeleg-after-blocks B --pool-reg-every-blocks E]

--witness-keys (shelley) says who signs the transactions.  `pool` (the
default, the chain every earlier version forged, byte for byte): every
transaction pays to, and is signed by, its pool owner's one payment key,
so a chain has as many witness keys as pools.  `fresh`: every
transaction pays to the public key of a secret derived from (seed,
running index), and the next transaction of that chain of spends is
signed by it — the HD-wallet key distribution (CIP-1852: one payment key
an address, a fresh address a transaction), in which no witness key
occurs twice in the chain.  An address IS its key here, so transaction
and block sizes are the same in both; the fresh addresses are not
delegated, which moves nothing before the first epoch boundary.

--tx-arrivals-per-slot with --tx-arrival-phase-slots (shelley; both or
neither, and then `--txs-per-block` says nothing): a block's body is
what the forger's mempool held.  Transactions ARRIVE every slot, R0 a
slot for the first S slots, R1 for the next S, the last rate for good; a
block takes what has arrived since the block before it, up to what fits
the mainnet genesis's maxBlockBodySize (65,536 bytes over the
transaction's size), and the rest waits for the next block.  No new
randomness: the arrivals are a fractional accumulator, and the slot gaps
the seed's leader schedule gives are what spread the block sizes.
`config.json` then also says what the chain came to hold
(`tx_arrivals.txs_per_block`: min, mean, max, empty blocks).

--deleg-txs-per-block D, --redeleg-txs-per-block R (shelley; with
neither, every transaction is the plain spend and the chain is byte for
byte what it was): of a block's `--txs-per-block` transactions, D are
FIRST DELEGATIONS (one input, one output, a `deleg` certificate of a
stake key derived from (seed, running index), which no earlier block has
seen; witnessed by the spender's key and by that stake key) and R are
RE-DELEGATIONS (the same shape, by a stake key that delegated at least
`--redeleg-after-blocks` blocks earlier, to another pool; while no key
is that old, first delegations stand in).  In every
`--pool-reg-every-blocks`-th block one first delegation gives its place
to a POOL REGISTRATION (a `pool` certificate of a new cold key and VRF
key, witnessed by the spender's key and the cold key).  The rest are
plain spends; the order inside a block and every delegation's target
(uniform over the pools registered so far) are drawn from the seed.
The change output goes where a plain spend's goes (a wallet's change
returns to its payment key, and an address IS a key here), so a stake
key holds no output and the delegated stake is 0: nothing reads it
before the first epoch boundary.  `config.json` records the mix, the
three transaction sizes and what was forged (`tx_mix`).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import sys
import time
from fractions import Fraction

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def open_out_db(fs, args):
    """The output store: our native ImmutableDB, or a reference-format
    writer (`--format reference`: the .primary/.secondary/.chunk dialect of
    Impl/Index/{Primary,Secondary}.hs) behind the same append_block shape."""
    from ouroboros_tpu.storage.immutabledb import ImmutableDB
    if getattr(args, "format", "native") != "reference":
        return ImmutableDB.open(fs, args.chunk_size, validate_all=False)

    from ouroboros_tpu.storage.refformat import RefDbWriter
    from ouroboros_tpu.utils import cbor as _cbor

    class _RefShim:
        """ImmutableDB.append_block signature over RefDbWriter, computing
        the header-within-block span the secondary entries record."""

        def __init__(self):
            self._w = RefDbWriter(fs, args.chunk_size,
                                  epoch_length=args.epoch_length)

        def append_block(self, slot, block_no, h, prev_hash, data,
                         is_ebb=False):
            obj = _cbor.loads(data)
            hdr_enc = _cbor.dumps(obj[0])
            off = data.find(hdr_enc)
            if off < 0:
                # fail loudly at write time: a wrong header span in the
                # secondary index would only surface as downstream garbage
                raise RuntimeError(
                    f"block at slot {slot}: header re-encoding is not a "
                    f"substring of the block bytes; cannot record the "
                    f"header span in the reference secondary index")
            self._w.append_block(slot, h, data, is_ebb=is_ebb,
                                 header_offset=off,
                                 header_size=len(hdr_enc))

        def close(self):
            self._w.close()

    return _RefShim()


def synth_mock_praos(args) -> dict:
    from ouroboros_tpu.consensus.headers import ProtocolBlock, make_header
    from ouroboros_tpu.consensus.protocols.praos import (
        HotKey, Praos, PraosConfig, PraosNode, praos_forge_fields,
    )
    from ouroboros_tpu.crypto import ed25519_ref, kes as kes_mod
    from ouroboros_tpu.ledgers.mock import Tx, TxIn, TxOut
    from ouroboros_tpu.storage.fs import IoFS

    seed = args.seed.encode()

    def h(tag: bytes, i: int) -> bytes:
        return hashlib.blake2b(seed + tag + i.to_bytes(4, "big"),
                               digest_size=32).digest()

    n = args.nodes
    vrf_sks = [h(b"vrf", i) for i in range(n)]
    vrf_vks = [ed25519_ref.public_key(sk) for sk in vrf_sks]
    kes_seeds = [h(b"kes", i) for i in range(n)]
    kes_vks = [kes_mod.vk_of(args.kes_depth, s) for s in kes_seeds]
    pay_sks = [h(b"pay", i) for i in range(n)]
    pay_vks = [ed25519_ref.public_key(sk) for sk in pay_sks]

    cfg = PraosConfig(
        nodes=tuple(PraosNode(vrf_vks[i], kes_vks[i], 1) for i in range(n)),
        k=2160, f=float(Fraction(args.f)), epoch_length=args.epoch_length,
        kes_depth=args.kes_depth,
        slots_per_kes_period=max(
            1, (args.blocks * 4) // kes_mod.total_periods(args.kes_depth)))
    protocol = Praos(cfg)
    hot_keys = [HotKey(kes_mod.KesSignKey(args.kes_depth, s))
                for s in kes_seeds]

    genesis = {pay_vks[i].hex(): 10_000 for i in range(n)}
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "config.json"), "w") as fh:
        json.dump({
            "protocol": "mock-praos",
            "k": cfg.k, "f": cfg.f, "epoch_length": cfg.epoch_length,
            "kes_depth": cfg.kes_depth,
            "slots_per_kes_period": cfg.slots_per_kes_period,
            "nodes": [{"vrf_vk": vrf_vks[i].hex(),
                       "kes_vk": kes_vks[i].hex(), "stake": 1}
                      for i in range(n)],
            "genesis": genesis,
            "chunk_size": args.chunk_size,
        }, fh, indent=2)

    fs = IoFS(args.out)
    db = open_out_db(fs, args)

    # spendable outputs per node, seeded from the genesis pseudo-tx whose
    # outputs MockLedger indexes in sorted(vk) order
    GEN = b"\x00" * 32
    spendable: dict[int, list] = {}
    for ix, vk in enumerate(sorted(pay_vks)):
        spendable[pay_vks.index(vk)] = [(GEN, ix, 10_000)]

    state = protocol.initial_chain_dep_state()
    prev = None
    slot = 0
    forged = 0
    t0 = time.time()
    while forged < args.blocks:
        view = None
        ticked = protocol.tick_chain_dep_state(state, view, slot)
        leader = None
        for i in range(n):
            pi = protocol.check_is_leader((i, vrf_sks[i]), slot, ticked,
                                          view)
            if pi is not None:
                leader = (i, pi)
                break
        if leader is None:
            slot += 1
            continue
        i, pi = leader
        body = []
        for t in range(args.txs_per_block):
            owner = (forged * args.txs_per_block + t) % n
            if not spendable[owner]:
                continue
            txid, ix, amount = spendable[owner].pop(0)
            tx = Tx((TxIn(txid, ix),), (TxOut(pay_vks[owner], amount),))
            sig = ed25519_ref.sign(pay_sks[owner], tx.txid)
            tx = Tx(tx.inputs, tx.outputs, ((pay_vks[owner], sig),))
            spendable[owner].append((tx.txid, 0, amount))
            body.append(tx)
        hdr = make_header(prev, slot, body, issuer=i)
        signed = praos_forge_fields(protocol, hot_keys[i], pi, hdr)
        block = ProtocolBlock(signed, tuple(body))
        db.append_block(block.slot, block.block_no, block.hash,
                        block.prev_hash, block.bytes)
        state = protocol.reupdate_chain_dep_state(ticked, signed, view)
        prev = signed
        forged += 1
        slot += 1
        if forged % 500 == 0:
            print(f"  forged {forged}/{args.blocks} "
                  f"({forged / (time.time() - t0):.0f} blocks/s)",
                  file=sys.stderr)
    if hasattr(db, "close"):
        db.close()              # flush the reference-format tail chunk
    return {"blocks": forged, "last_slot": slot - 1}


# mainnet shelley-genesis.json, protocolParams.maxBlockBodySize
MAX_BLOCK_BODY_SIZE = 65536


class _Mempool:
    """Transactions waiting for a block under `--tx-arrivals-per-slot`:
    `rates[i]` arrive in every slot of phase i (`phase_slots` slots
    each; the last rate holds after the last phase), exactly (Fractions),
    and a block takes the whole ones that wait, up to `cap`."""

    def __init__(self, rates: str, phase_slots: int, cap: int):
        self.rates = [Fraction(r) for r in rates.split(",")]
        if phase_slots < 1 or min(self.rates) < 0:
            raise SystemExit("db_synth: --tx-arrivals-per-slot takes "
                             "rates >= 0, --tx-arrival-phase-slots >= 1")
        self.phase_slots, self.cap = phase_slots, cap
        self.waiting = Fraction(0)
        self.next_slot = 0            # the first slot not yet arrived in
        self.taken: list[int] = []    # transactions of every block so far

    def take(self, slot: int) -> int:
        """Arrivals up to and including `slot`; the block's count."""
        for s in range(self.next_slot, slot + 1):
            self.waiting += self.rates[min(s // self.phase_slots,
                                           len(self.rates) - 1)]
        self.next_slot = slot + 1
        n = min(int(self.waiting), self.cap)
        self.waiting -= n
        self.taken.append(n)
        return n

    def summary(self) -> dict:
        t = self.taken
        return {"min": min(t), "mean": round(sum(t) / len(t), 3),
                "max": max(t), "empty_blocks": t.count(0),
                "cap": self.cap, "left_waiting": int(self.waiting)}


class _SpendChains:
    """The bodies `synth_shelley` and `synth_cardano` forge: every owner
    holds one open output, and a transaction spends one owner's output
    and pays its whole amount on, signed by whoever holds it: the
    owner's payment key, for good (`fresh` false) or until the first
    spend (`fresh`: each transaction pays to a key derived from (seed,
    running index), which signs that chain of spends' next transaction
    and nothing else).  The first outputs are the genesis pseudo-tx's,
    which both ledgers index in sorted(address) order."""

    def __init__(self, owners: list, genesis_txid: bytes, amount: int,
                 seed: bytes, fresh: bool = False):
        self.addrs = [addr for addr, _sk in owners]
        order = sorted(self.addrs)
        self.spendable = {i: [(genesis_txid, order.index(addr), amount)]
                          for i, addr in enumerate(self.addrs)}
        self.holder_sk = {i: sk for i, (_addr, sk) in enumerate(owners)}
        self.seed, self.fresh = seed, fresh
        self.n_tx = 0

    def body(self, n_body: int, make_tx, first=None, mix=None) -> list:
        """`n_body` transactions made by `make_tx(inputs, outputs, certs,
        signing_keys)`; transaction t is owner `(first + t) % owners`'s
        (`first` left out: the running transaction index).  With a
        `_CertMix`, each carries what the mix deals this block: nothing,
        or one certificate and its authorising key's witness."""
        from ouroboros_tpu.crypto import ed25519_ref
        if first is None:
            first = self.n_tx
        dealt = mix.deal(n_body) if mix is not None else None
        body = []
        for t in range(n_body):
            owner = (first + t) % len(self.addrs)
            if not self.spendable[owner]:
                continue
            txid, ix, amount = self.spendable[owner].pop(0)
            if self.fresh:
                # pay to a key no earlier block has seen; it signs this
                # chain of spends' next transaction and nothing else
                next_sk = hashlib.blake2b(
                    b"fresh-witness:%s:%d" % (self.seed, self.n_tx),
                    digest_size=32).digest()
                pay_to = ed25519_ref.public_key(next_sk)
            else:
                next_sk, pay_to = self.holder_sk[owner], self.addrs[owner]
            certs, cert_sks = dealt[t]() if dealt is not None else ([], [])
            tx = make_tx(inputs=[(txid, ix)], outputs=[(pay_to, amount)],
                         certs=certs,
                         signing_keys=[self.holder_sk[owner], *cert_sks])
            self.holder_sk[owner] = next_sk
            self.n_tx += 1
            self.spendable[owner].append((tx.txid, 0, amount))
            body.append(tx)
        return body


class _CertMix:
    """The certificates of a chain whose holders delegate: what each of a
    block's transactions carries beside its spend (module docstring,
    --deleg-txs-per-block).  `deal(n)` is called once a block and returns
    one maker a transaction, in an order drawn from the seed; a maker
    returns `(certs, extra signing keys)` and records what it made."""

    def __init__(self, seed: bytes, pool_ids: list, delegs: int,
                 redelegs: int, redeleg_after: int, pool_every: int):
        self.seed = seed
        self.rng = random.Random(hashlib.blake2b(
            b"cert-mix:" + seed, digest_size=8).digest())
        self.pool_ids = list(pool_ids)    # registered so far, in order
        self.delegs, self.redelegs = delegs, redelegs
        self.redeleg_after, self.pool_every = redeleg_after, pool_every
        # every stake key that has delegated: [secret, index of its pool],
        # in delegation order, and how many of them each block began with
        self.staked: list = []
        self.staked_before_block: list = []
        self.made = {"plain": 0, "deleg": 0, "redeleg": 0, "pool": 0}

    def _key(self, tag: bytes, n: int) -> bytes:
        return hashlib.blake2b(b"%s:%s:%d" % (tag, self.seed, n),
                               digest_size=32).digest()

    def _plain(self):
        self.made["plain"] += 1
        return [], []

    def _deleg_cert(self, entry: list):
        """Stake key `entry` delegating to the pool it now names."""
        from ouroboros_tpu.crypto import ed25519_ref
        from ouroboros_tpu.eras.shelley import CERT_DELEG
        sk, to = entry
        return [(CERT_DELEG, ed25519_ref.public_key(sk),
                 self.pool_ids[to])], [sk]

    def _deleg(self):
        entry = [self._key(b"stake-key", len(self.staked)),
                 self.rng.randrange(len(self.pool_ids))]
        self.staked.append(entry)
        self.made["deleg"] += 1
        return self._deleg_cert(entry)

    def _redeleg(self, old_enough: int):
        entry = self.staked[self.rng.randrange(old_enough)]
        # another pool than the one it delegates to now
        entry[1] = (entry[1] + 1 + self.rng.randrange(
            len(self.pool_ids) - 1)) % len(self.pool_ids)
        self.made["redeleg"] += 1
        return self._deleg_cert(entry)

    def _pool(self):
        from ouroboros_tpu.crypto import ed25519_ref
        from ouroboros_tpu.eras.shelley import CERT_POOL, pool_id_of
        n = self.made["pool"]
        cold_sk = self._key(b"pool-cold", n)
        cold_vk = ed25519_ref.public_key(cold_sk)
        vrf_vk = ed25519_ref.public_key(self._key(b"pool-vrf", n))
        self.pool_ids.append(pool_id_of(cold_vk))
        self.made["pool"] += 1
        return [(CERT_POOL, cold_vk, vrf_vk)], [cold_sk]

    def deal(self, n_body: int) -> list:
        block_no = len(self.staked_before_block)
        self.staked_before_block.append(len(self.staked))
        # stake keys old enough to delegate again: those of the blocks
        # up to `redeleg_after` before this one
        at = block_no - self.redeleg_after
        old_enough = self.staked_before_block[at + 1] if at >= 0 else 0
        n_re = self.redelegs if old_enough and len(self.pool_ids) > 1 else 0
        n_first = self.delegs + self.redelegs - n_re
        n_pool = int(self.pool_every > 0 and n_first > 0
                     and block_no % self.pool_every == self.pool_every - 1)
        makers = ([self._pool] * n_pool + [self._deleg] * (n_first - n_pool)
                  + [lambda: self._redeleg(old_enough)] * n_re
                  + [self._plain] * (n_body - n_first - n_re))
        self.rng.shuffle(makers)
        return makers

    def summary(self, tx_bytes: dict, txs_per_block: int) -> dict:
        return {"txs_per_block": txs_per_block,
                "deleg_txs_per_block": self.delegs,
                "redeleg_txs_per_block": self.redelegs,
                "redeleg_after_blocks": self.redeleg_after,
                "pool_reg_every_blocks": self.pool_every,
                "tx_bytes": tx_bytes, "made": dict(self.made),
                "stake_keys": len(self.staked),
                "pools_registered": len(self.pool_ids)}


def synth_shelley(args) -> dict:
    """Forge a TPraos/Shelley chain: the flagship replay workload.

    Reference: the Shelley chain the db-analyser validate-mainnet path
    replays (tools/db-analyser/Block/Shelley.hs + Shelley/Protocol.hs:
    433-442 PRTCL verifies per header; Ledger.hs:279-284 witnesses per
    body)."""
    from ouroboros_tpu.consensus.headers import ProtocolBlock, make_header
    from ouroboros_tpu.consensus.ledger import ExtLedgerRules
    from ouroboros_tpu.crypto import ed25519_ref, kes as kes_mod
    from ouroboros_tpu.eras.shelley import (
        TPraosConfig, forge_tpraos_fields, make_shelley_tx,
        shelley_genesis_setup,
    )
    from ouroboros_tpu.storage.fs import IoFS
    from ouroboros_tpu.utils import cbor

    f = Fraction(args.f)
    # KES periods must cover the whole chain: the genesis's own length
    # when it is given (mainnet's 129600 keeps a short chain inside the
    # first period), else one that spreads the chain over the key's life
    slots_per_period = getattr(args, "slots_per_kes_period", None) or max(
        1, int(args.blocks * 2 / f)
        // kes_mod.total_periods(args.kes_depth) + 1)
    cfg = TPraosConfig(
        k=2160, f=f, epoch_length=args.epoch_length,
        slots_per_kes_period=slots_per_period,
        kes_depth=args.kes_depth,
        max_kes_evolutions=kes_mod.total_periods(args.kes_depth) - 2)
    protocol, ledger, pools = shelley_genesis_setup(
        args.pools, cfg, stake_per_pool=100_000,
        seed=args.seed.encode())

    config = {
        "protocol": "shelley",
        "k": cfg.k, "f": str(f), "epoch_length": cfg.epoch_length,
        "slots_per_kes_period": cfg.slots_per_kes_period,
        "kes_depth": cfg.kes_depth,
        "max_kes_evolutions": cfg.max_kes_evolutions,
        "genesis_seed": "shelley-genesis",
        "genesis": {p["addr"].hex(): 100_000 for p in pools},
        "pools": [{"pool_id": p["keys"].pool_id.hex(),
                   "vrf_vk": p["keys"].vrf_vk.hex(),
                   "addr": p["addr"].hex()} for p in pools],
        "chunk_size": args.chunk_size,
    }

    def write_config() -> None:
        with open(os.path.join(args.out, "config.json"), "w") as fh:
            json.dump(config, fh, indent=2)

    os.makedirs(args.out, exist_ok=True)
    write_config()

    fs = IoFS(args.out)
    db = open_out_db(fs, args)

    ext = ExtLedgerRules(protocol, ledger)
    state = ext.initial_state()
    spends = _SpendChains(
        [(p["addr"], p["keys"].addr_sk) for p in pools],
        ledger.GENESIS_TXID, 100_000, args.seed.encode(),
        fresh=args.witness_keys == "fresh")
    GEN = ledger.GENESIS_TXID
    mix = None
    if args.deleg_txs_per_block or args.redeleg_txs_per_block:
        mix = _CertMix(args.seed.encode(),
                       [p["keys"].pool_id for p in pools],
                       args.deleg_txs_per_block, args.redeleg_txs_per_block,
                       args.redeleg_after_blocks, args.pool_reg_every_blocks)
        # three shapes, three sizes: a body's bytes are reckoned from what
        # it holds of each, against the genesis's limit
        sized = _CertMix(b"size", mix.pool_ids, 0, 0, 0, 0)
        mix_bytes = {
            kind: len(cbor.dumps(make_shelley_tx(
                inputs=[(GEN, 0)], outputs=[(pools[0]["addr"], 100_000)],
                certs=certs, signing_keys=[pools[0]["keys"].addr_sk,
                                           *sks]).encode()))
            for kind, (certs, sks) in (("plain", sized._plain()),
                                       ("deleg", sized._deleg()),
                                       ("pool", sized._pool()))}
        n_cert = args.deleg_txs_per_block + args.redeleg_txs_per_block
        n_pool = int(args.pool_reg_every_blocks > 0)    # at most one a block
        body_bytes = ((args.txs_per_block - n_cert) * mix_bytes["plain"]
                      + (n_cert - n_pool) * mix_bytes["deleg"]
                      + n_pool * mix_bytes["pool"])
        if n_cert > args.txs_per_block or body_bytes > MAX_BLOCK_BODY_SIZE:
            raise SystemExit(
                f"db_synth: {n_cert} certificate transactions of "
                f"{args.txs_per_block} a block come to {body_bytes} bytes; "
                f"a body holds {MAX_BLOCK_BODY_SIZE}")
        mix_bytes["body_max"] = body_bytes
    mempool = None
    if args.tx_arrivals_per_slot is not None:
        # every transaction made here has one input, one output and one
        # witness, so one of them says what all of them weigh
        tx_bytes = len(cbor.dumps(make_shelley_tx(
            inputs=[(GEN, 0)], outputs=[(pools[0]["addr"], 100_000)],
            certs=[], signing_keys=[pools[0]["keys"].addr_sk]).encode()))
        mempool = _Mempool(args.tx_arrivals_per_slot,
                           args.tx_arrival_phase_slots,
                           MAX_BLOCK_BODY_SIZE // tx_bytes)

    prev = None
    slot = 0
    forged = 0
    t0 = time.time()
    while forged < args.blocks:
        view = ledger.forecast_view(state.ledger, slot)
        ticked = protocol.tick_chain_dep_state(
            state.header.chain_dep_state, view, slot)
        lead = None
        for pi, p in enumerate(pools):
            lead = protocol.check_is_leader(p["can_be_leader"], slot,
                                            ticked, view)
            if lead is not None:
                leader_ix = pi
                break
        if lead is None:
            slot += 1
            continue
        p = pools[leader_ix]
        if mempool is None:
            body = spends.body(args.txs_per_block, make_shelley_tx,
                               first=forged * args.txs_per_block, mix=mix)
        else:
            body = spends.body(mempool.take(slot), make_shelley_tx)
        hdr = make_header(prev, slot, body, issuer=0)
        signed = forge_tpraos_fields(protocol, p["hot_key"],
                                     p["can_be_leader"], lead, hdr)
        block = ProtocolBlock(signed, tuple(body))
        db.append_block(block.slot, block.block_no, block.hash,
                        block.prev_hash, block.bytes)
        state = ext.tick_then_reapply(state, block)
        prev = signed
        forged += 1
        slot += 1
        if forged % 500 == 0:
            print(f"  forged {forged}/{args.blocks} "
                  f"({forged / (time.time() - t0):.0f} blocks/s)",
                  file=sys.stderr)
    if hasattr(db, "close"):
        db.close()              # flush the reference-format tail chunk
    if mempool is not None:
        config["tx_arrivals"] = {
            "per_slot": args.tx_arrivals_per_slot,
            "phase_slots": args.tx_arrival_phase_slots,
            "tx_bytes": tx_bytes, "txs_per_block": mempool.summary()}
    if mix is not None:
        config["tx_mix"] = mix.summary(mix_bytes, args.txs_per_block)
    if mempool is not None or mix is not None:
        write_config()
    return {"blocks": forged, "last_slot": slot - 1}


def synth_cardano(args) -> dict:
    """Forge a chain that crosses the hard fork, through the combinator.

    Without `--byron-blocks`: the full era ladder (BASELINE config #5
    shape, Byron->Shelley->Allegra->Mary per Cardano/Block.hs:161-186) on
    ONE small set of parameters for both eras: PBFT blocks + EBBs from
    slot 0, a Byron update proposal naming the Shelley fork epoch, TPraos
    blocks, then configured-epoch hops into Allegra (a validity-interval
    tx exercises the timelock gate) and Mary (a minting tx exercises
    multi-asset); empty bodies but for those.

    With `--byron-blocks N`: each era on its own genesis's parameters
    (mainnet: Byron epochs of 21,600 slots, k 2160, PBFT threshold 0.22
    over the last k blocks, seven genesis keys signing in turn; Shelley
    as `synth_shelley` takes it), and the shape of a sync that crosses
    the fork: N Byron blocks in the N consecutive slots that END Byron
    epoch 0, the fork at that epoch boundary (the first block's update
    proposal names epoch 1), then Shelley blocks up to `--blocks`.  The
    epoch's earlier slots carry no block, so the chain holds no EBB (an
    epoch's EBB lies before its first block).  Bodies in both eras are
    the chains of spends `synth_shelley` forges (`_SpendChains`),
    `--byron-txs-per-block` and `--txs-per-block` a block, the Byron
    era's open outputs crossing the fork in the translated UTxO."""
    from ouroboros_tpu.consensus.hardfork.combinator import ERA_FIELD
    from ouroboros_tpu.consensus.headers import ProtocolBlock, make_header
    from ouroboros_tpu.crypto import kes as kes_mod
    from ouroboros_tpu.eras.byron import (
        CERT_UPDATE, byron_sign_header, make_byron_tx, make_ebb,
    )
    from ouroboros_tpu.eras.cardano import (
        ALLEGRA, BYRON, MARY, cardano_rules,
    )
    from ouroboros_tpu.eras.shelley import (
        forge_tpraos_fields, make_shelley_tx, pool_id_of,
    )
    from ouroboros_tpu.storage.fs import IoFS

    epoch_length = args.epoch_length
    byron_blocks = getattr(args, "byron_blocks", None)
    per_era = byron_blocks is not None
    allegra_epoch = mary_epoch = None
    if per_era:
        byron_epoch_length = args.byron_epoch_length or epoch_length
        if not 0 < byron_blocks <= min(byron_epoch_length, args.blocks):
            raise SystemExit("db_synth: --byron-blocks has to fit the "
                             "Byron epoch and the chain")
        fork_epoch = 1
        first_slot = byron_epoch_length - byron_blocks
        byron_txs, shelley_txs = args.byron_txs_per_block, args.txs_per_block
        shelley = {
            "k": args.k, "f": args.f, "epoch_length": epoch_length,
            "slots_per_kes_period": args.slots_per_kes_period or max(
                1, int(args.blocks * 2 / Fraction(args.f))
                // kes_mod.total_periods(args.kes_depth) + 1),
            "kes_depth": args.kes_depth,
            "max_kes_evolutions": kes_mod.total_periods(args.kes_depth) - 2,
            "slot_length": args.slot_length}
        byron = {
            "genesis_keys": args.byron_keys or args.pools,
            "epoch_length": byron_epoch_length, "k": args.k,
            "threshold": args.pbft_threshold,
            "window": args.pbft_window or args.k,
            "slot_length": args.byron_slot_length}
    else:
        total_epochs = max(8, args.blocks // epoch_length)
        # Byron spans >= 2 epochs so the chain contains an EBB with a
        # same-slot Byron successor (the EBB layout the storage layer
        # must handle)
        fork_epoch = max(2, total_epochs // 4)
        first_slot = 0
        byron_txs = shelley_txs = 0
        if getattr(args, "eras", "ladder") != "byron-shelley":
            # (`byron-shelley`: the two-era chain of the streaming-replay
            # scenario, ISSUE 15: Byron EBBs -> ONE translation -> a long
            # Shelley tail, no intra-Shelley hops)
            allegra_epoch = fork_epoch + max(1, total_epochs // 4)
            mary_epoch = allegra_epoch + max(1, total_epochs // 4)
        # what every earlier version forged: one epoch length, k = 8 and
        # a depth-5 KES key for both eras.  KES periods must cover the
        # whole chain (synth_shelley discipline): 50 slots a period
        # exhausts the key's 30 usable evolutions after ~1500 slots
        shelley = {
            "k": 8, "f": "1/2", "epoch_length": epoch_length,
            "slots_per_kes_period": max(50, (args.blocks * 2) // 30 + 1),
            "kes_depth": 5, "max_kes_evolutions": 30, "slot_length": 0.5}
        byron = {"genesis_keys": args.pools, "epoch_length": epoch_length,
                 "k": 8, "threshold": 0.9, "window": 10, "slot_length": 1.0}
    # everything `db_analyser.load_db` needs to build the same two eras
    config = {
        "protocol": "cardano", "nodes": args.pools, "seed": args.seed,
        "fork_epoch": fork_epoch, "allegra_epoch": allegra_epoch,
        "mary_epoch": mary_epoch, "chunk_size": args.chunk_size,
        "byron": byron, "shelley": shelley,
    }
    eras, rules, nodes = cardano_rules(config)
    if per_era:
        config["chain"] = {"byron_blocks": byron_blocks,
                           "first_slot": first_slot,
                           "byron_txs_per_block": byron_txs,
                           "txs_per_block": shelley_txs}

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "config.json"), "w") as fh:
        json.dump(config, fh, indent=2)
    fs = IoFS(args.out)
    db = open_out_db(fs, args)

    byron_era, shelley_era = eras[0], eras[1]
    pools = [n for n in nodes if "can_be_leader" in n]
    spends = _SpendChains([(p["addr"], p["keys"].addr_sk) for p in pools],
                          byron_era.ledger.GENESIS_TXID, 1000,
                          args.seed.encode())
    state = rules.initial_state()
    prev = None
    slot = first_slot
    forged = 0
    update_sent = False
    # one feature tx per new era (none when the ladder stops at Shelley)
    feature_todo = ({ALLEGRA, MARY} if allegra_epoch is not None
                    else set())
    t0 = time.time()

    def append(blk):
        db.append_block(blk.slot, blk.block_no, blk.hash, blk.prev_hash,
                        blk.bytes, is_ebb=bool(blk.header.get("ebb", 0)))

    while forged < args.blocks:
        view = rules.ledger.ledger_view(rules.ledger.tick(state.ledger,
                                                          slot))
        ticked_dep = rules.protocol.tick_chain_dep_state(
            state.header.chain_dep_state, view, slot)
        if ticked_dep.era == BYRON:
            if slot % byron["epoch_length"] == 0 and slot > 0:
                ebb = make_ebb(prev, slot // byron["epoch_length"],
                               byron["epoch_length"])
                ebb = ebb.with_fields(**{ERA_FIELD: BYRON})
                blk = ProtocolBlock(ebb, ())
                state = rules.tick_then_reapply(state, blk)
                append(blk)
                forged += 1
                prev = ebb
            leader_ix = byron_era.protocol.slot_leader(slot)
            node = nodes[leader_ix]
            body = []
            if not update_sent:
                body.append(make_byron_tx(
                    inputs=[], outputs=[],
                    certs=[(CERT_UPDATE, fork_epoch.to_bytes(8, "big"),
                            b"")],
                    signing_keys=[node["genesis_sk"]]))
                update_sent = True
            body += spends.body(max(0, byron_txs - len(body)), make_byron_tx)
            hdr = make_header(prev, slot, body, issuer=leader_ix)
            hdr = hdr.with_fields(**{ERA_FIELD: BYRON})
            hdr = byron_sign_header(node["delegate_sk"], hdr)
            blk = ProtocolBlock(hdr, tuple(body))
        else:
            era_ix = ticked_dep.era
            lead = node = None
            for node in pools:
                lead = shelley_era.protocol.check_is_leader(
                    node["can_be_leader"], slot, ticked_dep.inner,
                    view.inner)
                if lead is not None:
                    break
            if lead is None:
                slot += 1
                continue
            # one feature tx per era entry: Allegra's validity interval,
            # Mary's mint — spending the forger's own crossing UTxO
            body = []
            if era_ix in feature_todo:
                owner_addr = node["addr"]
                entry = next((u for u in state.ledger.inner.utxo
                              if u[2] == owner_addr and not u[4]), None)
                if entry is not None:
                    t, i, _a, amt, _assets = entry
                    addr_vk = owner_addr
                    if era_ix == ALLEGRA:
                        tx = make_shelley_tx(
                            inputs=[(t, i)], outputs=[(owner_addr, amt)],
                            certs=[], signing_keys=[node["keys"].addr_sk],
                            validity=(0, slot + epoch_length))
                    else:                       # MARY: mint a native asset
                        aid = pool_id_of(addr_vk)
                        tx = make_shelley_tx(
                            inputs=[(t, i)],
                            outputs=[(owner_addr, amt - 1),
                                     (owner_addr, 1, ((aid, 5),))],
                            certs=[], signing_keys=[node["keys"].addr_sk],
                            mint=[(aid, 5)])
                    body.append(tx)
                    feature_todo.discard(era_ix)
            body += spends.body(shelley_txs, make_shelley_tx)
            hdr = make_header(prev, slot, body, issuer=0)
            hdr = hdr.with_fields(**{ERA_FIELD: era_ix})
            hdr = forge_tpraos_fields(shelley_era.protocol, node["hot_key"],
                                      node["can_be_leader"], lead, hdr)
            blk = ProtocolBlock(hdr, tuple(body))
        state = rules.tick_then_reapply(state, blk)
        append(blk)
        prev = blk.header
        forged += 1
        slot += 1
        if forged % 500 == 0:
            print(f"  forged {forged}/{args.blocks} "
                  f"({forged / (time.time() - t0):.0f} blocks/s)",
                  file=sys.stderr)
    if hasattr(db, "close"):
        db.close()              # flush the reference-format tail chunk
    return {"blocks": forged, "last_slot": slot - 1,
            "fork_epoch": fork_epoch}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", required=True, help="target directory")
    ap.add_argument("--protocol", default="mock-praos",
                    choices=["mock-praos", "shelley", "cardano"])
    ap.add_argument("--blocks", type=int, default=1000)
    ap.add_argument("--txs-per-block", type=int, default=2)
    ap.add_argument("--nodes", type=int, default=4,
                    help="mock-praos forgers")
    ap.add_argument("--pools", type=int, default=2,
                    help="shelley stake pools")
    ap.add_argument("--f", default="4/5",
                    help="active slot coefficient (fraction)")
    ap.add_argument("--epoch-length", type=int, default=500)
    ap.add_argument("--kes-depth", type=int, default=10)
    ap.add_argument("--chunk-size", type=int, default=100)
    ap.add_argument("--format", default="native",
                    choices=["native", "reference"],
                    help="on-disk dialect: our CBOR-indexed ImmutableDB or the reference .primary/.secondary layout")
    ap.add_argument("--eras", default="ladder",
                    choices=["ladder", "byron-shelley"],
                    help="cardano era span: the full "
                         "Byron->Shelley->Allegra->Mary ladder, or stop "
                         "at Shelley (the streaming-replay e2e shape)")
    ap.add_argument("--witness-keys", default="pool",
                    choices=["pool", "fresh"],
                    help="shelley: who signs the transactions: each "
                         "pool owner's one payment key, or a key the "
                         "chain has not seen before for every "
                         "transaction (HD-wallet addresses)")
    ap.add_argument("--slots-per-kes-period", type=int, default=None,
                    help="shelley: slots a KES period lasts, as the "
                         "genesis gives it (mainnet: 129600); left out, "
                         "it is derived from the chain's length so that "
                         "the chain walks through the key's periods")
    ap.add_argument("--tx-arrivals-per-slot", default=None,
                    metavar="R0,R1,...",
                    help="shelley: transactions that arrive in a slot, "
                         "by phase (the last rate holds for good); a "
                         "block takes what has arrived since the block "
                         "before it, up to the body limit")
    ap.add_argument("--tx-arrival-phase-slots", type=int, default=None,
                    help="shelley: slots a phase of "
                         "--tx-arrivals-per-slot lasts")
    ap.add_argument("--deleg-txs-per-block", type=int, default=0,
                    help="shelley: of --txs-per-block, the transactions "
                         "that carry a first delegation of a never-seen "
                         "stake key, and that key's witness")
    ap.add_argument("--redeleg-txs-per-block", type=int, default=0,
                    help="shelley: of --txs-per-block, the transactions "
                         "in which a stake key that delegated "
                         "--redeleg-after-blocks earlier delegates to "
                         "another pool (first delegations until then)")
    ap.add_argument("--redeleg-after-blocks", type=int, default=256,
                    help="shelley: blocks a stake key waits before it "
                         "may delegate again")
    ap.add_argument("--pool-reg-every-blocks", type=int, default=0,
                    help="shelley: every so many blocks one first "
                         "delegation gives its place to the registration "
                         "of a new pool (0: never)")
    ap.add_argument("--k", type=int, default=2160,
                    help="cardano with --byron-blocks: the security "
                         "parameter of both eras (mainnet: 2160)")
    ap.add_argument("--slot-length", type=float, default=1.0,
                    help="cardano with --byron-blocks: seconds a "
                         "Shelley slot lasts")
    ap.add_argument("--byron-blocks", type=int, default=None,
                    help="cardano: forge each era on its own "
                         "parameters, this many Byron blocks in the "
                         "last slots of Byron epoch 0 and the fork at "
                         "that epoch's end (left out: the small era "
                         "ladder from slot 0)")
    ap.add_argument("--byron-epoch-length", type=int, default=None,
                    help="with --byron-blocks: slots of a Byron epoch "
                         "(mainnet: 21600; left out: --epoch-length)")
    ap.add_argument("--byron-txs-per-block", type=int, default=2,
                    help="with --byron-blocks: transactions a Byron "
                         "block holds (--txs-per-block a Shelley one)")
    ap.add_argument("--byron-keys", type=int, default=None,
                    help="with --byron-blocks: genesis keys, one "
                         "delegate each, signing in turn (mainnet: 7; "
                         "left out: --pools)")
    ap.add_argument("--byron-slot-length", type=float, default=20.0,
                    help="with --byron-blocks: seconds a Byron slot "
                         "lasts")
    ap.add_argument("--pbft-threshold", type=float, default=0.22,
                    help="with --byron-blocks: the share of the last "
                         "--pbft-window blocks one genesis key may sign "
                         "(mainnet's PBftSignatureThreshold)")
    ap.add_argument("--pbft-window", type=int, default=None,
                    help="with --byron-blocks: blocks the threshold "
                         "looks back over (left out: --k)")
    ap.add_argument("--seed", default="db-synth")
    args = ap.parse_args()
    if (args.tx_arrivals_per_slot is None) != (
            args.tx_arrival_phase_slots is None) or (
            args.tx_arrivals_per_slot and args.protocol != "shelley"):
        ap.error("--tx-arrivals-per-slot and --tx-arrival-phase-slots "
                 "go together, on a shelley chain")
    if (args.deleg_txs_per_block or args.redeleg_txs_per_block) and (
            args.protocol != "shelley" or args.tx_arrivals_per_slot
            or min(args.deleg_txs_per_block, args.redeleg_txs_per_block,
                   args.pool_reg_every_blocks) < 0
            or args.redeleg_after_blocks < 1):
        ap.error("--deleg-txs-per-block and --redeleg-txs-per-block are "
                 "counts of a shelley chain's --txs-per-block (no "
                 "--tx-arrivals-per-slot), --redeleg-after-blocks >= 1")

    t0 = time.time()
    if args.protocol == "shelley":
        info = synth_shelley(args)
    elif args.protocol == "cardano":
        info = synth_cardano(args)
    else:
        info = synth_mock_praos(args)
    info.update({"protocol": args.protocol, "dir": args.out,
                 "synth_secs": round(time.time() - t0, 2)})
    print(json.dumps(info))


if __name__ == "__main__":
    main()
