#!/usr/bin/env python
"""db-analyser — open an on-disk chain DB, replay it, report.

Reference: ouroboros-consensus-cardano/tools/db-analyser/ —
Main.hs:27-40,95-145 (CLI: db dir, block-type config, --onlyImmutableDB,
analysis selection), Analysis.hs (ShowSlotBlockNo / CountTxOutputs /
ShowBlockHeaderSize / OnlyValidation streaming every block through an
iterator), and the validate-mainnet CI gate (§3.5) that replays the whole
chain through the ledger.

TPU twist: `--validate full` replays through consensus/batch.py — the
VRF+KES+Ed25519 proofs of a `--window` of blocks verified as ONE device
batch per window — with `--backend {ref,openssl,jax}` selecting the
CryptoBackend.  This is the BASELINE.md harness: blocks/sec + proofs/sec
per backend, plus the final ledger state hash for replay-parity checks.

Full validation routes through the STREAMING replay engine
(ouroboros_tpu/storage/stream.py, ISSUE 15): a bounded read-ahead
prefetcher streams ImmutableDB chunks on a background thread and has
them decoded in worker processes (storage/decode_pool.py; `load_db`'s
decoders are importable objects so that they can be sent there) while
earlier windows verify, `--snapshot-every N` checkpoints
the verified ledger state every N slots (crash-consistent LedgerDB
snapshots), and `--resume` restarts from the newest usable snapshot
instead of genesis — the db-analyser validate-mainnet path made both
disk-streaming and restartable.

Usage:
  python tools/db_analyser.py DIR --analysis show-slot-block-no
  python tools/db_analyser.py DIR --analysis count-tx-outputs
  python tools/db_analyser.py DIR --analysis show-header-size
  python tools/db_analyser.py DIR --analysis validate \\
      [--validate reapply|full] [--backend ref|openssl|jax] [--window 256] \\
      [--snapshot-every SLOTS] [--resume] [--read-ahead W]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_db(db_dir: str):
    from ouroboros_tpu.consensus.headers import BlockDecoder
    from ouroboros_tpu.consensus.ledger import ExtLedgerRules
    from ouroboros_tpu.storage.fs import IoFS

    with open(os.path.join(db_dir, "config.json")) as fh:
        cfg = json.load(fh)

    db = _open_immutable(IoFS(db_dir), cfg)

    if cfg["protocol"] == "mock-praos":
        from ouroboros_tpu.consensus.protocols.praos import (
            Praos, PraosConfig, PraosNode,
        )
        from ouroboros_tpu.ledgers.mock import MockLedger, Tx
        protocol = Praos(PraosConfig(
            nodes=tuple(PraosNode(bytes.fromhex(nd["vrf_vk"]),
                                  bytes.fromhex(nd["kes_vk"]), nd["stake"])
                        for nd in cfg["nodes"]),
            k=cfg["k"], f=cfg["f"], epoch_length=cfg["epoch_length"],
            kes_depth=cfg["kes_depth"],
            slots_per_kes_period=cfg["slots_per_kes_period"]))
        ledger = MockLedger({bytes.fromhex(vk): amt
                             for vk, amt in cfg["genesis"].items()})
        tx_decode = Tx.decode
        tx_body_elems = None
    elif cfg["protocol"] == "cardano":
        from ouroboros_tpu.eras.cardano import (
            CARDANO_DECODER, cardano_rules,
        )
        # both eras from the record db_synth left, each on its own
        # parameters; the decoder is the one every DB has, with the
        # header's era tag picking the transaction type
        _eras, rules, _nodes = cardano_rules(cfg)
        return db, rules, CARDANO_DECODER, cfg
    elif cfg["protocol"] == "shelley":
        from fractions import Fraction

        from ouroboros_tpu.eras.shelley import (
            SHELLEY_TX_BODY_ELEMS, ShelleyLedger, ShelleyTx, TPraos,
            TPraosConfig,
        )
        tcfg = TPraosConfig(
            k=cfg["k"], f=Fraction(cfg["f"]),
            epoch_length=cfg["epoch_length"],
            slots_per_kes_period=cfg["slots_per_kes_period"],
            kes_depth=cfg["kes_depth"],
            max_kes_evolutions=cfg["max_kes_evolutions"])
        protocol = TPraos(tcfg, cfg["genesis_seed"].encode())
        pools = {bytes.fromhex(p["pool_id"]): bytes.fromhex(p["vrf_vk"])
                 for p in cfg["pools"]}
        delegs = {bytes.fromhex(p["addr"]): bytes.fromhex(p["pool_id"])
                  for p in cfg["pools"]}
        ledger = ShelleyLedger(
            {bytes.fromhex(a): amt for a, amt in cfg["genesis"].items()},
            tcfg, pools, delegs)
        tx_decode = ShelleyTx.decode
        tx_body_elems = SHELLEY_TX_BODY_ELEMS
    else:
        raise SystemExit(f"unknown protocol {cfg['protocol']!r}")

    rules = ExtLedgerRules(protocol, ledger)

    # span-retaining decode: header bytes / KES message / tx ids come
    # from raw slices instead of re-encoding (the replay host pass).
    # An importable object, not a closure: the streamed replay ships it
    # to its decode worker processes (storage/decode_pool.py)
    return db, rules, BlockDecoder(tx_decode, tx_body_elems), cfg


def _open_immutable(fs, cfg):
    """Open either on-disk dialect: the reference's .primary/.secondary/
    .chunk layout (refformat.py; Impl/Index/{Primary,Secondary}.hs) is
    auto-detected by the presence of .primary index files, else our native
    CBOR-indexed ImmutableDB."""
    from ouroboros_tpu.storage import refformat
    from ouroboros_tpu.storage.immutabledb import ImmutableDB
    if refformat.is_reference_db(fs):
        return refformat.RefImmutableView(
            refformat.RefDbReader(fs, cfg.get("chunk_size", 100)))
    return ImmutableDB.open(fs, cfg.get("chunk_size", 100),
                            validate_all=False)


def make_backend(name: str):
    from ouroboros_tpu.crypto.backend import CpuRefBackend, OpensslBackend
    if name == "ref":
        return CpuRefBackend()
    if name == "openssl":
        return OpensslBackend()
    if name == "cpp":
        from ouroboros_tpu.crypto.cpp_backend import CppBackend
        return CppBackend()
    if name == "jax":
        from ouroboros_tpu.crypto.jax_backend import JaxBackend
        return JaxBackend()
    raise SystemExit(f"unknown backend {name}")


def analysis_show_slot_block_no(db, decode, out):
    for entry, raw in db.stream():
        b = decode(raw)
        out.write(f"{b.slot}\t{b.block_no}\t{b.hash.hex()[:16]}\n")


def analysis_count_tx_outputs(db, decode, out):
    total = blocks = txs = 0
    for entry, raw in db.stream():
        b = decode(raw)
        blocks += 1
        for tx in b.body:
            txs += 1
            total += len(tx.outputs)
    out.write(json.dumps({"blocks": blocks, "txs": txs,
                          "tx_outputs": total}) + "\n")


def analysis_show_header_size(db, decode, out):
    biggest = (0, None)
    for entry, raw in db.stream():
        b = decode(raw)
        size = len(b.header.bytes)
        if size > biggest[0]:
            biggest = (size, b.slot)
        out.write(f"{b.slot}\t{size}\n")
    out.write(f"# max header size {biggest[0]} at slot {biggest[1]}\n")


# proofs per header: mock-praos = VRF + KES; shelley = 2 VRF + KES + OCert;
# cardano = per era (Byron delegate sig | Shelley's 4; EBBs carry none)
def _cardano_hdr_proofs(b) -> int:
    if b.header.get("ebb"):
        return 0
    return 1 if b.header.get("hfc_era") == 0 else 4


HEADER_PROOFS = {"mock-praos": 2, "shelley": 4,
                 "cardano": _cardano_hdr_proofs}


def analysis_validate(db, rules, decode, backend_name: str, mode: str,
                      window: int, out, hdr_proofs: int = 2,
                      db_dir: str = None, snapshot_every: int = 0,
                      resume: bool = False, read_ahead: int = 4):
    # `backend_name` is a make_backend name, or a CryptoBackend a caller
    # already built (chip_smoke.py replays twice on ONE instance: the
    # compiled window programs live in the backend)
    backend = None
    if mode == "full":
        if isinstance(backend_name, str):
            backend = make_backend(backend_name)
        else:
            backend, backend_name = backend_name, backend_name.name
    hdr_count = hdr_proofs if callable(hdr_proofs) \
        else (lambda b, n=hdr_proofs: n)
    ext = rules.initial_state()
    counts = {"blocks": 0, "proofs": 0}

    def count(blocks) -> None:
        counts["blocks"] += len(blocks)
        counts["proofs"] += sum(
            hdr_count(b) + sum(len(tx.witnesses) for tx in b.body)
            for b in blocks)

    stream_stats = None
    t0 = time.time()
    if mode == "reapply":
        for entry, raw in db.stream():
            b = decode(raw)
            count([b])
            ext = rules.tick_then_reapply(ext, b)
    else:
        # the streaming engine: disk on a prefetch thread, decode in its
        # worker processes, DiskPolicy-driven snapshots,
        # resume-from-latest-snapshot
        from ouroboros_tpu.storage import (
            DiskPolicy, IoFS, StreamConfig, StreamingReplayEngine,
        )

        # `decode` goes to the engine as it came (a wrapper that counts
        # would be a closure, which no decode worker can be sent); the
        # counts are taken from the blocks the prefetcher hands on
        policy = DiskPolicy(
            snapshot_interval_slots=snapshot_every
            if snapshot_every > 0 else (1 << 62))
        engine = StreamingReplayEngine(
            IoFS(db_dir), db, rules, decode, backend=backend,
            on_decoded=count,
            config=StreamConfig(
                window=window, read_ahead=read_ahead, policy=policy,
                resume=bool(resume),
                # plain validation stays read-only on the DB dir;
                # --resume alone still writes the tip checkpoint so the
                # NEXT run restarts instantly
                take_snapshots=snapshot_every > 0 or bool(resume)))
        res = engine.replay()
        if not res.all_valid:
            raise SystemExit(
                f"validation FAILED at block {res.n_valid}: {res.error}")
        ext = res.final_state
        stream_stats = res.stats
    secs = time.time() - t0
    blocks, proofs = counts["blocks"], counts["proofs"]
    out.write(json.dumps({
        "analysis": "validate", "mode": mode,
        "backend": backend_name if mode == "full" else "n/a",
        # a device backend says where it really ran: "jax" alone reads
        # the same on the chip and on XLA:CPU
        **({"backend_name": backend.name, "platform": backend.platform,
            "device_kind": backend.device_kind,
            "device_count": backend.device_count}
           if hasattr(backend, "platform") else {}),
        "window": window if mode == "full" else None,
        "blocks": blocks, "proofs": proofs,
        "secs": round(secs, 3),
        "blocks_per_sec": round(blocks / secs, 1),
        "proofs_per_sec": round(proofs / secs, 1),
        "state_hash": ext.ledger.state_hash().hex(),
        "tip_slot": ext.header.tip.slot if ext.header.tip else None,
        **({"stream": stream_stats} if stream_stats is not None else {}),
    }) + "\n")


def analyse_real_shelley(path: str, backend_name: str, out) -> None:
    """Parse + fully validate REAL Cardano bytes (a header or a block
    file in any of the reference's encodings: bare, tag-24, or the HFC
    era wrapper).  Shelley bytes get the complete PRTCL/BBODY crypto —
    both VRF verify equations, KES over the body slice, OCert, witness
    multi-verify — on the chosen backend; Byron bytes get structural
    parse + the blake2b header-hash construction (the Ed25519-BIP32
    extended-key scheme lives outside this repo).

    VRF inputs default to the reference test examples' fixed seeds
    (Test.Consensus.Shelley.Examples mkBytes 0/1); real-chain replay would
    derive them from slot + epoch nonce."""
    import hashlib

    from ouroboros_tpu.eras import byron_cbor as BY
    from ouroboros_tpu.eras import shelley_cbor as SC
    raw = open(path, "rb").read()
    for kind, parse in (("block", BY.parse_block),
                        ("header", BY.parse_header)):
        try:
            parsed = parse(raw)
        except (ValueError, IndexError, TypeError, KeyError):
            continue
        hdr = parsed.header if kind == "block" else parsed
        what = "EBB" if hdr.is_ebb else "main"
        loc = f"epoch {hdr.epoch}" if hdr.is_ebb \
            else f"epoch {hdr.epoch} slot {hdr.slot}"
        extra = f" txs {parsed.n_txs}" if kind == "block" else ""
        print(f"byron {what} {kind}: {loc} magic {hdr.magic}{extra}",
              file=out)
        try:
            print(f"header hash: {hdr.header_hash.hex()}", file=out)
        except ValueError:
            pass
        return
    backend = make_backend(backend_name)
    a0 = hashlib.blake2b(b"\x00", digest_size=32).digest()
    a1 = hashlib.blake2b(b"\x01", digest_size=32).digest()
    try:
        tx = SC.parse_tx(raw)
    except (ValueError, IndexError, TypeError, KeyError):
        tx = None
    if tx is not None:
        ok = SC.validate_tx(tx, backend)
        print(f"shelley tx: txid {tx.body_hash.hex()} "
              f"witnesses {len(tx.witnesses)}; "
              f"witness crypto [{backend.name}]: "
              f"{'ok' if ok else 'FAILED'}", file=out)
        return
    try:
        blk = SC.parse_block(raw)
    except ValueError:
        blk = None
    if blk is not None:
        b = blk.header.body
        print(f"shelley block: slot {b.slot} block_no {b.block_no} "
              f"txs {len(blk.txs)} "
              f"witnesses {sum(len(t.witnesses) for t in blk.txs)}",
              file=out)
        ok = SC.validate_block(blk, a0, a1, backend,
                               check_body_size=False)
        print(f"body hash: "
              f"{'ok' if blk.computed_body_hash() == b.body_hash else 'BAD'}"
              f"; full crypto [{backend.name}]: "
              f"{'ok' if ok else 'FAILED'}", file=out)
        return
    hdr = SC.parse_header(raw)
    b = hdr.body
    print(f"shelley header: slot {b.slot} block_no {b.block_no} "
          f"issuer {b.issuer_vkey.hex()[:16]} "
          f"protover {b.protover_major}.{b.protover_minor}", file=out)
    ok = SC.validate_header(hdr, a0, a1, backend)
    print(f"full crypto [{backend.name}]: {'ok' if ok else 'FAILED'}",
          file=out)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("db", help="DB directory (from db_synth or a node), "
                               "or a raw real-Shelley header/block file "
                               "with --analysis validate-real")
    ap.add_argument("--analysis", default="validate",
                    choices=["show-slot-block-no", "count-tx-outputs",
                             "show-header-size", "validate",
                             "validate-real"])
    ap.add_argument("--validate", default="full",
                    choices=["reapply", "full"],
                    help="reapply: no crypto (snapshot-replay path); "
                         "full: all proofs verified")
    ap.add_argument("--backend", default="openssl",
                    choices=["ref", "openssl", "cpp", "jax"])
    ap.add_argument("--window", type=int, default=256,
                    help="blocks per device batch (full validation)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    metavar="SLOTS",
                    help="checkpoint the verified ledger state every N "
                         "slots during full validation (crash-"
                         "consistent LedgerDB snapshots; 0 = never)")
    ap.add_argument("--resume", action="store_true",
                    help="restart full validation from the newest "
                         "usable snapshot instead of genesis")
    ap.add_argument("--read-ahead", type=int, default=4, metavar="W",
                    help="prefetch bound in windows for the streaming "
                         "engine (full validation)")
    args = ap.parse_args()

    if args.analysis == "validate-real":
        analyse_real_shelley(args.db, args.backend, sys.stdout)
        return

    db, rules, decode, cfg = load_db(args.db)
    out = sys.stdout
    if args.analysis == "show-slot-block-no":
        analysis_show_slot_block_no(db, decode, out)
    elif args.analysis == "count-tx-outputs":
        analysis_count_tx_outputs(db, decode, out)
    elif args.analysis == "show-header-size":
        analysis_show_header_size(db, decode, out)
    else:
        analysis_validate(db, rules, decode, args.backend, args.validate,
                          args.window, out,
                          hdr_proofs=HEADER_PROOFS.get(cfg["protocol"], 2),
                          db_dir=args.db,
                          snapshot_every=args.snapshot_every,
                          resume=args.resume,
                          read_ahead=args.read_ahead)


if __name__ == "__main__":
    main()
