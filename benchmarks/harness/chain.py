"""Driving the program's own entry points on one chain DB: a replay as
`db_analyser --analysis validate --validate full` makes it, the key
caches cleared as a fresh process would find them, and the tampered
copy of a chain that the probe and the controls hand to a backend.

Used by the parent (the device path) and by the child that computes
the plain reference (`prepare.py`); imports no JAX itself.  Copied in
part from `chip_smoke.py`, which stays the repo's smoke.
"""
from __future__ import annotations

import dataclasses
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def open_chain(chain_dir: str):
    """(dba module, ctx) for `validate`: the DB opened as the tool's
    main() opens it."""
    from tools import db_analyser as dba
    db, rules, decode, cfg = dba.load_db(chain_dir)
    return dba, (db, rules, decode, cfg, chain_dir)


def clear_caches() -> None:
    """Start a replay as a fresh process would: no betas, no per-key
    tables, no KES hash-path outcomes.  The compiled programs stay."""
    from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE
    from ouroboros_tpu.crypto.precompute import GLOBAL_PRECOMPUTE_CACHE
    GLOBAL_BETA_CACHE.clear()
    GLOBAL_PRECOMPUTE_CACHE.clear()


def validate(dba, ctx, backend, mode: str, window: int,
             snapshot_every: int, decode=None) -> dict:
    """One whole replay from the open DB to the returned state hash;
    the program's JSON line as a dict.  Raises SystemExit when the
    program rejects a block.  `decode` replaces the DB's decoder (the
    controls alter a block where it is decoded)."""
    db, rules, db_decode, cfg, chain_dir = ctx
    out = io.StringIO()
    dba.analysis_validate(
        db, rules, decode or db_decode, backend, mode, window, out,
        hdr_proofs=dba.HEADER_PROOFS[cfg["protocol"]], db_dir=chain_dir,
        snapshot_every=snapshot_every)
    return json.loads(out.getvalue())


def tamper_block(blk, kind: str):
    """One bit flipped in the DECODED block (a byte flipped on disk may
    be caught by a CRC or the decoder, which proves nothing about the
    backend): `kes` flips the header's KES signature, `witness` the
    signature of the middle transaction's first witness."""
    from ouroboros_tpu.consensus.headers import ProtocolBlock
    if kind == "kes":
        from ouroboros_tpu.eras.shelley import KES_FIELD
        sig = bytearray(blk.header.get(KES_FIELD))
        sig[3] ^= 1
        return ProtocolBlock(
            blk.header.with_fields(**{KES_FIELD: bytes(sig)}), blk.body)
    if kind == "witness":
        body = list(blk.body)
        if not body:
            raise ValueError("witness tamper on a block with no "
                             "transaction")
        k = len(body) // 2
        (vk, sig), *rest = body[k].witnesses
        sig = bytearray(sig)
        sig[3] ^= 1
        body[k] = dataclasses.replace(
            body[k], witnesses=((vk, bytes(sig)), *rest))
        return ProtocolBlock(blk.header, type(blk.body)(body))
    raise ValueError(f"unknown tamper kind {kind!r}")


def tampering_decode(decode, kind: str, at_block: int):
    """A decoder that alters block number `at_block` (counted from 0 in
    stream order) as `tamper_block` does.  One use per replay."""
    seen = [0]

    def dec(raw: bytes):
        blk = decode(raw)
        if seen[0] == at_block:
            blk = tamper_block(blk, kind)
        seen[0] += 1
        return blk
    return dec


def probe_stop(rules, blocks, kind: str, backend, window: int,
               state=None, offset: int = 0) -> dict:
    """Replay `blocks` with the LAST one tampered through `backend`;
    where it stopped and with which error.  The last block, because a
    tampered header has a new hash: any successor would fail the host's
    prev-hash check first and cut the window short — a new window shape,
    minutes of compile on the device."""
    from ouroboros_tpu.consensus.batch import replay_blocks_pipelined
    bad = list(blocks)
    bad[-1] = tamper_block(bad[-1], kind)
    clear_caches()
    res = replay_blocks_pipelined(
        rules, bad, state if state is not None else rules.initial_state(),
        backend=backend, window=window)
    return {"n_valid": offset + res.n_valid,
            "error": type(res.error).__name__}
