"""Protocol-carrying header: concrete header + named protocol fields.

The reference attaches protocol evidence to headers via per-era header types
(e.g. mock Praos' `PraosFields` with VRF certs + KES signature,
ouroboros-consensus-mock/src/Ouroboros/Consensus/Mock/Protocol/Praos.hs;
BFT's `BftFields` DSIGN signature, Protocol/BFT.hs).  Here one generic
header type carries an ordered tuple of (name, value) protocol fields;
signatures cover the CBOR encoding with the signature fields dropped
(`bytes_dropping`), matching the reference's sign-the-header-minus-signature
convention.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field, replace
from typing import Any, Optional, Sequence

from ..chain.block import GENESIS_HASH
from ..observe import metrics as _metrics
from ..observe import spans as _spans
from ..utils import cbor


# blocks whose cached header bytes and header spans, and every
# transaction's id, all came from the offsets of the one walk
# (ProtocolBlock.from_bytes)
_ONE_WALK = _metrics.counter("replay.decode.one_walk_blocks")


@dataclass(frozen=True)
class ProtocolHeader:
    """HasHeader + protocol evidence fields."""
    slot: int
    block_no: int
    prev_hash: bytes
    body_hash: bytes
    issuer: int = 0                     # index into the ledger view's keys
    fields: tuple = ()                  # ((name, bytes-or-int), ...)

    _cache: dict = field(default_factory=dict, repr=False, hash=False,
                         compare=False)

    def encode(self, drop: Sequence[str] = ()):
        fs = [[k, v] for k, v in self.fields if k not in drop]
        return [self.slot, self.block_no, self.prev_hash, self.body_hash,
                self.issuer, fs]

    @classmethod
    def decode(cls, obj) -> "ProtocolHeader":
        fs = tuple((str(k) if isinstance(k, str) else bytes(k).decode(),
                    bytes(v) if isinstance(v, (bytes, bytearray)) else int(v))
                   for k, v in obj[5])
        return cls(int(obj[0]), int(obj[1]), bytes(obj[2]), bytes(obj[3]),
                   int(obj[4]), fs)

    def bytes_dropping(self, *drop: str) -> bytes:
        """Serialisation with the named fields removed — what gets signed.

        When the header was decoded from stored bytes (ProtocolBlock.
        from_bytes), the result is assembled from spans of the header's
        own stored bytes instead of re-encoding — re-encoding was ~40%
        of the replay host pass."""
        c = self._cache
        sp = c.get("spans")
        if sp is not None:
            own = c["bytes"]
            start, end, fields = sp
            keep = [own[a:b] for k, a, b in fields if k not in drop]
            return (cbor._head(4, 6) + own[start:end]
                    + cbor._head(4, len(keep)) + b"".join(keep))
        return cbor.dumps(self.encode(drop))

    @property
    def bytes(self) -> bytes:
        c = self._cache
        b = c.get("bytes")
        if b is None:
            b = c["bytes"] = cbor.dumps(self.encode())
        return b

    @property
    def hash(self) -> bytes:
        c = self._cache
        if "h" not in c:
            c["h"] = hashlib.blake2b(self.bytes, digest_size=32).digest()
        return c["h"]

    def get(self, name: str, default: Any = None) -> Any:
        for k, v in self.fields:
            if k == name:
                return v
        return default

    def with_fields(self, **kw) -> "ProtocolHeader":
        merged = dict(self.fields)
        merged.update(kw)
        return replace(self, fields=tuple(sorted(merged.items())),
                       _cache={})


@dataclass(frozen=True)
class ProtocolBlock:
    """Block = protocol header + opaque tx body tuple."""
    header: ProtocolHeader
    body: tuple = ()

    @property
    def slot(self) -> int:
        return self.header.slot

    @property
    def block_no(self) -> int:
        return self.header.block_no

    @property
    def hash(self) -> bytes:
        return self.header.hash

    @property
    def prev_hash(self) -> bytes:
        return self.header.prev_hash

    def encode(self):
        return [self.header.encode(), [t.encode() if hasattr(t, "encode")
                                       else t for t in self.body]]

    @classmethod
    def decode(cls, obj, tx_decode=None) -> "ProtocolBlock":
        """tx_decode: per-ledger body-item decoder (default: raw values)."""
        body = tuple(tx_decode(t) if tx_decode else t for t in obj[1])
        return cls(ProtocolHeader.decode(obj[0]), body)

    @classmethod
    def from_bytes(cls, raw: bytes, tx_decode=None,
                   tx_body_elems: int | None = None,
                   era_field: str | None = None,
                   era_txs: tuple = ()) -> "ProtocolBlock":
        """Decode AND keep what the hot sequential pass (header hash,
        KES signing bytes, tx ids) would otherwise re-encode for.  The
        bytes are walked once: the parse keeps the offsets of the list
        elements down to a transaction's, and the slices are cut there.
        What is kept is the header's OWN bytes with the offsets of its
        elements inside them, and a 32-byte id a transaction: never the
        block's bytes, so a built block pickles small (a decode worker's
        reply, storage/decode_pool.py).

        tx_body_elems: when set, each tx item is a list whose first
        tx_body_elems elements form the tx BODY (ShelleyTx: 6 body
        fields + witnesses) — the body encoding is assembled from the
        offsets and hashed here, and the transaction is handed its id
        (`with_txid`), so `txid` never encodes or hashes later.

        era_txs: the blocks of an era-composed DB hold another
        transaction type an era.  The header is decoded first and its
        `era_field` (an era index; the first era where it has none) picks
        that block's `(tx_decode, tx_body_elems)` out of `era_txs`, whose
        last pair serves every later era.  The same one walk, the same
        slices and ids: a Byron block and a Shelley block of one DB go
        through this one decode.

        An item not shaped so (a header that is not a 6-list, a tx of
        fewer elements, a transaction class without `with_txid`) stays
        as `decode` built it and is re-encoded when asked;
        `replay.decode.one_walk_blocks` counts the blocks where no item
        was.

        Its three stages each run in a `disk` span a block (decode.parse
        around the one walk, decode.build around `decode`, decode.slices
        around the cutting of the header's slices and the hashing of the
        ids), children of the prefetcher's `stream.decode` in a streamed
        replay."""
        with _spans.span("decode.parse", cat="disk"):
            obj, spans = cbor.loads_spans(raw, depth=2)
        with _spans.span("decode.build", cat="disk"):
            header = ProtocolHeader.decode(obj[0])
            if era_txs:
                era = header.get(era_field, 0)
                tx_decode, tx_body_elems = era_txs[
                    era if era.__class__ is int and 0 <= era < len(era_txs)
                    else -1]
            block = cls(header, tuple(tx_decode(t) for t in obj[1])
                        if tx_decode else tuple(obj[1]))
        with _spans.span("decode.slices", cat="disk"):
            body, whole = _cache_slices(block, raw, spans, tx_body_elems)
            if body is not None:
                block = cls(block.header, body)
            if whole:
                _ONE_WALK.inc()
        return block

    @property
    def bytes(self) -> bytes:
        return cbor.dumps(self.encode())


@dataclass(frozen=True)
class BlockDecoder:
    """`ProtocolBlock.from_bytes` with its arguments bound: a DB's
    decoder as an importable, picklable callable (a closure over them
    could not be sent to the streamed replay's decode worker processes,
    storage/decode_pool.py).  `tx_decode` has to pickle too: a
    module-level function or a classmethod (`ShelleyTx.decode`).  A DB
    of one era binds the first two; an era-composed DB binds the header
    field that holds a block's era and a `(tx_decode, tx_body_elems)`
    an era (`eras/cardano.py CARDANO_DECODER`)."""
    tx_decode: Any = None
    tx_body_elems: Optional[int] = None
    era_field: Optional[str] = None
    era_txs: tuple = ()

    def __call__(self, raw: bytes) -> ProtocolBlock:
        return ProtocolBlock.from_bytes(raw, self.tx_decode,
                                        self.tx_body_elems,
                                        self.era_field, self.era_txs)


def _cache_slices(block: ProtocolBlock, raw: bytes, spans,
                  tx_body_elems: int | None):
    """Fill the header's cache, and hash the transactions' ids, from the
    offsets `cbor.loads_spans(raw, depth=2)` kept of `[header, [tx,
    ...]]`.  Returns `(body, whole)`: the block's transactions each
    carrying its id (None where none took one), and whether every item
    was shaped as expected, so nothing is left to re-encode.

    The header keeps `bytes`, its own stored bytes, and `spans` =
    `(start, end, ((name, a, b), ...))`, offsets INTO those bytes: of
    its first five elements together and of each protocol field."""
    if spans is None:
        return None, False
    bounds, subs = spans
    whole = True
    hsp = subs[0]
    if hsp is not None and len(hsp[1]) == 6 and hsp[1][5] is not None:
        off = bounds[0]
        hb, fb = hsp[0], hsp[1][5][0]
        cache = block.header._cache
        cache["bytes"] = raw[off:bounds[1]]
        cache["spans"] = (
            hb[0] - off, hb[5] - off,
            tuple((k, a - off, b - off) for (k, _v), a, b
                  in zip(block.header.fields, fb, fb[1:])))
    else:
        whole = False
    if tx_body_elems is None or not block.body:
        return None, whole
    if subs[1] is None:
        return None, False
    head = cbor._head(4, tx_body_elems)
    blake2b = hashlib.blake2b
    body, took = [], False
    for tx, tsp in zip(block.body, subs[1][1]):
        take = getattr(tx, "with_txid", None)
        if take is None or tsp is None or len(tsp[0]) <= tx_body_elems:
            whole = False
        else:
            tb = tsp[0]
            tx = take(blake2b(head + raw[tb[0]:tb[tx_body_elems]],
                              digest_size=32).digest())
            took = True
        body.append(tx)
    return (tuple(body) if took else None), whole


def body_hash_of(body: Sequence) -> bytes:
    enc = [t.encode() if hasattr(t, "encode") else t for t in body]
    return hashlib.blake2b(cbor.dumps(enc), digest_size=32).digest()


def make_header(prev: Optional[ProtocolHeader], slot: int, body: Sequence,
                issuer: int) -> ProtocolHeader:
    """Unsigned header extending `prev`; protocols add evidence fields."""
    if prev is None:
        prev_hash, block_no = GENESIS_HASH, 0
    else:
        prev_hash, block_no = prev.hash, prev.block_no + 1
    return ProtocolHeader(slot=slot, block_no=block_no, prev_hash=prev_hash,
                          body_hash=body_hash_of(body), issuer=issuer)
