"""A block decoded in one walk of its bytes (ISSUE 26).

`ProtocolBlock.from_bytes` parses with `cbor.loads_spans`, which keeps the
offsets of the list elements down to a transaction's, and cuts the cached
raw slices (header bytes, header spans, tx body bytes) at those offsets.
Differential tests: the block against `ProtocolBlock.decode(loads(raw))`,
the cached bytes against what a cache-less block gives by re-encoding, and
the walker itself against the plain decoder it replaced, kept below as the
reference (`cbor.loads` runs the new walker too).
"""
import hashlib
import struct
from dataclasses import dataclass, field

import pytest

from ouroboros_tpu import observe
from ouroboros_tpu.consensus import headers
from ouroboros_tpu.consensus.headers import ProtocolBlock, make_header
from ouroboros_tpu.eras.shelley import ShelleyTx
from ouroboros_tpu.ledgers.mock import Tx, TxIn, TxOut
from ouroboros_tpu.utils import cbor
from ouroboros_tpu.utils.cbor import CBORError, CBORTruncated

from test_golden_wire import _CODECS, H, _corpus, _era_corpus


# -- the reference: the decoder `cbor.loads` ran before this walker ------------

class RefDecoder:
    def __init__(self, data: bytes):
        self.data = data
        self.pos = 0

    def _take(self, n: int) -> bytes:
        if self.pos + n > len(self.data):
            raise CBORTruncated("truncated CBOR")
        b = self.data[self.pos:self.pos + n]
        self.pos += n
        return b

    def _arg(self, info: int) -> int:
        if info < 24:
            return info
        if info == 24:
            return self._take(1)[0]
        if info == 25:
            return int.from_bytes(self._take(2), "big")
        if info == 26:
            return int.from_bytes(self._take(4), "big")
        if info == 27:
            return int.from_bytes(self._take(8), "big")
        raise CBORError(f"unsupported additional info {info}")

    def decode(self):
        b = self._take(1)[0]
        major, info = b >> 5, b & 0x1F
        if major == 0:
            return self._arg(info)
        if major == 1:
            return -1 - self._arg(info)
        if major == 2:
            return bytes(self._take(self._arg(info)))
        if major == 3:
            return self._take(self._arg(info)).decode("utf-8")
        if major == 4:
            if info == 31:
                items = []
                while True:
                    if self.data[self.pos:self.pos + 1] == b"\xff":
                        self.pos += 1
                        return items
                    items.append(self.decode())
            return [self.decode() for _ in range(self._arg(info))]
        if major == 5:
            out = {}
            for _ in range(self._arg(info)):
                k = self.decode()
                v = self.decode()
                if isinstance(k, list):
                    k = cbor._freeze(k)
                if k in out:
                    raise CBORError(f"duplicate map key {k!r}")
                out[k] = v
            return out
        if major == 6:
            return cbor.Tag(self._arg(info), self.decode())
        if info == 20:
            return False
        if info == 21:
            return True
        if info == 22 or info == 23:
            return None
        if info == 25:
            return cbor._decode_half(int.from_bytes(self._take(2), "big"))
        if info == 26:
            return struct.unpack(">f", self._take(4))[0]
        if info == 27:
            return struct.unpack(">d", self._take(8))[0]
        raise CBORError(f"unsupported simple value {info}")


def ref_loads(data: bytes):
    dec = RefDecoder(data)
    obj = dec.decode()
    if dec.pos != len(data):
        raise CBORError(f"trailing bytes after CBOR value at {dec.pos}")
    return obj


def outcome(fn, *args):
    """What a decode gives: its value, or its error's class and text."""
    try:
        return fn(*args)
    except (CBORError, UnicodeDecodeError, TypeError) as e:
        return type(e), str(e)


def check_spans(raw, obj, spans, depth, at=0):
    """Every kept offset slices to the element's own bytes, down to
    `depth` and no deeper, and only for lists reached through lists."""
    if not isinstance(obj, list) or at > depth:
        assert spans is None
        return 0
    bounds, subs = spans
    assert len(bounds) == len(obj) + 1
    for i, el in enumerate(obj):
        piece = raw[bounds[i]:bounds[i + 1]]
        assert ref_loads(piece) == el
        # canonical but for an indefinite-length list inside it
        assert piece == cbor.dumps(el) or b"\x9f" in piece
    if at == depth:
        assert subs is None
        return len(obj)
    assert len(subs) == len(obj)
    return len(obj) + sum(check_spans(raw, el, sub, depth, at + 1)
                          for el, sub in zip(obj, subs))


# -- blocks --------------------------------------------------------------------

ONE_WALK = headers._ONE_WALK


def shelley_tx(i: int, rich: bool = False) -> ShelleyTx:
    """db_synth's shape (one input, one output, one witness), or one
    with every optional part filled; witnesses are never checked here."""
    tag = i.to_bytes(4, "big")
    wit = (H(b"vk" + tag), H(b"s1" + tag) + H(b"s2" + tag))
    if not rich:
        return ShelleyTx(((H(b"in" + tag), i % 3),),
                         ((H(b"addr" + tag), 1000 + i, ()),),
                         witnesses=(wit,))
    policy = H(b"pol")[:28]
    return ShelleyTx(
        ((H(b"in" + tag), 0), (H(b"in2" + tag), 70000)),
        ((H(b"addr" + tag), 2 ** 33, ((policy, 3), (policy + b"x", 2 ** 40))),
         (H(b"chg" + tag), 5, ())),
        certs=(("deleg", H(b"a")[:28], H(b"pool")[:28]),
               ("reg", H(b"pool")[:28], H(b"vrf"))),
        witnesses=(wit, (H(b"vk2" + tag), bytes(64))),
        validity=(2, 99999),
        mint=((policy, 3),),
        withdrawals=((H(b"pool")[:28], 12345),))


def mock_tx(i: int) -> Tx:
    tag = i.to_bytes(4, "big")
    return Tx((TxIn(H(b"m" + tag), i),), (TxOut(H(b"ma" + tag), 9 + i),),
              ((H(b"mvk" + tag), bytes(64)),))


def header_of(body: tuple):
    return make_header(None, 7, body, issuer=1).with_fields(
        kes_sig=bytes(range(200)) + bytes(248), vrf_proof=H(b"pi") * 2,
        ocert=H(b"oc") + b"\x01", counter=3)


def block_bytes(body: tuple, indefinite: bool = False) -> bytes:
    enc = ProtocolBlock(header_of(body), body).encode()
    if indefinite:
        enc[0][5] = cbor.IndefList(enc[0][5])
        enc[1] = cbor.IndefList(enc[1])
    return cbor.dumps(enc)


BLOCKS = {
    # name: (body, tx_decode, tx_body_elems, indefinite-length lists)
    "shelley-0tx": ((), ShelleyTx.decode, 6, False),
    "shelley-1tx": ((shelley_tx(0),), ShelleyTx.decode, 6, False),
    "shelley-352tx": (tuple(shelley_tx(i) for i in range(352)),
                      ShelleyTx.decode, 6, False),
    "shelley-rich": ((shelley_tx(0), shelley_tx(1, rich=True),
                      shelley_tx(2, rich=True)), ShelleyTx.decode, 6, False),
    "mock-no-tx-body": (tuple(mock_tx(i) for i in range(3)), Tx.decode,
                        None, False),
    "indefinite-lists": ((shelley_tx(0), shelley_tx(1, rich=True)),
                         ShelleyTx.decode, 6, True),
}


@pytest.mark.parametrize("name", sorted(BLOCKS))
def test_one_walk_block_equals_the_two_step_decode(name):
    body, tx_decode, elems, indefinite = BLOCKS[name]
    raw = block_bytes(body, indefinite)
    before = ONE_WALK.value
    blk = ProtocolBlock.from_bytes(raw, tx_decode=tx_decode,
                                   tx_body_elems=elems)
    assert ONE_WALK.value == before + 1
    fresh = ProtocolBlock.decode(ref_loads(raw), tx_decode=tx_decode)
    assert not fresh.header._cache
    assert blk == fresh and blk.body == body
    # the header's cached bytes are those the block has on disk
    after_header = RefDecoder(raw[1:])          # raw[0] heads [hdr, txs]
    assert after_header.decode() == fresh.header.encode()
    on_disk = raw[1:1 + after_header.pos]
    assert blk.header.bytes == on_disk
    assert blk.header.hash == hashlib.blake2b(on_disk,
                                              digest_size=32).digest()
    # ... and what re-encoding gives, but for an indefinite-length list
    assert (on_disk == fresh.header.bytes) is not indefinite
    assert (blk.header.hash == fresh.header.hash) is not indefinite
    for drop in (("kes_sig",), ("kes_sig", "counter"), ("absent",), ()):
        assert blk.header.bytes_dropping(*drop) \
            == fresh.header.bytes_dropping(*drop) \
            == cbor.dumps(fresh.header.encode(drop))
    if elems is not None:
        # the id was hashed from the body's bytes on disk, in the walk
        assert all(tx.txid_hashed for tx in blk.body)
        assert not any(tx.txid_hashed for tx in fresh.body)
        for tx in blk.body:
            assert tx.txid == hashlib.blake2b(
                cbor.dumps(tx.body_encode()), digest_size=32).digest()
    else:
        assert not any(tx._cache for tx in blk.body)
    assert [tx.txid for tx in blk.body] == [tx.txid for tx in fresh.body]


# -- malformed and unexpected --------------------------------------------------

@dataclass(frozen=True)
class LooseTx:
    """A body item of any length that takes an id, as ShelleyTx does."""
    items: tuple
    txid: bytes = field(default=None, compare=False)

    @classmethod
    def decode(cls, obj):
        return cls(tuple(obj))

    def with_txid(self, txid):
        return LooseTx(self.items, txid)


def _with_header(edit) -> bytes:
    enc = ProtocolBlock(header_of(()), ()).encode()
    edit(enc)
    return cbor.dumps(enc)


SMALL = block_bytes((shelley_tx(0),))


def _tx_list_holding(item: bytes) -> bytes:
    """A block whose transaction list holds the one item given raw."""
    raw = cbor.dumps([header_of(()).encode(), [7]])
    assert raw.endswith(b"\x81\x07")
    return raw[:-1] + item


MALFORMED = {
    "trailing-byte": SMALL + b"\x00",
    "trailing-break": SMALL + b"\xff",
    "duplicate-map-key": _tx_list_holding(b"\xa2\x01\x02\x01\x03"),
    "unsupported-head": _tx_list_holding(b"\x1c"),
    "indefinite-bytes": _tx_list_holding(b"\x5f\x41\x00\xff"),
    "indefinite-map": _tx_list_holding(b"\xbf\xff"),
    "break-for-an-item": _tx_list_holding(b"\xff"),
    "simple-value-in-a-byte": _tx_list_holding(b"\xf8\x20"),
    "text-not-utf8": _tx_list_holding(b"\x62\xc3\x28"),
    "list-as-a-key-twice": _tx_list_holding(
        b"\xa2\x81\x01\x00\x81\x01\x00"),
    "map-as-a-key": _tx_list_holding(b"\xa1\xa0\x00"),
    "tx-of-5-elements": _tx_list_holding(
        cbor.dumps(shelley_tx(0).encode()[:5])),
}


@pytest.mark.parametrize("name", sorted(MALFORMED))
def test_malformed_block_raises_what_loads_raises(name):
    raw = MALFORMED[name]

    def two_step():
        return ProtocolBlock.decode(ref_loads(raw),
                                    tx_decode=ShelleyTx.decode)

    try:
        two_step()
    except (CBORError, IndexError, ValueError, TypeError) as e:
        want = type(e), str(e)
    else:
        pytest.fail("the case is not malformed")
    assert repr(outcome(cbor.loads, raw)) == repr(outcome(ref_loads, raw))
    before = ONE_WALK.value
    with pytest.raises(want[0]) as got:
        ProtocolBlock.from_bytes(raw, tx_decode=ShelleyTx.decode,
                                 tx_body_elems=6)
    assert str(got.value) == want[1]
    assert ONE_WALK.value == before


def test_every_strict_prefix_of_a_block_is_truncated():
    before = ONE_WALK.value
    for n in range(len(SMALL)):
        for decode in (cbor.loads, cbor.loads_prefix, ref_loads,
                       lambda b: cbor.loads_spans(b, 2)):
            with pytest.raises(CBORTruncated):
                decode(SMALL[:n])
        with pytest.raises(CBORTruncated):
            ProtocolBlock.from_bytes(SMALL[:n], tx_decode=ShelleyTx.decode,
                                     tx_body_elems=6)
    assert ONE_WALK.value == before


def _seven_element_header(enc):
    enc[0].append(0)


def _header_as_a_map(enc):
    enc[0] = dict(enumerate(enc[0]))


def _fields_in_a_tag(enc):
    enc[0][5] = cbor.Tag(258, enc[0][5])


UNEXPECTED = {
    # name: (raw, tx_body_elems, header cached, txs handed their id)
    "short-tx-among-whole": (
        cbor.dumps([header_of(()).encode(),
                    [[1, 2, 3, 4, 5, 6, 7], [1, 2, 3, 4, 5], [[], 2, 3, 4, 5, 6],
                     9, {1: 2}]]), 6, True, (0, 2)),
    "seven-element-header": (_with_header(_seven_element_header), 6, False,
                             ()),
    "header-as-a-map": (_with_header(_header_as_a_map), None, False, ()),
    "tx-list-as-a-map": (
        cbor.dumps([header_of(()).encode(), {(1, 2, 3, 4, 5, 6): 0}]),
        6, True, ()),
}


@pytest.mark.parametrize("name", sorted(UNEXPECTED))
def test_unexpected_shape_decodes_uncached_and_uncounted(name):
    """An item not shaped as expected is handed nothing (it is
    re-encoded when asked), the others get theirs, and the block is
    not counted."""
    raw, elems, header_cached, cached_txs = UNEXPECTED[name]

    def tx_decode(t):
        return LooseTx.decode(t) if isinstance(t, (list, tuple)) else t

    before = ONE_WALK.value
    blk = ProtocolBlock.from_bytes(raw, tx_decode=tx_decode,
                                   tx_body_elems=elems)
    assert ONE_WALK.value == before
    fresh = ProtocolBlock.decode(ref_loads(raw), tx_decode=tx_decode)
    assert blk == fresh
    assert set(blk.header._cache) == (
        {"bytes", "spans"} if header_cached else set())
    assert blk.header.bytes_dropping("kes_sig") \
        == cbor.dumps(fresh.header.encode(("kes_sig",)))
    for i, tx in enumerate(blk.body):
        if i in cached_txs:
            assert tx.txid == hashlib.blake2b(
                cbor.dumps(list(tx.items[:elems])), digest_size=32).digest()
        else:
            assert getattr(tx, "txid", None) is None


def test_a_header_that_decode_refuses_raises_the_same():
    raw = _with_header(_fields_in_a_tag)
    with pytest.raises(TypeError):
        ProtocolBlock.decode(ref_loads(raw))
    with pytest.raises(TypeError):
        ProtocolBlock.from_bytes(raw)


def test_the_counter_follows_the_registry_switch():
    raw = block_bytes((shelley_tx(0),))
    assert ONE_WALK is observe.REGISTRY.get("replay.decode.one_walk_blocks")
    assert ONE_WALK.kind == "counter" and ONE_WALK.stable
    assert not ONE_WALK.always
    before = ONE_WALK.value
    observe.REGISTRY.disable()
    try:
        ProtocolBlock.from_bytes(raw, tx_decode=ShelleyTx.decode,
                                 tx_body_elems=6)
    finally:
        observe.REGISTRY.enable()
    assert ONE_WALK.value == before


# -- the walker against the plain decoder, on the repo's own corpus -----------

def _corpus_blobs() -> dict:
    """The pinned wire corpus of test_golden_wire.py (the messages
    test_cddl_conformance.py checks are these, encoded by these codecs)
    and the per-era tx and block encodings, as bytes."""
    out = {name: [_CODECS[name].encode(m) for m in msgs]
           for name, msgs in _corpus().items()}
    for name, blob in _era_corpus().items():
        out[name] = [blob]
    return out


CORPUS = _corpus_blobs()


@pytest.mark.parametrize("name", sorted(CORPUS))
def test_walker_equals_the_plain_decoder_on_the_corpus(name):
    kept = 0
    for raw in CORPUS[name]:
        want = ref_loads(raw)
        assert cbor.loads(raw) == want
        assert cbor.loads_prefix(raw + b"\x00") == (want, len(raw))
        assert cbor.loads(bytearray(raw)) == want
        for depth in (0, 1, 2, 9):
            obj, spans = cbor.loads_spans(raw, depth)
            assert obj == want
            kept += check_spans(raw, obj, spans, depth)
        for n in range(len(raw)):
            assert outcome(cbor.loads, raw[:n]) \
                == outcome(ref_loads, raw[:n]) \
                == (CBORTruncated, "truncated CBOR")
        for i in range(len(raw)):               # one byte changed
            bad = raw[:i] + bytes([raw[i] ^ 0x5F]) + raw[i + 1:]
            want = repr(outcome(ref_loads, bad))    # repr: a NaN is a NaN
            assert repr(outcome(cbor.loads, bad)) == want
            assert repr(outcome(
                lambda b: cbor.loads_spans(b, 2)[0], bad)) == want
    assert kept > 0


def test_walker_leaves_nothing_for_the_collector():
    import gc
    raw = block_bytes((shelley_tx(0),))
    gc.collect()
    gc.disable()
    try:
        cbor.loads_spans(raw, 2)
        with pytest.raises(CBORTruncated):
            cbor.loads(raw[:-1])
        assert gc.collect() == 0
    finally:
        gc.enable()
