"""Minimal CBOR (RFC 8949) encoder/decoder.

The reference serialises every protocol message and ledger snapshot as CBOR
(codecs under Protocol/*/Codec.hs; snapshots in Storage/LedgerDB/OnDisk.hs).
This is a compact self-contained implementation covering the subset those
formats need: uints/nints, byte/text strings, arrays, maps, tags, simple
values, floats, and indefinite-length arrays.
"""
from __future__ import annotations

import struct
from typing import Any

__all__ = ["dumps", "loads", "loads_spans", "CBORError", "CBORTruncated",
           "Tag"]


class CBORError(ValueError):
    pass


class CBORTruncated(CBORError):
    """Input ends mid-item — a partial message, not a corrupt stream.
    Framing layers catch this specifically and wait for more bytes."""


class Tag:
    __slots__ = ("tag", "value")

    def __init__(self, tag: int, value: Any):
        self.tag = tag
        self.value = value

    def __eq__(self, other):
        return (isinstance(other, Tag) and self.tag == other.tag
                and self.value == other.value)

    def __repr__(self):
        return f"Tag({self.tag}, {self.value!r})"


def _head(major: int, arg: int) -> bytes:
    if arg < 24:
        return bytes([(major << 5) | arg])
    if arg < 256:
        return bytes([(major << 5) | 24, arg])
    if arg < 65536:
        return bytes([(major << 5) | 25]) + arg.to_bytes(2, "big")
    if arg < 2**32:
        return bytes([(major << 5) | 26]) + arg.to_bytes(4, "big")
    if arg < 2**64:
        return bytes([(major << 5) | 27]) + arg.to_bytes(8, "big")
    raise CBORError("integer too large for CBOR head")


class IndefList(list):
    """A list encoded with indefinite length (0x9f ... 0xff) — some
    reference codecs REQUIRE this framing (e.g. TxSubmission's tsIdList,
    ouroboros-network/test/messages.cddl:78 note)."""


def _encode(obj: Any, out: bytearray) -> None:
    if obj is None:
        out.append(0xF6)
    elif obj is True:
        out.append(0xF5)
    elif obj is False:
        out.append(0xF4)
    elif isinstance(obj, int):
        if obj >= 0:
            out += _head(0, obj)
        else:
            out += _head(1, -1 - obj)
    elif isinstance(obj, bytes):
        out += _head(2, len(obj))
        out += obj
    elif isinstance(obj, str):
        raw = obj.encode("utf-8")
        out += _head(3, len(raw))
        out += raw
    elif isinstance(obj, IndefList):
        out.append(0x9F)
        for item in obj:
            _encode(item, out)
        out.append(0xFF)
    elif isinstance(obj, (list, tuple)):
        out += _head(4, len(obj))
        for item in obj:
            _encode(item, out)
    elif isinstance(obj, dict):
        out += _head(5, len(obj))
        for k, v in obj.items():
            _encode(k, out)
            _encode(v, out)
    elif isinstance(obj, Tag):
        out += _head(6, obj.tag)
        _encode(obj.value, out)
    elif isinstance(obj, float):
        out.append(0xFB)
        out += struct.pack(">d", obj)
    else:
        raise CBORError(f"cannot CBOR-encode {type(obj).__name__}")


def dumps(obj: Any) -> bytes:
    out = bytearray()
    _encode(obj, out)
    return bytes(out)


_TRUNCATED = "truncated CBOR"


def _walk(data, rec: int) -> tuple:
    """One walk of `data` from offset 0: (object, end offset, spans).

    `rec` is how many levels of list nesting have their elements'
    offsets kept (0: none; `loads_spans` says what `spans` holds).

    This is the decode's inner loop (a replay spends a third of its
    host time here), so a head is read in place: no slice for a byte,
    no call for an argument under 24, and inside a list the byte
    strings, small integers and empty lists are taken without a call
    at all.  A read past the end is an IndexError, caught once."""
    if type(data) is not bytes:
        data = bytes(data)
    size = len(data)
    pos = 0
    node = None          # spans of the list item() last returned, if kept

    def long_arg(info):
        """The argument after a head whose additional info is >= 24."""
        nonlocal pos
        if info == 24:
            pos += 1
            return data[pos - 1]
        if info > 27:
            raise CBORError(f"unsupported additional info {info}")
        width = 1 << (info - 24)
        pos += width
        if pos > size:
            raise CBORTruncated(_TRUNCATED)
        return int.from_bytes(data[pos - width:pos], "big")

    def item(rec):
        nonlocal pos, node
        b = data[pos]
        pos += 1
        if b < 0x18:
            return b
        major = b >> 5
        info = b & 0x1F
        if major == 4:
            out = []
            if info < 24:
                n = info
            elif info == 31:
                n = -1                         # indefinite length
            else:
                n = long_arg(info)
            if n < 0 or rec > 1:
                # every element through item(): the levels above the
                # deepest one kept, and indefinite length (rare)
                sub = rec - 1 if rec else 0
                bounds = [pos]
                subs = []
                if n < 0:
                    while data[pos] != 0xFF:
                        node = None
                        out.append(item(sub))
                        bounds.append(pos)
                        subs.append(node)
                    pos += 1
                else:
                    for _ in range(n):
                        node = None
                        out.append(item(sub))
                        bounds.append(pos)
                        subs.append(node)
                if rec:
                    node = (bounds, subs if sub else None)
                return out
            append = out.append
            if rec:
                bounds = [pos]
            for _ in range(n):
                b = data[pos]
                if b == 0x58:                  # bytes, one-byte length
                    start = pos + 2
                    pos = start + data[pos + 1]
                    if pos > size:
                        raise CBORTruncated(_TRUNCATED)
                    append(data[start:pos])
                elif b < 0x18:
                    pos += 1
                    append(b)
                elif b == 0x80:
                    pos += 1
                    append([])
                elif 0x40 <= b < 0x58:         # bytes shorter than 24
                    start = pos + 1
                    pos += b - 0x3F
                    if pos > size:
                        raise CBORTruncated(_TRUNCATED)
                    append(data[start:pos])
                else:
                    append(item(0))
                if rec:
                    bounds.append(pos)
            if rec:
                node = (bounds, None)
            return out
        if major == 2:
            n = info if info < 24 else long_arg(info)
            start = pos
            pos += n
            if pos > size:
                raise CBORTruncated(_TRUNCATED)
            return data[start:pos]
        if major == 0:
            return long_arg(info)
        if major == 1:
            return -1 - (info if info < 24 else long_arg(info))
        if major == 3:
            n = info if info < 24 else long_arg(info)
            start = pos
            pos += n
            if pos > size:
                raise CBORTruncated(_TRUNCATED)
            return str(data[start:pos], "utf-8")
        if major == 5:
            out = {}
            for _ in range(info if info < 24 else long_arg(info)):
                k = item(0)
                v = item(0)
                if isinstance(k, list):
                    # array map keys (Shelley tx bodies use them) become
                    # tuples so the dict stays usable; _encode re-emits
                    # tuples as arrays, preserving round-trips
                    k = _freeze(k)
                if k in out:
                    # RFC 8949 §5.6: maps with duplicate keys are invalid;
                    # silently keeping the last key let a peer smuggle
                    # conflicting entries past CDDL-unique-key rules
                    # (ADVICE r4 on the handshake versionTable)
                    raise CBORError(f"duplicate map key {k!r}")
                out[k] = v
            return out
        if major == 6:
            return Tag(info if info < 24 else long_arg(info), item(0))
        # major 7
        if info == 20:
            return False
        if info == 21:
            return True
        if info == 22 or info == 23:
            return None
        if 25 <= info <= 27:
            width = 1 << (info - 24)
            pos += width
            if pos > size:
                raise CBORTruncated(_TRUNCATED)
            raw = data[pos - width:pos]
            if info == 25:
                return _decode_half(int.from_bytes(raw, "big"))
            return struct.unpack(">f" if info == 26 else ">d", raw)[0]
        raise CBORError(f"unsupported simple value {info}")

    try:
        return item(rec), pos, node
    except IndexError:
        raise CBORTruncated(_TRUNCATED) from None
    finally:
        # item refers to itself through this cell: emptied, the closure
        # is freed now and not at the collector's next pass
        item = None


def _freeze(obj):
    """Recursively convert lists to tuples (for use as map keys)."""
    if isinstance(obj, list):
        return tuple(_freeze(x) for x in obj)
    return obj


def _decode_half(h: int) -> float:
    sign = -1.0 if h & 0x8000 else 1.0
    exp = (h >> 10) & 0x1F
    frac = h & 0x3FF
    if exp == 0:
        return sign * frac * 2.0 ** -24
    if exp == 31:
        return sign * (float("inf") if frac == 0 else float("nan"))
    return sign * (1 + frac / 1024.0) * 2.0 ** (exp - 15)


def loads(data: bytes, allow_trailing: bool = False):
    obj, end, _spans = _walk(data, 0)
    if not allow_trailing and end != len(data):
        raise CBORError(f"trailing bytes after CBOR value at {end}")
    return obj


def loads_spans(data: bytes, depth: int) -> tuple:
    """`loads(data)` and, from the same walk, where the elements of its
    lists lie: (object, spans), so a caller that keeps raw slices of
    sub-items (header bytes, tx bodies) never walks or re-encodes them.

    `spans` is None unless the item is a list; for a list it is
    `(bounds, subs)`.  `bounds` holds one offset more than the list has
    elements: element i is `data[bounds[i]:bounds[i + 1]]`.  `subs[i]` is
    the spans of element i where that is a list too, else None.  The
    outermost list is at depth 0 and a list's elements one deeper;
    lists deeper than `depth` are not kept, so at `depth` itself `subs`
    is None.  Only lists reached through lists are kept (not one inside
    a map or a tag)."""
    obj, end, spans = _walk(data, depth + 1)
    if end != len(data):
        raise CBORError(f"trailing bytes after CBOR value at {end}")
    return obj, spans


def unwrap_tag24(obj):
    """CBOR-in-CBOR unwrap (#6.24(bytes .cbor x), messages.cddl:34,55):
    returns the decoded inner value for a tag-24-over-bytes envelope,
    or the object unchanged otherwise."""
    if isinstance(obj, Tag) and obj.tag == 24 and isinstance(obj.value,
                                                             bytes):
        return loads(obj.value)
    return obj


def loads_prefix(data: bytes) -> tuple[Any, int]:
    """Decode one CBOR item, returning (value, bytes_consumed)."""
    obj, end, _spans = _walk(data, 0)
    return obj, end
