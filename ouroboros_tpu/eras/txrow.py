"""A transaction as one flat row: the base of `ShelleyTx` and `ByronTx`.

A replay holds one transaction object a transaction of every decoded
block, and a decode worker's reply (storage/decode_pool.py) carries them
all, so an instance is a single object: a tuple of the era's fields and,
last, the id; no `__dict__`, no cache dict, and it unpickles by
`tuple.__new__` alone, with no call into Python.  The subclass names the
fields (`_tuplegetter` descriptors under a `dataclass(frozen=True,
init=False, eq=False)`, so keywords, defaults, `dataclasses.replace`,
`fields` and the repr are a frozen dataclass's), makes the row in
`__new__` with `[None]` in the id's place, and says how a body encodes
(`body_encode`).

The id is Blake2b-256 of the body's encoding.  A transaction decoded
from stored bytes is handed it (`with_txid`: hashed from the exact bytes
on disk, where the block was decoded, `ProtocolBlock.from_bytes`); one
made without bytes (the forge, the mempool, `replace`) hashes its own
re-encoding on first use and keeps it in the one-slot list it carries
instead.  Equality and hashing are over the fields, never the id.
"""
from __future__ import annotations

import hashlib

from ..utils import cbor

tuple_new = tuple.__new__


class TxRow(tuple):
    __slots__ = ()

    def __reduce__(self):
        return tuple_new, (self.__class__, tuple(self))

    def __eq__(self, other):
        return other.__class__ is self.__class__ and self[:-1] == other[:-1]

    def __ne__(self, other):           # tuple's own would look at the id
        return not self == other

    def __hash__(self):
        return hash(self[:-1])

    @property
    def txid(self) -> bytes:
        t = self[-1]
        if t.__class__ is bytes:
            return t
        if t[0] is None:
            t[0] = hashlib.blake2b(cbor.dumps(self.body_encode()),
                                   digest_size=32).digest()
        return t[0]

    @property
    def txid_hashed(self) -> bool:
        """The id came with the transaction (`with_txid`); nothing is
        left to encode or hash."""
        return self[-1].__class__ is bytes

    def with_txid(self, txid: bytes):
        """This transaction carrying `txid`, the hash of the body bytes
        it was decoded from (`ProtocolBlock.from_bytes`)."""
        return tuple_new(self.__class__, self[:-1] + (txid,))
