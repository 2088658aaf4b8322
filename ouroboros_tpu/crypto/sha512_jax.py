"""Batched SHA-512 on device — the VRF challenge fold kernel.

Why this exists: the ECVRF verdict is `c == SHA512(suite || 0x02 || Y ||
H || U || V)[:16]` where H, U, V are DEVICE-computed points.  Until now
the fused window program shipped the (N, 130) compressed-point rows back
to the host, which re-hashed them in a Python loop — ~266 KB/window of
transfer plus 2k hashlib calls, all inside the drain on the replay's
critical path (the transfer's share is not measured on the present
chip).  With SHA-512 on device the
challenge comparison happens next to the ladder output and only a fold
scalar crosses the link (jax_backend fold composites).

Representation mirrors blake2b_jax: 64-bit words as (lo, hi) uint32
pairs, batch on the lane axis.  The 80 rounds run as a lax.fori_loop
with a rolling 16-word schedule window (a fully-unrolled trace makes
XLA:CPU compilation pathological, same lesson as blake2b's 12 rounds).

Messages here are FIXED-LENGTH per call site (130 B challenge preimage),
so padding is a static concatenation — no dynamic-length handling.

Oracle: hashlib.sha512 — tests/test_sha512_jax.py pins bit-exactness.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .blake2b_jax import _add64, _c64, _rotr64, _xor64

_H0 = (
    0x6A09E667F3BCC908, 0xBB67AE8584CAA73B,
    0x3C6EF372FE94F82B, 0xA54FF53A5F1D36F1,
    0x510E527FADE682D1, 0x9B05688C2B3E6C1F,
    0x1F83D9ABFB41BD6B, 0x5BE0CD19137E2179,
)

_K = (
    0x428A2F98D728AE22, 0x7137449123EF65CD, 0xB5C0FBCFEC4D3B2F,
    0xE9B5DBA58189DBBC, 0x3956C25BF348B538, 0x59F111F1B605D019,
    0x923F82A4AF194F9B, 0xAB1C5ED5DA6D8118, 0xD807AA98A3030242,
    0x12835B0145706FBE, 0x243185BE4EE4B28C, 0x550C7DC3D5FFB4E2,
    0x72BE5D74F27B896F, 0x80DEB1FE3B1696B1, 0x9BDC06A725C71235,
    0xC19BF174CF692694, 0xE49B69C19EF14AD2, 0xEFBE4786384F25E3,
    0x0FC19DC68B8CD5B5, 0x240CA1CC77AC9C65, 0x2DE92C6F592B0275,
    0x4A7484AA6EA6E483, 0x5CB0A9DCBD41FBD4, 0x76F988DA831153B5,
    0x983E5152EE66DFAB, 0xA831C66D2DB43210, 0xB00327C898FB213F,
    0xBF597FC7BEEF0EE4, 0xC6E00BF33DA88FC2, 0xD5A79147930AA725,
    0x06CA6351E003826F, 0x142929670A0E6E70, 0x27B70A8546D22FFC,
    0x2E1B21385C26C926, 0x4D2C6DFC5AC42AED, 0x53380D139D95B3DF,
    0x650A73548BAF63DE, 0x766A0ABB3C77B2A8, 0x81C2C92E47EDAEE6,
    0x92722C851482353B, 0xA2BFE8A14CF10364, 0xA81A664BBC423001,
    0xC24B8B70D0F89791, 0xC76C51A30654BE30, 0xD192E819D6EF5218,
    0xD69906245565A910, 0xF40E35855771202A, 0x106AA07032BBD1B8,
    0x19A4C116B8D2D0C8, 0x1E376C085141AB53, 0x2748774CDF8EEB99,
    0x34B0BCB5E19B48A8, 0x391C0CB3C5C95A63, 0x4ED8AA4AE3418ACB,
    0x5B9CCA4F7763E373, 0x682E6FF3D6B2B8A3, 0x748F82EE5DEFB2FC,
    0x78A5636F43172F60, 0x84C87814A1F0AB72, 0x8CC702081A6439EC,
    0x90BEFFFA23631E28, 0xA4506CEBDE82BDE9, 0xBEF9A3F7B2C67915,
    0xC67178F2E372532B, 0xCA273ECEEA26619C, 0xD186B8C721C0C207,
    0xEADA7DD6CDE0EB1E, 0xF57D4F7FEE6ED178, 0x06F067AA72176FBA,
    0x0A637DC5A2C898A6, 0x113F9804BEF90DAE, 0x1B710B35131C471B,
    0x28DB77F523047D84, 0x32CAAB7B40C72493, 0x3C9EBE0A15C9BEBC,
    0x431D67C49C100D4C, 0x4CC5D4BECB3E42B6, 0x597F299CFC657E2A,
    0x5FCB6FAB3AD6FAEC, 0x6C44198C4A475817,
)

# (80, 2) uint32 — K as (lo, hi) rows for a per-round jnp.take
_K_ARR = np.array([(k & 0xFFFFFFFF, k >> 32) for k in _K],
                  dtype=np.uint32)


def _shr64(a, r: int):
    lo, hi = a
    if r >= 32:
        return hi >> (r - 32), hi * jnp.uint32(0)
    return (lo >> r) | (hi << (32 - r)), hi >> r


def _and64(a, b):
    return a[0] & b[0], a[1] & b[1]


def _sigma(x, r1: int, r2: int, shift: int):
    """σ0/σ1: ROTR(r1) ^ ROTR(r2) ^ SHR(shift)."""
    return _xor64(_xor64(_rotr64(x, r1), _rotr64(x, r2)),
                  _shr64(x, shift))


def _big_sigma(x, r1: int, r2: int, r3: int):
    """Σ0/Σ1: three rotations."""
    return _xor64(_xor64(_rotr64(x, r1), _rotr64(x, r2)),
                  _rotr64(x, r3))


@functools.lru_cache(maxsize=32)
def _pad_tail(length: int) -> np.ndarray:
    """Host constant: the SHA-512 pad bytes for a fixed message length
    (0x80, zeros, 16-byte big-endian bit length).  Hoisted out of the
    jitted pad so no host byte construction runs inside a traced body."""
    n_blocks = (length + 17 + 127) // 128
    total = n_blocks * 128
    tail = bytearray(total - length)
    tail[0] = 0x80
    tail[-16:] = (length * 8).to_bytes(16, "big")
    return np.frombuffer(bytes(tail), dtype=np.uint8)


def pad_blocks(msg_u8, length: int):
    """(N, length) uint8 device rows -> padded (N, n_blocks*128) uint8.

    `length` is static: pad = 0x80, zeros, 16-byte big-endian bit length.
    """
    n = msg_u8.shape[0]
    tail_arr = jnp.asarray(_pad_tail(length))
    tail_b = jnp.broadcast_to(tail_arr, (n, tail_arr.shape[0]))
    return jnp.concatenate([msg_u8.astype(jnp.uint8), tail_b], axis=1)


def _blocks_words(padded):
    """(N, n_blocks*128) uint8 -> (n_blocks, 16, N) (lo, hi) word pairs
    as two uint32 arrays: big-endian 64-bit words split into halves."""
    n = padded.shape[0]
    b = padded.reshape(n, -1, 16, 8).astype(jnp.uint32)
    hi = (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]
    lo = (b[..., 4] << 24) | (b[..., 5] << 16) | (b[..., 6] << 8) | b[..., 7]
    # -> (n_blocks, 16, N)
    return (jnp.transpose(lo, (1, 2, 0)), jnp.transpose(hi, (1, 2, 0)))


def digest_words(msg_u8, length: int):
    """SHA-512 of (N, length) uint8 rows entirely on device.

    Returns (lo, hi): two (8, N) uint32 arrays — the digest as eight
    big-endian 64-bit words in (lo, hi) halves.
    """
    lo_b, hi_b = _blocks_words(pad_blocks(msg_u8, length))
    n_blocks = lo_b.shape[0]
    ref = lo_b[0, 0]
    h = tuple(_c64(x, ref) for x in _H0)
    kk = jnp.asarray(_K_ARR)

    for blk in range(n_blocks):      # static, <= 2 at our call sites
        # rolling 16-word schedule window: (16, 2, N)
        w = jnp.stack([jnp.stack([lo_b[blk, i], hi_b[blk, i]])
                       for i in range(16)])

        def round_body(t, carry, _kk=kk):
            (a, b, c, d, e, f, g, hh), w = carry
            wt = (w[0, 0], w[0, 1])
            kt_pair = jnp.take(_kk, t, axis=0)
            kt = (wt[0] * 0 + kt_pair[0], wt[1] * 0 + kt_pair[1])
            ch = _xor64(_and64(e, f),
                        _and64((~e[0], ~e[1]), g))
            t1 = _add64(_add64(_add64(hh, _big_sigma(e, 14, 18, 41)),
                               _add64(ch, kt)), wt)
            maj = _xor64(_xor64(_and64(a, b), _and64(a, c)),
                         _and64(b, c))
            t2 = _add64(_big_sigma(a, 28, 34, 39), maj)
            new_state = (_add64(t1, t2), a, b, c, _add64(d, t1), e, f, g)
            # w[t+16] = σ1(w[t+14]) + w[t+9] + σ0(w[t+1]) + w[t]
            nxt = _add64(
                _add64(_sigma((w[14, 0], w[14, 1]), 19, 61, 6),
                       (w[9, 0], w[9, 1])),
                _add64(_sigma((w[1, 0], w[1, 1]), 1, 8, 7), wt))
            w = jnp.roll(w, -1, axis=0)
            w = w.at[15].set(jnp.stack(nxt))
            return new_state, w

        state, _w = jax.lax.fori_loop(0, 80, round_body, (h, w))
        h = tuple(_add64(hi_, si) for hi_, si in zip(h, state))
    lo = jnp.stack([x[0] for x in h])
    hi = jnp.stack([x[1] for x in h])
    return lo, hi


def digest_bytes_rows(msg_u8, length: int):
    """SHA-512 as (N, 64) uint8 rows (device)."""
    lo, hi = digest_words(msg_u8, length)

    def be_bytes(x):                 # (8, N) uint32 -> (8, N, 4) uint8
        return jnp.stack([(x >> 24) & 0xFF, (x >> 16) & 0xFF,
                          (x >> 8) & 0xFF, x & 0xFF],
                         axis=-1).astype(jnp.uint8)
    hi_b, lo_b = be_bytes(hi), be_bytes(lo)
    words = jnp.concatenate([hi_b, lo_b], axis=-1)     # (8, N, 8)
    return jnp.transpose(words, (1, 0, 2)).reshape(msg_u8.shape[0], 64)


def prefix16_eq(msg_u8, length: int, c_u8):
    """digest(msg)[:16] == c, on device: (N,) bool.

    `c_u8` is (N, 16) uint8 — the expected ECVRF challenge bytes.  Only
    the first two 64-bit digest words are compared, as big-endian
    halves, so no byte materialisation of the digest is needed."""
    lo, hi = digest_words(msg_u8, length)
    c = c_u8.astype(jnp.uint32)

    def be32(b0, b1, b2, b3):
        return (b0 << 24) | (b1 << 16) | (b2 << 8) | b3
    want_hi0 = be32(c[:, 0], c[:, 1], c[:, 2], c[:, 3])
    want_lo0 = be32(c[:, 4], c[:, 5], c[:, 6], c[:, 7])
    want_hi1 = be32(c[:, 8], c[:, 9], c[:, 10], c[:, 11])
    want_lo1 = be32(c[:, 12], c[:, 13], c[:, 14], c[:, 15])
    return ((hi[0] == want_hi0) & (lo[0] == want_lo0)
            & (hi[1] == want_hi1) & (lo[1] == want_lo1))


_digest_rows_jit = jax.jit(digest_bytes_rows, static_argnums=1)


def sha512_batch(msgs: list[bytes]) -> list[bytes]:
    """Batched SHA-512 of equal-length messages (test/oracle entry)."""
    if not msgs:
        return []
    length = len(msgs[0])
    assert all(len(m) == length for m in msgs), "equal-length batches only"
    arr = (np.zeros((len(msgs), 0), dtype=np.uint8) if length == 0 else
           np.frombuffer(b"".join(msgs), dtype=np.uint8).reshape(-1, length))
    rows = np.asarray(_digest_rows_jit(jnp.asarray(arr), length))
    return [rows[j].tobytes() for j in range(len(msgs))]
