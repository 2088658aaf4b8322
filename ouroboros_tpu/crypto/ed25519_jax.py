"""Batched Ed25519 verification on TPU — the framework's flagship kernel.

Replaces the strictly-sequential per-header libsodium verify of the reference
hot path (SURVEY.md §3.3 CRYPTO HOT SPOTs; Shelley/Protocol.hs:433-442,
Shelley/Ledger/Ledger.hs:279-284) with one device batch.

Host/device split (SURVEY.md §7 "sequential-state / parallel-proof"):
- host: SHA-512 hashing (C-speed via hashlib), point decompression, scalar
  range checks, bit decomposition — all cheap or awkward on TPU;
- device: the 99% — a 256-iteration Strauss-Shamir double-scalar ladder
  computing Q = [s]B + [k](-A) for the whole batch simultaneously, then the
  projective comparison against R.  Uniform branch-free control flow
  (lax.fori_loop + one-hot 4-entry table select), int32 limb arithmetic
  (field_jax), batch on the lane axis.

Accept criterion is libsodium-compatible cofactorless verify:
[s]B == R + [k]A, with s < L enforced and non-canonical A/R rejected.
"""
from __future__ import annotations

import functools
import hashlib

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..compile_cache import cache_dir
from ..observe import metrics as _metrics
from ..observe import spans as _spans
from . import cpp_backend
from . import edwards as ed
from . import field_jax as F


# every device path imports this module, so the persistent compilation
# cache is configured before the first compile whichever entry point ran
# first (ladder compiles are minutes per shape)
cache_dir()

L = ed.L

# ---------------------------------------------------------------------------
# Point ops on batched limb vectors: point = (X, Y, Z, T) of (NLIMBS, N)
# ---------------------------------------------------------------------------

_2D = (2 * ed.D) % ed.P


def pt_add(p, q, n):
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    A = F.mul(F.sub(Y1, X1), F.sub(Y2, X2))
    B = F.mul(F.add(Y1, X1), F.add(Y2, X2))
    C = F.mul(F.mul(T1, T2), F.const_batch(_2D, n))
    ZZ = F.mul(Z1, Z2)
    D = F.add(ZZ, ZZ)
    E, Fv, G, H = F.sub(B, A), F.sub(D, C), F.add(D, C), F.add(B, A)
    return (F.mul(E, Fv), F.mul(G, H), F.mul(Fv, G), F.mul(E, H))


def pt_double(p):
    X, Y, Z, _ = p
    A = F.sqr(X)
    B = F.sqr(Y)
    ZZ = F.sqr(Z)
    C = F.add(ZZ, ZZ)
    H = F.add(A, B)
    XY = F.add(X, Y)
    E = F.sub(H, F.sqr(XY))
    G = F.sub(A, B)
    Fv = F.add(C, G)
    return (F.mul(E, Fv), F.mul(G, H), F.mul(Fv, G), F.mul(E, H))


# -- cached-point form: q as (Y-X, Y+X, 2Z, 2dT), the ref10 "ge_cached"
#    idea — one fewer field mul per ladder addition, and the 2d·T constant
#    multiply moves into the (once-per-batch) table build.

def to_cached(q, n):
    X2, Y2, Z2, T2 = q
    return (F.sub(Y2, X2), F.add(Y2, X2), F.add(Z2, Z2),
            F.mul(T2, F.const_batch(_2D, n)))


def const_cached(x: int, y: int, n):
    """Cached form of a CONSTANT affine point (Z = 1)."""
    return (F.const_batch((y - x) % ed.P, n),
            F.const_batch((y + x) % ed.P, n),
            F.const_batch(2, n),
            F.const_batch(2 * ed.D * x * y % ed.P, n))


def ident_cached(ref):
    """Cached form of the identity (0, 1, 1, 0) -> (1, 1, 2, 0)."""
    one = F.one_like(ref)
    return (one, one, F.add(one, one), ref * 0)


def pt_add_cached(p, q):
    """p (extended) + q (cached): 8 field muls (pt_add is 9)."""
    X1, Y1, Z1, T1 = p
    c0, c1, z2, t2 = q
    A = F.mul(F.sub(Y1, X1), c0)
    B = F.mul(F.add(Y1, X1), c1)
    C = F.mul(T1, t2)
    D = F.mul(Z1, z2)
    E, Fv, G, H = F.sub(B, A), F.sub(D, C), F.add(D, C), F.add(B, A)
    return (F.mul(E, Fv), F.mul(G, H), F.mul(Fv, G), F.mul(E, H))


def _identity_like(ref):
    """Identity point batch derived from an input array so it carries the
    same sharding/varying-axis type under shard_map (a constant-built carry
    would fail lax.fori_loop's carry-type check inside shard_map)."""
    zero = ref * 0
    one = F.one_like(ref)
    return (zero, one, one, zero)


# ---------------------------------------------------------------------------
# The jitted kernel
# ---------------------------------------------------------------------------

def _smalls_of(P, n, ident):
    """[identity, P, 2P, 3P] for a point batch (w=2 window digits)."""
    P2 = pt_double(P)
    P3 = pt_add(P2, P, n)
    return (ident, P, P2, P3)


def _const_smalls(x: int, y: int, n, ident):
    """[identity, P, 2P, 3P] for a CONSTANT affine point — multiples
    computed in Python ints, materialised as broadcast constants (no
    device work)."""
    out = [ident]
    base = ed.from_affine(x, y)
    for k in (1, 2, 3):
        px, py = ed.to_affine(ed.scalar_mult(k, base))
        out.append((F.const_batch(px, n), F.const_batch(py, n),
                    F.one_like(ident[1]),
                    F.const_batch(px * py % ed.P, n)))
    return tuple(out)


def joint_table_16(Bs, As, n):
    """16-entry joint table T[4*j + i] = Bs[i] + As[j] (i = low digit
    point multiple of the first scalar's base, j = second's).  Entries
    where either side is the identity reuse the other side directly, so
    the build costs 9 point additions."""
    table = []
    for j in range(4):
        for i in range(4):
            if i == 0:
                table.append(As[j])
            elif j == 0:
                table.append(Bs[i])
            else:
                table.append(pt_add(Bs[i], As[j], n))
    return table


def _onehot_entry(table, idx, k):
    """Sum-of-onehot select of a k-entry stacked table."""
    sel = (idx[None, :] == jnp.arange(k, dtype=jnp.int32)[:, None])
    sel = sel.astype(jnp.int32)[:, None, :]                       # (k,1,N)
    return tuple(jnp.sum(table[c] * sel, axis=0) for c in range(4))


def verify_core(negA_x, negA_y, negA_t, Rx, Ry, s_bits, k_bits, nbits=256):
    """Q = [s]B + [k](-A); return projective diffs vs affine R.

    Inputs: limb arrays (NLIMBS, N); bit arrays (nbits, N) MSB-first int32.
    Returns (d1, d2): d1 = Rx*Z_Q - X_Q, d2 = Ry*Z_Q - Y_Q — verification
    succeeds iff both ≡ 0 (mod p) (host checks after unpack).

    Windowed Strauss-Shamir, w = 2: nbits/2 iterations, each doing two
    doublings and ONE addition of T[s_digit + 4*k_digit] from a 16-entry
    joint table [i]B + [j](-A) — half the point additions of the 1-bit
    form for ~11 extra table-build additions (VERDICT r3 next-step 2).

    Un-jitted so parallel/sharded_verify.py can wrap it in shard_map; use
    `verify_kernel` for the single-device jitted form.
    """
    n = negA_x.shape[1]
    one = F.const_batch(1, n)
    gx, gy = ed.to_affine(ed.BASE)
    negA = (negA_x, negA_y, one, negA_t)
    ident = _identity_like(negA_x)
    Bs = _const_smalls(gx, gy, n, ident)
    As = _smalls_of(negA, n, ident)
    # stacked (16, NLIMBS, N) per coordinate: T[4j+i] = [i]B + [j](-A)
    tbl = joint_table_16(Bs, As, n)
    table = tuple(jnp.stack([t[c] for t in tbl]) for c in range(4))

    def body(i, Q):
        Q = pt_double(pt_double(Q))
        s_hi = lax.dynamic_index_in_dim(s_bits, 2 * i, 0, keepdims=False)
        s_lo = lax.dynamic_index_in_dim(s_bits, 2 * i + 1, 0, keepdims=False)
        k_hi = lax.dynamic_index_in_dim(k_bits, 2 * i, 0, keepdims=False)
        k_lo = lax.dynamic_index_in_dim(k_bits, 2 * i + 1, 0, keepdims=False)
        idx = (2 * s_hi + s_lo) + 4 * (2 * k_hi + k_lo)
        return pt_add(Q, _onehot_entry(table, idx, 16), n)

    Q = lax.fori_loop(0, nbits // 2, body, ident)
    X, Y, Z, _ = Q
    d1 = F.sub(F.mul(Rx, Z), X)
    d2 = F.sub(F.mul(Ry, Z), Y)
    return d1, d2


verify_kernel = jax.jit(verify_core, static_argnames=("nbits",))


# ---------------------------------------------------------------------------
# Split-128 ladder (VERDICT r4 next-step 1, the fixed-base direction):
# write s = s_lo + 2^128·s_hi and k = k_lo + 2^128·k_hi, so
#   Q = [s_lo]B + [s_hi]B' + [k_lo](-A) + [k_hi](-A')
# with B' = [2^128]B a compile-time constant and A' = [2^128]A memoised
# per verification key (keys repeat heavily on the replay path: pool
# cold/KES keys sign thousands of headers, payment keys re-witness).
# HALF the doubling chain of the 256-bit form: 128 doubles + 128 cached
# adds + a 10-add/12-mul table build vs 256 + 128 + 9.
# ---------------------------------------------------------------------------

_GX_AFF, _GY_AFF = ed.to_affine(ed.BASE)
_B128X, _B128Y = ed.to_affine(ed.scalar_mult(1 << 128, ed.BASE))
_BB128X, _BB128Y = ed.to_affine(ed.scalar_mult((1 << 128) + 1, ed.BASE))


def split_table_16(negA, negA128, n, ident):
    """16 cached-form entries T[c + 4v]: c indexes the constant half
    {1, B, B', B+B'}, v the variable half {1, -A, -A', -A-A'}."""
    consts_aff = (None, (_GX_AFF, _GY_AFF), (_B128X, _B128Y),
                  (_BB128X, _BB128Y))
    var_ext = (None, negA, negA128, pt_add(negA, negA128, n))
    table = []
    for v in range(4):
        for c in range(4):
            if v == 0 and c == 0:
                table.append(ident_cached(ident[0]))
            elif v == 0:
                x, y = consts_aff[c]
                table.append(const_cached(x, y, n))
            elif c == 0:
                table.append(to_cached(var_ext[v], n))
            else:
                x, y = consts_aff[c]
                cpt = (F.const_batch(x, n), F.const_batch(y, n),
                       F.one_like(ident[1]),
                       F.const_batch(x * y % ed.P, n))
                table.append(to_cached(pt_add(var_ext[v], cpt, n), n))
    return table


def split_idx_rows(s_words, k_words):
    """(8, N) uint32 scalar words -> (128, N) int32 joint window digits:
    row i = s_lo + 2·s_hi + 4·k_lo + 8·k_hi at ladder iteration i
    (MSB-first within each 128-bit half).  Cheap XLA elementwise work done
    ON DEVICE so only the packed words cross the host link."""
    rows = []
    for i in range(128):
        rows.append(F.bit_from_words(s_words, 127 - i)
                    + 2 * F.bit_from_words(s_words, 255 - i)
                    + 4 * F.bit_from_words(k_words, 127 - i)
                    + 8 * F.bit_from_words(k_words, 255 - i))
    return jnp.stack(rows)


def verify_split_idx_core(negA, negA128, Rx, Ry, idx_rows):
    """128-iteration split ladder; returns projective diffs vs affine R.

    negA/negA128: extended-coordinate batches of -A and [2^128](-A);
    idx_rows: (128, N) int32 joint digits (split_idx_rows)."""
    ident = _identity_like(negA[0])
    tbl = split_table_16(negA, negA128, negA[0].shape[1], ident)
    table = tuple(jnp.stack([t[c] for t in tbl]) for c in range(4))

    def body(i, Q):
        Q = pt_double(Q)
        idx = lax.dynamic_index_in_dim(idx_rows, i, 0, keepdims=False)
        return pt_add_cached(Q, _onehot_entry(table, idx, 16))

    Q = lax.fori_loop(0, 128, body, ident)
    X, Y, Z, _ = Q
    return F.sub(F.mul(Rx, Z), X), F.sub(F.mul(Ry, Z), Y)


def verify_full_split_words_core(Aw, xAw, A128xw, A128yw, Rw, signR,
                                 s_words, k_words):
    """Packed-words form: all 256-bit inputs as (8, N) uint32 word rows
    (8-32x smaller host->device transfers than limb/bit rows; see
    field_jax packed-I/O notes).  A's affine x arrives from the A128Cache
    (device-computed at first key sighting), so the only square root left
    is R's — the probe measured each pow-chain decompression at ~20% of
    the whole kernel.  Callers MUST mask lanes whose key was not `known`
    to the cache.  Returns (N,) int32 0/1."""
    yA = F.limbs_from_words(Aw)
    xA = F.limbs_from_words(xAw)
    yR = F.limbs_from_words(Rw)
    xA128 = F.limbs_from_words(A128xw)
    yA128 = F.limbs_from_words(A128yw)
    xR, okR = device_decompress(yR, signR)
    one = F.one_like(yA)
    nax = F.sub(yA * 0, xA)
    negA = (nax, yA, one, F.mul(nax, yA))
    nax128 = F.sub(yA * 0, xA128)
    negA128 = (nax128, yA128, one, F.mul(nax128, yA128))
    idx = split_idx_rows(s_words, k_words)
    d1, d2 = verify_split_idx_core(negA, negA128, xR, yR, idx)
    ok = jnp.logical_and(okR,
                         jnp.logical_and(F.is_zero(d1), F.is_zero(d2)))
    return ok.astype(jnp.int32)


verify_full_split_words_kernel = jax.jit(verify_full_split_words_core)


def a128_core(yA, signA):
    """Per-key precompute: decompress A, then [2^128]A via 128 doublings
    + one batched inversion to canonical affine limbs.  Returns
    (xA, x128, y128, ok) — the key's own affine x AND the shifted point.
    Run once a key, at its first sighting, and memoised by the per-key
    cache (crypto/precompute.py): a header key (a pool's cold, VRF and
    KES leaf keys) then recurs for thousands of blocks and the verify
    kernels skip the A square root for good; a witness key of a chain
    whose wallets take a fresh address a transaction is seen once, so on
    such a chain this runs for every witness lane of every window."""
    xA, ok = device_decompress(yA, signA)
    one = F.one_like(yA)
    P = (xA, yA, one, F.mul(xA, yA))
    P = lax.fori_loop(0, 128, lambda _, q: pt_double(q), P)
    Zi = pow_inv(P[2])
    return (xA, F.canon(F.mul(P[0], Zi)), F.canon(F.mul(P[1], Zi)), ok)


def a128_words_core(Aw, signA):
    """`a128_core` in the packed form the cache keeps and the verify
    kernels take: Aw (8, N) uint32 words of the compressed keys with the
    sign bit cleared, signA (N,) int32.  Returns ((24, N) uint32: the
    words of xA, x([2^128]A), y([2^128]A), eight rows each; ok (N,))."""
    xA, x128, y128, ok = a128_core(F.limbs_from_words(Aw), signA)
    return jnp.concatenate([F.words_from_limbs(xA),
                            F.words_from_limbs(x128),
                            F.words_from_limbs(y128)]), ok


# one program a WIDTH, whatever the count of new keys: the cache calls it
# a tile at a time (precompute.PrecomputeCache._dispatch_tables)
a128_words_kernel = jax.jit(a128_words_core)

# filler for padding / undecodable keys: [2^128]B (any valid point works —
# such entries are masked invalid by parse_ok before the result is read)
def _words_of_int(v: int) -> np.ndarray:
    return np.frombuffer(int(v).to_bytes(32, "little"),
                         dtype=np.uint32).copy()


_B128X_W = _words_of_int(_B128X)
_B128Y_W = _words_of_int(_B128Y)


_GX_W = _words_of_int(_GX_AFF)
# the (24,) table column of a key that does not decode
_FILLER_COL = np.concatenate([_GX_W, _B128X_W, _B128Y_W])


# The per-key [2^128]A cache grew into the cross-window precomputation
# cache shared by all three primitives (see crypto/precompute.py); the
# r5 names stay as aliases for the Ed25519-facing entry points.
from .precompute import (                                     # noqa: E402
    GLOBAL_PRECOMPUTE_CACHE as GLOBAL_A128_CACHE,
    PrecomputeCache as A128Cache,
)


def _sq_n(x, n):
    return lax.fori_loop(0, n, lambda _, v: F.sqr(v), x)


def _chain250(z):
    """Shared ref10 ladder prefix: returns (z^(2^250-1), z^11, z^2)."""
    z2 = F.sqr(z)                         # 2
    z9 = F.mul(z, _sq_n(z2, 2))           # 9
    z11 = F.mul(z2, z9)                   # 11
    t0 = F.mul(z9, F.mul(z11, z11))       # 31 = 2^5 - 1
    t0 = F.mul(_sq_n(t0, 5), t0)          # 2^10 - 1
    t1 = F.mul(_sq_n(t0, 10), t0)         # 2^20 - 1
    t1 = F.mul(_sq_n(t1, 20), t1)         # 2^40 - 1
    t0 = F.mul(_sq_n(t1, 10), t0)         # 2^50 - 1
    t1 = F.mul(_sq_n(t0, 50), t0)         # 2^100 - 1
    t1 = F.mul(_sq_n(t1, 100), t1)        # 2^200 - 1
    t0 = F.mul(_sq_n(t1, 50), t0)         # 2^250 - 1
    return t0, z11, z2


def pow_p58(z):
    """z^((p-5)/8) = z^(2^252 - 3), ref10 addition chain (~254 sq + 11 mul)."""
    t250, _z11, _z2 = _chain250(z)
    return F.mul(_sq_n(t250, 2), z)       # 2^252 - 3


def pow_inv(z):
    """z^(p-2) = z^(2^255 - 21): batched field inversion (inv(0) = 0,
    matching edwards.inv's pow semantics)."""
    t250, z11, _z2 = _chain250(z)
    return F.mul(_sq_n(t250, 5), z11)     # 2^255 - 32 + 11


def pow_chi(z):
    """z^((p-1)/2) = z^(2^254 - 10): Legendre symbol (1 / p-1 / 0)."""
    t250, _z11, z2 = _chain250(z)
    z4 = F.mul(z2, z2)
    z6 = F.mul(z4, z2)
    return F.mul(_sq_n(t250, 4), z6)      # 2^254 - 16 + 6


@jax.jit
def decompress_kernel(y):
    """Batched candidate square root for point decompression.

    Input: (NLIMBS, N) limbs of canonical y.  Output: x candidate with
    x = u*v^3*(u*v^7)^((p-5)/8) for u = y^2-1, v = d*y^2+1 (RFC 8032 §5.1.3).
    Host applies the cheap final steps (root-check, sqrt(-1) twist, sign).
    """
    n = y.shape[1]
    one = F.one_like(y)
    y2 = F.sqr(y)
    u = F.sub(y2, one)
    v = F.add(F.mul(F.const_batch(ed.D, n), y2), one)
    v3 = F.mul(F.sqr(v), v)
    v7 = F.mul(F.sqr(v3), v)
    return F.mul(F.mul(u, v3), pow_p58(F.mul(u, v7)))


def device_decompress(y, sign):
    """Full RFC 8032 §5.1.3 decompression on device.

    y: (NLIMBS, N) canonical limbs; sign: (N,) int32 x-parity bit.
    Returns (x, ok): x canonical with the requested parity; ok False where
    no square root exists or x == 0 with sign == 1.  Bit-exact vs
    edwards.decompress (host parse already rejected y >= p)."""
    n = y.shape[1]
    one = F.one_like(y)
    y2 = F.sqr(y)
    u = F.sub(y2, one)
    v = F.add(F.mul(F.const_batch(ed.D, n), y2), one)
    v3 = F.mul(F.sqr(v), v)
    v7 = F.mul(F.sqr(v3), v)
    xc = F.mul(F.mul(u, v3), pow_p58(F.mul(u, v7)))
    vx2 = F.mul(v, F.sqr(xc))
    root_direct = F.is_zero(F.sub(vx2, u))            # (N,) bool
    root_twist = F.is_zero(F.add(vx2, u))
    ok = jnp.logical_or(root_direct, root_twist)
    x_twist = F.mul(xc, F.const_batch(ed.SQRT_M1, n))
    x = jnp.where(root_direct[None, :], xc, x_twist)
    x = F.canon(x)
    parity = x[0] & 1
    x_is_zero = jnp.all(x == 0, axis=0)
    ok = jnp.logical_and(ok, ~jnp.logical_and(x_is_zero, sign == 1))
    # p - x for canonical x needs only one borrow pass (value in [1, p]);
    # for x == 0 it yields the limbs of p ≡ 0, harmless as ladder input
    x_neg, _ = F._exact_scan(F.p_col(x.shape[1]) - x)
    x = jnp.where((parity != sign)[None, :], x_neg, x)
    return x, ok


def verify_full_core(yA, signA, yR, signR, s_bits, k_bits):
    """Whole verification on device: decompress A and R, run the ladder,
    canonical zero-test.  Returns (N,) int32 0/1.

    This is the fused form batch_verify uses; the host side is reduced to
    byte parsing, SHA-512 and limb packing (all C-speed numpy/hashlib)."""
    xA, okA = device_decompress(yA, signA)
    xR, okR = device_decompress(yR, signR)
    nax = F.sub(yA * 0, xA)                           # -x_A
    nat = F.mul(nax, yA)
    d1, d2 = verify_core(nax, yA, nat, xR, yR, s_bits, k_bits)
    ok = jnp.logical_and(jnp.logical_and(okA, okR),
                         jnp.logical_and(F.is_zero(d1), F.is_zero(d2)))
    return ok.astype(jnp.int32)


verify_full_kernel = jax.jit(verify_full_core)


# ---------------------------------------------------------------------------
# Host orchestration
# ---------------------------------------------------------------------------

def _bits_msb_first(x: int, nbits: int = 256) -> np.ndarray:
    raw = np.frombuffer(x.to_bytes(nbits // 8, "big"), dtype=np.uint8)
    return np.unpackbits(raw).astype(np.int32)


def _finish_decompress(y: int, sign: int, x_cand: int):
    """Cheap host tail of decompression given the device sqrt candidate."""
    u = (y * y - 1) % ed.P
    v = (ed.D * y * y + 1) % ed.P
    vx2 = v * x_cand * x_cand % ed.P
    if vx2 == u:
        x = x_cand
    elif vx2 == ed.P - u:
        x = x_cand * ed.SQRT_M1 % ed.P
    else:
        return None
    if x == 0 and sign == 1:
        return None
    if x & 1 != sign:
        x = ed.P - x
    return x


def prepare_batch(vks, msgs, sigs):
    """Host/device prep: decode/hash every (vk, msg, sig) into kernel inputs.

    The expensive square root of point decompression runs batched on device
    (decompress_kernel); the host does parsing, SHA-512, the root-check /
    sign fix (a handful of modmuls each), and limb packing.

    Returns (arrays, valid_mask); invalid entries (bad point encoding,
    s >= L, wrong length) get dummy inputs and are masked False.
    """
    n = len(vks)
    y_A = [0] * n
    y_R = [0] * n
    sign_A = [0] * n
    sign_R = [0] * n
    ss = [0] * n
    ks = [0] * n
    parse_ok = np.zeros(n, dtype=bool)
    mask255 = (1 << 255) - 1
    for j in range(n):
        vk, msg, sig = vks[j], msgs[j], sigs[j]
        if len(sig) != 64 or len(vk) != 32:
            continue
        na = int.from_bytes(vk, "little")
        nr = int.from_bytes(sig[:32], "little")
        ya, yr = na & mask255, nr & mask255
        if ya >= ed.P or yr >= ed.P:
            continue
        s = int.from_bytes(sig[32:], "little")
        if s >= L:
            continue
        y_A[j], sign_A[j] = ya, na >> 255
        y_R[j], sign_R[j] = yr, nr >> 255
        ss[j] = s
        ks[j] = ed.sha512_int(sig[:32], vk, msg) % L
        parse_ok[j] = True
    # device: batched sqrt candidates for A-ys and R-ys in one call
    xc = np.asarray(decompress_kernel(jnp.asarray(F.pack(y_A + y_R))))
    xs = F.unpack(xc)
    vals = {name: [0] * n for name in ("nax", "nay", "nat", "rx", "ry")}
    s_bits = np.zeros((256, n), np.int32)
    k_bits = np.zeros((256, n), np.int32)
    valid = np.zeros(n, dtype=bool)
    for j in range(n):
        if not parse_ok[j]:
            continue
        ax = _finish_decompress(y_A[j], sign_A[j], int(xs[j]))
        rx = _finish_decompress(y_R[j], sign_R[j], int(xs[n + j]))
        if ax is None or rx is None:
            continue
        nax = (ed.P - ax) % ed.P
        vals["nax"][j] = nax
        vals["nay"][j] = y_A[j]
        vals["nat"][j] = nax * y_A[j] % ed.P
        vals["rx"][j] = rx
        vals["ry"][j] = y_R[j]
        s_bits[:, j] = _bits_msb_first(ss[j])
        k_bits[:, j] = _bits_msb_first(ks[j])
        valid[j] = True
    return (F.pack(vals["nax"]), F.pack(vals["nay"]), F.pack(vals["nat"]),
            F.pack(vals["rx"]), F.pack(vals["ry"]), s_bits, k_bits), valid


_WEIGHTS = np.array([1 << (F.RADIX * i) for i in range(F.NLIMBS)],
                    dtype=object)


def finalize(d1, d2, valid) -> list[bool]:
    """Reduce the (possibly non-canonical, possibly slightly negative) limb
    diffs to ints mod p and accept where both vanish."""
    v1 = (_WEIGHTS @ np.asarray(d1).astype(object)) % ed.P
    v2 = (_WEIGHTS @ np.asarray(d2).astype(object)) % ed.P
    ok = (v1 == 0) & (v2 == 0) & valid
    return [bool(b) for b in ok]


_LIMB_W = (1 << np.arange(F.RADIX, dtype=np.int64)).astype(np.int32)
# the challenge stage of the packers: lanes handed to it, and lanes the
# native batch call computed (equal, or 0 where the pure loop ran)
_CHALLENGE_LANES = _metrics.counter("ed25519.challenge_lanes")
_CHALLENGE_NATIVE_LANES = _metrics.counter("ed25519.challenge_native_lanes")
_L_TOP_ROWS = None  # lazy


def _bytes_rows(items, width) -> tuple[np.ndarray, np.ndarray]:
    """Stack byte strings into an (N, width) uint8 array; wrong-length rows
    become zeros with ok=False."""
    n = len(items)
    ok = np.ones(n, dtype=bool)
    bad = [j for j, b in enumerate(items) if len(b) != width]
    if bad:
        items = list(items)
        for j in bad:
            items[j] = b"\x00" * width
            ok[j] = False
    arr = np.frombuffer(b"".join(items), dtype=np.uint8).reshape(n, width)
    return arr, ok


def _decode_compressed(arr: np.ndarray):
    """(N,32) little-endian compressed points -> (y_limbs (20,N) int32,
    sign (N,) int32, ok (N,) canonical-y mask)."""
    n = arr.shape[0]
    bits = np.unpackbits(arr, axis=1, bitorder="little")      # (N, 256)
    sign = bits[:, 255].astype(np.int32)
    ybits = bits.copy()
    ybits[:, 255] = 0
    padded = np.pad(ybits, ((0, 0), (0, F.NLIMBS * F.RADIX - 256)))
    limbs = padded.reshape(n, F.NLIMBS, F.RADIX).astype(np.int32) @ _LIMB_W
    # y >= p iff y + 19 carries into bit 255 (y < 2^255 since bit cleared)
    v = limbs.astype(np.int64)
    v[:, 0] += 19
    for i in range(F.NLIMBS - 1):
        v[:, i + 1] += v[:, i] >> F.RADIX
        v[:, i] &= F.MASK
    ok = (v[:, F.NLIMBS - 1] >> 8) == 0
    return limbs.T.copy(), sign, ok


def _scalar_lt_L(s_rows: np.ndarray) -> np.ndarray:
    """(N,32) little-endian scalars: mask of s < L (L ≈ 2^252 + 2^124.x)."""
    top = s_rows[:, 31]
    ok = top < 0x10
    borderline = np.nonzero(top == 0x10)[0]
    for j in borderline:
        s = int.from_bytes(s_rows[j].tobytes(), "little")
        ok[j] = s < L
    return ok


def challenge_rows_pure(R_rows, A_rows, msgs, parse_ok) -> np.ndarray:
    """`challenge_rows` one lane at a time in Python: what runs where the
    native library cannot be built, and the oracle the tests hold the
    native call to."""
    zero = bytes(32)
    rows = [(ed.sha512_int(R_rows[j].tobytes(), A_rows[j].tobytes(),
                           msgs[j]) % L).to_bytes(32, "little")
            if parse_ok[j] else zero for j in range(len(msgs))]
    return np.frombuffer(b"".join(rows),
                         dtype=np.uint8).reshape(len(msgs), 32)


def challenge_rows(R_rows, A_rows, msgs, parse_ok) -> np.ndarray:
    """The batch's Ed25519 challenge scalars k = SHA-512(R || A || M) mod L
    as (N, 32) little-endian uint8 rows; a lane outside parse_ok reads
    k = 0.  One call into the native library for the whole batch (the
    interpreter lock is free for its length); whether the library loaded
    is all that selects, and `ed25519.challenge_native_lanes` says which
    ran."""
    n = len(msgs)
    with _spans.span("pack_ed.challenge", cat="dispatch"):
        _CHALLENGE_LANES.inc(n)
        k_rows = cpp_backend.ed25519_challenge_rows(R_rows, A_rows, msgs,
                                                    parse_ok)
        if k_rows is NotImplemented:
            return challenge_rows_pure(R_rows, A_rows, msgs, parse_ok)
        _CHALLENGE_NATIVE_LANES.inc(n)
        return k_rows


def prepare_bytes_batch(vks, msgs, sigs):
    """Numpy-only host prep for verify_full_kernel.

    Returns ((yA, signA, yR, signR, s_bits, k_bits), parse_ok); all per-point
    field math happens on device (device_decompress)."""
    vk_arr, vk_ok = _bytes_rows(vks, 32)
    sig_arr, sig_ok = _bytes_rows(sigs, 64)
    yA, signA, a_ok = _decode_compressed(vk_arr)
    yR, signR, r_ok = _decode_compressed(sig_arr[:, :32])
    s_ok = _scalar_lt_L(sig_arr[:, 32:])
    parse_ok = vk_ok & sig_ok & a_ok & r_ok & s_ok
    # s bits MSB-first: flip the little-endian bit order
    s_bits = np.flip(np.unpackbits(sig_arr[:, 32:], axis=1,
                                   bitorder="little"), axis=1)
    s_bits = np.ascontiguousarray(s_bits.T).astype(np.int32)
    k_rows = challenge_rows(sig_arr[:, :32], vk_arr, msgs, parse_ok)
    k_bits = np.flip(np.unpackbits(k_rows, axis=1, bitorder="little"),
                     axis=1)
    k_bits = np.ascontiguousarray(k_bits.T).astype(np.int32)
    return (yA, signA, yR, signR, s_bits, k_bits), parse_ok


def _y_canonical(arr: np.ndarray) -> np.ndarray:
    """(N, 32) little-endian point rows: mask of y < p with the sign bit
    ignored (y >= p iff the 255 low bits are all-ones down to byte 1 and
    byte 0 >= 0xED, since p = 2^255 - 19)."""
    return ~(((arr[:, 31] & 0x7F) == 0x7F)
             & (arr[:, 1:31] == 0xFF).all(axis=1)
             & (arr[:, 0] >= 0xED))


def _point_words(arr: np.ndarray):
    """(N, 32) compressed point rows -> ((8, N) uint32 words of y, the
    sign bit cleared; sign (N,) int32; mask of y < p)."""
    clear = arr.copy()
    clear[:, 31] &= 0x7F
    return (F.words_from_bytes_rows(clear),
            (arr[:, 31] >> 7).astype(np.int32), _y_canonical(arr))


def prepare_words_batch(vks, msgs, sigs):
    """Packed-words host prep for verify_full_split_words_kernel.

    Returns ((Aw, signA, Rw, signR, sw, kw), parse_ok): the 256-bit
    inputs as (8, N) uint32 word rows (sign bits cleared out of Aw/Rw
    into the (N,) int32 sign vectors) — the transfer-thin form."""
    vk_arr, vk_ok = _bytes_rows(vks, 32)
    sig_arr, sig_ok = _bytes_rows(sigs, 64)
    Aw, signA, a_ok = _point_words(vk_arr)
    Rw, signR, r_ok = _point_words(sig_arr[:, :32])
    s_rows = np.ascontiguousarray(sig_arr[:, 32:])
    s_ok = _scalar_lt_L(s_rows)
    parse_ok = vk_ok & sig_ok & a_ok & r_ok & s_ok
    k_rows = challenge_rows(sig_arr[:, :32], vk_arr, msgs, parse_ok)
    return ((Aw, signA, Rw, signR,
             F.words_from_bytes_rows(s_rows),
             F.words_from_bytes_rows(k_rows)), parse_ok)


def batch_verify(vks, msgs, sigs, pad_to: int | None = None) -> list[bool]:
    """End-to-end batched verify (full-device path). pad_to rounds the batch
    up to a fixed size so repeated calls hit the jit cache."""
    n = len(vks)
    if n == 0:
        return []
    m = pad_to if pad_to and pad_to >= n else n
    vks = list(vks) + [b"\x00" * 32] * (m - n)
    msgs = list(msgs) + [b""] * (m - n)
    sigs = list(sigs) + [b"\x00" * 64] * (m - n)
    arrays, parse_ok = prepare_bytes_batch(vks, msgs, sigs)
    ok = np.asarray(verify_full_kernel(*[jnp.asarray(a) for a in arrays]))
    return [bool(o) and bool(p) for o, p in zip(ok[:n], parse_ok[:n])]
