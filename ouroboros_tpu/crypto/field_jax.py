"""GF(2^255-19) arithmetic on batched int32 limb vectors — the TPU field core.

Design (TPU-first, see SURVEY.md §7):
- A field element batch is an int32 array of shape (NLIMBS, N): limbs on the
  sublane axis, batch on the 128-wide lane axis, so every op is elementwise
  over the batch with full lane utilisation.
- Radix 2^13 × 20 limbs = 260 bits.  All products a_i*b_j of carried inputs
  (≤ 2^13+ε) sum over ≤20 terms to < 2^31, so schoolbook multiplication
  accumulates exactly in int32 — no 64-bit arithmetic anywhere, which is the
  constraint that makes this map onto the TPU VPU's int32 lanes.
- Multiplication folds limbs ≥ 20 back via 2^260 ≡ 608 (mod p), splitting the
  high product limbs lo/hi so the ×608 stays inside int32.
- Carries are lazy: exactly the rounds needed to restore the ≤ 2^13+ε input
  bound are run after each op (2 after mul, 1 after add/sub).
- No data-dependent control flow: everything is fixed-trip-count and
  branch-free, so XLA compiles one static program per batch shape.

Bit-exactness oracle: ouroboros_tpu.crypto.edwards (Python ints).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

P = 2**255 - 19
NLIMBS = 20
RADIX = 13
MASK = (1 << RADIX) - 1
NPROD = 2 * NLIMBS - 1
# 2^260 = 2^5 * 2^255 ≡ 32*19 = 608 (mod p): weight of limb NLIMBS folding to 0
FOLD = 608


def int_to_limbs(x: int) -> list[int]:
    return [(x >> (RADIX * i)) & MASK for i in range(NLIMBS)]


def limbs_to_int(limbs) -> int:
    return sum(int(v) << (RADIX * i) for i, v in enumerate(limbs))


def pack(ints, dtype=np.int32) -> np.ndarray:
    """List of N field ints -> (NLIMBS, N) limb array."""
    vals = np.array(ints, dtype=object)
    out = np.empty((NLIMBS, len(ints)), dtype=dtype)
    for i in range(NLIMBS):
        out[i] = ((vals >> (RADIX * i)) & MASK).astype(dtype)
    return out


_UNPACK_WEIGHTS = np.array([1 << (RADIX * i) for i in range(NLIMBS)],
                           dtype=object)


def unpack(arr) -> list[int]:
    """(NLIMBS, N) limb array (possibly uncarried) -> N field ints mod p."""
    a = np.asarray(arr).astype(object)
    return list((_UNPACK_WEIGHTS @ a) % P)



# 2p in limb form, for subtraction without negatives: a - b := a + 2p - b.
_TWO_P_LIMBS = np.array(int_to_limbs(2 * P), dtype=np.int32)[:, None]


def _col(limbs, n: int) -> jnp.ndarray:
    """(NLIMBS, n) int32 limb constant built from Python-int scalars
    broadcast to full width (XLA constant-folds the concat)."""
    return jnp.concatenate(
        [jnp.full((1, n), int(v), jnp.int32) for v in limbs], axis=0)


def two_p_col(n: int):
    return _col(int_to_limbs(2 * P), n)


def p_col(n: int):
    return _col(int_to_limbs(P), n)


def carry_round(v):
    """One vectorized carry round; wrap-around carry folds with ×19.

    Carry out of limb 19 (weight 2^260) re-enters limb 0 with weight 608
    = FOLD; using 2^255 ≡ 19 directly on limb 19's excess (>> RADIX-5 split)
    would save nothing, so keep the uniform per-limb shift.
    """
    c = v >> RADIX
    lo = v & MASK
    shifted = jnp.concatenate([c[-1:] * FOLD, c[:-1]], axis=0)
    return lo + shifted


def carry3(v):
    """Three rounds: enough to bring post-multiplication limbs (< 2^31)
    back under ~2^13.3.  Bound chase: after r1 limb0 ≤ 8191+608*(2^31>>13);
    r2 brings all ≤ ~2^14.7; r3 lands ≤ 10015.  With inputs ≤ 10015,
    schoolbook sums stay ≤ 20*10015^2 < 2^31 — the invariant every op here
    preserves."""
    return carry_round(carry_round(carry_round(v)))


def add(a, b):
    return carry_round(a + b)


def sub(a, b):
    return carry_round(a + two_p_col(a.shape[1]) - b)


def _row_update(v, i, row):
    """v with row i replaced — concatenation, not scatter (XLA fuses the
    concat)."""
    parts = []
    if i > 0:
        parts.append(v[:i])
    parts.append(row[None, :] if row.ndim == 1 else row)
    if i + 1 < v.shape[0]:
        parts.append(v[i + 1:])
    return jnp.concatenate(parts, axis=0)


def mul(a, b):
    """Schoolbook product with fold; output carried to input bounds.
    Shifted-accumulate form: prod = Σ_j shift_j(a·b_j) with zero-pad
    concatenations — ~70 primitives per product, a small trace that XLA
    fuses."""
    n = a.shape[1]
    acc = None
    for j in range(NLIMBS):
        pj = a * b[j:j + 1]                          # (NLIMBS, n)
        parts = []
        if j:
            parts.append(jnp.zeros((j, n), jnp.int32))
        parts.append(pj)
        if NPROD - NLIMBS - j:
            parts.append(jnp.zeros((NPROD - NLIMBS - j, n), jnp.int32))
        shifted = jnp.concatenate(parts, axis=0) if len(parts) > 1 else pj
        acc = shifted if acc is None else acc + shifted
    low = acc[:NLIMBS]
    high = acc[NLIMBS:]                       # limbs 20..38 -> fold to 0..18
    z1 = jnp.zeros((1, n), jnp.int32)
    low = (low
           + jnp.concatenate([high & MASK, z1], axis=0) * FOLD
           + jnp.concatenate([z1, high >> RADIX], axis=0) * FOLD)
    return carry3(low)


def sqr(a):
    """Squaring (the shifted form has no cheaper squaring shape)."""
    return mul(a, a)


# 40*p as a 20-limb vector with an oversized top limb (40p needs 261 bits);
# added before canonicalisation so any intermediate value (|v| < ~40p for all
# ops in this module) becomes positive without changing it mod p.
def _pad_limbs(x: int) -> np.ndarray:
    out = [(x >> (RADIX * i)) & MASK for i in range(NLIMBS - 1)]
    out.append(x >> (RADIX * (NLIMBS - 1)))
    return np.array(out, dtype=np.int32)[:, None]


_FORTY_P = _pad_limbs(40 * P)
_P_LIMBS = np.array(int_to_limbs(P), dtype=np.int32)[:, None]


def _exact_scan(v):
    """Exact carry propagation over the limb axis (statically unrolled so
    XLA fuses it into straight-line code — a lax.scan of 20 tiny steps costs
    real wall-clock in dispatch).

    Returns (canonical limbs in [0, 2^13), carry-out of limb 19) — i.e. the
    base-2^13 digits of the value and floor(value / 2^260)."""
    c = jnp.zeros_like(v[0])
    outs = []
    for i in range(NLIMBS):
        t = v[i] + c
        outs.append(t & MASK)
        c = t >> RADIX
    return jnp.stack(outs), c


def forty_p_col(n: int):
    out = [(40 * P >> (RADIX * i)) & MASK for i in range(NLIMBS - 1)]
    out.append(40 * P >> (RADIX * (NLIMBS - 1)))
    return _col(out, n)


def canon(v):
    """Full canonicalisation to [0, p): exact, branch-free, vectorized.

    Precondition: value(v) > -40p and value(v) < ~41p (every op in this
    module stays far inside that; see the limb-bound invariant on carry3)."""
    v = v + forty_p_col(v.shape[1])
    digits, c20 = _exact_scan(v)                 # value < 81p < 2^262
    digits = _row_update(digits, 0, digits[0] + c20 * FOLD)  # 2^260 ≡ 608
    digits, c20 = _exact_scan(digits)            # c20 == 0 now; value < 2^260
    hi = digits[NLIMBS - 1] >> (255 - RADIX * (NLIMBS - 1))   # bits ≥ 255
    digits = _row_update(digits, NLIMBS - 1, digits[NLIMBS - 1] & 0xFF)
    digits = _row_update(digits, 0, digits[0] + hi * 19)  # 2^255 ≡ 19
    digits, _ = _exact_scan(digits)
    # single conditional subtract of p: v >= p iff v+19 has bit 255 set
    w = _row_update(digits, 0, digits[0] + 19)
    w, _ = _exact_scan(w)
    bit = w[NLIMBS - 1] >> 8                     # 0 or 1
    w = _row_update(w, NLIMBS - 1, w[NLIMBS - 1] & 0xFF)
    return jnp.where(bit[None, :] == 1, w, digits)


def is_zero(v):
    """(N,) bool: value(v) ≡ 0 (mod p), exactly."""
    return jnp.all(canon(v) == 0, axis=0)


# -- packed device I/O: 256-bit values travel host->device as (8, N) uint32
#    words (little-endian), 8x smaller than the (NLIMBS, N) int32 limb form
#    and 32x smaller than (256, N) bit rows.  The packed form was chosen
#    on a device whose host link no longer exists; what the transfer
#    costs beside the kernel is not measured on the present chip
#    (ROADMAP A2).  Unpacking is ~3 shifts/row on the VPU.

def words_from_bytes_rows(arr: np.ndarray) -> np.ndarray:
    """(N, 32) uint8 little-endian byte rows -> (8, N) uint32 words."""
    return np.ascontiguousarray(
        arr.reshape(-1, 8, 4).view(np.uint32)[:, :, 0].T)


def limbs_from_words(w):
    """(8, N) uint32 words -> (NLIMBS, N) int32 limbs (device op).

    Each 13-bit limb spans at most two 32-bit words."""
    rows = []
    for l in range(NLIMBS):
        bit = RADIX * l
        k, s = bit // 32, bit % 32
        v = w[k] >> s
        if 32 - s < RADIX and k + 1 < 8:
            v = v | (w[k + 1] << (32 - s))
        rows.append((v & MASK).astype(jnp.int32))
    return jnp.stack(rows)


def words_from_limbs(v):
    """(NLIMBS, N) int32 CANONICAL limbs (each in [0, 2^13), value
    < 2^255: what `canon` returns) -> (8, N) uint32 words, the inverse of
    `limbs_from_words` (device op).  The limbs' bits are disjoint, so a
    word is the OR of the two to four limbs that overlap it."""
    u = v.astype(jnp.uint32)
    rows = []
    for k in range(8):
        lo = 32 * k
        w = None
        for l in range(NLIMBS):
            bit = RADIX * l
            if bit + RADIX <= lo or bit >= lo + 32:
                continue
            part = u[l] << (bit - lo) if bit >= lo else u[l] >> (lo - bit)
            w = part if w is None else w | part
        rows.append(w)
    return jnp.stack(rows)


def bit_from_words(w, j: int):
    """Bit j (0 = LSB) of each lane's 256-bit value: (N,) int32."""
    return ((w[j // 32] >> (j % 32)) & 1).astype(jnp.int32)


def zeros_like_batch(n: int):
    return jnp.zeros((NLIMBS, n), dtype=jnp.int32)


def const_batch(x: int, n: int):
    return _col(int_to_limbs(x), n)


def one_like(x):
    """Limb vector of 1 with x's shape AND varying-axis type (derived from
    x, so it stays a legal lax.fori_loop carry under shard_map — a pure
    constant would not)."""
    return x * 0 + const_batch(1, x.shape[1])
