"""Fused Pallas TPU kernels for the batched Ed25519 / ECVRF hot loops.

Why pallas: the XLA op-by-op kernels (ed25519_jax.verify_full_kernel,
vrf_jax.vrf_verify_kernel) plateau at ~13k Ed25519/s and ~7k VRF/s on one
v5e chip — every field multiplication is ~45 separate HLO ops whose
intermediates round-trip HBM, so the ladder is bound by per-op overhead
and HBM bandwidth, not VPU arithmetic.  Fusing the whole Strauss-Shamir
ladder into one pallas kernel keeps Q, the select table, and every carry
chain in VMEM for all 256 iterations; only the inputs (limbs + scalar
bits) and the final acceptance mask cross HBM.

The field arithmetic is field_jax's: radix-2^13 × 20 int32 limbs, lazy
carries, fold via 2^260 ≡ 608 — pure jnp ops on static shapes, which is
exactly what Mosaic lowers; the functions are imported and used unchanged
inside the kernel body (bit-exactness oracle: ed25519_ref/vrf_ref, same as
the XLA path).

Grid: 1-D over lane tiles of TILE items; each program verifies TILE
signatures/proofs independently (batch on the 128-lane axis, limbs on
sublanes).

Reference seam (what this accelerates): the per-header VRF+KES+Ed25519
verification of Shelley/Protocol.hs:433-442 and the BBODY witness
multi-verify of Shelley/Ledger/Ledger.hs:279-284, batched per SURVEY.md §7.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ed25519_jax as EJ
from . import edwards as ed
from . import field_jax as F

TILE = 512          # batch items per grid program (lane axis)


def _interpret() -> bool:
    """Run the kernels in interpreter mode off-TPU (CPU tests / the
    8-device virtual mesh) — Mosaic lowering is TPU-only."""
    return jax.devices()[0].platform == "cpu"


def _mul_form() -> str:
    """Column-form multiplication is ~3.5x faster at runtime inside the
    fused Mosaic ladders but traces to ~10x more primitives; under the
    CPU interpreter the trace IS the cost (XLA:CPU compiles of the
    column-form kernels dominated the device test partition), so tests
    get the small shifted trace."""
    return "shifted" if _interpret() else "columns"


def _pt_double(p):
    return EJ.pt_double(p)


def _select16(table, idx):
    """16-entry point-table select by 4-bit index (N,): two-stage
    where-chain — pick within each 4-row group by the low 2 bits, then
    across groups by the high 2 — 15 wheres per coordinate either way but
    shorter dependence chains for the VPU."""
    lo = idx & 3
    hi = idx >> 2
    out = []
    for c in range(4):
        groups = []
        for g in range(4):
            t = table[4 * g][c]
            t = jnp.where((lo == 1)[None, :], table[4 * g + 1][c], t)
            t = jnp.where((lo == 2)[None, :], table[4 * g + 2][c], t)
            t = jnp.where((lo == 3)[None, :], table[4 * g + 3][c], t)
            groups.append(t)
        t = groups[0]
        t = jnp.where((hi == 1)[None, :], groups[1], t)
        t = jnp.where((hi == 2)[None, :], groups[2], t)
        t = jnp.where((hi == 3)[None, :], groups[3], t)
        out.append(t)
    return tuple(out)


# ---------------------------------------------------------------------------
# Split-128 Ed25519 kernel: ed25519_jax.verify_full_split_core as one fused
# Mosaic program — 128 doublings instead of 256 (see the split-ladder notes
# there; A128 = [2^128]A arrives from the host A128Cache).
# ---------------------------------------------------------------------------

def _ed25519_split_kernel(yA_ref, xA_ref, xA128_ref, yA128_ref,
                          yR_ref, signR_ref, idx_ref, ok_ref):
    yA = yA_ref[:]
    xA = xA_ref[:]
    yR = yR_ref[:]
    xA128 = xA128_ref[:]
    yA128 = yA128_ref[:]
    xR, okR = EJ.device_decompress(yR, signR_ref[0, :])
    one = F.one_like(yA)
    nax = F.sub(yA * 0, xA)
    negA = (nax, yA, one, F.mul(nax, yA))
    nax128 = F.sub(yA * 0, xA128)
    negA128 = (nax128, yA128, one, F.mul(nax128, yA128))
    n = TILE
    ident = EJ._identity_like(yA)
    table = EJ.split_table_16(negA, negA128, n, ident)

    def body(i, Q):
        Q = _pt_double(Q)
        return EJ.pt_add_cached(Q, _select16(table, idx_ref[i, :]))

    Q = lax.fori_loop(0, 128, body, ident)
    X, Y, Z, _ = Q
    d1 = F.sub(F.mul(xR, Z), X)
    d2 = F.sub(F.mul(yR, Z), Y)
    ok = jnp.logical_and(okR,
                         jnp.logical_and(F.is_zero(d1), F.is_zero(d2)))
    ok_ref[0, :] = ok.astype(jnp.int32)


def _ed25519_split_call(Aw, xAw, A128xw, A128yw, Rw, signR2d,
                        s_words, k_words, n: int):
    """Packed-words entry: XLA unpacks words -> limbs / window digits on
    device (tiny elementwise prologue), then the fused Mosaic ladder.
    A's affine x arrives from the A128Cache — callers mask not-`known`
    lanes."""
    yA = F.limbs_from_words(Aw)
    xA = F.limbs_from_words(xAw)
    yR = F.limbs_from_words(Rw)
    xA128 = F.limbs_from_words(A128xw)
    yA128 = F.limbs_from_words(A128yw)
    idx = EJ.split_idx_rows(s_words, k_words)
    grid = n // TILE
    lane = lambda i: (0, i)
    limb_spec = pl.BlockSpec((F.NLIMBS, TILE), lane,
                             memory_space=pltpu.VMEM)
    sign_spec = pl.BlockSpec((1, TILE), lane, memory_space=pltpu.VMEM)
    idx_spec = pl.BlockSpec((128, TILE), lane, memory_space=pltpu.VMEM)
    with F.mul_impl(_mul_form()):
        return pl.pallas_call(
            _ed25519_split_kernel,
            grid=(grid,),
            in_specs=[limb_spec, limb_spec, limb_spec, limb_spec,
                      limb_spec, sign_spec, idx_spec],
            out_specs=pl.BlockSpec((1, TILE), lane,
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
            interpret=_interpret(),
        )(yA, xA, xA128, yA128, yR, signR2d, idx)


_ed25519_split_jit = jax.jit(_ed25519_split_call, static_argnames=("n",))


def ed25519_split_pallas(Aw, xAw, A128xw, A128yw, Rw, signR,
                         s_words, k_words, n: int):
    """Batched split-ladder Ed25519 verify, pallas path; inputs as
    prepare_words_batch + A128Cache.assemble produce them."""
    return _ed25519_split_jit(
        jnp.asarray(Aw), jnp.asarray(xAw),
        jnp.asarray(A128xw), jnp.asarray(A128yw),
        jnp.asarray(Rw), jnp.asarray(signR).reshape(1, -1),
        jnp.asarray(s_words), jnp.asarray(k_words), n)


# ---------------------------------------------------------------------------
# VRF (ECVRF-ED25519-SHA512-Elligator2) — the vrf_jax.vrf_verify_core device
# half as one fused kernel
# ---------------------------------------------------------------------------

def _select8(table, idx):
    """8-entry point-table select by 3-bit index — where-chain per coord."""
    out = []
    for c in range(4):
        t = table[0][c]
        for e in range(1, 8):
            t = jnp.where((idx == e)[None, :], table[e][c], t)
        out.append(t)
    return tuple(out)


def _bytes_rows_from_limbs(yc, sign):
    """Canonical limbs (NLIMBS, M) + parity row (M,) -> (32, M) int32 byte
    values of the compressed encoding.  Each byte spans at most two 13-bit
    limbs: byte k = ((limb[l] >> s) | (limb[l+1] << (13-s))) & 0xFF with
    l = 8k // 13, s = 8k mod 13 — 2-D ops only (pallas-safe, unlike the
    XLA path's 3-D unpack in vrf_jax.compress_device)."""
    rows = []
    for k in range(32):
        bit = 8 * k
        l, s = bit // F.RADIX, bit % F.RADIX
        v = yc[l:l + 1] >> s
        if F.RADIX - s < 8 and l + 1 < F.NLIMBS:
            v = v | (yc[l + 1:l + 2] << (F.RADIX - s))
        rows.append(v & 0xFF)
    out = jnp.concatenate(rows, axis=0)
    return F._row_update(out, 31, out[31] + (sign << 7))


def _compress_rows(x_aff, y_aff):
    yc = F.canon(y_aff)
    xc = F.canon(x_aff)
    return _bytes_rows_from_limbs(yc, xc[0] & 1)


def _triple_ladder(P1, P1p, P2, idx_ref, n):
    """Q = [lo]P1 + [hi]P1' + [c]P2, 128 iterations, 8-entry cached-form
    where-select (vrf_jax._triple_ladder_idx, Mosaic-safe form: digit rows
    are read from a ref — a dynamic_slice of a value has no lowering — and
    no lane-direction concatenation anywhere)."""
    ident = EJ._identity_like(P1[0])
    table = _VJ._triple_table_cached(P1, P1p, P2, n)

    def body(i, Q):
        Q = EJ.pt_double(Q)
        return EJ.pt_add_cached(Q, _select8(table, idx_ref[i, :]))

    return lax.fori_loop(0, 128, body, ident)


def _affine_bytes(pt, n):
    """Projective point batch -> (32, n) compressed-encoding byte rows."""
    Zi = EJ.pow_inv(pt[2])
    return _compress_rows(F.mul(pt[0], Zi), F.mul(pt[1], Zi))


def _vrf_verify_kernel(yY_ref, xY_ref, yG_ref, signG_ref, r_ref,
                       idx_ref, out_ref):
    """One TILE of the VRF device half (vrf_jax.vrf_verify_idx_xy_core:
    Y's affine x pre-resolved from the point cache, so only Gamma pays a
    square-root chain).

    out rows: [0:32] H bytes, [32:64] U, [64:96] V, [96:128] [8]Gamma,
    [128] okY (constant 1 — host folds the cache mask), [129] okG."""
    from . import vrf_jax as VJ
    n = TILE
    yY = yY_ref[:]
    xY = xY_ref[:]
    yG = yG_ref[:]
    one = F.one_like(yY)
    xG, okG = EJ.device_decompress(yG, signG_ref[0, :])
    okY = okG | True
    H = VJ._double3(VJ.elligator2_fraction(r_ref[:]))
    G8 = VJ._double3((xG, yG, one, F.mul(xG, yG)))
    nYx = F.sub(yY * 0, xY)
    nGx = F.sub(yG * 0, xG)
    B = (F.const_batch(_GX, n), F.const_batch(_GY, n), one,
         F.const_batch(_GX * _GY % ed.P, n))
    Bp = (F.const_batch(_G2X, n), F.const_batch(_G2Y, n), one,
          F.const_batch(_G2X * _G2Y % ed.P, n))
    Hp = lax.fori_loop(0, 128, lambda _, p: EJ.pt_double(p), H)
    negY = (nYx, yY, one, F.mul(nYx, yY))
    negG = (nGx, yG, one, F.mul(nGx, yG))
    U = _triple_ladder(B, Bp, negY, idx_ref, n)
    V = _triple_ladder(H, Hp, negG, idx_ref, n)
    out_ref[:] = jnp.concatenate(
        [_affine_bytes(H, n), _affine_bytes(U, n), _affine_bytes(V, n),
         _affine_bytes(G8, n),
         okY.astype(jnp.int32)[None, :], okG.astype(jnp.int32)[None, :]],
        axis=0)


# module-constant mirrors of vrf_jax's (kept local so the kernel body has
# no numpy-array captures)
from . import vrf_jax as _VJ  # noqa: E402  (after EJ/F to avoid cycles)

_GX, _GY = _VJ._GX, _VJ._GY
_G2X, _G2Y = _VJ._G2X, _VJ._G2Y


def _vrf_verify_call(Yw, xYw, Gw, signG2d, rw, cw, sw, n: int):
    """Packed-words entry: XLA unpacks words -> limbs / digit rows on
    device, then the fused Mosaic kernel."""
    yY = F.limbs_from_words(Yw)
    xY = F.limbs_from_words(xYw)
    yG = F.limbs_from_words(Gw)
    r = F.limbs_from_words(rw)
    idx = _VJ._vrf_idx_rows(cw, sw)
    grid = n // TILE
    lane = lambda i: (0, i)
    limb_spec = pl.BlockSpec((F.NLIMBS, TILE), lane,
                             memory_space=pltpu.VMEM)
    sign_spec = pl.BlockSpec((1, TILE), lane, memory_space=pltpu.VMEM)
    idx_spec = pl.BlockSpec((128, TILE), lane, memory_space=pltpu.VMEM)
    with F.mul_impl(_mul_form()):
        rows = pl.pallas_call(
            _vrf_verify_kernel,
            grid=(grid,),
            in_specs=[limb_spec, limb_spec, limb_spec, sign_spec, limb_spec,
                      idx_spec],
            out_specs=pl.BlockSpec((130, TILE), lane,
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((130, n), jnp.int32),
            interpret=_interpret(),
        )(yY, xY, yG, signG2d, r, idx)
    # (N, 130) uint8, the layout vrf_jax._finish expects
    return rows.T.astype(jnp.uint8)


_vrf_verify_jit = jax.jit(_vrf_verify_call, static_argnames=("n",))


def vrf_verify_pallas(Yw, xYw, Gw, signG, rw, cw, sw):
    """vrf_jax packed runner (Y affine x from the point cache)."""
    n = Yw.shape[1]
    return _vrf_verify_jit(
        jnp.asarray(Yw), jnp.asarray(xYw),
        jnp.asarray(Gw), jnp.asarray(signG).reshape(1, -1),
        jnp.asarray(rw), jnp.asarray(cw), jnp.asarray(sw), n)


# ---------------------------------------------------------------------------
# [8]Gamma (proof_to_hash) — gamma8_kernel as a pallas kernel
# ---------------------------------------------------------------------------

def _gamma8_kernel(yG_ref, signG_ref, out_ref):
    yG = yG_ref[:]
    one = F.one_like(yG)
    xG, okG = EJ.device_decompress(yG, signG_ref[0, :])
    from . import vrf_jax as VJ
    G8 = VJ._double3((xG, yG, one, F.mul(xG, yG)))
    Zi = EJ.pow_inv(G8[2])
    comp = _compress_rows(F.mul(G8[0], Zi), F.mul(G8[1], Zi))
    out_ref[:] = jnp.concatenate(
        [comp, okG.astype(jnp.int32)[None, :]], axis=0)


def _gamma8_call(Gw, signG2d, n: int):
    yG = F.limbs_from_words(Gw)
    grid = n // TILE
    lane = lambda i: (0, i)
    with F.mul_impl(_mul_form()):
        rows = pl.pallas_call(
            _gamma8_kernel,
            grid=(grid,),
            in_specs=[pl.BlockSpec((F.NLIMBS, TILE), lane,
                                   memory_space=pltpu.VMEM),
                      pl.BlockSpec((1, TILE), lane,
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((33, TILE), lane,
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((33, n), jnp.int32),
            interpret=_interpret(),
        )(yG, signG2d)
    return rows.T.astype(jnp.uint8)      # (N, 33), vrf_jax._finish_betas


_gamma8_jit = jax.jit(_gamma8_call, static_argnames=("n",))


def gamma8_pallas(Gw, signG):
    """vrf_jax._submit_betas packed runner (words input)."""
    n = Gw.shape[1]
    return _gamma8_jit(jnp.asarray(Gw), jnp.asarray(signG).reshape(1, -1),
                       n)


# ---------------------------------------------------------------------------
# KES hash-path check (blake2b_jax.check_block64) as a pallas kernel, so the
# fused window composite stays homogeneous when the ladders run as Mosaic
# ---------------------------------------------------------------------------

def _kes_hash_kernel(m_ref, e_ref, ok_ref):
    from . import blake2b_jax as B
    # static 12-round unroll: a dynamic take of a value (the fori_loop
    # sigma gather of the XLA form) has no Mosaic lowering
    d = B.compress_block64(m_ref[:], unroll=True)
    ok_ref[0, :] = jnp.all(d == e_ref[:], axis=0).astype(jnp.int32)


def _kes_hash_call(mw, ew, n: int):
    grid = n // TILE
    lane = lambda i: (0, i)
    return pl.pallas_call(
        _kes_hash_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((16, TILE), lane, memory_space=pltpu.VMEM),
                  pl.BlockSpec((8, TILE), lane, memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, TILE), lane, memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=_interpret(),
    )(mw, ew)


_kes_hash_jit = jax.jit(_kes_hash_call, static_argnames=("n",))


def kes_hash_pallas(mw, ew):
    """(16, N) message words + (8, N) expected digests -> (1, N) ok."""
    return _kes_hash_jit(jnp.asarray(mw), jnp.asarray(ew), mw.shape[1])
