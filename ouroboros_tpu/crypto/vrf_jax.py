"""Batched ECVRF-ED25519-SHA512-Elligator2 verification on TPU.

The device-side analog of vrf_ref.verify (libsodium crypto_vrf_ietfdraft03,
the PraosVRF of Shelley/Protocol.hs:366-415): for a whole batch of proofs,

  host (numpy/hashlib, C-speed): byte parsing, canonical-y checks, the
      SHA-512s (Elligator input r, challenge recomputation, beta);
  device (one fused kernel): decompress Y and Gamma, the Elligator2 map in
      projective form (no inversions — the Legendre test and the square
      root run on numerator/denominator polynomials), cofactor clearing,
      [8]Gamma for beta, both Strauss-Shamir ladders U = [s]B - [c]Y,
      V = [s]H - [c]Gamma as one concatenated batch, then affine
      conversion via ONE batched inversion chain and on-device point
      compression to bytes.

The kernel returns a single (N, 130) uint8 array — compressed H, U, V,
[8]Gamma plus validity flags — one transfer per batch, sized ~130
bytes/item, instead of one per output.  (The design dates from a device
with a high fixed cost per transfer; that cost is not measured on the
present chip — ROADMAP A2.)

vrf_ref is the bit-exactness oracle; edge cases (non-square w fallback,
inv(0) = 0, failed decompression -> BASE) mirror its behavior via
branch-free selects.  The two measure-zero hash preimages where the
projective form would diverge from the reference (1 + 2r^2 = 0 and
u = -1) are explicitly selected to the reference's values.
"""
from __future__ import annotations

import hashlib

import numpy as np

import jax
import jax.numpy as jnp

from . import ed25519_jax as EJ
from . import edwards as ed
from . import field_jax as F
from .vrf_ref import PROOF_LEN, SUITE

_GX, _GY = ed.to_affine(ed.BASE)
# [2^128]B — compile-time constant for the split-scalar ladder
_B128 = ed.scalar_mult(1 << 128, ed.BASE)
_G2X, _G2Y = ed.to_affine(_B128)
_A = ed.A24                           # Montgomery A = 486662
# reference fallback for the measure-zero Elligator edge case 1+2r^2 == 0:
# host path yields u = -A, y = (-A-1)/(1-A)
_Y_W0 = (ed.P - _A - 1) * ed.inv((1 - _A) % ed.P) % ed.P


def _double_n(pt, n_doublings: int):
    return jax.lax.fori_loop(0, n_doublings,
                             lambda _, p: EJ.pt_double(p), pt)


def _triple_table_cached(P1, P1p, P2, n):
    """8-entry cached-form table over bit combinations lo + 2·hi + 4·c of
    Q += [lo]P1 + [hi]P1' + [c]P2 (4 extended adds + 7 to_cached muls)."""
    ident = EJ._identity_like(P1[0])
    t3 = EJ.pt_add(P1, P1p, n)
    t5 = EJ.pt_add(P1, P2, n)
    t6 = EJ.pt_add(P1p, P2, n)
    t7 = EJ.pt_add(t3, P2, n)
    ext = (P1, P1p, t3, P2, t5, t6, t7)
    return [EJ.ident_cached(P1[0])] + [EJ.to_cached(p, n) for p in ext]


def _triple_ladder_idx(P1, P1p, P2, idx_rows):
    """Q = [lo]P1 + [hi]P1' + [c]P2 in 128 iterations (all three scalars
    are < 2^128: the verification scalar s splits as s = hi*2^128 + lo
    with P1' = [2^128]P1, and the VRF challenge c is 16 bytes).  Halves
    the doubling chain of the naive 256-iteration dual ladder.
    idx_rows: (128, N) int32 digits lo + 2·hi + 4·c, MSB-first.
    Cached-form table adds (one fewer mul per iteration).  Points in
    full extended coordinates; returns projective (X, Y, Z)."""
    n = P1[0].shape[1]
    cach = _triple_table_cached(P1, P1p, P2, n)
    table = tuple(jnp.stack([t[c] for t in cach]) for c in range(4))
    ident = EJ._identity_like(P1[0])

    def body(i, Q):
        Q = EJ.pt_double(Q)
        idx = jax.lax.dynamic_index_in_dim(idx_rows, i, 0, keepdims=False)
        return EJ.pt_add_cached(Q, EJ._onehot_entry(table, idx, 8))

    Q = jax.lax.fori_loop(0, 128, body, ident)
    return Q[0], Q[1], Q[2]


def _select(mask, a, b):
    return jnp.where(mask[None, :], a, b)


def _sqrt_ratio(u, v):
    """x with x^2 = u/v (RFC 8032 §5.1.3 candidate + twist), plus ok mask.
    x is the even-parity affine root — the sign-0 decompression choice."""
    v3 = F.mul(F.mul(v, v), v)
    v7 = F.mul(F.mul(v3, v3), v)
    xc = F.mul(F.mul(u, v3), EJ.pow_p58(F.mul(u, v7)))
    vx2 = F.mul(v, F.mul(xc, xc))
    root_direct = F.is_zero(F.sub(vx2, u))
    root_twist = F.is_zero(F.add(vx2, u))
    ok = jnp.logical_or(root_direct, root_twist)
    x = _select(root_direct, xc, F.mul(xc, F.const_batch(ed.SQRT_M1,
                                                         u.shape[1])))
    x = F.canon(x)
    # parity 0 (sign bit 0 of the compressed-with-sign-0 encoding)
    x_neg, _ = F._exact_scan(F.p_col(x.shape[1]) - x)
    return _select((x[0] & 1) == 1, x_neg, x), ok


def elligator2_fraction(r):
    """Projective Elligator2: r -> Edwards point, inversion-free.

    Host reference (vrf_ref._hash_to_curve): u = -A/(1+2r^2), flipped to
    -A-u when w = u(u^2+Au+1) is non-square; y = (u-1)/(u+1); decompress
    with sign 0.  Here u = U/W with W = 1+2r^2 and U = -A or -2Ar^2, so
    chi(w) = chi(-A * c1 * W) with c1 = W^2 - 2A^2 r^2 (w scaled by the
    square W^4), and y = (U-W)/(U+W) stays a fraction all the way into
    the sqrt ratio.  Returns extended (X, Y, Z, T) with Z = U+W."""
    n = r.shape[1]
    one = F.one_like(r)
    Ac = F.const_batch(_A, n)
    r2 = F.mul(r, r)
    two_r2 = F.add(r2, r2)
    W = F.add(two_r2, one)                      # 1 + 2r^2
    # c1 = W^2 - 2 A^2 r^2 ;  chi input = -A * c1 * W
    c1 = F.sub(F.mul(W, W), F.mul(F.mul(Ac, Ac), two_r2))
    chi_in = F.sub(r * 0, F.mul(Ac, F.mul(c1, W)))
    is_sq = F.is_zero(F.sub(EJ.pow_chi(chi_in), one))
    negA = F.sub(r * 0, Ac)
    U = _select(is_sq, negA, F.mul(negA, two_r2))   # -A  |  -2A r^2
    Yn = F.sub(U, W)
    Yd = F.add(U, W)
    # measure-zero reference edge cases (see module docstring)
    w_zero = F.is_zero(W)
    Yn = _select(w_zero, F.const_batch(_Y_W0, n), Yn)
    Yd = _select(w_zero, one, Yd)
    d_zero = F.is_zero(Yd)
    Yn = _select(d_zero, r * 0, Yn)
    Yd = _select(d_zero, one, Yd)
    # decompress y = Yn/Yd with sign 0: x^2 = (y^2-1)/(d y^2+1)
    Yn2 = F.mul(Yn, Yn)
    Yd2 = F.mul(Yd, Yd)
    u_num = F.sub(Yn2, Yd2)
    v_num = F.add(F.mul(F.const_batch(ed.D, n), Yn2), Yd2)
    x, ok = _sqrt_ratio(u_num, v_num)
    # x == 0 with sign 0 is fine; failure -> BASE (vrf_ref:37)
    X = _select(ok, F.mul(x, Yd), F.const_batch(_GX, n))
    Y = _select(ok, Yn, F.const_batch(_GY, n))
    Z = _select(ok, Yd, one)
    T = _select(ok, F.mul(x, Yn), F.const_batch(_GX * _GY % ed.P, n))
    return (X, Y, Z, T)


def _double3(pt):
    return EJ.pt_double(EJ.pt_double(EJ.pt_double(pt)))


_BYTE_W = None


def compress_device(x_aff, y_aff):
    """Affine limb coords -> (32, N) int32 byte values of the compressed
    encoding (y LE with the x-parity sign in bit 255)."""
    yc = F.canon(y_aff)
    xc = F.canon(x_aff)
    sign = xc[0] & 1
    shifts = jnp.arange(F.RADIX, dtype=jnp.int32)[None, :, None]
    bits = (yc[:, None, :] >> shifts) & 1            # (NLIMBS, RADIX, N)
    bits = bits.reshape(F.NLIMBS * F.RADIX, -1)[:256]
    w = (1 << jnp.arange(8, dtype=jnp.int32))[None, :, None]
    byts = jnp.sum(bits.reshape(32, 8, -1) * w, axis=1)   # (32, N)
    return byts.at[31].add(sign << 7)


def vrf_verify_idx_xy_core(yY, xY, yG, signG, r, idx_rows):
    """Cached-Y form: the pool key's affine x arrives from the A128Cache
    (pool keys repeat across a whole epoch of headers), skipping one of
    the two pow-chain decompressions.  Row 128 (okY) is constant-true —
    the host folds the cache's `known` mask into parse_ok instead."""
    n = yY.shape[1]
    one = F.one_like(yY)
    xG, okG = EJ.device_decompress(yG, signG)
    H = _double3(elligator2_fraction(r))
    G8 = _double3((xG, yG, one, F.mul(xG, yG)))
    nYx = F.sub(yY * 0, xY)
    nGx = F.sub(yG * 0, xG)
    B = (F.const_batch(_GX, n), F.const_batch(_GY, n), one,
         F.const_batch(_GX * _GY % ed.P, n))
    Bp = (F.const_batch(_G2X, n), F.const_batch(_G2Y, n), one,
          F.const_batch(_G2X * _G2Y % ed.P, n))
    Hp = _double_n(H, 128)
    negY = (nYx, yY, one, F.mul(nYx, yY))
    negG = (nGx, yG, one, F.mul(nGx, yG))
    P1 = tuple(jnp.concatenate([B[c], H[c]], axis=1) for c in range(4))
    P1p = tuple(jnp.concatenate([Bp[c], Hp[c]], axis=1) for c in range(4))
    P2 = tuple(jnp.concatenate([negY[c], negG[c]], axis=1)
               for c in range(4))
    idx2 = jnp.concatenate([idx_rows, idx_rows], axis=1)
    UV = _triple_ladder_idx(P1, P1p, P2, idx2)
    Zall = jnp.concatenate([H[2], UV[2], G8[2]], axis=1)
    Zi = EJ.pow_inv(Zall)
    Xall = jnp.concatenate([H[0], UV[0], G8[0]], axis=1)
    Yall = jnp.concatenate([H[1], UV[1], G8[1]], axis=1)
    comp = compress_device(F.mul(Xall, Zi), F.mul(Yall, Zi))
    ones = okG.astype(jnp.int32) * 0 + 1
    rows = jnp.concatenate([comp[:, :n], comp[:, n:2 * n],
                            comp[:, 2 * n:3 * n], comp[:, 3 * n:],
                            ones[None, :],
                            okG.astype(jnp.int32)[None, :]], axis=0)
    return rows.T.astype(jnp.uint8)


def _vrf_idx_rows(c_words, s_words):
    """(4, N) challenge words + (8, N) scalar words -> (128, N) digits."""
    rows = []
    for i in range(128):
        rows.append(F.bit_from_words(s_words, 127 - i)
                    + 2 * F.bit_from_words(s_words, 255 - i)
                    + 4 * F.bit_from_words(c_words, 127 - i))
    return jnp.stack(rows)


def vrf_verify_words_core(Yw, xYw, Gw, signG, rw, cw, sw):
    """Packed-words form: 256-bit inputs as (8, N) uint32 word rows (the
    challenge as (4, N)); unpacking happens on device; Y's affine x comes
    pre-resolved from the point cache.  Transfer-thin — see field_jax
    packed-I/O notes."""
    return vrf_verify_idx_xy_core(
        F.limbs_from_words(Yw), F.limbs_from_words(xYw),
        F.limbs_from_words(Gw), signG,
        F.limbs_from_words(rw), _vrf_idx_rows(cw, sw))


vrf_verify_words_kernel = jax.jit(vrf_verify_words_core)


# challenge preimage prefix bytes (suite || 0x02), a host constant hoisted
# out of the jitted fold body
_SUITE2 = np.frombuffer(SUITE + b"\x02", dtype=np.uint8)


def challenge_ok_device(rows, gamma_bytes, c_bytes):
    """Device-side ECVRF challenge verdict from the kernel's (N, 130)
    output rows: c == SHA512(suite || 0x02 || H || Gamma || U || V)[:16]
    (vrf_ref._hash_points order), folded with the rows' decompression
    flags.  Returns (N,) bool.

    This is the device analog of the host loop in `_finish` — with it,
    the fused window program ships ONE fold scalar instead of 130 bytes
    per proof (sha512_jax has the transfer arithmetic).

    `gamma_bytes` is (N, 32) uint8 (proof bytes 0:32, the compressed
    Gamma), `c_bytes` (N, 16) uint8 (proof bytes 32:48) — both
    host-known inputs; H, U, V stay on device."""
    from . import sha512_jax as S
    n = rows.shape[0]
    prefix = jnp.broadcast_to(jnp.asarray(_SUITE2), (n, 2))
    msg = jnp.concatenate(
        [prefix, rows[:, 0:32], gamma_bytes.astype(jnp.uint8),
         rows[:, 32:96]], axis=1)
    c_match = S.prefix16_eq(msg, 130, c_bytes)
    okY = rows[:, 128].astype(bool)
    okG = rows[:, 129].astype(bool)
    return c_match & okY & okG


def vrf_verify_fold_words_core(Yw, xYw, Gw, signG, rw, cw, sw,
                               gamma_bytes, c_bytes, valid):
    """Packed-words verify + on-device challenge fold: (N,) uint8
    verdicts (valid & challenge & decompression flags) — the
    transfer-thin verdict form (16 B -> 1 B per 130 B row)."""
    rows = vrf_verify_words_core(Yw, xYw, Gw, signG, rw, cw, sw)
    ok = challenge_ok_device(rows, gamma_bytes, c_bytes)
    return (ok & (valid != 0)).astype(jnp.uint8)


vrf_verify_fold_words_kernel = jax.jit(vrf_verify_fold_words_core)


@jax.jit
def gamma8_kernel(yG, signG):
    """[8]Gamma compressed, for batched beta derivation (proof_to_hash).
    Returns (N, 33) uint8: compressed [8]Gamma + ok flag."""
    n = yG.shape[1]
    one = F.one_like(yG)
    xG, okG = EJ.device_decompress(yG, signG)
    G8 = _double3((xG, yG, one, F.mul(xG, yG)))
    Zi = EJ.pow_inv(G8[2])
    comp = compress_device(F.mul(G8[0], Zi), F.mul(G8[1], Zi))
    rows = jnp.concatenate([comp, okG.astype(jnp.int32)[None, :]], axis=0)
    return rows.T.astype(jnp.uint8)


def gamma8_words_core(Gw, signG):
    """Packed-words form of gamma8_kernel (unpack on device)."""
    yG = F.limbs_from_words(Gw)
    one = F.one_like(yG)
    xG, okG = EJ.device_decompress(yG, signG)
    G8 = _double3((xG, yG, one, F.mul(xG, yG)))
    Zi = EJ.pow_inv(G8[2])
    comp = compress_device(F.mul(G8[0], Zi), F.mul(G8[1], Zi))
    rows = jnp.concatenate([comp, okG.astype(jnp.int32)[None, :]], axis=0)
    return rows.T.astype(jnp.uint8)


gamma8_words_kernel = jax.jit(gamma8_words_core)


def _prepare_betas_words(proofs):
    """Packed-words host parse of a gamma8 batch: ((Gw, signG), ok)."""
    pf_arr, pf_ok = EJ._bytes_rows(proofs, PROOF_LEN)
    signG = (pf_arr[:, 31] >> 7).astype(np.int32)
    okGc = EJ._y_canonical(pf_arr[:, :32])
    s_ok = EJ._scalar_lt_L(np.ascontiguousarray(pf_arr[:, 48:80]))
    g_clear = pf_arr[:, :32].copy()
    g_clear[:, 31] &= 0x7F
    return ((F.words_from_bytes_rows(g_clear), signG),
            pf_ok & okGc & s_ok)


# ---------------------------------------------------------------------------
# Host orchestration
# ---------------------------------------------------------------------------

def _r_rows(vks, alphas) -> np.ndarray:
    """Elligator2 input byte rows: r = SHA512(suite || 0x01 || vk ||
    alpha)[:32] with the top bit masked (vrf_ref._hash_to_curve:25-27)."""
    rows = bytearray()
    for vk, alpha in zip(vks, alphas):
        rows += hashlib.sha512(SUITE + b"\x01" + vk + alpha).digest()[:32]
    arr = np.frombuffer(bytes(rows), dtype=np.uint8).reshape(len(vks), 32)
    arr = arr.copy()
    arr[:, 31] &= 0x7F
    return arr


def _prepare_words(vks, alphas, proofs):
    """Packed-words host prep.

    Returns (kernel_args, parse_ok, gamma_ok, s_ok, pf_arr) with
    kernel_args = (Yw, signY, Gw, signG, rw, cw, sw) — uint32 word rows
    for vrf_verify_words_kernel."""
    vk_arr, vk_ok = EJ._bytes_rows(vks, 32)
    pf_arr, pf_ok = EJ._bytes_rows(proofs, PROOF_LEN)
    signY = (vk_arr[:, 31] >> 7).astype(np.int32)
    signG = (pf_arr[:, 31] >> 7).astype(np.int32)
    okYc = EJ._y_canonical(vk_arr)
    okGc = EJ._y_canonical(pf_arr[:, :32])
    s_rows = np.ascontiguousarray(pf_arr[:, 48:80])
    s_ok = EJ._scalar_lt_L(s_rows)
    gamma_ok = pf_ok & okGc
    parse_ok = vk_ok & okYc & gamma_ok & s_ok
    vk_clear = vk_arr.copy()
    vk_clear[:, 31] &= 0x7F
    g_clear = pf_arr[:, :32].copy()
    g_clear[:, 31] &= 0x7F
    c_rows = np.ascontiguousarray(pf_arr[:, 32:48])
    cw = np.ascontiguousarray(
        c_rows.reshape(-1, 4, 4).view(np.uint32)[:, :, 0].T)
    args = (F.words_from_bytes_rows(vk_clear), signY,
            F.words_from_bytes_rows(g_clear), signG,
            F.words_from_bytes_rows(_r_rows(vks, alphas)), cw,
            F.words_from_bytes_rows(s_rows))
    return args, parse_ok, gamma_ok, s_ok, pf_arr


def _submit(vks, alphas, proofs, m):
    """Parse + dispatch one padded batch; returns (device handle, masks,
    proof rows).  Does not block — callers may pipeline.  Y's affine x
    is resolved through the global point cache; unknown/bad keys fold
    into parse_ok."""
    from .precompute import GLOBAL_PRECOMPUTE_CACHE
    args, parse_ok, gamma_ok, s_ok, pf_arr = _prepare_words(vks, alphas,
                                                            proofs)
    Yw, _signY, Gw, signG, rw, cw, sw = args
    xa, _x128, _y128, known = GLOBAL_PRECOMPUTE_CACHE.assemble(list(vks))
    handle = vrf_verify_words_kernel(
        *(jnp.asarray(a) for a in (Yw, xa, Gw, signG, rw, cw, sw)))
    return handle, parse_ok & known, gamma_ok, s_ok, pf_arr


def _finish(handle, parse_ok, gamma_ok, s_ok, pf_arr, n):
    rows = np.asarray(handle)                        # ONE transfer
    okY = rows[:, 128].astype(bool)
    okG = rows[:, 129].astype(bool)
    oks: list[bool] = []
    betas: list = []
    for j in range(n):
        row = rows[j]
        # beta is total given a decodable proof (Gamma decodes, s < L) —
        # the decode_proof precondition of vrf_ref.proof_to_hash
        if gamma_ok[j] and s_ok[j] and okG[j]:
            betas.append(hashlib.sha512(
                SUITE + b"\x03" + row[96:128].tobytes()).digest())
        else:
            betas.append(None)
        if not (parse_ok[j] and okY[j] and okG[j]):
            oks.append(False)
            continue
        c_prime = hashlib.sha512(
            SUITE + b"\x02" + row[0:32].tobytes() + bytes(pf_arr[j, :32])
            + row[32:64].tobytes() + row[64:96].tobytes()).digest()[:16]
        oks.append(c_prime == bytes(pf_arr[j, 32:48]))
    return oks, betas


def batch_verify_vrf(vks, alphas, proofs,
                     pad_to: int | None = None) -> tuple[list, list]:
    """Batched VRF verify; returns (ok list[bool], beta list[bytes|None]).

    beta[j] is the VRF output hash (proof_to_hash) whenever the proof
    decodes — independent of overall verification success, matching
    vrf_ref.proof_to_hash's totality."""
    n = len(vks)
    if n == 0:
        return [], []
    m = pad_to if pad_to and pad_to >= n else n
    vks = list(vks) + [b"\x00" * 32] * (m - n)
    alphas = list(alphas) + [b""] * (m - n)
    proofs = list(proofs) + [b"\x00" * PROOF_LEN] * (m - n)
    handle, parse_ok, gamma_ok, s_ok, pf_arr = _submit(vks, alphas,
                                                       proofs, m)
    return _finish(handle, parse_ok, gamma_ok, s_ok, pf_arr, n)


def _prepare_betas(proofs):
    """Host-side parse of a gamma8 batch: ((yG, signG), decode_ok)."""
    pf_arr, pf_ok = EJ._bytes_rows(proofs, PROOF_LEN)
    yG, signG, okGc = EJ._decode_compressed(pf_arr[:, :32])
    s_ok = EJ._scalar_lt_L(np.ascontiguousarray(pf_arr[:, 48:80]))
    return (yG, signG.astype(np.int32)), pf_ok & okGc & s_ok


def _finish_betas(rows: np.ndarray, decode_ok, n: int) -> list:
    ok = rows[:, 32].astype(bool) & decode_ok
    return [hashlib.sha512(SUITE + b"\x03" + rows[j, :32].tobytes()).digest()
            if ok[j] else None
            for j in range(n)]


def batch_betas(proofs, pad_to: int | None = None) -> list:
    """Batched proof_to_hash: beta bytes per proof, None where the proof
    does not decode (vrf_ref.proof_to_hash raises there)."""
    n = len(proofs)
    if n == 0:
        return []
    m = pad_to if pad_to and pad_to >= n else n
    proofs = list(proofs) + [b"\x00" * PROOF_LEN] * (m - n)
    (Gw, signG), decode_ok = _prepare_betas_words(proofs)
    handle = gamma8_words_kernel(jnp.asarray(Gw), jnp.asarray(signG))
    return _finish_betas(np.asarray(handle), decode_ok, n)
