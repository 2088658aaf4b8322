"""Round-5 kernel-path tests: split-128 ladder, packed-words I/O, device
Blake2b-256 KES hash path, and the A128 per-key cache.

Reference seams: Shelley/Protocol.hs:433-442 (per-header VRF+KES+Ed25519),
Shelley/Protocol/Crypto.hs:15-23 (Sum6KES(Ed25519, Blake2b_256)).  Oracles:
ed25519_ref / vrf_ref / hashlib / kes.verify (pure host Python).

The field-level pieces (sqr, cached adds, words pack/unpack, blake2b) are
fast and live in the default partition; the full 128-iteration ladder runs
are minutes through XLA:CPU and carry the `device` mark.
"""
import hashlib
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ouroboros_tpu.crypto import blake2b_jax as B2  # noqa: E402
from ouroboros_tpu.crypto import ed25519_jax as EJ  # noqa: E402
from ouroboros_tpu.crypto import ed25519_ref  # noqa: E402
from ouroboros_tpu.crypto import edwards as ed  # noqa: E402
from ouroboros_tpu.crypto import field_jax as F  # noqa: E402
from ouroboros_tpu.crypto import kes  # noqa: E402

rng = random.Random(555)


def _rand_fe(n):
    return [rng.randrange(ed.P) for _ in range(n)]


# ---------------------------------------------------------------------------
# fast partition: field/word/hash building blocks
# ---------------------------------------------------------------------------

def test_sqr_matches_python_both_forms():
    xs = _rand_fe(24) + [0, 1, ed.P - 1, 2**255 - 20]
    arr = jnp.asarray(F.pack(xs))
    got = F.unpack(np.asarray(F.sqr(arr)))
    assert got == [x * x % ed.P for x in xs]


def test_words_roundtrip_limbs():
    xs = _rand_fe(32)
    rows = np.frombuffer(
        b"".join(int(x).to_bytes(32, "little") for x in xs),
        dtype=np.uint8).reshape(-1, 32)
    w = F.words_from_bytes_rows(rows)
    assert w.shape == (8, 32) and w.dtype == np.uint32
    limbs = np.asarray(F.limbs_from_words(jnp.asarray(w)))
    assert F.unpack(limbs) == xs


def test_bit_from_words_matches_int_bits():
    xs = [rng.randrange(2**256) for _ in range(8)]
    rows = np.frombuffer(
        b"".join(int(x).to_bytes(32, "little") for x in xs),
        dtype=np.uint8).reshape(-1, 32)
    w = jnp.asarray(F.words_from_bytes_rows(rows))
    for j in (0, 1, 13, 127, 128, 200, 255):
        got = list(np.asarray(F.bit_from_words(w, j)))
        assert got == [(x >> j) & 1 for x in xs], j


def test_cached_add_matches_reference():
    n = 8
    ps = [ed.scalar_mult(rng.randrange(1, ed.L), ed.BASE) for _ in range(n)]
    qs = [ed.scalar_mult(rng.randrange(1, ed.L), ed.BASE) for _ in range(n)]

    def pack_pts(pts):
        aff = [ed.to_affine(p) for p in pts]
        x = jnp.asarray(F.pack([a[0] for a in aff]))
        y = jnp.asarray(F.pack([a[1] for a in aff]))
        return (x, y, F.one_like(x), F.mul(x, y))

    P, Q = pack_pts(ps), pack_pts(qs)
    R = EJ.pt_add_cached(P, EJ.to_cached(Q, n))
    Zi = EJ.pow_inv(R[2])
    gx = F.unpack(np.asarray(F.canon(F.mul(R[0], Zi))))
    gy = F.unpack(np.asarray(F.canon(F.mul(R[1], Zi))))
    for j in range(n):
        assert (gx[j], gy[j]) == ed.to_affine(ed.pt_add(ps[j], qs[j]))
    # identity and constant forms
    Ri = EJ.pt_add_cached(P, EJ.ident_cached(P[0]))
    Zi = EJ.pow_inv(Ri[2])
    assert F.unpack(np.asarray(F.canon(F.mul(Ri[0], Zi)))) == \
        [ed.to_affine(p)[0] for p in ps]
    cx, cy = ed.to_affine(qs[0])
    Rc = EJ.pt_add_cached(P, EJ.const_cached(cx, cy, n))
    Zi = EJ.pow_inv(Rc[2])
    assert F.unpack(np.asarray(F.canon(F.mul(Rc[0], Zi)))) == \
        [ed.to_affine(ed.pt_add(p, qs[0]))[0] for p in ps]


def test_blake2b_device_matches_hashlib():
    msgs = [bytes([rng.randrange(256) for _ in range(64)])
            for _ in range(33)]
    got = B2.blake2b_256_batch(msgs)
    assert got == [hashlib.blake2b(m, digest_size=32).digest()
                   for m in msgs]


def test_blake2b_check_kernel_flags_mismatch():
    msgs = [b"\x01" * 64, b"\x02" * 64, b"\x03" * 64]
    digs = [hashlib.blake2b(m, digest_size=32).digest() for m in msgs]
    digs[1] = digs[1][:10] + b"\x00" + digs[1][11:]
    arr = np.frombuffer(b"".join(msgs), dtype=np.uint8).reshape(-1, 64)
    exp = np.frombuffer(b"".join(digs), dtype=np.uint8).reshape(-1, 32)
    ok = np.asarray(B2.check_block64_jit(
        jnp.asarray(B2.msg_words(arr)), jnp.asarray(B2.digest_words(exp))))
    assert list(ok) == [1, 0, 1]


def test_kes_verify_walk_matches_verify():
    sk = kes.KesSignKey(3, hashlib.sha256(b"walk").digest())
    vk = sk.verification_key
    msg = b"hello"
    for period in range(6):
        sig = sk.sign(msg)
        walk = kes.verify_walk(3, vk, period, sig)
        assert walk is not None
        leaf_vk, leaf_sig, jobs = walk
        job_ok = all(hashlib.blake2b(m, digest_size=32).digest() == e
                     for m, e in jobs)
        ed_ok = ed25519_ref.verify(leaf_vk, msg, leaf_sig)
        assert (job_ok and ed_ok) == kes.verify(3, vk, period, msg, sig)
        sk.evolve()
    # structural rejects
    sig = sk.sign(msg)
    assert kes.verify_walk(3, vk, 8, sig) is None          # period range
    assert kes.verify_walk(2, vk, 0, sig) is None          # path length
    # wrong period -> hash jobs still pass but leaf differs; tampered
    # merkle -> some job fails
    bad = kes.KesSig(sig.leaf_sig,
                     ((b"\x00" * 32, b"\x00" * 32),) + sig.merkle[1:])
    walk = kes.verify_walk(3, vk, sk.period, bad)
    _lvk, _lsig, jobs = walk
    assert not all(hashlib.blake2b(m, digest_size=32).digest() == e
                   for m, e in jobs)


def test_y_canonical_mask():
    rows = np.zeros((5, 32), dtype=np.uint8)
    rows[0] = np.frombuffer((ed.P - 1).to_bytes(32, "little"), np.uint8)
    rows[1] = np.frombuffer(ed.P.to_bytes(32, "little"), np.uint8)
    rows[2] = np.frombuffer((ed.P + 18).to_bytes(32, "little"), np.uint8)
    # sign bit must be ignored
    v = (ed.P - 1) | (1 << 255)
    rows[3] = np.frombuffer(v.to_bytes(32, "little"), np.uint8)
    rows[4] = np.frombuffer((2**255 - 20).to_bytes(32, "little"), np.uint8)
    assert list(EJ._y_canonical(rows)) == [True, False, False, True, True]


# ---------------------------------------------------------------------------
# device partition: full ladder paths
# ---------------------------------------------------------------------------

@pytest.mark.device
# slow: ~26s tracing the split-words program at this test's own shape;
# bit-exactness of the packed-words cores stays covered nightly, and
# the end-to-end verdict path is tier-1-gated by test_served_replay.py::
# test_mixed_verdict_equals_reference
@pytest.mark.slow
def test_split_words_verify_bit_exact_vs_reference():
    n = 128
    keys = [hashlib.sha256(b"k%d" % (i % 5)).digest() for i in range(n)]
    vks = [ed25519_ref.public_key(k) for k in keys]
    msgs = [b"m%d" % i for i in range(n)]
    sigs = [ed25519_ref.sign(k, m) for k, m in zip(keys, msgs)]
    # corruptions: bad sig, bad vk bytes, swapped message
    sigs[3] = sigs[3][:63] + bytes([sigs[3][63] ^ 1])
    vks[5] = b"\xff" * 32
    msgs[9] = b"other"
    (Aw, _signA, Rw, signR, sw, kw), parse_ok = EJ.prepare_words_batch(
        vks, msgs, sigs)
    cache = EJ.A128Cache()
    xa, xw, yw, known = cache.assemble(vks)
    assert not known[5]                 # bad vk bytes -> not cacheable
    ok = np.asarray(EJ.verify_full_split_words_kernel(
        jnp.asarray(Aw), jnp.asarray(xa), jnp.asarray(xw),
        jnp.asarray(yw), jnp.asarray(Rw), jnp.asarray(signR),
        jnp.asarray(sw), jnp.asarray(kw)))
    got = [bool(o) and bool(p) and bool(k)
           for o, p, k in zip(ok, parse_ok, known)]
    want = [ed25519_ref.verify(vks[i], msgs[i], sigs[i]) for i in range(n)]
    assert got == want
    # second assemble hits the cache (no growth)
    before = len(cache)
    cache.assemble(vks)
    assert len(cache) == before


@pytest.mark.device
def test_a128_cache_entries_match_scalar_mult():
    vk = ed25519_ref.public_key(hashlib.sha256(b"a128").digest())
    cache = EJ.A128Cache()
    xa, xw, yw, known = cache.assemble([vk])
    assert known[0]
    A = ed.decompress(vk)
    wx, wy = ed.to_affine(ed.scalar_mult(1 << 128, A))
    got_xa = int.from_bytes(xa[:, 0].tobytes(), "little")
    got_x = int.from_bytes(xw[:, 0].tobytes(), "little")
    got_y = int.from_bytes(yw[:, 0].tobytes(), "little")
    assert (got_x, got_y) == (wx, wy)
    assert got_xa == ed.to_affine(A)[0]


@pytest.mark.device
@pytest.mark.slow
def test_jax_backend_mixed_window_with_kes_device_hashes():
    """JaxBackend (XLA path off-chip) verify_mixed over Ed25519 + VRF +
    KES requests matches the pure-host oracle, including KES signatures
    with tampered hash paths (caught by the device Blake2b batch, not
    host hashing).

    slow: ~75s of per-process composite tracing for this test's own
    window shape (no persistent cache avoids tracing — the PR 8
    discipline); tier-1 gates the same mixed cold-KES window with
    tampered hash paths in test_served_replay.py::
    test_mixed_verdict_equals_reference[kes-tampered-merkle-node]."""
    from ouroboros_tpu.crypto import vrf_ref
    from ouroboros_tpu.crypto.backend import (
        CpuRefBackend, Ed25519Req, KesReq, VrfReq,
    )
    from ouroboros_tpu.crypto.jax_backend import JaxBackend

    sk = hashlib.sha256(b"mix-ed").digest()
    vk = ed25519_ref.public_key(sk)
    vsk = hashlib.sha256(b"mix-vrf").digest()
    vvk = vrf_ref.public_key(vsk)
    ksk = kes.KesSignKey(2, hashlib.sha256(b"mix-kes").digest())
    kvk = ksk.verification_key

    reqs = []
    for i in range(3):
        m = b"e%d" % i
        reqs.append(Ed25519Req(vk, m, ed25519_ref.sign(sk, m)))
    reqs.append(Ed25519Req(vk, b"bad", ed25519_ref.sign(sk, b"good")))
    for i in range(2):
        a = b"v%d" % i
        reqs.append(VrfReq(vvk, a, vrf_ref.prove(vsk, a)))
    reqs.append(VrfReq(vvk, b"bad-alpha", vrf_ref.prove(vsk, b"va")))
    good_sig = ksk.sign(b"kmsg")
    reqs.append(KesReq(2, kvk, 0, b"kmsg", good_sig.to_bytes()))
    # tampered merkle node: ed leaf still fine, hash path must fail
    tam = kes.KesSig(good_sig.leaf_sig,
                     ((good_sig.merkle[0][0],
                       bytes(32)),) + good_sig.merkle[1:])
    reqs.append(KesReq(2, kvk, 0, b"kmsg", tam.to_bytes()))
    # wrong period
    reqs.append(KesReq(2, kvk, 1, b"kmsg", good_sig.to_bytes()))
    # structurally broken
    reqs.append(KesReq(2, kvk, 0, b"kmsg", b"\x00" * 7))

    jb = JaxBackend(use_pallas=False, autotune=False)
    got = jb.verify_mixed(reqs)
    want = CpuRefBackend().verify_mixed(reqs)
    assert got == want
    assert got[-4] is True and got[-3] is False and got[-2] is False \
        and got[-1] is False


@pytest.mark.device
def test_jax_backend_submit_finish_betas_roundtrip():
    from ouroboros_tpu.crypto import vrf_ref
    from ouroboros_tpu.crypto.jax_backend import JaxBackend
    vsk = hashlib.sha256(b"beta-seed").digest()
    proofs = [vrf_ref.prove(vsk, b"b%d" % i) for i in range(5)]
    proofs.append(b"\xff" * 80)          # undecodable
    jb = JaxBackend(use_pallas=False, autotune=False)
    sub = jb.submit_window([], next_beta_proofs=proofs)
    ok, betas = jb.finish_window(sub)
    assert ok == []
    for p in proofs[:5]:
        assert betas[p] == vrf_ref.proof_to_hash(p)
    assert betas[proofs[5]] is None
