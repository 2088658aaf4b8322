"""Bit-exactness tests: JAX field/curve kernels vs the Python-int oracle."""
import hashlib
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")

# full 256-iteration ladder executions: ~minutes through XLA:CPU, so these
# live in the device partition (`pytest -m device`)
pytestmark = pytest.mark.device
import jax.numpy as jnp  # noqa: E402

from ouroboros_tpu.crypto import ed25519_ref  # noqa: E402
from ouroboros_tpu.crypto import edwards as ed  # noqa: E402
from ouroboros_tpu.crypto import field_jax as F  # noqa: E402
from ouroboros_tpu.crypto import ed25519_jax as EJ  # noqa: E402

rng = random.Random(1234)


def rand_fe(n):
    return [rng.randrange(ed.P) for _ in range(n)]


def test_pack_unpack_roundtrip():
    xs = rand_fe(16)
    assert F.unpack(F.pack(xs)) == [x % ed.P for x in xs]


def test_field_mul_matches_python():
    n = 32
    a, b = rand_fe(n), rand_fe(n)
    got = F.unpack(np.asarray(F.mul(jnp.asarray(F.pack(a)),
                                    jnp.asarray(F.pack(b)))))
    assert got == [(x * y) % ed.P for x, y in zip(a, b)]


def test_field_add_sub_match_python():
    n = 16
    a, b = rand_fe(n), rand_fe(n)
    ja, jb = jnp.asarray(F.pack(a)), jnp.asarray(F.pack(b))
    assert F.unpack(np.asarray(F.add(ja, jb))) == [(x + y) % ed.P
                                                  for x, y in zip(a, b)]
    assert F.unpack(np.asarray(F.sub(ja, jb))) == [(x - y) % ed.P
                                                  for x, y in zip(a, b)]


def test_field_mul_chain_stays_bounded():
    """Repeated squaring keeps limbs inside the int32 invariant (no drift)."""
    n = 4
    a = jnp.asarray(F.pack(rand_fe(n)))
    expect = F.unpack(np.asarray(a))
    for _ in range(50):
        a = F.mul(a, a)
        expect = [(x * x) % ed.P for x in expect]
    assert F.unpack(np.asarray(a)) == expect
    assert int(jnp.max(jnp.abs(a))) < (1 << 15)


def _pts_to_batch(pts):
    xs, ys = zip(*[ed.to_affine(p) for p in pts])
    ts = [x * y % ed.P for x, y in zip(xs, ys)]
    return (jnp.asarray(F.pack(list(xs))), jnp.asarray(F.pack(list(ys))),
            jnp.asarray(F.pack([1] * len(pts))), jnp.asarray(F.pack(ts)))


def test_point_add_double_match_python():
    n = 8
    ks = [rng.randrange(1, ed.L) for _ in range(n)]
    js = [rng.randrange(1, ed.L) for _ in range(n)]
    P1 = [ed.scalar_mult(k, ed.BASE) for k in ks]
    P2 = [ed.scalar_mult(j, ed.BASE) for j in js]
    b1, b2 = _pts_to_batch(P1), _pts_to_batch(P2)
    s = EJ.pt_add(b1, b2, n)
    d = EJ.pt_double(b1)
    sx, sy, sz, _ = [np.asarray(c) for c in s]
    dx, dy, dz, _ = [np.asarray(c) for c in d]
    zs = F.unpack(sz)
    zd = F.unpack(dz)
    for i in range(n):
        want_add = ed.to_affine(ed.pt_add(P1[i], P2[i]))
        want_dbl = ed.to_affine(ed.pt_double(P1[i]))
        got_add = (F.unpack(sx)[i] * pow(zs[i], ed.P - 2, ed.P) % ed.P,
                   F.unpack(sy)[i] * pow(zs[i], ed.P - 2, ed.P) % ed.P)
        got_dbl = (F.unpack(dx)[i] * pow(zd[i], ed.P - 2, ed.P) % ed.P,
                   F.unpack(dy)[i] * pow(zd[i], ed.P - 2, ed.P) % ed.P)
        assert got_add == want_add
        assert got_dbl == want_dbl


# slow: ~27s tracing this test's own ed25519 batch shape; valid +
# tampered ed25519 verdicts vs the reference are tier-1-gated by
# test_served_replay.py::test_mixed_verdict_equals_reference (cases
# ed25519-good and ed25519-bad-signature)
@pytest.mark.slow
def test_batch_verify_valid_and_tampered():
    n = 12
    vks, msgs, sigs = [], [], []
    for i in range(n):
        sk = hashlib.sha256(f"jax-{i}".encode()).digest()
        msg = f"header-{i}".encode() * (i + 1)
        vks.append(ed25519_ref.public_key(sk))
        msgs.append(msg)
        sigs.append(ed25519_ref.sign(sk, msg))
    # tamper a few
    bad_sig = bytearray(sigs[3]); bad_sig[40] ^= 1; sigs[3] = bytes(bad_sig)
    msgs[7] = msgs[7] + b"!"
    bad_vk = bytearray(vks[9]); bad_vk[5] ^= 1; vks[9] = bytes(bad_vk)
    sigs[11] = sigs[11][:32] + (ed.L + 5).to_bytes(32, "little")  # s >= L
    got = EJ.batch_verify(vks, msgs, sigs)
    want = [ed25519_ref.verify(vks[i], msgs[i], sigs[i]) for i in range(n)]
    assert got == want
    assert want == [True, True, True, False, True, True, True, False,
                    True, False, True, False]


# slow: ~26s tracing a second ed25519 bucket shape just for the padding
# probe; test_served_replay.py's replay and mixed batch already run
# padded buckets (10 reqs in a 16-lane bucket) with verdict parity in
# tier-1
@pytest.mark.slow
def test_batch_verify_padding_hits_same_result():
    sk = hashlib.sha256(b"pad").digest()
    vk = ed25519_ref.public_key(sk)
    sig = ed25519_ref.sign(sk, b"m")
    assert EJ.batch_verify([vk], [b"m"], [sig], pad_to=8) == [True]


# slow: ~55s tracing this test's own composite shape; the VRF+KES
# verify_mixed path (valid + corrupted, vs CpuRefBackend) is
# tier-1-gated at a shared shape by test_served_replay.py::
# test_mixed_verdict_equals_reference (the vrf-* and kes-* cases)
@pytest.mark.slow
def test_jax_backend_vrf_and_kes():
    from ouroboros_tpu.crypto.jax_backend import JaxBackend
    from ouroboros_tpu.crypto import CpuRefBackend, Ed25519Req, KesReq, VrfReq
    from ouroboros_tpu.crypto import kes, vrf_ref
    jb = JaxBackend(min_bucket=16)
    ref = CpuRefBackend()
    vrfs, kess = [], []
    for i in range(5):
        sk = hashlib.sha256(f"jb{i}".encode()).digest()
        msg = f"alpha-{i}".encode()
        x, _ = vrf_ref._secret_expand(sk)
        vk = ed.compress(ed.scalar_mult(x, ed.BASE))
        vrfs.append(VrfReq(vk, msg, vrf_ref.prove(sk, msg)))
        ksk = kes.KesSignKey(2, sk)
        kess.append(KesReq(2, ksk.verification_key, 0, msg,
                           ksk.sign(msg).to_bytes()))
    bad = bytearray(vrfs[2].proof); bad[60] ^= 1
    vrfs.append(VrfReq(vrfs[2].vk, vrfs[2].alpha, bytes(bad)))
    kess.append(KesReq(2, kess[0].vk, 3, kess[0].msg, kess[0].sig_bytes))
    assert jb.verify_vrf_batch(vrfs) == ref.verify_vrf_batch(vrfs) \
        == [True] * 5 + [False]
    assert jb.verify_kes_batch(kess) == ref.verify_kes_batch(kess) \
        == [True] * 5 + [False]


# slow: ~35s tracing this test's own vrf batch shape; beta correctness
# is tier-1-gated through test_served_replay.py::
# test_device_replay_state_hash_equals_reference (betas feed the nonce
# evolution)
@pytest.mark.slow
def test_vrf_jax_batch_parity_and_betas():
    """batch_verify_vrf + batch_betas vs the pure-Python oracle, incl.
    tampered gamma/c/s, wrong vk, wrong alpha, garbage proofs."""
    import hashlib

    from ouroboros_tpu.crypto import vrf_jax, vrf_ref

    sks = [hashlib.sha256(b"vk%d" % i).digest() for i in range(3)]
    vks = [vrf_ref.public_key(sk) for sk in sks]
    vs, als, pis = [], [], []
    for i in range(12):
        als.append(b"al-%d" % i)
        vs.append(vks[i % 3])
        pis.append(vrf_ref.prove(sks[i % 3], als[-1]))
    pis[1] = pis[1][:10] + bytes([pis[1][10] ^ 1]) + pis[1][11:]   # gamma
    pis[2] = pis[2][:40] + bytes([pis[2][40] ^ 1]) + pis[2][41:]   # c
    pis[3] = pis[3][:60] + bytes([pis[3][60] ^ 1]) + pis[3][61:]   # s
    vs[4] = b"\x00" * 32
    als[5] = b"other"
    pis[6] = b"\x01" * 80
    pis[7] = b"short"
    oks, betas = vrf_jax.batch_verify_vrf(vs, als, pis, pad_to=16)
    assert oks == [vrf_ref.verify(v, a, p)
                   for v, a, p in zip(vs, als, pis)]
    for j in range(12):
        try:
            want = vrf_ref.proof_to_hash(pis[j])
        except ValueError:
            want = None
        assert betas[j] == want
    assert vrf_jax.batch_betas(pis, pad_to=16) == betas


def test_beta_prefetch_cache_used_in_seq_pass():
    """TPraos prefetch_window fills the cache; sequential_checks then
    agrees with the uncached path."""
    import hashlib

    from ouroboros_tpu.crypto.backend import OpensslBackend, VrfBetaCache
    from ouroboros_tpu.crypto import vrf_ref

    cache = VrfBetaCache()
    sk = hashlib.sha256(b"c").digest()
    pi = vrf_ref.prove(sk, b"msg")
    cache.prefetch([pi, b"junk" * 20], OpensslBackend())
    assert cache.get(pi) == vrf_ref.proof_to_hash(pi)
    import pytest
    with pytest.raises(ValueError):
        cache.get(b"junk" * 20)
