"""Device SHA-512 (crypto/sha512_jax.py) — bit-exactness vs hashlib.

The kernel exists for exactly one production call site: the ECVRF
challenge fold (`c == SHA512(suite || 0x02 || H || Gamma || U || V)[:16]`
over 130-byte preimages) inside the fused window program, so the fold's
verdicts can stay on device (jax_backend fold composites).  The oracle
tests still sweep message lengths across both padding-block boundaries —
a hash that is only right at 130 bytes is a latent bug.
"""
import hashlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ouroboros_tpu.crypto import sha512_jax as S  # noqa: E402


def _msgs(length, n=5):
    return [bytes((i * 31 + j * 7 + length) % 256 for j in range(length))
            for i in range(n)]


@pytest.mark.parametrize("length", [0, 1, 63, 64, 111, 112, 127, 128,
                                    130, 200])
def test_sha512_batch_matches_hashlib(length):
    msgs = _msgs(length)
    assert S.sha512_batch(msgs) == [hashlib.sha512(m).digest()
                                    for m in msgs]


def test_sha512_batch_distinguishes_rows():
    msgs = [b"A" * 130, b"A" * 129 + b"B", b"B" + b"A" * 129]
    got = S.sha512_batch(msgs)
    assert len(set(got)) == 3
    assert got == [hashlib.sha512(m).digest() for m in msgs]


def test_prefix16_eq_accepts_and_rejects():
    import jax.numpy as jnp
    msgs = _msgs(130, n=4)
    arr = jnp.asarray(np.frombuffer(b"".join(msgs),
                                    np.uint8).reshape(4, 130))
    cs = np.stack([np.frombuffer(hashlib.sha512(m).digest()[:16],
                                 np.uint8) for m in msgs]).copy()
    ok = np.asarray(S.prefix16_eq(arr, 130, jnp.asarray(cs)))
    assert ok.tolist() == [True] * 4
    # flip one byte in each 8-byte comparison half: both digest words
    # are actually compared, not just the first
    for byte in (0, 7, 8, 15):
        bad = cs.copy()
        bad[2, byte] ^= 1
        ok = np.asarray(S.prefix16_eq(arr, 130, jnp.asarray(bad)))
        assert ok.tolist() == [True, True, False, True], byte


@pytest.mark.slow
@pytest.mark.device
def test_challenge_ok_device_matches_host_verifier():
    """End-to-end VRF challenge fold vs the host _finish loop: the
    kernel's (N, 130) rows hashed on device must reproduce the host
    SHA-512 challenge verdict, including a tampered challenge.

    slow: compiles the full packed-words VRF verify kernel at a shape
    nothing else in the suite uses (~minutes of XLA:CPU).  The tier-1
    coverage of the same fold path is test_served_replay.py::
    test_fold_verdict_names_the_first_bad_request, which reuses the
    composite that file already compiles."""
    import jax.numpy as jnp

    from ouroboros_tpu.crypto import vrf_jax, vrf_ref
    sk = hashlib.sha256(b"sha-fold").digest()
    vk = vrf_ref.public_key(sk)
    alphas = [b"a%d" % i for i in range(4)]
    proofs = [vrf_ref.prove(sk, a) for a in alphas]
    bad = bytearray(proofs[1])
    bad[40] ^= 1                      # inside c: challenge mismatch
    proofs[1] = bytes(bad)
    args, parse_ok, gamma_ok, s_ok, pf_arr = vrf_jax._prepare_words(
        [vk] * 4, alphas, proofs)
    Yw, _sY, Gw, signG, rw, cw, sw = args
    from ouroboros_tpu.crypto.precompute import PrecomputeCache
    xa, _xs, _ys, known = PrecomputeCache().assemble([vk] * 4)
    rows = vrf_jax.vrf_verify_words_kernel(
        jnp.asarray(Yw), jnp.asarray(xa), jnp.asarray(Gw),
        jnp.asarray(signG), jnp.asarray(rw), jnp.asarray(cw),
        jnp.asarray(sw))
    host_ok, _betas = vrf_jax._finish(np.asarray(rows), parse_ok & known,
                                      gamma_ok, s_ok, pf_arr, 4)
    dev_ok = np.asarray(vrf_jax.challenge_ok_device(
        rows, jnp.asarray(np.ascontiguousarray(pf_arr[:, :32])),
        jnp.asarray(np.ascontiguousarray(pf_arr[:, 32:48]))))
    assert [bool(o) for o in dev_ok] == host_ok == [True, False, True,
                                                    True]
