"""Fast-partition coverage of the GF(2^255-19) limb core (field_jax) —
multiplication, canonicalisation and helpers, checked against
Python big-int arithmetic.  Tiny batches of plain jnp ops: milliseconds
on CPU, so the DEFAULT gate always exercises the arithmetic the ladder
kernels are built from (the full ladders live in the device partition)."""
import random

import pytest

jnp = pytest.importorskip("jax.numpy")
import numpy as np  # noqa: E402

from ouroboros_tpu.crypto import edwards as ed  # noqa: E402
from ouroboros_tpu.crypto import field_jax as F  # noqa: E402

rng = random.Random(99)
P = ed.P


def _vals(n):
    out = [0, 1, P - 1, P - 19, (1 << 255) - 20]
    out += [rng.randrange(P) for _ in range(n - len(out))]
    return out


N = 8
A = _vals(N)
B = list(reversed(_vals(N)))


class TestMulForms:  # one form since PR 44; the name keeps the ids
    def test_mul_matches_bigint(self):
        got = F.unpack(np.asarray(F.mul(jnp.asarray(F.pack(A)),
                                        jnp.asarray(F.pack(B)))))
        assert got == [a * b % P for a, b in zip(A, B)]

    def test_mul_chain_stays_in_bounds(self):
        """Repeated products keep limbs inside the carry3 invariant."""
        x = jnp.asarray(F.pack(A))
        for _ in range(5):
            x = F.mul(x, x)
        arr = np.asarray(x)
        assert int(arr.max()) < (1 << 14), int(arr.max())
        want = A
        for _ in range(5):
            want = [v * v % P for v in want]
        assert F.unpack(arr) == want


class TestAddSubCanon:
    def test_add_sub(self):
        a = jnp.asarray(F.pack(A))
        b = jnp.asarray(F.pack(B))
        assert F.unpack(np.asarray(F.add(a, b))) \
            == [(x + y) % P for x, y in zip(A, B)]
        assert F.unpack(np.asarray(F.sub(a, b))) \
            == [(x - y) % P for x, y in zip(A, B)]

    def test_canon_and_is_zero(self):
        a = jnp.asarray(F.pack(A))
        b = jnp.asarray(F.pack(A))
        diff = F.sub(a, b)
        assert list(np.asarray(F.is_zero(diff))) == [True] * N
        canon = np.asarray(F.canon(F.add(a, jnp.zeros_like(a))))
        # canonical: exact limbs of the value mod p
        for j, v in enumerate(A):
            assert F.limbs_to_int(canon[:, j]) == v % P

    def test_const_batch_and_one_like(self):
        c = np.asarray(F.const_batch(ed.D, N))
        assert all(F.limbs_to_int(c[:, j]) == ed.D for j in range(N))
        one = np.asarray(F.one_like(jnp.asarray(F.pack(A))))
        assert all(F.limbs_to_int(one[:, j]) == 1 for j in range(N))
