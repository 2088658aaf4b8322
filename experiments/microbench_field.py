#!/usr/bin/env python
"""Microbenchmark the crypto hot path on the real chip: device-only kernel
times vs host-prep times, plus per-field-op costs inside a jitted chain.

Run on the TPU machine:  python experiments/microbench_field.py [--ops]
"""
import argparse
import os
import statistics
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def timed(fn, reps=7, warm=2):
    for _ in range(warm):
        fn()
    vals = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        vals.append(time.perf_counter() - t0)
    vals.sort()
    return vals[len(vals) // 2], vals[0], vals[-1]


def report(name, med, lo, hi, per=None):
    extra = f"  ({per})" if per else ""
    print(f"{name:42s} med {med*1e3:8.1f}ms  min {lo*1e3:8.1f}  "
          f"max {hi*1e3:8.1f}{extra}", flush=True)


def bench_e2e():
    import hashlib

    import jax.numpy as jnp

    from ouroboros_tpu.crypto import ed25519_jax as EJ
    from ouroboros_tpu.crypto import ed25519_ref, kes, vrf_jax, vrf_ref
    from ouroboros_tpu.crypto.backend import KesReq

    n = 4096
    sk = hashlib.sha256(b"bench-ed").digest()
    vk = ed25519_ref.public_key(sk)
    msgs = [b"m%06d" % i for i in range(n)]
    sigs = [ed25519_ref.sign(sk, m) for m in msgs]
    vks = [vk] * n
    print("fixtures: ed ready", flush=True)

    # host prep
    med, lo, hi = timed(lambda: EJ.prepare_bytes_batch(vks, msgs, sigs))
    report(f"ed prep_bytes_batch n={n}", med, lo, hi)

    arrays, _ok = EJ.prepare_bytes_batch(vks, msgs, sigs)
    yA, signA, yR, signR, s_bits, k_bits = arrays

    # transfer cost: host->device of the same arrays
    def xfer():
        a = [jnp.asarray(x) for x in
             (yA, signA.reshape(1, -1), yR, signR.reshape(1, -1),
              s_bits, k_bits)]
        a[0].block_until_ready()
    med, lo, hi = timed(xfer)
    report(f"ed h2d transfer n={n}", med, lo, hi)

    # VRF (proof generation is pure-Python EC and slow: cache to disk)
    nv = 2048
    vsk = hashlib.sha256(b"bench-vrf").digest()
    vvk = vrf_ref.public_key(vsk)
    alphas = [b"a%d" % i for i in range(nv)]
    cache = os.path.join(tempfile.gettempdir(), f"ouro-vrf-proofs-{nv}.bin")
    if os.path.exists(cache):
        raw = open(cache, "rb").read()
        proofs = [raw[i * 80:(i + 1) * 80] for i in range(nv)]
    else:
        proofs = [vrf_ref.prove(vsk, a) for a in alphas]
        open(cache, "wb").write(b"".join(proofs))
    vvks = [vvk] * nv
    print("fixtures: vrf ready", flush=True)

    med, lo, hi = timed(
        lambda: vrf_jax._prepare_words(vvks, alphas, proofs))
    report(f"vrf _prepare_words n={nv}", med, lo, hi)

    def run_vrf():
        return np.asarray(vrf_jax._submit(vvks, alphas, proofs, nv)[0])
    med, lo, hi = timed(run_vrf)
    report(f"vrf prep + device n={nv}", med, lo, hi, per=f"{nv/med:.0f}/s")

    handle, parse_ok, gamma_ok, s_ok, pf_arr = vrf_jax._submit(
        vvks, alphas, proofs, nv)
    rows = np.asarray(handle)
    med, lo, hi = timed(lambda: vrf_jax._finish(rows, parse_ok, gamma_ok,
                                                s_ok, pf_arr, nv))
    report(f"vrf _finish n={nv}", med, lo, hi)

    # betas
    med, lo, hi = timed(lambda: vrf_jax._prepare_betas_words(proofs))
    report(f"beta _prepare_betas_words n={nv}", med, lo, hi)
    (Gw, signG), decode_ok = vrf_jax._prepare_betas_words(proofs)
    Gw, signG = jnp.asarray(Gw), jnp.asarray(signG)

    def run_beta():
        return np.asarray(vrf_jax.gamma8_words_kernel(Gw, signG))
    med, lo, hi = timed(run_beta)
    report(f"beta device n={nv}", med, lo, hi, per=f"{nv/med:.0f}/s")

    rows_b = run_beta()
    med, lo, hi = timed(lambda: vrf_jax._finish_betas(rows_b, decode_ok, nv))
    report(f"beta _finish n={nv}", med, lo, hi)

    # KES host hash path
    nk = 4096
    ksk = kes.KesSignKey(6, hashlib.sha256(b"bench-kes").digest())
    kreqs = [KesReq(6, ksk.verification_key, 0, b"m%d" % i,
                    ksk.sign(b"m%d" % i).to_bytes()) for i in range(nk)]
    from ouroboros_tpu.crypto.backend import CryptoBackend
    cb = CryptoBackend()
    med, lo, hi = timed(lambda: cb.split_mixed(kreqs))
    report(f"kes split_mixed (host hash path) n={nk}", med, lo, hi)


def bench_ops():
    """Per-op costs inside a jitted chain of K ops, difference two K
    values to cancel fixed overhead."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ouroboros_tpu.crypto import ed25519_jax as EJ
    from ouroboros_tpu.crypto import field_jax as F

    N = 4096
    rng = np.random.default_rng(0)
    a_np = rng.integers(0, 8191, size=(F.NLIMBS, N), dtype=np.int32)
    b_np = rng.integers(0, 8191, size=(F.NLIMBS, N), dtype=np.int32)

    def make_chain(op_name, k):
        def chain(a, b):
            def body(i, a):
                if op_name == "mul":
                    return F.mul(a, b)
                if op_name == "sqr":
                    return F.sqr(a)
                if op_name == "add":
                    return F.add(a, b)
                if op_name == "carry":
                    return F.carry_round(a)
                raise ValueError(op_name)
            return lax.fori_loop(0, k, body, a)
        return jax.jit(chain)

    def make_pt_chain(kind, k):
        """Chain of point ops: kind in dbl | addc (add with fixed point)."""
        def chain(x, y, z, t):
            P = (x, y, z, t)

            def body(i, Q):
                if kind == "dbl":
                    return EJ.pt_double(Q)
                return EJ.pt_add(Q, P, N)
            Q = lax.fori_loop(0, k, body, P)
            return Q[0] + Q[1] + Q[2] + Q[3]
        return jax.jit(chain)

    a = jnp.asarray(a_np)
    b = jnp.asarray(b_np)
    for op in ("mul", "sqr", "add", "carry"):
        k1, k2 = 64, 192
        f1, f2 = make_chain(op, k1), make_chain(op, k2)
        m1, _, _ = timed(lambda: np.asarray(f1(a, b)))
        m2, _, _ = timed(lambda: np.asarray(f2(a, b)))
        per = (m2 - m1) / (k2 - k1)
        print(f"field {op:6s}: {per*1e6:8.1f} us per batched op "
              f"(chain {k1}: {m1*1e3:.1f}ms, {k2}: {m2*1e3:.1f}ms)",
              flush=True)

    for kind in ("dbl", "addc"):
        k1, k2 = 32, 96
        f1, f2 = make_pt_chain(kind, k1), make_pt_chain(kind, k2)
        m1, _, _ = timed(lambda: np.asarray(f1(a, b, a, b)))
        m2, _, _ = timed(lambda: np.asarray(f2(a, b, a, b)))
        per = (m2 - m1) / (k2 - k1)
        print(f"point {kind:5s}: {per*1e6:8.1f} us per batched op "
              f"(chain {k1}: {m1*1e3:.1f}ms, {k2}: {m2*1e3:.1f}ms)",
              flush=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", action="store_true")
    ap.add_argument("--e2e", action="store_true")
    args = ap.parse_args()
    if not (args.ops or args.e2e):
        args.e2e = True
    if args.e2e:
        bench_e2e()
    if args.ops:
        bench_ops()
