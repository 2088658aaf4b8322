#!/usr/bin/env python
"""Step 0 of ISSUE 35, on the chip host: what each stage of the Ed25519
packer (`JaxBackend._prep_ed` -> `ed25519_jax.prepare_words_batch`,
the span `submit.pack_ed`) costs a window, stage by stage, and what the
challenge stage costs as the parent's per-lane Python loop, as today's
fallback loop and as the one native batch call.

A window's lanes as the cells ship them: `--lanes` real lanes a round
(90,624 = a full-body window, padded to 94,208; 23,040 = a quarter-body
window of `sync-longchain`, padded to 24,576), 352 (or 88) witness lanes
of a 32-byte txid, one OCert lane and one KES-leaf lane a block, two
witness keys and eight header keys, so the per-key tables hit as they do
in `sync-witness`.  R and s are random bytes (canonical, s < 2^252): the
packer never verifies, and signing 90,000 times in Python would take
minutes.  One JSON line a stage, best of `--reps` and the median (the
file also lands in `chiprun_out/pack_ed_step0.jsonl`):

    lists, bytes_rows, words, assemble (warm tables), dev (eight
    host-to-device copies), challenge.parent_loop, challenge.pure,
    challenge.native (with its own inner stages), prep_ed.native and
    prep_ed.pure (the whole call, which is what `submit.pack_ed` times),
    and `lock`: how far a second pure-Python thread gets while the
    challenge stage runs, against that thread alone (1.0 = the stage
    held no interpreter lock).

    chiprun --timeout 900 -- python experiments/pack_ed_step0.py

Off the chip: `JAX_PLATFORMS=cpu python experiments/pack_ed_step0.py`
(host stages mean what they mean on this host; `dev` is a memcpy).
"""
import argparse
import hashlib
import json
import os
import statistics
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

OUT = os.path.join(REPO, "chiprun_out", "pack_ed_step0.jsonl")


def emit(row: dict) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def timed(fn, reps: int):
    """(best ms, median ms, last result)."""
    ms = []
    for _ in range(reps):
        t = time.perf_counter()
        out = fn()
        ms.append((time.perf_counter() - t) * 1e3)
    return round(min(ms), 2), round(statistics.median(ms), 2), out


def window_reqs(lanes: int, seed: int) -> list:
    """`lanes` Ed25519 requests shaped like a window's: per block
    `lanes // 256 - 2` witnesses over a 32-byte txid, an OCert lane and
    a KES-leaf lane over a header body."""
    from ouroboros_tpu.crypto import ed25519_ref
    from ouroboros_tpu.crypto.backend import Ed25519Req
    rng = np.random.default_rng(seed)
    keys = [ed25519_ref.public_key(hashlib.sha256(b"step0-%d" % i).digest())
            for i in range(10)]
    raw = rng.integers(0, 256, size=(lanes, 96), dtype=np.uint8)
    raw[:, 31] &= 0x7F          # R: a canonical y, the sign bit clear
    raw[:, 63] &= 0x0F          # s < 2^252 < L
    per_block = lanes // 256
    reqs = []
    for j in range(lanes):
        row = raw[j].tobytes()
        slot = j % per_block
        if slot < per_block - 2:
            vk, msg = keys[slot & 1], row[64:]
        elif slot == per_block - 2:
            vk, msg = keys[2 + (j // per_block) % 4], row[64:] + row[:10]
        else:
            vk, msg = keys[6 + (j // per_block) % 4], row * 4
        reqs.append(Ed25519Req(vk, msg, row[:64]))
    return reqs


def parent_loop(sig_arr, vk_arr, msgs, parse_ok):
    """The challenge loop as the parent (PR 33) wrote it in
    `prepare_words_batch`, kept here as the thing step 0 sizes."""
    from ouroboros_tpu.crypto import edwards as ed
    k_bytes = bytearray()
    for j in range(len(msgs)):
        if parse_ok[j]:
            k = ed.sha512_int(bytes(sig_arr[j, :32]), bytes(vk_arr[j]),
                              msgs[j]) % ed.L
        else:
            k = 0
        k_bytes += k.to_bytes(32, "little")
    return np.frombuffer(bytes(k_bytes), dtype=np.uint8).reshape(-1, 32)


def spin_rate(stage) -> float:
    """Iterations a second a pure-Python thread makes while `stage` runs
    on this one."""
    stop = threading.Event()
    count = [0]

    def spin():
        n = 0
        while not stop.is_set():
            n += 1
        count[0] = n

    th = threading.Thread(target=spin)
    th.start()
    t = time.perf_counter()
    stage()
    secs = time.perf_counter() - t
    stop.set()
    th.join(timeout=10)
    return count[0] / secs


def one_size(jbk, lanes: int, reps: int, seed: int) -> None:
    from ouroboros_tpu.crypto import cpp_backend
    from ouroboros_tpu.crypto import ed25519_jax as EJ
    from ouroboros_tpu.crypto import field_jax as F
    reqs = window_reqs(lanes, seed)
    m = jbk._pad(lanes)
    pad = m - lanes
    tag = {"lanes": lanes, "padded": m}

    def lists():
        return ([r.vk for r in reqs] + [b"\x00" * 32] * pad,
                [r.msg for r in reqs] + [b""] * pad,
                [r.sig for r in reqs] + [b"\x00" * 64] * pad)

    best, med, (vks, msgs, sigs) = timed(lists, reps)
    emit({**tag, "stage": "lists", "best_ms": best, "median_ms": med})

    best, med, ((vk_arr, vk_ok), (sig_arr, sig_ok)) = timed(
        lambda: (EJ._bytes_rows(vks, 32), EJ._bytes_rows(sigs, 64)), reps)
    emit({**tag, "stage": "bytes_rows", "best_ms": best, "median_ms": med})

    r_rows = sig_arr[:, :32]
    k_any = np.zeros((m, 32), dtype=np.uint8)   # words() times, not reads

    def words():
        Aw, signA, a_ok = EJ._point_words(vk_arr)
        Rw, signR, r_ok = EJ._point_words(r_rows)
        s_rows = np.ascontiguousarray(sig_arr[:, 32:])
        s_ok = EJ._scalar_lt_L(s_rows)
        return (Aw, Rw, F.words_from_bytes_rows(s_rows),
                F.words_from_bytes_rows(k_any),
                vk_ok & sig_ok & a_ok & r_ok & s_ok)

    best, med, out = timed(words, reps)
    parse_ok = out[-1]
    emit({**tag, "stage": "words", "best_ms": best, "median_ms": med,
          "parse_ok": int(parse_ok.sum())})

    best, med, k_parent = timed(
        lambda: parent_loop(sig_arr, vk_arr, msgs, parse_ok), reps)
    emit({**tag, "stage": "challenge.parent_loop", "best_ms": best,
          "median_ms": med, "us_per_lane": round(best * 1e3 / m, 3)})
    best, med, k_pure = timed(
        lambda: EJ.challenge_rows_pure(r_rows, vk_arr, msgs, parse_ok), reps)
    emit({**tag, "stage": "challenge.pure", "best_ms": best,
          "median_ms": med, "us_per_lane": round(best * 1e3 / m, 3)})
    best, med, k_native = timed(
        lambda: cpp_backend.ed25519_challenge_rows(r_rows, vk_arr, msgs,
                                                   parse_ok), reps)
    inner = {
        "lens_ms": timed(lambda: np.fromiter(map(len, msgs), dtype=np.uint64,
                                             count=m), reps)[0],
        "join_ms": timed(lambda: b"".join(msgs), reps)[0],
        "r_copy_ms": timed(lambda: np.ascontiguousarray(r_rows), reps)[0]}
    emit({**tag, "stage": "challenge.native", "best_ms": best,
          "median_ms": med, "us_per_lane": round(best * 1e3 / m, 3),
          **inner,
          "equal": bool(np.array_equal(k_native, k_parent)
                        and np.array_equal(k_pure, k_parent))})

    jbk._prep_ed(reqs, m)                      # the ten keys' tables
    best, med, _ = timed(
        lambda: EJ.GLOBAL_A128_CACHE.assemble(vks), reps)
    emit({**tag, "stage": "assemble", "best_ms": best, "median_ms": med})

    host = list(out[:4]) + [np.zeros((1, m), np.int32)] * 2 \
        + [np.zeros((8, m), np.uint32)] * 2

    def dev():
        arrs = [jbk._dev(a) for a in host]
        for a in arrs:
            a.block_until_ready()

    best, med, _ = timed(dev, reps)
    emit({**tag, "stage": "dev", "best_ms": best, "median_ms": med,
          "note": "fenced: a replay's copies are not waited for"})

    def prep():
        args, _ok = jbk._prep_ed(reqs, m)
        return args

    best, med, a_nat = timed(prep, reps)
    emit({**tag, "stage": "prep_ed.native", "best_ms": best,
          "median_ms": med})
    real = cpp_backend.ed25519_challenge_rows
    cpp_backend.ed25519_challenge_rows = lambda *a: NotImplemented
    try:
        best, med, a_pure = timed(prep, reps)
    finally:
        cpp_backend.ed25519_challenge_rows = real
    emit({**tag, "stage": "prep_ed.pure", "best_ms": best, "median_ms": med,
          "equal": all(np.array_equal(np.asarray(x), np.asarray(y))
                       for x, y in zip(a_nat, a_pure))})

    def five(fn):
        return lambda: [fn(r_rows, vk_arr, msgs, parse_ok) for _ in range(5)]

    alone = spin_rate(lambda: time.sleep(0.3))
    emit({**tag, "stage": "lock",
          "native": round(spin_rate(
              five(cpp_backend.ed25519_challenge_rows)) / alone, 3),
          "pure": round(spin_rate(five(EJ.challenge_rows_pure)) / alone, 3)})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", type=int, nargs="*", default=[90624, 23040])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=3500000001)
    args = ap.parse_args()

    import jax
    from ouroboros_tpu.crypto import cpp_backend
    from ouroboros_tpu.crypto.jax_backend import JaxBackend
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    open(OUT, "w").close()
    t = time.perf_counter()
    lib = cpp_backend.shared_library()
    emit({"device": jax.devices()[0].device_kind,
          "platform": jax.devices()[0].platform, "host_cpus": os.cpu_count(),
          "library": type(lib).__name__,
          "library_load_s": round(time.perf_counter() - t, 2)})
    if lib is None:
        print("the native library did not build", file=sys.stderr)
        return 1
    jbk = JaxBackend(use_pallas=False, autotune=False)
    for lanes in args.lanes:
        one_size(jbk, lanes, args.reps, args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
