#!/usr/bin/env python
"""Step 0 of ISSUE 30, on the chip: what an Ed25519 lane costs the XLA
split ladder by the width of the program, and whether T-lane tiles run
one after the other keep the narrow program's cost.

Two tables, one JSON line a row (the whole file also lands in
`chiprun_out/ed_width_sweep.jsonl`):

1. `verify_full_split_words_kernel` alone at each width of `--widths`,
   real signatures (two keys, every message distinct, one lane
   tampered), device microseconds a lane from the profiler's trace and
   the device operations that took most of it.
2. The same `--lanes` lanes as back-to-back calls of ONE program as wide
   as each of `--tiles` (the window path's form since PR 38,
   `JaxBackend._ed_tile_program`; PR 30 ran them as a loop inside one
   program).

Each program is compiled ahead of time, run `--reps` times under the
host clock and once more under the profiler, and its row written before
the next program compiles.

    chiprun --timeout 1800 -- python experiments/ed_width_sweep.py

Off the chip: `JAX_PLATFORMS=cpu python experiments/ed_width_sweep.py
--rehearse` (tiny widths; its numbers are XLA:CPU's and mean nothing).
"""
import argparse
import hashlib
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import numpy as np  # noqa: E402

WIDTHS = (2048, 4096, 8192, 16384, 32768, 65536, 131072)
TILES = (8192, 4096, 16384, 2048, 32768)
LANES = 98304
OUT = os.path.join(REPO, "chiprun_out", "ed_width_sweep.jsonl")


def emit(row: dict) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def fixtures(n: int, bad: int):
    """n real signatures by two keys, lane `bad` tampered; the packed
    host arrays of `JaxBackend._prep_ed` and the expected verdicts."""
    from ouroboros_tpu.crypto import ed25519_ref
    from ouroboros_tpu.crypto.backend import Ed25519Req
    sks = [hashlib.sha256(b"sweep-%d" % i).digest() for i in range(2)]
    vks = [ed25519_ref.public_key(sk) for sk in sks]
    reqs = []
    for i in range(n):
        msg = b"lane-%07d" % i
        sig = ed25519_ref.sign(sks[i & 1], msg)
        if i == bad:
            sig = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
        reqs.append(Ed25519Req(vks[i & 1], msg, sig))
    expect = np.ones(n, np.int32)
    expect[bad] = 0
    return reqs, expect


def profile_once(run, trace_mod, rehearse: bool):
    """Device-busy seconds and the top operations of one call of run()."""
    import jax
    d = tempfile.mkdtemp(prefix="edsweep-")
    try:
        with jax.profiler.trace(d):
            run()
        ops = trace_mod.device_ops(
            trace_mod.load(trace_mod.find_xplane(d)), rehearse=rehearse)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    evs = [e for dev in ops.values() for e in dev]
    if not evs:
        return None, []
    lo, hi = min(e[0] for e in evs), max(e[1] for e in evs)
    busy = trace_mod.busy_ns(trace_mod.union(evs, lo, hi)) * 1e-9
    top = [[n, round(s, 6)] for n, s in
           trace_mod.top(trace_mod.self_times(evs, lo, hi), k=6)]
    return busy, top


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--widths", type=int, nargs="*", default=list(WIDTHS))
    ap.add_argument("--tiles", type=int, nargs="*", default=list(TILES))
    ap.add_argument("--lanes", type=int, default=LANES)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        args.widths, args.tiles, args.lanes = [32, 64, 128], [32, 64], 128

    import jax
    from harness import trace as trace_mod
    from ouroboros_tpu import compile_cache
    from ouroboros_tpu.crypto import ed25519_jax as EJ
    from ouroboros_tpu.crypto import jax_backend as JB
    compile_cache.cache_dir()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        print("no TPU: add --rehearse off the chip", file=sys.stderr)
        return 2
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    open(OUT, "w").close()
    emit({"device": jax.devices()[0].device_kind, "platform": platform,
          "cache": os.environ.get("JAX_COMPILATION_CACHE_DIR", "unset")})

    n = max(args.widths + [args.lanes])
    t0 = time.perf_counter()
    reqs, expect = fixtures(n, bad=n // 3)
    jbk = JB.JaxBackend(min_bucket=16 if args.rehearse else 128,
                        use_pallas=False, autotune=False)
    full, parse_ok = jbk._prep_ed(reqs, n)
    assert parse_ok.all()
    emit({"fixtures_s": round(time.perf_counter() - t0, 2), "lanes": n})

    def head(w):
        return tuple(a[..., :w] for a in full)

    def flat(Aw, xa, xw, yw, Rw, sR2, sw, kw):
        return EJ.verify_full_split_words_core(Aw, xa, xw, yw, Rw,
                                               sR2[0], sw, kw)

    # the programs in the order their rows matter, each compiled, run and
    # written out before the next: a call that dies keeps what it had.
    # (Compiling four at once in pool threads died in the chip's compiler
    # of a stack overflow, my chip run, PR 30; `main` therefore runs on
    # a thread with a large stack, one compile at a time.)
    jobs = [("tiles", t) for t in args.tiles[:3]] \
        + [("width", w) for w in args.widths] \
        + [("tiles", t) for t in args.tiles[3:]]
    for kind, size in jobs:
        if kind == "tiles" and (args.lanes % size or args.lanes <= size):
            continue
        t0 = time.perf_counter()
        if kind == "width":
            a = head(size)
            comp = jax.jit(flat).lower(*a).compile()
        else:
            a = head(args.lanes)
            tile = jax.jit(flat).lower(*head(size)).compile()
            parts = [tuple(x[..., o:o + size] for x in a)
                     for o in range(0, args.lanes, size)]

            def comp(*_a, tile=tile, parts=parts):
                return jax.numpy.concatenate([tile(*p) for p in parts])
        secs = time.perf_counter() - t0
        lanes = a[0].shape[-1]

        def run():
            return jax.block_until_ready(comp(*a))
        ok = np.asarray(run())
        walls = []
        for _ in range(args.reps):
            t = time.perf_counter()
            run()
            walls.append(time.perf_counter() - t)
        busy, top = profile_once(run, trace_mod, args.rehearse)
        emit({"kind": kind, "size": size, "lanes": lanes,
              "tiles": lanes // size if kind == "tiles" else 1,
              "correct": bool((ok == expect[:lanes]).all()),
              "compile_s": round(secs, 1),
              "wall_ms_min": round(min(walls) * 1e3, 3),
              "wall_us_per_lane": round(min(walls) * 1e6 / lanes, 3),
              "device_ms": None if busy is None else round(busy * 1e3, 3),
              "device_us_per_lane": None if busy is None
              else round(busy * 1e6 / lanes, 3),
              "top_ops_s": top})
    return 0


if __name__ == "__main__":
    import threading
    threading.stack_size(1 << 30)
    rc = []
    th = threading.Thread(target=lambda: rc.append(main()))
    th.start()
    th.join()
    sys.exit(rc[0] if rc else 1)
