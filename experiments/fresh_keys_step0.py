#!/usr/bin/env python
"""Step 0 of ISSUE 31, on the chip: what the per-key table path
(`PrecomputeCache.assemble`, crypto/precompute.py) costs a window in
which every Ed25519 lane's key is new, and what the same keys cost as
hits.

`--keys` distinct valid public keys a round (90,624 = the lanes of one
full 256-block window), made from a running index; one JSON line a round
(the file also lands in `chiprun_out/fresh_keys_step0.jsonl`):

    cold    the first round: every program the misses need compiles here
    miss    `--reps` more rounds, every key new again: host seconds of
            the one `assemble` call, its counters, and (last round, under
            the profiler) the device-busy seconds and the top operations
    hit     the last round's keys again

It calls nothing but `assemble`, so the same file reads the parent's path
and the change's.

    chiprun --timeout 1500 -- python experiments/fresh_keys_step0.py

Off the chip: `JAX_PLATFORMS=cpu python experiments/fresh_keys_step0.py
--rehearse` (300 keys; its numbers are XLA:CPU's and mean nothing).
"""
import argparse
import hashlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

OUT = os.path.join(REPO, "chiprun_out", "fresh_keys_step0.jsonl")


def emit(row: dict) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def keys(round_no: int, n: int) -> list:
    from ouroboros_tpu.crypto import ed25519_ref
    return [ed25519_ref.public_key(
        hashlib.sha256(b"step0-%d-%d" % (round_no, i)).digest())
        for i in range(n)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=90624)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        args.keys = 300

    import jax
    from ed_width_sweep import profile_once
    from harness import trace as trace_mod
    from ouroboros_tpu import compile_cache
    from ouroboros_tpu.crypto import edwards as ed
    from ouroboros_tpu.crypto.precompute import PrecomputeCache
    compile_cache.cache_dir()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        print("no TPU: add --rehearse off the chip", file=sys.stderr)
        return 2
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    open(OUT, "w").close()
    emit({"device": jax.devices()[0].device_kind, "platform": platform,
          "keys": args.keys,
          "cache": os.environ.get("JAX_COMPILATION_CACHE_DIR", "unset")})

    cache = PrecomputeCache()

    def one(kind: str, vks: list, profiled: bool = False) -> None:
        before = cache.stats()
        t = time.perf_counter()
        busy = top = None
        if profiled:
            busy, top = profile_once(lambda: cache.assemble(vks),
                                     trace_mod, args.rehearse)
            out = cache.assemble(vks[:1])   # the arrays' shape only
        else:
            out = cache.assemble(vks)
        secs = time.perf_counter() - t
        xa, xw, yw, known = out
        j = 0                               # one lane against the integers
        A = ed.decompress(vks[j])
        want = ed.to_affine(ed.scalar_mult(1 << 128, A))
        got = tuple(int.from_bytes(np.ascontiguousarray(w[:, j]).tobytes(),
                                   "little") for w in (xw, yw))
        after = cache.stats()
        emit({"round": kind, "keys": len(vks),
              "assemble_s": None if profiled else round(secs, 4),
              "us_per_key": None if profiled
              else round(secs * 1e6 / len(vks), 3),
              "correct": bool(known.all()) and got == want,
              "device_busy_s": busy, "top_ops_s": top,
              **{k: after[k] - before[k]
                 for k in ("hits", "misses", "device_fills",
                           "filled_keys", "evictions")},
              "entries": after["entries"]})

    t = time.perf_counter()
    sets = [keys(r, args.keys) for r in range(args.reps + 2)]
    emit({"made_keys_s": round(time.perf_counter() - t, 2)})
    one("cold", sets[0])
    cache.clear()
    for r in range(args.reps):
        one("miss", sets[1 + r])
        cache.clear()
    one("miss-profiled", sets[-1], profiled=True)
    one("hit", sets[-1])
    return 0


if __name__ == "__main__":
    sys.exit(main())
