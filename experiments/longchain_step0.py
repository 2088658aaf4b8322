#!/usr/bin/env python
"""Step 0 of ISSUE 33, before any change to the pipeline or the
composite: what an 8-window replay (2048 blocks, 88 transactions a
block, 256-block windows) meets on the chip.

One JSON line a row (also appended to `chiprun_out/longchain_step0.jsonl`):

    forge       seconds `tools/db_synth.py` of the tree `--forge-tree`
                names takes for the chain (the parent's tree proves the
                VRF in Python; this tree's natively), with the derived
                KES period and, where the tree takes the argument, the
                genesis's 129600
    replay      one `analysis_validate(..., "full", window=256,
                snapshot_every=4320)` on `JaxBackend(use_pallas=False,
                autotune=False)`: its seconds, the `(ne, nv, nb, nk)` key
                of every window's composite in submission order, and the
                seconds the FIRST call of each new key took (trace,
                lowering, compile or cache load); three replays a chain,
                because the key of window 1 depends on a race (the
                producer packs it before the caller's `kes_put` for
                window 0, or after)
    compiles    `jax.monitoring` compile events of the process so far, by
                stage: count and seconds
    part        device seconds of the parts a padded composite would run
                on no real lanes: `gamma8_words_kernel` at 512 lanes
                and `check_block64_jit` at 128, ten calls each, fenced

    chiprun --timeout 3000 -- bash _scratch/step0.sh
"""
import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

OUT = os.path.join(REPO, "chiprun_out", "longchain_step0.jsonl")
SYNTH = ["--protocol", "shelley", "--pools", "2", "--f", "1/20",
         "--epoch-length", "432000", "--kes-depth", "6",
         "--txs-per-block", "88", "--blocks", "2048"]


def emit(row: dict) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def forge(tree: str, out: str, seed: int, kes_period) -> None:
    args = [sys.executable, os.path.join(tree, "tools", "db_synth.py"),
            "--out", out, "--seed", str(seed), *SYNTH]
    if kes_period:
        args += ["--slots-per-kes-period", str(kes_period)]
    t = time.perf_counter()
    p = subprocess.run(args, capture_output=True, text=True)
    emit({"row": "forge", "tree": os.path.relpath(tree, REPO) or ".",
          "kes_period": kes_period or "derived", "rc": p.returncode,
          "secs": round(time.perf_counter() - t, 2),
          "stdout": p.stdout.strip()[-200:],
          "stderr": p.stderr.strip()[-200:] if p.returncode else ""})


def replays(chains: list, n: int, window: int) -> None:
    import jax
    from harness import chain as ch
    from ouroboros_tpu.crypto.jax_backend import JaxBackend

    events: dict = {}

    def on_event(event, secs, **kw):
        if event.startswith("/jax/core/compile/"):
            e = events.setdefault(event.rsplit("/", 1)[1], [0, 0.0])
            e[0] += 1
            e[1] += secs
    jax.monitoring.register_event_duration_secs_listener(on_event)

    def compiles(after: str) -> None:
        emit({"row": "compiles", "after": after,
              "events": {k: [c, round(s, 2)]
                         for k, (c, s) in events.items()}})
    backend = JaxBackend(use_pallas=False, autotune=False)
    emit({"row": "backend", "platform": backend.platform,
          "kind": backend.device_kind,
          "cache": os.environ.get("JAX_COMPILATION_CACHE_DIR", "unset")})
    keys: list = []
    first_call: dict = {}
    inner = backend._window_composite

    def logged(ne, nv, nb, nk, pallas):
        key = (ne, nv, nb, nk)
        keys.append(key)
        fn = inner(ne, nv, nb, nk, pallas)
        if key in first_call:
            return fn

        def timed(*a):
            t = time.perf_counter()
            out = fn(*a)
            first_call[key] = round(time.perf_counter() - t, 2)
            return out
        first_call[key] = None
        return timed
    backend._window_composite = logged
    for name, chain_dir in chains:
        dba, ctx = ch.open_chain(chain_dir)
        for i in range(n):
            ch.clear_caches()
            del keys[:]
            before = dict(first_call)
            t = time.perf_counter()
            res = ch.validate(dba, ctx, backend, "full", window, 4320)
            emit({"row": "replay", "chain": name, "i": i,
                  "secs": round(time.perf_counter() - t, 2),
                  "blocks": res["blocks"], "proofs": res["proofs"],
                  "keys": list(keys),
                  "first_call_secs": {str(k): v for k, v in
                                      first_call.items()
                                      if k not in before}})
        compiles(name)
    parts(jax)
    compiles("parts")


def parts(jax) -> None:
    import numpy as np
    from ouroboros_tpu.crypto import blake2b_jax as B2
    from ouroboros_tpu.crypto import vrf_jax

    def bench(name, fn, *args):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        first = time.perf_counter() - t
        secs = []
        for _ in range(10):
            t = time.perf_counter()
            jax.block_until_ready(fn(*args))
            secs.append(time.perf_counter() - t)
        emit({"row": "part", "what": name, "first_call_secs": round(first, 2),
              "min_ms": round(min(secs) * 1e3, 3),
              "median_ms": round(sorted(secs)[5] * 1e3, 3)})

    (Gw, signG), _ok = vrf_jax._prepare_betas_words([b"\x00" * 80] * 512)
    bench("gamma8_words_kernel(512)", vrf_jax.gamma8_words_kernel,
          jax.numpy.asarray(Gw), jax.numpy.asarray(signG))
    bench("check_block64_jit(128)", B2.check_block64_jit,
          jax.numpy.asarray(np.zeros((16, 128), np.uint32)),
          jax.numpy.asarray(np.zeros((8, 128), np.uint32)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--forge-tree", default=None,
                    help="forge with this tree's db_synth and stop")
    ap.add_argument("--out", default=None, help="where the forge writes")
    ap.add_argument("--kes-period", type=int, default=0)
    ap.add_argument("--seed", type=int, default=3300000001)
    ap.add_argument("--chains", default=None,
                    help="name=dir,name=dir: replay these, forging nothing")
    ap.add_argument("--replays", type=int, default=3)
    ap.add_argument("--window", type=int, default=256)
    a = ap.parse_args()
    if a.forge_tree:
        forge(os.path.abspath(a.forge_tree), a.out, a.seed, a.kes_period)
        return
    replays([c.split("=") for c in a.chains.split(",")], a.replays,
            a.window)


if __name__ == "__main__":
    main()
