#!/usr/bin/env python
"""Step 0 of ISSUE 32, host only: what decoding a chain's blocks costs
on the calling thread against W decode worker processes
(ouroboros_tpu/storage/decode_pool.py), on the host that drives the chip.

One JSON line a row (also appended to `chiprun_out/decode_workers_step0.jsonl`):

    in_thread   every chunk through `decode_blocks` on this thread, the
                collector's freeze after each chunk as a replay does it:
                wall seconds, and seconds of this thread's CPU
    workers     W = 2, 4, 8 (and what `worker_count()` gives here): a new
                pool, its start-up seconds (spawn, imports, the decoder
                loaded), then the prefetcher's loop (dispatch while a
                worker is idle, collect the oldest): wall seconds to the
                last block, seconds of this thread's CPU (pickling the
                requests, unpickling the replies: what stays under the
                replay's interpreter lock) and seconds waited for replies

The chain: `--chain DIR` (a `db_synth` DB), or forged here as the
benchmark's `sync-witness` cell forges it (`--seed`).  Touches no JAX:

    chiprun --timeout 600 -- python experiments/decode_workers_step0.py \\
        --chain _scratch/step0/chain
"""
import argparse
import gc
import json
import os
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

OUT = os.path.join(REPO, "chiprun_out", "decode_workers_step0.jsonl")


def emit(row: dict) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def forge(seed: int) -> str:
    """The chain of the benchmark's `sync-witness` cell for `seed`."""
    bench = os.path.join(REPO, "benchmarks")
    with open(os.path.join(bench, "configs", "shelley-sync-1chip.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "traffic", "bodies-full64k.json")) as f:
        traffic = json.load(f)
    out = tempfile.mkdtemp(prefix="step0-chain-")
    args = ["--out", out, "--blocks", str(cfg["blocks"]), "--seed", str(seed)]
    for k, v in {**cfg["synth"], **traffic["synth"]}.items():
        args += ["--" + k, str(v)]
    subprocess.run([sys.executable, os.path.join(REPO, "tools",
                                                 "db_synth.py"), *args],
                   check=True, stdout=subprocess.DEVNULL)
    return out


def in_thread(decode, chunks) -> dict:
    from ouroboros_tpu.storage.decode_pool import decode_blocks
    keep = []
    gc.collect()
    gc.freeze()
    t0, c0 = time.perf_counter(), time.thread_time()
    for raws in chunks:
        keep.append(decode_blocks(decode, raws))
        gc.freeze()
    wall, cpu = time.perf_counter() - t0, time.thread_time() - c0
    gc.unfreeze()
    return {"row": "in_thread", "wall_s": wall, "thread_cpu_s": cpu}


def with_workers(decode, chunks, w: int) -> dict:
    from ouroboros_tpu.storage import decode_pool
    cap, decode_pool.WORKER_CAP = decode_pool.WORKER_CAP, w
    pool = decode_pool.DecodePool()
    try:
        t0 = time.perf_counter()
        lease = pool.lease(decode)
        startup = time.perf_counter() - t0
        if lease is None:
            raise SystemExit("the decoder did not ship")
        started = len(pool.pids())
        wait0 = decode_pool._WORKER_WAIT_US.value
        keep = []
        gc.collect()
        gc.freeze()
        t0, c0 = time.perf_counter(), time.thread_time()
        for raws in chunks:
            while lease.blocks_in_flight and not lease.idle:
                keep.append(lease.collect(lambda: False))
                gc.freeze()
            lease.dispatch(raws)
        while lease.blocks_in_flight:
            keep.append(lease.collect(lambda: False))
            gc.freeze()
        wall, cpu = time.perf_counter() - t0, time.thread_time() - c0
        gc.unfreeze()
        lease.release()
        assert sum(map(len, keep)) == sum(map(len, chunks))
        return {"row": "workers", "asked": w, "started": started,
                "startup_s": startup, "wall_s": wall, "thread_cpu_s": cpu,
                "waited_s": (decode_pool._WORKER_WAIT_US.value - wait0)
                / 1e6}
    finally:
        pool.close()
        decode_pool.WORKER_CAP = cap


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chain", default=None)
    ap.add_argument("--seed", type=int, default=3200000001)
    ap.add_argument("--workers", default="2,4,8")
    ap.add_argument("--reps", type=int, default=2)
    args = ap.parse_args()
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    from ouroboros_tpu.storage import decode_pool
    from tools import db_analyser as dba
    chain = args.chain or forge(args.seed)
    db, _rules, decode, _cfg = dba.load_db(chain)
    chunks = [[raw for _e, raw in db.chunk_blocks(n)]
              for n in db.chunk_numbers()]
    chunks = [c for c in chunks if c]
    blocks = sum(map(len, chunks))
    emit({"row": "chain", "chain": chain, "blocks": blocks,
          "chunks": len(chunks), "bytes": sum(len(r) for c in chunks
                                              for r in c),
          "cores": len(os.sched_getaffinity(0)), "cpus": os.cpu_count(),
          "worker_count": decode_pool.worker_count(),
          "jax_imported": "jax" in sys.modules})
    ws = [int(w) for w in args.workers.split(",")]
    for rep in range(args.reps):
        for row in [in_thread(decode, chunks)] \
                + [with_workers(decode, chunks, w) for w in ws]:
            row.update(rep=rep, ms_per_block=1e3 * row["wall_s"] / blocks,
                       thread_cpu_ms_per_block=1e3 * row["thread_cpu_s"]
                       / blocks)
            emit(row)
    return 0


if __name__ == "__main__":
    sys.exit(main())
