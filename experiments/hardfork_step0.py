#!/usr/bin/env python
"""Step 0 of ISSUE 40, on the chip: what the backend's program rule makes
of a chain that starts in Byron and crosses into Shelley.

Forges the `sync-hardfork` chain (1,408 Byron blocks in the last slots of
Byron epoch 0, the fork at the boundary, 640 Shelley blocks; mainnet's
per-era parameters, 2 and 88 transactions a block) with this tree's
`tools/db_synth.py`, in a child that never touches JAX, then replays it
three times as `db_analyser --analysis validate --validate full` does
(windows of 256, `JaxBackend(use_pallas=False, autotune=False)`, key and
beta caches cleared before each) and writes one JSON line a replay to
`chiprun_out/hardfork_step0.jsonl`:

    secs                  the replay, open DB to state hash
    programs_built        `jax.monitoring` backend-compile events in it
                          (a cache load counts), and their seconds
    composites / folds    the keys `_window_composite` and `_fold_program`
                          were ASKED for, window by window (a window that
                          asked for none reads null), and the set built
    beta_host_computes    betas the host pass had to compute itself
                          (`beta_cache.host_computes`): 0 = the hand-off
                          two windows ahead held across the boundary
    state_hash, blocks, proofs

and a last line `drain`: a Byron window's 768 Ed25519 lanes through the
tile program, 20 times each way: the fold program of a window without a
composite (`_fold_program(0, 0, 0)`: one more program, 4 bytes back)
against reading the tile calls' running first-bad scalar as it is (no
program, 4 bytes back).

    chiprun --timeout 1800 -- python experiments/hardfork_step0.py

It reads the rule as the tree has it.  PERF.md section 6 (PR 40) has the
reading taken at PR 40's parent's rule (`_occasional_widths` with
`bool(v) == bool(nv)`, window 0 fixing the program), before the program
changed: a record, and a tool for any later change to that rule.
"""
import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

SYNTH = {"protocol": "cardano", "pools": 2, "f": "1/20",
         "epoch-length": 432000, "kes-depth": 6,
         "slots-per-kes-period": 129600, "k": 2160,
         "byron-epoch-length": 21600, "byron-keys": 7,
         "pbft-threshold": 0.22, "pbft-window": 2160}


def forge(out: str, args) -> float:
    t0 = time.perf_counter()
    argv = ["--out", out, "--blocks", str(args.blocks),
            "--byron-blocks", str(args.byron_blocks),
            "--byron-txs-per-block", str(args.byron_txs),
            "--txs-per-block", str(args.txs), "--seed", str(args.seed)]
    for k, v in {**SYNTH, "byron-epoch-length": args.byron_epoch}.items():
        argv += ["--" + k, str(v)]
    subprocess.run([sys.executable,
                    os.path.join(ROOT, "tools", "db_synth.py"), *argv],
                   check=True, stdout=subprocess.DEVNULL)
    return time.perf_counter() - t0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--blocks", type=int, default=2048)
    ap.add_argument("--byron-blocks", type=int, default=1408)
    ap.add_argument("--byron-epoch", type=int, default=21600)
    ap.add_argument("--byron-txs", type=int, default=2)
    ap.add_argument("--txs", type=int, default=88)
    ap.add_argument("--window", type=int, default=256)
    ap.add_argument("--min-bucket", type=int, default=128,
                    help="16 for a CPU rehearsal at tiny sizes")
    ap.add_argument("--seed", type=int, default=40)
    ap.add_argument("--replays", type=int, default=3)
    ap.add_argument("--out-dir", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args()

    out_dir = args.out_dir
    os.makedirs(out_dir, exist_ok=True)
    chain_dir = os.path.join(out_dir, "hardfork_step0_chain")
    subprocess.run(["rm", "-rf", chain_dir], check=True)
    forge_s = forge(chain_dir, args)

    import jax
    import numpy as np
    from harness import chain
    from ouroboros_tpu import observe
    from ouroboros_tpu.crypto.jax_backend import FOLD_SENT, JaxBackend

    events: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: events.append(secs)
        if ev.endswith("backend_compile_duration") else None)
    dev = JaxBackend(min_bucket=args.min_bucket, use_pallas=False,
                     autotune=False)
    tag = {"platform": dev.platform, "device_kind": dev.device_kind,
           "forge_s": round(forge_s, 2)}

    asked: dict = {"composites": [], "folds": []}
    for what, name in (("composites", "_window_composite"),
                       ("folds", "_fold_program")):
        inner = getattr(dev, name)

        def logged(*key, _inner=inner, _what=what):
            asked[_what].append(list(key[:3]))
            return _inner(*key)
        setattr(dev, name, logged)

    host_betas = observe.metrics.counter("beta_cache.host_computes")
    dba, ctx = chain.open_chain(chain_dir)
    rows = []
    with open(os.path.join(out_dir, "hardfork_step0.jsonl"), "w") as fh:
        def emit(row):
            rows.append(row)
            fh.write(json.dumps(row) + "\n")
            fh.flush()
            print(json.dumps(row), flush=True)

        for i in range(args.replays):
            chain.clear_caches()
            asked["composites"].clear()
            asked["folds"].clear()
            e0, b0 = len(events), host_betas.value
            t0 = time.perf_counter()
            res = chain.validate(dba, ctx, dev, "full", args.window, 4320)
            emit({"replay": i, **tag,
                  "secs": round(time.perf_counter() - t0, 3),
                  "programs_built": len(events) - e0,
                  "program_secs": round(sum(events[e0:]), 2),
                  "composites_asked": list(asked["composites"]),
                  "folds_asked": list(asked["folds"]),
                  "composites_built": sorted(k[:3] for k in dev._composites),
                  "folds_built": sorted(dev._folds),
                  "tile_programs": sorted(dev._ed_tile_programs),
                  "beta_host_computes": host_betas.value - b0,
                  "state_hash": res["state_hash"], "blocks": res["blocks"],
                  "proofs": res["proofs"],
                  "peak_bytes": (dev._devices[0].memory_stats() or {}).get(
                      "peak_bytes_in_use")})

        # a Byron window's drain, both ways, on the programs now built
        db, rules, decode, _cfg, _dir = ctx
        from ouroboros_tpu.consensus.batch import _seq_block_step
        st, reqs = rules.initial_state(), []
        for n, (_entry, raw) in enumerate(db.stream()):
            if n == args.window:
                break
            rs, st = _seq_block_step(rules.protocol, rules.ledger, st,
                                     decode(raw))
            reqs.extend(rs)
        ed_reqs = dev._split_mixed_device(reqs)[0]
        ne = dev._pad_ed_window(len(ed_reqs))
        run = dev._ed_tile_program(False, True)
        secs = {"fold_program": [], "scalar": []}
        for how in ("fold_program", "scalar") * 20:
            arrays, _ok = dev._pack_ed(ed_reqs, ne)
            tiles = dev._dev_tiles(arrays, ne)
            owns = dev._dev_tiles((np.arange(ne, dtype=np.int32)
                                   .reshape(1, -1),), ne)
            jax.block_until_ready((tiles, owns))
            t0 = time.perf_counter()
            bad = dev._dev_scalar(FOLD_SENT)
            for (own,), tile in zip(owns, tiles):
                bad = run(bad, own, *tile)
            if how == "fold_program":
                out = np.asarray(dev._fold_program(0, 0, 0)(
                    None, bad, np.zeros(0, np.int32),
                    np.zeros((0, 32), np.uint8), np.zeros((0, 16), np.uint8)))
            else:
                out = np.asarray(bad)
            secs[how].append(time.perf_counter() - t0)
            assert out.size in (1, 4)
        emit({"drain": {k: {"median_ms": round(1e3 * sorted(v)[len(v) // 2], 3),
                            "min_ms": round(1e3 * min(v), 3)}
                        for k, v in secs.items()},
              "ed_lanes": len(ed_reqs), "tiles": ne // dev.ed_tile, **tag})
    return 0


if __name__ == "__main__":
    sys.exit(main())
