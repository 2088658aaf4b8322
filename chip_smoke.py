#!/usr/bin/env python
"""chip_smoke.py — the served replay path, once, on the chip.

The quickest proof that the program still starts on one TPU v5e: disk ->
CBOR decode -> host sequential pass -> pack -> device window composite ->
folded verdict -> snapshot, through the entry points a user calls
(`tools/db_synth.py` as a child, `tools/db_analyser.py`'s
`analysis_validate`), checked against the CPU reference.  It is NOT a
benchmark: the rates it prints are smoke readings.

    python chip_smoke.py              one chip; fails off a TPU
    python chip_smoke.py --mesh 4     ONLY the sharded replay over four
                                      chips and what it is compared with
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --blocks 16 --window 8
                                      every phase, tiny, on the CPU

Phases (one JSON line each, then the contract's last line):
  synth      db_synth child: a small chain (shelley, 2 pools, f=4/5,
             2 txs/block, 600-slot epochs, depth-10 KES), fixed seed
  reference  the DB through analysis_validate on a CPU backend
  device     the same DB through analysis_validate on the device backend,
             snapshots on: all valid, state hash = reference, windows
             submitted = window count
  tamper     one KES signature byte flipped in the decoded header of the
             last block: the folded device verdict stops the replay at
             the same block as the CPU backend
  warm       a second device replay in the same process: zero composite
             builds, zero compile spans, zero XLA compiles

One process per chip: this process touches JAX first and holds the chip;
db_synth imports no JAX (tests/test_chip_smoke.py checks), so it may run
as a child.  Every replay starts with the per-key and beta caches
cleared, as a user's one-replay process would, so every replay needs the
same window shapes and the second one must find them all compiled.

Any failed phase raises: there is no try/except around a phase.
"""
from __future__ import annotations

import argparse
import io
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# Budget (ISSUE 22 step 4): the contract gives this script 1200 s,
# compilation included, and a cold process compiles everything.  The
# bill grows with the number of distinct window SHAPES, not with blocks,
# so the chain is two whole windows: one composite, one fold, the tile
# program (ROADMAP C10 has what is left to decide here).
WINDOW = 1024
BLOCKS = 2 * WINDOW
SEED = "chip-smoke-22"
SYNTH = ("--protocol", "shelley", "--pools", "2", "--f", "4/5",
         "--txs-per-block", "2", "--epoch-length", "600",
         "--kes-depth", "10")
# rehearsal 1 runs at the shapes tests/test_served_replay.py compiles
# (depth-4 KES, empty bodies, min_bucket 16): a new composite
# shape costs minutes of XLA:CPU compile
REHEARSE_SYNTH = ("--protocol", "shelley", "--pools", "2", "--f", "4/5",
                  "--txs-per-block", "0", "--epoch-length", "500",
                  "--kes-depth", "4")

_T0 = time.perf_counter()
_JAX_EVENTS: list = []      # (event, fun_name, secs) from jax.monitoring


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def require(ok, what: str) -> None:
    """A phase's check: stops the run (non-zero exit, no result line)."""
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def _on_jax_event(event: str, secs: float, **kw) -> None:
    if event.startswith("/jax/core/compile/"):
        _JAX_EVENTS.append((event.rsplit("/", 1)[1],
                            kw.get("fun_name", "?"), secs))


def compile_report(since: int) -> dict:
    """XLA's own account of the programs built since event `since`: how
    many were compiled, and lower + compile seconds of each that took a
    second or more, by function name.  (Trace seconds are left to the
    repo's compile spans, which are wall-clock: JAX reports a nested
    trace once per level.)"""
    per: dict = {}
    n = 0
    for stage, name, secs in _JAX_EVENTS[since:]:
        if stage == "jaxpr_trace_duration":
            continue
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]       # the compile stage wraps the name
        per[name] = per.get(name, 0.0) + secs
        n += stage == "backend_compile_duration"
    return {"programs_compiled": n,
            "lower_and_compile_secs": round(sum(per.values()), 1),
            "programs_over_1s": {k: round(v, 1)
                                 for k, v in per.items() if v >= 1.0}}


def compile_spans(observe) -> list:
    """The repo's own compile spans recorded since the last drain."""
    return [(sp.name, round(sp.duration, 1))
            for root in observe.spans.RECORDER.drain()
            for sp in root.walk() if sp.cat == "compile"]


def counter(observe, name: str) -> int:
    return observe.metrics.REGISTRY.get(name).value


def clear_caches() -> None:
    """Start a replay as a fresh process would: no betas, no per-key
    tables, no KES hash-path outcomes.  The compiled programs stay."""
    from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE
    from ouroboros_tpu.crypto.precompute import GLOBAL_PRECOMPUTE_CACHE
    GLOBAL_BETA_CACHE.clear()
    GLOBAL_PRECOMPUTE_CACHE.clear()


def validate(dba, ctx, backend, window: int, snapshot_every: int) -> dict:
    """One `db_analyser --analysis validate --validate full` run, as
    main() there drives it; returns its JSON line."""
    db, rules, decode, cfg, chain = ctx
    out = io.StringIO()
    dba.analysis_validate(
        db, rules, decode, backend, "full", window, out,
        hdr_proofs=dba.HEADER_PROOFS[cfg["protocol"]], db_dir=chain,
        snapshot_every=snapshot_every)
    return json.loads(out.getvalue())


def tamper(blocks: list, ix: int) -> list:
    """Flip one bit of block ix's KES signature in the DECODED header
    (a byte flipped on disk may be caught by a CRC or the decoder, which
    proves nothing about the device)."""
    from ouroboros_tpu.consensus.headers import ProtocolBlock
    from ouroboros_tpu.eras.shelley import KES_FIELD
    blk = blocks[ix]
    sig = bytearray(blk.header.get(KES_FIELD))
    sig[3] ^= 1
    out = list(blocks)
    out[ix] = ProtocolBlock(
        blk.header.with_fields(**{KES_FIELD: bytes(sig)}), blk.body)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="run ONLY the sharded replay over N chips and "
                         "the CPU reference it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a run off the TPU (rehearsals 1 and 2); "
                         "the last line then names the platform it "
                         "really ran on")
    ap.add_argument("--blocks", type=int, default=BLOCKS,
                    help="rehearsal only: a whole number of windows")
    ap.add_argument("--window", type=int, default=WINDOW,
                    help="rehearsal only")
    args = ap.parse_args()

    # -- JAX first: this process takes the chip, or stops here ---------
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: JAX reports platform "
              f"{device['platform']!r}, not 'tpu'; nothing was run "
              f"(--rehearse allows a CPU rehearsal)", file=sys.stderr)
        return 2
    if on_tpu and (args.blocks, args.window) != (BLOCKS, WINDOW):
        raise SystemExit("--blocks/--window are for the CPU rehearsal")
    n_windows, rem = divmod(args.blocks, args.window)
    if rem or n_windows < 2:
        raise SystemExit("--blocks must be >= 2 whole windows")
    if args.mesh and len(devs) < args.mesh:
        raise SystemExit(f"--mesh {args.mesh}: JAX reports {len(devs)} "
                         f"device(s)")
    jax.monitoring.register_event_duration_secs_listener(_on_jax_event)

    from ouroboros_tpu import observe
    from ouroboros_tpu.compile_cache import ENV_VAR, cache_dir
    from ouroboros_tpu.consensus.batch import replay_blocks_pipelined
    from ouroboros_tpu.crypto.jax_backend import JaxBackend
    from tools import db_analyser as dba
    observe.enable()
    emit(phase="start", device=device, rehearse=args.rehearse,
         blocks=args.blocks, window=args.window, windows=n_windows,
         mesh=args.mesh, cache_dir=cache_dir(),
         cache_dir_from_env=bool(os.environ.get(ENV_VAR)),
         jax=jax.__version__, host_cpus=os.cpu_count())

    # removed at the end, or by its finalizer if a phase fails
    tmpdir = tempfile.TemporaryDirectory(prefix="chip-smoke-")
    tmp = tmpdir.name
    # -- synth: a CHILD, which is safe only because db_synth has no JAX -
    t = time.perf_counter()
    chain = os.path.join(tmp, "chain")
    subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", chain, "--blocks", str(args.blocks), "--seed", SEED,
         *(REHEARSE_SYNTH if args.rehearse else SYNTH)],
        check=True, stdout=subprocess.DEVNULL)
    emit(phase="synth", secs=round(time.perf_counter() - t, 1),
         blocks=args.blocks, seed=SEED)

    db, rules, decode, cfg = dba.load_db(chain)
    ctx = (db, rules, decode, cfg, chain)
    # ~5/4 slots per block at f = 4/5: about one snapshot per window
    snapshot_every = args.window * 5 // 4

    # -- reference: the CPU backend's state hash -------------------------
    t = time.perf_counter()
    cpu_name = "cpp" if shutil.which("g++") else "openssl"
    cpu = dba.make_backend(cpu_name)
    clear_caches()
    ref = validate(dba, ctx, cpu, args.window, 0)
    emit(phase="reference", secs=round(time.perf_counter() - t, 1),
         backend=cpu_name, blocks=ref["blocks"], proofs=ref["proofs"],
         state_hash=ref["state_hash"])
    require(ref["blocks"] == args.blocks, "reference block count")

    # -- the device backend ----------------------------------------------
    if args.mesh:
        from ouroboros_tpu.parallel import ShardedJaxBackend, make_mesh
        mesh = make_mesh(args.mesh)
        jb = (ShardedJaxBackend(mesh, min_bucket=16) if args.rehearse
              else ShardedJaxBackend(mesh))
    elif args.rehearse:
        jb = JaxBackend(min_bucket=16)
    else:
        jb = dba.make_backend("jax")        # what `--backend jax` builds
    emit(phase="backend", name=jb.name, platform=jb.platform,
         device_kind=jb.device_kind, device_count=jb.device_count)
    require(jb.platform == device["platform"],
            "the backend took another platform than JAX reports")

    # -- device replay (cold: pays every compile) ------------------------
    observe.spans.RECORDER.drain()
    ev0, w0, l0 = (len(_JAX_EVENTS),
                   counter(observe, "jax_backend.windows_submitted"),
                   counter(observe, "jax_backend.lanes_used"))
    clear_caches()
    t = time.perf_counter()
    dev = validate(dba, ctx, jb, args.window, snapshot_every)
    secs = time.perf_counter() - t
    windows = counter(observe, "jax_backend.windows_submitted") - w0
    emit(phase="device", secs=round(secs, 1), blocks=dev["blocks"],
         proofs=dev["proofs"],
         lanes_used=counter(observe, "jax_backend.lanes_used") - l0,
         windows_submitted=windows, state_hash=dev["state_hash"],
         snapshots_written=dev["stream"]["snapshots_written"],
         line={k: dev[k] for k in ("backend_name", "platform",
                                   "device_kind", "device_count")},
         compile_spans=compile_spans(observe),
         xla=compile_report(ev0))
    require(dev["state_hash"] == ref["state_hash"],
            "device state hash differs from the reference")
    require(dev["blocks"] == args.blocks
            and dev["proofs"] == ref["proofs"], "block/proof counts")
    require(windows == n_windows,
            f"{windows} windows submitted, chain has {n_windows}")
    require(dev["stream"]["snapshots_written"] >= 1, "no snapshot")
    require(dev["platform"] == device["platform"],
            "db_analyser's line names another platform")

    if args.mesh:
        # evidence that every chip of the mesh worked, not only the
        # first: the lane sharding spreads a batch over N devices, and
        # (where the platform reports it) each device allocated memory
        import numpy as np
        probe = jb._dev(np.zeros((8, 16 * args.mesh), np.uint32))
        shard_devs = sorted(s.device.id for s in probe.addressable_shards)
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in mesh.devices.flat]
        emit(phase="mesh", shard_devices=shard_devs,
             peak_bytes_per_device=peaks,
             padding=jb.padding_stats(),
             smoke_reading_not_a_benchmark={
                 "blocks_per_sec_incl_compile":
                     round(args.blocks / secs, 1)})
        require(len(set(shard_devs)) == args.mesh,
                f"lanes sharded over {shard_devs}")
        require(not on_tpu or all(p and p > 0 for p in peaks),
                f"a mesh device allocated nothing: {peaks}")
    else:
        # -- tamper: the folded device verdict stops the replay ---------
        t = time.perf_counter()
        blocks = [decode(raw) for _entry, raw in db.stream()]
        # the LAST block: a tampered header has a new hash, so any
        # successor fails the host's prev-hash check first and cuts the
        # window short — a new window shape, minutes of compile
        bad_ix = args.blocks - 1
        bad = tamper(blocks, bad_ix)
        f0 = counter(observe, "jax_backend.fold_windows")
        stops = {}
        for name, backend in (("device", jb), (cpu_name, cpu)):
            clear_caches()
            res = replay_blocks_pipelined(
                rules, bad, rules.initial_state(), backend=backend,
                window=args.window)
            stops[name] = {"n_valid": res.n_valid,
                           "error": type(res.error).__name__}
        folds = counter(observe, "jax_backend.fold_windows") - f0
        emit(phase="tamper", secs=round(time.perf_counter() - t, 1),
             tampered_block=bad_ix, stopped_at=stops,
             device_fold_windows=folds,
             compile_spans=compile_spans(observe))
        require(stops["device"] == stops[cpu_name]
                and stops["device"]["n_valid"] == bad_ix,
                f"tampered chain stopped at {stops}")
        require(folds == n_windows, "the verdict was not device-folded")
        del blocks, bad

        # -- warm second replay: everything is compiled ------------------
        ev1, b0 = (len(_JAX_EVENTS),
                   counter(observe, "jax_backend.composite_builds"))
        clear_caches()
        t = time.perf_counter()
        warm = validate(dba, ctx, jb, args.window, 0)
        secs = time.perf_counter() - t
        builds = counter(observe, "jax_backend.composite_builds") - b0
        spans = compile_spans(observe)
        xla = compile_report(ev1)
        emit(phase="warm", secs=round(secs, 2),
             state_hash=warm["state_hash"], composite_builds=builds,
             compile_spans=spans, xla=xla,
             smoke_reading_not_a_benchmark={
                 "blocks_per_sec": round(args.blocks / secs, 1),
                 "proofs_per_sec": round(warm["proofs"] / secs, 1),
                 "host_seq_secs": warm["stream"]["host_seq_secs"],
                 "disk_secs": warm["stream"]["disk_secs"]})
        require(warm["state_hash"] == ref["state_hash"],
                "second replay's state hash")
        require(builds == 0 and not spans
                and xla["programs_compiled"] == 0,
                f"the second replay compiled: {builds} {spans} {xla}")

    stats = devs[0].memory_stats() or {}
    emit(phase="done", total_secs=round(time.perf_counter() - _T0, 1),
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"),
         xla_total=compile_report(0))
    tmpdir.cleanup()
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
