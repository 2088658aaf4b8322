#!/usr/bin/env python
"""Step 0 of ISSUE 38: a window's Ed25519 lanes at a width that changes
from window to window, by the two routes the issue names and by the
parent's form, on the chip.

One JSON line a row (also appended to `chiprun_out/mixedfill_step0.jsonl`).
Every route verifies the SAME packed lanes (`JaxBackend._pack_ed` of real
signatures, one tampered) and folds them to the first bad request index,
in windows of `--widths` tiles (1, 23, 1, 6, 23, 1 by default: a 1-tile
and a 23-tile window back to back, both ways), `--reps` rounds, the first
round apart (it pays every compile):

    tile_calls  the route that stayed: T asynchronous calls of the ONE
                tile program (`JaxBackend._ed_tile_program(False, True)`),
                the tiles' arrays in one `device_put`, the first-bad
                scalar handed from call to call
    trip_count  the route that went: ONE program over capacity-shaped
                buffers (`--capacity` tiles), `lax.fori_loop(0, n_tiles)`
                over `dynamic_slice` tiles with `n_tiles` a runtime
                scalar; every window ships the whole capacity
    static_map  (`--static`) the parent's form: `lax.map` over a STATIC
                tile count, one program a distinct count (what X10 is
                about; each count is a compile of minutes on the chip)

A row gives, for one window: `copy_ms` (host arrays to the device),
`dispatch_ms` (until the last call returned), `wall_ms` (until the
first-bad index is back on the host), `compiles` (`jax.monitoring`
backend compiles during it) and, in the first round, `first_s`.  A
`device` row a route and width gives the device-busy seconds of one
traced window (the benchmark's own trace reduction).

    chiprun --timeout 1500 -- python experiments/mixedfill_step0.py
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

OUT = os.path.join(REPO, "chiprun_out", "mixedfill_step0.jsonl")
SENT = 0x7FFFFFFF


def emit(row: dict) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def packed_lanes(jbk, lanes: int, bad: int):
    """(the eight (rows, lanes) host arrays, owners (1, lanes)) of
    `lanes` real signatures, 256 distinct ones repeated, lane `bad`
    tampered."""
    from ouroboros_tpu.crypto import ed25519_ref
    from ouroboros_tpu.crypto.backend import Ed25519Req
    sks = [hashlib.sha256(b"step0-%d" % i).digest() for i in range(2)]
    vks = [ed25519_ref.public_key(sk) for sk in sks]
    n = min(lanes, 256)
    reqs = [Ed25519Req(vks[i & 1], b"lane-%03d" % i,
                       ed25519_ref.sign(sks[i & 1], b"lane-%03d" % i))
            for i in range(n)]
    arrays, ok = jbk._pack_ed(reqs, n)
    assert ok.all()
    arrays = [np.ascontiguousarray(np.tile(a, (1, -(-lanes // n)))[:, :lanes])
              for a in arrays]
    arrays[6][0, bad] ^= 1                      # s of lane `bad`
    return arrays, np.arange(lanes, dtype=np.int32).reshape(1, -1)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--widths", type=int, nargs="*",
                    default=[1, 23, 1, 6, 23, 1])
    ap.add_argument("--capacity", type=int, default=23)
    ap.add_argument("--reps", type=int, default=4)
    ap.add_argument("--static", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax
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
    jbk = JB.JaxBackend(min_bucket=16 if args.rehearse else 128,
                        use_pallas=False, autotune=False)
    tile, cap = jbk.ed_tile, args.capacity
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append(secs)
        if ev.endswith("backend_compile_duration") else None)
    emit({"device": jax.devices()[0].device_kind, "platform": platform,
          "tile": tile, "capacity_tiles": cap, "widths": args.widths,
          "cache": os.environ.get("JAX_COMPILATION_CACHE_DIR", "unset")})
    t0 = time.perf_counter()
    lanes_of = {T: packed_lanes(jbk, T * tile, (T * tile) // 3)
                for T in sorted(set(args.widths))}
    emit({"fixtures_s": round(time.perf_counter() - t0, 2)})

    def verify(Aw, xa, xw, yw, Rw, sR2, sw, kw):
        return EJ.verify_full_split_words_core(Aw, xa, xw, yw, Rw, sR2[0],
                                               sw, kw)

    def first_bad(ok, own):
        return jnp.min(jnp.where(ok != 0, SENT, own))

    # -- the route that stayed ------------------------------------------------
    def tile_calls(T):
        arrays, own = lanes_of[T]
        t = time.perf_counter()
        tiles = jbk._dev_tiles(arrays, T * tile)
        owns = jbk._dev_tiles((own,), T * tile)
        t_copy = time.perf_counter()
        run = jbk._ed_tile_program(False, True)
        bad = jbk._dev_scalar(SENT)
        for (o,), lanes in zip(owns, tiles):
            bad = run(bad, o, *lanes)
        t_disp = time.perf_counter()
        return int(np.asarray(bad)), t, t_copy, t_disp

    # -- the route that went: a runtime trip count over capacity buffers -----
    @jax.jit
    def trip_program(n_tiles, own, *arrays):
        def body(t, bad):
            off = t * tile
            sl = [lax.dynamic_slice_in_dim(a, off, tile, 1) for a in arrays]
            o = lax.dynamic_slice_in_dim(own, off, tile, 1)[0]
            return jnp.minimum(bad, first_bad(verify(*sl), o))
        return lax.fori_loop(0, n_tiles, body, jnp.int32(SENT))

    def trip_count(T):
        arrays, own = lanes_of[T]
        t = time.perf_counter()
        pad = (cap - T) * tile
        dev = [jnp.asarray(np.pad(a, ((0, 0), (0, pad))))
               for a in [own] + list(arrays)]
        t_copy = time.perf_counter()
        bad = trip_program(jnp.int32(T), *dev)
        t_disp = time.perf_counter()
        return int(np.asarray(bad)), t, t_copy, t_disp

    # -- the parent's form: one program a distinct tile count ----------------
    static_programs = {}

    def static_map(T):
        arrays, own = lanes_of[T]
        t = time.perf_counter()
        dev = [jnp.asarray(a) for a in [own] + list(arrays)]
        t_copy = time.perf_counter()
        if T not in static_programs:
            def prog(own, *arrays, _T=T):
                def major(a):
                    return a.reshape(a.shape[0], _T, tile).transpose(1, 0, 2)
                ok = lax.map(lambda x: verify(*x),
                             tuple(major(a) for a in arrays)).reshape(-1)
                return first_bad(ok, own[0])
            static_programs[T] = jax.jit(prog)
        bad = static_programs[T](*dev)
        t_disp = time.perf_counter()
        return int(np.asarray(bad)), t, t_copy, t_disp

    def traced_busy(run):
        d = tempfile.mkdtemp(prefix="mixedfill-")
        try:
            with jax.profiler.trace(d):
                run()
            ops = trace_mod.device_ops(
                trace_mod.load(trace_mod.find_xplane(d)),
                rehearse=args.rehearse)
        finally:
            shutil.rmtree(d, ignore_errors=True)
        evs = [e for dev in ops.values() for e in dev]
        if not evs:
            return None
        lo, hi = min(e[0] for e in evs), max(e[1] for e in evs)
        return trace_mod.busy_ns(trace_mod.union(evs, lo, hi)) * 1e-9

    # compile one program at a time (the verify skill's note, PR 30)
    for name, route in (("tile_calls", tile_calls),
                        ("trip_count", trip_count),
                        ("static_map", static_map))[:3 if args.static
                                                    else 2]:
        for rep in range(args.reps):
            for T in args.widths:
                c0 = len(compiles)
                bad, t, t_copy, t_disp = route(T)
                t_end = time.perf_counter()
                row = {"route": name, "round": rep, "tiles": T,
                       "correct": bad == (T * tile) // 3,
                       "copy_ms": round((t_copy - t) * 1e3, 3),
                       "dispatch_ms": round((t_disp - t_copy) * 1e3, 3),
                       "wall_ms": round((t_end - t) * 1e3, 3),
                       "compiles": len(compiles) - c0}
                if rep == 0:
                    row["first_s"] = round(t_end - t, 2)
                    row["compile_s"] = round(sum(compiles[c0:]), 2)
                emit(row)
        for T in sorted(set(args.widths)):
            busy = traced_busy(lambda: route(T))
            emit({"route": name, "device": True, "tiles": T,
                  "device_ms": None if busy is None
                  else round(busy * 1e3, 3),
                  "device_us_per_real_lane": None if busy is None
                  else round(busy * 1e6 / (T * tile), 3)})
    return 0


if __name__ == "__main__":
    import threading
    threading.stack_size(1 << 30)
    rc = []
    th = threading.Thread(target=lambda: rc.append(main()))
    th.start()
    th.join()
    sys.exit(rc[0] if rc else 1)
