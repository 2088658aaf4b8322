#!/usr/bin/env python
"""The benchmark's one command: one cell, one seed, one process.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (all of it counted in `setup_s`): JAX first, so this process
takes the chip or stops; the seed's chain from the cache inside the
checkout, or forged by a child that never touches JAX; the
configuration's backend; a first replay that pays every compile or cache
load; the tamper probe; the traffic's untimed `warm_replays`.  The plain
reference's verdict is the same child's work, beside this process's
compile; it is waited for after the window and is no part of `setup_s`.
Then the window:
whole replays back to back until the first replay boundary at or after
`--seconds`, the key caches cleared and the garbage collected before
each.  `blocks_per_s` is every block of the whole replays over the whole
of that window, the time between replays included.

Without a TPU the run exits 2 and prints no result.  `--rehearse`
allows the CPU at the tiny sizes of the configuration's `rehearse`
block: every line then says so, and no number is printed under a
metric's name ("not measured").

For the builder's own use: `--seeds a,b,c` checks several seeds in one
process (one result line each); `--control wrong-reference` and
`--control flipped-witness` (or both, comma separated: every seed under
each in turn) are the runs that must read `correct: false` (README.md).

Every line is one JSON object naming platform, device kind and device
count.  The last line is the result: `correct`, `attempted`, `failed`,
`metrics`, `device` and, traced, `breakdown`.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()        # process start, as near as Python gives

import argparse    # noqa: E402
import gc          # noqa: E402
import importlib   # noqa: E402
import json        # noqa: E402
import os          # noqa: E402
import shutil      # noqa: E402
import statistics  # noqa: E402
import sys         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from harness import cache, chain, readers, trace, window   # noqa: E402
from harness.manifest import Manifest, check_values         # noqa: E402

CONTROLS = ("wrong-reference", "flipped-witness")
NOT_MEASURED = "not measured (CPU rehearsal)"


def resolve(obj):
    """A configuration names code as data: `"module:attr"` is imported,
    and `{"call": "module:attr", "args": [...], "kwargs": {...}}` is
    called with its own arguments resolved the same way (a mesh for a
    sharded backend is `{"call": "ouroboros_tpu.parallel:make_mesh",
    "args": [4]}`)."""
    if isinstance(obj, dict) and "call" in obj:
        mod, attr = obj["call"].split(":")
        fn = getattr(importlib.import_module(mod), attr)
        return fn(*[resolve(a) for a in obj.get("args", [])],
                  **{k: resolve(v) for k, v in obj.get("kwargs", {}).items()})
    return obj


class Run:
    """What one process holds across its seeds: the cell's data files,
    the device tag every line carries, the program's modules and the
    backend with its compiled programs."""

    def __init__(self, args, man: Manifest):
        self.args = args
        self.man = man
        self.cell = man.workload(args.workload)
        self.cfg = man.config(self.cell["config"])
        self.traffic = man.traffic(self.cell["traffic"])
        if args.rehearse:
            self.cfg = {**self.cfg, **self.cfg["rehearse"]}
            self.traffic = {**self.traffic, **self.traffic["rehearse"]}
        self.tag: dict = {}
        self.jax_events: list = []     # (stage, fun_name, secs)
        self.backend = None
        self.devices: list = []

    # -- output ---------------------------------------------------------------
    def emit(self, line: str, **kw) -> None:
        print(json.dumps({"line": line, **self.tag, **kw}), flush=True)

    # -- set-up, once a process ---------------------------------------------
    def take_device(self) -> bool:
        """JAX first: this process takes the chip(s), or the run stops."""
        import jax
        devs = jax.devices()
        self.jax = jax
        self.tag = {"platform": devs[0].platform,
                    "device_kind": devs[0].device_kind,
                    "device_count": len(devs)}
        if self.args.rehearse:
            self.tag["rehearse"] = True
        chips = self.cell["chips"]
        if devs[0].platform != "tpu" and not self.args.rehearse:
            print(f"run.py: JAX reports platform {devs[0].platform!r}, "
                  f"not 'tpu'; nothing was run (--rehearse allows a CPU "
                  f"rehearsal at tiny sizes)", file=sys.stderr)
            return False
        if len(devs) < chips:
            print(f"run.py: cell {self.cell['name']} needs {chips} "
                  f"chip(s), JAX reports {len(devs)}", file=sys.stderr)
            return False
        self.devices = devs[:chips]
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return True

    def _on_event(self, event: str, secs: float, **kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.jax_events.append((event.rsplit("/", 1)[1],
                                    kw.get("fun_name", "?"), secs))

    def spec(self) -> dict:
        """What the child that forges the chain and computes the
        reference needs to know."""
        return {"synth": {**self.cfg["synth"], **self.traffic["synth"]},
                "blocks": self.cfg["blocks"],
                "window_blocks": self.cfg["window_blocks"],
                "validate_mode": self.cfg["validate_mode"],
                "tamper": self.traffic["tamper"]}

    def build_backend(self, prebuilt=None) -> None:
        """The configuration's backend: its constructor as a dotted
        import path, its arguments as data (`resolve`).  `prebuilt` is
        for tests/, which drive several runs in one process and compile
        once."""
        from ouroboros_tpu import observe
        from ouroboros_tpu.compile_cache import cache_dir
        self.observe = observe
        if self.args.trace:
            observe.enable()          # spans; the counters are always on
        b = self.cfg["backend"]
        self.backend = prebuilt if prebuilt is not None else resolve(
            {"call": b["constructor"], "args": b.get("args", []),
             "kwargs": b.get("kwargs", {})})
        if self.backend.platform != self.tag["platform"]:
            raise SystemExit("the backend took another platform than JAX "
                             "reports")
        self.emit("backend", name=self.backend.name,
                  constructor=b["constructor"], args=b.get("args", []),
                  kwargs=b.get("kwargs", {}),
                  compile_cache=os.path.relpath(cache_dir(), ROOT),
                  jax=self.jax.__version__, host_cpus=os.cpu_count())

    # -- readings of the program ----------------------------------------------
    def counters(self) -> dict:
        return {i.name: i.value
                for i in self.observe.metrics.REGISTRY.instruments()
                if getattr(i, "kind", "") == "counter"}

    def drain_spans(self) -> list:
        """(t0, t1, name, cat) in perf_counter seconds, every completed
        span since the last drain."""
        return [(sp.t0, sp.t1, sp.name, sp.cat)
                for root in self.observe.spans.RECORDER.drain()
                for sp in root.walk() if sp.t1 is not None]

    def peak_bytes(self) -> list:
        return [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in self.devices]


def replay_fn(run: Run, dba, ctx, decode=None):
    cfg = run.cfg

    def once() -> dict:
        return chain.validate(dba, ctx, run.backend, cfg["validate_mode"],
                              cfg["window_blocks"],
                              cfg["snapshot_every_slots"], decode=decode)
    return once


def between_replays() -> None:
    """As a fresh process would start a replay: cold key caches, and the
    last replay's garbage gone.  Never inside a timed interval."""
    chain.clear_caches()
    gc.collect()


def run_seed(run: Run, seed: int, prep: cache.Prepared, t_start: float,
             control: str = None, wrong: cache.Prepared = None) -> dict:
    """Set-up for one seed, the window, the comparison; returns the
    result line."""
    args, cfg, traffic = run.args, run.cfg, run.traffic
    jax = run.jax
    phases: dict = {}

    def phase(name: str, t: float) -> None:
        phases[name] = time.perf_counter() - t

    t = time.perf_counter()
    chain_dir = prep.wait_chain()
    phase("chain_wait", t)
    dba, ctx = chain.open_chain(chain_dir)
    db, rules, decode, _c, _d = ctx
    once = replay_fn(run, dba, ctx)
    n_windows = cfg["blocks"] // cfg["window_blocks"]

    # -- the first replay pays every compile or cache load ------------------
    ev0 = len(run.jax_events)
    between_replays()
    t = time.perf_counter()
    setup_results = [once()]
    phase("first_replay", t)

    # -- the tamper probe: the device verdict stops the replay ---------------
    t = time.perf_counter()
    blocks = [decode(raw) for _entry, raw in db.stream()]
    dev_stop = chain.probe_stop(rules, blocks, traffic["tamper"],
                                run.backend, cfg["window_blocks"])
    del blocks
    phase("tamper_probe", t)

    # -- untimed replays first ---------------------------------------------
    t = time.perf_counter()
    for _ in range(traffic["warm_replays"]):
        between_replays()
        setup_results.append(once())
    phase("warm_replays", t)

    setup_events, setup_event_secs = {}, {}
    for stage, _fn, s in run.jax_events[ev0:]:
        setup_events[stage] = setup_events.get(stage, 0) + 1
        setup_event_secs[stage] = setup_event_secs.get(stage, 0.0) + s
    setup_spans: dict = {}
    if args.trace:
        for t0, t1, _n, cat in run.drain_spans():
            setup_spans[cat] = setup_spans.get(cat, 0.0) + (t1 - t0)

    # -- the window ------------------------------------------------------------
    window_once = once
    if control == "flipped-witness":
        # control: a chain with one witness signature flipped mid-window,
        # handed to the device path as if it were the clean one (the
        # decoder counts blocks, so each replay gets a fresh one)
        def window_once() -> dict:
            return replay_fn(run, dba, ctx, chain.tampering_decode(
                decode, "witness", cfg["window_blocks"] // 2))()
    traced = {"n": 0, "marks_perf_ns": [], "dir": None}
    if args.trace:
        traced["dir"] = os.path.join(
            HERE, "out", f"trace-{run.cell['name']}-{seed}")
        shutil.rmtree(traced["dir"], ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(traced["dir"], profiler_options=opts)

    def clock_mark() -> None:
        traced["marks_perf_ns"].append(time.perf_counter_ns())
        with jax.profiler.TraceAnnotation(trace.CLOCK_MARK):
            pass

    def after_each(i: int) -> None:
        if args.trace and i + 1 == traffic["traced_replays"]:
            clock_mark()
            jax.profiler.stop_trace()
            traced["n"] = i + 1

    between_replays()
    c0 = run.counters()
    ev1 = len(run.jax_events)
    if args.trace:
        clock_mark()
    setup_s = time.perf_counter() - t_start
    w = window.run_window(window_once, args.seconds,
                          before_each=between_replays,
                          after_each=after_each)
    if args.trace and not traced["n"]:       # window shorter than planned
        clock_mark()
        jax.profiler.stop_trace()
        traced["n"] = w.attempted
    c1 = run.counters()
    in_window_events = run.jax_events[ev1:]
    spans = run.drain_spans() if args.trace else []

    # -- the reference's verdict: a cache hit, or the child's, which ran
    #    beside the compile; waited for only now, so never part of setup_s --
    t = time.perf_counter()
    ref = prep.wait_verdict()
    if wrong is not None:
        # control: another seed's verdict in this seed's place
        ref = {**ref, "state_hash": wrong.wait_verdict()["state_hash"]}
    phase("reference_wait", t)

    # -- the comparison ----------------------------------------------------------
    def matches(res) -> bool:
        return (res is not None and res["state_hash"] == ref["state_hash"]
                and res["blocks"] == ref["blocks"]
                and res["proofs"] == ref["proofs"])

    whole = [r for r in w.replays if r.error is None and matches(r.result)]
    failed = w.attempted - len(whole)
    delta = {k: c1[k] - c0.get(k, 0) for k in c1}
    peaks = run.peak_bytes()
    compiles = sum(st == "backend_compile_duration"
                   for st, _f, _s in in_window_events)
    compared = [
        ("replays_failed", failed, 0),
        ("setup_replays_differing_from_reference",
         sum(not matches(r) for r in setup_results), 0),
        ("tamper_stop_blocks_from_reference",
         abs(dev_stop["n_valid"] - ref["tamper_stop"]["n_valid"]), 0),
        ("tamper_error_differs",
         int(dev_stop["error"] != ref["tamper_stop"]["error"]), 0),
        ("programs_compiled_in_window", compiles, 0),
        ("composite_builds_in_window",
         delta.get("jax_backend.composite_builds", 0), 0),
        ("compile_spans_in_window",
         sum(cat == "compile" and t0 >= w.t_start
             for t0, _t1, _n, cat in spans), 0),
        ("windows_not_submitted_to_the_device",
         abs(delta.get("jax_backend.windows_submitted", 0)
             - len(whole) * n_windows) if not failed else 0, 0),
        ("replays_without_snapshot",
         sum(r.result["stream"]["snapshots_written"] < 1 for r in whole), 0),
        ("devices_that_held_nothing",
         sum(p <= 0 for p in peaks) if run.tag["platform"] == "tpu" else 0,
         0),
        ("platform_is_not_tpu",
         int(run.tag["platform"] != "tpu" and not args.rehearse), 0),
    ]
    correct = all(v <= lim for _n, v, lim in compared) and bool(whole)
    run.emit("check", seed=seed, control=control,
             reference={k: ref[k] for k in ("state_hash", "blocks",
                                            "proofs", "tamper_stop")},
             device_tamper_stop=dev_stop,
             compared=[{"what": n, "value": v, "limit": lim,
                        "ok": v <= lim} for n, v, lim in compared],
             errors=[r.error for r in w.replays if r.error][:3])

    # -- per-replay record: an earlier line, and a file ------------------------
    secs = [r.seconds for r in whole]
    streams = [r.result["stream"] for r in whole]
    run.emit("replays", seed=seed, seconds=[r.seconds for r in w.replays],
             whole=len(whole), window_wall_s=w.t_end - w.t_start,
             stream=[{k: s[k] for k in ("disk_secs", "disk_hidden_secs",
                                        "host_seq_secs", "host_hidden_secs",
                                        "snapshot_write_secs", "replay_secs")}
                     for s in streams])
    os.makedirs(os.path.join(HERE, "out"), exist_ok=True)
    with open(os.path.join(HERE, "out",
                           f"{run.cell['name']}-{seed}.jsonl"), "w") as fh:
        for i, r in enumerate(w.replays):
            fh.write(json.dumps({
                "i": i, "seconds": r.seconds, "error": r.error,
                "stream": r.result["stream"] if r.result else None}) + "\n")

    # -- the metrics -------------------------------------------------------------
    values: dict = {}
    breakdown = None
    device = {"platform": run.tag["platform"],
              "kind": run.tag["device_kind"],
              "count": run.tag["device_count"],
              "memory_peak_bytes": max(peaks)}
    if whole and not args.trace:
        values = {"blocks_per_s": window.window_rate(ref["blocks"],
                                                     len(whole), w),
                  "setup_s": setup_s}
    elif whole:
        facts = gather_facts(run, w, whole, spans, delta, setup_spans,
                             setup_events, peaks, traced, ref)
        tr = facts["trace"]
        device.update(busy_s=tr.get("busy_s"), window_s=tr.get("window_s"))
        breakdown = {"device_ops": tr.get("device_ops", []),
                     "idle_gaps": tr.get("idle_gaps", [])}
        for m in run.man.metrics_for(run.cell["name"], "per_layer"):
            v = readers.read(run.man.layer_metric(m["name"])["reader"],
                             facts)
            if v is not None:
                values[m["name"]] = v
    kind = "per_layer" if args.trace else "end_to_end"
    units = {m["name"]: m["unit"]
             for m in run.man.metrics_for(run.cell["name"], kind)}
    metrics = {n: {"value": v, "unit": units[n]} for n, v in values.items()
               if n in units}
    for fault in check_values(metrics):
        print(f"run.py: {fault}", file=sys.stderr)
        correct = False
    run.emit("setup", seed=seed, setup_s=setup_s,
             phases={k: round(v, 3) for k, v in phases.items()},
             chain_cache_hit=prep.cache_hit, prepare_secs=prep.secs,
             setup_events=setup_events,
             setup_event_secs={k: round(v, 2)
                               for k, v in setup_event_secs.items()},
             median_replay_s=statistics.median(secs) if secs else None)
    if args.rehearse:
        run.emit("rehearsal", seed=seed,
                 cpu_readings_that_are_no_measurement=values)
        metrics = {n: {"value": None, "unit": u, "note": NOT_MEASURED}
                   for n, u in units.items()}
    result = {"correct": correct, "attempted": w.attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None and not args.rehearse:
        result["breakdown"] = breakdown
    if args.rehearse:
        result["rehearse"] = True
    return result


def gather_facts(run: Run, w, whole, spans, delta, setup_spans,
                 setup_events, peaks, traced, ref) -> dict:
    """The facts the metric readers draw on (readers.py lists the
    namespaces)."""
    secs = [r.seconds for r in whole]
    blocks = ref["blocks"] * len(whole)
    span_seconds: dict = {}
    span_count: dict = {}
    for t0, t1, name, _cat in spans:
        if t0 >= w.t_start:
            span_seconds[name] = span_seconds.get(name, 0.0) + (t1 - t0)
            span_count[name] = span_count.get(name, 0) + 1
    stream: dict = {}
    for r in whole:
        for k, v in r.result["stream"].items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                stream[k] = stream.get(k, 0) + v
    return {
        "window": {"replays": len(whole), "blocks": blocks,
                   "proofs": ref["proofs"] * len(whole),
                   "windows": delta.get("jax_backend.windows_submitted", 0),
                   "replay_seconds": sum(secs),
                   "median_replay_blocks_per_s":
                       window.median_rate(ref["blocks"], secs)},
        "span_seconds": span_seconds, "span_count": span_count,
        "counter": delta, "stream": stream,
        "setup_spans": setup_spans, "setup_events": setup_events,
        "device": {"peak_bytes": max(peaks)},
        "trace": reduce_trace(run, w, spans, traced, ref),
    }


def reduce_trace(run: Run, w, spans, traced, ref) -> dict:
    """The trace reduction over the traced replays' own intervals (what
    the harness does between replays is left out, as in the window)."""
    if not traced["n"]:
        return {}
    pd = trace.load(trace.find_xplane(traced["dir"]))
    marks = trace.marks(pd)
    perf = traced["marks_perf_ns"]
    if len(marks) != len(perf):
        run.emit("trace_fault", marks_in_trace=len(marks),
                 marks_made=len(perf), planes=trace.describe(pd))
        return {}
    off = trace.clock_offset_ns(marks[0], perf[0])
    drift_us = ((marks[-1] - perf[-1]) - off) / 1e3
    windows = [(r.t0 * 1e9 + off, (r.t0 + r.seconds) * 1e9 + off)
               for r in w.replays[:traced["n"]]]
    on_trace = [(t0 * 1e9 + off, t1 * 1e9 + off, name)
                for t0, t1, name, _cat in spans]
    ops = trace.device_ops(pd, rehearse=run.args.rehearse)
    out = trace.reduce_windows(ops, on_trace, windows)
    if not out:
        run.emit("trace_fault", planes=trace.describe(pd))
        return {}
    good = sum(r.error is None for r in w.replays[:traced["n"]])
    out["proofs"] = ref["proofs"] * good
    run.emit("trace", traced_replays=traced["n"], clock_drift_us=drift_us,
             devices=out["devices"], busy_s=out["busy_s"],
             window_s=out["window_s"],
             xplane_bytes=os.path.getsize(trace.find_xplane(traced["dir"])))
    shutil.rmtree(traced["dir"], ignore_errors=True)
    return out


def main(argv=None, backend=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="allow the CPU at the configuration's tiny "
                         "rehearsal sizes; nothing it prints is a "
                         "measurement")
    ap.add_argument("--seeds", default=None,
                    help="builder's use: several seeds in one process, "
                         "comma separated; one result line each")
    ap.add_argument("--control", default=None, metavar="|".join(CONTROLS),
                    help="builder's use: a run that must read correct: "
                         "false")
    args = ap.parse_args(argv)
    if (args.seed is None) == (args.seeds is None):
        ap.error("give --seed or --seeds")
    seeds = ([args.seed] if args.seeds is None
             else [int(s) for s in args.seeds.split(",")])
    controls = args.control.split(",") if args.control else [None]
    if args.control and not set(controls) <= set(CONTROLS):
        ap.error(f"--control takes {', '.join(CONTROLS)}")

    man = Manifest()
    faults = man.check()
    if faults:
        print("run.py: BENCHMARK.json or its data files are at fault:\n  "
              + "\n  ".join(faults), file=sys.stderr)
        return 2
    for needed in ("ouroboros_tpu", os.path.join("tools", "db_synth.py"),
                   os.path.join("tools", "db_analyser.py")):
        if not os.path.exists(os.path.join(ROOT, needed)):
            print(f"run.py: the program is not here ({needed} is "
                  f"missing); nothing was run", file=sys.stderr)
            return 2
    run = Run(args, man)
    if not run.take_device():
        return 2
    run.emit("start", workload=run.cell["name"], config=run.cell["config"],
             traffic=run.cell["traffic"], seeds=seeds, seconds=args.seconds,
             trace=args.trace, control=args.control)

    # children forge the chains and compute the reference verdicts while
    # this process compiles; at most four at a time
    spec = run.spec()
    name_c, name_t = run.cell["config"], run.cell["traffic"]
    todo = list(seeds)
    if "wrong-reference" in controls:
        todo += [s + 1 for s in seeds]
    preps: dict = {}

    def started(seed: int) -> cache.Prepared:
        for s in todo:
            alive = sum(p.proc is not None and p.proc.poll() is None
                        for p in preps.values())
            if s not in preps and (alive < 4 or s == seed):
                preps[s] = cache.prepare(name_c, name_t, s, args.rehearse,
                                         spec)
        return preps[seed]

    try:
        started(seeds[0])
        run.build_backend(backend)
        first = True
        for control in controls:
            for seed in seeds:
                t_start = _T0 if first else time.perf_counter()
                first = False
                wrong = (started(seed + 1)
                         if control == "wrong-reference" else None)
                result = run_seed(run, seed, started(seed), t_start,
                                  control, wrong)
                if len(seeds) > 1 or len(controls) > 1:
                    result.update(seed=seed, control=control)
                print(json.dumps(result), flush=True)
    finally:
        for p in preps.values():
            p.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
