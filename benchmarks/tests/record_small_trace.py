#!/usr/bin/env python
"""Builder's tool (chip only): record the small `.xplane.pb` that
`tests/test_trace.py` reduces, and print what planes and lines a trace
of this device has.

Three bursts of a small jitted program with sleeps between them, under
two host spans with known perf_counter edges, so the expected busy
union, idle share and gap attribution can be worked out by hand.

    python benchmarks/tests/record_small_trace.py <out_dir>

writes <out_dir>/small.xplane.pb and <out_dir>/small.spans.json.
"""
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    out = sys.argv[1]
    os.makedirs(out, exist_ok=True)
    import jax
    import jax.numpy as jnp
    from harness import trace

    @jax.jit
    def burst(x):
        for _ in range(4):
            x = jnp.tanh(x @ x) + 1.0
        return x

    x = jnp.ones((256, 256), jnp.float32)
    burst(x).block_until_ready()
    tdir = os.path.join(out, "small_trace")
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir, profiler_options=opts)
    marks, spans = [], []

    def mark():
        marks.append(time.perf_counter_ns())
        with jax.profiler.TraceAnnotation(trace.CLOCK_MARK):
            pass

    mark()
    for name, pause in (("work.a", 0.002), ("work.b", 0.004),
                        ("work.a", 0.001)):
        t0 = time.perf_counter()
        burst(x).block_until_ready()
        time.sleep(pause)
        spans.append((t0, time.perf_counter(), name))
    time.sleep(0.003)       # no span open here
    mark()
    jax.profiler.stop_trace()
    src = trace.find_xplane(tdir)
    shutil.copy(src, os.path.join(out, "small.xplane.pb"))
    with open(os.path.join(out, "small.spans.json"), "w") as fh:
        json.dump({"marks_perf_ns": marks, "spans": spans,
                   "device": jax.devices()[0].device_kind}, fh)
    pd = trace.load(src)
    print(json.dumps({"bytes": os.path.getsize(src),
                      "planes": trace.describe(pd),
                      "marks": trace.marks(pd)}))
    for plane, ops in trace.device_ops(pd).items():
        print(plane, len(ops), [(round(a), round(b - a), n[:60])
                                for a, b, n in ops[:12]])
    shutil.rmtree(tdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
