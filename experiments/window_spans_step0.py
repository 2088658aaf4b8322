#!/usr/bin/env python
"""Step 0 of ISSUE 46, on the chip: a traced run of the benchmark that
also prints what the result line leaves out, the window's seconds span
by span.

It runs the `benchmarks/run.py` of the tree it is started in (the
parent's, unpacked by `git archive`, or the change's) with that file's
arguments, and edits nothing: `gather_facts` is wrapped to print one
more line,

    {"line": "step0", "windows": ..., "ms_per_window": {span: ms},
     "count": {span: spans}, "counters": {"precompute.*": ...}}

and two spans are wrapped around calls that have none of their own, so
that `submit.pack_ed`'s residue can be told apart:
`step0.prepare_words` (`ed25519_jax.prepare_words_batch`: the lanes
hashed and packed, which needs no table) and `step0.dev_tiles`
(`JaxBackend._dev_tiles`: the tiles' copy to the device, the fold's
owner rows included).

    cd <tree> && python <this file> --workload sync-freshkeys \
        --seed 4601 --seconds 30 --trace 1
"""
import importlib.util
import json
import os
import sys

KEEP = ("window.submit", "submit.split", "submit.pack_ed",
        "submit.pack_vrf", "submit.pack_kes", "submit.fold",
        "submit.dispatch", "submit.ed_tiles", "precompute.fill",
        "fill.pack", "fill.dispatch", "fill.fetch", "fill.store",
        "pack_ed.challenge", "step0.prepare_words", "step0.dev_tiles",
        "window.host_seq", "pipeline.drain", "window.drain")


def load_run(root: str):
    """The tree's `benchmarks/run.py` as a module."""
    sys.path.insert(0, os.path.join(root, "benchmarks"))
    sys.path.insert(0, root)
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(root, "benchmarks", "run.py"))
    run = importlib.util.module_from_spec(spec)
    sys.modules["bench_run"] = run
    spec.loader.exec_module(run)
    return run


def print_spans(facts: dict) -> None:
    w = facts["window"]
    n = max(w["windows"], 1)
    print(json.dumps({
        "line": "step0", "windows": w["windows"], "replays": w["replays"],
        "replay_seconds": w["replay_seconds"],
        "ms_per_window": {k: round(facts["span_seconds"].get(k, 0.0)
                                   * 1e3 / n, 2) for k in KEEP},
        "count": {k: facts["span_count"].get(k, 0) for k in KEEP},
        "counters": {k: v for k, v in facts["counter"].items()
                     if k.startswith("precompute.")
                     or k.startswith("span.off_cpu_us.window.submit")},
    }), flush=True)


def wrap_program() -> None:
    """Spans around the two calls of `submit.pack_ed` that have none."""
    from ouroboros_tpu.crypto import ed25519_jax as EJ
    from ouroboros_tpu.crypto import jax_backend as JB
    from ouroboros_tpu.observe import spans as _spans
    pw = EJ.prepare_words_batch

    def prepare_words_batch(*a, **kw):
        with _spans.span("step0.prepare_words", cat="dispatch"):
            return pw(*a, **kw)
    EJ.prepare_words_batch = prepare_words_batch
    dt = JB.JaxBackend._dev_tiles

    def _dev_tiles(self, arrays, ne):
        with _spans.span("step0.dev_tiles", cat="dispatch"):
            return dt(self, arrays, ne)
    JB.JaxBackend._dev_tiles = _dev_tiles


def main() -> int:
    run = load_run(os.getcwd())
    gather, build = run.gather_facts, run.Run.build_backend

    def gather_and_print(*a, **kw):
        facts = gather(*a, **kw)
        print_spans(facts)
        return facts

    def build_backend(self, prebuilt=None):
        wrap_program()
        return build(self, prebuilt)

    run.gather_facts = gather_and_print
    run.Run.build_backend = build_backend
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
