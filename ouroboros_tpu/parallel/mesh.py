"""Device mesh construction for sharded batch validation."""
from __future__ import annotations

import sys
import time
from contextlib import contextmanager
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from ..observe import metrics as _metrics
from ..observe import spans as _spans

WINDOW_AXIS = "window"   # the header-window (proof-batch) axis

# pre-bound (OBS002): log_compile_time is cold, but the handle is static
_LAST_COMPILE = _metrics.gauge("parallel.last_compile_secs", stable=False)


@contextmanager
def log_compile_time(what: str, stream=None):
    """Wall-time a compile-heavy block and print one log line, so a
    multi-minute XLA compile shows up in the harness tail instead of
    looking like a hang until the timeout kills it.

    Also records a `compile` span and yields a result dict whose
    ``secs`` field carries the elapsed seconds after the block exits —
    callers that must REPORT compile cost (the multichip dryrun JSON)
    bind it: ``with log_compile_time(...) as ct: ...; ct["secs"]``."""
    stream = stream if stream is not None else sys.stderr
    out = {"what": what, "secs": None}
    t0 = time.perf_counter()
    print(f"[parallel] {what}: compiling...", file=stream, flush=True)
    span_cm = _spans.span(f"compile.{what}", cat="compile")
    span_cm.__enter__()
    try:
        yield out
    finally:
        span_cm.__exit__(None, None, None)
        out["secs"] = round(time.perf_counter() - t0, 3)
        _LAST_COMPILE.set(out["secs"])
        print(f"[parallel] {what}: done in {out['secs']:.1f}s",
              file=stream, flush=True)


def make_mesh(n_devices: Optional[int] = None,
              axis: str = WINDOW_AXIS) -> Mesh:
    """1-D mesh over the first n_devices devices.

    The framework's device-parallel dimension is the proof batch — the
    window of independent headers/tx-witnesses being validated (the
    sequence-parallel analog for a blockchain's 'sequence').  A 1-D mesh
    suffices because the ladder kernel has no cross-example communication;
    the fold's `pmin` is the only collective.
    """
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"requested {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))
