"""Persistent, fenced pallas-vs-XLA kernel autotuner.

The r5 `_pick` voted with a median-of-3 timed inline — no fence before a
rep (so a timed rep inherited whatever async dispatches were still in
flight), no persistence of the window-composite vote, and measurements
could run INSIDE a timed region when a shape first appeared there: the
VRF primitive once regressed 0.83x with a 45% spread and the pallas/xla
choice flip-flopping between runs.

This module replaces it with one process-wide tuner per device kind:

- measurement discipline: warm/compile both implementations, then k
  fenced reps each — drain the async dispatch queue (`block_until_ready`
  on a dummy transfer) before starting the clock — and keep the MIN.
  On a chip whose host is shared the min is the only estimator of the
  workload's true cost that a slow-tail outlier cannot move.
- persistence: choices (including derived window-composite votes) are
  stored per (kernel revision, device kind) in a JSON file next to the
  XLA compilation cache, so every later process starts pinned and two
  consecutive runs report byte-identical `kernel_choices`.
- fencing of timed regions: `freeze()` turns any further `_store_choice`
  into a `FrozenAutotunerError`, making "a retune happened
  mid-measurement" a loud failure instead of a silent 45% spread.
  OURO_RETUNE=1 drops the persisted file and re-measures from scratch.
"""
from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import dataclass

from ..compile_cache import cache_dir
from ..observe import metrics as _metrics
from ..observe import spans as _spans
from ..utils.tracer import Tracer

# bump when kernel internals change enough that a persisted pallas-vs-XLA
# choice could be stale (the choices file is keyed by this revision)
# r8: the simple-batch VRF path moved to the verify+challenge-fold form
# (device SHA-512, 1 B/proof transfer) under its own ("vrff", m) key;
# ("vrf", m) still names the rows form the window composite fuses.  r6
# choice files predate the split and must re-measure.
KERNEL_REV = "r8-fold-1"

WARMUP_REPS = 1
TIMED_REPS = 3

# registry counters (ISSUE 7).  frozen_writes is load-bearing (it must
# stay 0 across a frozen region) -> always.  measurements and
# stores depend on what an earlier process persisted, so they are
# excluded from the deterministic snapshot (stable=False) but still
# exported to Prometheus.
_FROZEN_WRITES = _metrics.counter("autotune.frozen_writes", always=True)
_MEASUREMENTS = _metrics.counter("autotune.measurements", always=True,
                                 stable=False)
_STORES = _metrics.counter("autotune.stores", always=True, stable=False)


@dataclass(frozen=True)
class AutotuneMeasured:
    """One head-to-head pallas-vs-XLA measurement (the typed decision
    event; TRACER forwards it to whoever is listening)."""
    device_kind: str
    key: tuple
    pallas_ms: float
    xla_ms: float
    use_pallas: bool


# decision event sink — NOP unless a test/exporter attaches one
TRACER = Tracer()


class FrozenAutotunerError(RuntimeError):
    """A kernel choice write was attempted inside a timed region."""


def _slug(s: str) -> str:
    return "".join(c if c.isalnum() or c in "-._" else "-" for c in s)


def _fence() -> None:
    """Drain the async dispatch queue so a timed rep never inherits the
    previous dispatch's in-flight device work."""
    import jax
    jax.block_until_ready(jax.device_put(0.0))


class Autotuner:
    """Measured pallas-vs-XLA choices for one (kernel rev, device kind).

    Keys are tuples like ("vrf", 2048) or ("win", nv, nb, nk); the
    value is True for pallas.  `pick` runners must BLOCK on their result
    (e.g. return np.asarray(...)) so a rep's wall time covers dispatch +
    compute + transfer."""

    def __init__(self, path: str, device_kind: str):
        self.path = path
        self.device_kind = device_kind
        self.frozen = False
        self.writes_while_frozen = 0
        self._choices: dict = {}
        self._timings: dict = {}
        self._load()

    # -- persistence ---------------------------------------------------------
    def _load(self) -> None:
        """Read the persisted choices.  "No file yet" is the only miss
        tolerated: an unreadable or malformed file raises, because
        carrying on would re-measure (and re-compile both forms of)
        every shape in every process without anyone noticing."""
        try:
            with open(self.path) as f:
                data = json.load(f)
        except FileNotFoundError:
            return
        for k, v in data.get("choices", {}).items():
            key = tuple(json.loads(k))
            self._choices[key] = bool(v["pallas"])
            if "pallas_ms" in v:
                self._timings[key] = (v.get("pallas_ms"),
                                      v.get("xla_ms"))

    def _save(self) -> None:
        """Persist atomically; a write failure raises (see _load)."""
        choices = {}
        for k in sorted(self._choices):
            ent: dict = {"pallas": self._choices[k]}
            t = self._timings.get(k)
            if t is not None:
                ent["pallas_ms"], ent["xla_ms"] = t
            choices[json.dumps(list(k))] = ent
        # per-process staging name: two processes saving at once must
        # not replace each other's half-written file away
        tmp = f"{self.path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump({"kernel_rev": KERNEL_REV,
                       "device_kind": self.device_kind,
                       "choices": choices}, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, self.path)

    def invalidate(self) -> None:
        """Forget every measured choice and drop the persisted file
        (`--retune`)."""
        self._choices.clear()
        self._timings.clear()
        try:
            os.remove(self.path)
        except FileNotFoundError:
            pass

    # -- reads ---------------------------------------------------------------
    def get(self, key):
        """Pinned choice for `key`, or None if never measured."""
        return self._choices.get(key)

    def choices_snapshot(self) -> dict:
        """Stable-ordered {key tuple: use_pallas} copy."""
        return {k: self._choices[k] for k in sorted(self._choices)}

    # -- writes --------------------------------------------------------------
    def freeze(self) -> None:
        self.frozen = True

    def thaw(self) -> None:
        self.frozen = False

    def _store_choice(self, key, use: bool, timings=None) -> None:
        if self.frozen:
            self.writes_while_frozen += 1
            _FROZEN_WRITES.inc()
            raise FrozenAutotunerError(
                f"kernel choice for {key} written inside a timed region "
                f"(autotuner frozen); pin all shapes in a warmup phase "
                f"before timing")
        self._choices[key] = bool(use)
        if timings is not None:
            self._timings[key] = timings
        _STORES.inc()
        self._save()

    def put_derived(self, key, use: bool) -> None:
        """Pin a choice computed from other choices (e.g. the homogeneous
        window-composite vote) without measuring."""
        if self._choices.get(key) == bool(use):
            return
        self._store_choice(key, use)

    def measure(self, key, run_pallas, run_xla):
        """Measure both implementations for `key` and pin the winner.

        Returns (use_pallas, last_result) with last_result the winning
        implementation's final rep output — callers may reuse it to skip
        one extra dispatch."""
        if self.frozen:
            # raise through _store_choice for a single error site
            self._store_choice(key, False)
        _MEASUREMENTS.inc()
        best = {}
        last = {}
        # compile phase: a measurement is shape-pinning work that must
        # never overlap a timed region, so the whole warm+measure block
        # is one fenced compile span (cold-path only — a pinned choice
        # returns from get() without ever reaching here)
        with _spans.span("autotune.measure", cat="compile", fence=True):
            for flag, fn in ((True, run_pallas), (False, run_xla)):
                for _ in range(WARMUP_REPS):
                    fn()                            # warm / compile
                vals = []
                for _ in range(TIMED_REPS):
                    _fence()
                    t0 = time.perf_counter()
                    last[flag] = fn()
                    vals.append(time.perf_counter() - t0)
                best[flag] = min(vals)
        use = best[True] <= best[False]
        TRACER.trace(AutotuneMeasured(
            self.device_kind, key, round(best[True] * 1e3, 3),
            round(best[False] * 1e3, 3), use))
        print(f"[autotune:{self.device_kind}] {key}: "
              f"pallas {best[True] * 1e3:.0f}ms / "
              f"xla {best[False] * 1e3:.0f}ms (min of {TIMED_REPS}) -> "
              f"{'pallas' if use else 'xla'}",
              file=sys.stderr, flush=True)
        self._store_choice(key, use,
                           (round(best[True] * 1e3, 3),
                            round(best[False] * 1e3, 3)))
        return use, last[use]


_TUNERS: dict = {}


def tuner_for(device_kind: str) -> Autotuner:
    """Process-wide tuner for a device kind (one choices file per
    (KERNEL_REV, device kind)).  Honors OURO_RETUNE=1 by invalidating the
    persisted choices when the tuner is first created."""
    t = _TUNERS.get(device_kind)
    if t is None:
        path = os.path.join(
            cache_dir(),
            f"ouro-autotune-{KERNEL_REV}-{_slug(device_kind)}.json")
        t = Autotuner(path, device_kind)
        if os.environ.get("OURO_RETUNE") == "1":
            t.invalidate()
        _TUNERS[device_kind] = t
    return t
