"""ouroboros_tpu.observe — the unified observability layer.

Three parts, one seam (ISSUE 7):

- `metrics`: a process-wide registry of named counters/gauges/histograms
  with deterministic sorted snapshots.  The precompute cache stats,
  subscription reconnects, watchdog firings and mux teardowns all live
  here.
- `spans`: hierarchical timing spans with explicit block_until_ready
  fencing, splitting every replay window into host-seq / dispatch /
  device / compile / sync phases.  Monotonic-clock only, sim-time aware
  (the same API yields virtual durations under simharness).
- `export`: Prometheus text exposition, chrome://tracing span dumps,
  and the typed-tracer-events -> JSONL bridge.
- `adapter`: NodeTracers -> metrics (typed protocol events count without
  string matching).
- `flight`: the always-on flight recorder — a bounded ring of recent
  spans/events/metric deltas, dumped as chrome-trace + JSONL on failure
  (ISSUE 9).
- `netmetrics`: bounded-cardinality per-peer network instruments — the
  `peer_label` LRU helper, labeled counters/gauges, and the mux traffic
  accounting (ISSUE 14).
- `propagation`: per-node block-propagation lifecycle timelines + the
  FleetTelemetry merge (time-to-adoption quantiles, per-edge delivery
  latency, partition healing) for chaos-fleet runs (ISSUE 14).
- `scrape` (imported on demand — it pulls the network stack): the live
  Prometheus scrape endpoint + periodic emitter over the project's own
  snocket/SDU transport.

Defaults: metric writes are ON (an enabled counter bump is one flag
read plus an int add) and span recording is OFF (spans allocate and
read clocks; the benchmark and tests enable them around regions they
study).
Both layers are near-free when off — `spans.span()` returns a shared
null context manager, a gated metric write is a single flag read — and
`enable()/disable()` flip them together.  The precompute counters are
`always=True`: they are load-bearing program
state (tests assert on them) that the registry exports, not
observation that the flag may drop.
"""
from __future__ import annotations

from . import adapter, export, flight, metrics, netmetrics, propagation, \
    spans
from .adapter import counting_node_tracers, metrics_node_tracers
from .flight import FLIGHT, FlightRecorder
from .metrics import REGISTRY, Counter, Gauge, Histogram, MetricsRegistry
from .netmetrics import peer_label
from .propagation import FleetTelemetry, PropagationTracker
from .spans import RECORDER, Span, SpanRecorder, span

# NOTE: observe.scrape is deliberately NOT imported here — it pulls in
# the network stack (snocket/mux), which itself imports observe.metrics;
# consumers `from ouroboros_tpu.observe import scrape` on demand.

__all__ = [
    "FLIGHT", "FleetTelemetry", "FlightRecorder",
    "REGISTRY", "RECORDER", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "PropagationTracker", "Span", "SpanRecorder",
    "adapter", "counting_node_tracers", "disable", "enable", "enabled",
    "export", "flight", "metrics", "metrics_node_tracers", "netmetrics",
    "peer_label", "propagation", "span", "spans",
]


def enable() -> None:
    """Turn on metrics writes and span recording."""
    metrics.REGISTRY.enable()
    spans.RECORDER.enable()


def disable() -> None:
    metrics.REGISTRY.disable()
    spans.RECORDER.disable()


def enabled() -> bool:
    return metrics.REGISTRY.enabled or spans.RECORDER.enabled
