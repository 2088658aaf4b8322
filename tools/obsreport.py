"""obsreport — a live view of a running node's scrape endpoint, and
renderers for what a run leaves behind.

    python -m tools.obsreport --live 127.0.0.1:9187 [--interval 5]
    python -m tools.obsreport --fleet fleet.json
    python -m tools.obsreport --flight /tmp/ouro-flight [--tail 20]

``--live ADDR`` scrapes a running process's metrics endpoint
(observe/scrape.py, served over the project's own snocket/SDU
transport) and renders replay progress (blocks done / ETA / blocks per
sec / windows in flight / hidden fraction) plus p50/p95/p99 for every
latency histogram — repeat with ``--interval N``.

``--fleet PATH`` renders a FleetTelemetry report (the JSON dict a chaos
threadnet run leaves on ``ChaosResult.fleet``, ISSUE 14): time-to-50%/
95%-adoption quantiles, per-edge delivery latency, partition-healing
times, and the per-peer mux byte accounting.

``--flight DIR`` renders a flight-recorder dump directory
(observe/flight.py): the reason header, aggregated metric deltas, and
the last ``--tail`` span/event ring entries — post-mortems no longer
require hand-reading flight.jsonl.

Exit codes: 0 report printed, 2 unreadable/unrecognised input.
"""
from __future__ import annotations

import json
import sys
from typing import List, Optional


def _table(rows: List[List[str]], header: List[str]) -> List[str]:
    widths = [max(len(str(r[i])) for r in [header] + rows)
              for i in range(len(header))]
    fmt = "  ".join(f"{{:<{w}}}" for w in widths)
    lines = [fmt.format(*header), fmt.format(*("-" * w for w in widths))]
    lines += [fmt.format(*(str(c) for c in row)) for row in rows]
    return lines


def _fmt_secs(v) -> str:
    return f"{v:.4f}" if isinstance(v, (int, float)) else "-"


# ---------------------------------------------------------------------------
# --fleet: render a FleetTelemetry report (ISSUE 14)
# ---------------------------------------------------------------------------

def load_fleet(path: str) -> dict:
    """The fleet report dict from `path`; accepts the bare report or a
    wrapper carrying it under ``fleet`` (a dumped ChaosResult)."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "adoption" not in doc \
            and isinstance(doc.get("fleet"), dict):
        doc = doc["fleet"]
    if not isinstance(doc, dict) or "adoption" not in doc \
            or "nodes" not in doc:
        raise ValueError("not a fleet report (no 'adoption'/'nodes')")
    return doc


def _fmt_dist(d: dict) -> List[str]:
    return [str(d.get("n", 0)), _fmt_secs(d.get("p50")),
            _fmt_secs(d.get("p95")), _fmt_secs(d.get("max"))]


def render_fleet(doc: dict) -> str:
    out: List[str] = []
    nodes = doc.get("nodes") or []
    ad = doc.get("adoption") or {}
    out.append(f"fleet telemetry: {len(nodes)} nodes, "
               f"{ad.get('blocks', 0)} blocks tracked "
               f"({ad.get('fully_adopted_blocks', 0)} adopted by every "
               f"node)")
    out.append("")
    out.append("block adoption (seconds from first adoption; "
               "quantiles over blocks):")
    rows = [["time to 50% of nodes"] + _fmt_dist(ad.get("time_to_50")
                                                 or {}),
            ["time to 95% of nodes"] + _fmt_dist(ad.get("time_to_95")
                                                 or {})]
    out += _table(rows, ["quantity", "blocks", "p50", "p95", "max"])

    edges = doc.get("per_edge_delivery") or {}
    out.append("")
    if edges:
        out.append("per-edge delivery latency (receiver first-header-"
                   "seen minus sender adoption, seconds):")
        rows = [[edge] + _fmt_dist(edges[edge]) for edge in sorted(edges)]
        out += _table(rows, ["edge", "n", "p50", "p95", "max"])
    else:
        out.append("no per-edge deliveries recorded")

    parts = doc.get("partitions") or []
    if parts:
        out.append("")
        out.append("partition healing (first cross-group delivery "
                   "after the window):")
        rows = [[p.get("start"), p.get("end"),
                 _fmt_secs(p.get("healed_after_secs"))
                 if p.get("healed_after_secs") is not None
                 else "NEVER"] for p in parts]
        out += _table(rows, ["start", "end", "healed after (s)"])

    mux = doc.get("mux") or {}
    out.append("")
    if mux:
        out.append("per-peer mux accounting (edge|side; bytes are SDU "
                   "payload bytes):")
        rows = []
        for key in sorted(mux):
            m = mux[key]
            rows.append([key, m.get("egress_bytes"),
                         m.get("egress_sdus"), m.get("ingress_bytes"),
                         m.get("ingress_sdus")])
        out += _table(rows, ["connection", "out B", "out SDU",
                             "in B", "in SDU"])
    else:
        out.append("no mux accounting in this report")
    return "\n".join(out) + "\n"


# ---------------------------------------------------------------------------
# --flight: render a flight-recorder dump directory (ISSUE 14)
# ---------------------------------------------------------------------------

def load_flight(dir_path: str) -> tuple:
    """(header, records) from DIR/flight.jsonl (observe/flight.py dump
    layout).  Raises on a missing/garbled dump."""
    import os
    path = os.path.join(dir_path, "flight.jsonl")
    header: Optional[dict] = None
    records: List[dict] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if header is None and rec.get("kind") == "flight":
                header = rec
                continue
            records.append(rec)
    if header is None:
        raise ValueError(f"{path}: no flight header line")
    return header, records


def render_flight(header: dict, records: List[dict],
                  tail: int = 20) -> str:
    out: List[str] = []
    out.append(f"flight dump: {header.get('entries')} ring entries — "
               f"reason: {header.get('reason') or '(none)'}")

    # -- aggregated metric deltas -------------------------------------------
    deltas: dict = {}
    for r in records:
        if r.get("kind") != "metric":
            continue
        name, op, v = r.get("name"), r.get("op"), r.get("v")
        d = deltas.setdefault(name, {"inc": 0, "observe": 0,
                                     "set": None})
        if op == "inc":
            d["inc"] += v
        elif op == "observe":
            d["observe"] += 1
        elif op == "set":
            d["set"] = v
    out.append("")
    if deltas:
        out.append("metric deltas over the ring:")
        rows = []
        for name in sorted(deltas):
            d = deltas[name]
            what = []
            if d["inc"]:
                what.append(f"+{d['inc']}")
            if d["observe"]:
                what.append(f"{d['observe']} obs")
            if d["set"] is not None:
                what.append(f"last={d['set']}")
            rows.append([name, " ".join(what) or "-"])
        out += _table(rows, ["metric", "delta"])
    else:
        out.append("no metric entries in the ring")

    # -- span/event tail -----------------------------------------------------
    trail = [r for r in records if r.get("kind") in ("span", "event")]
    out.append("")
    out.append(f"last {min(tail, len(trail))} span/event entries "
               f"(of {len(trail)}):")
    for r in trail[-tail:]:
        if r.get("kind") == "span":
            out.append(f"  {r.get('t'):>14.6f}  span   "
                       f"[{r.get('cat')}] {r.get('name')} "
                       f"({(r.get('t1') - r.get('t0')):.6f}s)")
        else:
            detail = {k: v for k, v in r.items()
                      if k not in ("t", "kind")}
            out.append(f"  {r.get('t'):>14.6f}  event  "
                       f"{json.dumps(detail, sort_keys=True)[:120]}")
    return "\n".join(out) + "\n"


# ---------------------------------------------------------------------------
# --live: render a scraped exposition (replay progress + latency quantiles)
# ---------------------------------------------------------------------------

PROGRESS_GAUGES = (
    ("ouro_replay_progress_blocks_done", "blocks done"),
    ("ouro_replay_progress_total_blocks", "total blocks"),
    ("ouro_replay_progress_windows_in_flight", "windows in flight"),
    ("ouro_replay_progress_blocks_per_sec", "blocks/sec"),
    ("ouro_replay_progress_eta_secs", "ETA (s)"),
    ("ouro_replay_progress_hidden_frac", "hidden fraction"),
    ("ouro_replay_progress_devices", "mesh devices"),
    ("ouro_replay_progress_padding_waste_frac", "padding waste frac"),
)


def render_live(parsed: dict) -> str:
    """One live frame from a parsed exposition: replay progress, then
    p50/p95/p99 of every histogram present (recomputed scraper-side from
    the cumulative buckets — byte-identical to the serving process's own
    quantiles for the same counts)."""
    from ouroboros_tpu.observe.export import (
        prom_histogram_quantiles, prom_histograms,
    )
    out: List[str] = []
    prog = [(label, parsed[key]) for key, label in PROGRESS_GAUGES
            if key in parsed]
    if prog:
        done = parsed.get("ouro_replay_progress_blocks_done")
        total = parsed.get("ouro_replay_progress_total_blocks")
        if total:
            out.append(f"replay progress: {done:.0f}/{total:.0f} blocks "
                       f"({100 * done / total:.1f}%)")
        out.append("")
        out += _table([[l, v] for l, v in prog], ["progress", "value"])
    else:
        out.append("no replay.progress.* gauges in this exposition")
    out.append("")
    hists = prom_histograms(parsed)
    if hists:
        rows = []
        for base, count in sorted(hists.items()):
            if not count:
                continue               # nothing observed yet: skip
            q = prom_histogram_quantiles(parsed, base)
            rows.append([base, int(count), q["p50"], q["p95"], q["p99"]])
        out.append("latency/size histograms (quantiles from scraped "
                   "buckets):")
        out += _table(rows, ["histogram", "count", "p50", "p95", "p99"])
    return "\n".join(out) + "\n"


def _live_once(addr: str) -> str:
    """One scrape over the project transport: host:port dials TCP, a
    /path dials the Unix socket."""
    from ouroboros_tpu.network.snocket import snocket_for
    from ouroboros_tpu.observe.scrape import scrape
    from ouroboros_tpu.simharness import io_run
    if addr.startswith("/"):
        target: object = addr
    else:
        host, port = addr.rsplit(":", 1)
        target = (host, int(port))
    return io_run(scrape(snocket_for(target), target))


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m tools.obsreport",
        description="render a running node's scrape endpoint, a fleet "
                    "report or a flight-recorder dump")
    ap.add_argument("--live", metavar="ADDR",
                    help="scrape host:port (or /unix/path) and render "
                         "replay progress + latency quantiles")
    ap.add_argument("--interval", type=float, default=0.0,
                    help="with --live: re-scrape every N seconds until "
                         "interrupted (default: once)")
    ap.add_argument("--fleet", metavar="PATH",
                    help="render a FleetTelemetry report JSON (a chaos "
                         "run's ChaosResult.fleet)")
    ap.add_argument("--flight", metavar="DIR",
                    help="render a flight-recorder dump directory")
    ap.add_argument("--tail", type=int, default=20,
                    help="with --flight: span/event tail length "
                         "(default 20)")
    args = ap.parse_args(sys.argv[1:] if argv is None else argv)
    modes = [m for m in (args.live, args.fleet, args.flight)
             if m is not None]
    if len(modes) != 1:
        ap.print_usage(sys.stderr)
        print("obsreport: give exactly one of --live ADDR, --fleet PATH "
              "or --flight DIR", file=sys.stderr)
        return 2
    if args.fleet:
        try:
            doc = load_fleet(args.fleet)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"obsreport: cannot read {args.fleet}: {e}",
                  file=sys.stderr)
            return 2
        sys.stdout.write(render_fleet(doc))
        return 0
    if args.flight:
        try:
            header, records = load_flight(args.flight)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"obsreport: cannot read flight dump {args.flight}: "
                  f"{e}", file=sys.stderr)
            return 2
        sys.stdout.write(render_flight(header, records, tail=args.tail))
        return 0
    from ouroboros_tpu.observe.export import parse_prometheus_text
    try:
        while True:
            sys.stdout.write(
                render_live(parse_prometheus_text(
                    _live_once(args.live))))
            sys.stdout.flush()
            if args.interval <= 0:
                return 0
            import time
            time.sleep(args.interval)
            sys.stdout.write("\n")
    except KeyboardInterrupt:
        return 0
    except Exception as e:
        print(f"obsreport: cannot scrape {args.live}: {e}",
              file=sys.stderr)
        return 2


if __name__ == "__main__":
    raise SystemExit(main())
