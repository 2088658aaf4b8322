"""`BENCHMARK.json` and the data files it names.

The manifest names cells, metrics and `paths`; everything that belongs
to one configuration, one traffic mix or one per-layer metric is a file
found here by that name:

    configs/<config>.json          the deployment as it is run
    traffic/<traffic>.json         the chain generator's arguments
    layer_metrics/<metric>.json    layer, unit, `moves`, and a reader

`check()` is the manifest check `tests/test_manifest.py` runs; `run.py`
runs it before every run, so a malformed file stops a run before it
costs chip time.
"""
from __future__ import annotations

import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _load(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = _load(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, self.doc["paths"][0])

    # -- lookups ------------------------------------------------------------
    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return _load(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _load(os.path.join(self.bench_dir, "traffic", name + ".json"))

    def layer_metric(self, name: str) -> dict:
        return _load(os.path.join(self.bench_dir, "layer_metrics",
                                  name + ".json"))

    def metrics_for(self, workload: str, kind: str) -> list:
        """The `end_to_end` or `per_layer` entries this cell reports: an
        entry without a `workloads` key is reported by every cell."""
        return [m for m in self.doc[kind]
                if workload in m.get("workloads", [workload])]

    # -- the manifest check -------------------------------------------------
    def check(self) -> list:
        """Every fault found, as sentences; empty when the manifest and
        its data files are sound."""
        bad = []
        doc = self.doc

        def name_ok(what, s):
            if not isinstance(s, str) or not NAME_RE.match(s):
                bad.append(f"{what}: {s!r} is not a name (letters, digits, "
                           f"_ . -, at most 64)")

        e2e = {m["name"]: m for m in doc["end_to_end"]}
        cells = {w["name"]: w for w in doc["workloads"]}
        for kind in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [x["name"] for x in doc[kind]]
            for n in names:
                name_ok(kind, n)
            if len(set(names)) != len(names):
                bad.append(f"{kind}: a name appears twice")
        if "setup_s" not in e2e:
            bad.append("end_to_end lacks setup_s")
        for c in doc["configs"]:
            if not os.path.isfile(os.path.join(self.root, c["file"])):
                bad.append(f"config {c['name']}: no file {c['file']}")
            elif not any(w["config"] == c["name"] for w in cells.values()):
                bad.append(f"config {c['name']}: used by no cell")
        for w in cells.values():
            name_ok("traffic", w["traffic"])
            if w["chips"] not in (1, 4):
                bad.append(f"cell {w['name']}: chips {w['chips']}")
            if not (0 < len(w["why"]) <= 200) or "\n" in w["why"]:
                bad.append(f"cell {w['name']}: why must be one line of "
                           f"at most 200 characters")
            try:
                cfg = self.config(w["config"])
                if cfg["chips"] != w["chips"]:
                    bad.append(f"cell {w['name']}: asks {w['chips']} chips, "
                               f"its configuration runs on {cfg['chips']}")
                self.traffic(w["traffic"])
            except (KeyError, OSError, ValueError) as e:
                bad.append(f"cell {w['name']}: {e}")
        for m in doc["end_to_end"] + doc["per_layer"]:
            if not UNIT_RE.match(m.get("unit", "")):
                bad.append(f"metric {m['name']}: unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                bad.append(f"metric {m['name']}: better")
            if m.get("source") not in SOURCES:
                bad.append(f"metric {m['name']}: source")
            for w in m.get("workloads", []):
                if w not in cells:
                    bad.append(f"metric {m['name']}: unknown cell {w}")
        for m in doc["end_to_end"]:
            if not (0 < m.get("bound", 0) <= 0.25):
                bad.append(f"metric {m['name']}: bound")
            if m["source"] not in ("host_clock", "device_trace"):
                bad.append(f"metric {m['name']}: an end-to-end metric is "
                           f"taken by the benchmark itself")
        for m in doc["per_layer"]:
            target = e2e.get(m.get("moves"))
            if target is None:
                bad.append(f"metric {m['name']}: moves {m.get('moves')!r}, "
                           f"which is no end-to-end metric")
                continue
            # every cell that reports this metric reports its target
            reported_in = m.get("workloads", list(cells))
            target_in = target.get("workloads", list(cells))
            for w in reported_in:
                if w not in target_in:
                    bad.append(f"metric {m['name']}: cell {w} does not "
                               f"report {m['moves']}")
            try:
                f = self.layer_metric(m["name"])
            except (OSError, ValueError) as e:
                bad.append(f"metric {m['name']}: {e}")
                continue
            for k in ("layer", "unit", "moves", "better", "source"):
                if f.get(k) != m.get(k):
                    bad.append(f"metric {m['name']}: {k} is {m.get(k)!r} in "
                               f"BENCHMARK.json and {f.get(k)!r} in its file")
            if "reader" not in f:
                bad.append(f"metric {m['name']}: its file has no reader")
            if m["name"].endswith("_share") and m["unit"] != "%":
                bad.append(f"metric {m['name']}: a share is a percentage")
        for w in cells:
            if len(self.metrics_for(w, "end_to_end")) < 2:
                bad.append(f"cell {w}: needs setup_s and one more "
                           f"end-to-end metric")
            if not self.metrics_for(w, "per_layer"):
                bad.append(f"cell {w}: no per-layer metric")
        return bad


def check_values(metrics: dict) -> list:
    """Faults in a result's metric values: every `_share` is a
    percentage between 0 and 100."""
    return [f"{n} = {m['value']} is outside 0-100"
            for n, m in metrics.items()
            if n.endswith("_share") and isinstance(m["value"], (int, float))
            and not 0.0 <= m["value"] <= 100.0]
