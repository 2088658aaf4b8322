"""The two per-layer metrics of the two-phase key fill (PR 46), through
the general reader, on made-up facts: a traced window of two windows in
which 180,232 distinct keys were filled, 180,224 of them begun ahead of
the lanes' hashing (the window path) and 8 by a plain `assemble` (the
header's VRF keys), and the producer stood 0.11 s in `fill.fetch`.  A
program before PR 46 has the span and not the counter: its line keeps
the wait and leaves the share out."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import readers  # noqa: E402

FACTS = {
    "window": {"replays": 1, "blocks": 512, "windows": 2},
    "span_seconds": {"precompute.fill": 0.26, "fill.pack": 0.03,
                     "fill.dispatch": 0.07, "fill.fetch": 0.11,
                     "fill.store": 0.05},
    "counter": {"precompute.filled_keys": 180232,
                "precompute.early_fill_keys": 180224,
                "precompute.fill_wait_us": 61000}}
WANT = {"key_fill_wait_ms_per_window": 55.0,
        "key_fill_early_share": 100 * 180224 / 180232}
NAMES = sorted(WANT)


def _metric(name):
    path = os.path.join(os.path.dirname(HERE), "layer_metrics",
                        name + ".json")
    with open(path) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", NAMES)
def test_reads_a_number_from_a_traced_windows_facts(name):
    got = readers.read(_metric(name)["reader"], FACTS)
    assert got == pytest.approx(WANT[name])


def test_the_parent_reports_the_wait_and_not_the_share():
    # the span was there before PR 46 (one blocking round trip a fill);
    # the counter was not
    parent = {"window": FACTS["window"],
              "span_seconds": {**FACTS["span_seconds"], "fill.fetch": 0.4},
              "counter": {"precompute.filled_keys": 180232}}
    assert readers.read(_metric("key_fill_wait_ms_per_window")["reader"],
                        parent) == pytest.approx(200.0)
    assert readers.read(_metric("key_fill_early_share")["reader"],
                        parent) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_window_that_filled_no_key_reads_nothing(name):
    # every lane a hit: no fill span opened, the counters there and flat
    warm = {"window": FACTS["window"], "span_seconds": {},
            "counter": {"precompute.filled_keys": 0,
                        "precompute.early_fill_keys": 0}}
    assert readers.read(_metric(name)["reader"], warm) is None


def test_fills_that_were_all_waited_for_at_once_read_zero():
    # keys filled, none begun ahead: the simple batch calls
    facts = {**FACTS, "counter": {"precompute.filled_keys": 40,
                                  "precompute.early_fill_keys": 0}}
    assert readers.read(_metric("key_fill_early_share")["reader"],
                        facts) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_is_declared_for_the_two_cells_that_fill(name):
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    data = _metric(name)
    assert entry["workloads"] == ["sync-freshkeys", "sync-delegrush"]
    assert entry["moves"] == data["moves"] == "blocks_per_s"
    assert (entry["unit"], entry["layer"], entry["source"],
            entry["better"]) == (data["unit"], data["layer"],
                                 data["source"], data["better"])
    assert bench["per_layer"].index(entry) >= len(bench["per_layer"]) - 2
