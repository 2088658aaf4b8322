"""The two per-layer metrics of the Shelley ledger walk's transaction
counters (PR 37), through the general reader: a number from facts that
hold the counters, nothing from facts that lack them (a program before
PR 37 has no such counters, and its line leaves both metrics out)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import readers  # noqa: E402

METRICS = ("body_light_tx_share", "host_body_us_per_tx")


def _reader(metric):
    path = os.path.join(os.path.dirname(HERE), "layer_metrics",
                        metric + ".json")
    with open(path) as fh:
        return json.load(fh)["reader"]


def _facts(counters):
    return {"window": {"blocks": 512, "replays": 1},
            "span_seconds": {"seq.body": 0.5},
            "counter": dict(counters)}


@pytest.mark.parametrize("metric,want", [
    ("body_light_tx_share", 75.0),
    ("host_body_us_per_tx", 0.5 / 180224 * 1e6)])
def test_reads_a_number_where_the_counters_are(metric, want):
    facts = _facts({"ledger.shelley.txs": 180224,
                    "ledger.shelley.light_txs": 135168})
    assert readers.read(_reader(metric), facts) == pytest.approx(want)


@pytest.mark.parametrize("metric", METRICS)
def test_reads_nothing_where_the_counters_are_not(metric):
    # the parent's registry: other counters, none of the walk's
    facts = _facts({"precompute.filled_keys": 7})
    assert readers.read(_reader(metric), facts) is None
    # the counters there and nothing walked: no division by zero
    facts = _facts({"ledger.shelley.txs": 0, "ledger.shelley.light_txs": 0})
    assert readers.read(_reader(metric), facts) is None
