"""The six per-layer metrics of the hard-fork cell (PR 40), through the
general reader: a number from the facts a rehearsal replay of
`sync-hardfork` gathers (64 blocks in 8 windows of 8, 44 of them Byron,
the fork inside window 5), nothing from facts that lack the counters and
the span (a program before PR 40 has none of them, and its line leaves
all six out)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import readers  # noqa: E402

# one whole replay of the rehearsal's chain, as `gather_facts` hands it on
REHEARSAL = {
    "window": {"replays": 1, "blocks": 64, "windows": 8},
    "span_seconds": {"hfc.translate": 0.000086},
    "counter": {"hfc.era_blocks.byron": 44, "hfc.era_blocks.shelley": 20,
                "hfc.era_host_us.byron": 7180, "hfc.era_host_us.shelley": 4895,
                "hfc.mixed_windows": 1,
                "jax_backend.composite_free_windows": 3}}
WANT = {"byron_block_share": 68.75,
        "byron_host_us_per_block": 7180 / 44,
        "shelley_host_us_per_block": 4895 / 20,
        "era_translate_ms_each": 0.086,
        "composite_free_window_share": 37.5,
        "era_mixed_window_share": 12.5}


def _metric(name):
    path = os.path.join(os.path.dirname(HERE), "layer_metrics",
                        name + ".json")
    with open(path) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reads_a_number_from_a_rehearsal_replays_facts(name):
    got = readers.read(_metric(name)["reader"], REHEARSAL)
    assert got == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reads_nothing_on_a_program_without_the_counters(name):
    # the parent's registry and spans: other names, none of these
    parent = {"window": REHEARSAL["window"],
              "span_seconds": {"window.host_seq": 0.5},
              "counter": {"jax_backend.windows_submitted": 8,
                          "ledger.shelley.txs": 20}}
    assert readers.read(_metric(name)["reader"], parent) is None


@pytest.mark.parametrize("name", ["byron_host_us_per_block",
                                  "shelley_host_us_per_block"])
def test_an_era_without_a_block_reads_nothing(name):
    # a Shelley-only chain through the combinator: the counters are there
    # and one era's stand at 0; no division by zero
    facts = {**REHEARSAL, "counter": {k: 0 for k in REHEARSAL["counter"]}}
    assert readers.read(_metric(name)["reader"], facts) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_the_metric_is_declared_for_the_cell_alone(name):
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    data = _metric(name)
    assert entry["workloads"] == ["sync-hardfork"]
    assert entry["moves"] == data["moves"] == "blocks_per_s"
    assert (entry["unit"], entry["layer"], entry["source"],
            entry["better"]) == (data["unit"], data["layer"],
                                 data["source"], data["better"])
