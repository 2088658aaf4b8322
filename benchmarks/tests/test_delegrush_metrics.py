"""The four per-layer metrics of the delegation cell (PR 45), through
the general reader: a number from the facts a rehearsal replay of
`sync-delegrush` gathers (64 blocks of 6 transactions in 8 windows of 8:
3 plain spends, 2 first delegations and 1 re-delegation a block, a pool
registered every 16th), nothing from facts that lack the counters (a
program before PR 45 has none of them, and its line leaves all four
out)."""
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
    "counter": {"ledger.shelley.txs": 384, "ledger.shelley.light_txs": 192,
                "ledger.shelley.cert_txs": 192,
                "ledger.shelley.certs.deleg": 188,
                "ledger.shelley.certs.pool": 4,
                "ledger.shelley.witnesses": 576,
                "ledger.shelley.deleg_new_entries": 132,
                "ledger.shelley.cert_us": 3415}}
WANT = {"body_cert_tx_share": 50.0,
        "witnesses_per_tx": 1.5,
        "deleg_new_entries_per_replay": 132.0,
        "cert_us_per_cert": 3415 / 192}


def _metric(name):
    path = os.path.join(os.path.dirname(HERE), "layer_metrics",
                        name + ".json")
    with open(path) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reads_a_number_from_a_rehearsal_replays_facts(name):
    got = readers.read(_metric(name)["reader"], REHEARSAL)
    assert got == pytest.approx(WANT[name])


def test_new_entries_are_counted_a_replay_not_a_window():
    # three whole replays in the window: the map still reaches 2 + 132
    facts = {"window": {**REHEARSAL["window"], "replays": 3},
             "counter": {k: 3 * v for k, v in REHEARSAL["counter"].items()}}
    got = readers.read(_metric("deleg_new_entries_per_replay")["reader"],
                       facts)
    assert got == pytest.approx(132.0)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reads_nothing_on_a_program_without_the_counters(name):
    # the parent's registry: the walk's two counters of PR 37, none of these
    parent = {"window": REHEARSAL["window"],
              "span_seconds": {"seq.body": 0.5},
              "counter": {"ledger.shelley.txs": 384,
                          "ledger.shelley.light_txs": 192}}
    assert readers.read(_metric(name)["reader"], parent) is None


@pytest.mark.parametrize("name", ["body_cert_tx_share", "witnesses_per_tx",
                                  "cert_us_per_cert"])
def test_a_chain_without_a_transaction_or_a_certificate_reads_nothing(name):
    # the counters there and nothing walked: no division by zero
    facts = {**REHEARSAL, "counter": {k: 0 for k in REHEARSAL["counter"]}}
    assert readers.read(_metric(name)["reader"], facts) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_the_metric_is_declared_for_the_cell_alone(name):
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    data = _metric(name)
    assert entry["workloads"] == ["sync-delegrush"]
    assert entry["moves"] == data["moves"] == "blocks_per_s"
    assert (entry["unit"], entry["layer"], entry["source"],
            entry["better"]) == (data["unit"], data["layer"],
                                 data["source"], data["better"])
