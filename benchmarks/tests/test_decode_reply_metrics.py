"""The three per-layer metrics of a decode worker's reply (PR 39),
through the general reader, from the counters of a chain streamed here
in the sandbox (no JAX, no chip: the prefetcher and its decode workers
alone): a number each where the chain was decoded in the workers,
`decode_txid_shipped_share` 100 through the pool and 0 where the decoder
does not ship and the prefetch thread decoded, and nothing from facts
that lack the counters (a program before PR 39 has none of them, and its
line leaves all three metrics out)."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

from harness import readers  # noqa: E402

METRICS = ("decode_reply_kb_per_block", "decode_reply_reads_per_block",
           "decode_txid_shipped_share")
BLOCKS, TXS = 24, 7


def _reader(metric):
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as fh:
        return json.load(fh)["reader"]


@pytest.fixture(scope="module")
def chain(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("replydb"))
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "db_synth.py"),
         "--out", d, "--protocol", "shelley", "--blocks", str(BLOCKS),
         "--txs-per-block", str(TXS), "--pools", "2", "--f", "4/5",
         "--epoch-length", "500", "--kes-depth", "4", "--chunk-size", "4"],
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    from tools import db_analyser
    db, _rules, decode, _cfg = db_analyser.load_db(d)
    return db, decode


def _streamed_facts(db, decode) -> dict:
    """The facts of one pass of the prefetcher over the chain, as
    `run.py` gathers them: counters as deltas over the pass."""
    from ouroboros_tpu import observe
    from ouroboros_tpu.storage.stream import BlockPrefetcher

    def counters():
        return {i.name: i.value for i in observe.REGISTRY.instruments()
                if i.kind == "counter"}
    c0 = counters()
    pre = BlockPrefetcher(db, decode, window=8, depth=2).start()
    try:
        blocks = list(pre)
    finally:
        pre.close()
    c1 = counters()
    assert len(blocks) == BLOCKS
    return {"window": {"blocks": len(blocks), "replays": 1},
            "counter": {k: c1[k] - c0.get(k, 0) for k in c1}}


def test_a_chain_decoded_in_the_workers_reports_all_three(chain):
    db, decode = chain
    facts = _streamed_facts(db, decode)
    got = {m: readers.read(_reader(m), facts) for m in METRICS}
    assert got["decode_txid_shipped_share"] == 100.0
    raw_kb = sum(len(raw) for _e, raw in db.stream()) / BLOCKS / 1e3
    # the built blocks and each header's own bytes (half of a block of
    # so few transactions), never the block's bytes besides
    assert raw_kb < got["decode_reply_kb_per_block"] < 2 * raw_kb
    chunks = len(db.chunk_numbers())
    # one read a reply that was waiting, one look more for one that was not
    assert chunks / BLOCKS <= got["decode_reply_reads_per_block"] \
        <= 3 * chunks / BLOCKS


def test_a_decoder_that_does_not_ship_reads_zero_shipped(chain):
    db, decode = chain
    facts = _streamed_facts(db, lambda raw: decode(raw))
    assert facts["counter"]["replay.decode.txs"] == BLOCKS * TXS
    assert readers.read(_reader("decode_txid_shipped_share"), facts) == 0.0
    assert readers.read(_reader("decode_reply_kb_per_block"), facts) == 0.0
    assert readers.read(_reader("decode_reply_reads_per_block"), facts) == 0.0


@pytest.mark.parametrize("metric", METRICS)
def test_reads_nothing_where_the_counters_are_not(metric):
    # the parent's registry: other counters, none of the reply's
    facts = {"window": {"blocks": 512, "replays": 1},
             "counter": {"replay.decode.worker_blocks": 512}}
    assert readers.read(_reader(metric), facts) is None


def test_a_chain_with_no_transaction_reports_no_share():
    facts = {"window": {"blocks": 512, "replays": 1},
             "counter": {"replay.decode.txs": 0,
                         "replay.decode.shipped_txids": 0}}
    assert readers.read(_reader("decode_txid_shipped_share"), facts) is None
