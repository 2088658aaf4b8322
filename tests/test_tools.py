"""db-synth + db-analyser CLI smoke tests (the db-analyser test surface +
validate-mainnet CI gate shape, SURVEY.md §3.5/§4.5)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv):
    return subprocess.run([sys.executable, *argv], cwd=REPO,
                          capture_output=True, text=True, timeout=300)


@pytest.fixture(scope="module")
def synth_db(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("synthdb"))
    r = _run("tools/db_synth.py", "--out", d, "--blocks", "40",
             "--txs-per-block", "1", "--nodes", "2")
    assert r.returncode == 0, r.stderr
    info = json.loads(r.stdout.strip().splitlines()[-1])
    assert info["blocks"] == 40
    return d


def test_show_slot_block_no(synth_db):
    r = _run("tools/db_analyser.py", synth_db,
             "--analysis", "show-slot-block-no")
    assert r.returncode == 0, r.stderr
    lines = r.stdout.strip().splitlines()
    assert len(lines) == 40
    block_nos = [int(l.split("\t")[1]) for l in lines]
    assert block_nos == list(range(40))


def test_count_tx_outputs(synth_db):
    r = _run("tools/db_analyser.py", synth_db,
             "--analysis", "count-tx-outputs")
    assert r.returncode == 0, r.stderr
    info = json.loads(r.stdout)
    assert info["blocks"] == 40 and info["txs"] == 40


def test_validate_reapply_and_full_agree(synth_db):
    r1 = _run("tools/db_analyser.py", synth_db, "--validate", "reapply")
    assert r1.returncode == 0, r1.stderr
    r2 = _run("tools/db_analyser.py", synth_db, "--validate", "full",
              "--backend", "openssl", "--window", "16")
    assert r2.returncode == 0, r2.stderr
    h1 = json.loads(r1.stdout)["state_hash"]
    h2 = json.loads(r2.stdout)["state_hash"]
    assert h1 == h2, "full validation and reapply disagree on final state"


def test_validate_snapshot_every_and_resume(synth_db, tmp_path):
    """ISSUE 15: `--snapshot-every` checkpoints the verified state
    during full validation (crash-consistent LedgerDB snapshots in the
    DB dir) and `--resume` restarts from the newest one — replaying
    ZERO blocks to the same state hash, reporting where it resumed."""
    import shutil
    d = str(tmp_path / "snapdb")
    shutil.copytree(synth_db, d)
    r1 = _run("tools/db_analyser.py", d, "--validate", "full",
              "--backend", "openssl", "--window", "16",
              "--snapshot-every", "10")
    assert r1.returncode == 0, r1.stderr
    i1 = json.loads(r1.stdout)
    assert i1["blocks"] == 40
    assert i1["stream"]["snapshots_written"] >= 2
    snaps = sorted(os.listdir(os.path.join(d, "ledger")))
    assert snaps and all(n.startswith("snap-") for n in snaps)
    r2 = _run("tools/db_analyser.py", d, "--validate", "full",
              "--backend", "openssl", "--window", "16", "--resume")
    assert r2.returncode == 0, r2.stderr
    i2 = json.loads(r2.stdout)
    assert i2["state_hash"] == i1["state_hash"]
    assert i2["blocks"] == 0                      # nothing re-replayed
    assert i2["stream"]["resumed_from_slot"] is not None
    # plain validation (no flags) stays read-only: no ledger/ dir
    d2 = str(tmp_path / "plaindb")
    shutil.copytree(synth_db, d2)
    r3 = _run("tools/db_analyser.py", d2, "--validate", "full",
              "--backend", "openssl", "--window", "16")
    assert r3.returncode == 0, r3.stderr
    assert json.loads(r3.stdout)["state_hash"] == i1["state_hash"]
    assert not os.path.exists(os.path.join(d2, "ledger"))


def test_validate_detects_corruption(synth_db, tmp_path):
    import shutil
    bad = str(tmp_path / "bad")
    shutil.copytree(synth_db, bad)
    # flip a byte mid-way through the first chunk file
    chunk = os.path.join(bad, "immutable", "00000.chunk")
    with open(chunk, "r+b") as f:
        f.seek(os.path.getsize(chunk) // 2)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    r = _run("tools/db_analyser.py", bad, "--validate", "full",
             "--backend", "openssl", "--window", "16")
    assert r.returncode != 0, "corrupted chain validated successfully"


# ---------------------------------------------------------------------------
# Shelley-path replay (the flagship/BASELINE harness, VERDICT r1 #1)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def shelley_db(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("shelleydb"))
    r = _run("tools/db_synth.py", "--out", d, "--protocol", "shelley",
             "--blocks", "30", "--txs-per-block", "2",
             "--epoch-length", "40", "--pools", "2")
    assert r.returncode == 0, r.stderr
    info = json.loads(r.stdout.strip().splitlines()[-1])
    assert info["blocks"] == 30
    return d


def test_shelley_replay_full_vs_reapply(shelley_db):
    r1 = _run("tools/db_analyser.py", shelley_db, "--validate", "reapply")
    assert r1.returncode == 0, r1.stderr
    r2 = _run("tools/db_analyser.py", shelley_db, "--validate", "full",
              "--backend", "openssl", "--window", "16")
    assert r2.returncode == 0, r2.stderr
    i1, i2 = json.loads(r1.stdout), json.loads(r2.stdout)
    assert i1["state_hash"] == i2["state_hash"]
    # 2 VRF + KES + OCert per header, 2 witnesses per body
    assert i2["proofs"] == 30 * (4 + 2)


def test_shelley_replay_backend_parity(shelley_db):
    """cpp backend replays the same chain to the same state hash."""
    r1 = _run("tools/db_analyser.py", shelley_db, "--validate", "full",
              "--backend", "cpp", "--window", "16")
    assert r1.returncode == 0, r1.stderr
    r2 = _run("tools/db_analyser.py", shelley_db, "--validate", "full",
              "--backend", "openssl", "--window", "16")
    assert r2.returncode == 0, r2.stderr
    assert (json.loads(r1.stdout)["state_hash"]
            == json.loads(r2.stdout)["state_hash"])


def test_obsreport_live_flag_wired():
    r = _run("-m", "tools.obsreport", "--help")
    assert r.returncode == 0, r.stderr
    assert "--live" in r.stdout and "--interval" in r.stdout
    # --live against a dead port is a clean rc 2, not a traceback
    r2 = _run("-m", "tools.obsreport", "--live", "127.0.0.1:1")
    assert r2.returncode == 2 and "cannot scrape" in r2.stderr
    # no mode at all is a usage error
    r3 = _run("-m", "tools.obsreport")
    assert r3.returncode == 2


def test_obsreport_fleet_renderer(tmp_path):
    """--fleet renders a FleetTelemetry report (bare dict or one nested
    under a dumped ChaosResult's `fleet` key); junk is rc 2."""
    fleet = {
        "nodes": ["node0", "node1"],
        "adoption": {"blocks": 3, "fully_adopted_blocks": 2,
                     "time_to_50": {"n": 3, "p50": 0.1, "p95": 0.2,
                                    "max": 0.2},
                     "time_to_95": {"n": 2, "p50": 0.3, "p95": 0.5,
                                    "max": 0.5},
                     "per_block": []},
        "per_edge_delivery": {"node0->node1": {"n": 4, "p50": 0.05,
                                               "p95": 0.07,
                                               "max": 0.07}},
        "partitions": [{"start": 3.0, "end": 5.0,
                        "healed_after_secs": 0.42},
                       {"start": 9.0, "end": 11.0,
                        "healed_after_secs": None}],
        "mux": {"node0->node1|i": {"ingress_bytes": 100,
                                   "egress_bytes": 200,
                                   "ingress_sdus": 2, "egress_sdus": 3,
                                   "by_proto": {}}},
    }
    bare = tmp_path / "fleet.json"
    bare.write_text(json.dumps(fleet))
    wrapped = tmp_path / "chaos.json"
    wrapped.write_text(json.dumps({"seed": 7, "fleet": fleet}))
    for p in (bare, wrapped):
        r = _run("-m", "tools.obsreport", "--fleet", str(p))
        assert r.returncode == 0, r.stderr
        assert "2 nodes, 3 blocks tracked" in r.stdout
        assert "time to 95% of nodes" in r.stdout
        assert "node0->node1" in r.stdout
        assert "0.4200" in r.stdout and "NEVER" in r.stdout
        assert "node0->node1|i" in r.stdout
    bad = tmp_path / "junk.json"
    bad.write_text('{"not": "a fleet report"}')
    r = _run("-m", "tools.obsreport", "--fleet", str(bad))
    assert r.returncode == 2 and "cannot read" in r.stderr


def test_obsreport_flight_renderer(tmp_path):
    """--flight renders a flight-recorder dump dir: reason header,
    aggregated metric deltas, span/event tail.  A dir without a dump is
    rc 2."""
    from ouroboros_tpu.observe import flight as fl
    from ouroboros_tpu.observe import metrics as om
    from ouroboros_tpu.observe import spans as sp
    reg = om.MetricsRegistry()
    rec = sp.SpanRecorder()
    f = fl.FlightRecorder(registry=reg, recorder=rec)
    f.arm()
    try:
        c = reg.counter("probe.count")
        c.inc(3)
        c.inc(2)
        reg.gauge("probe.gauge").set(7)
        with rec.span("w", cat="device"):
            pass
        f.note(("tail", "event"))
        d = tmp_path / "dump"
        f.dump(str(d), reason="unit probe")
    finally:
        f.disarm()
    r = _run("-m", "tools.obsreport", "--flight", str(d))
    assert r.returncode == 0, r.stderr
    assert "reason: unit probe" in r.stdout
    assert "probe.count" in r.stdout and "+5" in r.stdout
    assert "last=7" in r.stdout
    assert "[device] w" in r.stdout
    r2 = _run("-m", "tools.obsreport", "--flight", str(tmp_path / "no"))
    assert r2.returncode == 2 and "cannot read flight dump" in r2.stderr


def test_obsreport_cli(tmp_path):
    """Exactly one of --live, --fleet, --flight: two together, or a
    positional file, are a usage error (rc 2), not a traceback."""
    fleet = tmp_path / "fleet.json"
    fleet.write_text("{}")
    r = _run("-m", "tools.obsreport", "--fleet", str(fleet),
             "--flight", str(tmp_path))
    assert r.returncode == 2 and "exactly one of" in r.stderr
    r = _run("-m", "tools.obsreport", str(fleet))
    assert r.returncode == 2 and "Traceback" not in r.stderr


def test_shelley_replay_detects_tamper(shelley_db, tmp_path):
    import shutil
    bad = str(tmp_path / "badsh")
    shutil.copytree(shelley_db, bad)
    chunk = os.path.join(bad, "immutable", "00000.chunk")
    with open(chunk, "r+b") as f:
        f.seek(os.path.getsize(chunk) // 2)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    r = _run("tools/db_analyser.py", bad, "--validate", "full",
             "--backend", "openssl", "--window", "16")
    assert r.returncode != 0


# ---------------------------------------------------------------------------
# Cardano (Byron->Shelley) cross-fork replay (BASELINE config #5)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cardano_db(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("cardanodb"))
    r = _run("tools/db_synth.py", "--out", d, "--protocol", "cardano",
             "--blocks", "60", "--epoch-length", "10", "--pools", "2")
    assert r.returncode == 0, r.stderr
    info = json.loads(r.stdout.strip().splitlines()[-1])
    assert info["blocks"] == 60 and info["fork_epoch"] >= 1
    return d


def test_cardano_replay_crosses_fork_with_parity(cardano_db):
    r1 = _run("tools/db_analyser.py", cardano_db, "--validate", "full",
              "--backend", "cpp", "--window", "16")
    assert r1.returncode == 0, r1.stderr
    r2 = _run("tools/db_analyser.py", cardano_db, "--validate", "reapply")
    assert r2.returncode == 0, r2.stderr
    i1, i2 = json.loads(r1.stdout), json.loads(r2.stdout)
    assert i1["state_hash"] == i2["state_hash"]


def test_cardano_chain_has_both_eras_and_ebbs(cardano_db):
    r = _run("tools/db_analyser.py", cardano_db,
             "--analysis", "show-slot-block-no")
    assert r.returncode == 0, r.stderr
    # EBBs share their successor's slot: expect at least one duplicate slot
    slots = [int(l.split("\t")[0]) for l in r.stdout.strip().splitlines()]
    assert len(slots) != len(set(slots)), "no EBB/successor slot pair"


def test_cardano_chain_crosses_the_full_era_ladder(cardano_db):
    """The synthesized cardano chain spans Byron->Shelley->Allegra->Mary
    (Cardano/Block.hs:161-186) with the feature txs in the later eras, and
    full validation replays it."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "dba_t", os.path.join(REPO, "tools", "db_analyser.py"))
    dba = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(dba)
    db, rules, decode, cfg = dba.load_db(cardano_db)
    eras_seen = set()
    mint = validity = 0
    for _e, raw in db.stream():
        b = decode(raw)
        eras_seen.add(b.header.get("hfc_era", 0))
        for tx in b.body:
            mint += bool(getattr(tx, "mint", ()))
            validity += bool(getattr(tx, "validity", ()))
    assert eras_seen == {0, 1, 2, 3}, eras_seen
    assert mint >= 1 and validity >= 1
