"""db-synth + db-analyser CLI smoke tests (the db-analyser test surface +
validate-mainnet CI gate shape, SURVEY.md §3.5/§4.5)."""
import json
import os
import subprocess
import sys
import types

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


@pytest.mark.device
def test_bench_smoke_parity_gate():
    """`bench --smoke` in-process: the tier-1 guard that keeps the
    replay hot path honest between bench rounds — tiny synth chain, one
    JAX replay (the threaded producer/consumer pipeline with the device
    verdict fold) vs the CPU baseline (state-hash parity + cross-window
    key reuse), a cold+warm corrupted mixed batch (verdict parity in
    both vector and fold form + zero warm-path fill dispatches), the
    producer-thread shutdown check, the overlap-attribution plumbing
    probe, and the fenced vrf-spread gate."""
    pytest.importorskip("jax")
    sys.path.insert(0, REPO)
    import bench
    res = bench.smoke()
    assert res["state_hash_parity"] and res["verdict_parity"]
    assert res["fold_verdict_parity"]
    assert res["pipelined_producers_run"] >= 1
    assert res["producer_threads_leaked"] == 0
    assert res["overlap_probe"]["host_seq_secs"] > 0
    assert res["vrf_spread_probe"]["ok"]
    assert res["warm_device_fills"] == 0 and res["warm_kes_jobs"] == 0
    # ISSUE 9: tier-1 gates the scrape endpoint and the perf trajectory
    assert res["scrape_roundtrip"] and res["scrape_threads_leaked"] == 0
    q = res["scrape_submit_drain_quantiles"]
    assert 0 < q["p50"] <= q["p95"] <= q["p99"]
    assert res["perfgate_ok"]
    # ISSUE 11: the sharded parity probe either ran green or recorded
    # WHY it was skipped (host-platform devices: a sharded composite
    # compiles for minutes on XLA:CPU)
    sh = res["sharded_replay_smoke"]
    assert sh["ok"] is True
    assert sh.get("skipped") or sh["producer_threads_leaked"] == 0
    # ISSUE 12: the verification-service serve probe (seeded bursty sim
    # traces through the adaptive micro-batching coalescer) — >=5x the
    # unbatched per-request CPU baseline at saturation with p95 inside
    # the deadline, CPU fallback with ZERO device dispatches under
    # light load, back-pressure contract honored, byte-identical
    # verdicts and zero leaked sim threads on every leg
    sv = res["serve_probe"]
    assert sv["ok"] is True
    assert sv["saturated"]["vs_unbatched_cpu"] >= 5.0
    assert sv["saturated"]["p95_within_deadline"] is True
    assert sv["saturated"]["parity"] is True
    assert sv["light_load"]["device_batches"] == 0
    assert sv["light_load"]["parity"] is True
    assert sv["backpressure"]["backpressure_waits"] > 0
    assert sv["backpressure"]["parity"] is True
    for leg in ("saturated", "light_load", "backpressure"):
        assert sv[leg]["leaked_threads"] == 0
    # ISSUE 15: the streaming-engine probe — the same smoke chain
    # replayed FROM DISK through storage/stream.py (prefetch thread +
    # snapshots) at an already-compiled window shape, then a resumed
    # reopen restoring the tip checkpoint to the same hash
    st = res["stream_probe"]
    assert st["ok"] is True
    assert st["state_hash_parity"] and st["resume_parity"]
    assert st["threads_leaked"] == 0
    assert st["stats"]["chunks_read"] >= 1
    assert st["stats"]["snapshots_written"] >= 1
    assert res["blocks"] == 8


def test_bench_cli_flags_exist():
    """--smoke/--retune/--serve are wired (driver + CI call them
    blind)."""
    r = _run("bench.py", "--help")
    assert r.returncode == 0, r.stderr
    assert "--smoke" in r.stdout and "--retune" in r.stdout
    assert "--serve" in r.stdout


# ---------------------------------------------------------------------------
# perfgate: the BENCH trajectory as an enforced gate (ISSUE 9)
# ---------------------------------------------------------------------------

def _write_bench_history(d) -> list:
    """A short synthetic BENCH trajectory r01..r05 in the shape bench.py
    prints and tools/perfgate.py reads, harness-wrapped like a recorded
    round.  (The committed rounds were deleted in PR 22: they were
    measured on a device that no longer exists; git history holds them.)
    The early rounds lack the spread/overlap sections, as early recorded
    rounds did, and no round carries phases/variance/serve/stream."""
    rounds = [
        {"vs_baseline": 2.0},
        {"vs_baseline": 5.3, "blocks_per_sec": 1100.0,
         "state_hash_parity": True},
        {"vs_baseline": 2.6, "blocks_per_sec": 500.0,
         "state_hash_parity": True},
        {"vs_baseline": 5.7, "reps": 5, "spread": 0.55,
         "state_hash_parity": True,
         "replay_secs": {"median": 8.0, "min": 6.0, "max": 10.4}},
        {"vs_baseline": 12.1, "reps": 5, "spread": 0.285,
         "state_hash_parity": True, "blocks_per_sec": 2100.0,
         "cpu_baseline_proofs_per_sec": 1060.0,
         "replay_secs": {"median": 4.7, "min": 4.5, "max": 5.8},
         "cpu_replay_secs": {"median": 56.5, "spread": 0.256},
         "breakdown": {"device_secs": 3.8, "host_secs": 0.9},
         "kernel_choices": {"ed@4096": "xla", "vrf@2048": "pallas",
                            "win@4096@2048@2048@16384": "pallas"},
         "primitives": {"ed25519_batch_per_sec": 19000.0,
                        "ed25519_spread": 0.167,
                        "vrf_batch_per_sec": 8500.0, "vrf_spread": 0.45,
                        "kes_batch_per_sec": 11000.0,
                        "kes_spread": 0.294}},
    ]
    paths = []
    for n, fields in enumerate(rounds, 1):
        doc = {"metric": "shelley_replay_proofs_per_sec",
               "value": 1000.0 * fields["vs_baseline"],
               "unit": "proofs/s", **fields}
        path = d / f"BENCH_r{n:02d}.json"
        path.write_text(json.dumps({"n": n, "rc": 0, "parsed": doc}))
        paths.append(str(path))
    return paths


@pytest.fixture(scope="module")
def history(tmp_path_factory):
    """Synthetic recorded rounds (`.bench` and `.multichip` paths), the
    five of each that predate every later section of the gates — the
    MULTICHIP ones green with no MULTICHIP_OBS line, the last a red
    rc=124."""
    d = tmp_path_factory.mktemp("rounds")
    bench = _write_bench_history(d)
    multichip = [_multichip_round(d, n, 124 if n == 5 else 0)
                 for n in range(1, 6)]
    return types.SimpleNamespace(bench=bench, multichip=multichip)


def test_perfgate_passes_on_committed_trajectory(history):
    """Acceptance: rc 0 over a recorded-shape BENCH_r01..r05 history."""
    rounds = history.bench
    assert len(rounds) >= 5
    r = _run("-m", "tools.perfgate", "--check", *rounds)
    assert r.returncode == 0, r.stdout + r.stderr
    verdict = json.loads(r.stdout)
    assert verdict["ok"] is True
    results = {c["check"]: c["result"] for c in verdict["checks"]}
    assert results["vs_baseline"] == "pass"


def _regressed_round(tmp_path, **fields):
    d = tmp_path / "traj"
    d.mkdir()
    _write_bench_history(d)
    doc = {"metric": "shelley_replay_proofs_per_sec", "value": 5000.0,
           "unit": "proofs/s", **fields}
    (d / "BENCH_r06.json").write_text(
        json.dumps({"n": 6, "rc": 0, "parsed": doc}))
    return sorted(str(p) for p in d.glob("BENCH_r0*.json"))


def test_perfgate_fails_on_synthetic_regressed_round(tmp_path):
    """Acceptance: a regressed r06 (vs_baseline dropped past the floor,
    spread blown, hidden_frac collapsed) exits rc 1 with every check
    named FAIL."""
    paths = _regressed_round(tmp_path, vs_baseline=6.0, spread=0.6,
                             overlap={"hidden_frac_median": 0.05})
    r = _run("-m", "tools.perfgate", "--check", *paths)
    assert r.returncode == 1, r.stdout + r.stderr
    results = {c["check"]: c["result"]
               for c in json.loads(r.stdout)["checks"]}
    assert results == {"vs_baseline": "FAIL", "rep_spread": "FAIL",
                       "hidden_frac": "FAIL"}


def test_perfgate_single_check_failure_and_thresholds(tmp_path):
    """A round that only regresses spread fails exactly that check, and
    a loosened threshold flips it back to rc 0 (thresholds are real
    knobs, not decoration)."""
    paths = _regressed_round(tmp_path, vs_baseline=13.0, spread=0.6)
    r = _run("-m", "tools.perfgate", "--check", *paths)
    assert r.returncode == 1
    results = {c["check"]: c["result"]
               for c in json.loads(r.stdout)["checks"]}
    assert results["vs_baseline"] == "pass"
    assert results["rep_spread"] == "FAIL"
    assert results["hidden_frac"] == "skipped"
    r2 = _run("-m", "tools.perfgate", "--max-spread", "0.7",
              "--check", *paths)
    assert r2.returncode == 0, r2.stdout


def test_perfgate_tightened_spread_binds_from_r06(tmp_path, history):
    """ISSUE 12 satellite: the rep-spread bound tightened 0.45 -> 0.35
    now that the GC-discipline fix (PR 8) and the ('vrff', m) autotune
    key (PR 11) landed.  A 0.40-spread r06 — fine under the old bound —
    fails; the committed r01-r05 history stays tolerated (the legacy
    bound applies to rounds predating the variance fixes)."""
    paths = _regressed_round(tmp_path, vs_baseline=13.0, spread=0.40)
    r = _run("-m", "tools.perfgate", "--check", *paths)
    assert r.returncode == 1, r.stdout + r.stderr
    results = {c["check"]: c["result"]
               for c in json.loads(r.stdout)["checks"]}
    assert results["rep_spread"] == "FAIL"
    assert results["vs_baseline"] == "pass"
    # history alone (latest = r05) still passes under the legacy bound
    r2 = _run("-m", "tools.perfgate", "--check", *history.bench)
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_perfgate_unreadable_input_is_rc2(tmp_path):
    bad = tmp_path / "BENCH_r99.json"
    bad.write_text("not json")
    r = _run("-m", "tools.perfgate", "--check", str(bad))
    assert r.returncode == 2 and "cannot judge" in r.stderr
    r2 = _run("-m", "tools.perfgate")
    assert r2.returncode == 2


# ---------------------------------------------------------------------------
# perfgate --multichip: the mesh-dryrun trajectory as a gate (ISSUE 11)
# ---------------------------------------------------------------------------

def _multichip_round(tmp_path, n, rc, obs=None):
    tail = "harness noise\n"
    if obs is not None:
        tail += "MULTICHIP_OBS " + json.dumps(obs) + "\nmore noise\n"
    p = tmp_path / f"MULTICHIP_r{n:02d}.json"
    p.write_text(json.dumps({"n_devices": 8, "rc": rc, "ok": rc == 0,
                             "skipped": False, "tail": tail}))
    return str(p)


_GREEN_OBS = {"n_devices": 8, "prewarm_compile_secs": 201.3,
              "sharded_validate_compile_secs": 55.0,
              "state_hash_parity": True,
              "sharded_replay": {"blocks": 24, "proofs": 96,
                                 "proofs_per_sec": 140.0,
                                 "state_hash_parity": True}}


def test_perfgate_multichip_tolerates_presharded_history(history):
    """MULTICHIP_r01..r05-shaped rounds that predate the sharded replay
    (r05 a red rc=124 with no MULTICHIP_OBS at all): the gate reports
    every check skipped and passes — tier-1 must not fail retroactively
    on history the gate could never have enforced."""
    rounds = history.multichip
    assert len(rounds) >= 5
    r = _run("-m", "tools.perfgate", "--multichip", *rounds)
    assert r.returncode == 0, r.stdout + r.stderr
    mc = json.loads(r.stdout)["multichip"]
    assert mc["ok"] is True and mc["binding"] is False
    assert {c["result"] for c in mc["checks"]} == {"skipped"}


def test_perfgate_multichip_green_round_binds_and_passes(tmp_path):
    """A green r06 carrying the sharded_replay obs makes the gate
    binding: rc, compile attribution and parity all pass (rc 0)."""
    paths = [_multichip_round(tmp_path, 5, 124),
             _multichip_round(tmp_path, 6, 0, obs=_GREEN_OBS)]
    r = _run("-m", "tools.perfgate", "--multichip", *paths)
    assert r.returncode == 0, r.stdout + r.stderr
    mc = json.loads(r.stdout)["multichip"]
    assert mc["binding"] is True
    assert {c["check"]: c["result"] for c in mc["checks"]} == {
        "rc": "pass", "compile_attribution": "pass",
        "sharded_replay_parity": "pass"}


def test_perfgate_multichip_fails_red_round_after_green(tmp_path):
    """Once a green sharded round is recorded, a later red (timeout
    with no OBS line) fails every check — the MULTICHIP_r05 failure
    mode becomes a merge-gate regression instead of a shrug."""
    paths = [_multichip_round(tmp_path, 6, 0, obs=_GREEN_OBS),
             _multichip_round(tmp_path, 7, 124)]
    r = _run("-m", "tools.perfgate", "--multichip", *paths)
    assert r.returncode == 1, r.stdout + r.stderr
    mc = json.loads(r.stdout)["multichip"]
    assert {c["check"]: c["result"] for c in mc["checks"]} == {
        "rc": "FAIL", "compile_attribution": "FAIL",
        "sharded_replay_parity": "FAIL"}


def test_perfgate_multichip_fails_lost_parity(tmp_path):
    """An rc=0 round whose sharded replay lost state-hash parity fails
    exactly the parity check."""
    bad_obs = dict(_GREEN_OBS,
                   sharded_replay={"state_hash_parity": False})
    paths = [_multichip_round(tmp_path, 6, 0, obs=_GREEN_OBS),
             _multichip_round(tmp_path, 7, 0, obs=bad_obs)]
    r = _run("-m", "tools.perfgate", "--multichip", *paths)
    assert r.returncode == 1
    results = {c["check"]: c["result"]
               for c in json.loads(r.stdout)["multichip"]["checks"]}
    assert results == {"rc": "pass", "compile_attribution": "pass",
                       "sharded_replay_parity": "FAIL"}


def test_perfgate_bench_and_multichip_combined(tmp_path, history):
    """--check and --multichip compose: one verdict, ok only when both
    trajectories pass."""
    bench_rounds = history.bench
    mc = [_multichip_round(tmp_path, 6, 0, obs=_GREEN_OBS),
          _multichip_round(tmp_path, 7, 124)]
    r = _run("-m", "tools.perfgate", "--check", *bench_rounds,
             "--multichip", *mc)
    assert r.returncode == 1          # bench passes, multichip fails
    doc = json.loads(r.stdout)
    assert doc["ok"] is False
    assert all(c["result"] != "FAIL" for c in doc["checks"])


def _serve_round(tmp_path, n, serve=None):
    doc = {"metric": "shelley_replay_proofs_per_sec", "value": 5000.0,
           "unit": "proofs/s", "vs_baseline": 13.0}
    if serve is not None:
        doc["serve"] = serve
    p = tmp_path / f"BENCH_r{n:02d}.json"
    p.write_text(json.dumps({"n": n, "rc": 0, "parsed": doc}))
    return str(p)


_GREEN_SERVE = {"seed": 7, "deadline_secs": 0.05,
                "saturated": {"vs_unbatched_cpu": 6.3,
                              "p95_within_deadline": True}}


def test_perfgate_serve_skips_on_preservice_history(history):
    """ISSUE 14 satellite: r01-r05-shaped rounds predate the serve
    section — every serve check reports skipped and the gate passes
    (same binding pattern as --multichip)."""
    rounds = history.bench
    r = _run("-m", "tools.perfgate", "--serve", *rounds)
    assert r.returncode == 0, r.stdout + r.stderr
    sv = json.loads(r.stdout)["serve"]
    assert sv["ok"] is True and sv["binding"] is False
    assert {c["result"] for c in sv["checks"]} == {"skipped"}


def test_perfgate_serve_binds_and_gates(tmp_path):
    """A round carrying a serve section makes the gate binding: the
    5x-vs-unbatched floor and the p95-inside-deadline bar both
    enforce."""
    good = [_serve_round(tmp_path, 5),
            _serve_round(tmp_path, 6, serve=_GREEN_SERVE)]
    r = _run("-m", "tools.perfgate", "--serve", *good)
    assert r.returncode == 0, r.stdout + r.stderr
    sv = json.loads(r.stdout)["serve"]
    assert sv["binding"] is True
    assert {c["check"]: c["result"] for c in sv["checks"]} == {
        "serve_vs_unbatched": "pass", "serve_p95_deadline": "pass"}

    slow = dict(_GREEN_SERVE,
                saturated={"vs_unbatched_cpu": 3.0,
                           "p95_within_deadline": True})
    d2 = tmp_path / "slow"
    d2.mkdir()
    bad = [_serve_round(d2, 6, serve=_GREEN_SERVE),
           _serve_round(d2, 7, serve=slow)]
    r = _run("-m", "tools.perfgate", "--serve", *bad)
    assert r.returncode == 1, r.stdout + r.stderr
    results = {c["check"]: c["result"]
               for c in json.loads(r.stdout)["serve"]["checks"]}
    assert results == {"serve_vs_unbatched": "FAIL",
                       "serve_p95_deadline": "pass"}

    missed = dict(_GREEN_SERVE,
                  saturated={"vs_unbatched_cpu": 6.0,
                             "p95_within_deadline": False})
    d3 = tmp_path / "missed"
    d3.mkdir()
    bad = [_serve_round(d3, 6, serve=_GREEN_SERVE),
           _serve_round(d3, 7, serve=missed)]
    r = _run("-m", "tools.perfgate", "--serve", *bad)
    assert r.returncode == 1
    results = {c["check"]: c["result"]
               for c in json.loads(r.stdout)["serve"]["checks"]}
    assert results == {"serve_vs_unbatched": "pass",
                       "serve_p95_deadline": "FAIL"}


def test_obsreport_renders_mesh_section(tmp_path):
    """A MULTICHIP round with the full ISSUE-11 obs renders devices,
    compile attribution, sharded replay parity/throughput, per-shard
    padding waste, and the sharded-vs-single-device comparison."""
    obs = dict(_GREEN_OBS)
    obs["sharded_replay"] = dict(
        _GREEN_OBS["sharded_replay"],
        padding={"windows": 6, "lanes_used": 112, "lanes_padded": 192,
                 "waste_frac": 0.4167, "shards": 8,
                 "lanes_per_shard_per_window": 4})
    obs["single_device_replay"] = {"secs": 2.0, "proofs_per_sec": 70.0}
    p = _multichip_round(tmp_path, 6, 0, obs=obs)
    r = _run("-m", "tools.obsreport", p)
    assert r.returncode == 0, r.stderr
    assert "8 devices, rc=0 (green)" in r.stdout
    assert "prewarm_compile_secs" in r.stdout and "201.3" in r.stdout
    assert "state_hash_parity" in r.stdout
    assert "waste_frac" in r.stdout and "0.4167" in r.stdout
    assert "sharded vs single-device: 140.0 vs 70.0 proofs/s (2.00x" \
        in r.stdout


def test_obsreport_renders_overlap_section(tmp_path, history):
    """Regression (ISSUE 9 satellite): a BENCH_r06-shaped round — the
    ISSUE 8 `overlap` section with per-rep attributions and medians —
    renders the hidden-fraction/producer-stall medians instead of being
    silently dropped."""
    doc = {
        "metric": "shelley_replay_proofs_per_sec", "value": 20000.0,
        "unit": "proofs/s", "vs_baseline": 15.0, "reps": 5,
        "spread": 0.12,
        "overlap": {
            "per_rep": [
                {"host_seq_secs": 0.8, "device_secs": 2.9,
                 "host_hidden_secs": 0.7, "hidden_frac": 0.875,
                 "producer_stall_secs": 0.05}] * 5,
            "host_seq_secs_median": 0.8,
            "device_secs_median": 2.9,
            "host_hidden_secs_median": 0.7,
            "hidden_frac_median": 0.875,
            "producer_stall_secs_median": 0.05},
    }
    raw = tmp_path / "bench_r06_shape.json"
    raw.write_text(json.dumps(doc))
    wrapped = tmp_path / "BENCH_r06.json"
    wrapped.write_text(json.dumps({"n": 6, "rc": 0, "parsed": doc}))
    for p in (raw, wrapped):
        r = _run("-m", "tools.obsreport", str(p))
        assert r.returncode == 0, r.stderr
        assert "pipelined-replay overlap (medians over 5 reps)" \
            in r.stdout
        assert "hidden fraction" in r.stdout and "0.875" in r.stdout
        assert "producer permit stalls" in r.stdout and "0.05" in r.stdout
        assert "88% of the host sequential pass" in r.stdout
    # pre-ISSUE-8 rounds say so instead of rendering nothing
    r = _run("-m", "tools.obsreport", history.bench[-1])
    assert r.returncode == 0
    assert "no 'overlap' section" in r.stdout


def test_obsreport_renders_serve_section(tmp_path, history):
    """ISSUE 12 satellite: a round carrying the ``serve`` section (the
    adaptive batching service bench) renders the latency-quantile
    table, the coalesced-batch-size histogram and the fallback /
    deadline-miss / back-pressure accounting."""
    doc = {
        "metric": "verify_service_serve", "value": 6300.0,
        "unit": "proofs/s",
        "serve": {
            "seed": 7, "deadline_secs": 0.05, "modeled_costs": True,
            "break_even": {"device_kind": "modeled-device",
                           "entries": {"ed25519": {
                               "n_star": 3, "cpu_secs_per_req": 1e-3,
                               "device_secs_batch": 0.00712,
                               "bucket": 256}}},
            "saturated": {
                "requests": 2000, "proofs_per_sec": 6300.0,
                "cpu_unbatched_proofs_per_sec": 1000.0,
                "vs_unbatched_cpu": 6.3,
                "latency": {"p50": 0.026, "p95": 0.045, "p99": 0.051},
                "cpu_unbatched_latency": {"p50": 1.62, "p95": 3.26,
                                          "p99": 3.40},
                "p95_within_deadline": True, "deadline_misses": 45,
                "deadline_miss_frac": 0.011,
                "batch_size_hist": {"256": 7, "180": 1},
                "service": {"device_batches": 57,
                            "device_requests": 2000,
                            "fallback_batches": 0,
                            "fallback_requests": 0},
                "parity": True, "leaked_threads": 0},
            "light_load": {"requests": 21, "break_even_n": 3,
                           "device_batches": 0,
                           "fallback_requests": 21, "parity": True,
                           "leaked_threads": 0},
            "backpressure": {"requests": 198, "max_queue": 32,
                             "backpressure_waits": 166,
                             "completed": 198, "parity": True,
                             "leaked_threads": 0},
        },
    }
    p = tmp_path / "serve.json"
    p.write_text(json.dumps(doc))
    r = _run("-m", "tools.obsreport", str(p))
    assert r.returncode == 0, r.stderr
    assert "verification service" in r.stdout
    assert "6.3x the unbatched per-request CPU baseline" in r.stdout
    assert "p95 within deadline: True" in r.stdout
    assert "coalesced batch sizes" in r.stdout
    assert "device batches 0" in r.stdout          # light-load line
    assert "166 blocked submits" in r.stdout
    assert "verdict parity vs CpuRefBackend on every leg: True" \
        in r.stdout
    # a round without the section renders unchanged
    r2 = _run("-m", "tools.obsreport", history.bench[-1])
    assert r2.returncode == 0
    assert "verification service" not in r2.stdout


def test_obsreport_renders_stream_section(tmp_path, history):
    """ISSUE 15 satellite: a round carrying the ``stream`` section (the
    disk->decode->verify engine leg) renders the read-ahead hiding
    accounting and the snapshot/restart timings; rounds without one
    render unchanged."""
    doc = {
        "metric": "shelley_replay_proofs_per_sec", "value": 20000.0,
        "unit": "proofs/s", "vs_baseline": 15.0,
        "stream": {
            "blocks": 10000, "replay_secs": 4.1, "chunks_read": 125,
            "blocks_decoded": 10000, "bytes_read": 6_400_000,
            "era_crossings": 1, "prefetch_stalls": 12, "read_ahead": 4,
            "disk_secs": 1.9, "disk_hidden_secs": 1.7,
            "disk_hidden_frac": 0.894, "host_seq_secs": 0.9,
            "host_hidden_secs": 0.8, "snapshots_written": 5,
            "snapshot_write_secs": 0.21, "restore_secs": 0.0,
            "resumed_from_slot": None,
            "state_hash_parity": True, "proofs_per_sec": 14634.1,
            "restart": {"restore_secs": 0.034, "blocks_replayed": 0,
                        "state_hash_parity": True},
        },
    }
    p = tmp_path / "stream.json"
    p.write_text(json.dumps(doc))
    r = _run("-m", "tools.obsreport", str(p))
    assert r.returncode == 0, r.stderr
    assert "streaming replay (disk -> decode -> verify, read-ahead 4" \
        in r.stdout
    assert "89% of disk+decode ran while a window was in flight" \
        in r.stdout
    assert "era crossings in-stream" in r.stdout
    assert "snapshots: 5 written" in r.stdout
    assert "restart probe" in r.stdout and "0.0340" in r.stdout
    assert "state-hash parity True" in r.stdout
    # a round without the section renders unchanged
    r2 = _run("-m", "tools.obsreport", history.bench[-1])
    assert r2.returncode == 0
    assert "streaming replay" not in r2.stdout


def test_obsreport_live_flag_wired():
    r = _run("-m", "tools.obsreport", "--help")
    assert r.returncode == 0, r.stderr
    assert "--live" in r.stdout and "--interval" in r.stdout
    # --live against a dead port is a clean rc 2, not a traceback
    r2 = _run("-m", "tools.obsreport", "--live", "127.0.0.1:1")
    assert r2.returncode == 2 and "cannot scrape" in r2.stderr
    # PATH and --live are mutually exclusive
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


def test_obsreport_cli(tmp_path, history):
    """`python -m tools.obsreport` renders a bench JSON (raw or
    harness-wrapped) as the phase/variance/cache summary table, and
    reports pre-observability rounds' sections as absent."""
    doc = {
        "metric": "shelley_replay_proofs_per_sec", "value": 1000.0,
        "unit": "proofs/s", "vs_baseline": 10.0, "reps": 2,
        "spread": 0.1,
        "variance": {
            "per_phase": {
                "device": {"median": 2.0, "min": 1.5, "max": 2.5,
                           "spread_secs": 1.0, "spread_rel": 0.5},
                "host-seq": {"median": 1.0, "min": 0.9, "max": 1.1,
                             "spread_secs": 0.2, "spread_rel": 0.2}},
            "dominant_phase": "device", "dominant_spread_secs": 1.0},
        "precompute": {"hits": 5, "misses": 1},
        "metrics": {"precompute.hits": 5,
                    "d.sizes": {"count": 2, "sum": 3}},
    }
    raw = tmp_path / "bench.json"
    raw.write_text(json.dumps(doc))
    wrapped = tmp_path / "BENCH_rXX.json"
    wrapped.write_text(json.dumps({"n": 1, "rc": 0, "parsed": doc}))
    for p in (raw, wrapped):
        r = _run("-m", "tools.obsreport", str(p))
        assert r.returncode == 0, r.stderr
        assert "largest cross-rep spread: 'device'" in r.stdout
        assert "*device" in r.stdout and "precompute.hits" in r.stdout
    # historic rounds (no phases/variance/metrics) still render
    r = _run("-m", "tools.obsreport", history.bench[-1])
    assert r.returncode == 0, r.stderr
    assert "no 'variance' section" in r.stdout
    # a MULTICHIP round renders the mesh section since ISSUE 11 — a
    # red r05 with no MULTICHIP_OBS in its tail says so
    r = _run("-m", "tools.obsreport", history.multichip[-1])
    assert r.returncode == 0, r.stderr
    assert "8 devices, rc=124 (RED)" in r.stdout
    assert "no MULTICHIP_OBS line" in r.stdout
    # genuinely unrecognised input is still a usage error, not a traceback
    bad = tmp_path / "junk.json"
    bad.write_text('{"neither": "bench nor multichip"}')
    r = _run("-m", "tools.obsreport", str(bad))
    assert r.returncode == 2 and "cannot read" in r.stderr


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
