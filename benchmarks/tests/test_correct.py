"""`correct` has to come out false when it should: the two controls of
the issue at a size a test run can hold, and the timed path broken
underneath (a verdict forged where the device path produces it).

CPU rehearsal sizes (16 blocks, windows of 8, one transaction a block):
the first test compiles the XLA-form window composite for the CPU,
which takes minutes; the others reuse the backend.  Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_correct.py -q

The look for a chip is skipped by `--rehearse`; everything after it is
the run a chip would get.
"""
import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402

CELL = "sync-witness"


@pytest.fixture(scope="module")
def backend():
    from ouroboros_tpu.crypto.jax_backend import JaxBackend
    from harness.manifest import Manifest
    man = Manifest()
    cfg = man.config(man.workload(CELL)["config"])["rehearse"]
    return JaxBackend(**cfg["backend"]["kwargs"])


def result_of(capsys, backend, *extra):
    rc = bench_run.main(["--workload", CELL, "--seconds", "1", "--trace",
                         "0", "--rehearse", *extra], backend=backend)
    assert rc == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    check = next(ln for ln in lines if ln.get("line") == "check")
    return lines[-1], {c["what"]: c for c in check["compared"]}


def test_a_sound_run_is_correct(capsys, backend):
    res, compared = result_of(capsys, backend, "--seed", "41")
    assert res["correct"] is True and res["failed"] == 0
    assert all(c["ok"] for c in compared.values())
    # a rehearsal never prints a number under a metric's name
    assert all(m["value"] is None for m in res["metrics"].values())


def test_another_seeds_reference_verdict_fails(capsys, backend):
    res, compared = result_of(capsys, backend, "--seed", "41", "--control",
                              "wrong-reference")
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] > 0
    assert not compared["replays_failed"]["ok"]


def test_a_flipped_witness_handed_over_as_clean_fails(capsys, backend):
    res, compared = result_of(capsys, backend, "--seed", "41", "--control",
                              "flipped-witness")
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] > 0


def test_a_forged_device_verdict_fails(capsys, backend, monkeypatch):
    """The timed path broken underneath: the window's folded verdict
    says 'every proof held' whatever the device found.  State hashes
    still match (a signature is not part of the state), so only the
    tamper probe can tell — and does."""
    from ouroboros_tpu.crypto.backend import WindowVerdict
    real = type(backend)._finish_window_fold

    def forged(self, state):
        verdict, betas = real(self, state)
        return WindowVerdict(verdict.n, None), betas

    monkeypatch.setattr(type(backend), "_finish_window_fold", forged)
    res, compared = result_of(capsys, backend, "--seed", "41")
    assert res["failed"] == 0            # every replay's hash matched
    assert res["correct"] is False
    assert not compared["tamper_stop_blocks_from_reference"]["ok"] \
        or not compared["tamper_error_differs"]["ok"]
