"""What keeps the program from landing on the CPU without saying so
(ISSUE 22 steps 2-4): one compile-cache rule, no fallback that hides the
device, a backend that names the platform it took, and chip_smoke.py
refusing to run off the chip.

The chip itself is never touched here (tests/conftest.py holds JAX to
the CPU); chip_smoke.py's full CPU rehearsal is `slow` because a new
window-composite shape costs minutes of XLA:CPU compile.
"""
import json
import os
import subprocess
import sys

import pytest

import jax

from ouroboros_tpu import compile_cache
from ouroboros_tpu.crypto import backend as backend_mod
from ouroboros_tpu.crypto import jax_backend as JB
from ouroboros_tpu.crypto.batching import BreakEvenTable

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv, env=None, timeout=300):
    return subprocess.run(
        [sys.executable, *argv], cwd=REPO, capture_output=True, text=True,
        timeout=timeout, env={**os.environ, **(env or {})})


# ---------------------------------------------------------------------------
# one compile cache, placeable from outside
# ---------------------------------------------------------------------------

def test_cache_dir_follows_the_env_var_and_sets_nothing(monkeypatch,
                                                        tmp_path):
    """Variable set: that directory, for the break-even table too;
    JAX's own setting is left to JAX and the
    environment is not written."""
    d = str(tmp_path / "placed" / "cache")
    monkeypatch.setenv(compile_cache.ENV_VAR, d)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.cache_dir() == d and os.path.isdir(d)
    assert jax.config.jax_compilation_cache_dir == before
    assert os.environ[compile_cache.ENV_VAR] == d
    table = BreakEvenTable({}, "test kind/22")
    assert os.path.dirname(table.path_for("test kind/22")) == d
    saved = table.save()
    assert os.listdir(d) == [os.path.basename(saved)]


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout(monkeypatch):
    """Variable unset: the fixed git-ignored directory inside the
    checkout — never the temp dir, a pid or a time — and JAX is pointed
    at it without the environment being written."""
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    d = compile_cache.cache_dir()
    assert d == compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    assert compile_cache.cache_dir() == d           # same answer again
    assert jax.config.jax_compilation_cache_dir == d
    assert compile_cache.ENV_VAR not in os.environ
    assert os.path.dirname(BreakEvenTable.path_for("k")) == d
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_nothing_in_the_tree_assigns_the_cache_variable():
    """Code never writes JAX_COMPILATION_CACHE_DIR, and no compile-cache
    path is built from the temp dir."""
    offenders = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d != "tests"
                   and d != "__pycache__" and d != "chiprun_out"]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path) as f:
                for n, line in enumerate(f, 1):
                    code = line.split("#", 1)[0]
                    if "JAX_COMPILATION_CACHE_DIR" in code and (
                            "environ[" in code or "setdefault" in code
                            or "putenv" in code):
                        offenders.append(f"{path}:{n}")
                    if "jax-ouro-cache" in code:
                        offenders.append(f"{path}:{n}")
    assert not offenders, offenders


# ---------------------------------------------------------------------------
# no fallback that hides the device
# ---------------------------------------------------------------------------

class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"


@pytest.fixture
def fresh_default():
    old = backend_mod._default
    backend_mod.set_default_backend(None)
    yield
    backend_mod.set_default_backend(old)


def test_default_backend_raises_when_the_accelerator_backend_fails(
        monkeypatch, fresh_default):
    """JAX reports an accelerator and JaxBackend() fails: the error
    propagates instead of a quiet OpenSSL/pure-Python replay."""
    def boom(*a, **kw):
        raise RuntimeError("the compiler refused the program")
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
    monkeypatch.setattr(JB, "JaxBackend", boom)
    with pytest.raises(RuntimeError, match="compiler refused"):
        backend_mod.default_backend()


def test_default_backend_on_the_cpu_platform_is_a_cpu_backend(
        fresh_default):
    """The documented choice stays: CPU platform -> CPU backend."""
    b = backend_mod.default_backend()
    assert not isinstance(b, JB.JaxBackend)
    assert b.name.startswith("cpu")


def test_jax_backend_names_the_platform_it_took():
    jb = JB.JaxBackend(use_pallas=False, autotune=False)
    assert jb.platform == "cpu" and jb.name == "jax-cpu"
    assert jb.device_kind == jax.devices()[0].device_kind
    assert jb.device_count == len(jax.devices())


def test_db_analyser_line_carries_the_device(tmp_path):
    """`db_analyser --backend jax` says where it really ran.  The proofs
    are checked by the OpenSSL backend behind the JaxBackend instance: a
    real device window would cost minutes of XLA:CPU compile, and the
    line is what is under test."""
    import io

    from tools import db_analyser as dba
    chain = str(tmp_path / "chain")
    r = _run("tools/db_synth.py", "--out", chain, "--blocks", "12",
             "--txs-per-block", "1", "--nodes", "2")
    assert r.returncode == 0, r.stderr
    db, rules, decode, cfg = dba.load_db(chain)
    jb = JB.JaxBackend(use_pallas=False, autotune=False)
    cpu = dba.make_backend("openssl")
    jb.submit_window = None             # the synchronous driver
    jb.verify_mixed = cpu.verify_mixed
    jb.vrf_betas_batch = cpu.vrf_betas_batch
    out = io.StringIO()
    dba.analysis_validate(db, rules, decode, jb, "full", 8, out,
                          hdr_proofs=dba.HEADER_PROOFS[cfg["protocol"]],
                          db_dir=chain)
    line = json.loads(out.getvalue())
    assert line["blocks"] == 12
    assert line["backend"] == line["backend_name"] == "jax-cpu"
    assert line["platform"] == "cpu"
    assert line["device_kind"] == jb.device_kind
    assert line["device_count"] == jb.device_count
    # a CPU backend's line is unchanged
    out = io.StringIO()
    dba.analysis_validate(db, rules, decode, "openssl", "full", 8, out,
                          hdr_proofs=dba.HEADER_PROOFS[cfg["protocol"]],
                          db_dir=chain)
    assert "platform" not in json.loads(out.getvalue())


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------

def test_chip_smoke_refuses_to_run_off_the_chip():
    """Without --rehearse, under JAX_PLATFORMS=cpu: non-zero exit before
    any phase, and no result line."""
    r = _run("chip_smoke.py", env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode not in (0, None)
    assert r.stdout == ""
    assert "not 'tpu'" in r.stderr


def test_db_synth_never_imports_jax(tmp_path):
    """One process per chip: chip_smoke.py holds the chip and runs
    db_synth as a child, which is safe only while a whole synth
    run — the shelley path the smoke uses — leaves JAX unimported."""
    prog = (
        "import runpy, sys\n"
        f"sys.argv = ['db_synth.py', '--out', {str(tmp_path / 'c')!r},"
        " '--protocol', 'shelley', '--blocks', '6', '--pools', '2',"
        " '--f', '4/5', '--txs-per-block', '1', '--epoch-length', '500',"
        " '--kes-depth', '4']\n"
        "try:\n"
        "    runpy.run_path('tools/db_synth.py', run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    assert not e.code, e.code\n"
        "assert 'jax' not in sys.modules, 'db_synth imported jax'\n")
    r = _run("-c", prog)
    assert r.returncode == 0, r.stderr[-2000:]


def _result_lines(stdout: str) -> list:
    return [json.loads(l) for l in stdout.strip().splitlines()]


@pytest.mark.slow
def test_chip_smoke_rehearsal_runs_every_phase_on_the_cpu():
    """Rehearsal 1: every phase, tiny, on the CPU; the last line names
    the platform it really ran on and never says tpu."""
    r = _run("chip_smoke.py", "--rehearse", "--blocks", "16",
             "--window", "8", env={"JAX_PLATFORMS": "cpu"}, timeout=1500)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = _result_lines(r.stdout)
    assert [l.get("phase") for l in lines] == [
        "start", "synth", "reference", "backend", "device", "tamper",
        "warm", "done", None]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": lines[-1]["device"]["kind"],
        "count": lines[-1]["device"]["count"]}}
    assert '"tpu"' not in r.stdout


@pytest.mark.slow
def test_chip_smoke_mesh_rehearsal_on_four_virtual_devices():
    """Rehearsal 2: --mesh 4 over four forced host devices runs ONLY
    synth, reference, the sharded replay and the mesh evidence."""
    r = _run("chip_smoke.py", "--rehearse", "--mesh", "4", "--blocks",
             "16", "--window", "8", timeout=1500,
             env={"JAX_PLATFORMS": "cpu",
                  "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stderr[-3000:]
    lines = _result_lines(r.stdout)
    assert [l.get("phase") for l in lines] == [
        "start", "synth", "reference", "backend", "device", "mesh",
        "done", None]
    assert lines[-1]["device"] == {"platform": "cpu", "kind": "cpu",
                                   "count": 4}
    mesh = lines[5]
    assert len(set(mesh["shard_devices"])) == 4
