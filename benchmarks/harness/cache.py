"""The per-seed cache inside the checkout: the chain DB a seed gives and
the plain reference's verdict on it.

Key rule: `<bench>/.cache/<config>/<traffic>/<seed>/` (with `rehearse-`
before the configuration's name for a CPU rehearsal, whose chains are
tiny).  What decides the content (the configuration's and the traffic's
`synth` arguments, block count, window, tamper kind, seed) is written
beside it as `spec.json`; a hit whose stored spec differs from the
current one (a data file was edited under its old name) is thrown away
and made again.  A directory is complete only once `verdict.json` is in
it (the child writes it last, by rename); one without it was left by a
killed run and is thrown away too.

Nothing here is JAX: the parent polls, the child (`prepare.py`) fills.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)


def seed_dir(config: str, traffic: str, seed: int, rehearse: bool) -> str:
    cfg = ("rehearse-" if rehearse else "") + config
    return os.path.join(BENCH_DIR, ".cache", cfg, traffic, str(seed))


class Prepared:
    """One seed's chain and verdict, being made by a child or already
    in the cache.  `wait_chain()` returns the chain directory as soon as
    the child has forged it (the reference replay is then still running
    beside the parent's compile); `wait_verdict()` joins the child."""

    def __init__(self, final: str, proc: Optional[subprocess.Popen]):
        self.final = final
        self.proc = proc
        self.cache_hit = proc is None
        self.secs = {"chain": 0.0, "verdict": 0.0}
        self._t0 = time.perf_counter()

    def _check_child(self) -> None:
        rc = self.proc.poll()
        if rc not in (None, 0):
            raise RuntimeError(f"prepare child exited with {rc}")

    def wait_chain(self) -> str:
        chain = os.path.join(self.final, "chain")
        if self.proc is not None:
            marker = os.path.join(self.final, "chain.ok")
            while not os.path.exists(marker):
                self._check_child()
                time.sleep(0.05)
            self.secs["chain"] = time.perf_counter() - self._t0
        return chain

    def wait_verdict(self) -> dict:
        if self.proc is not None:
            rc = self.proc.wait()
            if rc != 0:
                raise RuntimeError(f"prepare child exited with {rc}")
            self.proc = None
        with open(os.path.join(self.final, "verdict.json")) as fh:
            verdict = json.load(fh)
        if not self.cache_hit:
            self.secs["verdict"] = verdict["secs"]["reference"]
        return verdict

    def stop(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


def prepare(config_name: str, traffic_name: str, seed: int, rehearse: bool,
            spec: dict) -> Prepared:
    """Start (or find) the chain and verdict of one seed.  `spec` is what
    the child needs: synth arguments, blocks, window, tamper kind."""
    final = seed_dir(config_name, traffic_name, seed, rehearse)
    spec = {**spec, "seed": seed}
    if os.path.isfile(os.path.join(final, "verdict.json")):
        try:
            with open(os.path.join(final, "spec.json")) as fh:
                if json.load(fh) == spec:
                    return Prepared(final, None)
        except (OSError, ValueError):
            pass
    shutil.rmtree(final, ignore_errors=True)
    os.makedirs(final)
    with open(os.path.join(final, "spec.json"), "w") as fh:
        json.dump(spec, fh)
    env = dict(os.environ)
    # the child never needs a device, and must never take the chip
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "prepare.py"), final],
        env=env, stdout=subprocess.DEVNULL)
    return Prepared(final, proc)
