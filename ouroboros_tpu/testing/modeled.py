"""A device for `VerifyService` that exists only on the runtime clock
(tests/test_batching.py): verdicts from a reference backend, latency
from a two-parameter model.
"""
from __future__ import annotations

from typing import Optional

from .. import simharness as sim
from ..crypto.backend import CpuRefBackend, CryptoBackend


class ModeledBackend(CryptoBackend):
    """`inner`'s verdicts + a latency model charged to the RUNTIME
    clock: ``verify_*_batch_async`` sleeps ``setup_secs + per_req_secs *
    n`` before answering — exact virtual seconds under the sim harness,
    real sleeps under io_run.

    The fake a test gives `VerifyService` in place of a device: the
    cost PARAMETERS are the test's, the DYNAMICS (coalescing, queueing,
    deadlines, back-pressure) play out in virtual time, and every
    verdict still comes from `inner` (CpuRefBackend by default — or a
    PrecheckedBackend over CpuRef-computed verdicts, so a big trace
    does not re-run pure-Python EC math per arrival), so parity checks
    stay byte-exact."""

    def __init__(self, setup_secs: float, per_req_secs: float,
                 inner: Optional[CryptoBackend] = None,
                 name: str = "modeled"):
        self.setup_secs = setup_secs
        self.per_req_secs = per_req_secs
        self.inner = inner if inner is not None else CpuRefBackend()
        self.name = name
        self.calls = 0

    # sync forms delegate straight through (no latency to charge: the
    # runtime clock only advances inside a thread that sleeps)
    def verify_ed25519_batch(self, reqs):
        return self.inner.verify_ed25519_batch(reqs)

    def verify_vrf_batch(self, reqs):
        return self.inner.verify_vrf_batch(reqs)

    def verify_kes_batch(self, reqs):
        return self.inner.verify_kes_batch(reqs)

    async def _charged(self, method, reqs):
        self.calls += 1
        await sim.sleep(self.setup_secs + self.per_req_secs * len(reqs))
        return getattr(self.inner, method)(reqs)

    async def verify_ed25519_batch_async(self, reqs):
        return await self._charged("verify_ed25519_batch", reqs)

    async def verify_vrf_batch_async(self, reqs):
        return await self._charged("verify_vrf_batch", reqs)

    async def verify_kes_batch_async(self, reqs):
        return await self._charged("verify_kes_batch", reqs)
