"""One kernel form (PR 44): `JaxBackend()` builds what the benchmark's
configurations pin with `use_pallas=False, autotune=False`, the two
names select nothing and refuse any other value, and nothing in the
package reaches for the second form again.

The backends here never compile: the device programs are replaced, at
the seam every builder passes its jitted program through
(`_compile_span_on_first_call`), by stand-ins that return all-false
verdicts of the right size.  What is compared is which programs a
backend ASKS for; the words cores are held to the reference by
tests/test_crypto_split.py and tests/test_ed_tiles.py.
"""
import hashlib
import os
import re

import numpy as np
import pytest

from ouroboros_tpu.crypto import ed25519_ref, kes, vrf_ref
from ouroboros_tpu.crypto import jax_backend as JB
from ouroboros_tpu.crypto.backend import Ed25519Req, KesReq, VrfReq
from ouroboros_tpu.crypto.batching import BreakEvenTable
from ouroboros_tpu.crypto.precompute import GLOBAL_PRECOMPUTE_CACHE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PINNED = {"use_pallas": False, "autotune": False}   # benchmarks/configs/


def _mixed_batch() -> list:
    """One request of each primitive and a few witnesses more."""
    sk = hashlib.sha256(b"one-form-ed").digest()
    vsk = hashlib.sha256(b"one-form-vrf").digest()
    ksk = kes.KesSignKey(4, hashlib.sha256(b"one-form-kes").digest())
    reqs = [Ed25519Req(ed25519_ref.public_key(sk), b"m%d" % i,
                       ed25519_ref.sign(sk, b"m%d" % i)) for i in range(5)]
    reqs.append(VrfReq(vrf_ref.public_key(vsk), b"alpha",
                       vrf_ref.prove(vsk, b"alpha")))
    reqs.append(KesReq(4, ksk.verification_key, 0, b"hdr",
                       ksk.sign(b"hdr").to_bytes()))
    return reqs


def _stand_in(fn, name: str):
    """In place of a jitted window program: zeros of its output's size."""
    kind, widths = re.fullmatch(r"window\.(\w+)\((.*)\)", name).groups()
    if kind == "ed_tile":
        tile = int(widths.split(",")[0])
        return lambda *lanes: np.zeros(tile, np.uint8)
    nv, nb, nk = (int(w) for w in widths.split(","))
    return lambda *parts: np.zeros(130 * nv + 33 * nb + nk, np.uint8)


def _programs_asked_for(monkeypatch, **kwargs) -> dict:
    monkeypatch.setattr(JB, "_compile_span_on_first_call", _stand_in)
    GLOBAL_PRECOMPUTE_CACHE._kes.clear()    # both walk the KES path cold
    jb = JB.JaxBackend(min_bucket=16, **kwargs)
    assert jb.verify_mixed(_mixed_batch()) == [False] * 7   # the stand-ins'
    return {"min_bucket": jb.min_bucket, "ed_tile": jb.ed_tile,
            "tiles": sorted(jb._ed_tile_programs),
            "composites": sorted(jb._composites),
            "folds": sorted(jb._folds)}


def test_no_argument_builds_what_the_configurations_pin(monkeypatch):
    plain = JB.JaxBackend()
    pinned = JB.JaxBackend(**PINNED)
    assert (plain.min_bucket, plain.ed_tile, plain.name) \
        == (pinned.min_bucket, pinned.ed_tile, pinned.name)
    asked = _programs_asked_for(monkeypatch)
    assert asked == _programs_asked_for(monkeypatch, **PINNED)
    # and they asked for something: the unfolded tile program of a
    # 16-lane tile, one composite for the VRF lane and the KES jobs
    assert asked["tiles"] == [False] and asked["ed_tile"] == 16
    assert asked["composites"] == [(16, 0, 16)] and asked["folds"] == []


@pytest.mark.parametrize("kwargs", [
    {"use_pallas": True}, {"autotune": True},
    {"use_pallas": True, "autotune": False}, {"use_pallas": "pallas"}],
    ids=lambda kw: ",".join(f"{k}={v}" for k, v in kw.items()))
def test_any_other_value_is_refused_by_name(kwargs):
    with pytest.raises(ValueError, match="PR 44"):
        JB.JaxBackend(**kwargs)


def test_none_is_what_it_was_the_default():
    jb = JB.JaxBackend(use_pallas=None, autotune=None)
    assert (jb.min_bucket, jb.ed_tile) == (128, 128)


def _package_sources():
    for root, dirs, files in os.walk(os.path.join(REPO, "ouroboros_tpu")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as f:
                    yield os.path.relpath(path, REPO), f.read()


@pytest.mark.parametrize("needle", [
    "jax.experimental.pallas", "jax.experimental import pallas",
    "pallas_call", "pallas_kernels", "OURO_RETUNE", "autotune_mod",
    "mul_impl", "_mul_columns", "_window_choice", "_pick("])
def test_nothing_in_the_package_names_the_second_form(needle):
    assert [path for path, text in _package_sources()
            if needle in text] == []


def test_the_two_modules_are_gone():
    crypto = os.path.join(REPO, "ouroboros_tpu", "crypto")
    assert not os.path.exists(os.path.join(crypto, "pallas_kernels.py"))
    assert not os.path.exists(os.path.join(crypto, "autotune.py"))


@pytest.mark.parametrize("device_kind,name", [
    ("TPU v5 lite", "ouro-breakeven-r8-fold-1-TPU-v5-lite.json"),
    ("cpu", "ouro-breakeven-r8-fold-1-cpu.json")])
def test_break_even_table_keeps_its_file_name(device_kind, name):
    """The name a table was saved under at PR 44's parent, where the
    revision and the slug came from the tuner's module: a saved table is
    still found."""
    assert os.path.basename(BreakEvenTable.path_for(device_kind)) == name
