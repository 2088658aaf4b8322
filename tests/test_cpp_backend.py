"""Native C++ backend: bit-exact parity with the Python reference crypto.

The conformance surface the reference gets from libsodium test vectors
(cardano-crypto-class) — here the pure-Python implementations are the
oracle, and the native library must agree on valid AND corrupted inputs.
"""
import ctypes
import hashlib
import json
import os
import random
import subprocess
import sys

import pytest

from ouroboros_tpu.crypto import cpp_backend as cpp
from ouroboros_tpu.crypto import ed25519_ref, kes as kes_mod, vrf_ref
from ouroboros_tpu.crypto.backend import Ed25519Req, KesReq, VrfReq
from ouroboros_tpu.crypto.cpp_backend import CppBackend


@pytest.fixture(scope="module")
def backend():
    return CppBackend()


def test_ed25519_parity(backend):
    rng = random.Random(7)
    reqs, expect = [], []
    for i in range(20):
        sk = hashlib.sha256(b"cpp-%d" % i).digest()
        vk = ed25519_ref.public_key(sk)
        msg = bytes(rng.randrange(256) for _ in range(rng.randrange(0, 150)))
        sig = ed25519_ref.sign(sk, msg)
        reqs.append(Ed25519Req(vk, msg, sig))
        expect.append(True)
        bad = bytearray(sig)
        bad[rng.randrange(64)] ^= 1 << rng.randrange(8)
        reqs.append(Ed25519Req(vk, msg, bytes(bad)))
        expect.append(ed25519_ref.verify(vk, msg, bytes(bad)))
    got = backend.verify_ed25519_batch(reqs)
    assert got == expect


def test_ed25519_garbage_inputs(backend):
    vk = b"\xff" * 32
    assert backend.verify_ed25519_batch(
        [Ed25519Req(vk, b"m", b"\x00" * 64),
         Ed25519Req(b"short", b"m", b"\x00" * 64),
         Ed25519Req(b"\x00" * 32, b"m", b"sig-too-short")]) == \
        [False, False, False]


def test_vrf_parity(backend):
    rng = random.Random(8)
    reqs, expect = [], []
    for i in range(8):
        sk = hashlib.sha256(b"cppv-%d" % i).digest()
        vk = ed25519_ref.public_key(sk)
        alpha = bytes(rng.randrange(256) for _ in range(rng.randrange(1, 64)))
        pi = vrf_ref.prove(sk, alpha)
        reqs.append(VrfReq(vk, alpha, pi))
        expect.append(True)
        bad = bytearray(pi)
        bad[rng.randrange(80)] ^= 1 << rng.randrange(8)
        reqs.append(VrfReq(vk, alpha, bytes(bad)))
        expect.append(vrf_ref.verify(vk, alpha, bytes(bad)))
    got = backend.verify_vrf_batch(reqs)
    assert got == expect


def test_vrf_proof_to_hash_parity(backend):
    sk = hashlib.sha256(b"beta").digest()
    pi = vrf_ref.prove(sk, b"alpha")
    assert backend.vrf_proof_to_hash(pi) == vrf_ref.proof_to_hash(pi)
    # the all-zero proof is a VALID encoding (y=0 decompresses) — both
    # implementations must agree on it too
    assert backend.vrf_proof_to_hash(b"\x00" * 80) == \
        vrf_ref.proof_to_hash(b"\x00" * 80)
    # s >= L is an invalid encoding in both
    bad = pi[:48] + b"\xff" * 32
    with pytest.raises(ValueError):
        backend.vrf_proof_to_hash(bad)
    with pytest.raises(ValueError):
        vrf_ref.proof_to_hash(bad)


def test_kes_via_native_leaves(backend):
    """KES decomposition (shared CryptoBackend path) over native ed25519."""
    key = kes_mod.KesSignKey(4, hashlib.sha256(b"cpp-kes").digest())
    vk = key.verification_key
    sigs = []
    for period in range(3):
        sigs.append((period, key.sign(b"msg-%d" % period).to_bytes()))
        key.evolve()
    reqs = [KesReq(depth=4, vk=vk, period=p, msg=b"msg-%d" % p,
                   sig_bytes=s) for p, s in sigs]
    reqs.append(KesReq(depth=4, vk=vk, period=0, msg=b"wrong",
                       sig_bytes=sigs[0][1]))
    assert backend.verify_kes_batch(reqs) == [True, True, True, False]


def test_build_is_cached():
    from ouroboros_tpu.crypto.cpp_backend import build_library
    import time
    p1 = build_library()
    t0 = time.time()
    p2 = build_library()
    assert p1 == p2 and time.time() - t0 < 0.05   # cache hit, no recompile


# -- the forging half: ouro_vrf_prove against the pure-Python oracle ---------

def _prove_pairs(group: int):
    """16 seeded (key, alpha) pairs: five keys in rotation (the native
    side keeps the last few keys' expansions), alphas of 0-95 bytes."""
    rng = random.Random(3300 + group)
    for i in range(16):
        sk = hashlib.sha256(b"prove-%d" % rng.randrange(5)).digest()
        yield sk, bytes(rng.randrange(256)
                        for _ in range(rng.randrange(96)))


@pytest.mark.parametrize("group", range(4))
def test_native_prove_is_prove_pure(backend, group):
    """64 pairs in four groups: proof, output and the batch form, byte
    for byte; every proof verifies natively."""
    pairs = list(_prove_pairs(group))
    pure = [vrf_ref.prove_pure(sk, alpha) for sk, alpha in pairs]
    assert [cpp.vrf_prove(sk, alpha) for sk, alpha in pairs] == pure
    assert cpp.vrf_prove_batch([sk for sk, _a in pairs],
                               [a for _sk, a in pairs]) == pure
    assert [cpp.vrf_output(sk, alpha) for sk, alpha in pairs] == \
        [vrf_ref.proof_to_hash(pi) for pi in pure]
    assert all(backend.verify_vrf_batch(
        [VrfReq(vrf_ref.public_key(sk), alpha, pi)
         for (sk, alpha), pi in zip(pairs, pure)]))


def test_vrf_ref_entry_points_take_the_native_path():
    sk = hashlib.sha256(b"entry").digest()
    alphas = [b"a", b"", b"x" * 70]
    pure = [vrf_ref.prove_pure(sk, a) for a in alphas]
    assert [vrf_ref.prove(sk, a) for a in alphas] == pure
    assert vrf_ref.prove_many(sk, alphas) == pure
    assert [vrf_ref.output(sk, a) for a in alphas] == \
        [vrf_ref.proof_to_hash(pi) for pi in pure]


def test_native_prove_not_on_curve_fallback(backend, monkeypatch):
    """No alpha is known to make Elligator2 leave the curve, so the
    fallback (H = [8]B) is reached by handing both sides the same
    off-curve y: `ouro_vrf_prove_from_y` natively, a patched
    `_hash_to_curve_bytes` under prove_pure."""
    from ouroboros_tpu.crypto import edwards as ed
    off_curve = next(y.to_bytes(32, "little") for y in range(2, 64)
                     if ed.decompress(y.to_bytes(32, "little")) is None)
    sk = hashlib.sha256(b"fallback").digest()
    on_curve = vrf_ref._hash_to_curve_bytes(vrf_ref.public_key(sk), b"al")
    pi = ctypes.create_string_buffer(80)
    backend.lib.ouro_vrf_prove_from_y(sk, on_curve, pi)
    assert pi.raw == vrf_ref.prove_pure(sk, b"al")      # the seam itself
    monkeypatch.setattr(vrf_ref, "_hash_to_curve_bytes",
                        lambda vk, alpha: off_curve)
    backend.lib.ouro_vrf_prove_from_y(sk, off_curve, pi)
    assert pi.raw == vrf_ref.prove_pure(sk, b"al")


# -- the forge: same leaders, same chains ------------------------------------

def test_check_is_leader_is_what_two_whole_proofs_gave():
    """The leader check reads the output alone and proves only a slot
    that wins; the TPraosIsLeader is the one two whole pure proofs and
    `proof_to_hash` gave, slot for slot."""
    from fractions import Fraction

    from ouroboros_tpu.eras import shelley as sh
    from ouroboros_tpu.eras.nonintegral import check_leader_value
    cfg = sh.TPraosConfig(k=4, f=Fraction(1, 2), epoch_length=40,
                          slots_per_kes_period=10, kes_depth=4,
                          max_kes_evolutions=14)
    protocol, ledger, pools = sh.shelley_genesis_setup(2, cfg)
    state = protocol.initial_chain_dep_state()
    view = ledger.forecast_view(ledger.initial_state(), 0)
    won = 0
    for slot in range(24):
        ticked = protocol.tick_chain_dep_state(state, view, slot)
        for p in pools:
            cbl = p["can_be_leader"]
            pi_leader = vrf_ref.prove_pure(
                cbl.vrf_sk, sh._vrf_alpha(b"leader", slot, ticked.eta0))
            expect = None
            if check_leader_value(
                    sh._leader_value(vrf_ref.proof_to_hash(pi_leader)),
                    8 * vrf_ref.OUTPUT_LEN, view.get(cbl.pool_id).sigma,
                    cfg.f):
                expect = sh.TPraosIsLeader(
                    eta_proof=vrf_ref.prove_pure(
                        cbl.vrf_sk,
                        sh._vrf_alpha(b"eta", slot, ticked.eta0)),
                    leader_proof=pi_leader)
                won += 1
            assert protocol.check_is_leader(cbl, slot, ticked,
                                            view) == expect
    assert 0 < won < 48


_SYNTH = ["--protocol", "shelley", "--blocks", "16", "--txs-per-block", "2",
          "--pools", "2", "--f", "4/5", "--epoch-length", "500",
          "--kes-depth", "4", "--seed", "golden-33"]
# sha256 over (relative path, NUL, bytes) of every file of the DB the
# PARENT's db_synth (dd83899) forged from _SYNTH, in sorted order
_PARENT_DB = "6781c5995c3fd4e0c40440cdac88ed5786d898745ade852e5b81f6139df8f13f"


def _db_digest(d: str) -> str:
    h = hashlib.sha256()
    for root, dirs, files in os.walk(d):
        dirs.sort()
        for f in sorted(files):
            p = os.path.join(root, f)
            h.update(os.path.relpath(p, d).encode() + b"\0")
            with open(p, "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()


def _forge(out: str, native: bool, *more: str) -> str:
    """db_synth in a child, as given or with the native library made
    unavailable (what a host without g++ sees)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = os.path.join(repo, "tools", "db_synth.py")
    args = ["--out", out, *_SYNTH, *more]
    if native:
        cmd = [sys.executable, tool, *args]
    else:
        cmd = [sys.executable, "-c",
               "import runpy, sys\n"
               f"sys.path.insert(0, {repo!r})\n"
               "from ouroboros_tpu.crypto import cpp_backend\n"
               "cpp_backend._CACHED_LIB = False\n"
               f"sys.argv = [{tool!r}] + {args!r}\n"
               f"runpy.run_path({tool!r}, run_name='__main__')\n"]
    subprocess.run(cmd, check=True, capture_output=True)
    return _db_digest(out)


@pytest.mark.parametrize("native", [True, False],
                         ids=["native", "pure-python"])
def test_default_forge_is_the_parents_chain(tmp_path, native):
    """Without `--slots-per-kes-period` the DB (config.json with the
    derived period, every chunk) is the parent's, byte for byte, with
    the native prove and without the library."""
    assert _forge(str(tmp_path / "db"), native) == _PARENT_DB


def test_kes_period_argument_reaches_the_config(tmp_path):
    out = str(tmp_path / "db")
    assert _forge(out, True, "--slots-per-kes-period", "129600") \
        != _PARENT_DB
    with open(f"{out}/config.json") as fh:
        assert json.load(fh)["slots_per_kes_period"] == 129600
