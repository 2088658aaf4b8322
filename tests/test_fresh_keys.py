"""The per-key table path when the keys are NEW (ISSUE 31), on the CPU.

A chain whose wallets take a fresh address for every transaction hands
the per-key cache (crypto/precompute.py) a window in which every
Ed25519 lane's key misses.  Here the fill's two program widths are
shrunk (`precompute.FILL_NARROW` 128 -> 8, `jax_backend.ED_TILE` 4,096 ->
16), as tests/test_ed_tiles.py shrinks the verify tile, so a handful of
keys walks one, two and three tiles on XLA:CPU.

What is held: every table entry (`xA`, `x([2^128]A)`, `y([2^128]A)`,
`known`) equals the plain integers of crypto/edwards.py at every count
either side of a tile boundary, for keys that do not decode, keys of
small order, a new key met twice in a batch and a batch of new and
cached keys; the verdicts through `JaxBackend` equal
crypto/ed25519_ref.py's, also with an LRU bound smaller than the batch;
the lanes handed to the fill programs are whole tiles and the programs
are two whatever the count; and `db_synth`'s default chain is the
parent's, byte for byte.

Since ISSUE 46 a fill has two phases (`begin_assemble` dispatches it,
`finish_assemble` collects it): every case of (a) and (b) runs both as
`assemble` and as the two calls, and (c) holds the window path to its
order: a window of new keys has its fill on the device before its
lanes are hashed, and gives the verdicts it gave before.
"""
import hashlib
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ouroboros_tpu.crypto import ed25519_jax as EJ  # noqa: E402
from ouroboros_tpu.crypto import ed25519_ref  # noqa: E402
from ouroboros_tpu.crypto import edwards as ed  # noqa: E402
from ouroboros_tpu.crypto import jax_backend as JB  # noqa: E402
from ouroboros_tpu.crypto import precompute  # noqa: E402
from ouroboros_tpu.crypto.backend import Ed25519Req  # noqa: E402
from ouroboros_tpu.crypto.precompute import PrecomputeCache  # noqa: E402

pytestmark = pytest.mark.device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, NARROW = 16, 8         # the fill's tile and narrow widths in this file


@pytest.fixture(autouse=True)
def small_widths(monkeypatch):
    monkeypatch.setattr(JB, "ED_TILE", T)
    monkeypatch.setattr(precompute, "FILL_NARROW", NARROW)


def _sk(i: int) -> bytes:
    return hashlib.sha256(b"fresh-key-%d" % i).digest()


def _vk(i: int) -> bytes:
    return ed25519_ref.public_key(_sk(i))


def _off_curve() -> bytes:
    y = 2
    while ed.decompress(y.to_bytes(32, "little")) is not None:
        y += 1
    return y.to_bytes(32, "little")


# keys that are no honest wallet's: no point at all, not 32 bytes, a y
# that is not reduced, and the points of order 1, 2 and 4
ODD_KEYS = {
    "off-curve": _off_curve(),
    "wrong-length": b"\x01" * 31,
    "y-not-reduced": (ed.P + 1).to_bytes(32, "little"),
    "order-1": (1).to_bytes(32, "little"),
    "order-2": (ed.P - 1).to_bytes(32, "little"),
    "order-4": (0).to_bytes(32, "little"),
}


def _want(vk: bytes):
    """(xA, x128, y128) by the plain integers, or None where the key
    does not decode."""
    A = ed.decompress(vk) if len(vk) == 32 else None
    if A is None:
        return None
    return (ed.to_affine(A)[0],) + tuple(
        ed.to_affine(ed.scalar_mult(1 << 128, A)))


def _check_lanes(vks, out) -> None:
    xa, xw, yw, known = out
    assert xa.shape == xw.shape == yw.shape == (8, len(vks))
    for j, vk in enumerate(vks):
        want = _want(vk)
        assert bool(known[j]) == (want is not None), (j, vk.hex())
        if want is not None:
            got = tuple(int.from_bytes(np.ascontiguousarray(w[:, j])
                                       .tobytes(), "little")
                        for w in (xa, xw, yw))
            assert got == want, (j, vk.hex())


def _lanes(n_keys: int) -> int:
    width = NARROW if n_keys <= NARROW else T
    return -(-n_keys // width) * width


# `assemble` in one go and as its two phases: the same tables, `known`
# and counters; `early_fill_keys` says which it was
HOW = ["assemble", "begin-finish"]


def _assemble(cache, vks, how):
    if how == "assemble":
        return cache.assemble(vks)
    return cache.finish_assemble(cache.begin_assemble(vks))


# -- (a) the table entries against the integers ------------------------------

@pytest.mark.parametrize("how", HOW)
@pytest.mark.parametrize("n", [1, NARROW, NARROW + 1, T - 1, T, T + 1, 3 * T])
def test_new_keys_get_the_integers_tables_at_every_count(n, how):
    cache = PrecomputeCache()
    vks = [_vk(1000 * n + i) for i in range(n)]
    _check_lanes(vks, _assemble(cache, vks, how))
    assert (cache.misses, cache.filled_keys, cache.device_fills) == (n, n, 1)
    assert cache.hits == 0 and len(cache) == n
    assert cache.fill_lanes_padded == _lanes(n)
    assert cache.early_fill_keys == (0 if how == "assemble" else n)
    # the same keys again: every lane a hit, nothing filled
    _check_lanes(vks, _assemble(cache, vks, how))
    assert (cache.hits, cache.filled_keys, cache.device_fills) == (n, n, 1)
    assert cache.early_fill_keys in (0, n) and cache.fill_wait_us >= 0


@pytest.mark.parametrize("how", HOW)
@pytest.mark.parametrize("odd", sorted(ODD_KEYS))
def test_a_key_no_wallet_made_among_new_keys(odd, how):
    cache = PrecomputeCache()
    vks = [_vk(1), ODD_KEYS[odd], _vk(2)]
    out = _assemble(cache, vks, how)
    _check_lanes(vks, out)
    assert bool(out[3][1]) == odd.startswith("order")
    # cached as it is, a negative entry too: no second fill
    _check_lanes(vks, _assemble(cache, vks, how))
    assert cache.device_fills == 1 and cache.hits == 3


@pytest.mark.parametrize("how", HOW)
def test_a_new_key_twice_in_one_batch_is_filled_once(how):
    cache = PrecomputeCache()
    vks = [_vk(i % 5) for i in range(T + 3)]      # 5 distinct, 19 lanes
    _check_lanes(vks, _assemble(cache, vks, how))
    assert (cache.misses, cache.filled_keys, len(cache)) == (5, 5, 5)
    assert cache.fill_lanes_padded == NARROW and cache.hits == 0


@pytest.mark.parametrize("how", HOW)
def test_new_and_cached_keys_mixed_in_one_batch(how):
    cache = PrecomputeCache()
    old = [_vk(100 + i) for i in range(6)]
    cache.assemble(old)
    new = [_vk(200 + i) for i in range(T + 1)] + [ODD_KEYS["off-curve"]]
    vks = [k for pair in zip(new, old * 3) for k in pair]   # interleaved
    _check_lanes(vks, _assemble(cache, vks, how))
    assert cache.hits == len(vks) // 2            # a hit is a lane
    assert cache.misses == 6 + len(new) == cache.filled_keys
    assert cache.fill_lanes_padded == NARROW + _lanes(len(new))
    assert cache.early_fill_keys == (0 if how == "assemble" else len(new))


def test_two_fills_in_flight_at_once_on_overlapping_keys():
    """Both on the device before either is collected: each key stored
    once, both batches' lanes right."""
    cache = PrecomputeCache()
    one = [_vk(900 + i) for i in range(NARROW + 2)]
    two = one[NARROW:] + [_vk(950 + i) for i in range(3)] + one[:1]
    first, second = cache.begin_assemble(one), cache.begin_assemble(two)
    assert len(cache) == 0 and cache.device_fills == 2
    _check_lanes(two, cache.finish_assemble(second))
    _check_lanes(one, cache.finish_assemble(first))
    assert len(cache) == len(set(one + two)) == NARROW + 5
    assert cache.filled_keys == cache.early_fill_keys == NARROW + 2 + 6
    _check_lanes(one + two, cache.assemble(one + two))
    assert cache.device_fills == 2


def test_the_fill_programs_are_two_whatever_the_count():
    """One program a width: a window of many tiles calls the tile's
    program many times and compiles nothing."""
    cache = PrecomputeCache()
    cache.assemble([_vk(300)])                     # the narrow program
    cache.assemble([_vk(400 + i) for i in range(T + 1)])   # two tiles
    programs = EJ.a128_words_kernel._cache_size()
    cache.assemble([_vk(500 + i) for i in range(5 * T - 2)])  # five
    cache.assemble([_vk(600 + i) for i in range(NARROW)])
    assert EJ.a128_words_kernel._cache_size() == programs
    assert cache.device_fills == 4
    assert cache.fill_lanes_padded == NARROW + 2 * T + 5 * T + NARROW


# -- (b) an LRU bound smaller than one batch ---------------------------------

@pytest.mark.parametrize("how", HOW)
def test_a_bound_smaller_than_the_batch_keeps_the_lanes_right(how):
    cache = PrecomputeCache(max_entries=4)
    cache.assemble([_vk(700), _vk(701)])
    vks = [_vk(700)] + [_vk(710 + i % 11) for i in range(T + 6)] + [_vk(701)]
    # hits evicted mid-batch: they were copied out at the begin
    _check_lanes(vks, _assemble(cache, vks, how))
    assert len(cache) == 4 and cache.evictions == 2 + 11 - 4
    # the last four of the fill stay, as if inserted one by one
    assert [_vk(710 + i) in cache for i in range(11)] == [False] * 7 + [True] * 4
    _check_lanes(vks, _assemble(cache, vks, how))
    assert len(cache) == 4


def _requests(flipped=(5,), off_curve=(9,), identity=(13,)):
    """Signatures by new keys, one tampered, one by a key that is no
    point, one by the identity, and a key that signs twice."""
    reqs = []
    for i in range(20):
        sk, msg = _sk(800 + i % 18), b"tx-%02d" % i
        sig = ed25519_ref.sign(sk, msg)
        vk = ed25519_ref.public_key(sk)
        if i in flipped:
            sig = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
        if i in off_curve:
            vk = ODD_KEYS["off-curve"]
        if i in identity:
            vk = ODD_KEYS["order-1"]
        reqs.append(Ed25519Req(vk, msg, sig))
    return reqs


@pytest.mark.parametrize("bound", [200_000, 4])
def test_verdicts_on_new_keys_equal_the_reference(monkeypatch, bound):
    cache = precompute.GLOBAL_PRECOMPUTE_CACHE
    cache.clear()
    monkeypatch.setattr(cache, "max_entries", bound)
    reqs = _requests()
    want = [ed25519_ref.verify(r.vk, r.msg, r.sig) for r in reqs]
    assert want.count(False) == 3
    be = JB.JaxBackend(min_bucket=16, use_pallas=False, autotune=False)
    try:
        assert be.verify_ed25519_batch(reqs) == want     # every key new
        assert be.verify_ed25519_batch(reqs) == want     # cached, or evicted
        assert len(cache) <= bound
    finally:
        cache.clear()


# -- (c) the window path: the fill before the hashing ---------------------------

# the requests that fail, as the parent's `_submit_window` answered (and
# as crypto/ed25519_ref.py does), by what the window holds
WINDOWS = {
    "flipped-witness-on-a-new-key": (
        dict(flipped=(7,), off_curve=(), identity=()), [7]),
    "undecodable-new-key": (
        dict(flipped=(), off_curve=(3,), identity=()), [3]),
}


@pytest.fixture(scope="module")
def window_backend():
    """One backend for the window cases: its two tile programs (fold
    and no fold) are traced and built once."""
    return JB.JaxBackend(min_bucket=16, use_pallas=False, autotune=False)


@pytest.mark.parametrize("fold", [False, True], ids=["no-fold", "fold"])
@pytest.mark.parametrize("window", sorted(WINDOWS))
def test_a_window_of_new_keys_fills_before_it_hashes(
        monkeypatch, window_backend, window, fold):
    """When the packer starts hashing the window's lanes
    (`EJ.challenge_rows`), the new keys' fill is already dispatched and
    counted and nothing is stored yet; the verdict is the parent's."""
    cache = precompute.GLOBAL_PRECOMPUTE_CACHE
    cache.clear()
    kwargs, bad = WINDOWS[window]
    reqs = _requests(**kwargs)
    assert [i for i, r in enumerate(reqs)
            if not ed25519_ref.verify(r.vk, r.msg, r.sig)] == bad
    new = len({r.vk for r in reqs}) + 1          # and the pad lanes' key
    before = (cache.device_fills, cache.early_fill_keys, cache.filled_keys)
    seen = []
    hashing = EJ.challenge_rows

    def spy(*args):
        seen.append((cache.device_fills - before[0],
                     cache.early_fill_keys - before[1],
                     cache.filled_keys - before[2], len(cache)))
        return hashing(*args)

    monkeypatch.setattr(EJ, "challenge_rows", spy)
    be = window_backend
    try:
        verdict, _betas = be.finish_window(be.submit_window(reqs, fold=fold))
        assert seen == [(1, new, new, 0)]
        assert len(cache) == new
        if fold:
            assert verdict.first_bad == (bad[0] if bad else None)
        else:
            assert [i for i, ok in enumerate(verdict) if not ok] == bad
        # the same window again: every lane a hit, a handle with nothing
        # in flight, the same verdict
        again, _betas = be.finish_window(be.submit_window(reqs, fold=fold))
        assert again == verdict and seen[1:] == [(1, new, new, new)]
    finally:
        cache.clear()


def test_a_malformed_witness_in_the_packer_leaves_the_cache_alone(
        monkeypatch, window_backend):
    """An exception between the window's begin and its finish (here in
    the hashing): nothing of the window is stored, and the same window
    later is filled and answered right."""
    cache = precompute.GLOBAL_PRECOMPUTE_CACHE
    cache.clear()
    reqs = _requests()
    want = [ed25519_ref.verify(r.vk, r.msg, r.sig) for r in reqs]
    be = window_backend
    filled = cache.filled_keys

    def refuse(*args):
        raise ValueError("malformed witness")

    try:
        with monkeypatch.context() as m:
            m.setattr(EJ, "challenge_rows", refuse)
            with pytest.raises(ValueError, match="malformed"):
                be.submit_window(reqs)
        assert len(cache) == 0 and cache.filled_keys > filled
        assert be.verify_mixed(reqs) == want
        assert len(cache) == len({r.vk for r in reqs}) + 1
    finally:
        cache.clear()


# -- (e) the default chain is the parent's ------------------------------------

def _forge(out: str, *extra: str) -> str:
    """Forge a small seeded chain; Blake2b over its files, names and
    bytes, in order."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", out, "--protocol", "shelley", "--blocks", "12",
         "--txs-per-block", "5", "--pools", "2", "--f", "1/20",
         "--epoch-length", "432000", "--kes-depth", "6", "--seed", "77",
         *extra],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    h = hashlib.blake2b(digest_size=16)
    for root, dirs, files in os.walk(out):
        dirs.sort()
        for name in sorted(files):
            path = os.path.join(root, name)
            h.update(os.path.relpath(path, out).encode() + b"\0")
            with open(path, "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()


# the chain `db_synth` forged at the parent of ISSUE 31 with these
# arguments (commit 170cc93), hashed as `_forge` hashes it
PARENT_CHAIN = "839216847e76c07271993f0a837b9899"


@pytest.mark.parametrize("extra", [(), ("--witness-keys", "pool")],
                         ids=["no-argument", "pool"])
def test_the_default_chain_is_the_parents_byte_for_byte(tmp_path, extra):
    assert _forge(str(tmp_path / "chain"), *extra) == PARENT_CHAIN


def test_a_fresh_chain_has_the_same_size_and_other_bytes(tmp_path):
    fresh = str(tmp_path / "fresh")
    assert _forge(fresh, "--witness-keys", "fresh") != PARENT_CHAIN
    pool = str(tmp_path / "pool")
    _forge(pool)

    def size(d):
        return sorted((os.path.relpath(os.path.join(r, f), d),
                       os.path.getsize(os.path.join(r, f)))
                      for r, _d, fs in os.walk(d) for f in fs)
    assert size(fresh) == size(pool)
