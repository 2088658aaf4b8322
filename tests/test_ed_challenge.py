"""The Ed25519 challenge stage of the device packers (ISSUE 35).

`ed25519_jax.challenge_rows` computes k = SHA-512(R || A || M) mod L for
a whole batch in one call into the native library; the per-lane Python
loop (`challenge_rows_pure`) is the fallback and the oracle.  Held here:
the native rows are the loop's rows byte for byte at every message
length and batch size the packers meet, the new reduction agrees with
Python's integers at the edges no hash produces on demand, both packers
hand the device the arrays the per-lane loop gave them, with the library
and without it, and the counters say which path ran.
"""
import ctypes
import hashlib
import random

import numpy as np
import pytest

from ouroboros_tpu.crypto import cpp_backend, ed25519_ref
from ouroboros_tpu.crypto import ed25519_jax as EJ
from ouroboros_tpu.crypto import edwards as ed
from ouroboros_tpu.observe import metrics as _metrics
from ouroboros_tpu.observe import spans as _spans

L = ed.L

@pytest.fixture(scope="module")
def lib():
    """The native library, built on first use inside a test (never while
    a module is imported); without a compiler its tests skip."""
    handle = cpp_backend.shared_library()
    if handle is None:
        pytest.skip("the native library cannot be built here (no g++)")
    return handle


needs_native = pytest.mark.usefixtures("lib")


def _k(R: bytes, A: bytes, msg: bytes) -> bytes:
    """The spec, one lane: hashlib and Python's integers."""
    h = hashlib.sha512(R + A + msg).digest()
    return (int.from_bytes(h, "little") % L).to_bytes(32, "little")


def _rows(n: int, seed: int):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 256, size=(n, 32), dtype=np.uint8),
            rng.integers(0, 256, size=(n, 32), dtype=np.uint8))


def _msg(length: int, tag: int) -> bytes:
    return hashlib.shake_256(b"msg-%d-%d" % (length, tag)).digest(length)


# -- the native call against the loop and the spec --------------------------

# R || A is 64 bytes, so a message of 47 bytes fills the first block's last
# free byte before the padding (111 in all), 48 forces a second block, 64
# ends on the block edge; the issue's list (0 .. 1,000) covers both SHA-512
# padding edges of a bare message as well
@needs_native
@pytest.mark.parametrize(
    "length", [0, 1, 31, 32, 47, 48, 63, 64, 111, 112, 128, 200, 1000])
def test_native_rows_equal_the_loop_at_message_length(length):
    n = 9
    R, A = _rows(n, length)
    msgs = [_msg(length, j) for j in range(n)]
    mask = np.ones(n, dtype=bool)
    got = cpp_backend.ed25519_challenge_rows(R, A, msgs, mask)
    assert got.dtype == np.uint8 and got.shape == (n, 32)
    assert np.array_equal(got, EJ.challenge_rows_pure(R, A, msgs, mask))
    assert [got[j].tobytes() for j in range(n)] == \
        [_k(R[j].tobytes(), A[j].tobytes(), msgs[j]) for j in range(n)]


@needs_native
@pytest.mark.parametrize("n", [0, 1, 4096, 4097])
def test_native_rows_equal_the_loop_at_batch_size(n):
    """Mixed lengths in one batch, so the offsets are walked: a txid, an
    OCert body, a header body, a pad lane's nothing."""
    R, A = _rows(n, n)
    msgs = [_msg((32, 42, 0, 300)[j % 4], j) for j in range(n)]
    mask = np.ones(n, dtype=bool)
    mask[2::7] = False
    got = cpp_backend.ed25519_challenge_rows(R, A, msgs, mask)
    assert got.shape == (n, 32)
    assert np.array_equal(got, EJ.challenge_rows_pure(R, A, msgs, mask))


@needs_native
def test_masked_out_lanes_read_zero_and_pad_lanes_what_the_loop_gave():
    """A lane outside parse_ok is k = 0.  A pad lane (zero key, zero
    signature, no message) parses, so it carries the hash of 64 zero
    bytes, as it always has: the device's inputs do not change."""
    R, A = _rows(6, 6)
    R[4] = A[4] = 0
    R[5] = A[5] = 0
    msgs = [b"a", b"", b"c" * 32, b"d" * 200, b"", b""]
    mask = np.array([True, False, True, False, True, False])
    got = cpp_backend.ed25519_challenge_rows(R, A, msgs, mask)
    for j in (1, 3, 5):
        assert got[j].tobytes() == bytes(32)
    assert got[4].tobytes() == _k(bytes(32), bytes(32), b"")
    assert got[4].any()
    assert np.array_equal(got, EJ.challenge_rows_pure(R, A, msgs, mask))


@needs_native
def test_a_scalar_with_leading_zero_bytes_keeps_its_width():
    """k < 2^248 (the top byte of its row is 0, one hash in ~16), and
    k < 2^240 further on: the row is still 32 bytes, zeros in place."""
    R, A = _rows(1, 99)
    r, a = R[0].tobytes(), A[0].tobytes()
    small, smaller = [], []
    for i in range(20000):
        m = b"%d" % i
        k = _k(r, a, m)
        if k[31] == 0:
            small.append(m)
            if k[30] == 0:
                smaller.append(m)
        if len(small) >= 8 and smaller:
            break
    assert len(small) >= 8 and smaller
    msgs = small[:8] + smaller[:1]
    n = len(msgs)
    got = cpp_backend.ed25519_challenge_rows(
        np.repeat(R, n, axis=0), np.repeat(A, n, axis=0), msgs,
        np.ones(n, dtype=bool))
    assert (got[:, 31] == 0).all() and got[-1, 30] == 0
    assert [got[j].tobytes() for j in range(n)] == \
        [_k(r, a, m) for m in msgs]


@needs_native
def test_rfc8032_vector_satisfies_the_verification_equation():
    """RFC 8032 7.1 TEST 1 and TEST 2 (tests/test_crypto_ref.py carries
    the first): [s]B = R + [k]A with the native k."""
    vk1 = bytes.fromhex(
        "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a")
    sig1 = bytes.fromhex(
        "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
        "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b")
    vk2 = bytes.fromhex(
        "3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c")
    sig2 = bytes.fromhex(
        "92a009a9f0d4cab8720e820b5f642540a2b27b5416503f8fb3762223ebdb69da"
        "085ac1e43e15996e458f3613d0f11d8c387b2eaeb4302aeeb00d291612bb0c00")
    cases = [(vk1, b"", sig1), (vk2, b"\x72", sig2)]
    R = np.frombuffer(b"".join(s[:32] for _, _, s in cases),
                      np.uint8).reshape(-1, 32)
    A = np.frombuffer(b"".join(v for v, _, _ in cases),
                      np.uint8).reshape(-1, 32)
    got = cpp_backend.ed25519_challenge_rows(
        R, A, [m for _, m, _ in cases], np.ones(2, dtype=bool))
    for j, (vk, _msg_, sig) in enumerate(cases):
        k = int.from_bytes(got[j].tobytes(), "little")
        s = int.from_bytes(sig[32:], "little")
        lhs = ed.scalar_mult(s, ed.BASE)
        rhs = ed.pt_add(ed.decompress(sig[:32]),
                        ed.scalar_mult(k, ed.decompress(vk)))
        assert ed.compress(lhs) == ed.compress(rhs)


# -- the reduction the batch entry brings ------------------------------------

_TOP = (1 << 512) - 1
_EDGES = [0, 1, L - 1, L, L + 1, 2 * L - 1, 2 * L, 4 * L - 1, 4 * L,
          (1 << 252) - 1, 1 << 252, (1 << 252) + 1, (1 << 256) - 1, 1 << 256,
          (1 << 260) - 1, 1 << 385, 1 << 511, _TOP,
          (_TOP // L) * L - 1, (_TOP // L) * L, (_TOP // L) * L + 1]


def _fold(x: int) -> int:
    out = ctypes.create_string_buffer(32)
    cpp_backend.shared_library().ouro_sc_reduce64_fold(
        x.to_bytes(64, "little"), out)
    return int.from_bytes(out.raw, "little")


@needs_native
@pytest.mark.parametrize("x", _EDGES, ids=[hex(x)[:18] for x in _EDGES])
def test_fold_reduction_at_the_edges(x):
    assert _fold(x) == x % L


@needs_native
def test_fold_reduction_on_random_values_of_every_width():
    rng = random.Random(35)
    xs = [rng.getrandbits(512) for _ in range(20000)]
    xs += [rng.getrandbits(w) for w in range(1, 513) for _ in range(8)]
    xs += [q * L + d for q in (rng.getrandbits(259) for _ in range(2000))
           for d in (-1, 0, 1) if 0 <= q * L + d <= _TOP]
    assert [x for x in xs if _fold(x) != x % L] == []


# -- the two packers ---------------------------------------------------------

def _mixed_batch():
    """tests/test_crypto_split.py's good/bad batch (a flipped signature,
    key bytes that are no point, a swapped message), and what else a lane
    can be: a key and a signature of the wrong length, s >= L, pad lanes."""
    n = 24
    keys = [hashlib.sha256(b"k%d" % (i % 5)).digest() for i in range(n)]
    vks = [ed25519_ref.public_key(k) for k in keys]
    msgs = [b"m%d" % i for i in range(n)]
    sigs = [ed25519_ref.sign(k, m) for k, m in zip(keys, msgs)]
    sigs[3] = sigs[3][:63] + bytes([sigs[3][63] ^ 1])
    vks[5] = b"\xff" * 32
    msgs[9] = b"other"
    vks[11] = vks[11][:31]
    sigs[13] = sigs[13] + b"\x00"
    sigs[15] = sigs[15][:32] + L.to_bytes(32, "little")
    msgs[17] = _msg(300, 17)
    pad = 8
    return (vks + [b"\x00" * 32] * pad, msgs + [b""] * pad,
            sigs + [b"\x00" * 64] * pad)


def _expected_k(vks, msgs, sigs, parse_ok):
    """Each lane's k as the per-lane loop of the parent computed it."""
    return [int.from_bytes(_k(sigs[j][:32], vks[j], msgs[j]), "little")
            if parse_ok[j] else 0 for j in range(len(vks))]


def _without_the_library(monkeypatch):
    monkeypatch.setattr(cpp_backend, "ed25519_challenge_rows",
                        lambda *a: NotImplemented)


@pytest.fixture
def counters():
    """The registry switched on for one test; (lanes, native lanes) since."""
    lanes = _metrics.counter("ed25519.challenge_lanes")
    native = _metrics.counter("ed25519.challenge_native_lanes")
    was = _metrics.REGISTRY.enabled
    _metrics.REGISTRY.enable()
    l0, n0 = lanes.value, native.value
    try:
        yield lambda: (lanes.value - l0, native.value - n0)
    finally:
        if not was:
            _metrics.REGISTRY.disable()


@pytest.mark.parametrize("native", [True, False],
                         ids=["native", "no-library"])
def test_prepare_words_batch_hands_over_the_parents_words(
        native, monkeypatch, counters, request):
    if native:
        request.getfixturevalue("lib")
    else:
        _without_the_library(monkeypatch)
    vks, msgs, sigs = _mixed_batch()
    n = len(vks)
    (Aw, signA, Rw, signR, sw, kw), parse_ok = EJ.prepare_words_batch(
        vks, msgs, sigs)
    assert [j for j in range(n) if not parse_ok[j]] == [5, 11, 13, 15]
    assert kw.shape == (8, n) and kw.dtype == np.uint32
    got = [int.from_bytes(np.ascontiguousarray(kw[:, j]).tobytes(), "little")
           for j in range(n)]
    assert got == _expected_k(vks, msgs, sigs, parse_ok)
    assert got[5] == got[11] == got[13] == got[15] == 0
    assert got[n - 1] == int.from_bytes(_k(bytes(32), bytes(32), b""),
                                        "little")
    assert counters() == (n, n if native else 0)


@pytest.mark.parametrize("native", [True, False],
                         ids=["native", "no-library"])
def test_prepare_bytes_batch_hands_over_the_parents_bits(
        native, monkeypatch, counters, request):
    if native:
        request.getfixturevalue("lib")
    else:
        _without_the_library(monkeypatch)
    vks, msgs, sigs = _mixed_batch()
    n = len(vks)
    (_yA, _sA, _yR, _sR, _s_bits, k_bits), parse_ok = \
        EJ.prepare_bytes_batch(vks, msgs, sigs)
    assert k_bits.shape == (256, n) and k_bits.dtype == np.int32
    # MSB first, as the ladder walks them
    got = [int("".join(str(b) for b in k_bits[:, j]), 2) for j in range(n)]
    assert got == _expected_k(vks, msgs, sigs, parse_ok)
    assert counters() == (n, n if native else 0)


@needs_native
def test_both_paths_give_the_same_arrays_bit_for_bit(monkeypatch):
    vks, msgs, sigs = _mixed_batch()
    words, w_ok = EJ.prepare_words_batch(vks, msgs, sigs)
    bits, b_ok = EJ.prepare_bytes_batch(vks, msgs, sigs)
    _without_the_library(monkeypatch)
    words2, w_ok2 = EJ.prepare_words_batch(vks, msgs, sigs)
    bits2, b_ok2 = EJ.prepare_bytes_batch(vks, msgs, sigs)
    for a, b in zip(words + bits + (w_ok, b_ok),
                    words2 + bits2 + (w_ok2, b_ok2)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_the_stage_is_one_span_named_pack_ed_challenge():
    vks, msgs, sigs = _mixed_batch()
    _spans.RECORDER.drain()
    was = _spans.RECORDER.enabled
    _spans.RECORDER.enable()
    try:
        EJ.prepare_words_batch(vks, msgs, sigs)
    finally:
        if not was:
            _spans.RECORDER.disable()
    names = [sp.name for root in _spans.RECORDER.drain()
             for sp in root.walk()]
    assert names.count("pack_ed.challenge") == 1


# -- the binding -------------------------------------------------------------

@needs_native
def test_the_handle_releases_the_interpreter_lock(lib):
    """A `CDLL` drops the lock around every foreign call; a `PyDLL`
    would hold it and the batch call would free nothing."""
    assert isinstance(lib, ctypes.CDLL)
    assert not isinstance(lib, ctypes.PyDLL)


@needs_native
def test_the_binding_refuses_rows_of_the_wrong_shape():
    R, A = _rows(3, 3)
    with pytest.raises(ValueError):
        cpp_backend.ed25519_challenge_rows(R[:2], A, [b""] * 3,
                                           np.ones(3, dtype=bool))
    with pytest.raises(ValueError):
        cpp_backend.ed25519_challenge_rows(R, A, [b""] * 3,
                                           np.ones(4, dtype=bool))


def test_no_library_means_not_implemented(monkeypatch):
    monkeypatch.setattr(cpp_backend, "_CACHED_LIB", False)
    R, A = _rows(2, 2)
    assert cpp_backend.ed25519_challenge_rows(
        R, A, [b"", b"x"], np.ones(2, dtype=bool)) is NotImplemented
    got = EJ.challenge_rows(R, A, [b"", b"x"], np.ones(2, dtype=bool))
    assert got[1].tobytes() == _k(R[1].tobytes(), A[1].tobytes(), b"x")
