"""CppBackend — the native CPU CryptoBackend over crypto/native/ouro_crypto.cpp.

The libsodium role (SURVEY.md: the reference's hot crypto lives in external
C reached through typeclass indirection — Shelley/Protocol/Crypto.hs:15-23):
a fast scalar path for batch-of-1 operation when the node is caught up
(BASELINE.json's fallback path), and the honest CPU baseline for replay
benchmarks.  The shared library is compiled on demand with g++ and kept
beside the source; bit-exactness versus ed25519_ref/vrf_ref is enforced by
tests/test_cpp_backend.py.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional, Sequence

import numpy as np

from . import kes as kes_mod
from .backend import (
    CryptoBackend, Ed25519Req, KesReq, VrfReq, ed25519_columns,
)

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "native")
_SRC = os.path.join(_NATIVE_DIR, "ouro_crypto.cpp")
_LIB = os.path.join(_NATIVE_DIR, "libouro_crypto.so")
_STAMP = os.path.join(_NATIVE_DIR, ".build-stamp")


def _src_digest() -> str:
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def build_library(force: bool = False) -> str:
    """Compile the shared library if missing or stale; returns its path."""
    digest = _src_digest()
    if not force and os.path.exists(_LIB) and os.path.exists(_STAMP):
        with open(_STAMP) as f:
            if f.read().strip() == digest:
                return _LIB
    # built beside its final name and moved over it: the forge child, the
    # reference child and the replay process may all find it stale at once
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-o", tmp, _SRC],
            check=True, capture_output=True, text=True)
        os.replace(tmp, _LIB)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    with open(_STAMP, "w") as f:
        f.write(digest)
    return _LIB


def load_library():
    lib = ctypes.CDLL(build_library())
    lib.ouro_ed25519_verify.restype = ctypes.c_int
    lib.ouro_ed25519_verify.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p]
    lib.ouro_ed25519_verify_batch.restype = None
    lib.ouro_vrf_verify.restype = ctypes.c_int
    lib.ouro_vrf_verify.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p]
    lib.ouro_vrf_verify_batch.restype = None
    lib.ouro_vrf_proof_to_hash.restype = ctypes.c_int
    lib.ouro_scalarmult_base.restype = None
    lib.ouro_scalarmult_base.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.ouro_vrf_prove.restype = None
    lib.ouro_vrf_prove.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p]
    lib.ouro_vrf_prove_from_y.restype = None
    lib.ouro_vrf_prove_from_y.argtypes = [ctypes.c_char_p] * 3
    lib.ouro_vrf_prove_batch.restype = None
    lib.ouro_vrf_output.restype = None
    lib.ouro_vrf_output.argtypes = lib.ouro_vrf_prove.argtypes
    lib.ouro_ed25519_challenge_batch.restype = None
    lib.ouro_ed25519_challenge_batch.argtypes = [
        ctypes.c_size_t, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_char_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.ouro_sc_reduce64_fold.restype = None
    lib.ouro_sc_reduce64_fold.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    return lib


_CACHED_LIB = None


def shared_library():
    """Build-once, load-once module-level handle (None if the toolchain is
    unavailable) — the host-side fast path of key derivation and the
    forger's VRF."""
    global _CACHED_LIB
    if _CACHED_LIB is None:
        try:
            _CACHED_LIB = load_library()
        except Exception:
            _CACHED_LIB = False
    return _CACHED_LIB or None


def scalarmult_base(scalar: int):
    lib = shared_library()
    if lib is None:
        return NotImplemented
    out = ctypes.create_string_buffer(32)
    lib.ouro_scalarmult_base(int.to_bytes(scalar, 32, "little"), out)
    return out.raw


def vrf_prove(sk: bytes, alpha: bytes):
    """The 80-byte ECVRF proof, vrf_ref.prove_pure's bytes."""
    lib = shared_library()
    if lib is None:
        return NotImplemented
    pi = ctypes.create_string_buffer(80)
    lib.ouro_vrf_prove(sk, alpha, len(alpha), pi)
    return pi.raw


def vrf_prove_batch(sks: Sequence[bytes], alphas: Sequence[bytes]):
    """Proofs of n (key, alpha) pairs in one call."""
    lib = shared_library()
    if lib is None:
        return NotImplemented
    n = len(sks)
    alens = (ctypes.c_size_t * n)(*[len(a) for a in alphas])
    pis = ctypes.create_string_buffer(80 * n)
    lib.ouro_vrf_prove_batch(n, b"".join(sks), b"".join(alphas), alens, pis)
    return [pis.raw[80 * i:80 * i + 80] for i in range(n)]


def vrf_output(sk: bytes, alpha: bytes):
    """beta = proof_to_hash(prove(sk, alpha)) without making the proof."""
    lib = shared_library()
    if lib is None:
        return NotImplemented
    beta = ctypes.create_string_buffer(64)
    lib.ouro_vrf_output(sk, alpha, len(alpha), beta)
    return beta.raw


def ed25519_challenge_rows(r_rows: np.ndarray, a_rows: np.ndarray,
                           msgs: Sequence[bytes], mask: np.ndarray):
    """The Ed25519 challenge scalars k = SHA-512(R || A || M) mod L of a
    whole batch in ONE native call, as (n, 32) little-endian uint8 rows;
    a lane outside `mask` reads 32 zero bytes.  The interpreter lock is
    released for the length of the call (a `ctypes.CDLL` handle)."""
    lib = shared_library()
    if lib is None:
        return NotImplemented
    n = len(msgs)
    if r_rows.shape != (n, 32) or a_rows.shape != (n, 32) \
            or mask.shape != (n,):
        raise ValueError("challenge rows: R and A are (n, 32), mask (n,)")
    r_rows = np.ascontiguousarray(r_rows, dtype=np.uint8)
    a_rows = np.ascontiguousarray(a_rows, dtype=np.uint8)
    lanes = np.ascontiguousarray(mask, dtype=np.uint8)
    offs = np.zeros(n + 1, dtype=np.uint64)
    np.cumsum(np.fromiter(map(len, msgs), dtype=np.uint64, count=n),
              out=offs[1:])
    joined = b"".join(msgs)
    k_rows = np.empty((n, 32), dtype=np.uint8)
    lib.ouro_ed25519_challenge_batch(
        n, r_rows.ctypes.data, a_rows.ctypes.data, joined,
        offs.ctypes.data, lanes.ctypes.data, k_rows.ctypes.data)
    return k_rows


class CppBackend(CryptoBackend):
    """Native scalar verification (ed25519 + ECVRF in C++; KES leaves via
    the shared KES decomposition onto the ed25519 batch)."""

    name = "cpu-native"

    def __init__(self):
        self.lib = load_library()

    def verify_ed25519_batch(self, reqs: Sequence[Ed25519Req]) -> list[bool]:
        if not reqs:
            return []
        n = len(reqs)
        vk_col, msg_col, sig_col = ed25519_columns(reqs)
        vks = b"".join(vk if len(vk) == 32 else b"\x00" * 32
                       for vk in vk_col)
        msgs = b"".join(msg_col)
        lens = (ctypes.c_size_t * n)(*map(len, msg_col))
        sigs = b"".join(sig if len(sig) == 64 else b"\x00" * 64
                        for sig in sig_col)
        out = (ctypes.c_uint8 * n)()
        self.lib.ouro_ed25519_verify_batch(n, vks, msgs, lens, sigs, out)
        return [bool(o) and len(vk) == 32 and len(sig) == 64
                for o, vk, sig in zip(out, vk_col, sig_col)]

    def verify_vrf_batch(self, reqs: Sequence[VrfReq]) -> list[bool]:
        if not reqs:
            return []
        n = len(reqs)
        vks = b"".join(r.vk if len(r.vk) == 32 else b"\x00" * 32
                       for r in reqs)
        alphas = b"".join(r.alpha for r in reqs)
        alens = (ctypes.c_size_t * n)(*[len(r.alpha) for r in reqs])
        pis = b"".join(r.proof if len(r.proof) == 80 else b"\x00" * 80
                       for r in reqs)
        out = (ctypes.c_uint8 * n)()
        self.lib.ouro_vrf_verify_batch(n, vks, alphas, alens, pis, out)
        return [bool(out[i]) and len(reqs[i].vk) == 32
                and len(reqs[i].proof) == 80 for i in range(n)]

    def vrf_proof_to_hash(self, proof: bytes) -> bytes:
        beta = ctypes.create_string_buffer(64)
        if len(proof) != 80 or \
                not self.lib.ouro_vrf_proof_to_hash(proof, beta):
            raise ValueError("invalid VRF proof")
        return beta.raw
