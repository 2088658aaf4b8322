"""A window's Ed25519 lanes as calls of ONE tile program (ISSUEs 30, 38),
on the CPU.

On an accelerator a tile is `jax_backend.ED_TILE` = 4,096 lanes; off one
it is the backend's `min_bucket` (`jax_backend.ed_tile_width`), here 16
lanes (4 a shard on the mesh of four), so seventy requests make a window
of five tiles on one device and of five tiles a shard on four.  Four
programs compile for the whole file (the tile program with and without
the fold, the same requests in one 128-lane bucket, the mesh's tile
program); one module fixture runs them and every test reads its record.

What is held: the tile calls' verdicts are the single-bucket program's
lane for lane (a bad signature in the first tile, either side of a tile
boundary, in the last tile next to the pad lanes, and a key that does
not decode); the folded verdict names the unfolded vector's first
failure whichever tile holds it, and the host-known failure when that
comes first; `_pad` is the power-of-two ladder up to ED_TILE and whole
tiles above it and `_pad_ed_window` whole tiles whatever the count, on
one chip and a shard; no program is built for a tile count, and
`jax_backend.ed_tiles` counts the tiles one device walked.
"""
import hashlib
from types import SimpleNamespace

import pytest

jax = pytest.importorskip("jax")

from ouroboros_tpu import observe  # noqa: E402
from ouroboros_tpu.crypto import ed25519_ref  # noqa: E402
from ouroboros_tpu.crypto import jax_backend as JB  # noqa: E402
from ouroboros_tpu.crypto.backend import Ed25519Req  # noqa: E402
from ouroboros_tpu.crypto.batching import VerifyService  # noqa: E402
from ouroboros_tpu.crypto.jax_backend import JaxBackend  # noqa: E402
from ouroboros_tpu.parallel import ShardedJaxBackend, make_mesh  # noqa: E402

pytestmark = pytest.mark.device

T = 16                    # one-chip tile of this file: the min_bucket
N = 70                    # requests: 80 lanes = 5 tiles of 16
TILES = 5
SHARDS, MESH_T = 4, 4     # 70 -> 80 lanes = 5 tiles of 4 lanes a shard
# a tampered signature in the first tile, either side of the boundary
# between tiles 0 and 1, and in the last real lane (its neighbour is the
# first pad lane); UNDECODABLE carries a key that is no curve point
BAD_SIGS = (3, 15, 16, 69)
UNDECODABLE = 40
BAD = sorted(BAD_SIGS + (UNDECODABLE,))


def _no_point() -> bytes:
    """32 bytes whose y has no x on the curve."""
    from ouroboros_tpu.crypto import edwards
    y = 2
    while edwards.decompress(y.to_bytes(32, "little")) is not None:
        y += 1
    return y.to_bytes(32, "little")


def _requests(bad_sigs=BAD_SIGS, undecodable=(UNDECODABLE,)) -> list:
    sks = [hashlib.sha256(b"tiles-%d" % i).digest() for i in range(2)]
    vks = [ed25519_ref.public_key(sk) for sk in sks]
    reqs = []
    for i in range(N):
        msg = b"lane-%03d" % i
        sig = ed25519_ref.sign(sks[i & 1], msg)
        if i in bad_sigs:
            sig = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
        vk = _no_point() if i in undecodable else vks[i & 1]
        reqs.append(Ed25519Req(vk, msg, sig))
    return reqs


def _counter(name: str) -> int:
    return observe.metrics.counter(name).value


COUNTERS = ("jax_backend.ed_tiles", "jax_backend.ed_lanes_real",
            "jax_backend.ed_lanes_walked", "jax_backend.composite_builds")


def _xla_backend() -> JaxBackend:
    return JaxBackend(min_bucket=16, use_pallas=False, autotune=False)


# first failures the fold is asked for: (tampered lanes, undecodable)
FOLDS = {
    "tile0": ((3, 69), ()),
    "before_boundary": ((15, 50), ()),
    "after_boundary": ((16, 50), ()),
    "last_tile_pad_neighbour": ((69,), ()),
    "host_known_first": ((69,), (UNDECODABLE,)),
    "device_before_host_known": ((17,), (UNDECODABLE,)),
    "all_good": ((), ()),
}


@pytest.fixture(scope="module")
def runs():
    reg = observe.metrics.registry()
    was_enabled = reg.enabled
    reg.enable()
    out = SimpleNamespace(fold={}, unfolded={}, host_first_bad={})
    try:
        reqs = _requests()
        # the simple batch entry point: one 128-lane bucket program
        flat = JaxBackend(use_pallas=False, autotune=False)
        out.flat_ne = flat._pad(N)
        out.flat = flat.verify_ed25519_batch(reqs)

        tiled = _xla_backend()
        out.tile = tiled.ed_tile
        out.tiled_ne = tiled._pad_ed_window(N)
        c0 = {n: _counter(n) for n in COUNTERS}
        out.tiled = tiled.verify_mixed(reqs)
        out.one_window = {n: _counter(n) - c0[n] for n in COUNTERS}
        for name, (sigs, keys) in FOLDS.items():
            rq = _requests(sigs, keys)
            out.unfolded[name] = tiled.verify_mixed(rq)
            st = tiled.submit_window(rq, fold=True)
            out.host_first_bad[name] = st["host_first_bad"]
            out.fold[name] = tiled.finish_window(st)[0]
        out.all_windows = {n: _counter(n) - c0[n] for n in COUNTERS}
        out.tiled_programs = (sorted(tiled._ed_tile_programs),
                              sorted(tiled._composites),
                              sorted(tiled._folds))
        out.windows = 1 + 2 * len(FOLDS)

        if len(jax.devices()) >= SHARDS:
            mesh = ShardedJaxBackend(make_mesh(SHARDS), min_bucket=16)
            out.mesh_tile = mesh.ed_tile
            out.mesh_ne = mesh._pad_ed_window(N)
            tiles0 = _counter("jax_backend.ed_tiles")
            out.mesh = mesh.verify_mixed(reqs)
            st = mesh.submit_window(_requests(*FOLDS["tile0"]), fold=True)
            out.mesh_fold = mesh.finish_window(st)[0]
            out.mesh_tiles = _counter("jax_backend.ed_tiles") - tiles0
            out.mesh_stats = mesh.padding_stats()
        else:
            out.mesh = None
    finally:
        reg.enabled = was_enabled
    return out


# -- the tile calls against the single bucket ----------------------------------

def test_single_bucket_run_is_the_batch_entry_point(runs):
    assert runs.flat_ne == 128
    assert [i for i, ok in enumerate(runs.flat) if not ok] == BAD


@pytest.mark.parametrize("lane", BAD + [0, 14, 17, 68])
def test_tiled_verdict_equals_single_bucket(runs, lane):
    assert runs.tiled[lane] == runs.flat[lane] == (lane not in BAD)


def test_tiled_verdicts_equal_lane_for_lane(runs):
    assert runs.tile == T and runs.tiled_ne == TILES * T
    assert runs.tiled == runs.flat and len(runs.tiled) == N


# -- the fold across tile boundaries -----------------------------------------

@pytest.mark.parametrize("case", sorted(FOLDS))
def test_fold_names_the_unfolded_first_failure(runs, case):
    sigs, keys = FOLDS[case]
    unfolded = runs.unfolded[case]
    want = min(sigs + keys) if sigs + keys else None
    assert [i for i, ok in enumerate(unfolded) if not ok] \
        == sorted(sigs + keys)
    verdict = runs.fold[case]
    assert verdict.n == N
    assert verdict.first_bad == want
    assert verdict.all_ok == (want is None)


@pytest.mark.parametrize("case", sorted(FOLDS))
def test_host_first_bad_is_the_undecodable_key(runs, case):
    _sigs, keys = FOLDS[case]
    assert runs.host_first_bad[case] == (min(keys) if keys
                                         else JB.FOLD_SENT)


# -- one program whatever the tile count, counted tiles ------------------------

def test_no_program_is_built_for_a_tile_count(runs):
    """Windows of five tiles ran the two forms of the ONE tile program;
    a window of Ed25519 lanes alone has no composite and no fold
    program: the tile calls' running index is its verdict."""
    tile_programs, composites, folds = runs.tiled_programs
    assert tile_programs == [False, True]
    assert composites == [] and folds == []
    assert runs.all_windows["jax_backend.composite_builds"] == 0


def test_ed_tiles_counts_the_tiles_of_every_window(runs):
    assert runs.one_window["jax_backend.ed_tiles"] == TILES
    assert runs.all_windows["jax_backend.ed_tiles"] == TILES * runs.windows


def test_real_and_walked_lanes_are_counted_a_window(runs):
    assert runs.one_window["jax_backend.ed_lanes_real"] == N
    assert runs.one_window["jax_backend.ed_lanes_walked"] == TILES * T
    assert runs.all_windows["jax_backend.ed_lanes_walked"] \
        == TILES * T * runs.windows


@pytest.mark.parametrize("platform,narrowest,want", [
    ("tpu", 128, 4096), ("tpu", 16, 4096), ("gpu", 512, 4096),
    ("cpu", 16, 16), ("cpu", 128, 128), ("cpu", 8192, 4096)])
def test_tile_width_by_platform(platform, narrowest, want):
    """ED_TILE on an accelerator, where the sweep found a cheapest
    width; the narrowest program of the backend off one."""
    assert JB.ED_TILE == 4096
    assert JB.ed_tile_width(platform, narrowest) == want


# -- the padding seam ---------------------------------------------------------

@pytest.mark.parametrize("n,want", [
    (1, 16), (16, 16), (17, 32), (32, 32),          # the ladder, to a tile
    (33, 64), (64, 64), (65, 96), (70, 96), (97, 128), (129, 160)])
def test_pad_is_the_ladder_to_a_tile_and_whole_tiles_above(monkeypatch, n,
                                                           want):
    monkeypatch.setattr(JB, "ED_TILE", 32)
    assert _xla_backend()._pad(n) == want


@pytest.mark.parametrize("n,want", [
    (0, 0), (1, 16), (15, 16), (16, 16), (17, 32), (48, 48), (70, 80),
    (87, 96)])
def test_window_lanes_pad_to_whole_tiles_whatever_the_count(n, want):
    assert _xla_backend()._pad_ed_window(n) == want


@pytest.mark.parametrize("n,want", [
    (1, 4096), (1536, 4096), (4096, 4096), (4097, 8192),
    (23040, 24576), (90624, 94208)])
def test_window_lanes_at_the_shipped_tile(n, want):
    """The benchmark's windows: 1,536 lanes of a night window walk one
    tile, `sync-longchain`'s 23,040 six, `sync-witness`'s 90,624 23."""
    jb = JaxBackend(use_pallas=False, autotune=False)
    jb.ed_tile = JB.ED_TILE          # as on the chip
    assert jb._pad_ed_window(n) == want


@pytest.mark.parametrize("n,want", [
    (1, 128), (129, 256), (512, 512), (2049, 4096), (4096, 4096),
    (4097, 8192), (8192, 8192), (8193, 12288), (90624, 94208),
    (94209, 98304)])
def test_pad_at_the_shipped_tile(n, want):
    assert JB.ED_TILE == 4096 and JB.ED_TILE % 512 == 0
    jb = JaxBackend(use_pallas=False, autotune=False)
    assert jb._pad(n) == want
    if n <= JB.ED_TILE:
        assert want == JB._bucket(n, 128)


@pytest.mark.parametrize("n,want", [
    (5, 16), (17, 20), (64, 64), (65, 128), (70, 128), (130, 192)])
def test_mesh_pad_is_whole_tiles_a_shard_above_a_tile(monkeypatch, n, want):
    if len(jax.devices()) < SHARDS:
        pytest.skip(f"needs {SHARDS} XLA devices (conftest forces 8)")
    monkeypatch.setattr(JB, "ED_TILE", 16)
    assert ShardedJaxBackend(make_mesh(SHARDS), min_bucket=16)._pad(n) \
        == want


def test_mesh_pad_of_the_benchmarks_window():
    """`sync-mesh4`'s 90,624 lanes: 22,656 a shard is wider than a tile,
    so a shard carries six tiles; 512 VRF lanes stay 128 a shard."""
    if len(jax.devices()) < SHARDS:
        pytest.skip(f"needs {SHARDS} XLA devices (conftest forces 8)")
    sb = ShardedJaxBackend(make_mesh(SHARDS))
    assert sb._pad(90624) == 4 * 6 * JB.ED_TILE == 98304
    assert sb._pad(512) == 512 and sb._pad(4 * JB.ED_TILE) == 4 * JB.ED_TILE
    sb.ed_tile = JB.ED_TILE          # as on the chip
    assert sb._pad_ed_window(90624) == 98304
    assert sb._pad_ed_window(1536) == 4 * JB.ED_TILE


# -- the mesh ------------------------------------------------------------------

def test_mesh_window_tiles_per_shard_and_agrees(runs):
    if runs.mesh is None:
        pytest.skip(f"needs {SHARDS} XLA devices (conftest forces 8)")
    assert runs.mesh_tile == MESH_T
    assert runs.mesh_ne == SHARDS * TILES * MESH_T
    assert runs.mesh_tiles == 2 * TILES    # tiles ONE shard walked, twice
    assert runs.mesh_stats["lanes_per_shard_per_window"] == TILES * MESH_T
    assert runs.mesh == runs.tiled == runs.flat


def test_mesh_fold_is_the_minimum_over_the_shards(runs):
    if runs.mesh is None:
        pytest.skip(f"needs {SHARDS} XLA devices (conftest forces 8)")
    assert runs.mesh_fold.first_bad == runs.fold["tile0"].first_bad == 3


# -- the service's histogram asks the backend ---------------------------------

@pytest.mark.parametrize("n", [1, 17, 33, 70])
def test_service_bucket_is_the_backends_pad(monkeypatch, n):
    monkeypatch.setattr(JB, "ED_TILE", 32)
    jb = _xla_backend()
    svc = VerifyService.__new__(VerifyService)
    svc.backend = jb
    assert svc._bucket_of(n) == jb._pad(n)
    svc.backend = object()        # a backend with no padding of its own
    assert svc._bucket_of(n) == n
