"""A window's Ed25519 lanes as fixed-width tiles (ISSUE 30), on the CPU.

`jax_backend.ED_TILE` is 4,096 lanes on the chip; here it is monkeypatched
to 32 (16 on the mesh) with `min_bucket=16`, so seventy requests make a
window of three tiles on one device and of two tiles a shard on four.
Four programs compile for the whole file (the tiled composite and its
fold, the same requests in one 128-lane bucket, the tiled mesh
composite); one module fixture runs them and every test reads its
record.

What is held: the tiled program's verdicts are the single-bucket
program's lane for lane (a bad signature in the first tile, across a
tile boundary, in the last tile next to the pad lanes, and a key that
does not decode); the folded verdict names the unfolded vector's first
failure whichever tile holds it, and the host-known failure when that
comes first; `_pad` is the power-of-two ladder up to a tile and whole
tiles above it, on one chip and a shard; one composite is built for a
window of many tiles, and `jax_backend.ed_tiles` counts the tiles one
device walked.
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

T = 32                    # one-chip tile of this file
N = 70                    # requests: 96 lanes = 3 tiles of 32
SHARDS, MESH_T = 4, 16    # 70 -> 128 lanes = 2 tiles of 16 a shard
# a tampered signature in the first tile, either side of the boundary
# between tiles 0 and 1, and in the last real lane (its neighbour is the
# first pad lane); UNDECODABLE carries a key that is no curve point
BAD_SIGS = (3, 31, 32, 69)
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


def _xla_backend() -> JaxBackend:
    return JaxBackend(min_bucket=16, use_pallas=False, autotune=False)


# first failures the fold is asked for: (tampered lanes, undecodable)
FOLDS = {
    "tile0": ((3, 69), ()),
    "before_boundary": ((31, 50), ()),
    "after_boundary": ((32, 50), ()),
    "last_tile_pad_neighbour": ((69,), ()),
    "host_known_first": ((69,), (UNDECODABLE,)),
    "device_before_host_known": ((33,), (UNDECODABLE,)),
    "all_good": ((), ()),
}


@pytest.fixture(scope="module")
def runs():
    reg = observe.metrics.registry()
    was_enabled = reg.enabled
    reg.enable()
    mp = pytest.MonkeyPatch()
    out = SimpleNamespace(fold={}, unfolded={}, host_first_bad={})
    try:
        reqs = _requests()
        # today's program: ED_TILE as shipped, one 128-lane bucket
        tiles0 = _counter("jax_backend.ed_tiles")
        flat = _xla_backend()
        out.flat_ne = flat._pad(N)
        out.flat = flat.verify_mixed(reqs)
        out.flat_tiles = _counter("jax_backend.ed_tiles") - tiles0

        mp.setattr(JB, "ED_TILE", T)
        tiled = _xla_backend()
        out.tiled_ne = tiled._pad(N)
        builds0 = _counter("jax_backend.composite_builds")
        tiles0 = _counter("jax_backend.ed_tiles")
        out.tiled = tiled.verify_mixed(reqs)
        out.tiles_one_window = _counter("jax_backend.ed_tiles") - tiles0
        for name, (sigs, keys) in FOLDS.items():
            rq = _requests(sigs, keys)
            out.unfolded[name] = tiled.verify_mixed(rq)
            st = tiled.submit_window(rq, fold=True)
            out.host_first_bad[name] = st["host_first_bad"]
            out.fold[name] = tiled.finish_window(st)[0]
        out.tiled_builds = _counter("jax_backend.composite_builds") - builds0
        out.tiled_programs = sorted(tiled._composites)
        out.windows = 1 + 2 * len(FOLDS)
        out.tiles_all = _counter("jax_backend.ed_tiles") - tiles0

        if len(jax.devices()) >= SHARDS:
            mp.setattr(JB, "ED_TILE", MESH_T)
            mesh = ShardedJaxBackend(make_mesh(SHARDS), min_bucket=16)
            out.mesh_ne = mesh._pad(N)
            tiles0 = _counter("jax_backend.ed_tiles")
            out.mesh = mesh.verify_mixed(reqs)
            out.mesh_tiles = _counter("jax_backend.ed_tiles") - tiles0
            out.mesh_stats = mesh.padding_stats()
        else:
            out.mesh = None
    finally:
        mp.undo()             # the tests below see the shipped ED_TILE
        reg.enabled = was_enabled
    return out


# -- the tiled program against the single bucket -----------------------------

def test_single_bucket_run_is_todays_program(runs):
    assert runs.flat_ne == 128 and runs.flat_tiles == 0
    assert [i for i, ok in enumerate(runs.flat) if not ok] == BAD


@pytest.mark.parametrize("lane", BAD + [0, 30, 33, 68])
def test_tiled_verdict_equals_single_bucket(runs, lane):
    assert runs.tiled[lane] == runs.flat[lane] == (lane not in BAD)


def test_tiled_verdicts_equal_lane_for_lane(runs):
    assert runs.tiled_ne == 3 * T
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


# -- one program, counted tiles ----------------------------------------------

def test_one_composite_for_a_window_of_many_tiles(runs):
    assert runs.tiled_builds == 1
    assert runs.tiled_programs == [(3 * T, 0, 0, 0, False)]


def test_ed_tiles_counts_the_tiles_of_every_window(runs):
    assert runs.tiles_one_window == runs.tiled_ne // T == 3
    assert runs.tiles_all == 3 * runs.windows


def test_ed_tiles_helper():
    T0 = JB.ED_TILE
    assert [JB.ed_tiles(n) for n in (0, 1, T0, T0 + 1, 2 * T0, 12 * T0)] \
        == [0, 0, 0, 1, 2, 12]


# -- the padding seam ---------------------------------------------------------

@pytest.mark.parametrize("n,want", [
    (1, 16), (16, 16), (17, 32), (32, 32),          # the ladder, to a tile
    (33, 64), (64, 64), (65, 96), (70, 96), (97, 128), (129, 160)])
def test_pad_is_the_ladder_to_a_tile_and_whole_tiles_above(monkeypatch, n,
                                                           want):
    monkeypatch.setattr(JB, "ED_TILE", T)
    assert _xla_backend()._pad(n) == want


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
    monkeypatch.setattr(JB, "ED_TILE", MESH_T)
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


# -- the mesh ------------------------------------------------------------------

def test_mesh_window_tiles_per_shard_and_agrees(runs):
    if runs.mesh is None:
        pytest.skip(f"needs {SHARDS} XLA devices (conftest forces 8)")
    assert runs.mesh_ne == SHARDS * 2 * MESH_T
    assert runs.mesh_tiles == 2            # tiles ONE shard walked
    assert runs.mesh_stats["lanes_per_shard_per_window"] == 2 * MESH_T
    assert runs.mesh == runs.tiled == runs.flat


# -- the service's histogram asks the backend ---------------------------------

@pytest.mark.parametrize("n", [1, 17, 33, 70])
def test_service_bucket_is_the_backends_pad(monkeypatch, n):
    monkeypatch.setattr(JB, "ED_TILE", T)
    jb = _xla_backend()
    svc = VerifyService.__new__(VerifyService)
    svc.backend = jb
    assert svc._bucket_of(n) == jb._pad(n)
    svc.backend = object()        # a backend with no padding of its own
    assert svc._bucket_of(n) == n
