"""Ask the chip's compiler, without a chip (rehearsal 3 of the
on-chip-measurement guide): the programs of ONE 1024-block replay window
of chip_smoke.py's chain, at their real widths, compiled for a described TPU
v5e by the compiler installed here.

What a CPU run cannot show, this does: a program that does not fit the
device's memory, or one the partitioner refuses.  Nothing runs, so it
says nothing about results or times on the chip — `chip_smoke.py` does
that.

The persistent compilation cache is off around these tests: a compile
for a described device is written to it but cannot be read back without
a chip.

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu, and under xdist every worker
imports every test file.  Keep these tests in this ONE file.

Tier-1 keeps the programs every window of every cell calls (the
Ed25519 tile program in both fold forms, the fold, the per-key fill)
and the cores of the Ed25519 ladder, gamma8 and the KES hash.  The VRF
core and the whole composites are `slow`; their seconds are in
CHANGES.md (PR 22).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from ouroboros_tpu.crypto import blake2b_jax as B2
from ouroboros_tpu.crypto import ed25519_jax as EJ
from ouroboros_tpu.crypto import jax_backend as JB
from ouroboros_tpu.crypto import vrf_jax as VJ

# minutes of compile off the chip: conftest.py starts this file first
pytestmark = pytest.mark.device

# one 1024-block window of chip_smoke.py's chain (SYNTH: 2 txs/block,
# depth-10 KES): OCert + KES leaf + 2 witnesses per block, 2 VRF proofs per block,
# the next-next window's betas, the cold KES hash-path jobs.  Since the
# hash-path outcomes are cached per (pool, period) a window of this
# chain ships ~1,200 Blake2b jobs (bucket 2048); a window in which every
# block opens a new period ships 10 per block (bucket 16384, the shape
# the last recorded rounds had).
NE, NV, NB, NK = 4096, 2048, 2048, 2048
NK_ALL_NEW = 16384

U32, I32, U8 = jnp.uint32, jnp.int32, jnp.uint8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs under /tmp
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # lower the jitted program itself, not the span wrapper around it
    mp.setattr(JB, "_compile_span_on_first_call", lambda fn, name: fn)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(s):
    """ShapeDtypeStruct maker for arguments placed by sharding `s`."""
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=s)


def _ed_args(n, s):
    S = _spec(s)
    return ((S((8, n), U32),) * 5 + (S((1, n), I32),)
            + (S((8, n), U32),) * 2)


def _vrf_args(n, s):
    S = _spec(s)
    return (S((8, n), U32), S((8, n), U32), S((8, n), U32),
            S((1, n), I32), S((8, n), U32), S((4, n), U32),
            S((8, n), U32))


def _beta_args(n, s):
    S = _spec(s)
    return (S((8, n), U32), S((1, n), I32))


def _kes_args(n, s):
    S = _spec(s)
    return (S((16, n), U32), S((8, n), U32))


XLA = {
    "ed25519_split": (
        lambda Aw, xa, xw, yw, Rw, sR, sw, kw:
            EJ.verify_full_split_words_core(Aw, xa, xw, yw, Rw, sR[0],
                                            sw, kw),
        lambda s: _ed_args(NE, s)),
    "vrf_verify": (
        lambda Yw, xa, Gw, sG, rw, cw, sw:
            VJ.vrf_verify_words_core(Yw, xa, Gw, sG[0], rw, cw, sw),
        lambda s: _vrf_args(NV, s)),
    "gamma8": (lambda Gw, sG: VJ.gamma8_words_core(Gw, sG[0]),
               lambda s: _beta_args(NB, s)),
    "kes_hash": (lambda mw, ew: B2.check_block64(mw, ew),
                 lambda s: _kes_args(NK, s)),
    "kes_hash_all_new": (lambda mw, ew: B2.check_block64(mw, ew),
                         lambda s: _kes_args(NK_ALL_NEW, s)),
}


@pytest.mark.parametrize("kernel", [
    "ed25519_split",
    pytest.param("vrf_verify", marks=pytest.mark.slow),
    "gamma8",
    "kes_hash",
    "kes_hash_all_new"])
def test_xla_form_compiles_for_v5e(kernel, one_chip):
    """The core of each window part, at the window's lane count: the
    one form there is (no custom call in it)."""
    fn, make_args = XLA[kernel]
    compiled = jax.jit(lambda *a: fn(*a)).lower(
        *make_args(one_chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def _backend() -> JB.JaxBackend:
    be = JB.JaxBackend()
    be._donate = True       # as on the chip (off on the CPU it sees here)
    be.ed_tile = JB.ED_TILE   # likewise: the CPU's tile is `min_bucket`
    return be


@pytest.mark.parametrize("fold", [True, False])
def test_ed_tile_program_compiles_for_v5e(fold, one_chip):
    """THE Ed25519 program of the window path, at the chip's tile: a
    full-body window (the benchmark's 90,624 lanes) is 23 calls of it,
    so its temporaries are a tile's: the flat program of 131,072 lanes
    asks the compiler for 4 GB."""
    S = _spec(one_chip)
    run = _backend()._ed_tile_program(fold)
    carry = (S((), I32), S((1, JB.ED_TILE), I32)) if fold else ()
    compiled = run.lower(*carry, *_ed_args(JB.ED_TILE, one_chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29


@pytest.mark.parametrize("nb,nk", [(NB, NK), (0, NK)])
def test_fold_program_compiles_for_v5e(nb, nk, one_chip):
    """The verdict fold (device SHA-512 challenge check + first-bad min)
    over one window's packed buffer and the Ed25519 tiles' running
    first-bad index, for both shapes a replay meets:
    windows that carry betas and the last two that do not."""
    S = _spec(one_chip)
    fold = _backend()._fold_program(NV, nb, nk)
    fold.lower(
        S((130 * NV + 33 * nb + nk,), U8), S((), I32),
        S((NV,), I32), S((NV, 32), U8), S((NV, 16), U8)).compile()


@pytest.mark.parametrize("width", ["narrow", "tile"])
def test_key_fill_compiles_for_v5e(width, one_chip):
    """The per-key fill the precompute cache dispatches for new keys
    (decompress + 128 doublings, words in and out), at its two widths:
    the narrow program of a handful of new keys and the ED_TILE-lane
    tile of a window whose witness keys are all new; a tile's
    temporaries stay far under the flat 131,072-lane program's 4 GB."""
    from ouroboros_tpu.crypto import precompute
    lanes = precompute.FILL_NARROW if width == "narrow" else JB.ED_TILE
    S = _spec(one_chip)
    compiled = jax.jit(EJ.a128_words_core).lower(
        S((8, lanes), U32), S((lanes,), I32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 27


@pytest.mark.slow
def test_window_composite_compiles_for_v5e(one_chip):
    """One whole fused window composite: what ONE new window shape
    costs a cold start."""
    comp = _backend()._window_composite(NV, NB, NK)
    compiled = comp.lower(
        _vrf_args(NV, one_chip), _beta_args(NB, one_chip),
        _kes_args(NK, one_chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.slow
def test_sharded_composite_compiles_for_four_v5e(topo):
    """`chip_smoke.py --mesh 4`'s programs: the sharded window composite
    and the sharded Ed25519 tile program over a mesh of the four
    described chips, each holding a quarter of the lanes."""
    from ouroboros_tpu.parallel import ShardedJaxBackend
    from ouroboros_tpu.parallel.mesh import WINDOW_AXIS
    mesh = Mesh(np.array(topo.devices[:4]), (WINDOW_AXIS,))
    lanes = NamedSharding(mesh, P(None, WINDOW_AXIS))
    sb = ShardedJaxBackend(mesh)
    assert sb._donate and sb.device_count == 4 and sb.platform == "tpu"
    comp = sb._window_composite(NV, NB, 0)
    compiled = comp.lower(
        _vrf_args(NV, lanes), _beta_args(NB, lanes), None).compile()
    per_dev = compiled.memory_analysis()
    assert per_dev.argument_size_in_bytes < 2 ** 30
    assert sb.ed_tile == JB.ED_TILE
    S = _spec(lanes)
    tile = sb._ed_tile_program(True).lower(
        _spec(NamedSharding(mesh, P()))((), I32),
        S((1, 4 * JB.ED_TILE), I32),
        *_ed_args(4 * JB.ED_TILE, lanes)).compile()
    assert "all-reduce" in tile.as_text()      # the shards' pmin
