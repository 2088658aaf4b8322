"""The window's programs over a device mesh: shard_map over the window axis.

Each device runs the one-chip packed-words cores (the Ed25519 tile body,
the VRF and beta parts of the composite) on its shard of the window's
lanes.  The proofs are independent, so the ladders need no cross-device
communication and throughput scales with the mesh; the one collective
is the fold's `pmin`, in which the shards' first-bad indexes meet.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observe import metrics as _metrics
from ..observe import spans as _spans
from .mesh import WINDOW_AXIS

# what the mesh adds to a window, counted on the mesh path only (the
# one-chip JaxBackend never reaches these): bytes handed to the sharded
# device_put, and the padded lanes ONE shard carries (handles pre-bound)
_SHARD_PUT_BYTES = _metrics.counter("jax_backend.shard_put_bytes")
_SHARD_LANES_PADDED = _metrics.counter("jax_backend.shard_lanes_padded")


@functools.lru_cache(maxsize=8)
def build_sharded_gamma8(mesh: Mesh):
    from ..crypto import vrf_jax
    axis = mesh.axis_names[0]
    mapped = jax.shard_map(
        vrf_jax.gamma8_kernel.__wrapped__, mesh=mesh,
        in_specs=(P(None, axis), P(axis)),
        out_specs=P(axis, None))
    return jax.jit(mapped)


from ..crypto.backend import CryptoBackend  # noqa: F401  (re-export)
from ..crypto import jax_backend as jb
from ..crypto.jax_backend import JaxBackend


class ShardedJaxBackend(JaxBackend):
    """JaxBackend over a device mesh: the window path (submit_window /
    finish_window / verify_mixed and the fold=True verdict reduction) is
    INHERITED — only the two window programs (the Ed25519 tile program
    and the fused composite) are replaced by a shard_map of the very
    same packed-words component cores over the window axis, and every
    batch input lands pre-sharded (`_dev`, `_dev_tiles`).  A tile call
    carries `ed_tile` lanes a shard, so the mesh's tile is `n_shards x
    ed_tile` lanes wide.

    Reusing the single-device composite body per shard is what makes the
    mesh path compile inside the multichip budget: the r5 mesh composite
    traced a mesh-wide monolith of the BIT-ROWS kernel forms (256-bit
    ladders over (256, N) rows), which XLA:CPU chewed on for 4m25s —
    the whole MULTICHIP_r05 rc=124.  The per-shard program here is the
    same split-ladder packed-words program the single-chip path compiles
    in seconds-to-a-minute, and its compiled executable persists in the
    XLA compile cache across processes (compile_cache.cache_dir), so a
    warm container pays no compile at all.

    Inheriting the prep also threads the mesh path through the
    cross-window precomputation cache (crypto/precompute.py): pool-key
    decompression + split tables are served from cache, so warm mesh
    windows ship zero per-key device work — previously a single-chip-
    only property.  KES hash paths still reduce on host here (via the
    cached split), so the composite stays Ed25519+VRF+betas.

    The standalone batch forms (`verify_ed25519_batch`,
    `verify_vrf_batch`) go through the window path too; no replay
    reaches them.  One bit-rows form is left: `vrf_betas_batch`,
    the bit-rows `gamma8_kernel` under shard_map, is what the producer's
    beta prefetch (VrfBetaCache.prefetch) calls for the first TWO
    windows' betas, all there are on a two-window chain; window w+2's
    ride in window w's composite as packed words (`gamma8_words_core`).
    So the mesh keeps a second gamma8 form, and host KES, where the
    one-chip path has packed words and device Blake2b (PERF.md section
    7, "Also open")."""

    def __init__(self, mesh: Mesh, min_bucket: int = 128):
        super().__init__(min_bucket=min_bucket)
        self.mesh = mesh
        self.name = f"jax-mesh-{mesh.devices.size}"
        # report the devices of the MESH, not whatever jax.devices()
        # lists first
        dev0 = mesh.devices.flat[0]
        self.platform = dev0.platform
        self.device_kind = dev0.device_kind
        self.device_count = int(mesh.devices.size)
        # buffer donation for the per-window inputs (see JaxBackend):
        # fresh arrays every window, never read back -> donation-safe
        self._donate = self.platform in ("tpu", "gpu")
        # `min_bucket` is the mesh's narrowest batch, so a shard's share
        # of it is the narrowest tile (off an accelerator)
        self.ed_tile = jb.ed_tile_width(
            self.platform, max(1, self.min_bucket // mesh.devices.size))
        axis = mesh.axis_names[0]
        self._lane_sharding = NamedSharding(mesh, P(None, axis))

    def _pad(self, n: int) -> int:
        """A mesh multiple of at least `min_bucket` lanes; once a shard
        is wider than ED_TILE, whole ED_TILE-wide tiles a shard.  (A
        window's Ed25519 lanes pad to whole tiles a shard whatever
        their count: the inherited `_pad_ed_window`.)"""
        d = self.mesh.devices.size
        m = -(-max(self.min_bucket, n) // d) * d
        if m // d > jb.ED_TILE:
            step = d * jb.ED_TILE
            m = -(-n // step) * step
        return m

    @property
    def n_shards(self) -> int:
        """padding_stats() reports lane occupancy per shard: _pad rounds
        every batch to a mesh multiple, so each device carries
        padded/n_shards lanes of which waste_frac are padding."""
        return int(self.mesh.devices.size)

    def _dev(self, a):
        # every window input is lane-axis-last: shard the lane axis
        a = np.asarray(a)
        _SHARD_PUT_BYTES.inc(a.nbytes)
        with _spans.span("submit.shard_put", cat="dispatch"):
            return jax.device_put(a, self._lane_sharding)

    def _dev_tiles(self, arrays, ne: int) -> list:
        # one sharded device_put for the whole window's tiles: a tile is
        # n_shards x ed_tile lanes, its lane axis split over the mesh
        _SHARD_PUT_BYTES.inc(sum(a.nbytes for a in arrays))
        with _spans.span("submit.shard_put", cat="dispatch"):
            return jax.device_put(self._tiles(arrays, ne),
                                  self._lane_sharding)

    def _dev_scalar(self, v: int):
        return jax.device_put(np.int32(v), NamedSharding(self.mesh, P()))

    def _note_padding(self, used: int, padded: int) -> None:
        super()._note_padding(used, padded)
        _SHARD_LANES_PADDED.inc(padded // self.n_shards)

    def _kes_lanes_to_come(self) -> int:
        return 0                # KES hash paths are walked on the host

    def _split_mixed_device(self, reqs):
        """Mesh windows reduce KES hash paths on host — through the
        cross-window outcome cache (one Merkle walk per (pool, period)
        per process) — so the sharded composite carries no Blake2b jobs.
        Same 8-tuple shape as the single-chip split, with empty KES
        slots."""
        ed_reqs, ed_owner, vrf_reqs, vrf_owner, n = \
            self.split_mixed_cached(reqs)
        return ed_reqs, ed_owner, vrf_reqs, vrf_owner, [], [], [], n

    def verify_ed25519_batch(self, reqs):
        """A standalone batch is a window of one kind: JaxBackend's own
        batch forms hand `_dev` inputs with no lane axis to shard."""
        return self.verify_mixed(reqs)

    verify_vrf_batch = verify_ed25519_batch

    def vrf_betas_batch(self, proofs):
        if not proofs:
            return []
        from ..crypto import vrf_jax
        n = len(proofs)
        m = self._pad(n)
        padded = list(proofs) + [b"\x00" * 80] * (m - n)
        fn = build_sharded_gamma8(self.mesh)
        axis = self.mesh.axis_names[0]
        s2 = NamedSharding(self.mesh, P(None, axis))
        s1 = NamedSharding(self.mesh, P(axis))
        (yG, signG), decode_ok = vrf_jax._prepare_betas(padded)
        handle = fn(jax.device_put(np.asarray(yG), s2),
                    jax.device_put(np.asarray(signG), s1))
        return vrf_jax._finish_betas(np.asarray(handle), decode_ok, n)

    # -- pipelined single-transfer window path ------------------------------
    # submit_window / finish_window / verify_mixed / the fold=True path
    # are inherited from JaxBackend; only the composite is mesh-built.

    def _ed_tile_program(self, fold: bool):
        """The one-chip tile program's body under shard_map: each shard
        verifies its `ed_tile` lanes of the tile; folding, the shards'
        first-bad indexes meet in one `pmin` and the running index stays
        replicated."""
        fn = self._ed_tile_programs.get(fold)
        if fn is not None:
            return fn
        mesh = self.mesh
        axis = mesh.axis_names[0]
        s2 = P(None, axis)
        body = self._ed_tile_body(fold, across=axis)
        mapped = jax.shard_map(
            body, mesh=mesh, in_specs=(P(),) + (s2,) * 9,
            out_specs=P()) if fold else jax.shard_map(
            body, mesh=mesh, in_specs=(s2,) * 8, out_specs=P(axis))
        return self._keep_ed_tile_program(fold, jax.jit(
            mapped, donate_argnums=self._ed_tile_donated(fold)))

    def _window_composite(self, nv: int, nb: int, nk: int):
        """One jitted mesh program per (VRF, beta) shape: shard_map of
        the SAME packed-words component cores the single-device
        composite fuses, each shard running the identical per-shard
        program, the results stitched into JaxBackend's flat uint8
        layout (so finish_window and the fold program are shared
        verbatim).

        Tracing the per-shard body instead of a mesh-wide monolith is
        the compile-budget fix: XLA compiles one shard-sized program +
        the SPMD partitioning, not an N-lane super-program."""
        assert nk == 0, "mesh windows reduce KES on host"
        key = (nv, nb, 0)
        fn = self._composites.get(key)
        if fn is not None:
            return fn
        from ..crypto import vrf_jax
        mesh = self.mesh
        axis = mesh.axis_names[0]
        s2 = P(None, axis)
        in_specs: list = []
        out_specs: list = []
        if nv:
            in_specs.append((s2,) * 7)
            out_specs.append(P(axis, None))
        if nb:
            in_specs.append((s2,) * 2)
            out_specs.append(P(axis, None))

        def body(*present):
            i = 0
            outs = []
            if nv:
                Yw, xa, Gw, sG2, rw, cw, sw_ = present[i]
                i += 1
                outs.append(vrf_jax.vrf_verify_words_core(
                    Yw, xa, Gw, sG2[0], rw, cw, sw_))
            if nb:
                bGw, bsG2 = present[i]
                i += 1
                outs.append(vrf_jax.gamma8_words_core(bGw, bsG2[0]))
            return tuple(outs)

        mapped = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                               out_specs=tuple(out_specs))

        def call(vrf_args, beta_args, kes_args):
            present = [a for a in (vrf_args, beta_args) if a is not None]
            parts = [o.reshape(-1) for o in mapped(*present)]
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

        fn = jax.jit(call, donate_argnums=(0, 1, 2)) if self._donate \
            else jax.jit(call)
        fn = jb._compile_span_on_first_call(
            fn, f"sharded.composite({nv},{nb})"
                f"@mesh{len(self.mesh.devices.flat)}")
        self._composites[key] = fn
        return fn

    # prewarm_window is INHERITED from JaxBackend (ISSUE 11): the mesh
    # and single-device paths share the same compile-outside-timed-
    # regions contract, span name and return shape.
