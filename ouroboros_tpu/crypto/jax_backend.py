"""JaxBackend — the TPU-batched CryptoBackend instance.

Routes Ed25519 batches through the split-128 ladder kernels (half the
doubling chain via the per-key [2^128]A cache, ed25519_jax split-ladder
notes) and VRF batches through the packed vrf kernels (decompression,
Elligator2 and both Strauss ladders fused into one device call).  KES
hash paths run as one batched Blake2b-256 device check (blake2b_jax)
instead of per-item host hashing.

ALL device inputs travel as packed uint32 words, 32x smaller than the
(256, N) int32 bit rows of earlier rounds.  Unpacking is a tiny
on-device prologue fused ahead of the ladders.  The link is
not the bottleneck it was on the device this form was designed for (a
whole 4096-lane Ed25519 dispatch + compute + drain took 14 ms on a TPU
v5 lite — smoke reading, PR 22, ROADMAP A2); the packed form is kept
because it is never the larger transfer.

Batch sizes up to ED_TILE lanes are padded to power-of-two buckets (min
128) so repeated calls hit the jit cache instead of recompiling per
shape.  A WINDOW's Ed25519 lanes are no program's shape at all: they go
to the device as whole tiles, one asynchronous call of the ONE tile
program a tile (`_ed_tile_program`; `ed_tile` lanes, ED_TILE on an
accelerator), so a replay whose windows hold 1,500 lanes and 90,000
builds what a replay of equal windows builds, and the device walks only
the tiles that hold a real lane.

Every device program has ONE form, the jitted XLA cores of
ed25519_jax, vrf_jax and blake2b_jax, on every platform: the form every
cell of the benchmark times.  (A second, Pallas form of each ladder and
a tuner that chose between the two went in PR 44; the last tree that
holds them is that PR's parent.)

Repeated verification keys cost nothing past their first window: the
cross-window precomputation cache (crypto/precompute.py) memoises the
per-key device work (Ed25519/VRF point decompression + split tables, KES
hash-path outcomes), so a cache-warm window dispatches only the ladders.
"""
from __future__ import annotations

import numpy as np

from ..observe import metrics as _metrics
from ..observe import spans as _spans
from . import blake2b_jax as B2
from . import ed25519_jax as EJ
from . import edwards as ed
from . import kes as kes_mod
from .backend import (
    CryptoBackend, Ed25519Cols, Ed25519Req, KesReq, VrfReq, ed25519_columns,
    lane_count,
)
from .precompute import GLOBAL_PRECOMPUTE_CACHE

# observational (gated) counters: window/dispatch volume on the hot path
_WINDOWS = _metrics.counter("jax_backend.windows_submitted")
_COMPOSITE_BUILDS = _metrics.counter("jax_backend.composite_builds")
_FOLD_WINDOWS = _metrics.counter("jax_backend.fold_windows")
# lane occupancy: real requests vs padded bucket lanes per window — the
# mesh backend's padding additionally rounds to a mesh multiple, so the
# waste fraction (1 - used/padded) is the per-shard occupancy cost the
# MULTICHIP_OBS line and the benchmark's `lane_pad_share` report
_LANES_USED = _metrics.counter("jax_backend.lanes_used")
_LANES_PADDED = _metrics.counter("jax_backend.lanes_padded")
# tiles ONE device walks for a window's Ed25519 lanes (one call of the
# tile program each), added once a window at dispatch
_ED_TILES = _metrics.counter("jax_backend.ed_tiles")
# a window's real Ed25519 lanes, and the lanes of the tiles handed to the
# device(s) for them (never a capacity: only tiles that hold a real lane);
# windows whose tile count differs from the previous window's of the same
# replay (`begin_replay` forgets the previous one)
_ED_LANES_REAL = _metrics.counter("jax_backend.ed_lanes_real")
_ED_LANES_WALKED = _metrics.counter("jax_backend.ed_lanes_walked")
# of a window's real Ed25519 lanes, those that reached the packer inside
# a columns item (`Ed25519Cols`: no request object was made for them);
# 0 = the ledger handed objects
_ED_ROW_LANES = _metrics.counter("jax_backend.ed_row_lanes")
_ED_WIDTH_CHANGES = _metrics.counter("jax_backend.ed_width_changes")
# what a window's two occasional parts held (real work, not padding):
# windows whose composite carried betas for the window two ahead and the
# beta rows they carried; windows that scheduled no KES hash-path job
_BETA_WINDOWS = _metrics.counter("jax_backend.beta_windows")
_BETA_ROWS = _metrics.counter("jax_backend.beta_rows_carried")
_KES_EMPTY = _metrics.counter("jax_backend.kes_empty_windows")
# windows that had none of the three (a Byron window that carries no
# beta for a Shelley window ahead): tile calls only, no composite, no
# fold program
_COMPOSITE_FREE = _metrics.counter("jax_backend.composite_free_windows")

# device-side verdict-fold sentinel: "no failing request".  int32 max so
# jnp.min over any real request index beats it; request lists are bounded
# far below it (a window is ~thousands of proofs).
FOLD_SENT = 0x7FFFFFFF


def _compile_span_on_first_call(fn, name: str):
    """Wrap a jitted program so its FIRST invocation — the one paying
    XLA trace+compile — runs inside a `compile` span.  Later calls go
    straight through: steady-state dispatch must not be attributed to
    compile (and costs one list lookup when observation is off)."""
    pending = [True]

    def run(*a):
        if pending:
            pending.clear()
            with _spans.span(name, cat="compile"):
                return fn(*a)
        return fn(*a)
    return run


def _bucket(n: int, lo: int = 128) -> int:
    """Smallest lo * 2^k that holds n: the shapes of every batch of at
    most ED_TILE lanes (`JaxBackend._pad`)."""
    m = lo
    while m < n:
        m *= 2
    return m


# Width of one Ed25519 tile on an accelerator, in lanes.  What a lane
# costs the ladder is not flat in the width of the program: 3.5 us at
# 2,048 and 4,096 lanes, 3.9 at 8,192, 5.8 at 16,384, 7.8 at 32,768, 19.0
# at 65,536 and 29.1 at 131,072 (a TPU v5e; PERF.md section 6, PR 30, has
# the sweep and the device operations that grow).  So a window's lanes go
# to the device ED_TILE at a time, one call of the one tile program a
# tile (`JaxBackend._ed_tile_program`), and the power-of-two buckets
# of the simple batch entry points top out here.  Tests reach a small
# tile by monkeypatching this name.
ED_TILE = 4096


def ed_tile_width(platform: str, narrowest: int) -> int:
    """Lanes ONE device walks in one call of the Ed25519 tile program.
    On an accelerator ED_TILE, the width at which the ladder's lane is
    cheapest (the sweep above).  XLA:CPU has no such width: a lane costs
    it the same in a 64-lane and a 4,096-lane program (7.0 and 6.5 ms,
    sandbox), so a pad lane is never cheaper than a real one and the
    `narrowest` program the backend builds is the tile: tests and CPU
    rehearsals set it with `min_bucket`."""
    return ED_TILE if platform in ("tpu", "gpu") else min(ED_TILE,
                                                          narrowest)


def batch_inverse(vals: list[int]) -> list[int]:
    """Montgomery trick: invert N field elements with one pow."""
    n = len(vals)
    out = [0] * n
    prefix = [1] * (n + 1)
    for i, v in enumerate(vals):
        prefix[i + 1] = prefix[i] * (v if v else 1) % ed.P
    inv_all = pow(prefix[n], ed.P - 2, ed.P)
    for i in range(n - 1, -1, -1):
        v = vals[i] if vals[i] else 1
        out[i] = prefix[i] * inv_all % ed.P
        inv_all = inv_all * v % ed.P
    return out


def _pad_words(w: np.ndarray, m: int) -> np.ndarray:
    """Pad the lane axis of a words/sign array out to m columns."""
    n = w.shape[-1]
    if n == m:
        return w
    pad = [(0, 0)] * (w.ndim - 1) + [(0, m - n)]
    return np.pad(w, pad)


class JaxBackend(CryptoBackend):
    # instances carry "jax-<platform>" of the devices they took
    # (`platform`, `device_kind`, `device_count` beside it); the class
    # attribute only names the family
    name = "jax"
    # submit_window(fold=True) folds verdicts on device into one
    # WindowVerdict scalar instead of a per-proof vector (the
    # producer/consumer replay driver asks — consensus/pipeline.py)
    supports_window_fold = True

    def __init__(self, min_bucket: int = 128, use_pallas: bool | None = False,
                 autotune: bool | None = False):
        # `use_pallas` and `autotune` select nothing: the kernels have
        # one form since PR 44.  The names stay until the benchmark's
        # configurations stop passing them (ROADMAP C19).
        for name, value in (("use_pallas", use_pallas),
                            ("autotune", autotune)):
            if value not in (None, False):
                raise ValueError(
                    f"JaxBackend({name}={value!r}): the Pallas kernels "
                    f"and the tuner went in PR 44; the argument takes "
                    f"only False or None and selects nothing")
        import jax
        self._devices = jax.devices()
        self.platform = self._devices[0].platform
        self.device_kind = self._devices[0].device_kind
        self.device_count = len(self._devices)
        self.name = f"jax-{self.platform}"
        self.min_bucket = min_bucket
        self.ed_tile = ed_tile_width(self.platform, self.min_bucket)
        self._composites: dict = {}   # (nv, nb, nk) -> program
        self._folds: dict = {}        # (nv, nb, nk) -> fold program
        self._ed_tile_programs: dict = {}  # fold -> tile program
        # donate the window inputs to the composite so a warm-path window
        # reuses the previous window's device buffers instead of
        # reallocating (XLA:CPU ignores donation with a warning -> gate)
        self._donate = self.platform in ("tpu", "gpu")
        # per-instance lane occupancy accumulators (padding_stats());
        # written only on the submit path, which has a single writer
        # thread in the pipelined replay (the producer)
        self._lanes_used = 0
        self._lanes_padded = 0
        self._windows_padded = 0
        # tiles one device walked for the previous window of this replay
        self._prev_ed_tiles = None
        # padded VRF and beta lanes of the widest window the replay
        # driver has in sight (`expect_lanes`)
        self._in_sight = (0, 0)

    # -- subclass seams (ShardedJaxBackend overrides them) -------------------
    def _pad(self, n: int) -> int:
        """Padding of every batch but a window's Ed25519 lanes (the
        simple batch entry points; a window's VRF, beta and KES parts):
        power-of-two buckets from `min_bucket` up to ED_TILE lanes, above
        that the next multiple of ED_TILE.  The mesh backend pads to a
        mesh multiple.  A window's Ed25519 lanes pad to whole tiles
        (`_pad_ed_window`) and are no program's shape."""
        m = _bucket(n, self.min_bucket)
        return m if m <= ED_TILE else -(-n // ED_TILE) * ED_TILE

    def _pad_ed_window(self, n: int) -> int:
        """Lanes the device(s) walk for a window of `n` Ed25519 lanes:
        whole tiles of `ed_tile` lanes a device, at least one; 0 for
        none.  Only tiles that hold a real lane."""
        step = self.ed_tile * self.n_shards
        return -(-n // step) * step

    def _dev(self, a):
        """Host array -> device array for a lane-axis-last batch input;
        the mesh backend device_puts with the window-axis sharding."""
        import jax.numpy as jnp
        return jnp.asarray(a)

    def _tiles(self, arrays, ne: int) -> list:
        """A window's Ed25519 lane arrays ((rows, ne) each, lane axis
        last) cut into one tuple of host arrays a tile call: tile t
        holds lanes [t * step, (t + 1) * step), step = `ed_tile` lanes a
        device."""
        step = self.ed_tile * self.n_shards
        return [tuple(a[:, off:off + step] for a in arrays)
                for off in range(0, ne, step)]

    def _dev_tiles(self, arrays, ne: int) -> list:
        """`_tiles` on the device(s), in ONE device_put (the mesh
        backend splits each tile's lane axis over the mesh)."""
        import jax
        return jax.device_put(self._tiles(arrays, ne))

    def _dev_scalar(self, v: int):
        """One int32 on the device(s): the fold's running first-bad
        index before the first tile (the mesh backend replicates it)."""
        import jax
        return jax.device_put(np.int32(v))   # a copy, no program

    def begin_replay(self) -> None:
        """A new sequence of windows starts (the replay driver's
        producer says so): its first window has no previous window to
        differ from (`jax_backend.ed_width_changes`), and nothing is in
        sight yet."""
        self._prev_ed_tiles = None
        self._in_sight = (0, 0)

    def expect_lanes(self, vrf: int, betas: int) -> None:
        """The replay driver's word, before a submit, on the windows it
        has decoded and not yet submitted: the most VRF proofs one of
        them holds, and the most beta proofs this submit or the next
        will carry for them.  A composite built for this window is made
        wide enough for those too (`_occasional_widths`), so the windows
        of a chain ride ONE composite in whichever order its eras meet
        the backend."""
        self._in_sight = (self._pad(vrf) if vrf else 0,
                          self._pad(betas) if betas else 0)

    def _kes_lanes_to_come(self) -> int:
        """KES hash-job lanes a composite gets that is built BEFORE the
        first window with a KES signature: the narrowest part there is
        (a pool's path is walked once, `depth` jobs; a window that
        brings more than fit builds its own)."""
        return self._pad(1)

    # -- lane occupancy ------------------------------------------------------
    def _note_padding(self, used: int, padded: int) -> None:
        """Record one window's lane occupancy (real requests vs padded
        bucket lanes across every component batch).  Runs on the submit
        path — the producer thread in the pipelined replay."""
        self._lanes_used += used
        self._lanes_padded += padded
        self._windows_padded += 1
        _LANES_USED.inc(used)
        _LANES_PADDED.inc(padded)

    @property
    def n_shards(self) -> int:
        """Devices the window batch is split over (1 off-mesh; the mesh
        backend overrides via its mesh size)."""
        return 1

    def padding_stats(self, since: Optional[dict] = None) -> dict:
        """Lane occupancy over every window this instance submitted:
        ``waste_frac`` is the fraction of padded lanes that carried no
        real request — on the mesh backend the same fraction per shard,
        since sharding splits the padded batch evenly.  The MULTICHIP
        dryrun and chip_smoke.py embed this dict.  Pass a previously
        returned dict as `since` to get the delta (one replay's windows
        instead of the instance lifetime)."""
        used, padded = self._lanes_used, self._lanes_padded
        windows = self._windows_padded
        if since is not None:
            used -= since["lanes_used"]
            padded -= since["lanes_padded"]
            windows -= since["windows"]
        per_shard = padded // (self.n_shards * max(windows, 1))
        return {
            "windows": windows,
            "lanes_used": used,
            "lanes_padded": padded,
            "waste_frac": round(1.0 - used / padded, 4) if padded
            else 0.0,
            "shards": self.n_shards,
            "lanes_per_shard_per_window": per_shard,
        }

    def prewarm_window(self, reqs, next_beta_proofs=(),
                       fold: bool = False):
        """Run one full window for `reqs` NOW — compiling its composite
        (and, with fold=True, the verdict-fold program) outside any
        timed/timeout-budgeted region — returning ``(wall_seconds, ok)``:
        the seconds (dominated by XLA compile on a cold cache) plus the
        window's verdicts — the per-request bool vector, or with
        fold=True the WindowVerdict scalar (gate on ``ok.all_ok``) — so
        callers assert correctness on THIS run instead of paying a
        duplicate window for it.  Shared by the single-device and mesh
        paths (MULTICHIP_r05 follow-up: a silent 4m25s compile inside
        the timed region turned into rc=124 with zero attribution; the
        dryrun pre-warms and reports this number instead)."""
        import time as _time
        t0 = _time.perf_counter()
        with _spans.span("window.prewarm", cat="compile"):
            ok, _ = self.finish_window(
                self.submit_window(reqs, next_beta_proofs, fold=fold))
        return _time.perf_counter() - t0, ok

    # -- host prep ----------------------------------------------------------
    def _pack_ed(self, reqs, m: int):
        """Packed-words prep for an Ed25519 batch padded to m, on the
        host, with the per-key tables on their way: returns (the six
        arrays of `prepare_words_batch`, parse_ok, the tables' handle)
        for `_finish_ed`.

        The order is the point.  The key column is in hand first, so
        the tables come first: `begin_assemble` dispatches the fill of
        the keys the cache has not seen and waits for nothing.  Then the
        lanes are hashed and packed, which needs no table, with the
        device at work on the fill beside it; the caller may put more
        such work (the window's VRF and KES packers) before it asks for
        the tables in `_finish_ed`.  Hashed first, as it was until PR
        46, a window of new keys held the producer for the device's
        whole 0.2 s with nothing else of the window on the chip.  A
        batch whose keys all hit gets a handle with nothing in flight:
        one path for every window."""
        vks, msgs, sigs = ed25519_columns(reqs)
        pad = m - len(vks)
        vks = [*vks, *[b"\x00" * 32] * pad]
        tables = EJ.GLOBAL_A128_CACHE.begin_assemble(vks)
        arrays, parse_ok = EJ.prepare_words_batch(
            vks, [*msgs, *[b""] * pad], [*sigs, *[b"\x00" * 64] * pad])
        return arrays, parse_ok, tables

    def _finish_ed(self, packed):
        """`_pack_ed`'s batch with its tables: (the eight (rows, m) lane
        arrays of `verify_full_split_words_core`, parse_ok).  Waits for
        what is left of the fill and stores it; keys the cache could
        not decompress are masked out of parse_ok (the kernels trust the
        cached affine x and skip the A square root)."""
        (Aw, _signA, Rw, signR, sw, kw), parse_ok, tables = packed
        xa, xw, yw, known = EJ.GLOBAL_A128_CACHE.finish_assemble(tables)
        return ((Aw, xa, xw, yw, Rw, signR.reshape(1, -1), sw, kw),
                parse_ok & known)

    def _prep_ed(self, reqs, m: int):
        """`_pack_ed` and `_finish_ed` with every array on the device:
        one program as wide as the batch (the simple batch entry
        point)."""
        arrays, parse_ok = self._finish_ed(self._pack_ed(reqs, m))
        return tuple(self._dev(a) for a in arrays), parse_ok

    def verify_ed25519_batch(self, reqs):
        if not reqs:
            return []
        n = len(reqs)
        args, parse_ok = self._prep_ed(reqs, self._pad(n))
        Aw, xa, xw, yw, Rw, signR2, sw, kw = args
        ok = np.asarray(EJ.verify_full_split_words_kernel(
            Aw, xa, xw, yw, Rw, signR2[0], sw, kw))
        return [bool(o) and bool(p)
                for o, p in zip(ok[:n], parse_ok[:n])]

    def _prep_vrf(self, reqs, m: int):
        from . import vrf_jax
        pad = m - len(reqs)
        vks = [r.vk for r in reqs] + [b"\x00" * 32] * pad
        args, parse_ok, gamma_ok, s_ok, pf_arr = vrf_jax._prepare_words(
            vks,
            [r.alpha for r in reqs] + [b""] * pad,
            [r.proof for r in reqs] + [b"\x00" * 80] * pad)
        Yw, _signY, Gw, signG, rw, cw, sw = args
        xa, _x128, _y128, known = EJ.GLOBAL_A128_CACHE.assemble(vks)
        dev = (self._dev(Yw), self._dev(xa),
               self._dev(Gw), self._dev(signG.reshape(1, -1)),
               self._dev(rw), self._dev(cw), self._dev(sw))
        return dev, (parse_ok & known, gamma_ok, s_ok, pf_arr)

    def verify_vrf_batch(self, reqs):
        """Verify + on-device challenge fold: the (m, 130) point rows
        never leave the device — 1 B/proof crosses the link instead of
        130 B."""
        if not reqs:
            return []
        from . import vrf_jax
        n = len(reqs)
        dev, (parse_ok, _gamma_ok, _s_ok, pf_arr) = self._prep_vrf(
            reqs, self._pad(n))
        Yw, xa, Gw, signG2, rw, cw, sw = dev
        ok = np.asarray(vrf_jax.vrf_verify_fold_words_kernel(
            Yw, xa, Gw, signG2[0], rw, cw, sw,
            self._dev(np.ascontiguousarray(pf_arr[:, :32])),
            self._dev(np.ascontiguousarray(pf_arr[:, 32:48])),
            self._dev(parse_ok.astype(np.uint8))))
        return [bool(o) for o in ok[:n]]

    # largest single gamma8 dispatch: bounds the set of compiled shapes
    BETA_CHUNK = 2048

    def vrf_betas_batch(self, proofs):
        from . import vrf_jax
        n = len(proofs)
        if n == 0:
            return []
        if n > self.BETA_CHUNK:
            out = []
            for off in range(0, n, self.BETA_CHUNK):
                out.extend(self.vrf_betas_batch(
                    proofs[off:off + self.BETA_CHUNK]))
            return out
        m = self._pad(n)
        padded = list(proofs) + [b"\x00" * 80] * (m - n)
        (Gw, signG), decode_ok = vrf_jax._prepare_betas_words(padded)
        rows = vrf_jax.gamma8_words_kernel(
            self._dev(Gw), self._dev(signG.reshape(1, -1))[0])
        return vrf_jax._finish_betas(np.asarray(rows), decode_ok, n)

    # -- mixed windows -------------------------------------------------------
    def _split_mixed_device(self, reqs):
        """Like CryptoBackend.split_mixed but hash-free: KES hash paths
        become device Blake2b jobs instead of host hashing (VERDICT r4
        missing #2), and the jobs themselves are memoised cross-window —
        a hash path depends only on (depth, period, vk, merkle bytes),
        so a pool's per-period subtree is checked on device ONCE and its
        outcome served from the precomputation cache ever after (warm
        windows schedule zero Blake2b jobs).  Identical paths within one
        cold window collapse to one job slice too.

        `reqs` is a stream of items: a columns item (`Ed25519Cols`, a
        block body's witnesses) joins the Ed25519 lanes' three columns
        whole, at its place in the order, and answers for the run of
        request indices it stands for; a request object gives one lane.

        Returns (ed_reqs, ed_owner, vrf_reqs, vrf_owner, kes_msgs,
        kes_expects, kes_checks, n): the Ed25519 lanes as one
        `Ed25519Cols` and the request index each answers for; n the
        requests the items stand for; kes_checks lists the pending cache
        stores as (key, job_start, n_jobs, owners, leaf_vk) —
        finish_window folds the per-job verdicts into one outcome per
        path and records it."""
        cache = GLOBAL_PRECOMPUTE_CACHE
        ed_reqs = Ed25519Cols([], [], [])
        ed_owner: list[int] = []
        vrf_reqs: list = []
        vrf_owner: list[int] = []
        kes_msgs: list[bytes] = []
        kes_expects: list[bytes] = []
        pending: dict = {}     # key -> [start, n_jobs, owners, leaf_vk]
        n = 0                  # requests the items walked so far stand for
        for r in reqs:
            i = n              # the item's first request index
            if isinstance(r, Ed25519Cols):
                n += len(r)
                ed_reqs.extend(r)
                ed_owner.extend(range(i, n))
                continue
            n += 1
            if isinstance(r, Ed25519Req):
                ed_reqs.append(r.vk, r.msg, r.sig)
                ed_owner.append(i)
            elif isinstance(r, VrfReq):
                vrf_reqs.append(r)
                vrf_owner.append(i)
            elif isinstance(r, KesReq):
                key = kes_mod.hash_path_key(r.depth, r.vk, r.period,
                                            r.sig_bytes)
                if key is None:
                    continue          # structurally invalid: stays False
                ent = cache.kes_get(key)
                if ent is not None:                     # warm path
                    leaf_vk, path_ok = ent
                    if not path_ok:
                        continue      # known-bad hash path: stays False
                elif key in pending:  # cold, but already scheduled here
                    pend = pending[key]
                    pend[2].append(i)
                    leaf_vk = pend[3]
                else:                                   # cold path
                    sig = kes_mod.KesSig.from_bytes(r.depth, r.sig_bytes)
                    walk = kes_mod.verify_walk(r.depth, r.vk, r.period,
                                               sig)
                    leaf_vk, _leaf_sig, jobs = walk
                    start = len(kes_msgs)
                    for msg, expect in jobs:
                        kes_msgs.append(msg)
                        kes_expects.append(expect)
                    pending[key] = [start, len(jobs), [i], leaf_vk]
                ed_reqs.append(leaf_vk, r.msg, r.sig_bytes[:64])
                ed_owner.append(i)
            else:
                raise TypeError(f"unknown proof request type {type(r)}")
        kes_checks = [(key, start, nj, owners, leaf_vk)
                      for key, (start, nj, owners, leaf_vk)
                      in pending.items()]
        return (ed_reqs, ed_owner, vrf_reqs, vrf_owner,
                kes_msgs, kes_expects, kes_checks, n)

    def _prep_kes_hash(self, kes_msgs, kes_expects, m: int):
        msgs = np.frombuffer(b"".join(kes_msgs), dtype=np.uint8)
        msgs = msgs.reshape(-1, 64)
        exps = np.frombuffer(b"".join(kes_expects), dtype=np.uint8)
        exps = exps.reshape(-1, 32)
        mw = _pad_words(B2.msg_words(msgs), m)
        ew = _pad_words(B2.digest_words(exps), m)
        return self._dev(mw), self._dev(ew)

    def _ed_tile_program(self, fold: bool):
        """THE Ed25519 program of the window path: verify one tile of
        `ed_tile` lanes.  A window of T tiles is T asynchronous calls of
        it, so the tile COUNT is no program's shape: a replay builds
        this once whatever widths its windows have, and the device walks
        only the tiles it is handed.  It is
        `verify_full_split_words_core` at the width the power-of-two
        buckets top out at.  (What T launches cost the producer: span
        submit.ed_tiles; PERF.md section 6, PR 38.)

        fold=True: `(first_bad, own, Aw, xa, xw, yw, Rw, signR2, sw, kw)
        -> first_bad`, the tile's verdicts folded into the running
        first-bad request index: `own` (1, tile) int32 holds each lane's
        request index, FOLD_SENT on a pad lane and on a lane the host
        already knows is bad; each call is handed the scalar the one
        before returned.  fold=False: `(Aw, ..., kw) -> (tile,) uint8`
        verdicts.  The inputs are donated: fresh every window, never
        read again."""
        fn = self._ed_tile_programs.get(fold)
        if fn is not None:
            return fn
        import jax
        return self._keep_ed_tile_program(fold, jax.jit(
            self._ed_tile_body(fold),
            donate_argnums=self._ed_tile_donated(fold)))

    def _ed_tile_body(self, fold: bool, across=None):
        """The tile program before `jit`: what ONE device does with its
        `ed_tile` lanes.  `across` names the mesh axis the shards'
        first-bad indexes meet over (the mesh backend's, under
        shard_map)."""
        import jax
        import jax.numpy as jnp

        def verify(Aw, xa, xw, yw, Rw, signR2, sw, kw):
            return EJ.verify_full_split_words_core(
                Aw, xa, xw, yw, Rw, signR2[0], sw, kw)

        def fold_tile(first_bad, own, *lanes):
            bad = jnp.min(jnp.where(verify(*lanes) != 0, FOLD_SENT, own[0]))
            if across is not None:
                bad = jax.lax.pmin(bad, across)
            return jnp.minimum(first_bad, bad)

        def verdicts(*lanes):
            return verify(*lanes).astype(jnp.uint8)

        return fold_tile if fold else verdicts

    def _ed_tile_donated(self, fold: bool) -> tuple:
        """Every input of the tile program: fresh every window, never
        read again."""
        return tuple(range(10 if fold else 8)) if self._donate else ()

    def _keep_ed_tile_program(self, fold: bool, fn):
        fn = _compile_span_on_first_call(
            fn, f"window.ed_tile({self.ed_tile},fold={int(fold)})")
        self._ed_tile_programs[fold] = fn
        return fn

    def _window_composite(self, nv: int, nb: int, nk: int):
        """One jitted device program for the parts of a window whose
        widths the protocol fixes: VRF verify + next-window gamma8 betas
        + KES hash checks, results concatenated into the packed flat
        uint8 buffer on device.  ONE launch instead of one per part.
        (The Ed25519 lanes, whose count follows the bodies, are not in
        it: `_ed_tile_program`.  Every composite SHAPE is its own
        compile of a minute or more, so the three widths are few by
        construction: `_occasional_widths`.)"""
        key = (nv, nb, nk)
        fn = self._composites.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        from . import vrf_jax

        def call(vrf_args, beta_args, kes_args):
            parts = []
            if vrf_args is not None:
                Yw, xa, Gw, sG2, rw, cw, sw = vrf_args
                rows = vrf_jax.vrf_verify_words_core(
                    Yw, xa, Gw, sG2[0], rw, cw, sw)
                parts.append(rows.reshape(-1))
            if beta_args is not None:
                bGw, bsG2 = beta_args
                rows = vrf_jax.gamma8_words_core(bGw, bsG2[0])
                parts.append(rows.reshape(-1))
            if kes_args is not None:
                ok = B2.check_block64(*kes_args)
                parts.append(ok.reshape(-1).astype(jnp.uint8))
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

        # donate the window's input buffers: they are built fresh per
        # window and never read after the call, so XLA may overwrite
        # them in place — the double-buffered replay (two windows in
        # flight, consensus/batch.py) stops reallocating device memory
        # every window.  CPU ignores donation (warns), hence the gate.
        fn = jax.jit(call, donate_argnums=(0, 1, 2)) if self._donate \
            else jax.jit(call)
        _COMPOSITE_BUILDS.inc()
        fn = _compile_span_on_first_call(
            fn, f"window.composite({nv},{nb},{nk})")
        self._composites[key] = fn
        return fn

    def _occasional_widths(self, nv: int, nb: int, nk: int) -> tuple:
        """(nv, nb, nk) for a window whose VRF, beta and KES parts need
        that many lanes: (0, 0, 0), no composite at all, for a window
        that has none of the three; else those of the narrowest
        composite this backend has ALREADY built that holds all three;
        else its own, widened to what the driver has in sight.

        A sync meets these parts at several widths: betas ride in every
        window but a chain's last two, KES hash jobs only in the windows
        that first meet a pool's hash path (and whether window 1 does is
        a race with window 0's drain), a chain's last window, or one cut
        short at an invalid header, has fewer VRF lanes than the rest,
        and a Byron window has none: it needs a composite only for the
        betas it carries for a Shelley window two ahead, and the window
        that holds the hard fork has VRF lanes for its Shelley blocks
        alone.  Every (nv, nb, nk) is its own program, a minute or more
        to trace, lower and build or load, where the empty lanes of a
        wider part cost the device microseconds (PERF.md section 6, PRs
        33 and 40).  So a window rides a built program that covers it
        rather than building its own, whichever parts it has itself.
        On a Shelley chain the first window, the widest in all three,
        fixes the program for the rest.  Where the first window to need
        one is narrower than those behind it (a Byron window carrying
        the first Shelley betas), the program is built for what the
        driver says is coming (`expect_lanes`): the VRF and beta lanes
        in sight, and, those VRF lanes being of headers this window has
        none of, a KES part for their hash paths
        (`_kes_lanes_to_come`).  So a chain that starts in Byron builds
        the composite a Shelley chain of the same window builds, once,
        in set-up."""
        if not (nv or nb or nk):
            return 0, 0, 0
        best = None
        for v, b, k in self._composites:
            if v >= nv and b >= nb and k >= nk \
                    and (best is None or v + b + k < sum(best)):
                best = (v, b, k)
        if best is not None:
            return best
        sv, sb = self._in_sight
        if sv > nv:
            nk = max(nk, self._kes_lanes_to_come())
        return max(nv, sv), max(nb, sb), nk

    def submit_window(self, reqs, next_beta_proofs=(), fold: bool = False):
        """Dispatch one replay window's whole device workload — the mixed
        Ed25519/VRF/KES verification of `reqs` AND the VRF betas the NEXT
        window's sequential pass will need.  The Ed25519 lanes go as T
        asynchronous calls of the one tile program, the other three
        parts as ONE fused program whose results are packed into ONE
        flat uint8 array.  Returns an opaque state for finish_window.

        With fold=True the per-proof verdicts never cross the link: each
        tile call folds its verdicts into a running first-bad index on
        the device, and a second tiny program reduces the composite's
        packed output (on-device SHA-512 challenge fold for VRF —
        sha512_jax) and that index to the FIRST failing request index;
        finish_window returns a WindowVerdict scalar pair instead of the
        boolean vector, after ONE transfer.  A window without a
        composite needs no second program: the tile calls' index IS its
        verdict, and finish_window reads that scalar.  The composite is
        SHARED between both modes (same program, same compile); without
        fold the tiles run the bucket program of
        their width and finish_window fetches their verdicts.

        `reqs` is the window's stream of items as the sequential pass
        made it: request objects (the headers') and one columns item a
        block body (`Ed25519Cols`, which counts for `len` requests);
        verdicts are indices into the requests the items stand for.

        `window.submit` holds one span a stage, submit.pack_ed in two
        pieces: submit.split, submit.pack_ed (the new keys' fill
        dispatched, the lanes hashed and packed), submit.pack_vrf (beta
        words included), submit.pack_kes, submit.pack_ed again (the
        wait that is left for the key tables, their store, the tiles'
        copy to the device), folding submit.fold (the lanes' owner rows,
        which the tile calls read, and their copy to the device), and
        submit.dispatch (submit.ed_tiles = the T tile calls, the
        composite call and, folding, the fold program's)."""
        with _spans.span("window.submit", cat="dispatch", cpu=True):
            return self._submit_window(reqs, next_beta_proofs, fold)

    def _submit_window(self, reqs, next_beta_proofs=(),
                       fold: bool = False):
        from . import vrf_jax
        _WINDOWS.inc()
        with _spans.span("submit.split", cat="dispatch"):
            (ed_reqs, ed_owner, vrf_reqs, vrf_owner,
             kes_msgs, kes_expects, kes_checks, n) = \
                self._split_mixed_device(reqs)
            row_lanes = lane_count(
                r for r in reqs if isinstance(r, Ed25519Cols))
            beta_proofs = list(dict.fromkeys(next_beta_proofs))
            ne = self._pad_ed_window(len(ed_reqs))
            nv, nb, nk = self._occasional_widths(*(
                self._pad(len(part)) if part else 0
                for part in (vrf_reqs, beta_proofs, kes_msgs)))
        if beta_proofs:
            _BETA_WINDOWS.inc()
            _BETA_ROWS.inc(len(beta_proofs))
        if not kes_msgs:
            _KES_EMPTY.inc()
        state = {"packed": None, "n": n, "fold": fold,
                 "ed": None, "ed_owner": ed_owner, "ne": ne,
                 "vrf": None, "vrf_owner": vrf_owner,
                 "vrf_n": len(vrf_reqs), "nv": nv,
                 "beta": None, "beta_proofs": beta_proofs, "nb": nb,
                 "kes_checks": kes_checks, "nk": nk,
                 "kes_n": len(kes_msgs)}
        vrf_args = beta_args = kes_args = None
        tiles: list = []
        with _spans.span("submit.pack_ed", cat="dispatch"):
            if ne:
                ed_packed = self._pack_ed(ed_reqs, ne)
        with _spans.span("submit.pack_vrf", cat="dispatch"):
            if nv:
                vrf_args, masks = self._prep_vrf(vrf_reqs, nv)
                state["vrf"] = (None,) + masks
            if nb:
                padded = beta_proofs + [b"\x00" * 80] * (
                    nb - len(beta_proofs))
                (Gw, signG), decode_ok = vrf_jax._prepare_betas_words(
                    padded)
                state["beta"] = (decode_ok,)
                beta_args = (self._dev(Gw),
                             self._dev(signG.reshape(1, -1)))
        with _spans.span("submit.pack_kes", cat="dispatch"):
            if nk:
                kes_args = self._prep_kes_hash(kes_msgs, kes_expects, nk)
        # the key tables as late as the window's host work allows: the
        # VRF and KES packers need none, the tiles' copy and the owner
        # rows do
        with _spans.span("submit.pack_ed", cat="dispatch"):
            if ne:
                ed_arrays, parse_ok = self._finish_ed(ed_packed)
                state["ed"] = (None, parse_ok)
                tiles = self._dev_tiles(ed_arrays, ne)
        self._note_padding(
            len(ed_reqs) + len(vrf_reqs) + len(beta_proofs) + len(kes_msgs),
            ne + nv + nb + nk)
        own_tiles = vrf_own = None
        if fold:
            with _spans.span("submit.fold", cat="dispatch"):
                ed_own, vrf_own = self._fold_owners(state)
                own_tiles = self._dev_tiles((ed_own.reshape(1, -1),), ne)
        with _spans.span("submit.dispatch", cat="dispatch"):
            if tiles:
                self._note_ed_tiles(len(ed_reqs), ne)
                _ED_ROW_LANES.inc(row_lanes)
                run = self._ed_tile_program(fold)
                with _spans.span("submit.ed_tiles", cat="dispatch"):
                    if fold:
                        bad = self._dev_scalar(FOLD_SENT)
                        for (own,), tile in zip(own_tiles, tiles):
                            bad = run(bad, own, *tile)
                        state["ed_bad"] = bad
                    else:
                        state["ed_ok"] = [run(*tile) for tile in tiles]
            if nv or nb or nk:
                state["packed"] = self._window_composite(
                    nv, nb, nk)(vrf_args, beta_args, kes_args)
                if fold:
                    self._attach_fold(state, vrf_own)
            else:
                _COMPOSITE_FREE.inc()
        return state

    def _note_ed_tiles(self, real: int, walked: int) -> None:
        """Count one window's Ed25519 tiles at dispatch."""
        tiles = walked // (self.ed_tile * self.n_shards)
        _ED_TILES.inc(tiles)
        _ED_LANES_REAL.inc(real)
        _ED_LANES_WALKED.inc(walked)
        if self._prev_ed_tiles not in (None, tiles):
            _ED_WIDTH_CHANGES.inc()
        self._prev_ed_tiles = tiles

    def _fold_owners(self, state) -> tuple:
        """The request index each Ed25519 and VRF lane answers for, as
        the device fold reads it: ((ne,) int32, (nv,) int32), FOLD_SENT
        on pad lanes.

        Host-known failures (undecodable keys/sigs, structurally invalid
        KES, known-bad cached hash paths) never reach the device fold:
        their lanes carry the sentinel owner and their minimum index is
        kept in `host_first_bad` for finish_window to merge."""
        n = state["n"]
        covered = np.zeros(n, dtype=bool)
        host_bad = FOLD_SENT
        owns = []
        for part, lanes in (("ed", state["ne"]), ("vrf", state["nv"])):
            own = np.full(lanes, FOLD_SENT, np.int32)
            if state[part] is not None:
                owner = np.asarray(state[part + "_owner"], np.int32)
                ok = np.asarray(state[part][1], dtype=bool)[:owner.size]
                covered[owner] = True
                own[:owner.size] = np.where(ok, owner, FOLD_SENT)
                if not ok.all():
                    host_bad = min(host_bad, int(owner[~ok].min()))
            owns.append(own)
        uncovered = np.flatnonzero(~covered)
        if uncovered.size and uncovered[0] < host_bad:
            host_bad = int(uncovered[0])
        state["host_first_bad"] = host_bad
        return tuple(owns)

    def _attach_fold(self, state, vrf_own) -> None:
        """Reduce the window's device verdicts to [first failing request
        index (4 B LE) | KES job flags | beta rows]: the composite's
        packed buffer and the tiles' running first-bad index through the
        fold program.  The KES job flags still cross the link raw — they
        exist only on COLD hash paths and the precompute cache must see
        each path's outcome; warm windows ship zero of them."""
        import jax.numpy as jnp
        _FOLD_WINDOWS.inc()
        nv = state["nv"]
        gamma_b = np.zeros((nv, 32), np.uint8)
        c_b = np.zeros((nv, 16), np.uint8)
        if state["vrf"] is not None:
            pf_arr = state["vrf"][4]
            gamma_b = np.ascontiguousarray(pf_arr[:, :32])
            c_b = np.ascontiguousarray(pf_arr[:, 32:48])
        ed_bad = state.pop("ed_bad", None)
        if ed_bad is None:
            ed_bad = self._dev_scalar(FOLD_SENT)
        state["packed"] = self._fold_program(nv, state["nb"], state["nk"])(
            state["packed"], ed_bad, jnp.asarray(vrf_own),
            jnp.asarray(gamma_b), jnp.asarray(c_b))

    def _fold_program(self, nv: int, nb: int, nk: int):
        """Jitted verdict reduction over one window's packed buffer and
        the Ed25519 tiles' first-bad index.  Output layout: [first-bad
        index, uint32 LE (FOLD_SENT = none) | nk KES job flags | nb*33
        beta rows] — the transfer shrinks from ne + 130*nv + ... to
        4 + nk + 33*nb bytes."""
        key = (nv, nb, nk)
        fn = self._folds.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        from . import vrf_jax

        def fold(flat, ed_bad, vrf_own, gamma_b, c_b):
            off = 0
            m = ed_bad
            if nv:
                rows = flat[:nv * 130].reshape(nv, 130)
                ok = vrf_jax.challenge_ok_device(rows, gamma_b, c_b)
                m = jnp.minimum(m, jnp.min(
                    jnp.where(ok, FOLD_SENT, vrf_own)))
                off += nv * 130
            idx = m.astype(jnp.uint32)
            parts = [jnp.stack([idx & 0xFF, (idx >> 8) & 0xFF,
                                (idx >> 16) & 0xFF,
                                (idx >> 24) & 0xFF]).astype(jnp.uint8)]
            if nk:
                parts.append(flat[off + nb * 33:off + nb * 33 + nk])
            if nb:
                parts.append(flat[off:off + nb * 33])
            return jnp.concatenate(parts)

        # the composite's packed output is consumed here and never read
        # again — donate it so the fold reuses its buffer
        fn = jax.jit(fold, donate_argnums=(0,)) if self._donate \
            else jax.jit(fold)
        fn = _compile_span_on_first_call(
            fn, f"window.fold({nv},{nb},{nk})")
        self._folds[key] = fn
        return fn

    def finish_window(self, state):
        """Block on a submit_window dispatch; returns (ok list aligned
        with the submitted reqs, {proof: beta} for the requested
        next-window proofs).  For a fold=True submission (one transfer)
        the first element is a WindowVerdict instead of the boolean
        list."""
        if state.get("fold"):
            return self._finish_window_fold(state)
        out = [False] * state["n"]
        betas: dict = {}
        if state["packed"] is None and state["ed"] is None:
            return out, betas
        with _spans.span("window.drain", cat="device"):
            # the round trip: the composite's buffer and a tile's
            # verdicts a tile
            flat = (np.asarray(state["packed"])
                    if state["packed"] is not None else np.zeros(0, np.uint8))
            ed_ok = (np.concatenate([np.asarray(t)
                                     for t in state["ed_ok"]])
                     if state["ed"] is not None else None)
        off = 0
        if ed_ok is not None:
            _handle, parse_ok = state["ed"]
            for k, i in enumerate(state["ed_owner"]):
                out[i] = bool(ed_ok[k]) and bool(parse_ok[k])
        if state["vrf"] is not None:
            rows = flat[off:off + state["nv"] * 130].reshape(-1, 130)
            off += state["nv"] * 130
            from . import vrf_jax
            _h, parse_ok, gamma_ok, s_ok, pf_arr = state["vrf"]
            oks, _b = vrf_jax._finish(rows, parse_ok, gamma_ok, s_ok,
                                      pf_arr, state["vrf_n"])
            for i, ok in zip(state["vrf_owner"], oks):
                out[i] = ok
        if state["beta"] is not None:
            rows = flat[off:off + state["nb"] * 33].reshape(-1, 33)
            off += state["nb"] * 33
            from . import vrf_jax
            bs = vrf_jax._finish_betas(rows, state["beta"][0],
                                       len(state["beta_proofs"]))
            betas = dict(zip(state["beta_proofs"], bs))
        # a KES request is valid only if its leaf Ed25519 check passed
        # (handled via ed_owner above) AND its hash path checked out.
        # Each pending path's per-job verdicts fold into ONE outcome that
        # the precomputation cache remembers — warm windows carry no
        # kes_checks (and schedule no jobs) at all.
        kes_ok = (flat[off:off + state["nk"]] if state["nk"] else
                  np.zeros(0, dtype=np.uint8))
        for key, start, n_jobs, owners, leaf_vk in state["kes_checks"]:
            path_ok = bool(np.all(kes_ok[start:start + n_jobs])) \
                if n_jobs else True
            GLOBAL_PRECOMPUTE_CACHE.kes_put(key, leaf_vk, path_ok)
            if not path_ok:
                for i in owners:
                    out[i] = False
        return out, betas

    def _finish_window_fold(self, state):
        """Fold-mode drain: one tiny transfer — [first-bad idx | KES job
        flags | beta rows] — merged with the host-known failures into a
        WindowVerdict."""
        from . import vrf_jax
        from .backend import WindowVerdict
        n = state["n"]
        betas: dict = {}
        bad = state["host_first_bad"]
        if state["packed"] is None:
            # no composite, so no fold program: the tile calls' running
            # first-bad index is the device's whole verdict
            ed_bad = state.get("ed_bad")
            if ed_bad is not None:
                with _spans.span("window.drain", cat="device"):
                    bad = min(bad, int(np.asarray(ed_bad)))
            return WindowVerdict(
                n, None if bad >= FOLD_SENT else bad), betas
        with _spans.span("window.drain", cat="device"):
            flat = np.asarray(state["packed"])      # THE round trip
        dev_bad = (int(flat[0]) | int(flat[1]) << 8
                   | int(flat[2]) << 16 | int(flat[3]) << 24)
        bad = min(bad, dev_bad)
        off = 4
        kes_ok = flat[off:off + state["nk"]]
        off += state["nk"]
        for key, start, n_jobs, owners, leaf_vk in state["kes_checks"]:
            path_ok = bool(np.all(kes_ok[start:start + n_jobs])) \
                if n_jobs else True
            GLOBAL_PRECOMPUTE_CACHE.kes_put(key, leaf_vk, path_ok)
            if not path_ok:
                bad = min(bad, min(owners))
        if state["beta"] is not None:
            rows = flat[off:off + state["nb"] * 33].reshape(-1, 33)
            bs = vrf_jax._finish_betas(rows, state["beta"][0],
                                       len(state["beta_proofs"]))
            betas = dict(zip(state["beta_proofs"], bs))
        return WindowVerdict(n, None if bad >= FOLD_SENT else bad), betas

    def verify_kes_batch(self, reqs):
        """KES batch: leaf Ed25519 on the curve kernels + hash path on the
        Blake2b device kernel — no host hashing (VERDICT r4 missing #2)."""
        return self.verify_mixed(reqs)

    def verify_mixed(self, reqs):
        """Fused mixed batch: one packed device transfer for the whole
        window (see submit_window)."""
        ok, _betas = self.finish_window(self.submit_window(reqs))
        return ok
