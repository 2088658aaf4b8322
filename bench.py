#!/usr/bin/env python
"""Benchmark: Shelley-path db-validate replay, TPU batch backend vs
sequential CPU — the BASELINE.md north-star metric.

Prints ONE JSON line:
  {"metric": "shelley_replay_proofs_per_sec", "value": N,
   "unit": "proofs/s", "vs_baseline": N, ...}

Workload (BASELINE configs #2-#4 in one stream): a TPraos chain — per
header 2 ECVRF proofs + 1 KES signature + 1 OCert Ed25519 sig, per body
Ed25519 tx witnesses — replayed through consensus/batch.py
(validate_blocks_batched) with full proof verification and state-hash
parity asserted between backends.

Baseline: the same replay on the cpp backend (single-core C++ Ed25519 +
ECVRF, the libsodium-class stand-in; the reference validates sequentially
on exactly such a path — SURVEY.md §2 "TPU-relevant gap").  Falls back to
openssl if the cpp extension is unavailable.

Secondary metrics (stderr): primitive throughputs (Ed25519 batch e2e, VRF
batch, KES batch) and a host/device time breakdown of the replay.

Measurement discipline: every kernel choice is pinned in the warmup
phase (persistent fenced autotuner, crypto/autotune.py) and the tuners
are FROZEN around every timed region — a mid-bench retune raises instead
of silently skewing a rep (the BENCH_r05 VRF regression).  `--retune`
drops the persisted choices and re-measures.  `--smoke` runs a tiny
parity-only replay (1 rep, no timing assertions) — the tier-1 guard that
keeps the replay path honest between bench rounds.

`--mesh N` (ISSUE 11) additionally replays the same chain through the
sharded pipelined driver — ShardedJaxBackend over an N-device mesh, the
threaded producer/consumer pipeline with per-shard packed windows and
the device-side verdict fold — and reports sharded proofs/s (and the
per-shard padding waste) beside the single-device number under a
``sharded`` key.  In this container the mesh is N forced host-platform
XLA devices (the flag is set before jax initialises); on TPU the same
knob shards over the real chips.

Every round also runs a ``stream`` leg (ISSUE 15): the same chain
replayed FROM DISK through the streaming engine
(ouroboros_tpu/storage/stream.py) — bounded read-ahead prefetch +
periodic crash-consistent snapshots + a resumed restart — reporting how
many disk+decode seconds hid under device verify (`disk_hidden_frac`)
and the restore cost of a restart.

`--serve` (ISSUE 12) exercises the CAUGHT-UP path instead of the
syncing one: the adaptive micro-batching VerifyService
(crypto/batching.py) under seeded bursty Poisson arrival traces in
deterministic sim time — p50/p95/p99 request latency and proofs/s
versus the unbatched per-request CPU baseline, a light-load leg that
must take the CPU break-even fallback with ZERO device dispatches, and
a back-pressure leg against a tiny admission queue.  Results land under
a ``serve`` key; `--smoke` runs a scaled-down copy as a tier-1 gate.
"""
import argparse
import glob
import json
import os
import re
import shutil
import statistics
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# The persistent XLA compilation cache (the big ladder kernels take
# minutes to compile per shape) is configured by the device backends'
# import, to the one rule in ouroboros_tpu/compile_cache.py; nothing is
# set here, and this module's top level stays free of JAX (see
# synth_chain: one process per chip).

# 10k blocks (VERDICT r2: measure at the scale the claims are about) in
# windows of 1024 — per window ONE packed device dispatch carrying the
# 2048-proof VRF batch, the 4096-sig Ed25519 batch (OCert + KES leaves +
# witnesses) and the next-next window's 2048 betas, overlapped with the
# host sequential pass (consensus/batch.py software pipeline)
BLOCKS = 10000
TXS = 2
WINDOW = 1024
EPOCH_LEN = 600
# measurement discipline (VERDICT r3 next-step 1a): every timed quantity is
# the MEDIAN of >= REPS repetitions with the min/max spread reported; a
# single-shot number on this chip has ~30-50% run-to-run noise and cannot
# distinguish a 2x kernel win from weather
REPS = int(os.environ.get("BENCH_REPS", "5"))
CPU_REPS = int(os.environ.get("BENCH_CPU_REPS", "2"))
SPREAD_WARN = 0.30


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def median_spread(vals):
    """(median, spread) where spread = (max-min)/median."""
    med = statistics.median(vals)
    return med, ((max(vals) - min(vals)) / med if med else 0.0)


def check_spread(name, vals):
    med, spread = median_spread(vals)
    if spread > SPREAD_WARN:
        # min rides along (BENCH_r05 follow-up): on a noisy chip the min
        # is the best estimate of the workload's true cost — if min is
        # close to the median the spread is a slow-tail artifact, if the
        # median is close to max the warm path itself is unstable
        log(f"WARNING: {name} spread {100 * spread:.0f}% over {len(vals)} "
            f"reps exceeds {100 * SPREAD_WARN:.0f}% — treat the median "
            f"with suspicion, prefer min {min(vals):.3f}s vs median "
            f"{med:.3f}s (vals: {[round(v, 3) for v in vals]})")
    return med, spread


# the span phase vocabulary IS the bench phase schema — import it so a
# category added in observe/spans.py cannot silently fold into `other`
from ouroboros_tpu.observe.spans import PHASES as PHASE_ORDER  # noqa: E402


def _rep_phase_totals(observe, roots, rep_secs: float) -> dict:
    """One timed rep's seconds per phase from its drained span forest.
    `other` is the rep wall time no span claimed (host work outside the
    instrumented seams — result folding, python overhead)."""
    totals = observe.phase_totals(roots)
    out = {ph: round(totals.get(ph, 0.0), 4) for ph in PHASE_ORDER}
    claimed = sum(totals.values())
    out["other"] = round(max(0.0, rep_secs - claimed), 4)
    return out


def _phase_variance(rep_phases) -> dict:
    """Cross-rep stats per phase + the phase with the largest spread.

    Ranked by ABSOLUTE spread (max-min seconds): the phase contributing
    the most wall-clock variance to the rep totals — a ~0s phase with
    big relative jitter must not outrank the phase that actually moved
    the median (the BENCH_r05 '45% vrf spread' diagnosis, attributed)."""
    if not rep_phases:
        return {}
    per_phase = {}
    for ph in list(PHASE_ORDER) + ["other"]:
        vals = [d.get(ph, 0.0) for d in rep_phases]
        med, spread = median_spread(vals)
        per_phase[ph] = {"median": round(med, 4),
                         "min": round(min(vals), 4),
                         "max": round(max(vals), 4),
                         "spread_secs": round(max(vals) - min(vals), 4),
                         "spread_rel": round(spread, 3)}
    dominant = max(per_phase, key=lambda p: per_phase[p]["spread_secs"])
    return {"per_phase": per_phase, "dominant_phase": dominant,
            "dominant_spread_secs": per_phase[dominant]["spread_secs"]}


def _rep_overlap(observe, roots) -> dict:
    """One timed rep's host/device overlap attribution from its span
    forest (the pipelined replay's whole point, measured):

    * host_seq_secs       — producer-thread sequential-pass wall time
      (union of `window.host_seq` spans);
    * device_secs         — consumer-thread blocking drains (union of
      `window.drain` spans);
    * host_hidden_secs    — host-seq time that ran WHILE a window was in
      flight on device (k-th submit start .. k-th drain end; drains are
      FIFO, so sorted pairing is exact).  host+device stop being
      additive exactly when this approaches host_seq_secs;
    * hidden_frac         — host_hidden_secs / host_seq_secs;
    * producer_stall_secs — producer time parked on the permit gate
      (depth back-pressure): the pipeline's headroom indicator.
    """
    sp = observe.spans
    host = sp.merge_intervals(sp.intervals_of(roots,
                                              name="window.host_seq"))
    drains = sorted(sp.intervals_of(roots, name="window.drain"))
    subs = sorted(sp.intervals_of(roots, name="window.submit"))
    inflight = [(s[0], d[1]) for s, d in zip(subs, drains) if d[1] > s[0]]
    stall = sp.merge_intervals(sp.intervals_of(roots, cat="stall"))
    host_total = sum(t1 - t0 for t0, t1 in host)
    hidden = sp.overlap_seconds(host, inflight)
    return {
        "host_seq_secs": round(host_total, 4),
        "device_secs": round(sum(t1 - t0 for t0, t1 in
                                 sp.merge_intervals(drains)), 4),
        "host_hidden_secs": round(hidden, 4),
        "hidden_frac": round(hidden / host_total, 3) if host_total else 0.0,
        "producer_stall_secs": round(sum(t1 - t0 for t0, t1 in stall), 4),
    }


def _overlap_summary(rep_overlaps) -> dict:
    """Cross-rep medians of the per-rep overlap attribution."""
    if not rep_overlaps:
        return {}
    out = {"per_rep": rep_overlaps}
    for k in ("host_seq_secs", "device_secs", "host_hidden_secs",
              "hidden_frac", "producer_stall_secs"):
        out[k + "_median"] = round(
            statistics.median(r[k] for r in rep_overlaps), 4)
    return out


def bench_rounds():
    """Every recorded BENCH_r*.json as (round_no, parsed-result dict),
    ascending — the one loader for all history comparisons (harness
    wrapping unwrapped, unreadable files tolerated)."""
    out = []
    for path in glob.glob(os.path.join(REPO, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                data = json.load(f)
        except Exception:
            continue
        out.append((int(m.group(1)), data.get("parsed", data)))
    return sorted(out)


def previous_bench():
    """Latest recorded round, for the primitives-vs-previous-round
    comparison the bench prints itself (VERDICT r3 next-step 1e)."""
    rounds = bench_rounds()
    return rounds[-1] if rounds else None


def synth_chain(tmp: str, extra: tuple = ()) -> str:
    """Forge the bench chain in a CHILD process.  A chip belongs to one
    process at a time and a parent that has touched JAX holds it, so the
    rule is: this module's top level imports no JAX, db_synth imports no
    JAX at all (tests/test_chip_smoke.py checks), and main() calls this
    before it imports the device backend.  chip_smoke.py follows the
    same rule."""
    d = os.path.join(tmp, "chain")
    t0 = time.time()
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", d, "--protocol", "shelley", "--blocks", str(BLOCKS),
         "--txs-per-block", str(TXS), "--epoch-length", str(EPOCH_LEN),
         "--pools", "2", "--f", "4/5", *extra],
        capture_output=True, text=True)
    if r.returncode != 0:
        raise SystemExit(f"synth failed: {r.stderr[-2000:]}")
    log(f"synth: {BLOCKS} blocks in {time.time() - t0:.0f}s")
    return d


_DBA = None


def _dba():
    """The db_analyser module, loaded once (it is a script, not a
    package member)."""
    global _DBA
    if _DBA is None:
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "dba", os.path.join(REPO, "tools", "db_analyser.py"))
        _DBA = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_DBA)
    return _DBA


def load(db_dir):
    db, rules, decode, cfg = _dba().load_db(db_dir)
    blocks = [decode(raw) for _entry, raw in db.stream()]
    return rules, blocks


def load_stream_ctx(db_dir):
    """(fs, db, rules, decode) for the streaming engine — the on-disk
    half of what `load` materialises in memory."""
    from ouroboros_tpu.storage import IoFS
    db, rules, decode, _cfg = _dba().load_db(db_dir)
    return IoFS(db_dir), db, rules, decode


def replay(rules, blocks, backend, window: int):
    """Full-validation replay (software-pipelined when the backend
    supports async windows); returns (secs, state_hash, n_proofs)."""
    from ouroboros_tpu.consensus.batch import replay_blocks_pipelined
    ext = rules.initial_state()
    proofs = sum(4 + sum(len(tx.witnesses) for tx in b.body)
                 for b in blocks)
    t0 = time.perf_counter()
    res = replay_blocks_pipelined(rules, blocks, ext, backend=backend,
                                  window=window)
    if not res.all_valid:
        raise SystemExit(f"replay failed at block {res.n_valid}: "
                         f"{res.error}")
    secs = time.perf_counter() - t0
    return secs, res.final_state.ledger.state_hash(), proofs


class TimingBackend:
    """Wraps a CryptoBackend, accumulating wall time by seam:

    * device_secs   — blocking device waits: finish_window drains plus
      the synchronous batch verifies (caller-thread time actually spent
      waiting on results);
    * dispatch_secs — submit_window: host-side request packing + async
      dispatch.  In the producer/consumer replay this runs on the
      PRODUCER thread, overlapped with the consumer's drain — charging
      it to "device" (the r5 wrapper did) double-counted overlapped
      wall time and hid the packing cost once it moved off-thread.

    Each field has a single writer thread (dispatch: producer, device:
    consumer), so the unlocked accumulation is race-free."""

    _DEVICE_CALLS = ("verify_ed25519_batch", "verify_vrf_batch",
                     "verify_kes_batch", "verify_mixed",
                     "vrf_betas_batch", "finish_window")

    def __init__(self, inner):
        self._inner = inner
        self.device_secs = 0.0
        self.dispatch_secs = 0.0
        self.name = inner.name

    def _timed(self, fn, field, *a, **kw):
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        setattr(self, field,
                getattr(self, field) + time.perf_counter() - t0)
        return out

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name == "submit_window":
            return lambda *a, **kw: self._timed(attr, "dispatch_secs",
                                                *a, **kw)
        if name in self._DEVICE_CALLS:
            return lambda *a, **kw: self._timed(attr, "device_secs",
                                                *a, **kw)
        return attr


def _device_fence():
    """Drain the async dispatch queue so a timed rep never inherits the
    previous rep's in-flight device work (BENCH_r05: vrf primitive
    spread 45% came from un-fenced back-to-back dispatches).  Shares the
    autotuner's fence so both measurement disciplines stay identical."""
    from ouroboros_tpu.crypto.autotune import _fence
    _fence()


def _timed_reps(fn, reps=None, warmup=1):
    """Run fn() `warmup` un-timed times (pinning any kernel choice the
    shape needs), then `reps` timed reps with a block-until-ready fence
    before each and every autotuner FROZEN (a retune attempt inside a
    timed rep raises FrozenAutotunerError instead of poisoning the
    numbers); return the wall-times.

    Allocator/GC discipline (the r5 '45% vrf spread' fix, part 2): each
    rep's host garbage — result arrays, request lists, transfer staging
    buffers — is collected BEFORE the next rep's fence, and the cyclic
    GC is disabled inside the timed region, so a collection pause never
    lands inside a rep.  The transfer itself also shrank 130x (the
    fold-form verdict kernel), which removes the link-jitter term."""
    import gc

    from ouroboros_tpu.crypto import autotune
    for _ in range(warmup):
        fn()
    vals = []
    autotune.freeze_all()
    gc_was_enabled = gc.isenabled()
    try:
        for _ in range(reps or REPS):
            _device_fence()
            gc.collect()
            gc.disable()
            t0 = time.perf_counter()
            fn()
            vals.append(time.perf_counter() - t0)
            if gc_was_enabled:
                gc.enable()
    finally:
        if gc_was_enabled:
            gc.enable()
        autotune.thaw_all()
    return vals


def bench_primitives(jb):
    """Secondary metrics: primitive batch throughputs on the device —
    median of REPS with spread, per VERDICT r3's measurement discipline."""
    import hashlib

    from ouroboros_tpu.crypto import ed25519_ref, kes, vrf_ref
    from ouroboros_tpu.crypto.backend import Ed25519Req, KesReq, VrfReq
    out = {}
    # batch sizes match the replay's bucket shapes so the jit cache is
    # shared with the flagship run (fresh pallas shapes cost minutes)
    # Ed25519 (config #4 primitive)
    n = 4096
    sk = hashlib.sha256(b"bench-ed").digest()
    vk = ed25519_ref.public_key(sk)
    msgs = [b"m%06d" % i for i in range(n)]
    reqs = [Ed25519Req(vk, m, ed25519_ref.sign(sk, m)) for m in msgs]

    def run_ed():
        assert all(jb.verify_ed25519_batch(reqs))
    run_ed()                                # warm/compile (+ autotune)
    vals = _timed_reps(run_ed)              # + one fenced warmup rep
    med, spread = check_spread("ed25519 primitive", vals)
    out["ed25519_batch_per_sec"] = round(n / med, 1)
    out["ed25519_batch_per_sec_best"] = round(n / min(vals), 1)
    out["ed25519_spread"] = round(spread, 3)
    # VRF (config #2 primitive)
    nv = 2048
    vsk = hashlib.sha256(b"bench-vrf").digest()
    vvk = vrf_ref.public_key(vsk)
    vreqs = [VrfReq(vvk, b"a%d" % i, vrf_ref.prove(vsk, b"a%d" % i))
             for i in range(nv)]

    def run_vrf():
        assert all(jb.verify_vrf_batch(vreqs))
        # re-fence INSIDE the rep (ISSUE 11, the r04->r05 follow-up):
        # the verdict transfer syncs the fold output, but a rep must not
        # end while donated temporaries are still retiring — the next
        # rep's pre-fence would hide that tail OUTSIDE the timing and
        # re-expose it as run-to-run spread
        _device_fence()
    run_vrf()                               # warm/compile (+ autotune)
    vals = _timed_reps(run_vrf)             # + one fenced warmup rep
    med, spread = check_spread("vrf primitive", vals)
    out["vrf_batch_per_sec"] = round(nv / med, 1)
    out["vrf_batch_per_sec_best"] = round(nv / min(vals), 1)
    out["vrf_spread"] = round(spread, 3)
    # KES (config #3 primitive): hash path on host + leaf sigs on device
    nk = 4096
    ksk = kes.KesSignKey(6, hashlib.sha256(b"bench-kes").digest())
    kreqs = [KesReq(6, ksk.verification_key, 0, b"m%d" % i,
                    ksk.sign(b"m%d" % i).to_bytes()) for i in range(nk)]

    def run_kes():
        assert all(jb.verify_kes_batch(kreqs))
    run_kes()                               # warm/compile
    vals = _timed_reps(run_kes)             # + one fenced warmup rep
    med, spread = check_spread("kes primitive", vals)
    out["kes_batch_per_sec"] = round(nk / med, 1)
    out["kes_batch_per_sec_best"] = round(nk / min(vals), 1)
    out["kes_spread"] = round(spread, 3)
    return out


def compare_previous(prim):
    """Log primitive deltas vs the latest recorded round and return them
    for the output JSON ({} when no history)."""
    prev = previous_bench()
    if not prev:
        return {}
    rnd, doc = prev
    old = doc.get("primitives") or {}
    ratios = {}
    for k in ("ed25519_batch_per_sec", "vrf_batch_per_sec",
              "kes_batch_per_sec"):
        if k in old and k in prim and old[k]:
            delta = prim[k] / old[k]
            ratios[k] = round(delta, 3)
            log(f"vs BENCH_r{rnd:02d} {k}: {old[k]:.0f} -> {prim[k]:.0f} "
                f"({delta:.2f}x)")
    return {"vs_round": rnd, "ratios": ratios}


def vrf_attribution(prim):
    """The r04->r05 VRF primitive regression, attributed in-band (ISSUE
    11 satellite): if this round's vrf primitive throughput is below the
    best recorded round, the output JSON carries a note naming the two
    mechanical changes between the r04 and r05+ measurements — the
    primitive moved to the FOLD-form program (1 B/proof verdict transfer
    instead of 130 B point rows) and, since r06, autotunes under its own
    ("vrff", m) key instead of inheriting a choice pinned on the rows
    form the window composite measures.  Returns None when the round
    recovered (>= best)."""
    best = None
    for rnd, doc in bench_rounds():
        v = (doc.get("primitives") or {}).get("vrf_batch_per_sec")
        if v and (best is None or v > best[1]):
            best = (rnd, v)
    cur = prim.get("vrf_batch_per_sec")
    if best is None or cur is None or cur >= best[1]:
        return None
    return {
        "regressed_vs_round": best[0],
        "best_per_sec": best[1],
        "current_per_sec": cur,
        "note": ("verify_vrf_batch measures the fold-form program "
                 "(verify + on-device challenge fold, 1 B/proof "
                 "transfer) under its own ('vrff', m) autotune key; "
                 "r05 measured it under the rows-form ('vrf', m) key "
                 "pinned by the window composite AND shipped 130 "
                 "B/proof to the host. If this "
                 "round is still below the best, the variance section "
                 "names the phase that moved."),
    }


def _cpu_backend():
    """Best sequential CPU baseline: cpp, else openssl (which itself
    degrades to pure Python without the binding)."""
    from ouroboros_tpu.crypto.backend import OpensslBackend
    try:
        from ouroboros_tpu.crypto.cpp_backend import CppBackend
        return CppBackend()
    except Exception as e:
        log(f"cpp backend unavailable ({e}); openssl fallback")
        return OpensslBackend()


def _smoke_verdict_parity(jb):
    """Mixed-batch verdict parity vs the pure-Python oracle, including
    deliberate corruptions of every primitive (bad sig / wrong alpha /
    tampered Merkle node / wrong period / truncated KES bytes).  Runs
    the batch twice — cold, then warm from the precomputation cache —
    and returns (parity_ok, warm_fill_dispatches, warm_kes_jobs): the
    warm pass must serve every key and hash path from the cache (zero
    fills, zero Blake2b jobs)."""
    import hashlib

    from ouroboros_tpu.crypto import ed25519_ref, kes, vrf_ref
    from ouroboros_tpu.crypto.backend import (
        CpuRefBackend, Ed25519Req, KesReq, VrfReq,
    )
    from ouroboros_tpu.crypto.precompute import GLOBAL_PRECOMPUTE_CACHE
    sk = hashlib.sha256(b"smoke-ed").digest()
    vk = ed25519_ref.public_key(sk)
    vsk = hashlib.sha256(b"smoke-vrf").digest()
    vvk = vrf_ref.public_key(vsk)
    ksk = kes.KesSignKey(4, hashlib.sha256(b"smoke-kes").digest())
    kvk = ksk.verification_key
    reqs = [Ed25519Req(vk, b"m0", ed25519_ref.sign(sk, b"m0")),
            Ed25519Req(vk, b"bad", ed25519_ref.sign(sk, b"good")),
            VrfReq(vvk, b"a0", vrf_ref.prove(vsk, b"a0")),
            VrfReq(vvk, b"bad-alpha", vrf_ref.prove(vsk, b"a1"))]
    good = ksk.sign(b"kmsg")
    tam = kes.KesSig(good.leaf_sig,
                     ((good.merkle[0][0], bytes(32)),) + good.merkle[1:])
    reqs += [KesReq(4, kvk, 0, b"kmsg", good.to_bytes()),
             KesReq(4, kvk, 0, b"kmsg", tam.to_bytes()),
             KesReq(4, kvk, 1, b"kmsg", good.to_bytes()),
             KesReq(4, kvk, 0, b"kmsg", b"\x00" * 7)]
    # two evolved periods: 5 distinct depth-4 hash paths = 20 jobs, so
    # the KES bucket lands on the composite shape the replay just
    # compiled (off-chip runs stay cheap)
    for period in (1, 2):
        ksk.evolve()
        reqs.append(KesReq(4, kvk, period, b"p%d" % period,
                           ksk.sign(b"p%d" % period).to_bytes()))
    want = CpuRefBackend().verify_mixed(reqs)
    # fold-mode parity FIRST, while the KES paths are still cold: the
    # fold submission then has the same (ne, nv, nb, nk) window shape as
    # the plain cold batch below, so ONE composite compile serves both
    # (a warm-KES fold would be a different nk=0 shape — a fresh
    # multi-minute XLA:CPU compile the tier-1 budget cannot afford).
    # Only the tiny verdict-fold program is a new compile.
    from ouroboros_tpu.crypto.backend import WindowVerdict
    verdict, _b = jb.finish_window(jb.submit_window(reqs, fold=True))
    fold_ok = (isinstance(verdict, WindowVerdict)
               and verdict.first_bad == (want.index(False)
                                         if False in want else None))
    # the fold run cached the KES hash-path outcomes; re-cold them so
    # the plain batch below exercises the same cold shape it always did
    GLOBAL_PRECOMPUTE_CACHE._kes.clear()
    got = jb.verify_mixed(reqs)                               # cold
    # warm-path probe WITHOUT another ~composite dispatch (each one is
    # ~a minute of XLA:CPU in the tier-1 container): the host split and
    # table assembly must now serve everything from the cache — zero
    # fill dispatches, zero Blake2b hash-path jobs.  The full warm
    # window re-verification runs in tests/test_precompute.py
    # (slow+device) and in the hardware bench every round.
    fills = GLOBAL_PRECOMPUTE_CACHE.device_fills
    (eds, _eo, vrfs, _vo, kes_msgs, _ex, checks, _n) = \
        jb._split_mixed_device(reqs)
    point_vks = [r.vk for r in reqs if not isinstance(r, KesReq)] + \
        [e.vk for e in eds]
    GLOBAL_PRECOMPUTE_CACHE.assemble(point_vks)
    warm_fills = GLOBAL_PRECOMPUTE_CACHE.device_fills - fills
    return (got == want, fold_ok, warm_fills,
            len(kes_msgs) + len(checks), reqs)


def smoke(blocks: int = 8, window: int = 8):
    """Tiny parity-only replay gate (tier-1): synth a small TPraos
    chain, replay it once on the CPU baseline and once on the JAX
    backend (1 rep, no timing assertions), assert state-hash parity,
    key reuse during the replay, and mixed-batch verdict parity with a
    host-level zero-warm-work probe.  This catches a silently broken
    replay path between bench rounds, not a slow one.  (The heavier
    cold-vs-warm full re-verification lives in tests/test_precompute.py
    's slow+device partition.)  Returns the result dict."""
    global BLOCKS, TXS, EPOCH_LEN
    from ouroboros_tpu.crypto.precompute import GLOBAL_PRECOMPUTE_CACHE

    old = (BLOCKS, TXS, EPOCH_LEN)
    # empty bodies + depth-4 KES keep every device bucket at the shapes
    # the tier-1 suite already compiles (min_bucket 16, window 8)
    BLOCKS, TXS, EPOCH_LEN = blocks, 0, 500
    tmp = tempfile.mkdtemp(prefix="bench-smoke-")
    try:
        chain = synth_chain(tmp, extra=("--kes-depth", "4"))
        from ouroboros_tpu.crypto.jax_backend import JaxBackend
        rules, blocks_l = load(chain)
        cpu = _cpu_backend()
        _clear_beta_cache()
        _, cpu_hash, n_proofs = replay(rules, blocks_l, cpu, window)
        jb = JaxBackend(min_bucket=16, use_pallas=False, autotune=False)
        fills0 = GLOBAL_PRECOMPUTE_CACHE.device_fills
        _clear_beta_cache()
        # the JAX replay takes the producer/consumer pipelined path with
        # the fold=True device verdict reduction (consensus/pipeline.py)
        # — so state-hash parity below IS the threaded-path parity gate.
        # Record spans for it: the overlap plumbing (host-seq hidden
        # under in-flight windows) must produce a well-formed
        # attribution even at smoke scale.
        from ouroboros_tpu import observe
        from ouroboros_tpu.observe import metrics as _om
        started0 = _om.counter("pipeline.producers_started",
                               always=True).value
        observe.spans.RECORDER.enable()
        try:
            observe.spans.RECORDER.drain()
            _, jax_hash, _ = replay(rules, blocks_l, jb, window)
            overlap_probe = _rep_overlap(observe,
                                         observe.spans.RECORDER.drain())
        finally:
            observe.spans.RECORDER.disable()
        producers_run = _om.counter("pipeline.producers_started",
                                    always=True).value - started0
        leaked = _smoke_producer_leak()
        # 2 pools: every window past the first runs on cached keys, so
        # the whole replay needs at most one fill dispatch per prep path
        # (ed window, vrf window) — more means the cache is not reused
        replay_fills = GLOBAL_PRECOMPUTE_CACHE.device_fills - fills0
        hash_ok = cpu_hash == jax_hash
        verdict_ok, fold_ok, warm_fills, warm_jobs, parity_reqs = \
            _smoke_verdict_parity(jb)
        snapshot_ok, disabled_writes, disabled_spans = \
            _smoke_observe(jb, parity_reqs)
        vrf_probe = _smoke_vrf_spread(jb)
        scrape_ok, scrape_leaked, scrape_q = _smoke_scrape()
        net_probe = _smoke_net_disabled()
        perfgate_ok, _perfgate_verdict = _smoke_perfgate()
        sharded_probe = _smoke_sharded_replay(rules, blocks_l)
        serve_probe = _smoke_serve()
        stream_probe = _smoke_stream(chain, jb, cpu_hash)
        result = {"metric": "bench_smoke", "value": 1.0,
                  "blocks": len(blocks_l), "proofs": n_proofs,
                  "state_hash_parity": bool(hash_ok),
                  "verdict_parity": bool(verdict_ok),
                  "fold_verdict_parity": bool(fold_ok),
                  "pipelined_producers_run": int(producers_run),
                  "producer_threads_leaked": int(leaked),
                  "overlap_probe": overlap_probe,
                  "vrf_spread_probe": vrf_probe,
                  "replay_fill_dispatches": int(replay_fills),
                  "warm_device_fills": int(warm_fills),
                  "warm_kes_jobs": int(warm_jobs),
                  "observe_snapshot_parses": bool(snapshot_ok),
                  "disabled_registry_writes": int(disabled_writes),
                  "disabled_spans_recorded": int(disabled_spans),
                  "scrape_roundtrip": bool(scrape_ok),
                  "scrape_threads_leaked": int(scrape_leaked),
                  "scrape_submit_drain_quantiles": scrape_q,
                  "net_disabled_probe": net_probe,
                  "perfgate_ok": bool(perfgate_ok),
                  "sharded_replay_smoke": sharded_probe,
                  "serve_probe": serve_probe,
                  "stream_probe": stream_probe,
                  "precompute": GLOBAL_PRECOMPUTE_CACHE.stats()}
        if not (hash_ok and verdict_ok and fold_ok
                and producers_run >= 1 and leaked == 0
                and overlap_probe["host_seq_secs"] > 0
                and vrf_probe["ok"]
                and warm_fills == 0
                and warm_jobs == 0 and replay_fills <= 3
                and snapshot_ok and disabled_writes == 0
                and disabled_spans == 0
                and scrape_ok and scrape_leaked == 0
                and net_probe["ok"]
                and perfgate_ok and sharded_probe["ok"]
                and serve_probe["ok"] and stream_probe["ok"]):
            result["value"] = 0.0
            print(json.dumps(result))
            raise SystemExit(f"bench --smoke parity failure: {result}")
        print(json.dumps(result))
        return result
    finally:
        BLOCKS, TXS, EPOCH_LEN = old
        shutil.rmtree(tmp, ignore_errors=True)


def _smoke_producer_leak() -> int:
    """Count still-alive replay producer threads after joining grace:
    the pipeline must never leak its thread — started/finished counters
    plus a live-thread sweep (the counters catch a producer that died
    un-joined, the sweep catches one that never exited)."""
    import threading

    from ouroboros_tpu.observe import metrics as _om
    started = _om.counter("pipeline.producers_started", always=True).value
    finished = _om.counter("pipeline.producers_finished",
                           always=True).value
    alive = sum(t.name == "ouro-replay-producer" and t.is_alive()
                for t in threading.enumerate())
    return (started - finished) + alive


# scheduler/OS noise floor for the smoke spread gate: relative spread is
# only meaningful once a rep dwarfs it, so the threshold relaxes by
# floor/median — at hardware-bench rep durations (>= 1s) it converges to
# the strict 0.30 the ISSUE 8 satellite demands, while the tier-1 CPU
# container's ~0.2s reps are judged against the noise they actually sit in
_SPREAD_NOISE_FLOOR_SECS = 0.15


def _smoke_vrf_spread(jb, reps: int = 5, rounds: int = 3) -> dict:
    """The vrf-spread regression gate (BENCH_r05's 45% follow-through):
    fenced, GC-disciplined reps of the warm VRF primitive — the exact
    discipline _timed_reps applies in the hardware bench — must show
    bounded run-to-run spread now that the verdict transfer is 1 B/proof
    (fold kernel) and collection pauses are kept out of timed regions.
    Best round of `rounds` wins (one noisy neighbour must not fail
    tier-1); threshold = 0.30 + noise_floor/median."""
    import hashlib

    from ouroboros_tpu.crypto import vrf_ref
    from ouroboros_tpu.crypto.backend import VrfReq
    vsk = hashlib.sha256(b"smoke-spread").digest()
    vvk = vrf_ref.public_key(vsk)
    reqs = [VrfReq(vvk, b"s%d" % i, vrf_ref.prove(vsk, b"s%d" % i))
            for i in range(8)]

    def run():
        assert all(jb.verify_vrf_batch(reqs))
    run()                       # compile + pin outside the timed rounds
    best = None
    for _ in range(rounds):
        med, spread = median_spread(_timed_reps(run, reps=reps, warmup=0))
        allowed = SPREAD_WARN + _SPREAD_NOISE_FLOOR_SECS / max(med, 1e-9)
        if best is None or spread - allowed < best[0] - best[1]:
            best = (spread, allowed, med)
        if spread < allowed:
            break
    spread, allowed, med = best
    return {"ok": bool(spread < allowed), "spread": round(spread, 3),
            "allowed": round(allowed, 3), "median_secs": round(med, 4),
            "reps": reps}


def _smoke_observe(jb, probe_reqs):
    """Observability gates for --smoke (ISSUE 7 acceptance):

    1. the registry snapshot round-trips (deterministic JSON) and the
       Prometheus exposition re-parses — the export path is never the
       thing that breaks between bench rounds;
    2. with observation DISABLED, a fully instrumented window performs
       ZERO gated registry writes and records zero spans (the NOP fast
       path actually is one).

    `probe_reqs` must be a batch whose window shape is ALREADY compiled
    (the verdict-parity batch): the probe may not spend a fresh XLA:CPU
    composite compile inside the tier-1 budget.

    Returns (snapshot_ok, disabled_writes, disabled_spans)."""
    from ouroboros_tpu import observe
    from ouroboros_tpu.crypto.precompute import GLOBAL_PRECOMPUTE_CACHE

    # re-cold the KES hash-path outcomes: the verdict-parity probe left
    # them warm, and a warm-KES batch takes the DIFFERENT zero-KES-job
    # ('win', ne, nv, nb, 0) composite shape — a fresh multi-minute
    # XLA:CPU compile smoke never pins (measured ~160s of the tier-1
    # budget).  Cold, the batch reuses the parity probe's compiled
    # shape AND exercises more instrumented seams (Blake2b jobs, cache
    # fills) under the disabled flag — a stronger zero-write probe.
    GLOBAL_PRECOMPUTE_CACHE._kes.clear()
    reg = observe.metrics.registry()
    rec = observe.spans.RECORDER
    try:
        snap = json.loads(reg.snapshot_json())
        prom = observe.export.parse_prometheus_text(
            observe.export.prometheus_text(reg))
        snapshot_ok = isinstance(snap, dict) and len(prom) >= len(snap)
    except Exception as e:
        log(f"observe snapshot failed to parse: {e!r}")
        snapshot_ok = False
    # the disabled-observation probe: run an instrumented hot-path
    # window (spans + gated counters on every seam) with everything off
    was_reg, was_rec = reg.enabled, rec.enabled
    reg.disable()
    rec.disable()
    try:
        writes0, roots0 = reg.data_writes, len(rec.roots)
        jb.verify_mixed(probe_reqs)
        with observe.span("probe", cat="sync"):
            pass
        disabled_writes = reg.data_writes - writes0
        disabled_spans = len(rec.roots) - roots0
    finally:
        reg.enabled, rec.enabled = was_reg, was_rec
    return snapshot_ok, disabled_writes, disabled_spans


def _smoke_scrape():
    """Scrape-endpoint smoke (ISSUE 9): serve the process registry over
    the project's own snocket/SDU transport inside a deterministic sim,
    scrape it, and re-derive latency quantiles from the exposition.  The
    pipelined replay that just ran populated `pipeline.submit_drain_secs`
    — the scraped p50/p95/p99 must come back finite and ordered — and
    the sim must wind down with ZERO leaked threads (the clean-shutdown
    contract of ScrapeServer/PeriodicEmitter).

    Returns (ok, leaked_threads, quantiles)."""
    from ouroboros_tpu import simharness as sim
    from ouroboros_tpu.network.snocket import SimSnocket
    from ouroboros_tpu.observe import export
    from ouroboros_tpu.observe.scrape import (
        PeriodicEmitter, ScrapeServer, scrape,
    )

    emitted = []

    async def main():
        sn = SimSnocket()
        srv = await ScrapeServer(sn, "metrics").start()
        em = await PeriodicEmitter(1.0, emitted.append).start()
        text = await scrape(sn, "metrics")
        await sim.sleep(2.5)
        await srv.stop()
        await em.stop()
        return text

    text, trace = sim.run_trace(main())
    leaked = len(sim.leaked_threads(trace))
    try:
        parsed = export.parse_prometheus_text(text)
        q = export.prom_histogram_quantiles(
            parsed, "ouro_pipeline_submit_drain_secs")
        ok = (parsed.get("ouro_pipeline_submit_drain_secs_count", 0) > 0
              and 0 < q["p50"] <= q["p95"] <= q["p99"]
              and len(emitted) >= 2)
    except Exception as e:
        log(f"scrape smoke failed to parse: {e!r}")
        ok, q = False, {}
    return ok, leaked, q


def _smoke_net_disabled():
    """Disabled-observation probe for the mux hot path (ISSUE 14): with
    metrics OFF, pumping SDUs through a mux pair in sim performs ZERO
    gated registry writes and ZERO label formats (netmetrics counts its
    own formatting on an `always` counter, so the assertion holds even
    while the registry flag is down), and the per-peer accounting object
    is never even built."""
    from ouroboros_tpu import simharness as sim
    from ouroboros_tpu.network.mux import Mux, bearer_pair
    from ouroboros_tpu.observe import metrics as _om
    from ouroboros_tpu.observe import netmetrics as _net

    reg = _om.REGISTRY
    was = reg.enabled
    reg.disable()
    try:
        writes0 = reg.data_writes
        formats0 = _net.LABEL_FORMATS.value
        io_built = []

        async def main():
            ba, bb = bearer_pair(sdu_size=1024)
            ma, mb = Mux(ba, "smoke-net-a"), Mux(bb, "smoke-net-b")
            ma.start()
            mb.start()
            cha = ma.channel(2, 0)
            chb = mb.channel(2, 1)
            await cha.send(b"x" * 4096)
            got = b""
            while len(got) < 4096:
                got += await chb.recv()
            io_built.append((ma._io, mb._io))
            ma.stop()
            mb.stop()
            return len(got)

        n = sim.run(main(), seed=1)
        writes = reg.data_writes - writes0
        formats = _net.LABEL_FORMATS.value - formats0
        built = any(io is not None for pair in io_built for io in pair)
        return {"ok": bool(writes == 0 and formats == 0
                           and not built and n == 4096),
                "sdu_bytes": int(n),
                "disabled_net_writes": int(writes),
                "disabled_label_formats": int(formats),
                "mux_io_built": bool(built)}
    finally:
        reg.enabled = was


def _smoke_perfgate():
    """Run the trajectory gate over the committed BENCH_r*.json rounds —
    tier-1 fails the moment a regressed round is recorded (the prose
    trajectory in ROADMAP becomes an enforced gate).  Since ISSUE 11 the
    MULTICHIP rounds ride along: once a green sharded-replay round is
    recorded, a later red mesh round (rc!=0, unattributed compile, or
    parity lost) fails tier-1 too — rounds predating the sharded replay
    are tolerated as skipped.  Since ISSUE 14 the serve section is gated
    the same way: once a recorded round carries one, the latest must
    hold the 5x-vs-unbatched + p95-inside-deadline bar."""
    from tools.perfgate import check_multichip, check_serve, \
        check_trajectory
    paths = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    if not paths:
        return True, {"checks": [], "note": "no recorded rounds"}
    verdict = check_trajectory(paths)
    sv = check_serve(paths)
    verdict["serve"] = sv
    verdict["ok"] = verdict["ok"] and sv["ok"]
    mc_paths = sorted(glob.glob(os.path.join(REPO, "MULTICHIP_r*.json")))
    if mc_paths:
        mc = check_multichip(mc_paths)
        verdict["multichip"] = mc
        verdict["ok"] = verdict["ok"] and mc["ok"]
    if not verdict["ok"]:
        log(f"perfgate FAILED: {json.dumps(verdict['checks'])} "
            f"{json.dumps(sv['checks'])} "
            f"{json.dumps(verdict.get('multichip', {}).get('checks', []))}")
    return verdict["ok"], verdict


def _smoke_sharded_replay(rules, blocks_l, mesh_n: int = 2,
                          window: int = 4):
    """Sharded pipelined replay smoke (ISSUE 11): over `mesh_n` forced
    host-platform devices, the threaded sharded ReplayResult must be
    byte-identical to the synchronous single-device driver on a valid,
    a tampered, and a truncated chain, with zero leaked producer
    threads.

    Gated on the COST: a sharded composite costs minutes of XLA:CPU
    compile (257s/182s measured at exactly these smoke shapes) — past
    the whole tier-1 budget — so the probe skips on host-platform
    devices, recording why.  Real accelerators run it per smoke;
    `OURO_SMOKE_MESH=1` forces it anywhere (e.g. a CPU-only CI lane
    with a long budget); `__graft_entry__.dryrun_multichip` covers the
    mesh path in this container."""
    import jax
    forced = os.environ.get("OURO_SMOKE_MESH") == "1"
    if not forced and jax.devices()[0].platform not in ("tpu", "gpu"):
        return {"ok": True,
                "skipped": "host-platform devices: the sharded "
                           "composite's multi-minute XLA:CPU compile "
                           "exceeds the tier-1 budget "
                           "(OURO_SMOKE_MESH=1 forces the probe); "
                           "covered by dryrun_multichip"}
    if len(jax.devices()) < mesh_n:
        return {"ok": False, "skipped": None,
                "error": f"need {mesh_n} devices, have "
                         f"{len(jax.devices())} (XLA_FLAGS host-device "
                         f"forcing must precede jax init)"}
    from ouroboros_tpu.consensus.batch import replay_blocks_pipelined
    from ouroboros_tpu.consensus.headers import ProtocolBlock
    from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE
    from ouroboros_tpu.eras.shelley import KES_FIELD
    from ouroboros_tpu.parallel import ShardedJaxBackend, make_mesh

    def tamper(blks, ix):
        blk = blks[ix]
        sig = bytearray(blk.header.get(KES_FIELD))
        sig[3] ^= 1
        out = list(blks)
        out[ix] = ProtocolBlock(
            blk.header.with_fields(**{KES_FIELD: bytes(sig)}), blk.body)
        return out

    sb = ShardedJaxBackend(make_mesh(mesh_n), min_bucket=16)
    cpu = _cpu_backend()
    variants = [list(blocks_l), tamper(blocks_l, 5),
                list(blocks_l[:3]) + list(blocks_l[4:])]
    ok = True
    details = []
    for blks in variants:
        GLOBAL_BETA_CACHE.clear()
        sync = replay_blocks_pipelined(rules, blks, rules.initial_state(),
                                       backend=cpu, window=window)
        GLOBAL_BETA_CACHE.clear()
        shard = replay_blocks_pipelined(rules, blks,
                                        rules.initial_state(),
                                        backend=sb, window=window)
        same = (shard.n_valid == sync.n_valid
                and (shard.error is None) == (sync.error is None)
                and ((shard.final_state is None)
                     == (sync.final_state is None))
                and (sync.final_state is None
                     or (shard.final_state.ledger.state_hash()
                         == sync.final_state.ledger.state_hash())))
        ok = ok and same
        details.append({"n_valid": [sync.n_valid, shard.n_valid],
                        "match": bool(same)})
    leaked = _smoke_producer_leak()
    return {"ok": bool(ok and leaked == 0), "skipped": None,
            "devices": mesh_n, "variants": details,
            "producer_threads_leaked": int(leaked),
            "padding": sb.padding_stats()}


def _clear_beta_cache():
    from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE
    GLOBAL_BETA_CACHE.clear()


def _smoke_stream(chain_dir, jb, cpu_hash):
    """Streaming-engine smoke (ISSUE 15): replay the smoke chain FROM
    DISK through storage/stream.py — prefetch thread + pipelined verify
    + DiskPolicy snapshot — then reopen with resume and restore the tip
    checkpoint.  Composite-shape discipline (tier1-budget memory): the
    KES outcome cache is re-colded first so the engine's window takes
    the SAME cold ('win', ne, nv, nb, nk) shape the parity replay
    already compiled — zero fresh XLA:CPU compiles; the window size (8)
    matches for the same reason.

    Gates: state-hash parity vs the CPU baseline, >=1 chunk streamed,
    >=1 crash-consistent snapshot written, the resumed reopen replays
    ZERO blocks to the SAME hash, and neither the prefetcher nor the
    producer leaks a thread."""
    from ouroboros_tpu.crypto.precompute import GLOBAL_PRECOMPUTE_CACHE
    from ouroboros_tpu.storage import (
        DiskPolicy, StreamConfig, StreamingReplayEngine,
    )
    from ouroboros_tpu.storage.stream import prefetcher_threads_alive

    fs, db, rules, decode = load_stream_ctx(chain_dir)
    cfg = StreamConfig(window=8, read_ahead=2,
                       policy=DiskPolicy(num_snapshots=2,
                                         snapshot_interval_slots=4),
                       resume=False)
    GLOBAL_PRECOMPUTE_CACHE._kes.clear()
    _clear_beta_cache()
    res = StreamingReplayEngine(fs, db, rules, decode, backend=jb,
                                config=cfg).replay()
    hash_ok = (res.all_valid
               and res.final_state.ledger.state_hash() == cpu_hash)
    # resume: restores the tip snapshot, streams nothing, same hash —
    # the restart-in-seconds contract, on the real backend, for free
    _clear_beta_cache()
    res2 = StreamingReplayEngine(
        fs, db, rules, decode, backend=jb,
        config=StreamConfig(window=8, read_ahead=2, policy=cfg.policy,
                            resume=True)).replay()
    resume_ok = (res2.all_valid and res2.n_valid == 0
                 and res2.stats["resumed_from_slot"] is not None
                 and res2.final_state.ledger.state_hash() == cpu_hash)
    leaked = prefetcher_threads_alive() + _smoke_producer_leak()
    ok = (hash_ok and resume_ok and res.stats["chunks_read"] >= 1
          and res.stats["snapshots_written"] >= 1 and leaked == 0)
    return {"ok": bool(ok), "state_hash_parity": bool(hash_ok),
            "resume_parity": bool(resume_ok),
            "resumed_from_slot": res2.stats["resumed_from_slot"],
            "restore_secs": res2.stats["restore_secs"],
            "threads_leaked": int(leaked),
            "stats": res.stats}


# ---------------------------------------------------------------------------
# --serve: the adaptive micro-batching verification service under seeded
# bursty arrival traces, in deterministic sim time (ISSUE 12)
# ---------------------------------------------------------------------------

# modeled serving costs used when no break-even calibration file exists
# for a real device (this container has none): ~libsodium-class 1 ms per
# CPU-reference proof vs a device batch costing a fixed ~2 ms dispatch +
# 20 µs per lane — the cost SHAPE every accelerator shares; the absolute
# numbers only scale the virtual clock.  With these, break-even is n*=3.
SERVE_MODEL_DEFAULTS = {"cpu_secs_per_req": 1e-3,
                        "device_setup_secs": 2e-3,
                        "device_secs_per_req": 2e-5}


def _serve_population():
    """A small pool of (request, expected-verdict) pairs covering every
    primitive, valid and corrupted — verdicts computed ONCE by the
    pure-Python oracle; the sim samples from the pool so a long trace
    costs no per-arrival EC math."""
    import hashlib

    from ouroboros_tpu.crypto import ed25519_ref, kes, vrf_ref
    from ouroboros_tpu.crypto.backend import (
        CpuRefBackend, Ed25519Req, KesReq, VrfReq,
    )
    sk = hashlib.sha256(b"serve-ed").digest()
    vk = ed25519_ref.public_key(sk)
    vsk = hashlib.sha256(b"serve-vrf").digest()
    vvk = vrf_ref.public_key(vsk)
    ksk = kes.KesSignKey(4, hashlib.sha256(b"serve-kes").digest())
    kvk = ksk.verification_key
    good_kes = ksk.sign(b"kmsg")
    reqs = [Ed25519Req(vk, b"m%d" % i, ed25519_ref.sign(sk, b"m%d" % i))
            for i in range(4)]
    reqs.append(Ed25519Req(vk, b"bad", ed25519_ref.sign(sk, b"good")))
    reqs += [VrfReq(vvk, b"a%d" % i, vrf_ref.prove(vsk, b"a%d" % i))
             for i in range(3)]
    reqs.append(VrfReq(vvk, b"bad-alpha", vrf_ref.prove(vsk, b"a0")))
    reqs += [KesReq(4, kvk, 0, b"kmsg", good_kes.to_bytes()),
             KesReq(4, kvk, 1, b"kmsg", good_kes.to_bytes()),   # bad
             KesReq(4, kvk, 0, b"kmsg", b"\x00" * 7)]           # bad
    oracle = CpuRefBackend()
    want = {}
    want.update(zip(reqs[:5], oracle.verify_ed25519_batch(reqs[:5])))
    want.update(zip(reqs[5:9], oracle.verify_vrf_batch(reqs[5:9])))
    want.update(zip(reqs[9:], oracle.verify_kes_batch(reqs[9:])))
    return [(r, bool(want[r])) for r in reqs], want


def _serve_trace(seed, phases, population):
    """Seeded bursty arrival trace: per phase (label, duration_secs,
    rate_per_sec), Poisson arrivals (exponential gaps) each carrying a
    request sampled from the population.  Returns [(t, req, want)] —
    the SAME trace drives the service sim and the unbatched baseline."""
    import random
    rng = random.Random(seed)
    out = []
    t = 0.0
    for _label, duration, rate in phases:
        end = t + duration
        while True:
            t += rng.expovariate(rate)
            if t >= end:
                t = end
                break
            req, want = population[rng.randrange(len(population))]
            out.append((t, req, want))
    return out


def _serve_unbatched_baseline(trace, cpu_secs_per_req):
    """The per-request CPU baseline on the same trace: one sequential
    CPU verifier (an M/D/1 queue), each request costing
    `cpu_secs_per_req`.  Exact discrete-event fold — no sim needed.
    Returns (makespan_secs, latencies)."""
    free_at = 0.0
    lat = []
    for t, _req, _want in trace:
        start = max(t, free_at)
        free_at = start + cpu_secs_per_req
        lat.append(free_at - t)
    return (free_at if trace else 0.0), lat


def _pct(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return round(sorted_vals[i], 6)


def _run_serve_trace(trace, model, deadline, cfg_kw, break_even):
    """One seeded trace through the VerifyService in deterministic sim
    time.  Returns (stats dict, latencies, parity_ok, leaked)."""
    from ouroboros_tpu import simharness as sim
    from ouroboros_tpu.crypto.backend import CpuRefBackend
    from ouroboros_tpu.crypto.batching import (
        ModeledBackend, PrecheckedBackend, ServiceConfig, VerifyService,
    )
    arrivals = trace["arrivals"]
    lookup = PrecheckedBackend(CpuRefBackend(), dict(trace["want"]))
    device = ModeledBackend(model["device_setup_secs"],
                            model["device_secs_per_req"], inner=lookup,
                            name="modeled-device")
    cpu = ModeledBackend(0.0, model["cpu_secs_per_req"], inner=lookup,
                         name="modeled-cpu")
    results = []

    async def client(req, want):
        t0 = sim.now()
        ok = await svc.verify(req, deadline=deadline)
        results.append((sim.now() - t0, bool(ok) == want))

    svc = None

    async def main():
        nonlocal svc
        cfg = ServiceConfig(
            initial_latency=model["device_setup_secs"], **cfg_kw)
        svc = await VerifyService(device, cpu_ref=cpu, config=cfg,
                                  break_even=break_even).start()
        tasks = []
        for t, req, want in arrivals:
            gap = t - sim.now()
            if gap > 0:
                await sim.sleep(gap)
            tasks.append(sim.spawn(client(req, want),
                                   label=f"serve-client-{len(tasks)}"))
        for task in tasks:
            await task.wait()
        makespan = sim.now()
        await svc.stop()
        return makespan

    makespan, sim_trace = sim.run_trace(main())
    leaked = len(sim.leaked_threads(sim_trace))
    lat = sorted(l for l, _ in results)
    parity = all(ok for _, ok in results) and len(results) == len(arrivals)
    return {"makespan_secs": round(makespan, 6),
            "service": dict(svc.stats),
            "batch_size_hist": {str(k): svc.batch_sizes[k]
                                for k in sorted(svc.batch_sizes)}}, \
        lat, parity, leaked


def _serve_break_even(model, bucket=256):
    """BreakEvenTable derived from the latency model — NEVER from a
    persisted calibration file: the serve legs are a deterministic
    tier-1 gate, so routing (n*) and the modeled costs it was derived
    from must come from the same place.  Real-device calibration
    (`calibrate_break_even`, persisted beside the autotune choices) is
    for production services, where the same backend that was measured
    does the serving."""
    from ouroboros_tpu.crypto.batching import BreakEvenTable
    dev_batch = (model["device_setup_secs"]
                 + model["device_secs_per_req"] * bucket)
    cpu_one = model["cpu_secs_per_req"]
    # device cost is setup-dominated at coalescer sizes: break even where
    # n sequential CPU verifies outrun one device dispatch of n
    n_star = 1
    while (model["device_setup_secs"]
           + model["device_secs_per_req"] * n_star) >= cpu_one * n_star \
            and n_star < bucket:
        n_star += 1
    entries = {p: {"n_star": int(n_star),
                   "cpu_secs_per_req": cpu_one,
                   "device_secs_batch": round(dev_batch, 9),
                   "bucket": bucket}
               for p in ("ed25519", "vrf", "kes")}
    return BreakEvenTable(entries, "modeled-device"), True


def serve_bench(seed: int = 7, scale: float = 1.0,
                deadline: float = 0.05) -> dict:
    """The ``serve`` section: the coalescing service vs the unbatched
    per-request CPU baseline on seeded bursty sim traces.

    Three legs, all deterministic virtual time at a fixed seed:

    * **saturated** — Poisson warm phase + burst phases well past the
      single-CPU rate: the service must sustain >= 5x the unbatched
      baseline with p95 request latency inside the deadline;
    * **light_load** — arrival gaps far above the coalescing window:
      every flush is below break-even, so ZERO device dispatches (the
      whole trace rides the CPU fallback);
    * **backpressure** — a near-simultaneous burst against a tiny
      admission queue: submitters block (the back-pressure contract),
      nothing is lost, every verdict still lands.

    `scale` shrinks the trace for the tier-1 smoke (sub-minute);
    verdict parity vs the pure-Python oracle is asserted on EVERY leg.
    """
    population, want = _serve_population()
    model = dict(SERVE_MODEL_DEFAULTS)
    break_even, modeled = _serve_break_even(model)
    n_star = break_even.n_star("ed25519")

    def run(phases, cfg_kw):
        arrivals = _serve_trace(seed, phases, population)
        stats, lat, parity, leaked = _run_serve_trace(
            {"arrivals": arrivals, "want": want}, model, deadline,
            cfg_kw, break_even)
        return arrivals, stats, lat, parity, leaked

    out = {"seed": seed, "deadline_secs": deadline,
           "modeled_costs": modeled, "model": model,
           "break_even": break_even.snapshot()}

    # -- saturated: every phase's arrival rate sits well past the single-
    # CPU service rate (1/cpu_secs_per_req = 1000/s on the default
    # model), so the measured makespan ratio is the CAPACITY gap, not an
    # arrival-rate artifact — a cooldown below the CPU rate would let
    # the baseline catch up while the service idles
    phases = [("warm", 0.4 * scale, 5000.0),
              ("burst", 0.2 * scale, 10000.0)]
    arrivals, stats, lat, parity, leaked = run(
        phases, {"max_batch": 256, "max_queue": 2048})
    cpu_makespan, cpu_lat = _serve_unbatched_baseline(
        arrivals, model["cpu_secs_per_req"])
    cpu_lat.sort()
    n = len(arrivals)
    svc_stats = stats["service"]
    misses = svc_stats["deadline_misses"]
    out["saturated"] = {
        "phases": [[p, round(d, 3), r] for p, d, r in phases],
        "requests": n,
        "makespan_secs": stats["makespan_secs"],
        "proofs_per_sec": round(n / stats["makespan_secs"], 1),
        "cpu_unbatched_makespan_secs": round(cpu_makespan, 6),
        "cpu_unbatched_proofs_per_sec": round(n / cpu_makespan, 1),
        "vs_unbatched_cpu": round(cpu_makespan / stats["makespan_secs"],
                                  2),
        "latency": {"p50": _pct(lat, 0.50), "p95": _pct(lat, 0.95),
                    "p99": _pct(lat, 0.99)},
        "cpu_unbatched_latency": {"p50": _pct(cpu_lat, 0.50),
                                  "p95": _pct(cpu_lat, 0.95),
                                  "p99": _pct(cpu_lat, 0.99)},
        "p95_within_deadline": _pct(lat, 0.95) <= deadline,
        "deadline_misses": misses,
        "deadline_miss_frac": round(misses / n, 4) if n else 0.0,
        "service": svc_stats,
        "batch_size_hist": stats["batch_size_hist"],
        "parity": parity,
        "leaked_threads": leaked,
    }

    # -- light load: gaps far above the coalescing window -------------------
    phases = [("idle", max(8.0 * scale, 2.0), 2.0)]
    arrivals, stats, lat, parity, leaked = run(
        phases, {"max_batch": 256, "max_queue": 2048})
    svc_stats = stats["service"]
    out["light_load"] = {
        "requests": len(arrivals),
        "break_even_n": n_star,
        "device_batches": svc_stats["device_batches"],
        "fallback_requests": svc_stats["fallback_requests"],
        "latency_p95": _pct(lat, 0.95),
        "parity": parity,
        "leaked_threads": leaked,
    }

    # -- back-pressure: burst >> tiny admission queue -----------------------
    phases = [("slam", 0.01, 20000.0)]
    arrivals, stats, lat, parity, leaked = run(
        phases, {"max_batch": 64, "max_queue": 32})
    svc_stats = stats["service"]
    out["backpressure"] = {
        "requests": len(arrivals),
        "max_queue": 32,
        "backpressure_waits": svc_stats["backpressure_waits"],
        "completed": svc_stats["submitted"],
        "parity": parity,
        "leaked_threads": leaked,
    }
    out["ok"] = bool(
        out["saturated"]["parity"] and out["light_load"]["parity"]
        and out["backpressure"]["parity"]
        and out["saturated"]["vs_unbatched_cpu"] >= 5.0
        and out["saturated"]["p95_within_deadline"]
        and out["light_load"]["device_batches"] == 0
        and out["saturated"]["leaked_threads"] == 0
        and out["light_load"]["leaked_threads"] == 0
        and out["backpressure"]["leaked_threads"] == 0)
    return out


def _smoke_serve():
    """Sub-minute serve probe for --smoke/tier-1: the scaled-down
    serve_bench — parity on every leg, >=5x over the unbatched CPU
    baseline at saturation, p95 inside the deadline, zero device
    dispatches under light load, zero leaked sim threads."""
    res = serve_bench(seed=7, scale=0.5)
    return res


def _stream_leg(chain_dir, jb, cpu_hash, n_proofs):
    """The ``stream`` section of a bench round (ISSUE 15): ONE replay of
    the same chain FROM DISK through the streaming engine — read-ahead
    prefetch + pipelined verify + periodic snapshots — on the
    already-warm backend (every window shape was pinned by the main
    replays), then a resumed reopen restoring the tip checkpoint.  The
    disk_hidden_frac it reports is the engine's whole point: the
    fraction of disk+decode seconds that ran while a window was in
    flight on device."""
    from ouroboros_tpu.storage import (
        DiskPolicy, StreamConfig, StreamingReplayEngine,
    )
    fs, db, rules, decode = load_stream_ctx(chain_dir)
    cfg = StreamConfig(window=WINDOW, read_ahead=4,
                       policy=DiskPolicy(num_snapshots=2,
                                         snapshot_interval_slots=max(
                                             1, EPOCH_LEN)),
                       resume=False)
    _clear_beta_cache()
    res = StreamingReplayEngine(fs, db, rules, decode, backend=jb,
                                config=cfg).replay()
    if not res.all_valid:
        raise SystemExit(f"stream leg failed at block {res.n_valid}: "
                         f"{res.error}")
    parity = res.final_state.ledger.state_hash() == cpu_hash
    _clear_beta_cache()
    res2 = StreamingReplayEngine(
        fs, db, rules, decode, backend=jb,
        config=StreamConfig(window=WINDOW, read_ahead=4,
                            policy=cfg.policy, resume=True)).replay()
    out = dict(res.stats)
    out["state_hash_parity"] = bool(parity)
    out["proofs_per_sec"] = round(n_proofs / res.stats["replay_secs"], 1)
    out["restart"] = {
        "restore_secs": res2.stats["restore_secs"],
        "blocks_replayed": res2.n_valid,
        "state_hash_parity": bool(
            res2.all_valid and res2.final_state is not None
            and res2.final_state.ledger.state_hash() == cpu_hash),
    }
    if not parity:
        raise SystemExit("stream leg state hash parity violated")
    return out


def _mesh_leg(rules, blocks, cpu_hash, cpu_secs, tpu_secs, n_proofs,
              mesh_n: int):
    """The sharded pipelined replay leg of the bench (ISSUE 11): the
    SAME chain and window size through replay_blocks_pipelined over a
    ShardedJaxBackend — threaded producer/consumer, per-shard packed
    windows, fold verdicts — with the identical measurement discipline
    (cold-beta warmup x2, fenced timed reps, state-hash parity per rep).
    Returns the ``sharded`` dict for the output JSON."""
    import jax

    from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE
    from ouroboros_tpu.parallel import (
        ShardedJaxBackend, log_compile_time, make_mesh,
    )
    if len(jax.devices()) < mesh_n:
        raise SystemExit(
            f"--mesh {mesh_n}: only {len(jax.devices())} devices "
            f"visible (host-platform forcing happens before jax init; "
            f"re-run as a fresh process)")
    sb = TimingBackend(ShardedJaxBackend(make_mesh(mesh_n)))
    # warmup replay 1: compiles BOTH sharded window shapes (beta-carrying
    # and final beta-free) + the fold programs, fills the key cache —
    # attributed so a multi-minute XLA:CPU compile is named, not mystery
    with log_compile_time(f"mesh={mesh_n} sharded replay warmup"):
        GLOBAL_BETA_CACHE.clear()
        replay(rules, blocks, sb, WINDOW)
    # warmup replay 2: warm key cache, steady-state shapes
    GLOBAL_BETA_CACHE.clear()
    replay(rules, blocks, sb, WINDOW)
    pad0 = sb.padding_stats()
    times, dev_times, disp_times = [], [], []
    for _ in range(REPS):
        GLOBAL_BETA_CACHE.clear()
        _device_fence()
        sb.device_secs = sb.dispatch_secs = 0.0
        secs, mesh_hash, _ = replay(rules, blocks, sb, WINDOW)
        assert mesh_hash == cpu_hash, \
            "sharded replay state hash parity violated"
        times.append(secs)
        dev_times.append(sb.device_secs)
        disp_times.append(sb.dispatch_secs)
    med, spread = check_spread("sharded replay", times)
    return {
        "devices": mesh_n,
        "proofs_per_sec": round(n_proofs / med, 1),
        "vs_baseline": round(cpu_secs / med, 3),
        "vs_single_device": round(tpu_secs / med, 3),
        "replay_secs": {"median": round(med, 3),
                        "min": round(min(times), 3),
                        "max": round(max(times), 3)},
        "spread": round(spread, 3),
        # same attribution discipline as the single-device breakdown:
        # consumer-thread blocking drains vs producer-thread pack+submit
        "device_wait_secs": round(statistics.median(dev_times), 3),
        "dispatch_secs": round(statistics.median(disp_times), 3),
        "state_hash_parity": True,
        "padding": sb.padding_stats(since=pad0),   # timed reps only
    }


def main(mesh_n: int = None):
    tmp = tempfile.mkdtemp(prefix="bench-shelley-")
    try:
        # one process per chip: the db_synth child runs BEFORE this
        # process imports the device backend (see synth_chain)
        chain = synth_chain(tmp)
        from ouroboros_tpu.crypto.jax_backend import JaxBackend
        rules, blocks = load(chain)

        from ouroboros_tpu.crypto.backend import GLOBAL_BETA_CACHE

        # CPU baseline: sequential C++ (libsodium-class) replay.  Median of
        # CPU_REPS — host-local and compute-bound, so far less noisy than
        # the device path, but still repeated for honesty.
        cpu = _cpu_backend()
        cpu_times = []
        cpu_hash = n_proofs = None
        for _ in range(CPU_REPS):
            GLOBAL_BETA_CACHE.clear()   # cold cache for every timed replay
            secs, cpu_hash, n_proofs = replay(rules, blocks, cpu, WINDOW)
            cpu_times.append(secs)
        cpu_secs, cpu_spread = check_spread("cpu replay", cpu_times)
        log(f"cpu [{cpu.name}] replay: median {cpu_secs:.2f}s over "
            f"{CPU_REPS} reps (spread {100 * cpu_spread:.0f}%; "
            f"{n_proofs / cpu_secs:.0f} proofs/s, "
            f"{len(blocks) / cpu_secs:.0f} blocks/s)")

        # TPU path: warm-up replay from a cold cache (compiles, autotunes
        # AND precomputes exactly the shapes/keys the timed runs use),
        # then REPS timed replays, each from a cold beta cache but a WARM
        # per-key precomputation cache (the steady state: zero per-key
        # device work, only the ladders)
        from ouroboros_tpu.crypto import autotune
        from ouroboros_tpu.crypto.precompute import GLOBAL_PRECOMPUTE_CACHE
        jb = TimingBackend(JaxBackend())
        GLOBAL_BETA_CACHE.clear()
        replay(rules, blocks, jb, WINDOW)       # cold warmup: compiles,
        #                                         fills the key cache,
        #                                         pins cold window shapes
        log(f"precompute after warmup: {GLOBAL_PRECOMPUTE_CACHE.stats()}")
        # SECOND warmup from the now-warm key cache: warm windows carry
        # zero KES hash jobs, i.e. a DIFFERENT composite shape
        # ('win', ne, nv, nb, 0) than the cold pass — it must be pinned
        # (and compiled) before the tuners freeze, or the first timed
        # rep would be the one paying for it
        GLOBAL_BETA_CACHE.clear()
        replay(rules, blocks, jb, WINDOW)
        warm_fills = GLOBAL_PRECOMPUTE_CACHE.device_fills
        tpu_times, dev_times, disp_times = [], [], []
        rep_phases: list = []
        rep_overlaps: list = []
        tpu_hash = None
        # per-rep phase attribution (ISSUE 7): spans on for the timed
        # reps only — each rep yields sync/compile/dispatch/device/
        # host-seq totals, so a spread warning names the phase that
        # moved instead of leaving a bare 45% number
        from ouroboros_tpu import observe
        observe.spans.RECORDER.enable()
        autotune.freeze_all()   # any mid-bench retune now raises
        try:
            for _ in range(REPS):
                jb.device_secs = jb.dispatch_secs = 0.0
                GLOBAL_BETA_CACHE.clear()
                with observe.span("rep.fence", cat="sync", fence=True):
                    pass        # drain in-flight dispatches pre-rep
                # discard pre-rep spans (the fence above ran OUTSIDE the
                # timed rep — attributing it would make phases sum past
                # rep_secs and under-report `other`)
                observe.spans.RECORDER.drain()
                secs, tpu_hash, _ = replay(rules, blocks, jb, WINDOW)
                tpu_times.append(secs)
                dev_times.append(jb.device_secs)
                disp_times.append(jb.dispatch_secs)
                roots = observe.spans.RECORDER.drain()
                rep_phases.append(_rep_phase_totals(observe, roots, secs))
                rep_overlaps.append(_rep_overlap(observe, roots))
        except autotune.FrozenAutotunerError as e:
            raise SystemExit(
                f"mid-bench retune attempt inside a timed replay rep "
                f"({e}); the two warmup replays failed to pin every "
                f"window shape — numbers from this run are not "
                f"trustworthy") from e
        finally:
            autotune.thaw_all()
            observe.spans.RECORDER.disable()
        assert tpu_hash == cpu_hash, "state hash parity violated"
        warm_extra_fills = (GLOBAL_PRECOMPUTE_CACHE.device_fills
                            - warm_fills)
        assert warm_extra_fills == 0, (
            f"cache-warm replay dispatched {warm_extra_fills} per-key "
            f"fill kernels; the precomputation cache is leaking work "
            f"into the steady state")
        tpu_secs, tpu_spread = check_spread("tpu replay", tpu_times)
        dev_secs = statistics.median(dev_times)
        disp_secs = statistics.median(disp_times)
        overlap = _overlap_summary(rep_overlaps)
        log(f"tpu replay: median {tpu_secs:.2f}s over {REPS} reps "
            f"(spread {100 * tpu_spread:.0f}%; "
            f"{n_proofs / tpu_secs:.0f} proofs/s, "
            f"{len(blocks) / tpu_secs:.0f} blocks/s); "
            f"device-wait {dev_secs:.2f}s / dispatch {disp_secs:.2f}s "
            f"(producer thread)")
        if overlap:
            log(f"overlap: host-seq {overlap['host_seq_secs_median']:.2f}s "
                f"of which {overlap['host_hidden_secs_median']:.2f}s "
                f"({100 * overlap['hidden_frac_median']:.0f}%) hidden "
                f"under in-flight device windows; producer stalled "
                f"{overlap['producer_stall_secs_median']:.2f}s on the "
                f"permit gate")
        variance = _phase_variance(rep_phases)
        if variance:
            dom = variance["dominant_phase"]
            log(f"variance: largest cross-rep spread in phase '{dom}' "
                f"({variance['dominant_spread_secs']:.2f}s min->max; "
                f"per-phase "
                f"{ {p: v['spread_secs'] for p, v in variance['per_phase'].items()} })")

        prim = bench_primitives(JaxBackend())
        log(f"primitives: {prim}")
        prim_vs_prev = compare_previous(prim)
        vrf_attr = vrf_attribution(prim)
        if vrf_attr:
            log(f"vrf primitive below best recorded round: {vrf_attr}")

        # streaming-engine leg: the same chain replayed FROM DISK with
        # read-ahead + snapshots + a resumed restart (warm shapes only)
        stream = _stream_leg(chain, jb, cpu_hash, n_proofs)
        log(f"stream: {stream['disk_secs']}s disk+decode, "
            f"{100 * stream['disk_hidden_frac']:.0f}% hidden under "
            f"device; {stream['snapshots_written']} snapshots, restart "
            f"restored in {stream['restart']['restore_secs']}s")

        sharded = None
        if mesh_n:
            sharded = _mesh_leg(rules, blocks, cpu_hash, cpu_secs,
                                tpu_secs, n_proofs, mesh_n)
            log(f"sharded (mesh={mesh_n}): {sharded}")

        # belt-and-braces: a frozen write RAISES at the store site (the
        # except above / _timed_reps), so reaching here with a nonzero
        # count means some future code swallowed the error — still fail
        if autotune.frozen_write_count() != 0:
            raise SystemExit(
                "kernel choices were written inside a timed region — "
                "the warmup phase failed to pin every shape")
        rate = n_proofs / tpu_secs
        print(json.dumps({
            "metric": "shelley_replay_proofs_per_sec",
            "value": round(rate, 1),
            "unit": "proofs/s",
            "vs_baseline": round(tpu_secs and (cpu_secs / tpu_secs), 3),
            "blocks_per_sec": round(len(blocks) / tpu_secs, 1),
            "cpu_baseline_proofs_per_sec": round(n_proofs / cpu_secs, 1),
            "state_hash_parity": True,
            "reps": REPS,
            "spread": round(tpu_spread, 3),
            "replay_secs": {"median": round(tpu_secs, 3),
                            "min": round(min(tpu_times), 3),
                            "max": round(max(tpu_times), 3)},
            "cpu_replay_secs": {"median": round(cpu_secs, 3),
                                "spread": round(cpu_spread, 3)},
            "breakdown": {
                # device_wait = caller-thread blocking drains; dispatch =
                # producer-thread packing+submit (overlapped with the
                # waits, so the two may legitimately sum past wall time)
                "device_wait_secs": round(dev_secs, 3),
                "dispatch_secs": round(disp_secs, 3),
                "host_secs": round(tpu_secs - dev_secs, 3)},
            "overlap": overlap,
            "phases": rep_phases,
            "variance": variance,
            "metrics": observe.metrics.registry().snapshot(),
            "kernel_choices": {
                "@".join(str(p) for p in k): ("pallas" if v else "xla")
                for k, v in jb._inner.kernel_choices.items()},
            "precompute": GLOBAL_PRECOMPUTE_CACHE.stats(),
            "primitives": prim,
            "primitives_vs_previous": prim_vs_prev,
            "stream": stream,
            **({"vrf_attribution": vrf_attr} if vrf_attr else {}),
            **({"sharded": sharded} if sharded else {}),
        }))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny parity-only replay (1 rep, no timing "
                         "assertions); the tier-1 replay-path gate")
    ap.add_argument("--retune", action="store_true",
                    help="invalidate the persisted kernel choices and "
                         "re-measure pallas-vs-XLA from scratch")
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="also run the sharded pipelined replay over an "
                         "N-device mesh (forced host-platform devices "
                         "off-TPU) and report sharded proofs/s beside "
                         "the single-device number")
    ap.add_argument("--serve", action="store_true",
                    help="the adaptive micro-batching verification "
                         "service under seeded bursty arrival traces "
                         "in deterministic sim time: p50/p95/p99 "
                         "request latency and proofs/s vs the "
                         "unbatched per-request CPU baseline "
                         "(crypto/batching.py, ROADMAP item 3)")
    ap.add_argument("--serve-seed", type=int, default=7,
                    help="arrival-trace seed for --serve (default 7)")
    args = ap.parse_args()
    if args.retune:
        # tuner_for() reads this when the first backend is constructed
        os.environ["OURO_RETUNE"] = "1"
    if args.mesh or args.smoke:
        # mesh legs need multiple XLA devices; forcing host-platform
        # devices only works BEFORE jax initialises, which is why this
        # sits in __main__ (module level stays jax-free) and why the
        # flag is a no-op on real TPU platforms (it only multiplies the
        # HOST platform's device count)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            n = max(args.mesh or 0, 2)
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}"
            ).strip()
    if args.serve:
        res = serve_bench(seed=args.serve_seed)
        print(json.dumps({
            "metric": "verify_service_serve",
            "value": res["saturated"]["proofs_per_sec"],
            "unit": "proofs/s",
            "vs_unbatched_cpu": res["saturated"]["vs_unbatched_cpu"],
            "serve": res}))
        if not res["ok"]:
            raise SystemExit("bench --serve gate failure (see 'serve' "
                             "section)")
    elif args.smoke:
        smoke()
    else:
        main(mesh_n=args.mesh)
