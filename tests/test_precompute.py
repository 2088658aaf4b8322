"""Cross-window precomputation cache (crypto/precompute.py).

Host-only partition: LRU/eviction semantics (with a stubbed device
fill), the KES hash-path outcome namespace.
Device partition: cold-vs-warm parity for every primitive through the
real XLA kernels (a cache-warm window does ZERO per-key fill dispatches
and gives identical verdicts/betas).
"""
import hashlib

import numpy as np
import pytest

from ouroboros_tpu.crypto import ed25519_ref, kes, vrf_ref
from ouroboros_tpu.crypto.backend import (
    CpuRefBackend, Ed25519Req, KesReq, VrfReq,
)
from ouroboros_tpu.crypto.precompute import PrecomputeCache


def _stub_fill(cache, log=None):
    """Replace the device's part of a fill with a synthetic one (LRU
    tests must not depend on jax): a key's table words are derived from
    the key bytes, three copies of its SHA-256."""
    def tables(keys):
        if log is not None:
            log.append(list(keys))
        tab = np.empty((24, len(keys)), dtype=np.uint32)
        ok = np.ones(len(keys), dtype=bool)
        for j, vk in enumerate(keys):
            tab[:, j] = np.tile(np.frombuffer(hashlib.sha256(vk).digest(),
                                              dtype=np.uint32), 3)
            ok[j] = not vk.startswith(b"bad")
        return tab, ok
    cache._device_tables = tables
    return cache


# ---------------------------------------------------------------------------
# host partition: LRU semantics
# ---------------------------------------------------------------------------

def test_lru_eviction_drops_oldest_and_results_stay_correct():
    log = []
    c = _stub_fill(PrecomputeCache(max_entries=4), log)
    keys = [b"k%02d" % i + b"\x00" * 28 for i in range(6)]
    # fill past capacity: 6 inserts into a 4-entry cache
    xa, _xs, _ys, known = c.assemble(keys)
    assert known.all()
    assert len(c) == 4 and c.evictions == 2
    # the OLDEST two were evicted, the newest four retained
    assert [k in c for k in keys] == [False, False, True, True, True, True]
    # results of the over-capacity batch itself were still correct:
    # every lane got its own entry even though two were evicted mid-batch
    for j, k in enumerate(keys):
        want = np.frombuffer(hashlib.sha256(k).digest(), dtype=np.uint32)
        assert (xa[:, j] == want).all()
    # re-assembling an evicted key refills exactly that key
    c.assemble([keys[0]])
    assert log[-1] == [keys[0]]
    assert keys[0] in c


def test_lru_hit_refreshes_recency():
    c = _stub_fill(PrecomputeCache(max_entries=3))
    a, b, d, e = (b"a" * 32, b"b" * 32, b"d" * 32, b"e" * 32)
    c.assemble([a, b, d])
    c.assemble([a])              # refresh a: b is now the LRU entry
    c.assemble([e])              # evicts b, not a
    assert a in c and d in c and e in c and b not in c


def test_negative_entries_cached_without_refill():
    log = []
    c = _stub_fill(PrecomputeCache(max_entries=8), log)
    bad = b"bad" + b"\x00" * 29
    _, _, _, known = c.assemble([bad, b"ok" + b"\x00" * 30])
    assert list(known) == [False, True]
    fills = c.device_fills
    _, _, _, known2 = c.assemble([bad])
    assert not known2[0]
    assert c.device_fills == fills     # no refill for a known-bad key
    assert c.hits == 1


def test_kes_namespace_lru_and_outcomes():
    c = PrecomputeCache(max_entries=2)
    k1, k2, k3 = ((6, 0, b"v1", b"m1"), (6, 1, b"v1", b"m2"),
                  (6, 0, b"v2", b"m3"))
    c.kes_put(k1, b"leaf1", True)
    c.kes_put(k2, b"leaf2", False)
    assert c.kes_get(k1) == (b"leaf1", True)   # refreshes k1
    c.kes_put(k3, b"leaf3", True)              # evicts k2 (LRU)
    assert c.kes_get(k2) is None
    assert c.kes_get(k1) == (b"leaf1", True)
    assert c.kes_get(k3) == (b"leaf3", True)
    assert c.kes_len() == 2 and c.evictions == 1


def test_lock_striping_under_concurrent_submitters():
    """ISSUE 12 satellite: many REAL threads hammering the cache (the
    verification-service submitter shape) must keep the LRU coherent —
    every assemble answers correctly, the per-namespace stripes are
    independent, and contention is measured via `lock_wait` rather than
    guessed.  The eviction-tolerant PR 8 semantics are exercised at a
    capacity small enough that threads evict each other constantly."""
    from concurrent.futures import ThreadPoolExecutor

    c = _stub_fill(PrecomputeCache(max_entries=16))
    point_keys = [b"pt%02d" % i + b"\x00" * 27 for i in range(32)]
    kes_keys = [(4, i % 8, b"vk%d" % (i % 4), b"m%d" % i)
                for i in range(32)]

    def point_worker(seed):
        for r in range(40):
            ks = [point_keys[(seed + j + r) % len(point_keys)]
                  for j in range(5)]
            _xa, _xs, _ys, known = c.assemble(ks)
            assert known.all()      # stubbed fill decodes everything
        return True

    def kes_worker(seed):
        for r in range(60):
            k = kes_keys[(seed * 7 + r) % len(kes_keys)]
            got = c.kes_get(k)
            if got is None:
                c.kes_put(k, b"leaf", True)
            else:
                assert got == (b"leaf", True)
        return True

    with ThreadPoolExecutor(max_workers=8) as ex:
        futs = [ex.submit(point_worker, i) for i in range(4)]
        futs += [ex.submit(kes_worker, i) for i in range(4)]
        assert all(f.result(timeout=60) for f in futs)
    # LRU bounds respected under the stripes, counters coherent
    assert len(c) <= 16 and c.kes_len() <= 16
    assert c.hits > 0 and c.misses > 0
    assert c.lock_wait >= 0             # measured, present in stats
    assert c.stats()["lock_wait"] == c.lock_wait
    # a fresh single-threaded touch still behaves (no lock left held)
    _xa, _xs, _ys, known = c.assemble(point_keys[:3])
    assert known.all()


def test_lock_wait_counter_counts_real_contention():
    """Force contention deterministically: grab one namespace's stripe
    from a helper thread, touch the cache from this one, and watch
    `precompute.lock_wait` tick — the counter is wired, not cosmetic.
    The OTHER namespace must not wait (striping is per-namespace)."""
    import threading

    c = _stub_fill(PrecomputeCache(max_entries=8))
    c.kes_put((4, 0, b"v", b"m"), b"leaf", True)
    held = threading.Event()
    release = threading.Event()

    def holder():
        with c._lock_kes:
            held.set()
            release.wait(timeout=30)

    t = threading.Thread(target=holder)
    t.start()
    held.wait(timeout=30)
    waits0 = c.lock_wait
    # the point namespace is free: no wait recorded
    c.assemble([b"free" + b"\x00" * 28])
    assert c.lock_wait == waits0
    # the KES namespace is held: the lookup must record its wait
    releaser = threading.Timer(0.05, release.set)
    releaser.start()
    assert c.kes_get((4, 0, b"v", b"m")) == (b"leaf", True)
    assert c.lock_wait == waits0 + 1
    t.join(timeout=30)
    releaser.join(timeout=30)


def test_hash_path_key_structural_rejects():
    sk = kes.KesSignKey(3, hashlib.sha256(b"hp").digest())
    raw = sk.sign(b"m").to_bytes()
    key = kes.hash_path_key(3, sk.verification_key, 0, raw)
    assert key is not None
    # message-independent: a different msg signs to the same path key
    assert key == kes.hash_path_key(3, sk.verification_key, 0,
                                    sk.sign(b"other").to_bytes())
    assert kes.hash_path_key(3, sk.verification_key, 8, raw) is None
    assert kes.hash_path_key(3, sk.verification_key, -1, raw) is None
    assert kes.hash_path_key(2, sk.verification_key, 0, raw) is None
    assert kes.hash_path_key(3, sk.verification_key, 0, raw[:-1]) is None


def test_split_mixed_cached_warm_path_skips_host_hashing():
    c = PrecomputeCache()
    be = CpuRefBackend()
    sk = kes.KesSignKey(3, hashlib.sha256(b"smc").digest())
    vk = sk.verification_key
    good = KesReq(3, vk, 0, b"m1", sk.sign(b"m1").to_bytes())
    sig2 = sk.sign(b"m2")
    tam = kes.KesSig(sig2.leaf_sig,
                     ((b"\x00" * 32, b"\x00" * 32),) + sig2.merkle[1:])
    bad = KesReq(3, vk, 0, b"m2", tam.to_bytes())
    short = KesReq(3, vk, 0, b"m3", b"\x00" * 5)
    eds, owners, _v, _vo, n = be.split_mixed_cached(
        [good, bad, short], cache=c)
    assert n == 3 and owners == [0]        # bad path + structural skipped
    assert c.kes_len() == 2                # good + bad outcomes recorded
    misses = c.misses
    # warm pass: same answers, no new outcomes, all from cache
    eds2, owners2, _v, _vo, _n = be.split_mixed_cached(
        [good, bad, short], cache=c)
    assert owners2 == [0] and eds2[0].vk == eds[0].vk
    assert c.kes_len() == 2 and c.misses == misses
    # the oracle agrees with the leaf reduction
    assert ed25519_ref.verify(eds[0].vk, b"m1", eds[0].sig)


# ---------------------------------------------------------------------------
# device partition: cold-vs-warm parity through the real kernels
# ---------------------------------------------------------------------------

def _mixed_reqs():
    """Mixed window sized so every device bucket lands on the shapes the
    replay-pipeline device test already compiles at min_bucket 16
    (composite (16, 16, 16, 32)): <=16 Ed25519 lanes incl. KES leaves,
    <=16 VRF lanes, <=16 betas, 17..32 KES hash jobs (depth-4 paths)."""
    sk = hashlib.sha256(b"pw-ed").digest()
    vk = ed25519_ref.public_key(sk)
    vsk = hashlib.sha256(b"pw-vrf").digest()
    vvk = vrf_ref.public_key(vsk)
    ksk = kes.KesSignKey(4, hashlib.sha256(b"pw-kes").digest())
    kvk = ksk.verification_key
    reqs = [Ed25519Req(vk, b"e%d" % i, ed25519_ref.sign(sk, b"e%d" % i))
            for i in range(3)]
    reqs.append(Ed25519Req(vk, b"bad", ed25519_ref.sign(sk, b"good")))
    reqs.append(Ed25519Req(b"\xff" * 32, b"x", b"\x00" * 64))
    for i in range(2):
        a = b"v%d" % i
        reqs.append(VrfReq(vvk, a, vrf_ref.prove(vsk, a)))
    reqs.append(VrfReq(vvk, b"bad-alpha", vrf_ref.prove(vsk, b"va")))
    good = ksk.sign(b"kmsg")
    tam = kes.KesSig(good.leaf_sig,
                     ((good.merkle[0][0], bytes(32)),) + good.merkle[1:])
    reqs.append(KesReq(4, kvk, 0, b"kmsg", good.to_bytes()))
    reqs.append(KesReq(4, kvk, 0, b"kmsg2", ksk.sign(b"kmsg2").to_bytes()))
    reqs.append(KesReq(4, kvk, 0, b"kmsg", tam.to_bytes()))
    reqs.append(KesReq(4, kvk, 1, b"kmsg", good.to_bytes()))
    reqs.append(KesReq(4, kvk, 0, b"kmsg", b"\x00" * 7))
    # three more periods -> 5 distinct depth-4 hash paths = 20 jobs
    for period in (1, 2, 3):
        ksk.evolve()
        reqs.append(KesReq(4, kvk, period, b"p%d" % period,
                           ksk.sign(b"p%d" % period).to_bytes()))
    proofs = [vrf_ref.prove(vsk, b"b%d" % i) for i in range(4)]
    proofs.append(b"\xff" * 80)
    return reqs, proofs


@pytest.mark.device
@pytest.mark.slow
def test_cold_vs_warm_window_parity_and_zero_warm_fills():
    """The warm-window contract, in miniature: identical verdicts
    and betas cold and warm, with the warm window dispatching ZERO
    per-key fill kernels and ZERO Blake2b hash-path jobs.

    slow+device: ~2.5 min of XLA:CPU ladder executions — the tier-1
    run keeps the same contract through test_served_replay.py::
    test_warm_batch_needs_no_fill_and_no_kes_hashing; this test
    adds the corrupted-lane beta/verdict sweep and the simple-batch
    cache-sharing checks on top."""
    jax = pytest.importorskip("jax")  # noqa: F841
    from ouroboros_tpu.crypto import precompute
    from ouroboros_tpu.crypto.jax_backend import JaxBackend

    reqs, proofs = _mixed_reqs()
    want = CpuRefBackend().verify_mixed(reqs)
    want_betas = {}
    for p in proofs:
        try:
            want_betas[p] = vrf_ref.proof_to_hash(p)
        except ValueError:
            want_betas[p] = None

    cache = precompute.GLOBAL_PRECOMPUTE_CACHE
    jb = JaxBackend(min_bucket=16, use_pallas=False, autotune=False)
    # fresh cache: this test owns the global (restore after)
    saved = cache._kes.copy()   # point tables refill on demand
    cache.clear()
    try:
        sub = jb.submit_window(reqs, next_beta_proofs=proofs)
        assert sub["nk"] == 32             # cold: hash-path jobs shipped
        cold_ok, cold_betas = jb.finish_window(sub)
        assert cold_ok == want
        assert cold_betas == want_betas
        fills = cache.device_fills
        # warm: same window again — no fills, no kes jobs, same answers
        sub2 = jb.submit_window(reqs, next_beta_proofs=proofs)
        assert sub2["nk"] == 0 and sub2["kes_checks"] == []
        warm_ok, warm_betas = jb.finish_window(sub2)
        assert warm_ok == want
        assert warm_betas == want_betas
        assert cache.device_fills == fills
        # the per-primitive simple-batch paths share the cache: their
        # warm run adds no fills either, with verdicts matching the
        # oracle (the fused path above already covered the mixed form)
        ed_only = [r for r in reqs if isinstance(r, Ed25519Req)]
        vrf_only = [r for r in reqs if isinstance(r, VrfReq)]
        assert jb.verify_ed25519_batch(ed_only) == \
            CpuRefBackend().verify_ed25519_batch(ed_only)
        assert jb.verify_vrf_batch(vrf_only) == \
            CpuRefBackend().verify_vrf_batch(vrf_only)
        assert cache.device_fills == fills
    finally:
        cache.clear()
        cache._kes.update(saved)


def test_split_mixed_device_owner_mapping_cold_and_warm():
    """_split_mixed_device is pure host work: identical hash paths in
    one cold window collapse to ONE job slice with every owner attached,
    and a cached outcome removes the jobs entirely."""
    jax = pytest.importorskip("jax")  # noqa: F841
    from ouroboros_tpu.crypto import precompute
    from ouroboros_tpu.crypto.jax_backend import JaxBackend

    ksk = kes.KesSignKey(2, hashlib.sha256(b"own-kes").digest())
    kvk = ksk.verification_key
    reqs = [KesReq(2, kvk, 0, b"m%d" % i, ksk.sign(b"m%d" % i).to_bytes())
            for i in range(3)]
    jb = JaxBackend(use_pallas=False, autotune=False)
    cache = precompute.GLOBAL_PRECOMPUTE_CACHE
    saved = cache._kes.copy()   # point tables refill on demand
    cache.clear()
    try:
        (eds, ed_owner, _v, _vo, msgs, _exp, checks, n) = \
            jb._split_mixed_device(reqs)
        # three sigs share ONE hash path: one pending check, one job set
        assert n == 3 and ed_owner == [0, 1, 2] and len(eds) == 3
        assert len(checks) == 1
        key, start, njobs, owners, leaf = checks[0]
        assert owners == [0, 1, 2] and njobs == 2 and len(msgs) == 2
        assert start == 0
        # the device would fold the per-job verdicts into one outcome;
        # emulate a passing finish and take the warm path
        cache.kes_put(key, leaf, True)
        (eds2, ed_owner2, _v, _vo, msgs2, _exp, checks2, _n) = \
            jb._split_mixed_device(reqs)
        assert msgs2 == [] and checks2 == []
        assert ed_owner2 == [0, 1, 2]
        assert [e.vk for e in eds2] == [e.vk for e in eds]
        # a cached-bad path drops its requests without jobs either
        cache.kes_put(key, leaf, False)
        (eds3, _eo, _v, _vo, msgs3, _exp, checks3, _n) = \
            jb._split_mixed_device(reqs)
        assert eds3 == [] and msgs3 == [] and checks3 == []
    finally:
        cache.clear()
        cache._kes.update(saved)
