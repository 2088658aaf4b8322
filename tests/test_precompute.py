"""Cross-window precomputation cache (crypto/precompute.py).

Host-only partition: LRU/eviction semantics (with a stubbed device
fill), the KES hash-path outcome namespace.
Device partition: cold-vs-warm parity for every primitive through the
real XLA kernels (a cache-warm window does ZERO per-key fill dispatches
and gives identical verdicts/betas).
"""
import hashlib
import os

import numpy as np
import pytest

from ouroboros_tpu.crypto import ed25519_ref, kes, vrf_ref
from ouroboros_tpu.crypto.backend import (
    CpuRefBackend, Ed25519Req, KesReq, VrfReq,
)
from ouroboros_tpu.crypto.precompute import PrecomputeCache


def _sha_words(vk):
    return np.tile(np.frombuffer(hashlib.sha256(vk).digest(),
                                 dtype=np.uint32), 3)


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
            tab[:, j] = _sha_words(vk)
            ok[j] = not vk.startswith(b"bad")
        return tab, ok
    # the two halves of the device's part: "dispatched" tables are
    # already there, and the fetch hands them over
    cache._dispatch_tables = tables
    cache._fetch_tables = lambda pending: pending
    return cache


# `assemble` in one go, and as its two phases with the caller's own work
# in between (ISSUE 46): the same tables, `known` and LRU state
HOW = ["assemble", "begin-finish"]


def _assemble(cache, vks, how):
    if how == "assemble":
        return cache.assemble(vks)
    return cache.finish_assemble(cache.begin_assemble(vks))


def _check_stub_lanes(vks, out):
    xa, xw, yw, known = out
    tab = np.concatenate([xa, xw, yw])
    for j, vk in enumerate(vks):
        assert bool(known[j]) == (not vk.startswith(b"bad")), j
        if known[j]:
            assert (tab[:, j] == _sha_words(vk)).all(), j


# ---------------------------------------------------------------------------
# host partition: LRU semantics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", HOW)
def test_lru_eviction_drops_oldest_and_results_stay_correct(how):
    log = []
    c = _stub_fill(PrecomputeCache(max_entries=4), log)
    keys = [b"k%02d" % i + b"\x00" * 28 for i in range(6)]
    # fill past capacity: 6 inserts into a 4-entry cache
    xa, _xs, _ys, known = _assemble(c, keys, how)
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
    _assemble(c, [keys[0]], how)
    assert log[-1] == [keys[0]]
    assert keys[0] in c
    assert c.early_fill_keys == (0 if how == "assemble" else 7)


@pytest.mark.parametrize("how", HOW)
def test_lru_hit_refreshes_recency(how):
    c = _stub_fill(PrecomputeCache(max_entries=3))
    a, b, d, e = (b"a" * 32, b"b" * 32, b"d" * 32, b"e" * 32)
    _assemble(c, [a, b, d], how)
    _assemble(c, [a], how)       # refresh a: b is now the LRU entry
    _assemble(c, [e], how)       # evicts b, not a
    assert a in c and d in c and e in c and b not in c


@pytest.mark.parametrize("how", HOW)
def test_negative_entries_cached_without_refill(how):
    log = []
    c = _stub_fill(PrecomputeCache(max_entries=8), log)
    bad = b"bad" + b"\x00" * 29
    _, _, _, known = _assemble(c, [bad, b"ok" + b"\x00" * 30], how)
    assert list(known) == [False, True]
    fills = c.device_fills
    _, _, _, known2 = _assemble(c, [bad], how)
    assert not known2[0]
    assert c.device_fills == fills     # no refill for a known-bad key
    assert c.hits == 1


# ---------------------------------------------------------------------------
# host partition: a fill in two phases (ISSUE 46)
# ---------------------------------------------------------------------------

def _keys(*ids):
    return [b"k%03d" % i + b"\x00" * 28 for i in ids]


def _counts(c):
    return (c.hits, c.misses, c.device_fills, c.filled_keys, c.evictions)


@pytest.mark.parametrize("batch", [
    _keys(1),                                   # one new key
    _keys(*range(40)),                          # many
    _keys(1, 2, 1, 3, 2, 1),                    # a new key met twice
    _keys(100, 1, 101, 2, 100, 3),              # hits and misses mixed
    _keys(1, 2) + [b"bad" + b"\x00" * 29] + _keys(3),   # one undecodable
    _keys(100, 101),                            # every lane a hit
], ids=["one", "many", "twice", "mixed", "undecodable", "all-hits"])
@pytest.mark.parametrize("bound", [200_000, 3])
def test_begin_then_finish_is_assemble(batch, bound):
    """Lanes, `known`, counters and what stays cached are `assemble`'s,
    also with a bound smaller than the batch: the hits copied at the
    begin survive the store's evictions."""
    caches = []
    for how in HOW:
        c = _stub_fill(PrecomputeCache(max_entries=bound))
        c.assemble(_keys(100, 101))
        out = _assemble(c, batch, how)
        _check_stub_lanes(batch, out)
        caches.append((c, out))
    (one, out_one), (two, out_two) = caches
    assert all((a == b).all() for a, b in zip(out_one, out_two))
    assert _counts(one) == _counts(two)
    assert sorted(one._slot) == sorted(two._slot) and len(two) <= bound
    new = len(set(batch) - set(_keys(100, 101)))
    assert (one.early_fill_keys, two.early_fill_keys) == (0, new)
    assert two.stats()["early_fill_keys"] == new
    assert two.stats()["fill_wait_us"] == two.fill_wait_us == 0  # stubbed


def test_begin_dispatches_and_waits_for_nothing_and_stores_nothing():
    dispatched, fetched = [], []
    c = _stub_fill(PrecomputeCache(), dispatched)
    c._fetch_tables = lambda pending: fetched.append(1) or pending
    c.assemble(_keys(1))
    fetched.clear()
    fill = c.begin_assemble(_keys(1, 2, 3))
    # the device's part is on its way, counted, and the table untouched
    assert dispatched[-1] == _keys(2, 3) and not fetched
    assert (c.device_fills, c.filled_keys, c.early_fill_keys) == (2, 3, 2)
    assert len(c) == 1 and not c._lock_c._lock.locked()
    # the stripe is free between the phases: another batch goes through
    _check_stub_lanes(_keys(9), c.assemble(_keys(9)))
    fetched.clear()
    _check_stub_lanes(_keys(1, 2, 3), c.finish_assemble(fill))
    assert fetched == [1] and len(c) == 4


def test_a_handle_with_every_lane_a_hit_has_nothing_in_flight():
    dispatched = []
    c = _stub_fill(PrecomputeCache(), dispatched)
    c.assemble(_keys(1, 2))
    c._fetch_tables = None             # a finish that fetched would raise
    fill = c.begin_assemble(_keys(2, 1, 2))
    assert fill.keys == [] and fill.pending is None
    assert len(dispatched) == 1 and c.early_fill_keys == 0
    _check_stub_lanes(_keys(2, 1, 2), c.finish_assemble(fill))
    assert (c.hits, c.device_fills) == (3, 1)


def test_two_handles_open_at_once_on_overlapping_keys():
    """Two submitters each between their phases: a key both began is
    filled twice and stored once, and both get right lanes."""
    dispatched = []
    c = _stub_fill(PrecomputeCache(), dispatched)
    first = c.begin_assemble(_keys(1, 2, 3, 4))
    second = c.begin_assemble(_keys(3, 4, 5, 6, 3))
    assert dispatched == [_keys(1, 2, 3, 4), _keys(3, 4, 5, 6)]
    assert len(c) == 0
    # finished in the other order
    _check_stub_lanes(_keys(3, 4, 5, 6, 3), c.finish_assemble(second))
    assert len(c) == 4
    _check_stub_lanes(_keys(1, 2, 3, 4), c.finish_assemble(first))
    assert len(c) == 6 and sorted(c._slot) == _keys(1, 2, 3, 4, 5, 6)
    assert c._used == 6                      # one slot a key
    assert (c.misses, c.filled_keys, c.device_fills) == (8, 8, 2)
    assert c.early_fill_keys == 8 and c.evictions == 0
    # every key a hit from here on
    _check_stub_lanes(_keys(1, 3, 6), c.assemble(_keys(1, 3, 6)))
    assert c.device_fills == 2


def test_many_threads_each_between_their_phases():
    """More submitters than cores, each holding a handle while the
    others look up and store, at a switch interval short enough to cut
    into every compound step: every batch answers right, the bound
    holds, and no fill is lost from the counters."""
    import sys
    import threading
    n_threads = 2 * (os.cpu_count() or 4) + 1
    c = _stub_fill(PrecomputeCache(max_entries=16))
    begun = threading.Barrier(n_threads)
    failures = []

    def submitter(seed):
        try:
            for r in range(25):
                ks = _keys(*((seed + r + j) % 24 for j in range(6)))
                fill = c.begin_assemble(ks)
                if r == 0:
                    begun.wait(timeout=30)   # every thread holds a handle
                _check_stub_lanes(ks, c.finish_assemble(fill))
        except Exception as e:              # noqa: BLE001
            failures.append(repr(e))

    threads = [threading.Thread(target=submitter, args=(7 * i,))
               for i in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not failures
    assert len(c) <= 16 and len(c) == len(c._slot)
    assert not c._lock_c._lock.locked()


def test_a_handle_dropped_between_the_phases_costs_the_cache_nothing():
    """A malformed witness raising in the packer between begin and
    finish: the table is as if the keys had never been seen."""
    c = _stub_fill(PrecomputeCache())
    c.assemble(_keys(100))
    with pytest.raises(ValueError, match="malformed"):
        fill = c.begin_assemble(_keys(100, 1, 2, 3))
        assert fill.keys == _keys(1, 2, 3)
        raise ValueError("malformed witness")
    del fill
    assert len(c) == 1 and _keys(1)[0] not in c
    assert (c.filled_keys, c.device_fills, c.misses) == (4, 2, 4)
    # the same keys later: filled (again) and right
    _check_stub_lanes(_keys(100, 1, 2, 3), c.assemble(_keys(100, 1, 2, 3)))
    assert len(c) == 4 and (c.filled_keys, c.device_fills) == (7, 3)
    assert c.hits == 2


def test_clear_between_the_phases_loses_nothing():
    c = _stub_fill(PrecomputeCache())
    c.assemble(_keys(100, 101))
    fill = c.begin_assemble(_keys(100, 1, 101, 2))
    c.clear()                              # a replay boundary
    assert len(c) == 0
    # the hits were copied out at the begin; the new keys go into the
    # cleared table
    _check_stub_lanes(_keys(100, 1, 101, 2), c.finish_assemble(fill))
    assert sorted(c._slot) == _keys(1, 2)
    _check_stub_lanes(_keys(1, 100), c.assemble(_keys(1, 100)))
    assert len(c) == 3


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
