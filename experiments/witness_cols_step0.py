#!/usr/bin/env python
"""Step 0 of ISSUE 42, on the chip's host: what a window's Ed25519 body
witnesses cost the producer from the decoded block to the byte rows the
packer works on (`ed25519_jax._bytes_rows`), in three forms.  Host work
only: no JAX, no chip number; the script holds all three forms itself,
so it reads the same on the parent's tree and on the change's.

A window is 256 blocks of `--txs` one-witness transactions (the rows a
decode worker sends, `ShelleyTx` carrying its id) and four header
requests a block (two `VrfReq`, a `KesReq` whose leaf the split turns
into an Ed25519 lane, the OCert `Ed25519Req`): 352 = a full-body window
of 90,624 Ed25519 lanes (two a replay), 88 = `sync-longchain`'s 23,040
(eight a replay).  Every form keeps the window's request order, so the
same bytes reach the same lanes.

    a  objects    `[Ed25519Req(vk, tx.txid, sig) ...]` a block, joined to
                  the header's list, `reqs.extend`, `owner.extend([i] *
                  len(rs))`; the `isinstance` loop of
                  `_split_mixed_device`; `_pack_ed`'s three comprehensions;
                  `np.asarray(ed_owner)`; `_bytes_rows` of keys and
                  signatures, lengths and join of the messages
    b  lists      ONE item a block holding three Python lists (the
                  witnesses' own bytes); the stream is a list of items,
                  the block map one cumulative count a block; the split
                  walks the items (1,280 a window) and extends three
                  columns a columns item, owners from (start, count) runs
                  by one `np.repeat`; rows and join as in (a)
    c  joined     (b), and each column joined a block (`b"".join`) with
                  a length check (a witness of another length sends the
                  block back to lists); the split collects the chunks, the
                  rows are one join of ~770 chunks and one `frombuffer`,
                  the messages' offsets arithmetic.  The list of keys is
                  kept besides: the per-key table cache looks each up

Stage by stage, wall milliseconds a window (median of `--reps`; the
window before stays alive, as the pipeline's `DEPTH` keeps it; the
blocks are frozen out of the collector's sight, as `_ReplayCollector`
has them), each alone and beside a thread that always wants the
interpreter lock, with the cyclic collector on (its collections and the
seconds inside them counted by a `gc.callbacks` pair, and
`gc.get_count()` read after the eighth window) and off.  One JSON line
a row, appended to `chiprun_out/witness_cols_step0.jsonl`; the last line
of a size says what a replay would save.

    chiprun --timeout 600 -- python experiments/witness_cols_step0.py
"""
import argparse
import gc
import json
import os
import statistics
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.modules.setdefault("jax", None)        # host work: nothing here needs it

import numpy as np  # noqa: E402

from ouroboros_tpu.crypto.backend import (  # noqa: E402
    Ed25519Req, KesReq, VrfReq,
)
from ouroboros_tpu.eras.shelley import ShelleyTx  # noqa: E402

OUT = os.path.join(REPO, "chiprun_out", "witness_cols_step0.jsonl")
WINDOW = 256


def emit(row: dict) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


class Block:
    __slots__ = ("body", "heads")


def make_window(txs: int, seed: int) -> list:
    """256 blocks as the host pass meets them: transactions that carry
    their id, and the header's four requests."""
    rng = np.random.default_rng(seed)
    raw = rng.integers(0, 256, size=(WINDOW, txs + 4, 128),
                       dtype=np.uint8).tobytes()
    at = iter(range(0, len(raw), 128))
    blocks = []
    for _ in range(WINDOW):
        b = Block()
        body = []
        for _t in range(txs):
            o = next(at)
            tx = ShelleyTx(((raw[o:o + 32], 0),), ((raw[o:o + 32], 1, ()),),
                           witnesses=((raw[o + 32:o + 64],
                                       raw[o + 64:o + 128]),))
            body.append(tx.with_txid(raw[o:o + 32]))
        b.body = tuple(body)
        o = [next(at) for _ in range(4)]
        b.heads = [
            VrfReq(raw[o[0]:o[0] + 32], raw[o[0] + 32:o[0] + 48],
                   raw[o[0] + 48:o[0] + 128]),
            VrfReq(raw[o[1]:o[1] + 32], raw[o[1] + 32:o[1] + 48],
                   raw[o[1] + 48:o[1] + 128]),
            KesReq(6, raw[o[3]:o[3] + 32], 0, raw[o[3] + 32:o[3] + 64],
                   raw[o[3] + 64:o[3] + 128]),
            Ed25519Req(raw[o[2]:o[2] + 32], raw[o[2] + 32:o[2] + 64],
                       raw[o[2] + 64:o[2] + 128])]
        blocks.append(b)
    return blocks


def bytes_rows(items, width):
    """`ed25519_jax._bytes_rows`, copied so the script needs no JAX."""
    n = len(items)
    ok = np.ones(n, dtype=bool)
    bad = [j for j, b in enumerate(items) if len(b) != width]
    if bad:
        items = list(items)
        for j in bad:
            items[j] = b"\x00" * width
            ok[j] = False
    arr = np.frombuffer(b"".join(items), dtype=np.uint8).reshape(n, width)
    return arr, ok


def msg_offsets(msgs):
    """The messages' part of `cpp_backend.ed25519_challenge_rows`."""
    n = len(msgs)
    offs = np.zeros(n + 1, dtype=np.uint64)
    np.cumsum(np.fromiter(map(len, msgs), dtype=np.uint64, count=n),
              out=offs[1:])
    return b"".join(msgs), offs


def run_indices(starts, counts):
    """Request index of every lane of runs (first index, lanes)."""
    starts = np.asarray(starts, np.int64)
    counts = np.asarray(counts, np.int64)
    before = np.cumsum(counts) - counts
    return (np.repeat(starts - before, counts)
            + np.arange(int(counts.sum()))).astype(np.int32)


# -- a: the parent's form ----------------------------------------------------

def a_extract(blocks):
    reqs: list = []
    owner: list = []
    for i, b in enumerate(blocks):
        rs = b.heads + [Ed25519Req(vk=vk, msg=tx.txid, sig=sig)
                        for tx in b.body for vk, sig in tx.witnesses]
        reqs.extend(rs)
        owner.extend([i] * len(rs))
    return reqs, owner


def a_split(reqs):
    ed_reqs: list = []
    ed_owner: list = []
    vrf_reqs: list = []
    vrf_owner: list = []
    for i, r in enumerate(reqs):
        if isinstance(r, Ed25519Req):
            ed_reqs.append(r)
            ed_owner.append(i)
        elif isinstance(r, VrfReq):
            vrf_reqs.append(r)
            vrf_owner.append(i)
        elif isinstance(r, KesReq):
            ed_reqs.append(Ed25519Req(r.vk, r.msg, r.sig_bytes[:64]))
            ed_owner.append(i)
    return ed_reqs, ed_owner, vrf_reqs, vrf_owner


def run_a(blocks, ms):
    t0 = time.perf_counter()
    reqs, owner = a_extract(blocks)
    t1 = time.perf_counter()
    ed_reqs, ed_owner, vrf_reqs, vrf_owner = a_split(reqs)
    t2 = time.perf_counter()
    vks = [r.vk for r in ed_reqs]
    msgs = [r.msg for r in ed_reqs]
    sigs = [r.sig for r in ed_reqs]
    t3 = time.perf_counter()
    own = np.asarray(ed_owner, np.int32)
    t4 = time.perf_counter()
    rows = (bytes_rows(vks, 32), bytes_rows(sigs, 64), msg_offsets(msgs))
    t5 = time.perf_counter()
    for name, a, b in (("extract", t0, t1), ("split", t1, t2),
                       ("lists", t2, t3), ("owners", t3, t4),
                       ("rows", t4, t5), ("all", t0, t5)):
        ms.setdefault(name, []).append((b - a) * 1e3)
    # what a submitted window keeps until it drains, and what is compared
    return (reqs, owner, vrf_reqs, vrf_owner), (own, rows, vks)


# -- b: three lists a block --------------------------------------------------

class Cols:
    __slots__ = ("vks", "msgs", "sigs")

    def __init__(self, vks, msgs, sigs):
        self.vks, self.msgs, self.sigs = vks, msgs, sigs

    def __len__(self):
        return len(self.vks)


def cols_of(txs):
    wits = [tx.witnesses for tx in txs]
    msgs = [tx.txid for tx in txs]
    if list(map(len, wits)).count(1) == len(wits):
        if not wits:
            return None
        vks, sigs = zip(*[w[0] for w in wits])
        return Cols(vks, msgs, sigs)
    vks, sigs, ms = [], [], []
    for w, m in zip(wits, msgs):
        for vk, sig in w:
            vks.append(vk)
            ms.append(m)
            sigs.append(sig)
    return Cols(vks, ms, sigs) if vks else None


def b_extract(blocks, make=cols_of):
    items: list = []
    ends: list = []
    n = 0
    for b in blocks:
        c = make(b.body)
        its = b.heads + [c] if c is not None else b.heads
        items.extend(its)
        n += len(b.heads) + (len(c) if c is not None else 0)
        ends.append(n)
    return items, ends


def b_split(items):
    vks: list = []
    msgs: list = []
    sigs: list = []
    starts: list = []
    counts: list = []
    vrf_reqs: list = []
    vrf_owner: list = []
    i = 0
    for r in items:
        if r.__class__ is Cols:
            vks.extend(r.vks)
            msgs.extend(r.msgs)
            sigs.extend(r.sigs)
            starts.append(i)
            counts.append(len(r))
            i += len(r)
            continue
        if isinstance(r, Ed25519Req):
            vks.append(r.vk)
            msgs.append(r.msg)
            sigs.append(r.sig)
            starts.append(i)
            counts.append(1)
        elif isinstance(r, VrfReq):
            vrf_reqs.append(r)
            vrf_owner.append(i)
        elif isinstance(r, KesReq):
            vks.append(r.vk)
            msgs.append(r.msg)
            sigs.append(r.sig_bytes[:64])
            starts.append(i)
            counts.append(1)
        i += 1
    return (vks, msgs, sigs), (starts, counts), vrf_reqs, vrf_owner


def run_b(blocks, ms):
    t0 = time.perf_counter()
    items, ends = b_extract(blocks)
    t1 = time.perf_counter()
    (vks, msgs, sigs), runs, vrf_reqs, vrf_owner = b_split(items)
    t2 = time.perf_counter()
    own = run_indices(*runs)
    t3 = time.perf_counter()
    rows = (bytes_rows(vks, 32), bytes_rows(sigs, 64), msg_offsets(msgs))
    t4 = time.perf_counter()
    for name, a, b in (("extract", t0, t1), ("split", t1, t2),
                       ("owners", t2, t3), ("rows", t3, t4),
                       ("all", t0, t4)):
        ms.setdefault(name, []).append((b - a) * 1e3)
    return (items, ends, vrf_reqs, vrf_owner), (own, rows, vks)


# -- c: each column joined a block -------------------------------------------

class Joined:
    __slots__ = ("vks", "vkj", "msgj", "sigj", "n")

    def __len__(self):
        return self.n


def joined_of(txs):
    c = cols_of(txs)
    if c is None:
        return None
    n = len(c)
    j = Joined()
    j.vks, j.n = c.vks, n
    j.vkj = b"".join(c.vks)
    j.msgj = b"".join(c.msgs)
    j.sigj = b"".join(c.sigs)
    if len(j.vkj) != 32 * n or len(j.msgj) != 32 * n \
            or len(j.sigj) != 64 * n:
        return c                    # a malformed witness: stay lists
    return j


def c_split(items):
    vks: list = []
    vk_chunks: list = []
    msg_chunks: list = []
    msg_lens: list = []         # (length, lanes) runs
    sig_chunks: list = []
    starts: list = []
    counts: list = []
    vrf_reqs: list = []
    vrf_owner: list = []
    i = 0
    for r in items:
        if r.__class__ is Joined:
            vks.extend(r.vks)
            vk_chunks.append(r.vkj)
            msg_chunks.append(r.msgj)
            msg_lens.append((32, r.n))
            sig_chunks.append(r.sigj)
            starts.append(i)
            counts.append(r.n)
            i += r.n
            continue
        if isinstance(r, VrfReq):
            vrf_reqs.append(r)
            vrf_owner.append(i)
            i += 1
            continue
        if isinstance(r, Ed25519Req):
            vk, msg, sig = r.vk, r.msg, r.sig
        else:
            vk, msg, sig = r.vk, r.msg, r.sig_bytes[:64]
        # a single lane of another length would be zeroed here, as
        # `_bytes_rows` does (not timed: none in this traffic)
        vks.append(vk)
        vk_chunks.append(vk)
        msg_chunks.append(msg)
        msg_lens.append((len(msg), 1))
        sig_chunks.append(sig)
        starts.append(i)
        counts.append(1)
        i += 1
    return ((vks, vk_chunks, msg_chunks, msg_lens, sig_chunks),
            (starts, counts), vrf_reqs, vrf_owner)


def run_c(blocks, ms):
    t0 = time.perf_counter()
    items, ends = b_extract(blocks, joined_of)
    t1 = time.perf_counter()
    (vks, vk_chunks, msg_chunks, msg_lens, sig_chunks), runs, vrf_reqs, \
        vrf_owner = c_split(items)
    t2 = time.perf_counter()
    own = run_indices(*runs)
    t3 = time.perf_counter()
    n = own.size
    ok = np.ones(n, dtype=bool)
    lens, reps = np.asarray(msg_lens, np.uint64).T
    offs = np.zeros(n + 1, dtype=np.uint64)
    np.cumsum(np.repeat(lens, reps.astype(np.int64)), out=offs[1:])
    rows = ((np.frombuffer(b"".join(vk_chunks), np.uint8).reshape(n, 32), ok),
            (np.frombuffer(b"".join(sig_chunks), np.uint8).reshape(n, 64),
             ok),
            (b"".join(msg_chunks), offs))
    t4 = time.perf_counter()
    for name, a, b in (("extract", t0, t1), ("split", t1, t2),
                       ("owners", t2, t3), ("rows", t3, t4),
                       ("all", t0, t4)):
        ms.setdefault(name, []).append((b - a) * 1e3)
    return (items, ends, vrf_reqs, vrf_owner), (own, rows, vks)


FORMS = (("a_objects", run_a), ("b_lists", run_b), ("c_joined", run_c))


# -- timing ------------------------------------------------------------------

class Collections:
    """The collector's passes and the seconds inside them."""

    def __init__(self):
        self.passes = [0, 0, 0]
        self.secs = 0.0
        self._t = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.passes[info["generation"]] += 1
            self.secs += time.perf_counter() - self._t


def spin(stop: threading.Event) -> None:
    x = 0
    while not stop.is_set():
        for _ in range(1000):
            x += 1


def same(x, y) -> bool:
    (own_x, rows_x, vks_x), (own_y, rows_y, vks_y) = x, y
    return (np.array_equal(own_x, own_y) and list(vks_x) == list(vks_y)
            and all(np.array_equal(p[0], q[0]) and np.array_equal(p[1], q[1])
                    for p, q in zip(rows_x[:2], rows_y[:2]))
            and rows_x[2][0] == rows_y[2][0]
            and np.array_equal(rows_x[2][1], rows_y[2][1]))


def measure(txs: int, reps: int, seed: int) -> dict:
    # two windows of blocks taken in turn, so no pass finds its inputs
    # in the cache because it just read them
    windows = [make_window(txs, seed), make_window(txs, seed + 1)]
    lanes = WINDOW * (txs + 2)
    # every form hands the packer the same rows for the same requests
    want = run_a(windows[0], {})[1]
    assert want[0].size == lanes
    for _name, run in FORMS[1:]:
        assert same(want, run(windows[0], {})[1]), _name
    del want
    gc.collect()
    gc.freeze()
    medians: dict = {}
    for beside in (False, True):
        stop = threading.Event()
        if beside:
            threading.Thread(target=spin, args=(stop,), daemon=True).start()
        for collector in (True, False):
            for form, run in FORMS:
                gc.collect()
                (gc.enable if collector else gc.disable)()
                seen = Collections()
                gc.callbacks.append(seen)
                ms: dict = {}
                cur = None
                count8 = None
                for k in range(reps):
                    prev = cur          # the window before stays alive
                    cur = run(windows[k % 2], ms)[0]
                    if k == 7:
                        count8 = (gc.get_count(), list(seen.passes))
                gc.callbacks.remove(seen)
                gc.enable()
                del prev, cur
                row = {"lanes_a_window": lanes, "form": form,
                       "beside_a_spinning_thread": beside,
                       "collector": "on" if collector else "off",
                       "ms_a_window": {k: round(statistics.median(v), 3)
                                       for k, v in ms.items()},
                       "ms_a_window_min": {k: round(min(v), 3)
                                           for k, v in ms.items()},
                       "collections_a_window": [round(p / reps, 2)
                                                for p in seen.passes],
                       "after_eight_windows": {
                           "gc_get_count": count8 and list(count8[0]),
                           "collections": count8 and count8[1]},
                       "collector_ms_a_window":
                           round(seen.secs / reps * 1e3, 3)}
                emit(row)
                medians[(form, beside, collector)] = \
                    statistics.median(ms["all"])
        stop.set()
        time.sleep(0.05)
    gc.unfreeze()
    return medians


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=11)
    ap.add_argument("--seed", type=int, default=4200001001)
    ap.add_argument("--txs", type=int, nargs="*", default=[88, 352])
    args = ap.parse_args()
    emit({"host": os.uname().release, "python": sys.version.split()[0],
          "cores": len(os.sched_getaffinity(0)),
          "switch_interval_ms": sys.getswitchinterval() * 1e3})
    for txs in args.txs:
        med = measure(txs, max(args.reps, 8), args.seed)
        windows = 2 if txs == 352 else 8
        saved = {}
        for form in ("b_lists", "c_joined"):
            for beside in (False, True):
                for collector in (True, False):
                    key = form + (", beside" if beside else ", alone") + \
                        (", collector on" if collector
                         else ", collector off")
                    saved[key] = round(
                        (med[("a_objects", beside, collector)]
                         - med[(form, beside, collector)])
                        * windows / 1e3, 4)
        emit({"lanes_a_window": WINDOW * (txs + 2),
              "windows_a_replay": windows,
              "seconds_saved_a_replay_over_a": saved})


if __name__ == "__main__":
    main()
