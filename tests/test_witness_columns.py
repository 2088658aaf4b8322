"""The seam between the ledger pass and the Ed25519 packer (ISSUE 42): a
block's body witnesses cross it as ONE `Ed25519Cols` (three parallel
columns), an ITEM of the window's request stream that counts for a
request a witness, and every verdict is what the flat list of
`Ed25519Req` / `VrfReq` / `KesReq` objects gave.

The reference shares the seam (the benchmark's `cpp` child replays
through the same `_seq_block_step`), so `correct` cannot see a mistake
made on both sides: here a window's stream is handed to each backend
twice, as items and as the flat list of the request objects they stand
for, and both are held to a statement of the old rule kept in this file
(`_old_rule`: an owner int a request, the least owner among the
failures, the error's text).

The window is real: 4 Byron and 8 Shelley blocks of a forged Cardano
chain through the hard-fork combinator's `extract_proofs`, so it holds
all four kinds of header request and witnesses of both eras; its first
four blocks are a Byron window, its last eight a Shelley one.  Faults
are put into the ITEMS (a flipped signature, a key of 31 bytes), which
no sequential check sees, on `CpuRefBackend`, the `cpp` host backend
and `JaxBackend` on the CPU.

Off the chip every program a fresh backend builds costs one to six
minutes of trace and cache load, and tier-1 has none to spare, so
`JaxBackend` runs NO device program here: it is handed the window
WITHOUT its `VrfReq`s and with its KES hash paths already walked, so
every lane is an Ed25519 lane and no composite is called (the VRF and
KES parts are not what this seam changes; `tests/test_served_replay.py`
holds them), and both forms of its tile program are stand-ins:
`_honest_lanes` for the tile's verdicts, which knows a lane by the
signature packed into it, and `_folding`, the tile program's own fold
over those verdicts.  What is held there is the host's part, which lane
answers for which request; the real programs replay through the same
seam in `tests/test_ed_tiles.py`, `test_longchain.py`,
`test_mixedfill.py`, `test_hardfork_sync.py` and
`test_sharded_replay.py`.
"""
import dataclasses
import gc
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

pytest.importorskip("jax")

from ouroboros_tpu import observe                               # noqa: E402
from ouroboros_tpu import simharness as sim                     # noqa: E402
from ouroboros_tpu.consensus import batch, pipeline             # noqa: E402
from ouroboros_tpu.consensus.ledger import LedgerError          # noqa: E402
from ouroboros_tpu.consensus.mempool import Mempool             # noqa: E402
from ouroboros_tpu.crypto.backend import (                      # noqa: E402
    CpuRefBackend, Ed25519Cols, Ed25519Req, KesReq, VrfReq, WindowVerdict,
    ed25519_columns, iter_requests, lane_count, request_at,
)
from ouroboros_tpu.crypto.batching import (                     # noqa: E402
    ServiceConfig, VerifyService,
)
from ouroboros_tpu.crypto.jax_backend import (                  # noqa: E402
    FOLD_SENT, JaxBackend,
)
from tools import db_analyser as dba                            # noqa: E402

pytestmark = pytest.mark.device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BYRON, BLOCKS, BYRON_TXS, SHELLEY_TXS = 4, 12, 2, 3
START = 40                      # the window's first block in its replay
BACKENDS = ("cpu-ref", "cpp", "jax")
ERAS = {"byron": slice(0, BYRON), "shelley": slice(BYRON, BLOCKS),
        "mixed": slice(0, BLOCKS)}


def _flip(data: bytes, at: int = 3) -> bytes:
    out = bytearray(data)
    out[at] ^= 1
    return bytes(out)


def _forge(path: str, blocks: int, byron: int, shelley_txs: int,
           byron_txs: int, seed: str):
    subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", path, "--protocol", "cardano", "--blocks", str(blocks),
         "--txs-per-block", str(shelley_txs), "--pools", "2", "--f", "1/20",
         "--epoch-length", "432000", "--kes-depth", "6",
         "--slots-per-kes-period", "129600", "--seed", seed,
         "--byron-epoch-length", "60", "--byron-blocks", str(byron),
         "--byron-keys", "7", "--byron-txs-per-block", str(byron_txs),
         "--pbft-threshold", "0.22", "--pbft-window", "2160", "--k", "2160"],
        check=True, capture_output=True)
    db, rules, decode, _cfg = dba.load_db(path)
    return rules, [decode(raw) for _entry, raw in db.stream()]


# -- the window ---------------------------------------------------------------

@pytest.fixture(scope="module")
def window(tmp_path_factory):
    """The items of each block (its header's request objects, then what
    the ledger handed for its body), the rules, the blocks and the state
    before each."""
    rules, blocks = _forge(
        str(tmp_path_factory.mktemp("cols") / "chain"), BLOCKS, BYRON,
        SHELLEY_TXS, BYRON_TXS, "42-cols")
    rules.protocol.prefetch_window([b.header for b in blocks],
                                   CpuRefBackend())
    st, states, parts = rules.initial_state(), [], []
    for b in blocks:
        states.append(st)
        items, st = batch._seq_block_step(rules.protocol, rules.ledger,
                                          st, b)
        parts.append(items)
    return {"parts": parts, "rules": rules, "blocks": blocks,
            "states": states}


def test_the_ledgers_hand_one_columns_item_a_body(window):
    for b, items in zip(window["blocks"], window["parts"]):
        *heads, cols = items
        assert not any(isinstance(r, Ed25519Cols) for r in heads)
        assert isinstance(cols, Ed25519Cols)
        # the witnesses' own bytes, nothing copied
        assert all(vk is w[0] and sig is w[1] and msg is tx.txid
                   for (vk, msg, sig), (tx, w) in zip(
                       zip(cols.vks, cols.msgs, cols.sigs),
                       ((tx, w) for tx in b.body for w in tx.witnesses)))
        assert len(cols) == sum(len(tx.witnesses) for tx in b.body) > 0
    n_heads = [len(items) - 1 for items in window["parts"]]
    assert n_heads == [1] * BYRON + [4] * (BLOCKS - BYRON)


def _as_items(parts) -> list:
    return [r for items in parts for r in items]


def _as_objects(parts) -> list:
    return list(iter_requests(_as_items(parts)))


def _ends(parts) -> list:
    ends, n = [], 0
    for items in parts:
        n += lane_count(items)
        ends.append(n)
    return ends


def _old_rule(parts, ok, start: int):
    """What the flat list gave before the columns: (error text or None,
    the global index of the first bad block or of the window's end)."""
    reqs, owner = [], []
    for i, items in enumerate(parts):
        rs = list(iter_requests(items))
        reqs.extend(rs)
        owner.extend([i] * len(rs))
    first_bad, bad = len(parts), None
    for j, good in enumerate(ok):
        if not good and owner[j] < first_bad:
            first_bad, bad = owner[j], j
    if bad is None:
        return None, start + len(parts)
    return (f"proof {type(reqs[bad]).__name__} failed for block "
            f"{start + first_bad}"), start + first_bad


def _cols_at(items) -> int:
    return [type(r) for r in items].index(Ed25519Cols)


def _with_lane(parts, block: int, lane: int, **cols):
    """`parts` with one lane of one block's columns item changed."""
    out = list(parts)
    at = _cols_at(out[block])
    old = out[block][at]
    new = Ed25519Cols(list(old.vks), list(old.msgs), list(old.sigs))
    for col, fn in cols.items():
        getattr(new, col)[lane] = fn(getattr(new, col)[lane])
    out[block] = out[block][:at] + [new] + out[block][at + 1:]
    return out


def _head_at(parts, block: int, kind) -> int:
    """Where block `block`'s first request object of `kind` lies among
    its items (the header's come first, one request each)."""
    return [type(r) for r in parts[block]].index(kind)


def _with_head(parts, block: int, kind, change):
    out = list(parts)
    at = _head_at(parts, block, kind)
    out[block] = (out[block][:at] + [change(out[block][at])]
                  + out[block][at + 1:])
    return out


def _without(parts, kind):
    return [[r for r in items if not isinstance(r, kind)] for items in parts]


def _request_index(parts, block: int, at: int) -> int:
    """The index of block `block`'s `at`-th request in the window."""
    return sum(lane_count(items) for items in parts[:block]) + at


def _lane_index(parts, block: int, lane: int) -> int:
    """The request index of lane `lane` of block `block`'s columns."""
    return _request_index(parts, block, _cols_at(parts[block]) + lane)


# what is wrong with the window: name -> parts -> (parts, the request
# (or requests) that must fail, the first bad block, the proof the error
# names)
def _first_lane_of_a_block(parts):
    return (_with_lane(parts, 7, 0, sigs=_flip), _lane_index(parts, 7, 0),
            7, "Ed25519Req")


def _last_lane_of_a_block(parts):
    n = len(parts[7][-1])
    return (_with_lane(parts, 7, n - 1, sigs=_flip),
            _lane_index(parts, 7, n - 1), 7, "Ed25519Req")


def _first_lane_of_the_window(parts):
    return (_with_lane(parts, 0, 0, sigs=_flip), _lane_index(parts, 0, 0),
            0, "Ed25519Req")


def _last_lane_of_the_window(parts):
    last = len(parts) - 1
    n = len(parts[last][-1])
    return (_with_lane(parts, last, n - 1, sigs=_flip),
            _lane_index(parts, last, n - 1), last, "Ed25519Req")


def _key_of_31_bytes(parts):
    return (_with_lane(parts, 8, 1, vks=lambda vk: vk[:31]),
            _lane_index(parts, 8, 1), 8, "Ed25519Req")


def _signature_of_63_bytes(parts):
    return (_with_lane(parts, 1, 1, sigs=lambda sig: sig[:63]),
            _lane_index(parts, 1, 1), 1, "Ed25519Req")


def _empty_block_between_two_full_ones(parts):
    out = list(parts)
    out[5] = [r for r in out[5] if not isinstance(r, Ed25519Cols)]
    out = _with_lane(out, 6, 0, sigs=_flip)
    return out, _lane_index(out, 6, 0), 6, "Ed25519Req"


def _three_witnesses(parts, window):
    """Block 9's second transaction signed three times, the third
    signature flipped: its lanes follow in transaction-then-witness
    order."""
    body = list(window["blocks"][9].body)
    (vk, sig), = body[1].witnesses
    body[1] = dataclasses.replace(
        body[1], witnesses=((vk, sig), (vk, sig), (vk, _flip(sig))))
    cols = Ed25519Cols.of_witnesses(body)
    assert len(cols) == len(parts[9][-1]) + 2
    assert cols.msgs[1:4] == [body[1].txid] * 3
    out = list(parts)
    out[9] = out[9][:-1] + [cols]
    return out, _lane_index(out, 9, 3), 9, "Ed25519Req"


def _bad_ocert(parts):
    return (_with_head(parts, 6, Ed25519Req, lambda r: dataclasses.replace(
        r, sig=_flip(r.sig))), _request_index(
            parts, 6, _head_at(parts, 6, Ed25519Req)), 6, "Ed25519Req")


def _bad_kes_leaf(parts):
    # the first 64 bytes of a Sum-KES signature are the leaf's Ed25519
    # signature: the hash path still holds, the leaf does not
    return (_with_head(parts, 9, KesReq, lambda r: dataclasses.replace(
        r, sig_bytes=_flip(r.sig_bytes, 40))), _request_index(
            parts, 9, _head_at(parts, 9, KesReq)), 9, "KesReq")


def _bad_vrf(parts):
    return (_with_head(parts, 5, VrfReq, lambda r: dataclasses.replace(
        r, proof=_flip(r.proof, 70))), _request_index(
            parts, 5, _head_at(parts, 5, VrfReq)), 5, "VrfReq")


def _both_eras(parts):
    # a Shelley witness and a Byron one: the Byron block is the first bad
    out = _with_lane(parts, 10, 0, sigs=_flip)
    out = _with_lane(out, 2, 1, sigs=_flip)
    return (out, (_lane_index(out, 2, 1), _lane_index(out, 10, 0)), 2,
            "Ed25519Req")


def _no_columns_item_at_all(parts):
    # header validation only: every item is a request object
    out = _without(parts, Ed25519Cols)
    return (_with_head(out, 6, Ed25519Req, lambda r: dataclasses.replace(
        r, sig=_flip(r.sig))), _request_index(
            out, 6, _head_at(out, 6, Ed25519Req)), 6, "Ed25519Req")


FAULTS = {f.__name__.lstrip("_"): f for f in (
    _first_lane_of_a_block, _last_lane_of_a_block,
    _first_lane_of_the_window, _last_lane_of_the_window, _key_of_31_bytes,
    _signature_of_63_bytes, _empty_block_between_two_full_ones,
    _three_witnesses, _bad_ocert, _bad_kes_leaf, _bad_vrf, _both_eras,
    _no_columns_item_at_all)}


class _AsWindow:
    """`submit_window` / `finish_window` over a host backend's
    `verify_mixed`, so `pipeline._drain` runs for it as for a device."""

    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def submit_window(self, reqs, next_beta_proofs=()):
        return reqs

    def finish_window(self, state):
        return self.inner.verify_mixed(state), {}


def _honest_lanes(parts):
    """In place of the tile program without fold: a lane holds iff the
    signature packed into it (R's words and sign, s's words) is one the
    forge made."""
    honest = {sig for items in parts for r in iter_requests(items)
              for sig in ((r.sig,) if isinstance(r, Ed25519Req)
                          else (r.sig_bytes[:64],) if isinstance(r, KesReq)
                          else ())}

    def run(_Aw, _xa, _xw, _yw, Rw, signR2, sw, _kw):
        r_rows = np.ascontiguousarray(np.asarray(Rw).T).view(np.uint8)
        r_rows = r_rows.reshape(-1, 32).copy()
        r_rows[:, 31] |= (np.asarray(signR2)[0] << 7).astype(np.uint8)
        s_rows = np.ascontiguousarray(np.asarray(sw).T).view(np.uint8)
        return np.array([r.tobytes() + s.tobytes() in honest
                         for r, s in zip(r_rows, s_rows.reshape(-1, 32))],
                        np.uint8)
    return run


def _folding(run):
    """In place of the folding tile program: `_ed_tile_body`'s
    `fold_tile` over `run`'s verdicts."""
    def fold(first_bad, own, *lanes):
        bad = np.where(run(*lanes) != 0, FOLD_SENT, np.asarray(own)[0]).min()
        return np.minimum(np.asarray(first_bad), bad)
    return fold


def _stand_in_backend(parts) -> JaxBackend:
    jb = JaxBackend(min_bucket=16, use_pallas=False, autotune=False)
    honest = _honest_lanes(parts)
    jb._ed_tile_programs.update({False: honest, True: _folding(honest)})
    return jb


@pytest.fixture(scope="module")
def backends(window):
    # the hash paths walked once, on the host: the device path then
    # finds every outcome in the cache and schedules no Blake2b job
    CpuRefBackend().split_mixed_cached(_as_items(window["parts"]))
    return {"cpu-ref": CpuRefBackend(),
            "cpp": dba.make_backend("cpp" if shutil.which("g++")
                                    else "openssl"),
            "jax": _stand_in_backend(window["parts"])}


def _parts_for(name: str, parts):
    """The window as backend `name` is handed it (the module docstring
    says why the device path gets no `VrfReq`)."""
    return _without(parts, VrfReq) if name == "jax" else parts


def _verdicts(backend, reqs):
    """(per-request verdicts, the folded first-bad request index)."""
    vector = [bool(v) for v in backend.verify_mixed(reqs)]
    if getattr(backend, "supports_window_fold", False):
        ok, _betas = backend.finish_window(
            backend.submit_window(reqs, fold=True))
        assert isinstance(ok, WindowVerdict) and ok.n == len(vector)
        return vector, ok.first_bad
    return vector, batch.first_false(vector)


def _drained(backend, reqs, ends):
    """`pipeline._drain` of the window: (error, n_valid)."""
    if getattr(backend, "supports_window_fold", False):
        sub, via = backend.submit_window(reqs, fold=True), backend
    else:
        via = _AsWindow(backend)
        sub = via.submit_window(reqs)
    entry = (START, sub, reqs, ends, len(ends), 0.0, None, None, 0)
    return pipeline._drain(via, entry)


# the device path is handed no VrfReq here
@pytest.mark.parametrize("name,fault", [
    (name, fault) for name in BACKENDS for fault in sorted(FAULTS)
    if (name, fault) != ("jax", "bad_vrf")])
def test_items_give_what_the_object_list_gives(window, backends, name,
                                               fault):
    backend = backends[name]
    make = FAULTS[fault]
    parts = _parts_for(name, window["parts"])
    parts, bad, bad_block, proof = (make(parts, window)
                                    if make is _three_witnesses
                                    else make(parts))
    items, objects = _as_items(parts), _as_objects(parts)
    assert lane_count(items) == len(objects) > len(items) - (
        fault == "no_columns_item_at_all")
    assert [request_at(items, j) for j in range(len(objects))] == objects
    bads = bad if isinstance(bad, tuple) else (bad,)
    bad = min(bads)
    want = [j not in bads for j in range(len(objects))]
    got_items, fold_items = _verdicts(backend, items)
    got_objects, fold_objects = _verdicts(backend, objects)
    assert got_items == got_objects == want
    assert fold_items == fold_objects == bad
    ends = _ends(parts)
    assert batch.block_of(ends, bad) == bad_block
    text, n_valid = _old_rule(parts, got_objects, START)
    err, n = _drained(backend, items, ends)
    assert isinstance(err, LedgerError) and str(err) == text
    assert n == n_valid == START + bad_block
    assert f"proof {proof} failed for block {START + bad_block}" == text


@pytest.mark.parametrize("era", sorted(ERAS))
@pytest.mark.parametrize("name", BACKENDS)
def test_a_sound_window_holds_in_both_forms(window, backends, name, era):
    """A Byron window, a Shelley window and the window that mixes
    them."""
    backend = backends[name]
    parts = _parts_for(name, window["parts"])[ERAS[era]]
    items, objects = _as_items(parts), _as_objects(parts)
    got_items, fold_items = _verdicts(backend, items)
    got_objects, fold_objects = _verdicts(backend, objects)
    assert got_items == got_objects == [True] * len(objects)
    assert fold_items is fold_objects is None
    assert _drained(backend, items, _ends(parts)) == (
        None, START + len(parts))
    assert _old_rule(parts, got_objects, START) == (
        None, START + len(parts))
    if name == "jax":
        assert not backend._composites and not backend._folds


@pytest.mark.parametrize("era", ["byron", "shelley"])
@pytest.mark.parametrize("name", BACKENDS)
def test_one_flipped_witness_in_a_window_of_one_era(window, backends, name,
                                                    era):
    backend = backends[name]
    parts = _parts_for(name, window["parts"])[ERAS[era]]
    parts = _with_lane(parts, 2, 1, sigs=_flip)
    items, objects = _as_items(parts), _as_objects(parts)
    bad = _lane_index(parts, 2, 1)
    got_items, fold_items = _verdicts(backend, items)
    got_objects, fold_objects = _verdicts(backend, objects)
    assert got_items == got_objects
    assert [j for j, ok in enumerate(got_items) if not ok] == [bad]
    assert fold_items == fold_objects == bad
    err, n = _drained(backend, items, _ends(parts))
    assert (str(err), n) == _old_rule(parts, got_objects, START)
    assert str(err) == f"proof Ed25519Req failed for block {START + 2}"


@pytest.mark.parametrize("name", BACKENDS)
def test_validate_blocks_batched_names_the_block_and_the_proof(
        window, backends, name):
    """The synchronous driver over real blocks, one witness flipped in
    the decoded block itself: same block, same text as before.  The
    host backends take the Shelley blocks, the device path the Byron
    ones (no VRF lane: the module docstring says why)."""
    blocks = list(window["blocks"])
    first, end, at = (0, BYRON, 2) if name == "jax" else (BYRON, BLOCKS, 9)
    body = list(blocks[at].body)
    (vk, sig), *rest = body[1].witnesses
    body[1] = dataclasses.replace(body[1],
                                  witnesses=((vk, _flip(sig)), *rest))
    blocks[at] = type(blocks[at])(blocks[at].header,
                                  type(blocks[at].body)(body))
    res = batch.validate_blocks_batched(
        window["rules"], blocks[first:end], window["states"][first],
        backend=backends[name])
    assert res.n_valid == at - first and len(res.states) == at - first
    assert str(res.error) == (
        f"proof Ed25519Req failed for block index {at - first} "
        f"(slot {blocks[at].slot})")


@pytest.mark.parametrize("name", BACKENDS)
def test_tx_proofs_through_the_mempool_path(window, backends, name):
    """One transaction's `tx_proofs` are one columns item.  On the host
    backends the batching service verifies it as the requests it stands
    for, and the admission that honours its verdicts takes the sound
    transaction and refuses the one whose witness was flipped; the
    device backend's packer makes of the columns the lanes it makes of
    the objects."""
    rules, blocks, states = (window[k] for k in ("rules", "blocks",
                                                 "states"))
    backend = backends[name]
    at = BYRON + 2
    # the Shelley era's own rules and state: the combinator has no
    # transaction-level seam
    ledger = rules.ledger.eras[1].ledger
    state = ledger.tick(states[at].ledger.inner, blocks[at].slot)
    tx = blocks[at].body[0]
    (cols,) = ledger.tx_proofs(state, tx)
    assert isinstance(cols, Ed25519Cols) and len(cols) == len(tx.witnesses)
    assert list(cols) == [Ed25519Req(vk, tx.txid, sig)
                          for vk, sig in tx.witnesses]
    if name == "jax":
        from_cols, ok_cols = backend._finish_ed(backend._pack_ed(cols, 16))
        from_objects, ok_objects = backend._finish_ed(
            backend._pack_ed(list(cols), 16))
        assert all((a == b).all() for a, b in zip(from_cols, from_objects))
        assert (ok_cols == ok_objects).all() and ok_cols[:len(cols)].all()
        return
    assert backend.verify_ed25519_batch(cols) == \
        backend.verify_ed25519_batch(list(cols)) == [True] * len(cols)
    (vk, sig), *rest = tx.witnesses
    forged = dataclasses.replace(tx, witnesses=((vk, _flip(sig)), *rest))
    assert backend.verify_mixed(ledger.tx_proofs(state, forged)) \
        == [False] + [True] * len(rest)

    async def admit(txs):
        service = VerifyService(
            backend, cpu_ref=backend,
            config=ServiceConfig(max_batch=8, default_deadline=0.005))
        await service.start()
        pool = Mempool(ledger, lambda: (state, rules.tip(states[at])),
                       backend=backend, verify_service=service)
        got = await pool.try_add_txs_async(txs)
        await service.stop()
        return got, service.stats["submitted"]

    ((added, rejected), submitted), _trace = sim.run_trace(
        admit([forged, tx]))
    assert added == [tx.txid]
    assert [t.txid for t, _why in rejected] == [forged.txid]
    assert submitted == 2 * len(tx.witnesses)


# -- no request object a witness ---------------------------------------------

class _Counting:
    def __init__(self, monkeypatch):
        self.made = 0
        init = Ed25519Req.__init__

        def counted(req, *a, **kw):
            self.made += 1
            init(req, *a, **kw)
        monkeypatch.setattr(Ed25519Req, "__init__", counted)


@pytest.mark.parametrize("name", ["cpp", "jax"])
def test_no_request_object_is_made_for_a_witness(window, backends, name,
                                                 monkeypatch):
    """Over one window from the decoded blocks to the verdict, the only
    `Ed25519Req`s made are the header's own: one a block (the Byron
    delegate's signature, the Shelley OCert's).  The host backend
    replays all twelve blocks, the device path the Byron ones."""
    backend = backends[name]
    blocks = window["blocks"][:BYRON] if name == "jax" else window["blocks"]
    witnesses = sum(len(tx.witnesses) for b in blocks for tx in b.body)
    assert witnesses >= BYRON * BYRON_TXS
    counting = _Counting(monkeypatch)
    via = backend if name == "jax" else _AsWindow(backend)
    res = batch.replay_blocks_pipelined(
        window["rules"], blocks, window["states"][0],
        backend=via, window=BLOCKS)
    assert res.all_valid and res.n_valid == len(blocks)
    assert counting.made == len(blocks)


# eight small windows of a Byron chain (a PBFT header is one Ed25519
# lane, so the stand-in device path runs them whole): 4 blocks a window,
# 40 one-witness transactions a block
GC_WINDOWS, GC_WINDOW, GC_TXS = 8, 4, 40


@pytest.fixture(scope="module")
def byron_chain(tmp_path_factory):
    rules, blocks = _forge(
        str(tmp_path_factory.mktemp("cols-gc") / "chain"),
        GC_WINDOWS * GC_WINDOW + 1, GC_WINDOWS * GC_WINDOW, 1, GC_TXS,
        "42-gc")
    return rules, blocks[:GC_WINDOWS * GC_WINDOW]


def test_the_host_pass_keeps_no_tracked_object_a_witness(byron_chain):
    """A pipelined replay of eight small windows: between one window's
    submit and the next the collector's tracked objects grow by what a
    BLOCK keeps (its items, its states), not by a witness, and the
    columns' lanes are counted once a window.  Every window's stream is
    held here, so nothing a pass made is freed behind the count."""
    rules, blocks = byron_chain
    witnesses = sum(len(tx.witnesses) for b in blocks for tx in b.body)
    assert witnesses == GC_WINDOWS * GC_WINDOW * GC_TXS
    jb = _stand_in_backend(
        [batch._seq_block_step(rules.protocol, rules.ledger, st, b)[0]
         for st, b in _states_and_blocks(rules, blocks)])
    real = observe.metrics.counter("jax_backend.ed_lanes_real")
    rows = observe.metrics.counter("jax_backend.ed_row_lanes")
    seen, held = [], []
    submit = jb.submit_window

    def counting_submit(reqs, *a, **kw):
        held.append(reqs)
        seen.append((len(gc.get_objects()), len(reqs), lane_count(reqs)))
        return submit(reqs, *a, **kw)
    jb.submit_window = counting_submit
    was = observe.metrics.registry().enabled
    observe.metrics.registry().enable()
    gc.collect()
    gc.disable()
    try:
        r0, w0 = real.value, rows.value
        res = batch.replay_blocks_pipelined(
            rules, blocks, rules.initial_state(), backend=jb,
            window=GC_WINDOW)
        lanes_real, lanes_rows = real.value - r0, rows.value - w0
    finally:
        gc.enable()
        if not was:
            observe.metrics.registry().disable()
    assert res.all_valid and res.n_valid == len(blocks)
    assert len(seen) == GC_WINDOWS
    # a window's stream: a header request and a columns item a block
    assert {(n, lanes) for _c, n, lanes in seen} == {
        (2 * GC_WINDOW, GC_WINDOW * (GC_TXS + 1))}
    # the first submit loads what a backend's first window loads
    growth = [b[0] - a[0] for a, b in zip(seen[1:], seen[2:])]
    assert max(growth) <= 16 * GC_WINDOW < GC_WINDOW * GC_TXS // 2, growth
    # what the chain's shape says: every lane but the header's one a
    # block came inside a columns item
    assert lanes_rows == witnesses
    assert lanes_real == witnesses + len(blocks)
    assert 100.0 * lanes_rows / lanes_real == pytest.approx(
        100.0 * GC_TXS / (GC_TXS + 1))


def _states_and_blocks(rules, blocks):
    st = rules.initial_state()
    for b in blocks:
        yield st, b
        _items, st = batch._seq_block_step(rules.protocol, rules.ledger,
                                           st, b)


def test_objects_handed_by_a_ledger_count_no_row_lane(window, backends):
    real = observe.metrics.counter("jax_backend.ed_lanes_real")
    rows = observe.metrics.counter("jax_backend.ed_row_lanes")
    jb = backends["jax"]
    parts = _parts_for("jax", window["parts"])
    witnesses = sum(len(items[-1]) for items in parts)
    was = observe.metrics.registry().enabled
    observe.metrics.registry().enable()
    try:
        r0, w0 = real.value, rows.value
        jb.finish_window(jb.submit_window(_as_items(parts), fold=True))
        # Byron: the header's one lane; Shelley: the OCert's and the
        # KES leaf's
        assert real.value - r0 == witnesses + BYRON + 2 * (BLOCKS - BYRON)
        assert rows.value - w0 == witnesses
        r0, w0 = real.value, rows.value
        jb.finish_window(jb.submit_window(_as_objects(parts), fold=True))
        assert real.value - r0 == witnesses + BYRON + 2 * (BLOCKS - BYRON)
        assert rows.value - w0 == 0
    finally:
        if not was:
            observe.metrics.registry().disable()


# -- the type and the stream's helpers -----------------------------------------

def _cols(n: int, tag: bytes = b"r") -> Ed25519Cols:
    return Ed25519Cols([tag + b"k%d" % j for j in range(n)],
                       [tag + b"m%d" % j for j in range(n)],
                       [tag + b"s%d" % j for j in range(n)])


def test_columns_read_as_the_requests_they_stand_for():
    cols = _cols(5)
    want = [Ed25519Req(b"rk%d" % j, b"rm%d" % j, b"rs%d" % j)
            for j in range(5)]
    assert len(cols) == 5 and list(cols) == want and cols == want
    assert cols[3] == want[3] and cols[-1] == want[-1]
    assert cols != want[:4] and _cols(0) == [] and not _cols(0)
    assert cols == _cols(5) and cols != _cols(5, b"q")
    assert dict.fromkeys(cols) == dict.fromkeys(want)
    assert ed25519_columns(cols) == (cols.vks, cols.msgs, cols.sigs)
    assert ed25519_columns(cols)[0] is cols.vks
    assert ed25519_columns(want) == (cols.vks, cols.msgs, cols.sigs)
    with pytest.raises(IndexError):
        cols[5]
    with pytest.raises(TypeError):
        hash(cols)


def test_of_witnesses_walks_every_witness_in_order():
    Tx = dataclasses.make_dataclass("Tx", ["txid", "witnesses"])
    txs = [Tx(b"a", ((b"k1", b"s1"),)), Tx(b"b", ()),
           Tx(b"c", ((b"k2", b"s2"), (b"k3", b"s3"), (b"k4", b"s4"))),
           Tx(b"d", ((b"k5", b"s5"),))]
    cols = Ed25519Cols.of_witnesses(txs)
    assert (cols.vks, cols.msgs, cols.sigs) == (
        [b"k1", b"k2", b"k3", b"k4", b"k5"], [b"a", b"c", b"c", b"c", b"d"],
        [b"s1", b"s2", b"s3", b"s4", b"s5"])
    assert len(Ed25519Cols.of_witnesses(())) == 0


# (request objects, columns lanes) a block; -1 = no columns item
LAYOUTS = {
    "shelley": [(4, 3), (4, 3), (4, 3)],
    "byron-then-shelley": [(1, 2), (1, 2), (4, 5), (4, -1), (4, 1)],
    "empty-bodies": [(4, -1), (4, -1), (1, -1)],
    "no-headers": [(0, 3), (0, -1), (0, 2)],
    "one-block": [(2, 7)],
    "an-empty-columns-item": [(4, 2), (4, 0), (1, 3)],
    "nothing": [],
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_stream_of_items_keeps_the_flat_lists_index_space(layout):
    items, flat, owner, ends = [], [], [], []
    for i, (n_heads, n_lanes) in enumerate(LAYOUTS[layout]):
        block = [VrfReq(b"v%d" % i, b"a%d" % j, b"p")
                 for j in range(n_heads)]
        if n_lanes >= 0:
            block.append(_cols(n_lanes, b"%d" % i))
        items += block
        flat += list(iter_requests(block))
        owner += [i] * lane_count(block)
        ends.append(len(flat))
    assert lane_count(items) == len(flat)
    assert list(iter_requests(items)) == flat
    assert [request_at(items, j) for j in range(len(flat))] == flat
    assert [batch.block_of(ends, j) for j in range(len(flat))] == owner
    with pytest.raises(IndexError):
        request_at(items, len(flat))
    # a stream of request objects alone is its own flat list
    assert lane_count(flat) == len(flat)
    assert list(iter_requests(flat)) == flat


def test_first_false_is_the_first_failing_request():
    assert batch.first_false([]) is None
    assert batch.first_false([True, 1, np.True_]) is None
    assert batch.first_false([True, False, True, False]) == 1
    assert batch.first_false(np.array([1, 1, 0], np.uint8)) == 2


@pytest.mark.parametrize("split", ["split_mixed", "split_mixed_cached"])
def test_the_host_split_takes_the_columns_whole(window, split):
    parts = window["parts"]
    items, objects = _as_items(parts), _as_objects(parts)
    be = CpuRefBackend()
    ed_i, own_i, vrf_i, vown_i, n_i = getattr(be, split)(items)
    ed_o, own_o, vrf_o, vown_o, n_o = getattr(be, split)(objects)
    assert n_i == n_o == len(objects)
    assert isinstance(ed_i, Ed25519Cols) and isinstance(ed_o, Ed25519Cols)
    # the same lanes in the same order, each answering for the same
    # request
    assert (vrf_i, vown_i, own_i) == (vrf_o, vown_o, own_o)
    assert ed_i == ed_o and len(ed_i) == len(own_i) == len(set(own_i))
    for j, lane in zip(own_i, ed_i):
        if isinstance(objects[j], Ed25519Req):
            assert lane == objects[j]
        else:
            assert isinstance(objects[j], KesReq) \
                and lane.msg == objects[j].msg


def test_the_device_split_takes_the_columns_whole(window, backends):
    parts = window["parts"]
    jb = backends["jax"]
    got_i = jb._split_mixed_device(_as_items(parts))
    got_o = jb._split_mixed_device(_as_objects(parts))
    assert isinstance(got_i[0], Ed25519Cols)
    assert got_i == got_o
    # every hash path is in the cache: no job, no pending store
    assert got_i[4:7] == ([], [], [])
    assert got_i[7] == len(_as_objects(parts))
