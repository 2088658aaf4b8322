"""Decode worker processes (ISSUE 32, storage/decode_pool.py): the
prefetcher ships a chunk's bytes out and takes the built blocks back.

What has to hold: the blocks are the ones the prefetch thread would
have decoded, cached slices included; they arrive in chain order; an
error, a closed stream and a dead worker end a replay as loudly and as
cleanly as before; a decoder that cannot be shipped decodes in-thread;
and a worker can neither reach the chip nor outlive its parent.

One small forged Shelley chain of many chunks, no crypto backend, no
JAX; every test carries its own time limit (`limit`), because what it
guards against is a wait that never ends.
"""
import functools
import importlib
import os
import pickle
import signal
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from ouroboros_tpu import observe                              # noqa: E402
from ouroboros_tpu.eras.shelley import KES_FIELD               # noqa: E402
from ouroboros_tpu.storage import decode_pool                  # noqa: E402
from ouroboros_tpu.storage.decode_pool import (                # noqa: E402
    POOL, DecodeWorkerDied,
)
from ouroboros_tpu.storage.stream import (                     # noqa: E402
    BlockPrefetcher, prefetcher_threads_alive,
)
from tools import db_analyser                                  # noqa: E402

BLOCKS, TXS, WINDOW = 48, 5, 8
WORKER_BLOCKS = "replay.decode.worker_blocks"


def limit(seconds: int):
    """The test fails, and does not hang, once `seconds` have passed
    (SIGALRM: pytest and xdist run tests on the main thread)."""
    def deco(fn):
        @functools.wraps(fn)
        def run(*a, **kw):
            def late(_sig, _frame):
                raise TimeoutError(f"{fn.__name__}: over {seconds} s")
            old = signal.signal(signal.SIGALRM, late)
            signal.alarm(seconds)
            try:
                return fn(*a, **kw)
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, old)
        return run
    return deco


@pytest.fixture(scope="module")
def chain_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("workersdb"))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", d, "--protocol", "shelley", "--blocks", str(BLOCKS),
         "--txs-per-block", str(TXS), "--pools", "2", "--f", "4/5",
         "--epoch-length", "500", "--kes-depth", "4", "--chunk-size", "3"],
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    return d


@pytest.fixture(scope="module")
def loaded(chain_dir):
    db, _rules, decode, _cfg = db_analyser.load_db(chain_dir)
    assert len(db) == BLOCKS
    # more chunks than a process ever has workers: order is then the
    # prefetcher's doing, not an accident of one chunk a worker
    assert len(db.chunk_numbers()) > decode_pool.WORKER_CAP
    return db, decode


def in_thread(decode):
    """The same decoder as a closure: it cannot be shipped."""
    return lambda raw: decode(raw)


def worker_blocks() -> int:
    return observe.REGISTRY.get(WORKER_BLOCKS).value


def stream(db, decode, window=WINDOW, depth=2) -> list:
    pre = BlockPrefetcher(db, decode, window=window, depth=depth).start()
    try:
        return list(pre)
    finally:
        pre.close()
        assert prefetcher_threads_alive() == 0


class Tampered:
    """A chunked DB whose block number `at` (in stream order) reads back
    with its last byte cut off; everything else is the DB's."""

    def __init__(self, db, at: int, before_chunk=None):
        self._db, self._at, self._seen = db, at, 0
        self._before_chunk = before_chunk

    def __getattr__(self, name):
        return getattr(self._db, name)

    def chunk_blocks(self, n, from_index=0):
        if self._before_chunk is not None:
            self._before_chunk(n)
        pairs = self._db.chunk_blocks(n, from_index=from_index)
        out = []
        for entry, raw in pairs:
            out.append((entry, raw[:-1] if self._seen == self._at else raw))
            self._seen += 1
        return out


# -- the blocks are the same ---------------------------------------------------

@limit(120)
def test_blocks_from_workers_equal_blocks_decoded_in_thread(loaded):
    db, decode = loaded
    w0 = worker_blocks()
    shipped = stream(db, decode)
    assert worker_blocks() - w0 == BLOCKS
    local = stream(db, in_thread(decode))
    assert worker_blocks() - w0 == BLOCKS        # the closure shipped none
    assert len(shipped) == BLOCKS and shipped == local
    for a, b in zip(shipped, local):
        # what was cut at the walk's offsets crossed intact: the
        # header's own bytes and the spans inside them, and every
        # transaction's id, hashed where the block was decoded
        assert a.header._cache.keys() == b.header._cache.keys() \
            >= {"bytes", "spans"}
        assert a.header._cache["bytes"] == b.header._cache["bytes"]
        assert a.header._cache["spans"] == b.header._cache["spans"]
        assert a.hash == b.hash
        assert a.header.bytes_dropping(KES_FIELD) \
            == b.header.bytes_dropping(KES_FIELD)
        assert len(a.body) == TXS
        for ta, tb in zip(a.body, b.body):
            assert ta.txid_hashed and tb.txid_hashed
            assert ta.txid == tb.txid
    # and they are what the stored bytes say, read with no cache at all
    for blk, (_entry, raw) in zip(shipped, db.stream()):
        plain = type(blk).decode(
            importlib.import_module("ouroboros_tpu.utils.cbor").loads(raw),
            tx_decode=decode.tx_decode)
        assert plain == blk and plain.hash == blk.hash
        assert [t.txid for t in plain.body] == [t.txid for t in blk.body]


@limit(120)
def test_chain_order_with_more_chunks_than_workers(loaded):
    db, decode = loaded
    want = [entry.hash for entry, _raw in db.stream()]
    # depth 1 and a window of two blocks: the read-ahead bound throttles
    # the dispatch all the way, and order still holds
    for window, depth in ((WINDOW, 2), (2, 1), (BLOCKS, 4)):
        got = stream(db, decode, window=window, depth=depth)
        assert [b.hash for b in got] == want
        assert [b.block_no for b in got] == sorted(b.block_no for b in got)


@limit(120)
def test_cardano_decoder_ships_and_counts_era_crossings(tmp_path):
    import types
    spec = importlib.util.spec_from_file_location(
        "db_synth", os.path.join(REPO, "tools", "db_synth.py"))
    dbs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(dbs)
    d = str(tmp_path / "cardano")
    dbs.synth_cardano(types.SimpleNamespace(
        out=d, protocol="cardano", blocks=40, txs_per_block=1, nodes=2,
        pools=2, f="4/5", epoch_length=10, kes_depth=5, chunk_size=5,
        format="native", seed="workers-test", eras="byron-shelley"))
    db, _rules, decode, _cfg = db_analyser.load_db(d)
    crossings = []
    for dec in (decode, in_thread(decode)):
        w0 = worker_blocks()
        pre = BlockPrefetcher(db, dec, window=WINDOW, depth=2).start()
        try:
            blocks = list(pre)
        finally:
            pre.close()
        crossings.append((pre.era_crossings, pre.blocks_decoded,
                          [b.hash for b in blocks], worker_blocks() - w0))
    assert crossings[0][:3] == crossings[1][:3]
    assert crossings[0][0] >= 1
    assert crossings[0][3] == len(db) and crossings[1][3] == 0


# -- errors, closing, a dead worker -------------------------------------------------

@limit(120)
@pytest.mark.parametrize("at", [0, 21, BLOCKS - 1])
def test_a_corrupt_block_raises_as_in_thread_after_the_same_batches(
        loaded, at):
    db, decode = loaded
    seen = {}
    for name, dec in (("in-thread", in_thread(decode)), ("workers", decode)):
        pre = BlockPrefetcher(Tampered(db, at), dec, window=WINDOW,
                              depth=2).start()
        got = []
        try:
            with pytest.raises(Exception) as err:
                for b in pre:
                    got.append(b.hash)
        finally:
            pre.close()
        assert prefetcher_threads_alive() == 0
        seen[name] = (type(err.value), str(err.value), got)
    assert seen["workers"] == seen["in-thread"]
    kind, _msg, got = seen["workers"]
    assert kind is not DecodeWorkerDied
    # whole batches from before the bad block's chunk, in order
    want = [entry.hash for entry, _raw in db.stream()]
    assert got == want[:len(got)] and len(got) <= at
    assert len(got) % WINDOW == 0
    # the workers are none the worse for it
    assert len(stream(db, decode)) == BLOCKS


@limit(120)
def test_close_mid_stream_then_a_second_replay_on_the_same_workers(loaded):
    db, decode = loaded
    stream(db, decode)                 # the pool is up
    pids = sorted(POOL.pids())
    assert pids
    pre = BlockPrefetcher(db, decode, window=2, depth=1).start()
    it = iter(pre)
    first = next(it)
    pre.close()                        # chunks are out at the workers
    assert prefetcher_threads_alive() == 0
    assert pre.blocks_decoded < BLOCKS
    w0 = worker_blocks()
    again = stream(db, decode)
    assert again[0] == first and len(again) == BLOCKS
    assert worker_blocks() - w0 == BLOCKS
    assert sorted(POOL.pids()) == pids         # the same processes


@limit(120)
def test_a_killed_worker_fails_the_replay_with_its_exit_status(loaded):
    db, decode = loaded
    stream(db, decode)
    before = sorted(POOL.pids())

    def kill_all(n, at=db.chunk_numbers()[3]):
        if n == at:
            for pid in POOL.pids():
                os.kill(pid, signal.SIGKILL)

    pre = BlockPrefetcher(Tampered(db, -1, before_chunk=kill_all), decode,
                          window=WINDOW, depth=2).start()
    t0 = time.monotonic()
    try:
        with pytest.raises(DecodeWorkerDied) as err:
            list(pre)
    finally:
        pre.close()
    assert time.monotonic() - t0 < 30
    assert prefetcher_threads_alive() == 0
    assert "exit status -9" in str(err.value)
    assert decode_pool.WORKER_NAME in str(err.value)
    # SIGKILL is asynchronous: a worker not yet gone still looks alive to
    # the pool, which would load it for the next replay
    deadline = time.monotonic() + 10
    while POOL.pids() and time.monotonic() < deadline:
        time.sleep(0.01)
    # the next replay starts new workers and is whole
    assert len(stream(db, decode)) == BLOCKS
    after = sorted(POOL.pids())
    assert after and not set(after) & set(before)


# -- which decoders ship -----------------------------------------------------------

class Unloadable:
    """Pickles here, cannot be loaded in a worker (its module is not
    there): the replay has to find that out and decode in-thread."""

    def __init__(self, decode):
        self.decode = decode

    def __call__(self, raw):
        return self.decode(raw)

    def __reduce__(self):
        return (importlib.import_module, ("no_such_module_for_a_worker",))


class Stateful:
    """The benchmark's `tampering_decode` in small: it counts the blocks
    it has seen, which only means something in one process."""

    def __init__(self, decode):
        self.decode, self.seen, self._lock = decode, 0, threading.Lock()

    def __call__(self, raw):
        with self._lock:               # a lock does not pickle
            self.seen += 1
        return self.decode(raw)


@limit(120)
@pytest.mark.parametrize("kind", ["closure", "lambda", "stateful",
                                  "unloadable", "shipped"])
def test_only_a_decoder_that_ships_goes_to_the_workers(loaded, kind):
    db, decode = loaded
    dec = {"closure": in_thread(decode),
           "lambda": lambda raw, d=decode: d(raw),
           "stateful": Stateful(decode),
           "unloadable": Unloadable(decode),
           "shipped": decode}[kind]
    if kind == "unloadable":
        pickle.dumps(dec)              # it does pickle: the worker refuses
    want = stream(db, in_thread(decode))
    w0 = worker_blocks()
    assert stream(db, dec) == want
    assert worker_blocks() - w0 == (BLOCKS if kind == "shipped" else 0)
    if kind == "stateful":
        assert dec.seen == BLOCKS


@limit(120)
def test_a_second_replay_at_once_decodes_in_thread(loaded):
    db, decode = loaded
    held = POOL.lease(decode)
    assert held is not None
    try:
        assert POOL.lease(decode) is None
        w0 = worker_blocks()
        assert len(stream(db, decode)) == BLOCKS
        assert worker_blocks() == w0
    finally:
        held.release()
    w0 = worker_blocks()
    assert len(stream(db, decode)) == BLOCKS
    assert worker_blocks() - w0 == BLOCKS


@limit(180)
def test_replays_on_more_threads_than_cores_share_the_pool(loaded):
    """Whoever gets the pool ships, the others decode in-thread; every
    replay is whole and the lease is never held twice or left behind."""
    db, decode = loaded
    want = [b.hash for b in stream(db, in_thread(decode))]
    results, errors = [], []

    def one():
        try:
            for _ in range(3):
                results.append([b.hash for b in stream_no_leak_check(
                    db, decode)])
        except BaseException as e:     # read back below
            errors.append(e)

    def stream_no_leak_check(db, decode):
        pre = BlockPrefetcher(db, decode, window=WINDOW, depth=2).start()
        try:
            return list(pre)
        finally:
            pre.close()

    w0 = worker_blocks()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=one)
                   for _ in range(2 * (os.cpu_count() or 4))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=150)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors
    assert len(results) == 3 * len(threads)
    assert all(r == want for r in results)
    assert (worker_blocks() - w0) % BLOCKS == 0
    assert prefetcher_threads_alive() == 0
    lease = POOL.lease(decode)         # nobody kept it
    assert lease is not None
    lease.release()


def test_worker_count_follows_the_cores(monkeypatch):
    for cores, want in ((1, 1), (3, 1), (4, 1), (5, 2), (8, 5),
                        (13, decode_pool.WORKER_CAP),
                        (30, decode_pool.WORKER_CAP)):
        monkeypatch.setattr(os, "sched_getaffinity",
                            lambda _pid, n=cores: set(range(n)))
        assert decode_pool.worker_count() == want
    assert decode_pool.WORKER_CAP == 8 and decode_pool.REPLAY_THREADS == 3


# -- what a worker is ---------------------------------------------------------------

class Probe:
    """Loads in a worker as a function that answers every block with
    what the worker's interpreter holds (the one way to run a question
    there: the worker imports nothing of the tests')."""
    SRC = ("lambda raw: (sorted(k for k, v in __import__('sys').modules"
           ".items() if v is not None), "
           "__import__('sys').modules['__main__'].__spec__.name, "
           "__import__('sys').argv[1], __import__('os').getpid())")

    def __reduce__(self):
        return (eval, (self.SRC,))


@limit(120)
def test_a_worker_holds_no_jax_and_not_the_parents_main(loaded):
    db, _decode = loaded
    w0 = worker_blocks()
    answers = stream(db, Probe())
    assert worker_blocks() - w0 == BLOCKS
    assert {pid for _m, _s, _n, pid in answers} <= set(POOL.pids())
    for modules, main_spec, name, _pid in answers:
        assert not [m for m in modules
                    if m.split(".")[0] in ("jax", "jaxlib", "numpy")]
        assert main_spec == decode_pool.WORKER_MODULE
        assert name.startswith(decode_pool.WORKER_NAME)
        # pytest is this process's __main__; nothing of it is there
        assert "pytest" not in modules and "conftest" not in modules
        assert "ouroboros_tpu.storage.decode_pool" in modules


@limit(120)
def test_a_decoder_that_needs_jax_does_not_load_in_a_worker(loaded):
    class NeedsJax:
        def __call__(self, raw):
            return raw

        def __reduce__(self):
            return (importlib.import_module, ("jax",))

    db, _decode = loaded
    w0 = worker_blocks()
    assert stream(db, NeedsJax()) == [raw for _e, raw in db.stream()]
    assert worker_blocks() == w0


def _gone(pid: int) -> bool:
    """No such process, or one that has exited and waits to be reaped."""
    try:
        with open(f"/proc/{pid}/stat") as fh:
            return fh.read().rsplit(")", 1)[1].split()[0] == "Z"
    except OSError:
        return True


@limit(120)
@pytest.mark.parametrize("ending", ["returns", "killed"])
def test_no_worker_outlives_its_parent(chain_dir, ending):
    script = (
        "import os, sys, time\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from tools import db_analyser\n"
        "from ouroboros_tpu.storage.decode_pool import POOL\n"
        "from ouroboros_tpu.storage.stream import BlockPrefetcher\n"
        f"db, _r, decode, _c = db_analyser.load_db({chain_dir!r})\n"
        "pre = BlockPrefetcher(db, decode, window=8, depth=2).start()\n"
        "n = sum(1 for _b in pre)\n"
        "pre.close()\n"
        "print(n, *POOL.pids(), flush=True)\n"
        + ("time.sleep(600)\n" if ending == "killed" else ""))
    child = subprocess.Popen([sys.executable, "-c", script],
                             stdout=subprocess.PIPE, text=True)
    try:
        n, *pids = map(int, child.stdout.readline().split())
        assert n == BLOCKS and pids
        if ending == "killed":
            assert not any(_gone(p) for p in pids)
            child.kill()
        assert child.wait(timeout=60) == (-9 if ending == "killed" else 0)
        deadline = time.monotonic() + 30
        while not all(_gone(p) for p in pids):
            assert time.monotonic() < deadline, \
                f"workers {pids} outlived their parent"
            time.sleep(0.05)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()


# -- what a reply holds (ISSUE 39) -----------------------------------------------

def _through_a_worker(decode, raws) -> list:
    """`raws` decoded as one chunk by a worker of the process's pool."""
    lease = POOL.lease(decode)
    assert lease is not None
    try:
        lease.dispatch(list(raws))
        return lease.collect(lambda: False)
    finally:
        lease.release()


def _blake2b(data: bytes) -> bytes:
    import hashlib
    return hashlib.blake2b(data, digest_size=32).digest()


def _shelley_raw(n_txs: int, header_edit=None) -> bytes:
    from test_block_decode import block_bytes, header_of, shelley_tx
    from ouroboros_tpu.consensus.headers import ProtocolBlock
    from ouroboros_tpu.utils import cbor
    body = tuple(shelley_tx(i, rich=i % 7 == 3) for i in range(n_txs))
    if header_edit is None:
        return block_bytes(body)
    enc = ProtocolBlock(header_of(body), body).encode()
    header_edit(enc)
    return cbor.dumps(enc)


def _byron_raw() -> bytes:
    from ouroboros_tpu.consensus.headers import ProtocolBlock, make_header
    from ouroboros_tpu.eras.byron import make_byron_tx
    from ouroboros_tpu.eras.cardano import BYRON, ERA_FIELD
    body = tuple(make_byron_tx([(bytes([i]) * 32, i)],
                               [(bytes([9 - i]) * 32, 100 + i)], [],
                               [bytes([i + 1]) * 32]) for i in range(3))
    header = make_header(None, 5, body, issuer=1).with_fields(
        **{ERA_FIELD: BYRON, "byron_sig": bytes(64)})
    return ProtocolBlock(header, body).bytes


def _decoder_and_raw(kind: str):
    from ouroboros_tpu.consensus.headers import BlockDecoder
    from ouroboros_tpu.eras.cardano import CARDANO_DECODER
    from ouroboros_tpu.eras.shelley import ShelleyTx
    if kind == "byron":
        return CARDANO_DECODER, _byron_raw()
    return BlockDecoder(ShelleyTx.decode, 6), _shelley_raw(int(kind))


@limit(120)
@pytest.mark.parametrize("kind", ["0", "1", "88", "352", "byron"])
def test_a_block_from_a_worker_is_the_block_decoded_here(kind):
    from ouroboros_tpu.utils import cbor
    decode, raw = _decoder_and_raw(kind)
    w0 = worker_blocks()
    (shipped,) = _through_a_worker(decode, [raw])
    assert worker_blocks() - w0 == 1
    local = decode(raw)
    assert shipped == local and shipped.body == local.body
    assert shipped.header.hash == local.header.hash \
        == _blake2b(local.header.bytes)
    assert shipped.header.bytes == local.header.bytes
    assert raw[1:1 + len(local.header.bytes)] == local.header.bytes
    for drop in ((KES_FIELD,), ("kes_sig",), ()):
        assert shipped.header.bytes_dropping(*drop) \
            == local.header.bytes_dropping(*drop) \
            == cbor.dumps(local.header.encode(drop))
    assert [t.txid for t in shipped.body] == [t.txid for t in local.body] \
        == [_blake2b(cbor.dumps(t.body_encode())) for t in local.body]
    assert shipped.bytes == local.bytes == raw
    assert len(shipped.body) == (3 if kind == "byron" else int(kind))
    # hashed where the block was decoded, in either era; nothing is left
    # to encode
    assert all(t.txid_hashed for t in shipped.body)
    assert all(t.txid_hashed for t in local.body)
    # the header keeps its own bytes and offsets into them, no more
    assert max(len(v) for v in shipped.header._cache.values()
               if isinstance(v, bytes)) == len(local.header.bytes)


@pytest.fixture(scope="module")
def hfc_loaded(tmp_path_factory):
    """An HFC DB forged on each era's own parameters: 7 Byron blocks of 2
    transactions ending a 30-slot Byron epoch, then 5 Shelley blocks of
    3."""
    d = str(tmp_path_factory.mktemp("hfcdb"))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "db_synth.py"),
         "--out", d, "--protocol", "cardano", "--blocks", "12",
         "--byron-blocks", "7", "--byron-epoch-length", "30",
         "--byron-keys", "7", "--byron-txs-per-block", "2",
         "--txs-per-block", "3", "--pools", "2", "--f", "1/2",
         "--epoch-length", "5000", "--kes-depth", "4", "--k", "20",
         "--chunk-size", "4"],
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    db, _rules, decode, _cfg = db_analyser.load_db(d)
    return db, decode


@limit(120)
@pytest.mark.parametrize("era,at,n_txs", [("byron", 3, 2),
                                          ("shelley", 9, 3)])
def test_both_eras_of_an_hfc_db_cross_from_a_worker_with_their_ids(
        hfc_loaded, era, at, n_txs):
    """One decode for every DB: a Byron block and a Shelley block of one
    Cardano-composed DB come back from a worker as the in-thread decode
    builds them, from one walk of their bytes, every transaction carrying
    its id."""
    from ouroboros_tpu.eras.byron import ByronTx
    from ouroboros_tpu.eras.cardano import ERA_FIELD
    from ouroboros_tpu.eras.shelley import ShelleyTx
    from ouroboros_tpu.utils import cbor
    db, decode = hfc_loaded
    raws = [raw for _entry, raw in db.stream()]
    one_walk = observe.REGISTRY.get("replay.decode.one_walk_blocks")
    w0, o0 = worker_blocks(), one_walk.value
    shipped = stream(db, decode)
    assert worker_blocks() - w0 == len(raws) == 12
    assert one_walk.value - o0 == 12          # Byron blocks count too
    a, b = shipped[at], decode(raws[at])
    assert a.header.get(ERA_FIELD) == ("byron", "shelley").index(era)
    assert a == b and a.body == b.body and a.hash == b.hash
    assert a.header.bytes == b.header.bytes and a.bytes == b.bytes == raws[at]
    assert len(a.body) == n_txs
    for ta, tb in zip(a.body, b.body):
        assert type(ta) is type(tb) is (ByronTx if era == "byron"
                                        else ShelleyTx)
        assert ta.txid_hashed and tb.txid_hashed
        assert ta.txid == tb.txid == _blake2b(cbor.dumps(ta.body_encode()))
        assert pickle.loads(pickle.dumps(ta)).txid_hashed


def _seven_element_header(enc):
    enc[0].append(0)


@limit(120)
@pytest.mark.parametrize("case", ["header-not-a-6-list",
                                  "tx-of-too-few-elements"])
def test_a_malformed_item_from_a_worker_falls_back_to_re_encoding(case):
    """The item not shaped as expected comes back as `decode` built it
    and is re-encoded when asked; the block is not counted as one walk's,
    in the worker's registry or (through the reply's counts) in this
    one."""
    from ouroboros_tpu.consensus.headers import BlockDecoder
    from ouroboros_tpu.eras.shelley import ShelleyTx
    from ouroboros_tpu.utils import cbor
    one_walk = observe.REGISTRY.get("replay.decode.one_walk_blocks")
    if case == "header-not-a-6-list":
        decode = BlockDecoder(ShelleyTx.decode, 6)
        raw = _shelley_raw(3, _seven_element_header)
    else:       # a body of 8 elements is asked of 7-element transactions
        decode = BlockDecoder(ShelleyTx.decode, 8)
        raw = _shelley_raw(3)
    before = one_walk.value
    (blk,) = _through_a_worker(decode, [raw])
    assert one_walk.value == before
    good = BlockDecoder(ShelleyTx.decode, 6)
    (ok,) = _through_a_worker(good, [_shelley_raw(3)])
    assert one_walk.value == before + 1
    assert blk.body == ok.body
    if case == "header-not-a-6-list":
        assert not blk.header._cache
        assert all(t.txid_hashed for t in blk.body)
    else:
        assert set(blk.header._cache) == {"bytes", "spans"}
        assert not any(t.txid_hashed for t in blk.body)
    assert blk.header.bytes_dropping("kes_sig") \
        == cbor.dumps(blk.header.encode(("kes_sig",)))
    assert [t.txid for t in blk.body] == [t.txid for t in ok.body] \
        == [_blake2b(cbor.dumps(t.body_encode())) for t in blk.body]


@pytest.mark.parametrize("n_txs", [88, 352])
def test_a_reply_holds_no_block_bytes_and_loads_with_no_python_call(n_txs):
    """What a worker writes for a chunk: under 1.5x the chunk's bytes on
    disk (the decoded fields, the header's own bytes, a 32-byte id a
    transaction; 2.7x while the header's spans held the block's bytes
    and every transaction its body's), and `pickle.loads` of it runs no
    Python code of the package, whatever the number of transactions."""
    decode, raw = _decoder_and_raw(str(n_txs))
    raws = [raw] * 5
    blocks = decode_pool.decode_blocks(decode, raws)
    reply = pickle.dumps(("ok", (blocks, None, {})),
                         protocol=pickle.HIGHEST_PROTOCOL)
    assert len(reply) < 1.5 * sum(map(len, raws))
    tx_list = raw[1 + len(blocks[0].header.bytes):]
    assert tx_list[:256] not in reply and tx_list[-256:] not in reply
    calls = []

    def profile(frame, event, _arg):
        if event == "call":
            calls.append(frame.f_code.co_filename)
    sys.setprofile(profile)
    try:
        status, (back, _rows, _counts) = pickle.loads(reply)
    finally:
        sys.setprofile(None)
    assert [c for c in calls if "ouroboros_tpu" in c] == []
    assert len(calls) <= 2             # none a transaction, none a block
    assert status == "ok" and back == blocks
    assert [t.txid for b in back for t in b.body] \
        == [t.txid for b in blocks for t in b.body]
    # one object a transaction: no instance dict, no cache dict
    tx = back[0].body[0]
    assert not hasattr(tx, "__dict__") and not hasattr(tx, "_cache")


def test_shelley_tx_made_and_replaced_hashes_and_compares_as_before():
    import dataclasses
    from ouroboros_tpu.eras.shelley import ShelleyTx, make_shelley_tx
    from ouroboros_tpu.utils import cbor
    sk = bytes(range(32))
    tx = make_shelley_tx([(b"\x01" * 32, 0)], [(b"\x02" * 32, 7)], [], [sk])
    want = _blake2b(cbor.dumps(tx.body_encode()))
    assert tx.txid == want and len(tx.witnesses) == 1
    bare = ShelleyTx(tx.inputs, tx.outputs)
    assert not bare.txid_hashed and bare.txid == want
    assert bare.txid is bare.txid                  # hashed once, kept
    # equality and hashing: the seven fields, never the id
    same = ShelleyTx(tx.inputs, tx.outputs, witnesses=tx.witnesses)
    assert same == tx and hash(same) == hash(tx) and not (same != tx)
    assert same.with_txid(want) == tx and {tx: 1}[same.with_txid(want)] == 1
    assert bare != tx and tx != tx.encode() and tx != tuple(tx)
    assert ShelleyTx.decode(tx.encode()) == tx
    assert ShelleyTx.decode(cbor.loads(cbor.dumps(tx.encode()))).txid == want
    # replace: a witness flipped keeps the id's value, a body changed
    # has its own id, and neither touches the original
    flipped = dataclasses.replace(tx, witnesses=((b"k" * 32, b"s" * 64),))
    assert flipped != tx and flipped.txid == want
    assert flipped.inputs == tx.inputs and flipped.outputs == tx.outputs
    moved = dataclasses.replace(tx, outputs=((b"\x03" * 32, 7, ()),))
    assert moved.txid == _blake2b(cbor.dumps(moved.body_encode())) != want
    assert tx.txid == want
    assert [f.name for f in dataclasses.fields(tx)] == [
        "inputs", "outputs", "certs", "witnesses", "validity", "mint",
        "withdrawals"]
    with pytest.raises(dataclasses.FrozenInstanceError):
        tx.inputs = ()
    assert "witnesses=" in repr(tx) and repr(tx).startswith("ShelleyTx(")
    for t in (tx, bare, flipped):                  # with and without an id
        back = pickle.loads(pickle.dumps(t))
        assert back == t and back.txid == t.txid \
            and back.txid_hashed == t.txid_hashed


# -- the pipes (ISSUE 39) -----------------------------------------------------------

def _pipe_size(fd: int) -> int:
    import fcntl
    return fcntl.fcntl(fd, fcntl.F_GETPIPE_SZ)


@limit(120)
def test_reply_pipes_are_as_large_as_the_kernel_grants(loaded):
    db, decode = loaded
    stream(db, decode)                             # the pool is up
    workers = POOL._ensure()
    assert workers
    for w in workers:
        assert w.pipe_bytes == _pipe_size(w._out) >= 1 << 16
        assert _pipe_size(w._in) >= 1 << 16
        assert not os.get_blocking(w._out)
    # what is asked for is the kernel's stated limit; where it grants
    # that (a user over the pipe quota is held under it), that is it
    asked = decode_pool._pipe_max()
    r, w_ = os.pipe()
    try:
        got = decode_pool.grow_pipe(r)
        assert got == _pipe_size(r)
        assert got == asked or 1 << 16 <= got < asked
    finally:
        os.close(r)
        os.close(w_)
    if got == asked:
        assert max(w.pipe_bytes for w in workers) == asked


@limit(120)
@pytest.mark.parametrize("refusal", ["EPERM", "no-such-call"])
def test_a_refused_pipe_size_leaves_a_working_pool(monkeypatch, refusal):
    """The pipes stay as `subprocess` made them, and a reply larger than
    its pipe comes in pieces: the worker blocks in its write until the
    first piece is read, and the reads go on where they stopped."""
    import fcntl
    if refusal == "EPERM":
        real = fcntl.fcntl

        def fcntl_refusing(fd, cmd, *arg):
            if cmd == fcntl.F_SETPIPE_SZ:
                raise PermissionError(1, "Operation not permitted")
            return real(fd, cmd, *arg)
        monkeypatch.setattr(decode_pool.fcntl, "fcntl", fcntl_refusing)
    else:
        monkeypatch.delattr(decode_pool.fcntl, "F_SETPIPE_SZ")
    r, w_ = os.pipe()
    try:
        default = _pipe_size(r)
        assert decode_pool.grow_pipe(r) == (default if refusal == "EPERM"
                                            else 0)
        assert _pipe_size(r) == default
    finally:
        os.close(r)
        os.close(w_)
    decode, raw = _decoder_and_raw("88")
    pool = decode_pool.DecodePool()
    try:
        lease = pool.lease(decode)
        assert lease is not None
        reads = observe.REGISTRY.get("replay.decode.reply_reads")
        size = observe.REGISTRY.get("replay.decode.reply_bytes")
        r0, s0 = reads.value, size.value
        lease.dispatch([raw] * 8)
        blocks = lease.collect(lambda: False)
        lease.release()
        assert blocks == [decode(raw)] * 8
        assert size.value - s0 > 2 * default       # larger than its pipe
        assert reads.value - r0 >= 3               # ... so in pieces
        assert all(_pipe_size(w._out) == default for w in pool._workers)
    finally:
        pool.close()


@limit(60)
@pytest.mark.parametrize("grown", [False, True])
def test_a_frame_larger_than_its_pipe_is_read_whole(grown):
    """`write_frame` against `read_frame` / `read_exact` (a worker's end
    of both pipes, and the step-0 experiments): the writer blocks until
    the reader has made room, as often as it takes."""
    r, w = os.pipe()
    try:
        size = decode_pool.grow_pipe(r) if grown else _pipe_size(r)
        payload = os.urandom(3 * size + 12345)
        writer = threading.Thread(target=decode_pool.write_frame,
                                  args=(w, payload))
        writer.start()
        head = decode_pool.read_exact(r, 8)
        n = int.from_bytes(head, "little")
        assert n > 3 * size
        assert pickle.loads(decode_pool.read_exact(r, n)) == payload
        writer.join()
        decode_pool.write_frame(w, "small")
        assert pickle.loads(decode_pool.read_frame(r)) == "small"
        os.write(w, (100).to_bytes(8, "little") + b"cut short")
        os.close(w)
        w = None
        assert decode_pool.read_frame(r) is None   # a frame cut short
        assert decode_pool.read_exact(r, 8) is None        # end of file
    finally:
        for fd in (r, w):
            if fd is not None:
                os.close(fd)
