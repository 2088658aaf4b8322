#!/usr/bin/env python
"""Step 0 of ISSUE 39, host only: what a decode worker's reply costs the
thread that takes it in, by what the reply holds and how it is read.

For one chunk of the `sync-witness` chain (full blocks, 352 tx) and one
of the `sync-longchain` chain (quarter blocks, 88 tx), a child process
decodes the chunk with the repo's decoder, turns the blocks into each
candidate form and pickles it once; then, REPS times a form, the parent
asks for it, lets it arrive, and times reading and unpickling it (wall
and this thread's CPU), alone and beside a second Python thread that
spins as the replay's producer does.  One JSON line a row, appended to
`chiprun_out/decode_reply_step0.jsonl`:

    a_today        blocks as `ProtocolBlock.from_bytes` builds them today
                   (the header's spans hold the block's bytes, every
                   transaction a dataclass with `_cache["body_bytes"]`);
                   the pipe as `subprocess` makes it, read as
                   `decode_pool.read_frame` reads (select, head, body)
    b_pipe         the same reply; the pipe at /proc/sys/fs/pipe-max-size
                   (F_SETPIPE_SZ), head and body from one read into one
                   buffer, `select` only for a reply not there yet
    c_header       b, and the header keeps its own bytes only (spans
                   rebased onto them)
    d_tuple        c, and a transaction is a tuple-backed class of its
                   seven fields and its id, hashed in the child
    e_rows         c, and a block's body is bare tuples (the cost of
                   making transactions from them later is not in the row)
    f_dataclass    c, and a transaction is a dataclass whose id is a
                   plain field (no second dict)
    g_socket       d over an AF_UNIX socket pair with the largest buffers
                   the kernel grants (SO_SNDBUF / SO_RCVBUF), read the
                   same way

The pipe's size is asked of the kernel: /proc/sys/fs/pipe-max-size where
the file exists, else 1 MiB (Linux's default limit), halved until
F_SETPIPE_SZ accepts it; the `host` row says what was granted.

Each row: reply bytes a block, reads a reply, wall microseconds a block
(median and mean over REPS), thread-CPU microseconds a block (mean: the
chip host's thread clock ticks every 10 ms, so one reply reads 0 or
10,000), and the child's microseconds a block to pickle the form.  A last row a chain (`worker`) times the
child's decode of the chunk as today and with every id hashed there.

    chiprun --timeout 600 -- python experiments/decode_reply_step0.py

It ran BEFORE the program changed (commit 6d99c09) and builds its forms
from the blocks as that program made them (`_cache["spans"]` holding the
block's bytes, `_cache["body_bytes"]` a transaction): kept as the record
of what was measured (PERF.md section 6, PR 39); to run it again, run it
in a checkout of that commit.
"""
import argparse
import fcntl
import gc
import hashlib
import json
import os
import pickle
import select
import socket
import statistics
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

OUT = os.path.join(REPO, "chiprun_out", "decode_reply_step0.jsonl")
F_SETPIPE_SZ, F_GETPIPE_SZ = 1031, 1032
CHAINS = {"sync-witness": ("shelley-sync-1chip", "bodies-full64k"),
          "sync-longchain": ("shelley-sync-1chip-8w", "bodies-quarter16k")}
FORMS = ("a_today", "b_pipe", "c_header", "d_tuple", "e_rows",
         "f_dataclass", "g_socket")
_NEW = tuple.__new__


# -- the candidate forms -------------------------------------------------------
class TupleTx(tuple):
    """(d): inputs, outputs, certs, witnesses, validity, mint,
    withdrawals, txid."""
    __slots__ = ()

    def __reduce__(self):
        return (_NEW, (TupleTx, tuple(self)))


@dataclass(frozen=True)
class FieldTx:
    """(f): today's dataclass with the id a field and no `_cache`."""
    inputs: tuple
    outputs: tuple
    certs: tuple = ()
    witnesses: tuple = ()
    validity: tuple = ()
    mint: tuple = ()
    withdrawals: tuple = ()
    id_: bytes = None


def _row(tx) -> tuple:
    return (tx.inputs, tx.outputs, tx.certs, tx.witnesses, tx.validity,
            tx.mint, tx.withdrawals,
            hashlib.blake2b(tx._cache["body_bytes"],
                            digest_size=32).digest())


def _slim_header(h):
    """The header with its own bytes in the place of the block's."""
    from ouroboros_tpu.consensus.headers import ProtocolHeader
    raw, helems, fpairs = h._cache["spans"]
    own = h._cache["bytes"]
    off = helems[0][0] - 1
    assert raw[off:off + len(own)] == own
    slim = ProtocolHeader(h.slot, h.block_no, h.prev_hash, h.body_hash,
                          h.issuer, h.fields)
    slim._cache["bytes"] = own
    slim._cache["spans"] = (
        own, [(a - off, b - off) for a, b in helems],
        [(k, (a - off, b - off)) for k, (a, b) in fpairs])
    assert slim.bytes_dropping("kes_sig") == h.bytes_dropping("kes_sig")
    return slim


def as_form(form: str, blocks: list):
    from ouroboros_tpu.consensus.headers import ProtocolBlock
    if form in ("a_today", "b_pipe"):
        return blocks
    out = []
    for b in blocks:
        h = _slim_header(b.header)
        if form == "c_header":
            out.append(ProtocolBlock(h, b.body))
        elif form in ("d_tuple", "g_socket"):
            out.append(ProtocolBlock(
                h, tuple(_NEW(TupleTx, _row(t)) for t in b.body)))
        elif form == "e_rows":
            out.append((h, tuple(_row(t) for t in b.body)))
        else:
            out.append(ProtocolBlock(
                h, tuple(FieldTx(*_row(t)) for t in b.body)))
    return out


# -- the child -----------------------------------------------------------------
def child(chain_dir: str) -> int:
    from ouroboros_tpu.storage.decode_pool import (
        decode_blocks, read_frame, write_frame,
    )
    from tools import db_analyser
    inp = sys.stdin.fileno()
    out = os.dup(sys.stdout.fileno())
    os.dup2(sys.stderr.fileno(), sys.stdout.fileno())
    db, _rules, decode, _cfg = db_analyser.load_db(chain_dir)
    raws = max((db.chunk_blocks(n) for n in db.chunk_numbers()), key=len)
    raws = [raw for _entry, raw in raws]

    def best(fn, reps=9):
        got = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            got.append(time.perf_counter() - t0)
        return min(got) / len(raws) * 1e6

    def decode_and_hash():
        for b in decode_blocks(decode, raws):
            for t in b.body:
                hashlib.blake2b(t._cache["body_bytes"],
                                digest_size=32).digest()

    replies, facts = {}, {}
    for form in FORMS:
        body = ("ok", (as_form(form, decode_blocks(decode, raws)), None, {}))
        data = pickle.dumps(body, protocol=pickle.HIGHEST_PROTOCOL)
        replies[form] = len(data).to_bytes(8, "little") + data
        facts[form] = {
            "reply_bytes": len(data),
            "dumps_us_per_block": best(lambda: pickle.dumps(
                body, protocol=pickle.HIGHEST_PROTOCOL))}
    facts["worker"] = {
        "blocks": len(raws), "raw_bytes": sum(map(len, raws)),
        "txs": sum(len(b.body) for b in decode_blocks(decode, raws)),
        "decode_us_per_block": best(lambda: decode_blocks(decode, raws)),
        "decode_and_hash_us_per_block": best(decode_and_hash)}
    write_frame(out, facts)
    while True:
        frame = read_frame(inp)
        if frame is None:
            return 0
        view = memoryview(replies[pickle.loads(frame)])
        while view:
            view = view[os.write(out, view):]


# -- the parent ----------------------------------------------------------------
def read_today(fd: int):
    """As `Lease.collect` does today: select, 8-byte head, body."""
    from ouroboros_tpu.storage.decode_pool import read_exact
    reads = [0]
    readv = os.readv

    def counting(*a):
        reads[0] += 1
        return readv(*a)
    select.select([fd], [], [], 0.5)
    os.readv = counting
    try:
        head = read_exact(fd, 8)
        data = read_exact(fd, int.from_bytes(head, "little"))
    finally:
        os.readv = readv
    return data, reads[0] + 1          # the select gives the lock up too


class OneBuffer:
    """Head and body from one read into one buffer (the fd does not
    block); `select` only when nothing is there."""

    def __init__(self, fd: int):
        self.fd = fd
        os.set_blocking(fd, False)
        self.buf = bytearray(1 << 20)

    def read(self):
        view, got, need, calls = memoryview(self.buf), 0, None, 0
        while need is None or got < need:
            try:
                calls += 1
                k = os.readv(self.fd, [view[got:] if need is None
                                       else view[got:need]])
            except BlockingIOError:
                calls += 1
                select.select([self.fd], [], [], 0.5)
                continue
            if not k:
                raise EOFError
            got += k
            if need is None and got >= 8:
                need = 8 + int.from_bytes(view[:8], "little")
                if need > len(self.buf):
                    self.buf.extend(bytes(need - len(self.buf)))
                    view = memoryview(self.buf)
        return view[8:need], calls


def grow_pipe(fd: int) -> int:
    """Ask for the largest pipe the kernel grants; what it then is."""
    try:
        with open("/proc/sys/fs/pipe-max-size") as f:
            want = int(f.read())
    except (OSError, ValueError):
        want = 1 << 20
    try:
        have = fcntl.fcntl(fd, F_GETPIPE_SZ)
    except OSError:
        return -1
    while want > have:
        try:
            fcntl.fcntl(fd, F_SETPIPE_SZ, want)
            break
        except OSError:
            want //= 2
    return fcntl.fcntl(fd, F_GETPIPE_SZ)


def spin(stop: threading.Event) -> None:
    x = 0
    while not stop.is_set():
        for _ in range(1000):
            x += 1


def measure(chain: str, chain_dir: str, reps: int) -> None:
    from ouroboros_tpu.storage.decode_pool import read_frame, write_frame
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO, env.get("PYTHONPATH")) if p)
    procs, outs, sizes = {}, {}, {}
    cmd = [sys.executable, os.path.abspath(__file__), "--child", chain_dir]
    for pipe in ("default", "large", "socket"):
        if pipe == "socket":
            ours, theirs = socket.socketpair()
            for s in (ours, theirs):
                for opt in (socket.SO_SNDBUF, socket.SO_RCVBUF):
                    s.setsockopt(socket.SOL_SOCKET, opt, 1 << 21)
            sizes[pipe] = theirs.getsockopt(socket.SOL_SOCKET,
                                            socket.SO_SNDBUF)
            p = subprocess.Popen(cmd, stdin=subprocess.PIPE, stdout=theirs,
                                 bufsize=0, env=env)
            theirs.close()
            outs[pipe] = ours.detach()
        else:
            p = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                 stdout=subprocess.PIPE, bufsize=0, env=env)
            outs[pipe] = p.stdout.fileno()
            sizes[pipe] = grow_pipe(outs[pipe]) if pipe == "large" \
                else fcntl.fcntl(outs[pipe], F_GETPIPE_SZ)
        procs[pipe] = p
    facts = [pickle.loads(read_frame(outs[k])) for k in procs][0]
    readers = {k: OneBuffer(outs[k]) for k in ("large", "socket")}
    n = facts["worker"]["blocks"]
    for beside in (False, True):
        stop = threading.Event()
        if beside:
            threading.Thread(target=spin, args=(stop,), daemon=True).start()
        for form in FORMS:
            today = form == "a_today"
            via = "default" if today else \
                "socket" if form == "g_socket" else "large"
            p = procs[via]
            wall, cpu, reads = [], [], []
            gc.collect()
            gc.freeze()
            for _ in range(reps):
                write_frame(p.stdin.fileno(), form)
                time.sleep(0.004)          # the reply is waiting
                t0, c0 = time.perf_counter(), time.thread_time()
                data, calls = (read_today(outs[via]) if today
                               else readers[via].read())
                status, body = pickle.loads(data)
                wall.append(time.perf_counter() - t0)
                cpu.append(time.thread_time() - c0)
                reads.append(calls)
                gc.freeze()                # as the replay does a chunk
                assert status == "ok" and len(body[0]) == n
                del data, body
            gc.unfreeze()
            emit({"chain": chain, "form": form,
                  "beside_a_spinning_thread": beside,
                  "pipe_bytes": sizes[via],
                  "blocks_a_reply": n,
                  "reply_kb_per_block": facts[form]["reply_bytes"] / n / 1e3,
                  "calls_a_reply": statistics.median(reads),
                  "wall_us_per_block": statistics.median(wall) / n * 1e6,
                  "wall_us_per_block_mean": statistics.fmean(wall) / n * 1e6,
                  "cpu_us_per_block": statistics.fmean(cpu) / n * 1e6,
                  "child_dumps_us_per_block":
                      facts[form]["dumps_us_per_block"]})
        stop.set()
    emit({"chain": chain, "form": "worker", **facts["worker"]})
    for p in procs.values():
        p.stdin.close()
        p.wait(timeout=5)


def emit(row: dict) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def forge(cell: str, seed: int, blocks: int) -> str:
    """`blocks` blocks of the chain the benchmark's `cell` forges."""
    config, traffic = CHAINS[cell]
    bench = os.path.join(REPO, "benchmarks")
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "traffic", traffic + ".json")) as f:
        synth = {**cfg["synth"], **json.load(f)["synth"]}
    out = tempfile.mkdtemp(prefix=f"step0-{cell}-")
    args = ["--out", out, "--blocks", str(blocks), "--seed", str(seed)]
    for k, v in synth.items():
        args += ["--" + k, str(v)]
    subprocess.run([sys.executable,
                    os.path.join(REPO, "tools", "db_synth.py"), *args],
                   check=True, stdout=subprocess.DEVNULL)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child")
    ap.add_argument("--seed", type=int, default=39)
    ap.add_argument("--blocks", type=int, default=24)
    ap.add_argument("--reps", type=int, default=60)
    a = ap.parse_args()
    if a.child:
        return child(a.child)
    emit({"row": "host", "cores": len(os.sched_getaffinity(0)),
          "python": sys.version.split()[0], "kernel": os.uname().release,
          "pipe_max_size_file": os.path.exists("/proc/sys/fs/pipe-max-size"),
          "switch_interval_s": sys.getswitchinterval()})
    for cell in CHAINS:
        measure(cell, forge(cell, a.seed, a.blocks), a.reps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
