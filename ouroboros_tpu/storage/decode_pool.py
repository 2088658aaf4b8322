"""Decode worker processes: a chunk's raw blocks go out, the built
blocks come back.

The streamed replay's three threads (prefetcher, producer, caller)
share one interpreter lock, and CBOR decode is pure Python: decoded on
the prefetch thread, a chain's decode is seconds that the host pass and
the packers cannot overlap.  `BlockPrefetcher` (storage/stream.py)
therefore hands each chunk to one of a few long-lived worker processes
and unpickles the reply, which holds the lock for a fifth of the time.

A worker is a plain child, `python -m ouroboros_tpu.storage.decode_worker`,
spoken to over its stdin and stdout in frames of an 8-byte length and
a pickle.  It is never forked from this process (which may hold the TPU
runtime) and never imports this process's `__main__` (`multiprocessing`'s
spawn and forkserver do); it cannot import `jax`.  It exits at the end
of its stdin: when the pool closes it, and when this process ends,
however it ends.

One pool a process (`POOL`), started by the first replay that can use
it and lent whole to one replay at a time (`lease`); a second replay at
the same moment decodes on its own prefetch thread.  Whether a decoder
can be used here at all is asked of the input, never of an option: it
has to pickle in this process and load in a worker (`lease` returns
None where it does not: a closure, a lambda, a stateful decoder, one
whose module a worker cannot import).

Frames, parent to worker:
    ("load", pickled decoder)      -> ("ok", None) | ("err", exception)
    ("decode", (timed, [raw...]))  -> ("ok", (blocks, spans, counts))
                                      | ("err", exception)
`spans` are the worker's own span rows `(name, cat, t0, t1)` on
`time.perf_counter()`, which on Linux is the system-wide monotonic
clock this process's recorder reads too; None unless `timed`.  `counts`
holds what the worker's registry counters rose by while it decoded the
chunk (`replay.decode.one_walk_blocks`).

What a reply costs the thread that takes it in is what it holds and how
many system calls read it, because each call gives the interpreter lock
up and has to get it back from a producer that wants it (ISSUE 39's
step 0, PERF.md section 6: a reply read in 16 calls beside a spinning
thread took 19.7 ms a block, in one call 1.7).  So a frame is written
in one `write` and a reply that is waiting is read, head and body, by
ONE `readv` into the worker's buffer (`_Worker.poll_reply`; the read end
does not block, and `select` is only for a reply that is not there yet);
and both of a worker's pipes are grown to the largest size the kernel
grants (`grow_pipe`: 1 MiB on the chip's host against the default 64
KiB), so a whole reply fits and the worker that wrote it is free for
its next chunk.  What the blocks in it hold is the decoder's business
(`ProtocolBlock.from_bytes`: the header's own bytes, a transaction as
one flat row with its id).
"""
from __future__ import annotations

import fcntl
import os
import pickle
import select
import subprocess
import sys
import threading
from collections import deque
from typing import Any, Callable, List, Optional

from ..observe import metrics as _metrics
from ..observe import spans as _spans

#: most workers a process starts.  Step 0 of ISSUE 32 (the chip's host,
#: 13 cores, 512 full blocks; PERF.md section 6): 2 / 4 / 6 / 8 / 10
#: workers deliver the chain in 2.29 / 1.26 / 0.98 / 0.81 / 0.73 s
#: against 2.6-3.7 s in-thread, and the collecting thread's own CPU was
#: 0.40-0.52 s throughout (0.8-1.0 ms a block: the unpickling of the
#: replies as they were then).  At 8 the workers and that thread were
#: about level; two more bought 0.08 s a replay and leave the 13-core
#: host's runtime threads no core.  Since ISSUE 39 a full block unpickles
#: in ~0.45 ms (its step 0, same host), so the workers' ~0.7-0.8 ms a
#: block (5.5 ms over 8) is the floor of a full-body chain's decode
WORKER_CAP = 8
#: the replay's own threads (prefetcher, producer, caller) keep a core each
REPLAY_THREADS = 3
#: seconds between two looks at a worker this process is waiting for
LIVENESS_S = 0.5

WORKER_MODULE = "ouroboros_tpu.storage.decode_worker"
WORKER_NAME = "ouro-decode-worker"
_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the pipe size asked for where /proc/sys/fs/pipe-max-size cannot be
#: read: Linux's default limit for an unprivileged process
PIPE_BYTES = 1 << 20

# blocks that came back from a worker; whole microseconds the prefetch
# thread waited for a reply that was not there yet (scheduling: unstable)
_WORKER_BLOCKS = _metrics.counter("replay.decode.worker_blocks")
_WORKER_WAIT_US = _metrics.counter("replay.decode.worker_wait_us",
                                   stable=False)
# the replies of decoded chunks, added to once a reply: the bytes of
# their pickles, and the read calls that took them in (a call that found
# nothing there counts; how many depends on what had arrived: unstable)
_REPLY_BYTES = _metrics.counter("replay.decode.reply_bytes")
_REPLY_READS = _metrics.counter("replay.decode.reply_reads", stable=False)


class DecodeWorkerDied(RuntimeError):
    """A worker process ended (or closed its pipe) while a replay was
    waiting for it; the message carries its exit status."""


def decode_blocks(decode: Callable[[bytes], Any], raws) -> list:
    """The decode of one chunk: what a worker runs, and what the
    prefetch thread runs for a decoder that does not ship."""
    return [decode(raw) for raw in raws]


def worker_count() -> int:
    """From the cores this process may run on, less the replay's own
    threads; at least one (a lone worker still takes the decode off the
    interpreter lock), at most `WORKER_CAP`."""
    return max(1, min(WORKER_CAP,
                      len(os.sched_getaffinity(0)) - REPLAY_THREADS))


# -- the pipes -----------------------------------------------------------------
def _pipe_max() -> int:
    try:
        with open("/proc/sys/fs/pipe-max-size") as f:
            return int(f.read())
    except (OSError, ValueError):
        return PIPE_BYTES


def grow_pipe(fd: int) -> int:
    """Ask the kernel for the largest pipe it grants on `fd`: the limit
    it states (`_pipe_max`), halved until it accepts.  Returns the
    pipe's size after that, 0 where it cannot be told.  A refusal (no
    such call on this platform, EPERM, a pipe over the user's quota)
    leaves the pipe as it was: a smaller pipe costs reads, nothing else."""
    set_size = getattr(fcntl, "F_SETPIPE_SZ", None)
    get_size = getattr(fcntl, "F_GETPIPE_SZ", None)
    if set_size is None or get_size is None:
        return 0
    try:
        have = fcntl.fcntl(fd, get_size)
        want = _pipe_max()
        while want > have:
            try:
                return fcntl.fcntl(fd, set_size, want)
            except OSError:
                want //= 2
        return have
    except OSError:
        return 0


# -- framing (both ends) -------------------------------------------------------
def write_frame(fd: int, obj: Any) -> None:
    """Head and pickle in one `write` where the pipe has the room."""
    data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    rest = memoryview(len(data).to_bytes(8, "little") + data)
    while rest:
        rest = rest[os.write(fd, rest):]


def read_exact(fd: int, n: int) -> Optional[bytearray]:
    """`n` bytes, or None at end of file (a frame cut short is one)."""
    buf = bytearray(n)
    view, got = memoryview(buf), 0
    while got < n:
        k = os.readv(fd, [view[got:]])
        if not k:
            return None
        got += k
    return buf


def read_frame(fd: int) -> Optional[bytearray]:
    """The next frame's pickle, still packed; None at end of file."""
    head = read_exact(fd, 8)
    if head is None:
        return None
    return read_exact(fd, int.from_bytes(head, "little"))


def _never() -> bool:
    return False


# -- one worker, seen from the parent ------------------------------------------
class _Worker:
    def __init__(self, index: int):
        self.name = f"{WORKER_NAME}-{index}"
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (_PKG_ROOT, env.get("PYTHONPATH")) if p)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", WORKER_MODULE, self.name],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, bufsize=0,
            env=env)
        self._in = self.proc.stdin.fileno()
        self._out = self.proc.stdout.fileno()
        grow_pipe(self._in)
        #: bytes the reply pipe holds (0: not told), the buffer's first size
        self.pipe_bytes = grow_pipe(self._out)
        os.set_blocking(self._out, False)
        # the reply being read: its buffer (8-byte head, then the
        # pickle), the bytes of it in hand, and the read calls made
        # since the chunk went out
        self._buf = bytearray(8 + max(self.pipe_bytes, 1 << 16))
        self._got = 0
        self.reads = 0
        self.closed = False

    def send(self, kind: str, payload: Any) -> None:
        try:
            write_frame(self._in, (kind, payload))
        except OSError:
            raise self._died() from None

    def wait_reply(self, stopped: Callable[[], bool]) -> bool:
        """Block until the worker's reply starts to arrive; False when
        `stopped()` turned true first.  Looks at the worker every
        `LIVENESS_S`: one that has died raises."""
        while not select.select([self._out], [], [], LIVENESS_S)[0]:
            if self.proc.poll() is not None:
                raise self._died()
            if stopped():
                return False
        return True

    def poll_reply(self) -> Optional[memoryview]:
        """Read what has arrived of the reply, without blocking: its
        pickle (a view of this worker's buffer, good until the next
        call) once it is whole, None while more is to come.  A reply
        that was waiting whole costs one `readv`.  A worker answers one
        frame at a time, so whatever the pipe holds is this reply's."""
        buf, got = self._buf, self._got
        need = 8 + int.from_bytes(buf[:8], "little") if got >= 8 else None
        try:
            while need is None or got < need:
                if need is not None and need > len(buf):
                    buf.extend(bytes(need - len(buf)))
                self.reads += 1
                with memoryview(buf) as view:
                    k = os.readv(self._out, [view[got:need]])
                if not k:
                    raise self._died()
                got += k
                if need is None and got >= 8:
                    need = 8 + int.from_bytes(buf[:8], "little")
        except BlockingIOError:
            self._got = got
            return None
        self._got = 0
        if got > need:
            self.close()
            raise DecodeWorkerDied(
                f"decode worker {self.name} sent {got - need} bytes more "
                f"than its reply's head announced")
        return memoryview(buf)[8:need]

    def read_reply(self) -> memoryview:
        """The whole reply's pickle, waited for (see `poll_reply`)."""
        while True:
            data = self.poll_reply()
            if data is not None:
                return data
            self.wait_reply(_never)

    def _died(self) -> DecodeWorkerDied:
        self.close()
        return DecodeWorkerDied(
            f"decode worker {self.name} (pid {self.proc.pid}) ended with "
            f"exit status {self.proc.returncode} while a replay waited "
            f"for it")

    @property
    def alive(self) -> bool:
        return not self.closed and self.proc.poll() is None

    def close(self) -> None:
        """End of its stdin is the worker's signal to exit; one that
        does not within a second is killed."""
        self.closed = True
        for f in (self.proc.stdin, self.proc.stdout):
            try:
                f.close()
            except OSError:
                pass
        try:
            self.proc.wait(timeout=1.0)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()


class Lease:
    """The pool's workers, all loaded with one decoder, for the length
    of one replay.  Used from one thread (the prefetcher's).

    `dispatch` hands a chunk to an idle worker; `collect` returns the
    OLDEST chunk's blocks, so chunks come back in the order they went
    out whichever worker finishes first.  A worker holds one chunk at a
    time (its reply waits in its pipe until collected), so nothing is
    ever written to a worker that is not reading."""

    def __init__(self, pool: "DecodePool", workers: List[_Worker]):
        self._pool = pool
        self._idle = list(workers)
        self._flight: deque = deque()      # (worker, n_blocks), oldest first

    @property
    def idle(self) -> int:
        return len(self._idle)

    @property
    def blocks_in_flight(self) -> int:
        return sum(n for _w, n in self._flight)

    def dispatch(self, raws: list) -> None:
        w = self._idle.pop()
        self._flight.append((w, len(raws)))
        w.reads = 0
        w.send("decode", (_spans.RECORDER.enabled, raws))

    def collect(self, stopped: Callable[[], bool],
                into: Optional[_spans.Span] = None) -> Optional[list]:
        """The oldest chunk in flight as blocks; None when `stopped()`
        came true while waiting.  A decode error of the worker's is
        raised here as the worker raised it.  With `into` (the caller's
        open `stream.decode` span) the worker's stage spans become its
        children; reading the reply and unpickling it is timed as
        `decode.unpack` (one span where the reply was waiting whole,
        one more for every look that found it not all there yet)."""
        w, n = self._flight[0]
        reply = None
        while reply is None:
            # read first: a reply that is waiting costs this one call,
            # and `select` is for one that is not there yet
            with _spans.span("decode.unpack", cat="disk", cpu=True):
                data = w.poll_reply()
                if data is not None:
                    _REPLY_BYTES.inc(len(data))
                    reply = pickle.loads(data)
                    data.release()
            if reply is None:
                t0 = _spans.monotonic_now()
                ready = w.wait_reply(stopped)
                _WORKER_WAIT_US.inc(
                    int((_spans.monotonic_now() - t0) * 1e6))
                if not ready:
                    return None
        _REPLY_READS.inc(w.reads)
        status, body = reply
        self._flight.popleft()
        self._idle.append(w)
        if status != "ok":
            raise body
        blocks, rows, counts = body
        if into is not None and rows:
            _spans.adopt(into, rows, thread=w.name)
        for name, d in counts.items():
            inst = _metrics.REGISTRY.get(name)
            if inst is not None:
                inst.inc(d)
        _WORKER_BLOCKS.inc(n)
        return blocks

    def release(self) -> None:
        """Give the workers back, each idle again: the reply of every
        chunk still in flight is read and dropped (a replay closed
        mid-stream), and a worker that cannot give one is closed."""
        for w, _n in self._flight:
            try:
                if not w.closed and w.wait_reply(_never):
                    w.read_reply()
            except DecodeWorkerDied:
                pass
        self._flight.clear()
        self._idle = []
        self._pool._release()


class DecodePool:
    """The process's decode workers (see the module docstring)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._workers: List[_Worker] = []
        self._leased = False
        self._started = 0

    def lease(self, decode: Callable[[bytes], Any]) -> Optional[Lease]:
        """All workers, loaded with `decode`; None when `decode` does
        not ship or another replay holds the pool."""
        try:
            shipped = pickle.dumps(decode, protocol=pickle.HIGHEST_PROTOCOL)
        except Exception:          # whatever a decoder's pickling raises
            return None
        with self._lock:
            if self._leased:
                return None
            self._leased = True
        try:
            workers = self._ensure()
            for w in workers:          # all first: they import side by side
                w.send("load", shipped)
            replies = []
            for w in workers:
                w.wait_reply(_never)
                replies.append(pickle.loads(w.read_reply()))
        except BaseException:
            self.close()               # replies unread: start afresh
            self._release()
            raise
        if any(status != "ok" for status, _body in replies):
            self._release()
            return None
        return Lease(self, workers)

    def _ensure(self) -> List[_Worker]:
        self._workers = [w for w in self._workers if w.alive]
        while len(self._workers) < worker_count():
            self._workers.append(_Worker(self._started))
            self._started += 1
        return list(self._workers)

    def _release(self) -> None:
        with self._lock:
            self._leased = False

    def pids(self) -> List[int]:
        return [w.proc.pid for w in self._workers if w.alive]

    def close(self) -> None:
        """Stop every worker (tests; a process that ends needs no call:
        the workers see the end of their stdin)."""
        with self._lock:
            workers, self._workers = self._workers, []
        for w in workers:
            w.close()


#: the one pool of this process
POOL = DecodePool()
