"""A decode worker: `python -m ouroboros_tpu.storage.decode_worker NAME`.

The child end of storage/decode_pool.py (frames and their meaning are
in its docstring): reads a frame from stdin, answers on stdout, exits at
the end of stdin.  It cannot reach the chip: `jax` is made unimportable
before anything else is imported, so a decoder whose modules need it
fails to load and the replay decodes in-thread instead.  Whatever the
decoder prints goes to stderr; stdout carries frames only.
"""
from __future__ import annotations

import os
import pickle
import sys


def _shippable(exc: BaseException) -> BaseException:
    """`exc` if the parent can be handed it as it is, else its type's
    name and message in a RuntimeError."""
    try:
        pickle.loads(pickle.dumps(exc))
        return exc
    except Exception:
        return RuntimeError(f"{type(exc).__name__}: {exc}")


def main() -> int:
    sys.modules["jax"] = None          # `import jax` raises ImportError here
    from ouroboros_tpu.observe import metrics, spans
    from ouroboros_tpu.storage.decode_pool import (
        decode_blocks, read_frame, write_frame,
    )
    inp = sys.stdin.fileno()
    out = os.dup(sys.stdout.fileno())
    os.dup2(sys.stderr.fileno(), sys.stdout.fileno())
    rec = spans.RECORDER

    def counters() -> dict:
        return {i.name: i.value for i in metrics.REGISTRY.instruments()
                if i.kind == "counter"}

    decode = None
    while True:
        frame = read_frame(inp)
        if frame is None:              # the parent closed us, or is gone
            return 0
        kind, payload = pickle.loads(frame)
        try:
            if kind == "load":
                decode = pickle.loads(payload)
                reply = ("ok", None)
            else:
                timed, raws = payload
                c0 = counters()
                rec.enabled = timed
                try:
                    blocks = decode_blocks(decode, raws)
                finally:
                    rec.enabled = False
                rows = [(sp.name, sp.cat, sp.t0, sp.t1)
                        for root in rec.drain() for sp in root.walk()] \
                    if timed else None
                counts = {k: v - c0.get(k, 0)
                          for k, v in counters().items() if v != c0.get(k, 0)}
                reply = ("ok", (blocks, rows, counts))
        except Exception as e:         # the parent raises it in the replay
            rec.drain()
            reply = ("err", _shippable(e))
        write_frame(out, reply)


if __name__ == "__main__":
    sys.exit(main())
