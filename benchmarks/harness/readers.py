"""The general reader of per-layer metrics.

A run gathers FACTS: plain numbers under a namespace and a key, such as
`["span_seconds", "stream.read"]` or `["counter", "precompute.filled_keys"]`.
Each metric's own file, `layer_metrics/<metric>.json`, holds a `reader`:

    {"num": [[namespace, key], ...],     summed
     "den": [[namespace, key], ...],     summed; left out means 1
     "scale": 1000000,                   left out means 1
     "one_minus": true}                  value = scale * (1 - num/den)

A reader that finds a fact missing, or a zero divisor, returns nothing,
and the harness leaves that metric out of the line.

Namespaces (`gather_facts` in run.py fills them; a later PR that needs
another span, counter or stream key needs no new code, only a new file):

    window          replays, blocks, proofs, windows, replay_seconds
                    (whole replays of the window)
    span_seconds    seconds inside the window, by the program's span name
    span_count      how many such spans closed inside the window
    counter         the program's registry counters, as window deltas
    stream          the stream stats of `db_analyser`'s line, summed
    setup_spans     seconds of the program's spans during set-up, by cat
    setup_events    jax.monitoring compile events during set-up, by stage
    trace           the trace reduction: busy_s, window_s, idle_s, proofs
    device          peak_bytes (max over the devices used)
"""
from __future__ import annotations

from typing import Optional


def _total(facts: dict, refs) -> Optional[float]:
    total = 0.0
    for ns, key in refs:
        v = facts.get(ns, {}).get(key)
        if v is None:
            return None
        total += v
    return total


def read(reader: dict, facts: dict) -> Optional[float]:
    num = _total(facts, reader["num"])
    if num is None:
        return None
    value = num
    if "den" in reader:
        den = _total(facts, reader["den"])
        if not den:
            return None
        value = num / den
    if reader.get("one_minus"):
        value = 1.0 - value
    return value * reader.get("scale", 1)
