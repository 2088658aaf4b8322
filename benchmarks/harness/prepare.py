#!/usr/bin/env python
"""Child process: forge one seed's chain and compute the plain
reference's verdict on it.  Never touches JAX (the parent holds the
chip); `cache.py` starts it and reads what it leaves.

    python prepare.py <dir>        <dir>/spec.json says what to make

Leaves `<dir>/chain/` (then `chain.ok`), and last `verdict.json`:
`{state_hash, blocks, proofs, tamper_stop, backend, secs}`.

The reference is the program's `cpp` host backend (OpenSSL where there
is no g++), block by block, through the same `analysis_validate`; the
tampered chain's stop is read by re-applying the accepted prefix
without crypto and handing the tampered last block to that backend.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, os.path.dirname(HERE))

from harness import chain as ch   # noqa: E402


def synth(out_dir: str, spec: dict) -> None:
    args = ["--out", out_dir, "--blocks", str(spec["blocks"]),
            "--seed", str(spec["seed"])]
    for k, v in spec["synth"].items():
        args += ["--" + k, str(v)]
    subprocess.run([sys.executable,
                    os.path.join(ch.ROOT, "tools", "db_synth.py"), *args],
                   check=True, stdout=subprocess.DEVNULL,
                   stderr=subprocess.DEVNULL)


def reference(chain_dir: str, spec: dict) -> dict:
    dba, ctx = ch.open_chain(chain_dir)
    db, rules, decode, _cfg, _dir = ctx
    name = "cpp" if shutil.which("g++") else "openssl"
    cpu = dba.make_backend(name)
    ch.clear_caches()
    ref = ch.validate(dba, ctx, cpu, spec["validate_mode"],
                      spec["window_blocks"], 0)
    blocks = [decode(raw) for _entry, raw in db.stream()]
    ext = rules.initial_state()
    for b in blocks[:-1]:
        ext = rules.tick_then_reapply(ext, b)
    stop = ch.probe_stop(rules, blocks[-1:], spec["tamper"], cpu,
                         spec["window_blocks"], state=ext,
                         offset=len(blocks) - 1)
    return {"state_hash": ref["state_hash"], "blocks": ref["blocks"],
            "proofs": ref["proofs"], "tamper_stop": stop, "backend": name}


def main() -> int:
    d = sys.argv[1]
    with open(os.path.join(d, "spec.json")) as fh:
        spec = json.load(fh)
    t0 = time.perf_counter()
    chain_dir = os.path.join(d, "chain")
    synth(chain_dir, spec)
    t1 = time.perf_counter()
    open(os.path.join(d, "chain.ok"), "w").close()
    verdict = reference(chain_dir, spec)
    verdict["secs"] = {"synth": round(t1 - t0, 2),
                       "reference": round(time.perf_counter() - t1, 2)}
    tmp = os.path.join(d, "verdict.json.tmp")
    with open(tmp, "w") as fh:
        json.dump(verdict, fh)
    os.replace(tmp, os.path.join(d, "verdict.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
