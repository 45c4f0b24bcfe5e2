"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/readings.py --workload <cell> --seeds 12 --control-seeds 3 \
        --seconds <s> [--first-seed n] [--out readings_<cell>.jsonl]

In one process (set-up is long, and the compile cache is shared): for
each of ``--seeds`` seeds a whole run of the cell as ``bench/run.py``
makes it, then for each of ``--control-seeds`` seeds the same run with
the configuration's verifier replaced by the program's own
lower-precision path (``w4a8``: int4 weights, the step below the int8
weights the configuration states).  Each run's compared numbers are
printed and written as one JSON line.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CONTROL_VERIFIER = "w4a8"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from bench.harness import load_cell
    from bench.run import check_devices, run_once
    from repro.launch.compile_cache import enable_compile_cache
    cell = load_cell(args.workload)
    devices = check_devices(cell.chips)
    enable_compile_cache()
    out = Path(args.out or f"readings_{args.workload}.jsonl")
    out.parent.mkdir(parents=True, exist_ok=True)
    plan = ([("program", None)] * args.seeds
            + [("control", CONTROL_VERIFIER)] * args.control_seeds)
    with out.open("a") as f:
        for i, (kind, verifier) in enumerate(plan):
            seed = args.first_seed + 7919 * i
            t0 = time.perf_counter()
            try:
                res = run_once(cell, seed, args.seconds, False, devices,
                               t_start=t0, verifier=verifier)
                row = {"kind": kind, "seed": seed, "checks": res["checks"],
                       "metrics": res["metrics"],
                       "memory_peak_bytes":
                           res["device"]["memory_peak_bytes"]}
            except Exception as exc:  # noqa: BLE001 — a crash is a reading
                row = {"kind": kind, "seed": seed,
                       "error": f"{type(exc).__name__}: {exc}"}
            row["seconds"] = time.perf_counter() - t0
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
