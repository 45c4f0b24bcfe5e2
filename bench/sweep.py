"""Find an open-loop cell's knee once, on the chip: the highest arrival
rate the system sustains with its traffic mix.

    python3 bench/sweep.py --workload <cell> --rates 2,4,8 --seconds 20 [--seed n]

One process, one set-up: the cell's loop serves a window of the mix at
each rate in turn (lengths and burstiness as the mix states, only the
rate replaced).  For each rate it prints the requests due, those
admitted and finished inside the window, the backlog left when it
closes, and the tails of queue wait and time to first token (a request
with no first token yet counts +inf).  The knee is the highest rate whose
backlog does not grow with the window; the cell's mix then offers about
four fifths of it.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2_900_000_001)
    args = ap.parse_args()
    from bench import harness as H, stats
    from bench.run import check_devices
    from bench.traffic import Traffic, generate
    from bench.weights import make_weights
    from repro.launch.compile_cache import enable_compile_cache
    cell = H.load_cell(args.workload)
    check_devices(cell.chips)
    enable_compile_cache()
    rates = [float(r) for r in args.rates.split(",")]
    model = cell.config["model"]
    weights = make_weights(model, args.seed)
    mixes = []
    for i, r in enumerate(rates):
        mix = json.loads(json.dumps(cell.mix))
        mix["arrivals"]["rate_per_s"] = r
        # a seed per rate: mixes of one seed would share first tokens
        mixes.append(generate(mix, args.seed + i, model["vocab_size"],
                              args.seconds, warmup_new_tokens=1))
    warm = Traffic(**{**dataclasses.asdict(mixes[0]), "warmup": [
        w for t in mixes for w in t.warmup]})
    loop = H.build_loop(cell.config, warm, weights)
    H.warm_up(H.Driver(loop), warm)
    for rate, traffic in zip(rates, mixes):
        drv = H.Driver(loop)
        t0 = time.perf_counter()
        reqs, i = traffic.requests, 0
        while time.perf_counter() < t0 + args.seconds:
            now = time.perf_counter()
            while i < len(reqs) and t0 + reqs[i].due_s <= now:
                drv.send(reqs[i], t0 + reqs[i].due_s)
                i += 1
            if drv.live:
                drv.poll()
            else:
                time.sleep(0.001)
        drv.attach_admits()
        recs = list(drv.records.values())
        admitted = sum(r.admit_t is not None for r in recs)
        done = sum(r.done for r in recs)
        first = [r.emits[0][0] - r.due_t if r.emits else float("inf")
                 for r in recs]
        waits = [r.admit_t - r.due_t if r.admit_t is not None
                 else float("inf") for r in recs]
        print(json.dumps({
            "rate": rate, "due": len(recs), "admitted": admitted,
            "finished": done, "backlog": len(recs) - admitted,
            "queue_wait_p50_ms": stats.nearest_rank(waits, 50) * 1e3,
            "queue_wait_p95_ms": stats.nearest_rank(waits, 95) * 1e3,
            "ttft_p95_ms": stats.nearest_rank(first, 95) * 1e3,
            "tokens_per_s": stats.tokens_in(recs, t0, t0 + args.seconds)
            / args.seconds}), flush=True)
        loop.shutdown()
        drv.poll()
    return 0


if __name__ == "__main__":
    sys.exit(main())
