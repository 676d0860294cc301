#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: one rate after another.

    python bench/sweep.py --workload alexnet.serve --rates 150,200,250 \\
        --seconds 15 [--seed N]

One process sets the cell up once, then runs its mix at each rate in turn
and prints, per rate, the images per second completed in the window, the
latency percentiles from due time, and whether the backlog grew: the mean
latency of the last third of the requests against the first third.  The
cell's ``rate_per_s`` is set once from such a sweep; the benchmark's own
runs never search for a rate.  Runs only on the chip.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=4_000_000_000)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    harness.find_program()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sess = harness.Session(bench, args.workload, interpret=False)
    sess.load(args.seed)
    sess.warm()
    base = dict(sess.mix)
    for rate in (float(r) for r in args.rates.split(",")):
        sess.mix = dict(base, rate_per_s=rate)
        seen = sess.measure(args.seconds)
        sess.warm()  # clears what the window recorded
        lat = [(r.done - r.due) if r.done is not None else math.inf
               for r in seen.recs]
        third = max(1, len(lat) // 3)
        e2e = sess.loop.end_to_end(seen.recs, seen.window_s)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(seen.recs),
            "images_per_s": e2e["images_per_s"],
            "p50_ms": 1e3 * harness.percentile(lat, 50),
            "p95_ms": e2e["latency_p95_ms"],
            "p99_ms": 1e3 * harness.percentile(lat, 99),
            "first_third_mean_ms": 1e3 * sum(lat[:third]) / third,
            "last_third_mean_ms": 1e3 * sum(lat[-third:]) / third,
            "batch_fill": 100.0 * seen.rows / max(1, seen.calls * sess.mix["max_batch"]),
            "compiles": seen.compiles}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
