#!/usr/bin/env python3
"""Read the check's numbers over many seeds, for the program and the control.

    python bench/calibrate.py --workload alexnet.serve --seeds 12 \\
        --control-seeds 3 --seconds 5 [--first-seed N] [--out FILE]

One process sets the cell up once and runs a short window of its own traffic
per seed: first the program, then the control (the plain reference at three
bfloat16 passes in the program's place).  Each run's numbers, and the end-to-
end metrics of the short window, go to standard output as one JSON line, and
to ``--out`` when given.  The limits in a configuration's ``limits`` are set
from these readings; the benchmark's own runs never run the control.
Runs only on the chip.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import harness  # noqa: E402
from bench.run import execute  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    harness.find_program()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    out = open(args.out, "a") if args.out else None
    try:
        for kind, n, control in (("program", args.seeds, False),
                                 ("control", args.control_seeds, True)):
            if not n:
                continue
            sess = harness.Session(bench, args.workload, interpret=False,
                                   control=control)
            for i in range(n):
                seed = args.first_seed + i
                t = time.perf_counter()
                r = execute(sess, seed, args.seconds, t_start=t)
                line = json.dumps({"workload": args.workload, "kind": kind,
                                   "seed": seed, "correct": r["correct"],
                                   "check": r["check"], "metrics": r["metrics"],
                                   "memory_peak_bytes": r["device"]["memory_peak_bytes"]})
                print(line, flush=True)
                if out:
                    out.write(line + "\n")
                    out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
