#!/usr/bin/env python3
"""What profiling costs a cell: one run of ``bench/run.py``'s set-up, window
and check, with the profiler on or off, reporting the end-to-end metrics
either way.

    python bench/trace_cost.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 1`` the window is profiled and the harness's ``bench.*``
spans are on, as in a ``bench/run.py --trace 1`` run; the trace is then
dropped unread.  ``bench/run.py`` reports the end-to-end metrics only
untraced, so runs of the same seeds with ``--trace 0`` and ``--trace 1``
here, alternated on one host, give the cost of tracing.  The last line on
standard output is one JSON object: ``trace``, ``correct`` and the cell's
end-to-end metrics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import harness, run  # noqa: E402


def cost_run(sess: harness.Session, seed: int, seconds: float, *,
             t_start: float) -> dict:
    """One window of the cell, profiled where ``sess.trace`` says, and its
    end-to-end metrics with the check's verdict."""
    import jax

    sess.load(seed)
    sess.warm()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if sess.trace else None
    try:
        if trace_dir:
            run._profile(trace_dir)
        try:
            seen = sess.measure(seconds)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    e2e = dict(sess.loop.end_to_end(seen.recs, seen.window_s),
               setup_s=seen.t0 - t_start)
    sess.free()
    numbers = sess.verify(seen)
    return {"trace": int(sess.trace), "correct": harness.passed(numbers),
            "metrics": e2e}


def main(argv=None) -> int:
    args = run.parse(argv)
    bench = harness.load_benchmark()
    harness.find_program()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sess = harness.Session(bench, args.workload, interpret=False,
                           trace=bool(args.trace))
    print(json.dumps(cost_run(sess, args.seed, args.seconds, t_start=T_START)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
