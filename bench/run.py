#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's weights on the device from the seed (the plain
reference's draw, in the program's own container), makes the seeded inputs,
and warms every shape they reach through the timed path; the configuration's
family (``bench/programs/<family>.py``) says how.  The window then drives the
program's front with the cell's traffic for ``--seconds``.  After it, the
program's state is freed and every answer due in the window is compared with
the plain reference.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` profiles
the window and reports its per-layer metrics, with the device's busy time
and the longest ops and idle gaps.  Earlier lines on standard error give the
compilations inside the window, the device memory peak and how late the
generator ran; the last lines on standard error, and the result's ``check``
key, give each number compared beside its limit.  The last line on standard
output is the result, one JSON object.

Exits 2, printing no result, where the program is missing, JAX finds no TPU,
or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import harness, trace as tracing  # noqa: E402
from bench.harness import log  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the window's trace, gzipped, to this file")
    return ap.parse_args(argv)


def _profile(trace_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _read_trace(trace_dir: str, window_s: float, keep: str = None):
    path = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")[0]
    if keep:
        Path(keep).parent.mkdir(parents=True, exist_ok=True)
        Path(keep).write_bytes(gzip.compress(Path(path).read_bytes()))
    return tracing.summarize(tracing.load(path), window_s)


def execute(sess: harness.Session, seed: int, seconds: float, *,
            t_start: float, keep_trace: str = None) -> dict:
    """One run of one cell, below the platform check: the result line.
    ``sess.trace`` says whether the window is profiled."""
    import jax

    dev = jax.devices()[0]
    bench, name, trace = sess.bench, sess.spec["name"], sess.trace
    sess.load(seed)
    sess.warm()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            _profile(trace_dir)
        try:
            seen = sess.measure(seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        summary = (_read_trace(trace_dir, seen.window_s, keep_trace)
                   if trace else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = seen.t0 - t_start
    stats = dev.memory_stats() or {}
    log(f"compilations inside the window: {seen.compiles} {seen.compiled}")
    log(f"device memory peak bytes: {stats.get('peak_bytes_in_use')}")
    log(f"generator lateness ms (sent - due): {harness.ms_stats(seen.lateness)}")
    log(f"flush ms: {harness.ms_stats([d for _, d in seen.flushes])}; longest "
        "at s: " + ", ".join(f"{t:.3f} ({1e3 * d:.1f} ms)" for t, d in
                             sorted(seen.flushes, key=lambda f: -f[1])[:3]))
    log(f"gc pauses in the window: {len(seen.gc_pauses)}, ms "
        f"{harness.ms_stats(seen.gc_pauses)}")
    log(f"requests {len(seen.recs)}, window {seen.window_s:.3f}s, classify "
        f"calls {seen.calls} carrying {seen.rows} images, set-up {setup_s:.3f}s")

    # the program's state goes before the reference runs
    sess.free()
    t = time.perf_counter()
    numbers = sess.verify(seen)
    log(f"reference over {len(sess.pool)} pool images in "
        f"{time.perf_counter() - t:.3f}s")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    result = {"correct": harness.passed(numbers), "attempted": len(seen.recs),
              "failed": sum(r.done is None for r in seen.recs)}
    if trace:
        ctx = harness.reader_context(sess, seen, summary, dev.device_kind)
        result["metrics"] = harness.read_per_layer(bench, name, ctx, sess.root)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["device"] = device
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
        log(f"trace: {len(summary.calls)} classify runs inside the window, "
            f"{seen.calls} dispatched; busy {summary.busy_s:.6f}s of "
            f"{summary.window_s:.6f}s, Mosaic kernels {summary.kernel_busy_s:.6f}s")
        for line in sess.program.describe(sess.conf, sess.mix["max_batch"], ctx.peak):
            log(line)
    else:
        e2e = dict(sess.loop.end_to_end(seen.recs, seen.window_s), setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in harness.cell_metrics(bench, name, "end_to_end")}
        result["device"] = device
    result["check"] = numbers
    return result


def main(argv=None) -> int:
    args = parse(argv)
    bench = harness.load_benchmark()
    try:
        spec = harness.workload(bench, args.workload)
        harness.find_program()
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX platform {devices[0].platform!r}); the "
              "benchmark runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < spec["chips"]:
        print(f"bench: {args.workload} needs {spec['chips']} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache {enable_compile_cache()}")
    sess = harness.Session(bench, args.workload, interpret=False,
                           trace=bool(args.trace))
    result = execute(sess, args.seed, args.seconds, t_start=T_START,
                     keep_trace=args.keep_trace)
    for k, n in result["check"].items():
        print(f"check {k} {n['value']!r} limit {n['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
