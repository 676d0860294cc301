#!/usr/bin/env python3
"""Split a measured window's device idle time by the program's flush phases,
and its conv kernel time by stage.

``CnnBatcher.flush`` records host spans on the profiler's Python thread:
``cnn.flush`` around a flush and, per classify call, ``cnn.pad``,
``cnn.put``, ``cnn.call``, ``cnn.argmax`` and ``cnn.readback``.  Each idle
gap of the first chip goes, by its midpoint, to the innermost ``cnn.*`` span
that holds it (``bench.*`` spans are not consulted), giving two shares of
the window, in %:

- ``prep_idle``: idle while the host is in ``cnn.pad``, ``cnn.put`` or
  ``cnn.call``, the device waiting for its input;
- ``collect_idle``: idle while the host is in ``cnn.argmax`` or
  ``cnn.readback``, the device waiting while results come back.

The rest of the idle time is the benchmark loop's, or lies outside every
span.  A trace with no ``cnn.*`` span (a program that records none) gives
no shares.

Every conv stage's kernel is named ``conv<i>_<kind>`` (``conv2d(name=)``);
``stage_table`` gives each stage's kernel time per classify call and its
share of the stage's roofline at the full batch (``work.py``).

    python bench/flush_phases.py <trace.xplane.pb[.gz]> --result <file> \\
        --workload <cell>

reduces a trace that ``bench/run.py --trace 1 --keep-trace <file>`` kept,
with the window length and device kind from that run's result line (the
last line of its standard output, saved to ``--result``), and prints one
JSON object.  A device kind with no peaks in ``peaks.json`` is an error.
"""
from __future__ import annotations

import argparse
import bisect
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace as tracing, work  # noqa: E402
from bench.trace import Span  # noqa: E402

PREFIX = "cnn."
PREP = ("cnn.pad", "cnn.put", "cnn.call")
COLLECT = ("cnn.argmax", "cnn.readback")
OUTSIDE = "(no cnn span)"
UNNAMED = "(no bench span)"  # what trace.attribute_gaps calls a gap outside
STAGE = re.compile(r"conv(\d+)_[a-z_]+")


def program_spans(path) -> list:
    """The program's ``cnn.*`` host spans, on the host clock, by start; of
    two that start together the outer comes first, so that
    ``trace.host_span_at`` takes the inner."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(tracing.read_xspace(path))
    return sorted((Span(e.name, e.start_ns, e.end_ns)
                   for plane in pd.planes if plane.name == "/host:CPU"
                   for line in plane.lines for e in line.events
                   if e.name.startswith(PREFIX)),
                  key=lambda s: (s.start, -s.end))


def idle_by_phase(tr: tracing.Trace, spans: list, window_s: float) -> dict:
    """Idle seconds of the first chip in the window, by the innermost
    ``cnn.*`` span each gap's midpoint falls in (``trace.attribute_gaps``
    over the program's spans in place of the harness's)."""
    lo, hi = tr.window(window_s)
    gaps = tracing.idle_gaps(tracing.union(tr.devices[0].ops, lo, hi), lo, hi)
    ns = tracing.attribute_gaps(gaps, spans)
    return {OUTSIDE if k == UNNAMED else k: v / 1e9 for k, v in ns.items()}


def phase_shares(idle: dict, spans: list, window_s: float) -> dict:
    """``prep_idle`` and ``collect_idle`` in % of the window; empty where
    the trace holds no ``cnn.*`` span."""
    if not spans:
        return {}
    return {key: 100.0 * sum(idle.get(n, 0.0) for n in names) / window_s
            for key, names in (("prep_idle", PREP), ("collect_idle", COLLECT))}


def stage_kernels(tr: tracing.Trace, window_s: float) -> dict:
    """Mosaic kernel seconds in the window's classify runs, by kernel name
    without XLA's ``.<n>``, and the number of those runs."""
    lo, hi = tr.window(window_s)
    dev = tr.devices[0]
    calls = tracing.step_calls(dev, lo, hi)
    starts = [c.start for c in calls]
    out = defaultdict(float)
    for k in dev.ops:
        i = bisect.bisect_right(starts, k.start) - 1
        if tracing.is_kernel(k.name) and i >= 0 and k.start < calls[i].end:
            out[tracing.op_name(k.name).rsplit(".", 1)[0]] += k.dur / 1e9
    return {"calls": len(calls), "kernel_s": dict(out)}


def stage_table(kernels: dict, conf: dict, batch: int, pk: dict) -> list:
    """Per conv stage: kernel name, ms per classify call, and the share of
    that time the stage's roofline would take at ``batch`` (in %)."""
    sts = work.stages(conf)
    rows = []
    for name, s in sorted(kernels["kernel_s"].items(),
                          key=lambda kv: _stage_no(kv[0])):
        i = _stage_no(name)
        ms = 1e3 * s / kernels["calls"]
        row = {"kernel": name, "ms_per_call": ms}
        if 1 <= i <= len(sts):
            st = sts[i - 1]
            ideal = max(work.stage_flops(st, batch) / pk["bf16_flops_per_s"],
                        work.stage_bytes(st, batch, conf) / pk["hbm_bytes_per_s"])
            row["roofline_pct"] = 100.0 * ideal / (ms / 1e3)
        rows.append(row)
    return rows


def _stage_no(kernel: str) -> int:
    m = STAGE.fullmatch(kernel)
    return int(m.group(1)) if m else 0


def reduce(path, window_s: float, conf: dict = None, batch: int = None,
           device_kind: str = None) -> dict:
    """The idle split, and with ``conf`` the stage table at ``batch`` on a
    ``device_kind`` chip."""
    tr = tracing.load(path)
    spans = program_spans(path)
    summary = tracing.summarize(tr, window_s)
    idle = idle_by_phase(tr, spans, window_s)
    out = {"window_s": summary.window_s,
           "device_idle": 100.0 * (1.0 - summary.busy_s / summary.window_s),
           **phase_shares(idle, spans, window_s),
           "idle_s_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1]))}
    kernels = stage_kernels(tr, window_s)
    out["classify_calls"] = kernels["calls"]
    if conf is not None and kernels["calls"]:
        out["stages"] = stage_table(kernels, conf, batch, work.peak(device_kind))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--result", required=True,
                    help="the traced run's result line, as a file")
    ap.add_argument("--workload", default=None,
                    help="the cell, for the stage table's shapes and batch")
    args = ap.parse_args(argv)
    device = json.loads(Path(args.result).read_text().strip()
                        .splitlines()[-1])["device"]
    conf = batch = None
    if args.workload:
        from bench import harness

        spec = harness.workload(harness.load_benchmark(), args.workload)
        conf = harness.load_config(spec["config"])
        batch = harness.load_traffic(spec["traffic"])["max_batch"]
    print(json.dumps(reduce(args.trace, device["window_s"], conf, batch,
                            device["kind"])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
