"""Reduce a profiler trace of one measured window to device numbers.

The JAX profiler writes an ``.xplane.pb``.  On a TPU it holds, per chip, a
plane ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per
program run, with its ``run_id``) and ``XLA Ops`` (one event per HLO op; a
Mosaic kernel is a ``custom-call`` whose target is ``tpu_custom_call``), and
a plane ``/host:CPU`` with one line per host thread.  The benchmark's own
``jax.profiler.TraceAnnotation`` spans (``bench.*``) are on the Python
thread.

The device lines keep their own clock.  ``load`` moves them onto the host's:
the host's ``CompleteCallbacks`` event for a ``run_id`` starts after the
device finished that run, so the smallest gap between the two over all runs
is the offset, late by at most one callback's latency.

Every number here is a reduction over the measured window ``[lo, hi]``,
which starts with the ``bench.run`` span, in nanoseconds on the host clock.
"""
from __future__ import annotations

import dataclasses
import gzip
from collections import defaultdict
from pathlib import Path
from typing import Optional

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
WINDOW = "bench.run"
HOST_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    name: str
    ops: list  # Span per XLA op, host clock
    modules: list  # Span per program run, host clock


@dataclasses.dataclass
class Trace:
    devices: list  # Device per chip, in plane order
    host: list  # bench.* spans, host clock
    offset_ns: float  # added to device times to put them on the host clock

    def window(self, window_s: float) -> tuple:
        """``[lo, hi]``: ``window_s`` seconds from the start of ``bench.run``."""
        for s in self.host:
            if s.name == WINDOW:
                return s.start, s.start + 1e9 * window_s
        raise ValueError(f"no {WINDOW!r} span in the trace")


def op_name(event_name: str) -> str:
    """``%_conv_fwd_impl.6 = f32[...] custom-call(...)`` -> ``_conv_fwd_impl.6``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def is_kernel(event_name: str) -> bool:
    return KERNEL_MARK in event_name


def module_name(event_name: str) -> str:
    """``jit_f(11091741720988731829)`` -> ``jit_f``."""
    return event_name.split("(", 1)[0]


def read_xspace(path) -> bytes:
    data = Path(path).read_bytes()
    return gzip.decompress(data) if str(path).endswith(".gz") else data


def load(path) -> Trace:
    """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``) into a :class:`Trace`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(read_xspace(path))
    devices, host, done_at = [], [], {}
    run_end = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [Span(e.name, e.start_ns, e.end_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    for e in line.events:
                        modules.append(Span(e.name, e.start_ns, e.end_ns))
                        rid = dict(e.stats).get("run_id")
                        if rid is not None:
                            run_end[rid] = max(run_end.get(rid, 0.0), e.end_ns)
            devices.append(Device(plane.name, ops, modules))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append(Span(e.name, e.start_ns, e.end_ns))
                    elif e.name == "CompleteCallbacks":
                        rid = dict(e.stats).get("run_id")
                        if rid is not None:
                            done_at[rid] = min(done_at.get(rid, float("inf")),
                                               e.start_ns)
    if not devices:
        raise ValueError("no /device:TPU plane in the trace")
    gaps = [done_at[r] - run_end[r] for r in done_at if r in run_end]
    if not gaps:
        raise ValueError("no host CompleteCallbacks event matches a device "
                         "run: the device clock cannot be aligned")
    off = min(gaps)
    shift = lambda spans: [Span(s.name, s.start + off, s.end + off) for s in spans]
    devices = [Device(d.name, shift(d.ops), shift(d.modules)) for d in devices]
    return Trace(devices, sorted(host, key=lambda s: s.start), off)


def union(spans, lo: float, hi: float) -> list:
    """Merged ``(start, end)`` intervals of ``spans`` clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s.start, lo), min(s.end, hi)) for s in spans):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def idle_gaps(busy: list, lo: float, hi: float) -> list:
    """The complement of merged ``busy`` intervals within ``[lo, hi]``."""
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def host_span_at(host: list, t: float) -> Optional[str]:
    """The innermost ``bench.*`` span (latest start) that contains ``t``,
    the window itself excepted."""
    best = None
    for s in host:
        if s.name != WINDOW and s.start <= t < s.end:
            if best is None or s.start >= best.start:
                best = s
    return best.name if best else None


def attribute_gaps(gaps: list, host: list) -> dict:
    """Idle nanoseconds by the host span each gap's midpoint falls in."""
    out = defaultdict(float)
    for s, e in gaps:
        out[host_span_at(host, (s + e) / 2) or "(no bench span)"] += e - s
    return dict(out)


@dataclasses.dataclass(frozen=True)
class Call:
    """One run of the step program, wholly inside the window."""
    start: float
    end: float
    kernel_ns: float  # Mosaic kernel device time inside the run
    n_kernels: int


def step_calls(dev: Device, lo: float, hi: float) -> list:
    """Runs of the program that takes the most device time in the window
    (the classify step), each with the Mosaic kernel time inside it."""
    inside = [m for m in dev.modules if m.start >= lo and m.end <= hi]
    if not inside:
        return []
    by_name = defaultdict(float)
    for m in inside:
        by_name[module_name(m.name)] += m.dur
    step = max(by_name, key=by_name.get)
    kernels = sorted((s for s in dev.ops if is_kernel(s.name)), key=lambda s: s.start)
    calls, i = [], 0
    for m in sorted((m for m in inside if module_name(m.name) == step),
                    key=lambda m: m.start):
        while i < len(kernels) and kernels[i].start < m.start:
            i += 1
        ks = []
        while i < len(kernels) and kernels[i].start < m.end:
            ks.append(kernels[i])
            i += 1
        calls.append(Call(m.start, m.end, sum(k.dur for k in ks), len(ks)))
    return calls


def top_ops(dev: Device, lo: float, hi: float, n: int = 10) -> list:
    """``[op name, seconds]`` of the ops that took the most time in the window."""
    out = defaultdict(float)
    for s in dev.ops:
        d = min(s.end, hi) - max(s.start, lo)
        if d > 0:
            out[op_name(s.name)] += d
    return [[k, v / 1e9] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:n]]


@dataclasses.dataclass
class Summary:
    """What the per-layer readers read, for the first chip unless noted."""
    window_s: float
    busy_s: float  # union of op intervals, averaged over the chips
    kernel_busy_s: float  # union of Mosaic kernel intervals
    calls: list  # Call per step run inside the window
    device_ops: list  # [[op name, seconds]], the longest first
    idle_gaps: list  # [[host span, seconds]], the longest first


def summarize(tr: Trace, window_s: float, n_top: int = 10) -> Summary:
    lo, hi = tr.window(window_s)
    busy = [total(union(d.ops, lo, hi)) for d in tr.devices]
    dev = tr.devices[0]
    merged = union(dev.ops, lo, hi)
    gaps = attribute_gaps(idle_gaps(merged, lo, hi), tr.host)
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / len(busy) / 1e9,
        kernel_busy_s=total(union([s for s in dev.ops if is_kernel(s.name)],
                                  lo, hi)) / 1e9,
        calls=step_calls(dev, lo, hi),
        device_ops=top_ops(dev, lo, hi, n_top),
        idle_gaps=[[k, v / 1e9] for k, v in
                   sorted(gaps.items(), key=lambda kv: -kv[1])[:n_top]],
    )
