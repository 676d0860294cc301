"""The trace reduction: hand-made spans with known answers, and a trace
recorded on a TPU v5e (``data/serve_trace.xplane.pb.gz``: 3 s of
``alexnet.serve`` under ``bench/run.py --trace 1``)."""
from pathlib import Path

import pytest

from bench import trace as tr
from bench.trace import Span

DATA = Path(__file__).resolve().parent / "data"
KERNEL = ('%_conv_fwd_impl.{} = f32[8] custom-call(f32[8] %x), '
          'custom_call_target="tpu_custom_call"')
PLAIN = "%copy.{} = f32[8] copy(f32[8] %x)"


def test_busy_union_and_idle_share():
    ops = [Span("a", 0, 10), Span("b", 5, 15), Span("c", 20, 30),
           Span("d", 35, 50)]
    busy = tr.union(ops, 0, 40)
    assert busy == [(0, 15), (20, 30), (35, 40)]
    assert tr.total(busy) == 30
    assert tr.idle_gaps(busy, 0, 40) == [(15, 20), (30, 35)]
    assert tr.idle_gaps(tr.union(ops, -5, 60), -5, 60) == [
        (-5, 0), (15, 20), (30, 35), (50, 60)]


def _device(ops, modules):
    return tr.Device("/device:TPU:0", ops, modules)


def test_kernel_sum_and_per_call_split():
    ops = [Span(KERNEL.format(1), 1, 4), Span(PLAIN.format(1), 4, 5),
           Span(KERNEL.format(2), 5, 9),
           Span(KERNEL.format(1), 21, 23), Span(KERNEL.format(2), 23, 28),
           Span(PLAIN.format(2), 28, 29)]
    modules = [Span("jit_f(1)", 1, 9), Span("jit__argmax(2)", 9, 10),
               Span("jit_f(1)", 21, 29), Span("jit__argmax(2)", 29, 30),
               Span("jit_f(1)", 95, 105)]  # runs past the window: left out
    calls = tr.step_calls(_device(ops, modules), 0, 100)
    assert calls == [tr.Call(1, 9, 7, 2), tr.Call(21, 29, 7, 2)]
    assert tr.op_name(KERNEL.format(2)) == "_conv_fwd_impl.2"
    assert tr.module_name("jit_f(11091741720988731829)") == "jit_f"
    trace = tr.Trace([_device(ops, modules)],
                     [Span("bench.run", 0, 200)], 0.0)
    s = tr.summarize(trace, window_s=100e-9)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(16e-9)
    assert s.kernel_busy_s == pytest.approx(14e-9)
    assert s.device_ops[0] == ["_conv_fwd_impl.2", pytest.approx(9e-9)]


def test_idle_gap_goes_to_the_innermost_host_span():
    host = [Span("bench.run", 0, 100), Span("bench.flush", 10, 40),
            Span("bench.dispatch", 12, 20), Span("bench.wait", 50, 90)]
    assert tr.host_span_at(host, 15) == "bench.dispatch"
    assert tr.host_span_at(host, 30) == "bench.flush"
    assert tr.host_span_at(host, 95) is None  # the window span is no answer
    gaps = [(13, 17), (25, 35), (60, 80), (92, 98)]
    assert tr.attribute_gaps(gaps, host) == {
        "bench.dispatch": 4, "bench.flush": 10, "bench.wait": 20,
        "(no bench span)": 6}


@pytest.fixture(scope="module")
def recorded():
    return tr.load(DATA / "serve_trace.xplane.pb.gz")


def test_recorded_trace_loads_and_aligns(recorded):
    assert [d.name for d in recorded.devices] == ["/device:TPU:0"]
    dev = recorded.devices[0]
    assert dev.ops and dev.modules
    names = {s.name for s in recorded.host}
    assert {"bench.run", "bench.flush", "bench.dispatch", "bench.wait"} <= names
    # aligned: each classify run starts after the host dispatched it
    lo, hi = recorded.window(3.0)
    dispatches = sorted(s.start for s in recorded.host if s.name == "bench.dispatch")
    for c in tr.step_calls(dev, lo, hi):
        assert any(d <= c.start for d in dispatches)


def test_recorded_trace_reduction(recorded):
    s = tr.summarize(recorded, window_s=3.0)
    lo, hi = recorded.window(3.0)
    dev = recorded.devices[0]
    assert s.window_s == pytest.approx(3.0)
    assert 0 < s.kernel_busy_s <= s.busy_s < s.window_s
    assert s.busy_s == pytest.approx(tr.total(tr.union(dev.ops, lo, hi)) / 1e9)
    idle = tr.total(tr.idle_gaps(tr.union(dev.ops, lo, hi), lo, hi)) / 1e9
    assert idle + s.busy_s == pytest.approx(s.window_s)
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(idle)
    assert s.calls
    for c in s.calls:  # AlexNet: one Mosaic kernel per conv stage
        assert c.n_kernels == 5
        assert 0 < c.kernel_ns <= c.end - c.start
    assert s.device_ops[0][0].startswith("_conv_fwd_impl")
