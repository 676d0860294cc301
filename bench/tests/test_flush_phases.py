"""The flush-phase split of device idle time and the per-stage kernel table:
hand-made spans with known answers, and a trace recorded on a TPU v5e
(``data/serve_trace_spans.xplane.pb.gz``: 3 s of ``alexnet.serve`` under
``bench/run.py --trace 1 --keep-trace``, with the program's ``cnn.*`` spans
and named conv kernels)."""
import bisect
import json
from pathlib import Path

import pytest

from bench import flush_phases as fp
from bench import trace as tr
from bench.trace import Span

RECORDED = (Path(__file__).resolve().parent / "data"
            / "serve_trace_spans.xplane.pb.gz")
KERNEL = ('%{} = f32[8] custom-call(f32[8] %x), '
          'custom_call_target="tpu_custom_call"')
PLAIN = "%copy.{} = f32[8] copy(f32[8] %x)"


def _trace(ops, modules=(), host=()):
    dev = tr.Device("/device:TPU:0", list(ops), list(modules))
    return tr.Trace([dev], [Span("bench.run", 0, 1000), *host], 0.0)


def test_idle_goes_to_the_innermost_cnn_span():
    """Device busy in [0, 100) of a 200 ns window, then gaps: one inside a
    ``bench.dispatch`` nested in ``cnn.call`` (the bench span is ignored),
    one in ``cnn.readback``, one in ``cnn.pad`` inside ``cnn.flush``, one in
    ``cnn.flush`` alone and one outside every ``cnn.*`` span."""
    ops = [Span(PLAIN.format(i), s, e) for i, (s, e) in enumerate(
        [(0, 100), (110, 120), (140, 150), (160, 170), (175, 190)])]
    program = sorted([Span("cnn.flush", 95, 175), Span("cnn.call", 100, 112),
                      Span("cnn.readback", 120, 140), Span("cnn.pad", 150, 158)],
                     key=lambda s: (s.start, -s.end))
    host = [Span("bench.flush", 95, 175), Span("bench.dispatch", 101, 111)]
    trace = _trace(ops, host=host)
    idle = fp.idle_by_phase(trace, program, window_s=200e-9)
    assert idle == pytest.approx({
        "cnn.call": 10e-9, "cnn.readback": 20e-9, "cnn.pad": 10e-9,
        "cnn.flush": 5e-9, fp.OUTSIDE: 10e-9})
    shares = fp.phase_shares(idle, program, window_s=200e-9)
    assert shares == pytest.approx({"prep_idle": 100 * (10 + 10) / 200,
                                    "collect_idle": 100 * 20 / 200})
    summary = tr.summarize(trace, window_s=200e-9)
    device_idle = 100 * (1 - summary.busy_s / summary.window_s)
    assert device_idle == pytest.approx(100 * 55 / 200)
    assert shares["prep_idle"] + shares["collect_idle"] <= device_idle


def test_no_program_spans_no_shares():
    """A program that records no ``cnn.*`` span (as before the spans
    existed) gives no shares, and its idle time stays unattributed."""
    trace = _trace([Span(PLAIN.format(0), 0, 50)])
    idle = fp.idle_by_phase(trace, [], window_s=100e-9)
    assert idle == pytest.approx({fp.OUTSIDE: 50e-9})
    assert fp.phase_shares(idle, [], window_s=100e-9) == {}


def test_stage_table_per_call_and_roofline():
    """Two classify runs with two named conv kernels each; a kernel outside
    every run and a plain op do not count."""
    ops = [Span(KERNEL.format("conv1_pasm.3"), 1, 4),
           Span(KERNEL.format("conv2_pasm.5"), 4, 9), Span(PLAIN.format(1), 9, 10),
           Span(KERNEL.format("conv1_pasm.3"), 21, 23),
           Span(KERNEL.format("conv2_pasm.5"), 23, 30),
           Span(KERNEL.format("conv1_pasm.3"), 50, 60)]
    modules = [Span("jit_f(1)", 1, 10), Span("jit__argmax(2)", 10, 11),
               Span("jit_f(1)", 21, 30), Span("jit__argmax(2)", 30, 31)]
    kernels = fp.stage_kernels(_trace(ops, modules), window_s=100e-9)
    assert kernels["calls"] == 2
    assert kernels["kernel_s"] == pytest.approx(
        {"conv1_pasm": 5e-9, "conv2_pasm": 12e-9})

    conf = {"in_chw": [3, 8, 8], "padding": "same", "packed": True, "bins": 16,
            "convs": [{"c_out": 4, "k": 3, "stride": 1},
                      {"c_out": 4, "k": 3, "stride": 1}],
            "pools": [2, 1]}
    pk = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    rows = fp.stage_table(kernels, conf, 2, pk)
    assert [r["kernel"] for r in rows] == ["conv1_pasm", "conv2_pasm"]
    assert rows[0]["ms_per_call"] == pytest.approx(2.5e-6)
    # conv1: 2*2*8*8*3*9*4 = 27648 FLOPs -> 27.6 ns; bytes: input 4*2*3*64,
    # packed indices 3*9*4/2, dictionary 4*16, bias 4*4, pooled output
    # 4*2*4*4*4 = 2182 -> 2182 ns, so memory-bound
    assert rows[0]["roofline_pct"] == pytest.approx(100 * 2182e-9 / 2.5e-9)


@pytest.fixture(scope="module")
def recorded():
    trace = tr.load(RECORDED)
    return trace, fp.program_spans(RECORDED), trace.window(3.0)


def test_recorded_spans_share_the_device_clock(recorded):
    """Every classify run starts after its call's ``cnn.call`` began and
    ends before its ``cnn.readback`` ended."""
    trace, spans, (lo, hi) = recorded
    calls = [s for s in spans if s.name == "cnn.call"]
    readbacks = [s for s in spans if s.name == "cnn.readback"]
    assert len(calls) == len(readbacks)
    runs = tr.step_calls(trace.devices[0], lo, hi)
    assert len(runs) >= 80
    starts = [c.start for c in calls]
    matched = []
    for run in runs:
        i = bisect.bisect_right(starts, run.start) - 1
        assert i >= 0 and calls[i].start <= run.start
        assert run.end <= readbacks[i].end, (run, readbacks[i])
        matched.append(i)
    assert len(set(matched)) == len(runs)


def test_recorded_kernels_run_in_stage_order(recorded):
    trace, _, (lo, hi) = recorded
    dev = trace.devices[0]
    kernels = sorted((k for k in dev.ops if tr.is_kernel(k.name)),
                     key=lambda k: k.start)
    for run in tr.step_calls(dev, lo, hi):
        names = [tr.op_name(k.name).rsplit(".", 1)[0] for k in kernels
                 if run.start <= k.start < run.end]
        assert names == [f"conv{i}_pasm" for i in range(1, 6)]


def test_recorded_phase_shares_within_device_idle(recorded):
    trace, spans, _ = recorded
    idle = fp.idle_by_phase(trace, spans, window_s=3.0)
    shares = fp.phase_shares(idle, spans, window_s=3.0)
    s = tr.summarize(trace, window_s=3.0)
    device_idle = 100 * (1 - s.busy_s / s.window_s)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)
    assert 0 < shares["prep_idle"]
    assert shares["prep_idle"] + shares["collect_idle"] <= device_idle


def _result_line(tmp_path, kind):
    path = tmp_path / "result.json"
    path.write_text("log line\n" + json.dumps(
        {"correct": True, "device": {"kind": kind, "window_s": 3.0}}) + "\n")
    return str(path)


def test_cli_reads_window_and_kind_from_the_result_line(tmp_path, capsys):
    fp.main([str(RECORDED), "--result", _result_line(tmp_path, "TPU v5 lite"),
             "--workload", "alexnet.serve"])
    out = json.loads(capsys.readouterr().out)
    assert out["window_s"] == pytest.approx(3.0)
    assert [r["kernel"] for r in out["stages"]] == [
        f"conv{i}_pasm" for i in range(1, 6)]
    assert all(0 < r["roofline_pct"] < 100 for r in out["stages"])
    assert out["prep_idle"] + out["collect_idle"] <= out["device_idle"]


def test_cli_refuses_a_device_kind_without_peaks(tmp_path):
    with pytest.raises(KeyError, match="no peaks"):
        fp.main([str(RECORDED), "--result", _result_line(tmp_path, "TPU v9"),
                 "--workload", "alexnet.serve"])
