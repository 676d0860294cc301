"""``bench/trace_cost.py`` on the CPU: one short window of the smoke serve
mix with the profiler on and off."""
import time

import pytest

from bench import trace_cost

import bench_smoke as smoke


@pytest.mark.parametrize("trace", [0, 1])
def test_trace_cost_reports_end_to_end_either_way(tmp_path, trace):
    """``bench/trace_cost.py`` gives the same end-to-end metrics with the
    profiler on as off, and a passing check."""
    sess = smoke.session(tmp_path, "smoke.serve", trace=bool(trace))
    t0 = time.perf_counter()
    out = trace_cost.cost_run(sess, 2**31 + 5, 1.0, t_start=t0)
    assert out["trace"] == trace and out["correct"]
    assert set(out["metrics"]) == {"images_per_s", "latency_p95_ms", "setup_s"}
    assert 0 < out["metrics"]["setup_s"] < time.perf_counter() - t0
    assert out["metrics"]["images_per_s"] > 0
