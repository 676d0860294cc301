"""One short window of each mix through the harness on the CPU, below the
platform check: the loops, the due-time latency arithmetic, batch_fill and
the result line."""
import math
import time

import pytest

from bench import harness, run
from bench.harness import Rec

import bench_smoke as smoke


@pytest.fixture(scope="module")
def serve(tmp_path_factory):
    return smoke.session(tmp_path_factory.mktemp("serve"), "smoke.serve")


@pytest.fixture(scope="module")
def bulk(tmp_path_factory):
    return smoke.session(tmp_path_factory.mktemp("bulk"), "smoke.bulk")


def _window(sess, seed, seconds):
    sess.load(seed)
    sess.warm()
    return sess.measure(seconds)


def _batch_fill(sess, seen):
    ctx = harness.reader_context(sess, seen, None, "TPU v5 lite")
    return harness.load_reader("batch_fill.serve", sess.root)(ctx)


def test_open_loop_times_each_request_from_its_due_time(serve):
    seconds = 2.0
    seen = _window(serve, 2**31 + 17, seconds)
    due, _ = harness.traffic.open_schedule(
        serve.mix, 2**31 + 17, seconds, harness.load_arrivals("poisson", serve.root))
    assert len(seen.recs) == len(due) and seen.compiles == 0
    for r in seen.recs:
        assert r.due <= r.sent <= r.done
    lat = [r.done - r.due for r in seen.recs]
    e2e = serve.loop.end_to_end(seen.recs, seen.window_s)
    assert e2e["latency_p95_ms"] == 1e3 * harness.percentile(lat, 95)
    assert e2e["latency_p95_ms"] >= 1e3 * harness.percentile(
        [r.done - r.sent for r in seen.recs], 95)
    inside = sum(r.done <= seconds for r in seen.recs)
    assert e2e["images_per_s"] == inside / seconds
    # batch_fill: images the window's calls carried over calls x max_batch
    calls = [d for d in seen.dispatches if 0 <= d.t - seen.t0 <= seconds]
    rows = sum(len(d.uids) for d in calls)
    assert _batch_fill(serve, seen) == pytest.approx(
        100 * rows / (len(calls) * serve.mix["max_batch"]))
    serve.free()
    numbers = serve.verify(seen)
    assert harness.passed(numbers), numbers


def test_closed_loop_sends_when_the_last_request_returns(bulk):
    seen = _window(bulk, 5, 1.5)
    for a, b in zip(seen.recs, seen.recs[1:]):
        assert a.done <= b.sent
    assert seen.window_s == seen.recs[-1].done >= 1.5 > seen.recs[-1].sent
    assert _batch_fill(bulk, seen) == 100.0  # every request is a full batch
    e2e = bulk.loop.end_to_end(seen.recs, seen.window_s)
    assert "latency_p95_ms" not in e2e
    assert e2e["images_per_s"] == 4 * len(seen.recs) / seen.window_s
    bulk.free()
    assert harness.passed(bulk.verify(seen))


def test_a_request_never_answered_counts_as_missing_its_limit():
    recs = [Rec(0.0, [0], 0.0, 0.001 * k) for k in range(1, 19)]
    e2e = harness.load_loop("open").end_to_end
    one = recs + [Rec(0.0, [0], 0.0, None), Rec(0.0, [0], 0.0, 0.019)]
    assert e2e(one, 1.0)["latency_p95_ms"] == pytest.approx(19.0)
    two = recs + [Rec(0.0, [0], 0.0, None), Rec(0.0, [0], 0.0, None)]
    assert math.isinf(e2e(two, 1.0)["latency_p95_ms"])
    late = recs + [Rec(0.5, [0], 0.5, 1.5)]  # answered after the window
    assert e2e(late, 1.0)["images_per_s"] == 18.0


def test_result_line(serve):
    r = run.execute(serve, 11, 1.0, t_start=time.perf_counter())
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device", "check"]
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"images_per_s", "latency_p95_ms", "setup_s"}
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert set(r["check"]) == {"logit_err", "class_mismatch", "unanswered"}
