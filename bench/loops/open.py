"""Independent users: each request falls due on the schedule of the mix's
arrival process (``arrivals/<mix["arrivals"]>.py``) whatever the server
does.  Between flushes the loop submits every request that is due; after
the window it serves what still waits, for at most ``harness.GRACE_S``.

The window is ``seconds`` long.  Reported: ``images_per_s``, the images
answered inside the window over the window, and ``latency_p95_ms``, the
nearest-rank 95th percentile over every request due in the window, from its
due time to its class on the host; a request never answered counts as
infinitely late.
"""
import math
import time

from bench import harness, traffic


def run(b, pool, mix, seed, seconds, *, clock, span, root) -> tuple:
    due, picks = traffic.open_schedule(
        mix, seed, seconds, harness.load_arrivals(mix["arrivals"], root))
    recs = [harness.Rec(d, ids) for d, ids in zip(due, picks)]
    by_uid, i, n = {}, 0, len(recs)
    t0 = clock()
    while i < n or b.waiting:
        now = clock() - t0
        if now > seconds + harness.GRACE_S:
            break
        with span("bench.submit"):
            while i < n and recs[i].due <= now:
                b.send(recs[i], pool, now, by_uid)
                i += 1
        if b.waiting:
            b.serve(by_uid, t0)
        elif i < n:
            with span("bench.wait"):
                time.sleep(max(0.0, recs[i].due - (clock() - t0)))
    return recs, t0


def window_length(recs: list, seconds: float) -> float:
    return seconds


def end_to_end(recs: list, window_s: float) -> dict:
    lat = [(r.done - r.due if r.done is not None else math.inf) for r in recs]
    return {"images_per_s": harness.images_inside(recs, window_s) / window_s,
            "latency_p95_ms": 1e3 * harness.percentile(lat, 95)}
