"""One client: it sends its next request when the previous one returns, and
its last is the one sent before ``seconds`` had passed.

The window runs until that last request returns.  Reported:
``images_per_s``, the images answered inside the window over the window.
"""
from bench import harness, traffic


def run(b, pool, mix, seed, seconds, *, clock, span, root) -> tuple:
    requests = traffic.closed_requests(mix, seed)
    recs, by_uid = [], {}
    t0 = clock()
    while clock() - t0 < seconds:
        now = clock() - t0
        rec = harness.Rec(now, next(requests))
        recs.append(rec)
        with span("bench.submit"):
            b.send(rec, pool, now, by_uid)
        b.serve(by_uid, t0)
    return recs, t0


def window_length(recs: list, seconds: float) -> float:
    return max((r.done for r in recs if r.done is not None), default=seconds)


def end_to_end(recs: list, window_s: float) -> dict:
    return {"images_per_s": harness.images_inside(recs, window_s) / window_s}
