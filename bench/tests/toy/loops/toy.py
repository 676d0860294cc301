"""One client that sends a request, flushes the front and waits for it; the
window runs until the last request sent inside ``seconds`` returns.
Reported: ``images_per_s``, the inputs answered inside the window over it."""
from bench import harness, traffic


def run(b, pool, mix, seed, seconds, *, clock, span, root):
    picks = traffic.closed_requests(mix, seed)
    recs = []
    t0 = clock()
    while clock() - t0 < seconds:
        now = clock() - t0
        rec = harness.Rec(now, next(picks), sent=now)
        rec.uids = [b.submit(pool[j]) for j in rec.ids]
        b.flush()
        rec.done = clock() - t0
        b.flushes.append((now, rec.done - now))
        recs.append(rec)
    return recs, t0


def window_length(recs, seconds):
    return max((r.done for r in recs), default=seconds)


def end_to_end(recs, window_s):
    return {"images_per_s": harness.images_inside(recs, window_s) / window_s}
