"""The program side of the ``toy`` family: a front that scores vectors with
one dense layer, one padded batch per call.  It shares nothing with the
``cnn`` family: no image, no conv stage, no batcher of the program."""
import types

import numpy as np

from bench import harness, traffic


def config(conf):
    return (conf["width"], conf["classes"])


def params(conf, cfg, raw):
    return {"w": raw["w"]}


class Front:
    def __init__(self, cfg, max_batch, clock):
        import jax

        self.width, self.max_batch, self.clock = cfg[0], max_batch, clock
        self.params, self.wrap = None, None
        self.dispatches, self.flushes, self.answers = [], [], {}
        self.queue, self.next_uid = [], 0
        self.fn = jax.jit(lambda p, x: jax.numpy.dot(
            x, p["w"], precision=jax.lax.Precision.HIGHEST))

    @property
    def waiting(self):
        return bool(self.queue)

    def submit(self, x):
        self.next_uid += 1
        self.queue.append((self.next_uid, x))
        return self.next_uid

    def send(self, rec, pool, t, by_uid):
        """What the shared loops call to submit a request's inputs."""
        rec.sent = t
        for j in rec.ids:
            uid = self.submit(pool[j])
            rec.uids.append(uid)
            by_uid[uid] = rec

    def serve(self, by_uid, t0):
        """What the shared loops call to serve what waits."""
        uids, start = [u for u, _ in self.queue], self.clock() - t0
        self.flush()
        t = self.clock() - t0
        self.flushes.append((start, t - start))
        for u in uids:
            rec = by_uid[u]
            if all(v in self.answers for v in rec.uids):
                rec.done = t

    def flush(self):
        fn = self.fn if self.wrap is None else self.wrap("toy", self.fn)
        while self.queue:
            chunk, self.queue = self.queue[: self.max_batch], self.queue[self.max_batch:]
            x = np.zeros((self.max_batch, self.width), np.float32)
            for j, (_, v) in enumerate(chunk):
                x[j] = v
            t = self.clock()
            out = np.asarray(fn(self.params, x))
            uids = [u for u, _ in chunk]
            self.dispatches.append(types.SimpleNamespace(t=t, uids=uids))
            for j, u in enumerate(uids):
                self.answers[u] = out[j]


def front(cfg, max_batch, interpret, span, clock):
    return Front(cfg, max_batch, clock)


def pool(mix, conf, seed):
    g = traffic.rng(seed, 1)
    return list(g.standard_normal((mix["pool"], conf["width"]), dtype=np.float32))


def warm(b, pool):
    for v in pool[: b.max_batch]:
        b.submit(v)
    b.flush()
    b.dispatches.clear()
    b.answers.clear()
    b.flushes.clear()


def control(conf, ref):
    import jax

    return jax.jit(lambda w, x: ref.forward(conf, w, x, precision="bf16"))


def verify(conf, ref, raw, pool, seen):
    want = np.asarray(ref.forward(conf, raw, np.stack(pool)))
    err = 0.0
    for r in seen.recs:
        if r.done is not None:
            for u, pid in zip(r.uids, r.ids):
                e = harness.row_err(seen.answers[u], want[pid])
                err = e if not e <= err else err
    values = {"score_err": err, "unanswered": sum(r.done is None for r in seen.recs)}
    return {k: {"value": v, "limit": conf["limits"][k]} for k, v in values.items()}


def describe(conf, batch, peak):
    return [f"toy: {2 * batch * conf['width'] * conf['classes']} FLOPs a call"]
