"""The program side of the ``cnn`` family: sequential weight-shared CNNs
served by the program's ``CnnBatcher``.

A configuration of this family gives ``in_chw``, ``convs`` (``c_out``, ``k``,
``stride`` per stage), ``pools``, ``padding``, ``layout``, ``classes``,
``bins``, ``packed``, ``impl`` and ``mesh_shape``; its reference's
``forward(conf, w, x, precision)`` gives one logits row per image.

The front is a ``CnnBatcher`` that records each classify call on dispatch,
so the check compares the logits the timed path itself returned.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import harness, traffic, work

REF_BLOCK = 16  # images per reference call


@dataclasses.dataclass
class Dispatch:
    """One call of a classify closure, as the front saw it."""
    bucket: tuple
    t: float  # host clock at dispatch
    logits: object  # what the closure returned, on the host once its flush ends
    uids: list = dataclasses.field(default_factory=list)  # rows it served


def attribute(served: list, dispatches: list, max_batch: int) -> None:
    """Give each dispatch the requests it served.

    ``flush`` serves each bucket's requests in chunks of ``max_batch``, one
    closure call per chunk, and returns them in call order.
    """
    i = 0
    for d in dispatches:
        n = 0
        while (i + n < len(served) and n < max_batch
               and served[i + n].bucket == d.bucket):
            n += 1
        d.uids = [r.uid for r in served[i:i + n]]
        i += n
    if i != len(served):
        raise RuntimeError(f"{len(served) - i} served requests match no "
                           "classify call")


def config(conf: dict):
    """The configuration as the program's ``CNNConfig``."""
    from repro.configs.alexnet_conv import CNNConfig
    from repro.core.conv import Conv2D

    layers, c = [], conf["in_chw"][0]
    for s in conf["convs"]:
        layers.append(Conv2D(k=s["k"], c_in=c, c_out=s["c_out"],
                             stride=s["stride"], relu=True))
        c = s["c_out"]
    mesh = conf["mesh_shape"]
    return CNNConfig(
        name=conf["name"], in_chw=tuple(conf["in_chw"]), layers=tuple(layers),
        pools=tuple(conf["pools"]), classes=conf["classes"],
        bins=conf["bins"], impl=conf["impl"], padding=conf["padding"],
        layout=conf["layout"], packed=conf["packed"],
        mesh_shape=tuple(mesh) if mesh else None)


def params(conf: dict, cfg, raw: dict) -> dict:
    """The seeded weights in the program's container, packed as it serves."""
    from repro.core.conv import ConvParams

    convs = []
    for layer in raw["conv"]:
        p = ConvParams.shared(layer["idx"], layer["codebook"], bias=layer["bias"])
        convs.append(p.pack(layout=cfg.layout) if conf["packed"] else p)
    return {"conv": convs, "head": dict(raw["head"])}


def front(cfg, max_batch: int, interpret, span, clock):
    """A ``CnnBatcher`` whose classify calls are recorded on dispatch.

    Its weights are set per seed (``params``).  ``wrap``, when set, is
    ``wrap(bucket, fn) -> fn`` and stands in for the program's closure: the
    control and the planted faults use it.  ``dispatches``, ``flushes`` and
    ``answers`` collect what the service loops saw, through ``send`` and
    ``serve``.
    """
    from repro.serve.batcher import CnnBatcher

    class Recording(CnnBatcher):
        def __init__(self):
            super().__init__(cfg, None, max_batch=max_batch, interpret=interpret)
            self.wrap = None
            self.dispatches, self.flushes, self.answers = [], [], {}

        def _classify_fn(self, bucket):
            fn = super()._classify_fn(bucket)
            if self.wrap is not None:
                fn = self.wrap(bucket, fn)

            def dispatch(params, images):
                t = clock()
                with span("bench.dispatch"):
                    out = fn(params, images)
                out.copy_to_host_async()
                self.dispatches.append(Dispatch(bucket, t, out))
                return out
            return dispatch

        def send(self, rec, pool: list, t: float, by_uid: dict) -> None:
            rec.sent = t
            for j in rec.ids:
                uid = self.submit(pool[j]).uid
                rec.uids.append(uid)
                by_uid[uid] = rec

        def serve(self, by_uid: dict, t0: float) -> None:
            """One ``flush``: every waiting image classified; a request is
            done when all of its images are.  The logits the flush's calls
            returned move to the host (their copy began at dispatch), so the
            device memory the window holds is the program's alone."""
            k, start = len(self.dispatches), clock() - t0
            with span("bench.flush"):
                served = self.flush()
            t = clock() - t0
            self.flushes.append((start, t - start))
            attribute(served, self.dispatches[k:], self.max_batch)
            for d in self.dispatches[k:]:  # the device keeps none of the window's logits
                d.logits = np.asarray(d.logits)
            for r in served:
                self.answers[r.uid] = r.cls
                rec = by_uid[r.uid]
                if all(u in self.answers for u in rec.uids):
                    rec.done = t

    return Recording()


def pool(mix: dict, conf: dict, seed: int) -> list:
    """The run's distinct images, with the configuration's channels."""
    return traffic.make_pool(mix, conf["in_chw"][0], seed)


def warm(b, pool: list) -> None:
    """Every bucket the pool reaches, through the timed path."""
    for chunk in (pool, pool[: b.max_batch]):
        for im in chunk:
            b.submit(im)
        b.flush()
    b.dispatches.clear()
    b.answers.clear()
    b.flushes.clear()


def control(conf: dict, ref):
    """The reference at three bfloat16 passes, padding as the buckets do."""
    import jax
    import jax.numpy as jnp

    _, H, W = conf["in_chw"]
    return jax.jit(lambda w, x: ref.forward(conf, w, jnp.pad(
        x, ((0, 0), (0, 0), (0, H - x.shape[2]), (0, W - x.shape[3]))),
        precision="bf16x3"))


def reference_logits(ref, conf: dict, raw: dict, pool: list, block: int = REF_BLOCK):
    """The reference's logits of every pool image, zero-extended to the
    native size (what the batcher's buckets pad to), ``block`` at a time."""
    import jax
    import jax.numpy as jnp

    C, H, W = conf["in_chw"]
    f = jax.jit(lambda w, x: ref.forward(conf, w, x))
    out = []
    for i in range(0, len(pool), block):
        x = np.zeros((block, C, H, W), np.float32)
        for j, im in enumerate(pool[i:i + block]):
            x[j, :, : im.shape[1], : im.shape[2]] = im
        out.append(np.asarray(f(raw, jnp.asarray(x))))
    return np.concatenate(out)[: len(pool)]


def check(recs: list, dispatches: list, answers: dict, ref_logits, limits: dict) -> dict:
    """Each number compared, with its limit.

    - ``logit_err``: over every request due in the window, the largest
      ``max |served logit - reference logit| / max |reference logit|`` of an
      image, on the logits the classify closure returned.
    - ``class_mismatch``: answers that are not the argmax of those logits.
    - ``unanswered``: requests due in the window that never got an answer.
    """
    row = {}
    for d in dispatches:
        if d.uids:
            logits = np.asarray(d.logits)
            for j, u in enumerate(d.uids):
                row[u] = logits[j]
    err, mismatch = 0.0, 0
    for r in recs:
        if r.done is None:
            continue
        for u, pid in zip(r.uids, r.ids):
            got, want = row[u], ref_logits[pid]
            e = harness.row_err(got, want)
            err = e if not e <= err else err  # a nan sticks
            mismatch += int(answers[u] != int(np.argmax(got)))
    values = {"logit_err": err, "class_mismatch": mismatch,
              "unanswered": sum(r.done is None for r in recs)}
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}


def verify(conf: dict, ref, raw: dict, pool: list, seen) -> dict:
    """Reference logits for the pool, then every answer compared."""
    want = reference_logits(ref, conf, raw, pool)
    return check(seen.recs, seen.dispatches, seen.answers, want, conf["limits"])


def describe(conf: dict, batch: int, peak: dict) -> list:
    """Per conv stage, its FLOPs per byte at ``batch`` and what bounds it."""
    return [f"conv stage c_out={st['c_out']} k={st['k']}: {ai:.1f} "
            f"FLOP/byte, {bound}-bound"
            for st, (ai, bound) in zip(conf["convs"],
                                       work.conv_bounds(conf, batch, peak))]
