"""The benchmark's machinery: discovery, set-up, the check.

Everything that belongs to one configuration, traffic mix, service loop,
arrival process or per-layer metric is a file found by its name, so a later
change adds files and edits none:

- ``configs/<config>.json``: the sizes as run, ``source``, ``reduced``,
  ``assumed``, the limits of the check, and ``reference``, the file beside it
  that holds the plain reference (``weights`` and ``forward``).
- ``traffic/<mix>.json``: data only, read by ``traffic.py``.  It names its
  service loop (``loop``) and, for an open loop, its arrival process
  (``arrivals``).
- ``loops/<loop>.py``: ``run`` drives the batcher through one window,
  ``window_length`` says how long that window was, and ``end_to_end`` turns
  its requests into the end-to-end metrics it measures.
- ``arrivals/<process>.py``: ``due(mix, rng, seconds)``, the times at which
  requests fall due.
- ``metrics/<metric>.py``, else ``metrics/<metric up to its first dot>.py``:
  a reader ``read(ctx) -> float | None`` of one per-layer metric.

From the program the benchmark takes the system under test only: the
``CnnBatcher`` front, and the weight container it packs the seeded weights
into.  It reads the classify closure's logits by wrapping the closure, so the
check compares what the timed path itself returned.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import sys
import time
import types
from contextlib import nullcontext
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from bench import traffic, work

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
GRACE_S = 60.0  # how long a run waits, past the window, for requests due in it
REF_BLOCK = 16  # images per reference call


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# -- discovery ---------------------------------------------------------------


def load_benchmark(path: Path = REPO / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r}; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def load_config(name: str, root: Path = BENCH) -> dict:
    return json.loads((Path(root) / "configs" / f"{name}.json").read_text())


def load_traffic(name: str, root: Path = BENCH) -> dict:
    return json.loads((Path(root) / "traffic" / f"{name}.json").read_text())


def _module(path: Path, name: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_loop(name: str, root: Path = BENCH) -> types.ModuleType:
    """``loops/<name>.py``: one kind of service loop."""
    return _module(Path(root) / "loops" / f"{name}.py", f"bench_loop_{name}")


def load_arrivals(name: str, root: Path = BENCH) -> Callable:
    """``arrivals/<name>.py``'s ``due``: one arrival process."""
    return _module(Path(root) / "arrivals" / f"{name}.py", f"bench_arrivals_{name}").due


def load_reference(conf: dict, root: Path = BENCH) -> types.ModuleType:
    path = Path(root) / "configs" / conf["reference"]
    return _module(path, f"bench_reference_{path.stem}")


def load_reader(metric: str, root: Path = BENCH) -> Callable:
    """``metrics/<metric>.py``, else ``metrics/<metric up to its first dot>.py``."""
    d = Path(root) / "metrics"
    for stem in (metric, metric.split(".", 1)[0]):
        if (d / f"{stem}.py").is_file():
            return _module(d / f"{stem}.py", f"bench_metric_{stem}").read
    raise FileNotFoundError(f"no reader for per-layer metric {metric!r} in {d}")


def cell_metrics(bench: dict, name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports."""
    return [m for m in bench[kind] if name in m.get("workloads", [name])]


def find_program(repo: Path = REPO) -> Path:
    """The program under test, ``<checkout>/src``; it is not part of the
    benchmark, so a tree holding only the benchmark cannot run."""
    src = Path(repo) / "src"
    if not (src / "repro" / "serve" / "batcher.py").is_file():
        raise FileNotFoundError(f"program not found under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    return src


def seed_key(seed: int):
    """A PRNG key from all 64 bits of the seed."""
    import jax

    s = seed % 2**64
    return jax.random.wrap_key_data(
        np.array([s >> 32, s & 0xFFFFFFFF], np.uint32), impl="threefry2x32")


# -- the program's side ------------------------------------------------------


def program_config(conf: dict):
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


def program_params(conf: dict, cfg, raw: dict) -> dict:
    """The seeded weights in the program's container, packed as it serves."""
    from repro.core.conv import ConvParams

    convs = []
    for layer in raw["conv"]:
        p = ConvParams.shared(layer["idx"], layer["codebook"], bias=layer["bias"])
        convs.append(p.pack(layout=cfg.layout) if conf["packed"] else p)
    return {"conv": convs, "head": dict(raw["head"])}


@dataclasses.dataclass
class Dispatch:
    """One call of a classify closure, as the harness saw it."""
    bucket: tuple
    t: float  # host clock at dispatch
    logits: object  # what the closure returned, on the host once its flush ends
    uids: list = dataclasses.field(default_factory=list)  # rows it served


def make_batcher(cfg, max_batch: int, interpret, span, clock):
    """A ``CnnBatcher`` whose classify calls are recorded on dispatch.

    Its weights are set per seed (``params``).  ``wrap``, when set, is
    ``wrap(bucket, fn) -> fn`` and stands in for the program's closure: the
    control and the planted faults use it.  ``dispatches``, ``flushes`` and
    ``answers`` collect what the service loops saw.
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

    return Recording()


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


# -- what the service loops share ---------------------------------------------


@dataclasses.dataclass
class Rec:
    """One request: when it was due, sent and answered (seconds from the
    window's start), and the pool images it carries."""
    due: float
    ids: list
    sent: Optional[float] = None
    done: Optional[float] = None
    uids: list = dataclasses.field(default_factory=list)


def serve(b, recs_by_uid: dict, t0: float, clock, span) -> None:
    """One ``flush``: every waiting image classified; a request is done when
    all of its images are.  The logits the flush's calls returned move to the
    host (their copy began at dispatch), so the device memory the window
    holds is the program's alone."""
    k, start = len(b.dispatches), clock() - t0
    with span("bench.flush"):
        served = b.flush()
    t = clock() - t0
    b.flushes.append((start, t - start))
    attribute(served, b.dispatches[k:], b.max_batch)
    for d in b.dispatches[k:]:  # the device keeps none of the window's logits
        d.logits = np.asarray(d.logits)
    for r in served:
        b.answers[r.uid] = r.cls
        rec = recs_by_uid[r.uid]
        if all(u in b.answers for u in rec.uids):
            rec.done = t


def submit(b, rec: Rec, pool: list, t: float, recs_by_uid: dict) -> None:
    rec.sent = t
    for j in rec.ids:
        uid = b.submit(pool[j]).uid
        rec.uids.append(uid)
        recs_by_uid[uid] = rec


def images_inside(recs: list, window_s: float) -> int:
    """Images of the requests answered inside the window."""
    return sum(len(r.ids) for r in recs if r.done is not None and r.done <= window_s)


def percentile(xs, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]); nan on empty input.
    Copied from ``repro.serve.metrics.percentile``."""
    xs = sorted(x for x in xs if not math.isnan(x))
    if not xs:
        return math.nan
    rank = max(0, min(len(xs) - 1, math.ceil(q / 100.0 * len(xs)) - 1))
    return xs[rank]


# -- the check ---------------------------------------------------------------


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


def row_err(got, want) -> float:
    """``max |got - want| / max |want|`` of one image's logits."""
    return float(np.abs(got - want).max() / np.abs(want).max())


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
            e = row_err(got, want)
            err = e if not e <= err else err  # a nan sticks
            mismatch += int(answers[u] != int(np.argmax(got)))
    values = {"logit_err": err, "class_mismatch": mismatch,
              "unanswered": sum(r.done is None for r in recs)}
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}


def passed(numbers: dict) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers.values())


# -- one cell ----------------------------------------------------------------


class CompileCounter:
    """Counts tracing, lowering and compiling events while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.on, self.n, self.what = False, 0, []

    def __call__(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.n += 1
            self.what.append(f"{event.rsplit('/', 1)[-1]}:{kw.get('fun_name', '?')}")

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)


class Session:
    """One cell's program on the device: set up once, measured per seed.

    ``load(seed)`` swaps in that seed's weights and images without changing
    a shape, so the closures compiled by ``warm`` serve every seed.
    """

    def __init__(self, bench: dict, name: str, *, interpret=None, trace=False,
                 control=False, root: Path = BENCH):
        import jax

        self.bench, self.root, self.trace = bench, Path(root), trace
        self.spec = workload(bench, name)
        self.conf = load_config(self.spec["config"], root)
        self.mix = load_traffic(self.spec["traffic"], root)
        self.loop = load_loop(self.mix["loop"], root)
        self.ref = load_reference(self.conf, root)
        self.clock = time.perf_counter
        self.span = (jax.profiler.TraceAnnotation if trace
                     else lambda name: nullcontext())
        self.cfg = program_config(self.conf)
        self._raw = jax.jit(lambda k: self.ref.weights(self.conf, k))
        self._params = jax.jit(lambda k: program_params(
            self.conf, self.cfg, self.ref.weights(self.conf, k)))
        self.batcher = make_batcher(self.cfg, self.mix["max_batch"], interpret,
                                    self.span, self.clock)
        self.seed = None
        self._control = self._control_fn() if control else None

    def _control_fn(self):
        """The reference at three bfloat16 passes, padding as the buckets do."""
        import jax
        import jax.numpy as jnp

        conf, ref = self.conf, self.ref
        _, H, W = conf["in_chw"]
        return jax.jit(lambda w, x: ref.forward(conf, w, jnp.pad(
            x, ((0, 0), (0, 0), (0, H - x.shape[2]), (0, W - x.shape[3]))),
            precision="bf16x3"))

    def load(self, seed: int) -> None:
        """The seed's weights and images; with ``control``, the control
        takes the program's place in every classify call."""
        import jax

        self.seed = seed
        key = seed_key(seed)
        self.batcher.params = jax.block_until_ready(self._params(key))
        self.pool = traffic.make_pool(self.mix, self.conf["in_chw"][0], seed)
        if self._control is not None:
            raw, f = self._raw(key), self._control
            self.batcher.wrap = lambda bucket, fn: (lambda params, x: f(raw, x))

    def free(self) -> None:
        """Drop the program's weights, so the reference runs without them."""
        self.batcher.params = None
        gc.collect()

    def warm(self) -> None:
        """Every bucket the pool reaches, through the timed path."""
        b = self.batcher
        for chunk in (self.pool, self.pool[: b.max_batch]):
            for im in chunk:
                b.submit(im)
            b.flush()
        b.dispatches.clear()
        b.answers.clear()
        b.flushes.clear()

    def measure(self, seconds: float) -> types.SimpleNamespace:
        """One window of the cell's traffic; returns what it saw."""
        b, mix = self.batcher, self.mix
        pauses, began = [], []

        def on_gc(phase, info):
            if phase == "start":
                began.append(time.perf_counter())
            elif began:
                pauses.append(time.perf_counter() - began.pop())

        gc.collect()
        gc.freeze()
        gc.callbacks.append(on_gc)
        try:
            with CompileCounter() as cc:
                cc.on = True
                with self.span("bench.run"):
                    recs, t0 = self.loop.run(b, self.pool, mix, self.seed, seconds,
                                             clock=self.clock, span=self.span,
                                             root=self.root)
                cc.on = False
        finally:
            gc.callbacks.remove(on_gc)
            gc.unfreeze()
        window_s = self.loop.window_length(recs, seconds)
        calls = [d for d in b.dispatches if 0.0 <= d.t - t0 <= window_s]
        late = [r.sent - r.due for r in recs if r.sent is not None]
        return types.SimpleNamespace(
            recs=recs, t0=t0, window_s=window_s, compiles=cc.n,
            compiled=cc.what, dispatches=list(b.dispatches),
            answers=dict(b.answers), calls=len(calls),
            rows=sum(len(d.uids) for d in calls), lateness=late,
            flushes=list(b.flushes), gc_pauses=pauses)

    def verify(self, seen) -> dict:
        """Reference logits for the pool, then every answer compared."""
        raw = self._raw(seed_key(self.seed))
        ref = reference_logits(self.ref, self.conf, raw, self.pool)
        return check(seen.recs, seen.dispatches, seen.answers, ref,
                     self.conf["limits"])


def read_per_layer(bench: dict, name: str, ctx, root: Path = BENCH) -> dict:
    out = {}
    for m in cell_metrics(bench, name, "per_layer"):
        v = load_reader(m["name"], root)(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def reader_context(sess: Session, seen, summary, device_kind: str):
    return types.SimpleNamespace(
        conf=sess.conf, mix=sess.mix, peak=work.peak(device_kind),
        trace=summary, calls=seen.calls, rows=seen.rows,
        max_batch=sess.mix["max_batch"], window_s=seen.window_s,
        images=images_inside(seen.recs, seen.window_s))


def ms_stats(xs) -> str:
    if not xs:
        return "none"
    return (f"p50 {1e3 * percentile(xs, 50):.3f} p99 {1e3 * percentile(xs, 99):.3f} "
            f"max {1e3 * max(xs):.3f}")
