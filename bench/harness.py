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
- ``loops/<loop>.py``: ``run`` drives the front through one window,
  ``window_length`` says how long that window was, and ``end_to_end`` turns
  its requests into the end-to-end metrics it measures.
- ``arrivals/<process>.py``: ``due(mix, rng, seconds)``, the times at which
  requests fall due.
- ``metrics/<metric>.py``, else ``metrics/<metric up to its first dot>.py``:
  a reader ``read(ctx) -> float | None`` of one per-layer metric.
- ``programs/<family>.py``, by the configuration's ``family``: the program
  side of one family of configurations.  ``config(conf)`` and ``params(conf,
  cfg, raw)`` put the configuration and the reference's seeded weights in the
  program's own types; ``front(cfg, max_batch, interpret, span, clock)`` is
  the system under test that the mix's loop drives; ``pool(mix, conf, seed)``
  makes the seeded inputs and ``warm(front, pool)`` runs every shape they
  reach; ``control(conf, ref)`` is the reference one precision step down,
  called as the program's calls are; ``verify(conf, ref, raw, pool, seen)``
  gives each number compared with its limit; ``describe(conf, batch, peak)``
  gives the lines a traced run logs about the work.

A front keeps the seed's weights in ``params`` and records what the window
did: ``dispatches``, the program's calls, each with its host time ``t`` and
the request ids ``uids`` it served; ``answers`` by request id; ``flushes``
as ``(start, seconds)``.  ``wrap``, when set, is ``wrap(key, fn) -> fn`` and
stands in for each of the program's calls ``fn(params, inputs)``: the control
and the planted faults use it.  The shared loops (``loops/open.py``,
``loops/closed.py``) drive a front by three more names: ``waiting``, true
while inputs wait; ``send(rec, pool, t, by_uid)``, which submits a request's
pool inputs at ``t`` and files their request ids in ``rec.uids`` and
``by_uid``; and ``serve(by_uid, t0)``, one pass over what waits, which marks
each request ``done`` once all its inputs are answered.  A family whose
front lacks them brings its own loop.  From the program the benchmark takes
the system under test only; the check compares what the timed path returned.
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
    """The file at ``path`` as module ``name``, listed in ``sys.modules`` as
    an import would list it (a dataclass in it looks itself up there)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
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


def load_program(family: str, root: Path = BENCH) -> types.ModuleType:
    """``programs/<family>.py``: the program side of one family."""
    return _module(Path(root) / "programs" / f"{family}.py", f"bench_program_{family}")


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
    if not (src / "repro").is_dir():
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


def row_err(got, want) -> float:
    """``max |got - want| / max |want|`` of one image's logits."""
    return float(np.abs(got - want).max() / np.abs(want).max())


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
        self.program = load_program(self.conf["family"], root)
        self.clock = time.perf_counter
        self.span = (jax.profiler.TraceAnnotation if trace
                     else lambda name: nullcontext())
        self.cfg = self.program.config(self.conf)
        self._raw = jax.jit(lambda k: self.ref.weights(self.conf, k))
        self._params = jax.jit(lambda k: self.program.params(
            self.conf, self.cfg, self.ref.weights(self.conf, k)))
        self.batcher = self.program.front(self.cfg, self.mix["max_batch"],
                                          interpret, self.span, self.clock)
        self.seed = None
        self._control = self.program.control(self.conf, self.ref) if control else None

    def load(self, seed: int) -> None:
        """The seed's weights and images; with ``control``, the control
        takes the program's place in every call."""
        import jax

        self.seed = seed
        key = seed_key(seed)
        self.batcher.params = jax.block_until_ready(self._params(key))
        self.pool = self.program.pool(self.mix, self.conf, seed)
        if self._control is not None:
            raw, f = self._raw(key), self._control
            self.batcher.wrap = lambda _, fn: (lambda params, x: f(raw, x))

    def free(self) -> None:
        """Drop the program's weights, so the reference runs without them."""
        self.batcher.params = None
        gc.collect()

    def warm(self) -> None:
        """Every shape the pool reaches, through the timed path."""
        self.program.warm(self.batcher, self.pool)

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
        """The reference over the pool, then every answer compared."""
        raw = self._raw(seed_key(self.seed))
        return self.program.verify(self.conf, self.ref, raw, self.pool, seen)


def read_per_layer(bench: dict, name: str, ctx, root: Path = BENCH) -> dict:
    out = {}
    for m in cell_metrics(bench, name, "per_layer"):
        v = load_reader(m["name"], root)(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def reader_context(sess: Session, seen, summary, device_kind: str):
    """What a per-layer reader sees: the cell's ``conf`` and ``mix``, the
    chip's ``peak``, the trace's ``summary`` as ``trace``, the window's whole
    record as ``seen`` (its requests, the front's calls and answers), and
    counts taken from it: ``calls`` and ``rows`` inside the window, and the
    ``images`` answered there."""
    return types.SimpleNamespace(
        conf=sess.conf, mix=sess.mix, peak=work.peak(device_kind),
        trace=summary, seen=seen, calls=seen.calls, rows=seen.rows,
        max_batch=sess.mix["max_batch"], window_s=seen.window_s,
        images=images_inside(seen.recs, seen.window_s))


def ms_stats(xs) -> str:
    if not xs:
        return "none"
    return (f"p50 {1e3 * percentile(xs, 50):.3f} p99 {1e3 * percentile(xs, 99):.3f} "
            f"max {1e3 * max(xs):.3f}")
