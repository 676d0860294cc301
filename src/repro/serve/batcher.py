"""Continuous batcher for mixed LM + CNN traffic.

LM decode slots live in :class:`repro.serve.engine.Engine`; this module adds
the image-classification side and the loop that serves both:

- :class:`CnnBatcher` queues variable-sized images, rounds each up to an
  H×W *shape bucket* (host-side zero-pad), and flushes every bucket through
  ONE jitted classify closure per bucket.  Inside the jit the bucket pads up
  to the model's native ``cfg.in_chw`` — the fused conv2d stack has a fixed
  input geometry, so bucketing caps closure count while arbitrary (smaller)
  images still classify.  Zero-padding is exact for the PASM conv stack:
  SAME/VALID conv over zero rows adds zero patches, and the classifier head
  sees the same feature map as a natively-sized zero-extended image.
- :class:`MixedBatcher` interleaves one engine tick (admit + decode every
  live LM slot) with a CNN flush per service tick, so both traffic classes
  share the process continuously — neither waits for the other to drain.

Metrics ride the same :class:`repro.serve.metrics.Metrics` rollup (img/s,
p50/p99 latency and queue wait, batch fill) using ``"cnn-<n>"`` uids so a
shared Metrics instance never collides with the engine's integer LM uids.
A classifier has no first token, so CNN requests carry no ttft.

``CnnBatcher.flush`` records profiler spans (``jax.profiler.TraceAnnotation``,
a microsecond or two of host time each while no trace is being taken):
``cnn.flush`` around the whole flush and, for each classify call in turn,
``cnn.pad`` (host zero-pad into the batch buffer), ``cnn.put`` (host to
device), ``cnn.call`` (the classify closure), ``cnn.argmax`` (dispatch of
the class argmax) and ``cnn.readback`` (the blocking copy of the classes to
the host).  Each per-call span carries ``call`` (the running call index)
and ``rows`` (the images it carries).
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.metrics import Metrics

__all__ = ["CnnRequest", "CnnBatcher", "MixedBatcher", "default_hw_buckets"]


def default_hw_buckets(native_hw: Tuple[int, int]) -> List[Tuple[int, int]]:
    """Power-of-two-ish H×W ladder up to (and including) the native size."""
    H, W = native_hw
    ladder = []
    h = 8
    while h < max(H, W):
        ladder.append((min(h, H), min(h, W)))
        h *= 2
    ladder.append((H, W))
    return sorted(set(ladder))


@dataclasses.dataclass
class CnnRequest:
    uid: str
    image: np.ndarray  # (C, H, W) float32
    bucket: Tuple[int, int]
    cls: Optional[int] = None
    done: bool = False
    stuck: bool = False


class CnnBatcher:
    """Shape-bucketed image classification through the fused conv2d stack."""

    def __init__(
        self,
        cfg,  # CNNConfig
        params,
        *,
        max_batch: int = 8,
        buckets: Optional[List[Tuple[int, int]]] = None,
        metrics: Optional[Metrics] = None,
        clock: Callable[[], float] = time.perf_counter,
        interpret: Optional[bool] = None,
    ):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        C, H, W = cfg.in_chw
        self.native_hw = (H, W)
        self.buckets = sorted(buckets or default_hw_buckets((H, W)))
        self.metrics = metrics if metrics is not None else Metrics(clock=clock)
        self.interpret = interpret
        self.waiting: deque[CnnRequest] = deque()
        self._n = 0
        self._classify: Dict[Tuple[int, int], Callable] = {}

    def _bucket_for(self, h: int, w: int) -> Tuple[int, int]:
        for bh, bw in self.buckets:
            if h <= bh and w <= bw:
                return (bh, bw)
        raise ValueError(
            f"image {h}x{w} exceeds native input {self.native_hw} "
            f"(buckets: {self.buckets})"
        )

    def _classify_fn(self, bucket: Tuple[int, int]) -> Callable:
        if bucket not in self._classify:
            from repro.models import cnn as _cnn

            cfg, (bh, bw) = self.cfg, bucket
            C, (H, W) = cfg.in_chw[0], self.native_hw

            def f(params, images):  # (max_batch, C, bh, bw) → (max_batch, classes)
                x = jnp.pad(images, ((0, 0), (0, 0), (0, H - bh), (0, W - bw)))
                if cfg.layout == "NHWC":
                    x = jnp.transpose(x, (0, 2, 3, 1))
                return _cnn.forward(params, x, cfg, interpret=self.interpret)

            self._classify[bucket] = jax.jit(f)
        return self._classify[bucket]

    # -- request lifecycle ---------------------------------------------------

    def submit(self, image: np.ndarray, *, slo_s: Optional[float] = None) -> CnnRequest:
        image = np.asarray(image, np.float32)
        if image.ndim != 3 or image.shape[0] != self.cfg.in_chw[0]:
            raise ValueError(f"expected (C={self.cfg.in_chw[0]}, H, W), got {image.shape}")
        self._n += 1
        r = CnnRequest(
            uid=f"cnn-{self._n}", image=image,
            bucket=self._bucket_for(image.shape[1], image.shape[2]),
        )
        self.waiting.append(r)
        self.metrics.submit(r.uid, "cnn", slo_s=slo_s)
        return r

    def flush(self) -> List[CnnRequest]:
        """Serve every waiting image: group by bucket, pad, classify.

        Each classify call counts into the metrics' ``cnn_calls``,
        ``cnn_images`` and ``cnn_rows`` (the batch rows it carried, padding
        included)."""
        with jax.profiler.TraceAnnotation("cnn.flush"):
            by_bucket: Dict[Tuple[int, int], List[CnnRequest]] = {}
            while self.waiting:
                r = self.waiting.popleft()
                by_bucket.setdefault(r.bucket, []).append(r)
            served: List[CnnRequest] = []
            for bucket, reqs in by_bucket.items():
                for i in range(0, len(reqs), self.max_batch):
                    chunk = reqs[i : i + self.max_batch]
                    self._classify_chunk(bucket, chunk)
                    served.extend(chunk)
        return served

    def _classify_chunk(self, bucket: Tuple[int, int], chunk: List[CnnRequest]):
        """One classify call: pad, put, call, argmax, read back, each its
        own span."""
        (bh, bw), m = bucket, self.metrics
        span = functools.partial(jax.profiler.TraceAnnotation,
                                 call=m.counters.get("cnn_calls", 0),
                                 rows=len(chunk))
        with span("cnn.pad"):
            imgs = np.zeros((self.max_batch, self.cfg.in_chw[0], bh, bw),
                            np.float32)
            for j, r in enumerate(chunk):
                h, w = r.image.shape[1:]
                imgs[j, :, :h, :w] = r.image
                m.mark_admit(r.uid)
        with span("cnn.put"):
            x = jnp.asarray(imgs)
        with span("cnn.call"):
            logits = self._classify_fn(bucket)(self.params, x)
        with span("cnn.argmax"):
            cls = jnp.argmax(logits, axis=-1)
        with span("cnn.readback"):
            cls = np.asarray(cls)
        m.incr("cnn_calls")
        m.incr("cnn_images", len(chunk))
        m.incr("cnn_rows", self.max_batch)
        for j, r in enumerate(chunk):
            r.cls = int(cls[j])
            r.done = True
            m.mark_done(r.uid, 1)


class MixedBatcher:
    """One service loop over both traffic classes: every tick runs one LM
    engine step (continuous admit + batched decode) and one CNN flush."""

    def __init__(self, engine, cnn: Optional[CnnBatcher] = None):
        self.engine = engine
        self.cnn = cnn

    @property
    def drained(self) -> bool:
        # engine.busy covers live slots, the queue, AND pending retries —
        # a backoff-delayed retry keeps the loop ticking until it resolves
        lm_done = not self.engine.busy
        cnn_done = self.cnn is None or not self.cnn.waiting
        return lm_done and cnn_done

    def tick(self):
        self.engine.step()
        if self.cnn is not None:
            self.cnn.flush()

    def run_until_drained(self, max_ticks: int = 1000, *, strict: bool = True) -> int:
        t = 0
        while not self.drained and t < max_ticks:
            self.tick()
            t += 1
        if not self.drained:
            msg = f"MixedBatcher: traffic undrained after {max_ticks} ticks"
            if strict:
                raise RuntimeError(msg)
            warnings.warn(msg, RuntimeWarning, stacklevel=2)
        return t
