"""Continuous-batching serve subsystem: exactness, scheduling, metrics.

The load-bearing guarantees:

- CONTINUOUS ADMISSION IS EXACT: a request admitted while other slots are
  mid-decode produces a token stream bit-identical to running its prompt
  alone (transformer AND encdec — the two padded-prefill families).  This
  holds because prefill is batch-of-one against a fresh cache in both runs,
  per-slot ``KVCache.pos`` masks every slot's reads/writes at its own
  position, and decode is row-parallel at a fixed batch width.
- The left-pad bug is gone: prompts are right-padded to a length bucket and
  prefill consumes ``lengths=`` — a short prompt in a mixed-length batch
  matches its solo run (pads are structurally unattendable, never real keys).
- Slot reuse never leaks the previous occupant's KV; admission under full
  slots is FCFS; per-request ``max_new`` is honored under concurrent load;
  ``run_until_drained`` raises (and marks requests stuck) instead of
  silently returning at ``max_ticks``.
"""
import functools

import jax
import numpy as np
import pytest

from repro.configs import get_cnn_config, get_config
from repro.models import api, cnn
from repro.serve.batcher import CnnBatcher, MixedBatcher
from repro.serve.engine import Engine
from repro.serve.metrics import Metrics, percentile
from repro.serve.scheduler import Scheduler, exact_bucket, pow2_bucket

KEY = jax.random.PRNGKey(0)


@functools.lru_cache(maxsize=None)
def _setup(arch: str):
    cfg = get_config(arch, smoke=True)
    model = api.get_model(cfg)
    return cfg, model.init_params(cfg, KEY)


@functools.lru_cache(maxsize=None)
def _setup_cnn():
    ccfg = get_cnn_config("alexnet", smoke=True)
    params = cnn.quantize(cnn.init_params(ccfg, KEY), ccfg)
    return ccfg, params


def _solo_out(cfg, params, prompt, max_new, *, slots=3, max_seq=48):
    eng = Engine(cfg, params, batch_slots=slots, max_seq=max_seq)
    r = eng.submit(prompt, max_new=max_new)
    eng.run_until_drained()
    return r.out


# ---------------------------------------------------------------------------
# tentpole acceptance: continuous admission is bit-exact (transformer, encdec)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["stablelm-3b", "whisper-tiny"])
def test_continuous_admission_bit_identical(arch):
    """With slots mid-decode, a newly admitted request's full output equals
    its solo (batch-of-one prefill) run, token for token."""
    cfg, params = _setup(arch)
    rng = np.random.default_rng(3)
    probe = rng.integers(0, cfg.vocab, size=5)
    want = _solo_out(cfg, params, probe, 8)

    eng = Engine(cfg, params, batch_slots=3, max_seq=48)
    others = [
        eng.submit(rng.integers(0, cfg.vocab, size=int(n)), max_new=12)
        for n in (4, 9)
    ]
    for _ in range(3):
        eng.step()
    assert all(not o.done for o in others)  # traffic genuinely concurrent
    r = eng.submit(probe, max_new=8)
    assert eng.live  # admitted while other slots are live: no wave gate
    eng.run_until_drained()
    assert r.out == want
    assert all(o.done for o in others)


def test_mid_decode_admission_does_not_disturb_live_slots():
    cfg, params = _setup("stablelm-3b")
    rng = np.random.default_rng(5)
    p1 = rng.integers(0, cfg.vocab, size=7)
    want = _solo_out(cfg, params, p1, 10)

    eng = Engine(cfg, params, batch_slots=2, max_seq=48)
    r1 = eng.submit(p1, max_new=10)
    for _ in range(4):
        eng.step()
    eng.submit(rng.integers(0, cfg.vocab, size=3), max_new=5)
    eng.run_until_drained()
    assert r1.out == want  # the live slot never saw the admission


def test_left_pad_regression_short_prompt_in_mixed_batch():
    """Satellite: a SHORT prompt admitted alongside longer ones (same tick,
    same pow2 bucket machinery) matches its solo run — pad positions are
    never attended (right-pad + lengths; pads ≥ lengths are invalid keys)."""
    cfg, params = _setup("stablelm-3b")
    rng = np.random.default_rng(9)
    short = rng.integers(0, cfg.vocab, size=3)
    want = _solo_out(cfg, params, short, 8)

    eng = Engine(cfg, params, batch_slots=3, max_seq=48)
    eng.submit(rng.integers(0, cfg.vocab, size=11), max_new=8)
    r = eng.submit(short, max_new=8)  # same admission tick as the long one
    eng.submit(rng.integers(0, cfg.vocab, size=8), max_new=8)
    eng.run_until_drained()
    assert r.out == want


# ---------------------------------------------------------------------------
# satellite: scheduler invariants under continuous batching
# ---------------------------------------------------------------------------


def test_slot_reuse_never_leaks_prior_kv():
    """More requests than slots: a request served in a REUSED slot matches
    its solo run (prefill grafts a fresh cache; no stale attention prefix)."""
    cfg, params = _setup("stablelm-3b")
    rng = np.random.default_rng(11)
    probe = rng.integers(0, cfg.vocab, size=6)
    want = _solo_out(cfg, params, probe, 6, slots=1)

    eng = Engine(cfg, params, batch_slots=1, max_seq=48)
    first = eng.submit(rng.integers(0, cfg.vocab, size=6), max_new=6)
    second = eng.submit(probe, max_new=6)  # queued; reuses slot 0 afterwards
    eng.run_until_drained()
    assert first.done and second.done
    assert second.slot == first.slot
    assert second.out == want


def test_admission_under_full_slots_is_fcfs():
    cfg, params = _setup("stablelm-3b")
    rng = np.random.default_rng(13)
    eng = Engine(cfg, params, batch_slots=2, max_seq=48)
    reqs = [eng.submit(rng.integers(0, cfg.vocab, size=4), max_new=3 + i)
            for i in range(5)]
    eng.run_until_drained()
    admits = [eng.metrics.timelines[r.uid].t_admit for r in reqs]
    assert admits == sorted(admits)  # FCFS: admitted in submit order
    assert all(r.done for r in reqs)


def test_per_request_max_new_honored():
    cfg, params = _setup("stablelm-3b")
    rng = np.random.default_rng(17)
    eng = Engine(cfg, params, batch_slots=3, max_seq=48)
    reqs = [eng.submit(rng.integers(0, cfg.vocab, size=5), max_new=n)
            for n in (2, 7, 4, 9)]
    eng.run_until_drained()
    assert [len(r.out) for r in reqs] == [2, 7, 4, 9]


def test_scheduler_unit_fcfs_buckets_release():
    class R:
        def __init__(self, uid, n):
            self.uid, self.prompt = uid, list(range(n))

    s = Scheduler(2, bucket_fn=pow2_bucket, max_seq=64)
    for uid, n in ((1, 3), (2, 9), (3, 5)):
        s.submit(R(uid, n))
    plans = s.admit()
    assert [(p.req.uid, p.slot, p.bucket) for p in plans] == [(1, 0, 8), (2, 1, 16)]
    assert s.queue_depth == 1 and not s.free_slots
    assert s.admit() == []  # full: uid 3 stays queued
    s.release(0)
    (p,) = s.admit()
    assert (p.req.uid, p.slot, p.bucket) == (3, 0, 8)
    assert exact_bucket(5) == 5 and pow2_bucket(17, hi=16) == 16
    with pytest.raises(ValueError):
        s.submit(R(9, 99))  # prompt longer than max_seq


# ---------------------------------------------------------------------------
# satellite: run_until_drained must not silently return undrained
# ---------------------------------------------------------------------------


def test_run_until_drained_raises_and_marks_stuck():
    cfg, params = _setup("stablelm-3b")
    rng = np.random.default_rng(19)
    eng = Engine(cfg, params, batch_slots=1, max_seq=48)
    r1 = eng.submit(rng.integers(0, cfg.vocab, size=4), max_new=40)
    r2 = eng.submit(rng.integers(0, cfg.vocab, size=4), max_new=40)  # queued
    with pytest.raises(RuntimeError, match="undrained"):
        eng.run_until_drained(max_ticks=3)
    assert r1.stuck and r2.stuck
    assert r1.status == "stuck"
    assert eng.metrics.rollup()["n_stuck"] == 2

    # non-strict: a REAL warning (assertable, filterable — not a bare print),
    # and the engine can still be driven to drain
    eng2 = Engine(cfg, params, batch_slots=1, max_seq=48)
    r = eng2.submit(rng.integers(0, cfg.vocab, size=4), max_new=30)
    with pytest.warns(RuntimeWarning, match="undrained"):
        t = eng2.run_until_drained(max_ticks=2, strict=False)
    assert t == 2 and r.stuck and not r.done
    eng2.run_until_drained()
    assert r.done


def test_submit_validates_total_kv_footprint():
    """Regression: prompt + max_new - 1 must fit max_seq — a long prompt
    with the default max_new used to decode past the KV cache end and
    silently wrap/clobber.  The boundary case (exact fit) must pass."""
    cfg, params = _setup("stablelm-3b")
    rng = np.random.default_rng(29)
    eng = Engine(cfg, params, batch_slots=1, max_seq=32)
    with pytest.raises(ValueError, match="wrap"):
        eng.submit(rng.integers(0, cfg.vocab, size=30), max_new=16)
    with pytest.raises(ValueError, match="wrap"):
        eng.submit(rng.integers(0, cfg.vocab, size=20), max_new=14)
    r = eng.submit(rng.integers(0, cfg.vocab, size=20), max_new=13)  # 20+13-1=32
    eng.run_until_drained()
    assert r.done and len(r.out) == 13


# ---------------------------------------------------------------------------
# metrics + mixed LM/CNN dataflow
# ---------------------------------------------------------------------------


def test_metrics_rollup_deterministic_clock():
    t = [0.0]
    m = Metrics(clock=lambda: t[0])
    for uid, (dt_admit, dt_done, slo) in enumerate(
        [(1.0, 5.0, 10.0), (2.0, 8.0, 4.0)], start=1
    ):
        t[0] = 0.0
        m.submit(uid, "lm", slo_s=slo)
        t[0] = dt_admit
        m.mark_admit(uid)
        m.mark_first(uid)
        t[0] = dt_done
        m.mark_done(uid, n_out=4)
    roll = m.rollup()
    assert roll["lm_p50_latency_s"] == 5.0 and roll["lm_p99_latency_s"] == 8.0
    assert roll["slo_met"] == 1 and roll["slo_missed"] == 1
    assert percentile([], 50) != percentile([], 50)  # nan on empty
    assert percentile([3.0, 1.0, 2.0], 50) == 2.0


def test_cnn_batcher_buckets_and_pad_equivalence():
    ccfg, cparams = _setup_cnn()
    b = CnnBatcher(ccfg, cparams, max_batch=3)
    rng = np.random.default_rng(2)
    img = rng.standard_normal((3, 14, 18)).astype(np.float32)
    native = np.zeros((3,) + ccfg.in_chw[1:], np.float32)
    native[:, :14, :18] = img
    r_small = b.submit(img)
    r_full = b.submit(native)
    assert r_small.bucket == (16, 32) or r_small.bucket[0] >= 14
    b.flush()
    assert r_small.done and r_full.done
    # bucket→native zero-pad inside the jit is the same image the native
    # request classifies: identical logits ⇒ identical class
    assert r_small.cls == r_full.cls
    with pytest.raises(ValueError):
        b.submit(rng.standard_normal((3, 64, 64)).astype(np.float32))


def test_cnn_flush_spans_and_counters(tmp_path):
    """``flush`` records one ``cnn.flush`` span holding, per classify call
    and in order, ``cnn.pad``/``put``/``call``/``argmax``/``readback``, each
    tagged with the call index and the images it carries; its counters roll
    up into the batch fill.  11 images at ``max_batch`` 8 make two calls."""
    import glob

    from jax.profiler import ProfileData

    ccfg, cparams = _setup_cnn()
    b = CnnBatcher(ccfg, cparams, max_batch=8)
    rng = np.random.default_rng(5)
    for _ in range(11):
        b.submit(rng.standard_normal(ccfg.in_chw).astype(np.float32))
    with jax.profiler.trace(str(tmp_path)):
        served = b.flush()
    assert len(served) == 11 and all(r.done for r in served)

    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    spans = sorted(
        ((e.start_ns, e.end_ns, e.name, dict(e.stats))
         for plane in ProfileData.from_file(path).planes
         if plane.name == "/host:CPU"
         for line in plane.lines for e in line.events
         if e.name.startswith("cnn.")),
        key=lambda s: s[0])
    (flush,) = [s for s in spans if s[2] == "cnn.flush"]
    phases = [s for s in spans if s[2] != "cnn.flush"]
    order = ["cnn.pad", "cnn.put", "cnn.call", "cnn.argmax", "cnn.readback"]
    assert [s[2] for s in phases] == order * 2
    assert all(flush[0] <= s[0] and s[1] <= flush[1] for s in phases)
    assert all(a[1] <= b[0] for a, b in zip(phases, phases[1:]))
    assert [s[3] for s in phases] == (
        [{"call": 0, "rows": 8}] * 5 + [{"call": 1, "rows": 3}] * 5)

    roll = b.metrics.rollup()
    assert b.metrics.counters["cnn_calls"] == 2
    assert b.metrics.counters["cnn_images"] == 11
    assert roll["cnn_batch_fill"] == 11 / 16
    assert roll["cnn_p50_queue_s"] >= 0 and roll["cnn_p99_queue_s"] >= 0
    assert "cnn_p50_ttft_s" not in roll  # a classifier has no first token


def test_mixed_lm_cnn_traffic_drains_both():
    cfg, params = _setup("stablelm-3b")
    ccfg, cparams = _setup_cnn()
    metrics = Metrics()
    eng = Engine(cfg, params, batch_slots=2, max_seq=48, metrics=metrics)
    b = CnnBatcher(ccfg, cparams, max_batch=2, metrics=metrics)
    mix = MixedBatcher(eng, b)
    rng = np.random.default_rng(23)
    lm = [eng.submit(rng.integers(0, cfg.vocab, size=5), max_new=4) for _ in range(3)]
    im = [b.submit(rng.standard_normal((3, 16, 16)).astype(np.float32))
          for _ in range(3)]
    mix.run_until_drained(max_ticks=100)
    assert all(r.done for r in lm) and all(r.done for r in im)
    roll = metrics.rollup()
    assert roll["lm_n"] == 3 and roll["cnn_n"] == 3
    assert roll["tok_s"] > 0 and roll["img_s"] > 0
    assert 0 < roll["mean_occupancy"] <= 1
