"""Chaos suite: seeded, deterministic fault injection against the serve stack.

The contract under test (ISSUE 8 / DESIGN.md §2.4): with a seeded
:class:`FaultPlan` injecting NaN and raise faults,

- every NON-faulted request's token stream is **bit-identical**
  (``assert_array_equal``) to a fault-free run,
- every faulted request terminates with the right ``failed:*`` status and
  its partial output,
- the engine always drains (``run_until_drained`` completes, zero stuck),

plus the supporting machinery: seeded-plan determinism, quarantine-then-
reuse never leaks poisoned KV (the PR-7 no-KV-leak guarantee extended to
the numeric-fault path), the capped-exponential backoff schedule is pinned,
shed-expired vs reject backpressure policies, mid-decode deadline eviction
returns partial output, and kernel→dequant graceful degradation.

Deadline tests drive a DETERMINISTIC tick clock: the metrics clock reads the
engine's own tick counter, so "seconds" are ticks and every run is
identical.
"""
import functools

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.models.common import quantize_params
from repro.serve.engine import Engine
from repro.serve.faults import FaultInjected, FaultPlan, FaultSpec
from repro.serve.metrics import Metrics
from repro.serve.scheduler import QueueFullError, Scheduler

KEY = jax.random.PRNGKey(0)


@functools.lru_cache(maxsize=None)
def _setup(arch: str):
    cfg = get_config(arch, smoke=True)
    model = api.get_model(cfg)
    return cfg, model.init_params(cfg, KEY)


def _tick_engine(cfg, params, **kw):
    """Engine whose metrics clock IS its tick counter — deterministic
    deadlines (slo_s is a budget in ticks)."""
    holder = []
    metrics = Metrics(clock=lambda: float(holder[0].tick) if holder else 0.0)
    eng = Engine(cfg, params, metrics=metrics, **kw)
    holder.append(eng)
    return eng


def _solo_out(cfg, params, prompt, max_new, *, slots=3, max_seq=48):
    eng = Engine(cfg, params, batch_slots=slots, max_seq=max_seq)
    r = eng.submit(prompt, max_new=max_new)
    eng.run_until_drained()
    return r.out


# ---------------------------------------------------------------------------
# FaultPlan: seeded determinism
# ---------------------------------------------------------------------------


def test_fault_plan_seeded_determinism():
    kw = dict(n_ticks=30, n_slots=4, n_requests=8, n_nan=3, n_prefill=2,
              n_decode=2, n_slow=1, slow_delay_s=5.0, n_kernel=1)
    a = FaultPlan.sample(7, **kw)
    b = FaultPlan.sample(7, **kw)
    assert a.faults == b.faults  # same seed ⇒ same injected schedule
    assert len(a.faults) == 9
    c = FaultPlan.sample(8, **kw)
    assert c.faults != a.faults  # a different seed moves the schedule
    with pytest.raises(ValueError, match="kind"):
        FaultSpec("meteor", tick=3)


def test_fault_plan_hooks_fire_deterministically():
    plan = FaultPlan([
        FaultSpec("nan", tick=3, slot=1),
        FaultSpec("decode", tick=5),
        FaultSpec("prefill", uid=2, nth=1),
        FaultSpec("slow", tick=4, delay_s=2.5),
    ])
    assert plan.poison_slots(2) == [] and plan.poison_slots(3) == [1]
    assert plan.on_tick(4) == 2.5 and plan.on_tick(3) == 0.0
    plan.on_decode(4)  # no fault scheduled: no raise
    with pytest.raises(FaultInjected):
        plan.on_decode(5)
    plan.on_prefill(1, 1)  # uid 1 never faulted
    with pytest.raises(FaultInjected):
        plan.on_prefill(2, 1)  # uid 2, first attempt
    plan.on_prefill(2, 6)  # second attempt succeeds (nth=1 only)
    assert [f[0] for f in plan.fired] == ["nan", "slow", "decode", "prefill"]


# ---------------------------------------------------------------------------
# tentpole acceptance: chaos run — unaffected slots bit-identical, faulted
# requests terminal with partial output, engine drains (transformer+encdec)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["stablelm-3b", "whisper-tiny"])
def test_chaos_unaffected_requests_bit_identical(arch):
    cfg, params = _setup(arch)
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, cfg.vocab, size=n) for n in (5, 7, 4, 6)]

    # fault-free reference run: 4 requests over 3 slots
    ref = Engine(cfg, params, batch_slots=3, max_seq=48)
    ref_reqs = [ref.submit(p, max_new=8) for p in prompts]
    ref.run_until_drained()
    assert all(r.done for r in ref_reqs)

    # chaos run, max_retries=0 so faulted requests are terminal:
    # - NaN into slot 1 (second request) at tick 3 → failed:numeric
    # - uid 4's first prefill raises → failed:error
    # - a transient decode raise at tick 2 → whole tick replayed, no effect
    plan = FaultPlan([
        FaultSpec("nan", tick=3, slot=1),
        FaultSpec("prefill", uid=4, nth=1),
        FaultSpec("decode", tick=2),
    ])
    eng = Engine(cfg, params, batch_slots=3, max_seq=48, faults=plan,
                 max_retries=0)
    reqs = [eng.submit(p, max_new=8) for p in prompts]
    eng.run_until_drained()  # the engine always drains
    roll = eng.metrics.rollup()
    assert roll["n_stuck"] == 0

    # faulted requests: terminal failed:* with partial output preserved
    r_nan, r_err = reqs[1], reqs[3]
    assert r_nan.status == "failed:numeric"
    assert 0 < len(r_nan.out) < 8  # partial output, not silently empty/full
    assert r_err.status == "failed:error" and r_err.out == []
    assert roll["n_quarantined"] == 1 and roll["failed_numeric_n"] == 1
    assert roll["failed_error_n"] == 1 and roll["n_faults_decode"] == 1

    # every unaffected request: bit-identical to the fault-free run
    for got, want in ((reqs[0], ref_reqs[0]), (reqs[2], ref_reqs[2])):
        assert got.done
        np.testing.assert_array_equal(np.asarray(got.out), np.asarray(want.out))


@pytest.mark.parametrize("arch", ["stablelm-3b", "whisper-tiny"])
def test_quarantine_then_reuse_never_leaks_kv(arch):
    """PR-7's no-KV-leak guarantee extended to the quarantine path: a slot
    whose occupant was NaN-poisoned is re-grafted from the fresh template,
    and its next occupant matches a solo run bit for bit."""
    cfg, params = _setup(arch)
    rng = np.random.default_rng(37)
    victim_p = rng.integers(0, cfg.vocab, size=6)
    probe_p = rng.integers(0, cfg.vocab, size=5)
    want = _solo_out(cfg, params, probe_p, 6, slots=1)

    plan = FaultPlan([FaultSpec("nan", tick=2, slot=0)])
    eng = Engine(cfg, params, batch_slots=1, max_seq=48, faults=plan,
                 max_retries=0)
    victim = eng.submit(victim_p, max_new=6)
    eng.step()  # tick 1: admit victim
    eng.step()  # tick 2: decode → poisoned → quarantined
    assert victim.status == "failed:numeric"
    assert eng.sched.quarantined == {0}  # slot visibly quarantined
    assert eng.sched.free_slots == []  # and not handed out

    probe = eng.submit(probe_p, max_new=6)
    eng.run_until_drained()
    assert eng.sched.quarantined == set()  # scrubbed before reuse
    assert probe.done and probe.slot == 0
    np.testing.assert_array_equal(np.asarray(probe.out), np.asarray(want))


def test_numeric_retry_recovers_bit_exact():
    """A retryable numeric fault re-queues with backoff; the retry decodes
    fresh and lands the solo-run output exactly."""
    cfg, params = _setup("stablelm-3b")
    rng = np.random.default_rng(41)
    p = rng.integers(0, cfg.vocab, size=5)
    want = _solo_out(cfg, params, p, 6, slots=2)

    plan = FaultPlan([FaultSpec("nan", tick=2, slot=0)])
    eng = Engine(cfg, params, batch_slots=2, max_seq=48, faults=plan,
                 max_retries=2)
    r = eng.submit(p, max_new=6)
    eng.run_until_drained()
    roll = eng.metrics.rollup()
    assert r.done and r.failed is None and r.retries == 1
    assert roll["n_retried"] == 1 and roll["n_quarantined"] == 1
    np.testing.assert_array_equal(np.asarray(r.out), np.asarray(want))


def test_prefill_fault_retries_and_recovers():
    cfg, params = _setup("stablelm-3b")
    rng = np.random.default_rng(43)
    p = rng.integers(0, cfg.vocab, size=5)
    want = _solo_out(cfg, params, p, 6, slots=2)

    plan = FaultPlan([FaultSpec("prefill", uid=1, nth=1)])
    eng = Engine(cfg, params, batch_slots=2, max_seq=48, faults=plan,
                 max_retries=1)
    r = eng.submit(p, max_new=6)
    eng.run_until_drained()
    assert r.done and r.retries == 1
    assert eng.metrics.rollup()["n_retried"] == 1
    np.testing.assert_array_equal(np.asarray(r.out), np.asarray(want))


# ---------------------------------------------------------------------------
# retry backoff schedule
# ---------------------------------------------------------------------------


def test_backoff_schedule_pinned():
    """Deterministic tick-based capped exponential: delays 1, 2, 4, 8, 8…
    (base 1, cap 8) relative to the failing tick."""
    cfg, params = _setup("stablelm-3b")
    eng = Engine(cfg, params, batch_slots=1, max_seq=48, max_retries=5,
                 backoff_ticks=1, backoff_cap_ticks=8)
    from repro.serve.engine import Request

    r = Request(uid=99, prompt=np.zeros(4, np.int32))
    eng.metrics.submit(99, "lm")
    delays = []
    for _ in range(5):
        tick_before = eng.tick
        eng._fail_or_retry(r, "numeric")
        delays.append(r.retry_at - tick_before)
        eng._retry_q.clear()
    assert delays == [1, 2, 4, 8, 8]  # capped exponential, tick-based
    eng._fail_or_retry(r, "numeric")  # retries exhausted → terminal
    assert r.status == "failed:numeric"

    # deadline failures are never retryable
    r2 = Request(uid=100, prompt=np.zeros(4, np.int32))
    eng.metrics.submit(100, "lm")
    eng._fail_or_retry(r2, "deadline")
    assert r2.status == "failed:deadline" and not eng._retry_q


def test_retry_waits_out_backoff_before_readmission():
    cfg, params = _setup("stablelm-3b")
    rng = np.random.default_rng(47)
    plan = FaultPlan([FaultSpec("nan", tick=2, slot=0)])
    eng = Engine(cfg, params, batch_slots=1, max_seq=48, faults=plan,
                 max_retries=1, backoff_ticks=3)
    r = eng.submit(rng.integers(0, cfg.vocab, size=4), max_new=4)
    eng.step()  # tick 1: admit
    eng.step()  # tick 2: poisoned → retry_at = 2 + 3
    assert r.retry_at == 5 and eng._retry_q == [r]
    eng.step()  # tick 3: still backing off
    eng.step()  # tick 4: still backing off
    assert not eng.live and eng._retry_q == [r]
    eng.step()  # tick 5: re-queued and admitted
    assert r.uid in eng.live
    eng.run_until_drained()
    assert r.done


# ---------------------------------------------------------------------------
# backpressure: bounded queue policies
# ---------------------------------------------------------------------------


def test_scheduler_bounded_queue_policies():
    class R:
        def __init__(self, uid, deadline=None):
            self.uid, self.prompt, self.deadline = uid, list(range(4)), deadline

    s = Scheduler(1, max_seq=64, max_queue=2, policy="reject")
    s.submit(R(1))
    s.submit(R(2))
    with pytest.raises(QueueFullError):
        s.submit(R(3))
    assert [r.uid for r in s.waiting] == [1, 2]

    s = Scheduler(1, max_seq=64, max_queue=2, policy="shed_oldest")
    s.submit(R(1))
    s.submit(R(2))
    shed = s.submit(R(3))
    assert [r.uid for r in shed] == [1]
    assert [r.uid for r in s.waiting] == [2, 3]

    s = Scheduler(1, max_seq=64, max_queue=2, policy="shed_expired")
    s.submit(R(1, deadline=5.0), now=0.0)
    s.submit(R(2), now=0.0)
    shed = s.submit(R(3), now=10.0)  # uid 1 expired at t=10 → shed
    assert [r.uid for r in shed] == [1]
    assert [r.uid for r in s.waiting] == [2, 3]
    with pytest.raises(QueueFullError):  # nothing expired now → reject
        s.submit(R(4), now=10.0)
    with pytest.raises(ValueError, match="policy"):
        Scheduler(1, policy="drop_random")


def test_engine_reject_vs_shed_policies():
    cfg, params = _setup("stablelm-3b")
    rng = np.random.default_rng(53)
    mk = lambda: rng.integers(0, cfg.vocab, size=4)

    eng = Engine(cfg, params, batch_slots=1, max_seq=48, max_queue=1,
                 policy="reject")
    r1, r2 = eng.submit(mk(), max_new=3), eng.submit(mk(), max_new=3)
    assert r2.status == "failed:rejected"  # terminal at submit, no exception
    eng.run_until_drained()
    roll = eng.metrics.rollup()
    assert r1.done and roll["n_rejected"] == 1
    assert roll["failed_rejected_n"] == 1

    eng = Engine(cfg, params, batch_slots=1, max_seq=48, max_queue=1,
                 policy="shed_oldest")
    r1, r2 = eng.submit(mk(), max_new=3), eng.submit(mk(), max_new=3)
    assert r1.status == "failed:rejected" and r1.uid not in (
        q.uid for q in eng.sched.waiting
    )
    eng.run_until_drained()
    assert r2.done and eng.metrics.rollup()["n_shed"] == 1


def test_expired_queued_requests_shed_before_prefill():
    """A queued request whose SLO expires before a slot frees is shed —
    never admitted, never prefilled (t_admit stays nan)."""
    cfg, params = _setup("stablelm-3b")
    rng = np.random.default_rng(59)
    eng = _tick_engine(cfg, params, batch_slots=1, max_seq=48)
    hog = eng.submit(rng.integers(0, cfg.vocab, size=4), max_new=12)
    doomed = eng.submit(rng.integers(0, cfg.vocab, size=4), max_new=4, slo_s=3.0)
    eng.run_until_drained()
    roll = eng.metrics.rollup()
    assert hog.done
    assert doomed.status == "failed:deadline" and doomed.out == []
    assert roll["n_shed"] == 1 and roll["n_evicted_deadline"] == 0
    import math

    assert math.isnan(eng.metrics.timelines[doomed.uid].t_admit)  # no prefill spent


def test_deadline_eviction_returns_partial_output():
    """A live request that blows its deadline mid-decode is evicted with the
    tokens it produced so far; the freed slot serves the next request."""
    cfg, params = _setup("stablelm-3b")
    rng = np.random.default_rng(61)
    eng = _tick_engine(cfg, params, batch_slots=1, max_seq=48)
    r = eng.submit(rng.integers(0, cfg.vocab, size=4), max_new=20, slo_s=4.0)
    nxt = eng.submit(rng.integers(0, cfg.vocab, size=4), max_new=3)
    eng.run_until_drained()
    roll = eng.metrics.rollup()
    assert r.status == "failed:deadline"
    assert 0 < len(r.out) < 20  # partial output returned, not discarded
    assert roll["n_evicted_deadline"] == 1
    assert roll["failed_deadline_n"] == 1
    assert nxt.done  # the evicted slot was reusable immediately

    # eviction is configurable: with it off, the same request just finishes
    # late (and is counted as an SLO miss, not killed)
    eng2 = _tick_engine(cfg, params, batch_slots=1, max_seq=48,
                        deadline_eviction=False)
    r2 = eng2.submit(rng.integers(0, cfg.vocab, size=4), max_new=20, slo_s=4.0)
    eng2.run_until_drained()
    roll2 = eng2.metrics.rollup()
    assert r2.done and len(r2.out) == 20
    assert roll2["n_evicted_deadline"] == 0 and roll2["slo_missed"] == 1


def test_slow_tick_fault_advances_injected_clock_and_blows_deadline():
    """A slow-tick latency spike (injected stall) pushes the deterministic
    clock past a live request's deadline → mid-decode eviction."""
    cfg, params = _setup("stablelm-3b")
    rng = np.random.default_rng(67)
    box = [0.0]  # tick-clock with a skew the sleep hook advances
    holder = []
    metrics = Metrics(clock=lambda: (holder[0].tick if holder else 0) + box[0])
    plan = FaultPlan([FaultSpec("slow", tick=3, delay_s=50.0)])
    eng = Engine(cfg, params, batch_slots=1, max_seq=48, metrics=metrics,
                 faults=plan, sleep=lambda d: box.__setitem__(0, box[0] + d))
    holder.append(eng)
    r = eng.submit(rng.integers(0, cfg.vocab, size=4), max_new=20, slo_s=30.0)
    eng.run_until_drained()
    assert r.status == "failed:deadline" and 0 < len(r.out) < 20
    assert eng.metrics.rollup()["n_evicted_deadline"] == 1
    assert ("slow", 3, 50.0) in plan.fired


# ---------------------------------------------------------------------------
# graceful degradation: kernel → dequant, memoized, still serving
# ---------------------------------------------------------------------------


def test_kernel_failure_degrades_to_dequant_and_serves():
    cfg, params = _setup("stablelm-3b")
    qcfg = cfg.with_quant(enabled=True, bins=16, impl="kernel",
                          min_weight_elems=1024)
    qparams = quantize_params(params, qcfg)
    rng = np.random.default_rng(71)
    p = rng.integers(0, cfg.vocab, size=5)

    ref = Engine(qcfg, qparams, batch_slots=2, max_seq=48)
    want = ref.submit(p, max_new=5)
    ref.run_until_drained()
    assert ref._degraded == set()  # healthy kernels: no degradation

    plan = FaultPlan([FaultSpec("kernel", key="decode")])
    eng = Engine(qcfg, qparams, batch_slots=2, max_seq=48, faults=plan)
    with pytest.warns(RuntimeWarning, match="degrading"):
        r = eng.submit(p, max_new=5)
        eng.run_until_drained()
    assert eng._degraded == {"decode"}  # memoized: flipped exactly once
    assert eng.metrics.rollup()["n_degraded"] == 1
    assert r.done
    # the dequant oracle is the kernels' bit-exactness oracle: degraded
    # serving returns the same tokens
    np.testing.assert_array_equal(np.asarray(r.out), np.asarray(want.out))

    # degraded but SERVING: later traffic flows without re-tripping
    r2 = eng.submit(rng.integers(0, cfg.vocab, size=4), max_new=4)
    eng.run_until_drained()
    assert r2.done and eng.metrics.rollup()["n_degraded"] == 1


def test_degradation_unavailable_reraises():
    """With nothing to degrade to (dense weights), a persistent closure
    failure must surface, not loop."""
    cfg, params = _setup("stablelm-3b")
    plan = FaultPlan([FaultSpec("kernel", key="decode")])
    eng = Engine(cfg, params, batch_slots=1, max_seq=48, faults=plan)
    rng = np.random.default_rng(73)
    eng.submit(rng.integers(0, cfg.vocab, size=4), max_new=4)
    with pytest.raises(RuntimeError, match="injected persistent kernel"):
        eng.run_until_drained()


@pytest.mark.parametrize("closure", ["decode", "prefill"])
def test_real_kernel_error_raises(closure):
    """A real exception at the jit boundary (here a stand-in for a Mosaic
    lowering error) raises out of the engine instead of passing as a
    degraded success: only the injected FaultPlan drill degrades."""
    cfg, params = _setup("stablelm-3b")
    qcfg = cfg.with_quant(enabled=True, bins=16, impl="kernel",
                          min_weight_elems=1024)
    qparams = quantize_params(params, qcfg)
    eng = Engine(qcfg, qparams, batch_slots=1, max_seq=48)
    name = f"_{closure}_fn"
    healthy = getattr(eng, name)

    def broken(*a):
        if a[-1].quant.impl == "kernel":
            def fail(*_):
                raise RuntimeError("Mosaic failed to compile TPU kernel")
            return fail
        return healthy(*a)

    setattr(eng, name, broken)
    rng = np.random.default_rng(79)
    eng.submit(rng.integers(0, cfg.vocab, size=4), max_new=3)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        eng.run_until_drained()
    assert eng._degraded == set()
    assert eng.metrics.rollup()["n_degraded"] == 0
