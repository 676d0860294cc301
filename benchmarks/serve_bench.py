"""Traffic-replay serving benchmark: mixed LM + CNN under Poisson arrivals.

Replays a SEEDED trace (Poisson inter-arrival ticks, mixed LM decode and CNN
classification requests) through the continuous-batching service loop
(serve/engine.py + serve/batcher.py) and rolls the per-request timelines
(serve/metrics.py) into ``BENCH_serve.json``:

- measured rows: p50/p99 end-to-end latency and TTFT per traffic class,
  wall tok/s and img/s, mean slot occupancy, queue stats;
- modeled rows: decode tok/s on the v5e memory roofline
  (``HBM_BW / weight-stream bytes per decode step``) for dense-bf16 vs the
  PASM-quantized container — the weight-stream argument (DESIGN.md §2)
  applied to serving, gated by scripts/ci.sh (PASM modeled decode tok/s must
  be ≥ dense; wall-clock on a CPU host measures dequant arithmetic, not the
  HBM stream the accelerator would move, so the roofline rows carry the
  gate while the measured rows track this host's trajectory);
- fault rows (``--faults``): the SAME seeded trace replayed under a seeded
  :class:`~repro.serve.faults.FaultPlan` (NaN poisoning, prefill/decode
  raises, a slow-tick stall) on a deterministic tick clock —
  ``serve.faults.*`` rows carry the failure counters, the non-faulted SLO
  hit fraction, per-failure-kind latency, and the drained/stuck verdict
  that scripts/ci.sh gates (zero stuck, ≥95 % of non-faulted requests meet
  SLO).

    PYTHONPATH=src python benchmarks/serve_bench.py --smoke --json --faults
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT / "src"))
sys.path.insert(0, str(_ROOT))  # direct-script runs: make `benchmarks` importable

import jax
import numpy as np

from repro.configs import get_cnn_config, get_config
from repro.models import api, cnn
from repro.models.common import quantize_params, weight_bytes
from repro.roofline import HBM_BW
from repro.serve.batcher import CnnBatcher, MixedBatcher
from repro.serve.engine import Engine
from repro.serve.faults import FaultPlan
from repro.serve.metrics import FAILURE_COUNTERS, Metrics

from benchmarks.common import bench_row, emit

_RECORDS: list = []


def record(name, us, derived="", **kw) -> None:
    _RECORDS.append(bench_row(name, us, derived=derived, **kw))
    emit(name, us, derived, kw.get("hbm_bytes"))


def make_trace(rng, *, n_lm, n_cnn, rate, vocab, in_chw, max_prompt, max_new):
    """Seeded Poisson replay trace: [(arrival_tick, kind, payload), ...]."""
    events = []
    t = 0.0
    for kind in ["lm"] * n_lm + ["cnn"] * n_cnn:
        t += rng.exponential(1.0 / rate)  # Poisson arrivals → exp inter-arrival
        events.append((t, kind))
    rng.shuffle(events)  # interleave the classes along the arrival axis
    events.sort(key=lambda e: e[0])
    trace = []
    C, H, W = in_chw
    for t, kind in events:
        if kind == "lm":
            payload = {
                "prompt": rng.integers(0, vocab, size=int(rng.integers(3, max_prompt))),
                "max_new": max_new,
            }
        else:
            h = int(rng.integers(8, H + 1))
            w = int(rng.integers(8, W + 1))
            payload = {"image": rng.standard_normal((C, h, w)).astype(np.float32)}
        trace.append((int(t), kind, payload))
    return trace


def replay(trace, engine: Engine, cnn_b: CnnBatcher, *, slo_s=None,
           clock_box=None) -> int:
    """Drive the mixed service loop: submit due arrivals, tick, repeat.

    With ``clock_box`` (a one-element list the engine's metrics clock and
    injected ``sleep`` read/advance), the replay runs on a deterministic
    tick clock: one tick = one second, slow-tick faults add their stall on
    top — deadlines and the SLO gate are then seed-reproducible.
    """
    mix = MixedBatcher(engine, cnn_b)
    i, tick = 0, 0
    while i < len(trace) or not mix.drained:
        while i < len(trace) and trace[i][0] <= tick:
            _, kind, payload = trace[i]
            if kind == "lm":
                engine.submit(payload["prompt"], payload["max_new"], slo_s=slo_s)
            else:
                cnn_b.submit(payload["image"])
            i += 1
        mix.tick()
        tick += 1
        if clock_box is not None:
            clock_box[0] += 1.0
        if tick > 100_000:
            raise RuntimeError("replay did not drain")
    return tick


def measured_rows(rollup: dict, *, slots: int, tag: str) -> None:
    """Metrics rollup → BENCH rows (latency rows carry µs in us_per_call)."""
    for kind in ("lm", "cnn"):
        for pct in ("p50", "p99"):
            lat = rollup[f"{kind}_{pct}_latency_s"]
            record(f"serve.{tag}.{kind}.{pct}_latency", float(lat * 1e6),
                   derived=f"n={rollup[f'{kind}_n']}", n_requests=rollup[f"{kind}_n"])
            if kind == "lm":  # a classifier has no first token
                ttft = rollup[f"lm_{pct}_ttft_s"]
                record(f"serve.{tag}.lm.{pct}_ttft", float(ttft * 1e6),
                       n_requests=rollup["lm_n"])
    tok_s = rollup["tok_s"]
    record(f"serve.{tag}.lm.tok_s", float(1e6 / tok_s) if tok_s else float("nan"),
           derived=f"{tok_s:.1f} tok/s", tok_s=tok_s)
    img_s = rollup["img_s"]
    record(f"serve.{tag}.cnn.img_s", float(1e6 / img_s) if img_s else float("nan"),
           derived=f"{img_s:.1f} img/s", img_s=img_s)
    record(f"serve.{tag}.occupancy", 0.0,
           derived=f"mean {rollup['mean_occupancy']:.2f} over {slots} slots",
           mean_occupancy=rollup["mean_occupancy"],
           slo_met=rollup["slo_met"], slo_missed=rollup["slo_missed"])


def fault_rows(roll: dict, *, tag: str = "faults") -> None:
    """Fault-replay rollup → BENCH rows: counters, SLO fraction over the
    NON-faulted population, per-failure-kind latency, drained verdict."""
    counters = {k: roll[k] for k in FAILURE_COUNTERS}
    tripped = ", ".join(f"{k[2:]}={v}" for k, v in counters.items() if v)
    record(f"serve.{tag}.counters", 0.0,
           derived=tripped or "no faults tripped",
           n_failed=roll["n_failed"], **counters)
    met, missed = roll["slo_met"], roll["slo_missed"]
    frac = met / max(met + missed, 1)
    record(f"serve.{tag}.slo", 0.0,
           derived=f"{met}/{met + missed} non-faulted requests met SLO",
           slo_met=met, slo_missed=missed, slo_frac=frac)
    for kind in ("deadline", "numeric", "error", "rejected"):
        n = roll.get(f"failed_{kind}_n", 0)
        if n:
            record(f"serve.{tag}.failed.{kind}.p99_latency",
                   float(roll[f"failed_{kind}_p99_latency_s"] * 1e6),
                   derived=f"n={n}", n_requests=n)
    record(f"serve.{tag}.drained", 0.0,
           derived=f"n_stuck={roll['n_stuck']} n_done={roll['n_done']}"
                   f"/{roll['n_requests']}",
           n_stuck=roll["n_stuck"], n_done=roll["n_done"],
           n_requests=roll["n_requests"])


def modeled_decode_rows(dense_params, pasm_params, *, batch: int) -> None:
    """Memory-roofline decode tok/s: the batched step streams the weights
    once, so tok/s = batch · HBM_BW / weight_bytes (decode is weight-bound;
    DESIGN.md §2)."""
    for tag, params in (("dense", dense_params), ("pasm", pasm_params)):
        wb = weight_bytes(params)
        stream = wb["stored"] if tag == "pasm" else wb["dense"]
        tok_s = batch * HBM_BW / max(stream, 1)
        record(f"serve.decode.tok_s_modeled.{tag}", 1e6 / tok_s,
               derived=f"{tok_s:.0f} tok/s @ {stream} weight B",
               hbm_bytes=int(stream), tok_s_modeled=tok_s, batch=batch)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="CI sizing")
    ap.add_argument("--json", nargs="?", const="BENCH_serve.json", default=None,
                    metavar="PATH", help="write rows to JSON (default BENCH_serve.json)")
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--lm-requests", type=int, default=12)
    ap.add_argument("--cnn-requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.5, help="arrivals per tick")
    ap.add_argument("--bins", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--faults", action="store_true",
                    help="also replay the trace under a seeded FaultPlan")
    ap.add_argument("--policy", default="reject",
                    help="bounded-queue admission policy for the fault replay")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded queue depth for the fault replay")
    ap.add_argument("--max-retries", type=int, default=1)
    ap.add_argument("--slo-ticks", type=float, default=400.0,
                    help="per-request SLO (ticks on the deterministic clock)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.lm_requests = min(args.lm_requests, 6)
        args.cnn_requests = min(args.cnn_requests, 4)
        args.max_new = min(args.max_new, 6)

    cfg = get_config(args.arch, smoke=args.smoke)
    model = api.get_model(cfg)
    dense_params = model.init_params(cfg, jax.random.PRNGKey(args.seed))
    # min_weight_elems=1024 keeps smoke-size layers quantizable (the default
    # B ≪ N rule would leave the tiny smoke matrices dense and the modeled
    # weight stream identical to dense — no win to measure)
    qcfg = cfg.with_quant(enabled=True, bins=args.bins, impl="dequant",
                          min_weight_elems=1024)
    pasm_params = quantize_params(dense_params, qcfg)

    ccfg = get_cnn_config("alexnet", smoke=args.smoke)
    cparams = cnn.quantize(cnn.init_params(ccfg, jax.random.PRNGKey(args.seed)), ccfg)

    rng = np.random.default_rng(args.seed)
    trace = make_trace(
        rng, n_lm=args.lm_requests, n_cnn=args.cnn_requests, rate=args.rate,
        vocab=cfg.vocab, in_chw=ccfg.in_chw,
        max_prompt=max(4, args.max_seq // 4), max_new=args.max_new,
    )

    print("name,us_per_call,hbm_bytes,derived")
    for tag, c, p in (("dense", cfg, dense_params), ("pasm", qcfg, pasm_params)):
        metrics = Metrics()
        engine = Engine(c, p, batch_slots=args.slots, max_seq=args.max_seq,
                        metrics=metrics)
        cnn_b = CnnBatcher(ccfg, cparams, max_batch=args.slots, metrics=metrics)
        ticks = replay(trace, engine, cnn_b)
        roll = metrics.rollup()
        assert roll["n_stuck"] == 0, roll
        measured_rows(roll, slots=args.slots, tag=tag)
        print(f"[serve_bench] {tag}: {roll['n_done']} requests drained "
              f"in {ticks} ticks", file=sys.stderr)

    modeled_decode_rows(dense_params, pasm_params, batch=args.slots)

    if args.faults:
        # same trace, PASM weights, seeded chaos on a deterministic tick
        # clock: the metrics clock reads clock_box[0] (one tick = 1 s), the
        # injected sleep adds slow-fault stalls on top — fully reproducible
        clock_box = [0.0]
        metrics = Metrics(clock=lambda: clock_box[0])
        plan = FaultPlan.sample(
            args.seed, n_ticks=20, n_slots=args.slots,
            n_requests=args.lm_requests, n_nan=2, n_prefill=1, n_decode=1,
            n_slow=1, slow_delay_s=3.0,
        )
        engine = Engine(
            qcfg, pasm_params, batch_slots=args.slots, max_seq=args.max_seq,
            metrics=metrics, faults=plan, max_retries=args.max_retries,
            max_queue=args.max_queue, policy=args.policy,
            sleep=lambda d: clock_box.__setitem__(0, clock_box[0] + d),
        )
        cnn_b = CnnBatcher(ccfg, cparams, max_batch=args.slots, metrics=metrics)
        ticks = replay(trace, engine, cnn_b, slo_s=float(args.slo_ticks),
                       clock_box=clock_box)
        roll = metrics.rollup()
        assert roll["n_stuck"] == 0, roll
        fault_rows(roll, tag="faults")
        print(f"[serve_bench] faults: {len(plan.fired)} injections fired, "
              f"{roll['n_done']}/{roll['n_requests']} done, "
              f"{roll['n_failed']} failed, drained in {ticks} ticks",
              file=sys.stderr)

    if args.json:
        payload = {
            "benchmark": "serve",
            "smoke": bool(args.smoke),
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "devices": 1,
            "seed": args.seed,
            "trace": {"lm": args.lm_requests, "cnn": args.cnn_requests,
                      "rate": args.rate},
            "faults": bool(args.faults),
            "records": _RECORDS,
        }
        Path(args.json).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"wrote {len(_RECORDS)} records to {args.json}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
