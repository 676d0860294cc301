"""Training launcher: end-to-end driver with checkpoint/restart + supervision.

Single-host example (the same SPMD program runs per-host on a fleet):

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b --smoke \\
        --steps 200 --ckpt-dir /tmp/ckpt --resume auto

Fault tolerance (DESIGN.md §4): the loop is ``train/loop.py::run_loop``
under ``ft.Supervisor``.  Every jitted step carries the fused non-finite
guard — a NaN/inf batch skips its update bit-exactly and ``--guard-max-skip``
consecutive skips escalate to a restorable error; checkpoints are CRC32'd
and fsync'd, and restore falls back past a corrupt newest checkpoint to the
newest *valid* one; the supervisor classifies failures (same step failing
the same way twice across a restore → fail fast as deterministic; anything
else → backoff restart threading the failure's ``resume_step`` hint); the
data pipeline is step-addressed so no batches are replayed or skipped, and
per-step wall times feed the straggler detector every step.

Flags beyond the obvious:

``--guard-max-skip K``   escalate after K consecutive non-finite steps (3)
``--keep N``             checkpoint rotation depth (3)
``--max-restarts N``     supervisor restart budget (3)
``--faults-seed S``      chaos drill: run under a seeded
                         ``train.faults.TrainFaultPlan`` sampled from S
                         (crash / data-io / ckpt-io / nan / spike / slow —
                         the same plans the chaos suite asserts on)
``--resume auto``        restore the newest checkpoint passing integrity;
                         with no ``--ckpt-dir``, a supervisor restart warns
                         LOUDLY that all progress is lost and re-runs from
                         step 0.
"""
from __future__ import annotations

import argparse
import warnings
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp

from repro import ft
from repro.ckpt import checkpoint as ckpt
from repro.configs import get_config
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.models.common import ShardCtx, quantize_params
from repro.train import faults as train_faults
from repro.train import loop as loop_mod
from repro.train import optimizer as opt
from repro.train import step as step_mod


def build_state(cfg, key, quant: str):
    model = api.get_model(cfg)
    params = model.init_params(cfg, key)
    if quant == "pasm" or quant == "qat":
        qcfg = cfg.with_quant(enabled=True, impl="kernel" if quant == "pasm" else "dequant")
        params = quantize_params(params, qcfg)
        cfg = qcfg
    return cfg, params


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--quant", default="dense", choices=["dense", "pasm", "qat"])
    ap.add_argument("--compress-grads", type=int, default=0, help="bins; 0=off")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--keep", type=int, default=3, help="checkpoint rotation depth")
    ap.add_argument("--resume", default="no", choices=["no", "auto"])
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--guard-max-skip", type=int, default=3,
                    help="consecutive non-finite steps before escalating")
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--faults-seed", type=int, default=None,
                    help="chaos drill: sample a TrainFaultPlan from this seed")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    print(f"[train] compile cache: {enable_compile_cache()}")

    cfg = get_config(args.arch, smoke=args.smoke)
    ocfg = opt.AdamWConfig(lr=args.lr, total_steps=args.steps, warmup_steps=max(args.steps // 20, 5))
    dcfg = DataConfig(
        seed=args.seed, vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch
    )
    mgr = ckpt.CheckpointManager(args.ckpt_dir, keep=args.keep) if args.ckpt_dir else None
    detector = ft.StragglerDetector(n_hosts=jax.process_count())
    plan = (
        train_faults.TrainFaultPlan.sample(args.faults_seed, n_steps=args.steps)
        if args.faults_seed is not None
        else None
    )
    sup = ft.Supervisor(ft.RestartPolicy(max_restarts=args.max_restarts))
    losses: dict = {}
    step_times: dict = {}

    def loop(resume_step: Optional[int]) -> int:
        if sup.restarts and mgr is None:
            warnings.warn(
                "supervisor restart with no --ckpt-dir: ALL training progress "
                "is lost and the run re-executes from step 0 — pass --ckpt-dir "
                "to make restarts resume instead",
                RuntimeWarning,
                stacklevel=2,
            )
        cfg_t, params = build_state(cfg, jax.random.PRNGKey(args.seed), args.quant)
        opt_state = opt.init_opt_state(params)
        start = 0
        if mgr and args.resume == "auto" and ckpt.latest_step(mgr.dir) is not None:
            # restore the resume hint when the supervisor threaded one
            # through, else the newest checkpoint passing integrity
            if resume_step is not None:
                (params, opt_state), manifest = ckpt.restore(
                    mgr.dir, (params, opt_state), step=resume_step
                )
            else:
                (params, opt_state), manifest = mgr.restore_latest((params, opt_state))
            start = manifest["step"]
            print(f"[train] resumed from step {start}")

        train_step = jax.jit(
            step_mod.make_train_step(
                cfg_t,
                ocfg,
                ShardCtx(),
                microbatches=args.microbatches,
                compress_grads_bins=args.compress_grads,
            ),
            donate_argnums=(0, 1),
        )

        res = loop_mod.run_loop(
            train_step,
            (params, opt_state),
            lambda s: synthetic_batch(dcfg, s),
            steps=args.steps,
            start_step=start,
            mgr=mgr,
            ckpt_every=args.ckpt_every,
            ckpt_extra={"arch": args.arch},
            faults=plan,
            detector=detector,
            max_consecutive_nonfinite=args.guard_max_skip,
            log_every=args.log_every,
            losses=losses,
            step_times=step_times,
        )
        if res.n_skipped:
            print(f"[train] guard skipped {res.n_skipped} non-finite steps")
        if res.n_ckpt_failures:
            print(f"[train] {res.n_ckpt_failures} checkpoint saves failed (training continued)")
        if detector.stragglers():
            print(f"[train] stragglers detected: {detector.stragglers()}")
        return res.last_step

    last = sup.run(loop)
    if plan is not None:
        print(f"[train] chaos drill: {len(plan.fired)} injections fired: "
              f"{[f[0] for f in plan.fired]}")
    print(f"[train] done at step {last} (restarts: {sup.restarts})")
    return last


if __name__ == "__main__":
    main()
