"""Measured latency of the conv-accelerator variants (paper §5 analog).

Two tiers:

* ``conv_variants_latency`` — the paper's own §4 single-image configuration,
  all three einsum ports.  On TPU hardware the PASM variant's +N→N+B latency
  shows up per §4; on this CPU container we confirm (a) numerical agreement
  and (b) the relative cost ordering — the PAS-histogram formulation costs
  ≈B× the MACs of the direct product, exactly the DESIGN.md §2 trade-off.

* ``batched_conv_latency`` / ``cnn_forward_latency`` — the production shape
  of the same workload (DESIGN.md §3): batched convs on the Pallas GEMMs at
  realistic AlexNet layer sizes (224×224×3→96, 27×27×96→256) with the
  bias/ReLU epilogue fused into the kernels, comparing the einsum port
  against ``pasm_matmul`` (explicit im2col), ``pasm_conv2d``
  (``kernel_implicit`` — implicit im2col, no patch matrix in HBM),
  ``pas_matmul`` (paper-faithful two-phase), and the **fused
  conv/ReLU/max-pool stage** (``conv.batched.kernel_implicit_pool.*`` —
  ``conv2d(pool=2)``, one pallas_call storing only the pooled map).  Every
  row carries a modeled ``hbm_bytes`` column — tile-plan aware
  (``ops.conv_hbm_bytes``) for the Pallas engines, the analytic
  ``hwmodel.conv_hbm_traffic`` (dense f32 weight stream) for the einsum
  rows — plus the ``engine``/``pool`` stamps, so fused and unfused rows
  stay comparable.  Every row also stamps ``slab_rows``/``n_slabs`` — the
  row-band slab plan the implicit engine uses at that layer shape
  (``n_slabs == 1`` → whole image VMEM-resident); the over-budget
  ``bigimg_conv1`` layer (3×512×512, double-buffered residency ≈ 6.3 MB >
  the 6 MiB budget) records ``n_slabs >= 2`` and strictly fewer implicit
  than explicit modeled bytes — the ci.sh slab gate.  On CPU the kernels
  run in interpret mode, so the *bytes* column is the hardware-meaningful
  trajectory signal and µs only compares formulations on equal footing
  (``--smoke`` shrinks batch/iters for CI).

``--json [PATH]`` additionally writes every row to ``BENCH_conv.json`` so CI
tracks the engine trajectory from this PR onward; ``--engine e1,e2`` runs
*only* the batched suite restricted to those engines (the CI comparison mode
that gates implicit-vs-explicit modeled HBM bytes).

``--devices N`` runs the *sharded* suite instead, on N host-platform fake
devices (the flag must be seen before jax initializes, so it is peeked off
``sys.argv`` below): every conv layer dispatches through ``conv2d(mesh=)``
over a ``(N, 1)`` data mesh, and each row reports per-device throughput
(``img/s/dev``) plus the modeled **per-device** HBM bytes
(``ops.conv_hbm_bytes(shards=)``) next to the single-device figure
(``hbm_bytes_1dev``) — the CI gate asserts per-device < single-device on
AlexNet conv1.

    PYTHONPATH=src python benchmarks/conv_bench.py [--smoke] [--json [PATH]]
                                                   [--engine e1,e2]
                                                   [--devices N]
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT / "src"))
sys.path.insert(0, str(_ROOT))  # direct-script runs: make `benchmarks` importable

def _peek_devices(argv):
    """--devices N / --devices=N, read before argparse (and before jax)."""
    for i, a in enumerate(argv):
        if a == "--devices" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--devices="):
            return a.split("=", 1)[1]
    return None


_dev_arg = _peek_devices(sys.argv)
if _dev_arg is not None:
    # the fake-device count must be pinned BEFORE the first jax import;
    # invalid values (non-int, < 1) are left for the argparse check below
    # rather than crashing deep inside CPU-backend init
    try:
        if int(_dev_arg) >= 1:
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={int(_dev_arg)} "
                + os.environ.get("XLA_FLAGS", "")
            )
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
    except ValueError:
        pass

import jax
import jax.numpy as jnp

from repro.configs.alexnet_conv import PAPER_SPEC
from repro.core import conv as cv
from repro.core import hwmodel as hw
from repro.kernels import ops

from benchmarks.common import bench_row, emit, time_us

# the ISSUE's realistic layer sizes: AlexNet conv1 and conv2 (geometry-free
# specs; the image dims ride with the inputs), plus a conv1-style layer on a
# 512×512 image whose double-buffered residency (2·3·512·512·4 ≈ 6.3 MB)
# overflows the 6 MiB VMEM budget — the implicit engine streams it as
# row-band slabs (n_slabs ≥ 2 in the row stamps; the ci.sh slab gate)
REALISTIC_LAYERS = (
    ("alexnet_conv1", cv.Conv2D(k=11, c_in=3, c_out=96, stride=4, relu=True),
     (224, 224)),
    ("alexnet_conv2", cv.Conv2D(k=5, c_in=96, c_out=256, stride=1, relu=True),
     (27, 27)),
    ("bigimg_conv1", cv.Conv2D(k=11, c_in=3, c_out=96, stride=4, relu=True),
     (512, 512)),
)

PAPER_CONV = cv.Conv2D(k=(PAPER_SPEC.KY, PAPER_SPEC.KX), c_in=PAPER_SPEC.C,
                       c_out=PAPER_SPEC.M, stride=PAPER_SPEC.stride)

BATCH_ENGINES = ("einsum", "kernel", "kernel_implicit", "pas_kernel")

_RECORDS: list = []


def record(name: str, us_per_call: float, derived: str = "", hbm_bytes=None,
           mesh_shape=None, **extra) -> None:
    emit(name, us_per_call, derived, hbm_bytes=hbm_bytes)
    _RECORDS.append(bench_row(name, us_per_call, hbm_bytes=hbm_bytes,
                              derived=derived, mesh_shape=mesh_shape, **extra))


def _slab_info(t_gemm, geom, ih, iw) -> dict:
    """``slab_rows``/``n_slabs`` row stamps: the row-band slab plan the
    implicit engine uses at this layer shape under the default VMEM budget
    (``n_slabs == 1`` → whole-image resident; the ci.sh slab gate asserts
    the over-budget bigimg rows stream with ``n_slabs >= 2``)."""
    (plh, phh), (plw, phw) = geom.pad
    hp, wp = ih + plh + phh, iw + plw + phw
    K, N = t_gemm.shape
    G, B = t_gemm.codebook.shape
    plan = ops.conv_tile_plan(geom, hp, wp, k=K, n=N, groups=G, bins=B,
                              packed=t_gemm.packed).plan
    return {"slab_rows": plan.band_rows, "n_slabs": plan.n_slabs}


def _analytic_hbm(conv, ih, iw, batch, *, bins=16, implicit=False,
                  dense=False, pool=1):
    """`hwmodel.conv_hbm_traffic` on a Conv2D spec — the plan-free model that
    fills rows the tile-aware `ops.conv_hbm_bytes` cannot describe (einsum
    streams dense f32 weights, not indexed operands)."""
    geom = cv.conv_geom(conv, ih, iw)
    (plh, phh), (plw, phw) = geom.pad
    return hw.conv_hbm_traffic(
        IH=ih, IW=iw, C=conv.c_in, KY=conv.ky, KX=conv.kx, M=conv.c_out,
        stride=conv.stride, batch=batch, bins=bins, pad=(plh, phh, plw, phw),
        act_bytes=4, packed=False, implicit=implicit, pool=pool, dense=dense,
    )


def conv_variants_latency():
    key = jax.random.PRNGKey(0)
    img = jax.random.normal(key, (PAPER_SPEC.C, PAPER_SPEC.IH, PAPER_SPEC.IW))
    kern = jax.random.normal(
        jax.random.PRNGKey(1),
        (PAPER_SPEC.M, PAPER_SPEC.C, PAPER_SPEC.KY, PAPER_SPEC.KX),
    )
    hbm_dense = _analytic_hbm(PAPER_CONV, PAPER_SPEC.IH, PAPER_SPEC.IW, 1,
                              dense=True)
    for bins in (4, 8, 16):
        p = cv.ConvParams.quantize(kern, bins)
        dense = cv.ConvParams.dense(p.codebook[p.idx.astype(jnp.int32)])
        f_direct = jax.jit(lambda i, d=dense: cv.conv2d(i, d, PAPER_CONV))
        f_ws = jax.jit(lambda i, p=p: cv.conv2d(i, p, PAPER_CONV, engine="einsum"))
        f_pasm = jax.jit(lambda i, p=p: cv.conv2d(i, p, PAPER_CONV, engine="pas_einsum"))
        t_d = time_us(f_direct, img)
        t_w = time_us(f_ws, img)
        t_p = time_us(f_pasm, img)
        hbm_ws = _analytic_hbm(PAPER_CONV, PAPER_SPEC.IH, PAPER_SPEC.IW, 1,
                               bins=bins)
        slab = _slab_info(p.gemm_tensor(PAPER_CONV.layout),
                          cv.conv_geom(PAPER_CONV, PAPER_SPEC.IH, PAPER_SPEC.IW),
                          PAPER_SPEC.IH, PAPER_SPEC.IW)
        record(f"conv.direct.B{bins}", t_d, hbm_bytes=hbm_dense,
               engine="einsum", pool=1, **slab)
        record(f"conv.weight_shared.B{bins}", t_w, hbm_bytes=hbm_ws,
               engine="einsum", pool=1, **slab)
        record(f"conv.pasm.B{bins}", t_p, f"pasm/ws={t_p / max(t_w, 1e-9):.2f}",
               hbm_bytes=hbm_ws, engine="pas_einsum", pool=1, **slab)


def batched_conv_latency(smoke: bool = False, engines=BATCH_ENGINES):
    """Realistic layers, batched: einsum vs kernel vs kernel_implicit vs pas.

    Each row carries the tile-plan-aware modeled HBM bytes of its dataflow —
    explicit engines pay the materialized-patch-matrix write+read, implicit
    streams the padded image once per reuse window.
    """
    batch = 1 if smoke else 8
    iters = 1 if smoke else 5
    warmup = 1 if smoke else 2
    for name, conv, (ih, iw) in REALISTIC_LAYERS:
        imgs = jax.random.normal(jax.random.PRNGKey(2), (batch, conv.c_in, ih, iw))
        kern = jax.random.normal(
            jax.random.PRNGKey(3), (conv.c_out, conv.c_in, conv.ky, conv.kx)
        ) * conv.K ** -0.5
        params = cv.ConvParams.quantize(
            kern, 16, bias=jnp.linspace(-0.1, 0.1, conv.c_out)
        )
        t_gemm = params.gemm_tensor(conv.layout)
        geom = cv.conv_geom(conv, ih, iw)
        oh, ow = cv.conv_out_hw(ih, iw, conv)
        derived = f"P={batch * oh * ow} K={conv.K} M={conv.c_out}"
        slab = _slab_info(t_gemm, geom, ih, iw)

        for engine in engines:
            if engine == "pas_kernel" and smoke and (conv.K > 1000
                                                     or geom.P > 8000):
                # no silent caps: the one-hot PAS formulation costs B× the
                # MACs — conv2's K=2400 (or bigimg's P=15876 rows) is
                # minutes in interpret mode
                print(f"# skipped conv.batched.pas_kernel.{name}: K={conv.K} "
                      f"P={geom.P} too large for CI smoke (interpret mode)",
                      file=sys.stderr)
                continue
            # the tile-aware model describes the Pallas-kernel dataflows; the
            # XLA einsum port streams dense f32 weights over an explicit
            # patch matrix, which the analytic hwmodel covers (dense=True)
            if engine == "einsum":
                hbm = _analytic_hbm(conv, ih, iw, batch, dense=True)
            else:
                hbm = ops.conv_hbm_bytes(
                    t_gemm, geom, batch, ih, iw,
                    implicit=engine == "kernel_implicit", act_bytes=4,
                )
            f = jax.jit(lambda i, p=params, c=conv, e=engine:
                        cv.conv2d(i, p, c, engine=e))
            t = time_us(f, imgs, iters=iters, warmup=warmup)
            record(f"conv.batched.{engine}.{name}.bs{batch}", t, derived,
                   hbm_bytes=hbm, engine=engine, pool=1, **slab)

        if "kernel_implicit" in engines:
            # the fused conv/ReLU/max-pool stage (PR 5): ONE pallas_call,
            # pooled in-kernel — the AlexNet pool=2 window of both layers
            pool = 2
            geom_p = cv.conv_geom(conv, ih, iw, pool=pool)
            hbm_p = ops.conv_hbm_bytes(t_gemm, geom_p, batch, ih, iw,
                                       implicit=True, act_bytes=4)
            f = jax.jit(lambda i, p=params, c=conv, q=pool:
                        cv.conv2d(i, p, c, engine="kernel_implicit", pool=q,
                                  pool_impl="fused"))
            t = time_us(f, imgs, iters=iters, warmup=warmup)
            record(f"conv.batched.kernel_implicit_pool.{name}.bs{batch}", t,
                   f"{derived} pool={pool}", hbm_bytes=hbm_p,
                   engine="kernel_implicit", pool=pool,
                   **_slab_info(t_gemm, geom_p, ih, iw))


def sharded_conv_latency(
    n_devices: int, smoke: bool = False, engines=("kernel_implicit",)
):
    """Realistic layers through ``conv2d(mesh=)`` on an ``(N, 1)`` data mesh.

    One image per device at smoke scale (4 per device otherwise), so the
    per-device work matches the single-device smoke row.  Each row carries
    per-device throughput (``img/s/dev`` — wall time covers all shards, so
    device-seconds are ``t·N``) and the modeled per-device HBM bytes
    alongside the single-device figure for the same global batch.
    """
    from repro.launch.mesh import make_conv_mesh

    mesh = make_conv_mesh((n_devices, 1))
    batch = n_devices * (1 if smoke else 4)
    iters = 1 if smoke else 5
    warmup = 1 if smoke else 2
    for name, conv, (ih, iw) in REALISTIC_LAYERS:
        imgs = jax.random.normal(jax.random.PRNGKey(2), (batch, conv.c_in, ih, iw))
        kern = jax.random.normal(
            jax.random.PRNGKey(3), (conv.c_out, conv.c_in, conv.ky, conv.kx)
        ) * conv.K ** -0.5
        params = cv.ConvParams.quantize(
            kern, 16, bias=jnp.linspace(-0.1, 0.1, conv.c_out)
        )
        t_gemm = params.gemm_tensor(conv.layout)
        geom = cv.conv_geom(conv, ih, iw)
        slab = _slab_info(t_gemm, geom, ih, iw)
        for engine in engines:
            if engine in ("einsum", "pas_kernel") and smoke and conv.K > 1000:
                print(f"# skipped conv.sharded.{engine}.{name}: K={conv.K} "
                      "too large for CI smoke (interpret mode)", file=sys.stderr)
                continue
            hbm_dev = hbm_1dev = None
            if engine != "einsum":
                kw = dict(implicit=engine == "kernel_implicit", act_bytes=4)
                hbm_dev = ops.conv_hbm_bytes(
                    t_gemm, geom, batch, ih, iw, shards=(n_devices, 1), **kw
                )
                hbm_1dev = ops.conv_hbm_bytes(t_gemm, geom, batch, ih, iw, **kw)
            f = jax.jit(lambda i, p=params, c=conv, e=engine:
                        cv.conv2d(i, p, c, engine=e, mesh=mesh))
            t = time_us(f, imgs, iters=iters, warmup=warmup)
            img_s_dev = batch / n_devices / (t * 1e-6)
            record(
                f"conv.sharded.{engine}.{name}.bs{batch}.d{n_devices}", t,
                f"P={batch * geom.P} K={conv.K} M={conv.c_out} "
                f"img/s/dev={img_s_dev:.1f}",
                hbm_bytes=hbm_dev, mesh_shape=(n_devices, 1),
                hbm_bytes_1dev=hbm_1dev, engine=engine, pool=1, **slab,
            )


def cnn_forward_latency(smoke: bool = True):
    """Full AlexNet-style stack forward on the fused-dequant kernel path."""
    from repro.configs import get_cnn_config
    from repro.models import cnn

    cfg = get_cnn_config("alexnet", smoke=smoke)
    params = cnn.quantize(cnn.init_params(cfg, jax.random.PRNGKey(0)), cfg)
    batch = 2 if smoke else 8
    imgs = jax.random.normal(jax.random.PRNGKey(1), (batch, *cfg.in_chw))
    iters = 1 if smoke else 5
    t = time_us(lambda i: cnn.forward(params, i, cfg), imgs, iters=iters, warmup=1)
    # stack-level modeled bytes: resolve each stage's engine and pool
    # dispatch through cv.conv_plan — the same rule conv2d routes through —
    # so the row never claims a fused (or implicit) dataflow the measured
    # run didn't take
    hbm = 0
    n_slabs = 1  # stack stamp: the worst (max) per-stage slab count
    _, H, W = cfg.in_chw
    for p, (conv, pool) in zip(params["conv"], cnn.stages(cfg)):
        eng, fused = cv.conv_plan(p, conv, H, W, engine=cfg.impl, pool=pool,
                                  pool_impl=cfg.pool_impl,
                                  vmem_budget=cfg.vmem_budget)
        geom = cv.conv_geom(conv, H, W, pool=pool if fused else 1)
        t_gemm = p.gemm_tensor(cfg.layout)
        hbm += ops.conv_hbm_bytes(t_gemm, geom, batch, H, W,
                                  implicit="implicit" in eng, act_bytes=4)
        if "implicit" in eng:
            n_slabs = max(n_slabs, _slab_info(t_gemm, geom, H, W)["n_slabs"])
        if not fused and pool > 1:
            # the separate reduce_window pass: read pre-pool, store pooled
            hbm += batch * conv.c_out * 4 * (
                geom.oh * geom.ow + (geom.oh // pool) * (geom.ow // pool))
        H, W = geom.oh // pool, geom.ow // pool
    record(f"cnn.forward.{cfg.name}.bs{batch}", t, f"layers={len(cfg.layers)}",
           hbm_bytes=hbm, engine=cfg.impl, pool=None,  # per-stage pools vary
           slab_rows=None, n_slabs=n_slabs)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizing: batch 1-2, single timed iteration")
    ap.add_argument("--json", nargs="?", const="BENCH_conv.json", default=None,
                    metavar="PATH", help="also write rows to a JSON file "
                    "(default BENCH_conv.json)")
    ap.add_argument("--engine", default=None, metavar="E1,E2",
                    help="run ONLY the batched suite, restricted to these "
                    f"conv2d engines (choices: {','.join(BATCH_ENGINES)}) — "
                    "the CI implicit-vs-explicit comparison mode")
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="run ONLY the sharded suite on N host-platform fake "
                    "devices (conv2d(mesh=) over an (N, 1) data mesh); rows "
                    "report per-device throughput and modeled per-device "
                    "HBM bytes")
    args = ap.parse_args()
    engines = None
    if args.engine:
        engines = tuple(e.strip() for e in args.engine.split(",") if e.strip())
        bad = [e for e in engines if e not in BATCH_ENGINES]
        if bad:
            ap.error(f"unknown engine(s) {bad}; choices: {BATCH_ENGINES}")
    print("name,us_per_call,hbm_bytes,derived")
    if args.devices is not None:
        if args.devices < 1:
            ap.error(f"--devices must be >= 1, got {args.devices}")
        if jax.device_count() < args.devices:
            ap.error(f"--devices {args.devices}: only {jax.device_count()} "
                     "devices came up (the XLA_FLAGS peek runs before jax "
                     "init; is another backend pinned?)")
        sharded_conv_latency(args.devices, smoke=args.smoke,
                             engines=engines or ("kernel_implicit",))
    elif engines:
        batched_conv_latency(smoke=args.smoke, engines=engines)
    else:
        conv_variants_latency()
        batched_conv_latency(smoke=args.smoke)
        cnn_forward_latency(smoke=args.smoke)
    if args.json:
        payload = {
            "benchmark": "conv",
            "smoke": bool(args.smoke),
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "devices": args.devices or 1,
            "records": _RECORDS,
        }
        Path(args.json).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"wrote {len(_RECORDS)} records to {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
