"""Pallas TPU kernel: the paper-faithful PASM two-phase GEMM.

This is the literal TPU mapping of the PASM circuit (paper §2.2):

  PAS phase    — per k-tile, image values are accumulated into ``B`` bin
                 accumulators that live in a VMEM scratch block
                 (``S[b, m, n] += x[m, k]·[idx[k, n] = b]``); the bin
                 accumulators are the VMEM analogue of the PAS register file.
  post-pass    — at the *last* k step only, one multiply per bin folds the
                 codebook in: ``y[m, n] = Σ_b S[b, m, n]·cb[b]`` — the
                 "shared post-pass MAC" of the paper, amortized over the
                 whole reduction.  The bins fold in ascending ``b`` order in
                 every kernel here, so explicit and implicit engines round
                 identically.

The PAS phase is one MXU matmul per bin, ``x_tile @ [idx_tile = b]``, so it
costs ``B×`` the MACs of a direct product — on a fixed systolic array the
paper's gate-level win does not transfer (DESIGN.md §2).  This kernel exists
to (a) demonstrate the faithful formulation end-to-end, (b) let benchmarks
*measure* that trade-off against ``pasm_matmul`` instead of assuming it.

VMEM budget: scratch ``(B, bm, bn)`` f32 = 16·128·128·4 = 1 MiB at defaults
(bins lead, so no bin count is ever padded to a lane tile).

:func:`pas_conv_kernel_call` is the implicit-GEMM conv variant: the ``x``
operand is the phase-layout image batch and the patch tile is assembled in
VMEM by :func:`repro.kernels.pasm_matmul.assemble_tile` — same PAS phase and
post-pass, no ``(B·P, K)`` patch matrix in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pasm_matmul import (
    ConvGeom,
    SlabPlan,
    _dot,
    _params,
    assemble_tile,
    conv_image_spec,
    conv_out_spec,
    epilogue_store,
)

__all__ = ["pas_matmul_kernel_call", "pas_conv_kernel_call"]


def _pas_step(
    lhs, idx_ref, cb_ref, b_ref, o_ref, s_ref, *, k, n_k: int, relu: bool,
    pool: int = 1, transposed: bool = False,
):
    """The shared per-k-step body of BOTH entry points: PAS-phase one-hot
    accumulate into the VMEM bin scratch, then the post-pass multiply (plus
    the fused bias/ReLU/max-pool epilogue) at the last k step only.
    ``o_ref`` may carry leading length-1 axes (the conv grid)."""
    idx = idx_ref[...].astype(jnp.int32)  # Mosaic compares in 32 bits
    bins = s_ref.shape[0]
    for b in range(bins):
        s_ref[b] += _dot(lhs, (idx == b).astype(lhs.dtype), transposed)

    # post-pass multiply: executed once, after all accumulation — B multiplies
    # per output element instead of K.  The bias/ReLU epilogue rides the same
    # write-through (the paper's shared post-pass MAC carries the bias too).
    @pl.when(k == n_k - 1)
    def _postpass():
        y = s_ref[0] * cb_ref[0, 0]
        for b in range(1, bins):
            y = y + s_ref[b] * cb_ref[0, b]
        epilogue_store(y, b_ref, o_ref, relu=relu, pool=pool,
                       transposed=transposed)


def _kernel(x_ref, idx_ref, cb_ref, *rest, n_k: int, relu: bool, pool: int):
    b_ref, o_ref, s_ref = rest if len(rest) == 3 else (None, *rest)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        s_ref[...] = jnp.zeros_like(s_ref)

    _pas_step(
        x_ref[...], idx_ref, cb_ref, b_ref, o_ref, s_ref,
        k=k, n_k=n_k, relu=relu, pool=pool,
    )


def pas_matmul_kernel_call(
    x: jax.Array,
    idx: jax.Array,
    codebook: jax.Array,
    bias: "jax.Array | None" = None,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    relu: bool = False,
    pool: int = 1,
    interpret: bool = False,
    name: str = "pas_matmul",
) -> jax.Array:
    """``x (M,K) · idx (K,N) · codebook (1,B) → (M,N) f32`` (single dictionary).

    Paper-faithful: one dictionary per layer (groups == 1).  ``bias (1, N)``
    and ``relu`` fuse into the post-pass; ``pool > 1`` expects window-major
    rows and max-reduces each ``pool²`` group there too, returning the
    pooled ``(M/pool², N)``.  Shape preconditions as for
    :func:`pasm_matmul_kernel_call`.
    """
    M, K = x.shape
    N = idx.shape[1]
    G, B = codebook.shape
    assert G == 1, "PAS-formulation kernel is paper-faithful: one dictionary"
    pw = pool * pool
    assert bm % pw == 0 and M % pw == 0, (bm, M, pool)
    n_k = K // bk

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    operands = [x, idx, codebook.astype(jnp.float32)]
    if bias is not None:
        assert bias.shape == (1, N), bias.shape
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        operands.append(bias)

    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, relu=relu, pool=pool),
        grid=(M // bm, N // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm // pw, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M // pw, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((B, bm, bn), jnp.float32)],
        compiler_params=_params(3),
        interpret=interpret,
        name=name,
    )(*operands)


def _pas_conv_kernel(
    x_ref, idx_ref, cb_ref, *rest, geom: ConvGeom, plan: SlabPlan,
    n_k: int, relu: bool, bk: int, gs: int, gs_pad: int,
):
    """Implicit-GEMM body: assemble the transposed patch tile, then the same
    :func:`_pas_step` as the explicit GEMM."""
    *rest, t_ref, s_ref = rest
    b_ref, o_ref = rest if len(rest) == 2 else (None, rest[0])
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _zero():
        s_ref[...] = jnp.zeros_like(s_ref)

    base = (pl.program_id(1) % plan.n_blocks) * plan.bmp
    assemble_tile(x_ref, t_ref, base, k * bk, geom=geom, plan=plan, bk=bk,
                  gs=gs, gs_pad=gs_pad)
    _pas_step(
        t_ref[:bk, :], idx_ref, cb_ref, b_ref, o_ref, s_ref,
        k=k, n_k=n_k, relu=relu, pool=geom.pool, transposed=True,
    )


def pas_conv_kernel_call(
    x: jax.Array,
    idx: jax.Array,
    codebook: jax.Array,
    bias: "jax.Array | None" = None,
    *,
    geom: ConvGeom,
    plan: SlabPlan,
    gs: int,
    gs_pad: int,
    bn: int = 128,
    bk: int = 512,
    relu: bool = False,
    interpret: bool = False,
    name: str = "pas_conv",
) -> jax.Array:
    """Implicit-GEMM conv on the paper-faithful two-phase formulation.

    Operands and output as :func:`~repro.kernels.pasm_matmul.
    pasm_conv_kernel_call` (phase-layout image in, pooled wide pixels out;
    the fused max-pool rides the post-pass).  Single dictionary only, like
    :func:`pas_matmul_kernel_call`.
    """
    B_img, n_slabs, _, c_in, _ = x.shape
    G, B = codebook.shape
    assert G == 1, "PAS-formulation kernel is paper-faithful: one dictionary"
    Np = idx.shape[1]
    Kp = idx.shape[0]
    assert Kp == gs_pad and gs_pad % bk == 0, (Kp, gs_pad, bk)
    rows = geom.pool * geom.pool * plan.bmp
    n_k = Kp // bk

    in_specs = [
        conv_image_spec(geom, plan, c_in),
        pl.BlockSpec((bk, bn), lambda b, i, j, k: (k, j)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    operands = [x, idx, codebook.astype(jnp.float32)]
    if bias is not None:
        assert bias.shape == (1, Np), bias.shape
        in_specs.append(pl.BlockSpec((1, bn), lambda b, i, j, k: (0, j)))
        operands.append(bias)

    return pl.pallas_call(
        functools.partial(
            _pas_conv_kernel, geom=geom, plan=plan, n_k=n_k, relu=relu,
            bk=bk, gs=gs, gs_pad=gs_pad,
        ),
        grid=(B_img, n_slabs * plan.n_blocks, Np // bn, n_k),
        in_specs=in_specs,
        out_specs=conv_out_spec(plan, bn),
        out_shape=jax.ShapeDtypeStruct(
            (B_img, n_slabs, plan.n_blocks * plan.bmp, Np), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((-(-bk // 8) * 8, rows), x.dtype),
            pltpu.VMEM((B, rows, bn), jnp.float32),
        ],
        compiler_params=_params(4),
        interpret=interpret,
        name=name,
    )(*operands)
