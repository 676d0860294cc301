"""Public jit'd wrappers around the Pallas kernels.

Handles: shape padding to tile multiples, block-size selection, packed-int4
plumbing, interpret mode off-TPU (the CPU test path — any TPU backend compiles
the kernels with Mosaic, and chip runs pass ``interpret=False`` explicitly),
and a custom VJP so PASM layers are
differentiable (gradient w.r.t. activations flows through the dequantized
weight; quantized weights are leaves without gradients — QAT uses
``repro.core.qat`` on the dense master copy instead).

Every public wrapper additionally takes ``mesh=``: a ``jax.sharding.Mesh``
with a ``data`` axis (and optionally ``model``) routes the call through
``shard_map`` — rows/batch shard over ``data``, the output-channel N
dimension over ``model`` when it divides, and the per-shard call is the SAME
single-device impl on the *local* shapes.  The reduction axis K is never
sharded and the k-tile plan (``bk``/``gs_pad``) is a pure function of
K/groups alone, so every output element sees the identical accumulation
order on any mesh — sharded outputs are bit-exact vs single-device
(DESIGN.md §4.1).  Codebooks (and the PAS formulation's in-kernel bin
counters) stay per-shard-replicated; bias follows the N sharding.

Every public wrapper also takes ``name=``: the kernel's name in the compiled
program and in a profiler trace (``pallas_call(name=)``; XLA appends
``.<n>``).  It defaults to the kernel family: ``pasm_matmul``,
``pas_matmul``, ``pasm_conv``, ``pas_conv``.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pasm as _pasm
from repro.kernels import ref as _ref
from repro.kernels.pas_histogram import pas_conv_kernel_call, pas_matmul_kernel_call
from repro.kernels.pasm_matmul import (
    LANE,
    ConvGeom,
    SlabPlan,
    pasm_conv_kernel_call,
    pasm_matmul_kernel_call,
    phase_slabs,
)

__all__ = [
    "pasm_matmul",
    "pas_matmul",
    "pasm_conv2d",
    "pas_conv2d",
    "ConvGeom",
    "SlabPlan",
    "ConvTilePlan",
    "conv_slab_plan",
    "conv_tile_plan",
    "conv_whole_image_fits",
    "IMPLICIT_VMEM_BUDGET",
    "matmul_flops",
    "pasm_hbm_bytes",
    "conv_hbm_bytes",
    "pool_plan_exists",
]

# Per-grid-step VMEM budget (bytes) the slab planner sizes the implicit conv
# engines against.  Suits a ~16 MiB-VMEM TPU core with headroom for Mosaic's
# own allocations; per-call targets override via ``vmem_budget=``.  Keep in
# sync with ``repro.core.conv._IMPLICIT_VMEM_BUDGET`` (the dispatch-level
# default that conv2d resolves and threads down here).
IMPLICIT_VMEM_BUDGET = 6 * 1024 * 1024


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


# ---------------------------------------------------------------------------
# mesh plumbing (the shard_map sharded path)
# ---------------------------------------------------------------------------


def _mesh_sizes(mesh) -> tuple:
    """``(n_data, n_model)`` — one definition, in :mod:`repro.launch.mesh`."""
    from repro.launch.mesh import data_model_sizes

    return data_model_sizes(mesh)


def _n_spec(mesh, n: int):
    """N over ``model`` when divisible, else replicate — the shared
    :func:`repro.launch.mesh.n_shard_axis` rule (indivisible ``c_out`` keeps
    idx/bias N-replicated while ``data`` still shards the rows)."""
    from repro.launch.mesh import n_shard_axis

    return n_shard_axis(mesh, n)


def _shard_map(fn, mesh, in_specs, out_specs):
    # check_vma=False: the N-replicated fallback computes identical outputs
    # on every model-axis device, which the varying-axes checker cannot
    # prove through a pallas_call.
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def _shard_gemm(mesh, n_cols, local_fn, operands, *, x_rank, out_rank,
                bias=None, gather_output=False):
    """The one shard_map dispatch every sharded wrapper routes through.

    ``operands = (x, idx, codebook)`` (+ ``bias`` appended when given): x
    shards its leading dim over ``data``, idx rides ``P(None, ns)`` with
    ``ns`` the shared N rule, the codebook replicates, bias follows the N
    sharding, and the output puts ``data`` leading / ``ns`` trailing at
    ``out_rank``.  ``local_fn`` is the per-shard single-device impl —
    callers keep their own bias/no-bias *impl* split so the sharded call
    mirrors the single-device branch structure exactly (part of the bitwise
    guarantee), but the spec plumbing lives only here.

    ``gather_output=True`` fuses the inter-layer all-gather into the kernel
    epilogue: when N actually shards over ``model``, each shard's output is
    ``all_gather``'d (tiled, axis-index order — the exact N-tile layout)
    *inside* the shard_map body right after the pallas_call, and the out
    spec drops the trailing ``ns`` (model-replicated activations).  The next
    layer's x operand is then already replicated over ``model``, so XLA has
    no reshard to insert between consecutive pallas_calls (DESIGN.md §4.1).
    Tiled all-gather concatenates the per-device N tiles in order — the
    bitwise-identical full-N output.  Differentiable: the all-gather's
    transpose is a psum_scatter, so the fused collective rides the existing
    custom VJPs unchanged.
    """
    from jax.sharding import PartitionSpec as P

    ns = _n_spec(mesh, n_cols)
    in_specs = (P("data", *([None] * (x_rank - 1))), P(None, ns), P(None, None))
    if bias is not None:
        in_specs += (P(ns),)
        operands = operands + (bias,)
    fn, out_ns = local_fn, ns
    if gather_output and ns is not None:
        def fn(*ops):
            return jax.lax.all_gather(local_fn(*ops), ns, axis=-1, tiled=True)

        out_ns = None
    out_spec = P("data", *([None] * (out_rank - 2)), out_ns)
    return _shard_map(fn, mesh, in_specs, out_spec)(*operands)


def _pick_blocks(M: int, K: int, N: int, group_size: int, packed: bool):
    """Tile plan for an (M, K)·(K, N) PASM matmul.

    Returns ``(bm, bn, bk, gs_pad)`` where ``gs_pad`` is the padded per-group
    reduction length (``== group_size`` when the group tiles exactly).  A
    group that fits one k-tile (``group_size <= 512``) is never padded — this
    keeps the seed's tiling (and its numerics) on every aligned shape.  Larger
    groups must split into 128-aligned k-tiles; when no such divisor exists
    (e.g. conv im2col reductions like K = C·KY·KX = 2400) the group is padded
    up to the next 128 multiple and :func:`_pad_operands` maps the pad rows to
    a reserved zero-codebook bin instead of the former hard ``ValueError``.
    """
    bm = min(128, _round_up(M, 8))
    bn = min(128, _round_up(N, 128))
    if group_size <= 512 and not (packed and group_size % 2):
        return bm, bn, group_size, group_size  # one k-tile per group
    bk = 512
    while bk >= 128 and group_size % bk:
        bk //= 2
    if bk >= 128:
        return bm, bn, bk, group_size
    if packed and group_size % 2:
        # packed nibbles straddle the group boundary: no consistent layout
        raise ValueError(f"packed int4 needs an even group size, got {group_size}")
    gs_pad = _round_up(group_size, 128)
    bk = min(512, gs_pad)
    while gs_pad % bk:
        bk //= 2
    return bm, bn, bk, gs_pad


def _pool_row_align(pool: int) -> int:
    """Rows a pooled block must be a multiple of: ``lcm(pool², 8)`` — whole
    pool windows (the epilogue max is a ``(bm/pool², pool², bn)`` reshape)
    at MXU row alignment."""
    pw = pool * pool
    return pw * 8 // math.gcd(pw, 8)


def pool_plan_exists(pool: int) -> bool:
    """Whether a pool-aligned tile plan exists (``lcm(pool², 8) ≤ 256``
    rows).  THE source of truth shared by :func:`_pool_bm` and
    ``conv2d``'s fuse dispatch (:func:`repro.core.conv._pool_fusible`), so
    the two can never drift apart."""
    return pool == 1 or _pool_row_align(pool) <= 256


def _pool_bm(bm: int, pool: int) -> int:
    """Align ``bm`` to whole pool windows for the fused max-pool epilogue.

    Returns the largest :func:`_pool_row_align` multiple ≤ the unpooled
    ``bm`` (at least one window row group).  ``conv2d``'s dispatch only
    fuses when :func:`pool_plan_exists`, so the ValueError is a guard
    against direct misuse, not a reachable fallback.
    """
    if pool == 1:
        return bm
    a = _pool_row_align(pool)
    if not pool_plan_exists(pool):
        raise ValueError(
            f"no pool-aligned tile plan for pool={pool}: lcm(pool², 8)={a} "
            "exceeds the 256-row block cap — use the unfused reduce_window "
            "fallback (conv2d pool dispatch does this automatically)"
        )
    return max(a, bm - bm % a)


def _check_pool_operand(x, pool: int, mesh=None, n_data: int = 1) -> None:
    """The shared ``pool=`` preconditions of the explicit GEMM wrappers: a
    2-D window-major operand (``pool²`` consecutive rows per window), and —
    under ``mesh=`` — rows that split over ``data`` in whole pool windows
    (``conv2d`` guarantees this: it pads the batch to divide the axis, and
    each image contributes ``P_rows`` window-major rows, a multiple of
    ``pool²``, so per-image row runs never straddle a shard boundary)."""
    if x.ndim != 2 or x.shape[0] % (pool * pool):
        raise ValueError(
            "pool= needs a 2-D window-major x (pool² consecutive rows "
            f"per window), got shape {x.shape} with pool={pool}"
        )
    if mesh is not None and x.shape[0] % (n_data * pool * pool):
        raise ValueError(
            f"pool= under mesh= needs the window-major rows ({x.shape[0]}) "
            f"to split over the data axis ({n_data}) in whole pool windows; "
            "conv2d(mesh=) guarantees this by padding the batch first"
        )


def _conv_block_vmem_bytes(*, bmp: int, pool: int, bn: int, bk: int,
                           bins: int, packed: bool = False, pas: bool = False,
                           has_bias: bool = True, itemsize: int = 4) -> int:
    """Non-image VMEM bytes of one implicit-conv grid step.

    Counts what sits in VMEM next to the image block, at Mosaic's tile
    padding: the idx tile (uint8, 32-row tiles, halved when packed) and the
    bias row (one 8-row tile), both double-buffered like the pooled output
    block; the transposed patch tile the kernel assembles (``bk`` rows ×
    ``pool²·bmp`` lanes); and the accumulators — the PAS bin scratch
    (``bins + 1`` leaves room for the reserved pad bin) or, with a fused
    pool, the pre-pool accumulator.  The codebook lives in SMEM.
    """
    rows = pool * pool * bmp
    idx = 2 * _round_up(bk // 2 if packed else bk, 32) * bn
    bias = 2 * 8 * bn * 4 if has_bias else 0
    out = 2 * bmp * bn * 4
    tile = _round_up(bk, 8) * rows * itemsize
    if pas:
        scratch = (bins + 1) * rows * bn * 4
    else:
        scratch = rows * bn * 4 if pool > 1 else 0
    return idx + bias + out + tile + scratch


def _plan_rows(geom: ConvGeom, wq: int, rows_out: int, pas: bool) -> SlabPlan:
    """The :class:`SlabPlan` with ``rows_out`` pooled output rows per slab:
    blocks of ``bmp`` wide pixels (a lane multiple, at most 512 pre-pool
    rows per block — 128 for the PAS bin scratch), and the lane halo the
    largest tap offset reads past a slab's last block."""
    S = geom.phase
    pw = geom.pool * geom.pool
    cap = LANE if pas else max(LANE, 512 // pw // LANE * LANE)
    wide = rows_out * wq
    bmp = min(cap, _round_up(wide, LANE))
    n_blocks = -(-wide // bmp)
    halo_lanes = LANE * (geom.max_offset(wq) // LANE + 1)
    return SlabPlan(
        n_slabs=-(-max(geom.ohp, 1) // rows_out), rows_out=rows_out,
        band_rows=rows_out * S, halo_rows=max(geom.ky - geom.stride, 0),
        wq=wq, bmp=bmp, n_blocks=n_blocks, lanes=n_blocks * bmp + halo_lanes,
    )


def _plan_vmem_bytes(geom: ConvGeom, plan: SlabPlan, **blocks) -> int:
    """One grid step's VMEM: the double-buffered slab of phase images
    (``c_in`` rows padded to the 8-sublane tile) plus every other block."""
    itemsize = blocks.get("itemsize", 4)
    image = 2 * geom.phase ** 2 * _round_up(geom.c_in, 8) * plan.lanes * itemsize
    return image + _conv_block_vmem_bytes(bmp=plan.bmp, pool=geom.pool, **blocks)


def conv_whole_image_fits(
    geom: ConvGeom, hp: int, wp: int, *, bn: int, bk: int, bins: int,
    packed: bool = False, pas: bool = False, has_bias: bool = True,
    vmem_budget: Optional[int] = None, itemsize: int = 4,
) -> bool:
    """Whether the whole padded image (``hp × wp``) stays VMEM-resident.

    THE accounting shared by :func:`conv_slab_plan` and ``conv2d``'s
    :func:`repro.core.conv._implicit_fits` predicate: the phase-layout image
    counts **twice** (Pallas prefetches image ``b+1`` across the batch grid
    dimension — the double buffer is real VMEM) on top of every non-image
    per-grid-step block from :func:`_conv_block_vmem_bytes`.
    """
    del hp  # the phase layout's size follows from the output rows
    budget = IMPLICIT_VMEM_BUDGET if vmem_budget is None else vmem_budget
    wq = -(-wp // geom.phase)
    whole = _plan_rows(geom, wq, max(geom.ohp, 1), pas)
    return _plan_vmem_bytes(
        geom, whole, bn=bn, bk=bk, bins=bins, packed=packed, pas=pas,
        has_bias=has_bias, itemsize=itemsize,
    ) <= budget


def conv_slab_plan(
    geom: ConvGeom, hp: int, wp: int, *, bn: int, bk: int, bins: int,
    packed: bool = False, pas: bool = False, has_bias: bool = True,
    vmem_budget: Optional[int] = None, itemsize: int = 4,
) -> SlabPlan:
    """Size the implicit conv's image plan (DESIGN.md §3.3).

    Whole image first: when the double-buffered phase-layout image plus
    every non-image block fits ``vmem_budget``, the plan is a single slab.
    Otherwise the padded image is cut into the fewest row bands whose
    footprint fits (``rows_out`` pooled output rows each, balanced so the
    last band is not mostly padding).  Best-effort: when even one pooled
    row per slab exceeds the budget, the plan takes the fewest slabs at
    that smallest footprint — the budget is a sizing target, not a hard
    capacity, and slabs that save no VMEM are never cut.
    """
    budget = IMPLICIT_VMEM_BUDGET if vmem_budget is None else vmem_budget
    wq = -(-wp // geom.phase)
    ohp = max(geom.ohp, 1)
    blocks = dict(bn=bn, bk=bk, bins=bins, packed=packed, pas=pas,
                  has_bias=has_bias, itemsize=itemsize)

    def foot(rows):
        return _plan_vmem_bytes(geom, _plan_rows(geom, wq, rows, pas), **blocks)

    limit = max(budget, foot(1))
    rows = ohp
    while rows > 1 and foot(rows) > limit:
        rows -= 1
    n_slabs = -(-ohp // rows)
    return _plan_rows(geom, wq, -(-ohp // n_slabs), pas)


class ConvTilePlan(NamedTuple):
    """One implicit conv stage's whole tile plan (:func:`conv_tile_plan`).

    ``bk``/``gs_pad`` are the k-tile plan (:func:`_pick_blocks`, shared with
    the explicit GEMM); ``bn_conv`` is the output-channel block of one grid
    step, fed ``chunks_per_tile`` 128-lane chunks from one assembled patch
    tile; ``tiles_per_call`` counts the grid steps — patch-tile assemblies —
    of one call at the planned batch; ``plan`` is the image plan at
    ``bn_conv``.
    """

    bk: int
    gs_pad: int
    bn_conv: int
    chunks_per_tile: int
    tiles_per_call: int
    plan: SlabPlan


def conv_tile_plan(
    geom: ConvGeom, hp: int, wp: int, *, k: int, n: int, groups: int,
    bins: int, packed: bool = False, pas: bool = False, has_bias: bool = True,
    vmem_budget: Optional[int] = None, itemsize: int = 4, batch: int = 1,
) -> ConvTilePlan:
    """THE implicit conv planner, shared by ``_conv_fwd_impl`` (dispatch),
    :func:`repro.core.conv._implicit_fits` (planner) and
    :func:`conv_hbm_bytes` (model), so the three cannot drift apart.

    ``k`` is the GEMM reduction (pack-time K-pad included), ``n`` the
    output channels (the local share under a mesh).  The k-tile plan is
    :func:`_pick_blocks`'s.  The output-channel block ``bn_conv`` is the
    largest 128-multiple divisor of the padded ``Np`` whose
    :func:`conv_slab_plan` (same budget) cuts no more pixel blocks
    (``n_slabs·n_blocks``) than ``bn = 128`` does: every pixel block
    assembles its patch tile once per k-step and per output-channel block,
    so a wider block saves ``Np/bn_conv``-fold assemblies and must never buy
    them back with more slabs.  The PAS kernel keeps 128 (its bin scratch
    grows with the block).
    """
    _, bn, bk, gs_pad = _pick_blocks(geom.P_rows, k, n, k // groups, packed)
    n_pad = _round_up(n, bn)
    blocks = dict(bk=bk, bins=bins, packed=packed, pas=pas,
                  has_bias=has_bias, vmem_budget=vmem_budget,
                  itemsize=itemsize)
    plan = conv_slab_plan(geom, hp, wp, bn=bn, **blocks)
    pixel_blocks = plan.n_slabs * plan.n_blocks
    if not pas:
        for wide in range(n_pad, bn, -LANE):
            if n_pad % wide:
                continue
            cand = conv_slab_plan(geom, hp, wp, bn=wide, **blocks)
            if cand.n_slabs * cand.n_blocks <= pixel_blocks:
                bn, plan = wide, cand
                break
    tiles = (batch * plan.n_slabs * plan.n_blocks * (n_pad // bn)
             * (groups * gs_pad // bk))
    return ConvTilePlan(bk=bk, gs_pad=gs_pad, bn_conv=bn,
                        chunks_per_tile=bn // LANE, tiles_per_call=tiles,
                        plan=plan)


def _pad_weight_operands(idx, codebook, bn, gs_pad, packed):
    """K-pad (idx, codebook) per group and N-pad idx to the tile plan.

    K padding appends ``gs_pad - group_size`` index rows per group pointing
    at a reserved all-zero codebook bin (appended as bin ``B`` when
    representable), so padded positions are inert in both the fused-dequant
    and the PAS-histogram formulation — their paired activations are zero
    too (explicit path: zero-padded ``x`` rows; implicit path: the masked
    :func:`~repro.kernels.pasm_matmul.assemble_tile` mask).  When the pad bin
    is not representable (packed int4 at B=16, or B=256 saturating uint8)
    bin 0 is used instead — still exact, because the paired activations are
    zero.  Grouped codebooks pad per group so the kernel's
    ``k-block → group`` index map stays a pure division.  Returns
    ``(idx, codebook, N)`` with ``N`` the logical output width.
    """
    N = idx.shape[1]
    G, B = codebook.shape
    gs = idx.shape[0] * (2 if packed else 1) // G
    if gs_pad != gs:
        pad = gs_pad - gs
        if not packed and B < 256:
            codebook = jnp.pad(codebook, ((0, 0), (0, 1)))  # reserved zero bin
            pad_bin = B
        else:
            pad_bin = 0
        if packed:
            idxg = idx.reshape(G, gs // 2, N)
            idx = jnp.pad(idxg, ((0, 0), (0, pad // 2), (0, 0))).reshape(-1, N)
        else:
            idxg = idx.reshape(G, gs, N)
            idx = jnp.pad(
                idxg, ((0, 0), (0, pad), (0, 0)), constant_values=pad_bin
            ).reshape(-1, N)
    Np = _round_up(N, bn)
    idx = jnp.pad(idx, ((0, 0), (0, Np - N))) if Np != N else idx
    return idx, codebook, N


def _pad_operands(x, idx, codebook, bm, bn, gs_pad, packed):
    """Pad (x, idx, codebook) to the tile plan; returns logical (M, N, Kp).

    M/N padding is plain zero padding (sliced off the output); K padding is
    :func:`_pad_weight_operands` plus matching zero rows in ``x`` so padded
    positions are doubly inert.
    """
    M, K = x.shape
    G = codebook.shape[0]
    gs = K // G
    idx, codebook, N = _pad_weight_operands(idx, codebook, bn, gs_pad, packed)
    if gs_pad != gs:
        x = jnp.pad(x.reshape(M, G, gs), ((0, 0), (0, 0), (0, gs_pad - gs)))
        x = x.reshape(M, G * gs_pad)
        K = G * gs_pad
    Mp = _round_up(M, bm)
    x = jnp.pad(x, ((0, Mp - M), (0, 0))) if Mp != M else x
    return x, idx, codebook, (M, N, K)


@functools.partial(
    jax.jit,
    static_argnames=(
        "packed", "logical_k", "interpret", "use_ref", "relu", "pool", "name"
    ),
)
def _pasm_matmul_fwd_impl(
    x, idx, codebook, bias=None, *, packed, logical_k, interpret, use_ref,
    name, relu=False, pool=1,
):
    if use_ref:
        y = _ref.pasm_matmul_ref(x, idx, codebook, packed=packed)
        return _ref.max_pool_rows(_ref.apply_epilogue(y, bias, relu), pool)
    G, B = codebook.shape
    group_size = logical_k // G
    bm, bn, bk, gs_pad = _pick_blocks(
        x.shape[0], logical_k, idx.shape[1], group_size, packed
    )
    bm = _pool_bm(bm, pool)
    xp, idxp, cbp, (M, N, Kp) = _pad_operands(x, idx, codebook, bm, bn, gs_pad, packed)
    bias_row = None
    if bias is not None:
        bias_row = jnp.pad(bias.astype(jnp.float32), (0, idxp.shape[1] - N))
        bias_row = bias_row.reshape(1, -1)
    out = pasm_matmul_kernel_call(
        xp,
        idxp,
        cbp,
        bias_row,
        packed=packed,
        logical_k=Kp,
        bm=bm,
        bn=bn,
        bk=bk,
        relu=relu,
        pool=pool,
        interpret=interpret,
        name=name,
    )
    return out[: M // (pool * pool), :N]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _pasm_matmul(x, idx, codebook, packed, interpret, name):
    logical_k = x.shape[-1]
    return _pasm_matmul_fwd_impl(
        x,
        idx,
        codebook,
        packed=packed,
        logical_k=logical_k,
        interpret=interpret,
        use_ref=False,
        name=name,
    )


def _pasm_fwd(x, idx, codebook, packed, interpret, name):
    y = _pasm_matmul(x, idx, codebook, packed, interpret, name)
    return y, (x, idx, codebook)


def _pasm_bwd(packed, interpret, res, g):
    x, idx, codebook = res
    w = _ref.dequant_ref(idx, codebook, packed=packed).astype(x.dtype)
    dx = jnp.dot(g.astype(x.dtype), w.T)
    # codebook grad: Σ of (xᵀg) entries binned by index — the PAS identity on
    # the backward pass.  idx gets no gradient (integer).
    xg = jnp.dot(x.T.astype(jnp.float32), g.astype(jnp.float32))  # (K, N)
    li = _pasm.unpack_int4(idx) if packed else idx
    K, N = li.shape
    G, B = codebook.shape
    seg = li.reshape(G, K // G, N).astype(jnp.int32)
    xgg = xg.reshape(G, K // G, N)
    dcb = jax.vmap(
        lambda s, v: jax.ops.segment_sum(v.reshape(-1), s.reshape(-1), num_segments=B)
    )(seg, xgg)
    return dx, None, dcb.astype(codebook.dtype)


_pasm_matmul.defvjp(
    _pasm_fwd,
    lambda packed, interpret, name, res, g: _pasm_bwd(packed, interpret, res, g),
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _pasm_matmul_ep(x, idx, codebook, bias, packed, interpret, relu, pool,
                    name):
    """The fused-epilogue variant: bias/ReLU (and the ``pool`` max-reduce
    over window-major rows) applied inside the kernel."""
    return _pasm_matmul_fwd_impl(
        x,
        idx,
        codebook,
        bias,
        packed=packed,
        logical_k=x.shape[-1],
        interpret=interpret,
        use_ref=False,
        relu=relu,
        pool=pool,
        name=name,
    )


def _pasm_ep_fwd(x, idx, codebook, bias, packed, interpret, relu, pool, name):
    y = _pasm_matmul_ep(x, idx, codebook, bias, packed, interpret, relu, pool,
                        name)
    # y is a residual only for the ReLU mask (pool == 1: the pooled output
    # can't recover the pre-pool mask — the backward recomputes it instead)
    return y, (x, idx, codebook, bias, y if relu and pool == 1 else None)


def _pasm_ep_bwd(packed, interpret, relu, pool, name, res, g):
    x, idx, codebook, bias, y = res
    if pool > 1:
        # the fused forward never materializes the pre-pool activations —
        # recompute them and route g through the pool argmax + ReLU masks
        # (max_pool_rows' own VJP defines the argmax routing)
        w = _ref.dequant_ref(idx, codebook, packed=packed).astype(x.dtype)
        y_lin = jnp.dot(x, w, preferred_element_type=jnp.float32)
        _, vjp_post = jax.vjp(
            lambda yl: _ref.max_pool_rows(_ref.apply_epilogue(yl, bias, relu),
                                          pool),
            y_lin,
        )
        g, = vjp_post(g)
    elif relu:
        g = g * (y > 0).astype(g.dtype)  # mask through the fused ReLU
    dx, _, dcb = _pasm_bwd(packed, interpret, (x, idx, codebook), g)
    dbias = g.sum(axis=0).astype(bias.dtype)
    return dx, None, dcb, dbias


_pasm_matmul_ep.defvjp(_pasm_ep_fwd, _pasm_ep_bwd)


def pasm_matmul(
    x: jax.Array,
    t: _pasm.PASMTensor,
    *,
    bias: Optional[jax.Array] = None,
    relu: bool = False,
    interpret: Optional[bool] = None,
    mesh=None,
    pool: int = 1,
    name: str = "pasm_matmul",
) -> jax.Array:
    """``x @ t`` with the fused dequant kernel.  x: (..., K) → (..., N) f32.

    ``bias (N,)`` / ``relu`` fuse into the kernel's last-k-step write-through
    (one pallas_call per layer, no XLA epilogue).  Differentiable in ``x``,
    ``t.codebook`` and ``bias``.  With ``mesh=`` the rows shard over
    ``data`` (M padded up to the axis size when uneven) and N over ``model``
    when divisible — bit-exact vs the single-device call.

    ``pool > 1`` fuses a non-overlapping max-pool into the same
    write-through: ``x`` must be 2-D with **window-major** rows (each
    consecutive ``pool²`` rows one pool window — the explicit conv path's
    ``_pool_order_patches`` ordering) and the result is the pooled
    ``(M/pool², N)``.  Under ``mesh=`` the window-major rows must split
    over ``data`` in whole pool windows (``conv2d`` guarantees this by
    padding the batch to divide the axis — each image's ``P_rows`` rows are
    a multiple of ``pool²``, so shard boundaries land between windows and
    the explicit engines fuse pooling under a mesh too).
    """
    if interpret is None:
        interpret = _interpret_default()
    K, N = t.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)
    if pool > 1:
        nd = _mesh_sizes(mesh)[0] if mesh is not None else 1
        _check_pool_operand(x, pool, mesh, nd)
        b = jnp.zeros((N,), jnp.float32) if bias is None else bias
        if mesh is not None:
            return _shard_gemm(
                mesh, N,
                lambda xl, il, cl, bl: _pasm_matmul_ep(
                    xl, il, cl, bl, t.packed, interpret, relu, pool, name
                ),
                (x2, t.idx, t.codebook), x_rank=2, out_rank=2, bias=b,
            )
        return _pasm_matmul_ep(
            x2, t.idx, t.codebook, b, t.packed, interpret, relu, pool, name
        )
    if mesh is not None:
        nd, _ = _mesh_sizes(mesh)
        M = x2.shape[0]
        pad_m = -M % nd
        if pad_m:
            x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
        if bias is None and not relu:
            y = _shard_gemm(
                mesh, N,
                lambda xl, il, cl: _pasm_matmul(
                    xl, il, cl, t.packed, interpret, name
                ),
                (x2, t.idx, t.codebook), x_rank=2, out_rank=2,
            )
        else:
            b = jnp.zeros((N,), jnp.float32) if bias is None else bias
            y = _shard_gemm(
                mesh, N,
                lambda xl, il, cl, bl: _pasm_matmul_ep(
                    xl, il, cl, bl, t.packed, interpret, relu, 1, name
                ),
                (x2, t.idx, t.codebook), x_rank=2, out_rank=2, bias=b,
            )
        return y[:M].reshape(*lead, N)
    if bias is None and not relu:
        y = _pasm_matmul(x2, t.idx, t.codebook, t.packed, interpret, name)
    else:
        b = jnp.zeros((N,), jnp.float32) if bias is None else bias
        y = _pasm_matmul_ep(
            x2, t.idx, t.codebook, b, t.packed, interpret, relu, 1, name
        )
    return y.reshape(*lead, N)


@functools.partial(jax.jit,
                   static_argnames=("relu", "pool", "interpret", "name"))
def _pas_matmul_impl(x, idx, codebook, bias=None, *, relu=False, pool=1,
                     interpret, name):
    M, K = x.shape
    N = idx.shape[1]
    bm, bn, bk, gs_pad = _pick_blocks(M, K, N, K, packed=False)
    bm = _pool_bm(bm, pool)
    xp, idxp, cbp, (M, N, _) = _pad_operands(
        x, idx, codebook, bm, bn, gs_pad, packed=False
    )
    bias_row = None
    if bias is not None:
        bias_row = jnp.pad(bias.astype(jnp.float32), (0, idxp.shape[1] - N))
        bias_row = bias_row.reshape(1, -1)
    out = pas_matmul_kernel_call(
        xp, idxp, cbp, bias_row, bm=bm, bn=bn, bk=bk, relu=relu, pool=pool,
        interpret=interpret, name=name,
    )
    return out[: M // (pool * pool), :N]


def pas_matmul(
    x: jax.Array,
    t: _pasm.PASMTensor,
    *,
    bias: Optional[jax.Array] = None,
    relu: bool = False,
    interpret: Optional[bool] = None,
    mesh=None,
    pool: int = 1,
    name: str = "pas_matmul",
) -> jax.Array:
    """Paper-faithful PASM two-phase matmul (single dictionary).

    ``bias (N,)`` / ``relu`` fuse into the post-pass write-through, and
    ``pool > 1`` max-reduces window-major row groups there too (2-D x only,
    whole windows per ``data`` shard — same contract as
    :func:`pasm_matmul`).  With ``mesh=`` rows shard over ``data``, N over
    ``model`` when divisible; the in-kernel PAS bin counters are per-shard
    VMEM scratch, so they replicate with the kernel itself.
    """
    if interpret is None:
        interpret = _interpret_default()
    idx = _pasm.logical_idx(t)
    K, N = t.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)
    if pool > 1:
        nd = _mesh_sizes(mesh)[0] if mesh is not None else 1
        _check_pool_operand(x, pool, mesh, nd)
        if mesh is not None:
            if bias is None:
                return _shard_gemm(
                    mesh, N,
                    lambda xl, il, cl: _pas_matmul_impl(
                        xl, il, cl, relu=relu, pool=pool, interpret=interpret,
                        name=name,
                    ),
                    (x2, idx, t.codebook), x_rank=2, out_rank=2,
                )
            return _shard_gemm(
                mesh, N,
                lambda xl, il, cl, bl: _pas_matmul_impl(
                    xl, il, cl, bl, relu=relu, pool=pool, interpret=interpret,
                    name=name,
                ),
                (x2, idx, t.codebook), x_rank=2, out_rank=2, bias=bias,
            )
        return _pas_matmul_impl(
            x2, idx, t.codebook, bias, relu=relu, pool=pool, interpret=interpret,
            name=name,
        )
    if mesh is not None:
        nd, _ = _mesh_sizes(mesh)
        M = x2.shape[0]
        pad_m = -M % nd
        if pad_m:
            x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
        if bias is None:
            y = _shard_gemm(
                mesh, N,
                lambda xl, il, cl: _pas_matmul_impl(
                    xl, il, cl, relu=relu, interpret=interpret,
                    name=name,
                ),
                (x2, idx, t.codebook), x_rank=2, out_rank=2,
            )
        else:
            y = _shard_gemm(
                mesh, N,
                lambda xl, il, cl, bl: _pas_matmul_impl(
                    xl, il, cl, bl, relu=relu, interpret=interpret,
                    name=name,
                ),
                (x2, idx, t.codebook), x_rank=2, out_rank=2, bias=bias,
            )
        return y[:M].reshape(*lead, N)
    y = _pas_matmul_impl(x2, idx, t.codebook, bias, relu=relu,
                         interpret=interpret, name=name)
    return y.reshape(*lead, N)


# ---------------------------------------------------------------------------
# implicit-GEMM convolution (no materialized patch matrix)
# ---------------------------------------------------------------------------


def _pad_image(x, geom: ConvGeom):
    """Apply the spatial zero-pad of ``geom`` to an image batch (SAME halo)."""
    ph, pw = geom.pad
    if any(ph) or any(pw):
        cfg = ((0, 0), ph, pw, (0, 0)) if geom.nhwc else ((0, 0), (0, 0), ph, pw)
        x = jnp.pad(x, cfg)
    return x


def _geom_patches(x, geom: ConvGeom):
    """Explicit im2col from a :class:`ConvGeom` — backward/oracle use ONLY.

    The forward implicit path never materializes this ``(B·P, K)`` matrix;
    only the custom VJP does (col2im backward, per the initial
    implicit-GEMM scope).  Delegates to the one shared gather definition.
    """
    return _ref.im2col_patches(
        x, nhwc=geom.nhwc, ky=geom.ky, kx=geom.kx, stride=geom.stride,
        oh=geom.oh, ow=geom.ow, c_in=geom.c_in, pad=geom.pad,
    )


@functools.partial(
    jax.jit,
    static_argnames=("geom", "packed", "interpret", "relu", "use_pas",
                     "vmem_budget", "name"),
)
def _conv_fwd_impl(
    x, idx, codebook, bias=None, *, geom, packed, name, interpret=False,
    relu=False, use_pas=False, vmem_budget=None,
):
    """Shared implicit-conv forward: tile plan + weight padding + image
    relayout + kernel call.

    :func:`conv_tile_plan` plans the call: the reduction tiling
    (``bk``/``gs_pad``) is a pure function of K/N/groups in
    :func:`_pick_blocks`, so the implicit kernel walks the exact k-tile
    sequence of the explicit path, and the output-channel block
    ``bn_conv`` feeds every 128-lane chunk of it from one assembled patch
    tile.  The padded image (NHWC moved to channel-major first) is re-laid
    out into the plan's phase images
    (:func:`~repro.kernels.pasm_matmul.phase_slabs`) — whole, or as
    row-band slabs when the whole image would blow ``vmem_budget``
    (:func:`conv_slab_plan`).  The kernel returns pooled *wide* pixels per
    slab; the wide columns (``c ≥ owp``) and pad rows are dropped here.
    ``name`` names the kernel.
    """
    G, _ = codebook.shape
    K = idx.shape[0] * (2 if packed else 1)
    N = idx.shape[1]
    gs = K // G
    xp = _pad_image(x, geom)
    if geom.nhwc:
        xp = jnp.transpose(xp, (0, 3, 1, 2))
    tp = conv_tile_plan(
        geom, xp.shape[2], xp.shape[3], k=K, n=N, groups=G,
        bins=codebook.shape[1], packed=packed, pas=use_pas,
        has_bias=bias is not None, vmem_budget=vmem_budget,
        itemsize=xp.dtype.itemsize,
    )
    bn, bk, gs_pad, plan = tp.bn_conv, tp.bk, tp.gs_pad, tp.plan
    idxp, cbp, _ = _pad_weight_operands(idx, codebook, bn, gs_pad, packed)
    xs = phase_slabs(xp, geom, plan)
    bias_row = None
    if bias is not None:
        bias_row = jnp.pad(bias.astype(jnp.float32), (0, idxp.shape[1] - N))
        bias_row = bias_row.reshape(1, -1)
    if use_pas:
        out = pas_conv_kernel_call(
            xs, idxp, cbp, bias_row, geom=geom, plan=plan, gs=gs,
            gs_pad=gs_pad, bn=bn, bk=bk, relu=relu, interpret=interpret,
            name=name,
        )
    else:
        out = pasm_conv_kernel_call(
            xs, idxp, cbp, bias_row, geom=geom, plan=plan, packed=packed,
            gs=gs, gs_pad=gs_pad, bn=bn, bk=bk, relu=relu,
            interpret=interpret, name=name,
        )
    B = x.shape[0]
    out = out[:, :, : plan.rows_out * plan.wq, :N]
    out = out.reshape(B, plan.n_slabs * plan.rows_out, plan.wq, N)
    return out[:, : geom.ohp, : geom.owp].reshape(B, geom.P_out, N)


def _pool_rowmajor_ref(y, geom, batch):
    """Row-major conv output ``(B·P, N) → (B·P_out, N)`` pooled reference.

    The backward's oracle for the fused pool: floor-crops to whole windows,
    max-reduces each ``(pool, pool)`` window.  The pooled VJPs differentiate
    through this, so ``jnp.max``'s own VJP defines the argmax cotangent
    routing (remainder rows/cols the fused kernel never computes get zero).
    """
    p = geom.pool
    N = y.shape[-1]
    yb = y.reshape(batch, geom.oh, geom.ow, N)
    yb = yb[:, : geom.ohp * p, : geom.owp * p]
    yb = yb.reshape(batch, geom.ohp, p, geom.owp, p, N)
    return yb.max(axis=(2, 4)).reshape(batch * geom.P_out, N)


def _conv_bwd_core(geom, packed, interpret, relu, res, g):
    """Backward through the implicit conv via explicit col2im (initial scope):
    materialize patches, reuse the GEMM VJP, scatter back through im2colᵀ.

    With ``geom.pool > 1`` the fused forward never stores the pre-pool
    activations, so they are recomputed here (patches @ w + epilogue) and
    ``g`` routes through the pool argmax + ReLU masks before the GEMM VJP.
    The returned cotangent ``g2`` is always the one at the *linear* conv
    output, so the caller's ``dbias = g2.sum(axis=0)`` holds on both paths.
    """
    x, idx, codebook, bias, y = res
    g2 = g.reshape(-1, g.shape[-1])
    K = idx.shape[0] * (2 if packed else 1)
    patches, vjp_patch = jax.vjp(
        functools.partial(_geom_patches, geom=geom), x
    )
    if K != geom.conv_k:  # §3 pack-time K-pad rows carry zero activations
        patches = jnp.pad(patches, ((0, 0), (0, K - geom.conv_k)))
    if geom.pool > 1:
        w = _ref.dequant_ref(idx, codebook, packed=packed).astype(patches.dtype)
        y_lin = jnp.dot(patches, w, preferred_element_type=jnp.float32)
        _, vjp_post = jax.vjp(
            lambda yl: _pool_rowmajor_ref(
                _ref.apply_epilogue(yl, bias, relu), geom, x.shape[0]
            ),
            y_lin,
        )
        g2, = vjp_post(g2)
    elif relu:
        g2 = g2 * (y.reshape(g2.shape) > 0).astype(g2.dtype)
    dp, _, dcb = _pasm_bwd(packed, interpret, (patches, idx, codebook), g2)
    dx, = vjp_patch(dp[:, : geom.conv_k])
    return dx, dcb, g2


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _pasm_conv(x, idx, codebook, geom, packed, interpret, vmem_budget, name):
    return _conv_fwd_impl(
        x, idx, codebook, geom=geom, packed=packed, interpret=interpret,
        vmem_budget=vmem_budget, name=name,
    )


def _pasm_conv_fwd(x, idx, codebook, geom, packed, interpret, vmem_budget,
                   name):
    y = _pasm_conv(x, idx, codebook, geom, packed, interpret, vmem_budget,
                   name)
    return y, (x, idx, codebook)


def _pasm_conv_bwd(geom, packed, interpret, vmem_budget, name, res, g):
    x, idx, codebook = res
    dx, dcb, _ = _conv_bwd_core(
        geom, packed, interpret, False, (x, idx, codebook, None, None), g
    )
    return dx, None, dcb


_pasm_conv.defvjp(_pasm_conv_fwd, _pasm_conv_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _pasm_conv_ep(x, idx, codebook, bias, geom, packed, interpret, relu,
                  vmem_budget, name):
    """The fused-epilogue implicit conv: bias/ReLU applied inside the kernel."""
    return _conv_fwd_impl(
        x, idx, codebook, bias, geom=geom, packed=packed, interpret=interpret,
        relu=relu, vmem_budget=vmem_budget, name=name,
    )


def _pasm_conv_ep_fwd(x, idx, codebook, bias, geom, packed, interpret, relu,
                      vmem_budget, name):
    y = _pasm_conv_ep(x, idx, codebook, bias, geom, packed, interpret, relu,
                      vmem_budget, name)
    # y is a residual only for the ReLU mask (and only when unpooled — the
    # pooled output can't recover the pre-pool mask; the backward recomputes)
    return y, (x, idx, codebook, bias, y if relu and geom.pool == 1 else None)


def _pasm_conv_ep_bwd(geom, packed, interpret, relu, vmem_budget, name, res,
                      g):
    x, idx, codebook, bias, y = res
    dx, dcb, g2 = _conv_bwd_core(
        geom, packed, interpret, relu, (x, idx, codebook, bias, y), g
    )
    dbias = g2.sum(axis=0).astype(bias.dtype)
    return dx, None, dcb, dbias


_pasm_conv_ep.defvjp(_pasm_conv_ep_fwd, _pasm_conv_ep_bwd)


def pasm_conv2d(
    x: jax.Array,
    t: _pasm.PASMTensor,
    geom: ConvGeom,
    *,
    bias: Optional[jax.Array] = None,
    relu: bool = False,
    interpret: Optional[bool] = None,
    mesh=None,
    vmem_budget: Optional[int] = None,
    gather_output: bool = True,
    name: str = "pasm_conv",
) -> jax.Array:
    """Implicit-GEMM conv on the fused-dequant kernel: ``(B, img) → (B, P, N)``.

    One ``pallas_call`` over the (spatially padded, phase-laid-out) image
    batch — the im2col patch tiles are assembled inside the kernel, so no
    ``(B·P, K)`` patch matrix exists in HBM.  ``bias (N,)`` / ``relu`` fuse
    into the last-k-step write-through exactly as in :func:`pasm_matmul`,
    and ``geom.pool > 1`` additionally max-reduces each ``(pool, pool)``
    output window there — the whole conv/ReLU/pool stage is ONE pallas_call
    and the pre-pool activations never touch HBM.  Differentiable in ``x``,
    ``t.codebook`` and ``bias`` (the backward pass materializes patches
    explicitly — col2im — and recomputes the pre-pool map for the argmax
    routing, for now).  Pool windows live inside single images, so the fused
    pool shards over ``data`` unchanged.  With ``mesh=`` the image batch
    shards over ``data`` (the batch must already divide the axis — the
    ``conv2d`` front-end pads uneven remainders) and N over ``model`` when
    divisible; each shard derives its tile plan from the local shapes, and
    ``gather_output=True`` (the default) all-gathers N inside the sharded
    body so the returned activations are model-replicated — consecutive
    sharded conv layers see no XLA resharding between their pallas_calls.
    ``vmem_budget`` bounds the per-step image footprint: images whose
    double-buffered whole-image residency would blow the budget stream as
    row-band slabs (:func:`conv_slab_plan`).
    """
    if interpret is None:
        interpret = _interpret_default()
    if mesh is not None:
        nd, _ = _mesh_sizes(mesh)
        if x.shape[0] % nd:
            raise ValueError(
                f"batch {x.shape[0]} does not divide the data axis ({nd}); "
                "pad the batch first (conv2d(mesh=) handles the remainder)"
            )
        if bias is None and not relu and geom.pool == 1:
            return _shard_gemm(
                mesh, t.shape[1],
                lambda xl, il, cl: _pasm_conv(
                    xl, il, cl, geom, t.packed, interpret, vmem_budget, name
                ),
                (x, t.idx, t.codebook), x_rank=4, out_rank=3,
                gather_output=gather_output,
            )
        b = jnp.zeros((t.shape[1],), jnp.float32) if bias is None else bias
        return _shard_gemm(
            mesh, t.shape[1],
            lambda xl, il, cl, bl: _pasm_conv_ep(
                xl, il, cl, bl, geom, t.packed, interpret, relu, vmem_budget,
                name,
            ),
            (x, t.idx, t.codebook), x_rank=4, out_rank=3, bias=b,
            gather_output=gather_output,
        )
    # geom.pool > 1 always rides the epilogue variant: its VJP owns the
    # pooled (argmax-routed) backward
    if bias is None and not relu and geom.pool == 1:
        return _pasm_conv(
            x, t.idx, t.codebook, geom, t.packed, interpret, vmem_budget, name
        )
    b = jnp.zeros((t.shape[1],), jnp.float32) if bias is None else bias
    return _pasm_conv_ep(
        x, t.idx, t.codebook, b, geom, t.packed, interpret, relu, vmem_budget,
        name,
    )


def pas_conv2d(
    x: jax.Array,
    t: _pasm.PASMTensor,
    geom: ConvGeom,
    *,
    bias: Optional[jax.Array] = None,
    relu: bool = False,
    interpret: Optional[bool] = None,
    mesh=None,
    vmem_budget: Optional[int] = None,
    gather_output: bool = True,
    name: str = "pas_conv",
) -> jax.Array:
    """Implicit-GEMM conv on the paper-faithful two-phase PAS formulation.

    Single dictionary, forward-only — mirrors :func:`pas_matmul` (and its
    ``mesh=`` sharding: batch over ``data``, N over ``model`` when
    divisible, per-shard bin counters).  ``vmem_budget`` /
    ``gather_output`` behave exactly as in :func:`pasm_conv2d`.
    """
    if interpret is None:
        interpret = _interpret_default()
    idx = _pasm.logical_idx(t)
    if mesh is not None:
        nd, _ = _mesh_sizes(mesh)
        if x.shape[0] % nd:
            raise ValueError(
                f"batch {x.shape[0]} does not divide the data axis ({nd}); "
                "pad the batch first (conv2d(mesh=) handles the remainder)"
            )
        if bias is None:
            return _shard_gemm(
                mesh, t.shape[1],
                lambda xl, il, cl: _conv_fwd_impl(
                    xl, il, cl, geom=geom, packed=False, interpret=interpret,
                    relu=relu, use_pas=True, vmem_budget=vmem_budget, name=name,
                ),
                (x, idx, t.codebook), x_rank=4, out_rank=3,
                gather_output=gather_output,
            )
        return _shard_gemm(
            mesh, t.shape[1],
            lambda xl, il, cl, bl: _conv_fwd_impl(
                xl, il, cl, bl, geom=geom, packed=False, interpret=interpret,
                relu=relu, use_pas=True, vmem_budget=vmem_budget, name=name,
            ),
            (x, idx, t.codebook), x_rank=4, out_rank=3, bias=bias,
            gather_output=gather_output,
        )
    return _conv_fwd_impl(
        x, idx, t.codebook, bias, geom=geom, packed=False, interpret=interpret,
        relu=relu, use_pas=True, vmem_budget=vmem_budget, name=name,
    )


# ---------------------------------------------------------------------------
# roofline bookkeeping helpers
# ---------------------------------------------------------------------------


def matmul_flops(M: int, K: int, N: int) -> int:
    return 2 * M * K * N


def pasm_hbm_bytes(t: _pasm.PASMTensor, M: int, act_bytes: int = 2) -> int:
    """Bytes one (M,K)@(K,N) PASM matmul actually moves: x + idx + cb + out.

    Tile-plan aware (audited against :attr:`PASMTensor.nbytes_weights`): the
    kernel streams the *padded* operands, so shapes that route through the §3
    K-pad move ``G·gs_pad`` reduction rows (plus one reserved codebook bin per
    group), and M/N round up to the block plan.  The output is written f32
    (4 B) — the seed counted it at ``act_bytes``, under-reporting the store
    traffic.  On tile-aligned shapes the weight term equals
    ``t.nbytes_weights`` exactly.
    """
    K, N = t.shape
    G, B = t.codebook.shape
    bm, bn, bk, gs_pad = _pick_blocks(M, K, N, K // G, t.packed)
    Kp = G * gs_pad
    Mp, Np = _round_up(M, bm), _round_up(N, bn)
    idx_bytes = (Kp // 2 if t.packed else Kp) * Np
    padded_k = gs_pad != K // G
    cb_bytes = G * (B + (1 if padded_k and not t.packed and B < 256 else 0)) * 4
    return Mp * Kp * act_bytes + idx_bytes + cb_bytes + Mp * Np * 4


def conv_hbm_bytes(
    t: _pasm.PASMTensor,
    geom: ConvGeom,
    batch: int,
    ih: int,
    iw: int,
    *,
    implicit: bool,
    act_bytes: int = 4,
    shards: tuple = (1, 1),
    vmem_budget: Optional[int] = None,
) -> int:
    """Modeled HBM bytes of one conv layer on the PASM GEMM, tile-plan aware.

    ``implicit=False`` (explicit im2col): the ``(B·P, K)`` patch matrix is
    *written* by the XLA front-end and *read back* by the kernel — the
    activation term is twice the padded patch-matrix bytes, inflating input
    traffic by up to ``ky·kx/stride²`` over the raw image.

    ``implicit=True``: the kernel streams its phase-layout image operand
    once (each whole image or row-band slab stays VMEM-resident across its
    whole tile loop), so the activation term is the plan's operand size
    (:meth:`SlabPlan.image_elems` — the padded image plus lane/phase
    alignment padding, and each slab's halo rows when the image streams as
    slabs), and the store is the pooled *wide* pixel blocks the kernel
    writes.  Weight/codebook terms follow the same padded-operand
    accounting as :func:`pasm_hbm_bytes`.
    The logical-shape (plan-free) counterpart is
    :func:`repro.core.hwmodel.conv_hbm_traffic`.

    ``shards=(n_data, n_model)`` models the **per-device** bytes of the
    sharded path: the batch splits over ``data`` (uneven remainders round up
    — the padded images are real traffic), N over ``model`` when divisible
    (else the weights replicate, per the sharded dispatch rule), and the
    codebook replicates on every device.  The tile plan is recomputed from
    the local shapes, exactly as each shard does.

    ``geom.pool > 1`` models the **fused conv/ReLU/max-pool stage**: the
    GEMM walks the window-major ``P_rows`` (floor-remainder pixels never
    computed) and the store shrinks to the pooled ``P_out`` map — the
    pre-pool activations never touch HBM, which is exactly the bytes the
    separate ``reduce_window`` pass would have re-read and re-written.
    """
    K, N = t.shape
    G, B = t.codebook.shape
    P = geom.P_rows
    pw = geom.pool * geom.pool
    n_data, n_model = shards
    batch = -(-batch // n_data)  # per-device share, remainder rounded up
    if n_model > 1 and N % n_model == 0:
        N = N // n_model
    # bm mirrors the kernels: per-image rows on the implicit grid, batch-wide
    # rows explicit, aligned to whole pool windows when the pool is fused
    bm, bn, bk, gs_pad = _pick_blocks(
        P if implicit else batch * P, K, N, K // G, t.packed
    )
    bm = _pool_bm(bm, geom.pool)
    Kp = G * gs_pad
    Np = _round_up(N, bn)
    idx_bytes = (Kp // 2 if t.packed else Kp) * Np
    padded_k = gs_pad != K // G
    cb_bytes = G * (B + (1 if padded_k and not t.packed and B < 256 else 0)) * 4
    if implicit:
        (plh, phh), (plw, phw) = geom.pad
        hp, wp = ih + plh + phh, iw + plw + phw
        plan = conv_tile_plan(
            geom, hp, wp, k=K, n=N, groups=G, bins=B, packed=t.packed,
            has_bias=True, vmem_budget=vmem_budget, itemsize=act_bytes,
        ).plan
        x_bytes = batch * plan.image_elems(geom) * act_bytes
        out_bytes = batch * plan.n_slabs * plan.n_blocks * plan.bmp * Np * 4
    else:
        Mp = _round_up(batch * P, bm)
        x_bytes = 2 * Mp * Kp * act_bytes  # im2col store + kernel stream
        out_bytes = (Mp // pw) * Np * 4
    return x_bytes + idx_bytes + cb_bytes + out_bytes


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    bq: int = 128,
    bk: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused flash attention.  q (B,Sq,H,hd); k,v (B,Sk,KV,hd) → (B,Sq,H,hd).

    GQA: query heads are regrouped under their KV head so each K/V tile is
    read once per group.  Pads Sq/Sk to tile multiples (pad keys masked).
    """
    from repro.kernels.flash_attention import flash_attention_kernel_call

    if interpret is None:
        interpret = _interpret_default()
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    bq = min(bq, max(8, 1 << (Sq - 1).bit_length()))
    bk = min(bk, max(8, 1 << (Sk - 1).bit_length()))
    Sqp, Skp = _round_up(Sq, bq), _round_up(Sk, bk)
    qg = jnp.moveaxis(q.reshape(B, Sq, KV, G, hd), 1, 3)  # (B, KV, G, Sq, hd)
    qg = qg.reshape(B * KV, G, Sq, hd)
    kg = jnp.moveaxis(k, 1, 2).reshape(B * KV, Sk, hd)
    vg = jnp.moveaxis(v, 1, 2).reshape(B * KV, Sk, hd)
    if Sqp != Sq:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
    if Skp != Sk:
        kg = jnp.pad(kg, ((0, 0), (0, Skp - Sk), (0, 0)))
        vg = jnp.pad(vg, ((0, 0), (0, Skp - Sk), (0, 0)))
    o = flash_attention_kernel_call(
        qg, kg, vg, causal=causal, sk_orig=Sk, bq=bq, bk=bk, interpret=interpret
    )
    o = o[:, :, :Sq].reshape(B, KV, G, Sq, hd)
    return jnp.moveaxis(o, 3, 1).reshape(B, Sq, H, hd)
