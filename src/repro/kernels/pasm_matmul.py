"""Pallas TPU kernel: codebook-dequant-fused matmul (the production PASM path).

``y = x @ W`` where ``W`` never exists in HBM: only ``log2(B)``-bit indices
(uint8, or two 4-bit indices packed per byte) plus a ``(G, B)`` codebook are
streamed.  Dequantization happens on the fly in VMEM, tile by tile — this is
the TPU adaptation of the paper's insight (DESIGN.md §2): HBM weight traffic
drops 4–8× versus bf16 weights, directly scaling the memory-roofline term in
the bandwidth-bound regimes (decode serving) where weights dominate bytes.

Tiling: grid ``(M/bm, N/bn, K/bk)`` with the reduction innermost; a VMEM
f32 accumulator block is zeroed at ``k==0`` and written through at the last
``k`` step — where the optional bias-add/ReLU epilogue is fused, so a conv
layer with bias+activation is a single ``pallas_call`` (no XLA epilogue).
Block shapes are MXU-aligned (multiples of 128 on N, 8/128 on M/K per dtype
tiling).  The whole ``(G, B)`` codebook sits in SMEM for the entire grid
(a few hundred scalars); the k-tile's group is ``k // (group_size/bk)``
(requires ``group_size % bk == 0``).

Dequantization is the one-hot select ``w = Σ_b cb[b]·[idx = b]`` — the PAS
selection network in vectorized form: ``B`` int32 compares + selects per
weight on the VPU, with the codebook entries read as SMEM scalars (Mosaic
has no general vector gather, and refuses sub-32-bit compares/shifts, so
indices widen to int32 before any arithmetic).

Two entry points share the kernel body:

  * :func:`pasm_matmul_kernel_call` — the plain GEMM: ``x`` is an explicit
    ``(M, K)`` operand (the conv path materializes an im2col patch matrix in
    HBM first).
  * :func:`pasm_conv_kernel_call` — **implicit-GEMM convolution**: ``x`` is
    the image batch in the *phase layout* (:func:`phase_slabs`); each patch
    tile is assembled *inside* the kernel (:func:`assemble_tile`), so no
    ``(B·P, K)`` patch matrix ever exists in HBM, once per grid step for
    every 128-lane chunk of a wide output-channel block.  The k-tile plan
    is the explicit path's, so every output sees the same dequantized
    weight tiles in the same k order.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import max_pool_rows

__all__ = ["pasm_matmul_kernel_call", "pasm_conv_kernel_call", "ConvGeom",
           "SlabPlan", "assemble_tile", "phase_slabs"]

LANE = 128
# Scoped-VMEM ceiling handed to Mosaic for every kernel here: the slab
# planner sizes the conv kernels' blocks against a smaller budget
# (``ops.IMPLICIT_VMEM_BUDGET``), this only lifts Mosaic's 16 MiB default so
# its own temporaries fit next to them (a v5e core has 128 MiB of VMEM).
VMEM_LIMIT = 64 * 1024 * 1024


class ConvGeom(NamedTuple):
    """Static conv geometry the implicit-GEMM kernels close over.

    Built by :func:`repro.core.conv.conv_geom`; hashable so it rides jit
    static args and ``custom_vjp`` nondiff args.  ``pad`` is the spatial
    zero-pad already applied to the image the kernel sees
    (``((lo_h, hi_h), (lo_w, hi_w))`` — SAME windowing happens *outside*).
    ``pool > 1`` fuses a non-overlapping ``(pool, pool)`` max-pool into the
    kernel epilogue: the output is the pooled ``P_out`` map and the
    pre-pool activations never leave VMEM (DESIGN.md §3.2).
    """

    nhwc: bool  # channels-minor (kkc) vs paper (ckk) reduction order
    ky: int
    kx: int
    stride: int
    oh: int
    ow: int
    c_in: int
    pad: tuple
    pool: int = 1  # fused non-overlapping max-pool window (1 = no pooling)

    @property
    def P(self) -> int:
        """Pre-pool output pixels per image."""
        return self.oh * self.ow

    @property
    def conv_k(self) -> int:
        """The true im2col reduction length ``c_in·ky·kx``."""
        return self.c_in * self.ky * self.kx

    @property
    def ohp(self) -> int:
        """Pooled output height (floor / VALID windowing)."""
        return self.oh // self.pool

    @property
    def owp(self) -> int:
        """Pooled output width (floor / VALID windowing)."""
        return self.ow // self.pool

    @property
    def P_out(self) -> int:
        """Stored output pixels per image (``== P`` when ``pool == 1``)."""
        return self.ohp * self.owp

    @property
    def P_rows(self) -> int:
        """GEMM rows per image: window pixels only — floor-dropped remainder
        rows/cols of the pre-pool map are never computed (``== P`` when
        ``pool == 1``)."""
        return self.P_out * self.pool * self.pool

    @property
    def phase(self) -> int:
        """Phase factor ``S = stride·pool`` of the kernel's image layout."""
        return self.stride * self.pool

    def max_offset(self, wq: int) -> int:
        """Largest flat lane offset a (window, tap) pair reads past its
        output pixel in the phase layout of row pitch ``wq``."""
        S = self.phase
        ey = (self.pool - 1) * self.stride + self.ky - 1
        ex = (self.pool - 1) * self.stride + self.kx - 1
        return (ey // S) * wq + ex // S


class SlabPlan(NamedTuple):
    """The implicit conv's image plan: phase layout, pixel blocks and slabs.

    Built by :func:`repro.kernels.ops.conv_slab_plan`; hashable so it rides
    jit static args.  The padded image is re-laid out (by XLA, outside the
    kernel) into ``S² = (stride·pool)²`` *phase images* — phase ``(py, px)``
    holds rows ``py::S`` and columns ``px::S`` — each flattened row-major to
    one lane axis of pitch ``wq = ceil(wp / S)``.  Every (window offset,
    tap) then reads a unit-stride lane window of one phase image: pooled
    output pixel ``(r, c)`` is lane ``r·wq + c`` (the *wide* pixel index;
    lanes with ``c ≥ owp`` are computed and dropped).

    ``n_slabs == 1`` keeps the whole padded image resident.  Otherwise the
    image is cut into row bands of ``rows_out`` pooled output rows
    (``band_rows = rows_out·S`` padded-image rows), each carrying the
    ``halo_rows = max(ky - stride, 0)`` rows the next band's windows
    overlap; the kernel's block index moves to the next slab every
    ``n_blocks`` pixel blocks, so Pallas's pipeline prefetches slab ``s+1``
    while slab ``s`` computes.  ``bmp`` is the pooled wide pixels (lanes) of
    one block, ``lanes`` the flattened length of one slab's phase images
    (blocks plus the lane halo the largest tap offset reaches).

    Each of the ``n_slabs·n_blocks`` pixel blocks assembles its patch tile
    once per k-step and per output-channel block; the plan is sized at the
    kernel's output-channel block ``bn_conv``, which
    :func:`repro.kernels.ops.conv_tile_plan` widens only while the pixel
    blocks do not multiply.
    """

    n_slabs: int
    rows_out: int
    band_rows: int
    halo_rows: int
    wq: int
    bmp: int
    n_blocks: int
    lanes: int

    def image_elems(self, geom: ConvGeom) -> int:
        """Elements of one image's kernel operand (every slab, halo and
        alignment padding included) — what HBM streams per image."""
        return self.n_slabs * geom.phase ** 2 * geom.c_in * self.lanes


def _dequant_tile(idx_tile, cb_ref, g, dtype):
    """(bk, bn) indices + SMEM ``(G, B)`` codebook row ``g`` → weights.

    One-hot select ``Σ_b cb[g, b]·[idx = b]``: int32 compares (Mosaic
    refuses the uint8 ones) and SMEM scalar reads — no vector gather."""
    idx = idx_tile.astype(jnp.int32)
    w = jnp.zeros(idx.shape, jnp.float32)
    for b in range(cb_ref.shape[1]):
        w = jnp.where(idx == b, cb_ref[g, b], w)
    return w.astype(dtype)


def _unpack_int4_tile(packed):
    """(bk//2, bn) packed → (bk, bn) int32: row 2i = lo nibble, 2i+1 = hi.
    Widened to int32 first — Mosaic cannot shift uint8 vectors."""
    p = packed.astype(jnp.int32)
    out = jnp.stack([p & 0x0F, p >> 4], axis=1)  # (bk//2, 2, bn)
    return out.reshape(p.shape[0] * 2, p.shape[1])


def _dot(lhs, w, transposed: bool):
    """``lhs @ w`` at full f32 precision; ``transposed`` lhs is the implicit
    kernels' ``(bk, M)`` tile (reduction on sublanes, pixels on lanes).

    ``HIGHEST`` keeps f32 operands f32 on the MXU (Mosaic's default rounds
    them to bfloat16), so the chip computes what the interpret-mode tests
    check; bf16 operands take the default (Mosaic refuses an fp32 contract
    precision on bf16 operands)."""
    dims = (((0,) if transposed else (1,), (0,)), ((), ()))
    precision = (jax.lax.Precision.HIGHEST if lhs.dtype == jnp.float32
                 else None)
    return jax.lax.dot_general(lhs, w, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def epilogue_store(y, b_ref, o_ref, *, relu: bool, pool: int,
                   transposed: bool):
    """The fused write-through shared by every kernel here: bias, ReLU, then
    the max-pool.  Explicit rows are window-major (each consecutive
    ``pool²`` rows one window); the implicit kernels' rows are
    window-offset-major (``pool²`` groups of one block's pixels each)."""
    if b_ref is not None:
        y = y + b_ref[...]  # (1, bn) broadcasts over rows
    if relu:
        y = jnp.maximum(y, 0.0)
    if pool > 1 and transposed:
        pw = pool * pool
        n = y.shape[0] // pw
        y = functools.reduce(
            jnp.maximum, [y[w * n:(w + 1) * n] for w in range(pw)]
        )
    elif pool > 1:
        y = max_pool_rows(y, pool)
    o_ref[...] = y.reshape(o_ref.shape)


def _fused_dequant_step(
    lhs, idx_ref, cb_ref, b_ref, o_ref, acc_ref=None, *, k, g, n_k: int,
    packed: bool, relu: bool, pool: int = 1, transposed: bool = False,
):
    """The shared per-k-step body of BOTH entry points: unpack+dequant the
    idx tile, accumulate ``lhs @ w``, and fuse the bias-add / ReLU / max-pool
    epilogue into the last-k-step write-through — so a conv layer with
    bias+activation(+pool) stays a single pallas_call.  ``o_ref`` may carry
    leading length-1 axes (the conv grid).  With ``pool > 1`` the pre-pool
    accumulator lives in the ``acc_ref`` VMEM scratch instead of ``o_ref``
    (their shapes differ)."""
    idx_tile = idx_ref[...]
    if packed:
        idx_tile = _unpack_int4_tile(idx_tile)
    w = _dequant_tile(idx_tile, cb_ref, g, lhs.dtype)
    acc = _dot(lhs, w, transposed)
    target = acc_ref if pool > 1 else o_ref
    target[...] += acc.reshape(target.shape)

    if pool > 1 or b_ref is not None or relu:

        @pl.when(k == n_k - 1)
        def _finish():
            y = target[...].reshape(acc.shape)
            epilogue_store(y, b_ref, o_ref, relu=relu, pool=pool,
                           transposed=transposed)


def _kernel(
    x_ref, idx_ref, cb_ref, *rest, packed: bool, n_k: int,
    blocks_per_group: int, relu: bool, pool: int,
):
    if pool > 1:
        acc_ref, rest = rest[-1], rest[:-1]
    else:
        acc_ref = None
    b_ref, o_ref = rest if len(rest) == 2 else (None, rest[0])
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        z = acc_ref if pool > 1 else o_ref
        z[...] = jnp.zeros_like(z)

    _fused_dequant_step(
        x_ref[...], idx_ref, cb_ref, b_ref, o_ref, acc_ref,
        k=k, g=k // blocks_per_group, n_k=n_k, packed=packed, relu=relu,
        pool=pool,
    )


def _params(n_grid: int):
    sem = ("parallel",) * (n_grid - 1) + ("arbitrary",)
    return pltpu.CompilerParams(dimension_semantics=sem,
                                vmem_limit_bytes=VMEM_LIMIT)


def pasm_matmul_kernel_call(
    x: jax.Array,
    idx: jax.Array,
    codebook: jax.Array,
    bias: "jax.Array | None" = None,
    *,
    packed: bool,
    logical_k: int,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    relu: bool = False,
    pool: int = 1,
    interpret: bool = False,
    name: str = "pasm_matmul",
) -> jax.Array:
    """Raw pallas_call; shape plumbing/padding lives in :mod:`repro.kernels.ops`.

    ``x (M, K) · idx (K or K//2, N) · codebook (G, B) → (M, N) f32``.
    ``bias (1, N)`` and ``relu`` are the fused epilogue, applied inside the
    last reduction step.  ``pool > 1`` expects **window-major** x rows (each
    consecutive ``pool²`` rows one max-pool window — the conv2d front-end's
    ordering) and returns the pooled ``(M/pool², N)``, max-reduced in the
    same write-through.  Preconditions (enforced by ops.py):
    M % bm == N % bn == K % bk == 0, group_size % bk == 0, bk even when
    packed, bm % pool² == 0.
    """
    M, K = x.shape
    N = idx.shape[1]
    assert K == logical_k
    G, _ = codebook.shape
    group_size = K // G
    assert group_size % bk == 0, (group_size, bk)
    pw = pool * pool
    assert bm % pw == 0 and M % pw == 0, (bm, M, pool)
    n_k = K // bk

    # index maps return BLOCK indices (scaled by block_shape internally)
    idx_block = (bk // 2, bn) if packed else (bk, bn)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec(idx_block, lambda i, j, k: (k, j)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    operands = [x, idx, codebook.astype(jnp.float32)]
    if bias is not None:
        assert bias.shape == (1, N), bias.shape
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        operands.append(bias)

    return pl.pallas_call(
        functools.partial(
            _kernel, packed=packed, n_k=n_k,
            blocks_per_group=group_size // bk, relu=relu, pool=pool,
        ),
        grid=(M // bm, N // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm // pw, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M // pw, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)] if pool > 1 else [],
        compiler_params=_params(3),
        interpret=interpret,
        name=name,
    )(*operands)


# ---------------------------------------------------------------------------
# implicit-GEMM convolution: phase layout + in-kernel patch-tile assembly
# ---------------------------------------------------------------------------


def phase_slabs(xp: jax.Array, geom: ConvGeom, plan: SlabPlan) -> jax.Array:
    """Padded NCHW images ``(B, C, hp, wp)`` → the kernel's image operand
    ``(B, n_slabs, S², C, lanes)`` (XLA relayout, outside the kernel).

    Phase ``py·S + px`` of slab ``s`` is rows ``s·band_rows + py::S`` and
    columns ``px::S``, flattened at pitch ``wq``.  Rows past the image are
    zero; rows no valid output reads are cropped.  Slab halos are copied
    into each slab (``n_slabs·(band+halo)`` rows, the same rows a row-band
    pipeline would re-fetch)."""
    S, wq, L = geom.phase, plan.wq, plan.lanes
    hs = -(-L // wq)  # phase rows one slab's lanes span
    rows = (plan.n_slabs - 1) * plan.band_rows + hs * S
    B, C, hp, wp = xp.shape
    xp = xp[:, :, :rows]
    xp = jnp.pad(xp, ((0, 0), (0, 0), (0, rows - xp.shape[2]),
                      (0, wq * S - wp)))
    slabs = []
    for s in range(plan.n_slabs):
        xs = xp[:, :, s * plan.band_rows: s * plan.band_rows + hs * S]
        xs = xs.reshape(B, C, hs, S, wq, S).transpose(0, 3, 5, 1, 2, 4)
        slabs.append(xs.reshape(B, S * S, C, hs * wq)[..., :L])
    return jnp.stack(slabs, axis=1)


def assemble_tile(img_ref, t_ref, base, q0, *, geom: ConvGeom, plan: SlabPlan,
                  bk: int, gs: int, gs_pad: int):
    """Write the transposed ``(bk, pool²·bmp)`` patch tile into ``t_ref``.

    ``img_ref`` is one slab's ``(1, 1, S², C, lanes)`` phase block; tile
    row ``r`` is *padded* GEMM reduction position ``q = q0 + r``, lane group
    ``w`` holds window offset ``w = (wy, wx)`` of the block's ``bmp`` pooled
    wide pixels starting at lane ``base``.  Each padded position unmaps to
    its logical ``(c, dy, dx)`` patch element in the layout's order:
    ``g = q // gs_pad`` picks the codebook group and ``r = q % gs_pad`` the
    row in it; rows with ``r >= gs`` (tile-plan K-pad) or ``g·gs + r >=
    conv_k`` (the §3 pack-time K-pad) read **zero**, pairing with the
    reserved zero-codebook bin exactly like the explicit path's zero patch
    columns.  Tap ``(dy, dx)`` under window offset ``w`` reads phase
    ``(ey % S, ex % S)`` at flat offset ``(ey // S)·wq + ex // S`` with
    ``ey = wy·stride + dy`` — a unit-stride lane window, fetched as an
    aligned load and a lane rotate (Mosaic has no unaligned dynamic lane
    slice and no vector gather).
    """
    S, pool, st = geom.phase, geom.pool, geom.stride
    bmp, wq = plan.bmp, plan.wq
    pw = pool * pool
    win = bmp + LANE
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, bmp), 0)

    def decode(q):
        """Padded reduction position → logical ``(c, dy, dx)`` + validity."""
        g, rr = q // gs_pad, q % gs_pad
        ql = g * gs + jnp.minimum(rr, gs - 1)
        valid = (rr < gs) & (ql < geom.conv_k)
        ql = jnp.minimum(ql, geom.conv_k - 1)
        if geom.nhwc:  # channels-minor (ky, kx, c)
            return (ql % geom.c_in, ql // (geom.kx * geom.c_in),
                    (ql // geom.c_in) % geom.kx, valid)
        # paper (c, ky, kx) loop order
        return (ql // (geom.ky * geom.kx), (ql // geom.kx) % geom.ky,
                ql % geom.kx, valid)

    def window(c, dy, dx, w):
        """Channel ``c``'s lanes under tap ``(dy, dx)``, window offset ``w``."""
        ey, ex = (w // pool) * st + dy, (w % pool) * st + dx
        ph = (ey % S) * S + ex % S
        off = (ey // S) * wq + ex // S
        start = pl.multiple_of(base + (off // LANE) * LANE, LANE)
        v = img_ref[0, 0, ph, pl.ds(c, 1), pl.ds(start, win)]
        return pltpu.roll(v, (win - off % LANE) % win, 1)[:, :bmp]

    def rows8(r8, carry):
        # Mosaic stores only whole 8-row tiles at a dynamic row: gather 8
        # tile rows into one (8, bmp) block per window offset, then store
        r0 = pl.multiple_of(r8 * 8, 8)
        blocks = [jnp.zeros((8, bmp), t_ref.dtype) for _ in range(pw)]
        for i in range(8):
            c, dy, dx, valid = decode(q0 + r0 + i)
            keep = (sub == i) & valid
            for w in range(pw):
                v = jnp.broadcast_to(window(c, dy, dx, w), (8, bmp))
                blocks[w] = jnp.where(keep, v, blocks[w])
        for w in range(pw):
            t_ref[pl.ds(r0, 8), w * bmp:(w + 1) * bmp] = blocks[w]
        return carry

    jax.lax.fori_loop(0, -(-bk // 8), rows8, 0)


def conv_image_spec(geom: ConvGeom, plan: SlabPlan, c_in: int):
    """BlockSpec of the phase-layout image: one slab per block, advancing
    every ``n_blocks`` pixel blocks (Pallas prefetches the next slab while
    the current one computes and never refetches an unchanged block)."""
    S, nb = geom.phase, plan.n_blocks
    return pl.BlockSpec((1, 1, S * S, c_in, plan.lanes),
                        lambda b, i, j, k: (b, i // nb, 0, 0, 0))


def conv_out_spec(plan: SlabPlan, bn: int):
    nb = plan.n_blocks
    return pl.BlockSpec((1, 1, plan.bmp, bn),
                        lambda b, i, j, k: (b, i // nb, i % nb, j))


def _pasm_conv_kernel(
    x_ref, idx_ref, cb_ref, *rest, geom: ConvGeom, plan: SlabPlan,
    packed: bool, n_k: int, relu: bool, bk: int, gs: int, gs_pad: int,
):
    """Implicit-GEMM body: assemble the transposed patch tile once, then run
    the explicit GEMM's :func:`_fused_dequant_step` on each 128-lane chunk
    of the output-channel block — every chunk's contraction has the shape
    and operands of a ``bn = 128`` grid step, so the outputs are too."""
    pool = geom.pool
    if pool > 1:
        rest, acc_ref = rest[:-1], rest[-1]
    else:
        acc_ref = None
    *rest, t_ref = rest
    b_ref, o_ref = rest if len(rest) == 2 else (None, rest[0])
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _zero():
        z = acc_ref if pool > 1 else o_ref
        z[...] = jnp.zeros_like(z)

    base = (pl.program_id(1) % plan.n_blocks) * plan.bmp
    assemble_tile(x_ref, t_ref, base, k * bk, geom=geom, plan=plan, bk=bk,
                  gs=gs, gs_pad=gs_pad)
    lhs = t_ref[:bk, :]
    for c in range(o_ref.shape[-1] // LANE):
        cols = slice(c * LANE, (c + 1) * LANE)
        _fused_dequant_step(
            lhs, idx_ref.at[:, cols], cb_ref,
            None if b_ref is None else b_ref.at[:, cols],
            o_ref.at[..., cols],
            None if acc_ref is None else acc_ref.at[:, cols],
            k=k, g=k // (gs_pad // bk), n_k=n_k, packed=packed, relu=relu,
            pool=pool, transposed=True,
        )


def pasm_conv_kernel_call(
    x: jax.Array,
    idx: jax.Array,
    codebook: jax.Array,
    bias: "jax.Array | None" = None,
    *,
    geom: ConvGeom,
    plan: SlabPlan,
    packed: bool,
    gs: int,
    gs_pad: int,
    bn: int = 128,
    bk: int = 512,
    relu: bool = False,
    interpret: bool = False,
    name: str = "pasm_conv",
) -> jax.Array:
    """Implicit-GEMM conv pallas_call over the phase-layout image.

    ``x (B, n_slabs, S², C, lanes)`` (:func:`phase_slabs`) · ``idx (Kp or
    Kp//2, Np)`` · ``codebook (G, B)`` → ``(B, n_slabs, n_blocks·bmp, Np)
    f32``: pooled wide pixels per slab (the caller drops the wide columns
    and pad rows).  Grid ``(B, n_slabs·n_blocks, Np/bn, Kp/bk)``; the
    k-tile sequence is the explicit path's (same ``bk``/``gs_pad``), so the
    dequantized weight tiles and their order match it.

    ``bn`` is the output-channel block (``bn_conv`` of
    :func:`repro.kernels.ops.conv_tile_plan`, a 128-multiple divisor of
    ``Np``): each grid step assembles its patch tile once and feeds every
    128-lane chunk of the block from it, one ``(bk, 128)`` dequant, dot and
    epilogue per chunk — the contractions of ``bn = 128`` grid steps, so
    the result does not depend on ``bn``.  Preconditions (enforced by
    ops.py): ``gs_pad % bk == 0``, ``Np % bn == bn % 128 == 0``, bias
    ``(1, Np)``.
    """
    B_img, n_slabs, _, c_in, _ = x.shape
    Np = idx.shape[1]
    Kp = idx.shape[0] * (2 if packed else 1)
    G = codebook.shape[0]
    assert Kp == G * gs_pad, (Kp, G, gs_pad)
    assert gs_pad % bk == 0, (gs_pad, bk)
    assert Np % bn == 0 and bn % LANE == 0, (Np, bn)
    pw = geom.pool * geom.pool
    n_k = Kp // bk

    idx_block = (bk // 2, bn) if packed else (bk, bn)
    in_specs = [
        conv_image_spec(geom, plan, c_in),
        pl.BlockSpec(idx_block, lambda b, i, j, k: (k, j)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    operands = [x, idx, codebook.astype(jnp.float32)]
    if bias is not None:
        assert bias.shape == (1, Np), bias.shape
        in_specs.append(pl.BlockSpec((1, bn), lambda b, i, j, k: (0, j)))
        operands.append(bias)
    scratch = [pltpu.VMEM((-(-bk // 8) * 8, pw * plan.bmp), x.dtype)]
    if geom.pool > 1:
        scratch.append(pltpu.VMEM((pw * plan.bmp, bn), jnp.float32))

    return pl.pallas_call(
        functools.partial(
            _pasm_conv_kernel, geom=geom, plan=plan, packed=packed, n_k=n_k,
            relu=relu, bk=bk, gs=gs, gs_pad=gs_pad,
        ),
        grid=(B_img, n_slabs * plan.n_blocks, Np // bn, n_k),
        in_specs=in_specs,
        out_specs=conv_out_spec(plan, bn),
        out_shape=jax.ShapeDtypeStruct(
            (B_img, n_slabs, plan.n_blocks * plan.bmp, Np), jnp.float32),
        scratch_shapes=scratch,
        compiler_params=_params(4),
        interpret=interpret,
        name=name,
    )(*operands)
