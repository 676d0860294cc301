"""Weight-shared convolution — the unified `ConvParams`/`conv2d` surface.

The paper evaluates ONE accelerator in three variants (§4, Fig 13):
non-weight-shared, weight-shared, and weight-shared-with-PASM, each with
stride, bias and ReLU (bias/activation are *not* shared — §4).  This module
exposes that accelerator through two types and one entry point:

* :class:`ConvParams` — a tagged weight container: a ``dense`` kernel, a
  weight-shared dictionary (``shared``: uint8 bin indices + codebook), or an
  int4-``packed`` dictionary (two 4-bit indices per byte, §3 K-pad applied
  before packing so odd ``C·KY·KX`` reductions work).  Built via
  :meth:`ConvParams.dense` / :meth:`ConvParams.quantize` /
  :meth:`ConvParams.shared`, converted with :meth:`ConvParams.pack`.
* :class:`Conv2D` — the geometry-free layer spec: kernel size, channel
  counts, stride, ``padding="valid_centred"|"valid"|"same"``,
  ``layout="NCHW"|"NHWC"``, and the epilogue (``bias`` gate + ``relu`` flag).
  Image height/width are *not* part of the spec — they are read off the
  input, so one spec serves every image size.
* :func:`conv2d` — ``conv2d(x, params, conv, *, engine, interpret)``
  dispatches every (params kind × engine) combination:

  ===========  ================================================================
  engine       meaning
  ===========  ================================================================
  ``auto``     dense → einsum; shared/packed → implicit-GEMM Pallas kernel
               when batched (images past the VMEM budget stream as
               row-band slabs — no explicit fallback), einsum reference
               for single images
  ``einsum``   pure-XLA reference: (dequantized) dense GEMM + XLA epilogue
  ``kernel``   :func:`repro.kernels.ops.pasm_matmul` — fused-dequant Pallas
               GEMM with the bias/ReLU epilogue fused into the last-k-step
               write-through (one ``pallas_call`` per conv layer) over an
               explicitly materialized im2col patch matrix
  ``kernel_implicit``  :func:`repro.kernels.ops.pasm_conv2d` — **implicit
               im2col**: one ``pallas_call`` over the raw (padded) image;
               patch tiles are assembled inside the kernel, no ``(B·P, K)``
               patch matrix in HBM (bit-exact vs ``kernel``)
  ``pas_kernel``  :func:`repro.kernels.ops.pas_matmul` — the paper-faithful
               two-phase PAS formulation, epilogue fused into the post-pass
  ``pas_kernel_implicit``  :func:`repro.kernels.ops.pas_conv2d` — the
               two-phase formulation with implicit im2col
  ``pas_einsum``  the two-phase formulation as pure XLA (one-hot histogram +
               post-pass) — the seed's ``conv2d_pasm`` einsum port
  ===========  ================================================================

Convolution lowers onto the PASM GEMMs via im2col in the layout's column
order — ``(B, C, IH, IW) → (B·P, C·KY·KX)`` in the paper's ``(c, ky, kx)``
order for NCHW, or ``(B, IH, IW, C) → (B·P, KY·KX·C)`` channels-minor
(TPU-native) for NHWC — and the weight container flattens itself into the
matching ``(K, M)`` GEMM operand.  The explicit engines materialize that
patch matrix in HBM; the ``*_implicit`` engines assemble patch tiles inside
the kernel from the VMEM-resident image (DESIGN.md §3).

:class:`ConvParams` is the conv-geometry face of the one weight-shared
container: quantize/pack/groups/§3-K-pad semantics live in
:class:`repro.core.params.PasmParams`, and ConvParams delegates to it after
flattening kernels into the layout's ``(K, c_out)`` order — a dense FFN
weight and a conv kernel share one pack rule, one reserved-zero-bin pad,
one byte model.

The PR-1 ``ConvSpec``/``conv2d_direct``/``conv2d_weight_shared``/
``conv2d_pasm`` surface (deprecation-shimmed since PR 2) is gone; the
migration table lives in DESIGN.md §2.  ``quantize_conv_weights`` survives
as the paper's one-dictionary-per-layer helper.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.core import pasm as _pasm
from repro.core.params import PasmParams

__all__ = [
    "Conv2D",
    "ConvParams",
    "conv2d",
    "conv_out_hw",
    "conv_geom",
    "conv_plan",
    "max_pool2d",
    "quantize_conv_weights",
    "PADDINGS",
    "LAYOUTS",
    "POOL_IMPLS",
]

PADDINGS = ("valid_centred", "valid", "same")
LAYOUTS = ("NCHW", "NHWC")
ENGINES = (
    "auto",
    "einsum",
    "kernel",
    "kernel_implicit",
    "pas_kernel",
    "pas_kernel_implicit",
    "pas_einsum",
)
_IMPLICIT_ENGINES = ("kernel_implicit", "pas_kernel_implicit")
_PAS_ENGINES = ("pas_kernel", "pas_kernel_implicit", "pas_einsum")
# conv2d(pool=) fusion policy: "auto" fuses the max-pool into the kernel
# epilogue whenever the engine/geometry allow (reduce_window fallback
# otherwise — bit-exact either way), "fused" demands the fused path (raises
# when impossible), "unfused" always runs the separate reduce_window.
POOL_IMPLS = ("auto", "fused", "unfused")

# The implicit engines' per-image VMEM budget: the double-buffered padded
# image (or row-band slab) plus the idx / codebook / bias / output blocks
# must fit under it.  Images past the budget stream as slabs
# (``ops.conv_slab_plan``) — the budget sizes the slabs, it no longer flips
# ``auto`` to the explicit engine.  This module-level default suits a
# ~16 MiB-VMEM TPU core; per-call targets override it with
# ``conv2d(vmem_budget=)`` / ``CNNConfig.vmem_budget``.  Keep in sync with
# ``repro.kernels.ops.IMPLICIT_VMEM_BUDGET``.
_IMPLICIT_VMEM_BUDGET = 6 * 1024 * 1024

# GEMM column order per layout: NCHW flattens patches (and weights) in the
# paper's (c, ky, kx) loop-nest order (Fig 1); NHWC is channels-minor
# (ky, kx, c) — the TPU-native layout.
_ORDER = {"NCHW": "ckk", "NHWC": "kkc"}


# ---------------------------------------------------------------------------
# the layer spec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Conv2D:
    """Geometry-free conv layer spec (image H/W are read off the input)."""

    k: Union[int, tuple]
    c_in: int
    c_out: int
    stride: int = 1
    padding: str = "valid_centred"
    layout: str = "NCHW"
    bias: bool = True  # apply ``params.bias`` when present
    relu: bool = False

    def __post_init__(self):
        k = (self.k, self.k) if isinstance(self.k, int) else tuple(self.k)
        object.__setattr__(self, "k", k)
        if self.padding not in PADDINGS:
            raise ValueError(f"padding must be one of {PADDINGS}, got {self.padding!r}")
        if self.layout not in LAYOUTS:
            raise ValueError(f"layout must be one of {LAYOUTS}, got {self.layout!r}")

    @property
    def ky(self) -> int:
        return self.k[0]

    @property
    def kx(self) -> int:
        return self.k[1]

    @property
    def K(self) -> int:
        """The im2col reduction length ``c_in·ky·kx``."""
        return self.c_in * self.ky * self.kx


def _axis_geometry(size: int, k: int, stride: int, padding: str) -> tuple:
    """One spatial axis → ``(out, pad_lo, pad_hi)``.

    ``same`` matches XLA/TF SAME (out = ceil(size/stride), asymmetric zero
    pad); ``valid`` is standard VALID; ``valid_centred`` is the paper's
    kernel-centred loop bounds (Fig 1) — identical to ``valid`` for odd
    kernels, one output short when an even kernel tiles the axis exactly.
    """
    if padding == "same":
        out = -(-size // stride)
        pad = max((out - 1) * stride + k - size, 0)
        return out, pad // 2, pad - pad // 2
    if padding == "valid":
        return (size - k) // stride + 1, 0, 0
    return (size - 2 * (k // 2) + stride - 1) // stride, 0, 0


def conv_out_hw(ih: int, iw: int, conv: Conv2D) -> tuple:
    """Output (OH, OW) of ``conv`` on an ``ih × iw`` image."""
    oh, _, _ = _axis_geometry(ih, conv.ky, conv.stride, conv.padding)
    ow, _, _ = _axis_geometry(iw, conv.kx, conv.stride, conv.padding)
    return oh, ow


def conv_geom(conv: Conv2D, ih: int, iw: int, pool: int = 1):
    """The static geometry the implicit-GEMM kernels consume.

    Resolves the spec against an ``ih × iw`` image into the hashable
    :class:`repro.kernels.ops.ConvGeom` (output dims + spatial pad + the
    layout's reduction order) that rides jit static args.  ``pool > 1``
    requests the fused max-pool epilogue: the kernels walk window-major rows
    and store the pooled ``(oh//pool, ow//pool)`` map (DESIGN.md §3.2).
    """
    from repro.kernels import ops as _kops  # deferred: core must not need pallas

    oh, plo_h, phi_h = _axis_geometry(ih, conv.ky, conv.stride, conv.padding)
    ow, plo_w, phi_w = _axis_geometry(iw, conv.kx, conv.stride, conv.padding)
    return _kops.ConvGeom(
        nhwc=conv.layout == "NHWC",
        ky=conv.ky,
        kx=conv.kx,
        stride=conv.stride,
        oh=oh,
        ow=ow,
        c_in=conv.c_in,
        pad=((plo_h, phi_h), (plo_w, phi_w)),
        pool=pool,
    )


def _implicit_fits(
    conv: Conv2D, ih: int, iw: int, budget: Optional[int] = None,
    params: Optional["ConvParams"] = None, pool: int = 1,
) -> bool:
    """Whole-image VMEM residency predicate for the implicit-GEMM path.

    True when the *double-buffered* padded image plus every other
    per-grid-step VMEM block — idx / codebook / bias / (pooled) output
    block, their double buffers, and the pool (or PAS bin) scratch —
    fits ``budget`` (:func:`repro.kernels.ops.conv_whole_image_fits`,
    audited against the kernels' BlockSpecs), at the output-channel block
    the kernel takes (:func:`repro.kernels.ops.conv_tile_plan`).  The seed
    counted only one copy of the raw image bytes, under-reporting residency
    by the pipeline double buffer and the whole fixed-block overhead.

    Shapes that fail no longer fall back to explicit im2col: ``auto``
    keeps the implicit engine and the kernel wrappers stream the image as
    row-band slabs sized to the same ``budget``
    (:func:`repro.kernels.ops.conv_slab_plan`).  This predicate now marks
    the whole-image/slab boundary rather than gating dispatch.

    ``budget`` is the per-call VMEM budget in bytes
    (``conv2d(vmem_budget=)``); ``None`` takes the module default.
    ``params``/``pool`` refine the block accounting (packed idx bytes,
    bins, bias presence, pool-aligned ``bm``); without ``params`` the
    defaults model a shared unpacked dictionary with bias.
    """
    if budget is None:
        budget = _IMPLICIT_VMEM_BUDGET
    oh, plo_h, phi_h = _axis_geometry(ih, conv.ky, conv.stride, conv.padding)
    ow, plo_w, phi_w = _axis_geometry(iw, conv.kx, conv.stride, conv.padding)
    if oh <= 0 or ow <= 0:
        return False
    hp, wp = ih + plo_h + phi_h, iw + plo_w + phi_w
    from repro.kernels import ops as _kops  # deferred: core must not need pallas

    geom = conv_geom(conv, ih, iw, pool=pool)
    packed = params is not None and params.kind == "packed"
    pad_k = params.pad_k if params is not None else 0
    groups = params.groups if params is not None else 1
    bins = params.bins if params is not None else 16
    has_bias = params is None or params.bias is not None
    tp = _kops.conv_tile_plan(
        geom, hp, wp, k=conv.K + pad_k, n=conv.c_out, groups=groups,
        bins=bins, packed=packed, has_bias=has_bias, vmem_budget=budget,
    )
    return _kops.conv_whole_image_fits(
        geom, hp, wp, bn=tp.bn_conv, bk=tp.bk, bins=bins, packed=packed,
        pas=False, has_bias=has_bias, vmem_budget=budget,
    )


# ---------------------------------------------------------------------------
# the weight container
# ---------------------------------------------------------------------------


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["kernel", "idx", "codebook", "bias"],
    meta_fields=["kind", "kshape", "bins", "order", "pad_k"],
)
@dataclasses.dataclass(frozen=True)
class ConvParams:
    """Tagged conv weights: ``dense`` | weight-``shared`` | int4-``packed``.

    ``dense``   ``kernel (c_out, c_in, ky, kx)``; ``idx``/``codebook`` None.
    ``shared``  ``idx (c_out, c_in, ky, kx) uint8`` bin indices +
                ``codebook (bins,)`` — one dictionary per layer (paper §4) —
                or ``(groups, bins)`` with one dictionary per segment of the
                GEMM reduction axis (beyond-paper accuracy knob; ``order``
                records which layout's flatten order the groups split).
    ``packed``  ``idx (Kp//2, c_out) uint8`` — two 4-bit indices per byte in
                the GEMM ``(K, M)`` layout of ``order`` (baked at pack time);
                ``pad_k`` zero-activation rows were appended by the §3 K-pad
                so an odd ``C·KY·KX`` packs.
    ``bias``    ``(c_out,)`` or None on every kind — never shared (paper §4).
    """

    kernel: Optional[jax.Array] = None
    idx: Optional[jax.Array] = None
    codebook: Optional[jax.Array] = None
    bias: Optional[jax.Array] = None
    kind: str = "dense"
    kshape: tuple = ()
    bins: Optional[int] = None
    order: Optional[str] = None
    pad_k: int = 0

    # -- constructors -------------------------------------------------------

    @classmethod
    def dense(cls, kernel: jax.Array, *, bias: Optional[jax.Array] = None):
        """Non-weight-shared params from a ``(c_out, c_in, ky, kx)`` kernel."""
        if kernel.ndim != 4:
            raise ValueError(f"kernel must be (c_out, c_in, ky, kx), got {kernel.shape}")
        return cls(kernel=kernel, bias=bias, kind="dense", kshape=tuple(kernel.shape))

    @classmethod
    def shared(
        cls,
        idx: jax.Array,
        codebook: jax.Array,
        *,
        bias: Optional[jax.Array] = None,
        order: Optional[str] = None,
    ):
        """Weight-shared params from existing bin indices + dictionary.

        A 1-D ``codebook (bins,)`` is the paper's one-dictionary-per-layer
        rule; a 2-D ``(groups, bins)`` splits the GEMM reduction axis into
        ``groups`` segments with one dictionary each, and then ``order``
        (``"ckk"``/``"kkc"``) must name the flatten order the grouping was
        built for — group membership is a function of the flat K position.
        """
        if idx.ndim != 4:
            raise ValueError(f"idx must be (c_out, c_in, ky, kx), got {idx.shape}")
        if codebook.ndim == 2 and codebook.shape[0] == 1:
            codebook = codebook.reshape(-1)  # (1, B) ≡ the single-dict rule
        groups = 1 if codebook.ndim == 1 else int(codebook.shape[0])
        if groups > 1 and order not in _ORDER.values():
            raise ValueError(
                "grouped codebooks split the flattened reduction axis: pass "
                f"order='ckk'|'kkc' (the layout they were built for), got {order!r}"
            )
        if int(idx[0].size) % groups:
            raise ValueError(
                f"K = c_in·ky·kx = {idx[0].size} not divisible by "
                f"groups={groups}"
            )
        return cls(
            idx=idx.astype(jnp.uint8),
            codebook=codebook,
            bias=bias,
            kind="shared",
            kshape=tuple(idx.shape),
            bins=int(codebook.shape[-1]),
            order=order if groups > 1 else None,
        )

    @classmethod
    def quantize(
        cls,
        kernel: jax.Array,
        bins: int = 16,
        *,
        bias: Optional[jax.Array] = None,
        iters: int = 16,
        groups: int = 1,
        layout: str = "NCHW",
    ):
        """K-means weight-share a dense kernel.

        ``groups=1`` (default) is the paper rule — one dictionary per layer.
        ``groups > 1`` splits the GEMM reduction axis (``K = c_in·ky·kx``,
        flattened in ``layout``'s order) into that many segments with one
        dictionary each — the ROADMAP accuracy knob for small ``bins``; the
        resulting params are pinned to ``layout`` (``gemm_tensor`` refuses a
        mismatch, like packed params do).
        """
        if groups == 1:
            cb, idx = quantize_conv_weights(kernel, bins, iters=iters)
            return cls.shared(idx, cb, bias=bias)
        if layout not in LAYOUTS:
            raise ValueError(f"layout must be one of {LAYOUTS}, got {layout!r}")
        K = int(kernel[0].size)
        if K % groups:
            raise ValueError(
                f"K = c_in·ky·kx = {K} not divisible by groups={groups}"
            )
        order = _ORDER[layout]
        flat = _flatten_kernel(kernel, order)  # (K, c_out)
        p = PasmParams.quantize(flat, bins, groups=groups, iters=iters)
        return cls.shared(
            _unflatten_kernel(p.idx, order, tuple(kernel.shape)), p.codebook,
            bias=bias, order=order,
        )

    def pack(self, *, layout: str = "NCHW") -> "ConvParams":
        """int4-pack the dictionary indices into the GEMM layout of ``layout``.

        Halves conv weight bytes (two 4-bit indices per byte).  Odd
        ``C·KY·KX`` gets the §3 K-pad first: one pad row is appended, mapped
        to a reserved all-zero codebook bin when representable (``bins < 16``)
        or to bin 0 otherwise — exact either way, because :func:`conv2d`
        pairs the pad rows with zero patch columns.
        """
        if self.kind != "shared":
            raise ValueError(
                f"pack() needs shared params (got {self.kind!r}); "
                "quantize() dense kernels first"
            )
        if layout not in LAYOUTS:
            raise ValueError(f"layout must be one of {LAYOUTS}, got {layout!r}")
        order = _ORDER[layout]
        self._check_order(order)
        # flatten into the GEMM layout, then the geometry-free container owns
        # the pack rule (bins gate, grouped evenness, §3 reserved-zero-bin pad)
        base = PasmParams.shared(
            _flatten_kernel(self.idx, order), self.codebook
        ).pack()
        return ConvParams(
            idx=base.idx,
            codebook=(base.codebook.reshape(-1) if self.codebook.ndim == 1
                      else base.codebook),
            bias=self.bias,
            kind="packed",
            kshape=self.kshape,
            bins=base.bins,
            order=order,
            pad_k=base.pad_k,
        )

    # -- views --------------------------------------------------------------

    @property
    def c_out(self) -> int:
        return self.kshape[0]

    @property
    def groups(self) -> int:
        """Codebook groups along the GEMM reduction axis (1 = paper rule)."""
        cb = self.codebook
        return 1 if cb is None or cb.ndim == 1 else int(cb.shape[0])

    def _grouped_codebook(self) -> jax.Array:
        """The ``(G, B)`` f32 codebook the kernels consume."""
        cb = self.codebook.astype(jnp.float32)
        return cb.reshape(1, -1) if cb.ndim == 1 else cb

    def _check_order(self, order: str) -> None:
        if self.order is not None and order != self.order:
            what = "packed" if self.kind == "packed" else "grouped"
            fix = "re-pack" if self.kind == "packed" else "re-quantize"
            raise ValueError(
                f"params were {what} for order {self.order!r} but this layout "
                f"needs {order!r}; {fix} for this layout"
            )

    def _as_pasm(self, order: str) -> PasmParams:
        """The geometry-free container view, idx flattened into ``order``.

        The bridge that makes ConvParams a thin wrapper: GEMM-operand and
        dense-matrix construction live on :class:`PasmParams`; this just
        supplies the conv-specific flatten.
        """
        if self.kind == "packed":
            return PasmParams(
                idx=self.idx,
                codebook=self._grouped_codebook(),
                bias=self.bias,
                kind="packed",
                shape=(self.idx.shape[0] * 2 - self.pad_k, self.c_out),
                bins=self.bins,
                pad_k=self.pad_k,
            )
        if self.kind == "shared":
            return PasmParams(
                idx=_flatten_kernel(self.idx, order),
                codebook=self._grouped_codebook(),
                bias=self.bias,
                kind="shared",
                shape=(int(self.idx[0].size), self.c_out),
                bins=self.bins,
            )
        return PasmParams.dense(
            _flatten_kernel(self.kernel, order), bias=self.bias
        )

    def gemm_tensor(self, layout: str = "NCHW") -> _pasm.PASMTensor:
        """The dictionary as the ``(K, M)`` Pallas GEMM operand for ``layout``."""
        order = _ORDER[layout]
        if self.kind == "dense":
            raise ValueError("dense params have no dictionary; use engine='einsum'")
        self._check_order(order)
        return self._as_pasm(order).gemm_tensor()

    def dense_operand(self, layout: str = "NCHW") -> jax.Array:
        """The ``(K(+pad_k), M)`` dense GEMM operand (einsum reference path).

        Dtype is preserved for dense/shared kinds so integer-exactness claims
        (§5.3) survive the reference path; packed dequantizes to f32.
        """
        if self.kind == "dense":
            return _flatten_kernel(self.kernel, _ORDER[layout])
        if self.kind == "shared":
            if self.groups == 1:
                kernel = self.codebook[self.idx.astype(jnp.int32)]
                return _flatten_kernel(kernel, _ORDER[layout])
            self._check_order(_ORDER[layout])
            idxf = _flatten_kernel(self.idx, _ORDER[layout]).astype(jnp.int32)
            K, M = idxf.shape
            wg = jax.vmap(lambda cb, ix: cb[ix])(
                self.codebook, idxf.reshape(self.groups, K // self.groups, M)
            )
            return wg.reshape(K, M)
        return _pasm.dequantize(self.gemm_tensor(layout))


def _flatten_kernel(a: jax.Array, order: str) -> jax.Array:
    """(c_out, c_in, ky, kx) → (K, c_out) flat in ``order`` ∈ {ckk, kkc}."""
    if order == "kkc":
        a = a.transpose(0, 2, 3, 1)  # (c_out, ky, kx, c_in)
    return a.reshape(a.shape[0], -1).T


def _unflatten_kernel(flat: jax.Array, order: str, kshape: tuple) -> jax.Array:
    """Inverse of :func:`_flatten_kernel`: (K, c_out) → (c_out, c_in, ky, kx)."""
    c_out, c_in, ky, kx = kshape
    a = flat.T
    if order == "kkc":
        return a.reshape(c_out, ky, kx, c_in).transpose(0, 3, 1, 2)
    return a.reshape(kshape)


# ---------------------------------------------------------------------------
# im2col (both layouts, all paddings)
# ---------------------------------------------------------------------------


def _batched4(x: jax.Array) -> tuple:
    if x.ndim == 3:
        return x[None], True
    if x.ndim == 4:
        return x, False
    raise ValueError(f"x must be a single image (3-D) or a batch (4-D), got {x.shape}")


def _im2col(xb: jax.Array, conv: Conv2D) -> tuple:
    """Batched patches in the layout's GEMM column order.

    NCHW ``(B, C, IH, IW) → (B·P, C·KY·KX)`` (paper (c, ky, kx) order);
    NHWC ``(B, IH, IW, C) → (B·P, KY·KX·C)`` (channels-minor, TPU-native).
    Returns ``(patches, (oh, ow))``.  The gather itself lives in
    :func:`repro.kernels.ref.im2col_patches` (pure jnp, pallas-free) — one
    definition shared with the implicit path's col2im backward.
    """
    from repro.kernels.ref import im2col_patches

    nhwc = conv.layout == "NHWC"
    ih, iw = (xb.shape[1], xb.shape[2]) if nhwc else (xb.shape[2], xb.shape[3])
    oh, plo_h, phi_h = _axis_geometry(ih, conv.ky, conv.stride, conv.padding)
    ow, plo_w, phi_w = _axis_geometry(iw, conv.kx, conv.stride, conv.padding)
    patches = im2col_patches(
        xb, nhwc=nhwc, ky=conv.ky, kx=conv.kx, stride=conv.stride,
        oh=oh, ow=ow, c_in=conv.c_in, pad=((plo_h, phi_h), (plo_w, phi_w)),
    )
    return patches, (oh, ow)


def _col2im(y: jax.Array, conv: Conv2D, batch: int, oh: int, ow: int, squeeze: bool):
    """GEMM output (B·P, M) → feature map in the spec's layout."""
    if conv.layout == "NHWC":
        out = y.reshape(batch, oh, ow, conv.c_out)
    else:
        out = y.reshape(batch, oh * ow, conv.c_out)
        out = jnp.moveaxis(out, -1, 1).reshape(batch, conv.c_out, oh, ow)
    return out[0] if squeeze else out


def _epilogue(y: jax.Array, bias: Optional[jax.Array], relu: bool) -> jax.Array:
    # one definition shared with the kernel oracles (repro.kernels.ref has no
    # pallas dependency, so core stays pallas-free)
    from repro.kernels.ref import apply_epilogue

    return apply_epilogue(y, bias, relu)


def max_pool2d(x: jax.Array, pool: int, layout: str) -> jax.Array:
    """Non-overlapping max pool, VALID (floor) windowing, layout-aware.

    The unfused reference (and fallback path) of ``conv2d(pool=)``; accepts
    a batched 4-D feature map or a single squeezed 3-D one.  The window init
    is the dtype's max-monoid identity: ``jnp.iinfo(dtype).min`` for
    integer/quantized activations (the former unconditional ``-jnp.inf``
    would fail the integer ``reduce_window`` dtype check), ``-inf`` for
    floats (``jnp.finfo(...).min`` would stop XLA from recognizing the max
    monoid and lose the ``reduce_window_max`` primitive — and with it the
    VJP).  Every window is fully covered (non-overlapping VALID), so the
    init never leaks into the output either way.
    """
    if pool == 1:
        return x
    # a NumPy scalar of the operand dtype: the value must equal THAT dtype's
    # max identity for jax to recognize the monoid (reduce_window_max, which
    # carries the VJP) — a weak python int or a mismatched-dtype init falls
    # into the generic non-differentiable reduce_window
    if jnp.issubdtype(x.dtype, jnp.integer):
        init = x.dtype.type(jnp.iinfo(x.dtype).min)
    else:
        init = x.dtype.type(-jnp.inf)
    if x.ndim == 4:
        window = (1, pool, pool, 1) if layout == "NHWC" else (1, 1, pool, pool)
    elif x.ndim == 3:
        window = (pool, pool, 1) if layout == "NHWC" else (1, pool, pool)
    else:
        raise ValueError(f"max_pool2d needs a 3-D or 4-D feature map, got {x.shape}")
    return jax.lax.reduce_window(x, init, jax.lax.max, window, window, "VALID")


# ---------------------------------------------------------------------------
# the entry point
# ---------------------------------------------------------------------------


def _resolve_engine(
    engine: str, params: ConvParams, squeeze: bool, conv: Conv2D, ih: int,
    iw: int, budget: Optional[int] = None,
) -> str:
    if engine not in ENGINES:
        raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
    if params.kind == "dense":
        if engine in ("auto", "einsum"):
            return "einsum"
        raise ValueError(f"dense params have no dictionary; engine {engine!r} "
                         "needs shared/packed params")
    if params.groups > 1 and engine in _PAS_ENGINES:
        raise ValueError(
            "the PAS formulation is paper-faithful single-dictionary; grouped "
            "codebooks need engine='kernel'/'kernel_implicit'/'einsum'"
        )
    if engine == "auto":
        # batched inputs ride the Pallas fast path — always implicit im2col:
        # images past the VMEM budget stream as row-band slabs instead of
        # falling back to explicit im2col (``budget``/``vmem_budget`` now
        # sizes the slabs, it no longer flips the engine); single images keep
        # the einsum reference port (the semantics the kernels are tested
        # against).  Degenerate geometry (no output pixels) keeps the
        # explicit path, whose empty patch matrix handles it.
        if squeeze:
            return "einsum"
        oh, ow = conv_out_hw(ih, iw, conv)
        return "kernel_implicit" if oh > 0 and ow > 0 else "kernel"
    return engine


def _pool_fusible(eng: str, conv: Conv2D, ih: int, iw: int, pool: int,
                  mesh) -> bool:
    """``conv2d(pool=)``'s ``auto`` fuse predicate.

    Fuses when: a Pallas engine; the pooled output is non-empty (floor
    windowing needs at least one whole window per axis); and a pool-aligned
    tile plan exists (``lcm(pool², 8) ≤ 256`` rows — the kernels reduce
    whole windows per block).  A mesh no longer blocks the explicit
    engines: ``conv2d`` pads the batch to divide ``data``, so the
    window-major patch rows split as ``(batch/n_data)·P_rows`` per shard —
    always whole pool windows (``P_rows`` is a multiple of ``pool²``) —
    and the explicit fused pool shards like the implicit one (the PR-5
    carve-out is closed).  Everything this refuses runs the bit-exact
    ``reduce_window`` fallback.
    """
    del mesh  # no longer consulted (and may be any mesh-like object)
    if pool == 1 or eng in ("einsum", "pas_einsum"):
        return False
    oh, ow = conv_out_hw(ih, iw, conv)
    if oh < pool or ow < pool:
        return False
    from repro.kernels import ops as _kops  # deferred: core must not need pallas

    return _kops.pool_plan_exists(pool)


def conv_plan(
    params: "ConvParams", conv: Conv2D, ih: int, iw: int, *,
    engine: str = "auto", pool: int = 1, pool_impl: str = "auto",
    vmem_budget: Optional[int] = None, mesh=None, batched: bool = True,
) -> tuple:
    """The ``(engine, fused_pool)`` pair :func:`conv2d` would dispatch.

    Public so benches/models can model a stage's dataflow (engine choice,
    whether the max-pool folds into the kernel) without re-implementing the
    dispatch rules — :func:`conv2d` itself routes through this, so the two
    can never drift apart.
    """
    eng = _resolve_engine(engine, params, not batched, conv, ih, iw,
                          vmem_budget)
    fused = (pool > 1 and pool_impl != "unfused"
             and _pool_fusible(eng, conv, ih, iw, pool, mesh))
    return eng, fused


def _pool_order_patches(patches: jax.Array, batch: int, oh: int, ow: int,
                        pool: int) -> jax.Array:
    """Row-major ``(B·P, K)`` patches → window-major ``(B·P_out·pool², K)``.

    The explicit fused-pool GEMM's row contract: each consecutive ``pool²``
    rows form one pool window (so the kernel's epilogue max is a pure
    reshape), floor-remainder pixels are dropped before the GEMM ever runs —
    the same windows the implicit kernel pools in its epilogue.
    """
    K = patches.shape[1]
    ohp, owp = oh // pool, ow // pool
    pm = patches.reshape(batch, oh, ow, K)[:, : ohp * pool, : owp * pool]
    pm = pm.reshape(batch, ohp, pool, owp, pool, K).transpose(0, 1, 3, 2, 4, 5)
    return pm.reshape(batch * ohp * owp * pool * pool, K)


def _einsum_sharded(patches, w, bias, relu: bool, mesh):
    """The pure-XLA reference engine under shard_map (the dense-params path).

    Rows over ``data``, the N output dim over ``model`` when divisible (else
    the dense operand replicates) — the same axis mapping (and the same
    :func:`repro.launch.mesh.n_shard_axis` rule) as the Pallas engines, so
    dense params shard like dictionary params do.
    """
    from jax.sharding import PartitionSpec as P
    from repro.kernels.ref import apply_epilogue  # pallas-free
    from repro.launch.mesh import n_shard_axis

    ns = n_shard_axis(mesh, w.shape[1])
    if bias is None:
        return jax.shard_map(
            lambda pt, wl: apply_epilogue(pt @ wl, None, relu),
            mesh=mesh, in_specs=(P("data", None), P(None, ns)),
            out_specs=P("data", ns), check_vma=False,
        )(patches, w)
    return jax.shard_map(
        lambda pt, wl, bl: apply_epilogue(pt @ wl, bl, relu),
        mesh=mesh, in_specs=(P("data", None), P(None, ns), P(ns)),
        out_specs=P("data", ns), check_vma=False,
    )(patches, w, bias)


def conv2d(
    x: jax.Array,
    params: ConvParams,
    conv: Conv2D,
    *,
    engine: str = "auto",
    interpret: Optional[bool] = None,
    mesh=None,
    vmem_budget: Optional[int] = None,
    pool: int = 1,
    pool_impl: str = "auto",
    name: Optional[str] = None,
) -> jax.Array:
    """The unified conv entry point: any params kind, any engine, any layout.

    ``x`` is a single image or a batch in ``conv.layout`` order.  On the
    Pallas engines the bias/ReLU epilogue is fused into the kernel's final
    reduction step, so a batched conv layer is exactly one ``pallas_call`` —
    and on the ``*_implicit`` engines that call consumes the raw (padded)
    image directly, with the im2col tiles assembled in VMEM.

    ``pool > 1`` appends a non-overlapping ``(pool, pool)`` max-pool (VALID
    floor windowing — :func:`max_pool2d` semantics).  ``pool_impl="auto"``
    fuses it into the kernel epilogue whenever :func:`_pool_fusible` allows
    — the whole conv/ReLU/pool stage is then ONE ``pallas_call`` storing
    only the pooled map (DESIGN.md §3.2) — and falls back to the separate
    ``reduce_window`` otherwise; the two paths are bit-exact.  ``"fused"``
    demands the fused path (raises when impossible), ``"unfused"`` forces
    the fallback.

    ``mesh=`` (a ``jax.sharding.Mesh`` with a ``data`` axis, optionally
    ``model``) runs the layer sharded: the batch over ``data`` (uneven
    remainders are zero-padded in and sliced off — DESIGN.md §4.1), the
    output channels over ``model`` when divisible.  Sharded outputs are
    bit-exact vs the single-device call on every engine but ``pas_einsum``
    (the single-device reference port, which refuses a mesh).

    ``vmem_budget=`` overrides the implicit engines' per-image VMEM budget
    in bytes (default ``_IMPLICIT_VMEM_BUDGET``).  Images whose
    double-buffered whole-image residency exceeds it stream through the
    kernel as row-band slabs (:func:`repro.kernels.ops.conv_slab_plan`) —
    bit-exact vs the whole-image schedule — so the budget tunes slab
    sizing per target core rather than flipping ``auto`` to the explicit
    engine.

    ``name=`` names the layer's kernel in the compiled program and in a
    profiler trace: ``<name>_pasm``/``<name>_pas`` on the implicit engines,
    ``<name>_pasm_patch``/``<name>_pas_patch`` on the explicit ones.
    Without it the kernel keeps its family's name (``pasm_conv``,
    ``pas_conv``, ``pasm_matmul``, ``pas_matmul``).
    """
    if pool_impl not in POOL_IMPLS:
        raise ValueError(f"pool_impl must be one of {POOL_IMPLS}, got {pool_impl!r}")
    if int(pool) != pool or pool < 1:
        raise ValueError(f"pool must be a positive integer window, got {pool!r}")
    pool = int(pool)  # accept integral floats; downstream math needs an int
    xb, squeeze = _batched4(x)
    nhwc = conv.layout == "NHWC"
    c_axis = -1 if nhwc else 1
    if xb.shape[c_axis] != conv.c_in:
        raise ValueError(
            f"input {x.shape} has {xb.shape[c_axis]} channels on the "
            f"{conv.layout} channel axis; spec says c_in={conv.c_in}"
        )
    if params.kshape != (conv.c_out, conv.c_in, conv.ky, conv.kx):
        raise ValueError(
            f"params kshape {params.kshape} does not match spec "
            f"{(conv.c_out, conv.c_in, conv.ky, conv.kx)}"
        )
    ih, iw = (xb.shape[1], xb.shape[2]) if nhwc else (xb.shape[2], xb.shape[3])
    eng, fuse_pool = conv_plan(
        params, conv, ih, iw, engine=engine, pool=pool, pool_impl=pool_impl,
        vmem_budget=vmem_budget, mesh=mesh, batched=not squeeze,
    )
    bias = params.bias if conv.bias else None
    if pool_impl == "fused" and pool > 1 and not fuse_pool:
        raise ValueError(
            f"pool_impl='fused' but engine {eng!r} cannot fuse pool={pool} "
            "here (einsum engines, sub-window outputs and oversize windows "
            "all need the reduce_window fallback — pool_impl='auto' picks "
            "it automatically)"
        )

    batch = xb.shape[0]
    if mesh is not None:
        if squeeze:
            raise ValueError(
                "mesh= shards the batch over the 'data' axis; pass a batched "
                "4-D input"
            )
        if eng == "pas_einsum":
            raise ValueError(
                "pas_einsum is the single-device reference port; mesh= runs "
                "on einsum or the Pallas engines"
            )
        from repro.launch.mesh import data_model_sizes  # pallas-free, jax-only

        pad_b = -batch % data_model_sizes(mesh)[0]
        if pad_b:  # uneven batch remainder: zero images in, sliced off below
            xb = jnp.pad(xb, ((0, pad_b),) + ((0, 0),) * 3)

    if eng in _IMPLICIT_ENGINES:
        from repro.kernels import ops as _kops  # deferred: core must not need pallas

        geom = conv_geom(conv, ih, iw, pool=pool if fuse_pool else 1)
        t = params.gemm_tensor(conv.layout)
        f, kind = ((_kops.pasm_conv2d, "pasm") if eng == "kernel_implicit"
                   else (_kops.pas_conv2d, "pas"))
        named = {"name": f"{name}_{kind}"} if name else {}
        # resolve the budget here (not in the kernel wrappers) so per-call
        # overrides AND the module default both reach the slab planner
        y = f(xb, t, geom, bias=bias, relu=conv.relu, interpret=interpret,
              mesh=mesh,
              vmem_budget=(vmem_budget if vmem_budget is not None
                           else _IMPLICIT_VMEM_BUDGET), **named)
        y = y.reshape(-1, conv.c_out)  # (B, P, M) → (B·P, M), after the kernel
        if fuse_pool:  # the kernel already stored the pooled map
            out = _col2im(y, conv, xb.shape[0], geom.ohp, geom.owp, squeeze)
        else:
            out = _col2im(y, conv, xb.shape[0], geom.oh, geom.ow, squeeze)
            out = max_pool2d(out, pool, conv.layout)
        return out[:batch] if mesh is not None else out

    patches, (oh, ow) = _im2col(xb, conv)
    if fuse_pool:  # explicit fused pool: window-major rows for the kernels
        patches = _pool_order_patches(patches, xb.shape[0], oh, ow, pool)

    if eng == "einsum":
        w = params.dense_operand(conv.layout)
        if params.pad_k:
            patches = jnp.pad(patches, ((0, 0), (0, params.pad_k)))
        if mesh is not None:
            y = _einsum_sharded(patches, w, bias, conv.relu, mesh)
        else:
            y = _epilogue(patches @ w, bias, conv.relu)
    elif eng == "pas_einsum":
        y = _pas_einsum(patches, params, conv.layout)
        y = _epilogue(y, bias, conv.relu)
    else:
        from repro.kernels import ops as _kops  # deferred: core must not need pallas

        t = params.gemm_tensor(conv.layout)
        if params.pad_k:
            patches = jnp.pad(patches, ((0, 0), (0, params.pad_k)))
        f, kind = ((_kops.pasm_matmul, "pasm") if eng == "kernel"
                   else (_kops.pas_matmul, "pas"))
        named = {"name": f"{name}_{kind}_patch"} if name else {}
        y = f(patches, t, bias=bias, relu=conv.relu, interpret=interpret,
              mesh=mesh, pool=pool if fuse_pool else 1, **named)
    if fuse_pool:
        out = _col2im(y, conv, xb.shape[0], oh // pool, ow // pool, squeeze)
    else:
        out = _col2im(y, conv, xb.shape[0], oh, ow, squeeze)
        out = max_pool2d(out, pool, conv.layout)
    return out[:batch] if mesh is not None else out


def _pas_einsum(patches: jax.Array, params: ConvParams, layout: str) -> jax.Array:
    """The two-phase PASM formulation in pure XLA (Fig 13, the seed's port).

    Per output pixel and channel: PAS bins via a one-hot histogram over the
    patch axis, then one multiply per bin — bit-exact on integer inputs.
    """
    if params.kind == "packed":
        idx = _pasm.logical_idx(params.gemm_tensor(layout)).T  # (M, K+pad)
        if params.pad_k:
            patches = jnp.pad(patches, ((0, 0), (0, params.pad_k)))
    else:
        idx = _flatten_kernel(params.idx, _ORDER[layout]).T  # (M, K)
    B = params.codebook.shape[-1]
    onehot = jax.nn.one_hot(idx, B, dtype=patches.dtype)  # (M, K, B)
    # PAS phase: imageBin[p, m, b] = Σ_n patches[p, n]·[idx[m, n] = b]
    image_bins = jnp.einsum("pn,mnb->pmb", patches, onehot)
    # post-pass multiply: one multiply per bin, not per element
    return jnp.einsum("pmb,b->pm", image_bins, params.codebook.astype(patches.dtype))


# ---------------------------------------------------------------------------
# kept helper: the paper's one-dictionary quantizer on raw kernels
# ---------------------------------------------------------------------------


def quantize_conv_weights(
    kernel: jax.Array, bins: int, *, iters: int = 16
) -> tuple:
    """K-means weight-share a conv kernel: one dictionary per layer (paper §4).

    Returns ``(codebook (B,), bin_idx (M, C, KY, KX) uint8)`` — the raw
    pieces for callers that build their own :meth:`ConvParams.shared`.  The
    clustering is the 1-D k-means every PASM quantizer shares, over the
    kernel flattened to one axis: the TPU compiler takes about a minute per
    conv layer to relayout the kernel's ``(…, ky, kx)`` tiles into a 2-D
    ``(1, n)`` or ``(n, 1)`` matrix, and seconds into a vector.
    """
    cb, idx = _pasm._kmeans_1d(kernel.reshape(-1).astype(jnp.float32), bins,
                               iters)
    return cb, idx.reshape(kernel.shape).astype(jnp.uint8)
