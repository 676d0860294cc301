"""AlexNet-style CNN on the weight-shared conv accelerator (DESIGN.md §3).

The paper evaluates ONE AlexNet conv layer; this model stacks the same
accelerator into the full network shape it was drawn from: conv/ReLU/pool
stages, each conv carrying its own PASM dictionary (per-layer codebooks, the
paper's one-dictionary-per-layer rule), followed by a dense classifier head
(fully-connected layers are outside the paper's conv accelerator and stay
dense).  Every stage is one :class:`repro.core.conv.ConvParams` +
:class:`~repro.core.conv.Conv2D` pair dispatched through
:func:`repro.core.conv.conv2d`; on the Pallas engines bias+ReLU — and the
stage's max-pool (``conv2d(pool=)``, DESIGN.md §3.2) — fuse into the kernel,
so each batched conv/ReLU/pool stage is a single ``pallas_call`` whose store
is already the pooled map.

``cfg.padding``/``cfg.layout`` apply stack-wide (``same``+``NHWC`` gives
torchvision-exact geometry on the TPU-native layout); ``cfg.packed``
int4-packs every conv dictionary at quantize time.

Usage (see also ``examples/paper_conv.py`` and ``benchmarks/conv_bench.py``)::

    cfg = get_cnn_config("alexnet", smoke=True)
    params = cnn.init_params(cfg, key)          # dense ConvParams per stage
    qparams = cnn.quantize(params, cfg)         # per-layer k-means codebooks
    logits = cnn.forward(qparams, images, cfg)  # (B, classes) via Pallas

Sharded (``cfg.mesh_shape`` → ``launch.mesh.make_conv_mesh``)::

    mesh = conv_mesh(cfg)                        # ("data", "model")
    qparams = cnn.quantize(params, cfg, mesh=mesh)   # pspec-placed weights
    logits = cnn.forward(qparams, imgs, cfg, mesh=mesh)  # shard_map per layer

QAT (``core/qat.py`` STE through the conv dictionaries)::

    cbs = cnn.qat_codebooks(params, cfg)         # per-layer dictionaries
    logits = cnn.qat_forward(params, cbs, imgs, cfg)  # STE-snapped forward
    qparams = cnn.qat_requantize(params, cbs, cfg)    # freeze for serving
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.alexnet_conv import CNNConfig
from repro.core import conv as _conv
from repro.core import pasm as _pasm
from repro.core import qat as _qat
from repro.models.common import Initializer

__all__ = ["stages", "feature_shape", "init_params", "quantize", "forward",
           "forward_dense", "conv_mesh", "qat_codebooks", "qat_apply",
           "qat_forward", "qat_requantize"]

#  CNNConfig.impl == conv2d engine (kernel_implicit = implicit-GEMM Pallas;
#  auto lets conv2d pick per layer under cfg.vmem_budget)
_IMPLS = ("auto", "einsum", "kernel", "kernel_implicit", "pas_kernel",
          "pas_kernel_implicit")


def stages(cfg: CNNConfig) -> list:
    """Per-stage ``(Conv2D, pool)`` with the stack-wide padding/layout applied."""
    return [
        (dataclasses.replace(c, padding=cfg.padding, layout=cfg.layout), p)
        for c, p in zip(cfg.layers, cfg.pools)
    ]


def feature_shape(cfg: CNNConfig) -> tuple:
    """(C, H, W) entering the classifier head."""
    _, H, W = cfg.in_chw
    C = cfg.in_chw[0]
    for conv, pool in stages(cfg):
        H, W = _conv.conv_out_hw(H, W, conv)
        if pool > 1:
            H, W = H // pool, W // pool
        C = conv.c_out
    return C, H, W


def init_params(cfg: CNNConfig, key: jax.Array) -> dict:
    """Dense master weights: per-layer ConvParams + head matrix."""
    ini = Initializer(key)
    convs = []
    for conv, _pool in stages(cfg):
        fan_in = conv.c_in * conv.ky * conv.kx
        kernel = ini.dense((conv.c_out, conv.c_in, conv.ky, conv.kx), fan_in=fan_in)
        convs.append(_conv.ConvParams.dense(
            kernel, bias=jnp.zeros((conv.c_out,), jnp.float32)
        ))
    C, H, W = feature_shape(cfg)
    return {
        "conv": convs,
        "head": {"w": ini.dense((C * H * W, cfg.classes)),
                 "b": jnp.zeros((cfg.classes,), jnp.float32)},
    }


def conv_mesh(cfg: CNNConfig):
    """``cfg.mesh_shape`` → the stack's ``("data", "model")`` mesh."""
    from repro.launch.mesh import make_conv_mesh

    return make_conv_mesh(cfg.mesh_shape)


def _place(params: dict, mesh) -> dict:
    """Put every leaf on ``mesh`` per the models/sharding.py CNN rules."""
    from repro.launch.mesh import axis_sizes
    from repro.models import sharding as _sharding

    specs = _sharding.conv_param_pspecs(params, axis_sizes(mesh))
    return jax.tree.map(
        lambda leaf, s: jax.device_put(leaf, jax.sharding.NamedSharding(mesh, s)),
        params, specs,
    )


def quantize(params: dict, cfg: CNNConfig, *, iters: int = 16, mesh=None) -> dict:
    """K-means weight-share every conv layer: one PASM dictionary per layer.

    Each dense ConvParams becomes a ``shared`` one (bias stays dense — §4:
    bias/activation not shared); ``cfg.groups > 1`` gives every layer that
    many reduction-axis dictionaries (beyond-paper accuracy knob) and
    ``cfg.packed`` additionally int4-packs the dictionary indices into the
    stack layout's GEMM order.  ``mesh=`` places the result per the
    models/sharding.py CNN rules (c_out over ``model``, codebooks
    replicated) so per-device weight HBM shrinks with the mesh.
    """
    convs = []
    for p in params["conv"]:
        q = _conv.ConvParams.quantize(
            p.kernel, cfg.bins, bias=p.bias, iters=iters, groups=cfg.groups,
            layout=cfg.layout,
        )
        if cfg.packed:
            q = q.pack(layout=cfg.layout)
        convs.append(q)
    out = {"conv": convs, "head": params["head"]}
    return _place(out, mesh) if mesh is not None else out


# NOTE: the former ``_max_pool`` helper is gone — conv stages pass ``pool=``
# straight to :func:`repro.core.conv.conv2d` (fused into the kernel epilogue
# where possible), and the standalone fallback is the public
# :func:`repro.core.conv.max_pool2d` (dtype-correct window init: ``iinfo``
# minimum for integer/quantized maps, ``-inf`` — the differentiable max
# identity — for floats).


def _head(x: jax.Array, head: dict, mesh=None) -> jax.Array:
    """Dense classifier.  Under ``mesh=`` the matmul runs in shard_map (rows
    over ``data``, classes over ``model`` when divisible) so the contraction
    keeps the full feature axis per shard — XLA would otherwise split the
    model-sharded channel dim into a psum whose reduction order differs from
    single-device, costing stack-level bit-exactness.

    The matmul runs at ``HIGHEST`` precision, as the conv kernels do: the
    TPU's default rounds f32 operands to bfloat16."""
    B = x.shape[0]
    xf = x.reshape(B, -1)

    def fc(xl, wl, bl):
        return jnp.dot(xl, wl, precision=jax.lax.Precision.HIGHEST) + bl

    if mesh is None:
        return fc(xf, head["w"], head["b"])
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import data_model_sizes, n_shard_axis
    from repro.models.sharding import conv_batch_pad

    nd, _ = data_model_sizes(mesh)
    pad = conv_batch_pad(B, nd)
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    ns = n_shard_axis(mesh, head["w"].shape[1])
    y = jax.shard_map(
        fc, mesh=mesh, in_specs=(P("data", None), P(None, ns), P(ns)),
        out_specs=P("data", ns), check_vma=False,
    )(xf, head["w"], head["b"])
    return y[:B]


def forward(
    params: dict,
    images: jax.Array,
    cfg: CNNConfig,
    *,
    interpret: Optional[bool] = None,
    mesh=None,
) -> jax.Array:
    """Quantized forward: images (in ``cfg.layout`` order) → logits.

    ``cfg.impl`` picks the conv engine per DESIGN.md §2/§3: ``kernel`` runs
    the fused-dequant ``pasm_matmul`` over an explicit im2col patch matrix,
    ``kernel_implicit`` the implicit-GEMM ``pasm_conv2d`` (patch tiles
    assembled in VMEM, no patch matrix in HBM), ``pas_kernel`` the
    paper-faithful two-phase ``pas_matmul`` and ``pas_kernel_implicit`` its
    implicit-GEMM ``pas_conv2d`` (all with the bias/ReLU epilogue fused into
    the pallas_call), ``einsum`` the pure-XLA reference port.

    Each stage's max-pool rides ``conv2d(pool=)``: on the Pallas engines the
    pool fuses into the conv kernel's epilogue (one ``pallas_call`` per
    conv/ReLU/pool stage, pre-pool activations never in HBM — DESIGN.md
    §3.2), with the bit-exact ``reduce_window`` fallback wherever fusion is
    impossible; ``cfg.pool_impl`` pins the policy.

    ``mesh=`` runs every conv layer sharded (``conv2d(mesh=)``: batch over
    ``data``, output channels over ``model``); the fused pool shards with
    the images on every Pallas engine (implicit windows live inside one
    image; explicit window-major patch rows split per image in whole
    windows), and each sharded conv all-gathers its ``model``-sharded
    output channels inside the kernel's shard_map body (the epilogue-fused
    collective) — consecutive conv layers hand over model-replicated
    activations, so XLA inserts no resharding between their pallas_calls.
    ``cfg.vmem_budget`` bounds the implicit engines' per-image VMEM
    footprint: larger images stream through the kernel as row-band slabs,
    bit-exact (DESIGN.md §3.3).

    Stage ``i`` (from 1) names its kernel ``conv<i>_<engine kind>``
    (``conv2d(name=)``), so a profiler trace shows ``conv1_pasm`` … per
    stage.  The stage also runs under ``jax.named_scope("conv<i>")`` and the
    classifier head under ``"head"``: every relayout, pad and head op then
    carries its stage in the HLO ``op_name`` metadata, which the compiled
    program's text and a profile taken with HLO protos show.
    """
    if cfg.impl not in _IMPLS:
        raise ValueError(
            f"impl must be one of {'|'.join(_IMPLS)}, got {cfg.impl!r}"
        )
    x = images
    for i, (p, (conv, pool)) in enumerate(zip(params["conv"], stages(cfg)), 1):
        with jax.named_scope(f"conv{i}"):
            x = _conv.conv2d(x, p, conv, engine=cfg.impl, interpret=interpret,
                             mesh=mesh, vmem_budget=cfg.vmem_budget, pool=pool,
                             pool_impl=cfg.pool_impl, name=f"conv{i}")
    with jax.named_scope("head"):
        return _head(x, params["head"], mesh=mesh)


def forward_dense(
    params: dict, images: jax.Array, cfg: CNNConfig, *, mesh=None
) -> jax.Array:
    """Reference forward on the dense master weights (no weight sharing)."""
    x = images
    for p, (conv, pool) in zip(params["conv"], stages(cfg)):
        x = _conv.conv2d(x, p, conv, engine="einsum", mesh=mesh, pool=pool)
        # einsum is pure XLA: conv2d pools via the reduce_window fallback
    return _head(x, params["head"], mesh=mesh)


# ---------------------------------------------------------------------------
# QAT: core/qat.py's STE through the conv stack's per-layer dictionaries
# ---------------------------------------------------------------------------


def _qat_check_groups(cfg: CNNConfig) -> None:
    if cfg.groups > 1:
        raise ValueError(
            "CNN QAT is single-dictionary (the paper's per-layer rule): "
            f"cfg.groups={cfg.groups} would train/freeze a different "
            "quantization scheme than quantize() serves; set groups=1"
        )


def qat_codebooks(params: dict, cfg: CNNConfig, *, iters: int = 16) -> list:
    """Initial per-layer dictionaries: k-means over each dense master kernel
    (the same assignment rule :func:`quantize` bakes into ``shared`` params,
    kept as plain ``(bins,)`` leaves so they can be trained)."""
    _qat_check_groups(cfg)
    cbs = []
    for p in params["conv"]:
        flat = p.kernel.reshape(1, -1).T  # single group = single dictionary
        cb, _ = _pasm.kmeans_codebook(flat, cfg.bins, groups=1, iters=iters)
        cbs.append(cb[0])
    return cbs


def qat_apply(params: dict, codebooks: Sequence[jax.Array]) -> dict:
    """STE-snap every dense master ConvParams onto its layer dictionary.

    The forward value is the codebook-snapped kernel (what the PASM engines
    would serve); the gradient flows straight through to the dense master
    (``qat.ste_quantize``) while each codebook entry accumulates the
    bin-summed grads of its assigned weights.  Bias stays dense (§4).
    """
    convs = [
        _conv.ConvParams.dense(_qat.ste_quantize(p.kernel, cb), bias=p.bias)
        for p, cb in zip(params["conv"], codebooks)
    ]
    return {"conv": convs, "head": params["head"]}


def qat_forward(
    params: dict,
    codebooks: Sequence[jax.Array],
    images: jax.Array,
    cfg: CNNConfig,
    *,
    mesh=None,
) -> jax.Array:
    """QAT training forward: dense masters STE-snapped per step, then the
    dense reference engine (differentiable in masters, codebooks, bias and
    head — the ROADMAP "CNN QAT" wiring)."""
    return forward_dense(qat_apply(params, codebooks), images, cfg, mesh=mesh)


def qat_requantize(
    params: dict, codebooks: Sequence[jax.Array], cfg: CNNConfig, *, mesh=None
) -> dict:
    """Freeze trained masters onto their dictionaries for serving.

    The nearest-entry re-assignment is :func:`repro.core.qat.assign_bins` —
    the STE forward's rule, and per group :func:`repro.core.pasm.
    quantize_like`'s — so the frozen ``shared`` ConvParams' :func:`forward`
    equals :func:`qat_forward` at the same masters/codebooks.
    """
    _qat_check_groups(cfg)
    convs = []
    for p, cb in zip(params["conv"], codebooks):
        idx = _qat.assign_bins(p.kernel, cb).astype(jnp.uint8)
        q = _conv.ConvParams.shared(idx, cb, bias=p.bias)
        if cfg.packed:
            q = q.pack(layout=cfg.layout)
        convs.append(q)
    out = {"conv": convs, "head": params["head"]}
    return _place(out, mesh) if mesh is not None else out
