"""PASM weight-sharing: codebook quantization of dense weights.

Implements the weight-sharing scheme PASM depends on (Han et al. 2015/2016, as
used by Garland & Gregg 2018): every weight of a layer is replaced by a
``log2(B)``-bit index into a tiny codebook ("dictionary") of ``B`` shared
values.  The paper uses one dictionary per layer (``groups=1``); we additionally
support group-wise codebooks along the reduction axis (a beyond-paper accuracy
feature, ``groups>1``).

The quantized weight is carried through jit as a :class:`PASMTensor` pytree —
``idx`` (uint8, optionally two 4-bit indices packed per byte) plus ``codebook``
(``(G, B)`` float32).  Dequantization happens either in the Pallas kernel
(production path) or via :func:`dequantize` (oracle path).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PASMTensor",
    "kmeans_codebook",
    "quantize",
    "dequantize",
    "pack_int4",
    "unpack_int4",
    "bits_for_bins",
]


def bits_for_bins(bins: int) -> int:
    """Index bit-width for ``bins`` dictionary entries (paper: 2^2..2^8 bins)."""
    if bins < 2 or bins > 256:
        raise ValueError(f"PASM supports 2..256 bins, got {bins}")
    return 4 if bins <= 16 else 8


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["idx", "codebook"],
    meta_fields=["shape", "bins", "bits", "packed"],
)
@dataclasses.dataclass(frozen=True)
class PASMTensor:
    """A weight-shared tensor: per-element bin indices + shared-value codebook.

    ``idx``       uint8 indices.  Logical shape is ``shape`` (always 2-D,
                  ``(K, N)`` = (reduction, output)).  When ``packed`` the K axis
                  holds two 4-bit indices per byte: physical ``(K//2, N)``.
    ``codebook``  ``(G, B)`` float32 shared weight values; group ``g`` covers
                  rows ``[g*K/G, (g+1)*K/G)`` of the reduction axis.
    """

    idx: jax.Array
    codebook: jax.Array
    shape: tuple
    bins: int
    bits: int
    packed: bool

    @property
    def groups(self) -> int:
        return self.codebook.shape[0]

    @property
    def nbytes_weights(self) -> int:
        """HBM bytes for the weight payload (what the memory roofline sees)."""
        return int(np.prod(self.idx.shape)) * 1 + self.codebook.size * 4

    @property
    def nbytes_dense_bf16(self) -> int:
        return int(np.prod(self.shape)) * 2

    @property
    def compression_ratio(self) -> float:
        return self.nbytes_dense_bf16 / self.nbytes_weights


# ---------------------------------------------------------------------------
# k-means clustering (Lloyd iterations, quantile init — deterministic)
# ---------------------------------------------------------------------------


def _order_stats(values: jax.Array, ranks: jax.Array) -> jax.Array:
    """``jnp.sort(values)[ranks]`` without the sort (flat f32 ``values``).

    A 32-step radix select over the floats' order-preserving uint32 keys:
    step ``i`` fixes answer bit ``31 - i`` by counting the keys at or below
    the largest candidate with that bit clear.  The TPU compiler takes tens
    of seconds to compile a sort of one conv layer's million weights; this
    compiles in under one."""
    bits = jax.lax.bitcast_convert_type(values, jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000))
    need = ranks.astype(jnp.int32) + 1

    def step(i, ans):
        bit = jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32))
        probe = ans | (bit - 1)
        below = jnp.sum(keys[:, None] <= probe[None, :], axis=0,
                        dtype=jnp.int32)
        return jnp.where(below >= need, ans, ans | bit)

    ans = jax.lax.fori_loop(0, 32, step, jnp.zeros(ranks.shape, jnp.uint32))
    bits = jnp.where(ans >> 31 == 1, ans & jnp.uint32(0x7FFFFFFF), ~ans)
    return jax.lax.bitcast_convert_type(bits, values.dtype)


def _quantiles(values: jax.Array, qs: jax.Array) -> jax.Array:
    """``jnp.quantile(values, qs)`` (linear method, same arithmetic) over
    :func:`_order_stats` — equal values, only the sign of a zero may differ."""
    n = jnp.float32(values.shape[0])
    pos = qs * (n - 1)
    low, high = jnp.floor(pos), jnp.ceil(pos)
    high_w = pos - low
    low_w = 1 - high_w
    ranks = jnp.clip(jnp.concatenate([low, high]), 0, n - 1).astype(jnp.int32)
    v = _order_stats(values, ranks)
    return v[: qs.shape[0]] * low_w + v[qs.shape[0]:] * high_w


def _kmeans_1d(values: jax.Array, bins: int, iters: int) -> tuple[jax.Array, jax.Array]:
    """1-D k-means on ``values`` (flat). Returns (codebook (B,), idx (len,))."""
    # Quantile init spreads centroids across the empirical distribution —
    # deterministic and robust for weight distributions (approx. zero-mean).
    qs = (jnp.arange(bins, dtype=jnp.float32) + 0.5) / bins
    centroids = _quantiles(values, qs)

    def assign(c):
        d = jnp.abs(values[:, None] - c[None, :])
        return jnp.argmin(d, axis=1)

    def step(c, _):
        a = assign(c)
        one_hot = jax.nn.one_hot(a, bins, dtype=values.dtype)
        counts = one_hot.sum(axis=0)
        sums = one_hot.T @ values
        new_c = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), c)
        return new_c, None

    centroids, _ = jax.lax.scan(step, centroids, None, length=iters)
    centroids = jnp.sort(centroids)
    return centroids, assign(centroids)


def kmeans_codebook(
    w: jax.Array, bins: int, *, groups: int = 1, iters: int = 16
) -> tuple[jax.Array, jax.Array]:
    """Cluster a 2-D weight ``(K, N)`` into ``groups`` codebooks of ``bins``.

    Returns ``(codebook (G, B) f32, idx (K, N) uint8)``.
    """
    if w.ndim != 2:
        raise ValueError(f"kmeans_codebook expects 2-D (K, N), got {w.shape}")
    K, N = w.shape
    if K % groups != 0:
        raise ValueError(f"K={K} not divisible by groups={groups}")
    wg = w.astype(jnp.float32).reshape(groups, K // groups * N)
    codebooks, idx = jax.vmap(lambda v: _kmeans_1d(v, bins, iters))(wg)
    idx = idx.reshape(groups, K // groups, N).reshape(K, N).astype(jnp.uint8)
    return codebooks, idx


# ---------------------------------------------------------------------------
# int4 packing (two indices per byte along the reduction axis)
# ---------------------------------------------------------------------------


def pack_int4(idx: jax.Array) -> jax.Array:
    """Pack ``(K, N)`` uint8 values < 16 into ``(K//2, N)``: lo nibble = even row."""
    K = idx.shape[0]
    if K % 2 != 0:
        raise ValueError(f"K={K} must be even to pack int4")
    lo = idx[0::2]
    hi = idx[1::2]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4` → ``(2*Kp, N)`` uint8."""
    lo = packed & 0x0F
    hi = packed >> 4
    out = jnp.stack([lo, hi], axis=1)  # (Kp, 2, N)
    return out.reshape(packed.shape[0] * 2, *packed.shape[1:]).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# quantize / dequantize
# ---------------------------------------------------------------------------


def quantize(
    w: jax.Array,
    bins: int = 16,
    *,
    groups: int = 1,
    iters: int = 16,
    pack: Optional[bool] = None,
) -> PASMTensor:
    """Post-training weight-share a 2-D weight (paper-faithful for groups=1)."""
    bits = bits_for_bins(bins)
    if pack is None:
        pack = bits == 4
    if pack and bits != 4:
        raise ValueError("packing requires bins <= 16")
    codebook, idx = kmeans_codebook(w, bins, groups=groups, iters=iters)
    if pack:
        idx = pack_int4(idx)
    return PASMTensor(
        idx=idx,
        codebook=codebook,
        shape=tuple(w.shape),
        bins=bins,
        bits=bits,
        packed=bool(pack),
    )


def logical_idx(t: PASMTensor) -> jax.Array:
    """The ``(K, N)`` uint8 index array regardless of packing."""
    return unpack_int4(t.idx) if t.packed else t.idx


def dequantize(t: PASMTensor, dtype=jnp.float32) -> jax.Array:
    """Reconstruct the dense ``(K, N)`` weight — the weight-shared MAC's view."""
    idx = logical_idx(t)
    K, N = t.shape
    G = t.groups
    idxg = idx.reshape(G, K // G, N)
    wg = jax.vmap(lambda cb, ix: cb[ix])(t.codebook, idxg)
    return wg.reshape(K, N).astype(dtype)


def quantize_like(t: PASMTensor, w: jax.Array) -> PASMTensor:
    """Re-assign ``w`` to the nearest entries of an existing codebook (QAT path)."""
    K, N = t.shape
    G = t.groups
    wg = w.astype(jnp.float32).reshape(G, K // G, N)

    def assign(cb, v):
        return jnp.argmin(jnp.abs(v[..., None] - cb), axis=-1).astype(jnp.uint8)

    idx = jax.vmap(assign)(t.codebook, wg).reshape(K, N)
    if t.packed:
        idx = pack_int4(idx)
    return dataclasses.replace(t, idx=idx)
