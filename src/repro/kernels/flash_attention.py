"""Pallas TPU kernel: fused flash attention (forward), GQA-aware.

The §Perf log identifies attention-chunk HBM round-trips as the dominant
residual memory term for the 32 k-prefill cells: the pure-XLA online-softmax
scan spills its (m, l, o) carries to HBM every KV block.  This kernel keeps
the whole running state in VMEM/VREG — HBM traffic collapses to one read of
Q/K/V and one write of O, the flash-attention bound.

Layout: q (BKV, G, Sq, hd) — query heads regrouped under their KV head so
K/V tiles are shared by the whole group; grid (BKV, G, Sq/bq) with the KV
sequence loop *inside* the kernel (fori over bk-sized slices of the VMEM-
resident K/V block).  Causal masking prunes fully-masked KV blocks via the
loop upper bound.

VMEM budget per program: K,V (Sk·hd bf16 ≈ 8 MiB each at 32 k × 128) +
q/acc tiles — within the ~128 MiB v5e VMEM for the assigned shapes; longer
contexts would tile K/V over a second grid axis (not needed for the 40 cells).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention_kernel_call"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, sk: int, sk_orig: int,
            scale: float, causal: bool):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, hd)
    hd = q.shape[-1]
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    n_blocks = sk // bk
    if causal:
        # highest KV block any row of this q tile can see
        last = (qi + 1) * bq  # exclusive
        n_live = (last + bk - 1) // bk
        upper = jnp.minimum(n_blocks, n_live)
    else:
        upper = n_blocks

    def step(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(j * bk, bk), :].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, pl.dslice(j * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = k_pos < sk_orig
        if causal:
            mask &= q_pos >= k_pos
        s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, upper, step, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_kernel_call(
    q: jax.Array,  # (BKV, G, Sq, hd)
    k: jax.Array,  # (BKV, Sk, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    sk_orig: int | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    BKV, G, Sq, hd = q.shape
    Sk = k.shape[1]
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    scale = hd ** -0.5
    return pl.pallas_call(
        functools.partial(
            _kernel, bq=bq, bk=bk, sk=Sk, sk_orig=sk_orig or Sk, scale=scale,
            causal=causal,
        ),
        grid=(BKV, G, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, g, i: (b, g, i, 0)),
            pl.BlockSpec((1, Sk, hd), lambda b, g, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, hd), lambda b, g, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, g, i: (b, g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BKV, G, Sq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=interpret,
    )(q, k, v)
