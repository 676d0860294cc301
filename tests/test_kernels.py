"""Per-kernel allclose vs the pure-jnp oracles (interpret=True on CPU).

Sweeps shapes/dtypes per the assignment: every Pallas kernel is validated
against its ref.py oracle across M/K/N, bins, groups, packing, and dtype.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st

from repro.core import pasm
from repro.kernels import ops, ref


def _mk(M, K, N, bins, groups, dtype, seed=0):
    kk = jax.random.PRNGKey(seed)
    w = jax.random.normal(kk, (K, N))
    t = pasm.quantize(w, bins=bins, groups=groups)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (M, K)).astype(dtype)
    return x, t


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "M,K,N,bins,groups",
    [
        (8, 64, 32, 16, 1),      # packed int4, single dictionary
        (8, 64, 32, 64, 1),      # uint8
        (16, 128, 128, 16, 4),   # grouped + packed
        (5, 96, 17, 16, 2),      # non-tile-aligned M/N (padding path)
        (1, 256, 256, 256, 1),   # max bins, M=1 (decode-like)
        (33, 512, 64, 8, 8),     # many groups
    ],
)
def test_pasm_matmul_vs_oracle(M, K, N, bins, groups, dtype):
    x, t = _mk(M, K, N, bins, groups, dtype)
    got = ops.pasm_matmul(x, t, interpret=True)
    want = ref.pasm_matmul_ref(x, t.idx, t.codebook, packed=t.packed)
    # f32 tolerance covers k-tile reassociation (kernel accumulates per tile)
    tol = 5e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("bins", [8, 64], ids=["int4", "uint8"])
def test_gather_strategies_agree(bins):
    """The kernel's one-hot select (the only dequant Mosaic lowers) agrees
    with the oracle's codebook gather, packed int4 and plain uint8 indices."""
    x, t = _mk(8, 64, 32, bins, 1, jnp.float32)
    assert t.packed == (bins <= 16)
    got = ops.pasm_matmul(x, t, interpret=True)
    want = ref.pasm_matmul_ref(x, t.idx, t.codebook, packed=t.packed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "M,K,N,bins",
    [(8, 64, 32, 16), (16, 128, 64, 4), (4, 256, 128, 16)],
)
def test_pas_histogram_kernel_vs_oracle(M, K, N, bins):
    """The paper-faithful two-phase kernel: PAS bins in VMEM + post-pass."""
    x, t = _mk(M, K, N, bins, 1, jnp.float32)
    t = dataclasses.replace(t, idx=pasm.logical_idx(t), packed=False)
    got = ops.pas_matmul(x, t, interpret=True)
    want = ref.pas_matmul_ref(x, t.idx, t.codebook)
    ws = ref.pasm_matmul_ref(x, t.idx, t.codebook, packed=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
    # the PASM identity holds at the kernel level too
    np.testing.assert_allclose(np.asarray(got), np.asarray(ws), rtol=1e-3, atol=1e-3)


@settings(deadline=None, max_examples=10)
@given(
    m=st.integers(1, 40),
    n=st.integers(1, 40),
    kmul=st.integers(1, 4),
    bins=st.sampled_from([4, 16, 64]),
    seed=st.integers(0, 1000),
)
def test_pasm_matmul_property(m, n, kmul, bins, seed):
    """Property sweep: arbitrary shapes route through padding correctly."""
    K = 32 * kmul
    x, t = _mk(m, K, n, bins, 1, jnp.float32, seed)
    got = ops.pasm_matmul(x, t, interpret=True)
    want = ref.pasm_matmul_ref(x, t.idx, t.codebook, packed=t.packed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(
    "M,K,N,bins,groups",
    [
        (9, 363, 96, 16, 1),     # odd K (AlexNet conv1 im2col: 3·11·11)
        (16, 2400, 256, 16, 1),  # K = 96·5·5 (conv2), packed, padded to 2432
        (8, 1125, 32, 64, 1),    # odd K > 512: the seed raised ValueError here
        (8, 1200, 32, 16, 2),    # grouped + packed: per-group K padding
    ],
)
def test_pasm_matmul_kpad_vs_oracle(M, K, N, bins, groups):
    """Reduction dims with no clean tile divisor route through K-padding
    (reserved zero-codebook bin) instead of the seed's hard ``ValueError``."""
    pack = None if K % 2 == 0 else False
    kk = jax.random.PRNGKey(0)
    w = jax.random.normal(kk, (K, N))
    t = pasm.quantize(w, bins=bins, groups=groups, pack=pack)
    x = jax.random.normal(jax.random.PRNGKey(1), (M, K))
    got = ops.pasm_matmul(x, t, interpret=True)
    want = ref.pasm_matmul_ref(x, t.idx, t.codebook, packed=t.packed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4, atol=5e-4)


def test_pas_matmul_kpad_vs_oracle():
    """The paper-faithful kernel also accepts K-padded reductions."""
    K = 2400
    w = jax.random.normal(jax.random.PRNGKey(2), (K, 64))
    t = pasm.quantize(w, bins=16, groups=1, pack=False)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, K))
    got = ops.pas_matmul(x, t, interpret=True)
    want = ref.pas_matmul_ref(x, t.idx, t.codebook)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize(
    "bins,groups,packed",
    [(16, 4, True), (16, 2, True), (64, 4, False), (16, 1, True)],
)
def test_pasm_bwd_gradcheck_vs_dequant_chain(bins, groups, packed):
    """The custom VJP (packed int4 + groups>1 included) ≡ grad through
    dequantize-then-dot: same codebook/activation gradients."""
    M, K, N = 6, 128, 48
    w = jax.random.normal(jax.random.PRNGKey(4), (K, N))
    t = pasm.quantize(w, bins=bins, groups=groups, pack=packed)
    assert t.packed == packed
    x = jax.random.normal(jax.random.PRNGKey(5), (M, K))

    def loss_kernel(x, cb):
        tt = dataclasses.replace(t, codebook=cb)
        return (ops.pasm_matmul(x, tt, interpret=True) ** 2).sum()

    def loss_chain(x, cb):
        tt = dataclasses.replace(t, codebook=cb)
        wd = pasm.dequantize(tt, dtype=x.dtype)
        return (jnp.dot(x, wd, preferred_element_type=jnp.float32) ** 2).sum()

    gx_k, gcb_k = jax.grad(loss_kernel, argnums=(0, 1))(x, t.codebook)
    gx_c, gcb_c = jax.grad(loss_chain, argnums=(0, 1))(x, t.codebook)
    assert gcb_k.shape == t.codebook.shape == (groups, bins)
    np.testing.assert_allclose(np.asarray(gx_k), np.asarray(gx_c), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gcb_k), np.asarray(gcb_c), rtol=1e-4, atol=1e-4)


def test_gradients_match_numeric():
    x, t = _mk(6, 64, 24, 16, 2, jnp.float32)

    def loss(x, cb):
        tt = dataclasses.replace(t, codebook=cb)
        return (ops.pasm_matmul(x, tt, interpret=True) ** 2).sum()

    gx, gcb = jax.grad(loss, argnums=(0, 1))(x, t.codebook)
    eps = 5e-2  # central differences (f32 loss values ~1e3: large eps needed)
    num = (loss(x, t.codebook.at[1, 5].add(eps)) - loss(x, t.codebook.at[1, 5].add(-eps))) / (2 * eps)
    np.testing.assert_allclose(float(num), float(gcb[1, 5]), rtol=5e-2)
    num_x = (loss(x.at[2, 3].add(eps), t.codebook) - loss(x.at[2, 3].add(-eps), t.codebook)) / (2 * eps)
    np.testing.assert_allclose(float(num_x), float(gx[2, 3]), rtol=5e-2)


def test_batched_leading_dims():
    x, t = _mk(12, 64, 32, 16, 1, jnp.bfloat16)
    x3 = x.reshape(3, 4, 64)
    y3 = ops.pasm_matmul(x3, t, interpret=True)
    y2 = ops.pasm_matmul(x, t, interpret=True)
    assert y3.shape == (3, 4, 32)
    np.testing.assert_allclose(
        np.asarray(y3.reshape(12, 32)), np.asarray(y2), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# fused bias/ReLU epilogue (the last-k-step write-through)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize(
    "M,K,N,bins,groups,packed",
    [
        (8, 64, 32, 16, 1, True),    # packed, aligned
        (5, 96, 17, 16, 2, False),   # grouped + padding path (bias padded too)
        (16, 2400, 256, 16, 1, True),  # conv2-sized K-padded reduction
    ],
)
def test_pasm_matmul_fused_epilogue_vs_oracle(relu, M, K, N, bins, groups, packed):
    w = jax.random.normal(jax.random.PRNGKey(0), (K, N))
    t = pasm.quantize(w, bins=bins, groups=groups, pack=packed)
    x = jax.random.normal(jax.random.PRNGKey(1), (M, K))
    bias = jnp.linspace(-2.0, 2.0, N)
    got = ops.pasm_matmul(x, t, bias=bias, relu=relu, interpret=True)
    want = ref.apply_epilogue(
        ref.pasm_matmul_ref(x, t.idx, t.codebook, packed=t.packed), bias, relu
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4, atol=5e-4)
    if relu:
        assert float(got.min()) >= 0.0


@pytest.mark.parametrize("relu", [False, True])
def test_pas_matmul_fused_epilogue_vs_oracle(relu):
    x, t = _mk(8, 128, 48, 16, 1, jnp.float32)
    t = dataclasses.replace(t, idx=pasm.logical_idx(t), packed=False)
    bias = jnp.linspace(-1.0, 1.0, 48)
    got = ops.pas_matmul(x, t, bias=bias, relu=relu, interpret=True)
    want = ref.apply_epilogue(ref.pas_matmul_ref(x, t.idx, t.codebook), bias, relu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_fused_epilogue_gradcheck():
    """The fused-path VJP ≡ grad through dequantize→dot→bias→ReLU."""
    M, K, N = 6, 128, 48
    w = jax.random.normal(jax.random.PRNGKey(4), (K, N))
    t = pasm.quantize(w, bins=16, groups=2, pack=True)
    x = jax.random.normal(jax.random.PRNGKey(5), (M, K))
    bias = jnp.linspace(-0.5, 0.5, N)

    def loss_kernel(x, cb, b):
        tt = dataclasses.replace(t, codebook=cb)
        return (ops.pasm_matmul(x, tt, bias=b, relu=True, interpret=True) ** 2).sum()

    def loss_chain(x, cb, b):
        tt = dataclasses.replace(t, codebook=cb)
        wd = pasm.dequantize(tt, dtype=x.dtype)
        y = jnp.dot(x, wd, preferred_element_type=jnp.float32) + b
        return (jnp.maximum(y, 0.0) ** 2).sum()

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(x, t.codebook, bias)
    gc = jax.grad(loss_chain, argnums=(0, 1, 2))(x, t.codebook, bias)
    for a, b in zip(gk, gc):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention kernel
# ---------------------------------------------------------------------------


def _naive_attn(q, k, v, causal):
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k) * hd ** -0.5
    if causal:
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(k.shape[1])[None, :]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v)
    return o.reshape(B, Sq, H, hd)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "B,S,H,KV,hd,bq,bk",
    [
        (2, 64, 4, 2, 16, 16, 16),   # GQA
        (1, 56, 4, 4, 16, 16, 16),   # MHA, non-divisible S (pad path)
        (1, 128, 8, 1, 32, 32, 64),  # MQA, rectangular blocks
    ],
)
def test_flash_attention_vs_naive(causal, B, S, H, KV, hd, bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    got = ops.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk, interpret=True)
    want = _naive_attn(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 16), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 64, 2, 16), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 64, 2, 16), jnp.bfloat16)
    got = ops.flash_attention(q, k, v, bq=16, bk=16, interpret=True)
    want = _naive_attn(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=3e-2, atol=3e-2
    )
