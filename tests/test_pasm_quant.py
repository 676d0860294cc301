"""Weight-sharing quantizer: k-means, packing, compression accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st

from repro.core import pasm


@settings(deadline=None, max_examples=30)
@given(
    kdim=st.integers(1, 32).map(lambda v: v * 2),
    n=st.integers(1, 48),
    seed=st.integers(0, 2**31 - 1),
)
def test_pack_unpack_roundtrip(kdim, n, seed):
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, 16, size=(kdim, n)), jnp.uint8)
    packed = pasm.pack_int4(idx)
    assert packed.shape == (kdim // 2, n)
    np.testing.assert_array_equal(np.asarray(pasm.unpack_int4(packed)), np.asarray(idx))


def test_quantize_error_decreases_with_bins():
    w = jax.random.normal(jax.random.PRNGKey(0), (128, 64))
    errs = []
    for bins in (4, 16, 64, 256):
        t = pasm.quantize(w, bins=bins)
        errs.append(float(jnp.abs(w - pasm.dequantize(t)).mean()))
    assert errs == sorted(errs, reverse=True)
    assert errs[-1] < 0.02  # 256 bins ≈ near-lossless for gaussians


def test_grouped_codebooks_beat_single():
    """Beyond-paper: per-group dictionaries reduce quantization error."""
    k = jax.random.PRNGKey(3)
    # heterogeneous rows: scale varies by block — groups should win
    w = jax.random.normal(k, (128, 32)) * jnp.repeat(
        jnp.array([0.1, 1.0, 5.0, 20.0]), 32
    )[:, None]
    e1 = float(jnp.abs(w - pasm.dequantize(pasm.quantize(w, 16, groups=1))).mean())
    e4 = float(jnp.abs(w - pasm.dequantize(pasm.quantize(w, 16, groups=4))).mean())
    assert e4 < e1


def test_compression_ratio_accounting():
    w = jnp.zeros((256, 256))
    t16 = pasm.quantize(w, bins=16)  # packed int4
    t256 = pasm.quantize(w, bins=256)  # uint8
    assert t16.packed and t16.idx.shape == (128, 256)
    assert not t256.packed and t256.idx.shape == (256, 256)
    # bf16 dense = 131072 B; int4 = 32768 B + codebook
    assert 3.9 < t16.compression_ratio <= 4.0
    assert 1.9 < t256.compression_ratio <= 2.0


def test_bins_bits_mapping():
    assert pasm.bits_for_bins(16) == 4
    assert pasm.bits_for_bins(17) == 8
    assert pasm.bits_for_bins(256) == 8
    with pytest.raises(ValueError):
        pasm.bits_for_bins(257)
    with pytest.raises(ValueError):
        pasm.bits_for_bins(1)


def test_quantize_like_reassigns():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    t = pasm.quantize(w, bins=16)
    w2 = w + 0.01 * jax.random.normal(jax.random.PRNGKey(1), w.shape)
    t2 = pasm.quantize_like(t, w2)
    np.testing.assert_array_equal(np.asarray(t2.codebook), np.asarray(t.codebook))
    err = float(jnp.abs(pasm.dequantize(t2) - w2).mean())
    base = float(jnp.abs(pasm.dequantize(t) - w2).mean())
    assert err <= base + 1e-6


@pytest.mark.parametrize("n,bins,kind", [
    (1, 16, "normal"), (7, 16, "normal"), (1000, 4, "normal"),
    (35_000, 16, "ties"), (4096, 256, "zeros"), (12_288, 16, "wide"),
])
def test_sort_free_quantiles_match_jnp(n, bins, kind):
    """k-means' quantile init selects order statistics without a sort and
    returns jnp.quantile's values (ties, signed zeros, huge magnitudes)."""
    rng = np.random.default_rng(n)
    v = rng.standard_normal(n).astype(np.float32) * 0.05
    if kind == "ties":
        v = np.round(v * 20) / 20
    elif kind == "zeros":
        v[: n // 3], v[n // 3: n // 2] = 0.0, -0.0
    elif kind == "wide":
        v = v * np.float32(10.0) ** rng.integers(-30, 30, n).astype(np.float32)
    qs = (jnp.arange(bins, dtype=jnp.float32) + 0.5) / bins  # as k-means
    got = jax.jit(lambda v: pasm._quantiles(v, qs))(v)
    want = jax.jit(lambda v: jnp.quantile(v, qs))(v)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kmeans_deterministic():
    w = jax.random.normal(jax.random.PRNGKey(7), (64, 64))
    a = pasm.quantize(w, bins=16)
    b = pasm.quantize(w, bins=16)
    np.testing.assert_array_equal(np.asarray(a.idx), np.asarray(b.idx))
    np.testing.assert_array_equal(np.asarray(a.codebook), np.asarray(b.codebook))
