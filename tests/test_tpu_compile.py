"""The main-path Pallas kernels compile for a TPU v5e, at real widths.

Interpret mode cannot refuse what Mosaic refuses (vector gathers, sub-32-bit
compares and shifts, misaligned blocks, unsupported shape casts, VMEM
overruns), so each test here lowers and compiles for a *described* v5e —
the installed TPU compiler, no chip attached — and checks the kernels are
really in the program (``tpu_custom_call``).  Nothing runs; results and
times need the chip (``chip_smoke.py``).

The topology is described inside a module fixture (never at import time):
only one process may hold the TPU library, and every test worker imports
this file.  The persistent compilation cache is off around these compiles:
a compile for a described chip is written to it but cannot be read back
without one.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import pasm as _pasm
from repro.kernels import ops

# stablelm-3b FFN GEMM: d_model 2560 → d_ff 6912, one prefill chunk of rows
FFN_M, FFN_K, FFN_N = 256, 2560, 6912


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    """Abstract leaves of ``tree`` placed on the described device."""
    return jax.tree.map(lambda a: _sds(a.shape, a.dtype, sharding), tree)


def _compile(fn, *args) -> str:
    """Lower + compile for the described chip; the optimized HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _tensor(sharding, *, packed: bool, groups: int) -> _pasm.PASMTensor:
    rows = FFN_K // 2 if packed else FFN_K
    return _pasm.PASMTensor(
        idx=_sds((rows, FFN_N), jnp.uint8, sharding),
        codebook=_sds((groups, 16), jnp.float32, sharding),
        shape=(FFN_K, FFN_N), bins=16, bits=4, packed=packed,
    )


@pytest.mark.parametrize(
    "packed,groups", [(False, 1), (True, 1), (False, 10)],
    ids=["shared", "packed", "grouped"],
)
def test_pasm_matmul_compiles_ffn_width(one_chip, packed, groups):
    t = _tensor(one_chip, packed=packed, groups=groups)
    x = _sds((FFN_M, FFN_K), jnp.bfloat16, one_chip)
    hlo = _compile(lambda x, t: ops.pasm_matmul(x, t, interpret=False), x, t)
    assert hlo.count("tpu_custom_call") >= 1


def test_pas_matmul_compiles(one_chip):
    """The paper's two-phase PASM GEMM (one MXU pass per bin)."""
    t = _tensor(one_chip, packed=False, groups=1)
    x = _sds((FFN_M, FFN_K), jnp.float32, one_chip)
    b = _sds((FFN_N,), jnp.float32, one_chip)
    hlo = _compile(
        lambda x, t, b: ops.pas_matmul(x, t, bias=b, relu=True,
                                       interpret=False), x, t, b)
    assert hlo.count("tpu_custom_call") >= 1


@pytest.mark.parametrize("impl", ["auto", "pas_kernel_implicit"])
def test_alexnet_forward_compiles(one_chip, impl):
    """Full AlexNet (3×224×224, 96/256/384/384/256, 1000 classes) at batch
    8 with int4-packed dictionaries: every conv stage is one implicit-GEMM
    kernel with its max-pool fused."""
    from repro.configs.alexnet_conv import config
    from repro.models import cnn

    cfg = dataclasses.replace(config(), impl=impl, packed=True,
                              mesh_shape=None)
    params = jax.eval_shape(
        lambda k: cnn.quantize(cnn.init_params(cfg, k), cfg, iters=1),
        jax.random.PRNGKey(0),
    )
    x = _sds((8, *cfg.in_chw), jnp.float32, one_chip)
    hlo = _compile(
        lambda p, x: cnn.forward(p, x, cfg, interpret=False),
        _on(params, one_chip), x,
    )
    assert hlo.count("tpu_custom_call") >= len(cfg.layers)
    # every stage's kernel carries its stage name, not the jitted wrapper's
    kind = "pas" if impl == "pas_kernel_implicit" else "pasm"
    calls = [line.split(" = ", 1)[0].strip().lstrip("%")
             for line in hlo.splitlines() if "tpu_custom_call" in line
             and " = " in line]
    for i in range(1, len(cfg.layers) + 1):
        named = [c for c in calls if re.fullmatch(rf"conv{i}_{kind}(\.\d+)?", c)]
        assert len(named) == 1, (i, calls)
    assert not any(c.startswith("_conv_fwd_impl") for c in calls), calls
    # the relayout, pad and head ops carry their stage in the HLO metadata
    # (named scopes), so a compiled program or a profile with HLO protos
    # reads per stage; no op of the forward is left outside a scope
    scopes = {path.split("/")[0] for path in
              re.findall(r'op_name="jit\([^)]*\)/([^"]*)"', hlo)}
    assert scopes == {f"conv{i}" for i in range(1, len(cfg.layers) + 1)} | {
        "head"}, scopes


def test_slabbed_implicit_conv_compiles(one_chip):
    """AlexNet conv1 + its fused pool streamed as row-band slabs (the budget
    forces them): the slab-advancing image BlockSpec must compile too."""
    from repro.core import conv as cv

    conv = cv.Conv2D(k=11, c_in=3, c_out=96, stride=4, relu=True)
    budget = 2 * 1024 * 1024
    assert not cv._implicit_fits(conv, 224, 224, budget, pool=2)
    params = jax.eval_shape(
        lambda k: cv.ConvParams.quantize(
            jax.random.normal(k, (96, 3, 11, 11)), 16,
            bias=jnp.zeros((96,)), iters=1).pack(),
        jax.random.PRNGKey(0),
    )
    x = _sds((8, 3, 224, 224), jnp.float32, one_chip)
    hlo = _compile(
        lambda p, x: cv.conv2d(x, p, conv, engine="kernel_implicit",
                               interpret=False, vmem_budget=budget, pool=2,
                               pool_impl="fused"),
        _on(params, one_chip), x,
    )
    assert hlo.count("tpu_custom_call") == 1


@pytest.mark.parametrize("c_in,c_out,hw,pool,bn_conv,bk,n_slabs", [
    (512, 512, 28, 2, 512, 512, 2),  # VGG-16 conv10 (conv4_3 + pool)
    (256, 384, 14, 1, 384, 256, 1),  # AlexNet conv3
], ids=["vgg16_conv10", "alexnet_conv3"])
def test_wide_out_block_implicit_conv_compiles(one_chip, c_in, c_out, hw,
                                               pool, bn_conv, bk, n_slabs):
    """One assembled patch tile feeds every 128-lane chunk of a wide
    output-channel block: the chunked dequant, dot and epilogue must compile
    at the benchmark stages' real shapes, pooled and slabbed included."""
    from repro.core import conv as cv

    conv = cv.Conv2D(k=3, c_in=c_in, c_out=c_out, padding="same", relu=True)
    geom = cv.conv_geom(conv, hw, hw, pool)
    tp = ops.conv_tile_plan(geom, hw + 2, hw + 2, k=conv.K, n=c_out,
                            groups=1, bins=16, packed=True)
    assert (tp.bn_conv, tp.bk, tp.plan.n_slabs) == (bn_conv, bk, n_slabs)
    params = jax.eval_shape(
        lambda k: cv.ConvParams.quantize(
            jax.random.normal(k, (c_out, c_in, 3, 3)), 16,
            bias=jnp.zeros((c_out,)), iters=1).pack(),
        jax.random.PRNGKey(0),
    )
    x = _sds((8, c_in, hw, hw), jnp.float32, one_chip)
    hlo = _compile(
        lambda p, x: cv.conv2d(x, p, conv, engine="kernel_implicit",
                               interpret=False, pool=pool, pool_impl="fused"),
        _on(params, one_chip), x,
    )
    assert hlo.count("tpu_custom_call") == 1
