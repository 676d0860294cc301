"""Implicit-GEMM convolution: no materialized patch matrix, bit-exact.

Covers the perf_opt acceptance criteria:

* ``engine="kernel_implicit"`` / ``"pas_kernel_implicit"`` are **bit-exact**
  against the explicit-im2col kernel paths for shared / packed / grouped
  params — same tile plan, same accumulation order — across paddings,
  layouts and strides, wherever the k-tiles are shallow (DESIGN.md §3.1:
  512-row k-tiles match to a few ulp).
* jaxpr inspection: between the input and the single ``pallas_call`` there is
  no XLA ``gather``, no ``conv_general_dilated``, and no reshape producing
  the ``(B·P, K)`` patch matrix (the explicit path HAS one — the assertion
  is meaningful).
* exact oracle vs ``jax.lax.conv_general_dilated`` on the
  dictionary-dereferenced kernel, VALID and SAME, NCHW and NHWC, stride > 1.
* ``auto`` prefers the implicit engine when the image tiles into VMEM and
  falls back to explicit above the budget.
* the custom VJP (explicit col2im backward) matches grads through the einsum
  reference.
* grouped codebooks ride every non-PAS engine (`ConvParams.quantize(groups=)`,
  the ROADMAP plumbing) and refuse the PAS ones.
* the new traffic models: implicit strictly below explicit on the AlexNet
  conv1 geometry, both tile-plan-aware (`ops.conv_hbm_bytes`) and analytic
  (`hwmodel.conv_hbm_traffic`).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import conv as cv
from repro.core import hwmodel as hw
from repro.kernels import ops


def _mk(conv: cv.Conv2D, bins=16, seed=0, batch=2, hw=(13, 11)):
    ih, iw = hw
    shape = (batch, ih, iw, conv.c_in) if conv.layout == "NHWC" \
        else (batch, conv.c_in, ih, iw)
    imgs = jax.random.normal(jax.random.PRNGKey(seed), shape)
    kern = jax.random.normal(
        jax.random.PRNGKey(seed + 1), (conv.c_out, conv.c_in, conv.ky, conv.kx)
    ) * conv.K ** -0.5
    bias = jnp.linspace(-0.5, 0.5, conv.c_out)
    return imgs, kern, bias


def _lax_conv(imgs, kern, conv: cv.Conv2D):
    if conv.layout == "NHWC":
        dn, k = ("NHWC", "HWIO", "NHWC"), kern.transpose(2, 3, 1, 0)
    else:
        dn, k = ("NCHW", "OIHW", "NCHW"), kern
    return jax.lax.conv_general_dilated(
        imgs, k, (conv.stride, conv.stride), conv.padding.upper(),
        dimension_numbers=dn,
    )


# ---------------------------------------------------------------------------
# bit-exactness vs the explicit-im2col kernel paths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
@pytest.mark.parametrize("padding", ["same", "valid"])
def test_implicit_bitexact_vs_explicit(padding, layout, stride):
    conv = cv.Conv2D(k=3, c_in=5, c_out=8, stride=stride, padding=padding,
                     layout=layout, relu=True)
    imgs, kern, bias = _mk(conv)
    shared = cv.ConvParams.quantize(kern, 16, bias=bias)
    want = cv.conv2d(imgs, shared, conv, engine="kernel", interpret=True)
    got = cv.conv2d(imgs, shared, conv, engine="kernel_implicit", interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bins", [8, 16])
def test_implicit_bitexact_packed_odd_k(bins):
    """int4-packed dictionaries with the §3 K-pad (odd K=45): the in-kernel
    zero mask pairs with the reserved zero bin exactly like the explicit
    path's zero patch columns (bins=16 exercises the bin-0 fallback)."""
    conv = cv.Conv2D(k=3, c_in=5, c_out=8, stride=1, padding="same", relu=True)
    imgs, kern, bias = _mk(conv, hw=(10, 10))
    packed = cv.ConvParams.quantize(kern, bins, bias=bias).pack()
    assert packed.pad_k == 1
    want = cv.conv2d(imgs, packed, conv, engine="kernel", interpret=True)
    got = cv.conv2d(imgs, packed, conv, engine="kernel_implicit", interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pas_implicit_bitexact_vs_explicit():
    conv = cv.Conv2D(k=3, c_in=6, c_out=8, stride=2, padding="same", relu=True)
    imgs, kern, bias = _mk(conv)
    shared = cv.ConvParams.quantize(kern, 8, bias=bias)
    want = cv.conv2d(imgs, shared, conv, engine="pas_kernel", interpret=True)
    got = cv.conv2d(imgs, shared, conv, engine="pas_kernel_implicit",
                    interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_implicit_vs_lax_oracle_alexnet_conv1_geometry():
    """Exact oracle: AlexNet conv1 geometry (k=11, s=4, SAME, NHWC) against
    lax.conv_general_dilated on the dictionary-dereferenced kernel."""
    conv = cv.Conv2D(k=11, c_in=3, c_out=16, stride=4, padding="same",
                     layout="NHWC", relu=True)
    imgs, kern, bias = _mk(conv, batch=1, hw=(56, 56))
    shared = cv.ConvParams.quantize(kern, 16, bias=bias)
    kern_q = shared.codebook[shared.idx.astype(jnp.int32)]
    want = jnp.maximum(_lax_conv(imgs, kern_q, conv) + bias, 0)
    for engine in ("kernel_implicit", "pas_kernel_implicit"):
        got = cv.conv2d(imgs, shared, conv, engine=engine, interpret=True)
        assert got.shape == want.shape == (1, 14, 14, 16)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4,
            err_msg=engine,
        )


def test_implicit_single_image_and_valid_centred():
    """3-D inputs and the paper's kernel-centred windowing route too."""
    conv = cv.Conv2D(k=(3, 2), c_in=4, c_out=8, stride=2)
    imgs, kern, bias = _mk(conv, hw=(9, 8))
    shared = cv.ConvParams.quantize(kern, 16, bias=bias)
    want = cv.conv2d(imgs[0], shared, conv, engine="kernel", interpret=True)
    got = cv.conv2d(imgs[0], shared, conv, engine="kernel_implicit",
                    interpret=True)
    assert got.ndim == 3
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# jaxpr inspection: the patch matrix must not exist
# ---------------------------------------------------------------------------


def _iter_eqns(jaxpr):
    """All eqns, recursing into sub-jaxprs EXCEPT the pallas kernel body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue  # in-kernel tile assembly is the point; don't count it
        for v in eqn.params.values():
            yield from _iter_sub(v)


def _iter_sub(v):
    if hasattr(v, "jaxpr"):
        yield from _iter_eqns(v.jaxpr)
    elif hasattr(v, "eqns"):
        yield from _iter_eqns(v)
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _iter_sub(x)


def _profile(fn, *args):
    eqns = list(_iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
    names = [e.primitive.name for e in eqns]
    cut = names.index("pallas_call")
    return names, eqns[:cut]


def _patch_reshapes(eqns, P, K):
    """Reshape eqns whose output is the (B·P, K(+pad)) patch matrix."""
    return [
        e for e in eqns
        if e.primitive.name == "reshape"
        and len(e.outvars[0].aval.shape) == 2
        and e.outvars[0].aval.shape[0] == P
        and e.outvars[0].aval.shape[1] >= K
    ]


@pytest.mark.parametrize("engine", ["kernel_implicit", "pas_kernel_implicit"])
def test_implicit_jaxpr_has_no_patch_matrix(engine):
    """Acceptance: between input and pallas_call the implicit path has no
    XLA gather, no conv_general_dilated, and no (B·P, K) reshape."""
    conv = cv.Conv2D(k=3, c_in=4, c_out=8, stride=1, padding="same", relu=True)
    imgs, kern, bias = _mk(conv, hw=(9, 9))
    shared = cv.ConvParams.quantize(kern, 16, bias=bias)
    P, K = 2 * 9 * 9, conv.K

    names, pre = _profile(
        lambda x: cv.conv2d(x, shared, conv, engine=engine, interpret=True), imgs
    )
    assert names.count("pallas_call") == 1, names
    pre_names = [e.primitive.name for e in pre]
    assert "gather" not in pre_names, pre_names
    assert "conv_general_dilated" not in pre_names, pre_names
    assert not _patch_reshapes(pre, P, K), "patch matrix materialized in HBM"

    # the explicit path DOES gather a (B·P, K) patch matrix first — the
    # assertions above are meaningful
    names_e, pre_e = _profile(
        lambda x: cv.conv2d(x, shared, conv, engine="kernel", interpret=True),
        imgs,
    )
    pre_e_names = [e.primitive.name for e in pre_e]
    assert "gather" in pre_e_names
    assert _patch_reshapes(pre_e, P, K)


def test_fused_pool_cnn_forward_one_pallas_call_per_stage():
    """PR 5 regression: with the fused pool config, every conv/ReLU/pool
    stage of ``cnn.forward`` lowers to exactly ONE pallas_call and no
    ``reduce_window`` appears between conv stages (the smoke stack pools
    every stage, including the odd 13×13 → 6×6 floor of layer 2); forcing
    ``pool_impl='unfused'`` restores one reduce_window per stage, so the
    assertion is meaningful."""
    import dataclasses as dc

    from repro.configs import get_cnn_config
    from repro.models import cnn

    cfg = dc.replace(get_cnn_config("alexnet", smoke=True),
                     impl="kernel_implicit")
    params = cnn.quantize(cnn.init_params(cfg, jax.random.PRNGKey(0)), cfg)
    imgs = jax.random.normal(jax.random.PRNGKey(1), (2, *cfg.in_chw))

    def names_of(c):
        return [e.primitive.name for e in _iter_eqns(jax.make_jaxpr(
            lambda x: cnn.forward(params, x, c, interpret=True))(imgs).jaxpr)]

    names = names_of(cfg)
    assert names.count("pallas_call") == len(cfg.layers), names
    assert not any("reduce_window" in n or "select_and" in n for n in names)
    names_u = names_of(dc.replace(cfg, pool_impl="unfused"))
    assert names_u.count("pallas_call") == len(cfg.layers)
    assert sum("reduce_window" in n for n in names_u) == len(cfg.layers)


@pytest.mark.parametrize("engine,family,staged", [
    ("kernel_implicit", "pasm_conv", "conv3_pasm"),
    ("pas_kernel_implicit", "pas_conv", "conv3_pas"),
    ("kernel", "pasm_matmul", "conv3_pasm_patch"),
    ("pas_kernel", "pas_matmul", "conv3_pas_patch"),
])
def test_conv2d_kernel_names(engine, family, staged):
    """A direct ``conv2d`` call names its kernel after the kernel family; a
    stage name (``name=``) replaces it with ``<stage>_<engine kind>``."""
    conv = cv.Conv2D(k=3, c_in=4, c_out=8, stride=1, relu=True)
    imgs, kern, bias = _mk(conv, hw=(9, 9))
    shared = cv.ConvParams.quantize(kern, 16, bias=bias)

    def kernel_names(**kw):
        jx = jax.make_jaxpr(lambda x: cv.conv2d(
            x, shared, conv, engine=engine, interpret=True, pool=2, **kw))(imgs)
        return [e.params["name"] for e in _iter_eqns(jx.jaxpr)
                if e.primitive.name == "pallas_call"]

    assert kernel_names() == [family]
    assert kernel_names(name="conv3") == [staged]


def test_auto_always_implicit_no_explicit_fallback(monkeypatch):
    conv = cv.Conv2D(k=3, c_in=4, c_out=8, stride=1, padding="same")
    imgs, kern, _ = _mk(conv, hw=(9, 9))
    shared = cv.ConvParams.quantize(kern, 16)
    assert cv._resolve_engine("auto", shared, False, conv, 9, 9) == "kernel_implicit"
    # single images keep the einsum reference port
    assert cv._resolve_engine("auto", shared, True, conv, 9, 9) == "einsum"
    # above the VMEM budget auto STAYS implicit — the image streams as
    # row-band slabs instead of falling back to explicit im2col
    monkeypatch.setattr(cv, "_IMPLICIT_VMEM_BUDGET", 4 * 9 * 9 * 4 - 1)
    assert cv._resolve_engine(
        "auto", shared, False, conv, 9, 9
    ) == "kernel_implicit"
    monkeypatch.undo()
    # degenerate geometry (no output pixels) keeps the explicit path, whose
    # empty patch matrix handles it
    big = cv.Conv2D(k=12, c_in=4, c_out=8, stride=1, padding="valid")
    assert cv._resolve_engine("auto", shared, False, big, 9, 9) == "kernel"
    # and auto-batched output equals the explicit engine regardless
    got = cv.conv2d(imgs, shared, conv, engine="auto", interpret=True)
    want = cv.conv2d(imgs, shared, conv, engine="kernel", interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_vmem_budget_knob_tunes_slabs():
    """conv2d(vmem_budget=)/CNNConfig.vmem_budget replace the hard-coded
    6 MiB budget: a tight budget now splits the image into row-band slabs
    (it no longer flips auto to the explicit engine) — outputs bit-exact
    either way."""
    import dataclasses as dc

    conv = cv.Conv2D(k=3, c_in=4, c_out=8, stride=1, padding="same")
    imgs, kern, _ = _mk(conv, hw=(9, 9))
    shared = cv.ConvParams.quantize(kern, 16)
    img_bytes = 4 * 11 * 11 * 4  # c_in · (9+SAME pad)² · f32
    tight, roomy = img_bytes - 1, None
    # the tight budget fails the whole-image residency check but auto stays
    # on the implicit engine
    assert not cv._implicit_fits(conv, 9, 9, tight, params=shared)
    assert cv._resolve_engine(
        "auto", shared, False, conv, 9, 9, tight
    ) == "kernel_implicit"
    got_t = cv.conv2d(imgs, shared, conv, engine="auto", interpret=True,
                      vmem_budget=tight)
    got_r = cv.conv2d(imgs, shared, conv, engine="auto", interpret=True,
                      vmem_budget=roomy)
    np.testing.assert_array_equal(np.asarray(got_t), np.asarray(got_r))
    # the CNNConfig knob threads through models/cnn.py forward (impl="auto")
    from repro.configs import get_cnn_config
    from repro.models import cnn

    cfg = dc.replace(get_cnn_config("alexnet", smoke=True), impl="auto")
    params = cnn.quantize(cnn.init_params(cfg, jax.random.PRNGKey(0)), cfg)
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, *cfg.in_chw))
    want = cnn.forward(params, xs, cfg, interpret=True)
    got = cnn.forward(
        params, xs, dc.replace(cfg, vmem_budget=70_000), interpret=True
    )  # slab-streams every layer that can split — same logits
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# slab-pipelined streaming (DESIGN.md §3.3)
# ---------------------------------------------------------------------------


def _slab_plan(conv: cv.Conv2D, params: cv.ConvParams, ih, iw, pool, budget,
               pas=False):
    """The plan conv2d's implicit path would build (mirrors _conv_fwd_impl),
    and the VMEM footprint of its whole-image alternative."""
    geom = cv.conv_geom(conv, ih, iw, pool)
    (pt, pb), (pl, pr) = geom.pad
    hp, wp = ih + pt + pb, iw + pl + pr
    t = params.gemm_tensor(conv.layout)
    _, bn, bk, _ = ops._pick_blocks(
        geom.P_rows, t.shape[0], conv.c_out,
        t.shape[0] // t.codebook.shape[0], t.packed)
    blocks = dict(bn=bn, bk=bk, bins=t.codebook.shape[1], packed=t.packed,
                  pas=pas, has_bias=True)
    whole = ops._plan_rows(geom, -(-wp // geom.phase), geom.ohp, pas)
    return (ops.conv_slab_plan(geom, hp, wp, vmem_budget=budget, **blocks),
            ops._plan_vmem_bytes(geom, whole, **blocks))


@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
@pytest.mark.parametrize("pas", [False, True])
def test_slab_bitexact_all_engines(layout, pas):
    """The seam matrix: a budget one byte under the whole-image footprint
    cuts the image into row-band slabs (bands cross pool-window and halo
    boundaries) — bit-exact vs the explicit engines for shared / packed /
    grouped params, with and without the fused pool.  assert_array_equal:
    the k-tile sequence is untouched, so slabbing must not change a bit."""
    conv = cv.Conv2D(k=3, c_in=4, c_out=8, stride=1, padding="same",
                     layout=layout)
    hw = (96, 16)
    imgs, kern, _ = _mk(conv, hw=hw)
    shared = cv.ConvParams.quantize(kern, 16)
    kinds = [shared, shared.pack(layout=layout),
             cv.ConvParams.quantize(kern, 16, groups=2, layout=layout)]
    if pas:
        kinds = kinds[:2]  # PAS engines refuse grouped codebooks
        imp_eng, exp_eng = "pas_kernel_implicit", "pas_kernel"
    else:
        imp_eng, exp_eng = "kernel_implicit", "kernel"
    for params in kinds:
        for pool in (1, 2):
            _, whole = _slab_plan(conv, params, *hw, pool, None, pas)
            plan, _ = _slab_plan(conv, params, *hw, pool, whole - 1, pas)
            assert plan.n_slabs > 1 and plan.halo_rows > 0  # seams exercised
            got = cv.conv2d(imgs, params, conv, engine=imp_eng,
                            interpret=True, vmem_budget=whole - 1,
                            pool=pool, pool_impl="fused")
            want = cv.conv2d(imgs, params, conv, engine=exp_eng,
                             interpret=True, pool=pool, pool_impl="fused")
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_implicit_fits_counts_all_blocks():
    """Pinned accounting: the budget must cover EVERY per-grid-step block
    (idx/bias/output double-buffered, the assembled patch tile, the
    accumulators) on top of the double-buffered phase-layout image — an
    image-only model would under-count by exactly the fixed-block term."""
    # the fixed-block model itself, pinned by hand:
    #   idx 2·64·128 + bias 2·8·128·4 + out 2·128·128·4 + tile 64·128·4
    base = dict(bmp=128, pool=1, bn=128, bk=64, bins=16)
    assert ops._conv_block_vmem_bytes(**base) == \
        16384 + 8192 + 131072 + 32768
    # packed halves the idx tile (32 rows: still one uint8 tile)
    assert ops._conv_block_vmem_bytes(**base, packed=True) == \
        8192 + 8192 + 131072 + 32768
    # fused pool: the tile spans pool² lane groups + pre-pool accumulator
    assert ops._conv_block_vmem_bytes(**{**base, "pool": 2}) == \
        16384 + 8192 + 131072 + 64 * 512 * 4 + 512 * 128 * 4
    # PAS: the (bins + 1, rows, bn) histogram scratch dominates
    assert ops._conv_block_vmem_bytes(**base, pas=True) == \
        16384 + 8192 + 131072 + 32768 + 17 * 128 * 128 * 4
    # ...and _implicit_fits sits exactly at image + fixed blocks:
    conv = cv.Conv2D(k=3, c_in=4, c_out=8, stride=1, padding="same")
    _, kern, bias = _mk(conv, hw=(9, 9))
    shared = cv.ConvParams.quantize(kern, 16, bias=bias)
    # SAME-padded 11×11, stride 1: one phase of pitch 11; 9·11 = 99 wide
    # pixels → one 128-lane block + a 128-lane halo; c_in 4 pads to 8 rows
    img = 2 * 8 * 256 * 4  # double-buffered
    fixed = ops._conv_block_vmem_bytes(bmp=128, pool=1, bn=128, bk=36,
                                       bins=16)
    assert cv._implicit_fits(conv, 9, 9, fixed + img, params=shared)
    assert not cv._implicit_fits(conv, 9, 9, fixed + img - 1, params=shared)
    # regression: a budget covering only the image is NOT enough
    assert not cv._implicit_fits(conv, 9, 9, img, params=shared)


def test_slab_streams_image_failing_default_fits():
    """THE acceptance shape: an image whose double-buffered residency blows
    the default 6 MiB budget (16·256·256·f32 ≈ 8.4 MiB doubled) — auto
    stays on the implicit engine, the planner splits it into slabs, the
    output matches the explicit oracle, and the modeled HBM bytes land
    strictly below explicit."""
    conv = cv.Conv2D(k=11, c_in=16, c_out=32, stride=8, padding="same")
    imgs, kern, _ = _mk(conv, batch=1, hw=(256, 256))
    shared = cv.ConvParams.quantize(kern, 16)
    # fails whole-image residency at the DEFAULT budget...
    assert not cv._implicit_fits(conv, 256, 256, params=shared)
    # ...yet auto does NOT fall back to explicit
    assert cv._resolve_engine(
        "auto", shared, False, conv, 256, 256) == "kernel_implicit"
    plan, _ = _slab_plan(conv, shared, 256, 256, 1, None)
    # 32 output rows in 3 balanced bands of 11 (the last one padded); each
    # band advances 11·8 image rows and re-reads the 11 − 8 = 3 overlap rows
    assert plan.n_slabs == 3 and plan.rows_out == 11
    assert (plan.band_rows, plan.halo_rows) == (88, 3)
    got = cv.conv2d(imgs, shared, conv, engine="auto", interpret=True)
    want = cv.conv2d(imgs, shared, conv, engine="kernel", interpret=True)
    # K = 1936 runs in 512-deep k-tiles.  The implicit kernel contracts its
    # (bk, pixels) tile over the leading axis, the explicit one its
    # (pixels, bk) tile over the trailing axis, and XLA:CPU sums a k-tile
    # this deep in another order for the two layouts (36-deep k-tiles, as
    # in the bitwise tests above, sum alike).  Measured gap: 2.6e-6 at
    # outputs up to 3.94, about 11 ulp; the tolerance is 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    t = shared.gemm_tensor("NCHW")
    geom = cv.conv_geom(conv, 256, 256)
    imp = ops.conv_hbm_bytes(t, geom, 1, 256, 256, implicit=True)
    exp = ops.conv_hbm_bytes(t, geom, 1, 256, 256, implicit=False)
    assert imp < exp


def test_slab_cnn_forward_fused_one_pallas_call_per_stage():
    """Slab-pipelined fused conv/ReLU/pool stays ONE pallas_call per stage
    with zero reduce_window through cnn.forward — slabbing reshapes the
    grid and operands, never the stage count."""
    import dataclasses as dc

    from repro.configs import get_cnn_config
    from repro.models import cnn

    budget = 60_000
    cfg = dc.replace(get_cnn_config("alexnet", smoke=True),
                     impl="kernel_implicit", vmem_budget=budget)
    # every stage fails whole-image residency at this budget → all slab
    assert not cv._implicit_fits(cfg.layers[0], 32, 32, budget,
                                 pool=cfg.pools[0])
    params = cnn.quantize(cnn.init_params(cfg, jax.random.PRNGKey(0)), cfg)
    imgs = jax.random.normal(jax.random.PRNGKey(1), (2, *cfg.in_chw))
    names = [e.primitive.name for e in _iter_eqns(jax.make_jaxpr(
        lambda x: cnn.forward(params, x, cfg, interpret=True))(imgs).jaxpr)]
    assert names.count("pallas_call") == len(cfg.layers), names
    assert not any("reduce_window" in n or "select_and" in n for n in names)


def test_conv_hbm_bytes_slab_bigimg():
    """The CI gate's bigimg numbers (512×512 conv1-style, k=11/s=4): the
    slab-aware implicit model charges each slab's phase images, halo rows
    included — pinned: 4 slabs of 32 output rows, each advancing 128 image
    rows and re-reading the 7-row overlap — and stays far below the
    explicit patch-matrix stream."""
    conv = cv.Conv2D(k=11, c_in=3, c_out=96, stride=4, relu=True)
    kern = jax.random.normal(jax.random.PRNGKey(0), (96, 3, 11, 11))
    shared = cv.ConvParams.quantize(kern, 16)
    plan, _ = _slab_plan(conv, shared, 512, 512, 1, None)
    assert plan.n_slabs == 4
    assert (plan.band_rows, plan.halo_rows) == (128, 7)
    t = shared.gemm_tensor("NCHW")
    geom = cv.conv_geom(conv, 512, 512)
    imp = ops.conv_hbm_bytes(t, geom, 1, 512, 512, implicit=True)
    exp = ops.conv_hbm_bytes(t, geom, 1, 512, 512, implicit=False)
    assert imp < exp and exp / imp > 4
    # the image term charges exactly the slabs' operand elements: the
    # whole-image plan streams fewer (no halo copies), same output blocks
    roomy = ops.conv_hbm_bytes(t, geom, 1, 512, 512, implicit=True,
                               vmem_budget=1 << 30)  # whole image resident
    whole, _ = _slab_plan(conv, shared, 512, 512, 1, 1 << 30)
    assert whole.n_slabs == 1
    assert (plan.n_slabs * plan.n_blocks * plan.bmp
            == whole.n_blocks * whole.bmp)
    assert imp - roomy == 4 * (plan.image_elems(geom) - whole.image_elems(geom))
    assert imp > roomy
    # the analytic model charges seam halos too: 512×512×3 doubled is
    # exactly 6 MiB, so shrink the budget to force the split — 2 slabs
    # re-fetch (n_slabs−1)·max(ky−stride, 0) = 7 rows
    ana_slab = hw.conv_hbm_traffic(IH=512, IW=512, C=3, KY=11, KX=11, M=96,
                                   stride=4, implicit=True,
                                   vmem_budget=4 << 20)
    ana_whole = hw.conv_hbm_traffic(IH=512, IW=512, C=3, KY=11, KX=11, M=96,
                                    stride=4, implicit=True,
                                    vmem_budget=1 << 30)
    assert ana_slab - ana_whole == 3 * 7 * 512 * 4
    assert ana_slab < hw.conv_hbm_traffic(IH=512, IW=512, C=3, KY=11, KX=11,
                                          M=96, stride=4, implicit=False)


# ---------------------------------------------------------------------------
# wide output-channel blocks: one assembled patch tile, every 128-lane chunk
# ---------------------------------------------------------------------------


def _kernel_outputs(imgs, params, conv, geom, tp, bns):
    """The raw implicit kernel output at each output block in ``bns``, on the
    operands and image plan ``_conv_fwd_impl`` builds for ``tp``."""
    from repro.kernels.pasm_matmul import pasm_conv_kernel_call, phase_slabs

    t = params.gemm_tensor(conv.layout)
    K, N = t.idx.shape[0] * (2 if t.packed else 1), t.idx.shape[1]
    idxp, cbp, _ = ops._pad_weight_operands(t.idx, t.codebook, ops.LANE,
                                            tp.gs_pad, t.packed)
    xs = phase_slabs(ops._pad_image(imgs, geom), geom, tp.plan)
    bias = jnp.pad(params.bias, (0, idxp.shape[1] - N)).reshape(1, -1)
    return [np.asarray(pasm_conv_kernel_call(
        xs, idxp, cbp, bias, geom=geom, plan=tp.plan, packed=t.packed,
        gs=K // t.codebook.shape[0], gs_pad=tp.gs_pad, bn=bn, bk=tp.bk,
        relu=True, interpret=True)) for bn in bns]


@pytest.mark.parametrize("c_out,pool,slabbed,packed", [
    (256, 1, False, False), (256, 2, True, True),
    (384, 2, False, True), (384, 1, True, False),
    (512, 1, False, True), (512, 2, True, False),
])
def test_wide_out_block_bitexact(c_out, pool, slabbed, packed):
    """The picker widens the output-channel block to the whole padded
    ``Np``, whole-image and slabbed (a 1-byte budget cuts the fewest rows
    a slab of one pixel block can hold at every block width, so no width
    adds pixel blocks; the wide rows make that one row or two): the kernel
    output equals the ``bn = 128`` kernel's on the same operands and plan,
    and ``conv2d`` equals the explicit engine, bit for bit."""
    conv = cv.Conv2D(k=3, c_in=4, c_out=c_out, padding="same", relu=True)
    ih, iw = (8, 126) if slabbed else (12, 12)
    imgs, kern, bias = _mk(conv, hw=(ih, iw))
    params = cv.ConvParams.quantize(kern, 16, bias=bias)
    if packed:
        params = params.pack()
    budget = 1 if slabbed else None
    geom = cv.conv_geom(conv, ih, iw, pool)
    tp = ops.conv_tile_plan(geom, ih + 2, iw + 2, k=conv.K, n=c_out,
                            groups=1, bins=16, packed=packed,
                            vmem_budget=budget)
    assert tp.bn_conv == c_out and tp.chunks_per_tile == c_out // 128
    assert (tp.plan.n_slabs > 1) == slabbed
    narrow, wide = _kernel_outputs(imgs, params, conv, geom, tp,
                                   (128, tp.bn_conv))
    np.testing.assert_array_equal(wide, narrow)
    got = cv.conv2d(imgs, params, conv, engine="kernel_implicit",
                    interpret=True, vmem_budget=budget, pool=pool,
                    pool_impl="fused")
    want = cv.conv2d(imgs, params, conv, engine="kernel", interpret=True,
                     pool=pool, pool_impl="fused")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _bench_stage_plans(name: str, batch: int = 8):
    """Per conv stage of a benchmark configuration (``bench/configs``), the
    picked :class:`ops.ConvTilePlan` and the pixel blocks at ``bn = 128``."""
    import json
    from pathlib import Path

    conf = json.loads((Path(__file__).resolve().parents[1] / "bench" /
                       "configs" / f"{name}.json").read_text())
    c, H, W = conf["in_chw"]
    out = []
    for st, pool in zip(conf["convs"], conf["pools"]):
        conv = cv.Conv2D(k=st["k"], c_in=c, c_out=st["c_out"],
                         stride=st["stride"], padding=conf["padding"],
                         layout=conf["layout"], relu=True)
        geom = cv.conv_geom(conv, H, W, pool)
        (pt, pb), (pl, pr) = geom.pad
        K = conv.K + (conv.K % 2 if conf["packed"] else 0)  # the §3 K-pad
        tp = ops.conv_tile_plan(geom, H + pt + pb, W + pl + pr, k=K,
                                n=conv.c_out, groups=1, bins=conf["bins"],
                                packed=conf["packed"], batch=batch)
        base = ops.conv_slab_plan(geom, H + pt + pb, W + pl + pr, bn=128,
                                  bk=tp.bk, bins=conf["bins"],
                                  packed=conf["packed"])
        out.append((tp, base.n_slabs * base.n_blocks))
        c = conv.c_out
        H, W = cv.conv_out_hw(H, W, conv)
        H, W = H // pool, W // pool
    return out


@pytest.mark.parametrize("name,table,before", [
    ("alexnet", [(128, 1, 56), (256, 2, 304), (384, 3, 72), (384, 3, 216),
                 (256, 2, 216)], 1960),
    ("vgg16", [(128, 1, 792), (128, 1, 4080), (128, 1, 1040), (128, 1, 1944),
               (256, 2, 504), (256, 2, 576), (128, 1, 1152), (512, 4, 144),
               (512, 4, 144), (512, 4, 144), (512, 4, 72), (512, 4, 72),
               (512, 4, 72)], 13760),
])
def test_conv_tile_plan_benchmark_stages(name, table, before):
    """Pinned per stage at batch 8: ``(bn_conv, chunks_per_tile,
    tiles_per_call)``.  No stage cuts more pixel blocks than at ``bn = 128``
    (VGG-16 conv7 stays at 128: 256 would take 3 slabs × 3 blocks, not
    2 × 4), and the calls' assemblies fall from ``before``, the count at
    ``bn = 128`` everywhere."""
    plans = _bench_stage_plans(name)
    assert [(tp.bn_conv, tp.chunks_per_tile, tp.tiles_per_call)
            for tp, _ in plans] == table
    for tp, base_blocks in plans:
        assert tp.plan.n_slabs * tp.plan.n_blocks <= base_blocks
    assert sum(tp.tiles_per_call * tp.chunks_per_tile
               for tp, _ in plans) == before


# ---------------------------------------------------------------------------
# custom VJP (explicit col2im backward)
# ---------------------------------------------------------------------------


def test_implicit_grad_matches_einsum_reference():
    conv = cv.Conv2D(k=3, c_in=5, c_out=8, stride=2, padding="same", relu=True)
    imgs, kern, bias = _mk(conv)
    shared = cv.ConvParams.quantize(kern, 16, bias=bias)

    def loss(x, cb, b, engine):
        p = cv.ConvParams.shared(shared.idx, cb, bias=b)
        return (cv.conv2d(x, p, conv, engine=engine, interpret=True) ** 2).sum()

    gi = jax.grad(loss, argnums=(0, 1, 2))(imgs, shared.codebook, bias,
                                           "kernel_implicit")
    ge = jax.grad(loss, argnums=(0, 1, 2))(imgs, shared.codebook, bias, "einsum")
    for a, b, name in zip(gi, ge, ("x", "codebook", "bias")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4, err_msg=name
        )


def test_implicit_grad_packed_no_epilogue():
    """The no-epilogue VJP variant, packed params (K-pad rows get no grad)."""
    conv = cv.Conv2D(k=3, c_in=3, c_out=8, stride=1)  # K=27 odd → pad_k=1
    imgs, kern, _ = _mk(conv, hw=(8, 8))
    packed = cv.ConvParams.quantize(kern, 8).pack()

    def loss(x, cb, engine):
        p = dataclasses.replace(packed, codebook=cb)
        return (cv.conv2d(x, p, conv, engine=engine, interpret=True) ** 2).sum()

    gi = jax.grad(loss, argnums=(0, 1))(imgs, packed.codebook, "kernel_implicit")
    ge = jax.grad(loss, argnums=(0, 1))(imgs, packed.codebook, "einsum")
    for a, b in zip(gi, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


# ---------------------------------------------------------------------------
# grouped codebooks through ConvParams.quantize (ROADMAP plumbing)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
def test_grouped_codebooks_all_engines(layout):
    conv = cv.Conv2D(k=3, c_in=4, c_out=8, stride=1, padding="same",
                     layout=layout, relu=True)
    imgs, kern, bias = _mk(conv, hw=(9, 9))
    g = cv.ConvParams.quantize(kern, 8, bias=bias, groups=3, layout=layout)
    assert g.groups == 3 and g.codebook.shape == (3, 8)
    want = cv.conv2d(imgs, g, conv, engine="einsum")
    for engine in ("kernel", "kernel_implicit"):
        got = cv.conv2d(imgs, g, conv, engine=engine, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4,
            err_msg=engine,
        )
    # grouped quantization with more dictionaries reconstructs no worse
    g1 = cv.ConvParams.quantize(kern, 8, bias=bias)
    e1 = float(jnp.abs(g1.dense_operand(layout) - cv.ConvParams.dense(kern)
                       .dense_operand(layout)).mean())
    eg = float(jnp.abs(g.dense_operand(layout) - cv.ConvParams.dense(kern)
                       .dense_operand(layout)).mean())
    assert eg <= e1 * 1.05


def test_shared_normalizes_single_group_2d_codebook():
    """pasm.kmeans_codebook(groups=1) hands back a (1, B) codebook; shared()
    must treat it as the single-dictionary rule on every engine."""
    conv = cv.Conv2D(k=3, c_in=4, c_out=8, relu=True)
    imgs, kern, bias = _mk(conv, hw=(8, 8))
    flat = cv._flatten_kernel(kern, "ckk")
    from repro.core import pasm
    cb2, idxf = pasm.kmeans_codebook(flat, 8, groups=1)
    assert cb2.shape == (1, 8)
    p = cv.ConvParams.shared(
        cv._unflatten_kernel(idxf, "ckk", kern.shape), cb2, bias=bias
    )
    assert p.groups == 1 and p.codebook.shape == (8,)
    want = cv.conv2d(imgs, p, conv, engine="einsum")
    assert want.shape == (2, 8, 6, 6)
    got = cv.conv2d(imgs, p, conv, engine="kernel_implicit", interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-4)


def test_grouped_codebooks_validation():
    conv = cv.Conv2D(k=3, c_in=4, c_out=8)
    imgs, kern, _ = _mk(conv, hw=(6, 6))
    g = cv.ConvParams.quantize(kern, 8, groups=2, layout="NCHW")
    with pytest.raises(ValueError, match="re-quantize"):
        g.gemm_tensor("NHWC")  # group membership is order-dependent
    with pytest.raises(ValueError, match="single-dictionary"):
        cv.conv2d(imgs, g, conv, engine="pas_kernel", interpret=True)
    with pytest.raises(ValueError, match="divisible"):
        cv.ConvParams.quantize(kern, 8, groups=5)
    with pytest.raises(ValueError, match="order="):
        cv.ConvParams.shared(g.idx, g.codebook)  # grouped needs an order
    with pytest.raises(ValueError, match="divisible"):  # K=36, 5 ∤ 36
        cv.ConvParams.shared(g.idx, jnp.zeros((5, 8)), order="ckk")
    # grouped + packed: even per-group reduction packs and agrees
    p = cv.ConvParams.quantize(kern, 16, groups=2, layout="NCHW").pack()
    assert p.kind == "packed" and p.groups == 2
    want = cv.conv2d(imgs, p, conv, engine="kernel", interpret=True)
    got = cv.conv2d(imgs, p, conv, engine="kernel_implicit", interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # odd per-group reduction cannot pack (nibbles would straddle groups)
    odd = cv.ConvParams.quantize(kern, 16, groups=4, layout="NCHW")  # gs=9
    with pytest.raises(ValueError, match="even per-group"):
        odd.pack()


# ---------------------------------------------------------------------------
# the traffic models: implicit strictly below explicit
# ---------------------------------------------------------------------------


def test_conv_hbm_bytes_implicit_below_explicit():
    """Tile-plan-aware model, AlexNet conv1 geometry (the CI gate's numbers):
    the explicit path pays ≈2× the padded patch matrix, the implicit path
    one stream of its phase-layout image — >4× total-traffic reduction at
    stride 4."""
    conv = cv.Conv2D(k=11, c_in=3, c_out=96, stride=4, relu=True)
    kern = jax.random.normal(jax.random.PRNGKey(0), (96, 3, 11, 11))
    t = cv.ConvParams.quantize(kern, 16).gemm_tensor("NCHW")
    geom = cv.conv_geom(conv, 224, 224)
    assert (geom.oh, geom.ow) == (54, 54)
    imp = ops.conv_hbm_bytes(t, geom, 1, 224, 224, implicit=True)
    exp = ops.conv_hbm_bytes(t, geom, 1, 224, 224, implicit=False)
    # pinned: explicit streams 2·Mp·Kp·4 = 2·2944·363·4 patch bytes and
    # stores 2944 rows; implicit streams 4² phases × 3 channels × 3200 lanes
    # (six 512-lane blocks of the pitch-56 wide rows + a 128-lane halo) and
    # stores 6·512 wide rows — weights and codebook are the same on both
    assert exp - imp == (2 * 2944 * 363 * 4 - 16 * 3 * 3200 * 4
                         + (2944 - 6 * 512) * 128 * 4)
    assert imp < exp and exp / imp > 4


def test_hwmodel_conv_traffic_analytic():
    """Plan-free analytic model: the activation terms differ by exactly the
    im2col inflation factor (≈7.6× for conv1), implicit < explicit."""
    geo = dict(IH=224, IW=224, C=3, KY=11, KX=11, M=96, stride=4)
    imp = hw.conv_hbm_traffic(**geo, implicit=True)
    exp = hw.conv_hbm_traffic(**geo, implicit=False)
    assert imp < exp
    assert hw.im2col_inflation(11, 11, 4) == pytest.approx(7.5625)
    # activation terms only: explicit = 2·P·K·4, implicit = image·4
    P, K = 54 * 54, 3 * 11 * 11
    assert exp - imp == 2 * P * K * 4 - 3 * 224 * 224 * 4
