"""The work counts and peaks the kernels are read against."""
import dataclasses
import json

import numpy as np
import pytest

from bench import harness, work
from bench.configs import cnn_reference as ref


def conf(name):
    return harness.load_config(name)


def test_alexnet_conv_flops():
    # 2.396 GFLOP per image at 224x224 with SAME padding (maps
    # 56/28/14/14/14), conv2 the largest stage
    assert work.conv_flops(conf("alexnet")) == 2_395_803_648
    per_stage = [work.stage_flops(st, 1) for st in ref.stages(conf("alexnet"))]
    assert max(per_stage) == per_stage[1] == 963_379_200
    assert ref.feature_size(conf("alexnet")) == 256 * 7 * 7


def test_vgg16_conv_flops():
    # 30.69 GFLOP per image: 15.35 G multiply-adds (arXiv:1409.1556 geometry)
    assert work.conv_flops(conf("vgg16")) == 30_693_261_312


@pytest.mark.parametrize("name", ["alexnet", "vgg16"])
def test_count_is_the_same_under_every_engine(name):
    base = conf(name)
    pas = dict(base, impl="pas_kernel_implicit")
    pk = work.peak("TPU v5 lite")
    assert work.conv_flops(pas) == work.conv_flops(base)
    assert work.image_flops(pas) == work.image_flops(base)
    assert work.conv_ideal_s(pas, 8, pk) == work.conv_ideal_s(base, 8, pk)


def test_bytes_and_bound_of_one_stage():
    c = {"in_chw": [4, 10, 10], "padding": "valid", "packed": True, "bins": 16,
         "classes": 2, "convs": [{"c_out": 8, "k": 3, "stride": 1}], "pools": [2]}
    (st,) = ref.stages(c)
    assert (st["oh"], st["ph"]) == (8, 4)
    flops = 2 * 2 * 8 * 8 * 4 * 9 * 8
    nbytes = 4 * 2 * 4 * 100 + 144 + 64 + 32 + 4 * 2 * 8 * 16
    assert work.stage_flops(st, 2) == flops
    assert work.stage_bytes(st, 2, c) == nbytes
    pk = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert work.conv_ideal_s(c, 2, pk) == max(flops / 1e12, nbytes / 1e9)
    assert work.conv_bounds(c, 2, pk) == [(flops / nbytes, "memory")]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peak("TPU v99")


def test_peaks_table_names_its_source():
    table = json.loads(work.PEAKS.read_text())
    assert "Google Cloud" in table["source"]
    assert work.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert work.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("name", ["alexnet", "vgg16"])
def test_geometry_matches_the_program(name):
    from repro.core.conv import conv_out_hw
    from repro.models import cnn

    c = conf(name)
    cfg = harness.load_program(c["family"]).config(c)
    for st, (conv, pool) in zip(ref.stages(c), cnn.stages(cfg)):
        assert conv_out_hw(st["ih"], st["iw"], conv) == (st["oh"], st["ow"])
        assert pool == st["pool"]
    assert ref.feature_size(c) == int(np.prod(cnn.feature_shape(cfg)))


@pytest.mark.parametrize("name", ["alexnet", "vgg16"])
def test_cnn_family_config_is_the_program_config_it_was(name):
    """Every ``CNNConfig`` and ``Conv2D`` field is the configuration's, or
    the default where the configuration gives none."""
    c = conf(name)
    got = harness.load_program("cnn").config(c)
    chans = [c["in_chw"][0]] + [s["c_out"] for s in c["convs"]]
    assert [(l.k, l.c_in, l.c_out, l.stride, l.relu) for l in got.layers] == [
        ((s["k"], s["k"]), ci, s["c_out"], s["stride"], True)
        for s, ci in zip(c["convs"], chans)]
    for layer in got.layers:  # the rest of each stage keeps the default
        for f in dataclasses.fields(layer):
            if f.name not in {"k", "c_in", "c_out", "stride", "relu"}:
                assert getattr(layer, f.name) == f.default, f.name
    assert (got.name, got.in_chw, got.pools, got.mesh_shape) == (
        c["name"], tuple(c["in_chw"]), tuple(c["pools"]),
        tuple(c["mesh_shape"]) if c["mesh_shape"] else None)
    for k in ("classes", "bins", "impl", "padding", "layout", "packed"):
        assert getattr(got, k) == c[k], k
    rest = {"name", "in_chw", "layers", "pools", "mesh_shape", "classes", "bins",
            "impl", "padding", "layout", "packed"}
    for f in dataclasses.fields(got):  # what it never set keeps the default
        if f.name not in rest:
            assert getattr(got, f.name) == f.default, f.name
