"""The paper's own accelerator configuration (§4): one AlexNet-style conv
layer — 5×5 image, 15 channels, 3×3 kernel, 2 output channels, stride 1 —
with B ∈ {4, 8, 16} weight bins.  This is the faithful-reproduction target
for Figs 14–22; see benchmarks/ and tests/test_conv.py.

Beyond the single paper layer, :class:`CNNConfig` scales the same accelerator
to a full AlexNet-style conv stack (the network the paper's layer is drawn
from): per-stage geometry-free :class:`repro.core.conv.Conv2D` specs with one
PASM dictionary per conv layer and a dense classifier head, running on the
batched Pallas conv path (DESIGN.md §3).  The ``padding`` knob selects the
windowing stack-wide: the default ``valid_centred`` keeps the paper's
kernel-centred loop bounds; ``same`` reproduces torchvision-exact AlexNet/VGG
geometries.  ``layout`` picks NCHW (paper loop order) or NHWC (TPU-native,
channels-minor im2col), and ``packed`` int4-packs every conv dictionary.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

from typing import NamedTuple

from repro.core.conv import Conv2D


class PaperAccel(NamedTuple):
    """The paper's §4 accelerator dims (image geometry + layer shape).

    Image H/W live here — NOT in :class:`Conv2D` — because this names the
    paper's fixed evaluation configuration (Figs 14–22), where the 5×5 image
    is part of the spec.
    """

    IH: int = 5
    IW: int = 5
    C: int = 15
    KY: int = 3
    KX: int = 3
    M: int = 2
    stride: int = 1

    def conv(self, *, relu: bool = False, bias: bool = False) -> Conv2D:
        """The geometry-free layer spec (paper kernel-centred windowing)."""
        return Conv2D(
            k=(self.KY, self.KX), c_in=self.C, c_out=self.M,
            stride=self.stride, padding="valid_centred", layout="NCHW",
            bias=bias, relu=relu,
        )


PAPER_SPEC = PaperAccel()
PAPER_BINS = (4, 8, 16)
PAPER_BITWIDTHS = (8, 32)  # kernel bit-widths evaluated in the paper


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    """An AlexNet-family CNN on the weight-shared conv accelerator."""

    name: str
    in_chw: tuple  # (C, H, W) input images (C leads regardless of layout)
    layers: Sequence[Conv2D]  # per-stage specs (relu baked in; c_in chained)
    pools: Sequence[int]  # per-stage max-pool window == stride; 1 = none
    classes: int
    bins: int = 16  # PASM dictionary size, one dictionary per conv layer
    groups: int = 1  # reduction-axis codebook groups per layer (1 = paper rule)
    # auto | einsum | kernel | kernel_implicit | pas_kernel | pas_kernel_implicit
    impl: str = "kernel"
    padding: str = "valid_centred"  # stack-wide: valid_centred | valid | same
    layout: str = "NCHW"  # stack-wide: NCHW | NHWC
    packed: bool = False  # int4-pack the conv dictionaries at quantize time
    # image-block VMEM budget (bytes) for the auto engine's implicit-GEMM
    # preference; None = the core default (~6 MiB, a 16 MiB-VMEM TPU core)
    vmem_budget: Optional[int] = None
    # conv2d(pool_impl=) policy for the per-stage max-pools: "auto" fuses the
    # pool into the conv kernel epilogue where possible (one pallas_call per
    # conv/ReLU/pool stage), "unfused" keeps the separate reduce_window,
    # "fused" demands fusion (raises where impossible) — bit-exact either way
    pool_impl: str = "auto"
    # (n_data, n_model) for launch.mesh.make_conv_mesh — the mesh the stack
    # shards over (conv2d(mesh=), DESIGN.md §4.1); None = single device
    mesh_shape: Optional[tuple] = None
    family: str = "cnn"  # models/api dispatch key

    def __post_init__(self):
        if len(self.layers) != len(self.pools):
            raise ValueError(
                f"{self.name}: {len(self.layers)} conv layers but "
                f"{len(self.pools)} pool entries — the sequences are parallel"
            )
        c_in = self.in_chw[0]
        for i, conv in enumerate(self.layers):
            if conv.c_in != c_in:
                raise ValueError(
                    f"{self.name}: layer {i} expects c_in={conv.c_in} but the "
                    f"stack feeds it {c_in} channels"
                )
            c_in = conv.c_out


def _stack(c_in: int, *stages: tuple) -> tuple:
    """(c_out, k, stride) stages → chained Conv2D specs with ReLU."""
    layers = []
    for c_out, k, stride in stages:
        layers.append(Conv2D(k=k, c_in=c_in, c_out=c_out, stride=stride, relu=True))
        c_in = c_out
    return tuple(layers)


def config() -> CNNConfig:
    """Full AlexNet-style stack at the paper's ImageNet-scale layer sizes.

    ``mesh_shape`` pins the production single-pod mesh
    (:data:`repro.launch.mesh.SINGLE_POD`): batch over 16-way ``data``,
    output channels over 16-way ``model`` (96/256/384 all divide 16; the
    1000-class head falls back to replicated per the divisibility rule).
    """
    from repro.launch.mesh import SINGLE_POD

    return CNNConfig(
        name="alexnet",
        in_chw=(3, 224, 224),
        layers=_stack(
            3,
            (96, 11, 4),  # 224→54→27 (valid_centred; SAME: 224→56→28)
            (256, 5, 1),  # 27→23→11
            (384, 3, 1),  # 11→9
            (384, 3, 1),  # 9→7
            (256, 3, 1),  # 7→5→2
        ),
        pools=(2, 2, 1, 1, 2),
        classes=1000,
        mesh_shape=SINGLE_POD,
    )


def smoke_config() -> CNNConfig:
    """CIFAR-sized stack: same code path, CPU-testable in interpret mode."""
    return CNNConfig(
        name="alexnet-smoke",
        in_chw=(3, 32, 32),
        layers=_stack(
            3,
            (16, 3, 1),  # 32→30→15
            (32, 3, 1),  # 15→13→6
            (32, 3, 1),  # 6→4→2
        ),
        pools=(2, 2, 2),
        classes=10,
    )
