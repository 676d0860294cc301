"""Production mesh definitions (TPU v5e pods; host-device placeholders in CI).

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init).
"""
from __future__ import annotations

import jax

__all__ = [
    "make_production_mesh",
    "make_conv_mesh",
    "axis_sizes",
    "data_model_sizes",
    "n_shard_axis",
    "SINGLE_POD",
    "MULTI_POD",
]

SINGLE_POD = (16, 16)  # 256 chips
MULTI_POD = (2, 16, 16)  # 2 pods × 256 chips


def make_production_mesh(*, multi_pod: bool = False):
    import numpy as np

    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    return jax.make_mesh(
        shape,
        axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=jax.devices()[:n],
    )


def make_conv_mesh(shape=None):
    """The ``("data", "model")`` mesh the sharded conv stack runs on.

    ``shape=(n_data, n_model)`` must fit the visible devices; ``None`` puts
    every device on ``data`` (pure batch sharding).  The production AlexNet
    config pins :data:`SINGLE_POD` here (``CNNConfig.mesh_shape``); CI and
    the ``--devices N`` bench mode use host-platform fake devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    """
    import numpy as np

    if shape is None:
        shape = (len(jax.devices()), 1)
    shape = tuple(int(s) for s in shape)
    n = int(np.prod(shape))
    if n > len(jax.devices()):
        raise ValueError(
            f"mesh shape {shape} needs {n} devices but only "
            f"{len(jax.devices())} are visible"
        )
    # Auto axes: the conv dispatch shards through shard_map and leaves the
    # glue (batch pad/slice, head) to the partitioner — jax.make_mesh would
    # otherwise default to Explicit axes, whose sharding-in-types refuses
    # that glue's indexing
    return jax.make_mesh(
        shape, ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:n],
    )


def axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def data_model_sizes(mesh) -> tuple:
    """``(n_data, n_model)`` of a conv/GEMM mesh; absent ``model`` counts 1.

    The one definition every sharded-dispatch layer derives its axis sizes
    from (kernels/ops.py, core/conv.py, models/cnn.py)."""
    sizes = axis_sizes(mesh)
    if "data" not in sizes:
        raise ValueError(
            f"mesh needs a 'data' axis (got axes {mesh.axis_names}); build "
            "one with repro.launch.mesh.make_conv_mesh"
        )
    return int(sizes["data"]), int(sizes.get("model", 1))


def n_shard_axis(mesh, n: int):
    """The GEMM N-dimension's mesh axis: ``"model"`` when it divides, else
    ``None`` (replicate).

    THE divisibility rule of the sharded conv dispatch (DESIGN.md §4.1) —
    `models/sharding.py::conv_param_pspecs` applies the same test, so weight
    placement and compute can never disagree."""
    _, nm = data_model_sizes(mesh)
    return "model" if nm > 1 and n % nm == 0 else None
