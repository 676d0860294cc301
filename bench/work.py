"""Work counts for the conv stages: the yardstick every kernel is read against.

A stage's work is counted the same way whatever engine runs it, from the
configuration's shapes alone:

- FLOPs: ``2·B·Ho·Wo·C·ky·kx·M``, the dense-equivalent multiply-adds.  The
  PAS engine's extra MXU passes per bin are not work.
- Bytes: the input map in f32, the int4-packed indices (one byte each when
  not packed), the f32 dictionary and bias, and the stage's pooled output in
  f32.

Peaks come from ``peaks.json``, keyed by the device kind JAX reports.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

from bench.configs.cnn_reference import feature_size, stages

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str, path: Path = PEAKS) -> dict:
    """The chip's peaks; a device kind that is not in the table is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def stage_flops(st: dict, batch: int) -> int:
    return 2 * batch * st["oh"] * st["ow"] * st["c_in"] * st["k"] ** 2 * st["c_out"]


def stage_bytes(st: dict, batch: int, conf: dict) -> int:
    n_weights = st["c_in"] * st["k"] ** 2 * st["c_out"]
    idx = math.ceil(n_weights / 2) if conf["packed"] else n_weights
    return (4 * batch * st["c_in"] * st["ih"] * st["iw"] + idx
            + 4 * conf["bins"] + 4 * st["c_out"]
            + 4 * batch * st["c_out"] * st["ph"] * st["pw"])


def conv_flops(conf: dict, batch: int = 1) -> int:
    return sum(stage_flops(st, batch) for st in stages(conf))


def image_flops(conf: dict) -> int:
    """Dense-equivalent FLOPs of one image: every conv stage plus the head."""
    return conv_flops(conf) + 2 * feature_size(conf) * conf["classes"]


def conv_ideal_s(conf: dict, batch: int, pk: dict) -> float:
    """Least time the chip could spend on one call's conv stages: per stage
    the larger of FLOPs over the bf16 peak and bytes over HBM bandwidth."""
    return sum(max(stage_flops(st, batch) / pk["bf16_flops_per_s"],
                   stage_bytes(st, batch, conf) / pk["hbm_bytes_per_s"])
               for st in stages(conf))


def conv_bounds(conf: dict, batch: int, pk: dict) -> list:
    """Per stage ``(flops_per_byte, "compute" | "memory")``."""
    ridge = pk["bf16_flops_per_s"] / pk["hbm_bytes_per_s"]
    out = []
    for st in stages(conf):
        ai = stage_flops(st, batch) / stage_bytes(st, batch, conf)
        out.append((ai, "compute" if ai >= ridge else "memory"))
    return out
