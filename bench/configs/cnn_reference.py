"""Plain reference for the CNN configurations, and the weights they share.

Nothing here imports the program under test.  ``weights`` draws a
configuration's weight-shared parameters from a key in the form a weight-
shared CNN stores them (per conv layer: bin indices, one ``bins``-entry f32
dictionary, an f32 bias; a dense f32 classifier head).  The benchmark hands
the same draw to the program, which packs it into its own layout, and to
``forward``, which computes the network straightforwardly:

    conv (lax.conv_general_dilated on the dequantized kernel) -> + bias ->
    ReLU -> max-pool (window = stride = pool, floor) -> ... -> flatten (NCHW)
    -> dense head

Every matmul and conv runs in float32 at ``HIGHEST`` precision, the precision
the configurations state.  ``precision="bf16x3"`` is the control: the same
network with each product computed as three bfloat16 passes (hi*hi + hi*lo +
lo*hi, the split XLA calls ``Precision.HIGH``), written out so that it
computes the same on every backend: each pass multiplies bfloat16 values,
which a float32 product at ``HIGHEST`` holds exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def out_size(n: int, k: int, stride: int, padding: str) -> int:
    """Output length of one spatial axis."""
    if padding == "same":
        return -(-n // stride)
    if padding == "valid" or (padding == "valid_centred" and k % 2):
        return (n - k) // stride + 1
    raise ValueError(f"padding {padding!r} with kernel {k} is not supported")


def stages(conf: dict) -> list:
    """Per conv stage: channels, kernel, stride, pool and spatial sizes."""
    C, H, W = conf["in_chw"]
    out = []
    for c, pool in zip(conf["convs"], conf["pools"], strict=True):
        k, s = c["k"], c["stride"]
        oh, ow = (out_size(H, k, s, conf["padding"]),
                  out_size(W, k, s, conf["padding"]))
        out.append(dict(c_in=C, c_out=c["c_out"], k=k, stride=s, pool=pool,
                        ih=H, iw=W, oh=oh, ow=ow, ph=oh // pool, pw=ow // pool))
        C, H, W = c["c_out"], oh // pool, ow // pool
    return out


def feature_size(conf: dict) -> int:
    st = stages(conf)[-1]
    return st["c_out"] * st["ph"] * st["pw"]


def weights(conf: dict, key: jax.Array) -> dict:
    """Seeded weight-shared parameters, drawn on the device.

    Indices are uniform over the bins; each dictionary is ``bins`` normal
    draws, centred and scaled to He variance ``2 / fan_in`` so activations
    keep their scale through the stack; biases are small normal draws.
    """
    bins = conf["bins"]
    convs = []
    for st in stages(conf):
        key, k_idx, k_cb, k_b = jax.random.split(key, 4)
        shape = (st["c_out"], st["c_in"], st["k"], st["k"])
        cb = jax.random.normal(k_cb, (bins,), jnp.float32)
        cb = (cb - cb.mean()) * (2.0 / (st["c_in"] * st["k"] ** 2)) ** 0.5
        convs.append({
            "idx": jax.random.randint(k_idx, shape, 0, bins).astype(jnp.uint8),
            "codebook": cb,
            "bias": 0.01 * jax.random.normal(k_b, (st["c_out"],), jnp.float32),
        })
    F = feature_size(conf)
    k_w, k_b = jax.random.split(key)
    head = {"w": jax.random.normal(k_w, (F, conf["classes"]), jnp.float32) * F ** -0.5,
            "b": 0.01 * jax.random.normal(k_b, (conf["classes"],), jnp.float32)}
    return {"conv": convs, "head": head}


def _bf16(x):
    """``x`` rounded to bfloat16 (to nearest, ties to even), kept in float32.
    Integer arithmetic, not a cast: a compiler that may keep excess precision
    is free to drop a float32 -> bfloat16 -> float32 round trip."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    b = b + jnp.uint32(0x7FFF) + ((b >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(b & jnp.uint32(0xFFFF0000), jnp.float32)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def _product(op, a, b, precision: str):
    if precision == "highest":
        return op(a, b)
    if precision != "bf16x3":
        raise ValueError(f"precision must be highest|bf16x3, got {precision!r}")
    (a_hi, a_lo), (b_hi, b_lo) = _split(a), _split(b)
    return op(a_hi, b_hi) + (op(a_hi, b_lo) + op(a_lo, b_hi))


def forward(conf: dict, w: dict, x: jax.Array, precision: str = "highest"):
    """``(B, C, H, W)`` images at the configuration's input size -> logits."""
    pad = "SAME" if conf["padding"] == "same" else "VALID"
    for st, p in zip(stages(conf), w["conv"]):
        kernel = p["codebook"][p["idx"].astype(jnp.int32)]
        conv = lambda a, b, s=st["stride"]: jax.lax.conv_general_dilated(
            a, b, (s, s), pad, dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=HIGHEST)
        x = jnp.maximum(_product(conv, x, kernel, precision)
                        + p["bias"][None, :, None, None], 0.0)
        if st["pool"] > 1:
            q = st["pool"]
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, q, q),
                                      (1, 1, q, q), "VALID")
    dot = lambda a, b: jnp.dot(a, b, precision=HIGHEST)
    return _product(dot, x.reshape(x.shape[0], -1), w["head"]["w"],
                    precision) + w["head"]["b"]
