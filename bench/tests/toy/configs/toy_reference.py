"""Plain reference of the toy family: one dense layer, ``x @ w``."""
import jax
import jax.numpy as jnp


def weights(conf, key):
    return {"w": jax.random.normal(key, (conf["width"], conf["classes"]), jnp.float32)}


def forward(conf, w, x, precision="highest"):
    if precision == "bf16":
        return jnp.dot(x.astype(jnp.bfloat16), w["w"].astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return jnp.dot(x, w["w"], precision=jax.lax.Precision.HIGHEST)
