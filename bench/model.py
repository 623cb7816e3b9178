"""Weights of a configuration, made from the seed on the device."""
from __future__ import annotations

import numpy as np


def jax_key(seed: int):
    """A PRNG key from any whole-number seed (beyond 32 bits too)."""
    import jax
    return jax.random.PRNGKey(
        int(np.random.default_rng(seed).integers(0, 2 ** 31 - 1)))


def _init_fn(input_dim: int, hidden: int, classes: int, w: dict):
    import jax
    import jax.numpy as jnp

    def scaled(key, shape, max_abs):
        z = jax.random.normal(key, shape, jnp.float32)
        return z * (max_abs / jnp.max(jnp.abs(z)))

    @jax.jit
    def init(key):
        k = jax.random.split(key, 4)
        H3 = 3 * hidden
        return {
            "w_x": scaled(k[0], (input_dim, H3), w["w_x_max"]),
            "w_h": scaled(k[1], (hidden, H3), w["w_h_max"]),
            "b": w["b_std"] * jax.random.normal(k[2], (H3,), jnp.float32),
            "w_fc": scaled(k[3], (hidden, classes), w["w_fc_max"]),
            "b_fc": jnp.zeros((classes,), jnp.float32),
        }
    return init


def make_params(config: dict, seed: int) -> dict:
    """float32 weights as the program serves them, in one jitted call on
    the default device (the int8 session promotes them itself).

    Each weight tensor is normal, scaled so that its largest magnitude is
    the configuration's ``*_max``: the int8 path's per-tensor power-of-two
    scales, which the program compiles into its step, are then the same
    for every seed, and a new seed never compiles a new program."""
    w = {k: float(config["weights"][k])
         for k in ("w_x_max", "w_h_max", "w_fc_max", "b_std")}
    init = _init_fn(int(config["input_dim"]), int(config["hidden"]),
                    int(config["classes"]), w)
    return init(jax_key(seed))
