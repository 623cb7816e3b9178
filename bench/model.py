"""Weights of a configuration, made from the seed on the device.

A configuration states a stack of ``layers`` recurrent layers (default
1), all of width ``hidden`` and all of one ``cell`` (default ``"gru"``,
the ΔGRU; ``"lstm"``, the ΔLSTM): the first reads the ``input_dim``
features, each layer above reads the hidden state of the one below, and
the FC head reads the top layer.  ``CELLS`` is the one table of what a
cell is: its gate count G and whether it carries a cell vector c beside
h.  The weights are one dict, as the program's session is handed them:
the first layer's ``w_x, w_h, b``, the head's ``w_fc, b_fc``, and layer
``l`` above the first (``l`` = 1, 2, ...) as ``w_x_<l>, w_h_<l>, b_<l>``;
every layer's are ``(·, G·H)``, gate-major (GRU r, u, c; LSTM i, f, g,
o).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


def jax_key(seed: int):
    """A PRNG key from any whole-number seed (beyond 32 bits too)."""
    import jax
    return jax.random.PRNGKey(
        int(np.random.default_rng(seed).integers(0, 2 ** 31 - 1)))


class Cell(NamedTuple):
    gates: int           # G: blocks of H in a layer's pre-activations
    carries_c: bool      # a second carried vector, the cell state c


CELLS = {"gru": Cell(gates=3, carries_c=False),
         "lstm": Cell(gates=4, carries_c=True)}


def layers(config: dict) -> int:
    """Depth of the configuration's recurrent stack."""
    return int(config.get("layers", 1))


def cell(config: dict) -> str:
    """The configuration's recurrent cell, a key of ``CELLS``."""
    name = config.get("cell", "gru")
    if name not in CELLS:
        raise ValueError(f"unknown cell {name!r}; known: {sorted(CELLS)}")
    return name


def layer_keys(layer: int) -> tuple[str, str, str]:
    """The keys of layer ``layer``'s (0: the first) input weights,
    recurrent weights and bias."""
    suffix = f"_{layer}" if layer else ""
    return ("w_x" + suffix, "w_h" + suffix, "b" + suffix)


def _init_fn(input_dim: int, hidden: int, classes: int, depth: int,
             n_gates: int, w: dict):
    import jax
    import jax.numpy as jnp

    def scaled(key, shape, max_abs):
        z = jax.random.normal(key, shape, jnp.float32)
        return z * (max_abs / jnp.max(jnp.abs(z)))

    @jax.jit
    def init(key):
        k = jax.random.split(key, 4)
        GH = n_gates * hidden
        params = {
            "w_x": scaled(k[0], (input_dim, GH), w["w_x_max"]),
            "w_h": scaled(k[1], (hidden, GH), w["w_h_max"]),
            "b": w["b_std"] * jax.random.normal(k[2], (GH,), jnp.float32),
            "w_fc": scaled(k[3], (hidden, classes), w["w_fc_max"]),
            "b_fc": jnp.zeros((classes,), jnp.float32),
        }
        # Layers above the first draw from keys of their own, after the
        # first layer's and the head's; their input is a hidden state, of
        # the recurrent weights' range.
        for layer in range(1, depth):
            kl = jax.random.split(jax.random.fold_in(key, layer), 3)
            w_x, w_h, b = layer_keys(layer)
            params[w_x] = scaled(kl[0], (hidden, GH), w["w_h_max"])
            params[w_h] = scaled(kl[1], (hidden, GH), w["w_h_max"])
            params[b] = w["b_std"] * jax.random.normal(kl[2], (GH,),
                                                       jnp.float32)
        return params
    return init


def make_params(config: dict, seed: int) -> dict:
    """float32 weights as the program serves them, every layer's in one
    jitted call on the default device (the int8 session promotes them
    itself).

    Each weight tensor is normal, scaled so that its largest magnitude is
    the configuration's ``*_max``: the int8 path's per-tensor power-of-two
    scales, which the program compiles into its step, are then the same
    for every seed, and a new seed never compiles a new program."""
    w = {k: float(config["weights"][k])
         for k in ("w_x_max", "w_h_max", "w_fc_max", "b_std")}
    init = _init_fn(int(config["input_dim"]), int(config["hidden"]),
                    int(config["classes"]), layers(config),
                    CELLS[cell(config)].gates, w)
    return init(jax_key(seed))
