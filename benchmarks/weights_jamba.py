"""Weights from ``--seed`` for the ``jamba`` family (Mamba-1 selective
state-space layers and NoPE attention layers in one stack, a dense SwiGLU in
every layer, a tied head), by ``weights.py``'s counter hash.

The same contract as ``weights.py``: every element is a hash of (seed, leaf,
layer, element index), the same numbers alone, stacked or sliced.

Matrices are uniform with mean 0 and ``weights.py``'s standard deviation 0.02
AT THE PUBLISHED HIDDEN WIDTH (2,560); at another width (the tests' debug
configuration) the deviation is ``0.02 x sqrt(2560 / hidden_size)``. Leaves
that are no matrices of that kind are drawn LARGE ENOUGH TO MATTER, so that a
program that leaves one out, or gets one wrong, fails the comparison:

- the convolution's taps, four numbers a channel that multiply ``x~``
  directly: deviation 0.5 (as ``weights_lfm2_moe.py``'s), its bias 0.02;
- ``mamba_w_dt`` uniform in ``+-R^-0.5`` (Mamba's own), so that ``Delta``
  moves with the token around ``b_dt``; ``B`` and ``C`` are of order one by
  their norms;
- ``mamba_a_log[c, n] = log(n + 1)``, ``mamba_d = 1`` and ``mamba_dt_bias``
  the inverse softplus of a log-uniform ``dt`` in [0.001, 0.1] a channel
  (Mamba's published start): a step's decay ``exp(Delta A)`` then lies in
  0.2-0.999, so a state neither dies in one step nor never decays, and what a
  sequence wrote a thousand tokens ago still moves its logits.

The two mixer kinds have leaves of different shapes, and ``runners/serve.py``
jits ``layer_weights(cfg, key, l)`` with ``l`` traced, so a layer's leaf
SHAPES cannot depend on ``l``: ``layer_weights`` returns the leaves of BOTH
kinds for every layer (the reference reads those of the layer's kind), while
``stacked_weights`` makes only what each layer has, stacked by kind, from the
same hash.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.weights import (DTYPES, MATRIX_STD, _draw, _uniform,  # noqa: F401
                                seed_key)

ATTENTION, MAMBA = "attn", "mamba"
PUBLISHED_HIDDEN = 2560
TAPS_STD = 0.5
DT_RANGE = (0.001, 0.1)         # softplus(dt_bias): a channel's step
# the leaves of each kind, in the program's own grouping (runners/_jamba.py)
KINDS = {
    "norms": ("mixer_norm", "ffn_norm"),
    ATTENTION: ("attn_wq", "attn_wk", "attn_wv", "attn_wo"),
    MAMBA: ("mamba_w_in", "mamba_conv", "mamba_conv_bias", "mamba_w_x",
            "mamba_dt_norm", "mamba_b_norm", "mamba_c_norm", "mamba_w_dt",
            "mamba_dt_bias", "mamba_a_log", "mamba_d", "mamba_w_out"),
    "ffn": ("gate", "up", "down"),
}


def is_attention(cfg: dict, l: int) -> bool:
    return l % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def kind_of(cfg: dict, l: int) -> str:
    return ATTENTION if is_attention(cfg, l) else MAMBA


def channels(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def matrix_std(cfg: dict, name: str = "") -> float:
    """The deviation of a leaf's elements (module docstring)."""
    if name == "mamba_conv":
        return TAPS_STD
    if name == "mamba_conv_bias":
        return MATRIX_STD
    return MATRIX_STD * (PUBLISHED_HIDDEN / cfg["hidden_size"]) ** 0.5


def layer_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) of one layer's leaves, of every kind of layer,
    in draw order."""
    e, d, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    c, n, r = channels(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    return {
        "mixer_norm": ((e,), "scale"), "ffn_norm": ((e,), "scale"),
        "attn_wq": ((e, hq), "matrix"), "attn_wk": ((e, hkv), "matrix"),
        "attn_wv": ((e, hkv), "matrix"), "attn_wo": ((hq, e), "matrix"),
        "mamba_w_in": ((e, 2 * c), "matrix"),       # x~ first, then z
        # conv[:, j] multiplies x~'s row t - (taps - 1) + j
        "mamba_conv": ((c, cfg["mamba_d_conv"]), "matrix"),
        "mamba_conv_bias": ((c,), "matrix"),
        "mamba_w_x": ((c, r + 2 * n), "matrix"),    # dt~, B, C in that order
        "mamba_dt_norm": ((r,), "scale"), "mamba_b_norm": ((n,), "scale"),
        "mamba_c_norm": ((n,), "scale"),
        "mamba_w_dt": ((r, c), "dt_proj"), "mamba_dt_bias": ((c,), "dt_bias"),
        "mamba_a_log": ((c, n), "a_log"), "mamba_d": ((c,), "ones"),
        "mamba_w_out": ((c, e), "matrix"),
        "gate": ((e, f), "matrix"), "up": ((e, f), "matrix"),
        "down": ((f, e), "matrix"),
    }


def _leaf(cfg, key, leaf, layer, shape, kind, dtype, name=""):
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "a_log":         # A = -(1 .. N) along the state axis
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)),
            shape).astype(dtype)
    u = _uniform(key, leaf, layer, shape)
    if kind == "matrix":
        return (matrix_std(cfg, name) * (u - 0.5) * (2.0 * 3.0 ** 0.5)
                ).astype(dtype)
    if kind == "dt_proj":       # uniform in +-R^-0.5
        return ((2.0 * u - 1.0) * shape[0] ** -0.5).astype(dtype)
    if kind == "dt_bias":       # softplus^-1 of a step log-uniform in DT_RANGE
        lo, hi = (math.log(x) for x in DT_RANGE)
        dt = jnp.exp(lo + (hi - lo) * u)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return _draw(key, leaf, layer, shape, kind, dtype)


def _leaves(cfg: dict, key, layer, names, dtype) -> dict:
    shapes = layer_shapes(cfg)
    order = list(shapes)
    layer = jnp.asarray(layer, jnp.uint32)
    return {name: _leaf(cfg, key, 100 + order.index(name), layer,
                        *shapes[name], dtype, name) for name in names}


def layer_weights(cfg: dict, key, layer, dtype=None) -> dict:
    """One layer's leaves of EVERY kind (module docstring). ``layer`` may be
    traced."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    return _leaves(cfg, key, layer, list(layer_shapes(cfg)), dtype)


def top_weights(cfg: dict, key, dtype=None) -> dict:
    """The embedding (also the head: tied) and the final norm."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": _leaf(cfg, key, 0, 0, (v, e), "matrix", dtype),
            "final_norm": _draw(key, 1, 0, (e,), "scale", dtype)}


def layers_of(cfg: dict) -> dict:
    """kind -> this configuration's layers that have leaves of that kind."""
    n = cfg["num_hidden_layers"]
    return {
        "norms": list(range(n)), "ffn": list(range(n)),
        ATTENTION: [l for l in range(n) if is_attention(cfg, l)],
        MAMBA: [l for l in range(n) if not is_attention(cfg, l)],
    }


def stacked_weights(cfg: dict, key, dtype=None) -> dict:
    """What the model HOLDS: ``{"top": {...}, kind: {leaf: [layers of that
    kind, ...]}}``, each layer's leaves the ones ``layer_weights`` gives it.
    Call it under one ``jax.jit`` so the weights are made on the device."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    out = {"top": top_weights(cfg, key, dtype)}
    for kind, layers in layers_of(cfg).items():
        ids = jnp.asarray(layers, jnp.uint32)
        out[kind] = jax.vmap(
            lambda l, kind=kind: _leaves(cfg, key, l, KINDS[kind], dtype))(ids)
    return out


def num_params(cfg: dict) -> int:
    """Every parameter, the tied embedding once."""
    size = {name: math.prod(shape)
            for name, (shape, _) in layer_shapes(cfg).items()}
    total = cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    for kind, layers in layers_of(cfg).items():
        total += len(layers) * sum(size[name] for name in KINDS[kind])
    return total
