"""Weights from ``--seed`` for the ``lfm2_moe`` family (gated short
convolutions and attention layers in one stack, dense and routed FFNs), by
``weights.py``'s counter hash.

The same contract as ``weights.py``: every element is a hash of (seed, leaf,
layer, element index), the same numbers alone, stacked or sliced. A layer is
numbered as the PUBLISHED model numbers it: layer ``i`` of a cut
configuration is published layer ``i + published_layer_offset`` (the cell
holds published layers 1-9), and an expert's matrices are a function of
(seed, leaf, published layer, expert id).

Matrices are uniform with mean 0 and ``weights.py``'s standard deviation 0.02
AT THE PUBLISHED HIDDEN WIDTH (2,048), which keeps a sublayer's output at the
size of its input; at another width (the tests' debug configuration) the
deviation is ``0.02 x sqrt(2048 / hidden_size)``, or every sublayer would
shrink to nothing beside the embedding and no fault in one would move a
logit. The convolution's taps are no matrix of that kind: three numbers a
channel that multiply ``g`` directly, drawn with deviation 0.5, so that the
operator's output is the size of the attention operator's, the taps' order
matters, and two thirds of a sequence's first outputs come from its state.

Consecutive layers differ in kind, and ``runners/serve.py`` jits
``layer_weights(cfg, key, l)`` with ``l`` traced, so a layer's leaf SHAPES
cannot depend on ``l``: ``layer_weights`` returns the leaves of BOTH operator
kinds and BOTH FFN kinds for every layer (2.8 GB of float32 at the published
widths, for an instant; the reference reads those of the layer's kind), while
``stacked_weights`` makes only what each layer has, stacked by kind, from the
same hash.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.weights import (DTYPES, MATRIX_STD, _draw, _uniform,  # noqa: F401
                                seed_key)

ATTENTION, CONV = "full_attention", "conv"
PUBLISHED_HIDDEN = 2048
TAPS_STD = 0.5
EXPERT_LEAVES = ("gate", "up", "down")
# the leaves of each kind, in the program's own stacking (runners/_lfm2_moe.py)
KINDS = {
    "norms": ("operator_norm", "ffn_norm"),
    "attn": ("wq", "wk", "wv", "wo", "q_norm", "k_norm"),
    "conv": ("w_in", "taps", "w_out"),
    "dense": ("dense_gate", "dense_up", "dense_down"),
    "moe": ("router", "router_bias"),
}


def matrix_std(cfg: dict, name: str = "") -> float:
    """The deviation of a matrix leaf's elements (module docstring)."""
    if name == "taps":
        return TAPS_STD
    return MATRIX_STD * (PUBLISHED_HIDDEN / cfg["hidden_size"]) ** 0.5


def _matrix(cfg, key, leaf, layer, shape, dtype, name=""):
    x = (_uniform(key, leaf, layer, shape) - 0.5) * (2.0 * 3.0 ** 0.5)
    return (matrix_std(cfg, name) * x).astype(dtype)


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) of one layer's leaves outside the routed
    experts, of every kind of layer, in draw order."""
    e, d = cfg["hidden_size"], head_dim(cfg)
    hq, hkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    f, taps = cfg["intermediate_size"], cfg["conv_L_cache"]
    return {
        "operator_norm": ((e,), "scale"), "ffn_norm": ((e,), "scale"),
        "wq": ((e, hq), "matrix"), "wk": ((e, hkv), "matrix"),
        "wv": ((e, hkv), "matrix"), "wo": ((hq, e), "matrix"),
        "q_norm": ((d,), "scale"), "k_norm": ((d,), "scale"),
        # [B | C | z] = u W_in; taps[:, j] multiplies g_{t - (L-1) + j}
        "w_in": ((e, 3 * e), "matrix"), "taps": ((e, taps), "matrix"),
        "w_out": ((e, e), "matrix"),
        "dense_gate": ((e, f), "matrix"), "dense_up": ((e, f), "matrix"),
        "dense_down": ((f, e), "matrix"),
        "router": ((e, cfg["num_experts"]), "matrix"),
        "router_bias": ((cfg["num_experts"],), "matrix"),
    }


def expert_shapes(cfg: dict) -> dict:
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {"gate": (e, f), "up": (e, f), "down": (f, e)}


def published_layer(cfg: dict, layer):
    """The published model's number of this configuration's layer ``layer``
    (which may be traced)."""
    return (jnp.asarray(layer, jnp.uint32)
            + jnp.uint32(cfg.get("published_layer_offset", 0)))


def expert_weights(cfg: dict, key, layer, expert, dtype=None) -> dict:
    """One routed expert's three matrices; ``layer`` and ``expert`` may be
    traced."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    salt = (published_layer(cfg, layer) * jnp.uint32(65536) + jnp.uint32(1)
            + jnp.asarray(expert, jnp.uint32))
    return {name: _matrix(cfg, key, 200 + i, salt, shape, dtype)
            for i, (name, shape) in enumerate(expert_shapes(cfg).items())}


def _leaves(cfg: dict, key, layer, names, dtype) -> dict:
    shapes = layer_shapes(cfg)
    order = list(shapes)
    layer = published_layer(cfg, layer)

    def draw(name):
        shape, kind = shapes[name]
        leaf = 100 + order.index(name)
        if kind == "matrix":
            return _matrix(cfg, key, leaf, layer, shape, dtype, name)
        return _draw(key, leaf, layer, shape, kind, dtype)

    return {name: draw(name) for name in names}


def _experts(cfg: dict, key, layer, dtype) -> dict:
    ids = jnp.arange(cfg["num_experts"], dtype=jnp.uint32)
    return jax.vmap(lambda ex: expert_weights(cfg, key, layer, ex, dtype))(ids)


def layer_weights(cfg: dict, key, layer, dtype=None) -> dict:
    """One layer's leaves of EVERY kind (module docstring), the routed
    experts stacked ``[experts, ...]``. ``layer`` may be traced."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    return {**_leaves(cfg, key, layer, list(layer_shapes(cfg)), dtype),
            **_experts(cfg, key, layer, dtype)}


def top_weights(cfg: dict, key, dtype=None) -> dict:
    """The embedding, which is the head too (tied), and the final norm."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": _matrix(cfg, key, 0, 0, (v, e), dtype),
            "final_norm": _draw(key, 1, 0, (e,), "scale", dtype)}


def layers_of(cfg: dict) -> dict:
    """kind -> this configuration's layers that have leaves of that kind."""
    n = cfg["num_hidden_layers"]
    types, dense = cfg["layer_types"], cfg["num_dense_layers"]
    return {
        "norms": list(range(n)),
        "attn": [l for l in range(n) if types[l] == ATTENTION],
        "conv": [l for l in range(n) if types[l] == CONV],
        "dense": list(range(dense)), "moe": list(range(dense, n)),
    }


def stacked_weights(cfg: dict, key, dtype=None) -> dict:
    """What the model HOLDS: ``{"top": {...}, kind: {leaf: [layers of that
    kind, ...]}}``, each layer's leaves the ones ``layer_weights`` gives it.
    Call it under one ``jax.jit`` so the weights are made on the device."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    out = {"top": top_weights(cfg, key, dtype)}
    for kind, layers in layers_of(cfg).items():
        ids = jnp.asarray(layers, jnp.uint32)

        def draw(l, kind=kind):
            leaves = _leaves(cfg, key, l, KINDS[kind], dtype)
            if kind == "moe":
                leaves.update(_experts(cfg, key, l, dtype))
            return leaves

        out[kind] = jax.vmap(draw)(ids)
    return out


def num_params(cfg: dict) -> int:
    """Parameters held (the tied embedding once)."""
    shapes = layer_shapes(cfg)
    size = {name: math.prod(shape) for name, (shape, _) in shapes.items()}
    expert = sum(math.prod(s) for s in expert_shapes(cfg).values())
    total = cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    for kind, layers in layers_of(cfg).items():
        per = sum(size[name] for name in KINDS[kind])
        if kind == "moe":
            per += cfg["num_experts"] * expert
        total += len(layers) * per
    return total
