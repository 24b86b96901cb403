"""Weights from ``--seed`` for the ``mimo_v2`` family (full and window
attention layers in one stack, a leading dense FFN, routed experts of which
this chip holds a share), by ``weights.py``'s counter hash.

The same contract as ``weights.py``: every element is a hash of (seed, leaf,
layer, element index), the same numbers alone, stacked or sliced. Layer ``i``
of a cut configuration is published layer ``i + published_layer_offset``, and
an expert's matrices are a function of (seed, leaf, published layer, PUBLISHED
expert id): expert ``e`` of the full model is expert ``e`` here.

Matrices are uniform with mean 0 and ``weights.py``'s standard deviation 0.02
AT THE PUBLISHED HIDDEN WIDTH (4,096); at another width (the tests' debug
configuration) the deviation is ``0.02 x sqrt(4096 / hidden_size)``, or every
sublayer would shrink to nothing beside the embedding and no fault in one
would move a logit. Two leaves are no matrices of that kind and are drawn
LARGE ENOUGH TO MATTER, so that a program that leaves either out fails the
comparison: the router's choice bias with deviation 0.02 beside sigmoid scores
that lie within a few hundredths of 0.5 (as ``weights_mla_moe.py``'s), and the
window layers' sink logits with deviation 1 beside scores of the same size
(a trained model's are learned).

The two attention kinds have leaves of different shapes (4 and 8 kv heads),
and ``runners/serve.py`` jits ``layer_weights(cfg, key, l)`` with ``l``
traced, so a layer's leaf SHAPES cannot depend on ``l``: ``layer_weights``
returns the leaves of BOTH attention kinds and BOTH FFN kinds for every layer
(3.3 GB of float32 at the published widths, for an instant; the reference
reads those of the layer's kind), while ``stacked_weights`` makes only what
each layer has, stacked by kind, from the same hash.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.weights import (DTYPES, MATRIX_STD, _draw, _uniform,  # noqa: F401
                                seed_key)

FULL, WINDOW = "full", "window"
PUBLISHED_HIDDEN = 4096
SINK_STD = 1.0
EXPERT_LEAVES = ("gate", "up", "down")
# the leaves of each kind, in the program's own stacking (runners/_mimo_v2.py)
KINDS = {
    "norms": ("attn_norm", "ffn_norm"),
    "attn_full": ("full_wq", "full_wk", "full_wv", "full_wo"),
    "attn_window": ("window_wq", "window_wk", "window_wv", "window_wo",
                    "window_sink"),
    "dense": ("dense_gate", "dense_up", "dense_down"),
    "moe": ("router", "router_bias"),
}


def kind_of(cfg: dict, l: int) -> str:
    return WINDOW if cfg["hybrid_layer_pattern"][l] else FULL


def kv_heads(cfg: dict, kind: str) -> int:
    return cfg["swa_num_key_value_heads" if kind == WINDOW
               else "num_key_value_heads"]


def has_sink(cfg: dict, kind: str) -> bool:
    return bool(cfg["add_swa_attention_sink_bias" if kind == WINDOW
                    else "add_full_attention_sink_bias"])


def router_experts(cfg: dict) -> int:
    """The router's outputs: the published expert count."""
    return cfg.get("router_experts", cfg["n_routed_experts"])


def matrix_std(cfg: dict, name: str = "") -> float:
    """The deviation of a leaf's elements (module docstring)."""
    if name.endswith("sink"):
        return SINK_STD
    if name == "router_bias":
        return MATRIX_STD
    return MATRIX_STD * (PUBLISHED_HIDDEN / cfg["hidden_size"]) ** 0.5


def _matrix(cfg, key, leaf, layer, shape, dtype, name=""):
    x = (_uniform(key, leaf, layer, shape) - 0.5) * (2.0 * 3.0 ** 0.5)
    return (matrix_std(cfg, name) * x).astype(dtype)


def layer_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) of one layer's leaves outside the routed
    experts, of every kind of layer, in draw order."""
    e, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    shapes = {"attn_norm": ((e,), "scale"), "ffn_norm": ((e,), "scale")}
    for kind in (FULL, WINDOW):
        hkv = kv_heads(cfg, kind)
        shapes.update({
            f"{kind}_wq": ((e, hq * dk), "matrix"),
            f"{kind}_wk": ((e, hkv * dk), "matrix"),
            f"{kind}_wv": ((e, hkv * dv), "matrix"),
            f"{kind}_wo": ((hq * dv, e), "matrix"),
            f"{kind}_sink": ((hq,), "matrix"),
        })
    f = cfg["intermediate_size"]
    shapes.update({
        "dense_gate": ((e, f), "matrix"), "dense_up": ((e, f), "matrix"),
        "dense_down": ((f, e), "matrix"),
        "router": ((e, router_experts(cfg)), "matrix"),
        "router_bias": ((router_experts(cfg),), "matrix"),
    })
    return shapes


def layer_leaves(cfg: dict) -> list:
    """The leaves ``layer_weights`` returns (a sink only where a kind has
    one)."""
    return [name for name in layer_shapes(cfg)
            if not name.endswith("_sink")
            or has_sink(cfg, name.split("_")[0])]


def expert_shapes(cfg: dict) -> dict:
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {"gate": (e, f), "up": (e, f), "down": (f, e)}


def published_layer(cfg: dict, layer):
    """The published model's number of this configuration's layer ``layer``
    (which may be traced)."""
    return (jnp.asarray(layer, jnp.uint32)
            + jnp.uint32(cfg.get("published_layer_offset", 0)))


def expert_weights(cfg: dict, key, layer, expert, dtype=None) -> dict:
    """One routed expert's three matrices by its PUBLISHED id; ``layer`` and
    ``expert`` may be traced."""
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
    """The experts this chip holds, stacked ``[held, ...]``."""
    ids = (jnp.uint32(cfg.get("experts_held_first", 0))
           + jnp.arange(cfg["n_routed_experts"], dtype=jnp.uint32))
    return jax.vmap(lambda ex: expert_weights(cfg, key, layer, ex, dtype))(ids)


def layer_weights(cfg: dict, key, layer, dtype=None) -> dict:
    """One layer's leaves of EVERY kind (module docstring), the held experts
    stacked ``[held, ...]``. ``layer`` may be traced."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    return {**_leaves(cfg, key, layer, layer_leaves(cfg), dtype),
            **_experts(cfg, key, layer, dtype)}


def top_weights(cfg: dict, key, dtype=None) -> dict:
    """The embedding, the final norm and the untied head."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": _matrix(cfg, key, 0, 0, (v, e), dtype),
            "final_norm": _draw(key, 1, 0, (e,), "scale", dtype),
            "lm_head": _matrix(cfg, key, 2, 0, (v, e), dtype).T}


def layers_of(cfg: dict) -> dict:
    """kind -> this configuration's layers that have leaves of that kind."""
    n = cfg["num_hidden_layers"]
    return {
        "norms": list(range(n)),
        "attn_full": [l for l in range(n) if kind_of(cfg, l) == FULL],
        "attn_window": [l for l in range(n) if kind_of(cfg, l) == WINDOW],
        "dense": [l for l in range(n) if not cfg["moe_layer_freq"][l]],
        "moe": [l for l in range(n) if cfg["moe_layer_freq"][l]],
    }


def kind_leaves(cfg: dict, kind: str) -> tuple:
    return tuple(name for name in KINDS[kind] if name in layer_leaves(cfg))


def stacked_weights(cfg: dict, key, dtype=None) -> dict:
    """What the model HOLDS: ``{"top": {...}, kind: {leaf: [layers of that
    kind, ...]}}``, each layer's leaves the ones ``layer_weights`` gives it.
    Call it under one ``jax.jit`` so the weights are made on the device."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    out = {"top": top_weights(cfg, key, dtype)}
    for kind, layers in layers_of(cfg).items():
        ids = jnp.asarray(layers, jnp.uint32)

        def draw(l, kind=kind):
            leaves = _leaves(cfg, key, l, kind_leaves(cfg, kind), dtype)
            if kind == "moe":
                leaves.update(_experts(cfg, key, l, dtype))
            return leaves

        out[kind] = jax.vmap(draw)(ids)
    return out


def num_params(cfg: dict) -> int:
    """Parameters held (the experts this chip holds; embedding and head)."""
    shapes = layer_shapes(cfg)
    size = {name: math.prod(shape) for name, (shape, _) in shapes.items()}
    expert = sum(math.prod(s) for s in expert_shapes(cfg).values())
    total = 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    for kind, layers in layers_of(cfg).items():
        per = sum(size[name] for name in kind_leaves(cfg, kind))
        if kind == "moe":
            per += cfg["n_routed_experts"] * expert
        total += len(layers) * per
    return total
