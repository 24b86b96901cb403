"""Weights from ``--seed`` for the ``laguna`` family (full and window
attention layers of different query-head counts, a gated output, a leading
dense FFN, then routed experts of which this chip holds a share, and a shared
expert), by ``weights.py``'s counter hash.

The same contract as ``weights.py``: every element is a hash of (seed, leaf,
layer, element index). Layer ``i`` of a cut configuration is published layer
``i + published_layer_offset``, and an expert's matrices are a function of
(seed, leaf, published layer, PUBLISHED expert id): expert ``e`` of the full
model is expert ``e`` here.

The layers differ in SHAPE (48 against 64 query heads), so the model is a LIST
of per-layer dicts, ``{"top": {...}, "layers": [{leaf: array}, ...]}``, and
``layer_weights(cfg, key, l)`` takes a static ``l``.

Matrices are uniform with mean 0 and ``weights.py``'s standard deviation 0.02
AT THE PUBLISHED HIDDEN WIDTH (2,048); at another width (the tests' tiny
configuration) the deviation is ``0.02 x sqrt(2048 / hidden_size)``, or every
sublayer would shrink to nothing beside the embedding. The output gate ``wg``
is drawn with deviation 0.05 at the published width, LARGE ENOUGH TO MATTER:
the gate's logits ``u W_g`` then have deviation about 0.05 x sqrt(2048) = 2.3
(``u`` is a normed row), so ``g`` spreads over 0.1 .. 0.9 and a program that
leaves the gate out, or puts it elsewhere, fails the comparison. (ISSUE 42
wrote 0.5: logits of deviation 23, every gate 0 or 1 and its gradient nothing;
the departure is listed in the configuration file.)
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.weights import (DTYPES, MATRIX_STD, _draw, _uniform,  # noqa: F401
                                seed_key)

PUBLISHED_HIDDEN = 2048
GATE_STD = 0.05
EXPERT_LEAVES = ("gate", "up", "down")
# every leaf a layer can have, in the order that numbers them for the hash
LEAVES = ("attn_norm", "ffn_norm", "wq", "wk", "wv", "wo", "wg",
          "dense_gate", "dense_up", "dense_down", "router",
          "shared_gate", "shared_up", "shared_down")


def is_window(cfg: dict, l: int) -> bool:
    return cfg["layer_types"][l] == "sliding_attention"


def is_dense(cfg: dict, l: int) -> bool:
    return cfg["mlp_layer_types"][l] == "dense"


def router_experts(cfg: dict) -> int:
    """The router's outputs: the published expert count."""
    return cfg.get("router_experts", cfg["num_experts"])


def sparse_layers(cfg: dict) -> list:
    return [l for l in range(cfg["num_hidden_layers"]) if not is_dense(cfg, l)]


def matrix_std(cfg: dict, name: str) -> float:
    scale = (PUBLISHED_HIDDEN / cfg["hidden_size"]) ** 0.5
    return (GATE_STD if name == "wg" else MATRIX_STD) * scale


def layer_shapes(cfg: dict, l: int) -> dict:
    """name -> (shape, kind) of layer ``l``'s leaves outside the routed
    experts."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads_per_layer"][l], cfg["num_key_value_heads"]
    shapes = {
        "attn_norm": ((e,), "scale"), "ffn_norm": ((e,), "scale"),
        "wq": ((e, hq * d), "matrix"), "wk": ((e, hkv * d), "matrix"),
        "wv": ((e, hkv * d), "matrix"), "wo": ((hq * d, e), "matrix"),
        "wg": ((e, hq), "matrix"),
    }
    if is_dense(cfg, l):
        f = cfg["intermediate_size"]
        shapes.update(dense_gate=((e, f), "matrix"), dense_up=((e, f), "matrix"),
                      dense_down=((f, e), "matrix"))
    else:
        fs = cfg["shared_expert_intermediate_size"]
        shapes.update(router=((e, router_experts(cfg)), "matrix"),
                      shared_gate=((e, fs), "matrix"),
                      shared_up=((e, fs), "matrix"),
                      shared_down=((fs, e), "matrix"))
    return shapes


def expert_shapes(cfg: dict) -> dict:
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {"gate": (e, f), "up": (e, f), "down": (f, e)}


def _matrix(cfg, key, leaf, salt, shape, dtype, name=""):
    x = (_uniform(key, leaf, salt, shape) - 0.5) * (2.0 * 3.0 ** 0.5)
    return (matrix_std(cfg, name) * x).astype(dtype)


def published_layer(cfg: dict, l: int):
    return jnp.uint32(l + cfg.get("published_layer_offset", 0))


def expert_weights(cfg: dict, key, l: int, expert, dtype=None) -> dict:
    """One routed expert's three matrices by its PUBLISHED id (which may be
    traced)."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    salt = (published_layer(cfg, l) * jnp.uint32(65536) + jnp.uint32(1)
            + jnp.asarray(expert, jnp.uint32))
    return {name: _matrix(cfg, key, 200 + i, salt, shape, dtype)
            for i, (name, shape) in enumerate(expert_shapes(cfg).items())}


def held_experts(cfg: dict, key, l: int, dtype=None, first=None,
                 count=None) -> dict:
    """Experts ``first .. first + count`` (the configuration's held share
    unless given), stacked ``[count, ...]``."""
    first = cfg.get("experts_held_first", 0) if first is None else first
    count = cfg["num_experts"] if count is None else count
    ids = jnp.uint32(first) + jnp.arange(count, dtype=jnp.uint32)
    return jax.vmap(lambda ex: expert_weights(cfg, key, l, ex, dtype))(ids)


def layer_weights(cfg: dict, key, l: int, dtype=None) -> dict:
    """Layer ``l``'s leaves (``l`` static), a sparse layer's held experts
    stacked ``[held, ...]`` under ``gate`` / ``up`` / ``down``."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    salt = published_layer(cfg, l)
    out = {}
    for name, (shape, kind) in layer_shapes(cfg, l).items():
        leaf = 100 + LEAVES.index(name)
        out[name] = (_matrix(cfg, key, leaf, salt, shape, dtype, name)
                     if kind == "matrix"
                     else _draw(key, leaf, salt, shape, kind, dtype))
    if not is_dense(cfg, l):
        out.update(held_experts(cfg, key, l, dtype))
    return out


def top_weights(cfg: dict, key, dtype=None) -> dict:
    """The embedding, the final norm and the untied head."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": _matrix(cfg, key, 0, 0, (v, e), dtype),
            "final_norm": _draw(key, 1, 0, (e,), "scale", dtype),
            "lm_head": _matrix(cfg, key, 2, 0, (v, e), dtype).T}


def model_weights(cfg: dict, key, dtype=None) -> dict:
    """What the model HOLDS: ``{"top": {...}, "layers": [layer 0's leaves,
    ...]}``. Call it under one ``jax.jit`` so the weights are made on the
    device."""
    return {"top": top_weights(cfg, key, dtype),
            "layers": [layer_weights(cfg, key, l, dtype)
                       for l in range(cfg["num_hidden_layers"])]}


def num_params(cfg: dict) -> int:
    """Parameters held (the experts this chip holds; embedding and head)."""
    expert = sum(math.prod(s) for s in expert_shapes(cfg).values())
    total = 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    for l in range(cfg["num_hidden_layers"]):
        total += sum(math.prod(s) for s, _ in layer_shapes(cfg, l).values())
        if not is_dense(cfg, l):
            total += cfg["num_experts"] * expert
    return total
