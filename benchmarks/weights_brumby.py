"""Weights from ``--seed`` for the ``brumby`` family (Qwen3's block with power
retention for its mixer, an UNTIED head), by ``weights.py``'s counter hash.

The same contract as ``weights.py``: every element is a hash of (seed, leaf,
layer, element index), the same numbers alone, stacked or sliced.

Matrices are uniform with mean 0 and ``weights.py``'s standard deviation 0.02
AT THE PUBLISHED HIDDEN WIDTH (5,120); at another width (the tests' debug
configuration) the deviation is ``0.02 x sqrt(5120 / hidden_size)``, so that
the gate's pre-activation ``a = u W_g`` has the deviation 1.4 it has at the
published width (``0.02 x sqrt(5120)``) and ``gamma = sigmoid(a + 6.906768)``
lies in 0.98-0.9999 at any width: a memory of 50 to 10,000 tokens, so a gate
left out or a state that forgets moves the logits. Norm scales are ``1 + 0.1
x uniform`` (deviation 1), the per-head scales of ``q`` and ``k`` too.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.weights import (DTYPES, MATRIX_STD, NORM_JITTER,  # noqa: F401
                                _SQRT3, _uniform, seed_key)

PUBLISHED_HIDDEN = 5120


def matrix_std(cfg: dict) -> float:
    return MATRIX_STD * (PUBLISHED_HIDDEN / cfg["hidden_size"]) ** 0.5


def layer_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) of one layer's leaves, in draw order."""
    e, d, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "mixer_norm": ((e,), "scale"), "ffn_norm": ((e,), "scale"),
        "wq": ((e, hq * d), "matrix"), "wk": ((e, hkv * d), "matrix"),
        "wv": ((e, hkv * d), "matrix"),
        "wg": ((e, hkv), "matrix"),         # one gate a kv head, no bias
        "wo": ((hq * d, e), "matrix"),
        "q_norm": ((d,), "scale"), "k_norm": ((d,), "scale"),
        "gate": ((e, f), "matrix"), "up": ((e, f), "matrix"),
        "down": ((f, e), "matrix"),
    }


def top_shapes(cfg: dict) -> dict:
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": ((v, e), "matrix"), "final_norm": ((e,), "scale"),
            "lm_head": ((e, v), "matrix")}


def _draw(cfg, key, leaf, layer, shape, kind, dtype):
    x = (_uniform(key, leaf, layer, shape) - 0.5) * (2.0 * _SQRT3)  # std 1
    x = matrix_std(cfg) * x if kind == "matrix" else 1.0 + NORM_JITTER * x
    return x.astype(dtype)


def layer_weights(cfg: dict, key, layer, dtype=None) -> dict:
    """One layer's leaves. ``layer`` may be a traced index."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    layer = jnp.asarray(layer, jnp.uint32)
    return {name: _draw(cfg, key, 100 + i, layer, shape, kind, dtype)
            for i, (name, (shape, kind))
            in enumerate(layer_shapes(cfg).items())}


def top_weights(cfg: dict, key, dtype=None) -> dict:
    """The embedding, the final norm and the head (untied)."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    return {name: _draw(cfg, key, i, 0, shape, kind, dtype)
            for i, (name, (shape, kind))
            in enumerate(top_shapes(cfg).items())}


def stacked_weights(cfg: dict, key, dtype=None) -> dict:
    """What the model HOLDS: ``{"top": {...}, "layers": {leaf: [L, ...]}}``.
    Call it under one ``jax.jit`` so the weights are made on the device."""
    layers = jax.vmap(lambda l: layer_weights(cfg, key, l, dtype))(
        jnp.arange(cfg["num_hidden_layers"], dtype=jnp.uint32))
    return {"top": top_weights(cfg, key, dtype), "layers": layers}


def num_params(cfg: dict) -> int:
    """Every parameter, the embedding and the untied head each once."""
    per_layer = sum(math.prod(s) for s, _ in layer_shapes(cfg).values())
    top = sum(math.prod(s) for s, _ in top_shapes(cfg).values())
    return top + cfg["num_hidden_layers"] * per_layer
