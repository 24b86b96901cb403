"""Weights from ``--seed``: the one generator both sides use.

The benchmark makes the weights, never the program: the program under test is
handed them in its own tree (``runners/*`` map the names), and the plain
reference calls ``layer_weights`` / ``top_weights`` again for the same seed, a
layer at a time where the whole model does not fit. Matrices are
uniform with mean 0 and standard deviation 0.02; norm scales are 1 + 0.1 * a
uniform of standard deviation 1, so that a norm in the wrong place or left out
changes the result.

Every element is a hash of (seed, leaf, layer, element index): a leaf of one
layer is the same numbers whether it is drawn alone or as a row of the stacked
``[L, ...]`` array (``stacked_weights`` vmaps the same draw), on any backend
and under any sharding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MATRIX_STD = 0.02
NORM_JITTER = 0.1
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
_SQRT3 = 3.0 ** 0.5


def seed_key(seed: int) -> np.ndarray:
    """``--seed`` may exceed 32 signed bits: its low and high 32-bit words,
    as a ``uint32[2]`` array. Hand it to a jitted program as an OPERAND
    (``jax.jit(lambda key: ...)(seed_key(seed))``), never through a closure:
    a seed baked in as a constant compiles the program again for every seed."""
    seed = int(seed)
    return np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def _fmix32(x):
    """MurmurHash3's 32-bit finalizer: a bijection of uint32 that mixes well."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _uniform(key, leaf: int, layer, shape):
    """Uniform [0, 1) per element, a pure function of (seed, leaf, layer,
    element index): a counter-based generator, so the same numbers come out
    whether a leaf is drawn alone, as a row of a stacked array, on one chip or
    sharded over four, and it costs a dozen integer operations an element
    (``jax.random``'s threefry took 37 s for 4 B weights on the chip)."""
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for axis in reversed(range(len(shape))):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, axis) * jnp.uint32(stride)
        stride *= shape[axis]
    salt = _fmix32(jnp.uint32(key[0]) ^ _fmix32(
        jnp.uint32(key[1]) + jnp.uint32(0x9E3779B9) * jnp.uint32(leaf + 1)))
    salt = _fmix32(salt + jnp.uint32(0x85EBCA77) * jnp.asarray(layer, jnp.uint32))
    bits = _fmix32(_fmix32(idx ^ salt) + jnp.uint32(0x6A09E667))
    return (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def layer_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) of one decoder layer's leaves, in draw order."""
    e, f, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    hq = cfg["num_attention_heads"] * d
    hkv = cfg["num_key_value_heads"] * d
    shapes = {
        "wq": ((e, hq), "matrix"), "wk": ((e, hkv), "matrix"),
        "wv": ((e, hkv), "matrix"), "wo": ((hq, e), "matrix"),
        "gate": ((e, f), "matrix"), "up": ((e, f), "matrix"),
        "down": ((f, e), "matrix"),
    }
    if cfg["qk_norm"] == "per_head":
        shapes.update(q_norm=((d,), "scale"), k_norm=((d,), "scale"))
    elif cfg["qk_norm"] == "flat":
        shapes.update(q_norm=((hq,), "scale"), k_norm=((hkv,), "scale"))
    if cfg["wiring"] == "pre_norm":
        shapes.update(input_norm=((e,), "scale"),
                      post_attn_norm=((e,), "scale"))
    elif cfg["wiring"] == "post_norm":
        shapes.update(attn_out_norm=((e,), "scale"),
                      mlp_out_norm=((e,), "scale"))
    else:
        raise ValueError(f"unknown wiring {cfg['wiring']!r}")
    return shapes


def top_shapes(cfg: dict) -> dict:
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"embed": ((v, e), "matrix"), "final_norm": ((e,), "scale")}
    if not cfg["tie_word_embeddings"]:
        shapes["lm_head"] = ((e, v), "matrix")
    return shapes


def _draw(key, leaf, layer, shape, kind, dtype):
    x = (_uniform(key, leaf, layer, shape) - 0.5) * (2.0 * _SQRT3)  # std 1
    x = MATRIX_STD * x if kind == "matrix" else 1.0 + NORM_JITTER * x
    return x.astype(dtype)


def layer_weights(cfg: dict, key, layer, dtype=None) -> dict:
    """One layer's leaves. ``layer`` may be a traced index."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    return {name: _draw(key, 100 + i, layer, shape, kind, dtype)
            for i, (name, (shape, kind)) in enumerate(layer_shapes(cfg).items())}


def top_weights(cfg: dict, key, dtype=None) -> dict:
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    return {name: _draw(key, i, 0, shape, kind, dtype)
            for i, (name, (shape, kind)) in enumerate(top_shapes(cfg).items())}


def stacked_weights(cfg: dict, key, dtype=None) -> dict:
    """The whole model: ``{"top": {...}, "layers": {name: [L, ...]}}``.
    Call it under one ``jax.jit`` so the weights are made on the device."""
    n = cfg["num_hidden_layers"]
    layers = jax.vmap(lambda l: layer_weights(cfg, key, l, dtype))(
        jnp.arange(n, dtype=jnp.uint32))
    return {"top": top_weights(cfg, key, dtype), "layers": layers}


def num_params(cfg: dict) -> int:
    import math

    per_layer = sum(math.prod(s) for s, _ in layer_shapes(cfg).values())
    top = sum(math.prod(s) for s, _ in top_shapes(cfg).values())
    return top + cfg["num_hidden_layers"] * per_layer
