"""Weights from ``--seed`` for the ``mla_moe`` family (latent attention,
routed experts with a shared one), by ``weights.py``'s counter hash.

The same contract as ``weights.py``: every element is a hash of (seed, leaf,
layer, element index), the same numbers alone, stacked or sliced. What a cut
configuration HOLDS is a slice of the published model: expert ``e`` here is
expert ``experts_held_first + e`` of the full layer (an expert's matrices are
a function of (seed, leaf, layer, expert id)), and vocabulary row ``i`` is
row ``i`` (the head is drawn as ``[V, E]`` rows and transposed). The router
keeps its published width (``router_experts``); its selection bias is drawn
like a matrix, so a router that leaves it out chooses other experts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.weights import DTYPES, _draw, seed_key  # noqa: F401

EXPERT_LEAVES = ("gate", "up", "down")


def dims(cfg: dict) -> dict:
    return {
        "e": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "ql": cfg["q_lora_rank"], "kl": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "f": cfg["moe_intermediate_size"],
        "fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        "held": cfg["n_routed_experts"], "routed": cfg["router_experts"],
        "first": cfg.get("experts_held_first", 0),
    }


def layer_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) of one layer's leaves outside the routed
    experts, in draw order."""
    d = dims(cfg)
    e, h = d["e"], d["h"]
    return {
        "wq_a": ((e, d["ql"]), "matrix"), "q_a_norm": ((d["ql"],), "scale"),
        "wq_b": ((d["ql"], h * (d["nope"] + d["rope"])), "matrix"),
        "wkv_a": ((e, d["kl"] + d["rope"]), "matrix"),
        "kv_a_norm": ((d["kl"],), "scale"),
        "wkv_b": ((d["kl"], h * (d["nope"] + d["v"])), "matrix"),
        "wo": ((h * d["v"], e), "matrix"),
        "input_norm": ((e,), "scale"), "post_attn_norm": ((e,), "scale"),
        "router": ((e, d["routed"]), "matrix"),
        "router_bias": ((d["routed"],), "matrix"),
        "shared_gate_proj": ((e, d["fs"]), "matrix"),
        "shared_up": ((e, d["fs"]), "matrix"),
        "shared_down": ((d["fs"], e), "matrix"),
    }


def expert_shapes(cfg: dict) -> dict:
    d = dims(cfg)
    return {"gate": (d["e"], d["f"]), "up": (d["e"], d["f"]),
            "down": (d["f"], d["e"])}


def expert_weights(cfg: dict, key, layer, expert, dtype=None) -> dict:
    """One routed expert's three matrices by its PUBLISHED id. ``layer`` and
    ``expert`` may be traced."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    salt = (jnp.asarray(layer, jnp.uint32) * jnp.uint32(65536)
            + jnp.uint32(1) + jnp.asarray(expert, jnp.uint32))
    return {name: _draw(key, 200 + i, salt, shape, "matrix", dtype)
            for i, (name, shape) in enumerate(expert_shapes(cfg).items())}


def layer_weights(cfg: dict, key, layer, dtype=None) -> dict:
    """One layer's leaves; the routed experts held, stacked ``[held, ...]``."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    d = dims(cfg)
    out = {name: _draw(key, 100 + i, layer, shape, kind, dtype)
           for i, (name, (shape, kind)) in enumerate(layer_shapes(cfg).items())}
    ids = d["first"] + jnp.arange(d["held"], dtype=jnp.uint32)
    out.update(jax.vmap(
        lambda ex: expert_weights(cfg, key, layer, ex, dtype))(ids))
    return out


def top_weights(cfg: dict, key, dtype=None) -> dict:
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": _draw(key, 0, 0, (v, e), "matrix", dtype),
            "final_norm": _draw(key, 1, 0, (e,), "scale", dtype),
            "lm_head": _draw(key, 2, 0, (v, e), "matrix", dtype).T}


def stacked_weights(cfg: dict, key, dtype=None) -> dict:
    """The whole held model: ``{"top": {...}, "layers": {name: [L, ...]}}``.
    Call it under one ``jax.jit`` so the weights are made on the device."""
    n = cfg["num_hidden_layers"]
    layers = jax.vmap(lambda l: layer_weights(cfg, key, l, dtype))(
        jnp.arange(n, dtype=jnp.uint32))
    return {"top": top_weights(cfg, key, dtype), "layers": layers}


def num_params(cfg: dict) -> int:
    import math

    per_layer = sum(math.prod(s) for s, _ in layer_shapes(cfg).values())
    per_layer += cfg["n_routed_experts"] * sum(
        math.prod(s) for s in expert_shapes(cfg).values())
    return (2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
            + cfg["num_hidden_layers"] * per_layer)
