"""Weights from ``--seed`` for the ``solar_open2`` family (KDA linear-attention
layers and gated NoPE GQA layers in one stack, routed experts of which this
chip holds a share beside one shared expert), by ``weights.py``'s counter
hash.

The same contract as ``weights.py``: every element is a hash of (seed, leaf,
layer, element index), the same numbers alone, stacked or sliced. Layer ``i``
of a cut configuration is published layer ``i + published_layer_offset``, and
an expert's matrices are a function of (seed, leaf, published layer, PUBLISHED
expert id): expert ``e`` of the full model is expert ``e`` here.

Matrices are uniform with mean 0 and ``weights.py``'s standard deviation 0.02
AT THE PUBLISHED HIDDEN WIDTH (4,096); at another width (the tests' debug
configuration) the deviation is ``0.02 x sqrt(4096 / hidden_size)``. Leaves
that are no matrices of that kind are drawn LARGE ENOUGH TO MATTER, so that a
program that leaves one out, or gets one wrong, fails the comparison:

- the convolutions' taps, four numbers a channel that multiply the stream
  directly: deviation 0.5 (as ``weights_lfm2_moe.py``'s);
- the two output gates' last matrices (``gqa_wg``, ``kda_w_gb``): deviation
  0.05, so the gates' logits have deviation about 3 and 0.7 and the sigmoids
  neither sit at a half nor saturate (``weights_laguna.py`` found 0.5 too
  much);
- ``kda_a_log = log(U(1, 16))`` a head and ``kda_dt_bias`` the inverse
  softplus of a log-uniform ``dt`` in [0.001, 0.1] a channel (KDA's published
  start): ``alpha = exp(-A softplus(. + dt_bias))`` then lies in 0.2-0.999 a
  step, so a state neither dies in one step nor never decays, and what a
  sequence wrote a thousand tokens ago still moves its logits;
- the router's choice bias: deviation 0.02 beside sigmoid scores within a few
  hundredths of 0.5 (as ``weights_mla_moe.py``'s).

The two mixer kinds have leaves of different shapes, and ``runners/serve.py``
jits ``layer_weights(cfg, key, l)`` with ``l`` traced, so a layer's leaf
SHAPES cannot depend on ``l``: ``layer_weights`` returns the leaves of BOTH
kinds for every layer (1.6 GB of float32 at the published widths, for an
instant; the reference reads those of the layer's kind), while
``stacked_weights`` makes only what each layer has, stacked by kind, from the
same hash.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.weights import (DTYPES, MATRIX_STD, _draw, _uniform,  # noqa: F401
                                seed_key)

GQA, KDA = "gqa", "kda"
PUBLISHED_HIDDEN = 4096
TAPS_STD = 0.5
GATE_STD = 0.05
RATE_RANGE = (1.0, 16.0)        # exp(A_log): a head's decay rate
DT_RANGE = (0.001, 0.1)         # softplus(dt_bias): a channel's step
EXPERT_LEAVES = ("gate", "up", "down")
# the leaves of each kind, in the program's own grouping (runners/_solar_open2.py)
KINDS = {
    "norms": ("mixer_norm", "ffn_norm"),
    GQA: ("gqa_wq", "gqa_wk", "gqa_wv", "gqa_wg", "gqa_wo"),
    KDA: ("kda_wq", "kda_wk", "kda_wv", "kda_conv_q", "kda_conv_k",
          "kda_conv_v", "kda_w_fa", "kda_w_fb", "kda_a_log", "kda_dt_bias",
          "kda_w_beta", "kda_w_ga", "kda_w_gb", "kda_o_norm", "kda_wo"),
    "ffn": ("router", "router_bias", "shared_gate", "shared_up",
            "shared_down"),
}


def kind_of(cfg: dict, l: int) -> str:
    return GQA if l in cfg["gqa_layers"] else KDA


def router_experts(cfg: dict) -> int:
    """The router's outputs: the published expert count."""
    return cfg.get("router_experts", cfg["n_routed_experts"])


def kda_sizes(cfg: dict) -> tuple:
    """``(heads, head_dim, taps)`` of the KDA mixer."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def matrix_std(cfg: dict, name: str = "") -> float:
    """The deviation of a leaf's elements (module docstring)."""
    if name.startswith("kda_conv_"):
        return TAPS_STD
    if name == "router_bias":
        return MATRIX_STD
    scale = (PUBLISHED_HIDDEN / cfg["hidden_size"]) ** 0.5
    return (GATE_STD if name in ("gqa_wg", "kda_w_gb") else MATRIX_STD) * scale


def layer_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) of one layer's leaves outside the routed
    experts, of every kind of layer, in draw order."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    heads, dk, taps = kda_sizes(cfg)
    c = heads * dk
    fs = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    x = router_experts(cfg)
    return {
        "mixer_norm": ((e,), "scale"), "ffn_norm": ((e,), "scale"),
        "gqa_wq": ((e, hq), "matrix"), "gqa_wk": ((e, hkv), "matrix"),
        "gqa_wv": ((e, hkv), "matrix"), "gqa_wg": ((e, hq), "matrix"),
        "gqa_wo": ((hq, e), "matrix"),
        "kda_wq": ((e, c), "matrix"), "kda_wk": ((e, c), "matrix"),
        "kda_wv": ((e, c), "matrix"),
        # taps[:, j] multiplies the stream's row t - (taps - 1) + j
        "kda_conv_q": ((c, taps), "matrix"), "kda_conv_k": ((c, taps), "matrix"),
        "kda_conv_v": ((c, taps), "matrix"),
        "kda_w_fa": ((e, dk), "matrix"), "kda_w_fb": ((dk, c), "matrix"),
        "kda_a_log": ((heads,), "log_rate"), "kda_dt_bias": ((c,), "dt_bias"),
        "kda_w_beta": ((e, heads), "matrix"),
        "kda_w_ga": ((e, dk), "matrix"), "kda_w_gb": ((dk, c), "matrix"),
        "kda_o_norm": ((dk,), "scale"), "kda_wo": ((c, e), "matrix"),
        "router": ((e, x), "matrix"), "router_bias": ((x,), "matrix"),
        "shared_gate": ((e, fs), "matrix"), "shared_up": ((e, fs), "matrix"),
        "shared_down": ((fs, e), "matrix"),
    }


def expert_shapes(cfg: dict) -> dict:
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {"gate": (e, f), "up": (e, f), "down": (f, e)}


def published_layer(cfg: dict, layer):
    """The published model's number of this configuration's layer ``layer``
    (which may be traced)."""
    return (jnp.asarray(layer, jnp.uint32)
            + jnp.uint32(cfg.get("published_layer_offset", 0)))


def _leaf(cfg, key, leaf, layer, shape, kind, dtype, name=""):
    u = _uniform(key, leaf, layer, shape)
    if kind == "matrix":
        return (matrix_std(cfg, name) * (u - 0.5) * (2.0 * 3.0 ** 0.5)
                ).astype(dtype)
    if kind == "log_rate":      # log of a rate uniform in RATE_RANGE
        lo, hi = RATE_RANGE
        return jnp.log(lo + (hi - lo) * u).astype(dtype)
    if kind == "dt_bias":       # softplus^-1 of a step log-uniform in DT_RANGE
        lo, hi = (math.log(x) for x in DT_RANGE)
        dt = jnp.exp(lo + (hi - lo) * u)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return _draw(key, leaf, layer, shape, kind, dtype)


def expert_weights(cfg: dict, key, layer, expert, dtype=None) -> dict:
    """One routed expert's three matrices by its PUBLISHED id; ``layer`` and
    ``expert`` may be traced."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    salt = (published_layer(cfg, layer) * jnp.uint32(65536) + jnp.uint32(1)
            + jnp.asarray(expert, jnp.uint32))
    return {name: _leaf(cfg, key, 200 + i, salt, shape, "matrix", dtype)
            for i, (name, shape) in enumerate(expert_shapes(cfg).items())}


def _leaves(cfg: dict, key, layer, names, dtype) -> dict:
    shapes = layer_shapes(cfg)
    order = list(shapes)
    layer = published_layer(cfg, layer)
    return {name: _leaf(cfg, key, 100 + order.index(name), layer,
                        *shapes[name], dtype, name) for name in names}


def _experts(cfg: dict, key, layer, dtype) -> dict:
    """The experts this chip holds, stacked ``[held, ...]``."""
    ids = (jnp.uint32(cfg.get("experts_held_first", 0))
           + jnp.arange(cfg["n_routed_experts"], dtype=jnp.uint32))
    return jax.vmap(lambda ex: expert_weights(cfg, key, layer, ex, dtype))(ids)


def layer_weights(cfg: dict, key, layer, dtype=None) -> dict:
    """One layer's leaves of EVERY kind (module docstring), the held experts
    stacked ``[held, ...]``. ``layer`` may be traced."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    return {**_leaves(cfg, key, layer, list(layer_shapes(cfg)), dtype),
            **_experts(cfg, key, layer, dtype)}


def top_weights(cfg: dict, key, dtype=None) -> dict:
    """The embedding, the final norm and the untied head."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": _leaf(cfg, key, 0, 0, (v, e), "matrix", dtype),
            "final_norm": _draw(key, 1, 0, (e,), "scale", dtype),
            "lm_head": _leaf(cfg, key, 2, 0, (v, e), "matrix", dtype).T}


def layers_of(cfg: dict) -> dict:
    """kind -> this configuration's layers that have leaves of that kind."""
    n = cfg["num_hidden_layers"]
    return {
        "norms": list(range(n)), "ffn": list(range(n)),
        GQA: [l for l in range(n) if kind_of(cfg, l) == GQA],
        KDA: [l for l in range(n) if kind_of(cfg, l) == KDA],
    }


def stacked_weights(cfg: dict, key, dtype=None) -> dict:
    """What the model HOLDS: ``{"top": {...}, kind: {leaf: [layers of that
    kind, ...]}}`` (the held experts under ``"ffn"``), each layer's leaves
    the ones ``layer_weights`` gives it. Call it under one ``jax.jit`` so the
    weights are made on the device."""
    dtype = dtype or DTYPES[cfg["weights_dtype"]]
    out = {"top": top_weights(cfg, key, dtype)}
    for kind, layers in layers_of(cfg).items():
        ids = jnp.asarray(layers, jnp.uint32)

        def draw(l, kind=kind):
            leaves = _leaves(cfg, key, l, KINDS[kind], dtype)
            if kind == "ffn":
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
        per = sum(size[name] for name in KINDS[kind])
        if kind == "ffn":
            per += cfg["n_routed_experts"] * expert
        total += len(layers) * per
    return total
