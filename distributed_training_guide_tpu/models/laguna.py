"""Laguna (poolside ``laguna``): a decoder whose attention layers differ in
REACH and in SHAPE. ``config.layer_types[l]`` says whether layer ``l`` attends
over every earlier position (``full_attention``) or over the last
``sliding_window`` positions, its own included (``sliding_attention``), and
``config.num_heads_per_layer[l]`` how many query heads it has (48 in a full
layer and 64 in a window layer as published, over the same 8 kv heads of 128
columns): ``wq``, ``wo`` and the gate of the two kinds are matrices of
different shapes, so no one scan carries the layers and they are WALKED, each
layer its own dict of leaves (``params["layers"][l]``), like
``models/lfm2.py`` and ``models/mimo_v2.py`` walk theirs.

Attention (:func:`attention_sublayer`): no bias, no QK-norm. A rope per kind:
a full layer turns the FIRST ``int(head_dim x partial_rotary_factor)`` columns
of every q and k head with YaRN frequencies (``rope_scaling``;
``ops/rope.py``), a window layer all of them with plain rope at its own base
(rotate-half inside the turned columns). Scores ``q k^T / sqrt(head_dim)``.
The output is GATED a head: ``g = sigmoid(u W_g)`` (``W_g [E, Hq]``, float32,
from the sublayer's normed input ``u``) multiplies head ``h``'s attention
output before ``W_o`` (``gating: true``). The window is a Python int per
layer, so on the chip a window layer's flash kernels walk the static band's
live tiles alone (``ops/flash_attention.py``).

FFN: ``config.mlp_layer_types[l]`` is ``dense`` (a SwiGLU of
``intermediate_size``, ``llama.mlp_sublayer``) or ``sparse``:
``models/moe._moe_ffn`` with the sigmoid router, weights ``routed_scaling_factor
x s / (sum of the chosen + 1e-20)``, a shared expert added as it is, ragged
dispatch and ``experts_held`` (one chip's share of an expert-parallel layer:
the router keeps its width, pairs of absent experts are left out). No
auxiliary loss is published (``router_aux_coef`` 0).

TRAINING is the path this family has (:func:`apply_with_aux`, the Trainer's
entry): each walked layer under its own ``jax.checkpoint`` with the Trainer's
policy, on ONE device (a held share computes a partial sum whose exchange no
plan has yet; ``train/step.py`` refuses it by name on a larger mesh). The
step's metrics carry what the routed layers counted (:data:`TRAIN_METRICS`).
Serving is refused whole (:data:`SERVE_REFUSES`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import llama
from .llama import _rmsnorm, mlp_sublayer
from .moe import _moe_ffn, experts_held, rows_walked
from ..ops.attention import multihead_attention
from ..ops.rope import apply_rope, freeze_rope_scaling

FULL, WINDOW = "full", "window"
_KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}
_PERIOD = ("full_attention",) + ("sliding_attention",) * 3

# what ServeEngine refuses for this family: every engine (the one-line
# reasons name the module that would have to change)
SERVE_REFUSES = {
    "serving": "serve/kv_pages.py's page classes take one query-head count "
               "and models/laguna.py exports no paged_decode_step (the "
               "head-wise output gate is not in any paged step)",
}

# the train step's extra metrics and how grad accumulation joins them over
# microbatches (train/step.py): int32 counts from _moe_ffn's return_counts,
# and the sorted rows the sparse layers' dispatches walked (moe.rows_walked:
# walked / held is about 2 while the held pairs fit the compact prefix, and
# a layer that overflowed it adds its whole k T)
TRAIN_METRICS = {"moe_pairs_routed": "sum", "moe_pairs_held": "sum",
                 "moe_fullest_expert_rows": "max", "moe_rows_walked": "sum"}


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    layer_types: tuple = _PERIOD * 10
    mlp_layer_types: tuple = ("dense",) + ("sparse",) * 39
    num_heads_per_layer: tuple = (48, 64, 64, 64) * 10
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    # the full layers' rope: YaRN over the turned columns
    rope_theta: float = 500000.0
    rope_scaling: Optional[tuple] = freeze_rope_scaling({
        "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672})
    partial_rotary_factor: float = 0.5
    # the window layers': plain rope on every column
    swa_rope_theta: float = 10000.0
    swa_partial_rotary_factor: float = 1.0
    intermediate_size: int = 8192             # the dense FFN's width
    moe_intermediate_size: int = 512          # every routed expert's width
    shared_expert_intermediate_size: int = 512
    num_experts: int = 256                    # the router's outputs
    experts_per_token: int = 8
    # (first, count): the routed experts whose weights this program holds
    # (None = all): one chip's share of an expert-parallel layer
    experts_held: Optional[tuple] = None
    router_act: str = "sigmoid"
    norm_topk_prob: bool = True
    norm_topk_eps: float = 1e-20              # weights = s / (sum + eps)
    routed_scaling_factor: float = 2.5
    moe_dispatch: str = "ragged"              # a held share is ragged only
    router_aux_coef: float = 0.0              # none is published
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        n = len(self.layer_types)
        for name in ("mlp_layer_types", "num_heads_per_layer"):
            if len(getattr(self, name)) != n:
                raise ValueError(f"{name} names {len(getattr(self, name))} "
                                 f"layers, layer_types {n}")
        bad = [t for t in self.layer_types if t not in _KINDS] + [
            t for t in self.mlp_layer_types if t not in ("dense", "sparse")]
        if bad:
            raise ValueError(f"unknown layer types {sorted(set(bad))}")
        if any(h % self.num_kv_heads for h in self.num_heads_per_layer):
            raise ValueError(f"query heads {self.num_heads_per_layer} are not "
                             f"multiples of {self.num_kv_heads} kv heads")
        if self.moe_dispatch != "ragged":
            raise ValueError("the laguna family routes through the ragged "
                             "dispatch (models/moe.py) alone")
        for kind in (FULL, WINDOW):
            rot = self.rotary_dims(kind)
            if rot % 2 or not 0 < rot <= self.head_dim:
                raise ValueError(f"rope on {rot} of {self.head_dim} columns")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_size(self) -> int:
        return self.head_dim

    def kind(self, l: int) -> str:
        return _KINDS[self.layer_types[l]]

    def is_dense(self, l: int) -> bool:
        return self.mlp_layer_types[l] == "dense"

    def rotary_dims(self, kind: str) -> int:
        factor = (self.swa_partial_rotary_factor if kind == WINDOW
                  else self.partial_rotary_factor)
        return int(self.head_dim * factor)

    def _sizes(self) -> dict:
        e, d, hkv = self.hidden_size, self.head_dim, self.num_kv_heads
        return {"attn": lambda hq: 2 * e * hq * d + 2 * e * hkv * d + e * hq,
                "dense": 3 * e * self.intermediate_size,
                "expert": 3 * e * self.moe_intermediate_size,
                "shared": 3 * e * self.shared_expert_intermediate_size,
                "router": e * self.num_experts}

    def _count(self, experts: int) -> int:
        s, e = self._sizes(), self.hidden_size
        total = self.vocab_size * e * (1 if self.tie_word_embeddings else 2) + e
        for l, hq in enumerate(self.num_heads_per_layer):
            total += 2 * e + s["attn"](hq)
            total += s["dense"] if self.is_dense(l) else (
                s["router"] + s["shared"] + experts * s["expert"])
        return total

    def num_params(self) -> int:
        """Parameters HELD (``experts_held`` experts a sparse layer)."""
        return self._count(experts_held(self)[1])

    def num_active_params(self) -> int:
        return self._count(self.experts_per_token)


def layer_shapes(config: LagunaConfig, l: int) -> dict:
    """Layer ``l``'s leaves: ``{group or leaf: shape or {leaf: shape}}``."""
    e, d, hkv = config.hidden_size, config.head_dim, config.num_kv_heads
    hq = config.num_heads_per_layer[l]
    shapes = {
        "attn_norm": (e,), "ffn_norm": (e,),
        "attn": {"wq": (e, hq * d), "wk": (e, hkv * d), "wv": (e, hkv * d),
                 "wo": (hq * d, e), "wg": (e, hq)},
    }
    if config.is_dense(l):
        f = config.intermediate_size
        shapes["mlp"] = {"gate": (e, f), "up": (e, f), "down": (f, e)}
    else:
        fe, fs = (config.moe_intermediate_size,
                  config.shared_expert_intermediate_size)
        held = experts_held(config)[1]
        shapes["moe"] = {
            "router": (e, config.num_experts),
            "gate": (held, e, fe), "up": (held, e, fe), "down": (held, fe, e),
            "shared_gate_proj": (e, fs), "shared_up": (e, fs),
            "shared_down": (fs, e)}
    return shapes


def init(config: LagunaConfig, rng: jax.Array) -> dict:
    e, v, pdt = config.hidden_size, config.vocab_size, config.param_dtype
    keys = iter(jax.random.split(rng, 4 + 16 * config.num_layers))

    def dense(shape, std=0.02):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(pdt)

    def leaf(name, shape):
        if name.endswith("_norm"):
            return jnp.ones(shape, pdt)
        return dense(shape)

    def layer(l):
        return {name: ({k: leaf(k, s) for k, s in shape.items()}
                       if isinstance(shape, dict) else leaf(name, shape))
                for name, shape in layer_shapes(config, l).items()}

    params = {"embed": {"embedding": dense((v, e))},
              "layers": [layer(l) for l in range(config.num_layers)],
              "final_norm": jnp.ones((e,), pdt)}
    if not config.tie_word_embeddings:
        params["lm_head"] = dense((e, v))
    return params


_AXES = {
    "attn_norm": ("embed_vector",), "ffn_norm": ("embed_vector",),
    "wq": ("embed", "heads"), "wk": ("embed", "kv"), "wv": ("embed", "kv"),
    "wo": ("heads", "embed"), "wg": ("embed", "heads_vector"),
    "router": ("embed", "experts_vector"),
    "shared_gate_proj": ("embed", "mlp"), "shared_up": ("embed", "mlp"),
    "shared_down": ("mlp", "embed"),
}
_MLP_AXES = {"gate": ("embed", "mlp"), "up": ("embed", "mlp"),
             "down": ("mlp", "embed")}
_EXPERT_AXES = {"gate": ("experts", "embed", "mlp"),
                "up": ("experts", "embed", "mlp"),
                "down": ("experts", "mlp", "embed")}


def param_logical_axes(config: LagunaConfig) -> dict:
    """Logical axes, a dict a layer like the leaves."""
    def group(name, shapes):
        table = {"mlp": _MLP_AXES, "moe": {**_AXES, **_EXPERT_AXES}}.get(
            name, _AXES)
        return {k: table[k] for k in shapes}

    def layer(l):
        return {name: (group(name, shape) if isinstance(shape, dict)
                       else _AXES[name])
                for name, shape in layer_shapes(config, l).items()}

    axes = {"embed": {"embedding": ("vocab", "embed")},
            "layers": [layer(l) for l in range(config.num_layers)],
            "final_norm": ("embed_vector",)}
    if not config.tie_word_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


@jax.named_scope("attn")
def attention_sublayer(config: LagunaConfig, x: jnp.ndarray, p: dict,
                       norm_scale, positions: jnp.ndarray, kind: str,
                       attn_impl: str = "auto",
                       standard_layout: bool = True) -> jnp.ndarray:
    """norm -> gated attention of one ``kind`` -> output projection (the
    caller adds the residual). x [B, S, E]; the query-head count is the
    leaves' own. Everything lies under the sub-scope ``attn_full`` or
    ``attn_window``, so the two kinds' device time can be read apart."""
    with (jax.named_scope("attn_window") if kind == WINDOW
          else jax.named_scope("attn_full")):
        b, s, _ = x.shape
        cdt, d = config.dtype, config.head_dim
        window = config.sliding_window if kind == WINDOW else None
        theta, scaling = ((config.swa_rope_theta, None) if kind == WINDOW
                          else (config.rope_theta, config.rope_scaling))
        rot = config.rotary_dims(kind)
        h = _rmsnorm(x, norm_scale, config.rms_norm_eps)
        q = (h @ p["wq"].astype(cdt)).reshape(b, s, -1, d)
        k = (h @ p["wk"].astype(cdt)).reshape(b, s, -1, d)
        v = (h @ p["wv"].astype(cdt)).reshape(b, s, -1, d)
        gate = jax.nn.sigmoid(h.astype(jnp.float32)
                              @ p["wg"].astype(jnp.float32))    # [B, S, Hq]

        def rope(t):    # the first `rot` columns turn, the rest pass through
            turned = apply_rope(t[..., :rot], positions, theta, scaling,
                                config.max_position_embeddings)
            if rot == d:
                return turned
            return jnp.concatenate([turned, t[..., rot:]], axis=-1)

        attn = multihead_attention(
            rope(q), rope(k), v, causal=True, positions=positions,
            kv_positions=positions, impl=attn_impl,
            standard_layout=standard_layout, window=window)
        attn = attn * gate[..., None].astype(cdt)
        return attn.reshape(b, s, -1) @ p["wo"].astype(cdt)


def _layer(config: LagunaConfig, l: int, attn_impl, standard_layout,
           x, p: dict, positions):
    """Layer ``l`` with both residuals -> ``(x, routing counts)``: int32
    ``[pairs routed, pairs held here, experts touched, the fullest expert's
    pairs]`` (``_moe_ffn``'s ``return_counts``; zeros for a dense layer)."""
    x = x + attention_sublayer(config, x, p["attn"], p["attn_norm"],
                               positions, config.kind(l), attn_impl,
                               standard_layout)
    if config.is_dense(l):
        y = mlp_sublayer(config, x, {"post_attn_norm": p["ffn_norm"],
                                     "mlp": p["mlp"]})
        return x + y, jnp.zeros((4,), jnp.int32)
    with jax.named_scope("experts"):   # the FFN's pre-norm is its own
        h = _rmsnorm(x, p["ffn_norm"], config.rms_norm_eps)
    y, _, _, counts = _moe_ffn(config, h, p["moe"], return_counts=True)
    return x + y, jax.lax.stop_gradient(counts)


embed_tokens = llama.embed_tokens
lm_head_logits = llama.lm_head_logits
final_hidden = llama.final_hidden
output_weights = llama.output_weights


def apply_with_aux(
    config: LagunaConfig,
    params: dict,
    input_ids: jnp.ndarray,
    positions: Optional[jnp.ndarray] = None,
    *,
    remat: bool = False,
    remat_policy: Optional[Any] = None,
    attn_impl: str = "auto",
    activation_sharding: Optional[Any] = None,
    return_metrics: bool = False,
    return_hidden: bool = False,
    moe_ep=None,
):
    """Forward -> (logits [B, S, V] fp32, aux loss (0: none is published)[,
    metrics]): ``models/moe.apply_with_aux``'s contract over walked layers.
    ``return_hidden`` swaps the logits for the final-normed hidden states
    (the chunked loss; pair with ``output_weights``). ``return_metrics``
    adds :data:`TRAIN_METRICS`. ``remat`` puts every layer under its own
    ``jax.checkpoint`` with ``remat_policy``."""
    if moe_ep is not None or callable(attn_impl):
        raise NotImplementedError(
            "models/laguna.py trains on one device: the sharded ragged "
            "exchange and the sharded attention wrappers are not threaded "
            "through its walked layers")
    standard_layout = positions is None
    if positions is None:
        positions = jnp.arange(input_ids.shape[1])[None, :]
    positions = jnp.broadcast_to(positions, input_ids.shape)
    x = embed_tokens(config, params, input_ids, positions)
    counts = []
    with jax.named_scope("layers"):
        for l, p in enumerate(params["layers"]):
            layer = functools.partial(_layer, config, l, attn_impl,
                                      standard_layout)
            if remat:
                layer = jax.checkpoint(
                    layer, policy=remat_policy
                    or jax.checkpoint_policies.nothing_saveable)
            x, n = layer(x, p, positions)
            if activation_sharding is not None:
                x = jax.lax.with_sharding_constraint(x, activation_sharding)
            if not config.is_dense(l):
                counts.append(n)
    out = (final_hidden(config, params, x) if return_hidden
           else lm_head_logits(config, params, x))
    aux = jnp.zeros((), jnp.float32)
    if not return_metrics:
        return out, aux
    counts = jnp.stack(counts) if counts else jnp.zeros((0, 4), jnp.int32)
    return out, aux, {
        "moe_pairs_routed": jnp.sum(counts[:, 0]),
        "moe_pairs_held": jnp.sum(counts[:, 1]),
        "moe_fullest_expert_rows": jnp.max(counts[:, 3], initial=0),
        "moe_rows_walked": jnp.sum(
            rows_walked(config, input_ids.size, counts[:, 1]))}


def apply(config, params, input_ids, positions=None, **kw):
    logits, _ = apply_with_aux(config, params, input_ids, positions, **kw)
    return logits


PRESETS = {
    # every kind of layer: dense + full, sparse + window (twice), sparse +
    # full; 6 and 4 query heads over 2 kv heads; YaRN on half of a head's
    # columns against plain rope on all; a window shorter than the tests'
    # sequences; 2 of 8 experts held (the second of 4 shares)
    "laguna-debug": LagunaConfig(
        vocab_size=512, hidden_size=64, layer_types=_PERIOD[:1] + _PERIOD[2:]
        + _PERIOD[:1], mlp_layer_types=("dense",) + ("sparse",) * 3,
        num_heads_per_layer=(4, 6, 6, 4), num_kv_heads=2, head_dim=16,
        sliding_window=12, intermediate_size=128, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, num_experts=8,
        experts_per_token=2, experts_held=(2, 2),
        rope_scaling=freeze_rope_scaling({
            "rope_type": "yarn", "factor": 4,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.1386294361119891}),
        max_position_embeddings=512),
    # poolside/Laguna-XS.2 config.json
    "laguna-xs.2": LagunaConfig(),
    # one of 8 chips that share each layer: experts 0-31, an eighth of the
    # vocabulary, published layers 0-4 (benchmarks/configs/)
    "laguna-xs.2-ep8-l5": LagunaConfig(
        vocab_size=12544, layer_types=(_PERIOD + _PERIOD[:1]),
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        num_heads_per_layer=(48, 64, 64, 64, 48), experts_held=(0, 32)),
}
