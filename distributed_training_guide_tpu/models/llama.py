"""Llama-family decoder, TPU-first.

Capability parity with the reference's use of HF ``LlamaForCausalLM``
(``05-training-llama-405b/train_llm.py``, ``06-tensor-parallel/train_llm.py``)
but designed for XLA rather than translated from torch:

- parameters are a plain pytree with layers *stacked* on a leading axis and the
  forward is a ``lax.scan`` over layers — one compiled block body instead of L
  unrolled copies (compile time and HLO size stay flat as L grows to 126 for
  405B);
- every leaf carries *logical axis names* (``param_logical_axes``); the
  parallel layer maps logical axes -> mesh axes to produce NamedShardings, so
  DDP/FSDP/TP/2D are pure sharding-plan changes (the torch reference needs a
  different wrapper API per chapter);
- activation checkpointing is ``jax.checkpoint`` around the scanned block
  (reference C20, ``05:163-178``);
- attention dispatches to the Pallas flash kernel on TPU (reference uses the
  flash-attn CUDA wheel, ``05:93``).

Weights are kept 2-D ([in, out]) with fused head dims so TP shardings are a
single named axis on one dimension.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.attention import multihead_attention
from ..ops.collectives import psum as _psum
from ..ops.quantized_matmul import quantized_matmul, quantized_take
from ..ops.rope import apply_rope, freeze_rope_scaling


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 22
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: Optional[int] = None
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    # HF rope_scaling in frozen-tuple form (ops.rope.freeze_rope_scaling);
    # None = plain RoPE. All six HF rope types are supported (ops/rope.py)
    rope_scaling: Optional[tuple] = None
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    # sliding-window attention (Mistral/Qwen2/Phi-3 checkpoints): query i
    # attends keys with 0 <= i - j < window; None = full causal
    sliding_window: Optional[int] = None
    attn_bias: bool = False         # QKV projection biases (Qwen2-style)
    # RMSNorm on q/k pre-rope: False | True (per-head [head_dim], Qwen3) |
    # "flat" (full-width [heads*head_dim], applied before the head reshape,
    # OLMo-2)
    qk_norm: Any = False
    # OLMo-2 block wiring: NO pre-norms; RMSNorm applied to each sublayer's
    # OUTPUT before the residual add (x = x + norm(attn(x)))
    post_norm: bool = False
    # Gemma-2 block wiring: norms on BOTH sides of each sublayer
    # (x = x + norm(attn(norm(x))); x = x + norm(mlp(norm(x))))
    sandwich_norm: bool = False
    # Gemma-2 attention extras: tanh capping of attention scores / final
    # logits, score scale override (query_pre_attn_scalar ** -0.5), and the
    # per-layer window pattern (an L-tuple, 0 = full attention that layer —
    # Gemma-2 alternates sliding/full). All run on the xla attention path.
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    query_pre_attn_scalar: Optional[float] = None
    layer_windows: Optional[tuple] = None
    act_fn: str = "silu"            # MLP gate activation: silu | gelu_tanh (Gemma)
    norm_plus_one: bool = False     # RMSNorm scales by (1 + w) (Gemma)
    scale_embed: bool = False       # multiply embeddings by sqrt(hidden) (Gemma)
    dtype: Any = jnp.bfloat16       # activation/compute dtype
    param_dtype: Any = jnp.float32  # storage dtype

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def num_params(self) -> int:
        e, f, v = self.hidden_size, self.intermediate_size, self.vocab_size
        hq = self.num_heads * self.head_size
        hkv = self.num_kv_heads * self.head_size
        per_layer = e * hq + 2 * e * hkv + hq * e + 3 * e * f + 2 * e
        if self.attn_bias:
            per_layer += hq + 2 * hkv
        if self.qk_norm == "flat":
            per_layer += hq + hkv
        elif self.qk_norm:
            per_layer += 2 * self.head_size
        head = 0 if self.tie_word_embeddings else e * v
        return v * e + self.num_layers * per_layer + e + head


def init(config: LlamaConfig, rng: jax.Array) -> dict:
    """Random init (normal(0.02), zeros-free — matches HF from_config init scale)."""
    e, f, v, l = (config.hidden_size, config.intermediate_size,
                  config.vocab_size, config.num_layers)
    d = config.head_size
    hq, hkv = config.num_heads * d, config.num_kv_heads * d
    keys = iter(jax.random.split(rng, 16))

    def dense(key, shape):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(config.param_dtype)

    attn = {
        "wq": dense(next(keys), (l, e, hq)),
        "wk": dense(next(keys), (l, e, hkv)),
        "wv": dense(next(keys), (l, e, hkv)),
        "wo": dense(next(keys), (l, hq, e)),
    }
    if config.attn_bias:  # Qwen2-style QKV biases (zeros, like HF init)
        attn.update(bq=jnp.zeros((l, hq), config.param_dtype),
                    bk=jnp.zeros((l, hkv), config.param_dtype),
                    bv=jnp.zeros((l, hkv), config.param_dtype))
    if config.qk_norm == "flat":  # OLMo-2 full-width q/k RMSNorm scales
        attn.update(q_norm=jnp.ones((l, hq), config.param_dtype),
                    k_norm=jnp.ones((l, hkv), config.param_dtype))
    elif config.qk_norm:  # Qwen3 per-head q/k RMSNorm scales (ones, HF init)
        attn.update(q_norm=jnp.ones((l, d), config.param_dtype),
                    k_norm=jnp.ones((l, d), config.param_dtype))
    # key-consumption ORDER is part of the determinism contract (same seed
    # -> same params across versions): embed draws before the MLP leaves,
    # exactly as in every prior release
    embed = dense(next(keys), (v, e))
    layers = {
        "attn": attn,
        "mlp": {
            "gate": dense(next(keys), (l, e, f)),
            "up": dense(next(keys), (l, e, f)),
            "down": dense(next(keys), (l, f, e)),
        },
    }
    if config.post_norm:   # OLMo-2: norms sit on the sublayer OUTPUTS
        layers.update(attn_out_norm=jnp.ones((l, e), config.param_dtype),
                      mlp_out_norm=jnp.ones((l, e), config.param_dtype))
    elif config.sandwich_norm:   # Gemma-2: norms on BOTH sides
        layers.update(input_norm=jnp.ones((l, e), config.param_dtype),
                      attn_out_norm=jnp.ones((l, e), config.param_dtype),
                      post_attn_norm=jnp.ones((l, e), config.param_dtype),
                      mlp_out_norm=jnp.ones((l, e), config.param_dtype))
    else:
        layers.update(input_norm=jnp.ones((l, e), config.param_dtype),
                      post_attn_norm=jnp.ones((l, e), config.param_dtype))
    params = {
        "embed": {"embedding": embed},
        "layers": layers,
        "final_norm": jnp.ones((e,), config.param_dtype),
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = dense(next(keys), (e, v))
    return params


def param_logical_axes(config: LlamaConfig) -> dict:
    """Logical axis names for every leaf, mirroring ``init``'s structure.

    Names: vocab, embed, heads (fused q-heads x head_dim), kv (fused kv-heads),
    mlp, layers (the scan axis). ``None`` = never sharded on that dim.
    """
    attn_axes = {
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv"),
        "wv": ("layers", "embed", "kv"),
        "wo": ("layers", "heads", "embed"),
    }
    if config.attn_bias:  # biases shard with the head dim they add onto
        attn_axes.update(bq=("layers", "heads"), bk=("layers", "kv"),
                         bv=("layers", "kv"))
    if config.qk_norm == "flat":  # full-width scales shard with their heads
        attn_axes.update(q_norm=("layers", "heads_vector"),
                         k_norm=("layers", "kv_vector"))
    elif config.qk_norm:  # one [head_dim] scale shared by every head: never
        attn_axes.update(q_norm=("layers", "head_dim_vector"),  # sharded
                         k_norm=("layers", "head_dim_vector"))
    layer_axes = {
        "attn": attn_axes,
        "mlp": {
            "gate": ("layers", "embed", "mlp"),
            "up": ("layers", "embed", "mlp"),
            "down": ("layers", "mlp", "embed"),
        },
    }
    if config.post_norm:
        layer_axes.update(attn_out_norm=("layers", "embed_vector"),
                          mlp_out_norm=("layers", "embed_vector"))
    elif config.sandwich_norm:
        layer_axes.update(input_norm=("layers", "embed_vector"),
                          attn_out_norm=("layers", "embed_vector"),
                          post_attn_norm=("layers", "embed_vector"),
                          mlp_out_norm=("layers", "embed_vector"))
    else:
        layer_axes.update(input_norm=("layers", "embed_vector"),
                          post_attn_norm=("layers", "embed_vector"))
    axes = {
        "embed": {"embedding": ("vocab", "embed")},
        "layers": layer_axes,
        "final_norm": ("embed_vector",),
    }
    if not config.tie_word_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


ACT_FNS = {
    "silu": jax.nn.silu,
    "gelu_tanh": partial(jax.nn.gelu, approximate=True),  # HF gelu_pytorch_tanh
}


def _is_qt(w) -> bool:
    """Duck-typed ``train/precision.py`` ``Quantized`` check: the serving
    engine stores its projection weights as int8 payload + per-block fp32
    scales under ``weight_dtype='int8'`` (serve/weights.py). Structural,
    not isinstance — ``train`` imports ``models`` (train/step.py), so the
    model family cannot import ``train.precision`` back."""
    return hasattr(w, "q") and hasattr(w, "scale")


def _wmat(h: jnp.ndarray, w, cdt) -> jnp.ndarray:
    """``h @ w`` in compute dtype for a float weight; block-dequant matmul
    (fp32 accumulate, then the same compute-dtype cast) for a Quantized
    one — no full fp32 weight tensor materializes on that path."""
    if _is_qt(w):
        return quantized_matmul(h, w).astype(cdt)
    return h @ w.astype(cdt)


def _rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float,
             plus_one: bool = False) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    scale = scale.astype(jnp.float32)
    if plus_one:            # Gemma stores w, applies (1 + w)
        scale = scale + 1.0
    return (x * scale).astype(dtype)


def _flat_rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float,
                  tp_axis: Optional[str]) -> jnp.ndarray:
    """RMSNorm over the FULL flattened heads width (OLMo-2 q/k norm).
    Outside manual regions this is plain ``_rmsnorm`` (GSPMD inserts any
    needed collective itself); inside a manual-tp shard_map the local shard
    is ``[.., width/tp]``, so the sum-of-squares is psum'd across members
    and divided by the GLOBAL width before the local scale applies."""
    if tp_axis is None:
        return _rmsnorm(x, scale, eps)
    xf = x.astype(jnp.float32)
    ss = _psum(jnp.sum(xf * xf, axis=-1, keepdims=True), tp_axis)
    width = x.shape[-1] * jax.lax.psum(1, tp_axis)
    normed = xf * jax.lax.rsqrt(ss / width + eps)
    return (normed * scale.astype(jnp.float32)).astype(x.dtype)


def attention_extras(config):
    """Gemma-2 attention extras as (score_scale, logit_softcap) — None/None
    everywhere else. The ONE derivation of ``query_pre_attn_scalar ** -0.5``
    shared by the model dispatch and the Trainer's wrapper factories (both
    paths must bake the identical scale or flash/CP would silently diverge
    from xla)."""
    qpas = getattr(config, "query_pre_attn_scalar", None)
    return ((qpas ** -0.5) if qpas else None,
            getattr(config, "attn_logit_softcap", None))


@jax.named_scope("attn")
def attention_sublayer(config, x: jnp.ndarray, attn_params: dict, norm_scale,
                       positions: jnp.ndarray, attn_impl,
                       standard_layout: bool = True,
                       tp_axis: Optional[str] = None,
                       window_override=None, attend_override=None,
                       wmat_override=None):
    """norm -> rope'd GQA attention -> output proj (residual added by caller).

    Shared by the dense Llama block and the MoE family (config is duck-typed:
    needs num_heads/num_kv_heads/head_size/rope_theta/rms_norm_eps/dtype).

    ``tp_axis``: set when called inside a shard_map region where tp is a
    *manual* axis (the pipeline schedule) — weights arrive as per-member head
    shards (head counts are inferred from the weight shapes, not the config)
    and the output projection's partial sum is psum'd explicitly, the
    megatron Rowwise reduction GSPMD otherwise inserts.

    ``attend_override`` (the serving engine's paged-KV hook, the only
    cached decode there is): a callable ``(q, k, v, *, window, scale,
    softcap) -> (attn, aux)`` replacing the attend entirely — it receives
    the rope'd/normed per-head projections and the family-resolved
    attention extras, and whatever functional cache state it updates rides
    back through ``aux``: the call then returns ``(out, aux)``. Default
    None — the training path is untouched and returns ``out`` alone.

    ``wmat_override`` (the multi-LoRA serving hook): a callable
    ``(name, h, w) -> out`` replacing each target projection's
    ``_wmat`` — the batched adapter delta adds there without ever
    materializing a merged weight. Default None keeps every training
    path byte-identical."""
    b, s, e = x.shape
    d = config.head_size
    cdt = config.dtype
    if wmat_override is None:
        def wmat_override(name, hh, ww):
            return _wmat(hh, ww, cdt)
    if norm_scale is None:  # post-norm wiring (OLMo-2): raw residual in;
        h = x               # the caller norms the OUTPUT instead
    else:
        h = _rmsnorm(x, norm_scale, config.rms_norm_eps,
                     getattr(config, "norm_plus_one", False))
    q, k, v = (wmat_override(w, h, attn_params[w])
               for w in ("wq", "wk", "wv"))
    if "bq" in attn_params:  # Qwen2-style QKV biases; shard-local under
        q = q + attn_params["bq"].astype(cdt)  # manual tp (bias carries the
        k = k + attn_params["bk"].astype(cdt)  # same heads/kv logical axis
        v = v + attn_params["bv"].astype(cdt)  # as its matmul output)
    qk_mode = getattr(config, "qk_norm", False)
    if qk_mode == "flat":  # OLMo-2: full-width RMSNorm BEFORE the head
        # reshape; the [hq]/[hkv] scales carry heads/kv logical axes so each
        # member's SCALE shard matches its local width — but the RMS itself
        # is a reduction over the full width, so under manual tp the
        # sum-of-squares must cross the shard boundary (shard-local mean
        # would be silently wrong numerics)
        q = _flat_rmsnorm(q, attn_params["q_norm"], config.rms_norm_eps,
                          tp_axis)
        k = _flat_rmsnorm(k, attn_params["k_norm"], config.rms_norm_eps,
                          tp_axis)
    q = q.reshape(b, s, -1, d)
    k = k.reshape(b, s, -1, d)
    v = v.reshape(b, s, -1, d)
    if qk_mode is True:  # Qwen3: per-head RMSNorm pre-rope; the [head_dim]
        # scale is head-independent, so it is replicated under manual tp
        # (elementwise per head — no collective needed)
        q = _rmsnorm(q, attn_params["q_norm"], config.rms_norm_eps)
        k = _rmsnorm(k, attn_params["k_norm"], config.rms_norm_eps)
    rs = getattr(config, "rope_scaling", None)
    q = apply_rope(q, positions, config.rope_theta, rs,
                   config.max_position_embeddings)
    k = apply_rope(k, positions, config.rope_theta, rs,
                   config.max_position_embeddings)
    window = getattr(config, "sliding_window", None)
    if window_override is not None:  # per-layer pattern (Gemma-2): a traced
        window = window_override     # scalar, already 0 -> "no band" resolved
    attn_scale, softcap = attention_extras(config)
    if attend_override is not None:
        attn, aux = attend_override(q, k, v, window=window, scale=attn_scale,
                                    softcap=softcap)
        out = wmat_override("wo", attn.reshape(b, s, -1), attn_params["wo"])
        if tp_axis is not None:
            out = _psum(out, tp_axis)
        return out, aux
    if callable(attn_impl):  # e.g. ring attention under context parallelism
        # Trainer-built wrappers (sharded flash, ring, ulysses) declare
        # accepts_window and take the per-call window — uniform bands come
        # through unchanged and traced per-layer schedules (Gemma-2) ride
        # each wrapper's dynamic band plumbing; softcap/scale are baked in
        # by the Trainer factories. Other callables keep the bare contract
        # (Trainer validation rejects them when extras are configured).
        if getattr(attn_impl, "accepts_window", False):
            attn = attn_impl(q, k, v, standard_layout=standard_layout,
                             window=window)
        else:
            attn = attn_impl(q, k, v, standard_layout=standard_layout)
    else:
        attn = multihead_attention(q, k, v, causal=True, positions=positions,
                                   kv_positions=positions, impl=attn_impl,
                                   standard_layout=standard_layout,
                                   window=window, scale=attn_scale,
                                   logit_softcap=softcap)
    out = wmat_override("wo", attn.reshape(b, s, -1), attn_params["wo"])
    if tp_axis is not None:
        out = _psum(out, tp_axis)
    return out


@jax.named_scope("mlp")
def mlp_sublayer(config, x: jnp.ndarray, layer: dict,
                 tp_axis: Optional[str] = None,
                 wmat_override=None) -> jnp.ndarray:
    """post-attn norm -> gated MLP (residual added by caller). Under
    post-norm wiring (no ``post_attn_norm`` leaf) the raw stream feeds the
    MLP and the caller norms the output."""
    cdt = config.dtype
    if wmat_override is None:
        def wmat_override(name, hh, ww):
            return _wmat(hh, ww, cdt)
    scale = layer.get("post_attn_norm")
    if scale is None:
        h = x
    else:
        h = _rmsnorm(x, scale, config.rms_norm_eps,
                     getattr(config, "norm_plus_one", False))
    gate = wmat_override("gate", h, layer["mlp"]["gate"])
    up = wmat_override("up", h, layer["mlp"]["up"])
    act_fn = ACT_FNS[getattr(config, "act_fn", "silu")]
    # tagged for REMAT_POLICIES["attn_mlp"]: saving the [B,S,I] inner
    # activation skips the gate/up matmul recompute in backward
    act = checkpoint_name(act_fn(gate) * up, "mlp_act")
    down = wmat_override("down", act, layer["mlp"]["down"])
    if tp_axis is not None:  # megatron Rowwise: down-proj partial sums
        down = _psum(down, tp_axis)
    return down


def _block(config: LlamaConfig, x: jnp.ndarray, layer: dict,
           positions: jnp.ndarray, attn_impl: str,
           activation_sharding: Optional[Any] = None,
           standard_layout: bool = True,
           tp_axis: Optional[str] = None,
           window_override=None) -> jnp.ndarray:
    def constrain(y):
        if activation_sharding is not None:
            return jax.lax.with_sharding_constraint(y, activation_sharding)
        return y

    plus_one = getattr(config, "norm_plus_one", False)
    if getattr(config, "post_norm", False):   # OLMo-2 wiring
        attn = attention_sublayer(config, x, layer["attn"], None,
                                  positions, attn_impl, standard_layout,
                                  tp_axis, window_override=window_override)
        with jax.named_scope("attn"):   # the output norm is the sublayer's
            x = constrain(x + _rmsnorm(attn, layer["attn_out_norm"],
                                       config.rms_norm_eps, plus_one))
        mlp = mlp_sublayer(config, x, layer, tp_axis)
        with jax.named_scope("mlp"):
            return constrain(x + _rmsnorm(mlp, layer["mlp_out_norm"],
                                          config.rms_norm_eps, plus_one))

    if getattr(config, "sandwich_norm", False):   # Gemma-2 wiring: norms on
        # both sides of each sublayer; mlp_sublayer's pre-norm reads the
        # post_attn_norm leaf (HF pre_feedforward_layernorm)
        attn = attention_sublayer(config, x, layer["attn"],
                                  layer["input_norm"], positions, attn_impl,
                                  standard_layout, tp_axis,
                                  window_override=window_override)
        with jax.named_scope("attn"):   # the output norm is the sublayer's
            x = constrain(x + _rmsnorm(attn, layer["attn_out_norm"],
                                       config.rms_norm_eps, plus_one))
        mlp = mlp_sublayer(config, x, layer, tp_axis)
        with jax.named_scope("mlp"):
            return constrain(x + _rmsnorm(mlp, layer["mlp_out_norm"],
                                          config.rms_norm_eps, plus_one))

    attn = attention_sublayer(config, x, layer["attn"], layer["input_norm"],
                              positions, attn_impl, standard_layout, tp_axis,
                              window_override=window_override)
    x = constrain(x + attn)
    return constrain(x + mlp_sublayer(config, x, layer, tp_axis))


@jax.named_scope("embed")
def embed_tokens(config: LlamaConfig, params: dict, input_ids: jnp.ndarray,
                 positions: jnp.ndarray) -> jnp.ndarray:
    """Embedding sub-forward (pipeline stage-0 entry)."""
    del positions  # rope is applied inside blocks
    table = params["embed"]["embedding"]
    if _is_qt(table):  # int8 serve weights: gather rows THEN dequantize —
        # only the looked-up tokens, never the whole table
        x = quantized_take(table, input_ids).astype(config.dtype)
    else:
        x = jnp.take(table, input_ids, axis=0).astype(config.dtype)
    if getattr(config, "scale_embed", False):   # Gemma's sqrt(E) normalizer
        x = x * jnp.asarray(config.hidden_size ** 0.5, config.dtype)
    return x


def output_weights(config: LlamaConfig, params: dict) -> jnp.ndarray:
    """[E, V] output projection (tied or dedicated), in compute dtype."""
    if config.tie_word_embeddings:
        return params["embed"]["embedding"].T.astype(config.dtype)
    return params["lm_head"].astype(config.dtype)


def _output_container(config: LlamaConfig, params: dict):
    """The raw output-projection leaf (tied table or lm_head) plus whether
    the quantized matmul must run in transpose form (tied: blocks tile the
    contracted embed axis)."""
    if config.tie_word_embeddings:
        return params["embed"]["embedding"], True
    return params["lm_head"], False


@jax.named_scope("embed")
def tp_embed(config: LlamaConfig, params: dict, input_ids: jnp.ndarray,
             positions: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Stage-0 embedding when tp is a manual axis (pipeline schedule):
    megatron vocab parallelism over the sharded table."""
    del positions  # rope is applied inside blocks
    from ..ops.vocab_parallel import vocab_parallel_embed

    x = vocab_parallel_embed(params["embed"]["embedding"].astype(config.dtype),
                             input_ids, axis)
    if getattr(config, "scale_embed", False):   # Gemma's sqrt(E) normalizer
        x = x * jnp.asarray(config.hidden_size ** 0.5, config.dtype)
    return x


@jax.named_scope("final_norm")
def final_hidden(config: LlamaConfig, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Final norm only — pair with ``output_weights`` for chunked losses."""
    return _rmsnorm(x, params["final_norm"], config.rms_norm_eps,
                    getattr(config, "norm_plus_one", False))


@jax.named_scope("loss_head")
def lm_head_logits(config: LlamaConfig, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Final norm + output projection (pipeline last-stage exit)."""
    w, transpose = _output_container(config, params)
    if _is_qt(w):  # fp32 accumulate either way; the fp32 [tokens, V]
        # accumulator of the transpose form IS the logits tensor
        logits = quantized_matmul(final_hidden(config, params, x), w,
                                  transpose=transpose)
    else:
        logits = jnp.dot(final_hidden(config, params, x),
                         output_weights(config, params),
                         preferred_element_type=jnp.float32)
    cap = getattr(config, "final_logit_softcap", None)
    if cap:   # Gemma-2 final logit capping
        logits = jnp.tanh(logits / cap) * cap
    return logits


def apply(
    config: LlamaConfig,
    params: dict,
    input_ids: jnp.ndarray,
    positions: Optional[jnp.ndarray] = None,
    *,
    remat: bool = False,
    remat_policy: Optional[Any] = None,
    attn_impl: str = "auto",
    activation_sharding: Optional[Any] = None,
    return_hidden: bool = False,
) -> jnp.ndarray:
    """Forward pass -> logits [B, S, V] in float32 (or the final-normed
    hidden states [B, S, E] when ``return_hidden``, for chunked losses).

    ``positions`` must be passed explicitly when the sequence dim is sharded
    (sequence/context parallelism) — same constraint the reference hits at
    ``06-tensor-parallel/train_llm.py:210-212``.
    ``activation_sharding`` optionally constrains the inter-block residual
    stream (e.g. P('dp', 'tp', None) for sequence parallelism).
    """
    standard_layout = positions is None
    if positions is None:
        positions = jnp.arange(input_ids.shape[1])[None, :]
    positions = jnp.broadcast_to(positions, input_ids.shape)

    x = embed_tokens(config, params, input_ids, positions)

    block = partial(_block, config, positions=positions, attn_impl=attn_impl,
                    activation_sharding=activation_sharding,
                    standard_layout=standard_layout)

    wins = _layer_window_column(config)
    if wins is not None:
        # per-layer sliding-window pattern (Gemma-2 alternates sliding /
        # full): the window rides the scan as a traced per-layer scalar;
        # 0 (= full attention) maps to a band wider than any sequence

        def scan_body(carry, xs):
            layer_params, w = xs
            return block(carry, layer_params, window_override=w), None

        scan_xs = (params["layers"], wins)
    else:
        def scan_body(carry, layer_params):
            return block(carry, layer_params), None

        scan_xs = params["layers"]

    if remat:
        policy = remat_policy or jax.checkpoint_policies.nothing_saveable
        scan_body = jax.checkpoint(scan_body, policy=policy, prevent_cse=False)

    # the scan's own work (slicing the stacked leaves, stacking what the
    # backward pass keeps and the weight gradients) is neither sublayer's
    with jax.named_scope("layers"):
        x, _ = jax.lax.scan(scan_body, x, scan_xs)

    if return_hidden:
        return final_hidden(config, params, x)
    return lm_head_logits(config, params, x)


# ---------------------------------------------------------------------------
# KV-cached decode: the serving engine's paged step (serve/engine.py;
# models/sample.py --kv-cache runs that engine at one slot). The cache is
# the engine's stacked page pools, a functional pytree carried through
# lax.scan over layers (scan_paged_layers). A family serves by exporting
# paged_decode_step alone. Training paths are unaffected (separate entry
# points).
# ---------------------------------------------------------------------------

def _decode_residuals(config, x, layer, attn, wmat_override=None):
    """Shared residual wiring for the paged step's layer bodies (pre-,
    post-, and sandwich-norm variants); returns (new_x, None)."""
    plus_one = getattr(config, "norm_plus_one", False)
    if getattr(config, "post_norm", False) or getattr(config, "sandwich_norm",
                                                      False):
        with jax.named_scope("attn"):
            x = x + _rmsnorm(attn, layer["attn_out_norm"],
                             config.rms_norm_eps, plus_one)
        mlp = mlp_sublayer(config, x, layer, wmat_override=wmat_override)
        with jax.named_scope("mlp"):
            x = x + _rmsnorm(mlp, layer["mlp_out_norm"], config.rms_norm_eps,
                             plus_one)
    else:
        x = x + attn
        x = x + mlp_sublayer(config, x, layer, wmat_override=wmat_override)
    return x, None


# ---------------------------------------------------------------------------
# Batched multi-LoRA (serve/adapters.py): the low-rank delta
# ``scale * (x @ A_g) @ B_g`` added per target projection as a RAGGED
# GROUPED GEMM over rows sorted by adapter (S-LoRA arXiv:2311.03285 /
# Punica arXiv:2310.18547 — the MoE dispatch pattern applied to the decode
# batch). The base projection is NEVER merged with the delta into a dense
# ``W + scale*A@B`` weight: over a quantized base the merged tensor does
# not even exist in fp, and per-adapter merges would materialize
# ``[G, in, out]`` copies of every target — the delta stays a separate
# rank-r bottleneck add (HLO-pinned in tests).
# ---------------------------------------------------------------------------

def _lora_sort(adapters, t: int, g: int):
    """The PR-3 dispatch triplet for a ``[S]`` per-slot adapter vector:
    stable sort order, its int32 inversion, and the per-group SORTED-ROW
    counts (slot histogram x the T tokens each slot contributes)."""
    ids = adapters.astype(jnp.int32)
    order = jnp.argsort(ids)
    inv = jnp.argsort(order)
    sizes = jnp.zeros((g,), jnp.int32).at[ids].add(jnp.int32(t))
    return order, inv, sizes


def _lora_wmat_override(config, lora, lstack, sort):
    """Per-layer projection hook: base ``_wmat`` plus the grouped-GEMM
    adapter delta for targets present in ``lstack`` (this layer's
    ``{t: {"a" [G, in, r], "b" [G, r, out]}}`` pool slices). Slot 0's
    rows are zeros, so base-only requests contribute an exact fp ``+0``
    — the adapter-0 == base-engine bitwise identity."""
    from ..ops.grouped_matmul import grouped_matmul

    order, inv, sizes = sort
    cdt = config.dtype
    scale = lora["scale"]
    impl = lora.get("impl", "auto")

    def ov(name, h, w):
        base = _wmat(h, w, cdt)
        pair = lstack.get(name)
        if pair is None:
            return base
        s, t, k = h.shape
        hs = h[order].reshape(s * t, k).astype(jnp.float32)
        d = grouped_matmul(hs, pair["a"], sizes, impl=impl)
        d = grouped_matmul(d, pair["b"], sizes, impl=impl)
        d = d.reshape(s, t, -1)[inv]
        return base + (jnp.float32(scale) * d).astype(base.dtype)

    return ov


def _layer_window_column(config):
    """Per-layer window column for the layer scans — training AND decode
    share this one translation (None when uniform; 0 -> a band wider than
    any supported sequence)."""
    lw = getattr(config, "layer_windows", None)
    if not lw:
        return None
    bad = [w for w in lw if w < 0]
    if bad:
        # a window <= 0 reaching the kernels as a traced value would mask
        # every score and return all-zero attention with no error; 0 is the
        # sanctioned "full attention" encoding, anything below is a bug
        raise ValueError(f"layer_windows entries must be >= 0 "
                         f"(0 = full attention); got {bad}")
    return jnp.asarray([w if w else 2 ** 30 for w in lw], jnp.int32)


# the page pools a cache dict may hold (k_win, v_win: a second page class)
POOL_LEAVES = ("k", "v", "state", "k_win", "v_win")


def cache_pools(cache: dict) -> tuple:
    """The pools of a paged step's ``cache``, in ``POOL_LEAVES`` order: k and
    v, then a family's per-page recurrent state where it has one."""
    return tuple(cache[name] for name in POOL_LEAVES if name in cache)


def pools_dict(cache: dict, pools: tuple) -> dict:
    """:func:`cache_pools`' inverse: the updated pools under their names."""
    return dict(zip((n for n in POOL_LEAVES if n in cache), pools))


def scan_paged_layers(body, x, layers, cache, wins=None, lora_stacks=None):
    """The layer scan of every family's PAGED step. The stacked page pools
    of ``cache`` (``"k"``, ``"v"``: [L, P, page, heads, width] leaves; where
    the family keeps recurrent state beside them, ``"state"``: [Ls, P, rows,
    width]) ride the
    scan as part of the CARRY, whole, beside ``x``; the scanned columns are
    the layers' weights (``layers``: the ``[L, ...]`` leaves the body wants
    one layer of), the layer's index and, where a family has them, the
    per-layer windows and the multi-LoRA stacks (None columns are empty
    pytrees: the scan sees nothing there). A stacked leaf that a Pallas
    kernel reads rides WHOLE instead, as the pools do: the scan's slice of a
    column fuses into a ``dot``'s operand, but a custom call needs it
    materialised, which is a copy of the layer's share in every iteration.
    So a routing family leaves its expert leaves out of ``layers``, closes
    its body over them (loop invariants; ``moe.experts_in_place``) and lets
    ``gmm`` address the layer by ``i``. ``body(x, pools, layer, i, w,
    lstack) -> (x, pools, ys)`` hands the pools and ``i`` to the attend
    callback (``serve/kv_pages.paged_attend``'s contract), which writes and
    reads them addressed by layer, and returns them whole: nothing
    pool-sized is sliced per iteration or stacked per output, so the donated
    pools are updated in place. ``ys`` is what really is per layer (a routing
    family's counts; None elsewhere). Returns ``(x, pools under their
    names, ys)``. A family whose consecutive layers differ in kind
    (``models/lfm2.py``) walks its layers itself and keeps the same carry:
    ``cache_pools`` in, ``pools_dict`` out."""
    n_layers = jax.tree.leaves(layers)[0].shape[0]

    def step(carry, columns):
        x, pools, ys = body(*carry, *columns)
        return (x, pools), ys

    with jax.named_scope("layers"):   # the scan's slicing of the weights
        (x, pools), ys = jax.lax.scan(
            step, (x, cache_pools(cache)),
            (layers, jnp.arange(n_layers, dtype=jnp.int32), wins,
             lora_stacks))
    return x, pools_dict(cache, pools), ys


def paged_positions(token_ids: jnp.ndarray,
                    positions: jnp.ndarray) -> jnp.ndarray:
    """[S, T] absolute positions for a paged decode/chunk call: slot s's
    T tokens sit at ``positions[s] + 0..T-1`` (T == 1 is the decode step,
    T > 1 a prefill chunk). Shared by every family's paged entry point."""
    t = token_ids.shape[1]
    return positions[:, None] + jnp.arange(t, dtype=positions.dtype)[None, :]


def paged_logits_at(lm_head, config, params, x, last_index,
                    all_logits=False):
    """Slice the hidden states at the position whose logits the caller
    wants BEFORE the head projection: projecting a whole chunk to
    [S, T, V] fp32 only to keep one row would cost T x the lm_head matmul
    and a chunk-length-scaled logits buffer (norm + projection are
    per-position). ``None``
    keeps the decode contract — the last position. ``all_logits=True``
    keeps EVERY position ([S, T, V]): the speculative-decoding
    verification forward (serve/engine.py ``verify_for``) needs one
    target distribution per drafted token — T there is the speculation
    depth k+1, not a prompt length, so the full projection is the point,
    not a waste."""
    if all_logits:
        return lm_head(config, params, x)
    x_last = (x[:, -1:] if last_index is None
              else jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1))
    return lm_head(config, params, x_last)[:, 0]


def paged_decode_step(config: LlamaConfig, params: dict,
                      token_ids: jnp.ndarray, positions: jnp.ndarray,
                      cache: dict, attend, last_index=None,
                      all_logits=False, lora=None):
    """One step over a PAGED multi-request cache (serve/engine.py):
    ``token_ids`` [S, T] are each slot's next T tokens starting at
    PER-SLOT position ``positions`` [S]. This is the ONE function a family
    exports to serve (the engine's decode, chunk-prefill, verify and
    horizon programs and the drafter all call it; ``pool_layout`` in
    serve/kv_pages.py sizes the family's cache rows). T == 1 is the
    batched decode step; T > 1 is
    a chunked-prefill call (S == 1 in practice) whose queries attend over
    the committed history AND the chunk itself — ``last_index`` (traced)
    then selects the real last token's logits out of a padded chunk —
    or a speculative-decoding VERIFICATION step (S slots, T = k+1
    candidates each), which instead passes ``all_logits=True`` for the
    [S, T, V] logits at every position (one target distribution per
    drafted token). ``cache`` holds the STACKED page pools ``{"k","v"}:
    [L, n_pages, page, kvh, hd]``, carried whole through the layer scan
    (:func:`scan_paged_layers`), and ``attend(q, k, v, kp, vp, layer, *,
    window, scale, softcap)`` (built by serve/kv_pages.py, whose
    ``paged_attend`` states the contract) scatters the new k/v into that
    layer's pages of the stacked pools and attends each slot over its own
    block table there. Returns (logits [S, V] — or [S, T, V] under
    ``all_logits`` — and the updated cache).

    ``lora`` (multi-LoRA serving, see ``_lora_wmat_override``):
    ``{"scale", "adapters" [S] int32, "stacks", "impl"}`` — per-slot
    adapter deltas batched as one ragged grouped GEMM per target per
    layer, slots gather-sorted by adapter and int32-inversion unsorted.
    The SAME compiled program serves every adapter mix: the stacks and
    the adapter vector are array arguments, never trace constants."""
    pos2d = paged_positions(token_ids, positions)
    x = embed_tokens(config, params, token_ids, pos2d)

    wins = _layer_window_column(config)
    sort = None
    if lora is not None:
        g = jax.tree.leaves(lora["stacks"])[0].shape[1]
        sort = _lora_sort(lora["adapters"], token_ids.shape[1], g)

    def body(x, pools, layer, i, w, lstack):
        ov = (None if lora is None
              else _lora_wmat_override(config, lora, lstack, sort))

        def override(q, k, v, *, window, scale, softcap):
            return attend(q, k, v, *pools, i, window=window, scale=scale,
                          softcap=softcap)

        attn, pools = attention_sublayer(
            config, x, layer["attn"],
            None if config.post_norm else layer["input_norm"], pos2d,
            "xla", window_override=w, attend_override=override,
            wmat_override=ov)
        x, _ = _decode_residuals(config, x, layer, attn, wmat_override=ov)
        return x, pools, None

    x, pools, _ = scan_paged_layers(
        body, x, params["layers"], cache, wins,
        None if lora is None else lora["stacks"])
    return (paged_logits_at(lm_head_logits, config, params, x, last_index,
                            all_logits), pools)


# ---------------------------------------------------------------------------
# Presets (shapes from the public model cards; the reference trains these via
# HF checkpoints — `05-training-llama-405b/README.md`, `06/README.md`).
# ---------------------------------------------------------------------------

# Llama-3.1 / 3.2 cards ship the llama3 band-wise rescale (the checkpoints'
# config.json rope_scaling); the presets carry it so long-context numerics
# match HF out of the box (reference trains these checkpoints through
# AutoModelForCausalLM, 05-training-llama-405b/train_llm.py:74-146)
_LLAMA3_ROPE_8X = freeze_rope_scaling({
    "rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
    "high_freq_factor": 4.0, "original_max_position_embeddings": 8192})
_LLAMA3_ROPE_32X = freeze_rope_scaling({
    "rope_type": "llama3", "factor": 32.0, "low_freq_factor": 1.0,
    "high_freq_factor": 4.0, "original_max_position_embeddings": 8192})

PRESETS = {
    "llama-debug": LlamaConfig(vocab_size=512, hidden_size=64, intermediate_size=128,
                               num_layers=2, num_heads=4, num_kv_heads=2,
                               max_position_embeddings=256),
    "tinyllama-1.1b": LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                                  num_layers=22, num_heads=32, num_kv_heads=4),
    # single-chip benchmark config: ~650M params, head_dim 128 (MXU/flash
    # friendly), fits params+Adam in fp32 on a 16 GB chip at seq 2048
    "llama-650m": LlamaConfig(vocab_size=32000, hidden_size=1536, intermediate_size=6144,
                              num_layers=16, num_heads=12, num_kv_heads=4,
                              max_position_embeddings=4096),
    # tinyllama's parameter count with 16 heads x 128 where tinyllama runs
    # 32 x 64 — half-width head tiles waste half of every 128x128 MXU pass,
    # so this preset isolates the head-dim lever at 1B scale (not measured
    # on this tree)
    "llama-1b-hd128": LlamaConfig(vocab_size=32000, hidden_size=2048,
                                  intermediate_size=8192, num_layers=16,
                                  num_heads=16, num_kv_heads=4,
                                  max_position_embeddings=4096),
    "llama-3.2-1b": LlamaConfig(vocab_size=128256, hidden_size=2048, intermediate_size=8192,
                                num_layers=16, num_heads=32, num_kv_heads=8,
                                rope_theta=500000.0, max_position_embeddings=131072,
                                rope_scaling=_LLAMA3_ROPE_32X,
                                tie_word_embeddings=True),
    "llama-3.2-3b": LlamaConfig(vocab_size=128256, hidden_size=3072, intermediate_size=8192,
                                num_layers=28, num_heads=24, num_kv_heads=8,
                                rope_theta=500000.0, max_position_embeddings=131072,
                                rope_scaling=_LLAMA3_ROPE_32X,
                                tie_word_embeddings=True),
    "llama-3.1-8b": LlamaConfig(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                                num_layers=32, num_heads=32, num_kv_heads=8,
                                rope_theta=500000.0, max_position_embeddings=131072,
                                rope_scaling=_LLAMA3_ROPE_8X),
    "llama-3.1-70b": LlamaConfig(vocab_size=128256, hidden_size=8192, intermediate_size=28672,
                                 num_layers=80, num_heads=64, num_kv_heads=8,
                                 rope_theta=500000.0, max_position_embeddings=131072,
                                 rope_scaling=_LLAMA3_ROPE_8X),
    "llama-3.1-405b": LlamaConfig(vocab_size=128256, hidden_size=16384, intermediate_size=53248,
                                  num_layers=126, num_heads=128, num_kv_heads=8,
                                  rope_theta=500000.0, max_position_embeddings=131072,
                                  rope_scaling=_LLAMA3_ROPE_8X),
    # Mistral dense is llama-architecture exactly (HF MistralForCausalLM uses
    # the same tensor names/layouts as LlamaForCausalLM); shapes are the
    # v0.3 card (no sliding window, 32768-token vocab)
    "mistral-7b": LlamaConfig(vocab_size=32768, hidden_size=4096, intermediate_size=14336,
                              num_layers=32, num_heads=32, num_kv_heads=8,
                              rope_theta=1e6, max_position_embeddings=32768),
    # Gemma = llama + GeGLU + (1+w) RMSNorm + sqrt(E)-scaled embeddings,
    # explicit head_dim 256, always-tied embeddings (gemma-2b is MQA: kv=1)
    "gemma-2b": LlamaConfig(vocab_size=256000, hidden_size=2048, intermediate_size=16384,
                            num_layers=18, num_heads=8, num_kv_heads=1, head_dim=256,
                            act_fn="gelu_tanh", norm_plus_one=True, scale_embed=True,
                            rms_norm_eps=1e-6, tie_word_embeddings=True,
                            max_position_embeddings=8192),
    "gemma-7b": LlamaConfig(vocab_size=256000, hidden_size=3072, intermediate_size=24576,
                            num_layers=28, num_heads=16, num_kv_heads=16, head_dim=256,
                            act_fn="gelu_tanh", norm_plus_one=True, scale_embed=True,
                            rms_norm_eps=1e-6, tie_word_embeddings=True,
                            max_position_embeddings=8192),
    # Gemma-2 = Gemma + sandwich norms, tanh softcaps (attention 50, final
    # 30), query_pre_attn_scalar score scale, and the alternating
    # sliding/full window pattern (sliding on even layers, window 4096)
    "gemma2-2b": LlamaConfig(vocab_size=256000, hidden_size=2304, intermediate_size=9216,
                             num_layers=26, num_heads=8, num_kv_heads=4, head_dim=256,
                             act_fn="gelu_tanh", norm_plus_one=True, scale_embed=True,
                             sandwich_norm=True, rms_norm_eps=1e-6,
                             tie_word_embeddings=True, attn_logit_softcap=50.0,
                             final_logit_softcap=30.0, query_pre_attn_scalar=256.0,
                             layer_windows=tuple(4096 if i % 2 == 0 else 0
                                                 for i in range(26)),
                             max_position_embeddings=8192),
    "gemma2-9b": LlamaConfig(vocab_size=256000, hidden_size=3584, intermediate_size=14336,
                             num_layers=42, num_heads=16, num_kv_heads=8, head_dim=256,
                             act_fn="gelu_tanh", norm_plus_one=True, scale_embed=True,
                             sandwich_norm=True, rms_norm_eps=1e-6,
                             tie_word_embeddings=True, attn_logit_softcap=50.0,
                             final_logit_softcap=30.0, query_pre_attn_scalar=256.0,
                             layer_windows=tuple(4096 if i % 2 == 0 else 0
                                                 for i in range(42)),
                             max_position_embeddings=8192),
    # Qwen2.5 dense = llama + QKV biases (attn_bias); small cards tie embeddings
    "qwen2.5-0.5b": LlamaConfig(vocab_size=151936, hidden_size=896, intermediate_size=4864,
                                num_layers=24, num_heads=14, num_kv_heads=2,
                                rope_theta=1e6, rms_norm_eps=1e-6, attn_bias=True,
                                tie_word_embeddings=True,
                                max_position_embeddings=32768),
    "qwen2.5-7b": LlamaConfig(vocab_size=152064, hidden_size=3584, intermediate_size=18944,
                              num_layers=28, num_heads=28, num_kv_heads=4,
                              rope_theta=1e6, rms_norm_eps=1e-6, attn_bias=True,
                              max_position_embeddings=32768),
    # Qwen3 dense = llama + per-head q/k RMSNorm (qk_norm) and NO qkv biases;
    # explicit head_dim 128 regardless of hidden/heads (public model cards)
    "qwen3-0.6b": LlamaConfig(vocab_size=151936, hidden_size=1024, intermediate_size=3072,
                              num_layers=28, num_heads=16, num_kv_heads=8,
                              head_dim=128, qk_norm=True, rope_theta=1e6,
                              rms_norm_eps=1e-6, tie_word_embeddings=True,
                              max_position_embeddings=40960),
    "qwen3-8b": LlamaConfig(vocab_size=151936, hidden_size=4096, intermediate_size=12288,
                            num_layers=36, num_heads=32, num_kv_heads=8,
                            head_dim=128, qk_norm=True, rope_theta=1e6,
                            rms_norm_eps=1e-6,
                            max_position_embeddings=40960),
    # OLMo-2 = llama + post-norm block wiring (norms on sublayer outputs)
    # + full-width q/k RMSNorm; MHA (kv == heads), public 1124-7B card
    "olmo2-7b": LlamaConfig(vocab_size=100352, hidden_size=4096, intermediate_size=11008,
                            num_layers=32, num_heads=32, num_kv_heads=32,
                            post_norm=True, qk_norm="flat",
                            rope_theta=500000.0, rms_norm_eps=1e-6,
                            max_position_embeddings=4096),
}
