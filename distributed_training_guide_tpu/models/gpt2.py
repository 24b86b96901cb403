"""GPT-2 decoder, TPU-first.

The reference's chapter-1 smoke model is HF ``gpt2`` (124M)
(``01-single-gpu/README.md:11``). Same scan-over-layers / logical-axes design
as ``llama.py``; differences: learned position embeddings, LayerNorm with
bias, fused-QKV projection, gelu MLP, tied LM head.
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


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_position_embeddings: int = 1024
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def head_size(self) -> int:
        return self.hidden_size // self.num_heads

    def num_params(self) -> int:
        e, v, p, l = (self.hidden_size, self.vocab_size,
                      self.max_position_embeddings, self.num_layers)
        per_layer = 3 * e * e + 3 * e + e * e + e + 8 * e * e + 5 * e + 4 * e
        return v * e + p * e + l * per_layer + 2 * e


def init(config: GPT2Config, rng: jax.Array) -> dict:
    e, v, p, l = (config.hidden_size, config.vocab_size,
                  config.max_position_embeddings, config.num_layers)
    keys = iter(jax.random.split(rng, 8))

    def dense(key, shape):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(config.param_dtype)

    def ln(shape):
        return {"scale": jnp.ones(shape, config.param_dtype),
                "bias": jnp.zeros(shape, config.param_dtype)}

    return {
        "wte": dense(next(keys), (v, e)),
        "wpe": dense(next(keys), (p, e)),
        "layers": {
            "ln1": ln((l, e)),
            "attn": {
                # fused QKV as [l, e, 3, e] (not [l, e, 3e]) so the head dim
                # is the trailing axis: sharding it over tp gives each member
                # the q/k/v columns of ITS heads — a contiguous slice of the
                # flat 3e dim would instead split q/k/v unevenly
                "wqkv": dense(next(keys), (l, e, 3, e)),
                "bqkv": jnp.zeros((l, 3, e), config.param_dtype),
                "wo": dense(next(keys), (l, e, e)),
                "bo": jnp.zeros((l, e), config.param_dtype),
            },
            "ln2": ln((l, e)),
            "mlp": {
                "wi": dense(next(keys), (l, e, 4 * e)),
                "bi": jnp.zeros((l, 4 * e), config.param_dtype),
                "wo": dense(next(keys), (l, 4 * e, e)),
                "bo": jnp.zeros((l, e), config.param_dtype),
            },
        },
        "lnf": ln((e,)),
    }


def param_logical_axes(config: GPT2Config) -> dict:
    del config
    ln_l = {"scale": ("layers", "embed_vector"), "bias": ("layers", "embed_vector")}
    return {
        "wte": ("vocab", "embed"),
        "wpe": ("pos", "embed"),
        "layers": {
            "ln1": ln_l,
            "attn": {
                "wqkv": ("layers", "embed", "qkv", "heads"),
                "bqkv": ("layers", "qkv", "heads_vector"),
                "wo": ("layers", "heads", "embed"),
                "bo": ("layers", "embed_vector"),
            },
            "ln2": ln_l,
            "mlp": {
                "wi": ("layers", "embed", "mlp"),
                "bi": ("layers", "mlp_vector"),
                "wo": ("layers", "mlp", "embed"),
                "bo": ("layers", "embed_vector"),
            },
        },
        "lnf": {"scale": ("embed_vector",), "bias": ("embed_vector",)},
    }


def _layernorm(x, p, eps):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(dtype)


@jax.named_scope("attn")
def _attn_sublayer(config, y, layer, positions, attn_impl,
                   standard_layout=True, attend_override=None):
    """ln'd input -> fused QKV -> attention -> out proj (no residual, no
    psum, no row bias — the block owns those). ``attend_override`` follows
    llama.attention_sublayer's decode contract: with it the call returns
    ``(out, aux)`` (no rope here: gpt2's positions are the learned table
    applied at embed time)."""
    b, s, e = y.shape
    d = config.head_size
    cdt = config.dtype
    wqkv = layer["attn"]["wqkv"]          # [e, 3, e/tp] under manual tp
    e_loc = wqkv.shape[-1]
    h_loc = e_loc // d
    # project WITHOUT flattening [3, e_loc] into 3*e_loc: the trailing head
    # dim may be tp-sharded, and GSPMD cannot represent the strided tiling a
    # merged 3e dim would need — it would all-gather the QKV weight on the
    # auto tp/tp_fsdp paths (the layout's whole point is that it shards)
    qkv = (jnp.einsum("bse,eqh->bsqh", y, wqkv.astype(cdt))
           + layer["attn"]["bqkv"].astype(cdt))
    q = qkv[:, :, 0].reshape(b, s, h_loc, d)
    k = qkv[:, :, 1].reshape(b, s, h_loc, d)
    v = qkv[:, :, 2].reshape(b, s, h_loc, d)
    if attend_override is not None:
        attn, aux = attend_override(q, k, v, window=None, scale=None,
                                    softcap=None)
        out = attn.reshape(b, s, e_loc) @ layer["attn"]["wo"].astype(cdt)
        return out, aux
    if callable(attn_impl):  # e.g. ring attention under context parallelism
        attn = attn_impl(q, k, v, standard_layout=standard_layout)
    else:
        attn = multihead_attention(q, k, v, causal=True, positions=positions,
                                   kv_positions=positions, impl=attn_impl,
                                   standard_layout=standard_layout)
    return attn.reshape(b, s, e_loc) @ layer["attn"]["wo"].astype(cdt)


@jax.named_scope("mlp")
def _mlp_sublayer(config, y, layer):
    """ln2'd input -> gelu MLP (no residual, no psum, no row bias)."""
    cdt = config.dtype
    y = jax.nn.gelu(y @ layer["mlp"]["wi"].astype(cdt)
                    + layer["mlp"]["bi"].astype(cdt), approximate=True)
    # tagged for REMAT_POLICIES["attn_mlp"] (same role as llama's mlp_act)
    y = checkpoint_name(y, "mlp_act")
    return y @ layer["mlp"]["wo"].astype(cdt)


def _block(config: GPT2Config, x, layer, positions, attn_impl,
           standard_layout=True, tp_axis=None):
    """One pre-LN transformer block.

    ``tp_axis``: set inside a shard_map region where tp is a *manual* axis
    (the pipeline schedule, ``parallel/pipeline.py``): wqkv/bqkv/wi/bi arrive
    column-sharded (local head / mlp slices, inferred from shapes), wo / mlp
    wo row-sharded with an explicit psum of the partial sums, and the
    replicated row biases are added once, after the psum."""
    cdt = config.dtype

    y = _layernorm(x, layer["ln1"], config.layer_norm_eps)
    attn = _attn_sublayer(config, y, layer, positions, attn_impl,
                          standard_layout)
    if tp_axis is not None:  # megatron Rowwise: out-proj partial sums
        attn = _psum(attn, tp_axis)
    x = x + attn + layer["attn"]["bo"].astype(cdt)

    y = _mlp_sublayer(config, _layernorm(x, layer["ln2"],
                                         config.layer_norm_eps), layer)
    if tp_axis is not None:
        y = _psum(y, tp_axis)
    return x + y + layer["mlp"]["bo"].astype(cdt)


@jax.named_scope("embed")
def embed_tokens(config: GPT2Config, params: dict, input_ids: jnp.ndarray,
                 positions: jnp.ndarray) -> jnp.ndarray:
    """Token + learned-position embedding (pipeline stage-0 entry)."""
    tok = jnp.take(params["wte"], input_ids, axis=0)
    pos = jnp.take(params["wpe"], positions, axis=0)
    return (tok + pos).astype(config.dtype)


def output_weights(config: GPT2Config, params: dict) -> jnp.ndarray:
    """[E, V] tied output projection in compute dtype."""
    return params["wte"].T.astype(config.dtype)


@jax.named_scope("embed")
def tp_embed(config: GPT2Config, params: dict, input_ids: jnp.ndarray,
             positions: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Stage-0 embedding when tp is a manual axis: vocab-sharded token table
    (megatron vocab parallelism) + the replicated learned-position table."""
    from ..ops.vocab_parallel import vocab_parallel_embed

    tok = vocab_parallel_embed(params["wte"].astype(config.dtype),
                               input_ids, axis)
    pos = jnp.take(params["wpe"], positions, axis=0).astype(config.dtype)
    return tok + pos


@jax.named_scope("final_norm")
def final_hidden(config: GPT2Config, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    return _layernorm(x, params["lnf"], config.layer_norm_eps)


@jax.named_scope("loss_head")
def lm_head_logits(config: GPT2Config, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Final LN + tied output projection (pipeline last-stage exit)."""
    return jnp.dot(final_hidden(config, params, x), output_weights(config, params),
                   preferred_element_type=jnp.float32)


def apply(
    config: GPT2Config,
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
    del activation_sharding  # gpt2 path is small; SP constraint not needed
    standard_layout = positions is None
    if positions is None:
        positions = jnp.arange(input_ids.shape[1])[None, :]
    positions = jnp.broadcast_to(positions, input_ids.shape)

    x = embed_tokens(config, params, input_ids, positions)

    block = partial(_block, config, positions=positions, attn_impl=attn_impl,
                    standard_layout=standard_layout)

    def scan_body(carry, layer_params):
        return block(carry, layer_params), None

    if remat:
        policy = remat_policy or jax.checkpoint_policies.nothing_saveable
        scan_body = jax.checkpoint(scan_body, policy=policy,
                                   prevent_cse=False)

    x, _ = jax.lax.scan(scan_body, x, params["layers"])
    if return_hidden:
        return final_hidden(config, params, x)
    return lm_head_logits(config, params, x)


# ---------------------------------------------------------------------------
# KV-cached decode: the serving engine's paged step (llama.paged_decode_step
# contract). The simplest case of the families: no rope, the learned
# position row is added at embed time, so cached k/v are exactly the
# projections.
# ---------------------------------------------------------------------------

def _cached_block(config, x, layer, positions, attend_override):
    cdt = config.dtype
    y = _layernorm(x, layer["ln1"], config.layer_norm_eps)
    attn, pools = _attn_sublayer(config, y, layer, positions, "xla",
                                 attend_override=attend_override)
    x = x + attn + layer["attn"]["bo"].astype(cdt)
    y = _mlp_sublayer(config, _layernorm(x, layer["ln2"],
                                         config.layer_norm_eps), layer)
    return x + y + layer["mlp"]["bo"].astype(cdt), pools


def paged_decode_step(config: GPT2Config, params: dict,
                      token_ids: jnp.ndarray, positions: jnp.ndarray,
                      cache: dict, attend, last_index=None,
                      all_logits=False):
    """Paged multi-request decode/chunk step (llama.paged_decode_step
    contract): ``token_ids`` [S, T] starting at per-slot ``positions``
    [S] index the learned position table at embed time; ``attend`` owns
    the page scatter + block-table attend; ``last_index`` selects the
    logits row for a padded chunk, ``all_logits=True`` keeps every row
    (speculative verification). The block wiring is ``_cached_block``."""
    from .llama import (paged_logits_at, paged_positions,
                        scan_paged_layers)

    pos2d = paged_positions(token_ids, positions)
    x = embed_tokens(config, params, token_ids, pos2d)

    def body(x, pools, layer, i, *_):
        def override(q, k, v, *, window, scale, softcap):
            del window, scale, softcap  # no gpt2 attention extras
            return attend(q, k, v, *pools, i)

        x, pools = _cached_block(config, x, layer, pos2d, override)
        return x, pools, None

    x, pools, _ = scan_paged_layers(body, x, params["layers"], cache)
    return (paged_logits_at(lm_head_logits, config, params, x, last_index,
                            all_logits), pools)


PRESETS = {
    "gpt2-debug": GPT2Config(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                             max_position_embeddings=256),
    "gpt2": GPT2Config(),
    "gpt2-medium": GPT2Config(hidden_size=1024, num_layers=24, num_heads=16),
    "gpt2-large": GPT2Config(hidden_size=1280, num_layers=36, num_heads=20),
    "gpt2-xl": GPT2Config(hidden_size=1600, num_layers=48, num_heads=25),
}
