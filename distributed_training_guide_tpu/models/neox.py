"""GPT-NeoX / Pythia decoder, TPU-first.

Seventh HF family, and the first with the *parallel-residual* block:
``x + attn(ln1(x)) + mlp(ln2(x))`` — attention and MLP read the same
input and their outputs sum into one residual update (the GPT-J/NeoX
design). The reference would train these through ``AutoModelForCausalLM``
(``01-single-gpu/train_llm.py:57``); here the family is native, with the
same scan-over-layers / logical-axes design as ``llama.py`` / ``gpt2.py``
so every sharding plan (ddp/fsdp/tp/2D/pp/cp) applies unchanged.

Architectural deltas vs the in-repo families:

- **parallel residual** (``use_parallel_residual``): under manual tensor
  parallelism this is a real communication win — the attention out-proj
  and MLP down-proj partial sums are added *before* a single ``psum``,
  one all-reduce per layer where the sequential block needs two;
- **partial rotary** (``rotary_pct``, 0.25 for Pythia): RoPE rotates only
  the first ``rotary_pct * head_dim`` dims of each head, the rest pass
  through position-free;
- LayerNorm (scale+bias) everywhere, exact (erf) GELU MLP with biases,
  fused QKV, MHA (no GQA), untied ``embed_in`` / ``embed_out``.

The fused QKV is stored ``[L, E, 3, H*D]`` (gpt2's layout) so the
trailing head dim shards over tp as one named axis; the HF checkpoint's
per-head-interleaved ``query_key_value`` layout is de-interleaved at
conversion time (``hf_convert._map_neox``).
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
from ..ops.rope import apply_rope
from .gpt2 import _layernorm


@dataclasses.dataclass(frozen=True)
class NeoXConfig:
    vocab_size: int = 50304
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_layers: int = 24
    num_heads: int = 16
    max_position_embeddings: int = 2048
    rotary_pct: float = 0.25
    rope_theta: float = 10000.0
    rope_scaling: Optional[tuple] = None  # frozen HF rope_scaling (ops/rope.py)
    layer_norm_eps: float = 1e-5
    use_parallel_residual: bool = True
    act_fn: str = "gelu"            # exact erf gelu (HF hidden_act="gelu")
    dtype: Any = jnp.bfloat16       # activation/compute dtype
    param_dtype: Any = jnp.float32  # storage dtype

    @property
    def head_size(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def rotary_ndims(self) -> int:
        n = int(self.head_size * self.rotary_pct)
        return n - (n % 2)  # the half-rotation needs an even count

    def num_params(self) -> int:
        e, f, v, l = (self.hidden_size, self.intermediate_size,
                      self.vocab_size, self.num_layers)
        per_layer = (3 * e * e + 3 * e        # fused qkv
                     + e * e + e              # out proj
                     + e * f + f + f * e + e  # mlp
                     + 4 * e)                 # two layernorms
        return 2 * v * e + l * per_layer + 2 * e  # embed_in/out + final ln


def init(config: NeoXConfig, rng: jax.Array) -> dict:
    e, f, v, l = (config.hidden_size, config.intermediate_size,
                  config.vocab_size, config.num_layers)
    keys = iter(jax.random.split(rng, 8))

    def dense(key, shape):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(config.param_dtype)

    def ln(shape):
        return {"scale": jnp.ones(shape, config.param_dtype),
                "bias": jnp.zeros(shape, config.param_dtype)}

    return {
        "embed_in": dense(next(keys), (v, e)),
        "layers": {
            "ln1": ln((l, e)),
            "attn": {
                # [l, e, 3, e]: trailing fused-head dim shards over tp as
                # one axis (see gpt2.py's wqkv layout rationale)
                "wqkv": dense(next(keys), (l, e, 3, e)),
                "bqkv": jnp.zeros((l, 3, e), config.param_dtype),
                "wo": dense(next(keys), (l, e, e)),
                "bo": jnp.zeros((l, e), config.param_dtype),
            },
            "ln2": ln((l, e)),
            "mlp": {
                "wi": dense(next(keys), (l, e, f)),
                "bi": jnp.zeros((l, f), config.param_dtype),
                "wo": dense(next(keys), (l, f, e)),
                "bo": jnp.zeros((l, e), config.param_dtype),
            },
        },
        "lnf": ln((e,)),
        "embed_out": dense(next(keys), (e, v)),
    }


def param_logical_axes(config: NeoXConfig) -> dict:
    del config
    ln_l = {"scale": ("layers", "embed_vector"), "bias": ("layers", "embed_vector")}
    return {
        "embed_in": ("vocab", "embed"),
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
        "embed_out": ("embed", "vocab"),
    }


ACT_FNS = {
    "gelu": partial(jax.nn.gelu, approximate=False),      # HF "gelu" (erf)
    "gelu_tanh": partial(jax.nn.gelu, approximate=True),  # HF gelu_new
}


def _rope_partial(x: jnp.ndarray, positions: jnp.ndarray, config) -> jnp.ndarray:
    """NeoX partial rotary: rotate the first ``rotary_ndims`` dims of each
    head (frequencies computed over ``rotary_ndims``, matching HF
    ``GPTNeoXRotaryEmbedding`` — which also computes any rope_scaling at the
    partial dim, HF's partial_rotary_factor), pass the rest through."""
    theta, rotary_dim = config.rope_theta, config.rotary_ndims
    rs = getattr(config, "rope_scaling", None)
    mp = config.max_position_embeddings
    if rotary_dim >= x.shape[-1]:
        return apply_rope(x, positions, theta, rs, mp)
    rot, passthrough = x[..., :rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([apply_rope(rot, positions, theta, rs, mp),
                            passthrough], axis=-1)


@jax.named_scope("attn")
def _attn_branch(config, y, layer, positions, attn_impl,
                 standard_layout=True, attend_override=None):
    """ln'd input -> fused QKV -> partial rope -> attention -> out proj
    (no residual, no psum — the block owns those). ``attend_override``
    follows llama.attention_sublayer's decode contract: with it the call
    returns ``(out, aux)``."""
    b, s, e = y.shape
    d = config.head_size
    cdt = config.dtype
    wqkv = layer["attn"]["wqkv"]          # [e, 3, e/tp] under manual tp
    e_loc = wqkv.shape[-1]
    h_loc = e_loc // d
    qkv = (jnp.einsum("bse,eqh->bsqh", y, wqkv.astype(cdt))
           + layer["attn"]["bqkv"].astype(cdt))
    q = qkv[:, :, 0].reshape(b, s, h_loc, d)
    k = qkv[:, :, 1].reshape(b, s, h_loc, d)
    v = qkv[:, :, 2].reshape(b, s, h_loc, d)
    q = _rope_partial(q, positions, config)
    k = _rope_partial(k, positions, config)
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
def _mlp_branch(config, y, layer):
    """ln'd input -> gelu MLP (no residual, no psum, no row bias)."""
    cdt = config.dtype
    act_fn = ACT_FNS[config.act_fn]
    y = act_fn(y @ layer["mlp"]["wi"].astype(cdt)
               + layer["mlp"]["bi"].astype(cdt))
    # tagged for REMAT_POLICIES["attn_mlp"] (same role as llama's mlp_act)
    y = checkpoint_name(y, "mlp_act")
    return y @ layer["mlp"]["wo"].astype(cdt)


def _block(config: NeoXConfig, x, layer, positions, attn_impl,
           standard_layout=True, tp_axis=None):
    """One parallel-residual block (or sequential when the config says so).

    ``tp_axis``: set inside a shard_map region where tp is a *manual* axis
    (the pipeline schedule): wqkv/bqkv/wi/bi arrive column-sharded (local
    head / mlp slices, inferred from shapes), wo / mlp wo row-sharded. In
    the parallel-residual case the two row-parallel partial sums are added
    BEFORE one psum — the block's structural communication advantage."""
    cdt = config.dtype

    def attn_branch(y):
        return _attn_branch(config, y, layer, positions, attn_impl,
                            standard_layout)

    def mlp_branch(y):
        return _mlp_branch(config, y, layer)

    biases = (layer["attn"]["bo"].astype(cdt) + layer["mlp"]["bo"].astype(cdt))
    if config.use_parallel_residual:
        # x + attn(ln1 x) + mlp(ln2 x): one residual update; under manual tp
        # the two partial sums share ONE all-reduce (row biases, replicated,
        # are added after it)
        update = (attn_branch(_layernorm(x, layer["ln1"], config.layer_norm_eps))
                  + mlp_branch(_layernorm(x, layer["ln2"], config.layer_norm_eps)))
        if tp_axis is not None:
            update = _psum(update, tp_axis)
        return x + update + biases
    # sequential (use_parallel_residual=False checkpoints): gpt2-shaped
    attn = attn_branch(_layernorm(x, layer["ln1"], config.layer_norm_eps))
    if tp_axis is not None:
        attn = _psum(attn, tp_axis)
    x = x + attn + layer["attn"]["bo"].astype(cdt)
    mlp = mlp_branch(_layernorm(x, layer["ln2"], config.layer_norm_eps))
    if tp_axis is not None:
        mlp = _psum(mlp, tp_axis)
    return x + mlp + layer["mlp"]["bo"].astype(cdt)


@jax.named_scope("embed")
def embed_tokens(config: NeoXConfig, params: dict, input_ids: jnp.ndarray,
                 positions: jnp.ndarray) -> jnp.ndarray:
    """Token embedding (pipeline stage-0 entry); rope happens inside blocks."""
    del positions
    return jnp.take(params["embed_in"], input_ids, axis=0).astype(config.dtype)


def output_weights(config: NeoXConfig, params: dict) -> jnp.ndarray:
    """[E, V] untied output projection in compute dtype."""
    return params["embed_out"].astype(config.dtype)


@jax.named_scope("embed")
def tp_embed(config: NeoXConfig, params: dict, input_ids: jnp.ndarray,
             positions: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Stage-0 embedding when tp is a manual axis: megatron vocab
    parallelism over the sharded ``embed_in`` table."""
    del positions
    from ..ops.vocab_parallel import vocab_parallel_embed

    return vocab_parallel_embed(params["embed_in"].astype(config.dtype),
                                input_ids, axis)


@jax.named_scope("final_norm")
def final_hidden(config: NeoXConfig, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    return _layernorm(x, params["lnf"], config.layer_norm_eps)


@jax.named_scope("loss_head")
def lm_head_logits(config: NeoXConfig, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Final LN + untied output projection (pipeline last-stage exit)."""
    return jnp.dot(final_hidden(config, params, x), output_weights(config, params),
                   preferred_element_type=jnp.float32)


def apply(
    config: NeoXConfig,
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
    """Forward -> float32 logits [B, S, V] (or final-normed hiddens [B, S, E]
    when ``return_hidden``, for chunked losses). Same contract as
    ``llama.apply`` — explicit ``positions`` required when the sequence dim
    is sharded (context parallelism)."""
    standard_layout = positions is None
    if positions is None:
        positions = jnp.arange(input_ids.shape[1])[None, :]
    positions = jnp.broadcast_to(positions, input_ids.shape)

    x = embed_tokens(config, params, input_ids, positions)

    block = partial(_block, config, positions=positions, attn_impl=attn_impl,
                    standard_layout=standard_layout)

    def constrained_block(carry, layer_params):
        y = block(carry, layer_params)
        if activation_sharding is not None:
            y = jax.lax.with_sharding_constraint(y, activation_sharding)
        return y

    def scan_body(carry, layer_params):
        return constrained_block(carry, layer_params), None

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
# contract); the block math here is the parallel residual (x + attn + mlp
# in ONE update) with partial rope.
# ---------------------------------------------------------------------------

def _cached_block(config, x, layer, positions, attend_override):
    """Parallel- or sequential-residual block through the paged attend;
    returns (x, pools)."""
    eps = config.layer_norm_eps
    cdt = config.dtype
    attn, pools = _attn_branch(config, _layernorm(x, layer["ln1"], eps),
                               layer, positions, "xla",
                               attend_override=attend_override)
    if config.use_parallel_residual:
        update = attn + _mlp_branch(config, _layernorm(x, layer["ln2"], eps),
                                    layer)
        biases = (layer["attn"]["bo"].astype(cdt)
                  + layer["mlp"]["bo"].astype(cdt))
        return x + update + biases, pools
    x = x + attn + layer["attn"]["bo"].astype(cdt)
    mlp = _mlp_branch(config, _layernorm(x, layer["ln2"], eps), layer)
    return x + mlp + layer["mlp"]["bo"].astype(cdt), pools


def paged_decode_step(config: NeoXConfig, params: dict,
                      token_ids: jnp.ndarray, positions: jnp.ndarray,
                      cache: dict, attend, last_index=None,
                      all_logits=False):
    """Paged multi-request decode/chunk step (llama.paged_decode_step
    contract) through ``_cached_block``, the parallel-/sequential-
    residual body. ``all_logits=True`` keeps
    every position's logits (speculative verification)."""
    from .llama import (paged_logits_at, paged_positions,
                        scan_paged_layers)

    pos2d = paged_positions(token_ids, positions)
    x = embed_tokens(config, params, token_ids, pos2d)

    def body(x, pools, layer, i, *_):
        def override(q, k, v, *, window, scale, softcap):
            del window, scale, softcap  # no neox attention extras
            return attend(q, k, v, *pools, i)

        x, pools = _cached_block(config, x, layer, pos2d, override)
        return x, pools, None

    x, pools, _ = scan_paged_layers(body, x, params["layers"], cache)
    return (paged_logits_at(lm_head_logits, config, params, x, last_index,
                            all_logits), pools)


# ---------------------------------------------------------------------------
# Presets (shapes from the Pythia suite / NeoX-20B model cards; the
# reference reaches these via AutoModelForCausalLM, `01:57`).
# ---------------------------------------------------------------------------

PRESETS = {
    "neox-debug": NeoXConfig(vocab_size=512, hidden_size=64, intermediate_size=256,
                             num_layers=2, num_heads=4, max_position_embeddings=256),
    "pythia-70m": NeoXConfig(vocab_size=50304, hidden_size=512, intermediate_size=2048,
                             num_layers=6, num_heads=8),
    "pythia-160m": NeoXConfig(vocab_size=50304, hidden_size=768, intermediate_size=3072,
                              num_layers=12, num_heads=12),
    "pythia-410m": NeoXConfig(vocab_size=50304, hidden_size=1024, intermediate_size=4096,
                              num_layers=24, num_heads=16),
    "pythia-1.4b": NeoXConfig(vocab_size=50304, hidden_size=2048, intermediate_size=8192,
                              num_layers=24, num_heads=16),
    "pythia-6.9b": NeoXConfig(vocab_size=50432, hidden_size=4096, intermediate_size=16384,
                              num_layers=32, num_heads=32),
    "gpt-neox-20b": NeoXConfig(vocab_size=50432, hidden_size=6144, intermediate_size=24576,
                               num_layers=44, num_heads=64),
}
