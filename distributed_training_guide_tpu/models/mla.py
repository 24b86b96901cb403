"""Latent-attention (MLA) decoder with routed experts and a shared expert:
the DeepSeek-V2/V3 layer, as Mistral-Small-4-119B's config spells it.

Pre-norm block, ``x += attn(norm(x))``, ``x += ffn(norm(x))``.

Attention (:func:`latent_attention_sublayer`). Queries go through a
low-rank path, ``c_q = RMSNorm(x W_dq)``, ``[q_nope | q_rope] = c_q W_uq``
per head; keys and values through another, ``[c_kv | k_r] = x W_dkv``,
``c_kv <- RMSNorm(c_kv)``, ``[k_nope | v] = c_kv W_ukv`` per head, with ONE
rope key ``k_r`` for all heads. Rope (YaRN, ``ops/rope.py``) turns ``q_rope``
and ``k_r``, on adjacent pairs under ``rope_interleave``. The score is
``scale * g(t) * q . [k_nope | k_r]`` with ``scale = (nope + rope)^-0.5 *
m(factor, mscale_all_dim)^2`` and ``g(t) = 1 + beta ln(1 + floor(t /
original_max))`` on the query at its own position (``query_scale_beta``,
the config's ``llama_4_scaling_beta``).

What a cached token costs is the latent row ``(c_kv after its norm, k_r
after rope)``, not per-head k and v: ``kv_layout`` tells the page pool
(``serve/kv_pages.py``). Two forms compute the same sum over it:

- DECOMPRESSED: expand the rows to per-head k and v and attend as usual
  (the full forward, a prefill chunk);
- ABSORBED: fold ``W_uk`` into the query and ``W_uv`` into the output,
  ``q~_h = W_uk,h q_nope_h``, ``score = q~_h . c_kv + q_rope_h . k_r``,
  ``o_h = W_uv,h^T sum p c_kv``, so every head attends the one stored row
  (the decode step, through ``ops/paged_decode.paged_latent_attend``).

FFN: ``models/moe._moe_ffn`` with the sigmoid router and its selection
bias, an ungated shared expert, and ``experts_held`` (one chip's share of
the routed experts: it routes over all of them and adds its own experts'
part). Embedding, norms, head and the layer scans are the llama family's.

Serving only: ``apply`` is the plain forward (tests, the sampler's
recompute path); the Trainer's options are not taken.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import llama
from .llama import _rmsnorm
from .moe import _moe_ffn, experts_held, experts_in_place
from ..ops.attention import multihead_attention
from ..ops.paged_decode import ROWS_ALL_HEADS
from ..ops.rope import _scaling_dict, apply_rope, position_query_scale

# what ServeEngine refuses for this family, by the option's name: none of
# these paths knows a latent pool or a held share of experts
SERVE_REFUSES = {
    "kv_dtype='int8'": "the latent pool is stored in float",
    "weight_dtype='int8'": "serve/weights.py selects llama leaves only",
    "max_adapters": "the LoRA hooks wrap llama's projections",
    "speculate": "the verify tile has no latent attend",
    "host_tier_bytes": "the tier's gather assumes k and v of one shape",
    "plan / shard_kv": "the tp serve mesh splits kv heads; there is one "
                       "latent row",
    "disaggregation": "the handoff moves k and v pages of one shape",
}


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    latent_cache = True     # the pool holds latent rows (kv_pages.is_latent)

    vocab_size: int = 131072
    hidden_size: int = 4096
    num_layers: int = 36
    num_heads: int = 32
    q_lora_rank: int = 1024
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 2048             # width of every expert
    shared_expert_intermediate: Optional[int] = 2048
    num_experts: int = 128                    # the router's width
    experts_per_token: int = 4
    # (first, count): the routed experts whose weights this program holds
    # (None = all): one chip's share of an expert-parallel layer
    experts_held: Optional[tuple] = None
    router_act: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    moe_dispatch: str = "ragged"              # a held share is ragged only
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.0
    rope_theta: float = 10000.0
    rope_scaling: Optional[tuple] = None      # frozen HF dict (ops/rope.py)
    rope_interleave: bool = True
    query_scale_beta: float = 0.0             # llama_4_scaling_beta
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def head_size(self) -> int:
        """The width of a head's OUTPUT (what ``wo`` takes per head)."""
        return self.v_head_dim

    @property
    def rope_width(self) -> int:
        """The rope key's row in the pool: whole 128-lane tiles."""
        return -(-self.qk_rope_head_dim // 128) * 128

    def kv_layout(self) -> dict:
        """One cached token in one layer (``serve/kv_pages.pool_layout``)."""
        return {"k": (1, self.rope_width), "v": (1, self.kv_lora_rank)}

    def softmax_scale(self) -> float:
        scale = self.qk_head_dim ** -0.5
        s = _scaling_dict(self.rope_scaling) if self.rope_scaling else {}
        factor, all_dim = s.get("factor", 1.0), s.get("mscale_all_dim", 0)
        if factor > 1 and all_dim:
            scale *= (0.1 * all_dim * math.log(factor) + 1.0) ** 2
        return scale

    def _layer_params(self, n_experts: int) -> int:
        e, h = self.hidden_size, self.num_heads
        attn = (e * self.q_lora_rank + self.q_lora_rank
                + self.q_lora_rank * h * self.qk_head_dim
                + e * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank
                + self.kv_lora_rank * h * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + h * self.v_head_dim * e)
        ffn = (e * self.num_experts + self.num_experts
               + n_experts * 3 * e * self.intermediate_size
               + 3 * e * (self.shared_expert_intermediate or 0))
        return attn + ffn + 2 * e

    def num_params(self) -> int:
        """Parameters HELD (``experts_held`` experts a layer)."""
        top = self.vocab_size * self.hidden_size * (
            1 if self.tie_word_embeddings else 2) + self.hidden_size
        return top + self.num_layers * self._layer_params(
            experts_held(self)[1])

    def num_active_params(self) -> int:
        top = self.vocab_size * self.hidden_size * (
            1 if self.tie_word_embeddings else 2) + self.hidden_size
        return top + self.num_layers * self._layer_params(
            self.experts_per_token)


def init(config: MlaMoeConfig, rng: jax.Array) -> dict:
    e, f, v, l = (config.hidden_size, config.intermediate_size,
                  config.vocab_size, config.num_layers)
    h, ex = config.num_heads, config.num_experts
    held = experts_held(config)[1]
    ql, kl = config.q_lora_rank, config.kv_lora_rank
    keys = iter(jax.random.split(rng, 20))
    pdt = config.param_dtype

    def dense(shape):
        return (0.02 * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(pdt)

    attn = {
        "wq_a": dense((l, e, ql)), "q_a_norm": jnp.ones((l, ql), pdt),
        "wq_b": dense((l, ql, h * config.qk_head_dim)),
        "wkv_a": dense((l, e, kl + config.qk_rope_head_dim)),
        "kv_a_norm": jnp.ones((l, kl), pdt),
        "wkv_b": dense((l, kl, h * (config.qk_nope_head_dim
                                    + config.v_head_dim))),
        "wo": dense((l, h * config.v_head_dim, e)),
    }
    moe = {
        "router": dense((l, e, ex)), "router_bias": dense((l, ex)),
        "gate": dense((l, held, e, f)), "up": dense((l, held, e, f)),
        "down": dense((l, held, f, e)),
    }
    if config.shared_expert_intermediate:
        fs = config.shared_expert_intermediate
        moe.update(shared_gate_proj=dense((l, e, fs)),
                   shared_up=dense((l, e, fs)), shared_down=dense((l, fs, e)))
    params = {
        "embed": {"embedding": dense((v, e))},
        "layers": {"attn": attn, "moe": moe,
                   "input_norm": jnp.ones((l, e), pdt),
                   "post_attn_norm": jnp.ones((l, e), pdt)},
        "final_norm": jnp.ones((e,), pdt),
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = dense((e, v))
    return params


def param_logical_axes(config: MlaMoeConfig) -> dict:
    """Logical axes (replicated low-rank leaves; heads on the up-projections
    and ``wo``; experts on the expert dim). No serve mesh runs this family
    yet (``SERVE_REFUSES``)."""
    attn = {
        "wq_a": ("layers", "embed", None), "q_a_norm": ("layers", None),
        "wq_b": ("layers", None, "heads"),
        "wkv_a": ("layers", "embed", None), "kv_a_norm": ("layers", None),
        "wkv_b": ("layers", None, "heads"), "wo": ("layers", "heads", "embed"),
    }
    moe = {
        "router": ("layers", "embed", "experts_vector"),
        "router_bias": ("layers", "experts_vector"),
        "gate": ("layers", "experts", "embed", "mlp"),
        "up": ("layers", "experts", "embed", "mlp"),
        "down": ("layers", "experts", "mlp", "embed"),
    }
    if config.shared_expert_intermediate:
        moe.update(shared_gate_proj=("layers", "embed", "mlp"),
                   shared_up=("layers", "embed", "mlp"),
                   shared_down=("layers", "mlp", "embed"))
    axes = {
        "embed": {"embedding": ("vocab", "embed")},
        "layers": {"attn": attn, "moe": moe,
                   "input_norm": ("layers", "embed_vector"),
                   "post_attn_norm": ("layers", "embed_vector")},
        "final_norm": ("embed_vector",),
    }
    if not config.tie_word_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# ---------------------------------------------------------------------------
# the attention sublayer
# ---------------------------------------------------------------------------

def _rope(config, x, positions):
    return apply_rope(x, positions, config.rope_theta, config.rope_scaling,
                      config.max_position_embeddings,
                      interleave=config.rope_interleave)


@jax.named_scope("latent_proj")
def latent_projections(config: MlaMoeConfig, h: jnp.ndarray, p: dict,
                       positions: jnp.ndarray):
    """The two low-rank paths of one layer on normed ``h`` [B, S, E]:
    ``(q_nope [B,S,H,n], q_rope [B,S,H,r], c_kv [B,S,C], k_r [B,S,r])``,
    rope and ``g(t)`` applied: ``(c_kv, k_r)`` is the row the cache holds."""
    cdt = config.dtype
    b, s, _ = h.shape
    eps = config.rms_norm_eps
    cq = _rmsnorm(h @ p["wq_a"].astype(cdt), p["q_a_norm"], eps)
    q = (cq @ p["wq_b"].astype(cdt)).reshape(b, s, config.num_heads,
                                             config.qk_head_dim)
    if config.query_scale_beta:
        original = _scaling_dict(config.rope_scaling)[
            "original_max_position_embeddings"]
        g = position_query_scale(positions, config.query_scale_beta, original)
        q = (q.astype(jnp.float32) * g[..., None, None]).astype(cdt)
    q_nope = q[..., :config.qk_nope_head_dim]
    q_rope = _rope(config, q[..., config.qk_nope_head_dim:], positions)
    kv = h @ p["wkv_a"].astype(cdt)
    c_kv = _rmsnorm(kv[..., :config.kv_lora_rank], p["kv_a_norm"], eps)
    k_r = _rope(config, kv[..., None, config.kv_lora_rank:], positions)[:, :, 0]
    return q_nope, q_rope, c_kv, k_r


def _up_weights(config: MlaMoeConfig, p: dict):
    """``W_ukv`` by head: ``(w_uk [C, H, n], w_uv [C, H, v])``."""
    w = p["wkv_b"].astype(config.dtype).reshape(
        config.kv_lora_rank, config.num_heads,
        config.qk_nope_head_dim + config.v_head_dim)
    return w[..., :config.qk_nope_head_dim], w[..., config.qk_nope_head_dim:]


@jax.named_scope("latent_proj")
def expand_latent(config: MlaMoeConfig, p: dict, c_kv: jnp.ndarray,
                  k_r: jnp.ndarray):
    """Decompress latent rows ``c_kv [B, N, C]``, ``k_r [B, N, r]`` to
    per-head ``(k [B, N, H, n + r], v [B, N, H, v])``."""
    w_uk, w_uv = _up_weights(config, p)
    k_nope = jnp.einsum("bnc,chd->bnhd", c_kv, w_uk)
    v = jnp.einsum("bnc,chd->bnhd", c_kv, w_uv)
    k_rope = jnp.broadcast_to(k_r[:, :, None, :],
                              (*k_nope.shape[:3], k_r.shape[-1]))
    return jnp.concatenate([k_nope, k_rope], axis=-1), v


def _cache_rows(config: MlaMoeConfig, c_kv, k_r):
    """``(k_new, v_new)`` as the pool's leaves take them: the rope key padded
    to its lane tiles, one "head" each."""
    pad = config.rope_width - config.qk_rope_head_dim
    k_new = jnp.pad(k_r, ((0, 0), (0, 0), (0, pad)))[:, :, None, :]
    return k_new, c_kv[:, :, None, :]


@jax.named_scope("attn")
def latent_attention_sublayer(config: MlaMoeConfig, x: jnp.ndarray, p: dict,
                              norm_scale, positions: jnp.ndarray,
                              attend=None):
    """norm -> latent attention -> output projection (the caller adds the
    residual). Returns ``(out, pools)``.

    ``attend`` None (the full forward): causal attention over the call's own
    tokens, decompressed; nothing is cached and ``pools`` is None. Else the
    serving engine's paged hook ``attend(q, k_new, v_new, **kw) -> (attn,
    pools)`` (``serve/kv_pages.paged_attend`` with the stacked pools and the
    layer's index bound), which writes the new latent rows ``(k_new,
    v_new)`` [B, S, 1, *]: a query tile of up to ``ROWS_ALL_HEADS`` rows
    (the decode step) goes ABSORBED, a larger one (a prefill chunk)
    DECOMPRESSED; ``pools`` is the updated pools."""
    cdt = config.dtype
    b, s, _ = x.shape
    h = _rmsnorm(x, norm_scale, config.rms_norm_eps)
    q_nope, q_rope, c_kv, k_r = latent_projections(config, h, p, positions)
    scale = config.softmax_scale()
    rope = config.qk_rope_head_dim
    if attend is None:
        k, v = expand_latent(config, p, c_kv, k_r)
        attn = multihead_attention(
            jnp.concatenate([q_nope, q_rope], axis=-1), k, v, causal=True,
            positions=positions, kv_positions=positions, impl="xla",
            standard_layout=False, scale=scale)
        out = attn.reshape(b, s, -1).astype(cdt) @ p["wo"].astype(cdt)
        return out, None
    k_new, v_new = _cache_rows(config, c_kv, k_r)
    if s * config.num_heads <= ROWS_ALL_HEADS:
        with jax.named_scope("latent_proj"):
            w_uk, w_uv = _up_weights(config, p)
            q_abs = jnp.concatenate(
                [jnp.einsum("bshd,chd->bshc", q_nope, w_uk), q_rope], axis=-1)
        lat, pools = attend(q_abs, k_new, v_new, scale=scale,
                            latent_rope=rope)
        with jax.named_scope("latent_proj"):
            attn = jnp.einsum("bshc,chd->bshd", lat.astype(cdt), w_uv)
    else:
        attn, pools = attend(
            jnp.concatenate([q_nope, q_rope], axis=-1), k_new, v_new,
            scale=scale, latent_rope=rope,
            expand=lambda c, r: expand_latent(config, p, c, r))
    out = attn.reshape(b, s, -1).astype(cdt) @ p["wo"].astype(cdt)
    return out, pools


def _ffn(config: MlaMoeConfig, x, layer, return_counts=False,
         layer_index=None):
    """``layer_index``: ``layer["moe"]`` holds the routed-expert leaves of
    all layers (``moe.experts_in_place``), this layer's at that index."""
    with jax.named_scope("experts"):   # the FFN's pre-norm is its own
        h = _rmsnorm(x, layer["post_attn_norm"], config.rms_norm_eps)
    out = _moe_ffn(config, h, layer["moe"], no_drop=True,
                   return_counts=return_counts, layer_index=layer_index)
    return (x + out[0], out[3]) if return_counts else x + out[0]


# ---------------------------------------------------------------------------
# forwards
# ---------------------------------------------------------------------------

embed_tokens = llama.embed_tokens
lm_head_logits = llama.lm_head_logits
final_hidden = llama.final_hidden
output_weights = llama.output_weights


def apply(config: MlaMoeConfig, params: dict, input_ids: jnp.ndarray,
          positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Plain forward -> logits [B, S, V] float32."""
    if positions is None:
        positions = jnp.arange(input_ids.shape[1])[None, :]
    positions = jnp.broadcast_to(positions, input_ids.shape)
    x = embed_tokens(config, params, input_ids, positions)

    def body(x, layer):
        attn, _ = latent_attention_sublayer(
            config, x, layer["attn"], layer["input_norm"], positions)
        return _ffn(config, x + attn, layer), None

    with jax.named_scope("layers"):
        x, _ = jax.lax.scan(body, x, params["layers"])
    return lm_head_logits(config, params, x)


def paged_decode_step(config: MlaMoeConfig, params: dict,
                      token_ids: jnp.ndarray, positions: jnp.ndarray,
                      cache: dict, attend, last_index=None,
                      all_logits=False):
    """Paged multi-request decode/chunk step (``llama.paged_decode_step``'s
    contract) over the stacked latent pools ``{"k": rope keys, "v": latent
    rows}``, a carry of ``llama.scan_paged_layers``; the routing counts are
    the scan's one per-layer output.
    The returned cache also carries ``"routing"``: int32 ``[pairs routed,
    pairs held here, experts touched, the fullest expert's pairs]``, the
    first three summed over the layers, which the decode program hands to
    the host with its tokens."""
    pos2d = llama.paged_positions(token_ids, positions)
    x = embed_tokens(config, params, token_ids, pos2d)
    layers, experts = experts_in_place(config, params["layers"])

    def body(x, pools, layer, i, *_):
        def bound(q, k_new, v_new, **kw):
            return attend(q, k_new, v_new, *pools, i, **kw)

        attn, pools = latent_attention_sublayer(
            config, x, layer["attn"], layer["input_norm"], pos2d, bound)
        layer = {**layer, "moe": {**layer["moe"], **experts}}
        x, counts = _ffn(config, x + attn, layer, return_counts=True,
                         layer_index=i if experts else None)
        return x, pools, counts

    x, pools, counts = llama.scan_paged_layers(body, x, layers, cache)
    routing = jnp.concatenate([jnp.sum(counts[:, :3], axis=0),
                               jnp.max(counts[:, 3:], axis=0)])
    return (llama.paged_logits_at(lm_head_logits, config, params, x,
                                  last_index, all_logits),
            {**pools, "routing": routing})


def _yarn(factor, original, **extra) -> tuple:
    from ..ops.rope import freeze_rope_scaling

    return freeze_rope_scaling({
        "rope_type": "yarn", "factor": factor, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": original, **extra})


PRESETS = {
    "mla-moe-debug": MlaMoeConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=32,
        shared_expert_intermediate=32, num_experts=8, experts_per_token=2,
        rope_scaling=_yarn(4.0, 64), query_scale_beta=0.1,
        max_position_embeddings=256),
    # mistralai/Mistral-Small-4-119B-2603 config.json (language model)
    "mistral-small-4-119b": MlaMoeConfig(
        rope_scaling=_yarn(128.0, 8192), query_scale_beta=0.1),
}
