"""Brumby (manifestai ``brumby``; Brumby-14B-Base): Qwen3's decoder block
with softmax attention replaced by POWER RETENTION in EVERY layer. Layer
``l`` is ``x += Ret(norm(x))``, ``x += SwiGLU(norm(x))`` (pre-norm), a final
RMSNorm, an UNTIED head; no bias anywhere, no attention layer at all.

Retention mixer (:func:`retention_sublayer`; ``Hq`` query heads on ``Hkv`` kv
heads of width ``d``, 40 on 8 of 128): ``q, k, v = u W_q, u W_k, u W_v``;
per-head RMSNorm with a learned ``[d]`` scale on ``q`` and on ``k``, THEN
rope (the Qwen3 block's order); a gate a kv head a token, ``log gamma =
logsigmoid(u W_g + GATE_SHIFT)`` in float32 (``gamma`` = 0.999 at ``u W_g`` =
0); the recurrence of ``ops/retention.py`` (weights ``(q . k)^2`` under the
cumulative gates, the output divided by the weights' own sum, no softmax and
no epsilon); ``out = concat_j(o_j) W_o``. No output gate, no output norm.

What a layer must remember of a sequence is ``S`` (``Hkv x 9,216 x 128``
float32, 37.7 MB) and the normaliser ``Z`` (``Hkv x 128 x 128`` float32, 0.5
MB), whatever the sequence's length, and NOTHING a token: the family has no k
or v page (``kv_layout()`` is empty, ``num_kv_layers`` 0). Both lie in the
pool's STATE CLASS (``sequence_state_layout``; ``serve/kv_pages.py``): a
block a live SEQUENCE whose id rides each program beside the slot's block
table. The decode step updates the block where it lies (``retention_step``),
a prefill chunk likewise (``retention_chunk``); a sequence that starts at
position 0 starts from zeros whatever its block's last owner left. The
sequence lives in the state form from its first token: the authors' kernels
switch a short sequence to the attention form over cached k and v, which
this family does not have (PERF.md section 7).

The layers are WALKED like ``models/jamba.py``'s, every matrix a per-layer
leaf of a LIST, the two norms stacks.

Serving and the plain forward only, like ``models/mla.py``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import llama
from .llama import _rmsnorm, mlp_sublayer
from .state_class import STATE_CLASS_REFUSES
from ..ops.retention import retention_chunk, retention_step, state_shapes
from ..ops.rope import apply_rope

GATE_SHIFT = 6.906768       # logit(0.999): the gate of a zero pre-activation

# what ServeEngine refuses for this family, by the option's name, each with
# the module that would have to change: what the state class refuses for
# every family that keeps one, and nothing of its own
SERVE_REFUSES = STATE_CLASS_REFUSES


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    hidden_size: int = 5120
    num_layers: int = 40
    num_heads: int = 40
    num_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 17408
    max_position_embeddings: int = 32768
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads do not divide "
                             f"over {self.num_kv_heads} kv heads")

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def num_kv_layers(self) -> int:
        """Layers with k and v pages: none."""
        return 0

    def kv_layout(self) -> dict:
        """One cached token in one attending layer
        (``serve/kv_pages.pool_layout``): there is no such layer, the pool
        has no k and no v leaf, and a page holds nothing."""
        return {}

    def sequence_state_layout(self) -> dict:
        """The state class (``serve/kv_pages.sequence_state_layout``):
        ``{leaf: (shape of one sequence's block, layers first; storage)}``.
        Float32 whatever the pool's dtype: not an option
        (``ops/retention.py`` refuses a narrower pool)."""
        s, z = state_shapes(self.num_kv_heads, self.head_dim)
        return {"seq_state": ((self.num_layers, *s), "fp32"),
                "seq_norm": ((self.num_layers, *z), "fp32")}

    def num_params(self) -> int:
        e = self.hidden_size
        ffn = 3 * e * self.intermediate_size + 2 * e        # and two norms
        top = self.vocab_size * e * (1 if self.tie_word_embeddings else 2) + e
        return top + self.num_layers * (_size(_mixer_shapes(self)) + ffn)


def _mixer_shapes(config: BrumbyConfig) -> dict:
    e, d = config.hidden_size, config.head_dim
    hq, hkv = config.num_heads * d, config.num_kv_heads * d
    return {"wq": (e, hq), "wk": (e, hkv), "wv": (e, hkv),
            "wg": (e, config.num_kv_heads), "wo": (hq, e),
            "q_norm": (d,), "k_norm": (d,)}


def _mlp_shapes(config: BrumbyConfig) -> dict:
    e, f = config.hidden_size, config.intermediate_size
    return {"gate": (e, f), "up": (e, f), "down": (f, e)}


def _size(shapes: dict) -> int:
    return sum(math.prod(s) for s in shapes.values())


def init(config: BrumbyConfig, rng: jax.Array) -> dict:
    e, v, n = config.hidden_size, config.vocab_size, config.num_layers
    keys = iter(jax.random.split(rng, 4 + 12 * n))
    pdt = config.param_dtype

    def dense(shape, std=0.02):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(pdt)

    def mixer():
        p = {name: dense(shape)
             for name, shape in _mixer_shapes(config).items()}
        p["q_norm"], p["k_norm"] = (jnp.ones_like(p[name])
                                    for name in ("q_norm", "k_norm"))
        return p

    params = {
        "embed": {"embedding": dense((v, e))},
        "layers": {
            "mixer_norm": jnp.ones((n, e), pdt),
            "ffn_norm": jnp.ones((n, e), pdt),
            "mixer": [mixer() for _ in range(n)],
            "mlp": [{name: dense(shape) for name, shape
                     in _mlp_shapes(config).items()} for _ in range(n)],
        },
        "final_norm": jnp.ones((e,), pdt),
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = dense((e, v))
    return params


def param_logical_axes(config: BrumbyConfig) -> dict:
    """Logical axes: a list of per-layer leaves for the mixers and the FFN,
    stacks (leading axis ``layers``) for the two norms. No serve mesh runs
    this family yet (``SERVE_REFUSES``)."""
    mixer = {"wq": ("embed", "heads"), "wk": ("embed", "kv"),
             "wv": ("embed", "kv"), "wg": ("embed", None),
             "wo": ("heads", "embed"), "q_norm": (None,), "k_norm": (None,)}
    mlp = {"gate": ("embed", "mlp"), "up": ("embed", "mlp"),
           "down": ("mlp", "embed")}
    axes = {
        "embed": {"embedding": ("vocab", "embed")},
        "layers": {
            "mixer_norm": ("layers", "embed_vector"),
            "ffn_norm": ("layers", "embed_vector"),
            "mixer": [dict(mixer) for _ in range(config.num_layers)],
            "mlp": [dict(mlp) for _ in range(config.num_layers)],
        },
        "final_norm": ("embed_vector",),
    }
    if not config.tie_word_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


@jax.named_scope("attn")
def retention_sublayer(config: BrumbyConfig, x: jnp.ndarray, p: dict,
                       norm_scale, positions: jnp.ndarray, state=None):
    """norm -> power retention -> output projection (the caller adds the
    residual), under the sub-scope ``retention``. x [B, T, E]. ``state``
    None: whole sequences from zeros (the plain forward), returns ``out``.
    Else ``state = (S pool, Z pool, layer, attend)``, the serve path's state
    class and the paged hook that knows each slot's block, start and valid
    tokens: returns ``(out, (S pool, Z pool))``."""
    b, t, _ = x.shape
    cdt, d, eps = config.dtype, config.head_dim, config.rms_norm_eps
    with jax.named_scope("retention"):
        u = _rmsnorm(x, norm_scale, eps)
        q = (u @ p["wq"].astype(cdt)).reshape(b, t, config.num_heads, d)
        k = (u @ p["wk"].astype(cdt)).reshape(b, t, config.num_kv_heads, d)
        v = (u @ p["wv"].astype(cdt)).reshape(b, t, config.num_kv_heads, d)
        log_gamma = jax.nn.log_sigmoid(
            jnp.dot(u, p["wg"].astype(cdt),
                    preferred_element_type=jnp.float32) + GATE_SHIFT)
        q = apply_rope(_rmsnorm(q, p["q_norm"], eps), positions,
                       config.rope_theta, None,
                       config.max_position_embeddings)
        k = apply_rope(_rmsnorm(k, p["k_norm"], eps), positions,
                       config.rope_theta, None,
                       config.max_position_embeddings)
        if state is None:       # a block a sequence, born and dropped here
            shapes = state_shapes(config.num_kv_heads, d)
            pool, norm_pool = (jnp.zeros((1, b, *s), jnp.float32)
                               for s in shapes)
            o, _, _ = retention_chunk(pool, norm_pool, jnp.arange(b), 0, q,
                                      k, v, log_gamma,
                                      fresh=jnp.ones((b,), bool))
        else:
            pool, norm_pool, layer, attend = state
            blocks = attend.state_blocks
            fresh = attend.lengths == 0        # a sequence's first tokens
            if t == 1:
                o, pool, norm_pool = retention_step(
                    pool, norm_pool, blocks, layer, q[:, 0], k[:, 0],
                    v[:, 0], log_gamma[:, 0], fresh)
                o = o[:, None]
            else:
                o, pool, norm_pool = retention_chunk(
                    pool, norm_pool, blocks, layer, q, k, v, log_gamma,
                    fresh, attend.n_valid)
        out = o.reshape(b, t, -1).astype(cdt) @ p["wo"].astype(cdt)
    return out if state is None else (out, (pool, norm_pool))


def _ffn(config: BrumbyConfig, x, layers: dict, l: int):
    """The layer's dense SwiGLU with its pre-norm and its residual."""
    return x + mlp_sublayer(config, x, {"post_attn_norm": layers["ffn_norm"][l],
                                        "mlp": layers["mlp"][l]})


embed_tokens = llama.embed_tokens
lm_head_logits = llama.lm_head_logits
final_hidden = llama.final_hidden
output_weights = llama.output_weights


def apply(config: BrumbyConfig, params: dict, input_ids: jnp.ndarray,
          positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Plain forward over whole sequences -> logits [B, S, V] float32."""
    if positions is None:
        positions = jnp.arange(input_ids.shape[1])[None, :]
    positions = jnp.broadcast_to(positions, input_ids.shape)
    x = embed_tokens(config, params, input_ids, positions)
    layers = params["layers"]
    with jax.named_scope("layers"):
        for l in range(config.num_layers):
            x = x + retention_sublayer(config, x, layers["mixer"][l],
                                       layers["mixer_norm"][l], positions)
            x = _ffn(config, x, layers, l)
    return lm_head_logits(config, params, x)


def paged_decode_step(config: BrumbyConfig, params: dict,
                      token_ids: jnp.ndarray, positions: jnp.ndarray,
                      cache: dict, attend, last_index=None,
                      all_logits=False):
    """Paged multi-request decode/chunk step (``llama.paged_decode_step``'s
    contract) over the state class alone, ``"seq_state": [layers, blocks,
    Hkv, P, 256, d]`` and ``"seq_norm": [layers, blocks, Hkv, d, d]``
    float32, carried whole: every layer reads and writes each slot's block
    (``attend.state_blocks``) and NO layer calls ``attend`` itself: there is
    no page to write or read. T == 1 is the decode step and T > 1 a prefill
    chunk, through the same lines."""
    pos2d = llama.paged_positions(token_ids, positions)
    x = embed_tokens(config, params, token_ids, pos2d)
    layers = params["layers"]
    pools = (cache["seq_state"], cache["seq_norm"])
    with jax.named_scope("layers"):
        for l in range(config.num_layers):
            out, pools = retention_sublayer(
                config, x, layers["mixer"][l], layers["mixer_norm"][l],
                pos2d, state=(*pools, l, attend))
            x = _ffn(config, x + out, layers, l)
    return (llama.paged_logits_at(lm_head_logits, config, params, x,
                                  last_index, all_logits),
            {"seq_state": pools[0], "seq_norm": pools[1]})


PRESETS = {
    # two query heads a kv head, heads of two feature blocks (three block
    # pairs, 768 rows); narrow, so a test's state is 200 KB a layer
    "brumby-debug": BrumbyConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=32, intermediate_size=128,
        max_position_embeddings=512),
    # manifestai/Brumby-14B-Base config.json
    "brumby-14b": BrumbyConfig(),
}
