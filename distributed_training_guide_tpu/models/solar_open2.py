"""Solar Open 2 (upstage ``solar_open2``): a decoder whose mixers differ in
KIND. Layer ``l`` is ``x += Mixer(norm(x))``, ``x += FFN(norm(x))`` (pre-norm);
the layers ``config.gqa_layers`` names mix by gated softmax attention with NO
positional encoding, every other one by Kimi Delta Attention (KDA, a gated
delta-rule LINEAR attention: ``ops/kda.py``), and every layer's FFN routes
over experts beside one shared expert. The layer order is read from
``gqa_layers`` and from nothing else.

KDA mixer (:func:`kda_sublayer`): ``W_q, W_k, W_v`` (stored as one ``[E, 3 H
d]`` matrix, the streams in that order) each through its own depthwise causal
convolution of ``conv_kernel`` taps a channel and SiLU; per head ``q = q~ /
max(|q~|, 1e-6) / sqrt(d)``, ``k = k~ / max(|k~|, 1e-6)``; a log-decay a key
channel ``g = -exp(A_log) softplus(W_fb (W_fa u) + dt_bias)`` (low rank
through ``d``); a step size ``beta = 2 sigmoid(W_beta u)`` in ``(0, 2)`` (the
delta rule's eigenvalue ``1 - beta`` may be negative); the recurrence of
``ops/kda.py`` in float32; the read-out through an RMSNorm over each head's
``d`` columns (one scale, shared by the heads) times ``sigmoid(W_gb (W_ga
u))``, then ``W_o``. What the layer must remember of a sequence is the state
``S [H, d, d]`` (float32: 4 MB at 64 heads of 128) and the last ``conv_kernel
- 1`` rows of the three streams BEFORE the convolution.

GQA mixer (:func:`gqa_sublayer`): ``num_heads`` query heads on
``num_kv_heads`` kv heads, no rope, no QK-norm, scores ``q k^T / sqrt(d)``,
and an element-wise output gate: ``W_o (sigmoid(W_g u) * a)``.

The serve path keeps k and v of the GQA layers in pages (``kv_layout``,
``num_kv_layers``) and the KDA layers' state in the pool's STATE CLASS
(``sequence_state_layout``; ``serve/kv_pages.py``): a block a live SEQUENCE,
not a row a page (a page-addressed state of this size would be 13 MB a page),
whose id rides each program beside the slot's block table. The decode step
updates ``S`` where it lies (``kda_step``); a prefill chunk reads it, scans
its tokens (``kda_chunk``) and writes it back; a sequence that starts at
position 0 starts from zeros whatever its block's last owner left.

Experts: ``models/moe._moe_ffn`` with the sigmoid router, a per-expert bias
that moves the choice only, weights ``s / (sum + 1e-20)``, an ungated shared
expert and ``experts_held`` (one chip's share of an expert-parallel layer);
the expert leaves stay where they lie (``moe.experts_in_place``).

The layers are WALKED like ``models/lfm2.py``'s; the mixers' and the FFN's
own matrices are a LIST of per-layer leaves (``models/mimo_v2.py`` found why:
a static row of a stacked leaf reaches a ``dot`` as a copy), the routed
experts and the two norms stacks.

Serving and the plain forward only, like ``models/mla.py``.
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
from .state_class import STATE_CLASS_REFUSES
from ..ops.attention import multihead_attention
from ..ops.kda import kda_chunk, kda_step

GQA, KDA = "gqa", "kda"

# what ServeEngine refuses for this family, by the option's name, each with
# the module that would have to change: what the state class refuses for
# every family that keeps one, and nothing of its own
SERVE_REFUSES = STATE_CLASS_REFUSES


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_layers: int = 48
    gqa_layers: tuple = tuple(range(0, 48, 4))     # every other one is KDA
    num_heads: int = 64                       # the GQA layers' query heads
    num_kv_heads: int = 8
    head_dim: int = 128
    kda_heads: int = 64                       # q, k and v heads alike
    kda_head_dim: int = 128                   # d_k = d_v, and the low rank
    conv_kernel: int = 4                      # taps of the short convolution
    intermediate_size: int = 10240            # a dense FFN no layer has
    moe_intermediate_size: int = 1280         # every expert's width
    num_experts: int = 320                    # the router's outputs
    experts_per_token: int = 8
    # (first, count): the routed experts whose weights this program holds
    # (None = all): one chip's share of an expert-parallel layer
    experts_held: Optional[tuple] = None
    shared_expert_intermediate: int = 1280    # n_shared_experts x the width
    router_act: str = "sigmoid"
    norm_topk_prob: bool = True
    norm_topk_eps: float = 1e-20              # weights = s / (sum + eps)
    routed_scaling_factor: float = 1.0
    moe_dispatch: str = "ragged"              # a held share is ragged only
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.0
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        bad = [l for l in self.gqa_layers if not 0 <= l < self.num_layers]
        if bad or len(set(self.gqa_layers)) != len(self.gqa_layers):
            raise ValueError(f"gqa_layers names layers of 0.."
                             f"{self.num_layers - 1}, each once; got "
                             f"{self.gqa_layers}")
        if self.conv_kernel < 2:
            raise ValueError(f"conv_kernel must be >= 2, got "
                             f"{self.conv_kernel}")

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def num_kv_layers(self) -> int:
        """Layers with k and v pages: the GQA layers."""
        return len(self.gqa_layers)

    @property
    def num_kda_layers(self) -> int:
        return self.num_layers - self.num_kv_layers

    @property
    def kda_width(self) -> int:
        """Columns of one of the KDA mixer's three streams."""
        return self.kda_heads * self.kda_head_dim

    def kv_layout(self) -> dict:
        """One cached token in one GQA layer
        (``serve/kv_pages.pool_layout``)."""
        shape = (self.num_kv_heads, self.head_dim)
        return {"k": shape, "v": shape}

    def sequence_state_layout(self) -> Optional[dict]:
        """The state class (``serve/kv_pages.sequence_state_layout``):
        ``{leaf: (shape of one sequence's block, layers first; storage)}``,
        ``storage`` a float dtype's name or None for the pool's own. None
        where no layer is KDA."""
        n, d = self.num_kda_layers, self.kda_head_dim
        if not n:
            return None
        # S in float32 whatever the pool's dtype, as published for KDA: not
        # an option (``ops/kda.kda_step`` refuses a narrower pool)
        return {"seq_state": ((n, self.kda_heads, d, d), "fp32"),
                # (three rows a block tile badly and the compiler re-lays
                # the leaf four times a decode step, 1.2 ms at the cell's
                # size; a flat [blocks, 3 x width] leaf was tried on the chip
                # and cost 6 ms: its scatter became a loop over the slots)
                "seq_conv": ((n, self.conv_kernel - 1, 3 * self.kda_width),
                             None)}

    def layer_table(self) -> tuple:
        """Per layer ``(mixer kind, its row among the layers of that
        kind)``."""
        rows, seen = [], {GQA: 0, KDA: 0}
        for l in range(self.num_layers):
            kind = GQA if l in self.gqa_layers else KDA
            rows.append((kind, seen[kind]))
            seen[kind] += 1
        return tuple(rows)

    def _count(self, experts: int) -> int:
        e = self.hidden_size
        ffn = (e * self.num_experts + self.num_experts
               + 3 * e * self.shared_expert_intermediate
               + experts * 3 * e * self.moe_intermediate_size + 2 * e)
        mixers = (self.num_kv_layers * _size(_gqa_shapes(self))
                  + self.num_kda_layers * _size(_kda_shapes(self)))
        top = self.vocab_size * e * (1 if self.tie_word_embeddings else 2) + e
        return top + mixers + self.num_layers * ffn

    def num_params(self) -> int:
        """Parameters HELD (``experts_held`` experts a layer)."""
        return self._count(experts_held(self)[1])

    def num_active_params(self) -> int:
        return self._count(self.experts_per_token)


def _gqa_shapes(config: SolarOpen2Config) -> dict:
    e, d = config.hidden_size, config.head_dim
    hq, hkv = config.num_heads * d, config.num_kv_heads * d
    return {"wq": (e, hq), "wk": (e, hkv), "wv": (e, hkv), "wg": (e, hq),
            "wo": (hq, e)}


def _kda_shapes(config: SolarOpen2Config) -> dict:
    e, c = config.hidden_size, config.kda_width
    h, d = config.kda_heads, config.kda_head_dim
    return {"w_qkv": (e, 3 * c), "taps": (config.conv_kernel, 3 * c),
            "w_fa": (e, d), "w_fb": (d, c), "a_log": (h,), "dt_bias": (c,),
            "w_beta": (e, h), "w_ga": (e, d), "w_gb": (d, c),
            "o_norm": (d,), "wo": (c, e)}


def _size(shapes: dict) -> int:
    return sum(math.prod(s) for s in shapes.values())


def _ffn_shapes(config: SolarOpen2Config) -> dict:
    e, fs = config.hidden_size, config.shared_expert_intermediate
    return {"router": (e, config.num_experts),
            "router_bias": (config.num_experts,),
            "shared_gate_proj": (e, fs), "shared_up": (e, fs),
            "shared_down": (fs, e)}


def init(config: SolarOpen2Config, rng: jax.Array) -> dict:
    e, v, n = config.hidden_size, config.vocab_size, config.num_layers
    fe, held = config.moe_intermediate_size, experts_held(config)[1]
    keys = iter(jax.random.split(rng, 16 + 16 * n))
    pdt = config.param_dtype

    def dense(shape, std=0.02):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(pdt)

    def kda():
        p = {name: dense(shape) for name, shape in _kda_shapes(config).items()}
        p["taps"] = dense(p["taps"].shape, 0.5)
        p["w_gb"] = dense(p["w_gb"].shape, 0.05)
        p["o_norm"] = jnp.ones_like(p["o_norm"])
        # KDA's published start: decay rates 1..16, a step dt log-uniform in
        # [0.001, 0.1] and its inverse softplus as the bias
        p["a_log"] = jnp.log(jax.random.uniform(
            next(keys), p["a_log"].shape, jnp.float32, 1.0, 16.0)).astype(pdt)
        dt = jnp.exp(jax.random.uniform(
            next(keys), p["dt_bias"].shape, jnp.float32,
            math.log(0.001), math.log(0.1)))
        p["dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt)
        return p

    def gqa():
        p = {name: dense(shape) for name, shape in _gqa_shapes(config).items()}
        p["wg"] = dense(p["wg"].shape, 0.05)
        return p

    params = {
        "embed": {"embedding": dense((v, e))},
        "layers": {
            "mixer_norm": jnp.ones((n, e), pdt),
            "ffn_norm": jnp.ones((n, e), pdt),
            GQA: [gqa() for _ in range(config.num_kv_layers)],
            KDA: [kda() for _ in range(config.num_kda_layers)],
            "ffn": [{name: dense(shape)
                     for name, shape in _ffn_shapes(config).items()}
                    for _ in range(n)],
            "moe": {"gate": dense((n, held, e, fe)),
                    "up": dense((n, held, e, fe)),
                    "down": dense((n, held, fe, e))},
        },
        "final_norm": jnp.ones((e,), pdt),
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = dense((e, v))
    return params


def param_logical_axes(config: SolarOpen2Config) -> dict:
    """Logical axes: a list of per-layer leaves for the mixers and the FFN's
    own matrices, stacks (leading axis ``layers``) for the norms and the
    routed experts. No serve mesh runs this family yet (``SERVE_REFUSES``)."""
    gqa = {"wq": ("embed", "heads"), "wk": ("embed", "kv"),
           "wv": ("embed", "kv"), "wg": ("embed", "heads"),
           "wo": ("heads", "embed")}
    kda = {"w_qkv": ("embed", "heads"), "taps": (None, "heads"),
           "w_fa": ("embed", None), "w_fb": (None, "heads"),
           "a_log": (None,), "dt_bias": ("heads",),
           "w_beta": ("embed", None), "w_ga": ("embed", None),
           "w_gb": (None, "heads"), "o_norm": (None,),
           "wo": ("heads", "embed")}
    ffn = {"router": ("embed", "experts_vector"),
           "router_bias": ("experts_vector",),
           "shared_gate_proj": ("embed", "mlp"), "shared_up": ("embed", "mlp"),
           "shared_down": ("mlp", "embed")}
    axes = {
        "embed": {"embedding": ("vocab", "embed")},
        "layers": {
            "mixer_norm": ("layers", "embed_vector"),
            "ffn_norm": ("layers", "embed_vector"),
            GQA: [dict(gqa) for _ in range(config.num_kv_layers)],
            KDA: [dict(kda) for _ in range(config.num_kda_layers)],
            "ffn": [dict(ffn) for _ in range(config.num_layers)],
            "moe": {"gate": ("layers", "experts", "embed", "mlp"),
                    "up": ("layers", "experts", "embed", "mlp"),
                    "down": ("layers", "experts", "mlp", "embed")},
        },
        "final_norm": ("embed_vector",),
    }
    if not config.tie_word_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# ---------------------------------------------------------------------------
# the two mixers
# ---------------------------------------------------------------------------

def _kda_inputs(config: SolarOpen2Config, h: jnp.ndarray, p: dict,
                conv_state: Optional[jnp.ndarray]):
    """The recurrence's inputs from the normed ``h [B, T, E]``: ``(q, k, v,
    g, beta, history)``, float32, heads apart; ``conv_state [B, L - 1, 3 C]``
    is each sequence's last rows of the three streams before this call (None:
    a sequence's beginning, zeros), and ``history [B, L - 1 + T, 3 C]`` those
    rows followed by this call's, so the state after token i is ``history[:,
    i + 1 : i + L]``."""
    cdt = config.dtype
    b, t, _ = h.shape
    heads, d, taps_n = config.kda_heads, config.kda_head_dim, config.conv_kernel
    x = h @ p["w_qkv"].astype(cdt)                            # [B, T, 3 C]
    if conv_state is None:
        conv_state = jnp.zeros((b, taps_n - 1, x.shape[-1]), cdt)
    history = jnp.concatenate([conv_state.astype(cdt), x], axis=1)
    taps = p["taps"].astype(jnp.float32)                      # [L, 3 C]
    conv = sum(taps[j] * history[:, j:j + t].astype(jnp.float32)
               for j in range(taps_n))
    q, k, v = (s.reshape(b, t, heads, d)
               for s in jnp.split(jax.nn.silu(conv), 3, axis=-1))

    def unit(s):
        return s / jnp.maximum(jnp.linalg.norm(s, axis=-1, keepdims=True),
                               1e-6)

    low = (h @ p["w_fa"].astype(cdt)) @ p["w_fb"].astype(cdt)
    rate = jnp.exp(p["a_log"].astype(jnp.float32))[:, None]   # [H, 1]
    g = -rate * jax.nn.softplus(
        (low.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)
         ).reshape(b, t, heads, d))
    beta = 2.0 * jax.nn.sigmoid((h @ p["w_beta"].astype(cdt))
                                .astype(jnp.float32))         # [B, T, H]
    return unit(q) * d ** -0.5, unit(k), v, g, beta, history


def _kda_output(config: SolarOpen2Config, h: jnp.ndarray, p: dict,
                o: jnp.ndarray) -> jnp.ndarray:
    """The read-out ``o [B, T, H, d]`` (float32) through the gated per-head
    norm and ``W_o``."""
    cdt = config.dtype
    b, t, _ = h.shape
    gate = jax.nn.sigmoid(((h @ p["w_ga"].astype(cdt)) @ p["w_gb"].astype(cdt))
                          .astype(jnp.float32)).reshape(o.shape)
    o = _rmsnorm(o, p["o_norm"].astype(jnp.float32), config.rms_norm_eps)
    return (o * gate).astype(cdt).reshape(b, t, -1) @ p["wo"].astype(cdt)


@jax.named_scope("attn")
def kda_sublayer(config: SolarOpen2Config, x: jnp.ndarray, p: dict,
                 norm_scale, state=None):
    """norm -> KDA -> output projection (the caller adds the residual), under
    the sub-scope ``kda``. x [B, T, E]. ``state`` None: whole sequences from
    zeros (the plain forward), returns ``out``. Else ``state = (pool, conv
    pool, row, attend)``, the serve path's state class and the paged hook
    that knows each slot's block, start and valid tokens: returns ``(out,
    (pool, conv pool))``."""
    with jax.named_scope("kda"):
        h = _rmsnorm(x, norm_scale, config.rms_norm_eps)
        if state is None:
            q, k, v, g, beta, _ = _kda_inputs(config, h, p, None)
            zeros = jnp.zeros((x.shape[0], config.kda_heads,
                               config.kda_head_dim, config.kda_head_dim))
            o, _ = kda_chunk(zeros, q, k, v, g, beta)
            return _kda_output(config, h, p, o)
        pool, conv_pool, row, attend = state
        blocks, t = attend.state_blocks, x.shape[1]
        fresh = attend.lengths == 0        # a sequence's first tokens
        conv_state = jnp.where(fresh[:, None, None], 0,
                               conv_pool[row, blocks])
        q, k, v, g, beta, history = _kda_inputs(config, h, p, conv_state)
        if t == 1:
            # a decay of exp(-inf) = 0 is the zero state of a fresh sequence
            g = jnp.where(fresh[:, None, None, None], -jnp.inf, g)
            o, pool = kda_step(pool, blocks, row, q[:, 0], k[:, 0], v[:, 0],
                               g[:, 0], beta[:, 0])
            o = o[:, None]
        else:
            s0 = jnp.where(fresh[:, None, None, None], 0, pool[row, blocks])
            o, s_t = kda_chunk(s0, q, k, v, g, beta, attend.n_valid)
            with jax.named_scope("kv_write"):
                pool = pool.at[row, blocks].set(s_t)
        out = _kda_output(config, h, p, o)
    with jax.named_scope("kv_write"):
        # the streams' rows at the slot's last REAL token and the two before
        keep = config.conv_kernel - 1
        n_valid = (jnp.full(blocks.shape, t, jnp.int32)
                   if attend.n_valid is None else attend.n_valid)
        rows = n_valid[:, None] + jnp.arange(keep)[None, :]
        new = jnp.take_along_axis(history, rows[..., None], axis=1)
        conv_pool = conv_pool.at[row, blocks].set(new.astype(conv_pool.dtype))
    return out, (pool, conv_pool)


@jax.named_scope("attn")
def gqa_sublayer(config: SolarOpen2Config, x: jnp.ndarray, p: dict,
                 norm_scale, positions: jnp.ndarray, attend=None):
    """norm -> gated NoPE attention -> output projection (the caller adds
    the residual). ``attend`` (the serving engine's paged hook, ``(q, k, v)
    -> (attn, pools)``) replaces the attend; the call then returns ``(out,
    pools)``."""
    b, s, _ = x.shape
    cdt, d = config.dtype, config.head_dim
    h = _rmsnorm(x, norm_scale, config.rms_norm_eps)
    q = (h @ p["wq"].astype(cdt)).reshape(b, s, config.num_heads, d)
    k = (h @ p["wk"].astype(cdt)).reshape(b, s, config.num_kv_heads, d)
    v = (h @ p["wv"].astype(cdt)).reshape(b, s, config.num_kv_heads, d)
    if attend is None:
        attn = multihead_attention(
            q, k, v, causal=True, positions=positions, kv_positions=positions,
            impl="xla", standard_layout=False, scale=d ** -0.5)
    else:
        attn, pools = attend(q, k, v)
    gate = jax.nn.sigmoid((h @ p["wg"].astype(cdt)).astype(jnp.float32))
    out = (gate.astype(cdt) * attn.reshape(b, s, -1)) @ p["wo"].astype(cdt)
    return out if attend is None else (out, pools)


def _layer_of(stack: dict, row: int) -> dict:
    return jax.tree.map(lambda a: a[row], stack)


def _ffn(config: SolarOpen2Config, x, layers: dict, l: int, experts: dict):
    """The layer's routed FFN with its residual; ``(x, routing counts)``."""
    with jax.named_scope("experts"):   # the FFN's pre-norm is its own
        h = _rmsnorm(x, layers["ffn_norm"][l], config.rms_norm_eps)
    held = experts or _layer_of(layers["moe"], l)
    y, _, _, counts = _moe_ffn(config, h, {**layers["ffn"][l], **held},
                               no_drop=True, return_counts=True,
                               layer_index=l if experts else None)
    return x + y, counts


embed_tokens = llama.embed_tokens
lm_head_logits = llama.lm_head_logits
final_hidden = llama.final_hidden
output_weights = llama.output_weights


def apply(config: SolarOpen2Config, params: dict, input_ids: jnp.ndarray,
          positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Plain forward over whole sequences -> logits [B, S, V] float32."""
    if positions is None:
        positions = jnp.arange(input_ids.shape[1])[None, :]
    positions = jnp.broadcast_to(positions, input_ids.shape)
    x = embed_tokens(config, params, input_ids, positions)
    layers = params["layers"]
    with jax.named_scope("layers"):
        for l, (kind, row) in enumerate(config.layer_table()):
            norm = layers["mixer_norm"][l]
            if kind == GQA:
                out = gqa_sublayer(config, x, layers[GQA][row], norm,
                                   positions)
            else:
                out = kda_sublayer(config, x, layers[KDA][row], norm)
            x, _ = _ffn(config, x + out, layers, l, {})
    return lm_head_logits(config, params, x)


def paged_decode_step(config: SolarOpen2Config, params: dict,
                      token_ids: jnp.ndarray, positions: jnp.ndarray,
                      cache: dict, attend, last_index=None,
                      all_logits=False):
    """Paged multi-request decode/chunk step (``llama.paged_decode_step``'s
    contract) over the pools ``{"k", "v"}: [GQA layers, P, page, kv heads,
    head_dim]`` and the state class ``"seq_state": [KDA layers, blocks, H, d,
    d]`` float32, ``"seq_conv": [KDA layers, blocks, L - 1, 3 H d]``, carried
    whole and addressed by the layer's row among its kind. A GQA layer writes
    and reads k and v through ``attend``; a KDA layer reads and writes each
    slot's block (``attend.state_blocks``). T == 1 is the decode step and T >
    1 a prefill chunk, through the same lines. The returned cache also
    carries ``"routing"`` (``models/mla.py``), counted over all layers."""
    pos2d = llama.paged_positions(token_ids, positions)
    x = embed_tokens(config, params, token_ids, pos2d)
    layers, experts = experts_in_place(config, params["layers"])
    new_cache = {name: cache[name] for name in ("k", "v", "seq_state",
                                                "seq_conv") if name in cache}
    scale = config.head_dim ** -0.5
    counts = []
    with jax.named_scope("layers"):
        for l, (kind, row) in enumerate(config.layer_table()):
            norm = layers["mixer_norm"][l]
            if kind == GQA:
                def paged(q, k, v, row=row):
                    return attend(q, k, v, new_cache["k"], new_cache["v"],
                                  row, scale=scale)

                out, (new_cache["k"], new_cache["v"]) = gqa_sublayer(
                    config, x, layers[GQA][row], norm, pos2d, attend=paged)
            else:
                out, (new_cache["seq_state"], new_cache["seq_conv"]) = \
                    kda_sublayer(config, x, layers[KDA][row], norm,
                                 state=(new_cache["seq_state"],
                                        new_cache["seq_conv"], row, attend))
            x, n = _ffn(config, x + out, layers, l, experts)
            counts.append(n)
    counts = jnp.stack(counts)
    new_cache["routing"] = jnp.concatenate(
        [jnp.sum(counts[:, :3], axis=0), jnp.max(counts[:, 3:], axis=0)])
    return (llama.paged_logits_at(lm_head_logits, config, params, x,
                                  last_index, all_logits), new_cache)


PRESETS = {
    # every kind of layer, the published order (GQA first, then KDA), a
    # period cut short; narrow heads, so a test's state is a few kilobytes
    "solar-open2-debug": SolarOpen2Config(
        vocab_size=512, hidden_size=64, num_layers=3, gqa_layers=(0,),
        num_heads=4, num_kv_heads=2, head_dim=16, kda_heads=4,
        kda_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        num_experts=8, experts_per_token=2, shared_expert_intermediate=32,
        max_position_embeddings=512),
    # upstage/Solar-Open2-250B config.json
    "solar-open2-250b": SolarOpen2Config(),
}
