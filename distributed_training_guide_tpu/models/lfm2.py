"""LFM2-MoE (LiquidAI ``lfm2_moe``): a hybrid decoder whose layers differ in
KIND. Each layer is ``x += Op(norm(x))``, ``x += FFN(norm(x))`` (pre-norm);
``config.layer_types[l]`` says whether ``Op`` is grouped-query attention
(``"full_attention"``) or a gated SHORT CONVOLUTION (``"conv"``), and the
first ``config.num_dense_layers`` layers have a dense SwiGLU FFN where all
later ones route over experts. The layer order is read from those two fields
and from nothing else.

Short convolution (:func:`conv_sublayer`): ``[B | C | z] = u W_in`` (three
blocks of the hidden width), ``g = B * z``, a depthwise causal convolution of
``conv_l_cache`` taps a channel over ``g`` (``c_t = sum_j w[j] g_{t - (L-1) +
j}``, ``g`` zero before the sequence), ``out = (C * c) W_out``. What a layer
must remember of a sequence is the last ``L - 1`` rows of ``g``: that is its
recurrent STATE, and the serve path keeps it in the page pool's third leaf,
addressed by page (``serve/kv_pages.py``: ``state_layout``, ``read_state``,
``write_state``), so it follows the sequence wherever its pages go.

Attention is ``llama.attention_sublayer`` (per-head QK-norm before rope, GQA).
Only the attention layers have k and v pages (``num_kv_layers``). Heads are 64
wide where the compiled paged kernel wants 128-wide rows, so the pool stores
TWO kv heads a row (``kv_layout``; the same bytes) and :func:`_packed_attend`
places each query head's 64 columns in its kv head's half of a 128-wide row,
zeros in the other half, and keeps that half of the output: the products with
the zero half add nothing, the MXU does twice the (memory-bound) work and no
byte more is read. The gather path takes the same rows, so there is one
layout.

Experts: ``models/moe._moe_ffn`` with the sigmoid router, a per-expert bias
that moves the choice only (``use_expert_bias``), weights ``s / (sum + 1e-6)``
and every expert held; the expert leaves stay where they lie and ``gmm``
addresses the layer (``moe.experts_in_place``).

The layers are WALKED, not scanned: consecutive layers differ in kind, so
there is no one stacked column to scan; leaves are stacked by kind (``attn``
over the attention layers, ``conv`` over the conv layers, ``mlp`` over the
dense FFNs, ``moe`` over the routed ones, the two norms over all layers) and
layer ``l`` takes its row of each by a static index, which XLA reads in place
(a slice of a parameter feeding a ``dot``; ``gmm`` gets the layer's
``group_offset``). The pools ride the walk as ``llama.scan_paged_layers``
carries them: whole, addressed by layer, returned under their names.

Serving and the plain forward only, like ``models/mla.py``: ``apply`` takes
none of the Trainer's options.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import llama
from .llama import _rmsnorm, attention_sublayer, mlp_sublayer
from .moe import _moe_ffn, experts_in_place

ATTENTION, CONV = "full_attention", "conv"
LANES = 128     # the width of a pool row the compiled paged kernel takes

# what ServeEngine refuses for this family, by the option's name
SERVE_REFUSES = {
    "kv_dtype='int8'": "the conv state and the packed kv rows are stored in "
                       "float",
    "weight_dtype='int8'": "serve/weights.py selects llama leaves only",
    "max_adapters": "the LoRA hooks wrap llama's projections",
    "speculate": "a rejected draft would have to roll the conv state back",
    "plan / shard_kv": "the tp serve mesh splits kv heads; two share a row "
                       "and the state has none",
    "disaggregation": "the handoff moves k and v pages and no state rows",
}


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    qk_norm = True          # per-head RMSNorm of q and k before rope

    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: tuple = (CONV, CONV) + (ATTENTION, CONV, CONV, CONV) * 9 \
        + (ATTENTION, CONV)
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None            # None: hidden_size / num_heads
    intermediate_size: int = 11776            # the dense FFNs' width
    moe_intermediate_size: int = 1536         # every expert's width
    num_experts: int = 64
    experts_per_token: int = 4
    use_expert_bias: bool = True
    router_act: str = "sigmoid"
    norm_topk_prob: bool = True
    norm_topk_eps: float = 1e-6               # weights = s / (sum + eps)
    routed_scaling_factor: float = 1.0
    moe_dispatch: str = "ragged"
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.0
    conv_l_cache: int = 3                     # taps of the short convolution
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        bad = [t for t in self.layer_types if t not in (ATTENTION, CONV)]
        if bad:
            raise ValueError(f"layer_types entries are {ATTENTION!r} or "
                             f"{CONV!r}; got {bad}")
        if not 0 <= self.num_dense_layers <= len(self.layer_types):
            raise ValueError(f"num_dense_layers {self.num_dense_layers} of "
                             f"{len(self.layer_types)} layers")
        if self.conv_l_cache < 2:
            raise ValueError(f"conv_l_cache must be >= 2, got "
                             f"{self.conv_l_cache}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def num_kv_layers(self) -> int:
        """Layers with k and v pages: the attention layers."""
        return sum(t == ATTENTION for t in self.layer_types)

    @property
    def num_conv_layers(self) -> int:
        return self.num_layers - self.num_kv_layers

    @property
    def kv_pack(self) -> int:
        """kv heads stored in one pool row: 2 where heads are 64 wide (a
        128-lane row), else 1."""
        return 2 if (2 * self.head_size == LANES
                     and self.num_kv_heads % 2 == 0) else 1

    def kv_layout(self) -> dict:
        """One cached token in one attention layer
        (``serve/kv_pages.pool_layout``)."""
        shape = (self.num_kv_heads // self.kv_pack,
                 self.head_size * self.kv_pack)
        return {"k": shape, "v": shape}

    def state_layout(self) -> tuple:
        """``(layers, rows, width)`` of the conv state a page carries
        (``serve/kv_pages.state_layout``)."""
        return (self.num_conv_layers, self.conv_l_cache - 1, self.hidden_size)

    def layer_table(self) -> tuple:
        """Per layer ``(operator kind, its row among the layers of that kind,
        True where the FFN is dense, its row among the FFNs of that kind)``."""
        rows, seen = [], {ATTENTION: 0, CONV: 0, True: 0, False: 0}
        for l, kind in enumerate(self.layer_types):
            dense = l < self.num_dense_layers
            rows.append((kind, seen[kind], dense, seen[dense]))
            seen[kind] += 1
            seen[dense] += 1
        return tuple(rows)

    def _sizes(self) -> dict:
        e, d = self.hidden_size, self.head_size
        return {
            "attn": e * d * (2 * self.num_heads + 2 * self.num_kv_heads)
            + 2 * d,
            "conv": e * 3 * e + e * e + self.conv_l_cache * e,
            "dense": 3 * e * self.intermediate_size,
            "expert": 3 * e * self.moe_intermediate_size,
            "router": e * self.num_experts
            + (self.num_experts if self.use_expert_bias else 0),
        }

    def _count(self, experts: int) -> int:
        s, e = self._sizes(), self.hidden_size
        n_moe = self.num_layers - self.num_dense_layers
        top = self.vocab_size * e * (1 if self.tie_word_embeddings else 2) + e
        return (top + 2 * e * self.num_layers
                + self.num_kv_layers * s["attn"]
                + self.num_conv_layers * s["conv"]
                + self.num_dense_layers * s["dense"]
                + n_moe * (s["router"] + experts * s["expert"]))

    def num_params(self) -> int:
        return self._count(self.num_experts)

    def num_active_params(self) -> int:
        return self._count(self.experts_per_token)


def init(config: Lfm2MoeConfig, rng: jax.Array) -> dict:
    e, d, v = config.hidden_size, config.head_size, config.vocab_size
    hq, hkv = config.num_heads * d, config.num_kv_heads * d
    f, fe, ex = (config.intermediate_size, config.moe_intermediate_size,
                 config.num_experts)
    n, a, c = config.num_layers, config.num_kv_layers, config.num_conv_layers
    nd = config.num_dense_layers
    nm = n - nd
    keys = iter(jax.random.split(rng, 20))
    pdt = config.param_dtype

    def dense(shape):
        return (0.02 * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(pdt)

    moe = {"router": dense((nm, e, ex)), "gate": dense((nm, ex, e, fe)),
           "up": dense((nm, ex, e, fe)), "down": dense((nm, ex, fe, e))}
    if config.use_expert_bias:
        moe["router_bias"] = dense((nm, ex))
    params = {
        "embed": {"embedding": dense((v, e))},
        "layers": {
            "operator_norm": jnp.ones((n, e), pdt),
            "ffn_norm": jnp.ones((n, e), pdt),
            "attn": {"wq": dense((a, e, hq)), "wk": dense((a, e, hkv)),
                     "wv": dense((a, e, hkv)), "wo": dense((a, hq, e)),
                     "q_norm": jnp.ones((a, d), pdt),
                     "k_norm": jnp.ones((a, d), pdt)},
            "conv": {"w_in": dense((c, e, 3 * e)),
                     "taps": dense((c, config.conv_l_cache, e)),
                     "w_out": dense((c, e, e))},
            "mlp": {"gate": dense((nd, e, f)), "up": dense((nd, e, f)),
                    "down": dense((nd, f, e))},
            "moe": moe,
        },
        "final_norm": jnp.ones((e,), pdt),
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = dense((e, v))
    return params


def param_logical_axes(config: Lfm2MoeConfig) -> dict:
    """Logical axes, stacked by kind (the leading axis of every layer leaf
    counts the layers of its kind). No serve mesh runs this family yet
    (``SERVE_REFUSES``)."""
    moe = {"router": ("layers", "embed", "experts_vector"),
           "gate": ("layers", "experts", "embed", "mlp"),
           "up": ("layers", "experts", "embed", "mlp"),
           "down": ("layers", "experts", "mlp", "embed")}
    if config.use_expert_bias:
        moe["router_bias"] = ("layers", "experts_vector")
    axes = {
        "embed": {"embedding": ("vocab", "embed")},
        "layers": {
            "operator_norm": ("layers", "embed_vector"),
            "ffn_norm": ("layers", "embed_vector"),
            "attn": {"wq": ("layers", "embed", "heads"),
                     "wk": ("layers", "embed", "kv"),
                     "wv": ("layers", "embed", "kv"),
                     "wo": ("layers", "heads", "embed"),
                     "q_norm": ("layers", None), "k_norm": ("layers", None)},
            "conv": {"w_in": ("layers", "embed", "mlp"),
                     "taps": ("layers", None, "embed_vector"),
                     "w_out": ("layers", "mlp", "embed")},
            "mlp": {"gate": ("layers", "embed", "mlp"),
                    "up": ("layers", "embed", "mlp"),
                    "down": ("layers", "mlp", "embed")},
            "moe": moe,
        },
        "final_norm": ("embed_vector",),
    }
    if not config.tie_word_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# ---------------------------------------------------------------------------
# the two operators
# ---------------------------------------------------------------------------

@jax.named_scope("attn")
def conv_sublayer(config: Lfm2MoeConfig, x: jnp.ndarray, p: dict, norm_scale,
                  state: Optional[jnp.ndarray] = None):
    """norm -> gated short convolution -> output projection (the caller adds
    the residual), the whole of it under the sub-scope ``conv``. x [B, S, E];
    ``state`` [B, L - 1, E] is each sequence's last ``L - 1`` rows of ``g``
    before this call (None: a sequence's beginning, zeros). Returns ``(out,
    history [B, L - 1 + S, E])``: the state followed by this call's rows, so
    the state after token i is ``history[:, i + 1 : i + L]``."""
    with jax.named_scope("conv"):
        cdt = config.dtype
        b, s, e = x.shape
        taps_n = config.conv_l_cache
        h = _rmsnorm(x, norm_scale, config.rms_norm_eps)
        gate_b, gate_c, z = jnp.split(h @ p["w_in"].astype(cdt), 3, axis=-1)
        g = gate_b * z
        if state is None:
            state = jnp.zeros((b, taps_n - 1, e), cdt)
        history = jnp.concatenate([state.astype(cdt), g], axis=1)
        taps = p["taps"].astype(jnp.float32)                      # [L, E]
        conv = sum(taps[j] * history[:, j:j + s].astype(jnp.float32)
                   for j in range(taps_n))
        out = (gate_c * conv.astype(cdt)) @ p["w_out"].astype(cdt)
    return out, history


def _packed_attend(config: Lfm2MoeConfig, attend, pools, layer):
    """The serving engine's paged hook for one attention layer, as
    ``llama.attention_sublayer`` calls it, over pool rows that hold
    ``config.kv_pack`` kv heads each (module docstring)."""
    pack, d = config.kv_pack, config.head_size
    groups = config.num_heads // config.num_kv_heads

    def override(q, k, v, *, window, scale, softcap):
        scale = d ** -0.5 if scale is None else scale
        if pack == 1:
            return attend(q, k, v, *pools, layer, window=window, scale=scale,
                          softcap=softcap)
        s, t, hq, _ = q.shape
        # query head h reads kv head h // groups: the half of its row
        half = (jnp.arange(hq) // groups) % pack
        mine = half[:, None] == jnp.arange(pack)[None, :]        # [Hq, pack]
        wide = jnp.where(mine[:, :, None], q[..., None, :], 0)
        rows = (s, t, config.num_kv_heads // pack, pack * d)
        out, new_pools = attend(
            wide.reshape(s, t, hq, pack * d).astype(q.dtype),
            k.reshape(rows), v.reshape(rows), *pools, layer, window=window,
            scale=scale, softcap=softcap)
        out = out.reshape(s, t, hq, pack, d)
        out = jnp.sum(jnp.where(mine[:, :, None], out, 0), axis=-2)
        return out.astype(q.dtype), new_pools

    return override


def _layer_of(stack: dict, row: int) -> dict:
    return jax.tree.map(lambda a: a[row], stack)


def _ffn(config: Lfm2MoeConfig, x, layers: dict, l: int, dense: bool,
         row: int, experts: dict):
    """The layer's FFN with its residual; ``(x, routing counts or None)``."""
    norm = layers["ffn_norm"][l]
    if dense:
        return x + mlp_sublayer(config, x, {
            "post_attn_norm": norm, "mlp": _layer_of(layers["mlp"], row)}), None
    with jax.named_scope("experts"):   # the FFN's pre-norm is its own
        h = _rmsnorm(x, norm, config.rms_norm_eps)
    moe = {**_layer_of(layers["moe"], row), **experts}
    y, _, _, counts = _moe_ffn(config, h, moe, no_drop=True,
                               return_counts=True,
                               layer_index=row if experts else None)
    return x + y, counts


embed_tokens = llama.embed_tokens
lm_head_logits = llama.lm_head_logits
final_hidden = llama.final_hidden
output_weights = llama.output_weights


def apply(config: Lfm2MoeConfig, params: dict, input_ids: jnp.ndarray,
          positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Plain forward over whole sequences -> logits [B, S, V] float32."""
    if positions is None:
        positions = jnp.arange(input_ids.shape[1])[None, :]
    positions = jnp.broadcast_to(positions, input_ids.shape)
    x = embed_tokens(config, params, input_ids, positions)
    layers = params["layers"]
    with jax.named_scope("layers"):
        for l, (kind, row, dense, ffn_row) in enumerate(config.layer_table()):
            norm = layers["operator_norm"][l]
            if kind == ATTENTION:
                out = attention_sublayer(
                    config, x, _layer_of(layers["attn"], row), norm,
                    positions, "xla", standard_layout=False)
            else:
                out, _ = conv_sublayer(config, x,
                                       _layer_of(layers["conv"], row), norm)
            x, _ = _ffn(config, x + out, layers, l, dense, ffn_row, {})
    return lm_head_logits(config, params, x)


def paged_decode_step(config: Lfm2MoeConfig, params: dict,
                      token_ids: jnp.ndarray, positions: jnp.ndarray,
                      cache: dict, attend, last_index=None,
                      all_logits=False):
    """Paged multi-request decode/chunk step (``llama.paged_decode_step``'s
    contract) over the pools ``{"k", "v"}: [attention layers, P, page, rows,
    128 or head_dim]`` and ``"state": [conv layers, P, L - 1, E]``, carried
    whole and addressed by the layer's row among its kind. An attention
    layer writes and reads k and v through ``attend``; a conv layer reads
    each slot's state (``attend.read_state``: the row of the page that holds
    its previous token, zeros at a sequence's start) and writes the state
    after the last token of every page the call writes to
    (``attend.write_state``). T == 1 is the decode step and T > 1 a prefill
    chunk, through the same lines. The returned cache also carries
    ``"routing"`` (``models/mla.py``), counted over the expert layers."""
    pos2d = llama.paged_positions(token_ids, positions)
    x = embed_tokens(config, params, token_ids, pos2d)
    layers, experts = experts_in_place(config, params["layers"])
    kp, vp, sp = llama.cache_pools(cache)
    page = kp.shape[2]
    counts = []
    with jax.named_scope("layers"):
        for l, (kind, row, dense, ffn_row) in enumerate(config.layer_table()):
            norm = layers["operator_norm"][l]
            if kind == ATTENTION:
                out, (kp, vp) = attention_sublayer(
                    config, x, _layer_of(layers["attn"], row), norm, pos2d,
                    "xla", attend_override=_packed_attend(
                        config, attend, (kp, vp), row))
            else:
                with jax.named_scope("attn"), jax.named_scope("conv"):
                    state = attend.read_state(sp, row, page)
                out, history = conv_sublayer(
                    config, x, _layer_of(layers["conv"], row), norm, state)
                with jax.named_scope("attn"):
                    sp = attend.write_state(sp, row, page, history)
            x, n = _ffn(config, x + out, layers, l, dense, ffn_row, experts)
            if n is not None:
                counts.append(n)
    new_cache = llama.pools_dict(cache, (kp, vp, sp))
    if counts:
        counts = jnp.stack(counts)
        new_cache["routing"] = jnp.concatenate(
            [jnp.sum(counts[:, :3], axis=0), jnp.max(counts[:, 3:], axis=0)])
    return (llama.paged_logits_at(lm_head_logits, config, params, x,
                                  last_index, all_logits), new_cache)


PRESETS = {
    # every kind of layer: dense + conv, experts + attention, experts + conv;
    # 64-wide heads, so the packed pool rows are what every test serves from
    "lfm2-moe-debug": Lfm2MoeConfig(
        vocab_size=512, hidden_size=64, layer_types=(CONV, ATTENTION, CONV),
        num_dense_layers=1, num_heads=4, num_kv_heads=2, head_dim=64,
        intermediate_size=128, moe_intermediate_size=32, num_experts=8,
        experts_per_token=2, max_position_embeddings=256),
    # LiquidAI/LFM2-24B-A2B config.json
    "lfm2-24b-a2b": Lfm2MoeConfig(),
}
