"""Mixture-of-Experts Llama variant with expert parallelism.

The reference has no MoE/expert parallelism at all (SURVEY.md §2 scorecard:
"EP: absent entirely"); this adds the capability TPU-first:

- every layer's FFN is replaced by a router + E experts whose weights are
  *stacked* on an expert dim ``[L, E, ...]`` carrying the logical axis
  ``experts``; the "ep" plan maps it to the ``ep`` mesh axis. GSPMD
  partitions the index-based dispatch scatter and the expert einsums over
  ep WITHOUT replicating either the [E, C, D] buffers or the expert
  weights: each device computes only its E/ep experts and token movement
  lowers to collective-permutes — verified at the compiled-HLO level by
  ``tests/test_moe.py::test_ep_dispatch_stays_local`` (no hand-written
  collectives needed);
- routing is top-k (default 2) with a static per-expert capacity
  ``C = ceil(capacity_factor * k * tokens / E)`` — static shapes (XLA
  requirement), overflow tokens drop to the residual path (standard
  Switch/GShard behavior);
- a load-balance auxiliary loss (Switch-style: E * sum_e fraction_e * prob_e)
  is returned alongside the logits; the Trainer adds
  ``router_aux_coef * aux`` to the training loss.

Attention/norms/embedding reuse the dense Llama pieces so the families cannot
drift.

Dispatch is index-based (stable sort by expert + positional rank within the
group): O(k*T) index arrays and [E, C, D] expert buffers instead of the
GShard one-hot [T, E, C] dispatch/combine tensors, whose memory grows
O(T^2 * k / E * E) = O(T^2 * k) at fixed capacity factor. The router also
reports the dropped-(token, choice) fraction, surfaced as the
``moe_dropped_frac`` train metric.

``moe_dispatch="ragged"`` swaps the capacity buffers for MegaBlocks-style
DROPLESS dispatch (Gale et al., arXiv:2211.15841): sort the kT pairs by
expert id and run the three expert matmuls as grouped GEMMs over the ragged
[kT, D] sorted buffer (``ops/grouped_matmul.py``) — no padding compute, no
capacity/quality trade, ``moe_dropped_frac`` identically 0. On sharded
meshes the Trainer threads ``make_ragged_ep_dispatch`` (a manual shard_map
over the data axes: ep > 1 exchanges sorted groups by all-gather +
reduce-scatter; plain dp/fsdp bodies are collective-free). The decode
``no_drop`` path always runs ragged — O(t*k*d) transients instead of the
old worst-case O(E*k*t*d) capacity buffers.

``config.experts_held = (first, count)`` (one chip's share of an
expert-parallel layer; :func:`experts_held`): the router keeps its width and
the local ragged dispatch leaves out the pairs of absent experts. The held
pairs sort to the front, so the dispatch WALKS A STATIC PREFIX of the sorted
order and not all ``k T`` pairs: :func:`compact_rows` = twice the even share
``k T count / num_experts``, to a whole ``grouped_matmul`` row tile (its
largest, 512 rows). It
gathers that many rows, multiplies them and adds them into ``[T, D]`` at
their tokens (a scatter-add of that many rows). A routing that holds more
pairs than the prefix takes the full-width walk in that step
(:func:`rows_walked` is the rule and what a step's ``moe_rows_walked``
counts), so no held pair is ever dropped. The share
TRAINS as well as serves: ``_moe_ffn`` is differentiable over it (``gmm``
forward, ``gmm`` against the transposed matrices and ``tgmm`` backward, the
expert leaves' gradients the uncut gradient's slices), ``models/laguna.py``
trains through it, and ``train/step.py`` takes it on one device and refuses
it by name on a mesh (the exchange of the shares' partial sums is not
written).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from . import llama
from .llama import _rmsnorm, attention_sublayer
from ..ops.collectives import psum as _psum
from ..ops.collectives import psum_scatter as _psum_scatter
from ..ops.grouped_matmul import grouped_matmul

MOE_DISPATCH_MODES = ("dense", "ragged")


@dataclasses.dataclass(frozen=True)
class MoELlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632      # per-expert FFN width
    num_layers: int = 22
    num_heads: int = 32
    num_kv_heads: int = 4
    num_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # renormalize the chosen top-k weights (Mixtral: always; Qwen3-MoE:
    # the norm_topk_prob config flag)
    norm_topk_prob: bool = True
    # per-head RMSNorm on q/k pre-rope (Qwen3-MoE); shares
    # llama.attention_sublayer's contract. Only the per-head (True) form
    # exists in MoE checkpoints — no flat variant here
    qk_norm: bool = False
    # QKV projection biases (Qwen2-MoE attention is Qwen2-style)
    attn_bias: bool = False
    # Qwen2-MoE shared expert: a dense gated MLP of this width runs on
    # EVERY token, its output scaled by sigmoid(x @ shared_gate) and added
    # to the routed combine. None = no shared expert (Mixtral/Qwen3-MoE)
    shared_expert_intermediate: Optional[int] = None
    # expert-dispatch backend: "dense" = static [E, C, D] capacity buffers
    # (Switch/GShard; overflow drops to the residual), "ragged" = dropless
    # sort-based dispatch + grouped GEMMs over the [kT, D] sorted buffer
    # (MegaBlocks, arXiv:2211.15841) — no padding compute, no capacity knob,
    # dropped_frac identically 0. The decode/no_drop path always runs ragged
    moe_dispatch: str = "dense"
    head_dim: Optional[int] = None
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rope_scaling: Optional[tuple] = None  # frozen HF rope_scaling (ops/rope.py)
    sliding_window: Optional[int] = None  # SWA band (Mixtral 8x7B ships 4096)
    # per-layer window pattern (an L-tuple, 0 = full attention that layer) —
    # same contract as the dense family's Gemma-2 schedule; rides the layer
    # scans as a traced column (llama._layer_window_column)
    layer_windows: Optional[tuple] = None
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def num_params(self) -> int:
        e, f, v = self.hidden_size, self.intermediate_size, self.vocab_size
        d = self.head_size
        hq, hkv = self.num_heads * d, self.num_kv_heads * d
        attn = e * hq + 2 * e * hkv + hq * e
        if self.qk_norm:
            attn += 2 * d
        if self.attn_bias:
            attn += hq + 2 * hkv
        moe = e * self.num_experts + self.num_experts * 3 * e * f
        if self.shared_expert_intermediate:
            moe += 3 * e * self.shared_expert_intermediate + e
        per_layer = attn + moe + 2 * e
        head = 0 if self.tie_word_embeddings else e * v
        return v * e + self.num_layers * per_layer + e + head

    def num_active_params(self) -> int:
        """Params a token actually flows through (k of E experts) — the right
        N for FLOPs/MFU accounting (total params would overstate ~E/k x)."""
        e, f, v = self.hidden_size, self.intermediate_size, self.vocab_size
        d = self.head_size
        hq, hkv = self.num_heads * d, self.num_kv_heads * d
        attn = e * hq + 2 * e * hkv + hq * e
        if self.qk_norm:
            attn += 2 * d
        if self.attn_bias:
            attn += hq + 2 * hkv
        moe = e * self.num_experts + self.experts_per_token * 3 * e * f
        if self.shared_expert_intermediate:   # always active
            moe += 3 * e * self.shared_expert_intermediate + e
        per_layer = attn + moe + 2 * e
        head = 0 if self.tie_word_embeddings else e * v
        return v * e + self.num_layers * per_layer + e + head


def init(config: MoELlamaConfig, rng: jax.Array) -> dict:
    e, f, v, l = (config.hidden_size, config.intermediate_size,
                  config.vocab_size, config.num_layers)
    ex = config.num_experts
    d = config.head_size
    hq, hkv = config.num_heads * d, config.num_kv_heads * d
    keys = iter(jax.random.split(rng, 16))

    def dense(key, shape):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(config.param_dtype)

    # key-consumption ORDER is part of the determinism contract (same seed
    # -> same params across versions): embed draws first, as it always has
    embed = dense(next(keys), (v, e))
    attn = {
        "wq": dense(next(keys), (l, e, hq)),
        "wk": dense(next(keys), (l, e, hkv)),
        "wv": dense(next(keys), (l, e, hkv)),
        "wo": dense(next(keys), (l, hq, e)),
    }
    if config.qk_norm:     # Qwen3-MoE per-head q/k RMSNorm scales
        attn.update(q_norm=jnp.ones((l, d), config.param_dtype),
                    k_norm=jnp.ones((l, d), config.param_dtype))
    if config.attn_bias:   # Qwen2-MoE QKV biases (zeros, like HF init)
        attn.update(bq=jnp.zeros((l, hq), config.param_dtype),
                    bk=jnp.zeros((l, hkv), config.param_dtype),
                    bv=jnp.zeros((l, hkv), config.param_dtype))
    moe_leaves = {
        "router": dense(next(keys), (l, e, ex)),
        "gate": dense(next(keys), (l, ex, e, f)),
        "up": dense(next(keys), (l, ex, e, f)),
        "down": dense(next(keys), (l, ex, f, e)),
    }
    if config.shared_expert_intermediate:   # Qwen2-MoE shared expert
        fs = config.shared_expert_intermediate
        moe_leaves.update(
            shared_gate_proj=dense(next(keys), (l, e, fs)),
            shared_up=dense(next(keys), (l, e, fs)),
            shared_down=dense(next(keys), (l, fs, e)),
            shared_gate=dense(next(keys), (l, e)),
        )
    params = {
        "embed": {"embedding": embed},
        "layers": {
            "attn": attn,
            "moe": moe_leaves,
            "input_norm": jnp.ones((l, e), config.param_dtype),
            "post_attn_norm": jnp.ones((l, e), config.param_dtype),
        },
        "final_norm": jnp.ones((e,), config.param_dtype),
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = dense(next(keys), (e, v))
    return params


def param_logical_axes(config: MoELlamaConfig) -> dict:
    attn_axes = {
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv"),
        "wv": ("layers", "embed", "kv"),
        "wo": ("layers", "heads", "embed"),
    }
    if config.qk_norm:
        attn_axes.update(q_norm=("layers", "head_dim_vector"),
                         k_norm=("layers", "head_dim_vector"))
    if config.attn_bias:
        attn_axes.update(bq=("layers", "heads"), bk=("layers", "kv"),
                         bv=("layers", "kv"))
    moe_axes = {
        "router": ("layers", "embed", "experts_vector"),
        "gate": ("layers", "experts", "embed", "mlp"),
        "up": ("layers", "experts", "embed", "mlp"),
        "down": ("layers", "experts", "mlp", "embed"),
    }
    if config.shared_expert_intermediate:
        # the shared expert is a plain dense MLP: megatron mlp-dim shards
        # under tp, no expert dim (replicated over ep); the scalar gate
        # vector is never sharded
        moe_axes.update(shared_gate_proj=("layers", "embed", "mlp"),
                        shared_up=("layers", "embed", "mlp"),
                        shared_down=("layers", "mlp", "embed"),
                        shared_gate=("layers", "embed_vector"))
    axes = {
        "embed": {"embedding": ("vocab", "embed")},
        "layers": {
            "attn": attn_axes,
            "moe": moe_axes,
            "input_norm": ("layers", "embed_vector"),
            "post_attn_norm": ("layers", "embed_vector"),
        },
        "final_norm": ("embed_vector",),
    }
    if not config.tie_word_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def _ragged_expert_compute(x_rows: jnp.ndarray, gate, up, down,
                           group_sizes: jnp.ndarray, cdt,
                           group_offset=None) -> jnp.ndarray:
    """The three expert matmuls as grouped GEMMs over a group-sorted row
    buffer (rows beyond ``sum(group_sizes)`` come back zero — the EP local
    slice rides that contract). With ``group_offset`` the three leaves are
    stacks of more matrices than groups, read from that matrix on
    (``grouped_matmul``'s ``group_offset``; ``experts_in_place``)."""
    gmm = partial(grouped_matmul, group_sizes=group_sizes,
                  group_offset=group_offset)
    h = jax.nn.silu(gmm(x_rows, gate.astype(cdt)))
    h = h * gmm(x_rows, up.astype(cdt))
    # tagged for REMAT_POLICIES["attn_mlp"] (the [kT, F] inner activation;
    # same role as the dense path's [E, C, F] / llama's mlp_act)
    h = checkpoint_name(h, "mlp_act")
    return gmm(h, down.astype(cdt))


EXPERT_LEAVES = ("gate", "up", "down")


def experts_in_place(config, layers: dict):
    """Take the routed-expert leaves out of what a paged step's layer scan
    slices: ``(layers without them, {leaf: [L * E, K, N]})``, or ``(layers,
    {})`` where they are not stored in the compute dtype.

    A scanned ``[L, E, K, N]`` leaf reaches the body as layer ``i``'s ``[E,
    K, N]`` slice. A ``dot`` takes that slice fused into its operand; the
    ``gmm`` Pallas call needs a materialised one, so every layer of every
    step copied all E matrices of all three leaves (1.6 GB a layer at the
    Mistral cell's size, 29.6 of its 48 ms step) before the kernel read the
    touched ones. The body closes over the stacked leaves instead (loop
    invariants, which the scan does not slice), viewed ``[L * E, K, N]`` (the
    last two dims carry the tiled layout, so a bitcast), and
    ``_moe_ffn(layer_index=i)`` has ``gmm`` start at matrix ``i * E``.

    Decided on what the leaves are, not on the model's name: with fp32
    leaves under a bf16 compute dtype the cast would be of the whole stack,
    once a layer, which is worse than the copy; that case keeps the leaves
    in the scan and casts the layer's slice. Training keeps per-layer
    leaves too (``apply_with_aux``): a leaf's gradient is per layer there,
    and behind an offset it would have the size of the stack."""
    moe = layers["moe"]
    if any(moe[k].dtype != jnp.dtype(config.dtype) for k in EXPERT_LEAVES):
        return layers, {}
    stacked = {k: moe[k].reshape(-1, *moe[k].shape[2:])
               for k in EXPERT_LEAVES}
    rest = {k: v for k, v in moe.items() if k not in EXPERT_LEAVES}
    return {**layers, "moe": rest}, stacked


def experts_held(config) -> tuple[int, int]:
    """``(first, count)`` of the routed experts whose weights this program
    holds: all ``num_experts`` unless the config states a share
    (``experts_held``, one chip's part of an expert-parallel layer)."""
    held = getattr(config, "experts_held", None)
    return (0, config.num_experts) if held is None else tuple(held)


# the compact dispatch's buffer: this many times the even share of the pairs,
# rounded up to the LARGEST row tile grouped_matmul takes (its ``block_rows``
# cap; the tile itself follows rows-per-group, ``gmm_blocks``: a power of two
# no larger, so every tile it may choose divides the buffer)
_COMPACT_ROOM = 2
_ROW_TILE = 512


def compact_rows(config, t: int) -> int:
    """Sorted pairs the local ragged dispatch walks for ``t`` tokens while the
    held ones fit: ``k t`` (all of them) unless a held share makes twice its
    even share of the pairs, to a whole row tile, fewer. Static: shapes and
    ``experts_held`` decide it."""
    m = config.experts_per_token * t
    share = _COMPACT_ROOM * m * experts_held(config)[1] / config.num_experts
    return min(m, _ROW_TILE * math.ceil(share / _ROW_TILE))


def rows_walked(config, t: int, pairs_held) -> jnp.ndarray:
    """Rows one routed layer's dispatch gathers, multiplies and combines for
    ``t`` tokens of which ``pairs_held`` (token, expert) pairs are held here:
    :func:`compact_rows` where they fit it, else all ``k t``. THE rule: the
    dispatch's ``cond`` reads its predicate from it and
    ``models/laguna.py`` sums it into the step's ``moe_rows_walked``."""
    rows = compact_rows(config, t)
    return jnp.where(pairs_held <= rows, rows,
                     config.experts_per_token * t).astype(jnp.int32)


def _ragged_order(topk_idx, topk_probs, ex: int, k: int, first: int = 0):
    """Flatten (token, choice) pairs choice-rank-major and sort them by
    expert id counted from expert ``first`` (mod ``ex``), so the experts of a
    held share ``first .. first + count`` come FIRST in the sorted order.
    Returns (order [kT], group_sizes [ex], weight_flat [kT]);
    ``group_sizes[j]`` is expert ``(first + j) % ex``'s. Integers and the
    weights only: no row moves here."""
    t = topk_idx.shape[0]
    expert_flat = topk_idx.T.reshape(k * t)                      # [kT]
    if first:
        expert_flat = (expert_flat - first) % ex
    weight_flat = topk_probs.T.reshape(k * t)
    order = jnp.argsort(expert_flat, stable=True)
    group_sizes = jnp.bincount(expert_flat, length=ex).astype(jnp.int32)
    return order, group_sizes, weight_flat


def _ragged_sort(xt: jnp.ndarray, topk_idx, topk_probs, ex: int, k: int, cdt,
                 first: int = 0):
    """:func:`_ragged_order` and the FULL-WIDTH row buffer: returns (order,
    group_sizes, x_sorted [kT, D], weight_flat [kT]). What the sharded
    exchange (``make_ragged_ep_dispatch``) sorts with; the local dispatch
    gathers its own rows, a prefix of them where a share is held
    (``_ragged_dispatch``).

    Pair i is token (i mod t): sorted rows gather straight from xt. On this
    full-width path row movement is gather-only, like the dense path; the
    one int32 scatter lives in ``_ragged_combine``'s permutation inversion."""
    order, group_sizes, weight_flat = _ragged_order(topk_idx, topk_probs,
                                                    ex, k, first)
    x_sorted = xt[order % xt.shape[0]].astype(cdt)               # [kT, D]
    return order, group_sizes, x_sorted, weight_flat


def _ragged_combine(out_sorted: jnp.ndarray, order, weight_flat,
                    k: int, t: int, cdt) -> jnp.ndarray:
    """Weight the sorted output rows and sum each token's -> [t, D].

    ``out_sorted`` ``[kT, D]`` (every pair): unsort (int32 inversion scatter
    + row gather), weight, and combine the k contributions of each token
    (adjacent in the choice-rank-major layout: a reshape and a dense sum, no
    scatter-add). ``out_sorted`` ``[rows, D]`` with ``rows < kT`` (the
    compact dispatch: the first ``rows`` sorted pairs): ADD row i, weighted,
    into the output at pair ``order[i]``'s token, a scatter-add of ``rows``
    rows whose transpose is a gather of ``rows`` rows; rows past the held
    pairs are zero (``grouped_matmul``'s contract) and add nothing. Neither
    the ``[kT, D]`` buffers nor the ``[kT]`` inversion exist on that path."""
    m, d = k * t, out_sorted.shape[1]
    rows = out_sorted.shape[0]
    if rows < m:
        head = order[:rows]
        weighted = out_sorted * weight_flat[head][:, None].astype(cdt)
        return jnp.zeros((t, d), weighted.dtype).at[head % t].add(weighted)
    inv = (jnp.zeros((m,), jnp.int32)
           .at[order].set(jnp.arange(m, dtype=jnp.int32)))
    y_choice = out_sorted[inv]                                   # pair order
    return jnp.sum((y_choice * weight_flat[:, None].astype(cdt))
                   .reshape(k, t, d), axis=0)


def _walk(order, group_sizes, xt, weight_flat, gate, up, down, layer_index,
          *, rows: int, k: int, held: int, cdt):
    """The first ``rows`` sorted pairs: gather their rows, multiply, combine
    -> ``(y [t, D], the held group sizes)``. (The sizes are cut here, after
    the gather, and handed back, so a dispatch that walks every pair lowers
    to the text it always had.)"""
    t = xt.shape[0]
    x_sorted = xt[order[:rows] % t].astype(cdt)                  # [rows, D]
    sizes = group_sizes[:held]
    out_sorted = _ragged_expert_compute(
        x_sorted, gate, up, down, sizes, cdt,
        None if layer_index is None else layer_index * held)
    return _ragged_combine(out_sorted, order, weight_flat, k, t, cdt), sizes


# ONE trace and one lowering for every call of a shape: a step holds both
# branches of the dispatch's cond in every sparse layer, forward, rematted
# and transposed, and tracing each afresh (24 Pallas calls a layer) doubled
# the step's lowering time; a family's sparse layers call it with the same
# shapes. What the trace reads outside its arguments (grouped_matmul's
# choice of implementation for the backend) is fixed at the first call of a
# shape: a test that steers that choice clears it (``_walk_jit.clear_cache()``)
_walk_jit = jax.jit(_walk, static_argnames=("rows", "k", "held", "cdt"))


def _ragged_dispatch(config: MoELlamaConfig, xt: jnp.ndarray, topk_idx,
                     topk_probs, moe: dict, cdt,
                     layer_index=None) -> jnp.ndarray:
    """Dropless sorted dispatch (single-shard form): sort (token, choice)
    pairs by expert id, gather their rows, run the experts as grouped GEMMs
    over the sorted buffer, weight, combine. No capacity buffers, no drops;
    transients are O(k*T*D) — at decode (t == 1..few) that is O(t*k*d) vs
    the dense no_drop path's O(E*k*t*d) worst-case buffers.

    With a held share (``experts_held``) the router still chooses among all
    ``ex`` experts; the held ones sort first, the group sizes are theirs
    alone, and the pairs of absent experts lie past ``sum(group_sizes)``,
    where the grouped matmul returns zeros: their part of the sum is left
    out, which is one chip's output before an expert-parallel exchange.
    Such a dispatch walks the first :func:`compact_rows` sorted pairs only
    (static; twice the even share of the pairs, to a row tile) where that
    is fewer than ``k T``: the gather, the three grouped products and the
    combine (a scatter-add, ``_ragged_combine``) move that many rows. A
    routing that holds MORE pairs than that (every choice of a token may be
    a held expert) walks all ``k T`` in that step, chosen by ``lax.cond`` on
    :func:`rows_walked`, so the result is exact whatever the routing; that
    full-width branch is its own ``jax.checkpoint``, so a step that does
    not take it writes none of its ``[kT, .]`` residuals.
    ``layer_index``: the expert leaves are every layer's, stacked ``[L *
    held, K, N]``, and this layer's begin at ``layer_index * held``.
    Returns ``(y, group_sizes)``."""
    t = xt.shape[0]
    ex, k = config.num_experts, config.experts_per_token
    first, held = experts_held(config)
    order, group_sizes, weight_flat = _ragged_order(topk_idx, topk_probs,
                                                    ex, k, first)
    # the leaves in the compute dtype BEFORE the cond: both branches keep
    # the same three stacks for their backward, so they pass the cond as
    # its operands and neither branch writes a copy (or zeros) of them
    operands = (order, group_sizes, xt, weight_flat,
                *(moe[n].astype(cdt) for n in EXPERT_LEAVES), layer_index)
    rows = compact_rows(config, t)
    static = dict(k=k, held=held, cdt=cdt)
    if rows == k * t:     # every pair is walked: the one program there is
        return _walk(*operands, rows=rows, **static)
    fits = rows_walked(config, t, jnp.sum(group_sizes[:held])) == rows
    return jax.lax.cond(
        fits, partial(_walk_jit, rows=rows, **static),
        jax.checkpoint(partial(_walk_jit, rows=k * t, **static)), *operands)


@jax.named_scope("experts")
def _moe_ffn(config: MoELlamaConfig, x: jnp.ndarray, moe: dict,
             tp_axis: Optional[str] = None, no_drop: bool = False,
             moe_ep=None, return_counts: bool = False, layer_index=None):
    """Top-k routed FFN. x: [B, S, D]. Returns (y, aux_loss, dropped_frac).

    The router is a softmax over the experts, or (``config.router_act ==
    "sigmoid"``, DeepSeek-V3) a sigmoid per expert with the choice made on
    ``score + moe["router_bias"]`` and the weights taken from the scores
    alone, times ``routed_scaling_factor``. The shared expert
    (``shared_up`` present) is gated by ``shared_gate`` where that leaf
    exists (Qwen2-MoE) and added as it is where it does not.

    ``config.experts_held = (first, count)``: the expert leaves hold that
    share only (ragged dispatch; see ``_ragged_dispatch``).
    ``return_counts`` adds a fourth result, int32 ``[pairs routed, pairs
    held here, experts touched, the fullest expert's pairs]``.
    ``layer_index`` (the paged steps, ``experts_in_place``): ``moe``'s
    ``gate`` / ``up`` / ``down`` are the leaves of ALL layers, stacked ``[L *
    E, K, N]``, and the local ragged dispatch reads this layer's in place.

    Two dispatch backends, selected by ``config.moe_dispatch``:

    - ``"dense"`` (default, the parity reference): index-based gather-only
      dispatch into static [E, C, D] capacity buffers + batched expert
      einsums. O(k*T) index arrays; overflow pairs drop to the residual
      (Switch/GShard). Row data moves by GATHER only (the single scatter is
      the int32 slot-map inversion; the combine is a reshape+sum over the
      choice-rank-major pair layout) — TPU scatters serialize on write
      hazards (what a row scatter costs is not measured on this tree).
      Capacity priority is greedy by choice rank then token order.
    - ``"ragged"``: dropless sorted dispatch + grouped GEMMs over the
      [kT, D] sorted buffer (MegaBlocks, arXiv:2211.15841) — no padding
      compute, no capacity/quality trade, ``dropped_frac`` identically 0.
      A held share walks a static prefix of that buffer
      (``_ragged_dispatch``, :func:`compact_rows`).

    ``no_drop`` (the decode path) always runs ragged: it is dropless by
    construction at O(t*k*d) transients, where the old dense no_drop
    allocated worst-case ``k*t`` capacity per expert — O(E*k*t*d), ~2 GiB a
    layer on a 2k-token qwen1.5-moe prompt.

    ``tp_axis``: set inside a shard_map region where tp is a *manual* axis
    (the pipeline schedule). The router is replicated over tp, so every
    member computes identical dispatch indices; gate/up/down arrive as
    megatron mlp-dim shards and the combined output is a partial sum —
    combine is linear in the expert outputs, so one psum of y at the end is
    exact for both backends (it commutes with gathers and the reshape+sum
    combine, and grouped GEMMs contract the mlp dim only in ``down``).

    ``moe_ep``: expert-parallel ragged dispatch callable built by
    ``make_ragged_ep_dispatch`` (threaded in by the Trainer when the plan
    has ep > 1 and the config says ragged); replaces the local sorted
    dispatch with the shard_map'd sorted-group exchange.
    """
    b, s, d = x.shape
    t = b * s
    ex, k = config.num_experts, config.experts_per_token
    dispatch = getattr(config, "moe_dispatch", "dense")
    if dispatch not in MOE_DISPATCH_MODES:
        raise ValueError(f"unknown moe_dispatch {dispatch!r}; choose from "
                         f"{MOE_DISPATCH_MODES}")
    if no_drop:
        dispatch = "ragged"
    cdt = config.dtype
    if experts_held(config) != (0, ex) and (dispatch != "ragged"
                                            or moe_ep is not None):
        raise ValueError(
            "experts_held (one chip's share of the experts) runs the local "
            "ragged dispatch only: set moe_dispatch='ragged', no moe_ep")

    xt = x.reshape(t, d)
    with jax.named_scope("router"):
        router_logits = (xt.astype(jnp.float32)
                         @ moe["router"].astype(jnp.float32))   # [T, E]
        if getattr(config, "router_act", "softmax") == "sigmoid":
            scores = jax.nn.sigmoid(router_logits)
            choice = scores
            if "router_bias" in moe:    # moves the choice, not the weight
                choice = scores + moe["router_bias"].astype(jnp.float32)
            _, topk_idx = jax.lax.top_k(choice, k)               # [T, k]
            topk_probs = jnp.take_along_axis(scores, topk_idx, axis=-1)
            probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        else:
            probs = jax.nn.softmax(router_logits, axis=-1)
            topk_probs, topk_idx = jax.lax.top_k(probs, k)       # [T, k]
        if getattr(config, "norm_topk_prob", True):
            # renormalize the chosen weights (Mixtral: always; Qwen3-MoE:
            # the norm_topk_prob flag — off, the raw softmax mass is the
            # weight)
            total = jnp.sum(topk_probs, axis=-1, keepdims=True)
            eps = getattr(config, "norm_topk_eps", 0.0)   # LFM2: sum + 1e-6
            topk_probs = topk_probs / (total + eps if eps else total)
        routed_scale = getattr(config, "routed_scaling_factor", 1.0)
        if routed_scale != 1.0:
            topk_probs = topk_probs * routed_scale

    group_sizes = None
    if dispatch == "ragged":
        if moe_ep is not None:
            y = moe_ep(xt, topk_idx, topk_probs,
                       moe["gate"], moe["up"], moe["down"])
        else:
            y, group_sizes = _ragged_dispatch(config, xt, topk_idx,
                                              topk_probs, moe, cdt,
                                              layer_index)
        dropped_frac = jnp.zeros((), jnp.float32)  # dropless by construction
    else:
        capacity = max(int(math.ceil(config.capacity_factor * k * t / ex)), 1)

        # flatten (token, choice) pairs choice-rank-major -> greedy priority
        expert_flat = topk_idx.T.reshape(k * t)                  # [kT]
        weight_flat = topk_probs.T.reshape(k * t)

        # slot within each expert's buffer = rank of this pair among
        # same-expert pairs (stable sort keeps greedy priority in-group)
        order = jnp.argsort(expert_flat, stable=True)
        sorted_e = expert_flat[order]
        group_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
        pos_sorted = (jnp.arange(k * t, dtype=jnp.int32)
                      - group_start.astype(jnp.int32))
        pos_flat = jnp.zeros((k * t,), jnp.int32).at[order].set(pos_sorted)

        keep = pos_flat < capacity
        dropped_frac = 1.0 - jnp.mean(keep.astype(jnp.float32))
        # overflow pairs target a sacrificial slot that is sliced off
        dest = jnp.where(keep, expert_flat * capacity + pos_flat, ex * capacity)

        # Fill the [E, C, D] buffers by GATHER, not by scattering rows: the
        # only scatter is int32 — invert the slot map (which pair fills slot
        # (e, c)?), then gather rows. Slots nobody fills keep the sentinel
        # kT and gather the appended zero row.
        inv = (jnp.full((ex * capacity + 1,), k * t, jnp.int32)
               .at[dest].set(jnp.arange(k * t, dtype=jnp.int32),
                             mode="drop")[:-1])
        # pair i is token (i mod t): gather straight from xt — no k-fold
        # tiled copy — and mask empty slots to reproduce zero-filled buffers
        expert_in = jnp.where((inv < k * t)[:, None],
                              xt[inv % t].astype(cdt), 0).reshape(ex, capacity, d)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in,
                                   moe["gate"].astype(cdt)))
        h = h * jnp.einsum("ecd,edf->ecf", expert_in, moe["up"].astype(cdt))
        # tagged for REMAT_POLICIES["attn_mlp"] (the [E,C,F] inner
        # activation; same role as llama's mlp_act)
        h = checkpoint_name(h, "mlp_act")
        expert_out = jnp.einsum("ecf,efd->ecd", h, moe["down"].astype(cdt))

        out_flat = expert_out.reshape(ex * capacity, d)
        y_choice = out_flat[jnp.clip(dest, 0, ex * capacity - 1)]
        y_choice = jnp.where(keep[:, None], y_choice, 0)
        # un-route without a scatter-add: pair i is token (i mod t), so the
        # k contributions of each token are exactly the k rows of the
        # choice-rank-major layout — a reshape and a dense sum
        y = jnp.sum((y_choice * weight_flat[:, None].astype(cdt))
                    .reshape(k, t, d), axis=0)
    if "shared_up" in moe:   # shared expert: a dense gated MLP on every
        # token, ADDED to the routed combine, scaled by a sigmoid scalar gate
        # where the model has one (Qwen2-MoE's shared_gate). Under manual tp
        # its mlp-dim-sharded down-proj is a partial sum like the routed one
        # — the single psum below covers both (addition commutes with psum)
        with jax.named_scope("shared_expert"):
            xs = xt.astype(cdt)
            hs = jax.nn.silu(xs @ moe["shared_gate_proj"].astype(cdt))
            hs = hs * (xs @ moe["shared_up"].astype(cdt))
            shared_out = hs @ moe["shared_down"].astype(cdt)
            if "shared_gate" in moe:
                sgate = jax.nn.sigmoid(
                    (xt.astype(jnp.float32)
                     @ moe["shared_gate"].astype(jnp.float32))[:, None])
                shared_out = sgate.astype(cdt) * shared_out
            y = y + shared_out
    if tp_axis is not None:
        y = _psum(y, tp_axis)

    # Switch load-balance loss over ALL k dispatched choices (normalized by
    # k): E * sum_e (choice fraction)_e * (mean prob)_e — counting only the
    # first choice would never penalize second-choice hot spots
    with jax.named_scope("router"):
        token_frac = jnp.mean(
            jax.nn.one_hot(topk_idx, ex, dtype=jnp.float32), axis=(0, 1))
        prob_frac = jnp.mean(probs, axis=0)
        aux = ex * jnp.sum(token_frac * prob_frac)
    if return_counts:
        if group_sizes is None:
            raise ValueError("return_counts reads the local ragged "
                             "dispatch's group sizes")
        counts = jnp.stack([jnp.int32(k * t), jnp.sum(group_sizes),
                            jnp.sum(group_sizes > 0), jnp.max(group_sizes)]
                           ).astype(jnp.int32)
        return y.reshape(b, s, d), aux, dropped_frac, counts
    return y.reshape(b, s, d), aux, dropped_frac


def _local_groups_compute(x_sorted: jnp.ndarray, sizes: jnp.ndarray, gate,
                          up, down, e0, e_local: int, cdt) -> jnp.ndarray:
    """Grouped-GEMM the ``e_local`` experts starting at (traced) expert
    ``e0`` over their contiguous run of a group-sorted row buffer; rows
    outside those groups come back zero. The run starts at the sum of
    earlier group sizes — a worst-case-static window is sliced from a
    zero-padded copy (the tail past the local groups is garbage the
    grouped-matmul contract zeroes out). Shared by the bulk (all-gather)
    and ring (double-buffered) EP bodies."""
    m, d = x_sorted.shape
    ex = sizes.shape[0]
    local_sizes = jax.lax.dynamic_slice(sizes, (e0,), (e_local,))
    start = jnp.sum(jnp.where(jnp.arange(ex) < e0, sizes, 0))
    x_pad = jnp.concatenate([x_sorted, jnp.zeros_like(x_sorted)], axis=0)
    x_local = jax.lax.dynamic_slice(x_pad, (start, 0), (m, d))
    out_local = _ragged_expert_compute(x_local, gate, up, down,
                                       local_sizes, cdt)
    out_pad = jnp.zeros((2 * m, d), out_local.dtype)
    out_pad = jax.lax.dynamic_update_slice(out_pad, out_local, (start, 0))
    return out_pad[:m]  # zeros outside this shard's groups


def make_ragged_ep_dispatch(mesh, config: MoELlamaConfig, *,
                            data_axes=("dp", "fsdp", "ep"), ep_axis="ep",
                            embed_axis: Optional[str] = None):
    """Sharded dropless dispatch: a shard_map over the data axes that
    exchanges *sorted expert groups* instead of the dense path's [E, C, D]
    capacity buffer.

    Each (dp, fsdp) row all-gathers its token rows + routing over ``ep``,
    sorts (token, choice) pairs by expert id, and runs the grouped GEMMs on
    the slice of the sorted buffer belonging to its E/ep local experts (a
    worst-case-static [kT, D] window whose garbage tail the grouped-matmul
    contract zeroes); per-shard partial outputs reduce-scatter back to the
    local token rows. The gather + reduce-scatter pair carries the same
    O(T*D) bytes as the dense path's two GSPMD all-to-alls — what it removes
    is the E/ep-fold capacity-padding compute and the drop/quality trade.

    Also used WITHOUT an ep axis (plain dp/fsdp data sharding, ep == 1):
    every shard then owns all experts and the body is collective-free —
    local sort + grouped GEMMs over local tokens. Keeping the region manual
    matters twice: GSPMD cannot partition the data-dependent sort/gather the
    way it partitions the dense path's static einsums (on jax<0.5 CPU it
    aborts outright with "PartitionId instruction is not supported"), and
    on TPU the manual body guarantees zero cross-chip traffic for the
    dp-only case instead of whatever the partitioner falls back to.
    Returns None on a single-shard mesh (the plain local path IS the
    program).

    Autodiff works through the map because every collective is an
    all_gather/psum_scatter pair (clean transposes of each other) and the
    router math stays OUTSIDE the map (no replicated differentiable inputs).

    ``embed_axis``: mesh axis sharding the weights' embed dim (ep_fsdp
    plans pass "fsdp"); the body all-gathers that dim before compute and the
    transpose reduce-scatters the weight cotangent — exactly FSDP semantics,
    hand-spelled because the region is manual.
    """
    from jax.sharding import PartitionSpec as P

    ex, k = config.num_experts, config.experts_per_token
    ep = mesh.shape.get(ep_axis, 1)
    if ep > 1 and ex % ep:
        raise ValueError(
            f"moe_dispatch='ragged' under expert parallelism needs "
            f"num_experts divisible by the ep axis; got E={ex}, ep={ep} — "
            f"change the mesh or use moe_dispatch='dense' (which falls back "
            f"to replication on non-divisible dims)")
    e_local = ex // ep
    axes = tuple(a for a in data_axes if mesh.shape.get(a, 1) > 1)
    if embed_axis is not None and mesh.shape.get(embed_axis, 1) <= 1:
        embed_axis = None
    if not axes and embed_axis is None:
        return None  # single-shard mesh: the plain local path is the program
    manual = set(axes) | ({embed_axis} if embed_axis else set())
    cdt = config.dtype
    row_spec = P(axes if axes else None, None)
    gu_spec = P(ep_axis if ep > 1 else None, embed_axis, None)
    down_spec = P(ep_axis if ep > 1 else None, None, embed_axis)

    def body(xt, topk_idx, topk_probs, gate, up, down):
        if embed_axis is not None:
            gate = jax.lax.all_gather(gate, embed_axis, axis=1, tiled=True)
            up = jax.lax.all_gather(up, embed_axis, axis=1, tiled=True)
            down = jax.lax.all_gather(down, embed_axis, axis=2, tiled=True)
        if ep == 1:
            # no expert axis: every shard owns all experts and just runs
            # its own tokens — purely local, no collectives at all
            order, sizes, x_sorted, weight_flat = _ragged_sort(
                xt, topk_idx, topk_probs, ex, k, cdt)
            out_sorted = _ragged_expert_compute(x_sorted, gate, up, down,
                                                sizes, cdt)
            return _ragged_combine(out_sorted, order, weight_flat, k,
                                   xt.shape[0], cdt)
        # pull the whole (dp, fsdp) row's tokens + routing in, sort once
        # globally, compute the local experts' contiguous window (zeros for
        # rows routed elsewhere), reduce-scatter the partials back to each
        # token's home shard
        xt = jax.lax.all_gather(xt, ep_axis, axis=0, tiled=True)
        topk_idx = jax.lax.all_gather(topk_idx, ep_axis, axis=0, tiled=True)
        topk_probs = jax.lax.all_gather(topk_probs, ep_axis, axis=0,
                                        tiled=True)
        e0 = jax.lax.axis_index(ep_axis) * e_local
        order, sizes, x_sorted, weight_flat = _ragged_sort(
            xt, topk_idx, topk_probs, ex, k, cdt)
        out_sorted = _local_groups_compute(x_sorted, sizes, gate, up, down,
                                           e0, e_local, cdt)
        y = _ragged_combine(out_sorted, order, weight_flat, k, xt.shape[0],
                            cdt)
        return _psum_scatter(y, ep_axis)

    sm = jax.shard_map(body, mesh=mesh, axis_names=manual, check_vma=False,
                       in_specs=(row_spec, row_spec, row_spec,
                                 gu_spec, gu_spec, down_spec),
                       out_specs=row_spec)

    def dispatch(xt, topk_idx, topk_probs, gate, up, down):
        return sm(xt, topk_idx, topk_probs, gate, up, down)

    return dispatch


def _block(config: MoELlamaConfig, carry, layer: dict, positions, attn_impl,
           standard_layout=True, tp_axis=None, moe_ep=None,
           window_override=None):
    x, aux_acc, dropped_acc = carry
    attn = attention_sublayer(config, x, layer["attn"], layer["input_norm"],
                              positions, attn_impl, standard_layout, tp_axis,
                              window_override=window_override)
    x = x + attn

    with jax.named_scope("experts"):   # the FFN's pre-norm is its own
        h = _rmsnorm(x, layer["post_attn_norm"], config.rms_norm_eps)
    y, aux, dropped = _moe_ffn(config, h, layer["moe"], tp_axis,
                               moe_ep=moe_ep)
    return (x + y, aux_acc + aux, dropped_acc + dropped)


def apply_with_aux(
    config: MoELlamaConfig,
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
    """Forward -> (logits [B,S,V] fp32, mean router aux loss[, metrics]).

    ``return_metrics`` adds a dict of routing observability scalars
    (currently ``dropped_frac``: mean fraction of (token, choice) pairs that
    overflowed expert capacity — identically 0 under ragged dispatch)
    without changing the stable 2-tuple API. ``return_hidden`` swaps the
    logits for the final-normed hidden states [B, S, E] (chunked-loss path —
    pair with ``output_weights``). ``moe_ep``: expert-parallel ragged
    dispatch callable (``make_ragged_ep_dispatch``), threaded to every
    layer's routed FFN."""
    standard_layout = positions is None
    if positions is None:
        positions = jnp.arange(input_ids.shape[1])[None, :]
    positions = jnp.broadcast_to(positions, input_ids.shape)

    x = llama.embed_tokens(config, params, input_ids, positions)

    block = partial(_block, config, positions=positions, attn_impl=attn_impl,
                    standard_layout=standard_layout, moe_ep=moe_ep)

    wins = llama._layer_window_column(config)
    zero = jnp.zeros((), jnp.float32)

    def scan_body(carry, xs):
        if wins is not None:   # per-layer window column rides the scan
            layer_params, w = xs
            new_carry = block(carry, layer_params, window_override=w)
        else:
            new_carry = block(carry, xs)
        if activation_sharding is not None:
            new_carry = (jax.lax.with_sharding_constraint(
                new_carry[0], activation_sharding), *new_carry[1:])
        return new_carry, None

    if remat:
        policy = remat_policy or jax.checkpoint_policies.nothing_saveable
        scan_body = jax.checkpoint(scan_body, policy=policy,
                                   prevent_cse=False)

    scan_xs = (params["layers"] if wins is None
               else (params["layers"], wins))
    with jax.named_scope("layers"):   # the scan's own slicing and stacking
        (x, aux, dropped), _ = jax.lax.scan(scan_body, (x, zero, zero),
                                            scan_xs)

    out = (llama.final_hidden(config, params, x) if return_hidden
           else llama.lm_head_logits(config, params, x))
    aux = aux / config.num_layers
    if return_metrics:
        return out, aux, {"moe_dropped_frac": dropped / config.num_layers}
    return out, aux


def apply(config, params, input_ids, positions=None, **kw):
    logits, _ = apply_with_aux(config, params, input_ids, positions, **kw)
    return logits


# embedding/head sub-forwards are shared with the dense family (identical
# params layout) — re-exported for the pipeline schedule's stage-0/last-stage
# entry points and the chunked loss
embed_tokens = llama.embed_tokens
output_weights = llama.output_weights
final_hidden = llama.final_hidden
lm_head_logits = llama.lm_head_logits
tp_embed = llama.tp_embed


# ---------------------------------------------------------------------------
# KV-cached decode (the serving engine's paged step; models/sample.py
# --kv-cache runs it at one slot): the dense families' contract, with the
# routed FFN in the block body. Expert dispatch runs with ``no_drop=True``
# — a single decode token's k choices can exceed a capacity_factor-derived
# capacity of 1
# (both choices on one expert), and a qualitative sampling path must be
# routing-exact vs the full recompute, not throughput-shaped. no_drop
# resolves to the RAGGED backend: dropless by construction at O(t*k*d)
# transients (the old dense no_drop allocated worst-case C = k*t per-expert
# buffers — O(E*k*t*d), ~2 GiB/layer on a 2k-token qwen1.5-moe prompt).
# ---------------------------------------------------------------------------

def paged_decode_step(config: MoELlamaConfig, params: dict,
                      token_ids: jnp.ndarray, positions: jnp.ndarray,
                      cache: dict, attend, last_index=None,
                      all_logits=False):
    """Paged multi-request decode/chunk step (llama.paged_decode_step
    contract: the stacked pools ride ``llama.scan_paged_layers`` as a
    carry): the routed FFN runs drop-free (ragged backend) on the
    [S, T] tokens — per-token routing is independent of the co-resident
    slots, so continuous batching cannot perturb a request's expert
    choices (and a speculative verification chunk cannot perturb the
    tokens it verifies). ``all_logits=True`` keeps every position's
    logits (speculative verification)."""
    pos2d = llama.paged_positions(token_ids, positions)
    x = embed_tokens(config, params, token_ids, pos2d)

    wins = llama._layer_window_column(config)
    layers, experts = experts_in_place(config, params["layers"])

    def body(x, pools, layer, i, w, _):
        def override(q, k, v, *, window, scale, softcap):
            return attend(q, k, v, *pools, i, window=window, scale=scale,
                          softcap=softcap)

        attn, pools = attention_sublayer(
            config, x, layer["attn"], layer["input_norm"], pos2d,
            "xla", window_override=w, attend_override=override)
        x = x + attn
        h = _rmsnorm(x, layer["post_attn_norm"], config.rms_norm_eps)
        y, _, _ = _moe_ffn(config, h, {**layer["moe"], **experts},
                           no_drop=True, layer_index=i if experts else None)
        x = x + y
        return x, pools, None

    x, pools, _ = llama.scan_paged_layers(body, x, layers, cache, wins)
    return (llama.paged_logits_at(lm_head_logits, config, params, x,
                                  last_index, all_logits), pools)


PRESETS = {
    "moe-debug": MoELlamaConfig(vocab_size=512, hidden_size=64, intermediate_size=128,
                                num_layers=2, num_heads=4, num_kv_heads=2,
                                num_experts=4, max_position_embeddings=256),
    # single-chip benchable MoE: ~0.9B total / ~0.3B active (top-2 of 8),
    # llama-650m-family dims scaled so fp32 state + remat fits 16 GB HBM
    "moe-1b-8e": MoELlamaConfig(vocab_size=32000, hidden_size=1024,
                                intermediate_size=2816, num_layers=12,
                                num_heads=16, num_kv_heads=4, num_experts=8,
                                experts_per_token=2,
                                max_position_embeddings=4096),
    # Mixtral-8x7B-shaped (public model card dims)
    "mixtral-8x7b": MoELlamaConfig(vocab_size=32000, hidden_size=4096,
                                   intermediate_size=14336, num_layers=32,
                                   num_heads=32, num_kv_heads=8, num_experts=8,
                                   experts_per_token=2, rope_theta=1e6,
                                   max_position_embeddings=32768),
    # Qwen1.5-MoE-A2.7B-shaped (public card): Qwen2 attention (QKV biases)
    # + 60 experts top-4 at width 1408 + the 5632-wide shared expert
    "qwen1.5-moe-a2.7b": MoELlamaConfig(vocab_size=151936, hidden_size=2048,
                                        intermediate_size=1408, num_layers=24,
                                        num_heads=16, num_kv_heads=16,
                                        num_experts=60, experts_per_token=4,
                                        attn_bias=True, norm_topk_prob=False,
                                        shared_expert_intermediate=5632,
                                        rope_theta=1e6, rms_norm_eps=1e-6,
                                        max_position_embeddings=8192),
    # Qwen3-MoE 30B-A3B-shaped (public card): Qwen3 attention (qk_norm,
    # head_dim 128) + 128 experts top-8 at per-expert width 768
    "qwen3-30b-a3b": MoELlamaConfig(vocab_size=151936, hidden_size=2048,
                                    intermediate_size=768, num_layers=48,
                                    num_heads=32, num_kv_heads=4, head_dim=128,
                                    num_experts=128, experts_per_token=8,
                                    qk_norm=True, norm_topk_prob=True,
                                    rope_theta=1e6, rms_norm_eps=1e-6,
                                    max_position_embeddings=40960),
}
