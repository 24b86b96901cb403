"""MiMo-V2 (XiaomiMiMo ``mimo_v2``): a decoder whose attention layers differ
in REACH. ``config.hybrid_layer_pattern[l]`` says whether layer ``l`` attends
over every earlier position (``0``, a FULL layer) or over the last
``sliding_window`` positions, its own included (``1``, a WINDOW layer); the
two kinds have their own kv-head counts (``num_kv_heads`` /
``swa_num_kv_heads``) and rope bases (``rope_theta`` / ``swa_rope_theta``),
and a window layer's softmax has one more column a query head, a learned SINK
logit that takes probability and gives no value
(``add_swa_attention_sink_bias``). ``config.moe_layer_freq[l]`` says whether
the layer's FFN is dense (``0``) or routed (``1``). The layer order is read
from those two fields and from nothing else.

Attention, both kinds (:func:`attention_sublayer`): key heads are
``head_dim`` wide and value heads ``v_head_dim`` (192 and 128 as published),
no bias, no QK-norm; rope turns the FIRST ``int(head_dim x
partial_rotary_factor)`` columns of every q and k head (rotate-half inside
them) and the rest pass through; scores ``q k^T / sqrt(head_dim)``; the values
are multiplied by ``attention_value_scale``.

The cache has a PAGE CLASS per kind (``serve/kv_pages.py``): the full layers'
pages are kept for a sequence's whole context, the window layers' only while
a query can still see them, and the scheduler hands the rest back. Both
classes store a key in TWO 128-wide pool rows (192 -> 256 columns, the last
64 zeros; ``key_parts``: part ``j`` of a layer's keys is the k pool's layer
``2 l + j``) beside the 128-wide value row, so every pool leaf has rows of one
lane tile, which the compiled paged kernel reads where they lie (a 256-wide
row of 4 heads is tiled in HBM in an order the kernel's page view is not: a
3.5 GB copy a layer, which the compiler showed); q is padded alike and the
zero columns add nothing to a score.

Experts: ``models/moe._moe_ffn`` with the sigmoid router, a per-expert bias
that moves the choice only, weights ``s / sum`` and ``experts_held`` (one
chip's share of an expert-parallel layer); the expert leaves stay where they
lie and ``gmm`` addresses the layer (``moe.experts_in_place``).

The layers are WALKED like ``models/lfm2.py``'s (consecutive layers differ in
kind), layer ``l`` taking its leaves of each kind by a static index. The
attention and dense-FFN matrices are a LIST of per-layer leaves a kind
(``attn_full``, ``attn_window``, ``mlp``), not a stack: a static row of a
stacked ``[n, 4096, 12288]`` leaf reached the projection's ``dot`` as a slice
written out to HBM and read back (the compiled decode step showed a
``slice_bitcast_fusion`` and a ``copy`` a matrix: three passes over 1.7 GB of
weights a step where one is required), and a leaf of its own is read once. The
routed experts stay stacked (``gmm`` addresses the layer by offset), as do the
two norms and the routers. The pools ride the walk whole, addressed by the
layer's row among its kind.

Serving and the plain forward only, like ``models/mla.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import llama
from .llama import _rmsnorm, mlp_sublayer
from .moe import _moe_ffn, experts_held, experts_in_place
from ..ops.attention import multihead_attention
from ..ops.rope import apply_rope

FULL, WINDOW = "full", "window"     # the page classes' names (kv_pages)
LANES = 128

# what ServeEngine refuses for this family, by the option's name
SERVE_REFUSES = {
    "kv_dtype='int8'": "both page classes are stored in float",
    "weight_dtype='int8'": "serve/weights.py selects llama leaves only",
    "max_adapters": "the LoRA hooks wrap llama's projections",
    "speculate": "a rejected draft would have to take back window pages "
                 "already returned",
    "host_tier_bytes": "the tier's gather walks one page class",
    "plan / shard_kv": "the tp serve mesh splits one kv-head count; the two "
                       "page classes have two",
    "disaggregation": "the handoff moves the pages of one class",
    "engine swap": "a swap exports and seats the pages of one class",
    "prefix_cache": "a hit would need the window layers' last positions at "
                    "its end, and their pages were returned",
    "decode_horizon": "window pages are taken and returned on the host "
                      "between two steps",
}


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    # 0 = full attention, 1 = window attention, a layer each
    hybrid_layer_pattern: tuple = (0,) + ((1,) * 4 + (0,)) + \
        ((1,) * 5 + (0,)) * 7
    # 0 = dense FFN, 1 = routed experts
    moe_layer_freq: tuple = (0,) + (1,) * 47
    num_heads: int = 64
    num_kv_heads: int = 4                     # the full layers'
    swa_num_kv_heads: int = 8                 # the window layers'
    head_dim: int = 192                       # q and k
    v_head_dim: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 10000000.0            # the full layers'
    swa_rope_theta: float = 10000.0           # the window layers'
    sliding_window: int = 128
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    intermediate_size: int = 16384            # the dense FFNs' width
    moe_intermediate_size: int = 2048         # every expert's width
    num_experts: int = 256                    # the router's outputs
    experts_per_token: int = 8
    # (first, count): the routed experts whose weights this program holds
    # (None = all): one chip's share of an expert-parallel layer
    experts_held: Optional[tuple] = None
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
    # lanes a pool row is a multiple of: a lane tile, so that the compiled
    # paged kernel takes the rows (the tests' debug preset narrows it)
    row_lanes: int = LANES
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        n = len(self.hybrid_layer_pattern)
        if len(self.moe_layer_freq) != n:
            raise ValueError(f"moe_layer_freq names {len(self.moe_layer_freq)}"
                             f" layers, hybrid_layer_pattern {n}")
        for name in ("hybrid_layer_pattern", "moe_layer_freq"):
            bad = [v for v in getattr(self, name) if v not in (0, 1)]
            if bad:
                raise ValueError(f"{name} entries are 0 or 1; got {bad}")
        if self.rotary_dims % 2 or not 0 < self.rotary_dims <= self.head_dim:
            raise ValueError(f"rope on {self.rotary_dims} of {self.head_dim} "
                             f"columns")

    @property
    def num_layers(self) -> int:
        return len(self.hybrid_layer_pattern)

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def rotary_dims(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def num_window_layers(self) -> int:
        return sum(self.hybrid_layer_pattern)

    @property
    def num_kv_layers(self) -> int:
        """Layers with full-class pages: the full layers."""
        return self.num_layers - self.num_window_layers

    def kv_heads(self, kind: str) -> int:
        return self.swa_num_kv_heads if kind == WINDOW else self.num_kv_heads

    def has_sink(self, kind: str) -> bool:
        return (self.add_swa_attention_sink_bias if kind == WINDOW
                else self.add_full_attention_sink_bias)

    @property
    def row_width(self) -> int:
        """A pool row: the value head in whole ``row_lanes``."""
        return -(-self.v_head_dim // self.row_lanes) * self.row_lanes

    @property
    def key_parts(self) -> int:
        """Pool rows a key lies in (``serve/kv_pages.key_parts``)."""
        return -(-self.head_dim // self.row_width)

    def _row(self, kind: str) -> dict:
        shape = (self.kv_heads(kind), self.row_width)
        return {"k": shape, "v": shape}

    def kv_layout(self) -> dict:
        """One cached token in one FULL layer
        (``serve/kv_pages.pool_layout``): the value row, and each of the
        key's ``key_parts`` rows."""
        return self._row(FULL)

    def window_kv_layout(self) -> Optional[dict]:
        """The second page class (``serve/kv_pages.window_layout``): the
        window layers', or None where the pattern has none."""
        if not self.num_window_layers:
            return None
        return {"layers": self.num_window_layers,
                "window": self.sliding_window, **self._row(WINDOW)}

    def layer_table(self) -> tuple:
        """Per layer ``(attention kind, its row among the layers of that
        kind, True where the FFN is dense, its row among the FFNs of that
        kind)``."""
        rows, seen = [], {FULL: 0, WINDOW: 0, True: 0, False: 0}
        for window, routed in zip(self.hybrid_layer_pattern,
                                  self.moe_layer_freq):
            kind, dense = (WINDOW if window else FULL), not routed
            rows.append((kind, seen[kind], dense, seen[dense]))
            seen[kind] += 1
            seen[dense] += 1
        return tuple(rows)

    def _sizes(self) -> dict:
        e, hq = self.hidden_size, self.num_heads

        def attn(kind):
            hkv = self.kv_heads(kind)
            return (e * (hq * self.head_dim + hkv * self.head_dim
                         + hkv * self.v_head_dim)
                    + hq * self.v_head_dim * e
                    + (hq if self.has_sink(kind) else 0))

        return {FULL: attn(FULL), WINDOW: attn(WINDOW),
                "dense": 3 * e * self.intermediate_size,
                "expert": 3 * e * self.moe_intermediate_size,
                "router": e * self.num_experts + self.num_experts}

    def _count(self, experts: int) -> int:
        s, e = self._sizes(), self.hidden_size
        n_moe = sum(self.moe_layer_freq)
        top = self.vocab_size * e * (1 if self.tie_word_embeddings else 2) + e
        return (top + 2 * e * self.num_layers
                + self.num_kv_layers * s[FULL]
                + self.num_window_layers * s[WINDOW]
                + (self.num_layers - n_moe) * s["dense"]
                + n_moe * (s["router"] + experts * s["expert"]))

    def num_params(self) -> int:
        """Parameters HELD (``experts_held`` experts a routed layer)."""
        return self._count(experts_held(self)[1])

    def num_active_params(self) -> int:
        return self._count(self.experts_per_token)


def _attn_shapes(config: MimoV2Config, kind: str) -> dict:
    """One layer's attention leaves of ``kind``."""
    e, hq = config.hidden_size, config.num_heads
    hkv, dk, dv = config.kv_heads(kind), config.head_dim, config.v_head_dim
    # wq and wk are stored [out, in]: see attention_sublayer
    shapes = {"wq": (hq * dk, e), "wk": (hkv * dk, e),
              "wv": (e, hkv * dv), "wo": (hq * dv, e)}
    if config.has_sink(kind):
        shapes["sink"] = (hq,)
    return shapes


def init(config: MimoV2Config, rng: jax.Array) -> dict:
    e, v = config.hidden_size, config.vocab_size
    f, fe, ex = (config.intermediate_size, config.moe_intermediate_size,
                 config.num_experts)
    held = experts_held(config)[1]
    n, nw = config.num_layers, config.num_window_layers
    nm = sum(config.moe_layer_freq)
    keys = iter(jax.random.split(rng, 16 + 8 * n))
    pdt = config.param_dtype

    def dense(shape, std=0.02):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(pdt)

    def attn(kind, rows):
        return [{name: dense(shape, 1.0 if name == "sink" else 0.02)
                 for name, shape in _attn_shapes(config, kind).items()}
                for _ in range(rows)]

    params = {
        "embed": {"embedding": dense((v, e))},
        "layers": {
            "attn_norm": jnp.ones((n, e), pdt),
            "ffn_norm": jnp.ones((n, e), pdt),
            "attn_full": attn(FULL, n - nw),
            "attn_window": attn(WINDOW, nw),
            "mlp": [{"gate": dense((e, f)), "up": dense((e, f)),
                     "down": dense((f, e))} for _ in range(n - nm)],
            "moe": {"router": dense((nm, e, ex)),
                    "router_bias": dense((nm, ex)),
                    "gate": dense((nm, held, e, fe)),
                    "up": dense((nm, held, e, fe)),
                    "down": dense((nm, held, fe, e))},
        },
        "final_norm": jnp.ones((e,), pdt),
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = dense((e, v))
    return params


def param_logical_axes(config: MimoV2Config) -> dict:
    """Logical axes: a list of per-layer leaves for the attention kinds and
    the dense FFNs, stacks (leading axis ``layers``) for the norms and the
    routed FFNs. No serve mesh runs this family yet (``SERVE_REFUSES``)."""
    nw, nm = config.num_window_layers, sum(config.moe_layer_freq)

    def attn(kind, rows):
        axes = {"wq": ("heads", "embed"), "wk": ("kv", "embed"),
                "wv": ("embed", "kv"), "wo": ("heads", "embed")}
        if config.has_sink(kind):
            axes["sink"] = (None,)
        return [dict(axes) for _ in range(rows)]

    axes = {
        "embed": {"embedding": ("vocab", "embed")},
        "layers": {
            "attn_norm": ("layers", "embed_vector"),
            "ffn_norm": ("layers", "embed_vector"),
            "attn_full": attn(FULL, config.num_layers - nw),
            "attn_window": attn(WINDOW, nw),
            "mlp": [{"gate": ("embed", "mlp"), "up": ("embed", "mlp"),
                     "down": ("mlp", "embed")}
                    for _ in range(config.num_layers - nm)],
            "moe": {"router": ("layers", "embed", "experts_vector"),
                    "router_bias": ("layers", "experts_vector"),
                    "gate": ("layers", "experts", "embed", "mlp"),
                    "up": ("layers", "experts", "embed", "mlp"),
                    "down": ("layers", "experts", "mlp", "embed")},
        },
        "final_norm": ("embed_vector",),
    }
    if not config.tie_word_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


@jax.named_scope("attn")
def attention_sublayer(config: MimoV2Config, x: jnp.ndarray, p: dict,
                       norm_scale, positions: jnp.ndarray, kind: str,
                       attend=None):
    """norm -> attention of one ``kind`` -> output projection (the caller
    adds the residual). x [B, S, E]. ``attend`` (the serving engine's paged
    hook, ``(q, k, v, *, window, scale, sink) -> (attn, pools)``) replaces
    the attend; the call then returns ``(out, pools)``."""
    b, s, _ = x.shape
    cdt = config.dtype
    hq, hkv = config.num_heads, config.kv_heads(kind)
    dk, dv, rot = config.head_dim, config.v_head_dim, config.rotary_dims
    theta = config.swa_rope_theta if kind == WINDOW else config.rope_theta
    h = _rmsnorm(x, norm_scale, config.rms_norm_eps)
    # wq and wk are stored [out, in] and contracted over their minor
    # dimension: with heads of 192 columns (one and a half lane tiles) the
    # chip's compiler wants these two operands that way round, and stored
    # [in, out] it transposed each into a fresh HBM copy in every step
    q = jnp.einsum("bse,ne->bsn", h, p["wq"].astype(cdt)).reshape(
        b, s, hq, dk)
    k = jnp.einsum("bse,ne->bsn", h, p["wk"].astype(cdt)).reshape(
        b, s, hkv, dk)
    v = (h @ p["wv"].astype(cdt)).reshape(b, s, hkv, dv)

    def rope(t):    # the first `rot` columns turn, the rest pass through
        turned = apply_rope(t[..., :rot], positions, theta, None,
                            config.max_position_embeddings)
        return jnp.concatenate([turned, t[..., rot:]], axis=-1)

    q, k = rope(q), rope(k)
    v = v * jnp.asarray(config.attention_value_scale, cdt)
    window = config.sliding_window if kind == WINDOW else None
    sink = p["sink"] if config.has_sink(kind) else None
    scale = dk ** -0.5
    if attend is None:
        attn = multihead_attention(
            q, k, v, causal=True, positions=positions, kv_positions=positions,
            impl="xla", standard_layout=False, window=window, scale=scale,
            sink=sink)
    else:
        attn, pools = attend(q, k, v, window=window, scale=scale, sink=sink)
    out = attn.reshape(b, s, hq * dv) @ p["wo"].astype(cdt)
    return out if attend is None else (out, pools)


def _paged_attend(config: MimoV2Config, attend, pools, row, kind: str):
    """The paged hook of one attention layer: q and k padded to the pool's
    key row (zeros, which add nothing to a score), v to its value row, the
    call sent to the layer's page class, the value row's live columns kept."""
    dv = config.v_head_dim
    pad_k = config.key_parts * config.row_width - config.head_dim
    pad_v = config.row_width - dv

    def widen(t, pad):
        return jnp.pad(t, ((0, 0),) * 3 + ((0, pad),)) if pad else t

    def call(q, k, v, *, window, scale, sink):
        more = {} if sink is None else {"sink": sink}
        out, new_pools = attend(
            widen(q, pad_k), widen(k, pad_k), widen(v, pad_v), *pools, row,
            window=window, scale=scale, page_class=kind, **more)
        return out[..., :dv], new_pools

    return call


def _layer_of(stack: dict, row: int) -> dict:
    return jax.tree.map(lambda a: a[row], stack)


def _ffn(config: MimoV2Config, x, layers: dict, l: int, dense: bool,
         row: int, experts: dict):
    """The layer's FFN with its residual; ``(x, routing counts or None)``."""
    norm = layers["ffn_norm"][l]
    if dense:
        return x + mlp_sublayer(config, x, {
            "post_attn_norm": norm, "mlp": layers["mlp"][row]}), None
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


def apply(config: MimoV2Config, params: dict, input_ids: jnp.ndarray,
          positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Plain forward over whole sequences -> logits [B, S, V] float32."""
    if positions is None:
        positions = jnp.arange(input_ids.shape[1])[None, :]
    positions = jnp.broadcast_to(positions, input_ids.shape)
    x = embed_tokens(config, params, input_ids, positions)
    layers = params["layers"]
    with jax.named_scope("layers"):
        for l, (kind, row, dense, ffn_row) in enumerate(config.layer_table()):
            out = attention_sublayer(
                config, x, layers[f"attn_{kind}"][row],
                layers["attn_norm"][l], positions, kind)
            x, _ = _ffn(config, x + out, layers, l, dense, ffn_row, {})
    return lm_head_logits(config, params, x)


def paged_decode_step(config: MimoV2Config, params: dict,
                      token_ids: jnp.ndarray, positions: jnp.ndarray,
                      cache: dict, attend, last_index=None,
                      all_logits=False):
    """Paged multi-request decode/chunk step (``llama.paged_decode_step``'s
    contract) over the pools of BOTH page classes: ``{"k", "v"}: [full
    layers, P, page, kv heads, row]`` and ``{"k_win", "v_win"}: [window
    layers, Pw, page, swa kv heads, row]``, carried whole and addressed by
    the layer's row among its kind; ``attend`` sends each layer's call to its
    class's half of the tables (``page_class``). T == 1 is the decode step
    and T > 1 a prefill chunk, through the same lines. The returned cache also
    carries ``"routing"`` (``models/mla.py``), counted over the expert
    layers."""
    pos2d = llama.paged_positions(token_ids, positions)
    x = embed_tokens(config, params, token_ids, pos2d)
    layers, experts = experts_in_place(config, params["layers"])
    pools = {FULL: (cache["k"], cache["v"])}
    if "k_win" in cache:
        pools[WINDOW] = (cache["k_win"], cache["v_win"])
    counts = []
    with jax.named_scope("layers"):
        for l, (kind, row, dense, ffn_row) in enumerate(config.layer_table()):
            out, pools[kind] = attention_sublayer(
                config, x, layers[f"attn_{kind}"][row],
                layers["attn_norm"][l], pos2d, kind,
                attend=_paged_attend(config, attend, pools[kind], row, kind))
            x, n = _ffn(config, x + out, layers, l, dense, ffn_row, experts)
            if n is not None:
                counts.append(n)
    new_cache = dict(zip(("k", "v"), pools[FULL]))
    if WINDOW in pools:
        new_cache.update(zip(("k_win", "v_win"), pools[WINDOW]))
    if counts:
        counts = jnp.stack(counts)
        new_cache["routing"] = jnp.concatenate(
            [jnp.sum(counts[:, :3], axis=0), jnp.max(counts[:, 3:], axis=0)])
    return (llama.paged_logits_at(lm_head_logits, config, params, x,
                                  last_index, all_logits), new_cache)


PRESETS = {
    # every kind of layer: dense + full, experts + window (twice), experts +
    # full; key heads wider than value heads, rope on 8 of 24 columns, a
    # window shorter than the tests' contexts and not a multiple of a page
    "mimo-v2-debug": MimoV2Config(
        vocab_size=512, hidden_size=64, hybrid_layer_pattern=(0, 1, 1, 0),
        moe_layer_freq=(0, 1, 1, 1), num_heads=4, num_kv_heads=1,
        swa_num_kv_heads=2, head_dim=24, v_head_dim=16, sliding_window=12,
        intermediate_size=128, moe_intermediate_size=32, num_experts=8,
        experts_per_token=2, max_position_embeddings=512, row_lanes=16),
    # XiaomiMiMo/MiMo-V2.5 config.json
    "mimo-v2.5": MimoV2Config(),
}
