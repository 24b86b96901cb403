"""Jamba (ai21labs ``jamba``; AI21-Jamba2-3B): a decoder whose mixers differ
in KIND. Layer ``l`` is ``x += Mixer(norm(x))``, ``x += FFN(norm(x))``
(pre-norm); layer ``l`` mixes by softmax attention where ``l %
attn_layer_period == attn_layer_offset`` and by a Mamba-1 selective
state-space layer everywhere else (13 to 1 in Jamba2-3B), every layer's FFN
is one dense SwiGLU, the head is tied to the embedding, and NO layer has a
positional encoding.

Mamba mixer (:func:`mamba_sublayer`; ``C = mamba_expand x E`` channels, ``N
= mamba_d_state``, ``R = mamba_dt_rank``, ``L = mamba_d_conv`` taps): ``[x~,
z] = u W_in``; a depthwise causal convolution with bias and SiLU on ``x~``
alone; ``[dt~, B, C_] = x W_x``, each through Jamba's inner RMSNorm (its own
learned scale: the family's departure from Mamba-1, arXiv 2403.19887);
``Delta = softplus(dt~ W_dt + b_dt)``; ``A = -exp(A_log)``; the recurrence
of ``ops/ssm.py`` in float32; ``out = (y * silu(z)) W_out``. What the layer
must remember of a sequence is the state ``h [N, C]`` (float32: 327,680 B at
16 x 5,120) and the last ``L - 1`` rows of ``x~`` BEFORE the convolution.
``A_log`` is held ``[N, C]``, as the state lies (channels on the lanes; the
published leaf is its transpose).

Attention mixer (:func:`attention_sublayer`): ``num_heads`` query heads on
``num_kv_heads`` kv heads (20 on ONE in Jamba2-3B), no rope, no QK-norm, no
bias, scores ``q k^T / sqrt(d)``.

The serve path keeps k and v of the attention layers in pages
(``kv_layout``, ``num_kv_layers``) and the Mamba layers' state in the pool's
STATE CLASS (``sequence_state_layout``; ``serve/kv_pages.py``): a block a
live SEQUENCE whose id rides each program beside the slot's block table. The
decode step updates ``h`` where it lies (``ssm_step``); a prefill chunk
reads it, scans its tokens (``ssm_chunk``) and writes it back; a sequence
that starts at position 0 starts from zeros whatever its block's last owner
left.

The layers are WALKED like ``models/solar_open2.py``'s, every matrix a
per-layer leaf of a LIST (``models/mimo_v2.py`` found why: a static row of a
stacked leaf reaches a ``dot`` as a copy), the two norms stacks.

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
from ..ops.attention import multihead_attention
from ..ops.ssm import ssm_chunk, ssm_step

ATTENTION, MAMBA = "attn", "mamba"

# what ServeEngine refuses for this family, by the option's name, each with
# the module that would have to change: what the state class refuses for
# every family that keeps one, and nothing of its own
SERVE_REFUSES = STATE_CLASS_REFUSES


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    num_layers: int = 28
    attn_layer_period: int = 14         # layer l attends where
    attn_layer_offset: int = 7          # l % period == offset
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: int = 128                 # hidden_size / num_heads
    intermediate_size: int = 8192       # every layer's dense SwiGLU
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError(f"attn_layer_offset must lie in 0.."
                             f"{self.attn_layer_period - 1}, got "
                             f"{self.attn_layer_offset}")
        if self.mamba_d_conv < 2:
            raise ValueError(f"mamba_d_conv must be >= 2, got "
                             f"{self.mamba_d_conv}")

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def channels(self) -> int:
        """The Mamba mixer's inner width ``C``."""
        return self.mamba_expand * self.hidden_size

    def layer_table(self) -> tuple:
        """Per layer ``(mixer kind, its row among the layers of that
        kind)``."""
        rows, seen = [], {ATTENTION: 0, MAMBA: 0}
        for l in range(self.num_layers):
            kind = (ATTENTION if l % self.attn_layer_period
                    == self.attn_layer_offset else MAMBA)
            rows.append((kind, seen[kind]))
            seen[kind] += 1
        return tuple(rows)

    @property
    def num_kv_layers(self) -> int:
        """Layers with k and v pages: the attention layers."""
        return sum(kind == ATTENTION for kind, _ in self.layer_table())

    @property
    def num_mamba_layers(self) -> int:
        return self.num_layers - self.num_kv_layers

    def kv_layout(self) -> dict:
        """One cached token in one attention layer
        (``serve/kv_pages.pool_layout``)."""
        shape = (self.num_kv_heads, self.head_dim)
        return {"k": shape, "v": shape}

    def sequence_state_layout(self) -> Optional[dict]:
        """The state class (``serve/kv_pages.sequence_state_layout``):
        ``{leaf: (shape of one sequence's block, layers first; storage)}``.
        None where no layer is Mamba."""
        n = self.num_mamba_layers
        if not n:
            return None
        # h in float32 whatever the pool's dtype: not an option
        # (``ops/ssm.ssm_step`` refuses a narrower pool); [N, C], channels
        # on the lanes (N minor would pad 16 to 128 lanes)
        return {"seq_state": ((n, self.mamba_d_state, self.channels), "fp32"),
                "seq_conv": ((n, self.mamba_d_conv - 1, self.channels), None)}

    def num_params(self) -> int:
        e = self.hidden_size
        ffn = 3 * e * self.intermediate_size + 2 * e        # and two norms
        mixers = (self.num_kv_layers * _size(_attn_shapes(self))
                  + self.num_mamba_layers * _size(_mamba_shapes(self)))
        top = self.vocab_size * e * (1 if self.tie_word_embeddings else 2) + e
        return top + mixers + self.num_layers * ffn


def _attn_shapes(config: JambaConfig) -> dict:
    e, d = config.hidden_size, config.head_dim
    hq, hkv = config.num_heads * d, config.num_kv_heads * d
    return {"wq": (e, hq), "wk": (e, hkv), "wv": (e, hkv), "wo": (hq, e)}


def _mamba_shapes(config: JambaConfig) -> dict:
    e, c, n = config.hidden_size, config.channels, config.mamba_d_state
    r = config.mamba_dt_rank
    return {"w_in": (e, 2 * c), "taps": (config.mamba_d_conv, c),
            "conv_bias": (c,), "w_x": (c, r + 2 * n), "dt_norm": (r,),
            "b_norm": (n,), "c_norm": (n,), "w_dt": (r, c), "dt_bias": (c,),
            "a_log": (n, c), "d": (c,), "w_out": (c, e)}


def _mlp_shapes(config: JambaConfig) -> dict:
    e, f = config.hidden_size, config.intermediate_size
    return {"gate": (e, f), "up": (e, f), "down": (f, e)}


def _size(shapes: dict) -> int:
    return sum(math.prod(s) for s in shapes.values())


def init(config: JambaConfig, rng: jax.Array) -> dict:
    e, v, n = config.hidden_size, config.vocab_size, config.num_layers
    keys = iter(jax.random.split(rng, 8 + 20 * n))
    pdt = config.param_dtype

    def dense(shape, std=0.02):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(pdt)

    def mamba():
        p = {name: dense(shape)
             for name, shape in _mamba_shapes(config).items()}
        p["taps"] = dense(p["taps"].shape, 0.5)
        p["conv_bias"] = jnp.zeros_like(p["conv_bias"])
        for name in ("dt_norm", "b_norm", "c_norm", "d"):
            p[name] = jnp.ones_like(p[name])
        # Mamba's published start: W_dt uniform in +-R^-0.5, A = -(1..N), a
        # step dt log-uniform in [0.001, 0.1] and its inverse softplus as
        # the bias
        r = config.mamba_dt_rank
        p["w_dt"] = jax.random.uniform(next(keys), p["w_dt"].shape,
                                       jnp.float32, -r ** -0.5,
                                       r ** -0.5).astype(pdt)
        p["a_log"] = jnp.broadcast_to(
            jnp.log(jnp.arange(1, config.mamba_d_state + 1,
                               dtype=jnp.float32))[:, None],
            p["a_log"].shape).astype(pdt)
        dt = jnp.exp(jax.random.uniform(
            next(keys), p["dt_bias"].shape, jnp.float32,
            math.log(0.001), math.log(0.1)))
        p["dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt)
        return p

    return {
        "embed": {"embedding": dense((v, e))},
        "layers": {
            "mixer_norm": jnp.ones((n, e), pdt),
            "ffn_norm": jnp.ones((n, e), pdt),
            ATTENTION: [{name: dense(shape) for name, shape
                         in _attn_shapes(config).items()}
                        for _ in range(config.num_kv_layers)],
            MAMBA: [mamba() for _ in range(config.num_mamba_layers)],
            "mlp": [{name: dense(shape) for name, shape
                     in _mlp_shapes(config).items()} for _ in range(n)],
        },
        "final_norm": jnp.ones((e,), pdt),
    }


def param_logical_axes(config: JambaConfig) -> dict:
    """Logical axes: a list of per-layer leaves for the mixers and the FFN,
    stacks (leading axis ``layers``) for the two norms. No serve mesh runs
    this family yet (``SERVE_REFUSES``)."""
    attn = {"wq": ("embed", "heads"), "wk": ("embed", "kv"),
            "wv": ("embed", "kv"), "wo": ("heads", "embed")}
    mamba = {"w_in": ("embed", "heads"), "taps": (None, "heads"),
             "conv_bias": ("heads",), "w_x": ("heads", None),
             "dt_norm": (None,), "b_norm": (None,), "c_norm": (None,),
             "w_dt": (None, "heads"), "dt_bias": ("heads",),
             "a_log": (None, "heads"), "d": ("heads",),
             "w_out": ("heads", "embed")}
    mlp = {"gate": ("embed", "mlp"), "up": ("embed", "mlp"),
           "down": ("mlp", "embed")}
    return {
        "embed": {"embedding": ("vocab", "embed")},
        "layers": {
            "mixer_norm": ("layers", "embed_vector"),
            "ffn_norm": ("layers", "embed_vector"),
            ATTENTION: [dict(attn) for _ in range(config.num_kv_layers)],
            MAMBA: [dict(mamba) for _ in range(config.num_mamba_layers)],
            "mlp": [dict(mlp) for _ in range(config.num_layers)],
        },
        "final_norm": ("embed_vector",),
    }


# ---------------------------------------------------------------------------
# the two mixers
# ---------------------------------------------------------------------------

def _mamba_inputs(config: JambaConfig, u: jnp.ndarray, p: dict,
                  conv_state: Optional[jnp.ndarray]):
    """The recurrence's inputs from the normed ``u [B, T, E]``: ``(x, delta,
    b, c, z, history)``, the first four float32; ``conv_state [B, L - 1,
    C]`` is each sequence's last rows of ``x~`` before this call (None: a
    sequence's beginning, zeros), and ``history [B, L - 1 + T, C]`` those
    rows followed by this call's, so the state after token i is ``history[:,
    i + 1 : i + L]``."""
    cdt, f32 = config.dtype, jnp.float32
    bsz, t, _ = u.shape
    n, r, taps_n = (config.mamba_d_state, config.mamba_dt_rank,
                    config.mamba_d_conv)
    xs, z = jnp.split(u @ p["w_in"].astype(cdt), 2, axis=-1)    # [B, T, C]
    if conv_state is None:
        conv_state = jnp.zeros((bsz, taps_n - 1, xs.shape[-1]), cdt)
    history = jnp.concatenate([conv_state.astype(cdt), xs], axis=1)
    taps = p["taps"].astype(f32)                                # [L, C]
    x = jax.nn.silu(p["conv_bias"].astype(f32) + sum(
        taps[j] * history[:, j:j + t].astype(f32) for j in range(taps_n)))
    low = jnp.dot(x.astype(cdt), p["w_x"].astype(cdt),
                  preferred_element_type=f32)
    dt, b, c = jnp.split(low, [r, r + n], axis=-1)
    eps = config.rms_norm_eps
    dt, b, c = (_rmsnorm(v, p[name], eps) for v, name in (
        (dt, "dt_norm"), (b, "b_norm"), (c, "c_norm")))
    delta = jax.nn.softplus(
        jnp.dot(dt.astype(cdt), p["w_dt"].astype(cdt),
                preferred_element_type=f32) + p["dt_bias"].astype(f32))
    return x, delta, b, c, z, history


@jax.named_scope("attn")
def mamba_sublayer(config: JambaConfig, x: jnp.ndarray, p: dict, norm_scale,
                   state=None):
    """norm -> Mamba mixer -> output projection (the caller adds the
    residual), under the sub-scope ``ssm``. x [B, T, E]. ``state`` None:
    whole sequences from zeros (the plain forward), returns ``out``. Else
    ``state = (pool, conv pool, row, attend)``, the serve path's state class
    and the paged hook that knows each slot's block, start and valid tokens:
    returns ``(out, (pool, conv pool))``."""
    cdt = config.dtype
    with jax.named_scope("ssm"):
        a = -jnp.exp(p["a_log"].astype(jnp.float32))            # [N, C]
        u = _rmsnorm(x, norm_scale, config.rms_norm_eps)
        if state is None:
            xs, delta, b, c, z, _ = _mamba_inputs(config, u, p, None)
            zeros = jnp.zeros((x.shape[0], *a.shape), jnp.float32)
            y, _ = ssm_chunk(zeros, xs, delta, b, c, a, p["d"])
        else:
            pool, conv_pool, row, attend = state
            blocks, t = attend.state_blocks, x.shape[1]
            fresh = attend.lengths == 0        # a sequence's first tokens
            conv_state = jnp.where(fresh[:, None, None], 0,
                                   conv_pool[row, blocks])
            xs, delta, b, c, z, history = _mamba_inputs(config, u, p,
                                                        conv_state)
            if t == 1:
                y, pool = ssm_step(pool, blocks, row, xs[:, 0], delta[:, 0],
                                   b[:, 0], c[:, 0], a, p["d"], fresh)
                y = y[:, None]
            else:
                h0 = jnp.where(fresh[:, None, None], 0, pool[row, blocks])
                y, h_t = ssm_chunk(h0, xs, delta, b, c, a, p["d"],
                                   attend.n_valid)
                with jax.named_scope("kv_write"):
                    pool = pool.at[row, blocks].set(h_t)
        gated = y * jax.nn.silu(z.astype(jnp.float32))
        out = gated.astype(cdt) @ p["w_out"].astype(cdt)
    if state is None:
        return out
    with jax.named_scope("kv_write"):
        # x~'s rows at the slot's last REAL token and the two before
        keep = config.mamba_d_conv - 1
        n_valid = (jnp.full(blocks.shape, t, jnp.int32)
                   if attend.n_valid is None else attend.n_valid)
        rows = n_valid[:, None] + jnp.arange(keep)[None, :]
        new = jnp.take_along_axis(history, rows[..., None], axis=1)
        conv_pool = conv_pool.at[row, blocks].set(new.astype(conv_pool.dtype))
    return out, (pool, conv_pool)


@jax.named_scope("attn")
def attention_sublayer(config: JambaConfig, x: jnp.ndarray, p: dict,
                       norm_scale, positions: jnp.ndarray, attend=None):
    """norm -> NoPE attention -> output projection (the caller adds the
    residual). ``attend`` (the serving engine's paged hook, ``(q, k, v) ->
    (attn, pools)``) replaces the attend; the call then returns ``(out,
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
    out = attn.reshape(b, s, -1).astype(cdt) @ p["wo"].astype(cdt)
    return out if attend is None else (out, pools)


def _ffn(config: JambaConfig, x, layers: dict, l: int):
    """The layer's dense SwiGLU with its pre-norm and its residual."""
    return x + mlp_sublayer(config, x, {"post_attn_norm": layers["ffn_norm"][l],
                                        "mlp": layers["mlp"][l]})


embed_tokens = llama.embed_tokens
lm_head_logits = llama.lm_head_logits
final_hidden = llama.final_hidden
output_weights = llama.output_weights


def apply(config: JambaConfig, params: dict, input_ids: jnp.ndarray,
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
            if kind == ATTENTION:
                out = attention_sublayer(config, x, layers[ATTENTION][row],
                                         norm, positions)
            else:
                out = mamba_sublayer(config, x, layers[MAMBA][row], norm)
            x = _ffn(config, x + out, layers, l)
    return lm_head_logits(config, params, x)


def paged_decode_step(config: JambaConfig, params: dict,
                      token_ids: jnp.ndarray, positions: jnp.ndarray,
                      cache: dict, attend, last_index=None,
                      all_logits=False):
    """Paged multi-request decode/chunk step (``llama.paged_decode_step``'s
    contract) over the pools ``{"k", "v"}: [attention layers, P, page, kv
    heads, head_dim]`` and the state class ``"seq_state": [Mamba layers,
    blocks, N, C]`` float32, ``"seq_conv": [Mamba layers, blocks, L - 1,
    C]``, carried whole and addressed by the layer's row among its kind. An
    attention layer writes and reads k and v through ``attend``; a Mamba
    layer reads and writes each slot's block (``attend.state_blocks``). T ==
    1 is the decode step and T > 1 a prefill chunk, through the same
    lines."""
    pos2d = llama.paged_positions(token_ids, positions)
    x = embed_tokens(config, params, token_ids, pos2d)
    layers = params["layers"]
    new_cache = {name: cache[name] for name in ("k", "v", "seq_state",
                                                "seq_conv") if name in cache}
    scale = config.head_dim ** -0.5
    with jax.named_scope("layers"):
        for l, (kind, row) in enumerate(config.layer_table()):
            norm = layers["mixer_norm"][l]
            if kind == ATTENTION:
                def paged(q, k, v, row=row):
                    return attend(q, k, v, new_cache["k"], new_cache["v"],
                                  row, scale=scale)

                out, (new_cache["k"], new_cache["v"]) = attention_sublayer(
                    config, x, layers[ATTENTION][row], norm, pos2d,
                    attend=paged)
            else:
                out, (new_cache["seq_state"], new_cache["seq_conv"]) = \
                    mamba_sublayer(config, x, layers[MAMBA][row], norm,
                                   state=(new_cache["seq_state"],
                                          new_cache["seq_conv"], row, attend))
            x = _ffn(config, x + out, layers, l)
    return (llama.paged_logits_at(lm_head_logits, config, params, x,
                                  last_index, all_logits), new_cache)


PRESETS = {
    # both kinds of layer in the published order (Mamba, attention, Mamba:
    # a period of 3 with the attention layer in its middle); narrow, so a
    # test's state is a few kilobytes
    "jamba-debug": JambaConfig(
        vocab_size=512, hidden_size=64, num_layers=4, attn_layer_period=3,
        attn_layer_offset=1, num_heads=4, num_kv_heads=1, head_dim=16,
        intermediate_size=128, mamba_d_state=8, mamba_dt_rank=8,
        max_position_embeddings=512),
    # ai21labs/AI21-Jamba2-3B config.json
    "jamba2-3b": JambaConfig(),
}
