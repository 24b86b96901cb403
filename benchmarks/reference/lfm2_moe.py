"""Plain reference of the ``lfm2_moe`` decoder (LiquidAI LFM2-24B-A2B), written
from the equations below and the configuration, importing nothing of the
program. float32 ``jax.numpy``, matmuls at ``Precision.HIGHEST``, no kernel,
no cache, no batching of requests: whole sequences.

Layer ``l`` on ``x [S, E]``, ``n(.)`` an RMSNorm with ``norm_eps`` and a
learned scale: ``h = x + Op_l(n_op(x))``, ``y = h + FFN_l(n_ffn(h))``.

``Op_l`` is attention where ``layer_types[l] == "full_attention"``, else the
short convolution; ``u`` is the sublayer's normed input.

- Attention: ``q = u W_q``, ``k = u W_k``, ``v = u W_v`` (no bias),
  ``num_attention_heads`` query and ``num_key_value_heads`` kv heads of
  ``head_dim`` columns; an RMSNorm over the columns of every q head and every
  k head BEFORE rope; rope of ``rope_theta`` over the whole head, column ``i``
  paired with ``i + head_dim / 2`` (rotate-half); causal softmax of ``q k^T /
  sqrt(head_dim)``, query head ``h`` against kv head ``h // (query heads / kv
  heads)``; ``W_o``.
- Short convolution (``conv_L_cache`` = L taps, no bias): ``[B | C | z] = u
  W_in`` (three blocks of E columns, in that order); ``g = B * z``; ``c_t =
  sum_{j < L} w[:, j] * g_{t - (L - 1) + j}`` with ``g`` zero before the
  sequence's first token (depthwise, causal); ``out = (C * c) W_out``.

``FFN_l`` is dense for ``l < num_dense_layers``: ``W_2 (silu(W_1 u) * W_3 u)``;
else routed: ``s = sigmoid(u W_r)`` in float32; the ``num_experts_per_tok``
largest of ``s + b`` (``use_expert_bias``: ``b`` moves the choice only);
weights ``s[choice] / (sum + 1e-6)`` (``norm_topk_prob``) times
``routed_scaling_factor``; ``sum_k w_k E_k(u)``, every expert a SwiGLU.

Top: embedding, the layers, a final RMSNorm, logits through the embedding
matrix (tied).

Departures, each noted where it is made: (1) EVERY expert is evaluated on
EVERY row and weighted by the router's weight for it, which is zero where the
expert was not chosen: the same sums, no index lists, one program a layer (16
times the needed products at 4 of 64; 50 ms a layer on the chip); (2) a
sequence is padded at its end to a multiple of ``PAD_TO``, which under a
causal mask and a causal convolution changes nothing before it.

``mode`` lowers the precision for the control the comparison has to refuse:
``"int8"`` rounds both operands of every matmul to an int8 grid, ``"bf16"``
to bfloat16. ``"highest"`` is the reference itself. ``fault`` names one
deliberate error (the tests' sabotage): ``"taps_reversed"``, ``"bc_swapped"``,
``"no_choice_bias"``, ``"dense_gets_experts"``.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD_TO = 256         # a sequence is padded to a multiple: few shapes compile
ATTENTION = "full_attention"
FAULTS = (None, "taps_reversed", "bc_swapped", "no_choice_bias",
          "dense_gets_experts")


# ---- precision modes -------------------------------------------------------
def _lower(x, mode, axis):
    if mode == "highest":
        return x
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "int8":
        scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return jnp.round(x / scale) * scale
    raise ValueError(f"unknown precision mode {mode!r}")


def mm(a, b, mode="highest"):
    return jnp.matmul(_lower(a, mode, -1), _lower(b, mode, 0),
                      precision=HIGHEST)


# ---- pieces ----------------------------------------------------------------
def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def rope_half(x, positions, theta: float):
    """x [S, H, D]; positions [S]: column ``i`` and ``i + D / 2`` turned by
    ``positions * theta^(-2i / D)``."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, None].astype(jnp.float32) * inv_freq       # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(cfg, w, u, positions, mode="highest"):
    """The attention operator on the normed input u [S, E]."""
    s = u.shape[0]
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    eps, theta = cfg["norm_eps"], float(cfg["rope_parameters"]["rope_theta"])
    q = mm(u, w["wq"], mode).reshape(s, hq, d)
    k = mm(u, w["wk"], mode).reshape(s, hkv, d)
    v = mm(u, w["wv"], mode).reshape(s, hkv, d)
    q = rope_half(rmsnorm(q, w["q_norm"], eps), positions, theta)
    k = rope_half(rmsnorm(k, w["k_norm"], eps), positions, theta)
    k = jnp.repeat(k, hq // hkv, axis=1)      # query head h: kv head h // g
    v = jnp.repeat(v, hq // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", _lower(q, mode, -1),
                        _lower(k, mode, -1), precision=HIGHEST) / d ** 0.5
    mask = positions[None, :] <= positions[:, None]               # [q, k]
    p = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", _lower(p, mode, -1), _lower(v, mode, -1),
                   precision=HIGHEST)
    return mm(o.reshape(s, hq * d), w["wo"], mode)


def short_conv(cfg, w, u, mode="highest", fault=None):
    """The gated short convolution on the normed input u [S, E]."""
    s, e = u.shape
    taps_n = cfg["conv_L_cache"]
    gate_b, gate_c, z = jnp.split(mm(u, w["w_in"], mode), 3, axis=-1)
    if fault == "bc_swapped":
        gate_b, gate_c = gate_c, gate_b
    g = jnp.concatenate([jnp.zeros((taps_n - 1, e), u.dtype), gate_b * z], 0)
    taps = w["taps"][:, ::-1] if fault == "taps_reversed" else w["taps"]
    c = sum(taps[:, j] * g[j: j + s] for j in range(taps_n))
    return mm(gate_c * c, w["w_out"], mode)


def swiglu(x, gate, up, down, mode="highest"):
    return mm(jax.nn.silu(mm(x, gate, mode)) * mm(x, up, mode), down, mode)


def route(cfg, w, u, mode="highest", fault=None):
    """[S, experts] combine weights for rows u [S, E] (zero where an expert
    is not chosen)."""
    s = jax.nn.sigmoid(mm(u, w["router"], mode))
    choice = s
    if cfg["use_expert_bias"] and fault != "no_choice_bias":
        choice = s + w["router_bias"]
    _, idx = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-6)
    picked = picked * cfg["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(picked)


@functools.lru_cache(maxsize=None)
def _jits(cfg_key: str, mode, fault):
    cfg = json.loads(cfg_key)
    eps = cfg["norm_eps"]

    def f32(w):
        return {name: leaf.astype(jnp.float32) for name, leaf in w.items()}

    def operator(w, x, positions, attends):
        w = f32({name: leaf for name, leaf in w.items()
                 if name not in ("gate", "up", "down")})
        u = rmsnorm(x, w["operator_norm"], eps)
        if attends:
            return x + attention(cfg, w, u, positions, mode)
        return x + short_conv(cfg, w, u, mode, fault)

    def dense_ffn(w, h):
        w = f32(w)
        u = rmsnorm(h, w["ffn_norm"], eps)
        return h + swiglu(u, w["dense_gate"], w["dense_up"], w["dense_down"],
                          mode)

    def routed_ffn(w, h):
        experts = (w["gate"], w["up"], w["down"])
        w = f32({name: w[name] for name in ("ffn_norm", "router",
                                            "router_bias")})
        u = rmsnorm(h, w["ffn_norm"], eps)
        weights = route(cfg, w, u, mode, fault)                   # [S, X]

        def add_expert(y, expert):      # departure 1: every row, weighted
            gate, up, down, weight = (a.astype(jnp.float32) for a in expert)
            return y + swiglu(u, gate, up, down, mode) * weight[:, None], None

        y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                            (*experts, weights.T))
        return h + y

    def head_gaps(top, x, xc, nxt, control):
        logits = head_logits(cfg, top, x, mode)
        if control:
            nxt = jnp.argmax(head_logits(cfg, top, xc, control), -1)
        picked = jnp.take_along_axis(logits, nxt[..., None], -1)[..., 0]
        return jnp.max(logits, axis=-1) - picked

    return (jax.jit(operator, static_argnames="attends"), jax.jit(dense_ffn),
            jax.jit(routed_ffn), jax.jit(head_gaps, static_argnames="control"))


def _key(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)


def block(cfg, w, x, positions, l: int, mode="highest", fault=None):
    """Decoder layer ``l``. x [S, E] float32; w: ``layer_weights``' leaves
    (those of every kind; the layer's own kind is read)."""
    attends = cfg["layer_types"][l] == ATTENTION
    dense = l < cfg["num_dense_layers"] and fault != "dense_gets_experts"
    # the leaves of this layer's kind alone (made float32 inside the jits)
    names = ("operator_norm", "ffn_norm") + (
        ("wq", "wk", "wv", "wo", "q_norm", "k_norm") if attends
        else ("w_in", "taps", "w_out")) + (
        ("dense_gate", "dense_up", "dense_down") if dense
        else ("router", "router_bias", "gate", "up", "down"))
    w = {name: w[name] for name in names}
    operator, dense_ffn, routed_ffn, _ = _jits(_key(cfg), mode, fault)
    h = operator(w, x, positions, attends=attends)
    return dense_ffn(w, h) if dense else routed_ffn(w, h)


def embed(top, tokens):
    return top["embed"].astype(jnp.float32)[tokens]


def head_logits(cfg, top, x, mode="highest"):
    """Final norm, then logits through the embedding matrix (tied)."""
    x = rmsnorm(x, top["final_norm"].astype(jnp.float32), cfg["norm_eps"])
    return mm(x, top["embed"].astype(jnp.float32).T, mode)


def forward_logits(cfg, layer_fn, top, tokens, mode="highest", fault=None):
    """Logits [S, V] of the plain forward over one sequence ``tokens`` [S], a
    layer at a time (``layer_fn(l)`` gives layer l's leaves)."""
    tokens = jnp.asarray(tokens)
    positions = jnp.arange(tokens.shape[0])
    x = embed(top, tokens)
    for l in range(cfg["num_hidden_layers"]):
        x = block(cfg, layer_fn(l), x, positions, l, mode, fault)
    return head_logits(cfg, top, x, mode)


def served_token_gaps(cfg, layer_fn, top, tokens, n_prompt, mode="highest",
                      control_mode=None, fault=None):
    """``reference/decoder.py``'s ``served_token_gaps`` for this family:
    teacher-forced over one request's prompt + served tokens (a host array
    [S]), a layer at a time. For each served token (positions ``n_prompt ..
    S-1``), the gap by which its reference logit lies below the reference's
    best there; with ``control_mode``, the gap of the token a pass in that
    lower precision puts first. Departure 2: padded to ``PAD_TO``."""
    tokens = np.asarray(tokens, np.int32)
    s = tokens.shape[0]
    padded = -(-s // PAD_TO) * PAD_TO
    ids = jnp.asarray(np.pad(tokens, (0, padded - s)))
    positions = jnp.arange(padded)
    x = embed(top, ids)
    xc = x if control_mode else None
    for l in range(cfg["num_hidden_layers"]):
        w = layer_fn(l)
        x = block(cfg, w, x, positions, l, mode, fault)
        if control_mode:
            xc = block(cfg, w, xc, positions, l, control_mode, fault)
    head_gaps = _jits(_key(cfg), mode, fault)[-1]
    gaps = head_gaps(top, x, x if xc is None else xc, jnp.roll(ids, -1),
                     control=control_mode)
    return np.asarray(gaps)[n_prompt - 1: s - 1]
