"""Plain reference of the ``solar_open2`` decoder (upstage Solar-Open2-250B),
written from the equations below and the configuration, importing nothing of
the program. float32 ``jax.numpy``, matmuls at ``Precision.HIGHEST``, no
kernel, no cache, no batching of requests: whole sequences, and the linear
attention as a scan over TOKENS (the program scans blocks: the reference does
not share its algebra).

Layer ``l`` on ``x [S, E]``, ``n(.)`` an RMSNorm with ``rms_norm_eps`` and a
learned scale: ``h = x + Mixer_l(n_1(x))``, ``y = h + FFN_l(n_2(h))``. ``u`` is
a sublayer's normed input. ``use_rope`` false: no positional encoding.

- Mixer, KDA (``l`` not in ``gqa_layers``; ``linear_attn_config``: H heads of
  d columns, ``short_conv_kernel_size`` L taps): three streams ``u W_q``, ``u
  W_k``, ``u W_v`` (H d columns each), each through its own depthwise causal
  convolution and SiLU: ``q~_t = silu(sum_{j < L} c_q[:, j] * (u W_q)_{t - (L
  - 1) + j})`` (rows before the sequence are zero), likewise ``k~``, ``v``.
  Per head ``q = q~ / max(|q~|, 1e-6) / sqrt(d)``, ``k = k~ / max(|k~|,
  1e-6)``. Decay a key channel: ``g_t = -exp(A_log_h) softplus((u W_fa) W_fb
  + dt_bias)``, ``alpha_t = exp(g_t)``; step ``beta_t = 2 sigmoid(u W_beta)``
  a head. State ``S [d, d]`` a head, zero at the sequence's start:
  ``S' = diag(alpha_t) S``, ``S_t = S' + beta_t k_t (v_t - k_t^T S')^T``,
  ``o_t = S_t^T q_t``. Output ``W_o concat_h(RMSNorm_d(o_{t,h}; gamma) *
  sigmoid((u W_ga) W_gb)_h)``, one ``gamma [d]`` for all heads.
- Mixer, GQA (``l`` in ``gqa_layers``): ``q = u W_q`` (``num_attention_heads``
  heads of ``head_dim``), ``k = u W_k``, ``v = u W_v``
  (``num_key_value_heads`` heads), no rope, no QK-norm; causal softmax of ``q
  k^T / sqrt(head_dim)``, query head ``j`` reads kv head ``j // (heads /
  kv heads)``; ``use_gqa_gate``: ``W_o (sigmoid(u W_g) * a)`` element-wise.
- FFN (every layer): ``s = sigmoid(u W_r)`` in float32 over ALL the router's
  outputs (``router_experts``); the ``num_experts_per_tok`` largest of ``s +
  b`` (``b`` moves the choice only); weights ``s[choice] / (sum + 1e-20)``
  (``norm_topk_prob``) times ``routed_scaling_factor``; ``sum_k w_k E_k(u) +
  Shared(u)``, every expert and the shared one a SwiGLU. This chip HOLDS
  experts ``experts_held_first .. + n_routed_experts``: pairs of the absent
  experts are dropped and the partial sum goes on, in the program and here
  alike.

Top: embedding, the layers, a final RMSNorm, an untied head.

Departures, each noted where it is made: (1) every HELD expert is evaluated
on every row and weighted by the router's weight for it, which is zero where
the expert was not chosen: the same sums, no index lists; (2) a sequence is
padded at its end to a multiple of ``PAD_TO``, which under a causal mask, a
causal convolution and a recurrence changes nothing before it; (3) the GQA
layer's scores are taken in blocks of ``Q_BLOCK`` query rows against every
key under the mask: the same sums.

``mode`` lowers the precision for the control the comparison has to refuse:
``"int8"`` rounds both operands of every matmul (the recurrence's two
contractions with the state among them) to an int8 grid, ``"bf16"`` to
bfloat16. ``"highest"`` is the reference itself. ``fault`` names one
deliberate error (the tests' sabotage): see ``FAULTS``.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD_TO = 256         # a sequence is padded to a multiple: few shapes compile
Q_BLOCK = 256        # query rows whose scores are live at once
FAULTS = (None, "taps_reversed", "no_decay", "beta_unscaled", "state_columns",
          "no_kda_gate", "no_gqa_gate", "no_choice_bias", "no_shared_expert")


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


def is_gqa(cfg: dict, l: int) -> bool:
    return l in cfg["gqa_layers"]


def gqa(cfg, w, u, mode="highest", fault=None):
    """The gated NoPE attention on the normed input u [S, E]."""
    s = u.shape[0]
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = mm(u, w["gqa_wq"], mode).reshape(s, hq, d)
    k = mm(u, w["gqa_wk"], mode).reshape(s, hkv, d)
    v = mm(u, w["gqa_wv"], mode).reshape(s, hkv, d)
    k = _lower(jnp.repeat(k, hq // hkv, axis=1), mode, -1)   # head j: j // g
    v = _lower(jnp.repeat(v, hq // hkv, axis=1), mode, -1)
    keys = jnp.arange(s)

    def rows(args):         # departure 3: one block of query rows
        qb, pos = args
        scores = jnp.einsum("qhd,khd->hqk", _lower(qb, mode, -1), k,
                            precision=HIGHEST) / d ** 0.5
        mask = keys[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", _lower(p, mode, -1), v,
                          precision=HIGHEST)

    nb = s // Q_BLOCK
    a = jax.lax.map(rows, (q.reshape(nb, Q_BLOCK, hq, d),
                           keys.reshape(nb, Q_BLOCK))).reshape(s, hq * d)
    if fault != "no_gqa_gate":
        a = jax.nn.sigmoid(mm(u, w["gqa_wg"], mode)) * a
    return mm(a, w["gqa_wo"], mode)


def kda(cfg, w, u, mode="highest", fault=None):
    """Kimi Delta Attention on the normed input u [S, E], token by token."""
    s = u.shape[0]
    lin = cfg["linear_attn_config"]
    heads, d, taps_n = (lin["num_heads"], lin["head_dim"],
                        lin["short_conv_kernel_size"])

    def stream(name):
        x = mm(u, w[f"kda_w{name}"], mode)                          # [S, C]
        x = jnp.concatenate([jnp.zeros((taps_n - 1, x.shape[1])), x], 0)
        taps = w[f"kda_conv_{name}"]
        if fault == "taps_reversed":
            taps = taps[:, ::-1]
        y = sum(taps[:, j] * x[j: j + s] for j in range(taps_n))
        return jax.nn.silu(y).reshape(s, heads, d)

    def unit(x):
        return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True),
                               1e-6)

    q, k, v = unit(stream("q")) / d ** 0.5, unit(stream("k")), stream("v")
    low = mm(mm(u, w["kda_w_fa"], mode), w["kda_w_fb"], mode)
    g = -jnp.exp(w["kda_a_log"])[:, None] * jax.nn.softplus(
        (low + w["kda_dt_bias"]).reshape(s, heads, d))
    alpha = jnp.ones_like(g) if fault == "no_decay" else jnp.exp(g)
    beta = jax.nn.sigmoid(mm(u, w["kda_w_beta"], mode))             # [S, H]
    if fault != "beta_unscaled":
        beta = 2.0 * beta

    def step(state, row):   # state [H, d_k, d_v]; one token's rows
        q_t, k_t, v_t, alpha_t, beta_t = row
        if fault == "state_columns":    # the decay on the value axis
            state = state * alpha_t[:, None, :]
        else:
            state = state * alpha_t[:, :, None]
        seen = jnp.einsum("hk,hkv->hv", _lower(k_t, mode, -1),
                          _lower(state, mode, -2), precision=HIGHEST)
        state = state + (beta_t[:, None, None] * k_t[:, :, None]
                         * (v_t - seen)[:, None, :])
        out = jnp.einsum("hk,hkv->hv", _lower(q_t, mode, -1),
                         _lower(state, mode, -2), precision=HIGHEST)
        return state, out

    _, o = jax.lax.scan(step, jnp.zeros((heads, d, d)),
                        (q, k, v, alpha, beta))
    o = rmsnorm(o, w["kda_o_norm"], cfg["rms_norm_eps"])
    if fault != "no_kda_gate":
        gate = mm(mm(u, w["kda_w_ga"], mode), w["kda_w_gb"], mode)
        o = o * jax.nn.sigmoid(gate).reshape(s, heads, d)
    return mm(o.reshape(s, heads * d), w["kda_wo"], mode)


def swiglu(x, gate, up, down, mode="highest"):
    return mm(jax.nn.silu(mm(x, gate, mode)) * mm(x, up, mode), down, mode)


def route(cfg, w, u, mode="highest", fault=None):
    """[S, held experts] combine weights for rows u [S, E]: the router scores
    ALL its outputs and picks among all; the columns of the experts held
    here are kept (zero where one is not chosen)."""
    s = jax.nn.sigmoid(mm(u, w["router"], mode))
    choice = s if fault == "no_choice_bias" else s + w["router_bias"]
    _, idx = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    picked = picked * cfg["routed_scaling_factor"]
    every = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None],
                                 idx].set(picked)
    first = cfg.get("experts_held_first", 0)
    return every[:, first: first + cfg["n_routed_experts"]]


MIXER_LEAVES = {
    True: ("gqa_wq", "gqa_wk", "gqa_wv", "gqa_wg", "gqa_wo"),
    False: ("kda_wq", "kda_wk", "kda_wv", "kda_conv_q", "kda_conv_k",
            "kda_conv_v", "kda_w_fa", "kda_w_fb", "kda_a_log", "kda_dt_bias",
            "kda_w_beta", "kda_w_ga", "kda_w_gb", "kda_o_norm", "kda_wo"),
}
FFN_LEAVES = ("ffn_norm", "router", "router_bias", "shared_gate", "shared_up",
              "shared_down")


@functools.lru_cache(maxsize=None)
def _jits(cfg_key: str, mode, fault):
    cfg = json.loads(cfg_key)
    eps = cfg["rms_norm_eps"]

    def f32(w):
        return {name: leaf.astype(jnp.float32) for name, leaf in w.items()}

    def mixer(w, x, attends):
        w = f32(w)
        u = rmsnorm(x, w["mixer_norm"], eps)
        if attends:
            return x + gqa(cfg, w, u, mode, fault)
        return x + kda(cfg, w, u, mode, fault)

    def ffn(w, h):
        experts = (w["gate"], w["up"], w["down"])
        w = f32({name: w[name] for name in FFN_LEAVES})
        u = rmsnorm(h, w["ffn_norm"], eps)
        weights = route(cfg, w, u, mode, fault)               # [S, held]

        def add_expert(y, expert):      # departure 1: every row, weighted
            gate, up, down, weight = (a.astype(jnp.float32) for a in expert)
            return y + swiglu(u, gate, up, down, mode) * weight[:, None], None

        y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                            (*experts, weights.T))
        if fault != "no_shared_expert":
            y = y + swiglu(u, w["shared_gate"], w["shared_up"],
                           w["shared_down"], mode)
        return h + y

    def head_gaps(top, x, xc, nxt, control):
        logits = head_logits(cfg, top, x, mode)
        if control:
            nxt = jnp.argmax(head_logits(cfg, top, xc, control), -1)
        picked = jnp.take_along_axis(logits, nxt[..., None], -1)[..., 0]
        return jnp.max(logits, axis=-1) - picked

    return (jax.jit(mixer, static_argnames="attends"), jax.jit(ffn),
            jax.jit(head_gaps, static_argnames="control"))


def _key(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)


def block(cfg, w, x, l: int, mode="highest", fault=None):
    """Decoder layer ``l``. x [S, E] float32, S a multiple of ``Q_BLOCK``; w:
    ``layer_weights``' leaves (those of both mixer kinds; the layer's own
    kind is read)."""
    attends = is_gqa(cfg, l)
    mixer, ffn, _ = _jits(_key(cfg), mode, fault)
    h = mixer({name: w[name] for name in
               ("mixer_norm",) + MIXER_LEAVES[attends]}, x, attends=attends)
    return ffn({name: w[name] for name in FFN_LEAVES + ("gate", "up", "down")},
               h)


def embed(top, tokens):
    return top["embed"].astype(jnp.float32)[tokens]


def head_logits(cfg, top, x, mode="highest"):
    """Final norm, then the untied head."""
    x = rmsnorm(x, top["final_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
    return mm(x, top["lm_head"].astype(jnp.float32), mode)


def _padded(tokens):
    tokens = np.asarray(tokens, np.int32)
    s = tokens.shape[0]
    return jnp.asarray(np.pad(tokens, (0, -s % PAD_TO))), s


def forward_logits(cfg, layer_fn, top, tokens, mode="highest", fault=None):
    """Logits [S, V] of the plain forward over one sequence ``tokens`` [S], a
    layer at a time (``layer_fn(l)`` gives layer l's leaves). Departure 2:
    padded to ``PAD_TO``."""
    ids, s = _padded(tokens)
    x = embed(top, ids)
    for l in range(cfg["num_hidden_layers"]):
        x = block(cfg, layer_fn(l), x, l, mode, fault)
    return head_logits(cfg, top, x, mode)[:s]


def served_token_gaps(cfg, layer_fn, top, tokens, n_prompt, mode="highest",
                      control_mode=None, fault=None):
    """``reference/decoder.py``'s ``served_token_gaps`` for this family:
    teacher-forced over one request's prompt + served tokens (a host array
    [S]), a layer at a time. For each served token (positions ``n_prompt ..
    S-1``), the gap by which its reference logit lies below the reference's
    best there; with ``control_mode``, the gap of the token a pass in that
    lower precision puts first. Departure 2: padded to ``PAD_TO``."""
    ids, s = _padded(tokens)
    x = embed(top, ids)
    xc = x if control_mode else None
    for l in range(cfg["num_hidden_layers"]):
        w = layer_fn(l)
        x = block(cfg, w, x, l, mode, fault)
        if control_mode:
            xc = block(cfg, w, xc, l, control_mode, fault)
    head_gaps = _jits(_key(cfg), mode, fault)[-1]
    gaps = head_gaps(top, x, x if xc is None else xc, jnp.roll(ids, -1),
                     control=control_mode)
    return np.asarray(gaps)[n_prompt - 1: s - 1]
