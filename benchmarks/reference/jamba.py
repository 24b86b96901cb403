"""Plain reference of the ``jamba`` decoder (ai21labs AI21-Jamba2-3B), written
from the equations below and the configuration, importing nothing of the
program. float32 ``jax.numpy``, matmuls at ``Precision.HIGHEST``, no kernel,
no cache, no batching of requests: whole sequences, and the state-space layer
as a ``lax.scan`` over TOKENS of the recurrence as it is written.

Layer ``l`` on ``x [S, E]``, ``n(.)`` an RMSNorm with ``rms_norm_eps`` and a
learned scale: ``h = x + Mixer_l(n_1(x))``, ``y = h + FFN(n_2(h))``. ``u`` is
a sublayer's normed input. No positional encoding anywhere.

- Mixer, MAMBA (``l % attn_layer_period != attn_layer_offset``; ``C =
  mamba_expand x E`` channels, ``N = mamba_d_state``, ``R = mamba_dt_rank``,
  ``L = mamba_d_conv`` taps): ``[x~, z] = u W_in``. ``x_t = silu(b_c +
  sum_{j < L} w_c[:, j] * x~_{t - (L - 1) + j})`` (rows before the sequence
  are zero). ``[dt~_t, B_t, C_t] = x_t W_x``, then ``dt~ = RMSNorm_R(dt~)``,
  ``B = RMSNorm_N(B)``, ``C = RMSNorm_N(C)``, each with its own scale.
  ``Delta_t = softplus(dt~_t W_dt + b_dt)``; ``A = -exp(A_log)`` ``[C, N]``.
  State ``h [C, N]``, zero at the sequence's start:
  ``h_t[c, n] = exp(Delta_t[c] A[c, n]) h_{t-1}[c, n] + Delta_t[c] B_t[n]
  x_t[c]``, ``y_t[c] = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]``,
  ``out_t = (y_t * silu(z_t)) W_out``.
- Mixer, ATTENTION (``l % attn_layer_period == attn_layer_offset``): ``q = u
  W_q`` (``num_attention_heads`` heads of ``head_dim``), ``k = u W_k``, ``v =
  u W_v`` (``num_key_value_heads`` heads), no rope, no QK-norm, no bias;
  causal softmax of ``q k^T / sqrt(head_dim)``, query head ``j`` reads kv
  head ``j // (heads / kv heads)``; ``W_o a``.
- FFN (every layer): ``down(silu(gate(u)) * up(u))``.

Top: embedding, the layers, a final RMSNorm, the head TIED to the embedding.

Departures, each noted where it is made: (1) a sequence is padded at its end
to a multiple of ``PAD_TO``, which under a causal mask, a causal convolution
and a recurrence changes nothing before it; (2) the attention layer's scores
are taken in blocks of ``Q_BLOCK`` query rows against every key under the
mask: the same sums.

``mode`` lowers the precision for the control the comparison has to refuse:
``"int8"`` rounds both operands of every matmul to an int8 grid, ``"bf16"``
to bfloat16 (the recurrence itself has no matmul and stays as it is).
``"highest"`` is the reference itself. ``fault`` names one deliberate error
(the tests' sabotage): see ``FAULTS``.
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
FAULTS = (None, "taps_reversed", "no_conv_bias", "no_decay", "no_inner_norms",
          "no_skip", "no_gate", "state_bf16", "k_columns_rolled")


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


def is_attention(cfg: dict, l: int) -> bool:
    return l % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def attention(cfg, w, u, mode="highest", fault=None):
    """The NoPE attention on the normed input u [S, E]."""
    s = u.shape[0]
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = mm(u, w["attn_wq"], mode).reshape(s, hq, d)
    k = mm(u, w["attn_wk"], mode).reshape(s, hkv, d)
    v = mm(u, w["attn_wv"], mode).reshape(s, hkv, d)
    k = _lower(jnp.repeat(k, hq // hkv, axis=1), mode, -1)   # head j: j // g
    v = _lower(jnp.repeat(v, hq // hkv, axis=1), mode, -1)
    if fault == "k_columns_rolled":   # k read a column off
        k = jnp.roll(k, 1, axis=-1)
    keys = jnp.arange(s)

    def rows(args):         # departure 2: one block of query rows
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
    return mm(a, w["attn_wo"], mode)


def mamba(cfg, w, u, mode="highest", fault=None):
    """The Mamba-1 mixer on the normed input u [S, E], token by token."""
    s = u.shape[0]
    n, r, taps_n = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    eps = cfg["rms_norm_eps"]
    xz = mm(u, w["mamba_w_in"], mode)
    c = xz.shape[1] // 2
    xs, z = xz[:, :c], xz[:, c:]
    xs = jnp.concatenate([jnp.zeros((taps_n - 1, c)), xs], 0)
    taps = w["mamba_conv"]                                      # [C, L]
    if fault == "taps_reversed":
        taps = taps[:, ::-1]
    conv = sum(taps[:, j] * xs[j: j + s] for j in range(taps_n))
    if fault != "no_conv_bias":
        conv = conv + w["mamba_conv_bias"]
    x = jax.nn.silu(conv)                                       # [S, C]
    low = mm(x, w["mamba_w_x"], mode)
    dt, b, cc = low[:, :r], low[:, r:r + n], low[:, r + n:]
    if fault != "no_inner_norms":
        dt = rmsnorm(dt, w["mamba_dt_norm"], eps)
        b = rmsnorm(b, w["mamba_b_norm"], eps)
        cc = rmsnorm(cc, w["mamba_c_norm"], eps)
    delta = jax.nn.softplus(mm(dt, w["mamba_w_dt"], mode)
                            + w["mamba_dt_bias"])               # [S, C]
    a = -jnp.exp(w["mamba_a_log"])                              # [C, N]

    def step(h, row):       # h [C, N]; one token's rows
        x_t, delta_t, b_t, c_t = row
        decay = (jnp.ones_like(h) if fault == "no_decay"
                 else jnp.exp(delta_t[:, None] * a))
        h = decay * h + (delta_t * x_t)[:, None] * b_t[None, :]
        if fault == "state_bf16":   # the state rounded a step
            h = h.astype(jnp.bfloat16).astype(jnp.float32)
        return h, jnp.sum(h * c_t[None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((c, n)), (x, delta, b, cc))
    if fault != "no_skip":
        y = y + w["mamba_d"] * x
    if fault != "no_gate":
        y = y * jax.nn.silu(z)
    return mm(y, w["mamba_w_out"], mode)


def swiglu(x, gate, up, down, mode="highest"):
    return mm(jax.nn.silu(mm(x, gate, mode)) * mm(x, up, mode), down, mode)


MIXER_LEAVES = {
    True: ("attn_wq", "attn_wk", "attn_wv", "attn_wo"),
    False: ("mamba_w_in", "mamba_conv", "mamba_conv_bias", "mamba_w_x",
            "mamba_dt_norm", "mamba_b_norm", "mamba_c_norm", "mamba_w_dt",
            "mamba_dt_bias", "mamba_a_log", "mamba_d", "mamba_w_out"),
}
FFN_LEAVES = ("ffn_norm", "gate", "up", "down")


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
            return x + attention(cfg, w, u, mode, fault)
        return x + mamba(cfg, w, u, mode, fault)

    def ffn(w, h):
        w = f32(w)
        u = rmsnorm(h, w["ffn_norm"], eps)
        return h + swiglu(u, w["gate"], w["up"], w["down"], mode)

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
    attends = is_attention(cfg, l)
    mixer, ffn, _ = _jits(_key(cfg), mode, fault)
    h = mixer({name: w[name] for name in
               ("mixer_norm",) + MIXER_LEAVES[attends]}, x, attends=attends)
    return ffn({name: w[name] for name in FFN_LEAVES}, h)


def embed(top, tokens):
    return top["embed"].astype(jnp.float32)[tokens]


def head_logits(cfg, top, x, mode="highest"):
    """Final norm, then the head tied to the embedding."""
    x = rmsnorm(x, top["final_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
    return mm(x, top["embed"].astype(jnp.float32).T, mode)


def _padded(tokens):
    tokens = np.asarray(tokens, np.int32)
    s = tokens.shape[0]
    return jnp.asarray(np.pad(tokens, (0, -s % PAD_TO))), s


def forward_logits(cfg, layer_fn, top, tokens, mode="highest", fault=None):
    """Logits [S, V] of the plain forward over one sequence ``tokens`` [S], a
    layer at a time (``layer_fn(l)`` gives layer l's leaves). Departure 1:
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
    lower precision puts first. Departure 1: padded to ``PAD_TO``."""
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
