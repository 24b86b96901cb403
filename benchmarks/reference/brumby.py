"""Plain reference of the ``brumby`` decoder (manifestai Brumby-14B-Base),
written from the equations below and the configuration, importing nothing of
the program. float32 ``jax.numpy``, matmuls at ``Precision.HIGHEST``, no
kernel, no cache, no state, no feature map, no batching of requests: whole
sequences, and power retention in its ATTENTION FORM.

Layer ``l`` on ``x [S, E]``, ``n(.)`` an RMSNorm with ``rms_norm_eps`` and a
learned scale: ``h = x + Ret_l(n_1(x))``, ``y = h + down(silu(gate(n_2(h))) *
up(n_2(h)))``. All layers are alike. ``u`` is the mixer's normed input.

- ``q_t = u_t W_q`` (``Hq`` heads of ``d``), ``k_t = u_t W_k``, ``v_t = u_t
  W_v`` (``Hkv`` heads), ``a_t = u_t W_g`` (``Hkv`` numbers); no bias.
  Per-head RMSNorm with a learned ``[d]`` scale on ``q`` and on ``k``, THEN
  rope (``rope_theta``, the whole head, halves ``(i, i + d/2)`` paired).
- ``log gamma_t[h] = logsigmoid(a_t[h] + 6.906768)``; ``Gamma_{t,s}[h] =
  exp(sum_{r=s+1..t} log gamma_r[h])``, ``Gamma_{t,t}`` = 1.
- Query head ``j`` reads kv head ``h = j // (Hq / Hkv)``: ``w_{t,s} =
  Gamma_{t,s}[h] ((q_t[j] . k_s[h]) / sqrt(d))^2`` for ``s <= t``, ``o_t[j] =
  (sum_s w_{t,s} v_s[h]) / (sum_s w_{t,s})``: degree 2, no softmax, no
  epsilon. ``Ret(u)_t = concat_j(o_t[j]) W_o``.

Top: embedding, the layers, a final RMSNorm, an UNTIED head.

Departures, each noted where it is made: (1) a sequence is padded at its end
to a multiple of ``PAD_TO``, which under a causal mask changes nothing before
it; (2) the weights are taken in blocks of ``Q_BLOCK`` query rows against
every key under the mask, and the head's logits in blocks of ``Q_BLOCK`` rows
(13,000 rows of 151,936 logits are 7.9 GB): the same sums; (3) ``Gamma`` is
``exp(c_t - c_s)`` of the running sum ``c`` of ``log gamma`` over the whole
sequence (at 13,000 tokens ``|c|`` reaches 260, whose float32 rounding is
3e-5 in the exponent).

``mode`` lowers the precision for the control the comparison has to refuse:
``"int8"`` rounds both operands of every matmul to an int8 grid, ``"bf16"``
to bfloat16. ``"highest"`` is the reference itself. ``fault`` names one
deliberate error (the tests' sabotage): see ``FAULTS``.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD_TO = 1024        # a sequence is padded to a multiple: few shapes compile
Q_BLOCK = 256        # query rows whose weights (or logits) are live at once
GATE_SHIFT = 6.906768
FAULTS = (None, "no_gate", "no_normaliser", "p1", "no_rope",
          "k_columns_rolled")


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


def rope(x, positions, theta):
    """x [S, H, D]; positions [S]. Halves ``(i, i + D/2)`` rotated together."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def retention(cfg, w, u, mode="highest", fault=None):
    """Power retention on the normed input u [S, E], in its attention form."""
    s = u.shape[0]
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, pos = cfg["rms_norm_eps"], jnp.arange(s)
    q = mm(u, w["wq"], mode).reshape(s, hq, d)
    k = mm(u, w["wk"], mode).reshape(s, hkv, d)
    v = mm(u, w["wv"], mode).reshape(s, hkv, d)
    q, k = rmsnorm(q, w["q_norm"], eps), rmsnorm(k, w["k_norm"], eps)
    if fault != "no_rope":
        q = rope(q, pos, cfg["rope_theta"])
        k = rope(k, pos, cfg["rope_theta"])
    log_gamma = jax.nn.log_sigmoid(mm(u, w["wg"], mode) + GATE_SHIFT)
    if fault == "no_gate":
        log_gamma = jnp.zeros_like(log_gamma)
    c = jnp.cumsum(log_gamma, axis=0)               # departure 3: [S, Hkv]
    if fault == "k_columns_rolled":   # k read a column off
        k = jnp.roll(k, 1, axis=-1)
    k, v = _lower(k, mode, -1), _lower(v, mode, -1)
    qg = q.reshape(s, hkv, hq // hkv, d)            # head j reads j // g

    def rows(args):         # departure 2: one block of query rows
        qb, cb, pb = args
        score = jnp.einsum("thgd,shd->hgts", _lower(qb, mode, -1), k,
                           precision=HIGHEST) / d ** 0.5
        mask = pos[None, :] <= pb[:, None]                      # [t, s]
        decay = jnp.exp(jnp.where(mask[None], cb.T[:, :, None]
                                  - c.T[:, None, :], -jnp.inf))  # [h, t, s]
        weight = (jnp.abs(score) if fault == "p1" else score * score
                  ) * decay[:, None]
        num = jnp.einsum("hgts,shd->thgd", _lower(weight, mode, -1), v,
                         precision=HIGHEST)
        if fault == "no_normaliser":
            return num / d
        return num / jnp.moveaxis(jnp.sum(weight, -1), -1, 0)[..., None]

    nb = s // Q_BLOCK
    o = jax.lax.map(rows, (qg.reshape(nb, Q_BLOCK, hkv, hq // hkv, d),
                           c.reshape(nb, Q_BLOCK, hkv),
                           pos.reshape(nb, Q_BLOCK)))
    return mm(o.reshape(s, hq * d), w["wo"], mode)


def swiglu(x, gate, up, down, mode="highest"):
    return mm(jax.nn.silu(mm(x, gate, mode)) * mm(x, up, mode), down, mode)


MIXER_LEAVES = ("mixer_norm", "wq", "wk", "wv", "wg", "wo", "q_norm",
                "k_norm")
FFN_LEAVES = ("ffn_norm", "gate", "up", "down")


@functools.lru_cache(maxsize=None)
def _jits(cfg_key: str, mode, fault):
    cfg = json.loads(cfg_key)
    eps = cfg["rms_norm_eps"]

    def f32(w):
        return {name: leaf.astype(jnp.float32) for name, leaf in w.items()}

    def mixer(w, x):
        w = f32(w)
        return x + retention(cfg, w, rmsnorm(x, w["mixer_norm"], eps), mode,
                             fault)

    def ffn(w, h):
        w = f32(w)
        u = rmsnorm(h, w["ffn_norm"], eps)
        return h + swiglu(u, w["gate"], w["up"], w["down"], mode)

    def head_gaps(top, x, xc, nxt, control):
        def rows(args):     # departure 2: one block of rows of logits
            xb, xcb, nb = args
            logits = head_logits(cfg, top, xb, mode)
            if control:
                nb = jnp.argmax(head_logits(cfg, top, xcb, control), -1)
            picked = jnp.take_along_axis(logits, nb[..., None], -1)[..., 0]
            return jnp.max(logits, axis=-1) - picked

        def blocks(a):
            return a.reshape(-1, Q_BLOCK, *a.shape[1:])

        return jax.lax.map(rows, (blocks(x), blocks(xc),
                                  blocks(nxt))).reshape(-1)

    return jax.jit(mixer), jax.jit(ffn), jax.jit(
        head_gaps, static_argnames="control")


def _key(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)


def block(cfg, w, x, l: int, mode="highest", fault=None):
    """Decoder layer ``l`` (every layer is alike). x [S, E] float32, S a
    multiple of ``Q_BLOCK``; w: ``layer_weights``' leaves."""
    del l
    mixer, ffn, _ = _jits(_key(cfg), mode, fault)
    h = mixer({name: w[name] for name in MIXER_LEAVES}, x)
    return ffn({name: w[name] for name in FFN_LEAVES}, h)


def embed(top, tokens):
    return top["embed"][tokens].astype(jnp.float32)


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
