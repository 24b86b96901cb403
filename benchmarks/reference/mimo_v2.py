"""Plain reference of the ``mimo_v2`` decoder (XiaomiMiMo MiMo-V2.5), written
from the equations below and the configuration, importing nothing of the
program. float32 ``jax.numpy``, matmuls at ``Precision.HIGHEST``, no kernel,
no cache, no batching of requests: whole sequences.

Layer ``l`` on ``x [S, E]``, ``n(.)`` an RMSNorm with ``layernorm_epsilon``
and a learned scale: ``h = x + Attn_l(n_1(x))``, ``y = h + FFN_l(n_2(h))``.
``u`` is a sublayer's normed input.

- Attention, both kinds: ``q = u W_q`` (``num_attention_heads`` heads of
  ``head_dim``), ``k = u W_k`` (``Hkv`` heads of ``head_dim``), ``v = u W_v``
  (``Hkv`` heads of ``v_head_dim``), no bias, no QK-norm. Rope on the FIRST
  ``int(head_dim x partial_rotary_factor)`` columns of every q and k head,
  column ``i`` paired with ``i + half`` inside them (rotate-half), the other
  columns pass through. Scores ``q k^T / sqrt(head_dim)``; query head ``j``
  reads kv head ``j // (heads / Hkv)``; the values are multiplied by
  ``attention_value_scale``; ``W_o`` takes the heads' ``v_head_dim`` columns.
- FULL layer (``hybrid_layer_pattern[l] == 0``): ``Hkv =
  num_key_value_heads``, rope base ``rope_theta``, causal softmax over every
  earlier position and its own.
- WINDOW layer (``== 1``): ``Hkv = swa_num_key_value_heads``, rope base
  ``swa_rope_theta``, position ``t`` sees ``t - (sliding_window - 1) .. t``,
  and the softmax has one more column a query head holding its learned sink
  logit (``add_swa_attention_sink_bias``), dropped after the softmax: it
  takes probability and gives no value.
- FFN, dense (``moe_layer_freq[l] == 0``): ``W_2 (silu(W_1 u) * W_3 u)``.
- FFN, routed: ``s = sigmoid(u W_r)`` in float32 over ALL the router's
  outputs (``router_experts``); the ``num_experts_per_tok`` largest of ``s +
  b`` (``b`` moves the choice only); weights ``s[choice] / (sum + 1e-20)``
  (``norm_topk_prob``); ``sum_k w_k E_k(u)``, every expert a SwiGLU. This chip
  HOLDS experts ``experts_held_first .. + n_routed_experts``: pairs of the
  absent experts are dropped and the partial sum goes on, in the program and
  here alike.

Top: embedding, the layers, a final RMSNorm, an untied head.

Departures, each noted where it is made: (1) every HELD expert is evaluated
on every row and weighted by the router's weight for it, which is zero where
the expert was not chosen: the same sums, no index lists; (2) a sequence is
padded at its end to a multiple of ``PAD_TO``, which under a causal mask
changes nothing before it; (3) attention runs in blocks of ``Q_BLOCK`` query
rows (a 36 k-token request's scores would be 350 GB at once), a full layer's
block against every key under the mask, a window layer's against the
``Q_BLOCK + sliding_window - 1`` keys that can reach it: the same sums.

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
PAD_TO = 256         # a sequence is padded to a multiple: few shapes compile
Q_BLOCK = 128        # query rows whose scores are live at once
FAULTS = (None, "no_sink", "window_short", "window_wide", "no_value_scale",
          "window_theta_on_full", "no_choice_bias", "rope_on_all_columns")


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


def rotary_dims(cfg: dict) -> int:
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


def rope_partial(x, positions, theta: float, rot: int):
    """x [S, H, D]; positions [S]: of the first ``rot`` columns, ``i`` and ``i
    + rot / 2`` turned by ``positions * theta^(-2i / rot)``; the rest as they
    are."""
    inv_freq = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = positions[:, None].astype(jnp.float32) * inv_freq       # [S, rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], -1)


def attention(cfg, w, u, positions, window_layer: bool, mode="highest",
              fault=None):
    """The attention operator of one kind on the normed input u [S, E]; w
    holds that kind's ``wq, wk, wv, wo`` (and ``sink``)."""
    s = u.shape[0]
    hq, dk, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    hkv = cfg["swa_num_key_value_heads" if window_layer
              else "num_key_value_heads"]
    theta = float(cfg["swa_rope_theta" if window_layer else "rope_theta"])
    if fault == "window_theta_on_full":
        theta = float(cfg["swa_rope_theta"])
    rot = dk if fault == "rope_on_all_columns" else rotary_dims(cfg)
    q = mm(u, w["wq"], mode).reshape(s, hq, dk)
    k = mm(u, w["wk"], mode).reshape(s, hkv, dk)
    v = mm(u, w["wv"], mode).reshape(s, hkv, dv)
    q = rope_partial(q, positions, theta, rot)
    k = rope_partial(k, positions, theta, rot)
    if fault != "no_value_scale":
        v = v * cfg["attention_value_scale"]
    k = jnp.repeat(k, hq // hkv, axis=1)      # query head j: kv head j // g
    v = jnp.repeat(v, hq // hkv, axis=1)
    window = None
    if window_layer:
        window = cfg["sliding_window"] + {"window_short": -1,
                                          "window_wide": 1}.get(fault, 0)
    sink = w.get("sink") if fault != "no_sink" else None
    # departure 3: blocks of query rows; a window layer's block sees the keys
    # from `window - 1` before its first row to its last row
    reach = s if window is None else min(s, Q_BLOCK + window - 1)
    pad = reach - Q_BLOCK if window is not None else 0
    if pad:
        k = jnp.pad(k, ((pad, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((pad, 0), (0, 0), (0, 0)))
        kpos = jnp.pad(positions, (pad, 0), constant_values=-2 ** 30)
    else:
        kpos = positions

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK, 0)
        qpos = jax.lax.dynamic_slice_in_dim(positions, start, Q_BLOCK, 0)
        first = start if window is not None else 0   # in the padded keys
        kb = jax.lax.dynamic_slice_in_dim(k, first, reach, 0)
        vb = jax.lax.dynamic_slice_in_dim(v, first, reach, 0)
        kp = jax.lax.dynamic_slice_in_dim(kpos, first, reach, 0)
        scores = jnp.einsum("qhd,khd->hqk", _lower(qb, mode, -1),
                            _lower(kb, mode, -1), precision=HIGHEST) / dk ** 0.5
        mask = (kp[None, :] <= qpos[:, None]) & (kp[None, :] >= 0)
        if window is not None:
            mask &= qpos[:, None] - kp[None, :] < window
        scores = jnp.where(mask[None], scores, -jnp.inf)
        if sink is not None:    # one more column a head, dropped afterwards
            column = jnp.broadcast_to(sink[:, None, None], (hq, Q_BLOCK, 1))
            p = jax.nn.softmax(jnp.concatenate([scores, column], -1),
                               -1)[..., :-1]
        else:
            p = jax.nn.softmax(scores, -1)
        return jnp.einsum("hqk,khd->qhd", _lower(p, mode, -1),
                          _lower(vb, mode, -1), precision=HIGHEST)

    o = jax.lax.map(rows, jnp.arange(0, s, Q_BLOCK)).reshape(s, hq * dv)
    return mm(o, w["wo"], mode)


def swiglu(x, gate, up, down, mode="highest"):
    return mm(jax.nn.silu(mm(x, gate, mode)) * mm(x, up, mode), down, mode)


def route(cfg, w, u, mode="highest", fault=None):
    """[S, held experts] combine weights for rows u [S, E]: the router scores
    ALL its outputs and picks among all; the columns of the experts held
    here are returned (zero where one was not chosen)."""
    s = jax.nn.sigmoid(mm(u, w["router"], mode))
    choice = s if fault == "no_choice_bias" else s + w["router_bias"]
    _, idx = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    if cfg["routed_scaling_factor"]:
        picked = picked * cfg["routed_scaling_factor"]
    weights = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(
        picked)
    first = cfg.get("experts_held_first", 0)
    return weights[:, first: first + cfg["n_routed_experts"]]


@functools.lru_cache(maxsize=None)
def _jits(cfg_key: str, mode, fault):
    cfg = json.loads(cfg_key)
    eps = cfg["layernorm_epsilon"]

    def f32(w):
        return {name: leaf.astype(jnp.float32) for name, leaf in w.items()}

    def attn(w, x, positions, window_layer):
        w = f32(w)
        u = rmsnorm(x, w["attn_norm"], eps)
        return x + attention(cfg, w, u, positions, window_layer, mode, fault)

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
        weights = route(cfg, w, u, mode, fault)               # [S, held]

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

    return (jax.jit(attn, static_argnames="window_layer"), jax.jit(dense_ffn),
            jax.jit(routed_ffn), jax.jit(head_gaps, static_argnames="control"))


def _key(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)


def block(cfg, w, x, positions, l: int, mode="highest", fault=None):
    """Decoder layer ``l``. x [S, E] float32, S a multiple of ``Q_BLOCK``; w:
    ``layer_weights``' leaves (those of every kind; the layer's own kind is
    read)."""
    window_layer = bool(cfg["hybrid_layer_pattern"][l])
    routed = bool(cfg["moe_layer_freq"][l])
    kind = "window" if window_layer else "full"
    mine = {"attn_norm": w["attn_norm"],
            **{name.split("_", 1)[1]: leaf for name, leaf in w.items()
               if name.startswith(kind + "_")}}
    attn, dense_ffn, routed_ffn, _ = _jits(_key(cfg), mode, fault)
    h = attn(mine, x, positions, window_layer=window_layer)
    if routed:
        return routed_ffn({name: w[name] for name in (
            "ffn_norm", "router", "router_bias", "gate", "up", "down")}, h)
    return dense_ffn({name: w[name] for name in (
        "ffn_norm", "dense_gate", "dense_up", "dense_down")}, h)


def embed(top, tokens):
    return top["embed"].astype(jnp.float32)[tokens]


def head_logits(cfg, top, x, mode="highest"):
    """Final norm, then logits through the untied head."""
    x = rmsnorm(x, top["final_norm"].astype(jnp.float32),
                cfg["layernorm_epsilon"])
    return mm(x, top["lm_head"].astype(jnp.float32), mode)


def _padded(tokens):
    tokens = np.asarray(tokens, np.int32)
    padded = -(-tokens.shape[0] // PAD_TO) * PAD_TO
    return jnp.asarray(np.pad(tokens, (0, padded - tokens.shape[0])))


def forward_logits(cfg, layer_fn, top, tokens, mode="highest", fault=None):
    """Logits [S, V] of the plain forward over one sequence ``tokens`` [S], a
    layer at a time (``layer_fn(l)`` gives layer l's leaves)."""
    s = len(tokens)
    ids = _padded(tokens)                    # departure 2
    positions = jnp.arange(ids.shape[0])
    x = embed(top, ids)
    for l in range(cfg["num_hidden_layers"]):
        x = block(cfg, layer_fn(l), x, positions, l, mode, fault)
    return head_logits(cfg, top, x, mode)[:s]


def served_token_gaps(cfg, layer_fn, top, tokens, n_prompt, mode="highest",
                      control_mode=None, fault=None):
    """``reference/decoder.py``'s ``served_token_gaps`` for this family:
    teacher-forced over one request's prompt + served tokens (a host array
    [S]), a layer at a time. For each served token (positions ``n_prompt ..
    S-1``), the gap by which its reference logit lies below the reference's
    best there; with ``control_mode``, the gap of the token a pass in that
    lower precision puts first. Departure 2: padded to ``PAD_TO``."""
    s = len(tokens)
    ids = _padded(tokens)
    positions = jnp.arange(ids.shape[0])
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
