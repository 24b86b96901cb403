"""Plain reference of the ``mla_moe`` decoder (Mistral-Small-4-119B's language
model: DeepSeek-V3's layer), written from the equations below and importing
nothing of the program. float32 ``jax.numpy``, matmuls at
``Precision.HIGHEST``.

Pre-norm block, RMSNorm eps ``rms_norm_eps``: ``x += attn(norm(x))``,
``x += ffn(norm(x))``; a final RMSNorm and an untied head.

Attention, per token ``x`` and head h:
``c_q = RMSNorm(x W_dq)``; ``[q_nope_h | q_rope_h] = c_q W_uq,h``;
``[c_kv | k_r] = x W_dkv``; ``c_kv <- RMSNorm(c_kv)``;
``[k_nope_h | v_h] = c_kv W_ukv,h``; ``q_rope_h <- R_t q_rope_h``,
``k_r <- R_t k_r`` (ONE ``k_r`` for all heads). ``R_t`` is the YaRN rotation
at position t on adjacent pairs ``(2i, 2i+1)`` (``rope_interleave``):
frequency i is ``theta^(-2i/d)`` divided by ``factor`` where its wavelength
is long, untouched where it is short, with a linear ramp between the
dimensions that make ``beta_fast`` and ``beta_slow`` turns over the original
length; cos and sin are multiplied by ``m(factor, mscale) / m(factor,
mscale_all_dim)`` with ``m(s, a) = 0.1 a ln s + 1`` (1 here).
``k_h = [k_nope_h | k_r]``; ``score = s g(t) q_h . k_h`` with
``s = (nope + rope)^-0.5 m(factor, mscale_all_dim)^2`` and ``g(t) = 1 +
llama_4_scaling_beta ln(1 + floor(t / original_max))`` on the query at its
own position; causal softmax; ``o_h = sum p v_h``; ``out = concat_h(o_h) W_o``.

FFN: ``r = sigmoid(x W_r)``; the ``num_experts_per_tok`` largest of ``r + b``
(``b`` moves the choice only); ``w_i = r_i / sum_chosen r`` (``norm_topk_prob``)
times ``routed_scaling_factor``; ``ffn(x) = E_shared(x) + sum_i w_i E_i(x)``,
every expert ``E(x) = W_down (silu(W_gate x) * W_up x)``.

Departures, each noted where it is made: (1) the configuration HOLDS a share
of the routed experts (``n_routed_experts`` of ``router_experts``, from
``experts_held_first``): the router is the whole one, and the chosen experts
that are not held add nothing, here as in the program (what the absent chips
of an expert-parallel layer would add is left out and the partial sum goes
on); (2) attention is evaluated in blocks of query rows and an expert on the
rows routed to it (index lists made on the host, padded with weight 0): the
same sums; (3) ``n_group = topk_group = 1``, so the group limit is the
identity and is not written.

``mode`` lowers the precision for the control the comparison has to refuse:
``"int8"`` rounds both operands of every matmul to an int8 grid, ``"bf16"``
to bfloat16; ``"bf16_router"`` is ``"bf16"`` with the router's scores and the
softmax computed in bfloat16 too. ``"highest"`` is the reference itself.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512        # query rows whose [H, rows, S] scores are live at once
PAD_TO = 512         # a sequence is padded to a multiple: few shapes compile
ROW_PAD = 256        # an expert's rows are padded to a multiple


# ---- precision modes -------------------------------------------------------
def _lower(x, mode, axis):
    if mode == "highest":
        return x
    if mode in ("bf16", "bf16_router"):
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "int8":
        scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return jnp.round(x / scale) * scale
    raise ValueError(f"unknown precision mode {mode!r}")


def mm(a, b, mode="highest"):
    return jnp.matmul(_lower(a, mode, -1), _lower(b, mode, 0),
                      precision=HIGHEST)


def _soft_dtype(mode):
    return jnp.bfloat16 if mode == "bf16_router" else jnp.float32


# ---- pieces ----------------------------------------------------------------
def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def m_scale(factor, a):
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, rp: dict):
    """Frequencies of the ``dim // 2`` pairs and the cos/sin factor."""
    theta, factor = float(rp["rope_theta"]), float(rp["factor"])
    original = rp["original_max_position_embeddings"]

    def turn_dim(turns):     # the pair that makes ``turns`` over ``original``
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turn_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(turn_dim(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    inv_freq = freq / factor * ramp + freq * (1 - ramp)
    return inv_freq, (m_scale(factor, rp["mscale"])
                      / m_scale(factor, rp["mscale_all_dim"]))


def rope_pairs(x, positions, rp: dict):
    """x [..., S, H, D]; positions [..., S]: each adjacent pair
    ``(x[2i], x[2i+1])`` turned by ``positions * inv_freq[i]``."""
    inv_freq, factor = yarn_inv_freq(x.shape[-1], rp)
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    cos = (jnp.cos(ang) * factor)[..., None, :]
    sin = (jnp.sin(ang) * factor)[..., None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def query_scale(positions, rp: dict):
    """g(t) = 1 + beta ln(1 + floor(t / original_max))."""
    blocks = jnp.floor(positions / rp["original_max_position_embeddings"])
    return 1.0 + rp.get("llama_4_scaling_beta", 0.0) * jnp.log1p(
        blocks.astype(jnp.float32))


def softmax_scale(cfg: dict) -> float:
    rp = cfg["rope_parameters"]
    d = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return d ** -0.5 * m_scale(float(rp["factor"]), rp["mscale_all_dim"]) ** 2


def attention(cfg, w, x, positions, mode="highest"):
    """The attention sublayer on the normed input. x [B, S, E]."""
    b, s, _ = x.shape
    h, eps, rp = cfg["num_attention_heads"], cfg["rms_norm_eps"], cfg["rope_parameters"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kl = cfg["kv_lora_rank"]
    c_q = rmsnorm(mm(x, w["wq_a"], mode), w["q_a_norm"], eps)
    q = mm(c_q, w["wq_b"], mode).reshape(b, s, h, nope + rope)
    q = q * query_scale(positions, rp)[..., None, None]
    q = jnp.concatenate([q[..., :nope],
                         rope_pairs(q[..., nope:], positions, rp)], -1)
    kv = mm(x, w["wkv_a"], mode)
    c_kv = rmsnorm(kv[..., :kl], w["kv_a_norm"], eps)
    k_r = rope_pairs(kv[..., None, kl:], positions, rp)       # [B, S, 1, r]
    up = mm(c_kv, w["wkv_b"], mode).reshape(b, s, h, nope + vd)
    k = jnp.concatenate([up[..., :nope],
                         jnp.broadcast_to(k_r, (b, s, h, rope))], -1)
    v = _lower(up[..., nope:], mode, -1)
    k = _lower(k, mode, -1)
    scale, soft = softmax_scale(cfg), _soft_dtype(mode)

    def rows(args):     # one block of query rows against every key
        qb, pb = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", _lower(qb, mode, -1), k,
                        precision=HIGHEST) * scale
        mask = positions[:, None, None, :] <= pb[:, None, :, None]
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf).astype(soft), -1)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          _lower(p.astype(jnp.float32), mode, -1), v,
                          precision=HIGHEST)

    if s <= Q_BLOCK or s % Q_BLOCK:
        o = rows((q, positions))
    else:
        nb = s // Q_BLOCK
        qs = q.reshape(b, nb, Q_BLOCK, h, -1).swapaxes(0, 1)
        ps = positions.reshape(b, nb, Q_BLOCK).swapaxes(0, 1)
        o = jax.lax.map(rows, (qs, ps)).swapaxes(0, 1).reshape(b, s, h, vd)
    return mm(o.reshape(b, s, h * vd), w["wo"], mode)


def route(cfg, w, x, mode="highest", use_bias=True):
    """[T, held] combine weights of the HELD experts for rows x [T, E]: the
    whole router's choice, the columns of the experts held here (departure
    1). ``use_bias`` False leaves the selection bias out (a test's fault)."""
    soft = _soft_dtype(mode)
    r = jax.nn.sigmoid(mm(x, w["router"], mode).astype(soft)).astype(jnp.float32)
    choice = r + w["router_bias"] if use_bias else r
    _, idx = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(r, idx, -1)
    if cfg["norm_topk_prob"]:
        picked = picked / jnp.sum(picked, -1, keepdims=True)
    picked = picked * cfg["routed_scaling_factor"]
    dense = jnp.zeros_like(r).at[jnp.arange(r.shape[0])[:, None], idx].set(picked)
    first = cfg.get("experts_held_first", 0)
    return dense[:, first: first + cfg["n_routed_experts"]]


def expert(x, gate, up, down, mode="highest"):
    return mm(jax.nn.silu(mm(x, gate, mode)) * mm(x, up, mode), down, mode)


@functools.lru_cache(maxsize=None)
def _jits(cfg_key, mode, use_bias):
    cfg = _unkey(cfg_key)
    eps = cfg["rms_norm_eps"]

    def attn_part(w, x, positions):
        x = x + attention(cfg, w, rmsnorm(x, w["input_norm"], eps),
                          positions, mode)
        h = rmsnorm(x, w["post_attn_norm"], eps)
        b, s, e = h.shape
        shared = expert(h, w["shared_gate_proj"], w["shared_up"],
                        w["shared_down"], mode)
        return x + shared, h.reshape(b * s, e), route(
            cfg, w, h.reshape(b * s, e), mode, use_bias)

    def expert_rows(h, rows, weight, gate, up, down):
        return expert(h[rows], gate, up, down, mode) * weight[:, None]

    def head_gaps(top, x, xc, nxt, control):
        logits = head_logits(cfg, top, x, mode)
        if control:
            nxt = jnp.argmax(head_logits(cfg, top, xc, control), -1)
        picked = jnp.take_along_axis(logits, nxt[..., None], -1)[..., 0]
        return jnp.max(logits, axis=-1) - picked

    return (jax.jit(attn_part), jax.jit(expert_rows),
            jax.jit(head_gaps, static_argnames="control"))


def _key(cfg: dict):
    flat = {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, str, bool))}
    return (tuple(sorted(flat.items())),
            tuple(sorted(cfg["rope_parameters"].items())))


def _unkey(key) -> dict:
    return dict(key[0], rope_parameters=dict(key[1]))


def block(cfg, w, x, positions, mode="highest", use_bias=True):
    """One decoder layer. x [B, S, E] float32; w: one layer's leaves, the
    held experts stacked ``[held, ...]``."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    attn_part, expert_rows, _ = _jits(_key(cfg), mode, use_bias)
    x, h, weights = attn_part(w, x, positions)
    b, s, e = x.shape
    y = jnp.zeros((b * s, e), jnp.float32)
    on_host = np.asarray(weights)
    for j in range(on_host.shape[1]):       # departure 2: the rows routed to j
        rows = np.nonzero(on_host[:, j])[0]
        if not len(rows):
            continue
        pad = (-len(rows)) % ROW_PAD
        weight = np.pad(on_host[rows, j], (0, pad))
        rows = np.pad(rows, (0, pad))
        y = y.at[rows].add(expert_rows(h, rows, weight, w["gate"][j],
                                       w["up"][j], w["down"][j]))
    return x + y.reshape(b, s, e)


def embed(top, tokens):
    return top["embed"].astype(jnp.float32)[tokens]


def head_logits(cfg, top, x, mode="highest"):
    x = rmsnorm(x, top["final_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
    return mm(x, top["lm_head"].astype(jnp.float32), mode)


def forward_logits(cfg, layer_fn, top, tokens, mode="highest", use_bias=True):
    """Logits [B, S, V] of the plain forward, a layer at a time
    (``layer_fn(l)`` gives layer l's leaves)."""
    tokens = jnp.asarray(tokens)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None, :],
                                 tokens.shape)
    x = embed(top, tokens)
    for l in range(cfg["num_hidden_layers"]):
        x = block(cfg, layer_fn(l), x, positions, mode, use_bias)
    return head_logits(cfg, top, x, mode)


def served_token_gaps(cfg, layer_fn, top, tokens, n_prompt, mode="highest",
                      control_mode=None, use_bias=True):
    """``reference/decoder.py``'s ``served_token_gaps`` for this family:
    teacher-forced over one request's prompt + served tokens (a host array
    [S]), a layer at a time. For each served token (positions ``n_prompt ..
    S-1``), the gap by which its reference logit lies below the reference's
    best there; with ``control_mode``, the gap of the token a pass in that
    lower precision puts first. The sequence is padded at its end to a
    multiple of ``PAD_TO``; under a causal mask padding changes nothing
    before it."""
    tokens = np.asarray(tokens, np.int32)
    s = tokens.shape[0]
    padded = -(-s // PAD_TO) * PAD_TO
    ids = jnp.asarray(np.pad(tokens, (0, padded - s)))[None, :]
    positions = jnp.arange(padded)[None, :]
    x = embed(top, ids)
    xc = x if control_mode else None
    for l in range(cfg["num_hidden_layers"]):
        w = layer_fn(l)
        x = block(cfg, w, x, positions, mode, use_bias)
        if control_mode:
            xc = block(cfg, w, xc, positions, control_mode, use_bias)
    _, _, head_gaps = _jits(_key(cfg), mode, use_bias)
    gaps = head_gaps(top, x, x if xc is None else xc,
                     jnp.roll(ids, -1, axis=1), control=control_mode)
    return np.asarray(gaps)[0, n_prompt - 1: s - 1]
