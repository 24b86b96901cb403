"""Plain reference of the ``laguna`` decoder (poolside Laguna-XS.2), written
from the equations below and the configuration, importing nothing of the
program. float32 ``jax.numpy``, matmuls at ``Precision.HIGHEST``, no kernel, no
sort, no cache: whole sequences, forward, loss and gradients.

Layer ``l`` on ``x [B, S, E]``, ``n(.)`` an RMSNorm with ``rms_norm_eps`` and a
learned scale: ``h = x + Attn_l(n_1(x))``, ``y = h + FFN_l(n_2(h))``. ``u`` is
a sublayer's normed input.

- Layer kinds (``layer_types``): ``full_attention`` or ``sliding_attention``
  (a WINDOW layer); ``num_attention_heads_per_layer[l]`` query heads ``Hq``
  (48 / 64 as published), ``num_key_value_heads`` kv heads and ``head_dim``
  columns in both.
- Attention: ``q = u W_q``, ``k = u W_k``, ``v = u W_v``, no bias, no QK-norm.
  Rope, rotate-half pairing (column ``i`` with ``i + rot / 2``) inside the
  FIRST ``rot = int(head_dim x partial_rotary_factor)`` columns of every q and
  k head, the rest pass through. FULL (``rope_parameters.full_attention``):
  ``partial_rotary_factor`` 0.5 and YaRN over the turned columns: with
  ``dim(r) = rot ln(original_max / (2 pi r)) / (2 ln theta)``, ``low =
  floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))`` (clipped to ``0 ..
  rot - 1``), pair ``i``'s frequency is ``theta^(-2i / rot)`` for ``i <= low``,
  that over ``factor`` for ``i >= high`` and the linear blend between, and
  ``attention_factor`` multiplies cos and sin. WINDOW
  (``rope_parameters.sliding_attention``): plain rope, its own theta, every
  column. Scores ``q k^T / sqrt(head_dim)``, causal; in a window layer query
  ``i`` sees key ``j`` iff ``0 <= i - j < sliding_window``. Query head ``h``
  reads kv head ``h // (Hq / Hkv)``. ``g = sigmoid(u W_g)`` in ``R^Hq``
  (``gating``: one scalar a head a token) and ``o = W_o concat_h(g_h a_h)``.
- Dense FFN (``mlp_layer_types[l] == "dense"``): ``W_down (silu(W_gate u) *
  W_up u)`` of ``intermediate_size``.
- Sparse FFN: ``s = sigmoid(u W_r)`` over ALL the router's outputs
  (``router_experts``); the ``num_experts_per_tok`` largest ``s_e`` chosen;
  ``w_e = moe_routed_scaling_factor s_e / (sum of the chosen + 1e-20)``
  multiplying the expert's OUTPUT; ``sum_e w_e Expert_e(u) + Shared(u)``,
  every expert and the shared one a SwiGLU. This chip HOLDS experts
  ``experts_held_first .. + num_experts``: pairs of the absent experts are
  left out and that partial sum plus the shared expert goes on, in the program
  and here alike.
- Top: embedding, the layers, a final RMSNorm, an untied head. Loss: mean
  next-token cross-entropy over the (held) vocabulary; no auxiliary term.

Departures, each noted where it is made: (1) every HELD expert is evaluated on
every row and weighted by the router's weight for it, which is zero where the
expert was not chosen: the same sums, no index lists; (2) attention runs in
blocks of ``Q_BLOCK`` query rows, a full layer's block against every key under
the mask, a window layer's against the ``Q_BLOCK + sliding_window - 1`` keys
that can reach it: the same sums; (3) each layer and each block is under
``jax.checkpoint`` and gradients are summed over blocks of rows: memory, not
mathematics.

``mode`` lowers the precision of every matmul for the control the comparison
has to refuse (``reference/decoder.py``'s ``mm``: ``"bf16"``, ``"int8"``,
straight-through for gradients); ``"highest"`` is the reference itself.
``fault`` names one deliberate error (the tests' sabotage): see ``FAULTS``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.decoder import (HIGHEST, _lower, adamw_update, mm,
                                          rmsnorm)

Q_BLOCK = 1024       # query rows whose scores are live at once
FAULTS = (None, "no_gate", "window_rope_on_full", "full_rope_on_window",
          "softmax_router", "window_short")


def is_window(cfg: dict, l: int) -> bool:
    return cfg["layer_types"][l] == "sliding_attention"


# ---- rope ------------------------------------------------------------------
def rope_of(cfg: dict, window_layer: bool) -> tuple:
    """``(inverse frequencies [rot / 2], the factor on cos and sin, rot)`` of a
    layer kind."""
    rp = cfg["rope_parameters"]["sliding_attention" if window_layer
                                else "full_attention"]
    rot = int(cfg["head_dim"] * rp["partial_rotary_factor"])
    theta = float(rp["rope_theta"])
    i = jnp.arange(rot // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / rot)
    if rp["rope_type"] == "default":
        return plain, 1.0, rot
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r}")
    original = rp["original_max_position_embeddings"]

    def dim(rotations):  # the pair that turns `rotations` times over `original`
        return (rot * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim(rp["beta_fast"])), 0)
    high = min(math.ceil(dim(rp["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    blend = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    inv_freq = plain * (1.0 - blend) + plain / rp["factor"] * blend
    return inv_freq, float(rp["attention_factor"]), rot


def rope(x, positions, inv_freq, factor: float, rot: int):
    """x [B, S, H, D]; positions [B, S]."""
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    cos = (jnp.cos(ang) * factor)[..., None, :]
    sin = (jnp.sin(ang) * factor)[..., None, :]
    a, b = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], -1)


# ---- attention -------------------------------------------------------------
def attend(q, k, v, positions, window, mode="highest"):
    """q [B, S, Hq, D], k / v [B, S, Hkv, D] -> [B, S, Hq, D]; causal, and
    under ``window`` a key is seen by the ``window`` queries from its own
    position on. Departure 2: blocks of query rows."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    k, v = _lower(k, mode, -1), _lower(v, mode, -1)
    qg = q.reshape(b, s, hkv, hq // hkv, d)     # head h: kv head h // g
    blk = Q_BLOCK if s % Q_BLOCK == 0 else s
    reach = s if window is None else min(s, blk + window - 1)
    pad = reach - blk if window is not None else 0
    kpos = positions
    if pad:     # a window layer's first block reaches before the sequence
        k = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        kpos = jnp.pad(positions, ((0, 0), (pad, 0)),
                       constant_values=-2 ** 30)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, blk, 1)
        qp = jax.lax.dynamic_slice_in_dim(positions, start, blk, 1)
        first = start if window is not None else 0   # in the padded keys
        kb = jax.lax.dynamic_slice_in_dim(k, first, reach, 1)
        vb = jax.lax.dynamic_slice_in_dim(v, first, reach, 1)
        kp = jax.lax.dynamic_slice_in_dim(kpos, first, reach, 1)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", _lower(qb, mode, -1), kb,
                            precision=HIGHEST) / math.sqrt(d)
        mask = (kp[:, None, :] <= qp[:, :, None]) & (kp[:, None, :] >= 0)
        if window is not None:
            mask &= qp[:, :, None] - kp[:, None, :] < window
        scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", _lower(p, mode, -1), vb,
                          precision=HIGHEST)

    out = jax.lax.map(rows, jnp.arange(0, s, blk))     # [nb, B, blk, ...]
    return out.swapaxes(0, 1).reshape(b, s, hq, d)


def attention(cfg, w, u, positions, l: int, mode="highest", fault=None):
    """The attention operator of layer ``l`` on the normed input u [B, S, E];
    w holds ``wq, wk, wv, wo, wg``."""
    b, s, _ = u.shape
    d, window_layer = cfg["head_dim"], is_window(cfg, l)
    as_window = window_layer
    if fault == "window_rope_on_full" and not window_layer:
        as_window = True
    if fault == "full_rope_on_window" and window_layer:
        as_window = False
    inv_freq, factor, rot = rope_of(cfg, as_window)
    q = mm(u, w["wq"], mode).reshape(b, s, -1, d)
    k = mm(u, w["wk"], mode).reshape(b, s, -1, d)
    v = mm(u, w["wv"], mode).reshape(b, s, -1, d)
    q = rope(q, positions, inv_freq, factor, rot)
    k = rope(k, positions, inv_freq, factor, rot)
    window = None
    if window_layer:
        window = cfg["sliding_window"] - (fault == "window_short")
    a = attend(q, k, v, positions, window, mode)
    if fault != "no_gate":
        a = a * jax.nn.sigmoid(mm(u, w["wg"], mode))[..., None]
    return mm(a.reshape(b, s, -1), w["wo"], mode)


# ---- FFNs ------------------------------------------------------------------
def swiglu(x, gate, up, down, mode="highest"):
    return mm(jax.nn.silu(mm(x, gate, mode)) * mm(x, up, mode), down, mode)


def route(cfg, router, u, mode="highest", fault=None):
    """[T, router outputs] combine weights for rows u [T, E]: zero where an
    expert was not chosen."""
    logits = mm(u, router, mode)
    s = (jax.nn.softmax(logits, -1) if fault == "softmax_router"
         else jax.nn.sigmoid(logits))
    chosen, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    weights = cfg["moe_routed_scaling_factor"] * chosen / (
        jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(
        weights)


def routed(cfg, w, u, mode="highest", fault=None, first=None):
    """The held experts' part of the routed sum for rows u [T, E]: ``w``
    holds ``router`` and the held ``gate / up / down`` ``[held, ...]``, which
    are experts ``first ..`` (the configuration's share unless given)."""
    first = cfg.get("experts_held_first", 0) if first is None else first
    held = w["gate"].shape[0]
    weights = route(cfg, w["router"], u, mode, fault)[:, first: first + held]

    @jax.checkpoint
    def add_expert(y, expert):      # departure 1: every row, weighted
        gate, up, down, weight = expert
        return y + swiglu(u, gate, up, down, mode) * weight[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(u),
                        (w["gate"], w["up"], w["down"], weights.T))
    return y


def shared(w, u, mode="highest"):
    return swiglu(u, w["shared_gate"], w["shared_up"], w["shared_down"], mode)


def sparse_ffn(cfg, w, u, mode="highest", fault=None):
    return routed(cfg, w, u, mode, fault) + shared(w, u, mode)


# ---- the model -------------------------------------------------------------
def layer(cfg, w, x, positions, l: int, mode="highest", fault=None):
    """Decoder layer ``l``. x [B, S, E] float32; w: ``layer_weights``'
    leaves."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    eps = cfg["rms_norm_eps"]
    h = x + attention(cfg, w, rmsnorm(x, w["attn_norm"], eps), positions, l,
                      mode, fault)
    u = rmsnorm(h, w["ffn_norm"], eps)
    if cfg["mlp_layer_types"][l] == "dense":
        return h + swiglu(u, w["dense_gate"], w["dense_up"], w["dense_down"],
                          mode)
    b, s, e = u.shape
    return h + sparse_ffn(cfg, w, u.reshape(b * s, e), mode,
                          fault).reshape(b, s, e)


def hidden_states(cfg, params, tokens, mode="highest", fault=None):
    """Final residual stream for ``weights_laguna.model_weights``' layout,
    each layer under ``jax.checkpoint`` (departure 3)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x = params["top"]["embed"].astype(jnp.float32)[tokens]
    for l, w in enumerate(params["layers"]):
        step = functools.partial(layer, cfg, l=l, mode=mode, fault=fault)
        x = jax.checkpoint(step)(w, x, positions)
    return x


def head_logits(cfg, top, x, mode="highest"):
    x = rmsnorm(x, top["final_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
    return mm(x, top["lm_head"].astype(jnp.float32), mode)


def forward_logits(cfg, params, tokens, mode="highest", fault=None):
    return head_logits(cfg, params["top"],
                       hidden_states(cfg, params, tokens, mode, fault), mode)


def nll_sum(cfg, params, tokens, mode="highest", fault=None):
    """Sum over rows and positions of the next-token negative log-likelihood
    and the number of predicted positions."""
    logits = head_logits(cfg, params["top"], hidden_states(
        cfg, params, tokens, mode, fault)[:, :-1], mode)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.sum(logz - picked), tokens.shape[0] * (tokens.shape[1] - 1)


# ---- training: loss, gradients, AdamW --------------------------------------
def loss_and_grads(cfg, params, tokens, rows_per_block, mode="highest",
                   fault=None):
    """Mean next-token loss of the whole batch and its gradients, summed over
    blocks of rows (``tokens`` [B, S], B a multiple of the block)."""
    b, s = tokens.shape
    count = b * (s - 1)
    blocks = tokens.reshape(b // rows_per_block, rows_per_block, s)

    def one(carry, rows):
        loss_sum, grads = carry
        (l, _), g = jax.value_and_grad(
            lambda p: nll_sum(cfg, p, rows, mode, fault), has_aux=True)(params)
        return (loss_sum + l, jax.tree.map(jnp.add, grads, g)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (loss_sum, grads), _ = jax.lax.scan(
        one, (jnp.zeros((), jnp.float32), zeros), blocks)
    return loss_sum / count, jax.tree.map(lambda g: g / count, grads)


def leaf_norms(tree) -> dict:
    """Norm of every leaf: ``top/<name>`` a scalar, ``layers/<name>`` one
    norm for each layer that has the leaf, in layer order; a held expert's
    matrix is a leaf of its own (``layers/gate``: held norms a sparse layer,
    layer-major), so one expert's rows gone astray show in its own norm."""
    def norm(x, axes=None):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axes))

    out = {f"top/{name}": norm(x) for name, x in tree["top"].items()}
    by_name: dict = {}
    for leaves in tree["layers"]:
        for name, x in leaves.items():
            by_name.setdefault(name, []).append(
                norm(x, (1, 2)) if x.ndim == 3 else norm(x)[None])
    out.update({f"layers/{name}": jnp.concatenate(v)
                for name, v in by_name.items()})
    return out


def train_steps(cfg, opt, make_params, key, batches, rows_per_block,
                mode="highest", moments_on_host=False):
    """``reference/decoder.py``'s ``train_steps`` for this family on one
    chip: follow ``len(batches)`` AdamW steps from ``make_params(key)``;
    returns each step's loss, the per-leaf norms of the first gradient and of
    the parameters' change after the last step. With ``moments_on_host``
    Adam's two moments wait in host memory while the next gradient is
    computed."""
    delta_fn = jax.jit(lambda p, key: leaf_norms(
        jax.tree.map(lambda x, y: x - y, p, make_params(key))))
    p = jax.jit(make_params)(key)

    def grads_and_norms(p, tokens):
        loss, grads = loss_and_grads(cfg, p, tokens, rows_per_block, mode)
        return loss, grads, leaf_norms(grads)

    grad_fn = jax.jit(grads_and_norms)
    update_fn = jax.jit(lambda p, g, m, v, i: adamw_update(opt, p, g, m, v, i),
                        donate_argnums=(0, 1, 2, 3))
    m = v = None
    losses, grad_norms = [], None
    for i, tokens in enumerate(batches):
        loss, g, gn = grad_fn(p, tokens)
        losses.append(float(loss))
        if i == 0:
            grad_norms = jax.device_get(gn)
            m, v = (jax.tree.map(jnp.zeros_like, g) for _ in range(2))
        elif moments_on_host:
            m, v = jax.device_put(m), jax.device_put(v)
        p, m, v = update_fn(p, g, m, v, jnp.asarray(i, jnp.int32))
        del g
        if moments_on_host and i + 1 < len(batches):
            m, v = jax.device_get(m), jax.device_get(v)
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": jax.device_get(delta_fn(p, key))}
