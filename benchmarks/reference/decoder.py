"""Plain reference of the llama-family decoder, written from the published
descriptions and importing nothing of the program.

Two block wirings:

- ``pre_norm`` with ``qk_norm: per_head`` (Qwen3): ``x += attn(norm(x))``,
  ``x += mlp(norm(x))``; RMSNorm over each head's ``head_dim`` on q and k
  before the rotary embedding.
- ``post_norm`` with ``qk_norm: flat`` (OLMo-2): ``x += norm(attn(x))``,
  ``x += norm(mlp(x))``; RMSNorm over the full ``heads * head_dim`` width of
  q and k before the head split.

Both: grouped-query causal softmax attention with half-rotation RoPE, a
SwiGLU MLP, a final RMSNorm and a (tied or untied) output head. Everything is
float32 ``jax.numpy``; matmuls run at ``Precision.HIGHEST`` (on a TPU a float32
matmul is otherwise computed in bfloat16 passes).

``mode`` lowers the precision of every matmul for the *control* that the
comparison has to refuse: ``"bf16"`` rounds both operands to bfloat16,
``"int8"`` rounds them to an int8 grid (per-row absmax of the left operand,
per-column absmax of the right one), straight-through for gradients.
``"highest"`` is the reference itself.

Departures from the published models: none in the mathematics. Attention is
evaluated in blocks of query rows and the loss in blocks of positions so that
long sequences fit; the result is the same sum.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 1024
LOSS_BLOCK = 2048   # tokens whose [tokens, V] float32 logits are live at once


# ---- precision modes -------------------------------------------------------
def _int8_grid(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _lower(x, mode, axis):
    if mode == "highest":
        return x
    if mode == "bf16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif mode == "int8":
        q = _int8_grid(x, axis)
    else:
        raise ValueError(f"unknown precision mode {mode!r}")
    return x + jax.lax.stop_gradient(q - x)  # straight-through


def mm(a, b, mode="highest"):
    """``a @ b`` (contraction over a's last and b's first axis)."""
    return jnp.matmul(_lower(a, mode, -1), _lower(b, mode, 0),
                      precision=HIGHEST)


# ---- layers ----------------------------------------------------------------
def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """x [..., S, H, D]; positions [..., S]. Half-rotation convention."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, q_pos, k_pos, mode="highest"):
    """q [B,Sq,Hq,D], k/v [B,Sk,Hkv,D] -> [B,Sq,Hq,D]; a key is visible to
    a query at the same or a later position."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    k = _lower(k, mode, -1)
    v = _lower(v, mode, -1)
    qg = q.reshape(b, sq, hkv, g, d)

    def one_block(qb, qpb):
        s = jnp.einsum("bqhgd,bkhd->bhgqk", _lower(qb, mode, -1), k,
                       precision=HIGHEST) / math.sqrt(d)
        mask = k_pos[:, None, None, None, :] <= qpb[:, None, None, :, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", _lower(p, mode, -1), v,
                          precision=HIGHEST)

    if sq <= Q_BLOCK or sq % Q_BLOCK:
        out = one_block(qg, q_pos)
    else:
        nb = sq // Q_BLOCK
        qs = qg.reshape(b, nb, Q_BLOCK, hkv, g, d).swapaxes(0, 1)
        ps = q_pos.reshape(b, nb, Q_BLOCK).swapaxes(0, 1)
        out = jax.lax.map(lambda a: jax.checkpoint(one_block)(*a), (qs, ps))
        out = out.swapaxes(0, 1).reshape(b, sq, hkv, g, d)
    return out.reshape(b, sq, hq, d)


def block(cfg, w, x, positions, mode="highest"):
    """One decoder layer. x [B,S,E] float32; w: one layer's leaves."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    eps, d = cfg["rms_norm_eps"], cfg["head_dim"]
    b, s, _ = x.shape
    pre = cfg["wiring"] == "pre_norm"

    h = rmsnorm(x, w["input_norm"], eps) if pre else x
    q, k, v = mm(h, w["wq"], mode), mm(h, w["wk"], mode), mm(h, w["wv"], mode)
    if cfg["qk_norm"] == "flat":
        q, k = rmsnorm(q, w["q_norm"], eps), rmsnorm(k, w["k_norm"], eps)
    q = q.reshape(b, s, -1, d)
    k = k.reshape(b, s, -1, d)
    v = v.reshape(b, s, -1, d)
    if cfg["qk_norm"] == "per_head":
        q, k = rmsnorm(q, w["q_norm"], eps), rmsnorm(k, w["k_norm"], eps)
    q = rope(q, positions, cfg["rope_theta"])
    k = rope(k, positions, cfg["rope_theta"])
    a = causal_attention(q, k, v, positions, positions, mode)
    a = mm(a.reshape(b, s, -1), w["wo"], mode)
    x = x + (a if pre else rmsnorm(a, w["attn_out_norm"], eps))

    h = rmsnorm(x, w["post_attn_norm"], eps) if pre else x
    m = mm(jax.nn.silu(mm(h, w["gate"], mode)) * mm(h, w["up"], mode),
           w["down"], mode)
    return x + (m if pre else rmsnorm(m, w["mlp_out_norm"], eps))


def embed(top, tokens):
    return top["embed"].astype(jnp.float32)[tokens]


def head_logits(cfg, top, x, mode="highest"):
    x = rmsnorm(x, top["final_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
    w = (top["embed"].astype(jnp.float32).T if cfg["tie_word_embeddings"]
         else top["lm_head"].astype(jnp.float32))
    return mm(x, w, mode)


def hidden_states(cfg, params, tokens, mode="highest", remat=True):
    """Final residual stream for stacked ``params`` (``weights.stacked_weights``
    layout): a scan over the layer axis, one layer live at a time."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x = embed(params["top"], tokens)
    step = functools.partial(block, cfg, mode=mode)
    if remat:
        step = jax.checkpoint(step, static_argnums=())

    def body(x, w):
        return step(w, x, positions), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return x


def forward_logits(cfg, params, tokens, mode="highest"):
    return head_logits(cfg, params["top"],
                       hidden_states(cfg, params, tokens, mode, remat=False),
                       mode)


def nll_sum(cfg, params, tokens, mode="highest"):
    """Sum over rows and positions of the next-token negative log-likelihood,
    and the number of predicted positions. The loss is evaluated in blocks of
    positions: a [S, V] block of float32 logits for one published vocabulary is
    gigabytes."""
    x = hidden_states(cfg, params, tokens, mode)[:, :-1]
    b, n, e = x.shape
    x = x.reshape(b * n, e)
    targets = tokens[:, 1:].reshape(b * n)

    # a scan over blocks of tokens (padded with weight 0), so that the head's
    # gradient is one buffer added to, not one per block
    pad = (-(b * n)) % LOSS_BLOCK
    weight = jnp.pad(jnp.ones((b * n,), jnp.float32), (0, pad))
    x = jnp.pad(x, ((0, pad), (0, 0)))
    targets = jnp.pad(targets, (0, pad))
    nblk = (b * n + pad) // LOSS_BLOCK

    @jax.checkpoint
    def block_nll(total, blk):
        xb, tb, wb = blk
        logits = head_logits(cfg, params["top"], xb, mode)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tb[..., None], -1)[..., 0]
        return total + jnp.sum((logz - picked) * wb), None

    total, _ = jax.lax.scan(
        block_nll, jnp.zeros((), jnp.float32),
        (x.reshape(nblk, LOSS_BLOCK, e), targets.reshape(nblk, LOSS_BLOCK),
         weight.reshape(nblk, LOSS_BLOCK)))
    return total, b * n


# ---- training: loss, gradients, AdamW --------------------------------------
def loss_and_grads(cfg, params, tokens, rows_per_block, mode="highest"):
    """Mean next-token loss of the whole batch and its gradients, accumulated
    over blocks of rows (``tokens`` [B, S], B a multiple of the block)."""
    b, s = tokens.shape
    nb = b // rows_per_block
    count = b * (s - 1)
    if nb == 1:     # the whole batch at once: no second gradient buffer
        (l, _), g = jax.value_and_grad(
            lambda p: nll_sum(cfg, p, tokens, mode), has_aux=True)(params)
        return l / count, jax.tree.map(lambda x: x / count, g)
    blocks = tokens.reshape(nb, rows_per_block, s)

    def one(carry, rows):
        loss_sum, grads = carry
        (l, _), g = jax.value_and_grad(
            lambda p: nll_sum(cfg, p, rows, mode), has_aux=True)(params)
        return (loss_sum + l, jax.tree.map(jnp.add, grads, g)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (loss_sum, grads), _ = jax.lax.scan(
        one, (jnp.zeros((), jnp.float32), zeros), blocks)
    return loss_sum / count, jax.tree.map(lambda g: g / count, grads)


def cosine_lr(opt: dict, step):
    """Cosine from ``lr`` to ``lr * eta_min_ratio`` over ``t_max`` steps, flat
    after; ``step`` counts updates already made."""
    lr, eta_min = opt["lr"], opt["lr"] * opt["eta_min_ratio"]
    t = jnp.clip(step, 0, opt["t_max"])
    return eta_min + (lr - eta_min) * 0.5 * (1 + jnp.cos(jnp.pi * t / opt["t_max"]))


def adamw_update(opt: dict, params, grads, m, v, step):
    """Decoupled-weight-decay Adam (Loshchilov & Hutter), bias-corrected;
    ``step`` counts updates already made (0 for the first)."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    lr = cosine_lr(opt, step)
    t = step + 1
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m_, v_):
        return p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps) + wd * p)

    return jax.tree.map(upd, params, m, v), m, v


def leaf_norms(tree) -> dict:
    """Norm of every leaf; for a stacked ``[L, ...]`` layer leaf, one norm per
    layer. Keys are ``top/<name>`` and ``layers/<name>``."""
    out = {}
    for name, x in tree["top"].items():
        out[f"top/{name}"] = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    for name, x in tree["layers"].items():
        x = x.astype(jnp.float32)
        out[f"layers/{name}"] = jnp.sqrt(
            jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
    return out


def train_steps(cfg, opt, make_params, key, batches, rows_per_block,
                mode="highest", place=None, moments_on_host=False):
    """Follow ``len(batches)`` AdamW steps from ``make_params(key)``
    (traceable: the seed's weights; ``key`` is an operand of the programs that
    call it, so a new seed compiles nothing). Returns each step's loss, the
    per-leaf norms of the first gradient, and the per-leaf norms of the
    parameters' change after the last step. The starting weights are made again for that difference rather
    than kept: a float32 copy is gigabytes. ``place`` jits ``make_params``
    with the caller's shardings. With ``moments_on_host`` Adam's two moments
    wait in host memory while the next gradient is computed (float32
    parameters, gradients, moments and a long row's activations do not all
    fit one chip)."""
    delta_fn = jax.jit(lambda p, key: leaf_norms(
        jax.tree.map(lambda x, y: x - y, p, make_params(key))))
    p = (place or jax.jit)(make_params)(key)
    shardings = jax.tree.map(lambda x: x.sharding, p)
    # zeros do not depend on their argument, so without this their sharding
    # would be the compiler's choice: one whole copy on one chip
    zeros_like = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                         out_shardings=shardings)
    grad_fn = jax.jit(lambda p, tokens: _loss_grads_norms(
        cfg, p, tokens, rows_per_block, mode),
        out_shardings=(None, shardings, None))
    update_fn = jax.jit(lambda p, g, m, v, i: adamw_update(opt, p, g, m, v, i),
                        donate_argnums=(0, 1, 2, 3),
                        out_shardings=(shardings, shardings, shardings))
    m = v = None
    losses, grad_norms = [], None
    for i, tokens in enumerate(batches):
        loss, g, gn = grad_fn(p, tokens)
        losses.append(float(loss))
        if i == 0:
            grad_norms = jax.device_get(gn)
            m, v = zeros_like(g), zeros_like(g)
        elif moments_on_host:
            m, v = jax.device_put(m, shardings), jax.device_put(v, shardings)
        p, m, v = update_fn(p, g, m, v, jnp.asarray(i, jnp.int32))
        del g
        if moments_on_host and i + 1 < len(batches):
            m, v = jax.device_get(m), jax.device_get(v)
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": jax.device_get(delta_fn(p, key))}


def _loss_grads_norms(cfg, p, tokens, rows_per_block, mode):
    loss, grads = loss_and_grads(cfg, p, tokens, rows_per_block, mode)
    return loss, grads, leaf_norms(grads)


# ---- serving: teacher-forced logit gaps ------------------------------------
PAD_TO = 512


def served_token_gaps(cfg, layer_fn, top, tokens, n_prompt, mode="highest",
                      control_mode=None):
    """Teacher-forced pass over one request's prompt + served tokens
    (``tokens``: a host array [S]), a layer at a time (``layer_fn(l)`` gives
    layer l's leaves: the float32 model need never be held whole).

    Returns, for each served token (positions ``n_prompt .. S-1``), the gap by
    which its reference logit lies below the reference's best at that
    position. With ``control_mode`` it returns instead the gap of the token
    that a pass in that lower precision puts first at those positions.

    The sequence is padded at its end to a multiple of ``PAD_TO`` so that a
    handful of shapes compile; under a causal mask padding changes nothing
    before it."""
    import numpy as np

    tokens = np.asarray(tokens, np.int32)
    s = tokens.shape[0]
    padded = -(-s // PAD_TO) * PAD_TO
    ids = jnp.asarray(np.pad(tokens, (0, padded - s)))[None, :]
    positions = jnp.arange(padded)[None, :]
    x = _embed_jit(top, ids)
    xc = x if control_mode else None
    for l in range(cfg["num_hidden_layers"]):
        w = layer_fn(l)
        x = _block_jit(cfg, mode)(w, x, positions)
        if control_mode:
            xc = _block_jit(cfg, control_mode)(w, xc, positions)
    nxt = jnp.roll(ids, -1, axis=1)
    if control_mode:
        gaps = _gap_jit(cfg, mode, control_mode)(top, x, xc, nxt)
    else:
        gaps = _gap_jit(cfg, mode, None)(top, x, x, nxt)
    # the logits at position i predict token i + 1
    return np.asarray(gaps)[0, n_prompt - 1: s - 1]


_embed_jit = jax.jit(embed)


def _hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _block_jit_cached(cfg_items, mode):
    cfg = dict(cfg_items)
    return jax.jit(lambda w, x, pos: block(cfg, w, x, pos, mode))


def _block_jit(cfg, mode):
    return _block_jit_cached(_hashable(cfg), mode)


@functools.lru_cache(maxsize=None)
def _gap_jit_cached(cfg_items, mode, control_mode):
    cfg = dict(cfg_items)

    def gaps(top, x, xc, nxt):
        logits = head_logits(cfg, top, x, mode)
        if control_mode:
            nxt = jnp.argmax(head_logits(cfg, top, xc, control_mode), -1)
        picked = jnp.take_along_axis(logits, nxt[..., None], -1)[..., 0]
        return jnp.max(logits, axis=-1) - picked

    return jax.jit(gaps)


def _gap_jit(cfg, mode, control_mode):
    return _gap_jit_cached(_hashable(cfg), mode, control_mode)
