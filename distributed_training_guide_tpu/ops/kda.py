"""Kimi Delta Attention (KDA, arXiv 2510.26692): the gated delta rule with a
decay a key channel, as a recurrent STEP (decode) and a chunked SCAN (a
prefill chunk). One head keeps a state ``S`` in ``R^{d_k x d_v}``; a token
brings ``q, k`` (``d_k``), ``v`` (``d_v``), a log-decay ``g <= 0`` (``d_k``)
and a step size ``beta`` (a scalar), and

    S'  = diag(exp(g)) S                        # the key rows decay
    S_t = S' + beta k (v - k^T S')^T            # one rank-one correction
    o_t = S_t^T q

all of it in float32. Both functions compute exactly this recurrence: the
chunked form is the same sum in another order, not an approximation.

:func:`kda_step` takes the POOL the serve engine keeps the states in
(``[layers, blocks, H, d_k, d_v]`` float32, a block a live sequence,
``serve/kv_pages.py``'s state class) and each slot's block id, and returns
the pool with those blocks updated. On a TPU it is one Pallas kernel
(``name="kda_step"``) that reads each slot's block where it lies and writes
it back there, the pool aliased in and out: no pool-sized and no
slots-sized copy exists, and the step moves each live state once in and once
out, which is all the recurrence requires (it is memory-bound: 7 flops an
element of ``S``). A slot with nothing to decode carries block 0, the trash
block, which is read and written like any other and never read by a live
sequence. Off a TPU the same function is a gather, the three lines above in
``jnp`` and a scatter.

:func:`kda_chunk` runs T tokens of each sequence from a state ``S0`` in
blocks of ``BLOCK`` tokens. Inside a block, with ``G_t`` the running sum of
``g`` from the block's start (so ``G`` restarts at every block and every
``exp`` below is of a number ``<= 0``: with a decay of 0.2 a step a running
sum over 64 steps is -103, and ``exp(+103)`` overflows float32), ``w_t :=
beta_t (v_t - k_t^T diag(alpha_t) S_{t-1})`` solves the unit lower
triangular system ``(I + A) W = beta (V - (K * exp(G)) S_0)`` with ``A[t, s]
= beta_t sum_d k_t[d] k_s[d] exp(G_t[d] - G_s[d])`` for ``s < t``; then ``O
= (Q * exp(G)) S_0 + B W`` with ``B[t, s] = sum_d q_t[d] k_s[d] exp(G_t[d] -
G_s[d])`` for ``s <= t``, and the block hands on ``S_C = diag(exp(G_C)) S_0
+ (K * exp(G_C - G))^T W``. ``(I + A)^{-1}`` does not depend on the state,
so it is computed for all blocks at once (a float32 triangular solve) and the
scan over blocks carries ``S`` through matmuls alone. The pairwise ``exp(G_t
- G_s)`` is taken element by element over ``[C, C, d_k]`` (``C d_k``
products a token where the recurrence needs ``7 d_v``): a blocked form does
more than the required work.

Tokens past ``n_valid`` (the padded tail of a final chunk) leave the state as
it is: their ``g`` and ``beta`` are set to 0.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import note_choice, resolve_interpret

BLOCK = 64          # tokens a block of the chunked scan
HEAD_TILE = 16      # heads a grid step of the step kernel: a 1 MB tile of S
TRASH_BLOCK = 0     # the block idle slots carry (kv_pages' state class)
HIGHEST = jax.lax.Precision.HIGHEST


def _resolve_impl(impl: str) -> str:
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"kda impl must be 'auto', 'pallas' or 'xla', got "
                         f"{impl!r}")
    if impl == "auto":
        backend = jax.default_backend()
        impl = "pallas" if backend == "tpu" else "xla"
        note_choice("kda_step", impl, f"auto: backend is {backend}")
    return impl


# ---------------------------------------------------------------------------
# the recurrent step
# ---------------------------------------------------------------------------

def delta_step(s, q, k, v, g, beta):
    """The three lines of the module docstring on states ``s [..., d_k,
    d_v]``, rows ``q, k, g [..., d_k]``, ``v [..., d_v]`` and ``beta [...]``,
    element by element in float32 (no matmul: a TPU's default float32 product
    is rounded to bfloat16). Returns ``(o [..., d_v], s_t)``."""
    s = s * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(k[..., None] * s, axis=-2))
    s = s + k[..., None] * u[..., None, :]
    return jnp.sum(q[..., None] * s, axis=-2), s


def _step_kernel(ids_ref, s_ref, cols_ref, v_ref, o_ref, s_out_ref, *, hb):
    """One slot's ``hb`` heads. ``s_ref [1, 1, hb, d_k, d_v]`` is the slot's
    block of the pool; the vectors that run along the key axis (the decay,
    k, beta k, q) arrive as COLUMNS, ``cols_ref [1, 1, d_k, 4 hb]`` (column
    ``4 h + i`` is head h's i-th vector), so each is a lane broadcast away
    from multiplying S's rows; v and the output run along the value axis, the
    lanes of S, as rows."""
    del ids_ref
    cols = cols_ref[0, 0]
    for h in range(hb):
        decay, kc, kb, qc = (cols[:, 4 * h + i:4 * h + i + 1]
                             for i in range(4))
        s = s_ref[0, 0, h] * decay
        u = v_ref[0, h:h + 1, :] - jnp.sum(s * kc, axis=0, keepdims=True)
        s = s + kb * u
        s_out_ref[0, 0, h] = s
        o_ref[0, h:h + 1, :] = jnp.sum(s * qc, axis=0, keepdims=True)


def _step_pallas(pool, block_ids, layer: int, q, k, v, g, beta, interpret):
    n, h, dk = q.shape
    dv = v.shape[-1]
    hb = HEAD_TILE if h % HEAD_TILE == 0 else h
    cols = jnp.stack([jnp.exp(g), k, beta[..., None] * k, q], axis=-1)
    cols = (cols.reshape(n, h // hb, hb, dk, 4).transpose(0, 1, 3, 2, 4)
                .reshape(n, h // hb, dk, 4 * hb))
    state = pl.BlockSpec((1, 1, hb, dk, dv),
                         lambda s, j, ids: (layer, ids[s], j, 0, 0))
    rows = pl.BlockSpec((1, hb, dv), lambda s, j, ids: (s, j, 0))
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, h // hb),
            in_specs=[state,
                      pl.BlockSpec((1, 1, dk, 4 * hb),
                                   lambda s, j, ids: (s, j, 0, 0)),
                      rows],
            out_specs=[rows, state]),
        out_shape=[jax.ShapeDtypeStruct((n, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={1: 1},    # the pool, after the block ids
        interpret=interpret,
        name="kda_step",
    )(block_ids.astype(jnp.int32), pool, cols, v)
    return o, pool


def kda_step(pool, block_ids, layer: int, q, k, v, g, beta, *,
             impl: str = "auto", interpret: Optional[bool] = None):
    """One token a slot. ``pool [layers, blocks, H, d_k, d_v]``, FLOAT32 and
    nothing narrower (refused by name: no comparison the benchmark makes on
    served tokens sees a state rounded to bfloat16, PERF.md section 7, so
    nothing but this line and ``tests/test_kda.py`` would hold it);
    ``block_ids [S]`` each slot's block (idle slots: ``TRASH_BLOCK``);
    ``layer`` the pool's layer (static); ``q, k, g [S, H, d_k]``, ``v [S, H,
    d_v]``, ``beta [S, H]``, any float dtype (the recurrence is float32).
    Returns ``(o [S, H, d_v] float32, pool)`` with the slots' blocks of
    ``layer`` updated."""
    if pool.dtype != jnp.float32:
        raise TypeError(f"the KDA state pool is float32, got {pool.dtype}: "
                        f"the recurrence is carried in float32 from step to "
                        f"step as published")
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    with jax.named_scope("kda_step"):
        if _resolve_impl(impl) == "pallas":
            return _step_pallas(pool, block_ids, layer, q, k, v, g, beta,
                                resolve_interpret(interpret))
        o, s = delta_step(pool[layer, block_ids], q, k, v, g, beta)
        return o, pool.at[layer, block_ids].set(s)


# ---------------------------------------------------------------------------
# the chunked scan
# ---------------------------------------------------------------------------

def _block_terms(q, k, g, beta):
    """What a block's tokens give whatever state they meet, for blocks
    ``[..., C, d_k]`` (``beta [..., C]``): ``(G, T, B)`` with ``G`` the
    running log-decay from the block's start, ``T = (I + A)^{-1}`` and ``B``
    the read-out's lower triangle (module docstring)."""
    c = q.shape[-2]
    run = jnp.cumsum(g, axis=-2)
    t_idx = jnp.arange(c)
    # exp(G_t - G_s) over [.., t, s, d]: never of a positive number (s <= t),
    # and never taken at all where s > t
    diff = run[..., :, None, :] - run[..., None, :, :]
    lower = (t_idx[:, None] >= t_idx[None, :])[..., None]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    kk = jnp.sum(k[..., :, None, :] * k[..., None, :, :] * decay, axis=-1)
    qk = jnp.sum(q[..., :, None, :] * k[..., None, :, :] * decay, axis=-1)
    strict = t_idx[:, None] > t_idx[None, :]
    a = jnp.where(strict, beta[..., :, None] * kk, 0.0)
    eye = jnp.eye(c, dtype=jnp.float32)
    inv = jax.scipy.linalg.solve_triangular(
        eye + a, jnp.broadcast_to(eye, a.shape), lower=True,
        unit_diagonal=True)
    return run, inv, qk


def kda_chunk(s0, q, k, v, g, beta, n_valid=None, *, block: int = BLOCK):
    """T tokens a sequence from state ``s0 [S, H, d_k, d_v]`` (float32):
    ``q, k, g [S, T, H, d_k]``, ``v [S, T, H, d_v]``, ``beta [S, T, H]``,
    ``n_valid [S]`` the real tokens of each (default T). Returns ``(o [S, T,
    H, d_v] float32, s_T)``; rows past ``n_valid`` of ``o`` mean nothing."""
    n, t, h, dk = q.shape
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    with jax.named_scope("kda_chunk"):
        if n_valid is not None:
            real = jnp.arange(t)[None, :] < n_valid[:, None]        # [S, T]
            g = jnp.where(real[..., None, None], g, 0.0)
            beta = jnp.where(real[..., None], beta, 0.0)
        pad = -t % block
        if pad:     # whole blocks: padded tokens leave the state alone too
            q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for x in (q, k, v, g))
            beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
        nb = (t + pad) // block

        def blocks(x):      # [S, T, H, ...] -> [nb, S, H, C, ...]
            x = x.reshape(n, nb, block, *x.shape[2:])
            return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

        q, k, v, g = blocks(q), blocks(k), blocks(v), blocks(g)
        beta = blocks(beta)                                 # [nb, S, H, C]
        run, inv, qk = _block_terms(q, k, g, beta)
        k_in = k * jnp.exp(run)                 # K * exp(G): meets S_0
        q_in = q * jnp.exp(run)
        k_out = k * jnp.exp(run[..., -1:, :] - run)     # to the block's end
        total = jnp.exp(run[..., -1, :])                    # [nb, S, H, dk]

        def mm(spec, a, b):
            return jnp.einsum(spec, a, b, precision=HIGHEST)

        def one(s, xs):
            k_in, q_in, k_out, total, v, beta, inv, qk = xs
            w = mm("nhts,nhsv->nhtv", inv,
                   beta[..., None] * (v - mm("nhtk,nhkv->nhtv", k_in, s)))
            o = mm("nhtk,nhkv->nhtv", q_in, s) + mm("nhts,nhsv->nhtv", qk, w)
            s = total[..., None] * s + mm("nhtk,nhtv->nhkv", k_out, w)
            return s, o

        s_t, o = jax.lax.scan(one, s0.astype(jnp.float32),
                              (k_in, q_in, k_out, total, v, beta, inv, qk))
        o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1)   # [S, nb, C, H, dv]
        return o.reshape(n, nb * block, h, -1)[:, :t], s_t
