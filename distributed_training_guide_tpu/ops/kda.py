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
+ (K * exp(G_C - G))^T W``. ``(I + A)^{-1}`` does not depend on the state.

On a TPU it is one Pallas kernel (``name="kda_chunk"``) over the grid
``(sequences, head tiles, blocks)``, the blocks innermost and in order: a
tile's states stay in VMEM from the chunk's first block to its last (read
from ``S0`` once, written once), and the rows are blocked where they lie, as
``[BLOCK, heads d]`` strips of the ``[S, T, H d]`` view (``beta`` alone is
re-laid: T H floats). A block is cut into sub-blocks of ``SUB`` tokens. For
t in sub-block i with first row r and s in an EARLIER sub-block,
``exp(G_t - G_s) = exp(G_t - G_r) exp(G_r - G_s)``, both exponents ``<= 0``,
so neither factor overflows and the product underflows only where the value
does: those entries of ``A`` and ``B`` are products of ``[beta k; q] *
exp(G - G_r)`` (``[2 SUB, d_k]``) with ``(k * exp(G_r - G))^T`` on the MXU.
Only the DIAGONAL sub-blocks are taken element by element, a column a step
over ``[SUB, d_k]``, masked so that no ``exp`` of a positive number is taken
(``4 SUB^2 d_k`` elements a block where the pairwise form takes ``BLOCK^2
d_k``). ``(I + A)^-1`` is forward substitution on the diagonal sub-blocks
(a rank-one step a column, on vector registers) and then the merges
``[[P, 0], [-R A_21 P, R]]`` as products, 16 -> 32 -> 64 wide. The
log-decay is summed inside each sub-block (a product with a block-diagonal
triangle of ones) and every exponent is put together from those short sums
and the sub-blocks' totals, never as the difference of two block-long sums
(whose rounding, half an ulp of 100, would be the exponent's). All heads of
a tile go through each stage together (``[hb, ., .]`` operands, the
products batched over heads): a head's stages wait on each other and the
other heads fill the waits. Every product has float32 operands and a
float32 result at ``Precision.HIGHEST`` (Mosaic's ``contract_precision
<fp32>``): bfloat16 operands are another result, which
``tests/test_kda.py`` refuses and the benchmark's comparison of served
tokens would not see. Off a TPU the same blocks are ``jnp``: the pairwise
``exp(G_t - G_s)`` element by element over ``[C, C, d_k]``, a float32
triangular solve for all blocks at once and a ``lax.scan`` over the blocks
(what the kernel is tested against).

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
SUB = 16            # tokens a sub-block of a block (the chunk kernel)
HEAD_TILE = 16      # heads a grid step of the step kernel: a 1 MB tile of S
CHUNK_HEAD_TILE = 8     # heads a grid step of the chunk kernel
TRASH_BLOCK = 0     # the block idle slots carry (kv_pages' state class)
HIGHEST = jax.lax.Precision.HIGHEST


def _resolve_impl(impl: str, op: str = "kda_step") -> str:
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"kda impl must be 'auto', 'pallas' or 'xla', got "
                         f"{impl!r}")
    if impl == "auto":
        backend = jax.default_backend()
        impl = "pallas" if backend == "tpu" else "xla"
        note_choice(op, impl, f"auto: backend is {backend}")
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


def _chunk_xla(s0, q, k, v, g, beta):
    """The chunked scan in ``jnp`` over whole blocks: the off-TPU path, and
    what the kernel is tested against."""
    n, t, h, _ = q.shape
    nb = t // BLOCK

    def blocks(x):      # [S, T, H, ...] -> [nb, S, H, C, ...]
        x = x.reshape(n, nb, BLOCK, *x.shape[2:])
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

    s_t, o = jax.lax.scan(one, s0, (k_in, q_in, k_out, total, v, beta, inv,
                                    qk))
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1)   # [S, nb, C, H, dv]
    return o.reshape(n, t, h, -1), s_t


def _mm(a, b, contract):
    """A float32 product of float32 operands at full precision (on the MXU:
    Mosaic's ``contract_precision<fp32>``), a head a batch entry: ``a, b [hb,
    ., .]``, ``contract`` the contracted axis of each."""
    return jax.lax.dot_general(a, b, (((contract[0],), (contract[1],)),
                                      ((0,), (0,))), precision=HIGHEST,
                               preferred_element_type=jnp.float32)


_NN, _NT, _TN = (2, 1), (2, 2), (1, 1)


def _chunk_heads(q, k, v, g, b, st, tril, merges):
    """A block of ``C`` tokens of ``hb`` heads: ``q, k, g [hb, C, d_k]``, ``v
    [hb, C, d_v]``, ``b [hb, C, 1]`` and the states TRANSPOSED, ``st [hb,
    d_v, d_k]`` (the block's decay then runs along a state's lanes as it does
    along the rows'). Returns ``(o [hb, C, d_v], st)``. Module docstring: the
    decays off the diagonal sub-blocks through matmuls, the diagonal ones
    element by element, ``(I + A)^-1`` by substitution and merges. Every
    stage is taken for all heads (and all sub-blocks) at once: a head's
    stages wait on each other (a product's way through the MXU, a
    substitution's step on the one before), and the other heads are what
    fills those waits."""
    hb, c, dk = q.shape
    n, neg = c // SUB, -jnp.inf

    def subs(x):        # [hb, C, .] -> [hb n, SUB, .]: a sub-block an entry
        return x.reshape(hb * n, SUB, x.shape[-1])

    # the log-decay summed inside each SUB-BLOCK (`tril` is block diagonal)
    # and the sub-blocks' totals: every exponent below is a sum of these
    # short sums, never a difference of two block-long ones, whose rounding
    # (half an ulp of 100) would be the exponent's
    local = _mm(tril, g, _NN).reshape(hb, n, SUB, dk)
    tot = local[:, :, SUB - 1:]                             # [hb, n, 1, dk]
    rest = tot - local                          # to the sub-block's end
    run = jnp.concatenate(      # G, from the block's start
        [local[:, i] + sum(tot[:, j] for j in range(i)) for i in range(n)], 1)
    to_end = jnp.concatenate(
        [rest[:, i] + sum(tot[:, j] for j in range(i + 1, n))
         for i in range(n)], 1)
    kb = k * b
    # the diagonal sub-blocks, a column a step: exp(G_t - G_s) over [SUB,
    # d_k] for t >= s, never of a positive number. Sub-block i's columns are
    # lanes i SUB .. of the block's rows
    t_sub = jax.lax.broadcasted_iota(jnp.int32, (1, SUB, 1), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (hb * n, 1, c), 2) \
        - jax.lax.broadcasted_iota(jnp.int32, (hb * n, 1, c), 0) % n * SUB
    gi, ki, qi, kbi = local.reshape(hb * n, SUB, dk), subs(k), subs(q), subs(kb)
    a_d = b_d = jnp.zeros((hb * n, SUB, c), jnp.float32)
    cols = []
    for s in range(SUB):
        m = ki[:, s:s + 1] * jnp.exp(
            jnp.where(t_sub >= s, gi - gi[:, s:s + 1], neg))
        cols.append(jnp.where(t_sub > s, jnp.sum(kbi * m, 2, keepdims=True),
                              0.0))             # strictly lower
        a_d = jnp.where(lane == s, cols[s], a_d)
        b_d = jnp.where(lane == s, jnp.sum(qi * m, 2, keepdims=True), b_d)
    # (I + A_ii)^-1 by forward substitution, a rank-one step a column
    x = jnp.where(lane == t_sub, 1.0, 0.0)
    for s in range(SUB - 1):
        x = x - cols[s] * x[:, s:s + 1]
    inv = x.reshape(hb, c, c)
    a_d, b_d = a_d.reshape(hb, n, SUB, c), b_d.reshape(hb, n, SUB, c)
    # the earlier sub-blocks: exp(G_t - G_s) = exp(G_t - G_r) exp(G_r - G_s)
    # with r the first row of t's sub-block, both exponents <= 0; G_r - G_s
    # is s's way to its sub-block's end, the sub-blocks between, and g_r
    a_rows, b_rows = [a_d[:, 0]], [b_d[:, 0]]
    for i in range(1, n):
        lo = i * SUB
        first = local[:, i, :1]
        down = jnp.exp(local[:, i] - first)
        up = jnp.exp(jnp.concatenate(
            [rest[:, j] + (sum(tot[:, m] for m in range(j + 1, i)) + first)
             for j in range(i)] + [jnp.full((hb, c - lo, dk), neg)], 1))
        ab = _mm(jnp.concatenate([kb[:, lo:lo + SUB] * down,
                                  q[:, lo:lo + SUB] * down], 1), k * up, _NT)
        a_rows.append(a_d[:, i] + ab[:, :SUB])
        b_rows.append(b_d[:, i] + ab[:, SUB:])
    a, qk = jnp.concatenate(a_rows, 1), jnp.concatenate(b_rows, 1)
    for below in merges:        # [[P, 0], [-R A_21 P, R]], twice as wide
        inv = inv - _mm(_mm(inv, jnp.where(below, a, 0.0), _NN), inv, _NN)
    decay = jnp.exp(run)
    from_s = _mm(jnp.concatenate([k * decay, q * decay], 1), st, _NT)
    w = _mm(inv, b * (v - from_s[:, :c]), _NN)
    o = from_s[:, c:] + _mm(qk, w, _NN)
    st = st * jnp.exp(run[:, c - 1:]) + _mm(w, k * jnp.exp(to_end), _TN)
    return o, st


def _chunk_kernel(beta_ref, q_ref, k_ref, v_ref, g_ref, s0_ref, o_ref,
                  st_ref, s_scr, *, hb, dk, dv):
    """Block ``i`` of ``hb`` heads of one sequence. The rows arrive as
    ``[1, C, hb d]`` strips of the ``[S, T, H d]`` view, head h's in lanes
    ``h d .. (h + 1) d``; ``beta_ref [1, 1, C, hb]``. ``s_scr [hb, d_v,
    d_k]`` holds the tile's states, transposed, from the chunk's first block
    to its last."""
    i = pl.program_id(2)
    c = q_ref.shape[1]

    @pl.when(i == 0)
    def _():
        for h in range(hb):
            s_scr[h] = s0_ref[0, h].T

    r = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    tril = jnp.where((r >= s) & (r // SUB == s // SUB), 1.0, 0.0)
    merges, m = [], SUB
    while m < c:    # rows of an odd m-wide block, columns of the one before
        merges.append((r // m % 2 == 1) & (s // m == r // m - 1))
        m *= 2

    def heads(ref, d):
        return jnp.stack([ref[0, :, h * d:(h + 1) * d] for h in range(hb)])

    o, st = _chunk_heads(
        heads(q_ref, dk), heads(k_ref, dk), heads(v_ref, dv),
        heads(g_ref, dk),
        jnp.stack([beta_ref[0, 0, :, h:h + 1] for h in range(hb)]),
        s_scr[...], jnp.broadcast_to(tril, (hb, c, c)), merges)
    for h in range(hb):
        o_ref[0, :, h * dv:(h + 1) * dv] = o[h]
    s_scr[...] = st

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        for h in range(hb):
            st_ref[0, h] = s_scr[h].T


@functools.partial(jax.jit, static_argnames="interpret")
def _chunk_pallas(s0, q, k, v, g, beta, interpret: bool):
    """The kernel's call, a ``jit`` of the module: a model's KDA layers are
    walked, not scanned, and the kernel's body is traced once a program."""
    n, t, h, dk = q.shape
    dv = v.shape[-1]
    hb = CHUNK_HEAD_TILE if h % CHUNK_HEAD_TILE == 0 else h
    # beta alone is re-laid (T H floats): its heads do not fill a lane tile
    beta = beta.reshape(n, t, h // hb, hb).transpose(0, 2, 1, 3)
    rows_k = pl.BlockSpec((1, BLOCK, hb * dk), lambda s, j, i: (s, i, j))
    rows_v = pl.BlockSpec((1, BLOCK, hb * dv), lambda s, j, i: (s, i, j))
    state = pl.BlockSpec((1, hb, dk, dv), lambda s, j, i: (s, j, 0, 0))
    o, s_t = pl.pallas_call(
        functools.partial(_chunk_kernel, hb=hb, dk=dk, dv=dv),
        grid=(n, h // hb, t // BLOCK),
        in_specs=[pl.BlockSpec((1, 1, BLOCK, hb), lambda s, j, i: (s, j, i, 0)),
                  rows_k, rows_k, rows_v, rows_k, state],
        out_specs=[rows_v, state],
        out_shape=[jax.ShapeDtypeStruct((n, t, h * dv), jnp.float32),
                   jax.ShapeDtypeStruct(s0.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="kda_chunk",
    )(beta, q.reshape(n, t, h * dk), k.reshape(n, t, h * dk),
      v.reshape(n, t, h * dv), g.reshape(n, t, h * dk), s0)
    return o.reshape(n, t, h, dv), s_t


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _chunk_kernel_call(s0, q, k, v, g, beta, interpret):
    """The kernel forward; a gradient is the ``jnp`` form's (the kernel has
    no backward yet: ROADMAP C6 b), so a stack trains on a TPU as off it."""
    return _chunk_pallas(s0, q, k, v, g, beta, interpret)


def _chunk_fwd(s0, q, k, v, g, beta, interpret):
    return (_chunk_pallas(s0, q, k, v, g, beta, interpret),
            (s0, q, k, v, g, beta))


def _chunk_bwd(interpret, rows, ct):
    return jax.vjp(_chunk_xla, *rows)[1](tuple(ct))


_chunk_kernel_call.defvjp(_chunk_fwd, _chunk_bwd)


def kda_chunk(s0, q, k, v, g, beta, n_valid=None, *, impl: str = "auto",
              interpret: Optional[bool] = None):
    """T tokens a sequence from state ``s0 [S, H, d_k, d_v]`` (float32):
    ``q, k, g [S, T, H, d_k]``, ``v [S, T, H, d_v]``, ``beta [S, T, H]``,
    ``n_valid [S]`` the real tokens of each (default T). Returns ``(o [S, T,
    H, d_v] float32, s_T)``; rows past ``n_valid`` of ``o`` mean nothing."""
    t = q.shape[1]
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    with jax.named_scope("kda_chunk"):
        if n_valid is not None:
            real = jnp.arange(t)[None, :] < n_valid[:, None]        # [S, T]
            g = jnp.where(real[..., None, None], g, 0.0)
            beta = jnp.where(real[..., None], beta, 0.0)
        pad = -t % BLOCK
        if pad:     # whole blocks: padded tokens leave the state alone too
            q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for x in (q, k, v, g))
            beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
        s0 = s0.astype(jnp.float32)
        if _resolve_impl(impl, "kda_chunk") == "pallas":
            o, s_t = _chunk_kernel_call(s0, q, k, v, g, beta,
                                        resolve_interpret(interpret))
        else:
            o, s_t = _chunk_xla(s0, q, k, v, g, beta)
        return o[:, :t], s_t
