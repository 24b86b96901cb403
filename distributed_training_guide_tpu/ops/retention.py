"""Power retention of degree 2 ("Scaling Context Requires Rethinking
Attention", arXiv 2507.04239; Brumby-14B): linear attention whose weights are
``(q . k)^2``, with a scalar forget gate a kv head a token and the output
divided by the weights' own sum,

    w[t, s] = Gamma[t, s] (q_t . k_s)^2,   Gamma[t, s] = prod_{r=s+1..t} gamma_r
    o_t     = sum_{s<=t} w[t, s] v_s / sum_{s<=t} w[t, s]

as a recurrent STEP (decode) and a blocked SCAN (a prefill chunk) over a
carried state. With ``phi(x)`` the symmetric degree-2 feature map, ``phi(x) .
phi(y) = (x . y)^2``, a kv head carries

    S_t = gamma_t S_{t-1} + phi(k_t) v_t^T      [D, d]
    Z_t = gamma_t Z_{t-1} + k_t k_t^T           [d, d]
    o_t = S_t^T phi(q_t) / (q_t^T Z_t q_t)

from zeros, all of it in float32. The published normaliser ``z_t = gamma_t
z_{t-1} + phi(k_t)`` is the vech of the symmetric ``Z_t`` (``z . phi(q) =
q^T Z q``); it is carried as the MATRIX because a ``[d, d]`` tile is what a
product takes (``d^2`` floats a head beside ``D d`` of ``S``: 1.4%). The
score's ``1 / sqrt(d)`` cancels in the quotient and is not applied.

THE FEATURE MAP IS BLOCKED (:func:`feature_map`): ``d`` is cut into blocks of
``FEATURE_BLOCK`` = 16, and the rows of ``phi`` are the ``16 x 16`` outer
products ``x_I (x) x_J`` of the block pairs ``I <= J`` (:func:`feature_pairs`:
36 pairs of 256 rows at ``d`` = 128, ``D`` = 9,216), times ``sqrt 2`` where
``I < J``. A diagonal pair holds both ``x_a x_b`` and ``x_b x_a``: 960 of the
9,216 rows are such repeats (the exact map has 8,256), the price of rows that
are whole ``[16, 16]`` tiles which a kernel builds from two products with 0/1
matrices (``x E_rep`` repeats block ``I``'s entries 16 times each, ``x
E_tile`` lays block ``J`` 16 times side by side) and one multiplication, no
gather and no transpose.

:func:`retention_step` takes the POOLS the serve engine keeps ``S`` and ``Z``
in (``[layers, blocks, Hkv, P, 256, d]`` and ``[layers, blocks, Hkv, d, d]``
float32, a block a live sequence: ``serve/kv_pages.py``'s state class) and
each slot's block id. On a TPU the ``S`` part is one Pallas kernel
(``name="retention_step"``) over ``(slots, kv heads, pair tiles)`` that reads
a tile of the slot's state where it lies, adds the rank-one update, reads it
out for the head's query heads and writes it back, the pool aliased in and
out: one head's ``S`` is 4.7 MB, so the kernel tiles ``D`` and the state
moves once in and once out (byte-bound: 1.5 flops a byte). ``phi`` of the
step's one token a head is made by XLA (``PAIR_TILE x 8 x 256`` floats a grid
step beside ``PAIR_TILE x 256 x 128`` of state), ``Z`` (64 KB a head) is
updated and read by XLA. A slot at position 0 (``fresh``) reads zeros whatever
its block holds; idle slots carry block 0, the trash block.

:func:`retention_chunk` runs T tokens of each sequence against the state in
blocks of ``TOKENS``: the in-block causal pairs in the ``(q . k)^2`` form
(``4 d`` flops a pair a query head), the carried state's read-out ``phi(q)
S`` and update ``phi(k)^T v`` pair by pair, under the cumulative gates
(every ``exp`` is of a number ``<= 0``). On a TPU it is one Pallas kernel
(``name="retention_chunk"``) over ``(sequences, kv heads, token blocks)``,
the token blocks innermost: a head's whole state stays in VMEM from the
chunk's first block to its last, read from the pool once and written once,
in place; ``phi`` exists a ``[rows, 256]`` tile at a time in VMEM and never
in HBM (``phi(q)`` of a 1,024-token chunk would be 755 MB a layer); a
sequence's first chunk skips the read-out of a state that is zero. The
products that meet the float32 state run at ``Precision.HIGHEST``; ``q`` and
``k`` in bfloat16 go through the 0/1 products exactly in one pass (a product
of two bfloat16 numbers is exact in float32), float32 ``q`` and ``k`` at
``HIGHEST``.

Tokens past ``n_valid`` (the padded tail of a final chunk) leave the state as
it is: their log-gate is 0 and their key's weight 0.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import note_choice, resolve_interpret

FEATURE_BLOCK = 16  # entries a block of the head's width (the map's tiles)
TOKENS = 128        # tokens a block of the chunked scan
PAIR_TILE = 12      # block pairs a grid step of the step kernel (1.5 MB of S)
ROWS = 8            # sublanes of the step kernel's row operands
TRASH_BLOCK = 0     # the block idle slots carry (kv_pages' state class)
CHUNK_VMEM_BYTES = 56 * 2 ** 20     # the chunk kernel's: a head's S four times
HIGHEST = jax.lax.Precision.HIGHEST


def _resolve_impl(impl: str, op: str) -> str:
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"retention impl must be 'auto', 'pallas' or 'xla', "
                         f"got {impl!r}")
    if impl == "auto":
        backend = jax.default_backend()
        impl = "pallas" if backend == "tpu" else "xla"
        note_choice(op, impl, f"auto: backend is {backend}")
    return impl


# ---------------------------------------------------------------------------
# the feature map
# ---------------------------------------------------------------------------

def feature_pairs(d: int) -> tuple:
    """``(I, J)`` int32 arrays of the block pairs ``I <= J`` of a head of
    width ``d``, in the order the state's rows lie."""
    if d % FEATURE_BLOCK:
        raise ValueError(f"head width {d} is no multiple of the feature "
                         f"map's block, {FEATURE_BLOCK}")
    nb = d // FEATURE_BLOCK
    pairs = [(i, j) for i in range(nb) for j in range(i, nb)]
    return (np.asarray([p[0] for p in pairs], np.int32),
            np.asarray([p[1] for p in pairs], np.int32))


def feature_rows(d: int) -> int:
    """``D`` as stored: rows of ``phi`` (9,216 at ``d`` = 128; the exact
    symmetric map has ``d (d + 1) / 2`` = 8,256)."""
    return len(feature_pairs(d)[0]) * FEATURE_BLOCK ** 2


def feature_map(x: jnp.ndarray) -> jnp.ndarray:
    """``phi(x) [..., P, 256]`` float32 of ``x [..., d]``: pair ``(I, J)``'s
    row ``16 a + b`` is ``x[16 I + a] x[16 J + b]``, times ``sqrt 2`` where
    ``I < J``, so ``sum(phi(x) * phi(y)) = (x . y)^2``."""
    fb = FEATURE_BLOCK
    i, j = feature_pairs(x.shape[-1])
    xb = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, fb)
    w = jnp.where(i == j, 1.0, math.sqrt(2.0)).astype(jnp.float32)
    phi = xb[..., i, :, None] * xb[..., j, None, :] * w[:, None, None]
    return phi.reshape(*x.shape[:-1], len(i), fb * fb)


def state_shapes(n_kv_heads: int, d: int) -> tuple:
    """One sequence's ``(S, Z)`` in one layer, as the pools hold them."""
    pairs = len(feature_pairs(d)[0])
    return ((n_kv_heads, pairs, FEATURE_BLOCK ** 2, d), (n_kv_heads, d, d))


def _check_pools(pool, norm_pool):
    for name, leaf in (("S", pool), ("Z", norm_pool)):
        if leaf.dtype != jnp.float32:
            raise TypeError(
                f"the retention state pool ({name}) is float32, got "
                f"{leaf.dtype}: a sum over thousands of tokens at a gate "
                f"near 1 is carried in float32 from step to step")


def _group(q, n_kv: int):
    """``[..., Hq, d]`` -> ``[..., Hkv, G, d]``: query head ``j`` reads kv
    head ``j // G``."""
    return q.reshape(*q.shape[:-2], n_kv, q.shape[-2] // n_kv, q.shape[-1])


# ---------------------------------------------------------------------------
# the recurrent step
# ---------------------------------------------------------------------------

def _step_kernel(ids_ref, s_ref, phi_ref, v_ref, o_ref, s_out_ref, *, g):
    """Pair tile ``j`` of one kv head of one slot. ``s_ref [1, 1, 1, pt, 256,
    d]`` is the tile of the slot's block; ``phi_ref [1, 1, pt, ROWS, 256]``
    the feature rows of the head's ``g`` queries (rows ``0 .. g - 1``) and of
    its key (row ``g``), zeros below; ``v_ref [1, 1, ROWS, d]`` the value in
    row ``g`` (so ``phi^T v`` is the key's rank-one term alone), the gate in
    row ``g + 1`` and ``keep`` (0 for a slot at position 0) in row ``g + 2``,
    each along the lanes."""
    del ids_ref
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    rows = v_ref[0, 0]
    gamma, keep = rows[g + 1:g + 2], rows[g + 2:g + 3]
    # the value's row alone: the gate and keep rows meet zero rows of phi
    acc = jnp.zeros(o_ref.shape[2:], jnp.float32)
    for p in range(s_ref.shape[3]):
        phi = phi_ref[0, 0, p]
        s = jnp.where(keep > 0, s_ref[0, 0, 0, p], 0.0) * gamma
        s = s + jax.lax.dot_general(
            phi, rows, (((0,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)
        s_out_ref[0, 0, 0, p] = s
        acc = acc + jnp.dot(phi, s, precision=HIGHEST,
                            preferred_element_type=jnp.float32)
    o_ref[0, 0] += acc


def _step_pallas(pool, block_ids, layer: int, q, k, v, gamma, fresh,
                 interpret):
    n, hkv, g, d = q.shape
    pairs = pool.shape[3]
    pt = PAIR_TILE if pairs % PAIR_TILE == 0 else pairs
    if g + 3 > ROWS:
        raise ValueError(f"{g} query heads a kv head: the step kernel's row "
                         f"operands hold {ROWS - 3}")
    zeros = jnp.zeros((n, hkv, ROWS - g - 1, d), jnp.float32)
    phi = feature_map(jnp.concatenate([q, k[:, :, None], zeros], axis=2))
    phi = phi.swapaxes(2, 3)                        # [S, Hkv, P, ROWS, 256]
    keep = 1.0 - fresh.astype(jnp.float32)
    lanes = jnp.ones((n, hkv, 1, d), jnp.float32)
    rows = jnp.concatenate(
        [jnp.zeros((n, hkv, g, d), jnp.float32), v[:, :, None],
         gamma[:, :, None, None] * lanes, keep[:, None, None, None] * lanes,
         jnp.zeros((n, hkv, ROWS - g - 3, d), jnp.float32)], axis=2)
    state = pl.BlockSpec((1, 1, 1, pt, *pool.shape[4:]),
                         lambda s, h, j, ids: (layer, ids[s], h, j, 0, 0))
    out = pl.BlockSpec((1, 1, ROWS, d), lambda s, h, j, ids: (s, h, 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, g=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, hkv, pairs // pt),
            in_specs=[state,
                      pl.BlockSpec((1, 1, pt, ROWS, phi.shape[-1]),
                                   lambda s, h, j, ids: (s, h, j, 0, 0)),
                      out],
            out_specs=[out, state]),
        out_shape=[jax.ShapeDtypeStruct((n, hkv, ROWS, d), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={1: 1},    # the pool, after the block ids
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="retention_step",
    )(block_ids.astype(jnp.int32), pool, phi, rows)
    return o[:, :, :g], pool


def retention_step(pool, norm_pool, block_ids, layer: int, q, k, v,
                   log_gamma, fresh=None, *, impl: str = "auto",
                   interpret: Optional[bool] = None):
    """One token a slot. ``pool [layers, blocks, Hkv, P, 256, d]`` and
    ``norm_pool [layers, blocks, Hkv, d, d]``, FLOAT32 and nothing narrower
    (refused by name); ``block_ids [S]`` each slot's block (idle slots:
    ``TRASH_BLOCK``); ``layer`` the pools' layer (static); ``q [S, Hq, d]``,
    ``k, v [S, Hkv, d]``, any float dtype; ``log_gamma [S, Hkv]`` the gate's
    logarithm (``<= 0``); ``fresh [S]`` true where the slot's sequence starts
    here, which reads zeros for its state. Returns ``(o [S, Hq, d] float32,
    pool, norm_pool)`` with the slots' blocks of ``layer`` updated."""
    _check_pools(pool, norm_pool)
    hkv = k.shape[1]
    q, k, v, log_gamma = (x.astype(jnp.float32)
                          for x in (q, k, v, log_gamma))
    q = _group(q, hkv)                                  # [S, Hkv, G, d]
    if fresh is None:
        fresh = jnp.zeros(block_ids.shape, bool)
    gamma = jnp.exp(log_gamma)
    with jax.named_scope("retention_step"):
        # Z: 64 KB a head, element by element (a TPU's default float32
        # product is rounded to bfloat16)
        z = jnp.where(fresh[:, None, None, None], 0.0,
                      norm_pool[layer, block_ids])
        z = gamma[..., None, None] * z + k[..., :, None] * k[..., None, :]
        qz = jnp.sum(q[..., :, None] * z[:, :, None], axis=-2)
        den = jnp.sum(qz * q, axis=-1)                  # [S, Hkv, G]
        norm_pool = norm_pool.at[layer, block_ids].set(z)
        if _resolve_impl(impl, "retention_step") == "pallas":
            num, pool = _step_pallas(pool, block_ids, layer, q, k, v, gamma,
                                     fresh, resolve_interpret(interpret))
        else:
            s = jnp.where(fresh[:, None, None, None, None], 0.0,
                          pool[layer, block_ids])
            s = (gamma[..., None, None, None] * s
                 + feature_map(k)[..., None] * v[:, :, None, None, :])
            num = jnp.einsum("shgpr,shprv->shgv", feature_map(q), s,
                             precision=HIGHEST)
            pool = pool.at[layer, block_ids].set(s)
        o = num / den[..., None]
        return o.reshape(o.shape[0], -1, o.shape[-1]), pool, norm_pool


# ---------------------------------------------------------------------------
# the blocked scan over a chunk's tokens
# ---------------------------------------------------------------------------

def _block_jnp(s, z, q, k, v, c, weight):
    """One block of ``C`` tokens of every kv head of one sequence against the
    state ``(s [Hkv, P, 256, d], z [Hkv, d, d])``: ``q [C, Hkv, G, d]``, ``k,
    v [C, Hkv, d]``, ``c [C, Hkv]`` the log-gate summed from the block's
    start (inclusive), ``weight [C]`` 1 for a real token, else 0. Returns
    ``(o [C, Hkv, G, d], s, z)``. The module docstring's sums in ``jnp``."""
    n = q.shape[0]
    t_idx = jnp.arange(n)
    lower = t_idx[:, None] >= t_idx[None, :]
    diff = c.T[:, :, None] - c.T[:, None, :]                    # [Hkv, t, s]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    qk = jnp.einsum("thgd,shd->hgts", q, k, precision=HIGHEST)
    w = qk * qk * decay[:, None]
    num = jnp.einsum("hgts,shv->thgv", w, v, precision=HIGHEST)
    den = jnp.moveaxis(jnp.sum(w, axis=-1), -1, 0)              # [t, Hkv, G]
    from_start = jnp.exp(c)[:, :, None]                         # [t, Hkv, 1]
    num = num + from_start[..., None] * jnp.einsum(
        "thgpr,hprv->thgv", feature_map(q), s, precision=HIGHEST)
    den = den + from_start * jnp.einsum(
        "thgd,hde,thge->thg", q, z, q, precision=HIGHEST)
    to_end = jnp.exp(c[-1:] - c) * weight[:, None]              # [s, Hkv]
    total = jnp.exp(c[-1])
    s = total[:, None, None, None] * s + jnp.einsum(
        "thpr,thv->hprv", feature_map(k) * to_end[..., None, None], v,
        precision=HIGHEST)
    z = total[:, None, None] * z + jnp.einsum(
        "th,thd,the->hde", to_end, k, k, precision=HIGHEST)
    return num / den[..., None], s, z


def _chunk_xla(s0, z0, q, k, v, c, weight):
    """The scan over whole blocks in ``jnp``: the off-TPU path, and what the
    kernel is tested against. ``s0, z0`` lead with the sequences; ``q [S, nb,
    C, Hkv, G, d]``, ``k, v [S, nb, C, Hkv, d]``, ``c [S, nb, C, Hkv]``,
    ``weight [S, nb, C]``."""
    def sequence(s0, z0, *rows):
        def one(state, row):
            o, s, z = _block_jnp(*state, *row)
            return (s, z), o

        (s, z), o = jax.lax.scan(one, (s0, z0), rows)
        return o, s, z

    return jax.vmap(sequence)(s0, z0, q, k, v, c, weight)


def _chunk_kernel(ids_ref, fresh_ref, valid_ref, pi_ref, pj_ref, q_ref,
                  k_ref, v_ref, c_ref, s_in_ref, z_in_ref, o_ref, s_ref,
                  z_ref, num_scr, *, g):
    """Token block ``i`` of one kv head of one sequence. ``q_ref [1, C, g
    d]`` the head's ``g`` query heads side by side, ``k_ref, v_ref [1, C,
    d]``, ``c_ref [1, 1, 1, 1, C]`` the log-gate summed from the block's
    start; ``s_ref [1, 1, 1, P, 256, d]`` and ``z_ref [1, 1, 1, d, d]`` are
    the OUTPUT blocks, which stay in VMEM over the head's token blocks and
    hold the running state; ``num_scr [g C, d]`` collects the carried
    state's read-out over the pairs."""
    n, i = pl.program_id(0), pl.program_id(2)
    tb, d = k_ref.shape[1], k_ref.shape[2]
    fb = FEATURE_BLOCK
    fresh = fresh_ref[n] > 0
    f32 = jnp.float32

    @pl.when(i == 0)
    def _():
        @pl.when(fresh)
        def _():
            s_ref[...] = jnp.zeros_like(s_ref)
            z_ref[...] = jnp.zeros_like(z_ref)

        @pl.when(jnp.logical_not(fresh))
        def _():
            s_ref[...] = s_in_ref[...]
            z_ref[...] = z_in_ref[...]

    carried = jnp.logical_not(jnp.logical_and(fresh, i == 0))
    k, v = k_ref[0], v_ref[0].astype(f32)
    qs = [q_ref[0, :, j * d:(j + 1) * d] for j in range(g)]
    # float32 rows through the 0/1 products at full precision; bfloat16 rows
    # are exact in one pass
    prec = HIGHEST if k.dtype == f32 else None

    def mm(a, b, precision=HIGHEST):
        return jnp.dot(a, b, precision=precision, preferred_element_type=f32)

    def mm_tn(a, b):        # a^T b, the token axis contracted
        return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                                   precision=HIGHEST,
                                   preferred_element_type=f32)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (tb, tb), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (tb, tb), 1)
    c_row = c_ref[0, 0, 0]                                      # [1, C]
    c_col = jnp.sum(jnp.where(t_idx == s_idx, c_row, 0.0), axis=1,
                    keepdims=True)                              # [C, 1]
    c_last = c_row[:, tb - 1:tb]
    decay = jnp.exp(jnp.where(t_idx >= s_idx, c_col - c_row, -jnp.inf))
    real = (jax.lax.broadcasted_iota(jnp.int32, (tb, 1), 0)
            < valid_ref[n] - i * tb)
    to_end = jnp.where(real, jnp.exp(c_last - c_col), 0.0)      # [C, 1]
    total = jnp.exp(c_last)                                     # [1, 1]

    # the block's own causal pairs, a query head at a time
    nums, dens = [], []
    for qj in qs:
        qk = jax.lax.dot_general(qj, k, (((1,), (1,)), ((), ())),
                                 precision=prec, preferred_element_type=f32)
        w = qk * qk * decay
        nums.append(mm(w, v))
        dens.append(jnp.sum(w, axis=1, keepdims=True))

    # the carried state: Z whole, S pair by pair
    q_all = jnp.concatenate(qs, axis=0)                         # [g C, d]
    q32, k32 = q_all.astype(f32), k.astype(f32)
    z = z_ref[0, 0, 0]
    den_c = jnp.sum(mm(q32, z) * q32, axis=1, keepdims=True)    # [g C, 1]
    z_ref[0, 0, 0] = total * z + mm_tn(k32 * to_end, k32)
    num_scr[...] = jnp.zeros_like(num_scr)
    e_row = jax.lax.broadcasted_iota(jnp.int32, (d, fb * fb), 0)
    e_col = jax.lax.broadcasted_iota(jnp.int32, (d, fb * fb), 1)
    shift = fb.bit_length() - 1

    def pair(p, carry):
        bi, bj = pi_ref[p], pj_ref[p]
        rep = jnp.where(e_row == bi * fb + (e_col >> shift), 1.0,
                        0.0).astype(k.dtype)
        tile = jnp.where(e_row == bj * fb + (e_col & (fb - 1)), 1.0,
                         0.0).astype(k.dtype)
        wgt = jnp.where(bi == bj, 1.0, math.sqrt(2.0)).astype(f32)
        s_old = s_ref[0, 0, 0, p]

        @pl.when(carried)
        def _():
            phi_q = mm(q_all, rep, prec) * mm(q_all, tile, prec) * wgt
            num_scr[...] += mm(phi_q, s_old)

        phi_k = mm(k, rep, prec) * mm(k, tile, prec) * (to_end * wgt)
        s_ref[0, 0, 0, p] = total * s_old + mm_tn(phi_k, v)
        return carry

    jax.lax.fori_loop(0, s_ref.shape[3], pair, 0)
    from_start = jnp.exp(c_col)
    for j in range(g):
        rows = slice(j * tb, (j + 1) * tb)
        num = nums[j] + from_start * num_scr[rows, :]
        den = dens[j] + from_start * den_c[rows]
        o_ref[0, :, j * d:(j + 1) * d] = num / den


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def _chunk_pallas(pool, norm_pool, block_ids, fresh, n_valid, q, k, v, c,
                  layer: int, interpret: bool):
    """The kernel's call, a ``jit`` of the module: a model's layers are
    walked, not scanned, and the kernel's body is traced once a layer. ``c
    [S, nb, C, Hkv]``: the log-gate summed inside each token block."""
    n, t, hkv, d = k.shape
    g, nb = q.shape[2] // hkv, c.shape[1]
    pi, pj = feature_pairs(d)
    c = c.transpose(0, 3, 1, 2)[:, :, :, None]      # [S, Hkv, nb, 1, C]
    state = pl.BlockSpec((1, 1, 1, *pool.shape[3:]),
                         lambda s, h, i, ids, *_: (layer, ids[s], h, 0, 0, 0))
    norm = pl.BlockSpec((1, 1, 1, d, d),
                        lambda s, h, i, ids, *_: (layer, ids[s], h, 0, 0))
    rows_q = pl.BlockSpec((1, TOKENS, g * d), lambda s, h, i, *_: (s, i, h))
    rows_k = pl.BlockSpec((1, TOKENS, d), lambda s, h, i, *_: (s, i, h))
    o, pool, norm_pool = pl.pallas_call(
        functools.partial(_chunk_kernel, g=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n, hkv, nb),
            in_specs=[rows_q, rows_k, rows_k,
                      pl.BlockSpec((1, 1, 1, 1, TOKENS),
                                   lambda s, h, i, *_: (s, h, i, 0, 0)),
                      state, norm],
            out_specs=[rows_q, state, norm],
            scratch_shapes=[pltpu.VMEM((g * TOKENS, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((n, t, hkv * g * d), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct(norm_pool.shape, norm_pool.dtype)],
        # the pools, after the five scalar operands and q, k, v, c
        input_output_aliases={9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=CHUNK_VMEM_BYTES),
        interpret=interpret,
        name="retention_chunk",
    )(block_ids.astype(jnp.int32), fresh.astype(jnp.int32),
      n_valid.astype(jnp.int32), jnp.asarray(pi), jnp.asarray(pj),
      q.reshape(n, t, -1), k.reshape(n, t, -1), v.reshape(n, t, -1), c,
      pool, norm_pool)
    return o.reshape(n, t, hkv * g, d), pool, norm_pool


def retention_chunk(pool, norm_pool, block_ids, layer: int, q, k, v,
                    log_gamma, fresh=None, n_valid=None, *,
                    impl: str = "auto", interpret: Optional[bool] = None):
    """T tokens a sequence against its block of the pools
    (:func:`retention_step`'s, float32): ``q [S, T, Hq, d]``, ``k, v [S, T,
    Hkv, d]`` (float32, or bfloat16 as the model hands them), ``log_gamma [S,
    T, Hkv]``, ``fresh [S]`` true where the sequence starts with this chunk,
    ``n_valid [S]`` the real tokens of each (default T). Returns ``(o [S, T,
    Hq, d] float32, pool, norm_pool)``; rows past ``n_valid`` of ``o`` mean
    nothing."""
    _check_pools(pool, norm_pool)
    n, t, hkv, d = k.shape
    if fresh is None:
        fresh = jnp.zeros((n,), bool)
    if n_valid is None:
        n_valid = jnp.full((n,), t, jnp.int32)
    if q.dtype != k.dtype or q.dtype not in (jnp.float32, jnp.bfloat16):
        q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    with jax.named_scope("retention_chunk"):
        real = jnp.arange(t)[None, :] < n_valid[:, None]            # [S, T]
        log_gamma = jnp.where(real[..., None],
                              log_gamma.astype(jnp.float32), 0.0)
        pad = -t % TOKENS
        if pad:     # whole blocks: a padded token is not a real one
            q, k, v, log_gamma = (
                jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                for x in (q, k, v, log_gamma))
        nb = (t + pad) // TOKENS
        # the log-gate summed inside each block, from its start
        c = jnp.cumsum(log_gamma.reshape(n, nb, TOKENS, hkv), axis=2)
        if _resolve_impl(impl, "retention_chunk") == "pallas":
            o, pool, norm_pool = _chunk_pallas(
                pool, norm_pool, block_ids, fresh, n_valid, q, k, v, c,
                layer=layer, interpret=resolve_interpret(interpret))
            return o[:, :t], pool, norm_pool

        def blocks(x):
            return x.astype(jnp.float32).reshape(n, nb, TOKENS, *x.shape[2:])

        keep = jnp.logical_not(fresh)
        s0 = jnp.where(keep[:, None, None, None, None],
                       pool[layer, block_ids], 0.0)
        z0 = jnp.where(keep[:, None, None, None],
                       norm_pool[layer, block_ids], 0.0)
        weight = jnp.pad(real, ((0, 0), (0, pad))).astype(jnp.float32)
        o, s, z = _chunk_xla(s0, z0, blocks(_group(q, hkv)), blocks(k),
                             blocks(v), c, weight.reshape(n, nb, TOKENS))
        o = o.reshape(n, nb * TOKENS, -1, d)[:, :t]
        return (o, pool.at[layer, block_ids].set(s),
                norm_pool.at[layer, block_ids].set(z))
