"""Mamba-1's selective state-space recurrence (S6, arXiv 2312.00752) as a
recurrent STEP (decode) and a SCAN over a prefill chunk's tokens. A layer
keeps a state ``h`` in ``R^{N x C}`` a sequence (``N`` the state size, 16;
``C`` the channels, 5,120 for Jamba2-3B); a token brings ``x`` and a step
``Delta > 0`` (``C`` each) and ``B``, ``C_`` (``N`` each), and with ``A < 0``
(``[N, C]``) and a skip ``D`` (``C``)

    h_t[n, c] = exp(Delta_t[c] A[n, c]) h_{t-1}[n, c] + Delta_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] h_t[n, c] + D[c] x_t[c]

all of it in float32. The decay is DIAGONAL and input-dependent: there is no
matrix product anywhere in it, it is element-wise work (VPU) and one ``exp``
an element (EUP). The state lies ``[N, C]``, channels on the lanes: ``N`` =
16 as the minor dimension would pad to 128 lanes, eight times the bytes.

:func:`ssm_step` takes the POOL the serve engine keeps the states in
(``[layers, blocks, N, C]`` float32, a block a live sequence,
``serve/kv_pages.py``'s state class) and each slot's block id, and returns
the pool with those blocks updated. On a TPU it is one Pallas kernel
(``name="ssm_step"``), a slot a grid step, that reads the slot's block where
it lies and writes it back there, the pool aliased in and out: no pool-sized
and no slots-sized copy exists, and the step moves each live state once in
and once out, which is all the recurrence requires (memory-bound: about 9
flops an element). A slot with nothing to decode carries block 0, the trash
block, which is read and written like any other and never read by a live
sequence; a slot at position 0 (``fresh``) reads zeros whatever its block
holds. Off a TPU the same function is a gather, the two lines above in
``jnp`` and a scatter.

:func:`ssm_chunk` runs T tokens of each sequence from a state ``h0``, token
by token: exactly the recurrence, no blocked algebra, so no ``exp`` of a
difference exists that could overflow (``Delta A`` reaches -1.6 a step). On
a TPU it is one Pallas kernel (``name="ssm_chunk"``) over the grid
``(sequences, channel blocks, token blocks)``, the token blocks innermost
and in order: a channel block's state ``[N, CHANNELS]`` stays in VMEM from
the chunk's first token to its last (read from ``h0`` once, written once),
and a token's update is a dozen vector operations a vreg of it. ``B`` and
``C_`` run along the state's SUBLANES, so each token's pair arrives already
spread over a lane tile (``[T, N, 128]``, made by XLA outside the kernel:
16 MB a layer at T 1,024) and no transpose happens inside. Off a TPU the
same recurrence is a ``lax.scan`` over tokens.

Tokens past ``n_valid`` (the padded tail of a final chunk) leave the state as
it is: their ``Delta`` is set to 0, a decay of 1 and an input of 0.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import note_choice, resolve_interpret

CHANNELS = 1024     # channels a grid step of the chunk kernel (lanes)
TOKENS = 128        # tokens a grid step of the chunk kernel
LANES = 128
SUBLANES = 8        # tokens the chunk kernel takes from one aligned tile
TRASH_BLOCK = 0     # the block idle slots carry (kv_pages' state class)


def _resolve_impl(impl: str, op: str) -> str:
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"ssm impl must be 'auto', 'pallas' or 'xla', got "
                         f"{impl!r}")
    if impl == "auto":
        backend = jax.default_backend()
        impl = "pallas" if backend == "tpu" else "xla"
        note_choice(op, impl, f"auto: backend is {backend}")
    return impl


def selective_step(h, x, delta, b, c, a, d):
    """The two lines of the module docstring on states ``h [..., N, C]``,
    rows ``x, delta [..., C]``, ``b, c [..., N]``, ``a [N, C]`` and ``d
    [C]``, element by element in float32. Returns ``(y [..., C], h_t)``."""
    h = (jnp.exp(delta[..., None, :] * a) * h
         + (delta * x)[..., None, :] * b[..., :, None])
    return jnp.sum(c[..., :, None] * h, axis=-2) + d * x, h


# ---------------------------------------------------------------------------
# the recurrent step
# ---------------------------------------------------------------------------

def _step_kernel(ids_ref, h_ref, rows_ref, cols_ref, a_ref, d_ref, y_ref,
                 h_out_ref):
    """One slot. ``h_ref [1, 1, N, C]`` is the slot's block of the pool;
    ``rows_ref [1, 2, C]`` its ``x`` and ``Delta`` (they run along the
    lanes); ``cols_ref [1, N, 3]`` its ``B``, ``C_`` and ``keep`` (0 for a
    slot at position 0, else 1) as COLUMNS, a lane broadcast away from the
    state's rows."""
    del ids_ref
    x, delta = rows_ref[0, 0:1, :], rows_ref[0, 1:2, :]
    b, c, keep = (cols_ref[0, :, i:i + 1] for i in range(3))
    h = jnp.where(keep > 0, h_ref[0, 0], 0.0)
    h = jnp.exp(delta * a_ref[...]) * h + (delta * x) * b
    h_out_ref[0, 0] = h
    y_ref[0] = jnp.sum(h * c, axis=0, keepdims=True) + d_ref[...] * x


def _step_pallas(pool, block_ids, layer: int, x, delta, b, c, a, d, fresh,
                 interpret):
    s, ch = x.shape
    n = b.shape[-1]
    rows = jnp.stack([x, delta], axis=1)                        # [S, 2, C]
    keep = jnp.broadcast_to(1.0 - fresh.astype(jnp.float32)[:, None], (s, n))
    cols = jnp.stack([b, c, keep], axis=-1)                     # [S, N, 3]
    state = pl.BlockSpec((1, 1, n, ch), lambda i, ids: (layer, ids[i], 0, 0))
    y, pool = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s,),
            in_specs=[state,
                      pl.BlockSpec((1, 2, ch), lambda i, ids: (i, 0, 0)),
                      pl.BlockSpec((1, n, 3), lambda i, ids: (i, 0, 0)),
                      pl.BlockSpec((n, ch), lambda i, ids: (0, 0)),
                      pl.BlockSpec((1, ch), lambda i, ids: (0, 0))],
            out_specs=[pl.BlockSpec((1, 1, ch), lambda i, ids: (i, 0, 0)),
                       state]),
        out_shape=[jax.ShapeDtypeStruct((s, 1, ch), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={1: 1},    # the pool, after the block ids
        interpret=interpret,
        name="ssm_step",
    )(block_ids.astype(jnp.int32), pool, rows, cols, a, d[None, :])
    return y[:, 0], pool


def ssm_step(pool, block_ids, layer: int, x, delta, b, c, a, d, fresh=None,
             *, impl: str = "auto", interpret: Optional[bool] = None):
    """One token a slot. ``pool [layers, blocks, N, C]``, FLOAT32 and nothing
    narrower (refused by name: ``A = -1`` with ``Delta`` 0.001 remembers a
    thousand steps, and a state rounded to bfloat16 a step forgets what it
    adds); ``block_ids [S]`` each slot's block (idle slots:
    ``TRASH_BLOCK``); ``layer`` the pool's layer (static); ``x, delta [S,
    C]``, ``b, c [S, N]``, ``a [N, C]``, ``d [C]``, any float dtype (the
    recurrence is float32); ``fresh [S]`` true where the slot's sequence
    starts here, which reads zeros for its state. Returns ``(y [S, C]
    float32, pool)`` with the slots' blocks of ``layer`` updated."""
    if pool.dtype != jnp.float32:
        raise TypeError(f"the SSM state pool is float32, got {pool.dtype}: "
                        f"the recurrence is carried in float32 from step to "
                        f"step as published")
    x, delta, b, c, a, d = (v.astype(jnp.float32)
                            for v in (x, delta, b, c, a, d))
    if fresh is None:
        fresh = jnp.zeros(block_ids.shape, bool)
    with jax.named_scope("ssm_step"):
        if _resolve_impl(impl, "ssm_step") == "pallas":
            return _step_pallas(pool, block_ids, layer, x, delta, b, c, a, d,
                                fresh, resolve_interpret(interpret))
        h = jnp.where(fresh[:, None, None], 0.0, pool[layer, block_ids])
        y, h = selective_step(h, x, delta, b, c, a, d)
        return y, pool.at[layer, block_ids].set(h)


# ---------------------------------------------------------------------------
# the scan over a chunk's tokens
# ---------------------------------------------------------------------------

def _chunk_xla(h0, x, delta, b, c, a, d):
    """The recurrence as a ``lax.scan`` over tokens: the off-TPU path, and
    what the kernel is tested against."""
    def one(h, row):
        y, h = selective_step(h, *row, a, d)
        return h, y

    rows = tuple(jnp.moveaxis(v, 1, 0) for v in (x, delta, b, c))
    h_t, y = jax.lax.scan(one, h0, rows)
    return jnp.moveaxis(y, 0, 1), h_t


def _chunk_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, h0_ref, y_ref,
                  ht_ref, h_scr):
    """Token block ``i`` of one channel block of one sequence: ``x_ref,
    dt_ref [1, TOKENS, cb]``; ``b_ref, c_ref [1, TOKENS, N, 128]`` (a
    token's ``B`` / ``C_`` down the sublanes, the same in every lane);
    ``h_scr [N, cb]`` holds the state from the chunk's first token block to
    its last."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        h_scr[...] = h0_ref[0]

    a, d = a_ref[...], d_ref[...]
    wide = a.shape[1] // LANES

    def tile(t8, h):        # SUBLANES tokens out of one aligned tile
        at = pl.ds(pl.multiple_of(t8 * SUBLANES, SUBLANES), SUBLANES)
        x8, dt8 = x_ref[0, at, :], dt_ref[0, at, :]
        dx8 = dt8 * x8
        ys = []
        for j in range(SUBLANES):
            t = t8 * SUBLANES + j
            b = jnp.concatenate([b_ref[0, t]] * wide, axis=1)
            c = jnp.concatenate([c_ref[0, t]] * wide, axis=1)
            h = jnp.exp(dt8[j:j + 1] * a) * h + dx8[j:j + 1] * b
            ys.append(jnp.sum(h * c, axis=0, keepdims=True))
        y_ref[0, at, :] = jnp.concatenate(ys, axis=0) + d * x8
        return h

    h_scr[...] = jax.lax.fori_loop(0, x_ref.shape[1] // SUBLANES, tile,
                                   h_scr[...])

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        ht_ref[0] = h_scr[...]


@functools.partial(jax.jit, static_argnames="interpret")
def _chunk_pallas(h0, x, delta, b, c, a, d, interpret: bool):
    """The kernel's call, a ``jit`` of the module: a model's layers are
    walked, not scanned, and the kernel's body is traced once a program."""
    s, t, ch = x.shape
    n = b.shape[-1]
    cb = CHANNELS if ch % CHANNELS == 0 else ch
    # B and C_ multiply the state's rows: spread each over a lane tile here
    # (XLA), so the kernel loads a token's [N, 128] and transposes nothing
    b, c = (jnp.broadcast_to(v[..., None], (s, t, n, LANES)) for v in (b, c))
    rows = pl.BlockSpec((1, TOKENS, cb), lambda q, j, i: (q, i, j))
    cols = pl.BlockSpec((1, TOKENS, n, LANES), lambda q, j, i: (q, i, 0, 0))
    state = pl.BlockSpec((1, n, cb), lambda q, j, i: (q, 0, j))
    return pl.pallas_call(
        _chunk_kernel,
        grid=(s, ch // cb, t // TOKENS),
        in_specs=[rows, rows, cols, cols,
                  pl.BlockSpec((n, cb), lambda q, j, i: (0, j)),
                  pl.BlockSpec((1, cb), lambda q, j, i: (0, j)), state],
        out_specs=[rows, state],
        out_shape=[jax.ShapeDtypeStruct((s, t, ch), jnp.float32),
                   jax.ShapeDtypeStruct(h0.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, cb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_chunk",
    )(x, delta, b, c, a, d[None, :], h0)


def ssm_chunk(h0, x, delta, b, c, a, d, n_valid=None, *, impl: str = "auto",
              interpret: Optional[bool] = None):
    """T tokens a sequence from state ``h0 [S, N, C]`` (float32): ``x, delta
    [S, T, C]``, ``b, c [S, T, N]``, ``a [N, C]``, ``d [C]``, ``n_valid [S]``
    the real tokens of each (default T). Returns ``(y [S, T, C] float32,
    h_T)``; rows past ``n_valid`` of ``y`` mean nothing."""
    t = x.shape[1]
    x, delta, b, c, a, d = (v.astype(jnp.float32)
                            for v in (x, delta, b, c, a, d))
    with jax.named_scope("ssm_chunk"):
        if n_valid is not None:
            real = jnp.arange(t)[None, :] < n_valid[:, None]        # [S, T]
            delta = jnp.where(real[..., None], delta, 0.0)
        h0 = h0.astype(jnp.float32)
        if _resolve_impl(impl, "ssm_chunk") == "xla":
            return _chunk_xla(h0, x, delta, b, c, a, d)
        pad = -t % TOKENS
        if pad:     # whole token blocks: a padded token's Delta is 0 too
            x, delta, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                              for v in (x, delta, b, c))
        y, h_t = _chunk_pallas(h0, x, delta, b, c, a, d,
                               resolve_interpret(interpret))
        return y[:, :t], h_t
