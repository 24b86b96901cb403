"""Grouped (ragged) matmul: the expert-compute primitive of dropless MoE.

``grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G]) -> [M, N]``
multiplies row-block ``g`` of ``lhs`` (rows ``offs[g]:offs[g+1]`` where
``offs = cumsum(group_sizes)``) by ``rhs[g]``. Rows beyond
``sum(group_sizes)`` produce zeros (and receive zero gradient) — callers
exploit that contract for expert-parallel local slices, where a worst-case
static buffer carries a garbage tail.

``group_offset`` (a traced int32 scalar) reads the G matrices out of a
LARGER stack where it lies: ``rhs [Gtot, K, N]``, group ``g`` multiplies by
``rhs[group_offset + g]`` (the serve path's ``[L * E, K, N]`` expert leaf
and ``layer * E``; see :func:`grouped_matmul`).

Three implementations behind one dispatch:

- ``pallas``: a Mosaic kernel in the MegaBlocks spirit (Gale et al.,
  arXiv:2211.15841): the sorted token axis is tiled and the grid iterates a
  precomputed (group, row-tile) *work list* built from the per-expert
  offset/size metadata, so compute visits only tiles a group actually
  intersects — no ``[E, capacity]`` padding FLOPs, no per-group dense pass.
  Differentiable via custom_vjp (d_lhs is another grouped matmul against
  ``rhs`` transposed; d_rhs is the transposed grouped matmul ``tgmm``).
- ``scan``: a ``lax.scan`` over groups (mask the sorted rows to the group's
  contiguous range, dense matmul, accumulate) — O(G) more FLOPs than ideal
  but O(M*(K+N)) *memory*, pure jnp, differentiable. The off-TPU default:
  correctness everywhere without the dense expansion below.
- ``ragged``: ``jax.lax.ragged_dot`` (XLA's native ragged contraction,
  differentiable as-is). NOTE: on backends without a native lowering
  (CPU today) it decomposes to a dense ``[G, M, K]`` broadcast + batched
  dot — O(G*M) transient memory, the very padding blowup dropless dispatch
  exists to remove — which is why it is not the auto fallback.
- ``einsum``: segment-one-hot masked einsum, O(G x) padding FLOPs and
  contraction-order-dependent transients — the numerics cross-check in
  tests.

The Pallas kernels keep the whole K (contraction) dim resident per tile —
fine for transformer hidden/FFN widths (the blocks must fit VMEM); a
K-tiled variant is a follow-up if a model outgrows that.

The work list's geometry follows the call (:func:`gmm_blocks`, static per
compiled shape): the row tile is the mean rows a group up to a power of two
(64 rows, the least, at a decode step's 1-6 rows an expert; 512 at a train
step's thousand), the column tile is what the kernel's own blocks leave room
for at its operands' own widths (512 columns of a bf16 ``[4096, N]`` matrix,
a 4 MB DMA), and a padded work item skips its product. So a memory-bound
call's time is the touched groups' matrices streamed once. The backward
prices its two fp32 kernels for itself (:func:`_bwd_blocks`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import note_choice, resolve_interpret

_HAS_RAGGED_DOT = hasattr(jax.lax, "ragged_dot")


def _resolve_impl(impl: str) -> str:
    if impl == "auto":
        backend = jax.default_backend()
        impl = "pallas" if backend == "tpu" else "scan"
        note_choice("grouped_matmul", impl, f"auto: backend is {backend}")
        return impl
    if impl not in ("pallas", "scan", "ragged", "einsum"):
        raise ValueError(f"unknown grouped_matmul impl {impl!r}; use "
                         f"'auto', 'pallas', 'scan', 'ragged', or 'einsum'")
    if impl == "ragged" and not _HAS_RAGGED_DOT:
        raise ValueError("impl='ragged' needs jax.lax.ragged_dot, which this "
                         "jax build lacks; use 'scan' (or 'auto')")
    return impl


# ---------------------------------------------------------------------------
# XLA fallbacks (autodiff works through both as-is)
# ---------------------------------------------------------------------------

def _gmm_scan(lhs, rhs, group_sizes, out_dtype):
    """scan over groups: mask the sorted rows to the group's contiguous
    range, one dense matmul each, accumulate. O(M*(K+N)) transients — the
    memory-safe XLA formulation (decode's no_drop path compiles through
    this off-TPU, where ``ragged_dot`` would re-materialize the [G, M, K]
    dense expansion)."""
    m = lhs.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    rows = jnp.arange(m, dtype=group_sizes.dtype)

    def body(acc, inp):
        start, end, w = inp
        mask = (rows >= start) & (rows < end)
        masked = jnp.where(mask[:, None], lhs, 0)
        return acc + jnp.dot(masked, w,
                             preferred_element_type=jnp.float32), None

    acc0 = jnp.zeros((m, rhs.shape[2]), jnp.float32)
    out, _ = jax.lax.scan(body, acc0, (starts, ends, rhs))
    return out.astype(out_dtype)


def _gmm_einsum(lhs, rhs, group_sizes, out_dtype):
    """Segment-one-hot masked einsum. O(G) more FLOPs than ideal — the
    correctness fallback, not the fast path."""
    m, g = lhs.shape[0], rhs.shape[0]
    ends = jnp.cumsum(group_sizes)
    # row r belongs to group searchsorted(ends, r, 'right'); tail rows (r >=
    # ends[-1]) resolve to G, whose one_hot row is all-zero -> zero output
    seg = jnp.searchsorted(ends, jnp.arange(m, dtype=group_sizes.dtype),
                           side="right")
    onehot = jax.nn.one_hot(seg, g, dtype=lhs.dtype)
    return jnp.einsum("mk,mg,gkn->mn", lhs, onehot, rhs,
                      preferred_element_type=jnp.float32).astype(out_dtype)


def _tgmm_einsum(lhs, dy, group_sizes, g, out_dtype):
    """Transposed grouped matmul: d_rhs[g] = lhs_g^T @ dy_g -> [G, K, N]."""
    m = lhs.shape[0]
    ends = jnp.cumsum(group_sizes)
    seg = jnp.searchsorted(ends, jnp.arange(m, dtype=group_sizes.dtype),
                           side="right")
    onehot = jax.nn.one_hot(seg, g, dtype=lhs.dtype)
    return jnp.einsum("mg,mk,mn->gkn", onehot, lhs, dy,
                      preferred_element_type=jnp.float32).astype(out_dtype)


# ---------------------------------------------------------------------------
# Pallas kernel (MegaBlocks-style work list over the sorted token axis)
# ---------------------------------------------------------------------------

def work_items(m: int, g: int, bm: int) -> int:
    """The work list's static length ``nw``: every row tile, and a boundary
    item a group (see :func:`_work_list`)."""
    return pl.cdiv(m, bm) + g


def _work_list(group_sizes, m, bm, nw):
    """Static-size (group, row-tile) work list + metadata scalars.

    Groups are contiguous row ranges of the sorted buffer, so the number of
    (group, tile) intersections is at most m_tiles + G (each group spans
    ceil(size/bm) tiles plus at most one boundary tile) — ``nw`` is that
    bound. Padding entries repeat the last real pair (so they trigger no
    DMA and no accumulator init/flush edges) and the kernels skip their
    product (``w >= n_valid``).
    Enumeration is group-major; because groups tile a contiguous axis, the
    emitted row-tile sequence is non-decreasing, which is what lets the
    kernels treat "previous work item had a different tile/group" as the
    accumulator edge."""
    g = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    first_tile = offs[:-1] // bm
    last_tile = jnp.maximum(offs[1:] - 1, offs[:-1]) // bm
    spans = jnp.where(sizes > 0, last_tile - first_tile + 1, 0)
    base = jnp.cumsum(spans)                       # inclusive
    n_valid = base[-1]
    w = jnp.arange(nw, dtype=jnp.int32)
    wg_raw = jnp.searchsorted(base, w, side="right").astype(jnp.int32)
    wg_c = jnp.minimum(wg_raw, g - 1)
    start = base[wg_c] - spans[wg_c]               # exclusive base of group
    wm_raw = first_tile[wg_c] + (w - start)
    valid = w < n_valid
    # padding repeats the last valid (group, tile) pair; all-empty input
    # degenerates to pair (0, 0), whose contribution the valid mask kills
    last = jnp.minimum(jnp.maximum(n_valid - 1, 0), nw - 1)
    wg = jnp.where(valid, wg_c, wg_c[last])
    wm = jnp.where(valid, wm_raw, wm_raw[last])
    return offs, wg, wm, jnp.asarray(n_valid, jnp.int32)[None]


def _gmm_kernel(offs_ref, wg_ref, wm_ref, nvalid_ref, *refs, bm, nw):
    # a fifth scalar (the group offset into a larger stack) is the index
    # map's alone: rhs_ref already is the block it chose
    lhs_ref, rhs_ref, out_ref, acc_ref = refs[-4:]
    w = pl.program_id(1)
    g = wg_ref[w]
    mt = wm_ref[w]
    is_first = jnp.logical_or(w == 0, wm_ref[jnp.maximum(w - 1, 0)] != mt)

    @pl.when(is_first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a padded item repeats the last real pair: no block of it is fetched,
    # and it multiplies nothing
    @pl.when(w < nvalid_ref[0])
    def _():
        # a [bm, 1] column from the start: Mosaic has no 1-D -> 2-D shape cast
        rows = mt * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        member = (rows >= offs_ref[g]) & (rows < offs_ref[g + 1])
        x = jnp.where(member, lhs_ref[...], 0)
        acc_ref[...] += jnp.dot(x, rhs_ref[0],
                                preferred_element_type=jnp.float32)

    is_last = jnp.logical_or(w == nw - 1,
                             wm_ref[jnp.minimum(w + 1, nw - 1)] != mt)

    @pl.when(is_last)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tgmm_kernel(offs_ref, wg_ref, wm_ref, nvalid_ref, lhs_ref, dy_ref,
                 out_ref, acc_ref, *, bm, nw):
    w = pl.program_id(1)
    g = wg_ref[w]
    mt = wm_ref[w]
    is_first = jnp.logical_or(w == 0, wg_ref[jnp.maximum(w - 1, 0)] != g)

    @pl.when(is_first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(w < nvalid_ref[0])      # as in _gmm_kernel: the same list
    def _():
        # a [bm, 1] column from the start: Mosaic has no 1-D -> 2-D shape cast
        rows = mt * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        member = (rows >= offs_ref[g]) & (rows < offs_ref[g + 1])
        x = jnp.where(member, lhs_ref[...], 0)
        acc_ref[...] += jax.lax.dot_general(
            x, dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    is_last = jnp.logical_or(w == nw - 1,
                             wg_ref[jnp.minimum(w + 1, nw - 1)] != g)

    @pl.when(is_last)
    def _():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _pallas_gmm_raw(lhs, rhs, group_sizes, out_dtype, bm, bn, interpret,
                    group_offset=None):
    """``group_offset`` None: ``rhs [G, K, N]``. Else ``rhs [Gtot, K, N]``
    and the offset is one more scalar-prefetched operand, added where
    ``rhs``'s block is chosen and nowhere else: ``offs`` and the work list
    stay group-local."""
    m, k = lhs.shape
    g, n = group_sizes.shape[0], rhs.shape[2]
    n_tiles = pl.cdiv(n, bn)
    nw = work_items(m, g, bm)
    scalars = _work_list(group_sizes, m, bm, nw)   # offs, wg, wm, n_valid
    if group_offset is None:
        def rhs_block(ni, w, offs, wg, wm, nv):
            return wg[w], 0, ni
    else:
        # dynamic_slice's clamp (the XLA impls' contract): a block index
        # outside the stack would be a DMA outside the operand
        base = jnp.clip(group_offset, 0, rhs.shape[0] - g)
        scalars = (*scalars, base[None])

        def rhs_block(ni, w, offs, wg, wm, nv, base):
            return base[0] + wg[w], 0, ni

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(n_tiles, nw),
        in_specs=[
            pl.BlockSpec((bm, k), lambda ni, w, offs, wg, wm, *_: (wm[w], 0)),
            pl.BlockSpec((1, k, bn), rhs_block),
        ],
        out_specs=pl.BlockSpec((bm, bn),
                               lambda ni, w, offs, wg, wm, *_: (wm[w], ni)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, bm=bm, nw=nw),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
        name="gmm",
    )(*scalars, lhs, rhs)
    # row-tiles past the last group are never visited (their memory is
    # whatever the buffer held); the contract says zeros
    total = jnp.sum(group_sizes).astype(jnp.int32)
    return jnp.where(jnp.arange(m, dtype=jnp.int32)[:, None] < total, out, 0)


def _pallas_tgmm_raw(lhs, dy, group_sizes, g, out_dtype, bm, bn, interpret):
    """d_rhs [G, K, N] = per-group lhs_g^T @ dy_g (the 'tgmm')."""
    m, k = lhs.shape
    n = dy.shape[1]
    n_tiles = pl.cdiv(n, bn)
    nw = work_items(m, g, bm)
    offs, wg, wm, n_valid = _work_list(group_sizes, m, bm, nw)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_tiles, nw),
        in_specs=[
            pl.BlockSpec((bm, k), lambda ni, w, offs, wg, wm, nv: (wm[w], 0)),
            pl.BlockSpec((bm, bn), lambda ni, w, offs, wg, wm, nv: (wm[w], ni)),
        ],
        out_specs=pl.BlockSpec((1, k, bn),
                               lambda ni, w, offs, wg, wm, nv: (wg[w], 0, ni)),
        scratch_shapes=[pltpu.VMEM((k, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_tgmm_kernel, bm=bm, nw=nw),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, k, n), out_dtype),
        interpret=interpret,
        name="tgmm",
    )(offs, wg, wm, n_valid, lhs, dy)
    # empty groups own no work item, so their out block is never written
    return jnp.where((group_sizes > 0)[:, None, None], out, 0)


# Bytes of VMEM one kernel instance may plan for: the chip's compiler gives
# a kernel 16 MiB of scoped VMEM by default (v5e), and refuses the program
# when the double-buffered blocks plus the accumulator exceed it.
_VMEM_BUDGET = 12 * 2**20

# The smallest row tile the rule asks for. The MXU holds a 128 x 128 tile of
# the matrix and streams the rows past it, so any row tile up to 128 costs
# the same cycles: a narrower one saves nothing and only lays more groups
# across two tiles (each of which multiplies).
_MIN_ROWS = 64


def _gmm_bytes(bm, bn, k, lhs_b, rhs_b, out_b):
    """``gmm``'s blocks, double-buffered, and its fp32 accumulator."""
    blocks = bm * k * lhs_b + k * bn * rhs_b + bm * bn * out_b
    return 2 * blocks + bm * bn * 4


def _tgmm_bytes(bm, bn, k, lhs_b, dy_b, out_b):
    blocks = bm * k * lhs_b + bm * bn * dy_b + k * bn * out_b
    return 2 * blocks + k * bn * 4


def _halve_to_fit(bm, bn, k, least_rows, footprint):
    """Halve the larger of (bn, bm) until ``footprint(bm, bn)`` fits
    ``_VMEM_BUDGET``. The K dim stays resident, so a K too wide for the
    smallest blocks is an error."""
    while footprint(bm, bn) > _VMEM_BUDGET:
        if bn > 128 and bn >= bm:
            bn //= 2
        elif bm > least_rows:
            bm //= 2
        else:
            raise ValueError(
                f"grouped_matmul impl='pallas' keeps the contraction dim "
                f"resident in VMEM; K={k} does not fit {_VMEM_BUDGET} bytes "
                f"even at {bm}x{bn} blocks — use impl='ragged'")
    return bm, bn


def gmm_blocks(m: int, g: int, k: int, n: int, lhs_dtype, rhs_dtype, *,
               block_rows: int = 512,
               block_cols: int = 512) -> tuple[int, int]:
    """``(bm, bn)`` of one ``grouped_matmul(lhs [m, k], rhs [g, k, n])`` call
    (all the serve path runs): static, a function of the call's own shapes
    and dtypes; ``block_rows`` / ``block_cols`` cap it.

    The ROW tile follows rows-per-group: the mean ``m / g`` up to a power of
    two, no smaller than ``_MIN_ROWS`` (nor larger than ``m``). A decode
    step's expert holds 1-6 rows; row tiles past ``sum(group_sizes)`` are
    never visited, so a tile smaller than the buffer only adds padded items,
    which the kernel skips. The COLUMN tile is what ``gmm``'s own blocks
    leave room for at the operands' own widths (the output priced at the
    accumulator's 4 B): at bf16 and 64 rows, ``[4096, 512]`` twice is 8 MiB,
    one DMA 4 MB.
    """
    lhs_b, rhs_b = jnp.dtype(lhs_dtype).itemsize, jnp.dtype(rhs_dtype).itemsize
    mean_rows = -(-m // g)
    bm = min(max(_MIN_ROWS, 1 << (mean_rows - 1).bit_length()), block_rows, m)
    return _halve_to_fit(
        bm, min(block_cols, n), k, min(16, bm),
        lambda bm, bn: _gmm_bytes(bm, bn, k, lhs_b, rhs_b, 4))


def _bwd_blocks(m: int, k: int, n: int, *, block_rows: int = 512,
                block_cols: int = 512) -> tuple[int, int]:
    """``(bm, bn)`` of the backward of a ``[m, k] x [g, k, n]`` call: its
    ``gmm`` against the transposed matrices and its ``tgmm`` run in fp32 on
    ONE pair of blocks, so both footprints are priced at 4 B with the wider
    of (k, n) resident, from the caps down."""
    wide = max(k, n)
    return _halve_to_fit(
        min(block_rows, m), min(block_cols, n), wide, 128,
        lambda bm, bn: max(_gmm_bytes(bm, bn, wide, 4, 4, 4),
                           _tgmm_bytes(bm, bn, wide, 4, 4, 4)))


def _gmm_forward(lhs, rhs, group_sizes, group_offset, out_dtype, block_rows,
                 block_cols, interpret):
    (m, k), g, n = lhs.shape, group_sizes.shape[0], rhs.shape[2]
    bm, bn = gmm_blocks(m, g, k, n, lhs.dtype, rhs.dtype,
                        block_rows=block_rows, block_cols=block_cols)
    return _pallas_gmm_raw(lhs, rhs, group_sizes, out_dtype, bm, bn,
                           interpret, group_offset)


_pallas_gmm = jax.custom_vjp(_gmm_forward, nondiff_argnums=(4, 5, 6, 7))


def _pallas_gmm_fwd(lhs, rhs, group_sizes, group_offset, *static):
    return (_gmm_forward(lhs, rhs, group_sizes, group_offset, *static),
            (lhs, rhs, group_sizes, group_offset))


def _pallas_gmm_bwd(out_dtype, block_rows, block_cols, interpret, res, dy):
    lhs, stack, group_sizes, group_offset = res
    g = group_sizes.shape[0]
    # with an offset the backward slices its G matrices out and writes
    # their gradient back into zeros of the stack's shape: correct, not
    # fast (no caller differentiates through an offset; training hands over
    # per-layer leaves, whose gradient is per layer)
    rhs = (stack if group_offset is None
           else jax.lax.dynamic_slice_in_dim(stack, group_offset, g))
    # both kernels in fp32, on blocks the backward prices for itself
    (m, k), n = lhs.shape, rhs.shape[2]
    bm, bn = _bwd_blocks(m, k, n, block_rows=block_rows,
                         block_cols=block_cols)
    dy = dy.astype(jnp.float32)
    # d_lhs: the same grouped matmul against rhs^T — rows outside every
    # group get zero gradient (matching their zero primal output)
    dlhs = _pallas_gmm_raw(dy, rhs.astype(jnp.float32).transpose(0, 2, 1),
                           group_sizes, lhs.dtype, bm, bn, interpret)
    drhs = _pallas_tgmm_raw(lhs.astype(jnp.float32), dy, group_sizes,
                            g, rhs.dtype, bm, bn, interpret)
    if group_offset is None:
        return dlhs, drhs, None, None
    return dlhs, jax.lax.dynamic_update_slice_in_dim(
        jnp.zeros_like(stack), drhs, group_offset, 0), None, None


_pallas_gmm.defvjp(_pallas_gmm_fwd, _pallas_gmm_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def grouped_matmul(
    lhs: jnp.ndarray,          # [M, K] rows sorted by group
    rhs: jnp.ndarray,          # [G, K, N] one matrix per group
    group_sizes: jnp.ndarray,  # [G] int, sum <= M
    *,
    group_offset=None,         # int32 scalar: rhs is [Gtot, K, N], see below
    impl: str = "auto",
    block_rows: int = 512,
    block_cols: int = 512,
    interpret: Optional[bool] = None,
    preferred_element_type=None,
) -> jnp.ndarray:
    """Ragged grouped GEMM over a group-sorted row buffer -> [M, N].

    ``impl``: "pallas" (Mosaic work-list kernel; ``interpret=True`` runs it
    off-TPU for tests), "scan" (masked group-scan, O(M) memory), "ragged"
    (``jax.lax.ragged_dot``), "einsum" (masked one-hot), or "auto" (pallas
    on TPU, else scan). Rows at index >= ``sum(group_sizes)`` yield zeros
    and propagate zero gradient.

    ``group_offset`` (None, or a traced int32 scalar): ``rhs`` is a stack
    of ``Gtot >= G`` matrices, read where it lies, and group ``g``
    multiplies by ``rhs[group_offset + g]`` (an offset past ``Gtot - G``
    reads the last G matrices, as ``dynamic_slice`` clamps; it is never
    negative). What rides whole is the
    serve path's ``[L * E, K, N]`` expert leaf with ``layer * E``: the
    Pallas kernel adds the offset to the scalar-prefetched index that
    chooses ``rhs``'s block, and the XLA impls take a ``dynamic_slice``
    that fuses into their dots, so no impl copies the G matrices out first
    (a Pallas call needs a materialised operand: handed a layer sliced out
    of the leaf, it made XLA copy all E matrices of it, every layer of every
    step, before the kernel read the touched ones).
    ``None`` is the call without it (``Gtot == G``), the same program as
    before the argument existed. Gradients are correct with an offset
    (``d_rhs`` is zeros of the stack's shape but for its G matrices) and not
    fast: training hands over per-layer leaves.
    """
    if lhs.ndim != 2 or rhs.ndim != 3 or group_sizes.ndim != 1:
        raise ValueError(f"grouped_matmul expects lhs [M,K], rhs [G,K,N], "
                         f"group_sizes [G]; got {lhs.shape}, {rhs.shape}, "
                         f"{group_sizes.shape}")
    g = group_sizes.shape[0]
    stack_ok = rhs.shape[0] == g if group_offset is None else rhs.shape[0] >= g
    if lhs.shape[1] != rhs.shape[1] or not stack_ok:
        raise ValueError(f"grouped_matmul shape mismatch: lhs {lhs.shape}, "
                         f"rhs {rhs.shape}, group_sizes {group_sizes.shape} "
                         f"(rhs holds G matrices; G or more behind a "
                         f"group_offset)")
    impl = _resolve_impl(impl)
    out_dtype = preferred_element_type or jnp.promote_types(lhs.dtype,
                                                            rhs.dtype)
    group_sizes = group_sizes.astype(jnp.int32)
    if group_offset is not None:
        group_offset = jnp.asarray(group_offset, jnp.int32)
        if group_offset.ndim:
            raise ValueError(f"grouped_matmul: group_offset is one int32 "
                             f"scalar; got shape {group_offset.shape}")
        if impl != "pallas":
            rhs = jax.lax.dynamic_slice_in_dim(rhs, group_offset, g)
    if impl == "scan":
        return _gmm_scan(lhs, rhs, group_sizes, out_dtype)
    if impl == "ragged":
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes,
            preferred_element_type=preferred_element_type)
    if impl == "einsum":
        return _gmm_einsum(lhs, rhs, group_sizes, out_dtype)
    interpret = resolve_interpret(interpret)
    return _pallas_gmm(lhs, rhs, group_sizes, group_offset,
                       jnp.dtype(out_dtype), block_rows, block_cols,
                       interpret)
