"""Flash attention as a Pallas TPU kernel (forward + custom-VJP backward).

This is the TPU-native replacement for the reference's external ``flash-attn``
CUDA wheel (``05-training-llama-405b/train_llm.py:93``, install note
``README.md:57``) — the one component the reference cannot express in Python
and the SURVEY.md build plan's deliberate custom-kernel deliverable.

Design (standard blockwise online-softmax, laid out for the MXU/VMEM):

- q, k, v, o, do and the three gradients are read and written where the
  model holds them: ``[B, S, H, D]`` viewed ``[B, S, H*D]``, a ``[block, D]``
  tile being the column block of its head (``_Operands``; every entry,
  ``_flash_fwd`` and ``flash_bwd_with_stats`` included, takes and returns
  ``[B, S, H, D]``, and nothing is transposed on the way in or out). A
  128-lane column block of an (8, 128)-tiled array is whole tiles, so the
  DMA strides over tiles; a ``head_dim`` that does not fill 128 lanes (64)
  keeps ``[B, H, S, D]`` operands behind two transposes, chosen from the
  shape in that one class. lse and delta are ``[B, H, S]`` float32 (128
  lanes wide on their way through the kernels);
- TPU grids execute sequentially per core, so the online-softmax running
  state (m, l, acc) lives in VMEM scratch carried across the kv tiles of a
  q tile's walk. m and l stay ``[BQ, 128]``,
  one value a row replicated over the lanes, from the scratch to the
  accumulator (``_row_stat``, ``_widen``): no one-lane column is read out of
  them inside the tile loop;
- under a STATIC band (causal, with or without an int window) the grid is
  ``(batch, q-head, live tile)``: the band's live tiles are listed where the
  call is built (``_live_tiles``) and ride in as two scalar-prefetched int32
  operands that the index maps and the kernel read its (iq, ik) from, so a
  dead tile costs no grid step and no DMA. Under a DYNAMIC band (a traced
  per-layer window, the ring's chunk offsets: a [3] int32 SMEM operand) the
  band is not known there: the grid walks (batch, q-head, q-block, kv-block)
  whole and ``pl.when`` skips a dead tile's compute. Every live tile applies
  the element mask: leaving it off interior tiles moved no kernel by 2% on
  the chip (PERF.md section 6, PR 36), so there is one tile body;
- tiles are 512 wide, and 1024 for 2-byte operands at head_dim <= 128 on
  sequences of 4096 and more (``_pick_block``; the callers' ``block_q`` /
  ``block_k`` are ceilings);
- GQA is native: q-head h reads kv-head ``h // (Hq // Hkv)`` through the
  BlockSpec index maps — no materialized ``repeat`` of K/V (the XLA reference
  path in ``attention.py`` groups heads instead);
- scores/softmax accumulate in fp32 regardless of input dtype. The tiles are
  up-cast to fp32 as they are loaded; Mosaic feeds the MXU one bf16 pass of
  an fp32 operand at default precision, so on the chip bf16 tiles as stored
  and p / ds rounded to bf16 give the same bits and the same time within
  1.5% (PR 36): the up-cast stays, and the interpreter computes what the
  text says;
- backward recomputes attention blockwise (flash-bwd): a dq kernel with the
  forward's walk, and a dk/dv kernel walking (batch, kv-head, kv-block,
  q-block, group) — or (batch, kv-head, live tile, group) — that also
  reduces over the GQA group on-chip. The residuals are the raw q, k, v,
  the output (tagged ``flash_out``) and the logsumexp (``flash_lse``);
  ``delta = rowsum(dO * O)`` is taken in XLA (``row_dots``) — activation
  memory is O(B*H*S), not O(B*H*S^2).

``interpret=True`` runs the same kernels on CPU (used by the test suite's
numerics goldens against the XLA reference implementation).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import note_attention, note_choice, resolve_interpret

_VMEM = pltpu.VMEM

NEG_INF = -1e30


_LANES = 128
_TILE_CEILING = 1024    # what _pick_block may take where it fits and pays


def _pick_block(s: int, ceiling: int, dtype, head_dim: int, window=None) -> int:
    """The tile edge along a sequence of ``s``: the largest of 1024, 512,
    ... 8 that divides ``s`` and is at most ``ceiling`` (the caller's
    ``block_q`` / ``block_k``). 1024-wide tiles are taken for 2-byte
    operands at head_dim <= 128 (the fp32 score, probability and gradient
    tiles of a 1024 x 1024 tile are 4 MB each: fp32 operands at head_dim 256
    do not fit the kernels' VMEM there), for walks of at least four tiles a
    side (at seq 2048 two 1024-wide diagonal tiles of three waste the MXU
    work that the fewer grid steps save) and, under a static window, for
    bands at least as wide (a 1024-token band is three live 512-wide tiles
    a row, or two of 1024: a third more work). Measured: PERF.md section 6,
    PR 36."""
    if (jnp.dtype(dtype).itemsize > 2 or head_dim > 128 or s < 4 * 1024
            or (isinstance(window, int) and window < 4 * 1024)):
        ceiling = min(ceiling, 512)
    for cand in (1024, 512, 256, 128, 64, 32, 16, 8):
        if cand <= min(ceiling, s) and s % cand == 0:
            return cand
    return s


# ---------------------------------------------------------------------------
# the band, tile by tile
# ---------------------------------------------------------------------------

def _band_tile(causal, window, iq, ik, block_q, block_k, q_off=0, k_off=0):
    """``(live, interior)`` of the (iq, ik) tile under the causal /
    sliding-window band. A tile is LIVE when some (q_pos, k_pos) pair in it
    is inside the band and INTERIOR when every pair is (the element mask
    masks nothing there); a live tile that is not interior is an EDGE tile,
    the rest are DEAD. The band is ``0 <= q_pos - k_pos`` (``< window``),
    and over a tile that difference takes every value from ``q_lo - k_hi``
    to ``q_hi - k_lo``. A dead tile's compute is skipped — this is where
    SWA's speedup comes from (work is O(S*W) not O(S^2) once S >> window).
    ``window``/``q_off``/``k_off`` may be traced scalars (per-layer window
    schedules, ring chunk offsets): both predicates are scalar arithmetic
    on the grid position, which is runtime-valued anyway."""
    if not causal:
        return True, True
    q_lo = iq * block_q + q_off
    k_lo = ik * block_k + k_off
    most = q_lo + block_q - 1 - k_lo       # newest query over oldest key
    least = q_lo - (k_lo + block_k - 1)    # oldest query over newest key
    live, interior = most >= 0, least >= 0
    if window is not None:
        live &= least < window
        interior &= most < window
    return live, interior


def _band_grid(causal, window, nq, nk, block_q, block_k):
    """``_band_tile`` over a whole static band: ``(live, interior)`` as
    ``[nq, nk]`` bool arrays, on the host."""
    return tuple(np.broadcast_to(x, (nq, nk)) for x in _band_tile(
        causal, window, np.arange(nq)[:, None], np.arange(nk)[None, :],
        block_q, block_k))


def tile_counts(causal, window, sq, sk, block_q, block_k):
    """``(interior, edge, dead)`` tiles of one (batch row, head) walk under a
    static band: arithmetic on the shapes, for the line a call leaves."""
    live, interior = _band_grid(causal, window, sq // block_q, sk // block_k,
                                block_q, block_k)
    return (int((live & interior).sum()), int((live & ~interior).sum()),
            int((~live).sum()))


def describe_walk(q, k, causal, window, block_q=_TILE_CEILING,
                  block_k=_TILE_CEILING, seq_major=True) -> str:
    """What ``note_attention`` says of a flash call on ``[B, S, H, D]``
    operands: the layout the kernels address them in (``_Operands``), the
    tiles chosen and, under a static band, how many of a walk's tiles are
    interior, edge and dead (a traced band is walked whole and decides tile
    by tile at run time)."""
    block_q = _pick_block(q.shape[1], block_q, q.dtype, q.shape[-1], window)
    block_k = _pick_block(k.shape[1], block_k, k.dtype, q.shape[-1], window)
    tiles = f"tiles {block_q}x{block_k}"
    operands = f"{_Operands(q.shape[-1], seq_major)} operands"
    if not (window is None or isinstance(window, int)):
        return (f"{tiles}, traced window: the whole grid is walked; "
                f"{operands}")
    interior, edge, dead = tile_counts(causal, window, q.shape[1], k.shape[1],
                                       block_q, block_k)
    return (f"{tiles}, a walk: {interior} interior / {edge} edge / {dead} "
            f"dead; {operands}")


def _live_tiles(causal, window, nq, nk, block_q, block_k, by_rows: bool):
    """A static band's live tiles in walk order, as the two int32 operands
    the kernels' grids are prefetched from: ``(iq, ik)`` by q row then kv
    tile (``by_rows``: ``flash_fwd``, ``flash_dq``) or ``(ik, iq)`` by kv
    column then q tile (``flash_dkv``). A row (column) with no live tile
    keeps one dead entry, so its output block is still opened, zeroed and
    written."""
    live = _band_grid(causal, window, nq, nk, block_q, block_k)[0]
    live = (live if by_rows else live.T).copy()
    live[~live.any(axis=1), 0] = True
    outer, inner = np.nonzero(live)
    return outer.astype(np.int32), inner.astype(np.int32)


def _take_tiles(refs, tiled):
    """``(tiles, the other refs)``: a static band's kernels get the two
    prefetched live-tile operands first."""
    return (refs[:2], refs[2:]) if tiled else (None, refs)


def _walk(tiles, n_inner):
    """``(outer, inner, first, last)`` of this grid step: its tile, and
    whether the step opens / closes the walk of its outer index (a q row
    for ``flash_fwd`` / ``flash_dq``, a kv column for ``flash_dkv``).
    ``tiles`` are the prefetched live-tile operands of a static band, whose
    grid is ``(..., tile)``; None for the whole ``(..., outer, inner)``
    grid a dynamic band walks."""
    if tiles is None:
        outer, inner = pl.program_id(2), pl.program_id(3)
        return outer, inner, inner == 0, inner == n_inner - 1
    outer_ref, inner_ref = tiles
    t, n = pl.program_id(2), outer_ref.shape[0]
    outer = outer_ref[t]
    first = (t == 0) | (outer_ref[jnp.maximum(t - 1, 0)] != outer)
    last = (t == n - 1) | (outer_ref[jnp.minimum(t + 1, n - 1)] != outer)
    return outer, inner_ref[t], first, last


def _band_mask(causal, window, iq, ik, block_q, block_k, shape,
               q_off=0, k_off=0):
    """Element mask for a live tile (None = nothing masked)."""
    if not causal:
        return None
    q_pos = iq * block_q + q_off + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = ik * block_k + k_off + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    return mask


def _unpack_band(band_ref, window):
    """Kernel-side band parameters: (window, q_off, k_off).

    ``band_ref`` is the optional [3] int32 SMEM operand carrying a DYNAMIC
    band — [window, q_offset, k_offset] — used for traced per-layer windows
    (Gemma-2's alternating schedule rides a lax.scan) and the ring's global
    chunk offsets. When absent, ``window`` is the static compile-time int
    (or None = no band) and offsets are zero, exactly the pre-dynamic
    behavior."""
    if band_ref is not None:
        return band_ref[0], band_ref[1], band_ref[2]
    return window, 0, 0


def _softcap_fwd(s, softcap):
    """tanh logit capping (Gemma-2): cap * tanh(s / cap), scores-side."""
    return jnp.tanh(s / softcap) * softcap


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _widen(stat, n):
    """A lane-replicated ``[rows, 128]`` statistic at ``n`` lanes."""
    reps = -(-n // _LANES)
    if reps > 1:
        stat = jnp.tile(stat, (1, reps))
    return stat if n == reps * _LANES else stat[:, :n]


def _row_stat(x, fold, reduce):
    """``reduce`` (max or sum) over each row of ``x``, replicated over 128
    lanes: the row's lane groups ``fold`` into one elementwise, and one
    reduction crosses the lanes."""
    n = x.shape[1]
    if n > _LANES and n % _LANES == 0:
        x = functools.reduce(fold, [x[:, i:i + _LANES]
                                    for i in range(0, n, _LANES)])
    return jnp.broadcast_to(reduce(x, axis=1, keepdims=True),
                            (x.shape[0], _LANES))


def _fwd_kernel(*refs, scale, softcap, causal, window, banded, tiled, block_q,
                block_k, num_kv_blocks):
    tiles, refs = _take_tiles(refs, tiled)
    if banded:  # inputs carry the trailing dynamic [3] band operand
        q_ref, k_ref, v_ref, band_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        band_ref = None
    iq, ik, first, last = _walk(tiles, num_kv_blocks)
    window, q_off, k_off = _unpack_band(band_ref, window)
    d = acc_scr.shape[1]

    @pl.when(first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # band: kv block fully outside the causal/window band -> skip all compute
    live, _ = _band_tile(causal, window, iq, ik, block_q, block_k, q_off, k_off)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)           # [BQ, D]
        k = k_ref[...].astype(jnp.float32)           # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:  # Gemma-2: tanh cap BEFORE the mask
            s = _softcap_fwd(s, softcap)
        mask = _band_mask(causal, window, iq, ik, block_q, block_k, s.shape,
                          q_off, k_off)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        # the running statistics stay [BQ, 128], one value a row replicated
        # over the lanes, from the scratch through to the accumulator: a
        # [BQ, 1] column read out of them costs a lane broadcast of 64 vregs
        # wherever it meets a tile (half the kernel's time at 512-wide
        # tiles: PERF.md section 6, PR 36)
        m_prev, l_prev = m_scr[:], l_scr[:]
        m_new = jnp.maximum(m_prev, _row_stat(s, jnp.maximum, jnp.max))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _widen(m_new, block_k))       # [BQ, BK]
        if window is not None and mask is not None:
            # a live SWA tile can hold FULLY-masked q rows (window's lower
            # edge crosses the tile): there m_new == NEG_INF and
            # exp(s - m_new) == exp(0) == 1 — zero those lanes explicitly.
            # (Pure causal never hits this: every row's walk opens on a
            # tile that holds one of its keys.)
            p = jnp.where(mask, p, 0.0)
        v = v_ref[...].astype(jnp.float32)           # [BK, D]
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        l_scr[:] = alpha * l_prev + _row_stat(p, jnp.add, jnp.sum)
        m_scr[:] = m_new
        acc_scr[:] = acc_scr[:] * _widen(alpha, d) + pv

    @pl.when(last)
    def _finalize():
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[:] / _widen(safe_l, d)).astype(o_ref.dtype)
        lse_ref[...] = m_scr[:] + jnp.log(safe_l)


def check_static_window(window):
    """A static ``window < 1`` masks EVERY score: the kernel's safe_l path
    (and the xla softmax) would return all-zero attention with no error —
    silently-dead attention. Raise instead, at every entry point. Traced
    windows can't be checked here; their sanctioned producer
    (``_layer_window_column``) validates its config inputs."""
    if isinstance(window, int) and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")


def _pack_band(window, q_off=0, k_off=0):
    """The kernels' [window, q_offset, k_offset] int32 band operand (SMEM).
    This layout — and the 2**30 "full attention" encoding packed for a None
    window — is the one dynamic-band contract shared by _resolve_band, the
    sharded wrapper's per-call override, and the ring's chunk-offset pairs."""
    return jnp.stack([jnp.asarray(2 ** 30 if window is None else window),
                      jnp.asarray(q_off),
                      jnp.asarray(k_off)]).astype(jnp.int32)


def _resolve_band(window):
    """Split a caller's window into the kernels' static ``window`` +
    optional dynamic [3] int32 band operand ([window, q_offset, k_offset];
    offsets zero here — the ring packs nonzero chunk offsets directly).

    Static path (window None or a Python int): no operand — the band is
    baked into the kernel and into the list of live tiles its grid walks.
    Dynamic path (traced window): the band rides a tiny SMEM operand. A
    traced window of 2**30 (= "full attention this layer",
    _layer_window_column's encoding of 0) is wider than any supported
    sequence, so the banded program degenerates to plain causal numerics."""
    if window is None or isinstance(window, int):
        return window, None
    return None, _pack_band(window)


def _band_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


class _Operands:
    """How the three kernels address q, k, v, o, do and the three gradients,
    which every caller holds as ``[B, S, H, D]``; the one place that chooses.

    SEQ-MAJOR, where ``head_dim`` fills whole 128-lane tiles: the array as
    it lies, viewed ``[B, S, H*D]`` (a reshape that moves nothing), with head
    ``h``'s row tile ``i`` the ``(block, D)`` block at column block ``h``.
    That block is whole (8, 128) tiles of the array, so its DMA strides over
    tiles, not rows, and no relayout stands before or behind a kernel.
    HEAD-MAJOR otherwise: the call pays the transposes to ``[B, H, S, D]``
    and back that every call paid until PR 46. That is ``head_dim`` 64 (a
    64-lane column block is not a block the chip takes), and the sharded
    wrapper's maps, which ask for it (``seq_major=False``): what the
    compiler makes of the view is the MODEL's to decide, not the kernels'.
    A per-head reduction between projection and rope (Qwen3's QK-norm) lets
    the projection's own layout run through norm and rope up to one bf16
    copy in front of the kernel; without one (OLMo-2's flat norm, Laguna)
    rope is laid out anew around the view and the four-chip cell's step
    read 1.2% slower (PERF.md section 6, PR 46)."""

    def __init__(self, head_dim: int, seq_major: bool = True):
        self.d = head_dim
        self.seq_major = seq_major and head_dim % _LANES == 0

    def __str__(self):
        if self.seq_major:
            return "seq-major"
        return ("head-major" if self.d % _LANES == 0
                else f"head-major (head_dim {self.d})")

    def shape(self, b, s, h):
        return (b, s, h * self.d) if self.seq_major else (b, h, s, self.d)

    def to_kernel(self, *xs):
        if self.seq_major:
            return tuple(x.reshape(*x.shape[:2], -1) for x in xs)
        return tuple(x.transpose(0, 2, 1, 3) for x in xs)

    def from_kernel(self, *xs):
        if self.seq_major:
            return tuple(x.reshape(*x.shape[:2], -1, self.d) for x in xs)
        return tuple(x.transpose(0, 2, 1, 3) for x in xs)

    def spec(self, block, index_map):
        """The ``[block, D]`` tile that ``index_map`` names as (batch row,
        head, row tile)."""
        if self.seq_major:
            def where(*a):
                b, h, i = index_map(*a)
                return b, i, h
            return pl.BlockSpec((None, block, self.d), where,
                                memory_space=_VMEM)
        return pl.BlockSpec((None, None, block, self.d),
                            lambda *a: (*index_map(*a), 0),
                            memory_space=_VMEM)


def _stat_spec(block_q, index_map):
    """lse's and delta's ``[block_q, 128]`` tile of ``[B, H, S, 128]``."""
    return pl.BlockSpec((None, None, block_q, _LANES),
                        lambda *a: (*index_map(*a), 0), memory_space=_VMEM)


def _plan(q, k, causal, window, block_q, block_k, band, seq_major=True):
    """What the three pallas_calls share, from ``[B, S, H, D]`` operands:
    the tiles chosen, the static window and dynamic band operand, whether
    the band is static, in which case the grids hold its live tiles alone
    (``_live_tiles``): a dead tile then costs no grid step and no DMA, and
    how the kernels address the operands (``_Operands``). A dynamic band
    (traced window, ring offsets) is not known where the grid is built, so
    its kernels walk every tile and skip the dead ones' compute; without a
    band every tile is live."""
    d = q.shape[-1]
    if band is None:
        window, band = _resolve_band(window)
    else:
        window = None  # caller-packed dynamic band (the custom_vjp/ring path)
    block_q = _pick_block(q.shape[1], block_q, q.dtype, d, window)
    block_k = _pick_block(k.shape[1], block_k, k.dtype, d, window)
    return (block_q, block_k, window, band, causal and band is None,
            _Operands(d, seq_major))


def _row_walk(tiled, causal, window, b, hq, groups, nq, nk, block_q, block_k):
    """``flash_fwd``'s and ``flash_dq``'s walk by q row: the prefetched
    operands, the grid, and the q-side and kv-side index maps as (batch row,
    head, row tile): q-head h reads kv-head ``h // groups``."""
    if tiled:
        tiles = _live_tiles(causal, window, nq, nk, block_q, block_k, True)

        def q_map(b_, h, t, iq_ref, ik_ref):
            return b_, h, iq_ref[t]

        def kv_map(b_, h, t, iq_ref, ik_ref):
            return b_, h // groups, ik_ref[t]

        return tiles, (b, hq, len(tiles[0])), q_map, kv_map

    def q_map(b_, h, iq, ik):
        return b_, h, iq

    def kv_map(b_, h, iq, ik):
        return b_, h // groups, ik

    return (), (b, hq, nq, nk), q_map, kv_map


def _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret,
               scale=None, softcap=None, band=None, seq_major=True):
    """``[B, S, H, D]`` q, k, v -> (o ``[B, S, Hq, D]``, lse ``[B, Hq, S]``
    float32). ``seq_major=False`` keeps head-major operands whatever the
    ``head_dim`` (``_Operands``)."""
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    groups = hq // hkv
    block_q, block_k, window, band, tiled, ops = _plan(
        q, k, causal, window, block_q, block_k, band, seq_major)
    nq, nk = sq // block_q, sk // block_k
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    tiles, grid, q_map, kv_map = _row_walk(tiled, causal, window, b, hq, groups,
                                           nq, nk, block_q, block_k)
    q_spec, kv_spec = ops.spec(block_q, q_map), ops.spec(block_k, kv_map)
    in_specs = [q_spec, kv_spec, kv_spec]
    args = list(ops.to_kernel(q, k, v))
    if band is not None:
        in_specs.append(_band_spec())
        args.append(band)
    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, softcap=softcap, causal=causal,
            window=window, banded=band is not None, tiled=tiled,
            block_q=block_q, block_k=block_k, num_kv_blocks=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tiles),
            grid=grid,
            in_specs=in_specs,
            out_specs=(q_spec, _stat_spec(block_q, q_map)),
            scratch_shapes=[
                _VMEM((block_q, 128), jnp.float32),
                _VMEM((block_q, 128), jnp.float32),
                _VMEM((block_q, d), jnp.float32),
            ]),
        out_shape=(
            jax.ShapeDtypeStruct(ops.shape(b, sq, hq), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 128), jnp.float32),  # lane-padded
        ),
        interpret=interpret,
        name="flash_fwd",
    )(*tiles, *args)
    return ops.from_kernel(o)[0], lse[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_scores(q, k, lse, scale, softcap, mask):
    """Shared bwd-side score recompute: (p, softcap_grad) where ``p`` is the
    softmax probability rebuilt from the GLOBAL lse and ``softcap_grad`` the
    tanh chain factor (1 - tanh^2), None without capping. Masked lanes need
    no explicit zeroing: lse is finite (every causal row keeps its own key
    in-window), so exp(NEG_INF - lse) underflows to exactly 0."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    cap_grad = None
    if softcap is not None:
        t = jnp.tanh(s / softcap)
        s = t * softcap
        cap_grad = 1.0 - t * t   # d(cap*tanh(u/cap))/du, threaded into ds
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    return jnp.exp(s - lse), cap_grad


def _dq_kernel(*refs, scale, softcap, causal, window, banded, tiled, block_q,
               block_k, num_kv_blocks):
    tiles, refs = _take_tiles(refs, tiled)
    if banded:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, band_ref,
         dq_ref, dq_scr) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
        band_ref = None
    iq, ik, first, last = _walk(tiles, num_kv_blocks)
    window, q_off, k_off = _unpack_band(band_ref, window)

    @pl.when(first)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live, _ = _band_tile(causal, window, iq, ik, block_q, block_k, q_off, k_off)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[:, 0:1]
        delta = delta_ref[:, 0:1]

        mask = _band_mask(causal, window, iq, ik, block_q, block_k,
                          (block_q, block_k), q_off, k_off)
        p, cap_grad = _bwd_scores(q, k, lse, scale, softcap, mask)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        if cap_grad is not None:   # tanh backward: ds flows through the cap
            ds = ds * cap_grad
        ds = ds * scale
        dq_scr[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finalize():
        dq_ref[...] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, softcap, causal, window, banded, tiled, block_q,
                block_k, num_q_blocks, groups):
    # grid (b, hkv, ik, iq, ig), or (b, hkv, tile, ig) over a static band's
    # live tiles: the kv-block ik is OUTER to the (q-block, group member)
    # accumulation dims, so the scratch is initialized exactly when a new
    # dk/dv output block is first visited and flushed when last visited.
    tiles, refs = _take_tiles(refs, tiled)
    if banded:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, band_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        band_ref = None
    ik, iq, first, last = _walk(tiles, num_q_blocks)
    ig = pl.program_id(3 if tiled else 4)   # GQA group member
    window, q_off, k_off = _unpack_band(band_ref, window)

    @pl.when(first & (ig == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    live, _ = _band_tile(causal, window, iq, ik, block_q, block_k, q_off, k_off)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)                    # [BQ, D]
        k = k_ref[...].astype(jnp.float32)                    # [BK, D]
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[:, 0:1]
        delta = delta_ref[:, 0:1]

        mask = _band_mask(causal, window, iq, ik, block_q, block_k,
                          (block_q, block_k), q_off, k_off)
        p, cap_grad = _bwd_scores(q, k, lse, scale, softcap, mask)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                                 # [BQ, BK]
        if cap_grad is not None:
            ds = ds * cap_grad
        ds = ds * scale
        # the two transposed products last, side by side: 2.5-6% of the
        # kernel on the chip against dv's before dp (PERF.md, PR 36)
        dv_scr[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(last & (ig == groups - 1))
    def _finalize():
        dk_ref[...] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[:].astype(dv_ref.dtype)


def flash_bwd_with_stats(q, k, v, do, lse, delta, *, causal, window=None,
                         block_q=512, block_k=512, interpret=False,
                         scale=None, softcap=None, band=None,
                         seq_major=True):
    """Flash backward from caller-supplied softmax stats -> (dq, dk, dv),
    ``[B, S, H, D]`` like q, k, v and do.

    ``lse``/``delta`` ([B, Hq, Sq] fp32) are normally the forward's
    logsumexp and ``rowsum(do * o)``; ring attention passes the *global*
    (cross-chunk) stats here to get each chunk pair's exact gradient
    contribution without rebuilding the full attention matrix.
    ``scale``/``softcap``/``band`` mirror ``_flash_fwd``: the same score
    recompute (including the tanh cap, whose ``(1 - tanh^2)`` factor
    threads through ds) must run in backward for the identity to hold.
    """
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    groups = hq // hkv
    block_q, block_k, window, band, tiled, ops = _plan(
        q, k, causal, window, block_q, block_k, band, seq_major)
    nq, nk = sq // block_q, sk // block_k
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    lse_l = jnp.broadcast_to(lse[..., None], (*lse.shape, 128))
    delta_l = jnp.broadcast_to(delta[..., None], (*delta.shape, 128))
    args = [*ops.to_kernel(q, k, v, do), lse_l, delta_l,
            *([] if band is None else [band])]
    band_specs = [] if band is None else [_band_spec()]
    static = dict(scale=scale, softcap=softcap, causal=causal, window=window,
                  banded=band is not None, tiled=tiled, block_q=block_q,
                  block_k=block_k)

    # dq: flash_fwd's walk
    tiles, grid, q_map, kv_map = _row_walk(tiled, causal, window, b, hq, groups,
                                           nq, nk, block_q, block_k)
    q_spec, kv_spec = ops.spec(block_q, q_map), ops.spec(block_k, kv_map)
    stat_spec = _stat_spec(block_q, q_map)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, num_kv_blocks=nk, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tiles), grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec,
                      *band_specs],
            out_specs=q_spec,
            scratch_shapes=[_VMEM((block_q, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(ops.shape(b, sq, hq), q.dtype),
        interpret=interpret,
        name="flash_dq",
    )(*tiles, *args)

    # dk/dv: walk (b, kv-head, kv-block, q-block, group-member); q-side refs
    # index head = hkv * groups + ig
    if tiled:
        tiles = _live_tiles(causal, window, nq, nk, block_q, block_k, False)
        grid = (b, hkv, len(tiles[0]), groups)

        def q_idx(b_, hkv_, t, ig, ik_ref, iq_ref):
            return b_, hkv_ * groups + ig, iq_ref[t]

        def kv_idx(b_, hkv_, t, ig, ik_ref, iq_ref):
            return b_, hkv_, ik_ref[t]
    else:
        grid = (b, hkv, nk, nq, groups)

        def q_idx(b_, hkv_, ik, iq, ig):
            return b_, hkv_ * groups + ig, iq

        def kv_idx(b_, hkv_, ik, iq, ig):
            return b_, hkv_, ik

    q_spec, kv_spec = ops.spec(block_q, q_idx), ops.spec(block_k, kv_idx)
    stat_spec = _stat_spec(block_q, q_idx)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, num_q_blocks=nq, groups=groups,
                          **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tiles), grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec,
                      *band_specs],
            out_specs=(kv_spec, kv_spec),
            scratch_shapes=[_VMEM((block_k, d), jnp.float32),
                            _VMEM((block_k, d), jnp.float32)]),
        out_shape=(jax.ShapeDtypeStruct(ops.shape(b, sk, hkv), k.dtype),
                   jax.ShapeDtypeStruct(ops.shape(b, sk, hkv), v.dtype)),
        interpret=interpret,
        name="flash_dkv",
    )(*tiles, *args)

    return ops.from_kernel(dq, dk, dv)


def row_dots(do, o):
    """``delta = rowsum(do * o)`` a head: ``[B, S, H, D]`` -> ``[B, H, S]``
    float32. The sums are taken where the rows lie, by a product with the
    ``[H*D, H]`` matrix that says which head a column belongs to (exact: its
    entries are 0 and 1, and ``HIGHEST`` keeps float32 through the MXU). A
    ``sum(axis=-1)`` over the 4-D view writes the float32 product out and
    relayouts it whole before it reduces (2 x 134 MB a layer at the seq2048
    cell's shape, compiled for a v5e)."""
    b, s, h, d = o.shape
    # viewed [B, S, H*D] BEFORE the product, as the kernel wrote o and the
    # output projection's backward wrote do: a 4-D product is laid out anew
    do, o = (x.reshape(b, s, h * d).astype(jnp.float32) for x in (do, o))
    head_of = jnp.repeat(jnp.eye(h, dtype=jnp.float32), d, axis=0)
    return jnp.einsum("bsn,nh->bhs", do * o, head_of,
                      precision=jax.lax.Precision.HIGHEST)


def _flash_bwd(causal, window, block_q, block_k, interpret, scale, softcap,
               residuals, g, seq_major=True):
    q, k, v, o, lse, band = residuals
    do = g
    delta = row_dots(do, o)
    grads = flash_bwd_with_stats(q, k, v, do, lse, delta, causal=causal,
                                 window=window, block_q=block_q,
                                 block_k=block_k, interpret=interpret,
                                 scale=scale, softcap=softcap, band=band,
                                 seq_major=seq_major)
    # the dynamic band is integer-valued: its cotangent type is float0
    dband = (None if band is None
             else np.zeros(band.shape, jax.dtypes.float0))
    return (*grads, dband)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, band, causal, window, block_q, block_k, interpret,
           scale, softcap):
    o, _ = _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret,
                      scale=scale, softcap=softcap, band=band)
    return o


def _flash_vjp_fwd(q, k, v, band, causal, window, block_q, block_k,
                   interpret, scale, softcap):
    o, lse = _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret,
                        scale=scale, softcap=softcap, band=band)
    # checkpoint_name tags let a remat policy keep the kernel's backward
    # residuals (o + lse; q/k/v are cheap projections) so the forward kernel
    # is not re-run inside the backward pass — see train/step.py
    # REMAT_POLICIES["attn"]
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse, band)


_flash.defvjp(_flash_vjp_fwd, _flash_bwd)


def _in_manual_context() -> bool:
    """True when tracing inside a manual shard_map region (the pipeline):
    the attention wrappers must then build their shard_maps against the
    context AbstractMesh and skip their eager-entry jit (the caller's jit
    is already above us, and the eager jit's cache must never mix top-level
    and in-pipeline programs)."""
    m = jax.sharding.get_abstract_mesh()
    return bool(m.axis_names) and any(
        t == jax.sharding.AxisType.Manual for t in m.axis_types)


def resolve_wrapper_mesh(mesh):
    """Mesh an attention wrapper's shard_maps must be built against, resolved
    at TRACE time: inside another manual region (the pp pipeline) the context
    AbstractMesh marks pp/tp Manual and shard_map insists on an exact mesh
    match — nesting works iff the inner maps are built against that context
    mesh (their own manual axes stay the still-auto ones). At top level the
    context mesh is empty and the factory's concrete mesh applies."""
    return jax.sharding.get_abstract_mesh() if _in_manual_context() else mesh


def wrapper_shard_map(mesh, **kwargs):
    """``jax.shard_map`` for an attention wrapper whose body holds Pallas
    calls: manual over EVERY axis of the wrapper mesh that the context has
    not already made manual. The chip's lowering refuses a Mosaic kernel in
    a region where any mesh axis is still auto ("cannot be automatically
    partitioned"), size-1 axes included, so a shard_map manual over only
    the axes that shard attention does not compile there. Axes the specs
    do not name are replicated, which is what they were under GSPMD."""
    wmesh = resolve_wrapper_mesh(mesh)
    names = (frozenset(wmesh.axis_names)
             - frozenset(getattr(wmesh, "manual_axes", ())))
    return functools.partial(jax.shard_map, mesh=wmesh, axis_names=names,
                             check_vma=False, **kwargs)


def resolve_attention_manual_axes(mesh, batch_axes, head_axis):
    """Shared preamble for the manual-axes attention wrappers (this module's
    sharded flash, ``ring_attention``, and the Ulysses wrapper): keep only
    mesh axes of size > 1, and return (batch_axes, head_axis, tp, batch_div,
    b_spec, manual_set). ``head_axis`` may be one axis name or a tuple of
    names (Ulysses shards heads over ('tp', 'cp')); the normalized form is a
    tuple or None, and ``tp`` is the product of the head-axis sizes."""
    batch_axes = tuple(a for a in batch_axes
                       if a in mesh.shape and mesh.shape[a] > 1)
    if isinstance(head_axis, str):
        head_axis = (head_axis,)
    head_axis = tuple(a for a in (head_axis or ())
                      if mesh.shape.get(a, 1) > 1) or None
    tp = 1
    for a in head_axis or ():
        tp *= mesh.shape[a]
    batch_div = 1
    for a in batch_axes:
        batch_div *= mesh.shape[a]
    b_spec = batch_axes if batch_axes else None
    manual = set(batch_axes) | set(head_axis or ())
    return batch_axes, head_axis, tp, batch_div, b_spec, manual


def attention_divisibility_error(batch_axes, head_axis, tp, batch_div,
                                 hq, hkv, batch, kind):
    """Error text naming only the dimension(s) that actually failed."""
    problems = []
    if head_axis and (hq % tp or hkv % tp):
        problems.append(f"heads {hq}/{hkv} not divisible by "
                        f"{'x'.join(head_axis)}={tp}")
    if batch_axes and batch % batch_div:
        problems.append(f"batch {batch} not divisible by "
                        f"{'x'.join(batch_axes)}={batch_div}")
    return (f"{kind} shards attention over manual mesh axes (the Pallas "
            f"kernels cannot be auto-partitioned): "
            f"{'; '.join(problems)} — pad, or drop the unused mesh axis")


_UNSET = object()   # per-call window sentinel: "use the factory default"


def make_sharded_flash_attention(
    mesh,
    *,
    batch_axes=("dp", "fsdp", "ep"),
    head_axis: Optional[str] = "tp",
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = _TILE_CEILING,
    block_k: int = _TILE_CEILING,
    forced: bool = False,
    fallback=None,
    scale: Optional[float] = None,
    logit_softcap: Optional[float] = None,
):
    """Flash attention that PARTITIONS over batch/head mesh axes.

    ``fallback``: attention callable used instead of the plain-xla einsum
    when a shape is ineligible and ``forced`` is False — callers with their
    own sharding discipline (Ulysses) substitute their constraint-based
    path so 'auto' degrades to the RIGHT program, not an unconstrained one.

    The XLA SPMD partitioner cannot shard a Mosaic custom call: a bare
    ``flash_attention`` under a GSPMD mesh compiles, but the partitioner's
    fallback all-gathers q/k/v and runs the FULL kernel on every device
    (output sharding comes back replicated) — mesh_size x wasted attention
    FLOPs on a real pod. This factory returns an attention callable (the
    same contract as ``make_ring_attention``) whose pallas calls run inside
    a shard_map (manual over every mesh axis — ``wrapper_shard_map``) whose
    specs shard attention's data-parallel dims: batch over ``batch_axes``,
    heads over ``head_axis``.
    Attention has no cross-batch or cross-head interaction, so the body
    needs no collectives; the sequence dim stays unsharded (cp>1 uses the
    ring instead).

    Returns None when no relevant axis has size > 1 (single-device meshes:
    the plain kernel path is already optimal). Usable inside the pipeline's
    pp-manual shard_map too: the flash maps are built at trace time against
    the *context* mesh, so inside a manual region they nest as a
    dp/fsdp-manual sub-region over the still-auto data axes (pass
    ``head_axis=None`` there — heads arrive pre-sharded as manual megatron
    shards). Building against the factory's concrete mesh instead would
    fail: the trace context's AbstractMesh marks pp/tp Manual and shard_map
    requires an exact mesh match.

    The custom_vjp sits OUTSIDE the two shard_maps, like the ring's: grad
    cannot transpose through a partial-manual shard_map, so forward and
    backward are each a plain non-differentiated shard_map. Residuals are
    the RAW inputs plus the (checkpoint_name-tagged) primal output and lse
    — nothing residual-only leaves the fwd map, because a shard_map eqn is
    atomic under jax.checkpoint's partial-eval and rebuilding any such
    output would re-run the kernel. The core takes ``[B, S, H, D]`` as
    the maps' specs shard it, so the bwd map is handed the residuals as
    they are: no layout is re-derived outside a map. INSIDE the maps the
    kernels keep head-major operands (``_Operands``, ``seq_major=False``):
    with seq-major ones the four-chip cell lost 1.2% (OLMo-2's flat QK-norm
    leaves the compiler nothing to carry the projection's layout through
    rope with; PERF.md section 6, PR 46).
    """
    from jax.sharding import PartitionSpec as P

    check_static_window(window)
    batch_axes, head_axis, tp, batch_div, b_spec, manual = \
        resolve_attention_manual_axes(mesh, batch_axes, head_axis)
    if not manual:
        return None
    interpret = resolve_interpret(None)
    spec_bshd = P(b_spec, None, head_axis, None)   # q/k/v/o/do [B, S, H, D]
    spec_bhs = P(b_spec, head_axis, None)          # lse        [B, H, S]

    # ONLY the primal output + lse leave the fwd map: a shard_map eqn is
    # atomic under jax.checkpoint's partial-eval, so any residual-only
    # output would force the whole map — pallas call included — to re-run
    # in backward just to rebuild it. The core takes and returns the
    # model's [B, S, H, D], so the residuals ARE the raw inputs + the
    # tagged outputs. The maps' kernels keep head-major operands: seq-major
    # ones cost the four-chip cell 1.2% (``_Operands``).
    head_major = dict(seq_major=False)

    def fwd_body(q, k, v):
        return _flash_fwd(q, k, v, causal, window, block_q, block_k,
                          interpret, scale=scale, softcap=logit_softcap,
                          **head_major)

    def bwd_body(q, k, v, o, lse, do):
        return _flash_bwd(causal, window, block_q, block_k, interpret, scale,
                          logit_softcap, (q, k, v, o, lse, None), do,
                          **head_major)[:3]

    # dynamic-window twins: the per-layer window (Gemma-2's alternating
    # schedule) arrives as a traced scalar per call, packed into the [3]
    # band operand and riding the maps as a replicated arg — the kernels'
    # tile skipping is a runtime predicate either way
    def fwd_body_dyn(band, q, k, v):
        return _flash_fwd(q, k, v, causal, None, block_q, block_k, interpret,
                          scale=scale, softcap=logit_softcap, band=band,
                          **head_major)

    def bwd_body_dyn(band, q, k, v, o, lse, do):
        return _flash_bwd(causal, None, block_q, block_k, interpret, scale,
                          logit_softcap, (q, k, v, o, lse, band), do,
                          **head_major)[:3]

    res_specs = (spec_bshd, spec_bshd, spec_bshd, spec_bshd, spec_bhs)
    band_spec = P(None)   # [3] int32, replicated across every manual axis

    def _maps(dyn=False):
        sm = wrapper_shard_map(mesh)
        if dyn:
            fwd = sm(fwd_body_dyn, in_specs=(band_spec, *(spec_bshd,) * 3),
                     out_specs=(spec_bshd, spec_bhs))
            bwd = sm(bwd_body_dyn,
                     in_specs=(band_spec, *res_specs, spec_bshd),
                     out_specs=(spec_bshd,) * 3)
        else:
            fwd = sm(fwd_body, in_specs=(spec_bshd,) * 3,
                     out_specs=(spec_bshd, spec_bhs))
            bwd = sm(bwd_body, in_specs=(*res_specs, spec_bshd),
                     out_specs=(spec_bshd,) * 3)
        return fwd, bwd

    @jax.custom_vjp
    def sharded_flash(q, k, v):
        return _maps()[0](q, k, v)[0]

    def vjp_fwd(q, k, v):
        out, lse = _maps()[0](q, k, v)
        # same remat tags as the plain path (_flash_vjp_fwd): a
        # REMAT_POLICIES["attn"] policy keeps the attention output + lse so
        # backward never re-runs the forward kernel
        out = checkpoint_name(out, "flash_out")
        lse = checkpoint_name(lse, "flash_lse")
        return out, (q, k, v, out, lse)

    def vjp_bwd(res, do):
        return _maps()[1](*res, do)

    sharded_flash.defvjp(vjp_fwd, vjp_bwd)

    @jax.custom_vjp
    def sharded_flash_dyn(q, k, v, band):
        return _maps(dyn=True)[0](band, q, k, v)[0]

    def vjp_fwd_dyn(q, k, v, band):
        out, lse = _maps(dyn=True)[0](band, q, k, v)
        out = checkpoint_name(out, "flash_out")
        lse = checkpoint_name(lse, "flash_lse")
        return out, (q, k, v, out, lse, band)

    def vjp_bwd_dyn(res, do):
        *res, band = res
        grads = _maps(dyn=True)[1](band, *res, do)
        return (*grads, np.zeros(band.shape, jax.dtypes.float0))

    sharded_flash_dyn.defvjp(vjp_fwd_dyn, vjp_bwd_dyn)
    # partial-manual shard_map resolves auto-axis shardings only under jit,
    # so every top-level call — eager OR traced — goes through this jit.
    # ONLY manual-context callers (the pipeline) bypass it for the raw
    # custom_vjp: this jit's cache must hold concrete-mesh programs
    # exclusively, never a context-mesh trace
    sharded_flash_eager = jax.jit(sharded_flash)
    sharded_flash_dyn_eager = jax.jit(sharded_flash_dyn)

    window_default = window

    def attention(q, k, v, standard_layout: bool = True, window=_UNSET,
                  **kwargs):
        # per-call window (traced per-layer schedules) overrides the
        # factory default; _UNSET keeps the baked-in band
        wcall = window_default if window is _UNSET else window
        if not standard_layout:
            # the callable contract carries no positions, so a correct mask
            # for packed/sharded-seq layouts is unbuildable here — fail loud
            # like the ring does rather than mask with arange silently
            raise ValueError(
                "sharded flash attention assumes the standard contiguous "
                "position layout; for packed sequences or explicit positions "
                "on a sharded mesh use attn_impl='xla'")
        hq, hkv, d = q.shape[2], k.shape[2], q.shape[-1]
        eligible = (causal
                    and hq % tp == 0 and hkv % tp == 0
                    and q.shape[0] % batch_div == 0
                    # tile divisibility binds only on compiled Mosaic; the
                    # interpret path (CPU tests) takes any shape
                    and (interpret or (q.shape[1] % 8 == 0
                                       and k.shape[1] % 8 == 0
                                       and d % 64 == 0)))
        if not eligible:
            if forced:
                raise ValueError(
                    f"sharded flash attention needs causal masking, heads "
                    f"divisible by {'x'.join(head_axis or ())}={tp}, batch "
                    f"divisible by {'x'.join(batch_axes)}={batch_div}, seq "
                    f"divisible by 8 and head_dim by 64; got "
                    f"heads={hq}/{hkv}, batch={q.shape[0]}, "
                    f"seq={q.shape[1]}, head_dim={d} — pad, or use "
                    f"impl='xla'")
            reason = (f"auto: heads={hq}/{hkv}, batch={q.shape[0]}, "
                      f"seq={q.shape[1]}, head_dim={d}, causal={causal} is "
                      f"not a shape the sharded kernel takes")
            note_choice(
                "sharded flash attention",
                "caller's fallback" if fallback is not None else "xla",
                reason)
            note_attention("xla", reason)
            if fallback is not None:
                return fallback(q, k, v, standard_layout=standard_layout,
                                window=wcall, **kwargs)
            from .attention import multihead_attention

            return multihead_attention(q, k, v, causal=causal, window=wcall,
                                       scale=scale,
                                       logit_softcap=logit_softcap,
                                       impl="xla")
        note_attention("flash", ("forced" if forced else
                                 "auto: a shape the sharded kernel takes")
                       + "; " + describe_walk(q, k, causal, wcall, block_q,
                                              block_k, seq_major=False))
        in_manual = _in_manual_context()
        if wcall is window_default or (isinstance(wcall, int)
                                       and wcall == window_default):
            # static band (or none): the factory-baked maps
            if in_manual:  # nested in the pipeline: caller's jit is above us
                return sharded_flash(q, k, v)
            return sharded_flash_eager(q, k, v)
        # per-call override (traced per-layer window, an int differing from
        # the factory default, or None against a windowed factory): pack it
        # into the dynamic-band operand explicitly — _resolve_band would
        # treat a static int as "bake it in", which here would silently
        # replace the requested band with the 2**30 no-band encoding
        check_static_window(wcall)
        band = _pack_band(wcall)
        if in_manual:
            return sharded_flash_dyn(q, k, v, band)
        return sharded_flash_dyn_eager(q, k, v, band)

    attention.accepts_window = True
    return attention


def flash_attention(
    q: jnp.ndarray,   # [B, S, Hq, D]
    k: jnp.ndarray,   # [B, S, Hkv, D]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window=None,
    block_q: int = _TILE_CEILING,
    block_k: int = _TILE_CEILING,
    interpret: Optional[bool] = None,
    scale: Optional[float] = None,
    logit_softcap: Optional[float] = None,
) -> jnp.ndarray:
    """Blockwise fused attention; returns [B, S, Hq, D] in q.dtype.

    ``window``: sliding-window attention (HF ``sliding_window`` semantics —
    query i attends keys j with 0 <= i - j < window). kv tiles fully below
    the band are SKIPPED, so cost is O(S*window) once S >> window — the
    reference inherits the same trick from flash-attn's window_size
    (``05-training-llama-405b/train_llm.py:93``). A TRACED window (Gemma-2's
    per-layer schedule riding a lax.scan) rides a [3] int32 SMEM operand
    instead of the baked constant — tile skipping is a runtime predicate
    either way, so the banded cost model is unchanged.

    ``scale``: score-scale override (Gemma-2 ``query_pre_attn_scalar**-0.5``;
    default head_dim**-0.5). ``logit_softcap``: Gemma-2 tanh capping of the
    scaled scores, with the exact ``(1 - tanh^2)`` term in backward."""
    if window is not None and not causal:
        raise ValueError("window (sliding-window attention) requires causal=True")
    check_static_window(window)
    interpret = resolve_interpret(interpret)
    d = q.shape[-1]
    if not interpret and (q.shape[1] % 8 or k.shape[1] % 8 or d % 64):
        # without a tile-divisible block the kernel would fall back to one
        # full-sequence block — certain VMEM blowup / opaque Mosaic errors on
        # TPU. The "auto" dispatcher (ops/attention.py) guards this; a forced
        # impl="flash" fails loudly instead.
        raise ValueError(
            f"flash_attention needs seq divisible by 8 and head_dim by 64; "
            f"got seq_q={q.shape[1]}, seq_k={k.shape[1]}, head_dim={d} — "
            f"pad the sequence or use impl='xla'")
    static_window, band = _resolve_band(window)
    return _flash(q, k, v, band, causal, static_window, block_q, block_k,
                  interpret, scale, logit_softcap)
