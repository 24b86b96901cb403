"""Paged flash attention over block tables: ONE Pallas kernel family for
every serve-plane forward — decode (T=1), speculative verify (T=k+1),
and chunked prefill (T=chunk) — at query-tile size ``block_q = T``.

The XLA reference path in ``serve/kv_pages.paged_attend`` gathers every
slot's block table into a contiguous ``[S, M*page, Hkv, D]`` logical view
before attending — per forward that is an O(n_slots * max_len) HBM
round-trip (read the pages, WRITE the gathered copy, read it back),
whatever the live context actually is. This kernel is the PagedAttention
analog of ``ops/flash_attention.py`` (Kwon et al., arXiv:2309.06180;
FlashAttention-2, Dao arXiv:2307.08691): the grid walks
(slot, kv-head, kv-page), the block table rides as a SCALAR-PREFETCH
operand so each kv BlockSpec DMAs the slot's next *physical* page
directly from the pool, and the online-softmax partial (m, l, acc) is
carried across page steps in VMEM scratch — the same accumulation
``_fwd_kernel`` uses. Nothing context-sized is ever materialized: reads
are O(live pages) per forward and the only write is the [S, T, Hq, D]
output.

Scope — the whole [S, T] serve contract, one kernel form:

- **T == 1** is the batched decode step (the original block_q==1
  specialist, bitwise unchanged: the query tile is the [groups, hd] GQA
  group and each page step's math is identical op for op).
- **T > 1** carries a ``[T*groups, hd]`` query tile per (slot, kv-head):
  slot s's row r is its token ``r // groups`` at absolute position
  ``lengths[s] + r // groups``, so the shared band machinery
  (`_band_live` at block_q=T for the tile skip, `_band_mask` generalized
  per query row by ``_rows_band_mask``) drives each row's causal
  frontier independently — within-tile causality included, because the
  caller scatters the T new tokens into the pool BEFORE the attend and
  the mask is pure position arithmetic. This is the speculative
  verification forward (``ModelPrograms.verify_for``, T = k+1 candidates
  per slot) and the chunked-prefill chunk ([1, T] attending over its own
  tokens plus the committed history) — both previously exiled to the
  ~3x-byte gather path, and both now reading the context exactly once
  per forward with the read amortized over T tokens.

Feature parity with the serving attend contract rides the multi-token
form unchanged (Gemma-2 verifies and chunk-prefills through this):
``window`` (static, or traced per-layer schedules riding the same [3]
int32 band operand the training kernels use), ``scale``, ``softcap``.
Positions past a query row's own (trash-page rows, a final chunk's
``n_valid`` pad tail, stale rejected-draft garbage) are cut by the
per-row causal mask exactly as in the gather path — pad query rows
compute ignored garbage over the SAME pool bytes the gather view would
read, so flash-vs-gather parity holds on every row, not just live ones.

QUANTIZED pools (``serve/kv_pages.py`` ``kv_dtype="int8"``): pass the
per-(position, kv-head) fp32 scales as ``k_scale``/``v_scale``
``[P, page, Hkv]`` and the kernel dequantizes IN the tile loop — the
scale blocks ride their own block-table BlockSpec, so step (s, h, m)
DMAs physical page ``tables[s, m]``'s payload AND its scale row in the
same prefetch-driven pattern, multiplies them in fp32 inside the
online-softmax accumulation, and still writes only the float output.
The read drops to ~1/4 of the fp32 bytes (int8 payload + 4 B/vector
scales) with no float pool ever materialized — at any T.

``interpret=True`` runs the kernel on CPU — the tier-1 parity grids in
``tests/test_paged_decode.py`` pin it against the XLA gather path at
1e-5 across GQA/window/scale/softcap, shuffled physical layouts, and
multi-token tiles with ``n_valid`` tails.

Under the SHARDED page pool (``serve/sharding.py``) this kernel runs
inside a full-manual shard_map with a per-chip pool slice: GSPMD cannot
partition a ``pallas_call``, so the manual region is what takes the
kernel from "replicated over a replicated pool" to "each chip reads its
own kvh/tp heads' pages". Nothing here changes — the grid's kv-head axis
is just smaller (possibly 1), block tables/lengths arrive replicated,
and the GQA group count is per-KV-head and therefore shard-invariant;
the chunk and verify programs ride the same manual region the decode
does.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .dispatch import lane_column, resolve_interpret
from .flash_attention import (NEG_INF, _band_live, _pack_band,
                              check_static_window)


def _rows_band_mask(window, m_idx, block_q, groups, page, q_off):
    """``ops/flash_attention._band_mask`` generalized to the paged query
    tile's ``[block_q * groups, page]`` row layout: the GQA group axis is
    folded into rows, so query row r is the slot's token ``r // groups``
    at absolute position ``q_off + r // groups``, and key column j is
    position ``m_idx * page + j``. Same (causal, ``< window``) band,
    driven per query row — each row's causal frontier is its own
    ``length + t``. ``window`` is the kernel's [3] SMEM band value (2**30
    encodes "no window"), so the band term is always applied."""
    shape = (block_q * groups, page)
    q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // groups
    k_pos = m_idx * page + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (q_pos >= k_pos) & ((q_pos - k_pos) < window)


def _attend_kernel(lens_ref, tabs_ref, band_ref, q_ref, k_ref, v_ref, *rest,
                   scale, softcap, page, num_page_blocks, quantized,
                   block_q, groups):
    """Grid (slot, kv_head, page_block); page_block innermost so the
    (m, l, acc) scratch carries the online softmax across the slot's
    pages. The query tile is ``[block_q * groups, hd]`` — block_q tokens
    per slot with the GQA group folded into rows — and the tile's first
    token sits at ``lengths[slot]``, which drives the shared band
    machinery per row. block_q == 1 is the original decode specialist,
    op for op. Under ``quantized`` two more inputs follow k/v: the
    page's k/v scale rows, DMA'd through the same block-table index map
    and multiplied into the int8 payload right here in the tile loop."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    s_idx = pl.program_id(0)
    h_idx = pl.program_id(1)
    m_idx = pl.program_id(2)
    q_pos = lens_ref[s_idx]          # the FIRST new token's position; row
                                     # r sits at q_pos + r // groups
    window = band_ref[0]             # [window, q_off, k_off] contract;
                                     # 2**30 encodes "no window"

    @pl.when(m_idx == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # page fully outside every row's (causal, window) band -> no compute:
    # the newest row's frontier is q_pos + block_q - 1, the oldest row's
    # window edge is q_pos - (window - 1) — exactly _band_live at
    # block_q = T. Dead tiles past the slot's table alias the trash page
    # (table rows are 0-filled), so consecutive skipped steps
    # re-reference one block.
    live = _band_live(True, window, 0, m_idx, block_q, page, q_off=q_pos)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [T*G, D]
        k = k_ref[0].astype(jnp.float32)             # [page, D]
        if quantized:   # in-tile dequant: int8 payload x per-vector scale
            k = k * lane_column(ks_ref[0], h_idx)   # [page, Hkv] -> head
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:  # Gemma-2: tanh cap BEFORE the mask
            s = jnp.tanh(s / softcap) * softcap
        # [T*G, page] mask: each query row's own causal/window frontier
        mask = _rows_band_mask(window, m_idx, block_q, groups, page, q_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0:1]                       # [T*G, 1]
        l_prev = l_scr[:, 0:1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                       # [T*G, page]
        # a live page can still be fully masked for some rows (the
        # window's lower edge, or an early row of a tile kept live by a
        # later one): exp(NEG_INF - NEG_INF) = 1 would poison l — zero
        # masked lanes explicitly, as the training kernel does for SWA
        # tiles
        p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)             # [page, D]
        if quantized:
            v = v * lane_column(vs_ref[0], h_idx)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(m_idx == num_page_blocks - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


PAGED_GATE = "head_dim % 128 == 0 and page_size % 8 == 0"


def paged_decode_eligible(head_dim: int, page_size: int) -> bool:
    """Shape gate for the COMPILED kernel (the interpret path takes any
    shape), set from what the v5e compiler accepts
    (``tests/test_chip_compile.py``). The pool rides as
    ``[P, page, Hkv*D]`` and head h's kv block is the ``[page, D]`` lane
    window at ``h*D``: a lane-dim block must be a multiple of 128, so
    head_dim 64 models take the gather path. The page axis is a
    whole-dimension block, which Mosaic tiles for fp32, bf16 and int8
    payloads alike at every page size the compiler was shown (8..128), so
    one rule serves float and quantized pools. T-independent by
    construction (the query-tile row count only sizes VMEM scratch), which
    is what lets ``attend_impl='auto'`` resolve decode, verify, and chunk
    forwards to the SAME family: a shape either takes the kernel for all
    three or for none."""
    return head_dim % 128 == 0 and page_size % 8 == 0


def paged_flash_attend(
    q: jnp.ndarray,          # [S, T, Hq, D] query tile per slot
                             # (rank 3 [S, Hq, D] = the T == 1 decode form)
    k_pages: jnp.ndarray,    # [P, page, Hkv, D] — ONE layer's page pool
    v_pages: jnp.ndarray,    # (int8 payload when k_scale/v_scale given)
    tables: jnp.ndarray,     # [S, M] int32 physical page ids (0 = trash)
    lengths: jnp.ndarray,    # [S] int32 — the FIRST query token's
                             # position; slot s's token t sits at
                             # lengths[s] + t, kv positions <= it are live
    *,
    k_scale: Optional[jnp.ndarray] = None,   # [P, page, Hkv] fp32 — the
    v_scale: Optional[jnp.ndarray] = None,   # quantized pool's scales
    window=None,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash attention through the block table at query-tile size T;
    returns [S, T, Hq, D] (or [S, Hq, D] for a rank-3 q) in q.dtype
    (the output dtype is the QUERY's — a quantized pool still emits
    float attention).

    The caller has already scattered the T new tokens' k/v into the
    pages (``serve/kv_pages.paged_attend`` owns that write, trash-page
    routing of ``n_valid`` pad tails included), so positions
    ``lengths[s] .. lengths[s] + T - 1`` are resident and the per-row
    causal mask keeps everything past each row's own position (trash
    page, stale garbage, later draft rows) out — identical semantics to
    the XLA gather reference, without the gathered view.
    ``k_scale``/``v_scale`` (both or neither) switch on the in-kernel
    dequant of an int8 pool.
    """
    check_static_window(window)
    quantized = k_scale is not None or v_scale is not None
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError("pass both k_scale and v_scale (or neither) — a "
                         "half-quantized pool cannot exist")
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    s, t, hq, d = q.shape
    _, page, hkv, _ = k_pages.shape
    m = tables.shape[1]
    if hkv < 1 or hq % hkv:
        # a silent floor-division here would drop query heads (the
        # reshape below masks it for some shapes); seen when a sharded
        # caller splits q and the pool on mismatched axes
        raise ValueError(
            f"query heads ({hq}) must be a positive multiple of kv heads "
            f"({hkv}); mismatched head sharding?")
    groups = hq // hkv
    tg = t * groups
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    interpret = resolve_interpret(interpret)
    if not interpret and not paged_decode_eligible(d, page):
        raise ValueError(
            f"paged flash attend (compiled) needs {PAGED_GATE}; got "
            f"head_dim={d}, page_size={page} — use impl='xla'")
    band = _pack_band(window)     # [window|2**30, 0, 0] int32 — the same
                                  # dynamic-band contract as the training
                                  # kernels; traced per-layer windows ride it
    # fold (token, group) into one row axis per (slot, kv-head): row
    # r = t * groups + g, so the kernel recovers the token as r // groups.
    # For T == 1 the transpose is a no-op and qr is byte-identical to the
    # original decode layout [s, hkv, groups, d].
    qr = (q.reshape(s, t, hkv, groups, d)
           .transpose(0, 2, 1, 3, 4).reshape(s, hkv, tg, d))

    kernel = functools.partial(_attend_kernel, scale=scale, softcap=softcap,
                               page=page, num_page_blocks=m,
                               quantized=quantized, block_q=t, groups=groups)
    # the point of the kernel: the kv BlockSpecs read THROUGH the block
    # table — step (s, h, m) DMAs physical page tables[s, m]; a quantized
    # pool's scale rows ride the SAME index map as two more operands
    # the pool rides as [P, page, Hkv*D] (a free reshape): head h's
    # [page, D] window is then a lane-dim block, which Mosaic tiles; a
    # one-row block of the Hkv axis of the 4-D pool is refused
    table_kv = pl.BlockSpec((1, page, d),
                            lambda s_, h, m_, lens, tabs, band_:
                            (tabs[s_, m_], 0, h))
    table_scale = pl.BlockSpec((1, page, hkv),
                               lambda s_, h, m_, lens, tabs, band_:
                               (tabs[s_, m_], 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, tg, d),
                     lambda s_, h, m_, lens, tabs, band_: (s_, h, 0, 0)),
        table_kv,
        table_kv,
    ]
    n_phys = k_pages.shape[0]
    operands = [qr, k_pages.reshape(n_phys, page, hkv * d),
                v_pages.reshape(n_phys, page, hkv * d)]
    if quantized:
        in_specs += [table_scale, table_scale]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # lengths, tables, band
        grid=(s, hkv, m),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, tg, d),
                               lambda s_, h, m_, lens, tabs, band_:
                               (s_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((tg, 128), jnp.float32),   # running max
            pltpu.VMEM((tg, 128), jnp.float32),   # running sum
            pltpu.VMEM((tg, d), jnp.float32),     # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, hkv, tg, d), q.dtype),
        interpret=interpret,
        name="paged_attend",
    )(lengths.astype(jnp.int32), tables.astype(jnp.int32), band, *operands)
    out = (out.reshape(s, hkv, t, groups, d)
              .transpose(0, 2, 1, 3, 4).reshape(s, t, hq, d))
    return out[:, 0] if squeeze else out


# The block_q == 1 name the decode path shipped under; same kernel, same
# contract — kept so existing callers/tests read naturally.
paged_flash_decode = paged_flash_attend
