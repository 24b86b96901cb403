"""Paged flash attention over block tables: ONE Pallas kernel family for
every serve-plane forward — decode (T=1), speculative verify (T=k+1),
and chunked prefill (T=chunk) — at query-tile size ``block_q = T``.

The XLA reference path in ``serve/kv_pages.paged_attend`` gathers every
slot's block table into a contiguous ``[S, M*page, Hkv, D]`` logical view
before attending — per forward that is an O(n_slots * max_len) HBM
round-trip (read the pages, WRITE the gathered copy, read it back),
whatever the live context actually is. This kernel is the PagedAttention
analog of ``ops/flash_attention.py`` (Kwon et al., arXiv:2309.06180;
FlashAttention-2, Dao arXiv:2307.08691): online softmax over the pages a
slot's block table names, nothing context-sized ever materialized.

How the table is walked and in what units the pool is read:

- The grid is ``(slot, head block)``; for a decode step the head block is
  all of the device's kv heads, so a step of the grid is one slot. Inside
  it a ``fori_loop`` walks ONLY the pages the slot's length makes live:
  from the page holding the oldest position any row's window still
  reaches (page 0 without a window) to the page holding position
  ``lengths[s] + T - 1``. Its bounds are read from the scalar-prefetched
  ``lengths`` and band, so a short slot costs a short walk and a table's
  dead columns cost nothing.
- The pool stays in HBM in the layout it is stored in, ALL LAYERS of it
  (``memory_space=pl.ANY``; the stacked ``[L, P, page, Hkv, D]`` seen as
  ``[L*P, page*Hkv, D]``, the same bytes: no slice and no copy before the
  call, so the layer scan can carry the pools whole). The call takes the
  layer's index; ``layer * P`` rides as one more scalar-prefetched
  operand and a page is read for ALL kv heads in one DMA
  (``pool.at[layer * P + tables[s, i]]``, 128 KB of bf16 at 32 heads of
  128) into a double-buffered VMEM block of ``n`` pages (4 at most), the
  next block in flight while this one is attended to.
  Sub-pages of the last block past the live range read the layer's own
  trash page 0 (finite, and masked like every position past a row's own).
- A page's rows are ``(position, head)`` pairs. For ``hb`` heads at once
  the products are ``q[hb*T*G, D] . rows[page*hb, D]^T`` and
  ``p[hb*T*G, page*hb] . rows[page*hb, D]``, with the pairs whose heads
  differ masked like positions outside the band (their ``p`` is exactly
  0). ``hb`` is all heads while the query tile is small (decode, verify:
  the MXU streams each k and v row once either way, and no row is picked
  out of a page); a large tile (a prefill chunk) takes the heads that
  share a 32-bit word of the pool's dtype (1 fp32, 2 bf16, 4 int8),
  picked out of the VMEM block with one strided word load, so its
  products are not wasted on the mask (head_dim 128, whose rows are one
  lane tile, which that load needs; a wider head keeps all heads in one
  product at every T). How many heads a grid step holds and how many
  pages a block holds follow from the static shapes and a VMEM budget
  (``_plan``); nothing is chosen by a caller.

The arithmetic is what it was: scores, running max and sum, ``p`` and
the accumulator in fp32. q and k meet the MXU in their stored dtype when
both are bf16 (bf16 products in an fp32 accumulator are exact); ``p``
stays fp32.

Scope — the whole [S, T] serve contract, one kernel form:

- **T == 1** is the batched decode step: the query tile is the
  ``[Hkv*groups, D]`` block of every head's GQA group.
- **T > 1** carries ``T*groups`` rows per kv head: slot s's row r is its
  token ``r // groups`` at absolute position ``lengths[s] + r // groups``,
  so each row's causal frontier (and window edge) is its own — within-tile
  causality included, because the caller scatters the T new tokens into
  the pool BEFORE the attend and the mask is pure position arithmetic.
  This is the speculative verification forward
  (``ModelPrograms.verify_for``, T = k+1 candidates per slot) and the
  chunked-prefill chunk ([1, T] attending over its own tokens plus the
  committed history).

Feature parity with the serving attend contract rides every T unchanged
(Gemma-2 verifies and chunk-prefills through this): ``window`` (static,
or traced per-layer schedules riding the same [3] int32 band operand the
training kernels use), ``scale``, ``softcap``. Positions past a query
row's own (trash-page rows, a final chunk's ``n_valid`` pad tail, stale
rejected-draft garbage) are cut by the per-row causal mask exactly as in
the gather path — pad query rows compute ignored garbage over the SAME
pool bytes the gather view would read, so flash-vs-gather parity holds on
every row, not just live ones.

QUANTIZED pools (``serve/kv_pages.py`` ``kv_dtype="int8"``): pass the
per-(position, kv-head) fp32 scales as ``k_scale``/``v_scale``
``[L, P, page, Hkv]``. The LAYER's scales (1/32 of its payload's bytes)
are laid out as lane vectors before the call; they ride the same walk,
one small DMA a page beside the payload's, and are applied where they are
lane vectors: the k scale
to the score columns, the v scale to ``p``'s columns (``q . (k*s)`` is
``(q . k) * s``), so the int8 payload meets the MXU as it is read and no
float pool is ever materialized — at any T.

TWO EXTRAS of a family whose attention layers differ in kind
(``models/mimo_v2.py``), both static and both absent from every other
family's call: a key WIDER than the value row lies in ``parts`` pool rows of
one lane tile each (a 192-wide key in two 128-wide rows, the k pool holding
``parts`` layers a layer), so that the pools are still read where they lie; a
score is then the sum of the parts' products. And a SINK logit a query head
joins each row's softmax as a column that holds no value: the running
statistics start from it (max = the logit, sum = 1) instead of from nothing.

``interpret=True`` runs the kernel on CPU — the tier-1 parity grids in
``tests/test_paged_decode.py`` pin it against the XLA gather path at
1e-5 across GQA/MHA/window/scale/softcap, shuffled physical layouts,
lengths either side of every page and block edge, and multi-token tiles
with ``n_valid`` tails.

Under the SHARDED page pool (``serve/sharding.py``) this kernel runs
inside a full-manual shard_map with a per-chip pool slice: GSPMD cannot
partition a ``pallas_call``, so the manual region is what takes the
kernel from "replicated over a replicated pool" to "each chip reads its
own kvh/tp heads' pages". Nothing here changes — a page just holds fewer
heads (possibly 1), block tables/lengths arrive replicated, and the GQA
group count is per-KV-head and therefore shard-invariant; the chunk and
verify programs ride the same manual region the decode does.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .dispatch import resolve_interpret
from .flash_attention import NEG_INF, _pack_band, check_static_window

# _plan's budgets. A head block's q, out and accumulator tiles and the
# walk's page buffers each stay under their share of VMEM_LIMIT (the v5e
# has 128 MiB; the compiler's default plan is 16).
VMEM_LIMIT = 64 * 2 ** 20
TILE_BUDGET = 8 * 2 ** 20       # q + out (double-buffered) + m, l, acc
PAGES_BUDGET = 4 * 2 ** 20      # k + v page buffers, both halves
SCORE_BUDGET = 2 * 2 ** 20      # a block's fp32 scores
ROWS_ALL_HEADS = 256            # query rows up to which hb = all heads
MAX_BLOCK_PAGES = 4             # the block loop is unrolled over these
DEPTH = 2                       # page blocks in flight


def _lane_tiles(columns: int) -> int:
    """``columns`` rounded up to whole 128-lane tiles."""
    return -(-columns // 128) * 128


def _head_blocks(t, groups, hkv, d, q_dtype, pool_dtype, *, parts=1,
                 sink=False):
    """``(hb, fits)``: kv heads one product takes, and the head-block sizes
    whose q, out and accumulator tiles stay inside ``TILE_BUDGET``. Pool rows
    are ``d`` wide; a key is ``parts`` of them (a query ``parts * d``)."""
    tg = t * groups
    words = 4 // jnp.dtype(pool_dtype).itemsize     # rows sharing 32 bits
    # the strided load takes a VMEM block whose rows are one 128-lane tile
    if hkv * tg <= ROWS_ALL_HEADS or hkv % words or d != 128:
        hb = hkv
    else:
        hb = words
    q_item = jnp.dtype(q_dtype).itemsize
    per_head = tg * (2 * (parts + 1) * d * q_item + d * 4
                     + (3 if sink else 2) * 128 * 4)
    # a head block's rows are a block of q's second-minor dimension
    fits = [h for h in range(hb, hkv + 1, hb) if hkv % h == 0
            and h * per_head <= TILE_BUDGET
            and (h == hkv or (h * tg) % 16 == 0)]
    return hb, fits


def _query_block(t, groups, hkv, d, q_dtype, pool_dtype, **more) -> int:
    """Query tokens one kernel call takes: ``t`` where some head block's
    tile fits the budget, else the largest divisor of ``t`` for which one
    does (``t`` again if none). A chunk of 1,024 tokens at 8 query heads a
    pool row is 8,192 rows a row, 20 MB of tiles for the smallest head block:
    it goes in blocks of 128 tokens, each its own walk of the table."""
    for bt in sorted((b for b in range(1, t + 1) if t % b == 0),
                     reverse=True):
        if _head_blocks(bt, groups, hkv, d, q_dtype, pool_dtype, **more)[1]:
            return bt
    return t


def _plan(t, groups, hkv, d, page, max_pages, q_dtype, pool_dtype, *,
          parts=1, sink=False):
    """Block parameters from the static shapes: ``(hs, hb, n)`` = kv heads
    a grid step holds, kv heads one product takes (``hs % hb == 0``), pages
    a block of the walk holds."""
    tg = t * groups
    hb, fits = _head_blocks(t, groups, hkv, d, q_dtype, pool_dtype,
                            parts=parts, sink=sink)
    hs = max(fits) if fits else hkv
    # a page's k rows (each part) and v rows, both buffer halves of each
    page_bytes = page * hkv * d * jnp.dtype(pool_dtype).itemsize
    n = min(MAX_BLOCK_PAGES, max_pages,
            PAGES_BUDGET // ((parts + 1) * DEPTH * page_bytes),
            SCORE_BUDGET // (hb * tg * _lane_tiles(page * hb) * 4))
    return hs, hb, max(1, n)


def _head_rows(buf, slot, i, head, *, hb, hkv, page):
    """Rows ``(position, head .. head + hb)`` of sub-page i of a VMEM page
    block, ``[page * hb, D]``. All heads are the page as it lies; fewer are
    the heads of one 32-bit word, one strided word load."""
    ref = buf.at[slot, i]                            # [page * hkv, D]
    if hb == hkv:
        return ref[...]
    if hb == 1:
        return ref[pl.ds(head, page, stride=hkv), :]
    words = ref.bitcast(jnp.int32)                   # [page * hkv / hb, D]
    picked = words[pl.ds(head // hb, page, stride=hkv // hb), :]
    return pltpu.bitcast(picked, buf.dtype)


def _attend_kernel(lens_ref, tabs_ref, band_ref, base_ref, q_ref, k_hbm,
                   v_hbm, *rest, scale, softcap, page, hkv, hs, hb, n,
                   quantized, block_q, groups, has_sink=False, parts=1):
    """Grid (slot, head block). The walk over the slot's live pages is the
    ``fori_loop`` below; (m, l, acc) carry the online softmax across its
    blocks in VMEM scratch. Query row ``r`` of a head is the slot's token
    ``r // groups`` at position ``lengths[slot] + r // groups``. The pools
    are every layer's, page ``base_ref[0] + p`` being this layer's page
    ``p``. Under ``quantized`` the pages' k/v scale rows (this layer's
    alone) come along the same walk. Under ``has_sink`` a row's running
    softmax starts from its head's sink logit instead of from nothing: a
    column of the softmax that holds no value (max = the logit, sum = 1,
    accumulator 0). With ``parts`` > 1 a key is that many pool rows, part
    ``j`` of layer ``l`` being the k pool's layer ``l * parts + j`` (its first
    page ``base_ref[1 + j]``), and a score the sum of the parts' products
    with the query's matching columns."""
    if has_sink:
        sink_ref, *rest = rest
    if quantized:
        (ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, sems,
         m_scr, l_scr, acc_scr) = rest
    else:
        o_ref, kbuf, vbuf, sems, m_scr, l_scr, acc_scr = rest
    s_idx = pl.program_id(0)
    h_idx = pl.program_id(1)
    max_pages = tabs_ref.shape[1]
    tg = block_q * groups
    q_pos = lens_ref[s_idx]          # the FIRST new token's position; row
                                     # r sits at q_pos + r // groups
    window = band_ref[0]             # [window, q_off, k_off] contract;
                                     # 2**30 encodes "no window"
    base = base_ref[0]               # the layer's first page in the pools
    # live pages [lo, hi): the newest row's frontier is q_pos + block_q - 1,
    # the oldest row's window edge is q_pos - (window - 1)
    hi = jnp.minimum(pl.cdiv(q_pos + block_q, page), max_pages)
    lo = jnp.minimum(jnp.maximum(q_pos - (window - 1), 0) // page, hi - 1)
    n_blocks = pl.cdiv(hi - lo, n)

    def copies(b, slot):
        """Block b's DMAs into buffer half ``slot``: n pages of k and of
        v through the table (and their scale rows)."""
        pairs = [(k_hbm, kbuf, base), (v_hbm, vbuf, base)]
        if quantized:
            pairs += [(ks_hbm, ksbuf, 0), (vs_hbm, vsbuf, 0)]
        out = []
        for i in range(n):
            col = lo + b * n + i
            # past the live range: the trash page (table tails name it too)
            phys = jnp.where(col < hi,
                             tabs_ref[s_idx, jnp.minimum(col, max_pages - 1)],
                             0)
            for j, (hbm, buf, first) in enumerate(pairs):
                if parts > 1 and j == 0:    # the key's parts, a page each
                    for part in range(parts):
                        out.append(pltpu.make_async_copy(
                            hbm.at[base_ref[1 + part] + phys],
                            buf.at[slot, i * parts + part], sems.at[j, slot]))
                    continue
                out.append(pltpu.make_async_copy(
                    hbm.at[first + phys], buf.at[slot, i], sems.at[j, slot]))
        return out

    if has_sink:
        m_scr[:] = sink_ref[:]
        l_scr[:] = jnp.ones_like(l_scr)
    else:
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    # a product's rows are (head, token, group), its columns (position,
    # head) of one page. rel = the row's token minus the column's position
    # in the page, or far below zero where the heads differ, so that one
    # shifted comparison is the (head, causal, window) mask of any page
    shape = (hb * tg, page * hb)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    rel = jnp.where(row // tg == col % hb,
                    (row % tg) // groups - col // hb, -2 ** 30)

    # bf16 q and k meet the MXU as stored (their products are exact in its
    # fp32 accumulator); anything else as fp32
    mxu = (jnp.bfloat16 if q_ref.dtype == kbuf.dtype == jnp.bfloat16
           else jnp.float32)

    for c in copies(0, 0):
        c.start()

    def block(b, carry):
        slot = jax.lax.rem(b, DEPTH)

        @pl.when(b + 1 < n_blocks)
        def _prefetch():
            for c in copies(b + 1, jax.lax.rem(b + 1, DEPTH)):
                c.start()

        for c in copies(b, slot):
            c.wait()
        first = q_pos - (lo + b * n) * page   # q_pos seen from the block
        for g in range(hs // hb):
            rows = slice(g * hb * tg, (g + 1) * hb * tg)
            head = h_idx * hs + g * hb
            d = kbuf.shape[-1]
            qs = [q_ref[0, rows, pl.ds(part * d, d)].astype(mxu)
                  for part in range(parts)] if parts > 1 else [
                      q_ref[0, rows, :].astype(mxu)]
            scores, masks = [], []
            for i in range(n):
                s = None
                for part, q in enumerate(qs):
                    k = _head_rows(kbuf, slot, i * parts + part, head, hb=hb,
                                   hkv=hkv, page=page)
                    sp = jax.lax.dot_general(
                        q, k.astype(mxu), (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    s = sp if s is None else s + sp
                s = s * scale
                if quantized:   # the k scale, on the score's columns
                    s = s * ksbuf[slot, i, pl.ds(head // hb, 1),
                                  :page * hb]
                if softcap is not None:  # Gemma-2: tanh cap BEFORE the mask
                    s = jnp.tanh(s / softcap) * softcap
                x = rel + (first - i * page)
                mask = (x >= 0) & (x < window)
                scores.append(jnp.where(mask, s, NEG_INF))
                masks.append(mask)

            m_prev = m_scr[rows, 0:1]                    # [hb*T*G, 1]
            l_prev = l_scr[rows, 0:1]
            m_new = m_prev
            for s in scores:
                m_new = jnp.maximum(m_new, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev
            acc = acc_scr[rows, :] * alpha
            for i, (s, mask) in enumerate(zip(scores, masks)):
                # a live page can still be fully masked for some rows (the
                # window's lower edge, another head's columns, an early row
                # of a tile kept live by a later one): exp(NEG_INF -
                # NEG_INF) = 1 would poison l — zero masked lanes
                # explicitly, as the training kernel does for SWA tiles
                p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
                l_new = l_new + jnp.sum(p, axis=1, keepdims=True)
                if quantized:   # the v scale, on p's columns
                    p = p * vsbuf[slot, i, pl.ds(head // hb, 1),
                                  :page * hb]
                v = _head_rows(vbuf, slot, i, head, hb=hb, hkv=hkv, page=page)
                acc = acc + jax.lax.dot_general(
                    p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            acc_scr[rows, :] = acc
            m_scr[rows, :] = jnp.broadcast_to(m_new, (hb * tg, 128))
            l_scr[rows, :] = jnp.broadcast_to(l_new, (hb * tg, 128))
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)

    l = l_scr[:, 0:1]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


PAGED_GATE = ("pool row width % 128 == 0 (head_dim, two 64-wide heads a "
              "row, or a 192-wide key in two rows) and page_size % 8 == 0")


def paged_decode_eligible(head_dim: int, page_size: int) -> bool:
    """Shape gate for the COMPILED kernel (the interpret path takes any
    shape), set from what the v5e compiler accepts
    (``tests/test_chip_compile.py``). A page is DMA'd as its
    ``[page * Hkv, D]`` rows: D is the lane dimension of the VMEM block and
    of both products, so it is whole 128-lane tiles. ``head_dim`` here is
    the width of a pool ROW: a family with 64-wide heads stores two kv heads
    in one row and places each query head in its half (``models/lfm2.py``:
    the same bytes read, twice the memory-bound MXU work), and takes the
    kernel; a pool of bare 64-wide rows takes the gather path. The rows are
    whole sublane tiles of fp32, bf16
    and int8 payloads at every page size the compiler was shown (8..128),
    so one rule serves float and quantized pools. T-independent by
    construction (the query-tile row count only sizes VMEM blocks), which
    is what lets ``attend_impl='auto'`` resolve decode, verify, and chunk
    forwards to the SAME family: a shape either takes the kernel for all
    three or for none."""
    return head_dim % 128 == 0 and page_size % 8 == 0


def _layer_base(layer, n_phys: int, parts: int = 1) -> jnp.ndarray:
    """The scalar-prefetched ``[1]`` int32 first page of ``layer`` in pools
    seen as ``[L * n_phys, ...]``; with a key in ``parts`` rows, ``[1 +
    parts]``: the v pool's, then each key part's in the k pool."""
    base = (jnp.asarray(layer, jnp.int32) * n_phys).reshape(1)
    if parts == 1:
        return base
    return jnp.concatenate(
        [base, (base * parts + jnp.arange(parts, dtype=jnp.int32) * n_phys)])


def paged_flash_attend(
    q: jnp.ndarray,          # [S, T, Hq, D] query tile per slot
                             # (rank 3 [S, Hq, D] = the T == 1 decode form)
    k_pages: jnp.ndarray,    # [L, P, page, Hkv, D]: EVERY layer's page pool
    v_pages: jnp.ndarray,    # (int8 payload when k_scale/v_scale given)
    layer,                   # int32 scalar (traced in a layer scan): whose
                             # pages ``tables`` names
    tables: jnp.ndarray,     # [S, M] int32 physical page ids (0 = trash)
    lengths: jnp.ndarray,    # [S] int32 — the FIRST query token's
                             # position; slot s's token t sits at
                             # lengths[s] + t, kv positions <= it are live
    *,
    k_scale: Optional[jnp.ndarray] = None,   # [L, P, page, Hkv] fp32 — the
    v_scale: Optional[jnp.ndarray] = None,   # quantized pool's scales
    window=None,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    sink: Optional[jnp.ndarray] = None,      # [Hq] a sink logit a query head
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash attention through the block table at query-tile size T;
    returns [S, T, Hq, D] (or [S, Hq, D] for a rank-3 q) in q.dtype
    (the output dtype is the QUERY's — a quantized pool still emits
    float attention). ``D`` is the width of a pool ROW. A key wider than a
    row lies in ``parts`` of them (q ``[.., parts * D]``, ``k_pages [parts *
    L, ...]``: part ``j`` of layer ``l`` is the k pool's layer ``l * parts +
    j``; a 192-wide key padded to 256 is two rows beside a 128-wide value),
    each row one lane tile, so the pools are still read where they lie.
    ``sink`` adds one column to every row's softmax, holding the row's
    head's logit and no value.

    The pools are the stacked ones the engine holds, handed over whole:
    the kernel reads page ``layer * P + tables[s, i]`` of their
    ``[L * P, page * Hkv, D]`` view, so no layer's pool is sliced out for
    the call. The caller has already scattered the T new tokens' k/v into
    the layer's pages (``serve/kv_pages.paged_attend`` owns that write,
    trash-page routing of ``n_valid`` pad tails included), so positions
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
    s, t, hq, dq = q.shape
    n_layers, n_phys, page, hkv, d = v_pages.shape
    parts = dq // d
    if k_pages.shape != (parts * n_layers, n_phys, page, hkv, d) or (
            parts > 1 and quantized):
        raise ValueError(
            f"q rows of {dq} over v rows of {d} need a float k pool of "
            f"{parts} x {n_layers} layers of such rows; got {k_pages.shape}")
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
        scale = 1.0 / (dq ** 0.5)
    interpret = resolve_interpret(interpret)
    if not interpret and not paged_decode_eligible(d, page):
        raise ValueError(
            f"paged flash attend (compiled) needs {PAGED_GATE}; got "
            f"head_dim={d}, page_size={page} — use impl='xla'")
    has_sink = sink is not None
    more = {}       # what a one-part, sinkless call never passes on
    if parts > 1:
        more["parts"] = parts
    if has_sink:
        more["sink"] = True
    bt = _query_block(t, groups, hkv, d, q.dtype, k_pages.dtype, **more)
    if bt < t:
        # a tile no head block fits in VMEM: the query tokens in blocks of
        # bt, one after the other, each with its own walk from the tokens
        # it starts at (all T new tokens are in the pool already)
        def rows(block):
            qb, first = block
            return paged_flash_attend(
                qb, k_pages, v_pages, layer, tables, lengths + first,
                k_scale=k_scale, v_scale=v_scale, window=window, scale=scale,
                softcap=softcap, sink=sink, interpret=interpret)

        out = jax.lax.map(rows, (
            q.reshape(s, t // bt, bt, hq, dq).swapaxes(0, 1),
            jnp.arange(t // bt, dtype=lengths.dtype) * bt))
        return out.swapaxes(0, 1).reshape(s, t, hq, d)
    band = _pack_band(window)     # [window|2**30, 0, 0] int32 — the same
                                  # dynamic-band contract as the training
                                  # kernels; traced per-layer windows ride it
    hs, hb, n = _plan(t, groups, hkv, d, page, m, q.dtype, k_pages.dtype,
                      **more)
    # rows (head, token, group): row r of a kv head is token r // groups.
    # For T == 1 the transpose is a no-op.
    qr = (q.reshape(s, t, hkv, groups, dq)
           .transpose(0, 2, 1, 3, 4).reshape(s, hkv * tg, dq))

    kernel = functools.partial(_attend_kernel, scale=scale, softcap=softcap,
                               page=page, hkv=hkv, hs=hs, hb=hb, n=n,
                               quantized=quantized, block_q=t, groups=groups,
                               **({"has_sink": True} if has_sink else {}),
                               **({"parts": parts} if parts > 1 else {}))
    # the pools are handed over where they lie: a page's (position, head)
    # pairs as rows, every layer's pages in one run, the same bytes as
    # [L, P, page, Hkv, D]
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    def tile(width):
        return pl.BlockSpec((1, hs * tg, width),
                            lambda s_, h, lens, tabs, band_, base: (s_, h, 0))

    in_specs = [tile(dq), in_hbm, in_hbm]
    operands = [qr, k_pages.reshape(parts * n_layers * n_phys, page * hkv, d),
                v_pages.reshape(n_layers * n_phys, page * hkv, d)]
    if has_sink:
        # rows (head, token, group) like q's: row r of kv head h is query
        # head h * groups + r % groups; one lane tile wide, as m and l are
        rows = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(hkv, 1, groups, 1),
            (hkv, t, groups, 128)).reshape(hkv * tg, 128)
        in_specs.append(pl.BlockSpec(
            (hs * tg, 128), lambda s_, h, lens, tabs, band_, base: (h, 0)))
        operands.append(rows)       # the kernel takes it after the pools
    scratch = [pltpu.VMEM((DEPTH, n * parts, page * hkv, d), k_pages.dtype),
               pltpu.VMEM((DEPTH, n, page * hkv, d), v_pages.dtype)]
    if quantized:
        # a page's scales as one lane vector per product: [hkv/hb,
        # page * hb], columns (position, head) like the score's, padded
        # to whole 128-lane tiles (what a DMA moves). Only the layer's
        # scales are laid out so: a 1/32 of its payload, not the stack
        width = _lane_tiles(page * hb)

        def lanes(x):
            x = jax.lax.dynamic_index_in_dim(x, layer, keepdims=False)
            x = (x.astype(jnp.float32).reshape(n_phys, page, hkv // hb, hb)
                  .transpose(0, 2, 1, 3)
                  .reshape(n_phys, hkv // hb, page * hb))
            return jnp.pad(x, ((0, 0), (0, 0), (0, width - page * hb)))
        in_specs += [in_hbm, in_hbm]
        operands += [lanes(k_scale), lanes(v_scale)]
        scratch += [pltpu.VMEM((DEPTH, n, hkv // hb, width),
                               jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((4 if quantized else 2, DEPTH)),
        pltpu.VMEM((hs * tg, 128), jnp.float32),   # running max
        pltpu.VMEM((hs * tg, 128), jnp.float32),   # running sum
        pltpu.VMEM((hs * tg, d), jnp.float32),     # output accumulator
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,          # lengths, tables, band, layer base
        grid=(s, hkv // hs),
        in_specs=in_specs,
        out_specs=tile(d),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, hkv * tg, d), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="paged_attend",
    )(lengths.astype(jnp.int32), tables.astype(jnp.int32), band,
      _layer_base(layer, n_phys, parts), *operands)
    out = (out.reshape(s, hkv, t, groups, d)
              .transpose(0, 2, 1, 3, 4).reshape(s, t, hq, d))
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# Latent (MLA) pools: one shared "head" whose keys are [c_kv | k_rope] and
# whose values are c_kv again
# ---------------------------------------------------------------------------

LATENT_GATE = ("latent and rope widths % 128 == 0, page_size % 16 == 0, "
               "T * heads <= 256")
LATENT_BLOCK_PAGES = 8          # pages a block of the walk holds, at most
LATENT_BLOCK_TOKENS = 1024      # tokens a block holds, at most


def latent_decode_eligible(latent_dim: int, rope_width: int, page_size: int,
                           rows: int = 1) -> bool:
    """Shape gate of the COMPILED latent kernel (``tests/test_chip_compile``):
    both pools' rows are whole 128-lane tiles, a page's rows whole sublane
    tiles of bf16, and the query tile (tokens x heads) small enough that one
    product holds it (a prefill chunk takes the decompressed path)."""
    return (latent_dim % 128 == 0 and rope_width % 128 == 0
            and page_size % 16 == 0 and rows <= ROWS_ALL_HEADS)


def _latent_kernel(lens_ref, tabs_ref, base_ref, qc_ref, qr_ref, c_hbm, r_hbm,
                   o_ref, cbuf, rbuf, sems, m_scr, l_scr, acc_scr, *, scale,
                   page, n, heads):
    """Grid (slot,). Row ``r`` of the query tile is the slot's token
    ``r // heads`` at position ``lengths[slot] + r // heads``. The walk is
    ``_attend_kernel``'s: blocks of ``n`` live pages, the next block's DMAs
    in flight while this one is attended to, page ``base_ref[0] + p`` of the
    stacked pools being this layer's page ``p``. A page is read ONCE: its
    ``c_kv`` rows are the keys' first part and the values."""
    s_idx = pl.program_id(0)
    base = base_ref[0]
    max_pages = tabs_ref.shape[1]
    rows = qc_ref.shape[1]
    block_q = rows // heads
    q_pos = lens_ref[s_idx]
    hi = jnp.minimum(pl.cdiv(q_pos + block_q, page), max_pages)
    n_blocks = pl.cdiv(hi, n)

    def copies(b, slot):
        out = []
        for i in range(n):
            col = b * n + i
            phys = jnp.where(col < hi,
                             tabs_ref[s_idx, jnp.minimum(col, max_pages - 1)],
                             0)
            for j, (hbm, buf) in enumerate(((c_hbm, cbuf), (r_hbm, rbuf))):
                out.append(pltpu.make_async_copy(
                    hbm.at[base + phys], buf.at[slot, pl.ds(i * page, page)],
                    sems.at[j, slot]))
        return out

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    shape = (rows, n * page)
    # the row's token minus the column's position inside the block
    rel = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) // heads
           - jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    mxu = (jnp.bfloat16 if qc_ref.dtype == cbuf.dtype == jnp.bfloat16
           else jnp.float32)
    qc = qc_ref[0].astype(mxu)
    qr = qr_ref[0].astype(mxu)

    for c in copies(0, 0):
        c.start()

    def block(b, carry):
        slot = jax.lax.rem(b, DEPTH)

        @pl.when(b + 1 < n_blocks)
        def _prefetch():
            for c in copies(b + 1, jax.lax.rem(b + 1, DEPTH)):
                c.start()

        for c in copies(b, slot):
            c.wait()
        ckv = cbuf[slot]                                  # [n*page, C]
        s = jax.lax.dot_general(
            qc, ckv.astype(mxu), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(
            qr, rbuf[slot].astype(mxu), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        mask = rel + (q_pos - b * n * page) >= 0
        s = jnp.where(mask, s * scale, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, ckv.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    l = l_scr[:, 0:1]
    o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_latent_attend(
    q: jnp.ndarray,          # [S, T, H, C + R]: [absorbed nope | rope] queries
    k_pages: jnp.ndarray,    # [L, P, page, 1, Rw] rope keys, R live columns
    v_pages: jnp.ndarray,    # [L, P, page, 1, C] latent rows c_kv
    layer,                   # int32 scalar: whose pages ``tables`` names
    tables: jnp.ndarray,     # [S, M] int32 physical page ids (0 = trash)
    lengths: jnp.ndarray,    # [S] int32: the first query token's position
    *,
    scale: float,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Absorbed latent attention (MLA decode) through the block table:
    ``softmax(scale * (q_c . c_kv + q_r . k_rope)) . c_kv`` per head, all
    heads against the ONE latent row a token has; returns ``[S, T, H, C]``
    in q.dtype. The stacked pools are read where they lie (page
    ``layer * P + tables[s, i]``), a page's ``c_kv`` once for both products.
    The caller has scattered the T new rows already
    (``serve/kv_pages.paged_attend``), as for ``paged_flash_attend``."""
    s, t, h, width = q.shape
    n_layers, n_phys, page, _, c = v_pages.shape
    rw = k_pages.shape[-1]
    r = width - c
    m = tables.shape[1]
    rows = t * h
    interpret = resolve_interpret(interpret)
    if not interpret and not latent_decode_eligible(c, rw, page, rows):
        raise ValueError(
            f"paged latent attend (compiled) needs {LATENT_GATE}; got "
            f"latent {c}, rope width {rw}, page_size {page}, T*heads {rows}")
    n = max(1, min(LATENT_BLOCK_PAGES, m, LATENT_BLOCK_TOKENS // page))
    qc = q[..., :c].reshape(s, rows, c)
    qr = jnp.pad(q[..., c:], ((0, 0),) * 3 + ((0, rw - r),)
                 ).reshape(s, rows, rw)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)

    def tile(w):
        return pl.BlockSpec((1, rows, w),
                            lambda s_, lens, tabs, base: (s_, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # lengths, tables, layer base
        grid=(s,),
        in_specs=[tile(c), tile(rw), in_hbm, in_hbm],
        out_specs=tile(c),
        scratch_shapes=[
            pltpu.VMEM((DEPTH, n * page, c), v_pages.dtype),
            pltpu.VMEM((DEPTH, n * page, rw), k_pages.dtype),
            pltpu.SemaphoreType.DMA((2, DEPTH)),
            pltpu.VMEM((rows, 128), jnp.float32),   # running max
            pltpu.VMEM((rows, 128), jnp.float32),   # running sum
            pltpu.VMEM((rows, c), jnp.float32),     # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, page=page, n=n,
                          heads=h),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, rows, c), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="paged_latent_attend",
    )(lengths.astype(jnp.int32), tables.astype(jnp.int32),
      _layer_base(layer, n_phys), qc, qr,
      v_pages.reshape(n_layers * n_phys, page, c),
      k_pages.reshape(n_layers * n_phys, page, rw))
    return out.reshape(s, t, h, c)


# The block_q == 1 name the decode path shipped under; same kernel, same
# contract — kept so existing callers/tests read naturally.
paged_flash_decode = paged_flash_attend
