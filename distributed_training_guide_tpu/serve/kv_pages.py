"""Paged KV cache: fixed-size blocks, a refcounted free-list allocator,
per-sequence block tables, and the device-side attend over the table.

A contiguous decode cache is ``[L, B, max_len, kvh, hd]`` — a serving
engine sized that way pays ``n_slots x max_len`` resident bytes whether or
not the slots are full (vLLM measures 60-80% of such memory as waste), and
this package has none: the pool below is the only KV cache a model family
serves from. Here the resident cache is
a POOL of pages ``[L, n_pages, page_size, kvh, hd]`` (PagedAttention, Kwon
et al., arXiv:2309.06180): a sequence owns ``ceil(tokens / page_size)``
pages wired together by an int32 block table, pages return to the free
list when their last reference drops, and cache memory is O(allocated
pages) — priced by ``kv_page_bytes`` and pinned by ``tests/test_serve.py``.

Pages are REFCOUNTED so identical prompt prefixes can share physical
pages across slots (copy-on-write prefix sharing — the other half of
PagedAttention): ``alloc`` hands out pages at refcount 1, ``share``
takes additional references, and ``free`` releases one reference per
call, returning the page to the free list only at zero. A write into a
shared page must fork it first (``copy_pages`` is the device-side copy;
the scheduler decides when — see serve/scheduler.py's prefix cache).

Physical page 0 is RESERVED as the trash page: it is never allocated, so a
write routed to it (an idle slot in the fixed ``[n_slots]`` decode batch,
the padded tail of a prefill chunk) lands harmlessly —
active block tables never reference it, so garbage in page 0 can never
enter a live slot's attend. That convention is what lets ONE compiled
decode program serve any mix of active/idle slots with plain scatters, no
recompiles.

``paged_attend`` has two implementations behind one dispatch:
``impl="flash"`` (the Pallas ``ops/paged_decode.py`` kernel — reads k/v
*through* the block table, O(live pages) traffic per forward, the
default on TPU) and ``impl="xla"`` (gather the table into a contiguous
logical view and run the einsum reference — the parity baseline, and
the off-TPU default: the kernel's interpret mode is for CI correctness,
not CPU throughput). The dispatch is T-INDEPENDENT: the kernel's query
tile is ``block_q = T``, so single-token decode, the speculative
verification forward (T = k+1), and chunked prefill (T = chunk) all
resolve to the same family under one ``impl`` — which is what makes
"flash everywhere" a construction-time property of an engine rather
than a per-call choice (serve/engine.py threads its ``attend_impl``
through every program).

QUANTIZED pools (``kv_dtype="int8"``): the k/v payload is stored int8
with block-wise absmax scales (``train/precision.py``'s Dettmers
machinery, the same code path the adam8bit optimizer state uses) —
~4x fewer pool bytes than fp32 and ~4x fewer HBM bytes on the
bandwidth-bound decode read. The block is one (position, kv-head) k/v
vector — ``head_dim`` elements, one fp32 scale — so the scale tensor
``[L, P, page, kvh, 1]`` tiles the pool exactly: scale rows ride page
identity (CoW forks copy them, the prefix cache and the disaggregated
handoff share/move them for free, the sharded pool splits them on the
same kv-head axis). Deliberately NOT one scale per whole page: a
page-granular absmax would change when a LATER token raises the page's
absmax, forcing a requantization that mutates already-written k/v —
which would break the engine's bitwise guarantees (preemption replay
and speculative verification rewrite single tokens and must reproduce
the original pool bytes exactly). Per-token blocks keep every write
independent: ``quantize(x)`` is a pure function of that token's k/v, so
replay/verify/chunk writes are bitwise identical however the token
first arrived. Quantization happens at the one write site
(``_scatter_new``: the decode step's row, a prefill chunk's or a verify
step's T rows); dequantization at every read site (the gather view, and inside the
flash-decode kernel's tile loop — the scale rides a second block-table
DMA operand).

One consequence to know: under int8 token identity is PROGRAM-relative.
A prefill chunk attends over already-quantized history (every chunk reads
the pool), so two engines that cut the same prompt into chunks of
different sizes read different roundings of it — in fp32 they agree to
~1e-7 (argmax flips are a lottery the test suite never loses), but under
int8 the difference is a genuine 1-LSB cache rounding that CAN flip a
downstream near-tie. Every identity guarantee the engines make (batch-1
invariance, spec-on == spec-off, preemption replay) holds bitwise WITHIN
one engine configuration because each token's k/v is rewritten by the
same program that wrote it; comparing engines across chunk sizes is a
quality question (bounded by the attend error pinned in
tests/test_kv_quant.py), not an identity one.

WHAT a page holds is the family's (``pool_layout``): the description above
is k and v of ``kvh x hd`` each. A latent-attention family (``models/mla.py``)
caches ONE row a token a layer: the pool's ``v`` leaf holds the latent
``c_kv`` (the absorbed form's values and the first part of its keys), its
``k`` leaf the rope key all heads share, padded to whole lane tiles; the
allocator, the tables, the scatter and the CoW copy below are the same code
over those two leaves, and the attend is ``_attend_latent``.

A family whose layers keep RECURRENT state beside (or instead of) k and v
(``models/lfm2.py``: a short convolution's last rows) states it in
``config.state_layout()`` and the pool gains a third leaf, ``"state"``
``[state layers, n_pages, rows, width]``, ADDRESSED BY PAGE: row ``p`` of a
sequence's page ``p`` holds the state after the last token written to that
page. A step reads the row of the page that holds its previous token and
writes the row of every page it writes tokens to (:func:`read_state`,
:func:`write_state`, bound to the tables as ``attend.read_state`` /
``attend.write_state``). There is still one allocator: the state has page
identity, so CoW forks, the prefix cache, the host tier, an engine swap and
preemption move it with the k and v pages (``copy_pages`` and
``serve/transport.py`` walk every leaf). One thing follows for the prefix
cache: a row is the state at its page's LAST token, so a hit on such a pool
ends at a full page (``Scheduler(partial_page_hits=False)``). Only the
layers that attend have k and v pages (``config.num_kv_layers``).

A family whose layers attend over different REACHES (``models/mimo_v2.py``:
full layers, and window layers that see the last ``W`` positions) states a
second PAGE CLASS in ``config.window_kv_layout()``. The pool dict then holds
two more leaves, ``"k_win"`` / ``"v_win"`` ``[window layers, n_window_pages,
page, heads, width]``, with their own layout, their own page count and their
own id space (page 0 of it the trash page again). There is still ONE allocator
object: ``PagePool.window`` is the second class's free list, and a sequence
holds, beside its full-class pages, only the window-class pages its next
query can still see (``window_page_span``): the scheduler RETURNS a window
page to the free list, on the host and with no device copy, in the engine step
in which the window slides past its last position. A sequence's two tables
ride every program as ONE int32 array ``[S, 2 M]``, the full class's columns
first; both are indexed by the sequence's LOGICAL page, and a window-class
column whose page was returned (or never held) names the trash page: the
kernel's walk starts at the oldest page the window reaches, so it never looks
there, and the gather path masks those positions by the same band.
``make_attend`` hands each layer the half its ``page_class`` names. A
one-class family is the case without the second half: none of this runs.

A family whose recurrent state is too large to keep a row a page states a
STATE CLASS in ``config.sequence_state_layout()``, ADDRESSED BY SEQUENCE.
Three families do: ``models/solar_open2.py`` (KDA: a ``heads x d x d`` float32
matrix a layer, 4 MB, in 3 of its 4 layers), ``models/jamba.py`` (Mamba: a
``[d_state, channels]`` float32 block a layer, channels on the lanes, 320 KB,
in 26 of its 28 layers; megabytes a sequence either way) and
``models/brumby.py`` (power retention: ``kv heads x 9,216 x d`` float32 and a
``d x d`` normaliser a kv head, 38 MB a layer, in EVERY layer). The pool dict
gains the leaves the layout names (:data:`SEQUENCE_LEAVES`: the state, the
last rows of the family's short convolution, the state's normaliser), each
``[state layers, n_blocks, ...]``, a BLOCK a live sequence, with an id space
of its own (block 0 the trash block, which idle slots carry).
``PagePool.state`` is its free list: the scheduler takes a block when it
admits a sequence and returns it when the sequence leaves its slot (finished,
expired or preempted: a preempted sequence is prefilled again), so ``n_slots +
1`` blocks always suffice. A slot's block id rides every program as ONE MORE
COLUMN of its table row, the last (``make_attend(state_class=True)`` takes it
off and hands it to the family as ``attend.state_blocks``). A block is not
zeroed when it changes hands: a sequence that starts at position 0 reads zeros
instead of it (the family's step, on ``attend.lengths == 0``). The state has
no page identity, so what moves pages by id (``copy_pages``, the prefix cache,
the host tier, the handoff, an engine swap) does not move it, and every such
family refuses them by name, in the same words:
``models/state_class.STATE_CLASS_REFUSES`` (beside the models, because this
package imports them).

A family with NO ATTENDING LAYER (``models/brumby.py``: ``kv_layout()`` empty,
``num_kv_layers`` 0) has no ``k`` and no ``v`` leaf: its pool dict is the
state class alone, no program of it calls :func:`paged_attend` or writes a
page, and a page costs no byte (:func:`kv_page_bytes` 0), so ``max_len``
costs no memory and what bounds the batch is the state class's blocks. The
page ids and block tables STAY, as the host's bookkeeping: the scheduler
counts a sequence's length, its admission and its ``max_len`` in pages, the
table row is what carries the block id to the program, and a second
accounting for one family would be a second scheduler. They name nothing on
the device.

Device-side pieces (``paged_attend``, ``copy_pages``) are pure functions
of array arguments — block tables and lengths arrive as int32 arrays, so
requests coming and going never change a traced shape. The allocator
(``PagePool``) is host-side Python owned by the scheduler.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.attention import multihead_attention
from ..ops.dispatch import note_choice
from ..ops.paged_decode import (LATENT_GATE, PAGED_GATE,
                                latent_decode_eligible, paged_decode_eligible,
                                paged_flash_attend, paged_latent_attend)
from ..train.precision import (Quantized, dequantize_blockwise,
                               quantize_blockwise)

TRASH_PAGE = 0  # physical page id reserved for masked/idle writes

KV_DTYPES = ("fp32", "bf16", "int8")
_KV_FLOAT = {"fp32": jnp.float32, "bf16": jnp.bfloat16}


def pages_for_tokens(n_tokens: int, page_size: int) -> int:
    """Pages a sequence of ``n_tokens`` occupies."""
    return -(-n_tokens // page_size)


def num_kv_heads(config) -> int:
    """KV head count of the families whose cache is k and v of
    ``heads x head_dim`` (gpt2/neox cache full heads). A family with another
    cache states it in ``config.kv_layout()``: ask :func:`pool_layout`."""
    return getattr(config, "num_kv_heads", config.num_heads)


def pool_layout(config) -> dict:
    """``{leaf: (heads, width)}`` of one cached token in one layer, for the
    pool's two leaves. The family says what they hold: by default k and v
    of ``kv_heads x head_dim`` each; a latent-attention family
    (``models/mla.py``) gives ``config.kv_layout()``: ``v`` the latent row
    ``c_kv`` (the absorbed form's values AND the first part of its keys),
    ``k`` the one rope key all heads share, padded to whole lane tiles; a
    family with 64-wide heads (``models/lfm2.py``) packs two kv heads into
    one 128-wide row, the width the compiled kernel takes."""
    layout = getattr(config, "kv_layout", None)
    if layout is not None:
        return layout()
    shape = (num_kv_heads(config), config.head_size)
    return {"k": shape, "v": shape}


def key_parts(config) -> int:
    """Pool rows a key lies in (``config.key_parts``, default 1). A key wider
    than the value row (``models/mimo_v2.py``: 192 beside 128) is padded to
    that many rows; part ``j`` of layer ``l`` is the k pool's layer ``l *
    parts + j``, so every leaf keeps rows of one lane tile, which the
    compiled kernel reads where they lie."""
    return getattr(config, "key_parts", 1)


def is_latent(config) -> bool:
    """True where the pool holds latent rows (``config.latent_cache``): the
    attend is then the absorbed or the decompressed latent form."""
    return bool(getattr(config, "latent_cache", False))


def num_kv_layers(config) -> int:
    """Layers that have k and v pages: all of them unless the family says
    (``config.num_kv_layers``: a hybrid's attention layers)."""
    return getattr(config, "num_kv_layers", config.num_layers)


def state_layout(config) -> Optional[tuple]:
    """``(state layers, rows, width)`` of the per-page recurrent state a
    family keeps beside k and v (``config.state_layout()``), or None."""
    layout = getattr(config, "state_layout", None)
    return None if layout is None else layout()


WINDOW_LEAVES = ("k_win", "v_win")   # the window class's pools


def window_layout(config) -> Optional[dict]:
    """The second page class a family states (``config.window_kv_layout()``):
    ``{"layers": window layers, "window": W, "k": (heads, width), "v":
    (heads, width)}``, or None for the one-class families."""
    layout = getattr(config, "window_kv_layout", None)
    return None if layout is None else layout()


def window_page_span(start: int, n_tokens: int, window: int,
                     page_size: int) -> range:
    """The logical pages a window layer must hold while a sequence writes
    tokens ``start .. start + n_tokens - 1``: from the page of the oldest
    position the first of them sees to the page of the last."""
    return range(max(start - (window - 1), 0) // page_size,
                 (start + n_tokens - 1) // page_size + 1)


def window_pages_bound(window: int, page_size: int, n_slots: int,
                       chunk: int) -> int:
    """Window-class pages an engine needs so that no reservation can fail:
    the trash page, what every slot holds between two steps (the pages of
    ``window`` positions and of ONE write more, the one a decode program
    enqueued behind the one in flight makes, however they straddle), and what
    ONE prefill chunk holds while it runs (one chunk runs at a time)."""
    def most(n_tokens):     # pages that `window - 1 + n_tokens` positions
        return (window + n_tokens - 3) // page_size + 2     # can straddle

    return 1 + n_slots * most(2) + most(chunk)


# the state class's pools: the state, a short convolution's last rows, the
# state's normaliser
SEQUENCE_LEAVES = ("seq_state", "seq_conv", "seq_norm")


def sequence_state_layout(config) -> Optional[dict]:
    """The state class a family states (``config.sequence_state_layout()``):
    ``{leaf: (shape of one sequence's block, state layers first; "fp32" or
    None for the pool's float dtype)}`` over :data:`SEQUENCE_LEAVES`, or None
    for the families whose state, if any, rides the pages."""
    layout = getattr(config, "sequence_state_layout", None)
    return None if layout is None else layout()


def sequence_state_bytes(config, n_blocks: int = 1, kv_dtype=None) -> int:
    """Resident bytes of ``n_blocks`` blocks of the state class (0 where the
    family has none): what ONE live sequence costs beside its pages at
    ``n_blocks = 1``."""
    layout = sequence_state_layout(config)
    if layout is None:
        return 0
    name = kv_dtype_name(config, kv_dtype)
    return n_blocks * sum(
        math.prod(shape) * jnp.dtype(_state_dtype(storage or name)).itemsize
        for shape, storage in layout.values())


def kv_dtype_name(config, kv_dtype=None) -> str:
    """Normalize the engine's ``kv_dtype=`` knob: None inherits the
    model's storage dtype (the pre-quantization behavior), otherwise one
    of ``KV_DTYPES``. The name — not a jnp dtype — is the canonical form
    because "int8" is payload + scales, not a single dtype."""
    if kv_dtype is None:
        return "bf16" if jnp.dtype(config.dtype) == jnp.bfloat16 else "fp32"
    name = str(kv_dtype).lower()
    alias = {"float32": "fp32", "bfloat16": "bf16"}
    name = alias.get(name, name)
    if name not in KV_DTYPES:
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, got "
                         f"{kv_dtype!r}")
    return name


def quantize_kv(x: jax.Array) -> Quantized:
    """Block-wise absmax int8 of one or more k/v vectors: the block is
    the trailing ``head_dim`` axis, so each (position, kv-head) vector
    quantizes independently with one fp32 scale (``scale`` keeps a
    trailing size-1 block axis — the ``train/precision.py`` container
    contract). Pure per token, which is what keeps replay/verify writes
    bitwise reproducible (module docstring)."""
    return quantize_blockwise(x, block_size=x.shape[-1])


def dequantize_kv(qt: Quantized, dtype=jnp.float32) -> jax.Array:
    return dequantize_blockwise(qt, dtype=dtype)


def pool_nbytes(pages: dict) -> int:
    """Resident bytes of a pools dict, summed over LEAVES — the one place
    that knows a quantized pool's fp32 scales count too (consumed by the
    monolith's ``kv_cache_bytes`` and the disagg facade's report, so the
    two can never diverge on what 'pool bytes' means)."""
    return int(sum(x.nbytes for x in jax.tree.leaves(pages)))


def resolve_attend_impl(impl: str, head_dim: int, page_size: int,
                        latent_rope_width: Optional[int] = None
                        ) -> tuple[str, str]:
    """``(impl, reason)`` for the paged attend family of one engine —
    decode, verify and chunk forwards all resolve the same way, because
    the kernel's shape gate is T-independent. ``"xla"`` is the gather
    reference. ``"flash"`` is the user's choice: on a TPU backend a shape
    the compiled kernel cannot take raises HERE, at engine construction,
    instead of inside the first forward of a live request (off-TPU the
    kernel runs interpreted and takes any shape). ``"auto"`` picks the
    kernel on TPU when the shape passes the gate and the gather path
    otherwise, and says which and why.

    A latent pool (``latent_rope_width`` given; ``head_dim`` is then the
    latent row's width) resolves its DECODE step this way, to the
    ``paged_latent_attend`` kernel; its prefill chunks always decompress the
    gathered rows (``paged_attend``'s ``expand``)."""
    if impl not in ("auto", "flash", "xla"):
        raise ValueError(f"attend_impl must be 'auto', 'flash' or 'xla', "
                         f"got {impl!r}")
    backend = jax.default_backend()
    if latent_rope_width is None:
        eligible = paged_decode_eligible(head_dim, page_size)
        kernel, gate = "paged flash", PAGED_GATE
    else:
        eligible = latent_decode_eligible(head_dim, latent_rope_width,
                                          page_size)
        kernel, gate = "paged latent", LATENT_GATE
    if impl == "flash":
        if backend == "tpu" and not eligible:
            raise ValueError(
                f"attend_impl='flash': head_dim {head_dim} with page_size "
                f"{page_size} is not a shape the compiled {kernel} "
                f"kernel takes ({gate}) — use attend_impl='xla'")
        return impl, "forced"
    if impl == "xla":
        return impl, "forced"
    if backend != "tpu":
        return "xla", f"auto: backend is {backend}, not tpu"
    if not eligible:
        return "xla", (f"auto: head_dim {head_dim} / page_size {page_size} "
                       f"fails the {kernel} kernel's gate ({gate})")
    return "flash", (f"auto: tpu backend, shape passes the {kernel} "
                     f"kernel's gate")


def resolve_attend_for(config, impl: str, page_size: int) -> tuple[str, str]:
    """:func:`resolve_attend_impl` for a model's own cache layout."""
    layout = pool_layout(config)
    if not layout:      # no attending layer: there is no attend to resolve
        return "none", "the family has no attending layer"
    if not is_latent(config):   # the pool ROW's width (a packed row: 128)
        return resolve_attend_impl(impl, layout["k"][1], page_size)
    return resolve_attend_impl(impl, layout["v"][1], page_size,
                               latent_rope_width=layout["k"][1])


def _state_dtype(name: str):
    """A state pool is stored in float: the pool's own, fp32 beside int8."""
    return _KV_FLOAT["fp32" if name == "int8" else name]


def kv_page_bytes(config, *, page_size: int, n_pages: int = 1,
                  kv_dtype=None, window_class: bool = False) -> int:
    """Resident bytes of ``n_pages`` KV pages for this model at
    ``kv_dtype`` (None = the model's storage dtype): pages x layers x
    page_size x, summed over the pool's leaves (:func:`pool_layout`: k and
    v of ``kv_heads x head_dim``, or a latent family's row and rope key,
    lane padding counted), heads x (width x payload-itemsize [+ 4 B fp32
    scale per vector under int8 — the scales are pool state and are priced,
    not hidden]) — the per-slot serving cost is this at ``n_pages =
    pages_for_tokens(context)`` (train/preflight.py reports that table).
    The layers are those that attend (:func:`num_kv_layers`); a page's
    recurrent-state rows (:func:`state_layout`) are part of its price.
    ``window_class``: the same for a page of the second class
    (:func:`window_layout`)."""
    name = kv_dtype_name(config, kv_dtype)
    rows = {"k": key_parts(config)}     # pool rows a token's leaf holds
    if window_class:
        layout = window_layout(config)
        itemsize = jnp.dtype(_KV_FLOAT[name]).itemsize
        return (n_pages * layout["layers"] * page_size * itemsize
                * sum(rows.get(leaf, 1) * layout[leaf][0] * layout[leaf][1]
                      for leaf in ("k", "v")))
    per_token = sum(
        rows.get(leaf, 1) * heads * (
            width + 4 if name == "int8"
            else width * jnp.dtype(_KV_FLOAT[name]).itemsize)
        for leaf, (heads, width) in pool_layout(config).items())
    per_page = num_kv_layers(config) * page_size * per_token
    state = state_layout(config)
    if state is not None:
        layers, rows, width = state
        per_page += layers * rows * width * jnp.dtype(
            _state_dtype(name)).itemsize
    return n_pages * per_page


def init_pages(config, n_pages: int, page_size: int, kv_dtype=None,
               n_window_pages: Optional[int] = None,
               n_state_blocks: Optional[int] = None) -> dict:
    """Zeroed page pools {"k","v"}: STACKED [L, n_pages, page_size, heads,
    width] arrays, which every program takes, carries through its layer scan
    and returns whole (:func:`paged_attend`'s contract; page 0 of every
    layer is that layer's trash page), each leaf's (heads, width) from
    :func:`pool_layout`, or
    :class:`Quantized` (int8 payload of that shape + fp32 scales [L,
    n_pages, page_size, heads, 1]) under ``kv_dtype="int8"``. Zero scales
    dequantize to the same zero pool the float form starts with. L counts
    the layers that attend (:func:`num_kv_layers`). A family with per-page
    recurrent state (:func:`state_layout`) gets a third leaf ``"state"``
    ``[state layers, n_pages, rows, width]``, in the pool's float dtype. A
    family with a second page class (:func:`window_layout`) gets ``"k_win"``
    and ``"v_win"`` ``[window layers, n_window_pages, page_size, heads,
    width]``, float. A family with a state class
    (:func:`sequence_state_layout`) gets its leaves ``[state layers,
    n_state_blocks, ...]``."""
    name = kv_dtype_name(config, kv_dtype)

    parts = key_parts(config)
    if parts > 1 and name == "int8":
        raise ValueError("a key in several pool rows is stored in float")

    def pool(heads, width, rows=1):
        shape = (rows * num_kv_layers(config), n_pages, page_size, heads,
                 width)
        if name == "int8":
            return Quantized(q=jnp.zeros(shape, jnp.int8),
                             scale=jnp.zeros(shape[:-1] + (1,), jnp.float32))
        return jnp.zeros(shape, _KV_FLOAT[name])

    pages = {leaf: pool(*shape, rows=parts if leaf == "k" else 1)
             for leaf, shape in pool_layout(config).items()}
    state = state_layout(config)
    if state is not None:
        layers, rows, width = state
        pages["state"] = jnp.zeros((layers, n_pages, rows, width),
                                   _state_dtype(name))
    second = window_layout(config)
    if second is not None:
        if name == "int8" or n_window_pages is None:
            raise ValueError("a window page class is stored in float and "
                             "needs its page count (n_window_pages)")
        for leaf, key in zip(WINDOW_LEAVES, ("k", "v")):
            layers = second["layers"] * (parts if key == "k" else 1)
            pages[leaf] = jnp.zeros(
                (layers, n_window_pages, page_size, *second[key]),
                _KV_FLOAT[name])
    blocks = sequence_state_layout(config)
    if blocks is not None:
        if name == "int8" or n_state_blocks is None:
            raise ValueError("a state class is stored in float and needs "
                             "its block count (n_state_blocks)")
        for leaf, ((layers, *shape), storage) in blocks.items():
            pages[leaf] = jnp.zeros((layers, n_state_blocks, *shape),
                                    _state_dtype(storage or name))
    return pages


class PagePool:
    """Host-side refcounted free-list allocator over physical page ids
    1..n_pages-1 (page 0 is the trash page). Allocation is all-or-nothing:
    a request either gets every page asked for or none (backpressure — the
    scheduler refuses or preempts instead of corrupting a running
    sequence). ``share`` adds references to live pages (prefix sharing);
    ``free`` drops one reference per page and re-lists at zero.

    The free list is LIFO (recently-freed pages re-issue first, keeping
    the hot working set compact) with a parallel SET for membership — the
    old ``p in list`` scan made ``free`` O(n_free) per page, quadratic
    eviction at large pools.
    """

    def __init__(self, n_pages: int, page_size: int,
                 n_window_pages: Optional[int] = None,
                 n_state_blocks: Optional[int] = None):
        # the second page class's free list (kv_pages.window_layout): the
        # same allocator over its own id space, or None
        self.window = (None if n_window_pages is None
                       else PagePool(n_window_pages, page_size))
        # the state class's (kv_pages.sequence_state_layout): a block a
        # sequence, ids 1..n_state_blocks-1 (block 0 the trash block), or None
        self.state = (None if n_state_blocks is None
                      else PagePool(n_state_blocks, 1))
        if n_pages < 2:
            raise ValueError(f"n_pages must be >= 2 (page {TRASH_PAGE} is "
                             f"the reserved trash page), got {n_pages}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.n_pages = n_pages
        self.page_size = page_size
        self._free = list(range(n_pages - 1, TRASH_PAGE, -1))
        self._free_set = set(self._free)
        self._refs = [0] * n_pages      # live reference count per page

    @property
    def capacity(self) -> int:
        """Total allocatable pages (trash page excluded)."""
        return self.n_pages - 1

    @property
    def n_free(self) -> int:
        return len(self._free)

    def refcount(self, page: int) -> int:
        return self._refs[page]

    def describe(self, page: int) -> str:
        """One-line holder context for a page id — refcount, free-list
        membership, and the pool's pressure — so a validation error from
        a thousand-iteration chaos trace localizes itself instead of
        printing a bare id."""
        if not 0 <= page < self.n_pages:
            state = f"out of range (valid ids {TRASH_PAGE + 1}.."\
                    f"{self.n_pages - 1})"
        elif page == TRASH_PAGE:
            state = "the reserved trash page"
        else:
            state = (f"refcount {self._refs[page]}, "
                     + ("free-listed" if page in self._free_set else "held"))
        return (f"page {page}: {state}; pool {self.n_free}/{self.capacity} "
                f"free")

    def alloc(self, n: int) -> Optional[list[int]]:
        """``n`` pages at refcount 1 each, or None (never a partial
        grant)."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        if n > len(self._free):
            return None
        if n == 0:
            return []
        pages = self._free[-n:]
        del self._free[-n:]
        self._free_set.difference_update(pages)
        for p in pages:
            self._refs[p] = 1
        return pages

    def share(self, pages: list[int]) -> None:
        """Take one additional reference on each (already-live) page."""
        for p in pages:
            if not (TRASH_PAGE < p < self.n_pages) or self._refs[p] < 1:
                raise ValueError(f"sharing unallocated page id {p} "
                                 f"({self.describe(p)})")
        for p in pages:
            self._refs[p] += 1

    def free(self, pages: list[int]) -> None:
        """Release one reference per page; a page re-enters the free list
        exactly when its count hits zero. Validation (range, no release
        past the live count — including duplicates within one call) runs
        BEFORE any mutation, so a bad batch leaves the pool intact."""
        releases: dict[int, int] = {}
        for p in pages:
            if not (TRASH_PAGE < p < self.n_pages):
                raise ValueError(f"freeing invalid page id {p} "
                                 f"({self.describe(p)})")
            releases[p] = releases.get(p, 0) + 1
            if p in self._free_set or releases[p] > self._refs[p]:
                raise ValueError(
                    f"double free of page {p} ({self.describe(p)}; this "
                    f"batch releases it {releases[p]}x)")
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)
                self._free_set.add(p)


def pool_audit(pool: "PagePool", holder_maps, *, tier=None,
               window_holder_maps=None, state_holder_maps=None) -> None:
    """The per-iteration capacity identity, extended for the host tier.

    ``holder_maps``: iterables of ``{page: n_refs}`` — one map per
    holder class (slot tables, prefix cache, in-flight handoffs).
    Asserts each page's refcount equals its holder count, no held page
    sits on the free list, and

        free + distinct held pages == capacity

    Spilled pages FREE their HBM slots at spill time, so tiering leaves
    this identity unchanged; the tier's own ledger (``bytes_used ==
    sum(record bytes) <= budget``, ``spilled_pages == sum(record
    pages)``) audits separately via ``tier.audit()`` when one is
    attached. ``window_holder_maps``: the same maps for the pool's second
    page class (``pool.window``), which is audited alike, as are
    ``state_holder_maps`` for the state class's blocks (``pool.state``).
    Raises ``AssertionError`` naming the first imbalance."""
    if window_holder_maps is not None:
        pool_audit(pool.window, window_holder_maps)
    if state_holder_maps is not None:
        pool_audit(pool.state, state_holder_maps)
    held: dict = {}
    for m in holder_maps:
        for p, n in m.items():
            held[p] = held.get(p, 0) + n
    for p, n in held.items():
        assert pool.refcount(p) == n, \
            f"page {p}: {n} holders but refcount {pool.refcount(p)} " \
            f"({pool.describe(p)})"
        assert p not in pool._free_set, \
            f"held page {p} on the free list ({pool.describe(p)})"
    assert pool.n_free + len(held) == pool.capacity, (
        f"capacity audit failed: free={pool.n_free} + "
        f"held={len(held)} != capacity={pool.capacity}")
    if tier is not None:
        tier.audit()


def paged_attend(q, k_new, v_new, k_pages, v_pages, layer, tables, lengths,
                 *, window=None, scale=None, softcap=None, impl: str = "auto",
                 n_valid=None, latent_rope=None, expand=None, sink=None,
                 subscope: Optional[str] = None):
    """Scatter each slot's new k/v into its pages of layer ``layer``, then
    attend q over the slot's block-table context there.

    THE ATTEND CONTRACT, stated here for every caller (``make_attend``,
    ``serve/sharding.make_sharded_attend``, the families'
    ``paged_decode_step``, the drafter): the pools are the STACKED ones the
    engine holds, k_pages/v_pages [L, P, page, Hkv, D] (or ``Quantized``
    payload + scales), and ``layer`` an int32 scalar, traced in the layer
    scan (``models/llama.scan_paged_layers``), which carries the pools
    WHOLE. Everything here addresses them by ``layer``: the write is a
    scatter of S x T rows at ``[layer, page, offset]``, the kernels read
    page ``layer * P + tables[s, i]`` of the pools as they lie, the gather
    path reads ``pool[layer, tables]``. No layer's pool is ever sliced out
    of, or stacked back into, the ``[L, P, ...]`` arrays: that cost three
    reads and three writes of every pool in every step.

    q [S, T, Hq, D]; k_new/v_new [S, T, Hkv, D]; tables [S, M] int32
    physical page ids (0-filled rows/tails route to the layer's trash
    page); lengths [S] int32 = tokens already cached per slot —
    the T new tokens land at positions ``lengths[s] + 0..T-1``. T == 1 is
    the decode step; T > 1 is a prefill chunk attending over its own
    (already-scattered) tokens plus the cached history, or a speculative
    VERIFICATION step (serve/engine.py ``verify_for``: T = k+1 candidate
    tokens per slot, all slots at once). ``n_valid`` [S] (default T)
    marks how many of the T tokens are REAL — the padded tail of a final
    chunk (or of a slot that drafted fewer than k candidates) scatters to
    the trash page and its query rows are ignored by the caller.

    Rejected speculation needs no cleanup here: the engine simply rolls
    ``lengths`` back to the accepted prefix, and the NEXT call's scatter
    overwrites the dead k/v in place — every position up to a query's own
    is either live history or rewritten by the same call's scatter before
    the attend, and the causal mask cuts everything past it.

    impl: "flash" routes the call — at ANY T — through the Pallas
    block-table kernel (``ops/paged_decode.py``, query-tile block_q=T):
    the forward then reads O(live pages) once and materializes nothing
    context-sized, with the read amortized over the T query rows. "xla"
    gathers the table into a [S, M*page, Hkv, D] logical view (a
    TRANSIENT the size of the attended context) and attends with the
    einsum reference — the parity baseline. "auto" picks flash on TPU
    when the shapes satisfy the Mosaic tile gate, xla otherwise (off-TPU
    the kernel only runs interpreted — CI exercises it explicitly; the
    gather path is the faster CPU program). The gate is T-independent,
    so "auto" resolves decode, verify, and chunk forwards to the SAME
    family — the construction the spec-on == spec-off identity leans on.

    Positions past ``lengths + n_valid`` hold garbage (trash page / stale
    pages) and are cut by the causal mask — logical position of token j
    in a slot's context is j, so the standard (positions, kv_positions)
    masking applies unchanged, window/scale/softcap included (Gemma-2
    decodes through this same path).

    LATENT pools (``latent_rope`` = the rope key's live columns; the family
    passes it, ``models/mla.py``): ``v`` holds a token's latent row
    ``c_kv`` [1, C], ``k`` the rope key all heads share [1, Rw]; a token's
    key is ``[c_kv | k_rope]`` and its value ``c_kv`` again. Two forms, the
    same sum: ABSORBED (``expand`` None; the decode step) takes q
    [S, T, H, C + R] with the key up-projection folded in and returns
    [S, T, H, C], through ``paged_latent_attend`` on the pool as stored
    under "flash" or the gathered rows under "xla"; DECOMPRESSED
    (``expand(c_kv [S, N, C], k_rope [S, N, R]) -> (k, v) [S, N, H, D]``;
    a prefill chunk, whose query tile no kernel here takes) gathers the
    slot's rows whatever ``impl`` says, expands them per head and attends
    in blocks of query rows.

    ``sink`` [Hq]: a learned logit a query head that joins each row's
    softmax as one more column with no value (both impls). A key wider than
    a pool row lies in several (:func:`key_parts`: q and k_new ``[.., parts *
    D]``, ``k_pages [parts * L, ...]``); the result is a value row wide,
    ``[S, T, Hq, D]``. ``subscope`` (``"full"`` / ``"window"``: a two-class
    family's page class) puts the read half under ``attend_full`` /
    ``attend_window`` inside ``attend``.

    Returns (attn [S, T, Hq, D], (k_pages, v_pages) updated, stacked).
    """
    k_pages, v_pages, t_idx = _scatter_new(k_new, v_new, k_pages, v_pages,
                                           layer, tables, lengths, n_valid)
    if latent_rope is not None:
        return _attend_latent(q, k_pages, v_pages, layer, tables, lengths,
                              t_idx, scale=scale, impl=impl, rope=latent_rope,
                              expand=expand)
    return _attend_pages(q, k_pages, v_pages, layer, tables, lengths, t_idx,
                         window=window, scale=scale, softcap=softcap,
                         impl=impl, sink=sink, subscope=subscope)


@jax.named_scope("kv_write")
def _scatter_new(k_new, v_new, k_pages, v_pages, layer, tables, lengths,
                 n_valid):
    """The write half of :func:`paged_attend`: each slot's T new k/v rows
    into its pages of ``layer``, S x T rows scattered into the stacked
    pools. Returns the pools and the [S, T] absolute positions."""
    quantized = isinstance(k_pages, Quantized)
    s, t = k_new.shape[0], k_new.shape[1]
    page = (k_pages.q if quantized else k_pages).shape[2]
    m = tables.shape[1]
    slot = jnp.arange(s)
    t_idx = lengths[:, None] + jnp.arange(t)[None, :]          # [S, T]
    # clip the page lookup (an out-of-range gather would CLAMP to the last
    # table column — a real allocated page) and route anything past the
    # valid token count to the trash page explicitly
    phys = tables[slot[:, None], jnp.minimum(t_idx // page, m - 1)]
    if n_valid is not None:
        phys = jnp.where(t_idx < (lengths + n_valid)[:, None], phys,
                         TRASH_PAGE)
    off = t_idx % page
    at = (layer, phys, off)       # S x T rows of the stacked leaf
    d = (k_pages.q if quantized else k_pages).shape[-1]   # a pool row
    parts = k_new.shape[-1] // d
    if parts > 1:   # a key in `parts` pool rows: part j at layer l*parts + j
        for j in range(parts):
            k_pages = k_pages.at[(layer * parts + j, phys, off)].set(
                k_new[..., j * d:(j + 1) * d].astype(k_pages.dtype))
        v_pages = v_pages.at[at].set(v_new.astype(v_pages.dtype))
        return k_pages, v_pages, t_idx
    if quantized:
        # quantize-at-write: each new token's [Hkv, D] vector becomes int8
        # payload + one fp32 scale, scattered to the SAME (page, offset) —
        # the scale is pool state with page identity, nothing more
        kq, vq = quantize_kv(k_new), quantize_kv(v_new)
        k_pages = Quantized(q=k_pages.q.at[at].set(kq.q),
                            scale=k_pages.scale.at[at].set(kq.scale))
        v_pages = Quantized(q=v_pages.q.at[at].set(vq.q),
                            scale=v_pages.scale.at[at].set(vq.scale))
    else:
        k_pages = k_pages.at[at].set(k_new.astype(k_pages.dtype))
        v_pages = v_pages.at[at].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages, t_idx


@jax.named_scope("attend")
def _attend_pages(q, k_pages, v_pages, layer, tables, lengths, t_idx, *,
                  window, scale, softcap, impl, sink=None, subscope=None):
    """The read half of :func:`paged_attend`: q over each slot's block-table
    context in ``layer``, after the new tokens were scattered."""
    if subscope is not None:    # a two-class family's layer, by its class
        with (jax.named_scope("attend_window") if subscope == "window"
              else jax.named_scope("attend_full")):
            return _attend_pages(q, k_pages, v_pages, layer, tables, lengths,
                                 t_idx, window=window, scale=scale,
                                 softcap=softcap, impl=impl, sink=sink)
    more = {} if sink is None else {"sink": sink}
    quantized = isinstance(k_pages, Quantized)
    s = q.shape[0]
    page = (k_pages.q if quantized else k_pages).shape[2]
    # a key wider than a pool row lies in `parts` of them (key_parts)
    parts = 1 if quantized else q.shape[-1] // k_pages.shape[-1]
    if impl == "auto":
        impl, reason = resolve_attend_impl(impl, q.shape[-1] // parts, page)
        note_choice("paged_attend", impl, reason)
    if impl == "flash":
        # block_q = T: the same kernel serves the decode step (T == 1),
        # the verify forward, and a prefill chunk — the scatter above
        # already landed the T tokens (pad tails in the trash page), so
        # the kernel's per-row causal mask sees exactly the gather
        # path's semantics
        if quantized:
            attn = paged_flash_attend(
                q, k_pages.q, v_pages.q, layer, tables, lengths,
                k_scale=k_pages.scale[..., 0], v_scale=v_pages.scale[..., 0],
                window=window, scale=scale, softcap=softcap)
        else:
            attn = paged_flash_attend(q, k_pages, v_pages, layer, tables,
                                      lengths, window=window, scale=scale,
                                      softcap=softcap, **more)
        return attn, (k_pages, v_pages)

    # one gather from the stacked leaf: the layer's pages the tables name
    if quantized:
        # gather payload AND scales through the table, dequantize the
        # gathered view (context-sized transient, same as the float
        # gather) — the POOL itself never materializes in float
        kg = dequantize_kv(Quantized(q=k_pages.q[layer, tables],
                                     scale=k_pages.scale[layer, tables]),
                           q.dtype)
        vg = dequantize_kv(Quantized(q=v_pages.q[layer, tables],
                                     scale=v_pages.scale[layer, tables]),
                           q.dtype)
    elif parts > 1:
        kg = jnp.concatenate([k_pages[layer * parts + j, tables]
                              for j in range(parts)], axis=-1)
        vg = v_pages[layer, tables]
    else:
        kg = k_pages[layer, tables]               # [S, M, page, Hkv, D]
        vg = v_pages[layer, tables]
    tot = kg.shape[1] * page
    kg = kg.reshape(s, tot, *kg.shape[3:])
    vg = vg.reshape(s, tot, *vg.shape[3:])
    kv_pos = jnp.broadcast_to(jnp.arange(tot)[None, :], (s, tot))
    attn = multihead_attention(q, kg, vg, causal=True,
                               positions=t_idx,
                               kv_positions=kv_pos, impl="xla",
                               standard_layout=False, window=window,
                               scale=scale, logit_softcap=softcap, **more)
    return attn, (k_pages, v_pages)


LATENT_Q_BLOCK = 256    # query rows whose [H, rows, context] scores are live


@jax.named_scope("attend")
def _attend_latent(q, k_pages, v_pages, layer, tables, lengths, t_idx, *,
                   scale, impl, rope, expand):
    """The read half of :func:`paged_attend` over a latent pool."""
    if isinstance(k_pages, Quantized):
        raise ValueError("a latent pool is stored in float: int8 KV is not "
                         "implemented for latent attention")
    s, t, h, _ = q.shape
    page, c = v_pages.shape[2], v_pages.shape[-1]
    if expand is None:
        if impl == "auto":
            impl, reason = resolve_attend_impl(
                impl, c, page, latent_rope_width=k_pages.shape[-1])
            note_choice("paged_attend", impl, reason)
        if impl == "flash":
            return (paged_latent_attend(q, k_pages, v_pages, layer, tables,
                                        lengths, scale=scale),
                    (k_pages, v_pages))
    tot = tables.shape[1] * page
    ckv = v_pages[layer, tables].reshape(s, tot, c)              # [S, N, C]
    kr = k_pages[layer, tables].reshape(s, tot, -1)[..., :rope]  # [S, N, R]
    kv_pos = jnp.arange(tot)
    if expand is None:     # absorbed, over the gathered rows: the parity
        # baseline of the kernel, in float32 throughout (the CPU's runtime
        # has no bf16 x bf16 = f32 product of this shape inside a scan)
        keys = jnp.concatenate([ckv, kr], axis=-1).astype(jnp.float32)
        scores = jnp.einsum("sthw,snw->shtn", q.astype(jnp.float32),
                            keys) * scale
        mask = t_idx[:, None, :, None] >= kv_pos[None, None, None, :]
        probs = jax.nn.softmax(
            jnp.where(mask, scores, jnp.finfo(jnp.float32).min), axis=-1)
        attn = jnp.einsum("shtn,snc->sthc", probs, keys[..., :c])
        return attn.astype(q.dtype), (k_pages, v_pages)
    k, v = expand(ckv, kr)                                # [S, N, H, D]
    kv_positions = jnp.broadcast_to(kv_pos[None, :], (s, tot))

    def rows(args):     # one block of query rows against the whole context
        qb, pb = args
        return multihead_attention(
            qb, k, v, causal=True, positions=pb, kv_positions=kv_positions,
            impl="xla", standard_layout=False, scale=scale)

    if t <= LATENT_Q_BLOCK or t % LATENT_Q_BLOCK:
        attn = rows((q, t_idx))
    else:
        nb = t // LATENT_Q_BLOCK
        qs = q.reshape(s, nb, LATENT_Q_BLOCK, h, -1).swapaxes(0, 1)
        ps = t_idx.reshape(s, nb, LATENT_Q_BLOCK).swapaxes(0, 1)
        attn = jax.lax.map(rows, (qs, ps)).swapaxes(0, 1)
        attn = attn.reshape(s, t, h, -1)
    return attn, (k_pages, v_pages)


def make_attend(tables, lengths, *, impl: str = "auto", n_valid=None,
                state_class: bool = False):
    """Bind (tables, lengths, impl, n_valid) into the attend callback the
    family ``paged_decode_step`` hooks call in every layer with the stacked
    pools and the layer's index (:func:`paged_attend`'s contract). A latent
    family adds ``latent_rope`` (and ``expand`` for a chunk) to its call. A
    two-class family names the ``page_class`` of the pools it hands over
    (``"full"`` / ``"window"``): ``tables`` is then ``[S, 2 M]`` and the call
    takes its class's half (module docstring). ``state_class``: the tables'
    LAST column is each slot's block of the state class, taken off here and
    handed to the family as ``attend.state_blocks`` beside ``attend.lengths``
    and ``attend.n_valid``."""
    blocks = None
    if state_class:
        tables, blocks = tables[:, :-1], tables[:, -1]

    def attend(q, k_new, v_new, k_pages, v_pages, layer, *, window=None,
               scale=None, softcap=None, page_class=None, **more):
        mine = tables
        if page_class is not None:
            half = tables.shape[1] // 2
            mine = (tables[:, :half] if page_class == "full"
                    else tables[:, half:])
            more["subscope"] = page_class
        return paged_attend(q, k_new, v_new, k_pages, v_pages, layer, mine,
                            lengths, window=window, scale=scale,
                            softcap=softcap, impl=impl, n_valid=n_valid,
                            **more)

    # the same tables address a family's per-page recurrent state
    attend.read_state = partial(read_state, tables=tables, lengths=lengths)
    attend.write_state = partial(write_state, tables=tables, lengths=lengths,
                                 n_valid=n_valid)
    attend.state_blocks, attend.lengths, attend.n_valid = (blocks, lengths,
                                                           n_valid)
    return attend


def read_state(state, layer, page: int, *, tables, lengths):
    """Each slot's recurrent state after its PREVIOUS token, ``[S, rows,
    width]``, out of the per-page pool ``state [Ls, P, rows, width]`` at
    state layer ``layer``: the row of the page that holds token ``lengths -
    1``; zeros for a sequence with nothing cached yet (a reused slot starts
    from nothing, whatever its pages' last owner left)."""
    s, m = tables.shape
    col = jnp.clip((lengths - 1) // page, 0, m - 1)
    rows = state[layer, tables[jnp.arange(s), col]]
    return jnp.where((lengths > 0)[:, None, None], rows, 0)


@jax.named_scope("kv_write")
def write_state(state, layer, page: int, history, *, tables, lengths,
                n_valid=None):
    """The write half: ``history [S, rows + T, width]`` is each slot's state
    before the call followed by the T rows its new tokens add, so the state
    after new token i is ``history[i + 1 : i + 1 + rows]``. Every page the
    call writes tokens to gets the state after the LAST of them (a decode
    step: its one page; a chunk: each page it completes and the one it ends
    in); columns past the valid tokens go to the trash page."""
    s, m = tables.shape
    rows = state.shape[2]
    t = history.shape[1] - rows
    valid = jnp.full((s,), t, jnp.int32) if n_valid is None else n_valid
    end = lengths + valid                               # [S] first unwritten
    n_cols = (t + page - 2) // page + 1                 # pages T tokens touch
    col = (lengths // page)[:, None] + jnp.arange(n_cols)[None, :]
    last = jnp.minimum((col + 1) * page, end[:, None]) - 1      # [S, n_cols]
    touched = last >= jnp.maximum(col * page, lengths[:, None])
    phys = tables[jnp.arange(s)[:, None], jnp.minimum(col, m - 1)]
    phys = jnp.where(touched, phys, TRASH_PAGE)
    first = jnp.clip(last - lengths[:, None], 0, t - 1) + 1     # history row
    idx = (first[..., None] + jnp.arange(rows)).reshape(s, n_cols * rows)
    new = jnp.take_along_axis(history, idx[..., None], axis=1)
    new = new.reshape(s, n_cols, rows, -1).astype(state.dtype)
    return state.at[layer, phys].set(new)


@jax.named_scope("kv_write")
def copy_pages(pools, src, dst, window=None):
    """Copy-on-write fork: duplicate physical page ``src`` into ``dst``
    across every layer of every pool leaf ([L, P, ...]: k, v, a family's
    per-page state; src/dst are traced scalars, so one compile serves every
    fork). The scheduler calls this before any write lands in a page whose
    refcount is > 1. Tree-generic over the pool leaves, so a quantized
    pool's scales fork WITH their payload — a dst page whose scales still
    described the old content would dequantize garbage. The second page
    class's leaves (``WINDOW_LEAVES``) have ids of their own: ``window =
    (src, dst)`` forks one of ITS pages in the same call, and without it
    they pass through untouched. The state class's leaves
    (``SEQUENCE_LEAVES``) have no page identity and always pass through."""

    def fork(a, src=src, dst=dst):
        return a.at[:, dst].set(a[:, src])

    apart = WINDOW_LEAVES + SEQUENCE_LEAVES
    if not (isinstance(pools, dict) and any(n in pools for n in apart)):
        return jax.tree.map(fork, pools)
    out = jax.tree.map(fork, {name: leaf for name, leaf in pools.items()
                              if name not in apart})
    for name in apart:
        if name in pools:
            forks = name in WINDOW_LEAVES and window is not None
            out[name] = fork(pools[name], *window) if forks else pools[name]
    return out
