"""Paged-KV primitives: allocator invariants, the gather-based attend vs
the contiguous-cache reference, and the byte pricing the preflight report
uses. Pure serve/kv_pages.py coverage — the engine-level behavior
(scheduling, parity, backpressure) lives in test_serve.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.ops.attention import multihead_attention
from distributed_training_guide_tpu.serve.kv_pages import (
    TRASH_PAGE, PagePool, _scatter_new, kv_page_bytes, paged_attend,
    pages_for_tokens)
from tests.test_paged_decode import stacked_pool

pytestmark = pytest.mark.serve


# ---- allocator --------------------------------------------------------------

def test_pool_never_hands_out_the_trash_page():
    pool = PagePool(n_pages=8, page_size=4)
    got = pool.alloc(pool.capacity)
    assert got is not None and TRASH_PAGE not in got
    assert sorted(got) == list(range(1, 8))


def test_pool_all_or_nothing_and_backpressure():
    pool = PagePool(n_pages=6, page_size=4)   # 5 usable
    a = pool.alloc(3)
    assert len(a) == 3 and pool.n_free == 2
    assert pool.alloc(3) is None              # refuse, don't partially grant
    assert pool.n_free == 2                   # refusal left the pool intact
    pool.free(a)
    assert pool.alloc(5) is not None


def test_pool_free_validates():
    pool = PagePool(n_pages=6, page_size=4)
    pages = pool.alloc(2)
    pool.free(pages)
    with pytest.raises(ValueError, match="double free"):
        pool.free([pages[0]])
    with pytest.raises(ValueError, match="invalid page"):
        pool.free([TRASH_PAGE])
    with pytest.raises(ValueError, match="invalid page"):
        pool.free([99])


def test_pool_errors_carry_holder_context():
    """The localization satellite: validation errors name the page's
    refcount / free-list state and the pool's pressure — a bare id out
    of a thousand-iteration chaos trace was needlessly slow to chase."""
    pool = PagePool(n_pages=6, page_size=4)
    [p] = pool.alloc(1)
    pool.free([p])
    with pytest.raises(ValueError,
                       match=rf"page {p}: refcount 0, free-listed"):
        pool.free([p])
    with pytest.raises(ValueError, match=r"pool \d+/5 free"):
        pool.share([p])
    with pytest.raises(ValueError, match="out of range"):
        pool.free([99])
    with pytest.raises(ValueError, match="trash page"):
        pool.free([TRASH_PAGE])
    # a batch with duplicates reports how often the batch releases it
    [q] = pool.alloc(1)
    with pytest.raises(ValueError, match="releases it 2x"):
        pool.free([q, q])
    assert pool.refcount(q) == 1              # validated before mutation


def test_pool_refcount_lifecycle():
    """share/free reference counting: a page re-enters the free list at
    the LAST release exactly, sharing a dead page is refused, and a batch
    releasing more references than exist fails without mutating."""
    pool = PagePool(n_pages=6, page_size=4)
    [p] = pool.alloc(1)
    assert pool.refcount(p) == 1
    pool.share([p])
    pool.share([p])
    assert pool.refcount(p) == 3
    pool.free([p])
    pool.free([p])
    assert pool.refcount(p) == 1 and pool.n_free == 4   # still held
    pool.free([p])
    assert pool.refcount(p) == 0 and pool.n_free == 5   # last release
    with pytest.raises(ValueError, match="double free"):
        pool.free([p])
    with pytest.raises(ValueError, match="unallocated"):
        pool.share([p])
    # duplicate ids past the live count fail BEFORE any mutation
    [q] = pool.alloc(1)
    with pytest.raises(ValueError, match="double free"):
        pool.free([q, q])
    assert pool.refcount(q) == 1


def test_pool_free_list_is_lifo_with_set_membership():
    """The satellite fix: membership checks moved to a set, but reissue
    order stays LIFO (recently freed pages come back first, keeping the
    hot working set compact)."""
    pool = PagePool(n_pages=10, page_size=4)
    a = pool.alloc(4)
    pool.free(a)
    assert pool.alloc(4) == a                   # LIFO reissue
    assert pool._free_set == set(pool._free)    # set mirrors the list


def test_pages_for_tokens_rounds_up():
    assert pages_for_tokens(1, 16) == 1
    assert pages_for_tokens(16, 16) == 1
    assert pages_for_tokens(17, 16) == 2


def test_kv_page_bytes_formula():
    from distributed_training_guide_tpu.models import get_model

    cfg = get_model("llama-debug", dtype=jnp.float32).config
    # pages x layers x 2 (k,v) x page_size x kv_heads x head_dim x 4 bytes
    expect = 3 * cfg.num_layers * 2 * 16 * cfg.num_kv_heads * cfg.head_size * 4
    assert kv_page_bytes(cfg, page_size=16, n_pages=3) == expect


# ---- device-side ops --------------------------------------------------------

def _contiguous_reference(q, k_ctx, v_ctx, length):
    """Attend q over the first ``length`` contiguous positions (the
    dense-cache decode math)."""
    t = k_ctx.shape[0]
    kv_pos = jnp.arange(t)[None, :]
    return multihead_attention(
        q[None], k_ctx[None], v_ctx[None], causal=True,
        positions=jnp.asarray([[length]]), kv_positions=kv_pos,
        impl="xla", standard_layout=False)[0]


LAYER = 1      # of the three-layer pools ``stacked_pool`` makes


def test_paged_attend_matches_contiguous_cache():
    """Scatter a known contiguous k/v history into shuffled physical pages,
    then paged_attend must equal attention over the contiguous buffer —
    per slot, at different lengths, including the freshly written token."""
    page, n_pages, hkv, hq, d = 4, 16, 2, 4, 8
    s, m = 3, 4                               # 3 slots, 4 logical pages each
    rng = np.random.default_rng(0)
    lengths = np.array([5, 0, 11], np.int32)  # new token positions per slot
    # physical layout: shuffled non-overlapping pages per slot
    phys = rng.permutation(np.arange(1, n_pages))
    tables = np.zeros((s, m), np.int32)
    for i in range(s):
        tables[i] = phys[i * m:(i + 1) * m]

    ctx = rng.standard_normal((s, m * page, hkv, d)).astype(np.float32)
    k_pages = np.zeros((n_pages, page, hkv, d), np.float32)
    v_pages = np.zeros((n_pages, page, hkv, d), np.float32)
    vctx = rng.standard_normal((s, m * page, hkv, d)).astype(np.float32)
    for i in range(s):
        for t in range(int(lengths[i])):      # history: tokens 0..len-1
            k_pages[tables[i, t // page], t % page] = ctx[i, t]
            v_pages[tables[i, t // page], t % page] = vctx[i, t]

    q = rng.standard_normal((s, 1, hq, d)).astype(np.float32)
    k_new = rng.standard_normal((s, 1, hkv, d)).astype(np.float32)
    v_new = rng.standard_normal((s, 1, hkv, d)).astype(np.float32)

    out, (nkp, nvp) = jax.jit(paged_attend)(
        q, k_new, v_new, stacked_pool(k_pages, LAYER),
        stacked_pool(v_pages, LAYER), LAYER,
        jnp.asarray(tables), jnp.asarray(lengths))

    for i in range(s):
        n = int(lengths[i])
        k_ctx = np.concatenate([ctx[i, :n], k_new[i]], axis=0)
        v_ctx = np.concatenate([vctx[i, :n], v_new[i]], axis=0)
        ref = _contiguous_reference(jnp.asarray(q[i]), jnp.asarray(k_ctx),
                                    jnp.asarray(v_ctx), n)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        # the write landed at the slot's own (page, offset)
        np.testing.assert_array_equal(
            np.asarray(nkp[LAYER, tables[i, n // page], n % page]),
            k_new[i, 0])


def test_paged_attend_idle_slot_writes_to_trash():
    """A zeroed table row + length 0 (an idle lane of the fixed decode
    batch) must scatter into the layer's own page 0 only — allocated pages
    and the other layer stay bitwise untouched."""
    page, n_pages, h, d = 4, 6, 2, 8
    k_pages = jnp.asarray(
        np.random.default_rng(1).standard_normal((n_pages, page, h, d)),
        jnp.float32)
    v_pages = k_pages + 1
    tables = jnp.zeros((1, 2), jnp.int32)
    q = jnp.ones((1, 1, h, d), jnp.float32)
    kv = jnp.ones((1, 1, h, d), jnp.float32)
    kst, vst = stacked_pool(k_pages, LAYER), stacked_pool(v_pages, LAYER)
    _, (nkp, nvp) = paged_attend(q, kv, kv, kst, vst, LAYER, tables,
                                 jnp.zeros(1, jnp.int32))
    np.testing.assert_array_equal(np.asarray(nkp[LAYER, 1:]),
                                  np.asarray(k_pages[1:]))
    np.testing.assert_array_equal(np.asarray(nvp[LAYER, 1:]),
                                  np.asarray(v_pages[1:]))
    np.testing.assert_array_equal(np.asarray(nkp[LAYER, TRASH_PAGE, 0]),
                                  np.ones((h, d), np.float32))
    np.testing.assert_array_equal(np.asarray(nkp[0]), np.asarray(kst[0]))
    np.testing.assert_array_equal(np.asarray(nvp[0]), np.asarray(vst[0]))


def test_paged_attend_multi_token_chunk_matches_contiguous():
    """The chunked-prefill contract: T new tokens scatter at positions
    lengths..lengths+T-1 and attend over history + themselves; the padded
    tail (past n_valid) scatters to the trash page only."""
    page, n_pages, hkv, hq, d = 4, 12, 2, 4, 8
    m, t, hist = 4, 6, 5                     # 5 cached tokens, 6-token chunk
    rng = np.random.default_rng(7)
    tables = np.asarray([[3, 7, 2, 9]], np.int32)
    ctx = rng.standard_normal((hist + t, hkv, d)).astype(np.float32)
    vctx = rng.standard_normal((hist + t, hkv, d)).astype(np.float32)
    k_pages = np.zeros((n_pages, page, hkv, d), np.float32)
    v_pages = np.zeros((n_pages, page, hkv, d), np.float32)
    for j in range(hist):
        k_pages[tables[0, j // page], j % page] = ctx[j]
        v_pages[tables[0, j // page], j % page] = vctx[j]

    q = rng.standard_normal((1, t, hq, d)).astype(np.float32)
    real = 4                                  # final-chunk padding: 2 pad
    out, (nkp, nvp) = jax.jit(paged_attend, static_argnames=())(
        q, ctx[None, hist:], vctx[None, hist:], stacked_pool(k_pages, LAYER),
        stacked_pool(v_pages, LAYER), LAYER, jnp.asarray(tables),
        jnp.asarray([hist], jnp.int32), n_valid=jnp.asarray([real]))
    nkp = np.asarray(nkp)[LAYER]

    # real chunk rows equal attention over the contiguous history + chunk
    kv_pos = jnp.arange(hist + t)[None]
    ref = multihead_attention(
        q, jnp.asarray(ctx)[None], jnp.asarray(vctx)[None], causal=True,
        positions=jnp.asarray([[hist + j for j in range(t)]]),
        kv_positions=kv_pos, impl="xla", standard_layout=False)
    np.testing.assert_allclose(np.asarray(out)[0, :real],
                               np.asarray(ref)[0, :real],
                               rtol=1e-5, atol=1e-5)
    # real tokens landed at their logical (page, offset)
    for j in range(real):
        pos = hist + j
        np.testing.assert_array_equal(
            nkp[tables[0, pos // page], pos % page], ctx[pos])
    # pad tokens went to the trash page; the slot's own next positions are
    # untouched (still zero)
    for j in range(real, t):
        pos = hist + j
        assert not nkp[tables[0, pos // page], pos % page].any()


def test_copy_pages_forks_one_physical_page():
    """The CoW device copy: src duplicated into dst across all layers,
    everything else bitwise untouched."""
    from distributed_training_guide_tpu.serve.kv_pages import copy_pages

    rng = np.random.default_rng(8)
    kp = rng.standard_normal((2, 6, 4, 2, 8)).astype(np.float32)
    vp = rng.standard_normal((2, 6, 4, 2, 8)).astype(np.float32)
    nkp, nvp = jax.jit(copy_pages)((jnp.asarray(kp), jnp.asarray(vp)),
                                   jnp.asarray(3), jnp.asarray(5))
    nkp, nvp = np.asarray(nkp), np.asarray(nvp)
    np.testing.assert_array_equal(nkp[:, 5], kp[:, 3])
    np.testing.assert_array_equal(nvp[:, 5], vp[:, 3])
    others = [0, 1, 2, 3, 4]
    np.testing.assert_array_equal(nkp[:, others], kp[:, others])
    np.testing.assert_array_equal(nvp[:, others], vp[:, others])


def _scatter_every_layer(k_pages, v_pages, k_new, v_new, table_row, start,
                         n_valid):
    """A prefill chunk's write as the chunk program makes it: one slot's
    ``[L, T, h, d]`` new rows through ``_scatter_new``, a layer at a time
    (the layer scan), into the stacked pools."""
    @jax.jit
    def write(kp, vp):
        for layer in range(k_new.shape[0]):
            kp, vp, _ = _scatter_new(
                jnp.asarray(k_new[layer])[None], jnp.asarray(v_new[layer])[None],
                kp, vp, layer, table_row[None],
                jnp.asarray([start], jnp.int32),
                jnp.asarray([n_valid], jnp.int32))
        return kp, vp

    # tree-generic: an int8 pool is a Quantized pair of leaves
    return jax.tree.map(np.asarray, write(*jax.tree.map(
        jnp.asarray, (k_pages, v_pages))))


def test_chunk_scatter_skips_shared_prefix_start():
    """A chunk that starts past a shared prefix (``lengths`` = the shared
    length, what ``Admission.shared_len`` seats the slot at) writes its own
    positions only — the prefix page other sequences read through is never
    rewritten."""
    layers, page, n_pages, h, d = 2, 4, 8, 2, 4
    rng = np.random.default_rng(9)
    marker = rng.standard_normal((layers, page, h, d)).astype(np.float32)
    k_pages = np.zeros((layers, n_pages, page, h, d), np.float32)
    k_pages[:, 5] = marker                    # the shared page's content
    v_pages = np.zeros_like(k_pages)
    k_dense = rng.standard_normal((layers, 8, h, d)).astype(np.float32)
    v_dense = rng.standard_normal((layers, 8, h, d)).astype(np.float32)
    table_row = jnp.asarray([5, 3, 0, 0], jnp.int32)

    # positions 0-3 are shared (page 5); the chunk is positions 4.. of a
    # 6-token prompt, padded to 4 rows
    nkp, _ = _scatter_every_layer(k_pages, v_pages, k_dense[:, 4:],
                                  v_dense[:, 4:], table_row, start=4,
                                  n_valid=2)
    np.testing.assert_array_equal(nkp[:, 5], marker)        # untouched
    for t in (4, 5):                                        # committed
        np.testing.assert_array_equal(nkp[:, 3, t % page], k_dense[:, t])


def test_chunk_scatter_routes_pad_tail_to_trash():
    """A padded final chunk: real tokens land in the slot's pages in
    logical order, the padded tail goes to page 0, other pages untouched."""
    layers, page, n_pages, h, d = 2, 4, 8, 2, 4
    chunk, n_tokens = 8, 6
    rng = np.random.default_rng(2)
    k_pages = np.zeros((layers, n_pages, page, h, d), np.float32)
    v_pages = np.zeros_like(k_pages)
    k_dense = rng.standard_normal((layers, chunk, h, d)).astype(np.float32)
    v_dense = rng.standard_normal((layers, chunk, h, d)).astype(np.float32)
    table_row = jnp.asarray([5, 3, 0, 0], jnp.int32)

    nkp, nvp = _scatter_every_layer(k_pages, v_pages, k_dense, v_dense,
                                    table_row, start=0, n_valid=n_tokens)
    for t in range(n_tokens):
        pg = [5, 3][t // page]
        np.testing.assert_array_equal(nkp[:, pg, t % page], k_dense[:, t])
        np.testing.assert_array_equal(nvp[:, pg, t % page], v_dense[:, t])
    untouched = [p for p in range(1, n_pages) if p not in (5, 3)]
    assert not nkp[:, untouched].any() and not nvp[:, untouched].any()
    # the slot's own next positions (6, 7: the pad rows' logical places)
    # are still zero: the tail went to the trash page, not past the prompt
    assert not nkp[:, 3, 2:].any() and not nvp[:, 3, 2:].any()
