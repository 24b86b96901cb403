"""Grouped (ragged) GEMM coverage: every impl vs a dense per-row reference,
Pallas (interpret) vs XLA-fallback parity, gradients, and the zero-tail
contract the EP dispatch relies on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.ops.grouped_matmul import (
    _VMEM_BUDGET, _bwd_blocks, _gmm_bytes, _tgmm_bytes, gmm_blocks,
    grouped_matmul, work_items)

pytestmark = pytest.mark.grouped

# (m, k, n, sizes) — ragged group shapes incl. empty experts, a group
# spanning everything, tile-unaligned dims, and a garbage tail (sum < m)
SHAPES = [
    (16, 8, 12, [3, 0, 9, 4]),
    (64, 16, 24, [10, 0, 0, 30, 24]),
    (32, 8, 8, [0, 0, 0]),
    (40, 8, 8, [5, 5, 5, 5]),          # sum < m: tail rows must be zero
    (33, 7, 9, [33, 0, 0, 0, 0, 0]),   # one group takes all, odd dims
    (24, 8, 8, [1, 1, 1, 21]),
]


def _reference(lhs, rhs, sizes):
    seg = np.repeat(np.arange(len(sizes)), sizes)
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    for i, s in enumerate(seg):
        out[i] = lhs[i] @ rhs[s]
    return out


def _inputs(m, k, n, g, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(m, k), jnp.float32),
            jnp.asarray(rng.randn(g, k, n), jnp.float32))


# ``group_offset``: the G matrices lie in a stack of 3 G, at its start, in
# its middle or at its end (None: no stack, the call as it always was)
STACK = 3
OFFSETS = pytest.mark.parametrize(
    "at", [None, 0, 1, 2], ids=["no-offset", "offset-zero", "offset-middle",
                                "offset-last"])


def _stacked(m, k, n, g, at, seed=0):
    """``(lhs, rhs as handed over, its G matrices, the offset or None)``."""
    if at is None:
        lhs, rhs = _inputs(m, k, n, g, seed)
        return lhs, rhs, rhs, None
    lhs, stack = _inputs(m, k, n, STACK * g, seed)
    return lhs, stack, stack[at * g:(at + 1) * g], jnp.int32(at * g)


@OFFSETS
@pytest.mark.parametrize("impl", ["scan", "einsum", "ragged", "pallas"])
@pytest.mark.parametrize("m,k,n,sizes", SHAPES)
def test_matches_dense_reference(impl, m, k, n, sizes, at):
    lhs, rhs, mine, offset = _stacked(m, k, n, len(sizes), at)
    sz = jnp.asarray(sizes, jnp.int32)
    out = jax.jit(lambda l, r, s, o: grouped_matmul(
        l, r, s, group_offset=o, impl=impl, block_rows=8,
        block_cols=8))(lhs, rhs, sz, offset)
    np.testing.assert_allclose(np.asarray(out),
                               _reference(np.asarray(lhs), np.asarray(mine),
                                          sizes), rtol=1e-5, atol=1e-5)


def test_tail_rows_are_zero_with_zero_grad():
    """Rows past sum(group_sizes) produce zeros AND zero gradient — the
    contract the expert-parallel local-slice window depends on (its static
    worst-case buffer carries a garbage tail)."""
    lhs, rhs = _inputs(40, 8, 8, 4, seed=3)
    sz = jnp.asarray([5, 5, 5, 5], jnp.int32)  # total 20 of 40 rows
    for impl in ("scan", "einsum", "ragged", "pallas"):
        out = grouped_matmul(lhs, rhs, sz, impl=impl, block_rows=8,
                             block_cols=8)
        assert bool(jnp.all(out[20:] == 0)), impl
        g = jax.grad(
            lambda l: jnp.sum(grouped_matmul(l, rhs, sz, impl=impl,
                                             block_rows=8, block_cols=8)**2)
        )(lhs)
        assert bool(jnp.all(g[20:] == 0)), impl


@OFFSETS
@pytest.mark.parametrize("m,k,n,sizes", SHAPES[:4])
def test_pallas_grads_match_fallback(m, k, n, sizes, at):
    """The Pallas custom_vjp (gmm for d_lhs, tgmm for d_rhs) against plain
    autodiff through the einsum fallback, on the interpret path (the same
    kernels compile on TPU). With an offset ``d_rhs`` has the stack's shape
    and is zero outside the G matrices that were read."""
    g = len(sizes)
    lhs, rhs, _, offset = _stacked(m, k, n, g, at, seed=1)
    sz = jnp.asarray(sizes, jnp.int32)

    def loss(impl):
        return jax.jit(jax.grad(
            lambda l, r: jnp.sum(grouped_matmul(l, r, sz, group_offset=offset,
                                                impl=impl, block_rows=8,
                                                block_cols=8)**2),
            argnums=(0, 1)))(lhs, rhs)

    ref_dl, ref_dr = loss("einsum")
    pal_dl, pal_dr = loss("pallas")
    np.testing.assert_allclose(np.asarray(pal_dl), np.asarray(ref_dl),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(pal_dr), np.asarray(ref_dr),
                               rtol=1e-4, atol=1e-4)
    if at is not None:
        assert pal_dr.shape == rhs.shape
        outside = np.delete(np.asarray(pal_dr),
                            np.s_[at * g:(at + 1) * g], axis=0)
        assert not outside.any()
        if sum(sizes):
            assert np.asarray(pal_dr)[at * g:(at + 1) * g].any()


@pytest.mark.parametrize("impl", ["scan", "einsum", "ragged", "pallas"])
def test_without_an_offset_the_program_is_the_one_it_was(impl):
    """``group_offset=None`` traces what the call traced before the argument
    existed: no slice of ``rhs``, four scalar-prefetched operands in the
    Pallas call. With an offset the Pallas call takes the WHOLE stack and a
    fifth scalar (no slice either: the index map adds it), and the XLA
    impls take one ``dynamic_slice`` of the stack."""
    m, k, n, g = 16, 8, 16, 4
    lhs, stack = _inputs(m, k, n, STACK * g)
    sz = jnp.asarray([3, 0, 9, 4], jnp.int32)

    def traced(rhs, **kw):
        return str(jax.make_jaxpr(lambda l, r, s: grouped_matmul(
            l, r, s, impl=impl, block_rows=8, block_cols=8, **kw))(
                lhs, rhs, sz))

    plain = traced(stack[:g])
    assert traced(stack[:g], group_offset=None) == plain
    offset = traced(stack, group_offset=jnp.int32(g))
    sliced = f"f32[{g},{k},{n}] = dynamic_slice"
    assert sliced not in plain
    if impl == "pallas":
        def scalars(text):    # the kernel's scalar-prefetched operands
            head = text.split("pallas_call[")[1].split("jaxpr={ lambda ;")[1]
            return head.split(". let")[0].count("Ref<smem>")

        assert scalars(plain) == 4 and scalars(offset) == 5
        assert sliced not in offset
    else:
        assert offset.count(sliced) == 1


@pytest.mark.parametrize("impl", ["scan", "einsum", "ragged", "pallas"])
def test_an_offset_past_the_stack_clamps_as_dynamic_slice_does(impl):
    """All four impls keep one contract at the edge too: an offset past
    ``Gtot - G`` reads the last G matrices of the stack (the kernel must not
    DMA from outside its operand)."""
    m, k, n, sizes = SHAPES[0]
    g = len(sizes)
    lhs, stack = _inputs(m, k, n, STACK * g)
    sz = jnp.asarray(sizes, jnp.int32)
    out = grouped_matmul(lhs, stack, sz, group_offset=jnp.int32(STACK * g),
                         impl=impl, block_rows=8, block_cols=8)
    np.testing.assert_allclose(
        np.asarray(out),
        _reference(np.asarray(lhs), np.asarray(stack[-g:]), sizes),
        rtol=1e-5, atol=1e-5)


def test_bf16_inputs_and_out_dtype():
    lhs, rhs = _inputs(24, 8, 8, 4, seed=2)
    sz = jnp.asarray([6, 6, 6, 6], jnp.int32)
    ref = _reference(np.asarray(lhs), np.asarray(rhs), [6, 6, 6, 6])
    for impl in ("scan", "einsum", "ragged", "pallas"):
        out = grouped_matmul(lhs.astype(jnp.bfloat16),
                             rhs.astype(jnp.bfloat16), sz, impl=impl,
                             block_rows=8, block_cols=8)
        assert out.dtype == jnp.bfloat16, impl
        np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                                   rtol=5e-2, atol=5e-2)
        f32 = grouped_matmul(lhs.astype(jnp.bfloat16),
                             rhs.astype(jnp.bfloat16), sz, impl=impl,
                             block_rows=8, block_cols=8,
                             preferred_element_type=jnp.float32)
        assert f32.dtype == jnp.float32, impl


def test_shape_and_impl_validation():
    lhs, rhs = _inputs(16, 8, 8, 4)
    sz = jnp.asarray([4, 4, 4, 4], jnp.int32)
    with pytest.raises(ValueError, match="expects lhs"):
        grouped_matmul(lhs[0], rhs, sz)
    with pytest.raises(ValueError, match="mismatch"):
        grouped_matmul(lhs, rhs[:, :4], sz)
    with pytest.raises(ValueError, match="unknown grouped_matmul impl"):
        grouped_matmul(lhs, rhs, sz, impl="cuda")
    # a stack may hold more matrices than groups only behind an offset, never
    # fewer, and the offset is one scalar
    with pytest.raises(ValueError, match="mismatch"):
        grouped_matmul(lhs, jnp.concatenate([rhs, rhs]), sz)
    with pytest.raises(ValueError, match="G or more behind a group_offset"):
        grouped_matmul(lhs, rhs[:3], sz, group_offset=jnp.int32(0))
    with pytest.raises(ValueError, match="one int32 scalar"):
        grouped_matmul(lhs, rhs, sz, group_offset=jnp.zeros((4,), jnp.int32))


# ---- the work list's geometry (gmm_blocks, _bwd_blocks) ---------------------

BF16, F32 = jnp.bfloat16, jnp.float32
# name: (M, G, K, N, dtype) -> the call's (bm, bn), its work items, and the
# backward's (bm, bn): what ``_fit_blocks`` gave BOTH directions until PR 50
BLOCK_TABLE = {
    # the four serve cells' decode steps, into an expert and out of it:
    # 4, 4, 32 and 13 rows an expert all take the least row tile
    "mistral-in": ((128, 32, 4096, 2048, BF16), (64, 512), 34, (128, 128)),
    "mistral-out": ((128, 32, 2048, 4096, BF16), (64, 512), 34, (128, 128)),
    "chat64-in": ((256, 64, 2048, 1536, BF16), (64, 512), 68, (256, 256)),
    "chat64-out": ((256, 64, 1536, 2048, BF16), (64, 512), 68, (256, 256)),
    "mimo-in": ((512, 16, 4096, 2048, BF16), (64, 512), 24, (128, 128)),
    "mimo-out": ((512, 16, 2048, 4096, BF16), (64, 512), 24, (128, 128)),
    "solar-in": ((512, 40, 4096, 1280, BF16), (64, 512), 48, (128, 128)),
    "solar-out": ((512, 40, 1280, 4096, BF16), (64, 512), 48, (128, 128)),
    # the two chunk programs inside a window (64 and 102 rows an expert)
    "chat64-chunk": ((4096, 64, 2048, 1536, BF16), (64, 512), 128,
                     (256, 256)),
    "solar-chunk": ((4096, 40, 4096, 1280, BF16), (128, 512), 72,
                    (128, 128)),
    # Laguna's train step: a thousand rows an expert keep the cap
    "laguna-in": ((32768, 32, 2048, 512, BF16), (512, 512), 96, (256, 256)),
    "laguna-out": ((32768, 32, 512, 2048, BF16), (512, 512), 96, (256, 256)),
    # fp32 operands: the same row rule, the columns priced at 4 B (the wide
    # one halves both tiles to keep K resident)
    "fp32": ((8192, 128, 2048, 768, F32), (64, 512), 256, (256, 256)),
    "fp32-few-rows": ((128, 32, 2048, 768, F32), (64, 512), 34, (128, 256)),
    "fp32-wide": ((2048, 8, 4096, 4096, F32), (128, 128), 24, (128, 128)),
}


@pytest.mark.parametrize("name", sorted(BLOCK_TABLE))
def test_block_rule_table(name):
    """The row tile follows rows-per-group, the column tile is priced on the
    forward kernel's own blocks at its operands' widths, and the backward
    keeps the blocks it had."""
    (m, g, k, n, dtype), want, items, want_bwd = BLOCK_TABLE[name]
    bm, bn = gmm_blocks(m, g, k, n, dtype, dtype)
    assert (bm, bn) == want
    assert work_items(m, g, bm) == items
    width = jnp.dtype(dtype).itemsize
    assert _gmm_bytes(bm, bn, k, width, width, 4) <= _VMEM_BUDGET
    bwd = _bwd_blocks(m, k, n)
    assert bwd == want_bwd
    wide = max(k, n)
    assert _gmm_bytes(*bwd, wide, 4, 4, 4) <= _VMEM_BUDGET
    assert _tgmm_bytes(*bwd, wide, 4, 4, 4) <= _VMEM_BUDGET


def test_block_rule_caps_floors_and_refusals():
    # block_rows / block_cols cap both kernels' blocks; the buffer's rows do
    assert gmm_blocks(32768, 32, 2048, 512, BF16, BF16,
                      block_rows=128, block_cols=256) == (128, 256)
    assert _bwd_blocks(32768, 2048, 512,
                       block_rows=128, block_cols=256) == (128, 256)
    assert gmm_blocks(8, 4, 256, 256, BF16, BF16) == (8, 256)
    assert gmm_blocks(128, 32, 256, 256, BF16, BF16, block_rows=16)[0] == 16
    # mixed widths: each operand is priced at its own bytes
    assert gmm_blocks(128, 32, 4096, 2048, BF16, F32) == (64, 256)
    # a K that 128 columns of cannot stay resident is refused by name
    with pytest.raises(ValueError, match="contraction dim"):
        gmm_blocks(128, 32, 1 << 16, 2048, F32, F32)
    with pytest.raises(ValueError, match="contraction dim"):
        _bwd_blocks(128, 1 << 16, 2048)


def test_the_call_runs_at_the_rules_blocks():
    """``grouped_matmul`` traces its Pallas call at ``gmm_blocks``' tile and
    the backward's two at the backward's: the grid says so."""
    m, g, k, n = 256, 16, 128, 256
    lhs, rhs = _inputs(m, k, n, g)
    sz = jnp.full((g,), 3, jnp.int32)

    def grids(fn, *args):
        text = str(jax.make_jaxpr(fn)(*args))
        return [tuple(int(x) for x in part.split(")")[0].split(","))
                for part in text.split("grid=(")[1:]]

    call = lambda l, r: grouped_matmul(l, r, sz, impl="pallas",
                                       interpret=True)
    bm, bn = gmm_blocks(m, g, k, n, F32, F32)
    assert (bm, bn) == (64, 256)
    assert grids(call, lhs, rhs) == [(n // bn, work_items(m, g, bm))]
    bwd = grids(jax.grad(lambda l, r: call(l, r).sum(), argnums=(0, 1)),
                lhs, rhs)
    bm_b, bn_b = _bwd_blocks(m, k, n)
    assert (bm_b, bn_b) == (256, 256)
    assert sorted(bwd) == sorted([
        (n // bn, work_items(m, g, bm)),              # the forward
        (-(-k // bn_b), work_items(m, g, bm_b)),      # d_lhs: gmm on rhs^T
        (n // bn_b, work_items(m, g, bm_b))])         # d_rhs: tgmm


# ---- decode-like lists: a row tile smaller than the buffer ------------------

# 64 rows over 16 groups in fp32 at an 8-row tile (``block_rows`` caps the
# rule's 64): groups of 1-6 rows straddle tiles, most work items past the
# first are padding, and tiles past sum(sizes) are never visited
DECODE_LISTS = {
    "straddling": [3, 6, 1, 5, 2, 6, 4, 1, 3, 5, 2, 6, 1, 4, 6, 3],
    "empty-first": [0, 0, 0, 5, 2, 6, 4, 1, 3, 5, 2, 6, 1, 4, 6, 3],
    "empty-middle": [3, 6, 1, 5, 0, 0, 0, 0, 0, 5, 2, 6, 1, 4, 6, 3],
    "empty-last": [3, 6, 1, 5, 2, 6, 4, 1, 3, 5, 0, 0, 0, 0, 0, 0],
    "few-pairs": [0, 2, 0, 0, 1, 0, 0, 0, 6, 0, 0, 0, 0, 0, 1, 0],
    "one-group": [0, 0, 0, 0, 0, 0, 0, 6, 0, 0, 0, 0, 0, 0, 0, 0],
    "all-empty": [0] * 16,
    "full": [4] * 16,
}
DECODE_M, DECODE_K, DECODE_N = 64, 32, 48


def _decode_inputs(name, at, block_rows=8):
    sizes = DECODE_LISTS[name]
    lhs, rhs, mine, offset = _stacked(DECODE_M, DECODE_K, DECODE_N,
                                      len(sizes), at, seed=5)
    bm, _ = gmm_blocks(DECODE_M, len(sizes), DECODE_K, DECODE_N, F32, F32,
                       block_rows=block_rows)
    assert bm == block_rows < DECODE_M and sum(sizes) <= DECODE_M
    return lhs, rhs, mine, offset, sizes


@pytest.mark.parametrize("at", [None, 1], ids=["no-offset", "offset-middle"])
@pytest.mark.parametrize("name", sorted(DECODE_LISTS))
def test_decode_lists_match_the_dense_reference(name, at):
    lhs, rhs, mine, offset, sizes = _decode_inputs(name, at)
    out = jax.jit(lambda l, r, s, o: grouped_matmul(
        l, r, s, group_offset=o, impl="pallas", interpret=True,
        block_rows=8))(lhs, rhs, jnp.asarray(sizes, jnp.int32), offset)
    np.testing.assert_allclose(
        np.asarray(out), _reference(np.asarray(lhs), np.asarray(mine), sizes),
        rtol=1e-5, atol=1e-5)
    assert not np.asarray(out)[sum(sizes):].any()


@pytest.mark.parametrize("block_rows", [8, 32],
                         ids=["8-row-tiles", "32-row-tiles"])
@pytest.mark.parametrize("at", [None, 1], ids=["no-offset", "offset-middle"])
@pytest.mark.parametrize("name", sorted(DECODE_LISTS))
def test_decode_lists_grads_match_the_fallback(name, at, block_rows):
    """The forward and the backward's two kernels walk the same list at a
    tile smaller than the buffer: padded items skipped, empty groups'
    ``d_rhs`` zero, rows past the pairs zero in ``d_lhs``."""
    lhs, rhs, _, offset, sizes = _decode_inputs(name, at, block_rows)
    sz = jnp.asarray(sizes, jnp.int32)
    g = len(sizes)

    def grads(impl):
        return jax.jit(jax.grad(
            lambda l, r: jnp.sum(grouped_matmul(
                l, r, sz, group_offset=offset, impl=impl, interpret=True,
                block_rows=block_rows)**2), argnums=(0, 1)))(lhs, rhs)

    (ref_dl, ref_dr), (pal_dl, pal_dr) = grads("einsum"), grads("pallas")
    np.testing.assert_allclose(np.asarray(pal_dl), np.asarray(ref_dl),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(pal_dr), np.asarray(ref_dr),
                               rtol=1e-4, atol=1e-4)
    assert not np.asarray(pal_dl)[sum(sizes):].any()
    first = 0 if at is None else at * g
    for i, size in enumerate(sizes):
        assert bool(np.asarray(pal_dr)[first + i].any()) == (size > 0)


def test_decode_list_in_bf16_at_the_rules_tile():
    """The serve path's own widths and tile: 0-4 rows an expert in bf16, the
    256-row buffer walked at the rule's 64 rows."""
    m, g, k, n = 256, 64, 32, 48
    sizes = [(3 * i) % 5 for i in range(g)]          # 0-4 rows an expert
    lhs, rhs = _inputs(m, k, n, g, seed=7)
    assert gmm_blocks(m, g, k, n, BF16, BF16)[0] == 64
    out = grouped_matmul(lhs.astype(BF16), rhs.astype(BF16),
                         jnp.asarray(sizes, jnp.int32), impl="pallas",
                         interpret=True)
    ref = _reference(np.asarray(lhs.astype(BF16), np.float32),
                     np.asarray(rhs.astype(BF16), np.float32), sizes)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref, rtol=2e-2,
                               atol=2e-2)
    assert not np.asarray(out, np.float32)[sum(sizes):].any()
