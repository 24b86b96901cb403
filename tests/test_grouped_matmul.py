"""Grouped (ragged) GEMM coverage: every impl vs a dense per-row reference,
Pallas (interpret) vs XLA-fallback parity, gradients, and the zero-tail
contract the EP dispatch relies on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.ops.grouped_matmul import grouped_matmul

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
