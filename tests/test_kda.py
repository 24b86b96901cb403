"""``ops/kda.py`` on the CPU: the chunked scan against the recurrent step
applied token by token against a plain scan written here from the three
lines of the recurrence, in both forms (``jnp`` and the Pallas kernel,
interpreted); the step's Pallas kernel (interpreted) against the gather /
scatter path on a pool it must update in place."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.ops import kda

H, D = 3, 16
IMPLS = pytest.mark.parametrize("impl", ["xla", "pallas"])


def form(impl):
    """The keywords that pick one form (a kernel interpreted: this is the
    CPU)."""
    return {"impl": impl, **({"interpret": True} if impl == "pallas" else {})}


def chunk(impl):
    return jax.jit(lambda *a: kda.kda_chunk(*a, **form(impl)))


def rows(rng, shape, decay=(0.001, 1.7)):
    """Rows of the recurrence at ``shape = (..., heads)``: unit k, q of
    length 1 / sqrt(d), log-decays in ``-decay``, beta in (0, 2)."""
    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(*shape, D))) / D ** 0.5
    k = unit(rng.normal(size=(*shape, D)))
    v = rng.normal(size=(*shape, D))
    g = -rng.uniform(*decay, size=(*shape, D))
    beta = 2 / (1 + np.exp(-rng.normal(size=shape)))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def plain_scan(s0, q, k, v, g, beta, n_valid):
    """The recurrence as its three lines say it, one token at a time, in
    float64 numpy: S' = diag(alpha) S; S = S' + beta k (v - k^T S')^T; o =
    S^T q. Tokens past ``n_valid`` leave the state alone."""
    s = np.asarray(s0, np.float64).copy()
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    out = np.zeros(v.shape)
    for b in range(q.shape[0]):
        for t in range(int(n_valid[b])):
            for h in range(q.shape[2]):
                sp = np.exp(g[b, t, h])[:, None] * s[b, h]
                s[b, h] = sp + beta[b, t, h] * np.outer(
                    k[b, t, h], v[b, t, h] - k[b, t, h] @ sp)
                out[b, t, h] = s[b, h].T @ q[b, t, h]
    return out, s


@IMPLS
@pytest.mark.parametrize("t, n_valid, heads", [
    (150, (150, 97), H), (64, (64, 1), H), (7, (7, 3), H),
    (130, (130, 5), 32)],
    ids=["two_blocks_and_a_part", "one_block", "short", "four_head_tiles"])
def test_chunk_is_the_step_token_by_token_is_the_plain_scan(t, n_valid,
                                                            heads, impl):
    """T not a multiple of the block, ``n_valid`` < T and inside the first
    block, a chunk that starts from a non-zero state; 3 heads are one tile
    of the kernel, 32 are four."""
    rng = np.random.default_rng(t)
    r = rows(rng, (2, t, heads))
    s0 = jnp.asarray(rng.normal(size=(2, heads, D, D)), jnp.float32)
    nv = jnp.asarray(n_valid)
    assert heads % kda.CHUNK_HEAD_TILE == 0 or heads < kda.CHUNK_HEAD_TILE
    o, s_t = chunk(impl)(s0, *r, nv)
    want_o, want_s = plain_scan(s0, *r, n_valid)
    s, outs = s0, []
    for i in range(t):
        o_i, s_new = kda.delta_step(s, *(x[:, i] for x in r))
        s = jnp.where((i < nv)[:, None, None, None], s_new, s)
        outs.append(o_i)
    stepped = jnp.stack(outs, axis=1)
    for b, n in enumerate(n_valid):
        assert np.max(np.abs(o[b, :n] - want_o[b, :n])) < 2e-5
        assert np.max(np.abs(stepped[b, :n] - want_o[b, :n])) < 2e-5
    assert np.max(np.abs(s_t - want_s)) < 2e-5
    assert np.max(np.abs(s - want_s)) < 2e-5
    assert np.max(np.abs(want_s - np.asarray(s0))) > 0.1


def test_a_state_rounded_to_bfloat16_a_step_is_outside_the_tolerance():
    """What the tolerance above holds: S carried in float32. The same steps
    with S rounded to bfloat16 after each one (a narrower state class) end
    hundreds of tolerances away, so such a state cannot pass these tests;
    the benchmark's comparison of served tokens would not see it (PERF.md
    section 7)."""
    rng = np.random.default_rng(11)
    r = rows(rng, (2, 150, H), decay=(0.001, 0.1))
    s0 = jnp.zeros((2, H, D, D), jnp.float32)
    want_o, want_s = plain_scan(s0, *r, (150, 150))
    s, sound = s0, s0
    for i in range(150):
        o, s = kda.delta_step(s, *(x[:, i] for x in r))
        s = s.astype(jnp.bfloat16).astype(jnp.float32)
        o_sound, sound = kda.delta_step(sound, *(x[:, i] for x in r))
    assert np.max(np.abs(o_sound - want_o[:, -1])) < 2e-5
    assert np.max(np.abs(sound - want_s)) < 2e-5
    assert np.max(np.abs(s - want_s)) > 100 * 2e-5
    assert np.max(np.abs(o - want_o[:, -1])) > 20 * 2e-5


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_pool_narrower_than_float32_is_refused_by_name(impl):
    rng = np.random.default_rng(5)
    pool = jnp.zeros((1, 3, H, D, D), jnp.bfloat16)
    with pytest.raises(TypeError, match="state pool is float32, got bfloat16"):
        kda.kda_step(pool, jnp.asarray([1, 2]), 0, *rows(rng, (2, H)),
                     impl=impl, interpret=True)


@IMPLS
def test_a_whole_block_at_the_strongest_decay_does_not_overflow(impl):
    """alpha = 0.2 a step over two whole blocks: the running log-decay of a
    block reaches -103, whose negative no float32 ``exp`` survives; every
    ``exp`` the chunk takes is of a difference <= 0."""
    rng = np.random.default_rng(1)
    t = 2 * kda.BLOCK
    q, k, v, _, beta = rows(rng, (1, t, H))
    g = jnp.full((1, t, H, D), float(np.log(0.2)), jnp.float32)
    assert float(-jnp.sum(g[0, :kda.BLOCK, 0, 0])) > 88.8   # log(float32 max)
    s0 = jnp.asarray(rng.normal(size=(1, H, D, D)), jnp.float32)
    o, s_t = chunk(impl)(s0, q, k, v, g, beta)
    want_o, want_s = plain_scan(s0, q, k, v, g, beta, (t,))
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(s_t)))
    assert np.max(np.abs(o - want_o)) < 2e-5
    assert np.max(np.abs(s_t - want_s)) < 2e-5


@IMPLS
def test_a_factor_of_the_decay_that_underflows_is_where_the_value_does(impl):
    """The kernel takes ``exp(G_t - G_s)`` across sub-blocks as ``exp(G_t -
    G_r) exp(G_r - G_s)`` with r the first row of t's sub-block. alpha = 0.2
    a step leaves the second factor ``exp(-77)`` from the block's first row
    to its fourth sub-block (48 steps), and a channel at alpha = 0.1 leaves
    ``exp(-110)``, which float32 holds as 0: the product is 0 only where the
    pairwise decay itself is, so the float64 scan is still met; the channels
    that hardly decay keep early tokens in play beside them."""
    rng = np.random.default_rng(6)
    t = kda.BLOCK + 3 * kda.SUB
    q, k, v, _, beta = rows(rng, (1, t, H))
    g = np.full((1, t, H, D), np.log(0.2))
    g[..., :4], g[..., 4:8] = np.log(0.1), -0.001
    assert np.exp(np.float32(3 * kda.SUB * g[0, 0, 0, 0])) == 0.0
    g = jnp.asarray(g, jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(1, H, D, D)), jnp.float32)
    o, s_t = chunk(impl)(s0, q, k, v, g, beta)
    want_o, want_s = plain_scan(s0, q, k, v, g, beta, (t,))
    assert np.max(np.abs(o - want_o)) < 2e-5
    assert np.max(np.abs(s_t - want_s)) < 2e-5


def test_a_kernel_on_bfloat16_operands_is_outside_the_tolerance(monkeypatch):
    """What the tolerance holds of the kernel's products: float32 operands
    at full precision. The same kernel with every product's operands rounded
    to bfloat16 (what ``Precision.DEFAULT`` does to a float32 product on the
    MXU) ends tens of tolerances away; the benchmark's comparison of served
    tokens would not see it (PERF.md section 7)."""
    rng = np.random.default_rng(12)
    r = rows(rng, (1, 150, H), decay=(0.001, 0.1))
    s0 = jnp.asarray(rng.normal(size=(1, H, D, D)), jnp.float32)
    want_o, want_s = plain_scan(s0, *r, (150,))
    o, s_t = chunk("pallas")(s0, *r)
    assert np.max(np.abs(o - want_o)) < 2e-5
    assert np.max(np.abs(s_t - want_s)) < 2e-5
    sound = kda._mm

    def rounded(a, b, contract):
        return sound(*(x.astype(jnp.bfloat16).astype(jnp.float32)
                       for x in (a, b)), contract)

    # the kernel's call is traced once a shape (``_chunk_pallas`` is a jit)
    monkeypatch.setattr(kda, "_mm", rounded)
    kda._chunk_pallas.clear_cache()
    try:
        o, s_t = chunk("pallas")(s0, *r)
    finally:
        kda._chunk_pallas.clear_cache()
    assert np.max(np.abs(s_t - want_s)) > 50 * 2e-5
    assert np.max(np.abs(o - want_o)) > 20 * 2e-5


def test_a_gradient_through_the_kernel_is_the_jnp_forms():
    """The kernel has no backward of its own: a stack that trains on a TPU
    differentiates the ``jnp`` form at the kernel's inputs."""
    rng = np.random.default_rng(8)
    r = rows(rng, (1, 70, H))
    s0 = jnp.asarray(rng.normal(size=(1, H, D, D)), jnp.float32)

    def loss(impl, s0, *r):
        o, s_t = kda.kda_chunk(s0, *r, **form(impl))
        return jnp.sum(o * o) + jnp.sum(s_t)

    got, want = (jax.grad(functools.partial(loss, impl), argnums=range(6))(
        s0, *r) for impl in ("pallas", "xla"))
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(b))) > 1e-3
        assert np.max(np.abs(a - b)) < 2e-5 * max(1, float(jnp.max(jnp.abs(b))))


@IMPLS
def test_a_chunk_without_a_real_token_hands_its_state_on(impl):
    rng = np.random.default_rng(2)
    r = rows(rng, (1, 20, H))
    s0 = jnp.asarray(rng.normal(size=(1, H, D, D)), jnp.float32)
    _, s_t = chunk(impl)(s0, *r, jnp.asarray([0]))
    assert np.array_equal(np.asarray(s_t), np.asarray(s0))


@pytest.mark.parametrize("heads", [3, 32], ids=["one_tile", "two_tiles"])
def test_step_kernel_updates_the_pool_where_it_lies(heads):
    """The Pallas kernel (interpreted) against the gather / jnp / scatter
    path: the slots' blocks of the one layer updated, every other block and
    layer as it was; two idle slots share the trash block."""
    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.normal(size=(2, 6, heads, D, D)), jnp.float32)
    ids = jnp.asarray([4, kda.TRASH_BLOCK, 2, kda.TRASH_BLOCK, 5])
    q, k, v, g, beta = (x[..., :heads] if x.ndim == 2 else x for x in (
        jnp.asarray(a) for a in rows(rng, (5, heads))))
    o_k, new_k = kda.kda_step(pool, ids, 1, q, k, v, g, beta, impl="pallas",
                              interpret=True)
    o_x, new_x = kda.kda_step(pool, ids, 1, q, k, v, g, beta, impl="xla")
    held = np.array([0, 2, 4])
    assert np.max(np.abs(o_k[held] - o_x[held])) < 1e-6
    assert np.max(np.abs(new_k[1, [4, 2, 5]] - new_x[1, [4, 2, 5]])) < 1e-6
    want_o, want_s = kda.delta_step(pool[1, ids[held]], q[held], k[held],
                                    v[held], g[held], beta[held])
    assert np.max(np.abs(new_k[1, ids[held]] - want_s)) < 1e-6
    assert np.max(np.abs(o_k[held] - want_o)) < 1e-6
    for new in (new_k, new_x):
        assert np.array_equal(new[0], pool[0])
        assert np.array_equal(new[1, [1, 3]], pool[1, [1, 3]])


@IMPLS
def test_a_log_decay_of_minus_infinity_is_the_zero_state(impl):
    """What ``models/solar_open2.py`` hands a decode step for a sequence at
    position 0: the block's last owner's state decays to exactly zero."""
    rng = np.random.default_rng(4)
    pool = jnp.asarray(rng.normal(size=(1, 3, H, D, D)), jnp.float32)
    q, k, v, g, beta = rows(rng, (2, H))
    fresh = jnp.full_like(g, -jnp.inf)
    o, new = kda.kda_step(pool, jnp.asarray([1, 2]), 0, q, k, v, fresh, beta,
                          **form(impl))
    want_o, want_s = kda.delta_step(jnp.zeros((2, H, D, D)), q, k, v, g, beta)
    assert np.max(np.abs(new[0, 1:] - want_s)) < 1e-6
    assert np.max(np.abs(o - want_o)) < 1e-6


def test_both_forms_carry_their_names():
    """``kda_step`` / ``kda_chunk`` are in the lowered programs' op names
    whatever implements them (``utils/trace.py``: KERNELS)."""
    from distributed_training_guide_tpu.utils import trace

    rng = np.random.default_rng(5)
    r = rows(rng, (1, 8, H))
    s0 = jnp.zeros((1, H, D, D))
    for impl in ("xla", "pallas"):
        text = chunk(impl).lower(s0, *r).as_text(debug_info=True)
        assert "kda_chunk" in text and "kda_chunk" in trace.KERNELS
    pool = jnp.zeros((1, 2, H, D, D))
    step = jax.jit(lambda p, *r: kda.kda_step(p, jnp.asarray([1]), 0, *r,
                                              impl="xla")).lower(
        pool, *(x[:, 0] for x in r)).as_text(debug_info=True)
    assert "kda_step" in step and "kda_step" in trace.KERNELS
    assert "kda" in trace.SUBSCOPES and "serve.state" in trace.SPANS
