"""``ops/ssm.py`` on the CPU: the scan over a chunk's tokens against the
recurrent step applied token by token against the reference's scan
(``benchmarks/reference/jamba.py``'s form, ``[C, N]`` states) against a
float64 scan written here from the two lines of the recurrence, in both
forms (``jnp`` and the Pallas kernels, interpreted); the step's kernel
against the gather / scatter path on a pool it must update in place."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.ops import ssm

N, C = 16, 256
TOL = 2e-5      # float32 sums in another order: read 1e-6..4e-6
IMPLS = pytest.mark.parametrize("impl", ["xla", "pallas"])


def form(impl):
    """The keywords that pick one form (a kernel interpreted: this is the
    CPU)."""
    return {"impl": impl, **({"interpret": True} if impl == "pallas" else {})}


def rows(rng, shape, dt=(0.001, 0.1), channels=C):
    """Rows of the recurrence at ``shape = (...)``: x of order one, a step
    log-uniform in ``dt``, B and C of order one; A = -(1..N), D = 1."""
    x = rng.normal(size=(*shape, channels))
    delta = np.exp(rng.uniform(*np.log(dt), size=(*shape, channels)))
    b, c = rng.normal(size=(*shape, N)), rng.normal(size=(*shape, N))
    a = -np.broadcast_to(np.arange(1.0, N + 1)[:, None], (N, channels))
    d = np.ones(channels)
    return tuple(jnp.asarray(v, jnp.float32) for v in (x, delta, b, c, a, d))


def plain_scan(h0, x, delta, b, c, a, d, n_valid):
    """The recurrence as its two lines say it, one token at a time, in
    float64 numpy. Tokens past ``n_valid`` leave the state alone."""
    h = np.asarray(h0, np.float64).copy()
    x, delta, b, c, a, d = (np.asarray(v, np.float64)
                            for v in (x, delta, b, c, a, d))
    y = np.zeros(x.shape)
    for s in range(x.shape[0]):
        for t in range(int(n_valid[s])):
            h[s] = (np.exp(delta[s, t][None, :] * a) * h[s]
                    + (delta[s, t] * x[s, t])[None, :] * b[s, t][:, None])
            y[s, t] = c[s, t] @ h[s] + d * x[s, t]
    return y, h


def reference_scan(h0, x, delta, b, c, a, d):
    """The reference's own scan (``reference/jamba.py::mamba``'s ``step``: a
    state ``[C, N]``, one sequence), float32."""
    def step(h, row):
        x_t, delta_t, b_t, c_t = row
        h = jnp.exp(delta_t[:, None] * a.T) * h \
            + (delta_t * x_t)[:, None] * b_t[None, :]
        return h, jnp.sum(h * c_t[None, :], axis=-1) + d * x_t

    h, y = jax.lax.scan(step, h0.T, (x, delta, b, c))
    return y, h.T


@IMPLS
@pytest.mark.parametrize("t, n_valid", [
    (200, (200, 77)), (128, (128, 1)), (7, (7, 3))],
    ids=["a_block_and_a_part", "one_block", "short"])
def test_chunk_is_the_step_token_by_token_is_the_plain_scan(t, n_valid, impl):
    """T not a multiple of the kernel's token block, ``n_valid`` < T, a chunk
    that starts from a non-zero state."""
    rng = np.random.default_rng(t)
    r = rows(rng, (2, t))
    h0 = jnp.asarray(rng.normal(size=(2, N, C)), jnp.float32)
    nv = jnp.asarray(n_valid)
    y, h_t = jax.jit(lambda *a: ssm.ssm_chunk(*a, **form(impl)))(h0, *r, nv)
    want_y, want_h = plain_scan(h0, *r, n_valid)
    h, outs = h0, []
    for i in range(t):
        y_i, h_new = ssm.selective_step(h, *(v[:, i] for v in r[:4]), *r[4:])
        h = jnp.where((i < nv)[:, None, None], h_new, h)
        outs.append(y_i)
    stepped = jnp.stack(outs, axis=1)
    ref_y, ref_h = reference_scan(h0[0], *(v[0] for v in r[:4]), *r[4:])
    for s, n in enumerate(n_valid):
        assert np.max(np.abs(y[s, :n] - want_y[s, :n])) < TOL
        assert np.max(np.abs(stepped[s, :n] - want_y[s, :n])) < TOL
    assert np.max(np.abs(ref_y - want_y[0])) < TOL
    assert np.max(np.abs(ref_h - want_h[0])) < TOL
    assert np.max(np.abs(h_t - want_h)) < TOL
    assert np.max(np.abs(h - want_h)) < TOL
    assert np.max(np.abs(want_h - np.asarray(h0))) > 0.01


@IMPLS
def test_the_fastest_decay_over_a_whole_block_neither_overflows_nor_drifts(
        impl):
    """``Delta A`` at -1.6 a step (``Delta`` 0.1, ``A`` -16) over more than a
    token block: a blocked form that took ``exp`` of a difference of running
    sums would take ``e^+200``; the scan, token by token, takes none."""
    rng = np.random.default_rng(3)
    t = ssm.TOKENS + 24
    r = rows(rng, (1, t), dt=(0.0999, 0.1))
    h0 = jnp.asarray(rng.normal(size=(1, N, C)), jnp.float32)
    y, h_t = ssm.ssm_chunk(h0, *r, **form(impl))
    want_y, want_h = plain_scan(h0, *r, (t,))
    assert np.all(np.isfinite(y)) and np.all(np.isfinite(h_t))
    assert np.max(np.abs(y - want_y)) < TOL
    assert np.max(np.abs(h_t - want_h)) < TOL
    assert float(jnp.min(r[1] * r[4][-1])) < -1.59


def test_a_state_rounded_to_bfloat16_a_step_is_outside_the_tolerance():
    """What the tolerance above holds: h carried in float32. The same steps
    with h rounded to bfloat16 after each one (a narrower state class) end
    far outside it: the slow channels (``A`` -1, ``Delta`` 0.001) add a
    thousandth of their size a step, which 8 bits of mantissa drop."""
    rng = np.random.default_rng(11)
    r = rows(rng, (2, 200))
    h0 = jnp.zeros((2, N, C), jnp.float32)
    want_y, want_h = plain_scan(h0, *r, (200, 200))
    h, sound = h0, h0
    for i in range(200):
        step = tuple(v[:, i] for v in r[:4])
        y, h = ssm.selective_step(h, *step, *r[4:])
        h = h.astype(jnp.bfloat16).astype(jnp.float32)
        y_sound, sound = ssm.selective_step(sound, *step, *r[4:])
    assert np.max(np.abs(y_sound - want_y[:, -1])) < TOL
    assert np.max(np.abs(sound - want_h)) < TOL
    assert np.max(np.abs(h - want_h)) > 20 * TOL
    assert np.max(np.abs(y - want_y[:, -1])) > 20 * TOL


@IMPLS
def test_a_pool_narrower_than_float32_is_refused_by_name(impl):
    rng = np.random.default_rng(5)
    pool = jnp.zeros((1, 3, N, C), jnp.bfloat16)
    with pytest.raises(TypeError, match="state pool is float32, got bfloat16"):
        ssm.ssm_step(pool, jnp.asarray([1, 2]), 0, *rows(rng, (2,)),
                     **form(impl))


@IMPLS
def test_the_step_updates_the_slots_blocks_of_one_layer_in_place(impl):
    """Five slots on a pool of three layers and eight blocks: two idle ones
    on the trash block, one at position 0 (its block read as zeros whatever
    it holds); the slots' blocks of layer 1 are the plain step's, every
    other block and layer is left as it was."""
    rng = np.random.default_rng(7)
    pool = jnp.asarray(rng.normal(size=(3, 8, N, C)), jnp.float32)
    ids = jnp.asarray([5, ssm.TRASH_BLOCK, 2, ssm.TRASH_BLOCK, 7])
    fresh = jnp.asarray([False, False, True, False, False])
    r = rows(rng, (5,))
    y, new = jax.jit(lambda pool: ssm.ssm_step(
        pool, ids, 1, *r, fresh, **form(impl)))(pool)
    y, new, pool = (np.asarray(v) for v in (y, new, pool))
    held = [0, 2, 4]
    ids = np.asarray(ids)
    h0 = pool[1, ids].copy()
    h0[2] = 0.0
    want_y, want_h = (np.asarray(v) for v in ssm.selective_step(
        jnp.asarray(h0), *r))
    assert np.max(np.abs(y[held] - want_y[held])) < TOL
    assert np.max(np.abs(new[1, ids[held]] - want_h[held])) < TOL
    others = [b for b in range(8) if b not in (5, 2, 7, ssm.TRASH_BLOCK)]
    assert np.array_equal(new[1, others], pool[1, others])
    assert np.array_equal(new[[0, 2]], pool[[0, 2]])
    assert np.max(np.abs(new[1, 5] - pool[1, 5])) > 1e-3


def test_an_unknown_form_is_refused():
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError, match="'auto', 'pallas' or 'xla'"):
        ssm.ssm_chunk(jnp.zeros((1, N, C)), *rows(rng, (1, 4)), impl="mosaic")
