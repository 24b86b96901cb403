"""``ops/retention.py`` on the CPU: the blocked feature map's identity, and the
recurrent step and the blocked scan (the ``jnp`` forms and the Pallas kernels,
interpreted) against power retention's ATTENTION FORM in float64, which has
no state and no feature map in it: several chunks with a carried state, a
ragged final chunk, a block that held another sequence's state, decode steps
behind them; a state rounded to bfloat16 a step, the gate or the normaliser
left out are far outside the tolerance; a narrower pool is refused."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.ops import retention as ret

# float32 sums against float64, outputs of order one after the quotient: read
# 4e-7 to 6e-7 (both forms; the products that meet the state are HIGHEST)
TOL = 1e-5
HQ, HKV, D = 4, 2, 32


def attention_form(q, k, v, log_gamma):
    """``o [T, Hq, d]`` in float64: ``w[t, s] = Gamma[t, s] (q_t . k_s)^2``,
    the output divided by the weights' own sum; query head ``j`` on kv head
    ``j // g``."""
    q, k, v, c = (np.asarray(x, np.float64) for x in (q, k, v, log_gamma))
    c = np.cumsum(c, axis=0)
    g = q.shape[1] // k.shape[1]
    out = np.zeros(q.shape)
    for j in range(q.shape[1]):
        h = j // g
        score = q[:, j] @ k[:, h].T
        w = np.tril(score * score * np.exp(np.minimum(
            c[:, None, h] - c[None, :, h], 0.0)))
        out[:, j] = (w @ v[:, h]) / w.sum(1, keepdims=True)
    return out


def rows(t, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    q, k, v = (rng.standard_normal((t, h, D)).astype(dtype)
               for h in (HQ, HKV, HKV))
    gate = rng.standard_normal((t, HKV)) * 1.4 + 6.906768
    return q, k, v, np.asarray(jax.nn.log_sigmoid(gate), np.float32)


def pools(seed=1, blocks=3, layers=2):
    """Pools that hold ANOTHER sequence's state in every block."""
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((layers, blocks, *shape)),
                             jnp.float32)
                 for shape in ret.state_shapes(HKV, D))


def served(q, k, v, lg, plan, impl, block=2, layer=1, state=None, edit=None):
    """The sequence through the pools as the serve path runs it: ``plan`` is
    ``(real tokens, chunk width)`` a chunk (a chunk's rows past the real ones
    are another request's, and mean nothing), then a decode step a token.
    ``edit(pools) -> pools`` runs between any two calls."""
    state = pools() if state is None else state
    ids, outs, pos = jnp.asarray([block]), [], 0
    for n, width in plan:
        def padded(x, fill):
            buf = np.full((1, width, *x.shape[1:]), fill, x.dtype)
            buf[0, :n] = x[pos:pos + n]
            return jnp.asarray(buf)

        o, *state = ret.retention_chunk(
            *state, ids, layer, padded(q, 7), padded(k, 3), padded(v, 5),
            padded(lg, -1), jnp.asarray([pos == 0]), jnp.asarray([n]),
            impl=impl, interpret=True)
        outs.append(np.asarray(o[0, :n]))
        pos += n
        if edit is not None:
            state = edit(state)
    for t in range(pos, len(q)):
        o, *state = ret.retention_step(
            *state, ids, layer, *(jnp.asarray(x[t][None])
                                  for x in (q, k, v, lg)),
            jnp.asarray([t == 0]), impl=impl, interpret=True)
        outs.append(np.asarray(o))
        if edit is not None:
            state = edit(state)
    return np.concatenate(outs), state


@pytest.mark.parametrize("d", [16, 32, 128])
def test_the_feature_map_squares_the_dot_product(d):
    rng = np.random.default_rng(d)
    x, y = (rng.standard_normal((7, d)) for _ in range(2))
    phi_x, phi_y = ret.feature_map(jnp.asarray(x)), ret.feature_map(
        jnp.asarray(y))
    pairs = (d // 16) * (d // 16 + 1) // 2
    assert phi_x.shape == (7, pairs, 256) and phi_x.dtype == jnp.float32
    want = (x * y).sum(-1) ** 2
    got = np.asarray((phi_x * phi_y).sum((-1, -2)))
    assert np.max(np.abs(got - want) / (1 + np.abs(want))) < 1e-5
    assert ret.feature_rows(d) == pairs * 256


def test_the_stored_rows_are_the_blocked_layout_the_configuration_states():
    """9,216 rows at 128: the 36 block pairs of 256, 960 over the exact
    symmetric map's 8,256 and under the 9,288 (an eighth over) the issue
    allows; pair ``(I, J)`` lies where the kernels look for it."""
    assert ret.feature_rows(128) == 9216 <= 9288
    assert 128 * 129 // 2 == 8256
    i, j = ret.feature_pairs(128)
    assert len(i) == 36 and np.all(i <= j) and list(i[:9]) == [0] * 8 + [1]
    assert ret.state_shapes(8, 128) == ((8, 36, 256, 128), (8, 128, 128))
    assert ret.PAIR_TILE * 3 == 36
    with pytest.raises(ValueError, match="no multiple of the feature"):
        ret.feature_pairs(40)
    x = np.arange(1.0, 33.0)
    phi = np.asarray(ret.feature_map(jnp.asarray(x)))   # pairs 00, 01, 11
    assert phi[1, 16 * 2 + 5] == pytest.approx(math.sqrt(2) * x[2] * x[16 + 5])
    assert phi[2, 16 * 3 + 4] == pytest.approx(x[16 + 3] * x[16 + 4])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunks_then_steps_are_the_attention_form(impl):
    """400 tokens on a block that held another sequence's state: a whole
    first chunk (fresh: the block's content is not read), a carried chunk
    with 100 of 128 rows real, a carried chunk of two token blocks with 150
    of 160 real, then 22 decode steps: every output is the attention form's
    in float64, whichever form computes it."""
    q, k, v, lg = rows(400)
    got, _ = served(q, k, v, lg, ((128, 128), (100, 128), (150, 160)), impl)
    want = attention_form(q, k, v, lg)
    assert np.max(np.abs(got - want)) < TOL
    assert np.max(np.abs(want)) > 1.0


def test_the_kernels_take_the_models_bfloat16_rows_exactly():
    """q, k and v as the model hands them (bfloat16): the 0/1 products are
    exact in one pass, so the kernel's result is the float64 form's ON THOSE
    ROWS at the float32 tolerance, not at bfloat16's."""
    q, k, v, lg = rows(200, seed=3)
    q, k, v = (np.asarray(jnp.asarray(x, jnp.bfloat16)) for x in (q, k, v))
    got, _ = served(q, k, v, lg, ((128, 128), (40, 128)), "pallas")
    want = attention_form(*(np.asarray(x, np.float32) for x in (q, k, v)), lg)
    assert np.max(np.abs(got - want)) < TOL


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_both_forms_leave_the_same_state_and_no_other_block(impl):
    q, k, v, lg = rows(180, seed=4)
    before = pools()
    _, state = served(q, k, v, lg, ((128, 128), (40, 128)), impl,
                      state=before)
    _, other = served(q, k, v, lg, ((128, 128), (40, 128)),
                      "xla" if impl == "pallas" else "pallas", state=before)
    for new, ref, old in zip(state, other, before):
        assert np.max(np.abs(new[1, 2] - ref[1, 2])) < 1e-3 * np.max(
            np.abs(ref[1, 2]))
        assert np.array_equal(new[0], old[0])           # the other layer
        assert np.array_equal(new[1, :2], old[1, :2])   # the other blocks


def test_a_state_rounded_to_bfloat16_a_step_is_tens_of_tolerances_away():
    """The state through bfloat16 between any two calls (what a bfloat16 pool
    would hold): a sum over hundreds of tokens at a gate near 1 loses what
    each token adds."""
    q, k, v, lg = rows(300, seed=5)

    def rounded(state):
        return [x.astype(jnp.bfloat16).astype(jnp.float32) for x in state]

    got, _ = served(q, k, v, lg, ((128, 128),), "xla", edit=rounded)
    err = np.max(np.abs(got - attention_form(q, k, v, lg)))
    assert err > 30 * TOL, err


@pytest.mark.parametrize("fault", ["no_gate", "no_normaliser", "stale_block"])
def test_what_the_layer_is_made_of_each_moves_the_output(fault):
    q, k, v, lg = rows(200, seed=6)
    want = attention_form(q, k, v, lg)
    if fault == "no_gate":
        got, _ = served(q, k, v, np.zeros_like(lg), ((128, 128),), "xla")
    elif fault == "no_normaliser":      # Z left at what the block held
        def keep_z(state, z=pools()[1]):
            return [state[0], z]
        got, _ = served(q, k, v, lg, ((128, 128),), "xla", edit=keep_z)
    else:       # position 0 not told: the block's last owner is read
        state = pools()
        o, *_ = ret.retention_chunk(
            *state, jnp.asarray([2]), 1, *(jnp.asarray(x[None, :128])
                                           for x in (q, k, v, lg)),
            jnp.asarray([False]), impl="xla")
        got, want = np.asarray(o[0]), want[:128]
    assert np.max(np.abs(got - want[:len(got)])) > 100 * TOL


def test_idle_slots_share_the_trash_block_and_fresh_slots_read_zeros():
    """Four slots, two of them idle on block 0: the live slots' outputs and
    blocks are what each gets alone; a slot at position 0 reads out its one
    token (``o = v`` for every query head of the kv head)."""
    q, k, v, lg = rows(4, seed=7)
    state = pools(blocks=4)
    ids = jnp.asarray([2, ret.TRASH_BLOCK, 3, ret.TRASH_BLOCK])
    fresh = jnp.asarray([False, True, True, True])
    for impl in ("xla", "pallas"):
        o, s, z = ret.retention_step(*state, ids, 0, *map(jnp.asarray,
                                                         (q, k, v, lg)),
                                     fresh, impl=impl, interpret=True)
        alone, s1, _ = ret.retention_step(
            *state, ids[:1], 0, *(jnp.asarray(x[:1]) for x in (q, k, v, lg)),
            fresh[:1], impl=impl, interpret=True)
        assert np.allclose(o[0], alone[0], atol=1e-5)
        assert np.allclose(s[0, 2], s1[0, 2], atol=1e-4)
        want = np.repeat(v[2], HQ // HKV, axis=0)
        assert np.max(np.abs(np.asarray(o[2]) - want)) < 1e-3
        assert np.array_equal(s[0, 1], state[0][0, 1])      # block 1: no slot's


def test_a_narrower_pool_is_refused_by_name():
    q, k, v, lg = rows(2)
    s, z = pools()
    for bad in ((s.astype(jnp.bfloat16), z), (s, z.astype(jnp.bfloat16))):
        with pytest.raises(TypeError, match="state pool .* is float32"):
            ret.retention_step(*bad, jnp.asarray([1, 2]), 0,
                               *map(jnp.asarray, (q, k, v, lg)))
        with pytest.raises(TypeError, match="state pool .* is float32"):
            ret.retention_chunk(*bad, jnp.asarray([1]), 0,
                                *(jnp.asarray(x[None]) for x in (q, k, v, lg)))
    with pytest.raises(ValueError, match="retention impl must be"):
        ret.retention_step(s, z, jnp.asarray([1, 2]), 0,
                           *map(jnp.asarray, (q, k, v, lg)), impl="flash")
