"""Model zoo unit tests: shapes, determinism, gradient flow, param counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.ops import causal_lm_loss


@pytest.mark.parametrize("name", ["gpt2-debug", "llama-debug", "neox-debug"])
def test_forward_shapes_and_determinism(name):
    bundle = get_model(name)
    params = bundle.init(bundle.config, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 16), 0, bundle.config.vocab_size)
    logits = bundle.apply(bundle.config, params, ids)
    assert logits.shape == (2, 16, bundle.config.vocab_size)
    assert logits.dtype == jnp.float32
    logits2 = bundle.apply(bundle.config, params, ids)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits2))


@pytest.mark.parametrize("name", ["gpt2-debug", "llama-debug", "neox-debug"])
def test_causality(name):
    """Changing a future token must not affect past logits."""
    bundle = get_model(name)
    params = bundle.init(bundle.config, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (1, 12), 0, bundle.config.vocab_size)
    ids2 = ids.at[0, -1].set((ids[0, -1] + 1) % bundle.config.vocab_size)
    a = bundle.apply(bundle.config, params, ids)
    b = bundle.apply(bundle.config, params, ids2)
    np.testing.assert_allclose(np.asarray(a[:, :-1]), np.asarray(b[:, :-1]), atol=2e-2)


@pytest.mark.parametrize("name", ["gpt2-debug", "llama-debug", "neox-debug"])
def test_grads_nonzero(name):
    bundle = get_model(name)
    params = bundle.init(bundle.config, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 16), 0, bundle.config.vocab_size)

    def loss_fn(p):
        return causal_lm_loss(bundle.apply(bundle.config, p, ids), ids)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    norms = [float(jnp.linalg.norm(g)) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(n) for n in norms)
    assert sum(n > 0 for n in norms) >= len(norms) - 2  # norms may be ~0 early


@pytest.mark.parametrize("name", ["gpt2", "llama-3.1-8b", "llama-3.1-405b", "pythia-1.4b", "gpt-neox-20b"])
def test_param_count_formula(name):
    """num_params() formula matches the known public sizes within 1%."""
    known = {"gpt2": 124e6, "llama-3.1-8b": 8.03e9, "llama-3.1-405b": 405.8e9,
             "pythia-1.4b": 1.41e9, "gpt-neox-20b": 20.6e9}
    bundle = get_model(name)
    assert abs(bundle.num_params() - known[name]) / known[name] < 0.01


def test_remat_matches_no_remat():
    bundle = get_model("llama-debug")
    params = bundle.init(bundle.config, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (1, 16), 0, bundle.config.vocab_size)

    def loss_fn(p, remat):
        return causal_lm_loss(bundle.apply(bundle.config, p, ids, remat=remat), ids)

    g1 = jax.grad(lambda p: loss_fn(p, False))(params)
    g2 = jax.grad(lambda p: loss_fn(p, True))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        # bf16 activations: recompute order differs under remat, so allow
        # one-bf16-ulp noise.
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-2, atol=5e-3)


def test_remat_policies_match_no_remat():
    """Every named policy ("all"/"dots"/"attn") is a pure memory/time trade —
    gradients must match the no-remat program (attn relies on the
    checkpoint_name tags in ops/attention.py + ops/flash_attention.py)."""
    from distributed_training_guide_tpu.train.step import REMAT_POLICIES

    bundle = get_model("llama-debug")
    params = bundle.init(bundle.config, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (1, 16), 0, bundle.config.vocab_size)

    def grads(**kw):
        return jax.grad(lambda p: causal_lm_loss(
            bundle.apply(bundle.config, p, ids, **kw), ids))(params)

    ref = grads(remat=False)
    for name, policy in REMAT_POLICIES.items():
        got = grads(remat=True, remat_policy=policy)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-2, atol=5e-3, err_msg=name)


def test_logical_axes_mirror_params():
    for name in ["gpt2-debug", "llama-debug"]:
        bundle = get_model(name)
        params = bundle.init(bundle.config, jax.random.key(0))
        axes = bundle.param_logical_axes(bundle.config)
        p_struct = jax.tree.structure(params)
        a_struct = jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple))
        assert p_struct == a_struct
        for leaf, ax in zip(jax.tree.leaves(params),
                            jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))):
            assert leaf.ndim == len(ax), f"{name}: {leaf.shape} vs {ax}"


def test_init_fingerprints_are_stable():
    """The determinism CONTRACT: same seed -> same params across releases.
    The round-5 family refactors silently reordered init's jax.random key
    draws once (caught by a borderline tolerance failure, bisected, fixed);
    these committed fingerprints turn any future reorder into a direct,
    named failure instead. Values computed at the fixed seed on the debug
    presets (leaf-sum is order-sensitive through the key split)."""
    import jax
    import numpy as np

    from distributed_training_guide_tpu.models import get_model

    expected = {
        "llama-debug": 322.347783,
        "moe-debug": 322.682622,
        "gpt2-debug": 316.355518,
        "neox-debug": 312.050139,
    }
    for name, want in expected.items():
        b = get_model(name)
        p = b.init(b.config, jax.random.key(0))
        got = sum(float(np.asarray(leaf, np.float64).sum())
                  for leaf in jax.tree.leaves(p))
        np.testing.assert_allclose(got, want, rtol=1e-6, err_msg=name)


@pytest.mark.parametrize("name", [
    "gpt2-debug", "llama-debug", "neox-debug", "moe-debug", "laguna-debug",
    "mla-moe-debug", "lfm2-moe-debug", "mimo-v2-debug", "solar-open2-debug"])
def test_one_way_through_the_layers(name):
    """A family's forward has ONE traversal of its layers and the trainer one
    loss head a plan: no argument swaps the layer loop for another program
    (the ``layer_schedule=`` / ``overlap=`` fork went in PR 47), and no
    Trainer field asks for one."""
    import dataclasses
    import inspect

    from distributed_training_guide_tpu.models.moe import (
        make_ragged_ep_dispatch)
    from distributed_training_guide_tpu.train import Trainer

    bundle = get_model(name)
    forks = {"layer_schedule", "overlap", "overlap_schedule"}
    for fn in (bundle.apply, bundle.apply_with_aux, make_ragged_ep_dispatch):
        if fn is not None:
            assert not forks & set(inspect.signature(fn).parameters), fn
    assert not forks & {f.name for f in dataclasses.fields(Trainer)}
