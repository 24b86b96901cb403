"""Chunked cross-entropy must match the full-logits loss in value and grads."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.models import llama as llama_mod
from distributed_training_guide_tpu.ops.cross_entropy import (
    IGNORE_INDEX, causal_lm_loss, chunked_causal_lm_loss)
from distributed_training_guide_tpu.parallel import make_mesh, make_plan
from distributed_training_guide_tpu.train import Trainer, adamw_cosine
from distributed_training_guide_tpu.train.step import lower_step
from distributed_training_guide_tpu.utils import hlo


def test_chunked_matches_full_including_padding():
    rng = jax.random.key(0)
    b, s, e, v = 2, 13, 16, 32  # s-1 = 12, not divisible by 5 -> padding path
    hidden = jax.random.normal(rng, (b, s, e), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (e, v), jnp.float32)
    labels = jax.random.randint(jax.random.key(2), (b, s), 0, v)
    labels = labels.at[0, 3].set(IGNORE_INDEX)

    full = causal_lm_loss(jnp.einsum("bse,ev->bsv", hidden, w), labels)
    for chunks in (1, 3, 5):
        ck = chunked_causal_lm_loss(hidden, w, labels, num_chunks=chunks)
        np.testing.assert_allclose(float(ck), float(full), rtol=1e-6)

    g_full = jax.grad(lambda h, w: causal_lm_loss(
        jnp.einsum("bse,ev->bsv", h, w), labels), argnums=(0, 1))(hidden, w)
    g_ck = jax.grad(lambda h, w: chunked_causal_lm_loss(
        h, w, labels, num_chunks=3), argnums=(0, 1))(hidden, w)
    for a, c in zip(g_full, g_ck):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("wide", [False, True])
def test_a_wider_matrix_accumulates_its_gradient_wide(wide):
    """The product reads the matrix in ``hidden``'s dtype either way (the
    same loss to the bit); what the scan closes over decides how wide its
    cotangent is summed over the chunks: an fp32 matrix under bf16 hidden
    states gets an fp32 gradient that sixteen chunks did not round, a bf16
    matrix a running bf16 sum (the per-chunk program's carry)."""
    from distributed_training_guide_tpu.ops.cross_entropy import (
        chunked_nll_sums)

    b, s, e, v, chunks = 2, 65, 32, 256, 16
    hidden = jax.random.normal(jax.random.key(0), (b, s, e)).astype(
        jnp.bfloat16)
    w = (0.1 * jax.random.normal(jax.random.key(1), (e, v))).astype(
        jnp.bfloat16)          # exactly representable: widening is exact
    labels = jax.random.randint(jax.random.key(2), (b, s), 0, v)

    def loss(w_, h_):
        nll, n = chunked_nll_sums(h_, w_, labels, chunks)
        return nll / n

    w_in = w.astype(jnp.float32) if wide else w
    value, grad = jax.value_and_grad(loss)(w_in, hidden)
    assert grad.dtype == w_in.dtype
    assert float(value) == float(loss(w, hidden))
    want = jax.grad(loss)(w.astype(jnp.float32), hidden.astype(jnp.float32))
    err = float(jnp.linalg.norm(grad.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    # each chunk's product is rounded to bf16 once in both; only the narrow
    # carry rounds the running sum as well
    assert err < 3e-3 if wide else 3e-3 < err < 2e-2, err
    carries = [str(out.aval.dtype)
               for eqn in jax.make_jaxpr(jax.grad(loss))(w_in, hidden).eqns
               if eqn.primitive.name == "scan"
               for out in eqn.outvars if out.aval.shape == (e, v)]
    assert carries == ["float32" if wide else "bfloat16"], carries


def test_trainer_loss_chunks_matches(eight_devices):
    bundle = get_model("llama-debug", dtype=jnp.float32)
    opt = adamw_cosine(1e-3)
    ids = np.random.RandomState(0).randint(0, 512, (8, 33))

    def run(loss_chunks):
        t = Trainer(bundle=bundle, optimizer=opt,
                    plan=make_plan("fsdp", make_mesh(fsdp=8)),
                    loss_chunks=loss_chunks, donate=False)
        state = t.init_state(0)
        batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
                 for k in ("input_ids", "labels")}
        state, m = t.step_fn(state, batch)
        return float(m["loss"]), state

    loss_full, s1 = run(0)
    loss_chunked, s2 = run(4)
    np.testing.assert_allclose(loss_chunked, loss_full, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(jax.device_get(s1.params)),
                    jax.tree.leaves(jax.device_get(s2.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5)


def test_trainer_loss_chunks_matches_moe(eight_devices):
    """Chunked CE composes with the MoE aux-loss path (router aux + dropped
    metric must survive the return_hidden forward)."""
    bundle = get_model("moe-debug", dtype=jnp.float32)
    opt = adamw_cosine(1e-3)
    ids = np.random.RandomState(1).randint(0, 512, (8, 33))

    def run(loss_chunks):
        t = Trainer(bundle=bundle, optimizer=opt,
                    plan=make_plan("ep", make_mesh(ep=4)),
                    loss_chunks=loss_chunks, donate=False)
        state = t.init_state(0)
        batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
                 for k in ("input_ids", "labels")}
        state, m = t.step_fn(state, batch)
        assert "moe_dropped_frac" in m
        return float(m["loss"]), state

    loss_full, s1 = run(0)
    loss_chunked, s2 = run(4)
    np.testing.assert_allclose(loss_chunked, loss_full, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(jax.device_get(s1.params)),
                    jax.tree.leaves(jax.device_get(s2.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5)


# ---- a head sharded over data axes: one gather, one reduce-scatter a step ---

def _sgd_trainer(model, strategy, mesh, **over):
    """A trainer whose step moves every parameter by minus its gradient."""
    bundle = get_model(model, dtype=jnp.float32, **over)
    return Trainer(bundle=bundle, optimizer=optax.sgd(1.0),
                   plan=make_plan(strategy, mesh), loss_chunks=4,
                   donate=False)


def _loss_and_grads(trainer, ids):
    state = trainer.init_state(0)
    batch = {k: jax.device_put(jnp.asarray(ids), trainer.batch_shardings()[k])
             for k in ("input_ids", "labels")}
    new, m = trainer.step_fn(state, batch)
    grads = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                         jax.device_get(state.params),
                         jax.device_get(new.params))
    return float(m["loss"]), grads


def _head_collectives(text, head_elems, shards):
    """``(kind, in_a_loop)`` of every collective of a compiled step that
    moves the whole output matrix (or, a reduce-scatter, leaves one shard)."""
    return [(c.kind, in_loop) for c, in_loop in
            hlo.collectives_moving(text, head_elems, shards=shards)]


HEAD_CASES = {
    # name: (model, overrides, strategy, mesh axes on four devices, shards)
    "fsdp4": ("llama-debug", {"tie_word_embeddings": False}, "fsdp",
              {"fsdp": 4}, 4),
    "fsdp2-dp2": ("llama-debug", {"tie_word_embeddings": False}, "fsdp",
                  {"fsdp": 2, "dp": 2}, 2),
    # the tied head is the embedding table transposed: sharded on vocab
    "fsdp4-tied": ("llama-debug", {"tie_word_embeddings": True}, "fsdp",
                   {"fsdp": 4}, 4),
    # ep is a data axis and the head is replicated over it
    "ep-fsdp": ("moe-debug", {}, "ep_fsdp", {"ep": 2, "fsdp": 2}, 2),
}


@pytest.mark.parametrize("case", sorted(HEAD_CASES))
def test_sharded_head_is_gathered_once_a_step(eight_devices, case):
    """Loss and every gradient leaf of the trainer's step equal the
    replicated plan's, and the compiled step holds no collective over the
    whole output matrix in any loop: one all-gather and one reduce-scatter
    of it outside them."""
    model, over, strategy, axes, shards = HEAD_CASES[case]
    four = eight_devices[:4]
    ids = np.random.RandomState(0).randint(0, 512, (8, 33))
    trainer = _sgd_trainer(model, strategy, make_mesh(**axes, devices=four),
                           **over)
    assert trainer.head_gather["once"], trainer.head_gather["why"]
    loss, grads = _loss_and_grads(trainer, ids)
    want_loss, want = _loss_and_grads(
        _sgd_trainer(model, "ddp", make_mesh(dp=4, devices=four), **over),
        ids)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(grads)):
        np.testing.assert_allclose(b, a, rtol=1e-3, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))

    cfg = trainer.bundle.config
    lowered, _ = lower_step(trainer, global_batch=8, seq_length=33)
    found = _head_collectives(lowered.compile().as_text(),
                              cfg.hidden_size * cfg.vocab_size, shards)
    assert not [f for f in found if f[1]], found
    kinds = [kind for kind, _ in found]
    assert 1 <= kinds.count("all-gather") <= 2, found
    assert kinds.count("reduce-scatter") == 1, found
    assert "all-reduce" not in kinds, found


def test_a_head_that_does_not_fit_keeps_the_per_chunk_program(
        eight_devices, monkeypatch):
    """The rule reads shapes and the device's memory: told of a device that
    cannot hold the gathered matrix beside its state, the trainer lowers
    the program it lowered before (GSPMD's gather in the chunk loop)."""
    from distributed_training_guide_tpu.train import preflight

    mesh = make_mesh(fsdp=4, devices=eight_devices[:4])
    fits = _sgd_trainer("llama-debug", "fsdp", mesh,
                        tie_word_embeddings=False)
    priced = preflight.priced_state_bytes(fits)
    cfg = fits.bundle.config
    need = cfg.hidden_size * cfg.vocab_size * (4 + 4)  # fp32 compute
    monkeypatch.setattr(preflight, "device_bytes_limit",
                        lambda device: priced + need - 1)
    tight = _sgd_trainer("llama-debug", "fsdp", mesh,
                         tie_word_embeddings=False)
    assert not tight.head_gather["once"]
    assert "do not fit" in tight.head_gather["why"]
    monkeypatch.setattr(preflight, "device_bytes_limit",
                        lambda device: priced + need)
    roomy = _sgd_trainer("llama-debug", "fsdp", mesh,
                         tie_word_embeddings=False)
    assert roomy.head_gather["once"]

    lowered, _ = lower_step(tight, global_batch=8, seq_length=33)
    assert "head_gather" not in lowered.as_text(debug_info=True)
    found = _head_collectives(lowered.compile().as_text(),
                              cfg.hidden_size * cfg.vocab_size, 4)
    assert ("all-gather", True) in found, found
    # by shape alone (nothing is initialised): Llama-405B's 16,384 x 128,256
    # head over eight 95 GiB chips that already hold an eighth of its state
    monkeypatch.setattr(preflight, "device_bytes_limit",
                        lambda device: 95 * 2**30)
    big = Trainer(bundle=get_model("llama-3.1-405b"),
                  optimizer=optax.sgd(1.0),
                  plan=make_plan("fsdp", make_mesh(fsdp=8)), loss_chunks=16)
    assert not big.head_gather["once"]
    assert "11.74 GiB" in big.head_gather["why"]


@pytest.mark.parametrize("strategy,axes", [
    ("single", {}), ("ddp", {"dp": 4}), ("zero1", {"dp": 4}),
    ("tp", {"tp": 4}), ("tp_fsdp", {"tp": 2, "fsdp": 2}),
    ("fsdp", {"fsdp": 2, "cp": 2})])
def test_other_plans_keep_the_loss_they_had(eight_devices, strategy, axes):
    """A replicated head has nothing to gather; a vocab-, sequence- or
    context-parallel plan keeps the per-chunk path: no region in the loss."""
    devices = eight_devices[:1] if strategy == "single" else eight_devices[:4]
    trainer = _sgd_trainer("llama-debug", strategy,
                           make_mesh(**axes, devices=devices))
    assert not trainer.head_gather["once"]
    lowered, _ = lower_step(trainer, global_batch=8, seq_length=32)
    text = lowered.as_text(debug_info=True)
    assert "head_gather" not in text
    if strategy in ("single", "ddp", "zero1"):
        assert "shard_map" not in text and "manual" not in text
