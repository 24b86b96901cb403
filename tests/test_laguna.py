"""The ``laguna`` family (``models/laguna.py``) on the CPU, at debug widths
with EVERY kind of layer (dense + full, sparse + window twice, sparse + full;
4 and 6 gated query heads over 2 kv heads; YaRN on half of a head's columns
against plain rope on all; a window of 12; a shared expert; experts 2-3 of 8
held): the program against the plain reference
(``benchmarks/reference/laguna.py``) on seeded weights for logits, loss and
every leaf's gradient; the shares of an expert layer against the uncut layer;
the window layers' static band; the Trainer's step and what it refuses.
float32 both ways: only the order of sums differs."""
import dataclasses
import importlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import weights_laguna as weights  # noqa: E402
from benchmarks.reference import laguna as ref  # noqa: E402
from benchmarks.runners import _laguna  # noqa: E402
from distributed_training_guide_tpu.models import laguna, moe  # noqa: E402
from distributed_training_guide_tpu.models.registry import get_model  # noqa: E402
from distributed_training_guide_tpu.ops import flash_attention as fa  # noqa: E402
from distributed_training_guide_tpu.parallel import make_mesh, make_plan  # noqa: E402
from distributed_training_guide_tpu.train import Trainer  # noqa: E402

gmm_mod = importlib.import_module(
    "distributed_training_guide_tpu.ops.grouped_matmul")

DEBUG_CFG = ROOT / "tests" / "benchmarks" / "debug" / "configs" / "debug-laguna.json"
# read 2e-6 on logits of magnitude 3 and 3e-7 on a loss of 6.9
LOGIT_TOL, GRAD_TOL = 3e-5, 2e-5
SEQ = 40    # three windows of 12 and a part


def debug_cfg(**over) -> dict:
    return dict(json.loads(DEBUG_CFG.read_text()), **over)


@pytest.fixture(scope="module")
def seeded():
    cfg, key = debug_cfg(), weights.seed_key(2**31 + 9)
    w = jax.jit(lambda k: weights.model_weights(cfg, k, jnp.float32))(key)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, SEQ)), jnp.int32)
    return cfg, w, tokens


@pytest.fixture(scope="module")
def reference(seeded):
    cfg, w, tokens = seeded
    loss, grads = jax.jit(lambda p, t: ref.loss_and_grads(cfg, p, t, 1))(
        w, tokens)
    return ref.forward_logits(cfg, w, tokens), loss, grads


def program_loss(config, attn_impl):
    def loss(params, tokens):
        logits = laguna.apply(config, params, tokens, attn_impl=attn_impl)
        logp = jax.nn.log_softmax(logits[:, :-1], -1)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)
        return -jnp.mean(picked), logits
    return loss


def interpreted_pallas_gmm(monkeypatch):
    monkeypatch.setattr(gmm_mod, "_resolve_impl",
                        lambda impl: "pallas" if impl == "auto" else impl)


@pytest.mark.parametrize("attn_impl,gmm", [
    ("xla", "scan"), ("flash", "scan"), ("xla", "pallas")])
def test_program_matches_reference_logits_loss_and_every_gradient(
        seeded, reference, attn_impl, gmm, monkeypatch):
    """The einsum attention and the interpreted flash kernels (a static band
    a window layer, GQA groups of 2 and 3); the group scan and the
    interpreted ``gmm`` / ``tgmm`` kernels over the held share."""
    cfg, w, tokens = seeded
    if gmm == "pallas":
        interpreted_pallas_gmm(monkeypatch)
    config = _laguna.bundle_for(cfg, "debug").config
    assert moe.experts_held(config) == (2, 2) and config.num_experts == 8
    (loss, logits), grads = jax.jit(jax.value_and_grad(
        program_loss(config, attn_impl), has_aux=True))(
        _laguna.to_program(w), tokens)
    want_logits, want_loss, want_grads = reference
    assert float(jnp.max(jnp.abs(logits - want_logits))) < LOGIT_TOL
    assert abs(float(loss) - float(want_loss)) < 1e-5
    got = _laguna.from_program(grads)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves_with_path(want_grads)
    assert [p for p, _ in flat_got] == [p for p, _ in flat_want]
    names = {jax.tree_util.keystr(p) for p, _ in flat_got}
    assert {"['layers'][1]['gate']", "['layers'][1]['router']",
            "['layers'][0]['wg']", "['layers'][1]['wg']",
            "['layers'][0]['dense_up']", "['layers'][3]['shared_down']",
            "['top']['lm_head']"} <= names
    for (path, g), (_, wg) in zip(flat_got, flat_want):
        scale = max(float(jnp.max(jnp.abs(wg))), 1e-3)
        assert float(jnp.max(jnp.abs(g - wg))) < GRAD_TOL * scale + 1e-7, \
            jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(wg))) > 0, jax.tree_util.keystr(path)


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS if f] + ["bf16"])
def test_the_reference_sees_each_fault(seeded, reference, fault):
    """With the gate left out, the window layers' rope on a full layer or the
    full layers' on a window layer, a softmax router, a window of 11, or
    bfloat16 operands, the reference is no longer the program's forward."""
    cfg, w, tokens = seeded
    more = {"mode": "bf16"} if fault == "bf16" else {"fault": fault}
    diff = float(jnp.max(jnp.abs(
        ref.forward_logits(cfg, w, tokens, **more) - reference[0])))
    assert diff > 100 * LOGIT_TOL, (fault, diff)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """One routed layer's FFN on the same rows: the four shares' ROUTED parts
    (two experts each of eight; pairs of absent experts left out) plus the
    shared expert ONCE are the uncut reference layer, each share's part is
    the reference's for that share, and a share's expert-leaf gradients are
    the uncut gradient's slices."""
    whole = debug_cfg(num_experts=8, experts_held_first=0)
    key = weights.seed_key(11)
    u = jnp.asarray(np.random.default_rng(2).normal(size=(1, 48, 64)),
                    jnp.float32)
    r = jnp.asarray(np.random.default_rng(3).normal(size=(48, 64)),
                    jnp.float32)
    experts = ("gate", "up", "down")

    def program(cfg):
        """(routed part + shared expert, gradients of <y, r> by leaf)."""
        config = _laguna.bundle_for(cfg, "debug").config
        leaves = _laguna.to_program(
            weights.model_weights(cfg, key, jnp.float32))["layers"][1]["moe"]

        def y(leaves):
            return moe._moe_ffn(config, u, leaves)[0][0]
        return y(leaves), jax.grad(lambda l: jnp.sum(y(l) * r))(leaves)

    def reference_parts(cfg):
        w = weights.layer_weights(cfg, key, 1, jnp.float32)
        return ref.routed(cfg, w, u[0]), ref.shared(w, u[0]), w

    routed, shared, w = reference_parts(whole)
    want, want_grads = program(whole)
    assert float(jnp.max(jnp.abs(want - (routed + shared)))) < 1e-5
    uncut = jax.grad(lambda w: jnp.sum(ref.sparse_ffn(whole, w, u[0]) * r))(w)
    for name in experts:
        assert float(jnp.max(jnp.abs(want_grads[name] - uncut[name]))) < 1e-5
    total = shared
    for first in (0, 2, 4, 6):
        cfg = debug_cfg(num_experts=2, experts_held_first=first)
        part, grads = program(cfg)
        routed_part, shared_part, _ = reference_parts(cfg)
        assert float(jnp.max(jnp.abs(shared_part - shared))) == 0.0
        assert float(jnp.max(jnp.abs(part - shared - routed_part))) < 1e-5
        total = total + routed_part
        for name in experts:
            assert float(jnp.max(jnp.abs(
                grads[name] - uncut[name][first: first + 2]))) < 1e-5, name
            assert float(jnp.max(jnp.abs(grads[name]))) > 1e-3
    assert float(jnp.max(jnp.abs(total - want))) < 1e-5
    assert float(jnp.max(jnp.abs(routed))) > 1e-2


def test_window_layers_walk_a_static_band(seeded, monkeypatch):
    """Every flash call of the family takes its window as a Python int (the
    window layers) or None (the full layers): no walk is a traced window's,
    and at the cell's shape a window layer's walk is 31 of a full layer's
    136 tiles."""
    cfg, w, tokens = seeded
    config = _laguna.bundle_for(cfg, "debug").config
    walks, windows = [], []
    real = fa.describe_walk

    def spy(q, k, causal, window, *a, **kw):
        windows.append(window)
        walks.append(real(q, k, causal, window, *a, **kw))
        return walks[-1]
    monkeypatch.setattr(fa, "describe_walk", spy)
    jax.eval_shape(lambda p: laguna.apply(config, p, tokens,
                                          attn_impl="flash"),
                   _laguna.to_program(w))
    assert windows == [None, 12, 12, None]
    assert walks and not any("traced window" in walk for walk in walks)
    q = jax.ShapeDtypeStruct((2, 8192, 64, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 8192, 8, 128), jnp.bfloat16)
    count = lambda window: fa.tile_counts(True, window, 8192, 8192, 512, 512)
    assert "tiles 512x512" in real(q, k, True, 512)
    assert sum(count(512)[:2]) == 31 and sum(count(None)[:2]) == 136


def test_presets_and_counts():
    cut = get_model("laguna-xs.2-ep8-l5").config
    assert cut.num_params() == 691_623_936
    assert get_model("poolside/Laguna-XS.2").config.num_params() \
        == 33_442_596_864
    assert [cut.kind(l) for l in range(5)] == [
        "full", "window", "window", "window", "full"]
    assert cut.num_heads_per_layer == (48, 64, 64, 64, 48)
    assert moe.experts_held(cut) == (0, 32) and cut.num_experts == 256
    shapes = jax.eval_shape(lambda: laguna.init(cut, jax.random.key(0)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == cut.num_params()
    assert shapes["layers"][1]["moe"]["gate"].shape == (32, 2048, 512)
    assert shapes["layers"][1]["attn"]["wq"].shape == (2048, 8192)
    assert shapes["layers"][4]["attn"]["wg"].shape == (2048, 48)
    axes = laguna.param_logical_axes(cut)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    with pytest.raises(ValueError, match="ragged"):
        dataclasses.replace(cut, moe_dispatch="dense")


# ---- the Trainer ------------------------------------------------------------
def test_one_train_step_over_a_held_share_lowers_the_loss():
    bundle = get_model("laguna-debug", dtype=jnp.float32)
    trainer = Trainer(bundle=bundle, optimizer=optax.adamw(3e-3), remat=True,
                      loss_chunks=4, attn_impl="xla",
                      plan=make_plan("single",
                                     make_mesh(devices=jax.devices()[:1])))
    state = trainer.init_state(0)
    ids = jax.random.randint(jax.random.key(1), (2, 32), 0, 512)
    batch = {"input_ids": ids, "labels": ids}
    losses = []
    for _ in range(3):
        state, metrics = trainer.step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        assert all(np.isfinite(np.asarray(v)).all() for v in metrics.values())
        # 3 sparse layers x 64 tokens x top-2; 2 of 8 experts held
        assert int(metrics["moe_pairs_routed"]) == 3 * 64 * 2
        assert 0 < int(metrics["moe_pairs_held"]) < 3 * 64 * 2
        assert metrics["moe_pairs_held"].dtype == jnp.int32
        assert 0 < int(metrics["moe_fullest_expert_rows"]) <= 64
    assert losses[1] < losses[0] and losses[2] < losses[0]
    assert all(bool(jnp.all(jnp.isfinite(x)))
               for x in jax.tree.leaves(state.params))


def test_a_held_share_is_refused_by_name_on_a_mesh(eight_devices):
    bundle = get_model("laguna-debug", dtype=jnp.float32)
    for strategy, mesh in (("fsdp", {"fsdp": 4}), ("ddp", {"dp": 2})):
        with pytest.raises(ValueError, match="experts_held.*models/moe.py"):
            Trainer(bundle=bundle, optimizer=optax.adamw(1e-3),
                    plan=make_plan(strategy, make_mesh(
                        **mesh, devices=jax.devices()[:mesh[
                            next(iter(mesh))]])))


def test_the_serve_engine_refuses_the_family_by_name():
    from distributed_training_guide_tpu.serve import ServeEngine

    bundle = get_model("laguna-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    with pytest.raises(ValueError, match="'laguna' does not serve.*"
                                         "paged_decode_step"):
        ServeEngine(bundle, params, n_slots=2, max_len=32)
