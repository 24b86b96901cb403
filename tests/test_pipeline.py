"""Pipeline-parallel parity tests on the virtual 8-device mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.parallel import make_mesh, make_plan
from distributed_training_guide_tpu.train import Trainer, adamw_cosine

GB = 8
SEQ = 32


def run(strategy, mesh_kw, pp_microbatches=None, steps=2, n_devices=None,
        bundle=None, **trainer_kw):
    bundle = bundle or get_model("llama-debug", dtype=jnp.float32)
    if strategy == "single":
        mesh = make_mesh(devices=jax.devices()[:1])
    else:
        devices = jax.devices()[:n_devices] if n_devices else None
        mesh = make_mesh(devices=devices, **mesh_kw)
    t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                plan=make_plan(strategy, mesh), donate=False,
                pp_microbatches=pp_microbatches, **trainer_kw)
    state = t.init_state(0)
    ids = np.random.RandomState(0).randint(0, 512, (GB, SEQ))
    batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
             for k in ("input_ids", "labels")}
    losses = []
    for _ in range(steps):
        state, m = t.step_fn(state, batch)
        losses.append(float(m["loss"]))
    return losses, state


@pytest.fixture(scope="module")
def golden():
    return run("single", {})


def test_pp_matches_single(golden, eight_devices):
    # llama-debug has 2 layers -> pp=2 stages of 1 layer; dp=4 so the
    # microbatch (GB/M = 4) must stay divisible by dp
    losses, state = run("pp", {"pp": 2}, pp_microbatches=2)
    np.testing.assert_allclose(losses, golden[0], rtol=2e-4)
    for a, b in zip(jax.tree.leaves(jax.device_get(golden[1].params)),
                    jax.tree.leaves(jax.device_get(state.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-2, atol=1e-4)


def test_pp_params_sharded(eight_devices):
    bundle = get_model("llama-debug", dtype=jnp.float32)
    t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                plan=make_plan("pp", make_mesh(pp=2)), donate=False)
    state = t.init_state(0)
    wq = state.params["layers"]["attn"]["wq"]
    assert wq.sharding.spec[0] == "pp"


def test_pp_composes_with_fsdp(golden, eight_devices):
    losses, _ = run("pp_fsdp", {"pp": 2, "fsdp": 2}, pp_microbatches=2)
    np.testing.assert_allclose(losses, golden[0], rtol=2e-4)


def test_pp_composes_with_tp(golden, eight_devices):
    losses_tp, _ = run("pp_tp", {"pp": 2, "tp": 2}, pp_microbatches=2, n_devices=4)
    np.testing.assert_allclose(losses_tp, golden[0], rtol=2e-4)


def test_pp_tp_composes_with_dp(golden, eight_devices):
    # pp=2 x tp=2 x dp=2 on all 8 devices — tp is manual inside the pipeline
    # shard_map, so no XLA partitioner CHECK with a third nontrivial axis
    losses, state = run("pp_tp", {"pp": 2, "tp": 2}, pp_microbatches=2)
    np.testing.assert_allclose(losses, golden[0], rtol=2e-4)
    # atol is looser than the pure-pp golden: the vocab-parallel logsumexp
    # reorders reductions and Adam amplifies tiny grad differences
    for a, b in zip(jax.tree.leaves(jax.device_get(golden[1].params)),
                    jax.tree.leaves(jax.device_get(state.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-2, atol=4e-4)


def test_pp_tp_composes_with_fsdp(golden, eight_devices):
    losses, _ = run("pp_tp_fsdp", {"pp": 2, "tp": 2, "fsdp": 2}, pp_microbatches=2)
    np.testing.assert_allclose(losses, golden[0], rtol=2e-4)


def _nested_shard_maps(jaxpr):
    """(depth-inside-pp-region, manual_axes, in_specs) for every shard_map
    nested inside the pipeline's pp-manual shard_map."""
    def subjaxprs(params):
        for v in params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for w in vs:
                if hasattr(w, "jaxpr") and hasattr(w.jaxpr, "eqns"):
                    yield w.jaxpr
                elif hasattr(w, "eqns"):
                    yield w

    found = []

    def walk(jx, inside_pp):
        for eqn in jx.eqns:
            now_inside = inside_pp
            if eqn.primitive.name == "shard_map":
                axes = frozenset(eqn.params["manual_axes"])
                if inside_pp:
                    found.append((axes, eqn.params["in_specs"]))
                now_inside = inside_pp or "pp" in axes
            for sub in subjaxprs(eqn.params):
                walk(sub, now_inside)

    walk(jaxpr.jaxpr, False)
    return found


def test_pp_fsdp_flash_partitions_batch(golden, eight_devices):
    """Flash under pp (round-2 weakness closed): the sharded-flash wrapper
    nests inside the pp-manual schedule as a dp/fsdp-manual sub-region built
    against the context mesh, so the Pallas kernel runs on local batch
    shards — NOT the SPMD partitioner's gather-and-replicate fallback.
    Checks the trajectory against the single-device golden AND the program
    structure: nested batch-manual flash maps inside the pipeline region."""
    from jax.sharding import PartitionSpec as P

    bundle = get_model("llama-debug", dtype=jnp.float32)
    t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                plan=make_plan("pp_fsdp", make_mesh(pp=2, fsdp=2)),
                donate=False, pp_microbatches=2, attn_impl="flash")
    state = t.init_state(0)
    ids = np.random.RandomState(0).randint(0, 512, (GB, SEQ))
    batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
             for k in ("input_ids", "labels")}

    jaxpr = jax.make_jaxpr(lambda s, b: t.step_fn(s, b))(state, batch)
    nested = [(axes, specs) for axes, specs in _nested_shard_maps(jaxpr)
              if "fsdp" in axes]
    assert nested, "no batch-manual flash shard_map nested in the pp region"
    batch_spec = P(("dp", "fsdp"), None, None, None)
    # q, k and v ride in batch-sharded; the kernels' live-tile lists (small
    # int32 constants of the static band) come first, replicated
    assert any(list(specs).count(batch_spec) >= 3 for _, specs in nested), \
        [s for _, s in nested]

    losses = []
    for _ in range(2):
        state, m = t.step_fn(state, batch)
        losses.append(float(m["loss"]))
    np.testing.assert_allclose(losses, golden[0], rtol=2e-4)


@pytest.mark.parametrize("context_impl", ["ring", "ulysses"])
def test_pp_composes_with_cp(golden, eight_devices, context_impl):
    """pp x cp (round-2 gap closed): the long-context strategy and the
    pipeline are no longer mutually exclusive — the ring's / Ulysses'
    cp(+batch)-manual shard_map nests inside the pp-manual schedule (built
    against the context mesh, same mechanism as flash-under-pp), with the
    microbatch seq dim cp-sharded through the 1F1B ticks."""
    losses, _ = run("pp", {"pp": 2, "cp": 2}, pp_microbatches=2,
                    context_impl=context_impl)
    np.testing.assert_allclose(losses, golden[0], rtol=2e-4,
                               err_msg=context_impl)


def test_pp_tp_cp_three_axis(golden, eight_devices):
    """pp x tp x cp on all 8 devices: manual-tp megatron shards + the
    vocab-parallel head inside the pipeline, the ring's cp-manual shard_map
    nested under both, fully-masked ticks — the deepest manual-axis
    composition in the tree. Trajectory must match single-device."""
    losses, _ = run("pp_tp", {"pp": 2, "tp": 2, "cp": 2}, pp_microbatches=2,
                    context_impl="ring")
    np.testing.assert_allclose(losses, golden[0], rtol=2e-4)


def test_pp_cp_moe_aux_masking(eight_devices):
    """MoE under pp x cp pins the fully-masked schedule's router-aux
    cotangent path (daux * valid-mask): the dense pp x cp test never sets
    aux_coef > 0, so without this a broken masked-daux scaling would pass
    the whole suite while aux grads silently drift."""
    bundle = get_model("moe-debug", dtype=jnp.float32)
    ids = np.random.RandomState(0).randint(0, 512, (GB, SEQ))

    def run_moe(plan, **kw):
        t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3), plan=plan,
                    donate=False, **kw)
        state = t.init_state(0)
        batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
                 for k in ("input_ids", "labels")}
        losses = []
        for _ in range(2):
            state, m = t.step_fn(state, batch)
            losses.append(float(m["loss"]))
        return losses

    golden = run_moe(make_plan("single", make_mesh(devices=jax.devices()[:1])),
                     attn_impl="xla")
    pp_cp = run_moe(make_plan("pp", make_mesh(pp=2, cp=2)),
                    pp_microbatches=2, context_impl="ring")
    np.testing.assert_allclose(pp_cp, golden, rtol=2e-4)


def test_pp_four_stages(eight_devices):
    """pp=4 (all other pp tests run pp=2): exercises the non-degenerate
    saved-input ring buffer (K = 2pp-1 = 7 > C at small M is clamped),
    longer fill/drain bubbles, and 3-hop ppermute chains — both alone and
    with the cp-masked schedule nested inside."""
    bundle4 = get_model("llama-debug", dtype=jnp.float32, num_layers=4)
    golden4, _ = run("single", {}, bundle=bundle4)
    losses, _ = run("pp", {"pp": 4}, pp_microbatches=4, bundle=bundle4)
    np.testing.assert_allclose(losses, golden4, rtol=2e-4)
    losses, _ = run("pp", {"pp": 4, "cp": 2}, pp_microbatches=4,
                    bundle=bundle4, context_impl="ring")
    np.testing.assert_allclose(losses, golden4, rtol=2e-4)


def test_pp_gpt2_family(eight_devices):
    # gpt2 exercises tied embeddings + learned position embeddings through
    # the embed/head vjp paths; under pp x tp also the column-sharded fused
    # QKV ([l,e,3,e] layout), sharded biases, and the tied vocab-parallel head
    bundle = get_model("gpt2-debug", dtype=jnp.float32)
    golden_t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                       plan=make_plan("single", make_mesh(devices=jax.devices()[:1])),
                       donate=False)
    gstate = golden_t.init_state(0)
    ids = np.random.RandomState(0).randint(0, 512, (GB, SEQ))
    gbatch = {k: jax.device_put(jnp.asarray(ids), golden_t.batch_shardings()[k])
              for k in ("input_ids", "labels")}
    glosses = [float(golden_t.step_fn(gstate, gbatch)[1]["loss"])]

    for strategy, mesh_kw in (("pp", {"pp": 2}), ("pp_tp", {"pp": 2, "tp": 2})):
        t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                    plan=make_plan(strategy, make_mesh(**mesh_kw)), donate=False,
                    pp_microbatches=2)
        state = t.init_state(0)
        batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
                 for k in ("input_ids", "labels")}
        losses = [float(t.step_fn(state, batch)[1]["loss"])]
        np.testing.assert_allclose(losses, glosses, rtol=2e-4, err_msg=strategy)


def test_pp_neox_family(eight_devices):
    """NeoX under the 1F1B schedule: the parallel-residual block inside a
    pipeline stage, and under pp x tp the manual-tp path where BOTH
    row-parallel partial sums (attention out-proj + MLP down-proj) share a
    single psum — plus the untied vocab-parallel head."""
    bundle = get_model("neox-debug", dtype=jnp.float32)
    golden_t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                       plan=make_plan("single", make_mesh(devices=jax.devices()[:1])),
                       donate=False)
    gstate = golden_t.init_state(0)
    ids = np.random.RandomState(0).randint(0, 512, (GB, SEQ))
    gbatch = {k: jax.device_put(jnp.asarray(ids), golden_t.batch_shardings()[k])
              for k in ("input_ids", "labels")}
    glosses = [float(golden_t.step_fn(gstate, gbatch)[1]["loss"])]

    for strategy, mesh_kw in (("pp", {"pp": 2}), ("pp_tp", {"pp": 2, "tp": 2})):
        t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                    plan=make_plan(strategy, make_mesh(**mesh_kw)), donate=False,
                    pp_microbatches=2)
        state = t.init_state(0)
        batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
                 for k in ("input_ids", "labels")}
        losses = [float(t.step_fn(state, batch)[1]["loss"])]
        np.testing.assert_allclose(losses, glosses, rtol=2e-4, err_msg=strategy)


def test_flat_rmsnorm_manual_tp_matches_full_width(eight_devices):
    """The OLMo-2 full-width q/k RMSNorm under MANUAL tp: the statistic is
    a reduction over the sharded heads dim, so the psum'd sum-of-squares
    must reproduce the unsharded norm EXACTLY. x is deliberately
    anisotropic across the shard boundary (first half scaled 3x) so a
    shard-local mean cannot masquerade as the global one."""
    from jax.sharding import PartitionSpec as P
    from distributed_training_guide_tpu.models.llama import (_flat_rmsnorm,
                                                             _rmsnorm)
    from distributed_training_guide_tpu.parallel import make_mesh

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 4, 64), jnp.float32)
    x = x.at[..., :32].multiply(3.0)          # local stats != global stats
    scale = jnp.asarray(1.0 + 0.1 * rng.randn(64), jnp.float32)
    mesh = make_mesh(tp=2, devices=jax.devices()[:2])

    manual = jax.jit(jax.shard_map(
        lambda xs, ss: _flat_rmsnorm(xs, ss, 1e-5, "tp"),
        mesh=mesh, in_specs=(P(None, None, "tp"), P("tp")),
        out_specs=P(None, None, "tp")))(x, scale)
    ref = _rmsnorm(x, scale, 1e-5)
    np.testing.assert_allclose(np.asarray(manual), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    # and the shard-local statistic really WOULD diverge (test has teeth)
    local = jax.jit(jax.shard_map(
        lambda xs, ss: _rmsnorm(xs, ss, 1e-5),
        mesh=mesh, in_specs=(P(None, None, "tp"), P("tp")),
        out_specs=P(None, None, "tp")))(x, scale)
    assert np.abs(np.asarray(local) - np.asarray(ref)).max() > 0.1


def test_pp_olmo2_family(eight_devices):
    """OLMo-2 under the 1F1B schedule, incl. pp x tp MANUAL megatron
    shards: the full-width q/k RMSNorm is a reduction over the heads dim,
    which tp shards — the psum'd sum-of-squares (_flat_rmsnorm) must make
    the manual-tp trajectory match single-device exactly (a shard-local
    mean would silently diverge here)."""
    bundle = get_model("olmo2-7b", vocab_size=512, hidden_size=64,
                       intermediate_size=128, num_layers=2, num_heads=4,
                       num_kv_heads=2, max_position_embeddings=256,
                       dtype=jnp.float32)
    assert bundle.config.post_norm and bundle.config.qk_norm == "flat"
    golden_t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                       plan=make_plan("single",
                                      make_mesh(devices=jax.devices()[:1])),
                       donate=False)
    gstate = golden_t.init_state(0)
    ids = np.random.RandomState(0).randint(0, 512, (GB, SEQ))
    gbatch = {k: jax.device_put(jnp.asarray(ids), golden_t.batch_shardings()[k])
              for k in ("input_ids", "labels")}
    glosses = [float(golden_t.step_fn(gstate, gbatch)[1]["loss"])]

    for strategy, mesh_kw in (("pp", {"pp": 2}), ("pp_tp", {"pp": 2, "tp": 2})):
        t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                    plan=make_plan(strategy, make_mesh(**mesh_kw)), donate=False,
                    pp_microbatches=2)
        state = t.init_state(0)
        batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
                 for k in ("input_ids", "labels")}
        losses = [float(t.step_fn(state, batch)[1]["loss"])]
        np.testing.assert_allclose(losses, glosses, rtol=2e-4, err_msg=strategy)


def test_pp_qwen3_family(eight_devices):
    """Qwen3 under the 1F1B schedule incl. manual megatron tp: the per-head
    [head_dim] q/k norm scales are REPLICATED across tp members (the norm
    reduces over the unsharded head_dim), so the manual path needs no
    collective — trajectory must still match single-device."""
    bundle = get_model("qwen3-0.6b", vocab_size=512, hidden_size=64,
                       intermediate_size=128, num_layers=2, num_heads=4,
                       num_kv_heads=2, head_dim=16,
                       max_position_embeddings=256, dtype=jnp.float32)
    assert bundle.config.qk_norm is True
    golden_t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                       plan=make_plan("single",
                                      make_mesh(devices=jax.devices()[:1])),
                       donate=False)
    gstate = golden_t.init_state(0)
    ids = np.random.RandomState(0).randint(0, 512, (GB, SEQ))
    gbatch = {k: jax.device_put(jnp.asarray(ids), golden_t.batch_shardings()[k])
              for k in ("input_ids", "labels")}
    glosses = [float(golden_t.step_fn(gstate, gbatch)[1]["loss"])]

    t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                plan=make_plan("pp_tp", make_mesh(pp=2, tp=2)), donate=False,
                pp_microbatches=2)
    state = t.init_state(0)
    batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
             for k in ("input_ids", "labels")}
    losses = [float(t.step_fn(state, batch)[1]["loss"])]
    np.testing.assert_allclose(losses, glosses, rtol=2e-4)


def test_pp_moe_family(eight_devices):
    """MoE under the 1F1B schedule: router aux loss flows through the
    per-tick vjp (cotangent on the stage's aux output) and the trajectory
    matches the single-device MoE run."""
    bundle = get_model("moe-debug", dtype=jnp.float32)
    ids = np.random.RandomState(0).randint(0, 512, (GB, SEQ))

    def run_moe(plan, **kw):
        t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3), plan=plan,
                    donate=False, attn_impl="xla", **kw)
        state = t.init_state(0)
        batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
                 for k in ("input_ids", "labels")}
        losses = []
        for _ in range(2):
            state, m = t.step_fn(state, batch)
            losses.append(float(m["loss"]))
        return losses

    golden = run_moe(make_plan("single", make_mesh(devices=jax.devices()[:1])))
    pp = run_moe(make_plan("pp", make_mesh(pp=2)), pp_microbatches=2)
    np.testing.assert_allclose(pp, golden, rtol=2e-4)


@pytest.mark.parametrize("model,coef", [("llama-debug", None),
                                        ("moe-debug", 1.0),
                                        ("gpt2-debug", None)])
def test_pp_tp_grad_parity(eight_devices, model, coef):
    """pp x tp gradients must equal the single-device gradients EXACTLY (not
    just up to a scale — Adam is invariant to uniform grad scaling, so the
    trajectory goldens above cannot catch a tp x factor, but grad_norm,
    clipping, and plain SGD all can). The reference is the per-microbatch
    mean loss, matching the schedule's aux semantics. Covers the vocab-
    parallel head (psum-transposes-to-psum cotangent scaling) and, for moe,
    the tp-redundant router aux path."""
    from distributed_training_guide_tpu.ops.cross_entropy import causal_lm_loss
    from distributed_training_guide_tpu.parallel.pipeline import (
        make_pipeline_value_and_grad)

    kw = {"dtype": jnp.float32}
    if coef is not None:
        kw["router_aux_coef"] = coef
    bundle = get_model(model, **kw)
    cfg = bundle.config
    M = 2
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (GB, SEQ)))
    params = jax.jit(lambda: bundle.init(cfg, jax.random.key(0)))()

    def ref_loss(p):
        tot = 0.0
        for m in range(M):
            chunk = ids[m * (GB // M):(m + 1) * (GB // M)]
            if bundle.apply_with_aux is not None:
                logits, aux = bundle.apply_with_aux(cfg, p, chunk, attn_impl="xla")
                tot += causal_lm_loss(logits, chunk) + cfg.router_aux_coef * aux
            else:
                tot += causal_lm_loss(
                    bundle.apply(cfg, p, chunk, attn_impl="xla"), chunk)
        return tot / M

    ref_l, ref_g = jax.jit(jax.value_and_grad(ref_loss))(params)

    plan = make_plan("pp_tp", make_mesh(pp=2, tp=2, devices=jax.devices()[:4]))
    vag = make_pipeline_value_and_grad(bundle, plan, microbatches=M,
                                       attn_impl="xla")
    shardings = plan.param_shardings(
        bundle.param_logical_axes(cfg),
        jax.eval_shape(lambda: bundle.init(cfg, jax.random.key(0))))
    l, g = jax.jit(vag)(jax.device_put(params, shardings),
                        {"input_ids": ids, "labels": ids})

    np.testing.assert_allclose(float(l), float(ref_l), rtol=1e-6)
    for (path, r), p in zip(jax.tree_util.tree_flatten_with_path(ref_g)[0],
                            jax.tree.leaves(g)):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(p)), np.asarray(r), rtol=5e-3, atol=1e-5,
            err_msg=jax.tree_util.keystr(path))


def test_pp_tp_moe_trajectory(eight_devices):
    """pp=2 x tp=2 x dp=2 with the MoE family: megatron expert-FFN shards +
    vocab-parallel embed/head, trajectory matches single-device."""
    bundle = get_model("moe-debug", dtype=jnp.float32)
    ids = np.random.RandomState(0).randint(0, 512, (GB, SEQ))

    def run_moe(plan, **kw):
        t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3), plan=plan,
                    donate=False, attn_impl="xla", **kw)
        state = t.init_state(0)
        batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
                 for k in ("input_ids", "labels")}
        losses = []
        for _ in range(2):
            state, m = t.step_fn(state, batch)
            losses.append(float(m["loss"]))
        return losses

    golden = run_moe(make_plan("single", make_mesh(devices=jax.devices()[:1])))
    pp_tp = run_moe(make_plan("pp_tp", make_mesh(pp=2, tp=2)),
                    pp_microbatches=2)
    np.testing.assert_allclose(pp_tp, golden, rtol=2e-4)


def test_pp_with_loss_chunks(golden, eight_devices):
    # chunked CE on the last stage: same trajectory, no [mb,S,V] logits
    bundle = get_model("llama-debug", dtype=jnp.float32)
    t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                plan=make_plan("pp", make_mesh(pp=2)), donate=False,
                pp_microbatches=2, loss_chunks=4)
    state = t.init_state(0)
    ids = np.random.RandomState(0).randint(0, 512, (GB, SEQ))
    batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
             for k in ("input_ids", "labels")}
    losses = []
    for _ in range(2):
        state, m = t.step_fn(state, batch)
        losses.append(float(m["loss"]))
    np.testing.assert_allclose(losses, golden[0], rtol=2e-4)


def test_pp_rejects_per_layer_windows_pinned_contract(eight_devices):
    """The documented pp x layer_windows contract (09-pipeline-parallel
    README "Known limits"): traced per-layer window schedules (Gemma-2's
    alternating pattern) are NOT plumbed through the pipeline's manual
    region — construction must fail loudly, naming the limitation and the
    supported plans, BEFORE any compile. A UNIFORM sliding window has no
    traced per-layer column and stays accepted under pp."""
    lw_bundle = get_model("llama-debug", dtype=jnp.float32,
                          layer_windows=(16, 0))
    with pytest.raises(ValueError,
                       match="layer_windows.*pipeline|pipeline.*layer_win"):
        Trainer(bundle=lw_bundle, optimizer=adamw_cosine(1e-3),
                plan=make_plan("pp", make_mesh(pp=2)), donate=False,
                pp_microbatches=2)
    # same config on a cp plan (the composing case) constructs fine
    Trainer(bundle=lw_bundle, optimizer=adamw_cosine(1e-3),
            plan=make_plan("ddp", make_mesh(cp=2)), donate=False)
    # uniform window under pp: accepted (no per-layer column involved)
    sw_bundle = get_model("llama-debug", dtype=jnp.float32,
                          sliding_window=16)
    Trainer(bundle=sw_bundle, optimizer=adamw_cosine(1e-3),
            plan=make_plan("pp", make_mesh(pp=2)), donate=False,
            pp_microbatches=2)
