"""MoE model + expert-parallel plan tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.ops import causal_lm_loss
from distributed_training_guide_tpu.parallel import make_mesh, make_plan
from distributed_training_guide_tpu.train import Trainer, adamw_cosine
from distributed_training_guide_tpu.utils import hlo as hlo_util


def test_moe_forward_and_grads():
    bundle = get_model("moe-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 16), 0, bundle.config.vocab_size)
    logits, aux = bundle.apply_with_aux(bundle.config, params, ids)
    assert logits.shape == (2, 16, bundle.config.vocab_size)
    # aux >= 1 for any routing (equals num_experts * sum f_e p_e >= 1)
    assert float(aux) >= 0.99

    def loss_fn(p):
        lg, ax = bundle.apply_with_aux(bundle.config, p, ids)
        return causal_lm_loss(lg, ids) + 0.01 * ax

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    # router must receive gradient (routing is differentiable through combine)
    g_router = grads["layers"]["moe"]["router"]
    assert float(jnp.linalg.norm(g_router)) > 0


def test_moe_capacity_drops_are_bounded():
    """With capacity_factor >= num_experts every token fits (no drops):
    output must equal a full-capacity run."""
    bundle_small = get_model("moe-debug", dtype=jnp.float32, capacity_factor=8.0)
    bundle_huge = get_model("moe-debug", dtype=jnp.float32, capacity_factor=16.0)
    params = bundle_small.init(bundle_small.config, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (1, 16), 0, 512)
    a, _ = bundle_small.apply_with_aux(bundle_small.config, params, ids)
    b, _ = bundle_huge.apply_with_aux(bundle_huge.config, params, ids)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_moe_overflow_tokens_get_zero_output():
    """Force every token onto one expert with capacity 2: exactly the first 2
    tokens (greedy order) get expert output; overflow rows are exactly zero
    (they fall through on the residual)."""
    from distributed_training_guide_tpu.models.moe import _moe_ffn

    bundle = get_model("moe-debug", dtype=jnp.float32, experts_per_token=1,
                       capacity_factor=0.5)  # C = ceil(0.5 * 16 / 4) = 2
    cfg = bundle.config
    d, f, ex = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    rng = jax.random.key(0)
    router = jnp.zeros((d, ex)).at[:, 0].set(1.0)  # all tokens -> expert 0
    moe_params = {
        "router": router,
        "gate": jax.random.normal(rng, (ex, d, f)) * 0.02,
        "up": jax.random.normal(rng, (ex, d, f)) * 0.02,
        "down": jax.random.normal(rng, (ex, f, d)) * 0.02,
    }
    x = jnp.ones((1, 16, d))
    y, _, dropped = _moe_ffn(cfg, x, moe_params)
    y = np.asarray(y)[0]
    norms = np.linalg.norm(y, axis=-1)
    assert (norms[:2] > 0).all(), "in-capacity tokens must get expert output"
    np.testing.assert_array_equal(norms[2:], 0.0)
    np.testing.assert_allclose(float(dropped), 14 / 16, rtol=1e-6)


def test_ep_matches_single_device(eight_devices):
    """Params are created once and fed to every trainer: under a
    vocab/embed-sharded mesh the sharded init RNG draws different embedding
    values than single-device (non-partitionable threefry under GSPMD),
    which is init noise, not dispatch error — sharing the params pins the
    thing this test is about (the ep dispatch math) and lets the tolerance
    stay tight."""
    bundle = get_model("moe-debug", dtype=jnp.float32)
    opt = adamw_cosine(1e-3)
    ids = np.random.RandomState(0).randint(0, 512, (8, 32))
    params = bundle.init(bundle.config, jax.random.key(0))

    def run(plan):
        t = Trainer(bundle=bundle, optimizer=opt, plan=plan, donate=False)
        state = t.init_state_from_params(jax.device_put(params), 0)
        batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
                 for k in ("input_ids", "labels")}
        losses = []
        for _ in range(2):
            state, m = t.step_fn(state, batch)
            losses.append(float(m["loss"]))
        return losses, state

    golden, _ = run(make_plan("single", make_mesh(devices=jax.devices()[:1])))
    ep_losses, state = run(make_plan("ep", make_mesh(ep=4)))
    np.testing.assert_allclose(ep_losses, golden, rtol=2e-4)
    gate = state.params["layers"]["moe"]["gate"]
    assert gate.sharding.spec[1] == "ep"  # expert dim sharded

    ep_fsdp, _ = run(make_plan("ep_fsdp", make_mesh(ep=2, fsdp=2)))
    np.testing.assert_allclose(ep_fsdp, golden, rtol=2e-4)


def test_ep_dispatch_stays_local(eight_devices):
    """HLO-level locality proof for the index-based dispatch (the weight
    sharding + loss-trajectory checks above would NOT fail if GSPMD silently
    gathered the [E,C,D] buffers or the expert weights around the scatter —
    the silent-replication failure class the sharded-flash wrapper fixed).
    At E=8, ep=8: the compiled program must hold only E/ep-local expert
    buffers and weight shards on any device — the full-E shapes appearing
    anywhere means gather-and-replicate, which is also the per-device
    memory guarantee (1/ep buffers + weights, not Ex)."""
    import math

    bundle = get_model("moe-debug", dtype=jnp.float32, num_experts=8)
    cfg = bundle.config
    t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                plan=make_plan("ep", make_mesh(ep=8)), donate=False,
                attn_impl="xla")
    state = t.init_state(0)
    b, s = 8, 32
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (b, s))
    batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
             for k in ("input_ids", "labels")}
    hlo = jax.jit(t.step_fn).lower(state, batch).compile().as_text()

    E, D, F, L = (cfg.num_experts, cfg.hidden_size, cfg.intermediate_size,
                  cfg.num_layers)
    C = max(int(math.ceil(cfg.capacity_factor * cfg.experts_per_token
                          * b * s / E)), 1)
    # local (E/ep = 1) expert compute is present — the [1, C, F] inner
    # activation must materialize around the silu*up elementwise. (The
    # [1, C, D] INPUT buffer is no longer asserted: the gather-only
    # dispatch fuses it into the expert einsum, so it never exists as a
    # standalone tensor — that fusion is the point of the formulation.)
    assert hlo_util.has_aval(hlo, "f32", (1, C, F)), \
        "no ep-local expert activation in HLO"
    # ...and no device ever materializes the full-E dispatch/activation
    # buffers or the full expert-weight stacks (params, grads, or moments)
    for full in ((E, C, D), (E, C, F), (L, E, D, F), (L, E, F, D)):
        assert not hlo_util.has_aval(hlo, "f32", full), \
            f"full-E tensor f32{list(full)} in compiled HLO"


# ---------------------------------------------------------------------------
# dropless ragged dispatch (moe_dispatch="ragged", PR 3)
# ---------------------------------------------------------------------------

@pytest.mark.grouped
def test_ragged_matches_dense_loss_trajectory():
    """Acceptance pin: with capacity_factor high enough that dense drops
    nothing, the ragged backend must track the dense loss trajectory within
    1e-5 relative over 20 optimizer steps (same seed, same data) — the two
    dispatches are then the same math, reassociated."""
    opt = adamw_cosine(1e-3)
    ids = np.random.RandomState(7).randint(0, 512, (4, 32))

    def run(dispatch):
        bundle = get_model("moe-debug", dtype=jnp.float32,
                           capacity_factor=8.0, moe_dispatch=dispatch)
        t = Trainer(bundle=bundle, optimizer=opt,
                    plan=make_plan("single",
                                   make_mesh(devices=jax.devices()[:1])),
                    donate=False)
        state = t.init_state(0)
        batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
                 for k in ("input_ids", "labels")}
        losses, dropped = [], []
        for _ in range(20):
            state, m = t.step_fn(state, batch)
            losses.append(float(m["loss"]))
            dropped.append(float(m["moe_dropped_frac"]))
        return losses, dropped

    dense_losses, dense_dropped = run("dense")
    ragged_losses, ragged_dropped = run("ragged")
    assert max(dense_dropped) == 0.0  # precondition: dense dropped nothing
    np.testing.assert_allclose(ragged_losses, dense_losses, rtol=1e-5)
    assert ragged_dropped == [0.0] * 20


@pytest.mark.grouped
def test_ragged_dropped_frac_zero_even_when_dense_drops():
    """dropped_frac must be identically 0 under ragged dispatch — even at a
    capacity_factor where the dense backend drops most pairs (capacity is
    simply not a ragged concept), and every token must get expert output."""
    from distributed_training_guide_tpu.models.moe import _moe_ffn

    dense = get_model("moe-debug", dtype=jnp.float32, experts_per_token=1,
                      capacity_factor=0.5)
    ragged = get_model("moe-debug", dtype=jnp.float32, experts_per_token=1,
                       capacity_factor=0.5, moe_dispatch="ragged")
    params = dense.init(dense.config, jax.random.key(0))
    moe_layer0 = jax.tree.map(lambda x: x[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.key(1), (1, 16, dense.config.hidden_size))
    _, _, d_dense = _moe_ffn(dense.config, x, moe_layer0)
    y, _, d_ragged = _moe_ffn(ragged.config, x, moe_layer0)
    assert float(d_dense) >= 0.5         # dense is actually dropping here
    assert float(d_ragged) == 0.0
    norms = np.linalg.norm(np.asarray(y)[0], axis=-1)
    assert (norms > 0).all(), "dropless: every token gets expert output"


@pytest.mark.grouped
def test_ep_ragged_matches_single_device(eight_devices):
    """ep / ep x fsdp ragged runs (the shard_map'd sorted-group exchange)
    must reproduce the single-device ragged trajectory. Params are created
    once and fed to every trainer: sharded RNG makes vocab-sharded init
    draw different values (pre-existing; the dense test absorbs it in its
    tolerance), and this test pins the *dispatch* math, not the init."""
    bundle = get_model("moe-debug", dtype=jnp.float32, moe_dispatch="ragged")
    opt = adamw_cosine(1e-3)
    ids = np.random.RandomState(0).randint(0, 512, (8, 32))
    params = bundle.init(bundle.config, jax.random.key(0))

    def run(plan):
        t = Trainer(bundle=bundle, optimizer=opt, plan=plan, donate=False)
        state = t.init_state_from_params(jax.device_put(params), 0)
        batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
                 for k in ("input_ids", "labels")}
        losses = []
        for _ in range(3):
            state, m = t.step_fn(state, batch)
            losses.append(float(m["loss"]))
        return losses, m, state

    golden, _, _ = run(make_plan("single", make_mesh(devices=jax.devices()[:1])))
    ep_losses, m, state = run(make_plan("ep", make_mesh(ep=4)))
    np.testing.assert_allclose(ep_losses, golden, rtol=2e-5)
    assert float(m["moe_dropped_frac"]) == 0.0
    gate = state.params["layers"]["moe"]["gate"]
    assert gate.sharding.spec[1] == "ep"   # expert dim stays ep-sharded

    epf_losses, _, _ = run(make_plan("ep_fsdp", make_mesh(ep=2, fsdp=2)))
    np.testing.assert_allclose(epf_losses, golden, rtol=2e-5)


@pytest.mark.grouped
def test_ep_ragged_keeps_expert_stacks_local(eight_devices):
    """Compiled-HLO locality proof for the ragged backend, mirroring
    test_ep_dispatch_stays_local: at E=8, ep=8 no device may materialize
    the full expert weight stacks (params, grads, or moments) — the
    sorted-group exchange must keep grouped GEMMs on E/ep-local shards."""
    bundle = get_model("moe-debug", dtype=jnp.float32, num_experts=8,
                       moe_dispatch="ragged")
    cfg = bundle.config
    t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                plan=make_plan("ep", make_mesh(ep=8)), donate=False,
                attn_impl="xla")
    state = t.init_state(0)
    b, s = 8, 32
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (b, s))
    batch = {k: jax.device_put(jnp.asarray(ids), t.batch_shardings()[k])
             for k in ("input_ids", "labels")}
    hlo = jax.jit(t.step_fn).lower(state, batch).compile().as_text()

    E, D, F, L = (cfg.num_experts, cfg.hidden_size, cfg.intermediate_size,
                  cfg.num_layers)
    # local (E/ep = 1) expert weight shards are what the device holds (the
    # per-layer slice fuses into the scan body, so assert the stacked form)
    assert hlo_util.has_aval(hlo, "f32", (L, 1, D, F)), \
        "no ep-local expert stack in HLO"
    for full in ((L, E, D, F), (L, E, F, D), (E, D, F), (E, F, D)):
        assert not hlo_util.has_aval(hlo, "f32", full), \
            f"full-E tensor f32{list(full)} in compiled HLO"


@pytest.mark.grouped
def test_decode_no_drop_transients_scale_with_tokens():
    """Acceptance pin for the decode-path memory fix: lowering a
    qwen1.5-moe prefill chunk of T=2048 (the serve path's chunk program:
    ``paged_decode_step`` over the page pools) must show O(t*k*d) dispatch
    transients (the [kT, D] sorted buffer), and NONE of the old no_drop
    path's O(E*k*t*d) worst-case capacity buffers ([E, kT, D] /
    [E, kT, F] — ~2 GiB a layer in bf16). Abstract lowering only: no
    weights materialize."""
    from distributed_training_guide_tpu.models import moe
    from distributed_training_guide_tpu.serve import kv_pages

    cfg = moe.PRESETS["qwen1.5-moe-a2.7b"]
    T, page = 2048, 16
    params = jax.eval_shape(lambda: moe.init(cfg, jax.random.key(0)))
    cache = jax.eval_shape(
        lambda: kv_pages.init_pages(cfg, 1 + T // page, page))
    ids = jax.ShapeDtypeStruct((1, T), jnp.int32)
    table = jnp.arange(1, 1 + T // page, dtype=jnp.int32)[None]
    start = jnp.zeros(1, jnp.int32)
    txt = jax.jit(lambda p, i, c: moe.paged_decode_step(
        cfg, p, i, start, c,
        kv_pages.make_attend(table, start, impl="xla"),
        last_index=T - 1)).lower(params, ids, cache).as_text()
    kT = cfg.experts_per_token * T
    E, D, F = cfg.num_experts, cfg.hidden_size, cfg.intermediate_size
    assert hlo_util.has_shape_run(txt, (kT, D)), \
        "ragged [kT, D] sorted buffer missing"
    for dense_shape in ((E, kT, D), (E, kT, F), (kT, E)):
        assert not hlo_util.has_shape_run(txt, dense_shape), (
            f"O(E*k*t) dispatch transient {list(dense_shape)} in decode "
            f"lowering")


@pytest.mark.grouped
def test_moe_dispatch_validation():
    """Unknown moe_dispatch values fail loudly at Trainer build (and at
    forward time for direct model users)."""
    bundle = get_model("moe-debug", dtype=jnp.float32, moe_dispatch="sparse")
    with pytest.raises(ValueError, match="unknown moe_dispatch"):
        Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3), donate=False)
    params = bundle.init(bundle.config, jax.random.key(0))
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="unknown moe_dispatch"):
        bundle.apply_with_aux(bundle.config, params, ids)


def test_moe_per_layer_windows_flash_matches_xla():
    """MoE families thread the per-layer window column through their scan
    too (VERDICT #8b): moe-debug with an alternating sliding/full pattern —
    fwd+grad parity between the flash (interpret) and xla paths, and the
    band must genuinely bind (different loss than unwindowed). seq 32 >
    window 8, fp32."""
    bundle = get_model("moe-debug", dtype=jnp.float32, layer_windows=(8, 0))
    assert bundle.config.layer_windows == (8, 0)
    params = bundle.init(bundle.config, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 32), 0,
                             bundle.config.vocab_size)

    def loss_fn(p, impl):
        lg, ax = bundle.apply_with_aux(bundle.config, p, ids, attn_impl=impl)
        return causal_lm_loss(lg, ids) + 0.01 * ax

    lx, gx = jax.value_and_grad(loss_fn)(params, "xla")
    lf, gf = jax.value_and_grad(loss_fn)(params, "flash")
    np.testing.assert_allclose(float(lf), float(lx), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gx)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)

    # the window binds: an unwindowed twin's logits must differ
    full = get_model("moe-debug", dtype=jnp.float32)
    lg_win, _ = bundle.apply_with_aux(bundle.config, params, ids)
    lg_full, _ = full.apply_with_aux(full.config, params, ids)
    assert float(jnp.max(jnp.abs(lg_win - lg_full))) > 1e-4


# ---- the paged step's expert leaves -----------------------------------------

def expert_leaves_in_the_layer_scan(closed_jaxpr, moe_leaves) -> dict:
    """How the ``[L, E, K, N]`` routed-expert leaves reach a traced paged
    step's layer scan: ``sliced`` counts those among its scanned columns (a
    layer's ``[E, K, N]`` is sliced out each iteration), ``whole`` those its
    body closes over as ``[L * E, K, N]``."""
    stacked = {tuple(moe_leaves[k].shape) for k in ("gate", "up", "down")}
    flat = {(s[0] * s[1], *s[2:]) for s in stacked}
    found = {"sliced": 0, "whole": 0}
    for eqn in hlo_util._eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name != "scan":
            continue
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        found["whole"] += sum(tuple(v.aval.shape) in flat
                              for v in eqn.invars[:nc])
        found["sliced"] += sum(tuple(v.aval.shape) in stacked
                               for v in eqn.invars[nc + nk:])
    return found


def paged_step_logits(mod, config, params, tokens, page=4):
    """``mod.paged_decode_step`` teacher-forced over ``tokens`` as a chunk of
    all but the last and then one decode step; returns ``(jaxpr of the
    decode step, [chunk logits, decode logits])``."""
    from distributed_training_guide_tpu.serve import kv_pages

    n = len(tokens) - 1
    n_pages = 2 + -(-len(tokens) // page)
    pages = kv_pages.init_pages(config, n_pages, page)
    table = jnp.arange(1, n_pages, dtype=jnp.int32)[None]

    def step(p, kp, vp, ids, pos):
        nv = jnp.asarray([ids.shape[1]])
        logits, cache = mod.paged_decode_step(
            config, p, ids, pos, {"k": kp, "v": vp},
            kv_pages.make_attend(table, pos, impl="xla", n_valid=nv))
        return logits, cache["k"], cache["v"]

    first, kp, vp = jax.jit(step)(params, pages["k"], pages["v"],
                                  jnp.asarray([tokens[:n]]), jnp.asarray([0]))
    last = (params, kp, vp, jnp.asarray([tokens[n:]]), jnp.asarray([n]))
    return jax.make_jaxpr(step)(*last), [first[0], jax.jit(step)(*last)[0][0]]


def check_in_place_and_sliced_experts_agree(mod, bundle_for):
    """Shared by this family and the latent one (tests/test_mla.py): leaves
    stored in the compute dtype ride the layer scan whole and ``gmm`` reads
    them at ``layer * E``; fp32 leaves under a bf16 compute dtype stay
    scanned columns and the layer's slice is cast, as before. The two are the
    same numbers: the in-place program on the bf16-cast leaves returns the
    slice-then-cast program's logits bit for bit."""
    bundle = bundle_for(jnp.bfloat16)
    config = bundle.config
    params = bundle.init(config, jax.random.key(0))
    assert params["layers"]["moe"]["gate"].dtype == jnp.float32
    tokens = [int(x) for x in np.random.default_rng(3).integers(
        0, config.vocab_size, 11)]
    jaxpr, cast_late = paged_step_logits(mod, config, params, tokens)
    assert expert_leaves_in_the_layer_scan(
        jaxpr, params["layers"]["moe"]) == {"sliced": 3, "whole": 0}

    cast = {**params["layers"]["moe"], **{
        k: params["layers"]["moe"][k].astype(jnp.bfloat16)
        for k in ("gate", "up", "down")}}
    early = {**params, "layers": {**params["layers"], "moe": cast}}
    jaxpr, in_place = paged_step_logits(mod, config, early, tokens)
    assert expert_leaves_in_the_layer_scan(
        jaxpr, cast) == {"sliced": 0, "whole": 3}
    for a, b in zip(in_place, cast_late):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))

    # and in fp32 (leaves and compute) the in-place step is the plain
    # forward, token for token
    bundle = bundle_for(jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    jaxpr, (chunk, decode) = paged_step_logits(mod, bundle.config, params,
                                               tokens)
    assert expert_leaves_in_the_layer_scan(
        jaxpr, params["layers"]["moe"]) == {"sliced": 0, "whole": 3}
    want = bundle.apply(bundle.config, params, jnp.asarray([tokens]))[0]
    assert int(jnp.argmax(chunk)) == int(jnp.argmax(want[-2]))
    assert int(jnp.argmax(decode)) == int(jnp.argmax(want[-1]))
    np.testing.assert_allclose(np.asarray(decode), np.asarray(want[-1]),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.grouped
def test_paged_step_reads_the_experts_in_place_or_slices_then_casts():
    from distributed_training_guide_tpu.models import moe

    check_in_place_and_sliced_experts_agree(
        moe, lambda dtype: get_model("moe-debug", dtype=dtype,
                                     capacity_factor=4.0))


@pytest.mark.grouped
def test_in_place_experts_serve_the_recompute_tokens():
    """The engine on fp32 leaves under fp32 compute (read in place) against
    the full recompute, token for token (a held share of the experts:
    tests/test_mla.py, the family whose config states one)."""
    from distributed_training_guide_tpu.serve import Request, ServeEngine
    from distributed_training_guide_tpu.serve.api import generate_many

    bundle = get_model("moe-debug", dtype=jnp.float32, moe_dispatch="ragged")
    params = bundle.init(bundle.config, jax.random.key(0))
    reqs = [Request(prompt_ids=[3, 17, 42, 7, 9], max_new_tokens=6),
            Request(prompt_ids=[5, 6], max_new_tokens=8)]
    res = generate_many(
        ServeEngine(bundle, params, n_slots=2, page_size=4, max_len=32), reqs)
    for r in res:
        cur = list(r.prompt_ids)
        for _ in r.generated_ids:
            logits = bundle.apply(bundle.config, params, jnp.asarray([cur]))
            cur.append(int(jnp.argmax(logits[0, -1])))
        assert r.token_ids == cur


def test_dense_familys_layer_scan_still_slices_every_leaf():
    """The dense families hand ``scan_paged_layers`` every stacked leaf as a
    scanned column, as before the MoE families took theirs out: the decode
    step's layer scan closes over no weight (its constants are the step's
    small arrays), so their lowered programs are what they were."""
    from distributed_training_guide_tpu.models import llama

    bundle = get_model("llama-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    jaxpr, _ = paged_step_logits(llama, bundle.config, params,
                                 [3, 17, 42, 7, 9])
    (scan,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    nc, nk = scan.params["num_consts"], scan.params["num_carry"]
    columns = [tuple(v.aval.shape) for v in scan.invars[nc + nk:]]
    leaves = [tuple(x.shape) for x in jax.tree.leaves(params["layers"])]
    assert sorted(columns) == sorted(leaves + [(bundle.config.num_layers,)])
    weight_sized = min(np.prod(s) for s in leaves if len(s) > 2)
    assert all(np.prod(v.aval.shape) < weight_sized
               for v in scan.invars[:nc])
