"""End-to-end chapter-loop integration tests through run_training.

The reference's only 'tests' are its runnable smoke commands (SURVEY.md §4);
these are those smoke runs as pytest: full loop (data -> sharded step ->
logging -> checkpoint -> resume) on the virtual 8-device mesh, for the ddp and
tp_fsdp plans, plus the engine facade.
"""
import argparse

import jax
import numpy as np
import pytest

from distributed_training_guide_tpu.parallel import make_mesh, make_plan
from distributed_training_guide_tpu.train.cli import get_parser, run_training


def make_args(tmp_path, **over):
    args = get_parser().parse_args(["-m", "llama-debug"])
    args.dataset_name = "synthetic:60000"
    args.seq_length = 64
    args.batch_size = 1
    args.num_epochs = 1
    args.log_freq = 2
    args.max_steps = 4
    args.save_dir = str(tmp_path)
    for k, v in over.items():
        setattr(args, k, v)
    return args


def test_run_training_ddp(tmp_path, eight_devices):
    args = make_args(tmp_path)
    out = run_training(args, lambda: make_plan("ddp", make_mesh()))
    assert out["host_state"]["global_step"] == 4
    assert np.isfinite(out["last_info"]["running_loss"])
    assert out["last_info"]["tokens_per_s"] > 0


@pytest.mark.parametrize("attn_impl,traced", [("flash", "flash"),
                                              ("auto", "xla")])
def test_start_up_lines_describe_the_step_that_runs(tmp_path, eight_devices,
                                                    capsys, attn_impl, traced):
    """The JSON lines chip_smoke.py holds a run to. The device line names
    the attention implementation the lowered step TRACED (not one re-derived
    from the flags), the step is compiled once ahead of time and that
    executable is the one described (compile seconds; on a mesh its
    collectives) and the one that takes the steps, and after the first step
    every device's bytes_in_use is printed."""
    import json

    args = make_args(tmp_path, attn_impl=attn_impl, batch_size=1)
    out = run_training(args, lambda: make_plan(
        "fsdp", make_mesh(fsdp=4, devices=eight_devices[:4])))
    assert out["host_state"]["global_step"] == 4
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    device = next(l for l in lines if "device" in l)
    assert device["device"] == {"platform": "cpu", "device_kind": "cpu",
                                "count": len(jax.devices())}
    assert device["attention"]["impl"] == traced
    program = next(l["step_program"] for l in lines if "step_program" in l)
    assert program["compile_s"] > 0
    assert program["collectives"]["counts"]["all-gather"] > 0
    assert isinstance(program["collectives"]["largest_all_reduce_bytes"], int)
    memory = next(l["device_memory"] for l in lines if "device_memory" in l)
    assert [d["id"] for d in memory] == [d.id for d in jax.local_devices()]
    assert sum("step_program" in l for l in lines) == 1


def test_step_program_line_says_how_the_loss_head_is_gathered(
        tmp_path, eight_devices, capsys):
    """Under ``--loss-chunks`` on a mesh the line that describes the compiled
    step also says what the trainer chose for a sharded output matrix."""
    import json

    args = make_args(tmp_path, loss_chunks=4)
    run_training(args, lambda: make_plan(
        "fsdp", make_mesh(fsdp=4, devices=eight_devices[:4])))
    program = next(json.loads(l)["step_program"]
                   for l in capsys.readouterr().out.splitlines()
                   if l.startswith('{"step_program"'))
    assert "one gather, one reduce-scatter a step" in program["loss_head"]


def test_run_training_sliding_window_flag(tmp_path, eight_devices):
    """--sliding-window W overrides the model config and trains through the
    banded attention; loss differs from the full-causal run (the band binds)."""
    full = run_training(make_args(tmp_path / "a"),
                        lambda: make_plan("ddp", make_mesh()))
    swa = run_training(make_args(tmp_path / "b", sliding_window=16),
                       lambda: make_plan("ddp", make_mesh()))
    assert np.isfinite(swa["last_info"]["running_loss"])
    assert (abs(swa["last_info"]["running_loss"]
                - full["last_info"]["running_loss"]) > 1e-6)


def test_run_training_profile_trace(tmp_path, eight_devices):
    """--profile-dir captures a steady-state jax.profiler window (steps
    10-15, the C22 diagnostics surface) — never exercised by the other
    smokes, whose max_steps stops before the trace starts."""
    args = make_args(tmp_path, profile_dir=str(tmp_path / "prof"),
                     max_steps=15)
    run_training(args, lambda: make_plan("ddp", make_mesh()))
    produced = [p for p in (tmp_path / "prof").rglob("*") if p.is_file()]
    assert produced, "profiler trace directory is empty"


def test_run_training_fence_every_matches_per_step(tmp_path, eight_devices):
    """--fence-every N banks device losses and drains at fence/log/ckpt
    boundaries (the dispatch-ahead lever). The computation is unchanged, so
    the logged running_loss trajectory must be BIT-identical to the
    per-step-fenced default — including a fence group (3) that doesn't
    divide log_freq (2)."""
    out1 = run_training(make_args(tmp_path / "f1"),
                        lambda: make_plan("ddp", make_mesh()))
    out3 = run_training(make_args(tmp_path / "f3", fence_every=3),
                        lambda: make_plan("ddp", make_mesh()))
    assert out3["last_info"]["running_loss"] == out1["last_info"]["running_loss"]
    assert out3["host_state"]["global_step"] == out1["host_state"]["global_step"]


def test_run_training_param_dtype_bf16(tmp_path, eight_devices):
    """--param-dtype bfloat16 (the bench sweep's bf16-state lever as a
    product flag): params AND the mirrored optimizer moments store in bf16."""
    import jax.numpy as jnp

    args = make_args(tmp_path, param_dtype="bfloat16")
    out = run_training(args, lambda: make_plan("ddp", make_mesh()))
    assert out["host_state"]["global_step"] == 4
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree.leaves(out["state"].params))


def test_run_training_fence_every_rejects_zero(tmp_path, eight_devices):
    with pytest.raises(SystemExit):
        run_training(make_args(tmp_path, fence_every=0),
                     lambda: make_plan("ddp", make_mesh()))


def test_run_training_tp_fsdp_with_accum(tmp_path, eight_devices):
    args = make_args(tmp_path, grad_accum=2, batch_size=2,
                     checkpoint_activations=True)
    out = run_training(args, lambda: make_plan("tp_fsdp", make_mesh(tp=2, fsdp=2)))
    assert out["host_state"]["global_step"] == 4


def test_run_training_checkpoint_resume(tmp_path, eight_devices):
    args = make_args(tmp_path, experiment_name="exp", ckpt_freq=2, max_steps=3)
    plan_factory = lambda: make_plan("fsdp", make_mesh(fsdp=8))
    out1 = run_training(args, plan_factory)
    assert out1["host_state"]["global_step"] == 3
    # second invocation resumes from step 2's checkpoint and continues
    args2 = make_args(tmp_path, experiment_name="exp", ckpt_freq=2, max_steps=5)
    out2 = run_training(args2, plan_factory)
    assert out2["host_state"]["global_step"] == 5
    assert int(out2["state"].step) >= 3


def test_run_training_fence_checkpoint_resume_exact(tmp_path, eight_devices):
    """Resume under --fence-every where the fence group (3) straddles the
    checkpoint boundary (ckpt_freq 2): the pre-save drain must leave
    host_state's running_loss current, so the resumed run's logged
    trajectory is bit-identical to an uninterrupted per-step-fenced run."""
    plan_factory = lambda: make_plan("ddp", make_mesh())
    golden = run_training(make_args(tmp_path / "g", log_freq=5, max_steps=5),
                          plan_factory)

    args = make_args(tmp_path / "r", experiment_name="exp", ckpt_freq=2,
                     log_freq=5, max_steps=3, fence_every=3)
    out1 = run_training(args, plan_factory)
    assert out1["host_state"]["global_step"] == 3
    # resume must actually engage — otherwise run 2 retrains 1-5 from
    # scratch and the bit-equality below would pass vacuously
    from distributed_training_guide_tpu.checkpoint import CheckpointIO

    assert CheckpointIO(tmp_path / "r" / "exp").can_resume()
    args2 = make_args(tmp_path / "r", experiment_name="exp", ckpt_freq=2,
                      log_freq=5, max_steps=5, fence_every=3)
    out2 = run_training(args2, plan_factory)
    assert out2["host_state"]["global_step"] == 5
    assert int(out2["state"].step) >= 3  # continued, not retrained
    assert (out2["last_info"]["running_loss"]
            == golden["last_info"]["running_loss"])


def test_engine_roundtrip(tmp_path, eight_devices):
    from distributed_training_guide_tpu.train.engine import initialize

    config = {
        "model": "llama-debug",
        "zero_optimization": {"stage": 1},
        "tensor_parallel": 2,
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
    }
    engine = initialize(config)
    # stage1 + tp must keep ZeRO-1 opt-state sharding
    mu = engine.state.opt_state[0].mu["layers"]["attn"]["wq"]
    assert any(s is not None for s in mu.sharding.spec)
    ids = np.random.RandomState(0).randint(0, 512, (engine.global_batch_size, 32))
    batch_sh = engine.trainer.batch_shardings()
    batch = {k: jax.device_put(ids, batch_sh[k]) for k in ("input_ids", "labels")}
    m1 = engine.train_batch(batch)
    assert np.isfinite(m1["loss"])
    engine.save_checkpoint(tmp_path / "eng")
    host = engine.load_checkpoint(tmp_path / "eng")
    assert host["global_step"] == 1


def test_engine_accepts_canonical_deepspeed_config(eight_devices):
    """A config in the REFERENCE's exact ds_config.json shape (nested
    WarmupCosineLR scheduler params, offload flags under zero_optimization)
    must be honored, not silently ignored — only `model` is added."""
    from distributed_training_guide_tpu.train.engine import initialize

    config = {
        "model": "llama-debug",
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-5}},
        "scheduler": {"type": "WarmupCosineLR",
                      "params": {"total_num_steps": 777,
                                 "warmup_num_steps": 5,
                                 "cos_min_ratio": 1e-2}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3, "offload_param": False,
                              "offload_optimizer": False},
    }
    engine = initialize(config)
    assert engine.scheduler_config == {"t_max": 772, "warmup_steps": 5,
                                       "eta_min_ratio": 1e-2,
                                       "decay": "cosine"}  # 777 - 5 warmup:
    # DS decay ENDS at total_num_steps; native t_max counts post-warmup
    assert not engine.trainer.offload_opt_state
    ids = np.random.RandomState(0).randint(0, 512, (engine.global_batch_size, 32))
    batch_sh = engine.trainer.batch_shardings()
    batch = {k: jax.device_put(ids, batch_sh[k]) for k in ("input_ids", "labels")}
    assert np.isfinite(engine.train_batch(batch)["loss"])

    # the {"device": "none"} dict is DeepSpeed's canonical DISABLE spelling
    # — a truthy-dict check would invert it
    off = initialize({"model": "llama-debug",
                      "zero_optimization": {
                          "stage": 3,
                          "offload_optimizer": {"device": "none"},
                          "offload_param": {"device": "none"}}})
    assert not off.trainer.offload_opt_state and not off.trainer.offload_params

    with pytest.raises(ValueError, match="scheduler.type"):
        initialize({"model": "llama-debug",
                    "scheduler": {"type": "OneCycle", "params": {}}})
    with pytest.raises(ValueError, match="scheduler.params"):
        initialize({"model": "llama-debug",
                    "scheduler": {"type": "WarmupCosineLR",
                                  "params": {"warmup_max_lr": 1e-4}}})

    # WarmupDecayLR = DS's linear decay-to-zero; it maps to the linear
    # schedule (NOT silently onto cosine), and cos_min_ratio is invalid there
    lin = initialize({"model": "llama-debug",
                      "scheduler": {"type": "WarmupDecayLR",
                                    "params": {"total_num_steps": 500,
                                               "warmup_num_steps": 10}}})
    assert lin.scheduler_config == {"t_max": 490, "warmup_steps": 10,
                                    "eta_min_ratio": 0.0, "decay": "linear"}
    with pytest.raises(ValueError, match="scheduler.params"):
        initialize({"model": "llama-debug",
                    "scheduler": {"type": "WarmupDecayLR",
                                  "params": {"cos_min_ratio": 0.1}}})


def test_engine_optimizer_type_dispatch(eight_devices):
    from distributed_training_guide_tpu.train.engine import initialize

    config = {
        "model": "llama-debug",
        "zero_optimization": {"stage": 3},
        "optimizer": {"type": "Adafactor", "params": {"lr": 1e-2}},
    }
    engine = initialize(config)
    ids = np.random.RandomState(0).randint(0, 512, (engine.global_batch_size, 32))
    batch_sh = engine.trainer.batch_shardings()
    batch = {k: jax.device_put(ids, batch_sh[k]) for k in ("input_ids", "labels")}
    assert np.isfinite(engine.train_batch(batch)["loss"])
    # the config actually selected adafactor: no fp32 Adam mu anywhere
    state_names = {type(s).__name__ for s in engine.state.opt_state}
    assert "ScaleByAdamState" not in state_names

    lion_engine = initialize({"model": "llama-debug",
                              "optimizer": {"type": "Lion",
                                            "params": {"lr": 1e-4}}})
    lion_names = {type(s).__name__ for s in lion_engine.state.opt_state}
    assert "ScaleByLionState" in lion_names
    with pytest.raises(ValueError, match="optimizer.type"):
        initialize({"model": "llama-debug", "optimizer": {"type": "SGD"}})
    # 'eps' is in virtually every DeepSpeed-ported AdamW config (ADVICE r3):
    # it must load — and actually reach optax — not hard-error as unknown
    eps_engine = initialize({"model": "llama-debug",
                             "optimizer": {"type": "AdamW",
                                           "params": {"lr": 1e-3,
                                                      "eps": 1e-6}}})
    assert eps_engine is not None
    # ...but eps stays rejected for optimizers that have no such knob
    with pytest.raises(ValueError, match="eps"):
        initialize({"model": "llama-debug",
                    "optimizer": {"type": "Lion",
                                  "params": {"lr": 1e-4, "eps": 1e-6}}})


def test_engine_full_strategy_space(tmp_path, eight_devices):
    """The engine config covers pp/cp/ep + context_impl + remat policy, not
    just ZeRO stage + tp: a pp x tp config must build the pipeline plan and
    train, and the strategy-derivation guards must fire on bad combos."""
    from distributed_training_guide_tpu.train.engine import initialize

    engine = initialize({
        "model": "llama-debug",
        "zero_optimization": {"stage": 0},
        "tensor_parallel": 2,
        "pipeline_parallel": 2,
        "pp_microbatches": 2,
        "activation_checkpointing": {"enabled": True, "policy": "attn"},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
    })
    assert engine.trainer.plan.strategy == "pp_tp"
    assert dict(engine.trainer.plan.mesh.shape)["pp"] == 2
    assert engine.trainer.remat and engine.trainer.remat_policy == "attn"
    ids = np.random.RandomState(0).randint(0, 512, (4, 32))
    batch_sh = engine.trainer.batch_shardings()
    batch = {k: jax.device_put(ids, batch_sh[k])
             for k in ("input_ids", "labels")}
    losses = [engine.train_batch(batch)["loss"] for _ in range(2)]
    assert np.isfinite(losses).all() and losses[1] < losses[0]
    # the DeepSpeed-surface checkpoint API works on the pp x tp plan too
    # (pp-sharded layer stacks through abstract_train_state) — values, not
    # just host metadata, must round-trip
    leaf_before = np.asarray(
        jax.device_get(jax.tree.leaves(engine.state.params)[0]))
    engine.save_checkpoint(tmp_path / "eng_pp")
    engine.state = engine.trainer.init_state(1)  # clobber, then restore
    assert engine.load_checkpoint(tmp_path / "eng_pp")["global_step"] == 2
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(jax.tree.leaves(engine.state.params)[0])),
        leaf_before)

    # cp rides any strategy as a mesh axis + context_impl
    cp_engine = initialize({"model": "llama-debug", "context_parallel": 2,
                            "context_impl": "ulysses"})
    assert dict(cp_engine.trainer.plan.mesh.shape)["cp"] == 2
    assert cp_engine.trainer.context_impl == "ulysses"

    # ep x tp has no plan; ZeRO-1 x pp has no sharding rules — both must
    # fail loudly instead of silently dropping an axis
    with pytest.raises(ValueError, match="expert_parallel"):
        initialize({"model": "moe-debug", "expert_parallel": 2,
                    "tensor_parallel": 2})
    with pytest.raises(ValueError, match="stage"):
        initialize({"model": "llama-debug",
                    "zero_optimization": {"stage": 1},
                    "pipeline_parallel": 2})


def test_engine_moe_dispatch_key(eight_devices):
    """Top-level moe_dispatch threads to the model config and trains (the
    dp-sharded ragged path runs in the manual shard_map); non-MoE models
    reject the key loudly."""
    import jax.numpy as jnp

    from distributed_training_guide_tpu.train.engine import initialize

    engine = initialize({"model": "moe-debug", "moe_dispatch": "ragged",
                         "bf16": {"enabled": False}})
    assert engine.trainer.bundle.config.moe_dispatch == "ragged"
    ids = np.random.RandomState(0).randint(0, 512, (8, 16))
    batch_sh = engine.trainer.batch_shardings()
    batch = {k: jax.device_put(ids, batch_sh[k])
             for k in ("input_ids", "labels")}
    m = engine.train_batch(batch)
    assert np.isfinite(float(m["loss"]))
    assert float(m["moe_dropped_frac"]) == 0.0
    with pytest.raises(ValueError, match="moe_dispatch"):
        initialize({"model": "llama-debug", "moe_dispatch": "ragged"})


def test_preflight_budget_and_lowering(eight_devices):
    import jax.numpy as jnp

    from distributed_training_guide_tpu.models import get_model
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan
    from distributed_training_guide_tpu.train import Trainer, adamw_cosine
    from distributed_training_guide_tpu.train.preflight import run_preflight

    bundle = get_model("llama-debug")
    t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                plan=make_plan("fsdp", make_mesh(fsdp=8)), donate=False)
    rep = run_preflight(t, global_batch=8, seq_length=64)
    assert rep["lowered"] and rep["n_devices"] == 8
    assert "moe_dispatch" not in rep   # dense families aren't priced

    # serving-side KV pricing rides every preflight (serve/kv_pages.py):
    # pages x layers x 2 (k,v) x page_size x kv_heads x head_dim bytes
    sk = rep["serve_kv"]
    dcfg = bundle.config
    assert sk["pages_per_slot_at_seq"] == 4          # ceil(64 / 16)
    assert sk["bytes_per_page"] == (
        dcfg.num_layers * 2 * 16 * dcfg.num_kv_heads * dcfg.head_size
        * jnp.dtype(dcfg.dtype).itemsize)
    assert sk["bytes_per_slot_at_seq"] == 4 * sk["bytes_per_page"]
    # the dense column pays the full position table per slot
    assert sk["dense_bytes_per_slot"] == (
        sk["bytes_per_page"] // 16 * dcfg.max_position_embeddings)
    # decode traffic: the flash kernel reads the live context once per
    # token; the gather view moved ~3x that (read pool + write view +
    # read view). Prefix sharing amortizes the nominal system prompt's
    # full pages per extra co-resident slot (clamped to the context).
    assert sk["decode_read_bytes_per_token_flash"] == \
        sk["bytes_per_slot_at_seq"]
    assert sk["decode_traffic_bytes_per_token_gather"] == \
        3 * sk["bytes_per_slot_at_seq"]
    assert sk["shared_prefix_tokens_nominal"] == 64          # min(512, seq)
    assert sk["shared_prefix_bytes_amortized_per_extra_slot"] == \
        4 * sk["bytes_per_page"]
    # multi-token forwards (the block_q=T kernel family): a verify step
    # and a prefill chunk each read the context ONCE through the kernel
    # (same O(context) bytes as a decode token, amortized over T rows);
    # the gather form paid the 3x round-trip per forward. The per-token
    # verify row divides the kernel read over k+1 at full acceptance.
    assert sk["verify_read_bytes_per_step_flash"] == \
        sk["bytes_per_slot_at_seq"]
    assert sk["verify_traffic_bytes_per_step_gather"] == \
        3 * sk["bytes_per_slot_at_seq"]
    assert sk["chunk_prefill_read_bytes_per_chunk_flash"] == \
        sk["bytes_per_slot_at_seq"]
    assert sk["chunk_prefill_traffic_bytes_per_chunk_gather"] == \
        3 * sk["bytes_per_slot_at_seq"]
    assert sk["verify_read_bytes_per_token_flash_accept_1.0"] == \
        sk["bytes_per_slot_at_seq"] // (sk["spec_k_nominal"] + 1)
    # fsdp mesh: tp=1, pool replicated — per-chip column equals the full
    # one; handoff is 0 B same-host, per-slot payload cross-host
    assert sk["kv_shards"] == 1
    assert sk["bytes_per_page_per_chip"] == sk["bytes_per_page"]
    assert sk["handoff_bytes_same_host"] == 0
    assert sk["handoff_bytes_cross_host_at_seq"] == \
        sk["bytes_per_slot_at_seq"]
    # kv_dtype rows (quantized KV pages, serve/kv_pages.py): the int8
    # figure INCLUDES the per-(position, kv-head) fp32 scales — payload
    # bytes alone would overstate the capacity win
    by = sk["bytes_per_page_by_kv_dtype"]
    model_dtype = ("bf16" if jnp.dtype(dcfg.dtype) == jnp.bfloat16
                   else "fp32")
    assert by[model_dtype] == sk["bytes_per_page"]   # headline row = model
    assert by["fp32"] == (dcfg.num_layers * 2 * 16 * dcfg.num_kv_heads
                          * dcfg.head_size * 4)
    assert by["int8"] == (dcfg.num_layers * 2 * 16 * dcfg.num_kv_heads
                          * (dcfg.head_size + 4))
    assert sk["bytes_per_slot_by_kv_dtype"]["int8"] == 4 * by["int8"]
    assert sk["int8_bytes_vs_fp32"] <= 0.55
    # tiered-KV rows (serve/tiering.py): one spilled slot parks exactly
    # the per-slot pool bytes host-side (by dtype — the int8 row ships
    # its scales), a directory pull moves those same bytes once over the
    # wire, and the FLOPs-per-pull-byte ratio prices the pull against
    # re-prefilling at the training context
    assert sk["host_tier_bytes_per_spilled_slot_at_seq"] == \
        sk["bytes_per_slot_at_seq"]
    assert sk["host_tier_bytes_per_spilled_slot_by_kv_dtype"] == \
        sk["bytes_per_slot_by_kv_dtype"]
    assert sk["host_tier_slots_per_gib"] == \
        (1 << 30) // sk["bytes_per_slot_at_seq"]
    assert sk["directory_pull_wire_bytes_at_seq"] == \
        sk["bytes_per_slot_at_seq"]
    assert sk["reprefill_flops_at_seq"] == \
        2 * bundle.num_active_params() * 64
    assert sk["reprefill_flops_per_pull_byte"] == round(
        sk["reprefill_flops_at_seq"] / sk["bytes_per_slot_at_seq"], 2)

    # weight_dtype rows (serve/weights.py): STORAGE bytes per dtype —
    # the int8 row includes the per-block fp32 scales, same rule as the
    # kv rows above — and a publish or generation swap moves exactly
    # these bytes, so the payload tables equal the storage table
    sw = rep["serve_weights"]
    wb = sw["weight_bytes_by_dtype"]
    n_weights = sum(
        int(np.prod(sd.shape, dtype=np.int64)) for sd in jax.tree.leaves(
            jax.eval_shape(lambda: bundle.init(dcfg, jax.random.key(0)))))
    assert wb["fp32"] == 4 * n_weights
    assert wb["bf16"] == 2 * n_weights
    assert sw["int8_supported"] and 0 < wb["int8"] < wb["bf16"]
    assert sw["publish_payload_bytes_by_dtype"] == wb
    assert sw["swap_payload_bytes_by_dtype"] == wb
    # the acceptance pin: int8 weights (scales included) at least 1.9x
    # smaller than fp32 on every publish/swap payload
    assert sw["int8_bytes_vs_fp32"] <= 0.53
    # ...and the analytic rows match what an engine actually holds
    from distributed_training_guide_tpu.serve.engine import ServeEngine
    w_eng = ServeEngine(bundle, bundle.init(dcfg, jax.random.key(0)),
                        n_slots=2, page_size=16, max_len=64,
                        weight_dtype="int8")
    assert w_eng.weight_bytes() == wb["int8"]

    # adapter-pool rows (serve/adapters.py): the multi-LoRA pool priced
    # at the nominal serving shape (8 slots, rank 8, wq+wv) — fp32
    # factors A [L, e, r] + B [L, r, fan_out] per target, so the bytes
    # pin arithmetically from the config; the publish payload is ONE
    # adapter's factors (the consolidation lever vs a full publish)
    sa = rep["serve_adapters"]
    hq = dcfg.num_heads * dcfg.head_size
    hkv = dcfg.num_kv_heads * dcfg.head_size
    e, l, r = dcfg.hidden_size, dcfg.num_layers, 8
    per = 4 * l * ((e * r + r * hq) + (e * r + r * hkv))
    assert sa["max_adapters"] == 8 and sa["rank"] == 8
    assert sa["targets"] == ["wq", "wv"]
    assert sa["bytes_per_adapter"] == per
    assert sa["pool_bytes"] == 8 * per
    assert sa["publish_payload_bytes"] == per
    assert sa["pool_vs_fp32_weights"] == round(8 * per / wb["fp32"], 4)
    # ...and the analytic rows match what a pooled engine reports
    a_eng = ServeEngine(bundle, bundle.init(dcfg, jax.random.key(0)),
                        n_slots=2, page_size=16, max_len=64,
                        max_adapters=8, adapter_rank=8)
    a_rep = a_eng.adapter_report()
    assert a_rep["bytes_per_adapter"] == per
    assert a_rep["pool_bytes"] == 8 * per

    # colocation pricing under QLoRA (post/loop.py): the engine's merged
    # copy is priced at ITS weight_dtype — quantized base + fp adapters
    # in the trainer + an fp teacher all priced in one report
    from distributed_training_guide_tpu.models.lora import lora_bundle
    from distributed_training_guide_tpu.train.preflight import \
        price_post_colocation
    lt = Trainer(bundle=lora_bundle(bundle, rank=4),
                 optimizer=adamw_cosine(1e-3), lora_only=True)
    colo = price_post_colocation(lt, n_slots=4, max_len=64,
                                 weight_dtype="int8", teacher_bundle=bundle)
    assert colo["engine_weight_dtype"] == "int8"
    assert colo["engine_param_bytes"] == wb["int8"]
    assert colo["teacher_param_bytes"] == wb["fp32"]
    colo_fp = price_post_colocation(lt, n_slots=4, max_len=64)
    assert colo_fp["engine_weight_dtype"] == "model"
    assert colo_fp["engine_param_bytes"] == wb["fp32"]
    assert colo["total_bytes"] == \
        colo_fp["total_bytes"] - wb["fp32"] + wb["int8"] + wb["fp32"]

    # tp mesh: the sharded pool (serve/sharding.py kv-head split) halves
    # the per-CHIP page/slot bytes at tp=2 (llama-debug: 2 kv heads)
    tp_t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                   plan=make_plan("tp", make_mesh(
                       tp=2, devices=eight_devices[:2])), donate=False)
    tp_sk = run_preflight(tp_t, global_batch=2, seq_length=64)["serve_kv"]
    assert tp_sk["kv_shards"] == 2
    assert tp_sk["bytes_per_page_per_chip"] == sk["bytes_per_page"] // 2
    assert tp_sk["bytes_per_slot_per_chip_at_seq"] == \
        sk["bytes_per_slot_at_seq"] // 2

    # MoE configs get the dispatch-transient pricing (dense-vs-ragged bytes)
    moe_t = Trainer(bundle=get_model("moe-debug", dtype=jnp.float32),
                    optimizer=adamw_cosine(1e-3),
                    plan=make_plan("ep", make_mesh(ep=8)), donate=False)
    moe_rep = run_preflight(moe_t, global_batch=8, seq_length=64)
    md = moe_rep["moe_dispatch"]
    cfg = moe_t.bundle.config
    t_tok, k = 8 * 64, cfg.experts_per_token
    assert md["mode"] == "dense"
    assert md["per_layer_ragged_dispatch_bytes"] == (
        k * t_tok * (2 * cfg.hidden_size + cfg.intermediate_size) * 4)
    assert md["per_layer_dense_dispatch_bytes"] > 0
    assert md["dense_over_ragged"] == pytest.approx(
        md["per_layer_dense_dispatch_bytes"]
        / md["per_layer_ragged_dispatch_bytes"], rel=0.01)

    total_param_bytes = sum(
        np.prod(l.shape) * l.dtype.itemsize
        for l in jax.tree.leaves(t.param_shapes))
    # fsdp shards most leaves 8-ways; small replicated leaves (norms) mean
    # per-device sits between total/8 and total
    assert total_param_bytes / 8 <= rep["per_device_param_bytes"] < total_param_bytes
    # fp32 Adam: mu + nu ~= 2x the param bytes, same shardings
    assert 1.8 * rep["per_device_param_bytes"] < rep["per_device_opt_state_bytes"] \
        < 2.2 * rep["per_device_param_bytes"] + 4096
