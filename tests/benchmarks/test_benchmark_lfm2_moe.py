"""The ``lfm2_moe`` family in the benchmark, on the CPU: the plain reference
(``benchmarks/reference/lfm2_moe.py``) against ``models/lfm2.py`` with the
faults it has to see, the weights' contract (stacked by kind from the same
hash; layer i of the cut is published layer i + 1), the cell's data files,
the new work functions and readers by hand, and the runner end to end on a
debug-width cell (tests/benchmarks/debug/) with the faults ``correct`` has to
catch and the lower-precision control."""
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import flops, flops_lfm2_moe, harness  # noqa: E402
from benchmarks import weights_lfm2_moe as weights  # noqa: E402
from benchmarks.readers import hybrid_attend, program_busy  # noqa: E402
from benchmarks.reference import lfm2_moe as ref  # noqa: E402
from benchmarks.runners import _lfm2_moe  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
DEBUG = Path(__file__).resolve().parent / "debug"
CELL = "debug-lfm2-moe.serve.debug-chat"
REAL = "lfm2-24b-a2b-l9.serve.chat64"
REAL_CFG = ROOT / "benchmarks" / "configs" / "lfm2-24b-a2b-l9.json"
# float32 program against float32 reference: only summation order differs
LOGIT_TOL = 2e-5


def debug_cfg(**over):
    cfg = json.loads((DEBUG / "configs" / "debug-lfm2-moe.json").read_text())
    return dict(cfg, compute_dtype="float32", weights_dtype="float32", **over)


def layer_fn_of(cfg, key):
    return lambda l: weights.layer_weights(cfg, key, l, jnp.float32)


# ---- the reference against the program -----------------------------------------
@pytest.fixture(scope="module")
def forward():
    from distributed_training_guide_tpu.models import lfm2

    cfg, key = debug_cfg(), weights.seed_key(2**31 + 7)
    w = weights.stacked_weights(cfg, key, jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 512, 48).astype(np.int32)
    bundle = _lfm2_moe.bundle_for(cfg, "debug")
    got = lfm2.apply(bundle.config, _lfm2_moe.to_program(w),
                     jnp.asarray(tokens[None]))[0]
    return cfg, key, w["top"], tokens, got


@pytest.mark.parametrize("fault", ref.FAULTS, ids=[f or "sound" for f in ref.FAULTS])
def test_reference_matches_program_logits_and_sees_each_fault(forward, fault):
    """Every kind of layer in one model (dense + conv, experts + attention,
    experts + conv). The sound reference is the program's forward; with the
    taps reversed, B and C swapped, the choice bias left out or the dense
    layer given experts it is not."""
    cfg, key, top, tokens, got = forward
    want = ref.forward_logits(cfg, layer_fn_of(cfg, key), top, tokens,
                              fault=fault)
    diff = float(jnp.max(jnp.abs(got - want)))
    if fault is None:
        assert diff < LOGIT_TOL
    else:
        assert diff > 30 * LOGIT_TOL


def test_rope_pairs_column_i_with_i_plus_half():
    from distributed_training_guide_tpu.ops.rope import apply_rope

    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 6, 3, 64)),
                    jnp.float32)
    positions = jnp.asarray([[0, 1, 7, 100, 1023, 4000]])
    got = apply_rope(x, positions, 1e6, None, 128000)[0]
    want = ref.rope_half(x[0], positions[0], 1e6)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4
    assert float(jnp.max(jnp.abs(got - want)[:2])) < 1e-6


# ---- the weights -----------------------------------------------------------------
def test_stacked_weights_are_the_layers_own_draws_and_nothing_more():
    cfg = debug_cfg()
    key = weights.seed_key(2**31 + 9)
    stacked = weights.stacked_weights(cfg, key)
    kinds = weights.layers_of(cfg)
    assert kinds == {"norms": [0, 1, 2], "attn": [1], "conv": [0, 2],
                     "dense": [0], "moe": [1, 2]}
    for kind, layers in kinds.items():
        for row, l in enumerate(layers):
            alone = weights.layer_weights(cfg, key, l)
            names = weights.KINDS[kind] + (
                weights.EXPERT_LEAVES if kind == "moe" else ())
            assert set(stacked[kind]) == set(names)
            for name in names:
                assert np.array_equal(stacked[kind][name][row], alone[name]), name
    one = weights.expert_weights(cfg, key, 2, 3)
    assert np.array_equal(stacked["moe"]["down"][1, 3], one["down"])
    held = sum(x.size for x in jax.tree.leaves(stacked))
    assert weights.num_params(cfg) == held
    assert _lfm2_moe.bundle_for(cfg, "debug").config.num_params() == held
    tree = _lfm2_moe.to_program(stacked)
    assert tree["layers"]["conv"]["taps"].shape == (2, 3, 64)
    assert np.array_equal(tree["layers"]["conv"]["taps"][1, 0],
                          stacked["conv"]["taps"][1, :, 0])


def test_layer_i_of_the_cut_is_published_layer_i_plus_one():
    cut = debug_cfg()
    assert cut["published_layer_offset"] == 1
    whole = debug_cfg(published_layer_offset=0, num_hidden_layers=4,
                      layer_types=["conv"] + cut["layer_types"])
    key = weights.seed_key(5)
    for name, leaf in weights.layer_weights(cut, key, 1).items():
        assert np.array_equal(leaf, weights.layer_weights(whole, key, 2)[name])
    assert not np.array_equal(weights.layer_weights(cut, key, 1)["w_in"],
                              weights.layer_weights(whole, key, 1)["w_in"])


# ---- the data files ----------------------------------------------------------------
def test_the_cell_loads_with_the_published_widths_and_its_cut():
    loaded = harness.load_cell(BENCH, REAL)
    cfg, job, mix = loaded["config_data"], loaded["job"], loaded["traffic_data"]
    assert loaded["chips"] == 1 and job["runner"] == "serve"
    catalog = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
               "intermediate_size": 11776, "max_position_embeddings": 128000,
               "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
               "norm_eps": 1e-05, "norm_topk_prob": True,
               "num_attention_heads": 32, "num_experts": 64,
               "num_experts_per_tok": 4, "num_key_value_heads": 8,
               "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
               "routed_scaling_factor": 1, "use_expert_bias": True,
               "vocab_size": 65536}
    assert {k: cfg[k] for k in catalog} == catalog
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types"]
    published = cfg["published"]
    assert (published["num_hidden_layers"], published["num_dense_layers"]) == (40, 2)
    assert len(published["layer_types"]) == 40
    # published layers 1-9: one dense layer and two whole periods
    assert cfg["layer_types"] == published["layer_types"][1:10]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["published_layer_offset"]) == (9, 1, 1)
    assert cfg["layer_types"].count("conv") == 7
    assert cfg["head_dim"] == 64 and cfg["n_routed_experts"] == cfg["num_experts"]
    assert {"head_dim", "tie_word_embeddings", "norm_topk_eps", "hidden_act",
            "rope_pairing", "weights"} <= set(cfg["assumed"])
    assert "ONE chip" in cfg["deployment"] and "five" in cfg["deployment"]
    assert weights.num_params(cfg) == 5_177_950_976
    assert mix["clients"] == 64 and mix["prompt_len"] == {"fixed": 1024} \
        and mix["output_len"] == {"fixed": 256} \
        and mix["first_output_len"] == "staggered"
    eng = job["engine"]
    assert eng["n_slots"] == 64 and eng["page_size"] == 128 \
        and eng["prefill_chunk"] == 1024 and eng["attend_impl"] == "auto"
    assert eng["n_pages"] == 64 * (1024 + 256) // 128 + 1
    assert eng["max_len"] == 1024 + 256


def test_the_cell_is_listed_where_its_metrics_mean_the_same():
    def cells(name):
        return next(m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                    if m["name"] == name)["workloads"]
    for name in ("serve.out_tokens_per_s", "serve.itl_p95_ms", "gmm_roofline",
                 "serve.experts_device_ms", "serve.conv_device_ms",
                 "serve.chunk_device_ms", "hybrid_attend_roofline"):
        assert REAL in cells(name), name
    # flops.paged_attend counts every layer as an attention layer, and the
    # touched share divides by every layer: neither means the same here
    # ... and the walk slices nothing at run time: no event of the decode
    # program has `layers` as its innermost scope, so that reader reads nothing
    for name in ("paged_attend_roofline", "serve.experts_touched_pct",
                 "serve.expert_pairs_held_pct", "latent_attend_roofline",
                 "serve.layers_device_ms"):
        assert REAL not in cells(name), name
    assert len(BENCH["workloads"]) == 6
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1


def test_the_pool_is_4_kb_a_token_and_the_state_8_kb_a_conv_layer_a_page():
    from distributed_training_guide_tpu.serve import kv_pages

    cfg = harness.load_json(REAL_CFG)
    config = _lfm2_moe.bundle_for(cfg, "real").config
    assert flops_lfm2_moe.kv_bytes_per_token(cfg) == 4096
    assert kv_pages.pool_layout(config) == {"k": (4, 128), "v": (4, 128)}
    shapes = jax.eval_shape(lambda: kv_pages.init_pages(config, 641, 128))
    assert shapes["k"].shape == (2, 641, 128, 4, 128)
    assert shapes["state"].shape == (7, 641, 2, 2048)
    assert cfg["state_row"]["bytes_per_conv_layer_per_page"] == 2 * 2048 * 2
    assert sum(x.size * 2 for x in jax.tree.leaves(shapes)) == \
        kv_pages.kv_page_bytes(config, page_size=128, n_pages=641) == \
        641 * (128 * 4096 + 7 * 8192)
    assert config.num_params() == weights.num_params(cfg)


def test_required_work_of_the_hybrids_decode_step():
    cfg = harness.load_json(REAL_CFG)
    peak = harness.peak_for("TPU v5 lite")
    work = flops_lfm2_moe.paged_attend(cfg, 64 * 1150, 64)
    # 2 layers attend, not 9
    assert work["flops"] == 4 * 2 * 32 * 64 * 64 * 1150
    assert work["bytes"] == 4096 * 64 * 1150 + 2 * 2 * 2 * 64 * 32 * 64
    assert flops.least_time(work, peak)[1] == "memory"
    assert work["flops"] * 4.5 == flops.paged_attend(cfg, 64 * 1150, 64)["flops"]
    outside = flops_lfm2_moe.matmul_params_outside_experts(cfg)
    assert outside == (2 * 2048 * 64 * 80 + 7 * (2048 * 8192 + 2048 * 3)
                       + 3 * 2048 * 11776 + 8 * 2048 * 64 + 65536 * 2048)
    step = flops_lfm2_moe.decode_step_bytes(cfg, 64 * 1150, 8 * 63)
    assert step == (outside + 8 * 63 * 3 * 2048 * 1536) * 2 + 4096 * 64 * 1150
    # all of it once: the step's floor is 12.6 ms at the chip's bandwidth
    assert 12.0e-3 < step / peak["hbm_bytes_per_s"] < 13.0e-3


# ---- the two new readers ----------------------------------------------------------
def test_readers_return_nothing_where_there_is_nothing_to_read():
    ctx = {"trace": None, "trace_dir": None, "config": {}, "job": {}}
    assert program_busy.read(ctx, {"program": "serve_chunk_t{prefill_chunk}"}) is None
    assert hybrid_attend.read(ctx, {"component": "paged_attend",
                                    "program": "serve_decode"}) is None


def test_readers_read_a_chunks_busy_time_and_the_hybrids_roofline(monkeypatch):
    ms = 1_000_000
    paths = {"%k": "jit(serve_decode)/layers/attn/attend/paged_attend/pallas_call:",
             "%g": "jit(serve_decode)/layers/experts/gmm/pallas_call:",
             "%c": "jit(serve_chunk_t16)/layers/attn/conv/dot_general:"}
    ops = [("%k", 1 * ms, 3 * ms), ("%g", 3 * ms, 8 * ms),
           ("%c", 10 * ms, 14 * ms), ("%g", 15 * ms, 16 * ms),   # chunk: 5 busy
           ("%c", 30 * ms, 33 * ms), ("%k", 41 * ms, 42 * ms)]    # chunk: 3 busy
    modules = [("jit_serve_decode(7)", 0, 9 * ms), ("jit_serve_chunk_t16(9)", 10 * ms, 17 * ms),
               ("jit_serve_chunk_t16(9)", 29 * ms, 34 * ms), ("jit_serve_decode(7)", 40 * ms, 43 * ms)]
    trace = {"lo_ns": 0, "hi_ns": 50 * ms, "device_ops": {0: ops},
             "device_modules": {0: modules}, "host_spans": []}
    for mod in (program_busy, hybrid_attend):
        monkeypatch.setattr(mod._xplane, "traced", lambda ctx: (trace, "x"))
    monkeypatch.setattr(hybrid_attend.scope_time, "op_paths_of", lambda p: paths)
    cfg = harness.load_json(REAL_CFG)
    ctx = {"config": cfg, "peak": harness.peak_for("TPU v5 lite"),
           "job": {"engine": {"prefill_chunk": 16}}, "trace_window": (0.0, 1.0),
           "counters": {"kv_bytes": 2, "decode_context": [(0.5, 64 * 1150, 64),
                                                          (0.7, 64 * 1151, 64),
                                                          (2.0, 1, 1)]}}
    assert program_busy.read(ctx, {"program": "serve_chunk_t{prefill_chunk}"}) \
        == pytest.approx(4.0)
    assert program_busy.read(ctx, {"program": "serve_chunk_t32"}) is None
    work = flops_lfm2_moe.paged_attend(cfg, 64 * 2301, 128)
    assert hybrid_attend.read(ctx, {"component": "paged_attend",
                                    "program": "serve_decode"}) == pytest.approx(
        100 * (work["bytes"] / 819e9) / 3e-3)
    # a configuration whose every layer attends is path_component's to read
    assert hybrid_attend.read(dict(ctx, config={}), {
        "component": "paged_attend", "program": "serve_decode"}) is None


# ---- the runner, end to end at debug width ---------------------------------------
def make_root(tmp: Path) -> Path:
    bench = tmp / "benchmarks"
    bench.mkdir(parents=True)
    shutil.copytree(ROOT / "benchmarks" / "metrics", bench / "metrics")
    shutil.copy(ROOT / "benchmarks" / "peaks.json", bench / "peaks.json")
    for d in ("configs", "traffic", "workloads"):
        shutil.copytree(DEBUG / d, bench / d)
    doc = json.loads(json.dumps(BENCH))
    doc["configs"] = [{"name": "debug-lfm2-moe", "source": "debug", "reduced": [],
                       "why": "debug", "file": "benchmarks/configs/debug-lfm2-moe.json"}]
    doc["workloads"] = [{"name": CELL, "config": "debug-lfm2-moe",
                         "traffic": "serve.debug-chat", "chips": 1, "why": "debug"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if REAL in m["workloads"] else []
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp


@pytest.fixture(scope="module")
def debug_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("lfm2_root"))


def run(root, **kw):
    return harness.run_cell(
        root=root, workload=CELL, seed=kw.pop("seed", 2**31 + 23),
        seconds=kw.pop("seconds", 1.0), trace=kw.pop("trace", False),
        t_process_start=time.monotonic(), bench_dir=root / "benchmarks",
        require_platform=None)


def test_runner_end_to_end_on_the_debug_cell(debug_root, capsys):
    rc = harness.main(["--workload", CELL, "--seed", str(2**31 + 5),
                       "--seconds", "1", "--trace", "0"],
                      t_process_start=time.monotonic(), root=debug_root,
                      bench_dir=debug_root / "benchmarks", require_platform=None)
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 4
    assert set(last["metrics"]) == {"setup_s", "serve.out_tokens_per_s",
                                    "serve.itl_p95_ms"}
    window = next(json.loads(l)["window"] for l in lines if l.startswith('{"window"'))
    assert window["preemptions"] == 0 and window["refused"] == 0
    # replies end and prompts are prefilled INSIDE the window: slots are
    # reused (how often depends on the host: a loaded one takes few steps)
    assert window["completed"] >= 1 and window["prefill_calls"] >= 2
    routing = next(json.loads(l)["routing"] for l in lines if l.startswith('{"routing"'))
    # top-2 of 8 experts, every one held, counted over the 2 expert layers
    assert routing["pairs_held_a_step"] == routing["pairs_routed_a_step"] > 0
    assert any(l.startswith('{"reference_seconds"') for l in lines)


def test_traced_run_reports_the_counters_and_leaves_device_metrics_out(debug_root):
    result = run(debug_root, trace=True)
    assert result["correct"] is True
    names = set(result["metrics"])
    assert {"serve.step_ms_p50", "serve.batch_occupancy_pct",
            "serve.preemptions"} <= names
    # off a TPU there is no device plane: the new readers leave theirs out
    assert not any(n.endswith("_roofline") or n.endswith("device_ms") for n in names)
    assert result["metrics"]["serve.preemptions"]["value"] == 0
    steps = result["ctx"]["counters"]["routing_steps"]
    # a row is one decode step over the 2 expert layers: at most 4 slots x
    # top-2 pairs a layer, every pair held
    assert steps and all(0 < touched <= 2 * 8 and 0 < held <= 2 * 8
                         for _, held, touched in steps)


def alter_served_tokens(monkeypatch):
    import dataclasses

    from distributed_training_guide_tpu import serve

    def alter(tokens):
        tokens = list(tokens)
        if len(tokens) > 2:
            tokens[len(tokens) // 2] += 1
        return tokens

    class Altered(serve.ServeEngine):
        def step(self):
            return [dataclasses.replace(r, generated_ids=alter(r.generated_ids))
                    for r in super().step()]

        def partial_tokens(self):
            return {rid: alter(t) for rid, t in super().partial_tokens().items()}
    monkeypatch.setattr(serve, "ServeEngine", Altered)


def edit_program_tree(monkeypatch, edit):
    real = _lfm2_moe.to_program

    def edited(w):
        tree = real(w)
        edit(tree["layers"])
        return tree
    monkeypatch.setattr(_lfm2_moe, "to_program", edited)


def reverse_taps(monkeypatch):
    def edit(layers):
        layers["conv"]["taps"] = layers["conv"]["taps"][:, ::-1]
    edit_program_tree(monkeypatch, edit)


def swap_b_and_c(monkeypatch):
    def edit(layers):
        b, c, z = jnp.split(layers["conv"]["w_in"], 3, axis=-1)
        layers["conv"]["w_in"] = jnp.concatenate([c, b, z], axis=-1)
    edit_program_tree(monkeypatch, edit)


def drop_choice_bias(monkeypatch):
    def edit(layers):
        layers["moe"]["router_bias"] = jnp.zeros_like(layers["moe"]["router_bias"])
    edit_program_tree(monkeypatch, edit)


def keep_the_last_owners_state(monkeypatch):
    from distributed_training_guide_tpu.serve import kv_pages

    real = kv_pages.read_state

    def stale(state, layer, page, *, tables, lengths):
        return real(state, layer, page, tables=tables,
                    lengths=jnp.maximum(lengths, 1))
    monkeypatch.setattr(kv_pages, "read_state", stale)


FAULTS = {"alter_token": alter_served_tokens, "taps_reversed": reverse_taps,
          "b_and_c_swapped": swap_b_and_c, "no_choice_bias": drop_choice_bias,
          "stale_state": keep_the_last_owners_state}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(fault, debug_root, monkeypatch):
    FAULTS[fault](monkeypatch)
    result = run(debug_root)
    assert result["correct"] is False
    assert any(not row["ok"] for row in result["compared"])


def test_runner_control_reads_the_reference_in_a_lower_precision(debug_root):
    result = run(debug_root)
    runner = harness.load_module("runners", result["ctx"]["job"]["runner"])
    rows = runner.control(result["ctx"], "int8")
    assert set(rows) <= {r["check"] for r in result["compared"]}
    sound = {r["check"]: r["value"] for r in result["compared"]}
    limits = result["ctx"]["job"]["check"]["limits"]
    # the control moves the mean past the debug cell's limit
    assert rows["served_token_mean_logit_gap"] > \
        limits["served_token_mean_logit_gap"] >= sound["served_token_mean_logit_gap"]
