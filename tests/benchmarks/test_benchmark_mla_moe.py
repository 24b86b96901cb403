"""The ``mla_moe`` family in the benchmark, on the CPU: the plain reference
(``benchmarks/reference/mla_moe.py``) against ``models/mla.py``, the weights'
slicing contract, the share of the experts tied to the whole layer, the new
runner end to end on a debug-width cell (tests/benchmarks/debug/), the faults
``correct`` has to catch, and the reader that selects events by a path
component."""
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

from benchmarks import flops_mla_moe, harness  # noqa: E402
from benchmarks import weights_mla_moe as weights  # noqa: E402
from benchmarks.readers import path_component, scope_time  # noqa: E402
from benchmarks.reference import mla_moe as ref  # noqa: E402
from benchmarks.runners import _mla_moe  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
DEBUG = Path(__file__).resolve().parent / "debug"
CELL = "debug-mla-moe.serve.debug-decode"
REAL = "mistral-small-4-ep4-l6.serve.decode32-ctx8k"
SERVE_NAMED = ROOT / "benchmarks" / "testdata" / "serve_three_steps_named.xplane.pb"
# float32 program against float32 reference: only summation order differs
LOGIT_TOL = 2e-5


def debug_cfg(**over):
    cfg = json.loads((DEBUG / "configs" / "debug-mla-moe.json").read_text())
    return dict(cfg, compute_dtype="float32", weights_dtype="float32", **over)


def layer_fn_of(w):
    return lambda l: jax.tree.map(lambda a: a[l], w["layers"])


def tokens_for(cfg, seq, batch=2, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, seq)).astype(np.int32)


# ---- the reference against the program ---------------------------------------
@pytest.mark.parametrize("held", [(0, 8), (0, 4), (4, 4)],
                         ids=["all", "first-half", "second-half"])
def test_reference_matches_program_logits(held):
    """Full forward, 160 positions: past the debug config's original length
    of 64, so YaRN's ramp and g(t) both change inside the sequence."""
    from distributed_training_guide_tpu.models import mla

    cfg = debug_cfg(experts_held_first=held[0], n_routed_experts=held[1])
    w = weights.stacked_weights(cfg, weights.seed_key(3), jnp.float32)
    tokens = tokens_for(cfg, 160)
    want = ref.forward_logits(cfg, layer_fn_of(w), w["top"], tokens)
    bundle = _mla_moe.bundle_for(cfg, "debug")
    got = mla.apply(bundle.config, _mla_moe.to_program(w), jnp.asarray(tokens))
    assert float(jnp.max(jnp.abs(got - want))) < LOGIT_TOL
    # and the comparison sees a router that leaves its selection bias out
    nobias = ref.forward_logits(cfg, layer_fn_of(w), w["top"], tokens,
                                use_bias=False)
    assert float(jnp.max(jnp.abs(nobias - want))) > 100 * LOGIT_TOL


def test_rope_and_query_scale_match_the_reference_across_the_boundary():
    from distributed_training_guide_tpu.ops.rope import (
        apply_rope, freeze_rope_scaling, position_query_scale)

    rp = {"rope_type": "yarn", "factor": 128, "beta_fast": 32, "beta_slow": 1,
          "mscale": 1, "mscale_all_dim": 1, "rope_theta": 10000,
          "original_max_position_embeddings": 8192,
          "llama_4_scaling_beta": 0.1}
    positions = jnp.asarray([[0, 1, 8191, 8192, 8193, 12287, 16383, 16384]])
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 8, 3, 64)),
                    jnp.float32)
    scaling = freeze_rope_scaling({k: v for k, v in rp.items()
                                   if k not in ("rope_theta",
                                                "llama_4_scaling_beta")})
    got = apply_rope(x, positions, 10000.0, scaling, 1048576, interleave=True)
    want = ref.rope_pairs(x, positions, rp)
    # float32 angles: the two ways of writing YaRN's frequencies differ in
    # the last bit, times a position of 16 k
    assert float(jnp.max(jnp.abs(got - want))) < 2e-3
    assert float(jnp.max(jnp.abs(got - want)[:, :2])) < 1e-6
    # each adjacent pair keeps its length, and is not the half-rotation
    pairs = lambda a: a.reshape(*a.shape[:-1], 32, 2)
    assert np.allclose(np.linalg.norm(pairs(got), axis=-1),
                       np.linalg.norm(pairs(x), axis=-1), atol=1e-4)
    halves = apply_rope(x, positions, 10000.0, scaling, 1048576)
    assert float(jnp.max(jnp.abs(halves - got))) > 0.1
    g = position_query_scale(positions, 0.1, 8192)
    assert np.allclose(g, ref.query_scale(positions, rp))
    assert np.allclose(g[0, :5], [1, 1, 1, 1 + 0.1 * np.log(2),
                                  1 + 0.1 * np.log(2)])
    assert ref.softmax_scale({"qk_nope_head_dim": 64, "qk_rope_head_dim": 64,
                              "rope_parameters": rp}) == pytest.approx(
        128 ** -0.5 * (0.1 * np.log(128) + 1) ** 2)


# ---- the share tied to the model -----------------------------------------------
def test_the_four_shares_of_an_expert_layer_add_up_to_the_whole_layer():
    """One layer's FFN on the same rows: the routed parts the four shares give
    (two experts each of eight), with the shared expert, which every chip
    computes alike, counted once, add up to the uncut reference layer; and a
    share that holds every expert IS the plain layer. The program's layer
    against the reference's at each step."""
    from distributed_training_guide_tpu.models import mla

    whole = debug_cfg(n_routed_experts=8)
    key = weights.seed_key(11)
    w_all = weights.layer_weights(whole, key, 0, jnp.float32)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 48, 64)),
                    jnp.float32)

    def shared_of(w):
        return ref.expert(x[0], w["shared_gate_proj"], w["shared_up"],
                          w["shared_down"])

    def program_ffn(cfg, w):
        config = _mla_moe.bundle_for(cfg, "debug").config
        tree = _mla_moe.to_program({"top": weights.top_weights(cfg, key, jnp.float32),
                                    "layers": jax.tree.map(lambda a: a[None], w)})
        layer = jax.tree.map(lambda a: a[0], tree["layers"])
        y = mla._moe_ffn(config, x, layer["moe"], no_drop=True)[0]
        return y[0]

    def reference_ffn(cfg, w):
        weight = ref.route(cfg, w, x[0])
        routed = sum(weight[:, j: j + 1] * ref.expert(
            x[0], w["gate"][j], w["up"][j], w["down"][j])
            for j in range(cfg["n_routed_experts"]))
        return routed + shared_of(w)

    want = reference_ffn(whole, w_all)
    assert float(jnp.max(jnp.abs(program_ffn(whole, w_all) - want))) < 1e-5
    total = shared_of(w_all)
    for first in (0, 2, 4, 6):
        cfg = debug_cfg(n_routed_experts=2, experts_held_first=first)
        w = weights.layer_weights(cfg, key, 0, jnp.float32)
        # expert e of the whole layer is expert e - first of this share
        assert np.array_equal(w["gate"], w_all["gate"][first: first + 2])
        part = program_ffn(cfg, w)
        assert float(jnp.max(jnp.abs(part - reference_ffn(cfg, w)))) < 1e-5
        total = total + part - shared_of(w)
    assert float(jnp.max(jnp.abs(total - want))) < 1e-5
    assert float(jnp.max(jnp.abs(want - shared_of(w_all)))) > 1e-3


def test_weights_are_slices_of_the_published_model():
    cfg = debug_cfg()
    key = weights.seed_key(2**31 + 9)
    full = dict(cfg, vocab_size=4096, n_routed_experts=8)
    a, b = weights.top_weights(cfg, key), weights.top_weights(full, key)
    assert np.array_equal(a["embed"], b["embed"][:1024])
    assert np.array_equal(a["lm_head"], b["lm_head"][:, :1024])
    stacked = weights.stacked_weights(cfg, key)
    alone = weights.layer_weights(cfg, key, 1)
    for name in alone:
        assert np.array_equal(stacked["layers"][name][1], alone[name]), name
    one = weights.expert_weights(cfg, key, 1, 3)
    assert np.array_equal(alone["down"][3], one["down"])
    assert weights.num_params(cfg) == sum(
        x.size for x in jax.tree.leaves(stacked))
    real = harness.load_json(ROOT / "benchmarks" / "configs"
                             / "mistral-small-4-ep4-l6.json")
    assert weights.num_params(real) == pytest.approx(5.42e9, rel=0.005)


# ---- the data files --------------------------------------------------------------
def test_the_cell_loads_with_the_published_widths_and_its_cut():
    loaded = harness.load_cell(BENCH, REAL)
    cfg, job, mix = loaded["config_data"], loaded["job"], loaded["traffic_data"]
    assert loaded["chips"] == 1 and job["runner"] == "serve"
    catalog = {"hidden_size": 4096, "q_lora_rank": 1024, "kv_lora_rank": 256,
               "qk_nope_head_dim": 64, "qk_rope_head_dim": 64,
               "v_head_dim": 128, "num_attention_heads": 32,
               "moe_intermediate_size": 2048, "num_experts_per_tok": 4,
               "n_shared_experts": 1, "first_k_dense_replace": 0,
               "rms_norm_eps": 1e-06, "max_position_embeddings": 1048576}
    assert {k: cfg[k] for k in catalog} == catalog
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) \
        == (6, 32, 32768)
    assert cfg["published"] == {"num_hidden_layers": 36, "n_routed_experts": 128,
                                "vocab_size": 131072}
    assert cfg["router_experts"] == 128 and "4 chips" in cfg["deployment"]
    assert {"router", "softmax_scale", "query_scale", "rope_interleave"} \
        <= set(cfg["assumed"])
    assert mix["clients"] == 32 and mix["prompt_len"] == {"fixed": 8192} \
        and mix["output_len"] == {"fixed": 4096}
    eng = job["engine"]
    assert eng["n_slots"] == 32 and eng["max_len"] == 12288 \
        and eng["prefill_chunk"] == 2048 and eng["attend_impl"] == "auto"
    assert (eng["n_pages"] - 1) * eng["page_size"] == 32 * 12288
    assert job["ramp_steps"] == 32 * 8192 // 2048 + 8


def test_the_latent_pool_is_640_bytes_a_token_a_layer_as_published():
    from distributed_training_guide_tpu.serve import kv_pages

    cfg = harness.load_json(ROOT / "benchmarks" / "configs"
                            / "mistral-small-4-ep4-l6.json")
    config = _mla_moe.bundle_for(cfg, "real").config
    assert flops_mla_moe.latent_row_bytes(cfg) == 640
    assert cfg["pool_row"]["published_bytes_per_token_layer"] == 640
    # resident: the rope key's 64 columns padded to one 128-lane tile
    per_token_layer = kv_pages.kv_page_bytes(config, page_size=1) // 6
    assert per_token_layer == cfg["pool_row"]["resident_bytes_per_token_layer"] == 768
    assert kv_pages.pool_layout(config) == {"k": (1, 128), "v": (1, 256)}
    shapes = jax.eval_shape(lambda: kv_pages.init_pages(config, 3073, 128))
    assert shapes["v"].shape == (6, 3073, 128, 1, 256)
    assert sum(x.size * 2 for x in jax.tree.leaves(shapes)) == \
        kv_pages.kv_page_bytes(config, page_size=128, n_pages=3073)
    assert config.num_params() == weights.num_params(cfg)


def test_required_work_of_the_two_kernels():
    cfg = harness.load_json(ROOT / "benchmarks" / "configs"
                            / "mistral-small-4-ep4-l6.json")
    peak = harness.peak_for("TPU v5 lite")
    from benchmarks import flops

    work = flops_mla_moe.latent_attend(cfg, 32 * 9000, 32)
    assert work["flops"] == 2 * 6 * 32 * (320 + 256) * 32 * 9000
    assert work["bytes"] == 6 * 640 * 32 * 9000 + 2 * 6 * 32 * 32 * 576
    assert flops.least_time(work, peak)[1] == "memory"
    assert work["flops"] / (6 * 640 * 32 * 9000) == pytest.approx(57.6)
    gmm = flops_mla_moe.expert_gmm(cfg, experts_touched=20, pairs=32)
    assert gmm["flops"] == 2 * 3 * 4096 * 2048 * 32
    assert gmm["bytes"] == 20 * 3 * 4096 * 2048 * 2 + 32 * 3 * (4096 + 2048) * 2
    assert flops.least_time(gmm, peak)[1] == "memory"


# ---- the reader by path component --------------------------------------------------
def test_by_name_reader_takes_the_paged_attend_events_and_nothing_else():
    from benchmarks import trace_reduce

    device_ops, host_spans, _ = trace_reduce.read_planes(SERVE_NAMED)
    trace = trace_reduce.reduce_events(device_ops, host_spans, 1)
    paths = scope_time.op_paths_of(SERVE_NAMED)
    lo, hi = trace["lo_ns"], trace["hi_ns"]
    got = path_component.component_seconds(trace["device_ops"], paths,
                                           "paged_attend", lo, hi)
    by_hand = sum(b - a for ops in trace["device_ops"].values()
                  for n, a, b in ops
                  if a >= lo and b <= hi and "paged_attend" in n) / 1e9
    assert got == pytest.approx(by_hand) and got > 0
    # ... the attend scope's kernel without the two pool-sized reshapes that
    # scope still held when the trace was recorded (PERF.md, PR 27)
    table = scope_time.table_from(trace["device_ops"], paths, lo, hi, 3)
    assert 0.9 < 1e3 * got / 3 / table["ms_per_step"]["attend"] < 1.0
    for absent in ("gmm", "paged_latent_attend", "latent_proj", "paged"):
        assert path_component.component_seconds(
            trace["device_ops"], paths, absent, lo, hi) is None


def test_reader_returns_nothing_where_there_is_nothing_to_read():
    for params in (
            {"component": "gmm", "as": "roofline", "work": "expert_gmm"},
            {"component": "latent_proj", "as": "ms_per_step",
             "step_span": "engine.step"}):
        assert path_component.read({"trace": None, "trace_dir": None}, params) is None


def test_reader_reads_a_components_time_per_step_and_its_roofline(tmp_path, monkeypatch):
    ms = 1_000_000
    paths = {"%k": "jit(serve_decode)/layers/while/body/attn/attend/"
                   "paged_latent_attend/pallas_call:",
             "%g": "jit(serve_decode)/layers/while/body/experts/gmm/pallas_call:",
             "%p": "jit(serve_decode)/layers/while/body/attn/latent_proj/dot_general:",
             "%w": "jit(serve_decode)/layers/while:"}
    ops = [("%w", 0, 10 * ms), ("%p", 1 * ms, 2 * ms), ("%k", 2 * ms, 4 * ms),
           ("%g", 4 * ms, 8 * ms), ("%k", 30 * ms, 31 * ms)]
    trace = {"lo_ns": 0, "hi_ns": 20 * ms, "device_ops": {0: ops},
             "host_spans": [("engine.step", 0, 9 * ms), ("engine.step", 10 * ms, 19 * ms)]}
    monkeypatch.setattr(path_component._xplane, "traced", lambda ctx: (trace, "x"))
    monkeypatch.setattr(path_component.scope_time, "op_paths_of", lambda p: paths)
    cfg = harness.load_json(ROOT / "benchmarks" / "configs" / "mistral-small-4-ep4-l6.json")
    ctx = {"config": cfg, "peak": harness.peak_for("TPU v5 lite"),
           "trace_window": (0.0, 1.0),
           "counters": {"kv_bytes": 2, "decode_context": [(0.5, 32 * 9000, 32), (2.0, 1, 1)],
                        "routing_steps": [(0.5, 32, 20), (2.0, 99, 99)]}}
    assert path_component.read(ctx, {"component": "latent_proj", "as": "ms_per_step",
                                     "step_span": "engine.step"}) == pytest.approx(0.5)
    least = lambda work: max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert path_component.read(ctx, {"component": "paged_latent_attend", "as": "roofline",
                                     "work": "latent_attend"}) == pytest.approx(
        100 * least(flops_mla_moe.latent_attend(cfg, 32 * 9000, 32)) / 2e-3)
    assert path_component.read(ctx, {"component": "gmm", "as": "roofline",
                                     "work": "expert_gmm"}) == pytest.approx(
        100 * least(flops_mla_moe.expert_gmm(cfg, 20, 32)) / 4e-3)


# ---- the runner, end to end at debug width ---------------------------------------
def make_root(tmp: Path) -> Path:
    bench = tmp / "benchmarks"
    bench.mkdir(parents=True)
    shutil.copytree(ROOT / "benchmarks" / "metrics", bench / "metrics")
    shutil.copy(ROOT / "benchmarks" / "peaks.json", bench / "peaks.json")
    for d in ("configs", "traffic", "workloads"):
        shutil.copytree(DEBUG / d, bench / d)
    doc = json.loads(json.dumps(BENCH))
    doc["configs"] = [{"name": "debug-mla-moe", "source": "debug", "reduced": [],
                       "why": "debug", "file": "benchmarks/configs/debug-mla-moe.json"}]
    doc["workloads"] = [{"name": CELL, "config": "debug-mla-moe",
                         "traffic": "serve.debug-decode", "chips": 1, "why": "debug"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if REAL in m["workloads"] else []
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp


@pytest.fixture(scope="module")
def debug_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("mla_root"))


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
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 3
    assert set(last["metrics"]) == {"setup_s", "serve.out_tokens_per_s",
                                    "serve.itl_p95_ms"}
    window = next(json.loads(l)["window"] for l in lines if l.startswith('{"window"'))
    assert window["preemptions"] == 0 and window["refused"] == 0
    # a reply (400 tokens) can end in the window's last step on a fast host
    assert window["in_flight_at_close"] in (3, 4) and window["out_tokens"] > 0
    routing = next(json.loads(l)["routing"] for l in lines if l.startswith('{"routing"'))
    # 4 slots x top-2 of 8 routed experts; 4 held: about half the pairs
    assert routing["pairs_routed_a_step"] == 2 * 4 * 2
    assert 0 < routing["pairs_held_a_step"] < routing["pairs_routed_a_step"]
    assert any(l.startswith('{"reference_seconds"') for l in lines)


def test_traced_run_reports_the_counters_and_leaves_device_metrics_out(debug_root):
    result = run(debug_root, trace=True)
    assert result["correct"] is True
    names = set(result["metrics"])
    assert {"serve.expert_pairs_held_pct", "serve.experts_touched_pct",
            "serve.step_ms_p50", "serve.batch_occupancy_pct"} <= names
    assert not any(n.endswith("_roofline") or n.endswith("device_ms") for n in names)
    assert 0 < result["metrics"]["serve.expert_pairs_held_pct"]["value"] < 100
    assert result["metrics"]["serve.batch_occupancy_pct"]["value"] == 100.0
    steps = result["ctx"]["counters"]["routing_steps"]
    # a row is one decode step, summed over the 2 layers: 4 held experts,
    # 4 slots x top-2 pairs a layer
    assert steps and all(0 <= touched <= 2 * 4 and held <= 2 * 8
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


def drop_router_bias(monkeypatch):
    """The program's router without its selection bias."""
    real = _mla_moe.to_program

    def without(w):
        tree = real(w)
        tree["layers"]["moe"]["router_bias"] = jnp.zeros_like(
            tree["layers"]["moe"]["router_bias"])
        return tree
    monkeypatch.setattr(_mla_moe, "to_program", without)


@pytest.mark.parametrize("fault", ["alter_token", "no_router_bias"])
def test_a_broken_timed_path_is_not_correct(fault, debug_root, monkeypatch):
    {"alter_token": alter_served_tokens,
     "no_router_bias": drop_router_bias}[fault](monkeypatch)
    result = run(debug_root)
    assert result["correct"] is False
    assert any(not row["ok"] for row in result["compared"])


@pytest.mark.parametrize("mode", ["int8", "bf16_router"])
def test_runner_control_reads_the_reference_in_a_lower_precision(mode, debug_root):
    result = run(debug_root)
    runner = harness.load_module("runners", result["ctx"]["job"]["runner"])
    rows = runner.control(result["ctx"], mode)
    assert set(rows) <= {r["check"] for r in result["compared"]}
    sound = {r["check"]: r["value"] for r in result["compared"]}
    if mode == "int8":   # at debug width the control moves the mean, by
        # less than the cell's factor (PERF.md has the chip's readings)
        assert rows["served_token_mean_logit_gap"] > \
            sound["served_token_mean_logit_gap"]
