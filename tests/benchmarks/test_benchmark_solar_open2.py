"""The ``solar_open2`` family in the benchmark, on the CPU: the plain reference
(``benchmarks/reference/solar_open2.py``, a scan over tokens) against
``models/solar_open2.py`` with the faults it has to see, the held share of
the experts, the weights' contract, the cell's data files, the work functions
and the new reader by hand, and the runner end to end on a debug-width cell
(tests/benchmarks/debug/) with faults ``correct`` has to catch and the
lower-precision control."""
import json
import shutil
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import flops, flops_solar_open2, harness  # noqa: E402
from benchmarks import weights_solar_open2 as weights  # noqa: E402
from benchmarks.readers import kda_work, window_release  # noqa: E402
from benchmarks.reference import solar_open2 as ref  # noqa: E402
from benchmarks.runners import _solar_open2  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
DEBUG = Path(__file__).resolve().parent / "debug"
CELL = "debug-solar-open2.serve.debug-gen"
REAL = "solar-open2-ep8-l4.serve.gen192"
REAL_CFG = ROOT / "benchmarks" / "configs" / "solar-open2-ep8-l4.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW = ("serve.kda_device_ms", "kda_step_roofline", "kda_chunk_roofline",
       "serve.state_blocks_returned_per_step", "gqa_attend_roofline")
# float32 program against float32 reference: only summation order differs
# (read 2e-5 on logits of magnitude 5: a chunked scan against a token scan)
LOGIT_TOL = 1e-4


def debug_cfg(**over):
    cfg = json.loads((DEBUG / "configs" / "debug-solar-open2.json").read_text())
    return dict(cfg, **over)


def layer_fn_of(cfg, key):
    return lambda l: weights.layer_weights(cfg, key, l, jnp.float32)


# ---- the reference against the program ---------------------------------------
@pytest.fixture(scope="module")
def forward():
    from distributed_training_guide_tpu.models import solar_open2

    cfg, key = debug_cfg(), weights.seed_key(2**31 + 7)
    w = weights.stacked_weights(cfg, key, jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 512, 70).astype(np.int32)
    bundle = _solar_open2.bundle_for(cfg, "debug")
    got = solar_open2.apply(bundle.config, _solar_open2.to_program(w),
                            jnp.asarray(tokens[None]))[0]
    return cfg, key, w["top"], tokens, got


@pytest.mark.parametrize("fault", ref.FAULTS + ("int8",),
                         ids=[f or "sound" for f in ref.FAULTS] + ["int8"])
def test_reference_matches_program_logits_and_sees_each_fault(forward, fault):
    """One GQA and three KDA layers (the share holds experts 2-5 of 8), 70
    tokens: a block of the program's chunked scan and six tokens more. The
    sound reference, a scan over TOKENS, is the program's forward; with the
    taps reversed, no decay, beta in (0, 1), the decay on the value axis,
    either output gate left out, the choice bias or the shared expert left
    out, or int8 operands, it is not."""
    cfg, key, top, tokens, got = forward
    more = {"mode": "int8"} if fault == "int8" else {"fault": fault}
    want = ref.forward_logits(cfg, layer_fn_of(cfg, key), top, tokens, **more)
    diff = float(jnp.max(jnp.abs(got - want)))
    if fault is None:
        assert diff < LOGIT_TOL
    else:
        assert diff > 1000 * LOGIT_TOL


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """One layer's FFN on the same rows: the ROUTED parts the two shares give
    (four experts each of eight; pairs of absent experts dropped, the partial
    sum goes on) and the shared expert ONCE add up to the uncut reference
    layer. The program's ``_ffn`` against the reference's ``route`` and
    ``swiglu``."""
    from distributed_training_guide_tpu.models import solar_open2

    whole = debug_cfg(n_routed_experts=8, experts_held_first=0)
    key = weights.seed_key(11)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 48, 64)),
                    jnp.float32)

    def program_ffn(cfg):
        config = _solar_open2.bundle_for(cfg, "debug").config
        layers = _solar_open2.to_program(
            weights.stacked_weights(cfg, key, jnp.float32))["layers"]
        y, _ = solar_open2._ffn(config, x, layers, 1, {})
        return (y - x)[0]

    def reference_parts(cfg):
        w = {k: v.astype(jnp.float32) for k, v in
             weights.layer_weights(cfg, key, 1, jnp.float32).items()}
        u = ref.rmsnorm(x[0], w["ffn_norm"], cfg["rms_norm_eps"])
        weight = ref.route(cfg, w, u)
        routed = sum(weight[:, j: j + 1] * ref.swiglu(
            u, w["gate"][j], w["up"][j], w["down"][j])
            for j in range(cfg["n_routed_experts"]))
        return routed, ref.swiglu(u, w["shared_gate"], w["shared_up"],
                                  w["shared_down"])

    routed, shared = reference_parts(whole)
    assert float(jnp.max(jnp.abs(program_ffn(whole) - routed - shared))) < 1e-5
    total = shared
    for first in (0, 4):
        cfg = debug_cfg(n_routed_experts=4, experts_held_first=first)
        part, again = reference_parts(cfg)
        assert float(jnp.max(jnp.abs(again - shared))) == 0.0
        assert float(jnp.max(jnp.abs(program_ffn(cfg) - part - shared))) < 1e-5
        total = total + part
    assert float(jnp.max(jnp.abs(total - routed - shared))) < 1e-5
    assert float(jnp.max(jnp.abs(routed))) > 1e-2 < float(
        jnp.max(jnp.abs(shared)))


def test_stacked_weights_are_the_layers_own_draws_and_nothing_more():
    cfg, key = debug_cfg(), weights.seed_key(3)
    stacked = weights.stacked_weights(cfg, key, jnp.float32)
    kinds = weights.layers_of(cfg)
    assert kinds == {"norms": [0, 1, 2, 3], "ffn": [0, 1, 2, 3],
                     "gqa": [0], "kda": [1, 2, 3]}
    assert set(stacked["kda"]) == set(weights.KINDS["kda"])
    for kind, layers in kinds.items():
        for row, l in enumerate(layers):
            own = weights.layer_weights(cfg, key, l, jnp.float32)
            for name, leaf in stacked[kind].items():
                assert np.array_equal(leaf[row], own[name]), (kind, name)
    # held experts 2-5 are the uncut model's experts 2-5
    uncut = weights.layer_weights(
        debug_cfg(n_routed_experts=8, experts_held_first=0), key, 2)
    assert np.array_equal(stacked["ffn"]["up"][2], uncut["up"][2:6])
    # drawn large enough to matter
    kda = stacked["kda"]
    assert 0.4 < float(jnp.std(kda["kda_conv_k"])) < 0.6
    rate = jnp.exp(kda["kda_a_log"])
    assert 1.0 <= float(rate.min()) and float(rate.max()) <= 16.0
    step = jnp.log1p(jnp.exp(kda["kda_dt_bias"]))       # softplus
    assert 0.00099 < float(step.min()) and float(step.max()) < 0.1001
    # alpha = exp(-rate x step): between 0.2 and 0.999 a token
    assert float(jnp.exp(-16.0 * step.max())) > 0.2
    assert 0.01 < float(jnp.std(stacked["ffn"]["router_bias"])) < 0.03
    wide = float(jnp.std(kda["kda_w_gb"])) / float(jnp.std(kda["kda_w_fb"]))
    assert 2.3 < wide < 2.7
    assert weights.num_params(cfg) == _solar_open2.bundle_for(
        cfg, "debug").config.num_params()


# ---- the data files ----------------------------------------------------------
def test_the_cell_loads_with_the_published_widths_and_its_cut():
    loaded = harness.load_cell(BENCH, REAL)
    cfg, job, mix = loaded["config_data"], loaded["job"], loaded["traffic_data"]
    assert loaded["chips"] == 1 and job["runner"] == "serve"
    if CATALOG.exists():    # every number of the catalog's config, but the cut
        row = next(r for r in map(json.loads, CATALOG.open())
                   if r["name"] == "Solar-Open2-250B")
        assert cfg["source"] == row["source_url"]
        for name, value in row["config"].items():
            if name not in cfg["reduced"]:
                assert cfg[name] == value, name
            else:
                assert cfg["published"][name] == value, name
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "gqa_layers"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["router_experts"],
            cfg["experts_held_first"]) == (4, 40, 24576, 320, 0)
    # one whole period, as published: a GQA layer, then three KDA layers
    assert cfg["gqa_layers"] == [0] == cfg["published"]["gqa_layers"][:1]
    assert cfg["published"]["gqa_layers"][1] == 4 == cfg["gqa_interval"] + 1
    assert cfg["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert {"kda", "kda_low_rank", "kda_neg_eigval", "gqa_gate", "gqa",
            "router", "shared_expert", "intermediate_size", "state_dtype",
            "weights"} <= set(cfg["assumed"])
    assert "8 chips" in cfg["deployment"] and "12 such stages" in cfg["deployment"]
    assert weights.num_params(cfg) == 3_308_353_344
    assert f"{weights.num_params(cfg):,}" in cfg["deployment"]
    config = _solar_open2.bundle_for(cfg, "real").config
    assert config.num_params() == 3_308_353_344
    assert mix["clients"] == mix["distinct_requests"] == 192 \
        and mix["prompt_len"] == {"fixed": 2048} \
        and mix["output_len"] == {"fixed": 1536} and mix["loop"] == "closed" \
        and mix["first_output_len"] == "staggered" and mix["shared_prefix"] == 0
    eng = job["engine"]
    assert eng["n_slots"] == mix["clients"] and eng["page_size"] == 128 \
        and eng["prefill_chunk"] == 2048 and eng["attend_impl"] == "auto" \
        and eng["prefix_cache"] is False
    # every request whole and the trash page: nothing is preempted
    assert eng["max_len"] == 2048 + 1536
    assert eng["n_pages"] == eng["n_slots"] * (eng["max_len"] // 128) + 1
    # every client's first prompt and those of the 24 clients whose first
    # replies (8, 16, ... tokens) ended while the first prompts were prefilled
    assert job["ramp_steps"] == 192 + 24


def test_the_whole_model_counts_what_was_published():
    from distributed_training_guide_tpu.models import solar_open2

    whole = solar_open2.PRESETS["solar-open2-250b"]
    assert whole.num_params() == 250_287_810_304
    assert 14.5e9 < whole.num_active_params() < 15e9
    cfg = harness.load_json(REAL_CFG)
    uncut = dict(cfg, **cfg["published"])
    assert weights.num_params(uncut) == 250_287_810_304


def test_the_state_class_costs_what_the_configuration_file_says():
    import jax

    from distributed_training_guide_tpu.serve import kv_pages

    cfg = harness.load_json(REAL_CFG)
    job = harness.load_cell(BENCH, REAL)["job"]["engine"]
    config = _solar_open2.bundle_for(cfg, "real").config
    said = cfg["state_per_sequence"]
    assert kv_pages.sequence_state_bytes(config) == said["bytes"] == 13_025_280 \
        == said["kda_layers"] * (said["kda_state_bytes_a_layer"]
                                 + said["conv_rows_bytes_a_layer"])
    assert flops_solar_open2.state_bytes(cfg) == said["kda_state_bytes_a_layer"]
    assert kv_pages.kv_page_bytes(config, page_size=1) \
        == said["kv_bytes_per_token"] == 4096
    # float32 whatever the file says: the adapter runs no other state class,
    # and the roofline's bytes do not follow the file either
    narrow = dict(cfg, state_dtype="bfloat16")
    with pytest.raises(ValueError, match="state class is float32"):
        _solar_open2.bundle_for(narrow, "real")
    assert flops_solar_open2.kda_step(narrow, 192) \
        == flops_solar_open2.kda_step(cfg, 192)
    blocks = job["n_slots"] + 1
    shapes = jax.eval_shape(lambda: kv_pages.init_pages(
        config, job["n_pages"], 128, n_state_blocks=blocks))
    assert shapes["k"].shape == (1, 5377, 128, 8, 128)
    assert shapes["seq_state"].shape == (3, 193, 64, 128, 128) \
        and shapes["seq_state"].dtype == jnp.float32
    assert shapes["seq_conv"].shape == (3, 193, 3, 24576) \
        and shapes["seq_conv"].dtype == jnp.bfloat16
    state = kv_pages.sequence_state_bytes(config, blocks)
    kv = kv_pages.kv_page_bytes(config, page_size=128, n_pages=5377)
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(shapes)) == state + kv
    # 2.51 GB by sequence; by page the same state would be 70 GB
    assert 2.5e9 < state < 2.52e9 and 2.8e9 < kv < 2.83e9
    assert said["bytes"] * 5377 > 70e9


def test_the_cell_is_listed_where_its_readers_mean_the_same():
    def cells(name):
        return next(m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                    if m["name"] == name)["workloads"]
    for name in ("serve.out_tokens_per_s", "serve.itl_p95_ms", "gmm_roofline",
                 "serve.experts_device_ms", "serve.router_device_ms",
                 "serve.expert_pairs_held_pct", "serve.experts_touched_pct",
                 "serve.attend_device_ms", "serve.kv_write_device_ms",
                 "serve.chunk_device_ms", "serve.schedule_ms_per_step",
                 "device.peak_hbm_gb.serve", "device.idle_pct.serve",
                 "serve.step_ms_p50", "serve.host_ms_per_step"):
        assert REAL in cells(name), name
    for name in NEW:
        assert cells(name) == [REAL], name
    # flops.paged_attend multiplies by num_hidden_layers, the walk has no
    # innermost `layers` scope, flops_lfm2_moe reads `layer_types`; the step
    # waterfall's seven metrics are pinned to the cells they had by
    # test_benchmark_step_waterfall.py, which this PR may not edit
    for name in ("paged_attend_roofline", "serve.layers_device_ms",
                 "hybrid_attend_roofline", "mixed_attend_roofline",
                 "latent_attend_roofline", "serve.conv_device_ms",
                 "serve.launch_ms_per_step", "serve.chunk_step_gap_ms",
                 "serve.rebuild_steps_pct"):
        assert REAL not in cells(name), name
    entry = next(c for c in BENCH["workloads"] if c["name"] == REAL)
    config = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert entry["chips"] == 1 and entry["traffic"] == "serve.gen192"
    for why in (entry["why"], config["why"]):
        assert 1 <= len(why) <= 200 and why.isascii() and why.isprintable()
    assert config["reduced"] == harness.load_json(REAL_CFG)["reduced"]
    layers = {m["name"]: m["layer"] for m in BENCH["per_layer"]}
    assert layers["kda_step_roofline"] == layers["gmm_roofline"]
    assert layers["serve.kda_device_ms"] == layers["serve.conv_device_ms"]


def test_the_hybrid_attend_roofline_cannot_read_this_configuration():
    """``hybrid_attend_roofline``'s work function counts the attending layers
    from ``layer_types`` / ``num_dense_layers``, which this family's
    published configuration does not have (``gqa_layers`` names them): the
    cell stays off that list and ``gqa_attend_roofline`` reads the one
    ``paged_attend`` call with the family's own work function."""
    from benchmarks import flops_lfm2_moe

    with pytest.raises(KeyError):
        flops_lfm2_moe.paged_attend(harness.load_json(REAL_CFG), 1000, 4)


def test_required_work_of_the_gqa_layers_paged_attend():
    """The one attending layer of the cut: 4,096 B a token (8 kv heads of 128,
    k and v, bf16), 64 query heads, whatever the three KDA layers hold."""
    cfg = harness.load_json(REAL_CFG)
    peak = harness.peak_for("TPU v5 lite")
    # 192 slots at 2,800 tokens each, one step
    work = flops_solar_open2.gqa_attend(cfg, 192 * 2800, 192, kv_bytes=2)
    assert work["bytes"] == 4096 * 192 * 2800 + 2 * 2 * 192 * 64 * 128
    assert work["flops"] == 4 * 64 * 128 * 192 * 2800
    least, bound = flops.least_time(work, peak)
    assert bound == "memory" and 2.6e-3 < least < 2.8e-3
    # a second period doubles it; flops.paged_attend counts every layer
    assert flops_solar_open2.gqa_attend(
        dict(cfg, gqa_layers=[0, 4], num_hidden_layers=8), 1000, 4)["bytes"] \
        == 2 * flops_solar_open2.gqa_attend(cfg, 1000, 4)["bytes"]
    assert flops.paged_attend(cfg, 1000, 4)["flops"] \
        == 4 * flops_solar_open2.gqa_attend(cfg, 1000, 4)["flops"]


# ---- required work, and the readers ------------------------------------------
def test_required_work_of_the_two_kda_computations():
    cfg = harness.load_json(REAL_CFG)
    peak = harness.peak_for("TPU v5 lite")
    assert flops_solar_open2.token_flops(cfg) == 7 * 64 * 128 * 128 == 7_340_032
    assert flops_solar_open2.row_bytes(cfg) == (5 * 8192 + 64) * 4
    # 192 live slots, one step: each state in and out once in 3 layers
    step = flops_solar_open2.kda_step(cfg, 192)
    assert step["bytes"] == 3 * 192 * (2 * 4_194_304 + 164_096)
    assert step["flops"] == 3 * 192 * 7_340_032
    least, bound = flops.least_time(step, peak)
    assert bound == "memory" and 6.0e-3 < least < 6.1e-3
    # one chunk of 2,048 tokens
    chunk = flops_solar_open2.kda_chunk(cfg, 2048, 1)
    assert chunk["flops"] == 3 * 2048 * 7_340_032
    assert chunk["bytes"] == 3 * (2048 * 164_096 + 2 * 4_194_304)
    least, bound = flops.least_time(chunk, peak)
    assert bound == "memory" and 1.2e-3 < least < 1.3e-3


def test_readers_return_nothing_where_there_is_nothing_to_read():
    ctx = {"trace": None, "trace_dir": None, "config": {}, "job": {}}
    for component, work, program in (
            ("kda_step", "kda_step", "serve_decode"),
            ("kda_chunk", "kda_chunk", "serve_chunk_t2048"),
            ("paged_attend", "gqa_attend", "serve_decode")):
        assert kda_work.read(ctx, {"component": component, "work": work,
                                   "program": program}) is None
    assert window_release.read(ctx, {"span": "serve.state",
                                     "stat": "returned"}) is None


def test_readers_read_the_three_rooflines_and_the_returned_blocks(monkeypatch):
    ms = 1_000_000
    paths = {
        "%s": "jit(serve_decode)/layers/attn/kda/kda_step/pallas_call:",
        "%g": "jit(serve_decode)/layers/experts/gmm/pallas_call:",
        "%a": "jit(serve_decode)/layers/attn/attend/paged_attend/pallas_call:",
        "%c": "jit(serve_chunk_t2048)/layers/attn/kda/kda_chunk/while/body/dot_general:",
        "%p": "jit(serve_chunk_t2048)/layers/attn/kda/dot_general:"}
    ops = [("%s", 1 * ms, 4 * ms), ("%g", 4 * ms, 8 * ms),
           ("%a", 8 * ms, 9 * ms), ("%s", 11 * ms, 14 * ms),
           ("%a", 14 * ms, 15 * ms),
           ("%p", 21 * ms, 22 * ms), ("%c", 22 * ms, 26 * ms)]
    modules = [("jit_serve_decode(7)", 0, 9 * ms),
               ("jit_serve_decode(7)", 10 * ms, 15 * ms),
               ("jit_serve_chunk_t2048(9)", 20 * ms, 28 * ms)]
    trace = {"lo_ns": 0, "hi_ns": 50 * ms, "device_ops": {0: ops},
             "device_modules": {0: modules}, "host_spans": []}
    spans = [("serve.step", 0, 9 * ms, "t", {}),
             ("serve.state", 8 * ms, 8 * ms + 10, "t",
              {"taken": 0, "returned": 1, "live": 3}),
             ("serve.step", 10 * ms, 15 * ms, "t", {}),
             ("serve.step", 19 * ms, 40 * ms, "t", {}),        # a chunk step:
             ("serve.state", 19 * ms + 1, 19 * ms + 9, "t",    # not counted
              {"taken": 1, "returned": 0, "live": 4}),
             ("serve.prefill", 20 * ms, 29 * ms, "t", {"tokens": 2000})]
    for mod in (kda_work, window_release):
        monkeypatch.setattr(mod._xplane, "traced", lambda ctx: (trace, "x"))
        monkeypatch.setattr(mod._xplane, "program_spans", lambda path: spans)
    monkeypatch.setattr(kda_work.scope_time, "op_paths_of", lambda p: paths)
    cfg = harness.load_json(REAL_CFG)
    ctx = {"config": cfg, "peak": harness.peak_for("TPU v5 lite"),
           "trace_window": (0.0, 1.0),
           "counters": {"kv_bytes": 2, "decode_context": [
               (0.5, 500_000, 192), (0.7, 500_192, 191), (2.0, 1, 1)]}}
    step = flops_solar_open2.kda_step(cfg, 383)
    assert kda_work.read(ctx, {
        "component": "kda_step", "work": "kda_step",
        "program": "serve_decode"}) == pytest.approx(
        100 * (step["bytes"] / 819e9) / 6e-3)
    chunk = flops_solar_open2.kda_chunk(cfg, 2000, 1)
    assert kda_work.read(ctx, {
        "component": "kda_chunk", "work": "kda_chunk",
        "program": "serve_chunk_t2048"}) == pytest.approx(
        100 * (chunk["bytes"] / 819e9) / 4e-3)
    attend = flops_solar_open2.gqa_attend(cfg, 1_000_192, 383, 2)
    assert kda_work.read(ctx, {
        "component": "paged_attend", "work": "gqa_attend",
        "program": "serve_decode"}) == pytest.approx(
        100 * (attend["bytes"] / 819e9) / 2e-3)
    assert window_release.read(ctx, {"span": "serve.state",
                                     "stat": "returned"}) == pytest.approx(0.5)
    # another family's configuration, or its trace: nothing
    assert kda_work.read(dict(ctx, config={}), {
        "component": "kda_step", "work": "kda_step",
        "program": "serve_decode"}) is None
    monkeypatch.setattr(kda_work.scope_time, "op_paths_of",
                        lambda p: {"%g": paths["%g"]})
    assert kda_work.read(ctx, {"component": "kda_step", "work": "kda_step",
                               "program": "serve_decode"}) is None


# ---- the runner end to end on the debug cell ----------------------------------
def make_root(tmp: Path) -> Path:
    bench = tmp / "benchmarks"
    bench.mkdir(parents=True)
    shutil.copytree(ROOT / "benchmarks" / "metrics", bench / "metrics")
    shutil.copy(ROOT / "benchmarks" / "peaks.json", bench / "peaks.json")
    for d in ("configs", "traffic", "workloads"):
        shutil.copytree(DEBUG / d, bench / d)
    doc = json.loads(json.dumps(BENCH))
    doc["configs"] = [{"name": "debug-solar-open2", "source": "debug",
                       "reduced": [], "why": "debug",
                       "file": "benchmarks/configs/debug-solar-open2.json"}]
    doc["workloads"] = [{"name": CELL, "config": "debug-solar-open2",
                         "traffic": "serve.debug-gen", "chips": 1,
                         "why": "debug"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if REAL in m["workloads"] else []
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp


@pytest.fixture(scope="module")
def debug_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("solar_root"))


def run(root, **kw):
    return harness.run_cell(
        root=root, workload=CELL, seed=kw.pop("seed", 2**31 + 23),
        seconds=kw.pop("seconds", 1.0), trace=kw.pop("trace", False),
        t_process_start=time.monotonic(), bench_dir=root / "benchmarks",
        require_platform=None)


@pytest.fixture(scope="module")
def sound(debug_root):
    return run(debug_root, trace=True)


def test_runner_end_to_end_on_the_debug_cell(sound):
    """A traced run of the debug cell (prompts of 40 tokens in chunks of 16,
    replies of 24 staggered by 6, pages of 8, four blocks of the state class
    and the trash block): correct, nothing refused or preempted, replies end
    and blocks are returned and taken again inside the window, the counters
    are reported and the device metrics left out (no device plane off a
    TPU)."""
    assert sound["correct"] is True and sound["failed"] == 0
    names = set(sound["metrics"])
    assert {"serve.step_ms_p50", "serve.batch_occupancy_pct",
            "serve.preemptions", "serve.expert_pairs_held_pct",
            "serve.experts_touched_pct"} <= names
    assert not any(n.endswith("_roofline") or n.endswith("device_ms")
                   for n in names)
    assert sound["metrics"]["serve.preemptions"]["value"] == 0
    # top-2 of 8 experts, 4 held: about half the pairs
    assert 25 < sound["metrics"]["serve.expert_pairs_held_pct"]["value"] < 75
    assert 0 < sound["metrics"]["serve.experts_touched_pct"]["value"] <= 100
    assert sound["attempted"] >= 4


def keep_a_blocks_last_owner(monkeypatch):
    """The zero state of a sequence's start left out: a reused block is read
    as its last owner left it."""
    from distributed_training_guide_tpu.models import solar_open2

    real = solar_open2.kda_sublayer

    def stale(config, x, p, norm_scale, state=None):
        if state is not None:
            pool, conv_pool, row, attend = state

            class Started:      # every slot claims history
                state_blocks, n_valid = attend.state_blocks, attend.n_valid
                lengths = jnp.ones_like(attend.lengths)
            state = (pool, conv_pool, row, Started)
        return real(config, x, p, norm_scale, state)
    monkeypatch.setattr(solar_open2, "kda_sublayer", stale)


def taps_in_the_wrong_order(monkeypatch):
    real = _solar_open2.to_program

    def edited(w):
        tree = real(w)
        for layer in tree["layers"]["kda"]:
            layer["taps"] = layer["taps"][::-1]
        return tree
    monkeypatch.setattr(_solar_open2, "to_program", edited)


# (a state class stored in bfloat16 is NOT among them: it flips a served token
# too rarely for this comparison to see, here and on the chip: PERF.md
# section 7; tests/test_solar_open2.py holds the knob to what it does)
FAULTS = {"stale_block": keep_a_blocks_last_owner,
          "taps_reversed": taps_in_the_wrong_order}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(fault, debug_root, monkeypatch):
    FAULTS[fault](monkeypatch)
    result = run(debug_root)
    assert result["correct"] is False
    assert any(not row["ok"] for row in result["compared"])


def test_runner_control_reads_the_reference_in_a_lower_precision(sound):
    runner = harness.load_module("runners", sound["ctx"]["job"]["runner"])
    rows = runner.control(sound["ctx"], "int8")
    assert set(rows) <= {r["check"] for r in sound["compared"]}
    values = {r["check"]: r["value"] for r in sound["compared"]}
    limits = sound["ctx"]["job"]["check"]["limits"]
    # the control moves the mean past the debug cell's limit
    assert rows["served_token_mean_logit_gap"] > \
        limits["served_token_mean_logit_gap"] >= values["served_token_mean_logit_gap"]
