"""The ``laguna`` family in the benchmark, on the CPU: the cell's data files
against the catalog's row and the cut's hand count, ``flops_laguna`` against
hand arithmetic, the two new readers over a synthetic trace, the cell's
listings, and ``runners/train_family.py`` end to end on a debug-width cell
(tests/benchmarks/debug/) with a fault ``correct`` has to catch and the
lower-precision control. Program against reference, leaf by leaf, is
``tests/test_laguna.py``'s."""
import json
import shutil
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import flops, flops_laguna, harness  # noqa: E402
from benchmarks import weights_laguna as weights  # noqa: E402
from benchmarks.readers import family_work, mfu_family  # noqa: E402
from benchmarks.runners import _laguna, train_family  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
DEBUG = Path(__file__).resolve().parent / "debug"
CELL = "debug-laguna.train.debug"
REAL = "laguna-xs.2-ep8-l5.train.seq8192"
REAL_CFG = ROOT / "benchmarks" / "configs" / "laguna-xs.2-ep8-l5.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW = ("train.mfu_required_held_pct", "banded_flash_roofline",
       "train.gmm_roofline", "train.experts_device_ms",
       "train.router_device_ms", "train.attn_full_device_ms",
       "train.attn_window_device_ms", "train.expert_pairs_held_pct",
       "train.expert_rows_fullest_over_mean")


# ---- the data files ----------------------------------------------------------
def test_the_cell_loads_with_the_published_widths_and_its_cut():
    loaded = harness.load_cell(BENCH, REAL)
    cfg, job, mix = loaded["config_data"], loaded["job"], loaded["traffic_data"]
    assert loaded["chips"] == 1 and job["runner"] == "train_family"
    if CATALOG.exists():    # every number of the catalog's config, but the cut
        row = next(r for r in map(json.loads, CATALOG.open())
                   if r["name"] == "Laguna-XS.2")
        assert cfg["source"] == row["source_url"]
        for name, value in row["config"].items():
            if name not in cfg["reduced"]:
                assert cfg[name] == value, name
            else:
                assert cfg["published"][name] == value, name
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size",
                              "layer_types", "mlp_layer_types",
                              "num_attention_heads_per_layer"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["router_experts"], cfg["experts_held_first"]) == (
        5, 32, 12544, 256, 0)
    # the leading dense layer and one whole period: two full, three window
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert cfg[key] == cfg["published"][key][:5], key
    assert cfg["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"],
            cfg["sliding_window"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"]) == (2048, 128, 8, 512, 512, 8)
    assert {"gating", "router", "qk_norm", "aux_loss", "shared_expert",
            "activation", "rope", "window_edge", "weights"} <= set(
        cfg["assumed"])
    assert "8 chips" in cfg["deployment"]
    assert weights.num_params(cfg) == 691_623_936 \
        == _laguna.bundle_for(cfg, "real").config.num_params()
    assert f"{weights.num_params(cfg):,}" in cfg["deployment"]
    assert (mix["global_batch"], mix["seq_len"]) == (2, 8192)
    assert job["plan"] == {"strategy": "single"} and job["remat"] is True \
        and job["precision"] == "fp32" and job["attn_impl"] == "auto" \
        and job["loss_chunks"] == 16 and job["check"]["steps"] == 2
    qwen = harness.load_cell(BENCH, "qwen3-0.6b.train.seq8192")["job"]
    assert job["optimizer"] == qwen["optimizer"]


def test_the_parameter_table_by_hand():
    cfg = harness.load_json(REAL_CFG)
    shapes = lambda l: {k: s for k, (s, _) in
                        weights.layer_shapes(cfg, l).items()}
    count = lambda names, l: sum(
        shapes(l)[n][0] * (shapes(l)[n][1] if len(shapes(l)[n]) > 1 else 1)
        for n in names)
    attn = ("wq", "wk", "wv", "wo", "wg")
    assert count(attn, 0) == 29_458_432 and count(attn, 1) == 37_879_808
    assert count(("dense_gate", "dense_up", "dense_down"), 0) == 50_331_648
    expert = 3 * 2048 * 512
    layer = lambda l: (count(shapes(l), l)
                       + (0 if l == 0 else 32 * expert))
    assert [layer(l) for l in range(5)] == [
        79_794_176, 142_217_216, 142_217_216, 142_217_216, 133_795_840]
    assert sum(layer(l) for l in range(5)) + 2 * 12544 * 2048 + 2048 \
        == 691_623_936


def test_required_flops_against_hand_arithmetic():
    cfg = harness.load_json(REAL_CFG)
    parts = flops_laguna.train_flops_by_part(cfg, 8192)
    total = sum(parts.values())
    assert total == flops_laguna.train_flops_per_token(cfg, 8192)
    # forward 13.14 TFLOP a step of 16,384 tokens, x 3
    assert total * 16384 == pytest.approx(39.41e12, rel=1e-3)
    share = {k: round(100 * v / total, 1) for k, v in parts.items()}
    assert share == {"projections": 42.9, "gate": 0.1, "dense_ffn": 12.6,
                     "shared_expert": 3.1, "router": 0.5, "head": 6.4,
                     "routed_experts": 3.1, "attention_full": 25.1,
                     "attention_window": 6.1}
    assert parts["projections"] + parts["gate"] == 6 * (
        2 * 29_458_432 + 3 * 37_879_808)
    # a window layer's query sees 512 keys, the first 511 queries fewer
    assert flops_laguna.attended_pairs(cfg, 1, 8192) \
        == 512 * 513 // 2 + (8192 - 512) * 512
    assert flops_laguna.attended_pairs(cfg, 0, 8192) == 8192 * 8193 // 2
    assert parts["attention_window"] == 3 * 3 * 4 * 64 * 128 * (
        512 * 513 // 2 + 7680 * 512) / 8192
    # 4 sparse layers x top-8 x 32 / 256 held pairs a token, as expected;
    # the routed part follows what the steps counted
    assert flops_laguna.expected_pairs_held_per_token(cfg) == 4.0
    assert parts["routed_experts"] == 6 * 3 * 2048 * 512 * 4.0
    more = flops_laguna.train_flops_by_part(cfg, 8192, 5.0)
    assert more["routed_experts"] == 1.25 * parts["routed_experts"]
    assert {k: v for k, v in more.items() if k != "routed_experts"} \
        == {k: v for k, v in parts.items() if k != "routed_experts"}


def test_required_work_of_the_kernels():
    cfg = harness.load_json(REAL_CFG)
    peak = harness.peak_for("TPU v5 lite")
    full = flops_laguna.banded_flash(cfg, 2, 8192, "full")
    window = flops_laguna.banded_flash(cfg, 2, 8192, "window")
    both = flops_laguna.banded_flash(cfg, 2, 8192)
    assert both == {k: full[k] + window[k] for k in both}
    # forward once + backward at 2.5 x: 3.5 x 4 x B x Hq x D x pairs a layer
    assert full["flops"] == 2 * 3.5 * 4 * 2 * 48 * 128 * (8192 * 8193 / 2)
    assert window["flops"] == 3 * 3.5 * 4 * 2 * 64 * 128 * (
        512 * 513 / 2 + 7680 * 512)
    # rows in and out do not shrink with the band: q, k, v, o + lse forward;
    # q, k, v, o, do, dq, dk, dv + lse, delta backward
    assert window["bytes"] == 3 * (2 * 2 * 8192 * 128 * (6 * 64 + 6 * 8)
                                   + 3 * 4 * 2 * 8192 * 64)
    assert flops.least_time(full, peak)[1] == "compute"
    assert flops.least_time(window, peak)[1] == "compute"
    # 16,384 held pairs a layer in 4 layers of one step
    work = flops_laguna.held_gmm(cfg, 4 * 16384, 4)
    e, f = 2048, 512
    assert work["flops"] == 3 * 6 * e * f * 4 * 16384
    assert work["bytes"] == (4 * 32 * 3 * e * f * (2 + 2 + 4)
                             + 3 * 2 * 3 * (e + f) * 4 * 16384)
    assert flops.least_time(work, peak)[1] == "memory"
    steps = flops_laguna.window_work("banded_flash", cfg, {
        "global_batch": 2, "seq_len": 8192}, 3, [])
    assert steps["flops"] == 3 * both["flops"]
    assert flops_laguna.window_work("held_gmm", cfg, {
        "global_batch": 2, "seq_len": 8192}, 1, [(65536, 524288, 700)]) == work
    assert flops_laguna.window_work("held_gmm", cfg, {
        "global_batch": 2, "seq_len": 8192}, 1, []) is None
    with pytest.raises(KeyError):
        flops_laguna.window_work("paged_attend", cfg, {
            "global_batch": 2, "seq_len": 8192}, 1, [])


# ---- the cell's listings -----------------------------------------------------
def test_the_cell_is_listed_where_its_metrics_mean_the_same_and_nowhere_else():
    metrics = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in ("train.tokens_per_s_per_chip", "train.data_wait_ms",
                 "train.step_ms_p50", "train.step_ms_p95",
                 "train.attn_device_ms", "train.mlp_device_ms",
                 "train.loss_head_device_ms", "train.optimizer_device_ms",
                 "train.recompute_device_ms", "train.unscoped_device_ms",
                 "device.idle_pct.train", "device.peak_hbm_gb.train"):
        assert REAL in metrics[name]["workloads"], name
    for name in NEW:
        assert metrics[name]["workloads"] == [REAL], name
        assert metrics[name]["moves"] == "train.tokens_per_s_per_chip"
    # flops.py counts one head count, every layer full causal and dense; one
    # chip has no collective
    for name in ("train.mfu_required_pct", "flash_attention_roofline",
                 "train.collective_exposed_pct"):
        assert REAL not in metrics[name]["workloads"], name
    listed = {n for n, m in metrics.items() if REAL in m.get("workloads", ())}
    assert not any(n.startswith("serve.") or n.endswith(".serve")
                   for n in listed)
    cell = harness.find_cell(BENCH, REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-xs.2-ep8-l5", "train.seq8192", 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["file"] == "benchmarks/configs/laguna-xs.2-ep8-l5.json"
    # the driver refuses a `why` of more than 200 characters, a configuration's
    # as a cell's (test_benchmark_harness.py holds only the cells' to it)
    for why in (entry["why"], cell["why"]):
        assert 1 <= len(why) <= 200 and why.isascii() and why.isprintable()


# ---- the two new readers -----------------------------------------------------
def test_readers_return_nothing_where_there_is_nothing_to_read():
    ctx = {"trace": None, "trace_dir": None, "config": {"family": "laguna"},
           "job": {}, "peak": None, "counters": {}}
    for work in ("banded_flash", "held_gmm"):
        assert family_work.read(ctx, {"components": ["gmm"], "work": work,
                                      "step_span": "step"}) is None
    # a llama cell: no family key, no flops_llama module
    assert family_work.family_flops({"config": {}}) is None
    assert family_work.family_flops({"config": {"family": "llama"}}) is None


def test_readers_read_the_rooflines_and_the_held_mfu(monkeypatch):
    ms = 1_000_000
    paths = {"%f": "jit(train_step)/layers/attn/attn_full/flash_fwd/pallas_call:",
             "%q": "jit(train_step)/transpose(jvp(layers))/attn/attn_window/flash_dq/pallas_call:",
             "%g": "jit(train_step)/layers/experts/gmm/pallas_call:",
             "%t": "jit(train_step)/transpose(jvp(layers))/experts/tgmm/pallas_call:",
             "%x": "jit(train_step)/layers/mlp/dot_general:"}
    ops = [("%f", 1 * ms, 201 * ms), ("%q", 201 * ms, 401 * ms),
           ("%g", 401 * ms, 431 * ms), ("%t", 431 * ms, 441 * ms),
           ("%x", 441 * ms, 900 * ms)]
    trace = {"lo_ns": 0, "hi_ns": 2000 * ms, "device_ops": {0: ops},
             "device_modules": {0: []},
             "host_spans": [("step", 0, 950 * ms), ("step", 1000 * ms, 1900 * ms)]}
    monkeypatch.setattr(family_work._xplane, "traced", lambda ctx: (trace, "x"))
    monkeypatch.setattr(family_work.scope_time, "op_paths_of", lambda p: paths)
    cfg = harness.load_json(REAL_CFG)
    peak = harness.peak_for("TPU v5 lite")
    rows = [(0.9, 65000, 524288, 700), (1.9, 66000, 524288, 650),
            (9.0, 1, 1, 1)]            # the third: after the traced window
    ctx = {"config": cfg, "peak": peak, "trace_window": (0.0, 2.0),
           "traffic": {"global_batch": 2, "seq_len": 8192},
           "counters": {"routing_steps": rows}}
    flash = flops_laguna.banded_flash(cfg, 2, 8192)
    assert family_work.read(ctx, {
        "components": ["flash_fwd", "flash_dq", "flash_dkv"],
        "work": "banded_flash", "step_span": "step"}) == pytest.approx(
        100 * (2 * flash["flops"] / 197e12) / 0.4)
    gmm = flops_laguna.held_gmm(cfg, 131000, 8)
    assert family_work.read(ctx, {
        "components": ["gmm", "tgmm"], "work": "held_gmm",
        "step_span": "step"}) == pytest.approx(
        100 * (gmm["bytes"] / 819e9) / 0.04)
    # the parent's program has no such kernels: nothing to read
    assert family_work.read(ctx, {"components": ["qmm"], "work": "held_gmm",
                                  "step_span": "step"}) is None
    assert family_work.read(dict(ctx, config={}), {
        "components": ["gmm"], "work": "held_gmm", "step_span": "step"}) is None

    spans = harness.Spans()
    spans.items = {"step": [(0.0, 0.7), (0.7, 1.4), (1.4, 2.1)],
                   "data": [(0.0, 0.0), (0.7, 0.7)]}
    run = {"config": cfg, "peak": peak, "window": (0.0, 3.0), "spans": spans,
           "devices": [0], "traffic": {"seq_len": 8192},
           "counters": {"steps": 3, "tokens_per_step": 16384,
                        "pairs_held": 3 * 16384 * 5}}
    per_token = flops_laguna.train_flops_per_token(cfg, 8192, 5.0)
    assert mfu_family.read(run, {}) == pytest.approx(
        100 * (16384 / 0.7) * per_token / 197e12)
    assert 25 < mfu_family.read(run, {}) < 30
    assert mfu_family.read(dict(run, peak=None), {}) is None
    assert mfu_family.read(dict(run, config={}), {}) is None


def test_routing_counters():
    cfg = {"mlp_layer_types": ["dense", "sparse", "sparse"], "num_experts": 4}
    rows = [(1.0, 80, 640, 20), (2.0, 120, 640, 30)]
    got = train_family.routing_counters(cfg, rows)
    assert got["pairs_held"] == 200 and got["pairs_routed"] == 1280
    assert got["fullest_expert_rows"] == 30
    assert got["expert_pairs_held_pct"] == pytest.approx(15.625)
    # a step's fullest group over its mean group: 20 / (80 / 8), 30 / (120 / 8)
    assert got["expert_rows_fullest_over_mean"] == pytest.approx(2.0)
    assert train_family.routing_counters(cfg, []) == {}


# ---- the runner end to end on the debug cell ----------------------------------
def make_root(tmp: Path) -> Path:
    bench = tmp / "benchmarks"
    bench.mkdir(parents=True)
    shutil.copytree(ROOT / "benchmarks" / "metrics", bench / "metrics")
    shutil.copy(ROOT / "benchmarks" / "peaks.json", bench / "peaks.json")
    for d in ("configs", "traffic", "workloads"):
        shutil.copytree(DEBUG / d, bench / d)
    doc = json.loads(json.dumps(BENCH))
    doc["configs"] = [{"name": "debug-laguna", "source": "debug", "reduced": [],
                       "why": "debug", "file": "benchmarks/configs/debug-laguna.json"}]
    doc["workloads"] = [{"name": CELL, "config": "debug-laguna",
                         "traffic": "train.debug", "chips": 1, "why": "debug"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if REAL in m["workloads"] else []
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp


@pytest.fixture(scope="module")
def debug_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("laguna_root"))


def run(root, **kw):
    return harness.run_cell(
        root=root, workload=CELL, seed=kw.pop("seed", 2**31 + 29),
        seconds=kw.pop("seconds", 1.0), trace=kw.pop("trace", False),
        t_process_start=time.monotonic(), bench_dir=root / "benchmarks",
        require_platform=None)


@pytest.fixture(scope="module")
def sound(debug_root):
    return run(debug_root, trace=True)


def test_runner_end_to_end_on_the_debug_cell(sound):
    """A traced run of the debug cell (4 x 32 tokens a step, every kind of
    layer, experts 2-3 of 8 held): correct against the family's reference on
    losses, gradient norms by leaf (every held expert a leaf) and the
    parameters' change; the routing counts are read with the loss and
    reported, the device metrics left out (no device plane off a TPU)."""
    assert sound["correct"] is True and sound["failed"] == 0
    checks = {r["check"] for r in sound["compared"]}
    assert {"loss_step0_rel_gap", "loss_step1_rel_gap",
            "first_grad_norm_worst_leaf_gap", "first_grad_global_norm_gap",
            "param_change_norm_worst_leaf_gap"} <= checks
    names = set(sound["metrics"])
    assert {"train.step_ms_p50", "train.expert_pairs_held_pct",
            "train.expert_rows_fullest_over_mean"} <= names
    assert not any(n.endswith("_roofline") or n.endswith("device_ms")
                   or "mfu" in n for n in names)
    counters = sound["ctx"]["counters"]
    steps = counters["routing_steps"]
    assert len(steps) == counters["steps"] >= 2
    # 3 sparse layers x 128 tokens x top-2 routed a step; 2 of 8 held
    assert all(routed == 768 and 0 < held < 768 and 0 < fullest <= 128
               for _, held, routed, fullest in steps)
    assert 10 < sound["metrics"]["train.expert_pairs_held_pct"]["value"] < 45
    grads = sound["ctx"]["checked"]["want"]["grad_norms"]
    # a leaf a held expert (3 sparse layers x 2), a leaf a layer otherwise
    assert grads["layers/gate"].shape == (6,) and grads["layers/wg"].shape == (4,)
    assert grads["layers/router"].shape == (3,)


def halve_the_gate(monkeypatch):
    real = _laguna.to_program

    def edited(w):
        tree = real(w)
        for layer in tree["layers"]:
            layer["attn"]["wg"] = jnp.zeros_like(layer["attn"]["wg"])
        return tree
    monkeypatch.setattr(_laguna, "to_program", edited)


def hold_the_wrong_experts(monkeypatch):
    """The program told it holds experts 0-1 while its leaves (and the
    reference) are experts 2-3."""
    real = _laguna.bundle_for

    def edited(cfg, name):
        return real(dict(cfg, experts_held_first=0), name)
    monkeypatch.setattr(_laguna, "bundle_for", edited)


FAULTS = {"gate_at_a_half": halve_the_gate,
          "wrong_experts_held": hold_the_wrong_experts}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(fault, debug_root, monkeypatch):
    FAULTS[fault](monkeypatch)
    result = run(debug_root)
    assert result["correct"] is False
    assert any(not row["ok"] for row in result["compared"])


def test_runner_control_reads_the_reference_in_a_lower_precision(sound):
    runner = harness.load_module("runners", sound["ctx"]["job"]["runner"])
    rows = runner.control(sound["ctx"], "bf16")
    assert set(rows) <= {r["check"] for r in sound["compared"]}
    values = {r["check"]: r["value"] for r in sound["compared"]}
    limits = sound["ctx"]["job"]["check"]["limits"]
    # bfloat16 operands move every compared number past the debug cell's limit
    for check, limit in (("first_grad_norm_worst_leaf_gap",
                          "grad_norm_worst_leaf_gap"),
                         ("param_change_norm_worst_leaf_gap",
                          "param_change_norm_worst_leaf_gap")):
        assert rows[check] > limits[limit] >= values[check], check
