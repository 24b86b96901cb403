"""The ``mimo_v2`` family in the benchmark, on the CPU: the plain reference
(``benchmarks/reference/mimo_v2.py``) against ``models/mimo_v2.py`` with the
faults it has to see, the held share of the experts, the weights' contract,
the cell's data files, the new work function and readers by hand, and the
runner end to end on a debug-width cell (tests/benchmarks/debug/) with a
fault ``correct`` has to catch and the lower-precision control."""
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

from benchmarks import flops, flops_mimo_v2, harness  # noqa: E402
from benchmarks import weights_mimo_v2 as weights  # noqa: E402
from benchmarks.readers import mixed_attend, window_release  # noqa: E402
from benchmarks.reference import mimo_v2 as ref  # noqa: E402
from benchmarks.runners import _mimo_v2  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
DEBUG = Path(__file__).resolve().parent / "debug"
CELL = "debug-mimo-v2.serve.debug-mixed"
REAL = "mimo-v2.5-ep16-l7.serve.mixed64-ctx32k"
REAL_CFG = ROOT / "benchmarks" / "configs" / "mimo-v2.5-ep16-l7.json"
# float32 program against float32 reference: only summation order differs
# (read 8e-6 on logits of magnitude 5)
LOGIT_TOL = 3e-5


def debug_cfg(**over):
    cfg = json.loads((DEBUG / "configs" / "debug-mimo-v2.json").read_text())
    return dict(cfg, **over)


def layer_fn_of(cfg, key):
    return lambda l: weights.layer_weights(cfg, key, l, jnp.float32)


# ---- the reference against the program ---------------------------------------
@pytest.fixture(scope="module")
def forward():
    from distributed_training_guide_tpu.models import mimo_v2

    cfg, key = debug_cfg(), weights.seed_key(2**31 + 7)
    w = weights.stacked_weights(cfg, key, jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 512, 70).astype(np.int32)
    bundle = _mimo_v2.bundle_for(cfg, "debug")
    got = mimo_v2.apply(bundle.config, _mimo_v2.to_program(w),
                        jnp.asarray(tokens[None]))[0]
    return cfg, key, w["top"], tokens, got


@pytest.mark.parametrize("fault", ref.FAULTS + ("int8",),
                         ids=[f or "sound" for f in ref.FAULTS] + ["int8"])
def test_reference_matches_program_logits_and_sees_each_fault(forward, fault):
    """Every kind of layer in one model (dense + full, experts + window twice,
    experts + full; the share holds experts 2-5 of 8), 70 tokens: six windows
    of 12. The sound reference is the program's forward; with the sink left
    out, the window one position short or wide, the value scale left out, the
    window layers' rope base on a full layer, the choice bias left out, rope
    on every column, or int8 operands, it is not."""
    cfg, key, top, tokens, got = forward
    more = {"mode": "int8"} if fault == "int8" else {"fault": fault}
    want = ref.forward_logits(cfg, layer_fn_of(cfg, key), top, tokens, **more)
    diff = float(jnp.max(jnp.abs(got - want)))
    if fault is None:
        assert diff < LOGIT_TOL
    else:
        assert diff > 1000 * LOGIT_TOL


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """One routed layer's FFN on the same rows: the parts the two shares give
    (four experts each of eight; pairs of absent experts dropped, the partial
    sum goes on) add up to the uncut reference layer, and each share's part is
    the reference's for that share. The program's ``_ffn`` against the
    reference's ``route`` and ``swiglu``."""
    from distributed_training_guide_tpu.models import mimo_v2

    whole = debug_cfg(n_routed_experts=8, experts_held_first=0)
    key = weights.seed_key(11)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 48, 64)),
                    jnp.float32)

    def program_ffn(cfg):
        config = _mimo_v2.bundle_for(cfg, "debug").config
        layers = _mimo_v2.to_program(
            weights.stacked_weights(cfg, key, jnp.float32))["layers"]
        y, _ = mimo_v2._ffn(config, x, layers, 1, False, 0, {})
        return (y - x)[0]

    def reference_ffn(cfg):
        w = {k: v.astype(jnp.float32) for k, v in
             weights.layer_weights(cfg, key, 1, jnp.float32).items()}
        u = ref.rmsnorm(x[0], w["ffn_norm"], cfg["layernorm_epsilon"])
        weight = ref.route(cfg, w, u)
        return sum(weight[:, j: j + 1] * ref.swiglu(
            u, w["gate"][j], w["up"][j], w["down"][j])
            for j in range(cfg["n_routed_experts"]))

    want = reference_ffn(whole)
    assert float(jnp.max(jnp.abs(program_ffn(whole) - want))) < 1e-5
    total = 0.0
    for first in (0, 4):
        cfg = debug_cfg(n_routed_experts=4, experts_held_first=first)
        part = program_ffn(cfg)
        assert float(jnp.max(jnp.abs(part - reference_ffn(cfg)))) < 1e-5
        total = total + part
    assert float(jnp.max(jnp.abs(total - want))) < 1e-5
    assert float(jnp.max(jnp.abs(want))) > 1e-2


def test_stacked_weights_are_the_layers_own_draws_and_nothing_more():
    cfg, key = debug_cfg(), weights.seed_key(3)
    stacked = weights.stacked_weights(cfg, key, jnp.float32)
    kinds = weights.layers_of(cfg)
    assert kinds == {"norms": [0, 1, 2, 3], "attn_full": [0, 3],
                     "attn_window": [1, 2], "dense": [0], "moe": [1, 2, 3]}
    assert set(stacked["attn_window"]) == {"window_wq", "window_wk",
                                           "window_wv", "window_wo",
                                           "window_sink"}
    assert "full_sink" not in stacked["attn_full"]      # no sink configured
    for kind, layers in kinds.items():
        for row, l in enumerate(layers):
            own = weights.layer_weights(cfg, key, l, jnp.float32)
            for name, leaf in stacked[kind].items():
                assert np.array_equal(leaf[row], own[name]), (kind, name)
    # held experts 2-5 are the uncut model's experts 2-5
    uncut = weights.layer_weights(
        debug_cfg(n_routed_experts=8, experts_held_first=0), key, 2)
    assert np.array_equal(stacked["moe"]["up"][1], uncut["up"][2:6])
    # drawn large enough to matter: sinks of order 1, biases beside scores
    assert 0.5 < float(jnp.std(stacked["attn_window"]["window_sink"])) < 1.5
    assert 0.01 < float(jnp.std(stacked["moe"]["router_bias"])) < 0.03
    assert weights.num_params(cfg) == _mimo_v2.bundle_for(
        cfg, "debug").config.num_params()


# ---- the data files ----------------------------------------------------------
def test_the_cell_loads_with_the_published_widths_and_its_cut():
    loaded = harness.load_cell(BENCH, REAL)
    cfg, job, mix = loaded["config_data"], loaded["job"], loaded["traffic_data"]
    assert loaded["chips"] == 1 and job["runner"] == "serve"
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if json.loads(line)["name"] == "MiMo-V2.5") if Path(
        "/opt/skills/guides/model-configs/architectures.jsonl").exists() else None
    if row is not None:     # every number of the catalog's config, but the cut
        assert cfg["source"] == row["source_url"]
        for name, value in row["config"].items():
            if name not in cfg["reduced"]:
                assert cfg[name] == value, name
            else:
                assert cfg["published"][name] == value, name
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "hybrid_layer_pattern",
                              "moe_layer_freq"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["router_experts"]) == (7, 16, 19072, 256)
    # the leading dense layer and one whole period: two full, five window
    assert cfg["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1] \
        == cfg["published"]["hybrid_layer_pattern"][:7]
    assert cfg["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert (cfg["head_dim"], cfg["v_head_dim"], cfg["num_key_value_heads"],
            cfg["swa_num_key_value_heads"], cfg["sliding_window"]) == (
        192, 128, 4, 8, 128)
    assert {"rope", "sink", "sinks_drawn", "window_edge", "router",
            "router_bias", "attention_chunk_size", "left_out"} <= set(
        cfg["assumed"])
    assert "16 chips" in cfg["deployment"]
    assert weights.num_params(cfg) == 3_429_955_392
    assert f"{weights.num_params(cfg):,}" in cfg["deployment"]
    assert mix["clients"] == mix["distinct_requests"] == 64 \
        and mix["prompt_len"] == {"median": 6144, "sigma": 1.2, "min": 512,
                                  "max": 32768} \
        and mix["output_len"] == {"fixed": 4096} and mix["loop"] == "closed"
    from benchmarks.traffic import generate
    prompts = generate.lengths(mix["prompt_len"], 64)
    assert (sum(prompts), min(prompts), max(prompts)) == (637417, 512, 32768)
    eng = job["engine"]
    assert eng["n_slots"] == 64 and eng["page_size"] == 128 \
        and eng["prefill_chunk"] == 2048 and eng["attend_impl"] == "auto" \
        and eng["prefix_cache"] is False
    # every request whole, a page of admission headroom a slot, the trash page
    assert eng["n_pages"] == sum(-(-(p + 4096) // 128) for p in prompts) + 65
    assert eng["max_len"] == 32768 + 4096
    assert job["ramp_steps"] >= sum(-(-p // 2048) for p in prompts)


def test_the_cell_is_listed_where_its_readers_mean_the_same():
    def cells(name):
        return next(m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                    if m["name"] == name)["workloads"]
    for name in ("serve.out_tokens_per_s", "serve.itl_p95_ms", "gmm_roofline",
                 "serve.experts_device_ms", "serve.router_device_ms",
                 "serve.expert_pairs_held_pct", "serve.attend_device_ms",
                 "serve.kv_write_device_ms", "device.peak_hbm_gb.serve"):
        assert REAL in cells(name), name
    for name in ("serve.attend_full_device_ms", "serve.attend_window_device_ms",
                 "mixed_attend_roofline",
                 "serve.window_pages_released_per_step"):
        assert cells(name) == [REAL], name
    # flops.paged_attend multiplies by num_hidden_layers and one kv-head
    # count, the touched share divides by every layer (6 of 7 route), the
    # walk has no innermost `layers` scope
    for name in ("paged_attend_roofline", "serve.experts_touched_pct",
                 "hybrid_attend_roofline", "latent_attend_roofline",
                 "serve.layers_device_ms"):
        assert REAL not in cells(name), name
    assert [c["name"] for c in BENCH["workloads"]][-1] == REAL
    assert len(BENCH["workloads"]) == 7 == len(BENCH["configs"]) + 1
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1


def test_the_two_classes_cost_what_the_configuration_file_says():
    from distributed_training_guide_tpu.serve import kv_pages

    cfg = harness.load_json(REAL_CFG)
    job = harness.load_cell(BENCH, REAL)["job"]["engine"]
    config = _mimo_v2.bundle_for(cfg, "real").config
    row = cfg["pool_row"]
    assert flops_mimo_v2.token_bytes(cfg, "full") == 2560 \
        == row["published_bytes_per_token_full_layer"]
    assert flops_mimo_v2.token_bytes(cfg, "window") == 5120 \
        == row["published_bytes_per_token_window_layer"]
    assert kv_pages.kv_page_bytes(config, page_size=1) \
        == 2 * row["resident_bytes_per_token_full_layer"]
    assert kv_pages.kv_page_bytes(config, page_size=1, window_class=True) \
        == 5 * row["resident_bytes_per_token_window_layer"]
    n_window = kv_pages.window_pages_bound(128, 128, job["n_slots"],
                                           job["prefill_chunk"])
    assert n_window == 1 + 64 * 2 + 18
    shapes = jax.eval_shape(lambda: kv_pages.init_pages(
        config, job["n_pages"], 128, n_window_pages=n_window))
    assert shapes["k"].shape == (4, 7119, 128, 4, 128)
    assert shapes["v_win"].shape == (5, 147, 128, 8, 128)
    full = kv_pages.kv_page_bytes(config, page_size=128, n_pages=7119)
    window = kv_pages.kv_page_bytes(config, page_size=128, n_pages=147,
                                    window_class=True)
    assert sum(x.size * 2 for x in jax.tree.leaves(shapes)) == full + window
    # 5.6 GB + 0.58 GB, where ONE layout over seven layers would hold the
    # cell's 899,561 tokens in 23 GB even at the published bytes
    assert 5.5e9 < full < 5.7e9 and 0.55e9 < window < 0.6e9
    assert (637417 + 64 * 4096) * (2 * 2560 + 5 * 5120) > 23e9
    assert config.num_params() == weights.num_params(cfg)


def test_required_work_of_the_mixed_attend():
    cfg = harness.load_json(REAL_CFG)
    peak = harness.peak_for("TPU v5 lite")
    work = flops_mimo_v2.mixed_attend(cfg, 650_000, 64)
    # full layers read every live position, window layers 128 a slot
    assert work["bytes"] == (2 * 2560 * 650_000 + 5 * 5120 * 128 * 64
                             + 2 * 7 * 64 * 64 * 320)
    assert work["flops"] == 2.0 * 64 * 320 * (2 * 650_000 + 5 * 128 * 64)
    assert flops.least_time(work, peak)[1] == "memory"
    assert 4.0e-3 < flops.least_time(work, peak)[0] < 4.4e-3
    # contexts shorter than the window: capped by what there is
    short = flops_mimo_v2.mixed_attend(cfg, 64 * 100, 64)
    assert short["bytes"] == ((2 * 2560 + 5 * 5120) * 6400
                              + 2 * 7 * 64 * 64 * 320)


# ---- the two new readers -----------------------------------------------------
def test_readers_return_nothing_where_there_is_nothing_to_read():
    ctx = {"trace": None, "trace_dir": None, "config": {}, "job": {}}
    assert mixed_attend.read(ctx, {"component": "paged_attend",
                                   "program": "serve_decode"}) is None
    assert window_release.read(ctx, {"span": "serve.release",
                                     "stat": "pages"}) is None


def test_readers_read_the_mixed_roofline_and_the_released_pages(monkeypatch):
    ms = 1_000_000
    paths = {"%f": "jit(serve_decode)/layers/attn/attend/attend_full/paged_attend/pallas_call:",
             "%w": "jit(serve_decode)/layers/attn/attend/attend_window/paged_attend/pallas_call:",
             "%g": "jit(serve_decode)/layers/experts/gmm/pallas_call:"}
    ops = [("%f", 1 * ms, 3 * ms), ("%w", 3 * ms, 4 * ms), ("%g", 4 * ms, 8 * ms),
           ("%f", 11 * ms, 13 * ms), ("%w", 13 * ms, 14 * ms)]
    modules = [("jit_serve_decode(7)", 0, 9 * ms),
               ("jit_serve_decode(7)", 10 * ms, 15 * ms)]
    trace = {"lo_ns": 0, "hi_ns": 50 * ms, "device_ops": {0: ops},
             "device_modules": {0: modules}, "host_spans": []}
    spans = [("serve.step", 0, 9 * ms, "t", {}),
             ("serve.release", 8 * ms, 8 * ms + 10, "t", {"pages": 1}),
             ("serve.step", 10 * ms, 15 * ms, "t", {}),
             ("serve.release", 14 * ms, 14 * ms + 10, "t", {"pages": 2}),
             ("serve.step", 20 * ms, 30 * ms, "t", {}),        # a chunk step:
             ("serve.prefill", 21 * ms, 25 * ms, "t", {}),     # not counted
             ("serve.release", 26 * ms, 26 * ms + 10, "t", {"pages": 16})]
    for mod in (mixed_attend, window_release):
        monkeypatch.setattr(mod._xplane, "traced", lambda ctx: (trace, "x"))
    monkeypatch.setattr(window_release._xplane, "program_spans",
                        lambda path: spans)
    monkeypatch.setattr(mixed_attend.scope_time, "op_paths_of", lambda p: paths)
    cfg = harness.load_json(REAL_CFG)
    ctx = {"config": cfg, "peak": harness.peak_for("TPU v5 lite"),
           "trace_window": (0.0, 1.0),
           "counters": {"kv_bytes": 2, "decode_context": [
               (0.5, 650_000, 64), (0.7, 650_064, 64), (2.0, 1, 1)]}}
    work = flops_mimo_v2.mixed_attend(cfg, 1_300_064, 128)
    assert mixed_attend.read(ctx, {"component": "paged_attend",
                                   "program": "serve_decode"}) == pytest.approx(
        100 * (work["bytes"] / 819e9) / 6e-3)
    assert window_release.read(ctx, {"span": "serve.release",
                                     "stat": "pages"}) == pytest.approx(1.5)
    # a one-class family's trace has no such span
    monkeypatch.setattr(window_release._xplane, "program_spans",
                        lambda path: [s for s in spans if s[0] != "serve.release"])
    assert window_release.read(ctx, {"span": "serve.release",
                                     "stat": "pages"}) is None
    assert mixed_attend.read(dict(ctx, config={}), {
        "component": "paged_attend", "program": "serve_decode"}) is None


# ---- the runner end to end on the debug cell ----------------------------------
def make_root(tmp: Path) -> Path:
    bench = tmp / "benchmarks"
    bench.mkdir(parents=True)
    shutil.copytree(ROOT / "benchmarks" / "metrics", bench / "metrics")
    shutil.copy(ROOT / "benchmarks" / "peaks.json", bench / "peaks.json")
    for d in ("configs", "traffic", "workloads"):
        shutil.copytree(DEBUG / d, bench / d)
    doc = json.loads(json.dumps(BENCH))
    doc["configs"] = [{"name": "debug-mimo-v2", "source": "debug", "reduced": [],
                       "why": "debug", "file": "benchmarks/configs/debug-mimo-v2.json"}]
    doc["workloads"] = [{"name": CELL, "config": "debug-mimo-v2",
                         "traffic": "serve.debug-mixed", "chips": 1, "why": "debug"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if REAL in m["workloads"] else []
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp


@pytest.fixture(scope="module")
def debug_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("mimo_root"))


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
    """A traced run of the debug cell (prompts of 10 to 60 tokens in chunks of
    16, replies of 24, window 12, pages of 8): correct, nothing refused or
    preempted, replies end and slots are reused inside the window, the
    counters are reported and the device metrics left out (no device plane
    off a TPU)."""
    assert sound["correct"] is True and sound["failed"] == 0
    names = set(sound["metrics"])
    assert {"serve.step_ms_p50", "serve.batch_occupancy_pct",
            "serve.preemptions", "serve.expert_pairs_held_pct"} <= names
    assert not any(n.endswith("_roofline") or n.endswith("device_ms")
                   for n in names)
    assert sound["metrics"]["serve.preemptions"]["value"] == 0
    # 8 choices' worth: top-2 of 8 experts, 4 held: about half the pairs
    assert 25 < sound["metrics"]["serve.expert_pairs_held_pct"]["value"] < 75
    steps = sound["ctx"]["counters"]["routing_steps"]
    assert steps and all(0 < touched <= 3 * 4 and 0 < held <= 3 * 8
                         for _, held, touched in steps)
    assert sound["attempted"] >= 4


def drop_the_sinks(monkeypatch):
    real = _mimo_v2.to_program

    def edited(w):
        tree = real(w)
        for layer in tree["layers"]["attn_window"]:
            layer["sink"] = jnp.full_like(layer["sink"], -1e9)
        return tree
    monkeypatch.setattr(_mimo_v2, "to_program", edited)


def widen_the_band_by_a_page(monkeypatch):
    """The attend told a window 8 positions (one page) wider than the model's:
    a window layer then reads positions whose window-class page the scheduler
    has handed back (trash, or another sequence's keys by now)."""
    from distributed_training_guide_tpu.serve import kv_pages

    real = kv_pages.paged_attend

    def wide(*args, window=None, **kw):
        return real(*args, window=None if window is None else window + 8, **kw)
    monkeypatch.setattr(kv_pages, "paged_attend", wide)


FAULTS = {"no_sink": drop_the_sinks, "window_a_page_wide": widen_the_band_by_a_page}


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
