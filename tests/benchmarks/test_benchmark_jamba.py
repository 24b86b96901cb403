"""The ``jamba`` family in the benchmark, on the CPU: the plain reference
(``benchmarks/reference/jamba.py``, a scan over tokens) against
``models/jamba.py`` with the faults it has to see, the weights' contract,
the cell's data files, the work functions and the new reader by hand, and
the runner end to end on a debug-width cell (tests/benchmarks/debug/) with
faults ``correct`` has to catch and the lower-precision control."""
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

from benchmarks import flops, flops_jamba, harness  # noqa: E402
from benchmarks import weights_jamba as weights  # noqa: E402
from benchmarks.readers import ssm_work  # noqa: E402
from benchmarks.reference import jamba as ref  # noqa: E402
from benchmarks.runners import _jamba  # noqa: E402
from benchmarks.traffic import generate  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
DEBUG = Path(__file__).resolve().parent / "debug"
CELL = "debug-jamba.serve.debug-lognormal"
REAL = "jamba2-3b.serve.chat256"
REAL_CFG = ROOT / "benchmarks" / "configs" / "jamba2-3b.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW = ("serve.ssm_device_ms", "ssm_step_roofline", "ssm_chunk_roofline",
       "serve.chunk_fill_pct", "mqa_attend_roofline")
# float32 program against float32 reference: only summation order differs
# (read 3e-6 on logits of magnitude 2: two scans over tokens, a paged attend
# against a masked softmax)
LOGIT_TOL = 5e-5


def debug_cfg(**over):
    cfg = json.loads((DEBUG / "configs" / "debug-jamba.json").read_text())
    return dict(cfg, **over)


def layer_fn_of(cfg, key):
    return lambda l: weights.layer_weights(cfg, key, l, jnp.float32)


# ---- the reference against the program ---------------------------------------
@pytest.fixture(scope="module")
def forward():
    from distributed_training_guide_tpu.models import jamba

    cfg, key = debug_cfg(), weights.seed_key(2**31 + 7)
    w = weights.stacked_weights(cfg, key, jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 512, 70).astype(np.int32)
    bundle = _jamba.bundle_for(cfg, "debug")
    got = jamba.apply(bundle.config, _jamba.to_program(w),
                      jnp.asarray(tokens[None]))[0]
    return cfg, key, w["top"], tokens, got


@pytest.mark.parametrize("fault", ref.FAULTS + ("int8",),
                         ids=[f or "sound" for f in ref.FAULTS] + ["int8"])
def test_reference_matches_program_logits_and_sees_each_fault(forward, fault):
    """Three Mamba layers and one attention layer (the second), 70 tokens.
    The sound reference, a scan over TOKENS on ``[C, N]`` states, is the
    program's forward; with the taps reversed, the convolution's bias, the
    decay, the inner norms, the skip or the gate left out, the state rounded
    to bfloat16 a step, k read a column off, or int8 operands, it is not."""
    cfg, key, top, tokens, got = forward
    more = {"mode": "int8"} if fault == "int8" else {"fault": fault}
    want = ref.forward_logits(cfg, layer_fn_of(cfg, key), top, tokens, **more)
    diff = float(jnp.max(jnp.abs(got - want)))
    if fault is None:
        assert diff < LOGIT_TOL
    elif fault == "state_bf16":     # 8 bits of mantissa a step: far outside
        assert diff > 20 * LOGIT_TOL    # the tolerance, by less than a fault
    else:
        assert diff > 200 * LOGIT_TOL


def test_stacked_weights_are_the_layers_own_draws_and_nothing_more():
    cfg, key = debug_cfg(), weights.seed_key(3)
    stacked = weights.stacked_weights(cfg, key, jnp.float32)
    kinds = weights.layers_of(cfg)
    assert kinds == {"norms": [0, 1, 2, 3], "ffn": [0, 1, 2, 3],
                     "attn": [1], "mamba": [0, 2, 3]}
    assert set(stacked["mamba"]) == set(weights.KINDS["mamba"])
    for kind, layers in kinds.items():
        for row, l in enumerate(layers):
            own = weights.layer_weights(cfg, key, l, jnp.float32)
            for name, leaf in stacked[kind].items():
                assert np.array_equal(leaf[row], own[name]), (kind, name)
    # drawn large enough to matter, and Mamba's published start
    m = stacked["mamba"]
    assert 0.4 < float(jnp.std(m["mamba_conv"])) < 0.6
    assert np.allclose(jnp.exp(m["mamba_a_log"][0, 0]), np.arange(1, 9))
    assert float(jnp.min(m["mamba_d"])) == float(jnp.max(m["mamba_d"])) == 1.0
    step = jnp.log1p(jnp.exp(m["mamba_dt_bias"]))       # softplus
    assert 0.00099 < float(step.min()) and float(step.max()) < 0.1001
    r = cfg["mamba_dt_rank"]
    assert 0.9 * r ** -0.5 < float(jnp.max(jnp.abs(m["mamba_w_dt"]))) \
        <= r ** -0.5
    assert weights.num_params(cfg) == _jamba.bundle_for(
        cfg, "debug").config.num_params()
    # the program's tree: taps [L, C], A_log [N, C], nothing left over
    tree = _jamba.to_program(stacked)
    layer = tree["layers"]["mamba"][2]
    assert set(layer) == {"w_in", "taps", "conv_bias", "w_x", "dt_norm",
                          "b_norm", "c_norm", "w_dt", "dt_bias", "a_log",
                          "d", "w_out"}
    assert np.array_equal(layer["taps"], m["mamba_conv"][2].T)
    assert layer["a_log"].shape == (8, 128)


# ---- the data files ----------------------------------------------------------
def test_the_cell_loads_with_the_published_sizes_and_nothing_reduced():
    loaded = harness.load_cell(BENCH, REAL)
    cfg, job, mix = loaded["config_data"], loaded["job"], loaded["traffic_data"]
    assert loaded["chips"] == 1 and job["runner"] == "serve"
    if CATALOG.exists():    # every number of the catalog's config
        row = next(r for r in map(json.loads, CATALOG.open())
                   if r["name"] == "AI21-Jamba2-3B")
        assert cfg["source"] == row["source_url"]
        for name, value in row["config"].items():
            assert cfg[name] == value, name
    assert cfg["reduced"] == []
    assert set(cfg["assumed"]) >= {
        "layer_order", "head_dim", "inner_norms", "mamba", "positions",
        "activation", "state_dtype", "weights"}
    assert cfg["head_dim"] == cfg["hidden_size"] // cfg["num_attention_heads"]
    assert weights.num_params(cfg) == cfg["num_params"] == 3_029_337_472
    assert f"{weights.num_params(cfg):,}" in cfg["deployment"]
    config = _jamba.bundle_for(cfg, "real").config
    assert config.num_params() == 3_029_337_472
    table = config.layer_table()
    assert [l for l, (kind, _) in enumerate(table) if kind == "attn"] \
        == [7, 21] == weights.layers_of(cfg)["attn"]
    assert [ref.is_attention(cfg, l) for l in (6, 7, 8, 21)] == [
        False, True, False, True]
    assert mix["clients"] == mix["distinct_requests"] == 256 \
        and mix["prompt_len"] == {"median": 384, "sigma": 0.7, "min": 64,
                                  "max": 1024} \
        and mix["output_len"] == {"fixed": 512} and mix["loop"] == "closed" \
        and mix["first_output_len"] == "staggered" and mix["shared_prefix"] == 0
    lengths = generate.lengths(mix["prompt_len"], 256)
    assert round(sum(lengths) / 256) == 455 and max(lengths) == 1024
    assert sum(n <= 128 for n in lengths) == 15
    assert sum(n == 1024 for n in lengths) == 21
    eng = job["engine"]
    assert eng["n_slots"] == mix["clients"] and eng["page_size"] == 128 \
        and eng["prefill_chunk"] == 1024 == max(lengths) \
        and eng["attend_impl"] == "auto" and eng["prefix_cache"] is False
    # every request whole and the trash page: nothing is preempted
    assert eng["max_len"] == 1024 + 512
    assert eng["n_pages"] == eng["n_slots"] * (eng["max_len"] // 128) + 1
    # every client's first prompt (a chunk step each) and those of the 129
    # clients whose first replies (2, 4, ... tokens, client i's prefilled in
    # step i) ended meanwhile
    assert job["ramp_steps"] == 256 + 129


def test_the_state_class_costs_what_the_configuration_file_says():
    import jax

    from distributed_training_guide_tpu.serve import kv_pages

    cfg = harness.load_json(REAL_CFG)
    job = harness.load_cell(BENCH, REAL)["job"]["engine"]
    config = _jamba.bundle_for(cfg, "real").config
    said = cfg["state_per_sequence"]
    assert kv_pages.sequence_state_bytes(config) == said["bytes"] == 9_318_400 \
        == said["mamba_layers"] * (said["ssm_state_bytes_a_layer"]
                                   + said["conv_rows_bytes_a_layer"])
    assert flops_jamba.state_bytes(cfg) == said["ssm_state_bytes_a_layer"]
    assert kv_pages.kv_page_bytes(config, page_size=1) \
        == said["kv_bytes_per_token"] == 1024
    # float32 whatever the file says: the adapter runs no other state class,
    # and the roofline's bytes do not follow the file either
    narrow = dict(cfg, state_dtype="bfloat16")
    with pytest.raises(ValueError, match="state class is float32"):
        _jamba.bundle_for(narrow, "real")
    assert flops_jamba.ssm_step(narrow, 256) == flops_jamba.ssm_step(cfg, 256)
    blocks = job["n_slots"] + 1
    shapes = jax.eval_shape(lambda: kv_pages.init_pages(
        config, job["n_pages"], 128, n_state_blocks=blocks))
    assert shapes["k"].shape == (2, 3073, 128, 1, 128)
    assert shapes["seq_state"].shape == (26, 257, 16, 5120) \
        and shapes["seq_state"].dtype == jnp.float32
    assert shapes["seq_conv"].shape == (26, 257, 3, 5120) \
        and shapes["seq_conv"].dtype == jnp.bfloat16
    state = kv_pages.sequence_state_bytes(config, blocks)
    kv = kv_pages.kv_page_bytes(config, page_size=128, n_pages=3073)
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(shapes)) == state + kv
    assert 2.39e9 < state < 2.40e9 and 0.40e9 < kv < 0.41e9


def test_the_cell_is_listed_where_its_readers_mean_the_same():
    """Membership alone: no position in any list of ``BENCHMARK.json`` is
    pinned, nor any list's length, so a later PR appends its cell, its
    configuration or its metric, or this cell to a list that does not have
    it yet, without an edit here."""
    def cells(name):
        return next(m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                    if m["name"] == name)["workloads"]
    for name in ("serve.out_tokens_per_s", "serve.itl_p95_ms",
                 "serve.step_ms_p50", "serve.batch_occupancy_pct",
                 "serve.preemptions", "device.idle_pct.serve",
                 "device.peak_hbm_gb.serve",
                 "device.idle_unattributed_pct.serve",
                 "serve.attend_device_ms", "serve.kv_write_device_ms",
                 "serve.unscoped_device_ms", "serve.host_ms_per_step",
                 "serve.schedule_ms_per_step", "serve.chunk_device_ms",
                 *NEW):
        assert REAL in cells(name), name
    # off the lists whose work functions or scopes would read this family
    # wrongly: flops.paged_attend multiplies by num_hidden_layers,
    # flops_lfm2_moe and flops_solar_open2 count the attending layers from
    # keys this file does not have, nothing routes, and the KDA and conv
    # scopes are other families' mixers
    for name in ("paged_attend_roofline", "hybrid_attend_roofline",
                 "gqa_attend_roofline", "gmm_roofline",
                 "serve.experts_device_ms", "serve.router_device_ms",
                 "serve.kda_device_ms", "kda_step_roofline",
                 "serve.conv_device_ms"):
        assert REAL not in cells(name), name
    entry = next(c for c in BENCH["workloads"] if c["name"] == REAL)
    config = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert entry["chips"] == 1 and entry["traffic"] == "serve.chat256"
    for why in (entry["why"], config["why"]):
        assert 1 <= len(why) <= 200 and why.isascii() and why.isprintable()
    assert config["reduced"] == harness.load_json(REAL_CFG)["reduced"] == []
    layers = {m["name"]: m["layer"] for m in BENCH["per_layer"]}
    assert layers["ssm_step_roofline"] == layers["ssm_chunk_roofline"] \
        == layers["kda_step_roofline"]
    assert layers["serve.ssm_device_ms"] == layers["serve.kda_device_ms"]


def test_the_hybrid_attend_roofline_cannot_read_this_configuration():
    """``hybrid_attend_roofline``'s work function counts the attending layers
    from ``layer_types`` / ``num_dense_layers``, which this family's
    published configuration does not have (``attn_layer_period`` and
    ``attn_layer_offset`` name them): the cell stays off that list and
    ``mqa_attend_roofline`` reads the two ``paged_attend`` calls with the
    family's own work function."""
    from benchmarks import flops_lfm2_moe

    with pytest.raises(KeyError):
        flops_lfm2_moe.paged_attend(harness.load_json(REAL_CFG), 1000, 4)


# ---- required work, and the reader --------------------------------------------
def test_required_work_of_the_two_scans_and_the_attend():
    cfg = harness.load_json(REAL_CFG)
    peak = harness.peak_for("TPU v5 lite")
    assert flops_jamba.token_flops(cfg) == 9 * 5120 * 16 == 737_280
    assert flops_jamba.row_bytes(cfg) == (3 * 5120 + 32) * 4
    # 256 live slots, one step: each state in and out once in 26 layers
    step = flops_jamba.ssm_step(cfg, 256)
    assert step["bytes"] == 26 * 256 * (2 * 327_680 + 61_568)
    assert step["flops"] == 26 * 256 * 737_280
    least, bound = flops.least_time(step, peak)
    assert bound == "memory" and 5.8e-3 < least < 5.9e-3
    # one chunk of 455 real tokens: the rows' bytes are the floor (peaks.json
    # has no VPU rate)
    chunk = flops_jamba.ssm_chunk(cfg, 455, 1)
    assert chunk["flops"] == 26 * 455 * 737_280
    assert chunk["bytes"] == 26 * (455 * 61_568 + 2 * 327_680)
    least, bound = flops.least_time(chunk, peak)
    assert bound == "memory" and 0.9e-3 < least < 1.0e-3
    # the two attending layers: 512 B a token a layer, 20 query heads
    work = flops_jamba.mqa_attend(cfg, 256 * 710, 256, kv_bytes=2)
    assert work["bytes"] == 2 * 512 * 256 * 710 + 2 * 2 * 2 * 256 * 20 * 128
    assert work["flops"] == 4 * 2 * 20 * 128 * 256 * 710
    assert flops.paged_attend(cfg, 1000, 4)["flops"] \
        == 14 * flops_jamba.mqa_attend(cfg, 1000, 4)["flops"]


def test_the_reader_returns_nothing_where_there_is_nothing_to_read():
    ctx = {"trace": None, "trace_dir": None, "config": {}, "job": {}}
    for params in ({"component": "ssm_step", "work": "ssm_step",
                    "program": "serve_decode"},
                   {"component": "ssm_chunk", "work": "ssm_chunk",
                    "program": "serve_chunk_t1024"},
                   {"component": "paged_attend", "work": "mqa_attend",
                    "program": "serve_decode"},
                   {"as": "chunk_fill_pct"}):
        assert ssm_work.read(ctx, params) is None


def test_the_reader_reads_the_three_rooflines_and_the_fill(monkeypatch):
    ms = 1_000_000
    paths = {
        "%s": "jit(serve_decode)/layers/attn/ssm/ssm_step/pallas_call:",
        "%m": "jit(serve_decode)/layers/mlp/dot_general:",
        "%a": "jit(serve_decode)/layers/attn/attend/paged_attend/pallas_call:",
        "%b": "jit(serve_chunk_t1024)/layers/attn/ssm/ssm_chunk/broadcast_in_dim:",
        "%c": "jit(serve_chunk_t1024)/layers/attn/ssm/ssm_chunk/pallas_call:",
        "%p": "jit(serve_chunk_t1024)/layers/attn/ssm/dot_general:"}
    ops = [("%s", 1 * ms, 4 * ms), ("%m", 4 * ms, 8 * ms),
           ("%a", 8 * ms, 9 * ms), ("%s", 11 * ms, 14 * ms),
           ("%a", 14 * ms, 15 * ms),
           ("%p", 21 * ms, 22 * ms), ("%b", 22 * ms, 23 * ms),
           ("%c", 23 * ms, 26 * ms)]
    modules = [("jit_serve_decode(7)", 0, 9 * ms),
               ("jit_serve_decode(7)", 10 * ms, 15 * ms),
               ("jit_serve_chunk_t1024(9)", 20 * ms, 28 * ms)]
    trace = {"lo_ns": 0, "hi_ns": 50 * ms, "device_ops": {0: ops},
             "device_modules": {0: modules}, "host_spans": []}
    spans = [("serve.step", 0, 9 * ms, "t", {}),
             ("serve.step", 10 * ms, 15 * ms, "t", {}),
             ("serve.step", 19 * ms, 40 * ms, "t", {}),
             ("serve.prefill", 20 * ms, 29 * ms, "t", {"tokens": 400}),
             ("serve.prefill", 60 * ms, 69 * ms, "t", {"tokens": 1000})]
    monkeypatch.setattr(ssm_work._xplane, "traced", lambda ctx: (trace, "x"))
    monkeypatch.setattr(ssm_work._xplane, "program_spans", lambda path: spans)
    monkeypatch.setattr(ssm_work.scope_time, "op_paths_of", lambda p: paths)
    cfg = harness.load_json(REAL_CFG)
    ctx = {"config": cfg, "peak": harness.peak_for("TPU v5 lite"),
           "job": {"engine": {"prefill_chunk": 1024}},
           "trace_window": (0.0, 1.0),
           "counters": {"kv_bytes": 2, "decode_context": [
               (0.5, 180_000, 256), (0.7, 180_256, 255), (2.0, 1, 1)]}}
    step = flops_jamba.ssm_step(cfg, 511)
    assert ssm_work.read(ctx, {
        "component": "ssm_step", "work": "ssm_step",
        "program": "serve_decode"}) == pytest.approx(
        100 * (step["bytes"] / 819e9) / 6e-3)
    # the scope's XLA work (B and C spread over a lane tile) counts with the
    # kernel; the chunk outside the window does not
    chunk = flops_jamba.ssm_chunk(cfg, 400, 1)
    assert ssm_work.read(ctx, {
        "component": "ssm_chunk", "work": "ssm_chunk",
        "program": "serve_chunk_t1024"}) == pytest.approx(
        100 * (chunk["bytes"] / 819e9) / 4e-3)
    attend = flops_jamba.mqa_attend(cfg, 360_256, 511, 2)
    assert ssm_work.read(ctx, {
        "component": "paged_attend", "work": "mqa_attend",
        "program": "serve_decode"}) == pytest.approx(
        100 * (attend["bytes"] / 819e9) / 2e-3)
    assert ssm_work.read(ctx, {"as": "chunk_fill_pct"}) == pytest.approx(
        100 * 400 / 1024)
    # another family's configuration, or its trace: nothing
    assert ssm_work.read(dict(ctx, config={}), {"as": "chunk_fill_pct"}) is None
    monkeypatch.setattr(ssm_work.scope_time, "op_paths_of",
                        lambda p: {"%m": paths["%m"]})
    assert ssm_work.read(ctx, {"component": "ssm_step", "work": "ssm_step",
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
    doc["configs"] = [{"name": "debug-jamba", "source": "debug",
                       "reduced": [], "why": "debug",
                       "file": "benchmarks/configs/debug-jamba.json"}]
    doc["workloads"] = [{"name": CELL, "config": "debug-jamba",
                         "traffic": "serve.debug-lognormal", "chips": 1,
                         "why": "debug"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if REAL in m["workloads"] else []
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp


@pytest.fixture(scope="module")
def debug_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("jamba_root"))


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
    """A traced run of the debug cell (log-normal prompts of 6 to 40 tokens
    in chunks of 16, replies of 24 staggered by 6, pages of 8, four blocks of
    the state class and the trash block): correct, nothing refused or
    preempted, replies end and blocks are returned and taken again inside the
    window, the counters are reported and the device metrics and those read
    from the program's spans left out (no device plane off a TPU: the
    reader's own test feeds it spans by hand)."""
    assert sound["correct"] is True and sound["failed"] == 0
    names = set(sound["metrics"])
    assert {"serve.step_ms_p50", "serve.batch_occupancy_pct",
            "serve.preemptions"} <= names
    assert not any(n.endswith("_roofline") or n.endswith("device_ms")
                   or n == "serve.chunk_fill_pct" for n in names)
    assert sound["metrics"]["serve.preemptions"]["value"] == 0
    assert sound["attempted"] >= 4


def keep_a_blocks_last_owner(monkeypatch):
    """The zero state of a sequence's start left out: a reused block is read
    as its last owner left it."""
    from distributed_training_guide_tpu.models import jamba

    real = jamba.mamba_sublayer

    def stale(config, x, p, norm_scale, state=None):
        if state is not None:
            pool, conv_pool, row, attend = state

            class Started:      # every slot claims history
                state_blocks, n_valid = attend.state_blocks, attend.n_valid
                lengths = jnp.ones_like(attend.lengths)
            state = (pool, conv_pool, row, Started)
        return real(config, x, p, norm_scale, state)
    monkeypatch.setattr(jamba, "mamba_sublayer", stale)


def taps_in_the_wrong_order(monkeypatch):
    real = _jamba.to_program

    def edited(w):
        tree = real(w)
        for layer in tree["layers"]["mamba"]:
            layer["taps"] = layer["taps"][::-1]
        return tree
    monkeypatch.setattr(_jamba, "to_program", edited)


def state_axes_swapped(monkeypatch):
    """``A_log`` handed over as published, ``[C, N]`` read as ``[N, C]``."""
    real = _jamba.to_program

    def edited(w):
        tree = real(w)
        for layer in tree["layers"]["mamba"]:
            layer["a_log"] = layer["a_log"].T.reshape(layer["a_log"].shape)
        return tree
    monkeypatch.setattr(_jamba, "to_program", edited)


FAULTS = {"stale_block": keep_a_blocks_last_owner,
          "taps_reversed": taps_in_the_wrong_order,
          "a_log_as_published": state_axes_swapped}


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
