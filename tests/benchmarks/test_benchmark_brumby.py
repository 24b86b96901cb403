"""The ``brumby`` family in the benchmark, on the CPU: the plain reference
(``benchmarks/reference/brumby.py``, power retention in its ATTENTION form:
no state, no feature map) against ``models/brumby.py`` (the recurrent form
over the blocked feature map) with the faults it has to see, the weights'
contract, the cell's data files, the work functions and the new reader by
hand, and the runner end to end on a debug-width cell
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

from benchmarks import flops, flops_brumby, harness  # noqa: E402
from benchmarks import weights_brumby as weights  # noqa: E402
from benchmarks.readers import retention_work  # noqa: E402
from benchmarks.reference import brumby as ref  # noqa: E402
from benchmarks.runners import _brumby  # noqa: E402
from benchmarks.traffic import generate  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
DEBUG = Path(__file__).resolve().parent / "debug"
CELL = "debug-brumby.serve.debug-doc"
REAL = "brumby-14b-l8.serve.doc16"
REAL_CFG = ROOT / "benchmarks" / "configs" / "brumby-14b-l8.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW = ("serve.retention_device_ms", "retention_step_roofline",
       "retention_chunk_roofline")
# float32 program against float32 reference, two FORMS of one layer: the
# program sums over 768 feature rows what the reference squares (read 1.2e-5
# on logits of magnitude 7 over 300 tokens)
LOGIT_TOL = 1e-4


def debug_cfg(**over):
    cfg = json.loads((DEBUG / "configs" / "debug-brumby.json").read_text())
    return dict(cfg, **over)


def layer_fn_of(cfg, key):
    return lambda l: weights.layer_weights(cfg, key, l, jnp.float32)


# ---- the reference against the program ---------------------------------------
@pytest.fixture(scope="module")
def forward():
    from distributed_training_guide_tpu.models import brumby

    cfg, key = debug_cfg(), weights.seed_key(2**31 + 7)
    w = weights.stacked_weights(cfg, key, jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 512, 300).astype(np.int32)
    bundle = _brumby.bundle_for(cfg, "debug")
    got = brumby.apply(bundle.config, _brumby.to_program(w),
                       jnp.asarray(tokens[None]))[0]
    return cfg, key, w["top"], tokens, got


@pytest.mark.parametrize("fault", ref.FAULTS + ("int8", "bf16"),
                         ids=[f or "sound" for f in ref.FAULTS]
                         + ["int8", "bf16"])
def test_reference_matches_program_logits_and_sees_each_fault(forward, fault):
    """Two layers, 300 tokens (three token blocks: the program carries a
    state across two boundaries, the reference has none). The sound
    reference is the program's forward; with the gate or the normaliser left
    out, degree 1, no rope, k read a column off, or operands in int8 or
    bfloat16, it is not."""
    cfg, key, top, tokens, got = forward
    more = ({"mode": fault} if fault in ("int8", "bf16")
            else {"fault": fault})
    want = ref.forward_logits(cfg, layer_fn_of(cfg, key), top, tokens, **more)
    diff = float(jnp.max(jnp.abs(got - want)))
    if fault is None:
        assert diff < LOGIT_TOL
    else:
        assert diff > 500 * LOGIT_TOL


def test_the_reference_has_no_state_no_feature_map_and_nothing_of_the_program():
    src = (ROOT / "benchmarks" / "reference" / "brumby.py").read_text()
    code = src.split('"""', 2)[2]
    for word in ("distributed_training_guide_tpu", "pallas", "feature_map",
                 "phi", "lax.scan", "kv_cache", "pages"):
        assert word not in code, word
    assert "precision=HIGHEST" in code and "float32" in code


def test_stacked_weights_are_the_layers_own_draws_and_the_gate_moves():
    cfg, key = debug_cfg(), weights.seed_key(3)
    stacked = weights.stacked_weights(cfg, key, jnp.float32)
    for l in range(cfg["num_hidden_layers"]):
        own = weights.layer_weights(cfg, key, l, jnp.float32)
        for name, leaf in stacked["layers"].items():
            assert np.array_equal(leaf[l], own[name]), name
    assert set(stacked["top"]) == {"embed", "final_norm", "lm_head"}
    assert not np.array_equal(stacked["top"]["embed"],
                              stacked["top"]["lm_head"].T)      # untied
    # a = u W_g has the published width's deviation, 0.02 x sqrt(5120) = 1.4,
    # at any width: gamma then lies in 0.98-0.9999
    wg = stacked["layers"]["wg"]
    assert wg.shape == (2, 64, 2)
    std = float(jnp.std(wg)) * cfg["hidden_size"] ** 0.5
    assert 1.2 < std < 1.7
    real = harness.load_json(REAL_CFG)
    assert weights.matrix_std(real) == pytest.approx(0.02)
    assert weights.num_params(cfg) == _brumby.bundle_for(
        cfg, "debug").config.num_params()
    tree = _brumby.to_program(stacked)
    assert set(tree["layers"]["mixer"][1]) == set(_brumby.MIXER)
    assert np.array_equal(tree["layers"]["mixer"][1]["wg"], wg[1])
    assert np.array_equal(tree["lm_head"], stacked["top"]["lm_head"])


# ---- the data files ----------------------------------------------------------
def test_the_cell_loads_with_every_published_width_and_the_depth_alone_cut():
    loaded = harness.load_cell(BENCH, REAL)
    cfg, job, mix = loaded["config_data"], loaded["job"], loaded["traffic_data"]
    assert loaded["chips"] == 1 and job["runner"] == "serve"
    if CATALOG.exists():    # every number of the catalog's config but depth
        row = next(r for r in map(json.loads, CATALOG.open())
                   if r["name"] == "Brumby-14B-Base")
        assert cfg["source"] == row["source_url"]
        for name, value in row["config"].items():
            if name != "num_hidden_layers":
                assert cfg[name] == value, name
        assert cfg["published"] == {"num_hidden_layers":
                                    row["config"]["num_hidden_layers"]}
    assert cfg["reduced"] == ["num_hidden_layers"] \
        and cfg["num_hidden_layers"] == 8
    assert set(cfg["assumed"]) >= {
        "degree", "gate", "normaliser", "qk_norm_rope", "output",
        "state_dtype", "state_form", "stored_D", "weights"}
    for key, letter in zip(("degree", "gate", "normaliser", "qk_norm_rope",
                            "output", "state_dtype", "state_form",
                            "stored_D", "weights"), "abcdefghi"):
        assert cfg["assumed"][key].startswith(f"({letter})"), key
    assert weights.num_params(cfg) == cfg["num_params"] == 4_198_652_928
    assert f"{weights.num_params(cfg):,}" in cfg["deployment"]
    config = _brumby.bundle_for(cfg, "real").config
    assert config.num_params() == 4_198_652_928
    assert mix["clients"] == mix["distinct_requests"] == 16 \
        and mix["prompt_len"] == {"median": 4096, "sigma": 0.6, "min": 1024,
                                  "max": 12288} \
        and mix["output_len"] == {"fixed": 512} and mix["loop"] == "closed" \
        and mix["first_output_len"] == "staggered" and mix["shared_prefix"] == 0
    lengths = generate.lengths(mix["prompt_len"], 16)
    assert (lengths[0], lengths[-1]) == (1340, 12288)
    assert round(sum(lengths) / 16) == 4810
    eng = job["engine"]
    chunks = sum(-(-n // eng["prefill_chunk"]) for n in lengths)
    assert chunks == 83 and 0.90 < sum(lengths) / (chunks * 1024) < 0.91
    assert eng["n_slots"] == mix["clients"] and eng["prefill_chunk"] == 1024 \
        and eng["attend_impl"] == "auto" and eng["prefix_cache"] is False
    # every request whole: nothing is preempted (a page holds nothing)
    assert eng["max_len"] == 12288 + 512
    assert eng["n_pages"] == eng["n_slots"] * (
        eng["max_len"] // eng["page_size"]) + 1
    stream = generate.RequestStream(mix, cfg["vocab_size"], 7)
    firsts = [next(stream)[1] for _ in range(16)]
    assert firsts == [32 * (i + 1) for i in range(16)]
    assert next(stream)[1] == 512
    # the ramp: every client's first prompt prefilled (the first 90 engine
    # steps of the simulated schedule all run a chunk)
    assert job["ramp_steps"] == 96


def test_the_state_class_costs_what_the_configuration_file_says():
    import jax

    from distributed_training_guide_tpu.serve import kv_pages

    cfg = harness.load_json(REAL_CFG)
    job = harness.load_cell(BENCH, REAL)["job"]["engine"]
    config = _brumby.bundle_for(cfg, "real").config
    said = cfg["state_per_sequence"]
    assert kv_pages.sequence_state_bytes(config) == said["bytes"] \
        == 306_184_192 == said["layers"] * said["bytes_a_layer"]
    assert said["bytes_a_layer"] == said["S_bytes_a_layer"] \
        + said["Z_bytes_a_layer"] == 8 * 9216 * 128 * 4 + 8 * 128 * 128 * 4
    assert said["stored_rows_D"] == 9216 <= 9288 \
        and said["exact_rows_D"] == 8256 and said["block_pairs"] == 36
    # the yardstick counts the state AS PUBLISHED, not as stored
    assert flops_brumby.state_bytes(cfg) == 34_080_768 \
        == said["published_form_bytes_a_layer"]
    assert kv_pages.kv_page_bytes(config, page_size=128, n_pages=1601) \
        == said["kv_bytes_per_token"] == 0
    narrow = dict(cfg, state_dtype="bfloat16")
    with pytest.raises(ValueError, match="state class is float32"):
        _brumby.bundle_for(narrow, "real")
    assert flops_brumby.retention_step(narrow, 16) \
        == flops_brumby.retention_step(cfg, 16)
    blocks = job["n_slots"] + 1
    shapes = jax.eval_shape(lambda: kv_pages.init_pages(
        config, job["n_pages"], 128, n_state_blocks=blocks))
    assert set(shapes) == {"seq_state", "seq_norm"}
    assert shapes["seq_state"].shape == (8, 17, 8, 36, 256, 128) \
        and shapes["seq_state"].dtype == jnp.float32
    assert shapes["seq_norm"].shape == (8, 17, 8, 128, 128)
    state = kv_pages.sequence_state_bytes(config, blocks)
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(shapes)) == state
    assert 5.20e9 < state < 5.21e9
    # weights and state: 13.6 GB, 85% of the harness's 16 GB
    assert 0.70 * 16e9 < 2 * cfg["num_params"] + state < 0.87 * 16e9


def test_the_cell_is_listed_where_its_readers_mean_the_same():
    """Membership alone (``test_benchmark_jamba.py``'s rule): no position and
    no length of any list is pinned."""
    def cells(name):
        return next(m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                    if m["name"] == name)["workloads"]
    for name in ("serve.itl_p95_ms", "serve.step_ms_p50",
                 "serve.unscoped_device_ms", "serve.host_ms_per_step",
                 "serve.schedule_ms_per_step", "serve.chunk_device_ms", *NEW):
        assert REAL in cells(name), name
    # NOT serve.out_tokens_per_s, nor any per-layer metric that moves it
    # (occupancy, preemptions, the device's idle shares, peak HBM: a metric's
    # cells report the end-to-end metric it moves): the generator draws every
    # cycle's order from the seed, a prompt is 2 to 12 chunks, and on the chip
    # six seeds held 91 to 100 chunk steps in their 30 s windows and read
    # 330.5 to 342.6 tokens/s, 2.64% between the quartiles where the driver
    # admits a cell at 1.5% (serve.itl_p95_ms: 0.20%). PERF.md section 7 has
    # what `generate.length_pairs` needs before the cell can join them
    moving = [m["name"] for m in BENCH["per_layer"]
              if m["moves"] == "serve.out_tokens_per_s"]
    for name in ["serve.out_tokens_per_s", *moving]:
        assert REAL not in cells(name), name
    reported = {m["name"] for m in BENCH["end_to_end"]
                if REAL in m.get("workloads", [REAL])}
    assert reported == {"setup_s", "serve.itl_p95_ms"}
    for m in BENCH["per_layer"]:
        if REAL in m.get("workloads", []):
            assert m["moves"] in reported, m["name"]
    # nothing attends and no page is written: nothing is under those scopes;
    # the other families' mixers, routers and work functions are not this
    # one's (`serve.chunk_fill_pct`'s reader asks for a Mamba configuration)
    for name in ("serve.attend_device_ms", "serve.kv_write_device_ms",
                 "paged_attend_roofline", "hybrid_attend_roofline",
                 "gqa_attend_roofline", "mqa_attend_roofline", "gmm_roofline",
                 "serve.experts_device_ms", "serve.router_device_ms",
                 "serve.kda_device_ms", "kda_step_roofline",
                 "serve.ssm_device_ms", "ssm_step_roofline",
                 "serve.chunk_fill_pct", "serve.conv_device_ms"):
        assert REAL not in cells(name), name
    entry = next(c for c in BENCH["workloads"] if c["name"] == REAL)
    config = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert entry["chips"] == 1 and entry["traffic"] == "serve.doc16"
    for why in (entry["why"], config["why"]):
        assert 1 <= len(why) <= 200 and why.isascii() and why.isprintable()
    assert config["reduced"] == harness.load_json(REAL_CFG)["reduced"]
    layers = {m["name"]: m["layer"] for m in BENCH["per_layer"]}
    assert layers["retention_step_roofline"] \
        == layers["retention_chunk_roofline"] == layers["kda_step_roofline"]
    assert layers["serve.retention_device_ms"] == layers["serve.kda_device_ms"]


# ---- required work, and the reader --------------------------------------------
def test_required_work_of_the_step_and_the_chunk():
    cfg = harness.load_json(REAL_CFG)
    peak = harness.peak_for("TPU v5 lite")
    assert flops_brumby.feature_rows(cfg) == 8256
    assert flops_brumby.head_flops(cfg) == 2 * 8256 * 129 == 2_130_048
    rows = flops_brumby.row_bytes(cfg)
    assert rows == (40 + 16) * 128 * 2 + 40 * 128 * 4
    # 16 live slots, one step: each state in and out once in 8 layers
    step = flops_brumby.retention_step(cfg, 16)
    assert step["bytes"] == 8 * 16 * (2 * 34_080_768 + rows)
    assert step["flops"] == 8 * 16 * 48 * 2_130_048
    least, bound = flops.least_time(step, peak)
    assert bound == "memory" and 10.6e-3 < least < 10.7e-3
    # a first chunk of 1,024: no read-out of a zero state, no state read in
    first = flops_brumby.retention_chunk(cfg, [(1024, False)])
    pairs = 4 * 128 * 40 * 1024 * 1025 / 2
    assert first["flops"] == 8 * (1024 * 8 * 2_130_048 + pairs)
    assert first["bytes"] == 8 * (1024 * rows + 34_080_768)
    carried = flops_brumby.retention_chunk(cfg, [(1000, True)])
    assert carried["flops"] == 8 * (1000 * 48 * 2_130_048
                                    + 4 * 128 * 40 * 1000 * 1001 / 2)
    assert carried["bytes"] == 8 * (1000 * rows + 2 * 34_080_768)
    both = flops_brumby.retention_chunk(cfg, [(1024, False), (1000, True)])
    assert both["flops"] == first["flops"] + carried["flops"]
    least, bound = flops.least_time(carried, peak)
    assert bound == "compute" and 4.5e-3 < least < 4.7e-3


def test_the_reader_returns_nothing_where_there_is_nothing_to_read():
    ctx = {"trace": None, "trace_dir": None, "config": {}, "job": {}}
    for params in ({"component": "retention_step", "work": "retention_step",
                    "program": "serve_decode"},
                   {"component": "retention_chunk", "work": "retention_chunk",
                    "program": "serve_chunk_t1024"}):
        assert retention_work.read(ctx, params) is None


def test_the_reader_reads_the_two_rooflines(monkeypatch):
    ms = 1_000_000
    paths = {
        "%s": "jit(serve_decode)/layers/attn/retention/retention_step/"
              "pallas_call:",
        "%f": "jit(serve_decode)/layers/attn/retention/retention_step/mul:",
        "%m": "jit(serve_decode)/layers/mlp/dot_general:",
        "%c": "jit(serve_chunk_t1024)/layers/attn/retention/retention_chunk/"
              "pallas_call:",
        "%p": "jit(serve_chunk_t1024)/layers/attn/retention/dot_general:"}
    ops = [("%f", 1 * ms, 2 * ms), ("%s", 2 * ms, 14 * ms),
           ("%m", 14 * ms, 18 * ms), ("%s", 21 * ms, 34 * ms),
           ("%p", 41 * ms, 42 * ms), ("%c", 42 * ms, 72 * ms)]
    modules = [("jit_serve_decode(7)", 0, 19 * ms),
               ("jit_serve_decode(7)", 20 * ms, 35 * ms),
               ("jit_serve_chunk_t1024(9)", 40 * ms, 75 * ms)]
    trace = {"lo_ns": 0, "hi_ns": 100 * ms, "device_ops": {0: ops},
             "device_modules": {0: modules}, "host_spans": []}
    spans = [("serve.step", 0, 19 * ms, "t", {}),
             ("serve.prefill", 40 * ms, 76 * ms, "t",
              {"tokens": 1000, "start": 2048}),
             ("serve.prefill", 90 * ms, 99 * ms, "t", {"tokens": 400}),
             ("serve.prefill", 160 * ms, 169 * ms, "t",
              {"tokens": 1000, "start": 0})]
    monkeypatch.setattr(retention_work._xplane, "traced",
                        lambda ctx: (trace, "x"))
    monkeypatch.setattr(retention_work._xplane, "program_spans",
                        lambda path: spans)
    monkeypatch.setattr(retention_work.scope_time, "op_paths_of",
                        lambda p: paths)
    cfg = harness.load_json(REAL_CFG)
    ctx = {"config": cfg, "peak": harness.peak_for("TPU v5 lite"),
           "job": {"engine": {"prefill_chunk": 1024}},
           "trace_window": (0.0, 1.0),
           "counters": {"decode_context": [
               (0.5, 80_000, 16), (0.7, 80_016, 15), (2.0, 1, 1)]}}
    # the scope's XLA work (the step's feature rows) counts with the kernel
    step = flops_brumby.retention_step(cfg, 31)
    assert retention_work.read(ctx, {
        "component": "retention_step", "work": "retention_step",
        "program": "serve_decode"}) == pytest.approx(
        100 * (step["bytes"] / 819e9) / 26e-3)
    # the span without a `start` (a parent's) and the chunk outside the
    # window are not counted
    chunk = flops_brumby.retention_chunk(cfg, [(1000, True)])
    assert retention_work.read(ctx, {
        "component": "retention_chunk", "work": "retention_chunk",
        "program": "serve_chunk_t1024"}) == pytest.approx(
        100 * (chunk["flops"] / 197e12) / 30e-3)
    # another family's configuration, or its trace: nothing
    assert retention_work.read(dict(ctx, config={"family": "jamba"}), {
        "component": "retention_step", "work": "retention_step",
        "program": "serve_decode"}) is None
    monkeypatch.setattr(retention_work.scope_time, "op_paths_of",
                        lambda p: {"%m": paths["%m"]})
    assert retention_work.read(ctx, {
        "component": "retention_step", "work": "retention_step",
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
    doc["configs"] = [{"name": "debug-brumby", "source": "debug",
                       "reduced": [], "why": "debug",
                       "file": "benchmarks/configs/debug-brumby.json"}]
    doc["workloads"] = [{"name": CELL, "config": "debug-brumby",
                         "traffic": "serve.debug-doc", "chips": 1,
                         "why": "debug"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if REAL in m["workloads"] else []
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp


@pytest.fixture(scope="module")
def debug_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("brumby_root"))


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
    """A traced run of the debug cell (log-normal prompts of 12 to 90 tokens
    in chunks of 16, replies of 24 staggered by 6, four blocks of the state
    class and the trash block, pages that hold nothing): correct, nothing
    refused or preempted, replies end and blocks are returned and taken again
    inside the window, the counters are reported and the device metrics left
    out (no device plane off a TPU: the reader's own test feeds it spans by
    hand)."""
    assert sound["correct"] is True and sound["failed"] == 0
    names = set(sound["metrics"])
    assert "serve.step_ms_p50" in names
    assert not any(n.endswith("_roofline") or n.endswith("device_ms")
                   for n in names)
    assert sound["ctx"]["counters"]["preemptions"] == 0
    assert set(sound["ctx"]["end_to_end"]) >= {"setup_s", "serve.itl_p95_ms"}
    assert sound["attempted"] >= 4


def keep_a_blocks_last_owner(monkeypatch):
    """The zero state of a sequence's start left out: a reused block is read
    as its last owner left it."""
    from distributed_training_guide_tpu.models import brumby

    real = brumby.retention_sublayer

    def stale(config, x, p, norm_scale, positions, state=None):
        if state is not None:
            pool, norm_pool, layer, attend = state

            class Started:      # every slot claims history
                state_blocks, n_valid = attend.state_blocks, attend.n_valid
                lengths = jnp.ones_like(attend.lengths)
            state = (pool, norm_pool, layer, Started)
        return real(config, x, p, norm_scale, positions, state)
    monkeypatch.setattr(brumby, "retention_sublayer", stale)


def gate_left_out(monkeypatch):
    real = _brumby._tree

    def edited(top, layers):
        tree = real(top, layers)
        for layer in tree["layers"]["mixer"]:
            layer["wg"] = jnp.zeros_like(layer["wg"]) + 1e3    # gamma = 1
        return tree
    monkeypatch.setattr(_brumby, "_tree", edited)


def query_heads_on_the_wrong_kv_head(monkeypatch):
    """Query head ``j`` reading kv head ``j % Hkv`` (interleaved) where the
    model has ``j // g`` (blocked)."""
    real = _brumby._tree

    def edited(top, layers):
        tree = real(top, layers)
        for layer in tree["layers"]["mixer"]:
            e = layer["wq"].shape[0]
            wq = layer["wq"].reshape(e, 2, 2, -1)       # [E, Hkv, g, d]
            layer["wq"] = wq.swapaxes(1, 2).reshape(e, -1)
        return tree
    monkeypatch.setattr(_brumby, "_tree", edited)


FAULTS = {"stale_block": keep_a_blocks_last_owner,
          "no_gate": gate_left_out,
          "heads_interleaved": query_heads_on_the_wrong_kv_head}


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
