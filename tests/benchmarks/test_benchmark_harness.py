"""The benchmark's harness on the CPU: its data files, its contract, each
runner end to end at a debug-width configuration (tests/benchmarks/debug/),
the fault that ``correct`` has to catch, and that new cells are new files."""
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import flops, harness  # noqa: E402
from benchmarks.traffic import generate  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
DEBUG = Path(__file__).resolve().parent / "debug"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TRAIN, SERVE = "debug-qwen3.train.debug", "debug-olmo2.serve.debug"
STAGGERED = "debug-olmo2.serve.debug-staggered"


# ---- the data files ---------------------------------------------------------
def test_benchmark_json_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/run.py"]
    assert BENCH["paths"] == ["benchmarks", "tests/benchmarks"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]


def test_names_and_units_use_the_allowed_characters():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    names += [w["config"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    assert len(set(x["name"] for x in BENCH["end_to_end"] + BENCH["per_layer"])) \
        == len(BENCH["end_to_end"]) + len(BENCH["per_layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for path in (ROOT / "benchmarks").rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", str(path.relative_to(ROOT))), path


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_and_cross_references(cell):
    loaded = harness.load_cell(BENCH, cell)
    cfg = loaded["config_data"]
    entry = next(c for c in BENCH["configs"] if c["name"] == loaded["config"])
    assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"]
    assert "assumed" in cfg
    assert loaded["name"] == f"{loaded['config']}.{loaded['traffic']}"
    assert (ROOT / "benchmarks" / "runners" / f"{loaded['job']['runner']}.py").exists()
    e2e = {m["name"] for m in harness.metrics_for(BENCH, "end_to_end", cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = harness.metrics_for(BENCH, "per_layer", cell)
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_its_file_and_reader(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    spec = harness.load_json(ROOT / "benchmarks" / "metrics" / f"{metric}.json")
    # what BENCHMARK.json says of a metric is said there alone
    assert set(spec) <= {"reader", "params"}
    assert callable(harness.load_module("readers", spec["reader"]).read)
    if metric.endswith("_roofline") or "mfu" in metric:
        assert entry["unit"] == "%"


def test_peaks_table_refuses_an_unknown_device():
    assert harness.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert harness.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peak_for("cpu")


# ---- FLOP functions against hand counts -------------------------------------
@pytest.mark.parametrize("config,seq,want", [
    ("qwen3-0.6b", 2048, 4.28e9), ("qwen3-0.6b", 8192, 6.4e9),
    ("olmo2-7b-l8", 4096, 13.0e9)])
def test_train_flops_per_token_hand_counts(config, seq, want):
    cfg = harness.load_json(ROOT / "benchmarks" / "configs" / f"{config}.json")
    assert flops.train_flops_per_token(cfg, seq) == pytest.approx(want, rel=0.005)


def test_kernel_work_functions():
    cfg = harness.load_json(ROOT / "benchmarks" / "configs" / "qwen3-0.6b.json")
    fwd, bwd = flops.flash_fwd(cfg, 8, 2048), flops.flash_bwd(cfg, 8, 2048)
    # 4 FLOPs per (query, visible key, head, head_dim): 2048*2049/2 pairs
    assert fwd["flops"] == 4 * 8 * 16 * 128 * (2048 * 2049 // 2)
    assert bwd["flops"] == 2.5 * fwd["flops"]
    peak = harness.peak_for("TPU v5 lite")
    assert flops.least_time(fwd, peak)[1] == "compute"
    serve = harness.load_json(ROOT / "benchmarks" / "configs" / "olmo2-7b-l12.json")
    assert flops.kv_bytes_per_token(serve) == 12 * 2 * 32 * 128 * 2
    assert flops.least_time(flops.paged_attend(serve, 12000, 16), peak)[1] == "memory"


# ---- traffic ----------------------------------------------------------------
def test_traffic_repeats_for_a_seed_and_differs_across_seeds():
    mix = harness.load_json(DEBUG / "traffic" / "serve.debug.json")
    a = [next(generate.RequestStream(mix, 1000, 5)) for _ in range(1)]
    b = [next(generate.RequestStream(mix, 1000, 5)) for _ in range(1)]
    assert a == b
    s5, s6 = generate.RequestStream(mix, 1000, 5), generate.RequestStream(mix, 1000, 2**31 + 6)
    five = [next(s5) for _ in range(mix["distinct_requests"])]
    six = [next(s6) for _ in range(mix["distinct_requests"])]
    assert five != six
    # the same set of sizes a cycle, in an order that is the seed's
    sizes = lambda reqs: [(len(p), n) for p, n in reqs]
    assert sorted(sizes(five)) == sorted(sizes(six)) and sizes(five) != sizes(six)
    assert len({len(p) for p, _ in five}) > 10
    lens = [len(p) for p, _ in five]
    assert min(lens) >= 18 and max(lens) <= 80
    fixed = harness.load_json(ROOT / "benchmarks" / "traffic" / "serve.decode16.json")
    assert {(len(p), n) for p, n in (next(generate.RequestStream(fixed, 1000, s))
                                     for s in (5, 6))} == {(512, 48)}
    train = harness.load_json(ROOT / "benchmarks" / "traffic" / "train.seq2048.json")
    small = dict(train, sequences=8, seq_len=64)
    d1, d2 = (generate.train_dataset(small, 5000, s) for s in (7, 7))
    assert (d1 == d2).all() and len({r.tobytes() for r in d1}) == 8
    assert (generate.train_dataset(small, 5000, 8) != d1).any()
    assert generate.poisson_arrivals(5.0, 10.0, 3) == generate.poisson_arrivals(5.0, 10.0, 3)
    assert generate.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 0.9) == 9.0


@pytest.mark.parametrize("seed", [5, 2**31 + 6])
def test_staggered_first_replies_are_the_same_for_every_seed(seed):
    """``first_output_len: staggered``: client i of n's FIRST reply is
    (i + 1) / n of the mix's length, every later reply whole, whatever the
    seed; a mix without the parameter behaves as before."""
    mix = harness.load_json(ROOT / "benchmarks" / "traffic" / "serve.decode16.json")
    assert mix["first_output_len"] == "staggered" and mix["clients"] == 16
    stream = generate.RequestStream(mix, 1000, seed)
    reqs = [next(stream) for _ in range(48)]
    assert [n for _, n in reqs[:16]] == list(range(48, 769, 48))
    assert {n for _, n in reqs[16:]} == {768}
    assert {len(p) for p, _ in reqs} == {512}
    plain = {k: v for k, v in mix.items() if k != "first_output_len"}
    whole = generate.RequestStream(plain, 1000, seed)
    assert [next(whole)[1] for _ in range(20)] == [768] * 20
    # the parameter changes lengths only: the token ids are the seed's
    again = generate.RequestStream(plain, 1000, seed)
    assert [p for p, _ in reqs[:3]] == [next(again)[0] for _ in range(3)]
    with pytest.raises(ValueError):
        generate.RequestStream(dict(mix, first_output_len="random"), 1000, 1)


# ---- the runners, end to end at debug width ----------------------------------
def make_root(tmp: Path) -> Path:
    """A checkout-shaped directory: the real metric files and peaks, the
    debug configurations, traffic and cells, and a BENCHMARK.json over them."""
    bench = tmp / "benchmarks"
    bench.mkdir(parents=True)
    shutil.copytree(ROOT / "benchmarks" / "metrics", bench / "metrics")
    shutil.copy(ROOT / "benchmarks" / "peaks.json", bench / "peaks.json")
    for d in ("configs", "traffic", "workloads"):
        shutil.copytree(DEBUG / d, bench / d)
    doc = json.loads(json.dumps(BENCH))
    cells = [(TRAIN, "debug-qwen3", "train.debug"), (SERVE, "debug-olmo2", "serve.debug"),
             (STAGGERED, "debug-olmo2", "serve.debug-staggered")]
    doc["configs"] = [{"name": c, "source": "debug", "reduced": [], "why": "debug",
                       "file": f"benchmarks/configs/{c}.json"}
                      for c in ("debug-qwen3", "debug-olmo2")]
    doc["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                         "why": "debug"} for n, c, t in cells]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            kind = ".train." if any(".train." in w for w in m["workloads"]) else ".serve."
            m["workloads"] = [n for n, _, _ in cells if kind in n]
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp


def run(root, cell, **kw):
    return harness.run_cell(
        root=root, workload=cell, seed=kw.pop("seed", 2**31 + 17),
        seconds=kw.pop("seconds", 1.0), trace=kw.pop("trace", False),
        t_process_start=time.monotonic(), bench_dir=root / "benchmarks",
        require_platform=None)


@pytest.fixture(scope="module")
def debug_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"))


@pytest.mark.parametrize("cell", [TRAIN, SERVE])
def test_runner_end_to_end_prints_the_contract_line(cell, debug_root, capsys):
    rc = harness.main(["--workload", cell, "--seed", str(2**31 + 5),
                       "--seconds", "1", "--trace", "0"],
                      t_process_start=time.monotonic(), root=debug_root,
                      bench_dir=debug_root / "benchmarks", require_platform=None)
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert set(last["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    want = {m["name"] for m in harness.metrics_for(
        harness.load_benchmark(debug_root), "end_to_end", cell)}
    assert set(last["metrics"]) == want
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    compared = [json.loads(l)["compared"] for l in lines if l.startswith('{"compared"')]
    assert compared and all("limit" in row for row in compared)


@pytest.mark.parametrize("cell", [TRAIN, SERVE])
def test_traced_run_reports_per_layer_metrics(cell, debug_root):
    result = run(debug_root, cell, trace=True)
    assert result["correct"] is True
    names = set(result["metrics"])
    allowed = {m["name"] for m in harness.metrics_for(
        harness.load_benchmark(debug_root), "per_layer", cell)}
    assert names and names <= allowed
    # no device plane on the CPU: the device readers find nothing and say so
    assert not any(n.startswith("device.idle") or n.endswith("_roofline")
                   for n in names)
    assert ("train.step_ms_p50" in names) == (cell == TRAIN)
    assert ("serve.step_ms_p50" in names) == (cell == SERVE)


def test_staggered_mix_completes_in_distinct_steps_with_prefill_in_the_window(
        debug_root, capsys):
    """The shape of ``serve.decode16`` at debug width: replies end one after
    another, never two in one engine step, and each new prompt's chunk runs
    inside the window."""
    result = run(debug_root, STAGGERED, trace=True)
    assert result["correct"] is True and result["failed"] == 0
    lines = capsys.readouterr().out.splitlines()
    window = next(json.loads(l)["window"] for l in lines if l.startswith('{"window"'))
    assert window["completed"] >= 4 and window["most_completions_in_one_step"] == 1
    assert window["prefill_calls"] >= window["completed"] - 1 > 0
    assert 0 < window["prefill_step_share_pct"] < 50
    assert window["preemptions"] == 0 and window["refused"] == 0
    spans = result["ctx"]["spans"].items
    t0, t1 = result["ctx"]["window"]
    inside = lambda name: [s for s in spans[name] if s[0] >= t0 and s[1] <= t1]
    assert len(inside("engine.step.prefill")) == window["prefill_calls"]
    assert len(inside("engine.step.decode")) + len(inside("engine.step.prefill")) \
        == len(inside("engine.step"))
    # the step metric is read over the decode steps alone
    from benchmarks.traffic.generate import percentile
    assert result["metrics"]["serve.step_ms_p50"]["value"] == percentile(
        [1e3 * (b - a) for a, b in inside("engine.step.decode")], 0.5)


@pytest.mark.parametrize("config,adapter,weights,reference", [
    ("debug-olmo2", "_llama", "benchmarks.weights", "benchmarks.reference.decoder"),
    ("debug-mla-moe", "_mla_moe", "benchmarks.weights_mla_moe",
     "benchmarks.reference.mla_moe")])
def test_the_one_serve_runner_finds_the_family_by_name(config, adapter, weights,
                                                      reference):
    from benchmarks.runners import serve

    cfg = harness.load_json(DEBUG / "configs" / f"{config}.json")
    family = serve.family_of(cfg)
    assert family.__name__ == f"benchmarks.runners.{adapter}"
    assert family.weights.__name__ == weights
    assert family.reference.__name__ == reference
    assert callable(family.bundle_for) and callable(family.program_params)
    assert callable(family.reference.served_token_gaps)
    assert not (ROOT / "benchmarks" / "runners" / "serve_mla_moe.py").exists()
    for job in (ROOT / "benchmarks" / "workloads").glob("*.serve.*.json"):
        assert harness.load_json(job)["runner"] == "serve"
    with pytest.raises(ModuleNotFoundError):
        serve.family_of({"family": "no_such_family"})


# ---- the timed path broken underneath ----------------------------------------
def half_batch(step):
    """Part of the batch left out: its first half, twice."""
    import jax
    import jax.numpy as jnp

    def call(state, batch):
        ids = batch["input_ids"]
        half = ids.shape[0] // 2
        ids = jax.device_put(jnp.concatenate([ids[:half], ids[:half]]),
                             ids.sharding)
        return step(state, {"input_ids": ids, "labels": ids})
    return call


def frozen_step(step):
    """A step that returns its state unchanged (a copy: the step donates it)."""
    import jax
    import jax.numpy as jnp

    def call(state, batch):
        kept = jax.tree.map(jnp.copy, state)
        _, metrics = step(state, batch)
        return kept, metrics
    return call


def break_train_step(monkeypatch, wrap):
    from benchmarks.runners import train

    build = train.build

    def broken(ctx):
        trainer, step, state, loader = build(ctx)
        return trainer, wrap(step), state, loader
    monkeypatch.setattr(train, "build", broken)


def alter_served_tokens(monkeypatch):
    """A token altered where it is produced: what the engine hands out, of
    requests finished or in flight."""
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


@pytest.mark.parametrize("cell,fault", [
    (TRAIN, "half_batch"), (TRAIN, "frozen_step"), (SERVE, "alter_token")])
def test_a_broken_timed_path_is_not_correct(cell, fault, debug_root, monkeypatch):
    """The rest of a run with the timed path broken underneath: part of the
    batch left out, a step that returns its state unchanged, a served token
    altered where it is produced."""
    if fault == "alter_token":
        alter_served_tokens(monkeypatch)
    else:
        break_train_step(monkeypatch, {"half_batch": half_batch,
                                       "frozen_step": frozen_step}[fault])
    result = run(debug_root, cell)
    assert result["correct"] is False
    assert any(not row["ok"] for row in result["compared"])


def test_lower_precision_control_fails_at_debug_size(debug_root, monkeypatch):
    """The control kept as a test: the program's own lower-precision path
    (bf16 master weights), switched on as ``controls.py`` does it, fails the
    parameter-change limit that the stated precision meets."""
    from benchmarks import controls

    sound = run(debug_root, TRAIN)
    monkeypatch.setattr(harness, "load_cell", controls.edited(
        harness.load_cell, {"job.precision": "bf16-master"}))
    control = run(debug_root, TRAIN)
    row = lambda r: next(c for c in r["compared"]
                         if c["check"] == "param_change_norm_worst_leaf_gap")
    assert sound["correct"] and row(sound)["ok"]
    assert not row(control)["ok"] and not control["correct"]
    assert row(control)["value"] > 3 * row(sound)["value"]


@pytest.mark.parametrize("cell,mode,check", [
    (TRAIN, "bf16", "param_change_norm_worst_leaf_gap"),
    (SERVE, "int8", "served_token_widest_logit_gap")])
def test_runner_control_reads_the_reference_in_a_lower_precision(
        cell, mode, check, debug_root):
    result = run(debug_root, cell)
    runner = harness.load_module("runners", result["ctx"]["job"]["runner"])
    rows = runner.control(result["ctx"], mode)
    assert rows[check] >= 0 and set(rows) <= {r["check"] for r in result["compared"]}


def test_new_config_cell_and_metric_are_only_new_files(tmp_path):
    root = make_root(tmp_path / "root")
    bench = root / "benchmarks"
    cfg = json.loads((bench / "configs" / "debug-qwen3.json").read_text())
    cfg["num_hidden_layers"] = 3
    (bench / "configs" / "debug-deeper.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "train.debug.json").read_text())
    mix["seq_len"] = 48
    (bench / "traffic" / "train.longer.json").write_text(json.dumps(mix))
    shutil.copy(bench / "workloads" / f"{TRAIN}.json",
                bench / "workloads" / "debug-deeper.train.longer.json")
    (bench / "metrics" / "train.step_ms_mean.json").write_text(json.dumps({
        "name": "train.step_ms_mean", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "train step",
        "moves": "train.tokens_per_s_per_chip", "reader": "span_stat",
        "params": {"span": "step", "stat": "mean"}}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "debug-deeper", "source": "debug", "reduced": [],
                           "file": "benchmarks/configs/debug-deeper.json", "why": "x"})
    doc["workloads"].append({"name": "debug-deeper.train.longer", "chips": 1,
                             "config": "debug-deeper", "traffic": "train.longer",
                             "why": "x"})
    doc["end_to_end"][0]["workloads"].append("debug-deeper.train.longer")
    doc["per_layer"].append({"name": "train.step_ms_mean", "unit": "ms",
                             "better": "lower", "source": "program_span",
                             "layer": "train step",
                             "moves": "train.tokens_per_s_per_chip",
                             "workloads": ["debug-deeper.train.longer"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    result = run(root, "debug-deeper.train.longer", trace=True)
    assert result["correct"] is True
    assert result["metrics"]["train.step_ms_mean"]["value"] > 0


# ---- off the TPU -------------------------------------------------------------
def test_run_py_fails_and_prints_no_result_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout and '"metrics"' not in proc.stdout


def test_check_device_counts_chips():
    with pytest.raises(harness.NoChip):
        harness.check_device(1, "tpu")
    with pytest.raises(harness.NoChip):
        harness.check_device(64, None)
    assert harness.check_device(1, None)["platform"] == "cpu"
