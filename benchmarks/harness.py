"""The harness: one cell, one run, one process.

Everything that belongs to one configuration, one traffic mix, one cell or one
per-layer metric is a data file found by its name in ``BENCHMARK.json``:

    configs/<config>.json     sizes, source, reduced, assumed
    traffic/<traffic>.json    the job or traffic mix's parameters
    workloads/<cell>.json     runner kind, program pins, check settings
    metrics/<metric>.json     reader and its parameters

and code is found by name too: ``runners/<kind>.py`` drives the program,
``readers/<reader>.py`` turns spans, counters or the device trace into one
number. This module holds no list of cells, metrics, runners or readers.
"""
from __future__ import annotations

import contextlib
import importlib
import json
import math
import shutil
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---- data files ------------------------------------------------------------
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                     f"{[c['name'] for c in bench['workloads']]}")


def load_cell(bench: dict, name: str, bench_dir: Path = BENCH_DIR) -> dict:
    """The cell's entry with its data files read in: ``config``, ``traffic``
    and ``job`` (the workload file)."""
    cell = dict(find_cell(bench, name))
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    root = Path(bench_dir).parent
    cell["config_data"] = load_json(root / cfg_entry["file"])
    cell["traffic_data"] = load_json(
        Path(bench_dir) / "traffic" / f"{cell['traffic']}.json")
    cell["job"] = load_json(Path(bench_dir) / "workloads" / f"{name}.json")
    return cell


def metrics_for(bench: dict, group: str, cell_name: str) -> list:
    """Entries of ``end_to_end`` or ``per_layer`` that this cell reports."""
    out = []
    for m in bench[group]:
        cells = m.get("workloads")
        if cells is None or cell_name in cells:
            out.append(m)
    return out


def load_module(kind: str, name: str):
    """``runners/<name>.py`` or ``readers/<name>.py``, by name."""
    return importlib.import_module(f"benchmarks.{kind}.{name}")


def peak_for(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    peaks = load_json(Path(bench_dir) / "peaks.json")
    if device_kind not in peaks or device_kind.startswith("_"):
        raise KeyError(f"no peaks on record for device kind {device_kind!r}; "
                       f"add it to benchmarks/peaks.json with its source")
    return peaks[device_kind]


# ---- spans and counters ----------------------------------------------------
class Spans:
    """The benchmark's own spans around the calls into each layer, kept in
    memory. Each is also a ``TraceAnnotation`` so that a traced run has it on
    the profiler's clock (``bench.<name>``)."""

    def __init__(self):
        self.items: dict[str, list] = {}
        self._annotate = None

    def enable_annotations(self):
        import jax

        self._annotate = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def span(self, name: str):
        ann = self._annotate(f"bench.{name}") if self._annotate else None
        if ann is not None:
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.items.setdefault(name, []).append((t0, t1))

    def durations_ms(self, name: str, t_from: float = -math.inf,
                     t_to: float = math.inf) -> list:
        return [1e3 * (b - a) for a, b in self.items.get(name, ())
                if a >= t_from and b <= t_to]


class CompileCounter:
    """Compile requests as JAX's monitoring events count them: persistent
    cache hits and misses, and backend compiles."""

    def __init__(self):
        import jax

        self.requests = 0
        self.backend_compiles = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_):
        if event in ("/jax/compilation_cache/cache_hits",
                     "/jax/compilation_cache/cache_misses"):
            self.requests += 1

    def _on_duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def snapshot(self) -> tuple:
        return (self.requests, self.backend_compiles)


# ---- device ----------------------------------------------------------------
def check_device(chips: int, require_platform: str | None = "tpu") -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_platform is not None and platform != require_platform:
        raise NoChip(f"needs a {require_platform}, JAX found {platform!r}")
    if len(devices) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX found {len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# ---- the comparison's printed record ---------------------------------------
class Checks:
    """Every number compared, beside its limit. ``correct`` is their
    conjunction. ``kind`` is ``max`` (value may not exceed the limit) or
    ``min`` (value may not fall below it)."""

    def __init__(self):
        self.rows = []

    def add(self, name: str, value, limit, kind: str = "max", note: str = ""):
        value = float(value)
        ok = math.isfinite(value) and (
            value <= limit if kind == "max" else value >= limit)
        self.rows.append({"check": name, "value": value, "limit": limit,
                          "kind": kind, "ok": bool(ok), "note": note})
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def print(self):
        for row in self.rows:
            print(json.dumps({"compared": row}), flush=True)


# ---- one run ---------------------------------------------------------------
def run_cell(*, root: Path, workload: str, seed: int, seconds: float,
             trace: bool, t_process_start: float,
             bench_dir: Path = BENCH_DIR,
             require_platform: str | None = "tpu") -> dict:
    """Run one cell once and return the result line's object (with the run's
    ``ctx`` beside it, which ``controls.py`` reads on)."""
    root = Path(root)
    bench = load_benchmark(root)
    cell = load_cell(bench, workload, bench_dir)
    device = check_device(cell["chips"], require_platform)

    import jax

    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    # the program's own switch for JAX's persistent cache: a fixed directory
    # inside the checkout (or JAX_COMPILATION_CACHE_DIR where that is set)
    from distributed_training_guide_tpu.utils.compile_cache import (
        enable_compile_cache)

    cache = enable_compile_cache()
    compiles = CompileCounter()
    spans = Spans()
    devices = jax.devices()[: cell["chips"]]
    trace_dir = None
    if trace:
        spans.enable_annotations()
        trace_dir = root / ".bench_out" / "trace" / workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True, exist_ok=True)

    def phase(name: str):
        """Where set-up's seconds go: one line per phase, since process start."""
        print(json.dumps({"phase": name, "t_s": round(
            time.monotonic() - t_process_start, 3)}), flush=True)

    runner = load_module("runners", cell["job"]["runner"])
    ctx = {
        "phase": phase,
        "root": root, "bench_dir": Path(bench_dir), "cell": cell,
        "config": cell["config_data"], "traffic": cell["traffic_data"],
        "job": cell["job"], "seed": int(seed), "seconds": float(seconds),
        "devices": devices, "spans": spans, "compiles": compiles,
        "trace_dir": trace_dir, "t_process_start": t_process_start,
        "checks": Checks(), "device": device,
        "peak": (peak_for(device["kind"], bench_dir)
                 if device["platform"] == "tpu" else None),
    }
    out = runner.run(ctx)   # fills ctx: e2e values, counters, window bounds
    ctx.update(out)
    print(json.dumps({"compile_cache": {
        "directory": cache.directory, "hits": cache.hits,
        "misses": cache.misses,
        "requests_inside_window": ctx["compiles_in_window"]}}), flush=True)
    checks: Checks = ctx["checks"]
    checks.add("compile_requests_inside_window",
               sum(ctx["compiles_in_window"]), 0, "max",
               "every shape is warmed during set-up")
    checks.print()

    device = dict(device, memory_peak_bytes=ctx["memory_peak_bytes"])
    if trace:
        metrics, breakdown = read_per_layer(bench, workload, ctx, device)
    else:
        metrics, breakdown = {}, None
        for m in metrics_for(bench, "end_to_end", workload):
            if m["name"] in ctx["end_to_end"]:
                metrics[m["name"]] = {"value": ctx["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    result = {"correct": checks.correct, "attempted": ctx["attempted"],
              "failed": ctx["failed"], "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["compared"] = checks.rows          # neither is printed
    result["ctx"] = ctx
    return result


def read_per_layer(bench: dict, workload: str, ctx: dict, device: dict):
    """The traced run's metrics: each per-layer metric of this cell through
    its own reader. A reader that finds nothing to read returns None and the
    metric is left out of the line."""
    from benchmarks import trace_reduce

    reduced = None
    if ctx.get("trace_dir") is not None:
        reduced = trace_reduce.reduce_dir(
            ctx["trace_dir"], n_devices=len(ctx["devices"]))
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    ctx["trace"] = reduced
    metrics = {}
    for m in metrics_for(bench, "per_layer", workload):
        spec = load_json(ctx["bench_dir"] / "metrics" / f"{m['name']}.json")
        reader = load_module("readers", spec["reader"])
        value = reader.read(ctx, spec.get("params", {}))
        if value is None:
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    breakdown = None
    if reduced is not None:
        breakdown = {"device_ops": reduced["top_ops"][:10],
                     "idle_gaps": reduced["top_gaps"][:10]}
    return metrics, breakdown


def main(argv, *, t_process_start: float, root: Path | None = None,
         bench_dir: Path | None = None,
         require_platform: str | None = "tpu") -> int:
    import argparse

    parser = argparse.ArgumentParser(prog="benchmarks/run.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    root = Path(root) if root is not None else BENCH_DIR.parent
    try:
        result = run_cell(root=root, workload=args.workload, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          t_process_start=t_process_start,
                          bench_dir=bench_dir or BENCH_DIR,
                          require_platform=require_platform)
    except NoChip as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    line = {k: result[k] for k in (*RESULT_KEYS, "breakdown") if k in result}
    print(json.dumps(line), flush=True)
    return 0
