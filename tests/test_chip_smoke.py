"""chip_smoke.py's parent logic without a chip, and the small rules this
bring-up rests on: one compile cache, no invented peaks, no quiet stand-ins,
one JAX process per TPU host.

The children are faked by small scripts that print what a real entry point
prints; the parent's module constants (scripts, sizes) are patched here —
the script itself has no option or environment variable for it.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402

OK_TPU_1 = ('{"ok": true, "device": {"platform": "tpu", '
            '"kind": "TPU v5 lite", "count": 1}}')

FAKE_TRAIN = '''
import json, sys
name, platform, count, rc = {name!r}, {platform!r}, {count}, {rc}
losses = [float(s) for s in {losses}]
open({log!r}, "a").write(name + "\\n")
print(json.dumps({{"device": {{"platform": platform, "device_kind": "TPU v5 lite",
                              "count": count}},
                  "attention": {{"impl": {impl!r}, "reason": "forced"}}}}), flush=True)
program = {{"compile_s": 2.5}}
if count > 1:
    program["collectives"] = {collectives}
if {program_line}:
    print(json.dumps({{"step_program": program}}), flush=True)
for i, loss in enumerate(losses):
    print("[t] [proc 0/1] INFO:" + repr({{"global_step": i + 1, "running_loss": loss,
          "time/step": 10.0, "tokens_per_s": 5.0, "peak_alloc_gb": 1.5}}), flush=True)
if count > 1:
    print(json.dumps({{"device_memory": [{{"id": i, "bytes_in_use": {mem}[i]}}
                                         for i in range(count)]}}))
sys.exit(rc)
'''

FAKE_SERVE = '''
import json, sys
argv = sys.argv[1:]
open({log!r}, "a").write("serve\\n")
impl = argv[argv.index("--attend-impl") + 1]
steps = int(argv[argv.index("--steps") + 1])
prompts = [[int(t) for t in argv[i + 1].split(",")]
           for i, a in enumerate(argv) if a == "--prompt-ids"]
print(json.dumps({{"device": {{"platform": {platform!r}, "device_kind": "TPU v5 lite",
                              "count": 1}},
                  "attend": {{"impl": impl, "reason": "forced"}}}}), flush=True)
for i, p in enumerate(prompts[:{n_answered}]):
    new = [(7 * i + j + ({drift} if impl == "xla" and i in {drift_reqs}
                         and j >= {drift_from} else 0)) % 100
           for j in range(steps - {short})]
    print(json.dumps({{"request_id": i, "finish_reason": "length",
                      "latency_s": 0.1, "token_ids": p + new}}))
print(json.dumps({{"stats": {{"wall_s": 1.0}}}}))
sys.exit({rc})
'''

FAKE_PARITY = '''
import json, sys
open({log!r}, "a").write("parity\\n")
print(json.dumps({{"device": {{"platform": {platform!r}, "device_kind": "TPU v5 lite",
                              "count": 1}},
                  "attend": {{"impl": "flash", "reason": "forced"}}}}), flush=True)
print(json.dumps({{"kernel": "paged_attend", "T": 1, "ok": {ok}, "rtol": 0.03,
                  "max_abs_err_and_ref_max": {{"out": [{err}, 2.0]}}}}))
print(json.dumps({{"control": "zeros", "refused": {refused} > 0}}))
if {ok} and {refused} == 4:
    print(json.dumps({{"kernel_parity_ok": True, "cases": 1,
                      "controls_refused": {refused}}}))
    sys.exit(0)
sys.exit(1)
'''

GOOD_LOSSES = [11.9, 11.0, 10.0, 9.0, 8.0, 7.5, 7.2, 7.0]


def _summary(counts, largest_all_reduce_bytes=9216):
    """What utils/hlo.collective_summary prints (defaults: the chip's count
    for ch04's step at qwen3-0.6b)."""
    return {"counts": counts,
            "largest_all_reduce_bytes": largest_all_reduce_bytes}


@pytest.fixture
def fakes(tmp_path, monkeypatch):
    """Point the parent at fake children; returns (make_train, make_serve,
    log) where the makers rewrite a child with other behaviour."""
    log = tmp_path / "calls.log"
    log.write_text("")

    def make_train(attr, name, *, platform="tpu", count=1,
                   losses=GOOD_LOSSES, rc=0, impl="flash",
                   mem=(4, 4, 4, 4), collectives=None, program_line=True):
        path = tmp_path / f"{name}.py"
        path.write_text(textwrap.dedent(FAKE_TRAIN.format(
            name=name, platform=platform, count=count,
            losses=[repr(float(x)) for x in losses],
            rc=rc, impl=impl, log=str(log), mem=list(mem),
            program_line=program_line,
            collectives=collectives or _summary(
                {"all-gather": 25, "collective-permute": 88,
                 "all-reduce": 7, "reduce-scatter-fusion": 1}))))
        monkeypatch.setattr(chip_smoke, attr, path)

    def make_serve(*, platform="tpu", rc=0, n_answered=99, short=0, drift=0,
                   drift_from=0, drift_reqs=(1,)):
        path = tmp_path / "serve.py"
        path.write_text(textwrap.dedent(FAKE_SERVE.format(
            platform=platform, rc=rc, n_answered=n_answered, short=short,
            drift=drift, drift_from=drift_from, drift_reqs=tuple(drift_reqs),
            log=str(log))))
        monkeypatch.setattr(chip_smoke, "SERVE_CMD", [sys.executable, path])

    def make_parity(*, platform="tpu", ok=True, err=0.006, refused=4):
        path = tmp_path / "parity.py"
        path.write_text(textwrap.dedent(FAKE_PARITY.format(
            platform=platform, ok=ok, err=err, refused=refused,
            log=str(log))))
        monkeypatch.setattr(chip_smoke, "PARITY_SCRIPT", path)

    make_train("TRAIN_SCRIPT", "single")
    make_train("FSDP_SCRIPT", "fsdp", count=4)
    make_serve()
    make_parity()
    return make_train, make_serve, log, make_parity


def _run(capsys, argv=()):
    rc = chip_smoke.main(list(argv))
    out = capsys.readouterr()
    return rc, out.out.strip().splitlines(), out.err


def test_parent_never_imports_jax():
    """A parent that has touched JAX holds the chip its children need."""
    code = ("import sys, chip_smoke; "
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'jaxlib'))]; assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                   timeout=60)


def test_one_chip_success_prints_exact_last_line(fakes, capsys):
    rc, lines, _ = _run(capsys)
    assert rc == 0
    assert lines[-1] == OK_TPU_1
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    # one training child, then the kernel serve and its gather reference
    # and last the kernels against their references
    assert fakes[2].read_text().split() == ["single", "serve", "serve",
                                            "parity"]
    notes = [json.loads(l) for l in lines[:-1]]
    train = next(n for n in notes if n.get("loss_per_step"))
    assert train["step_compile_s"] == 2.5 and train["first_step_ms"] == 10.0
    assert any(n.get("shared_prefix_share") == 1.0 for n in notes)
    parity = next(n for n in notes if n.get("phase") == "kernel_parity"
                  and "cases" in n)
    assert parity["controls_refused"] == 4
    assert parity["worst_err_over_bound"] == pytest.approx(0.006 / 0.06)


def test_one_late_flip_in_one_request_is_tolerated(fakes, capsys):
    """Two programs that round differently may part ways late in a request
    (read on the chip: one of four, at its 10th token); that passes, and the
    note says where each request parted."""
    fakes[1](drift=1, drift_from=9)
    rc, lines, _ = _run(capsys)
    assert rc == 0 and lines[-1] == OK_TPU_1
    check = next(json.loads(l) for l in lines if "shared_prefix_share" in l)
    assert check["shared_prefix_per_request"] == [32, 9, 32, 32]
    assert check["shared_prefix_share"] == 0.8203


@pytest.mark.parametrize("break_it,says", [
    (lambda mt, ms, mp: mt("TRAIN_SCRIPT", "single", platform="cpu"),
     "platform 'cpu'"),
    (lambda mt, ms, mp: ms(platform="cpu"), "platform 'cpu'"),
    (lambda mt, ms, mp: mt("TRAIN_SCRIPT", "single", rc=3), "code 3"),
    (lambda mt, ms, mp: ms(rc=1), "code 1"),
    (lambda mt, ms, mp: mt("TRAIN_SCRIPT", "single", impl="xla"),
     "resolved to 'xla'"),
    (lambda mt, ms, mp: mt("TRAIN_SCRIPT", "single", losses=GOOD_LOSSES[:4]),
     "need >= 6"),
    (lambda mt, ms, mp: mt("TRAIN_SCRIPT", "single", losses=[11.9] * 7 + [12.5]),
     "did not fall"),
    (lambda mt, ms, mp: mt("TRAIN_SCRIPT", "single",
                       losses=[5.0, 4.0, 3.0, 2.5, 2.2, 2.0]), "ln(151936)"),
    (lambda mt, ms, mp: mt("TRAIN_SCRIPT", "single",
                       losses=[11.9, 11.0, float("nan"), 9, 8, 7.0]),
     "non-finite"),
    (lambda mt, ms, mp: ms(n_answered=3), "3 results for 4"),
    (lambda mt, ms, mp: ms(short=2), "30 new tokens"),
    (lambda mt, ms, mp: ms(drift=1), "after 0 new tokens"),
    (lambda mt, ms, mp: ms(drift=1, drift_from=1), "after 1 new tokens"),
    (lambda mt, ms, mp: ms(drift=1, drift_from=5, drift_reqs=(0, 1, 2)),
     "share only 0.367"),
    (lambda mt, ms, mp: mt("TRAIN_SCRIPT", "single", program_line=False),
     "no step_program line"),
    (lambda mt, ms, mp: mp(ok=False, err=0.5), "kernel_parity: child exited"),
    (lambda mt, ms, mp: mp(refused=3), "kernel_parity: child exited"),
    (lambda mt, ms, mp: mp(platform="cpu"), "platform 'cpu'"),
], ids=["train-on-cpu", "serve-on-cpu", "train-exit-3", "serve-exit-1",
        "kernel-not-forced", "too-few-steps", "loss-not-falling",
        "loss-not-from-ln-vocab", "loss-nan", "missing-request",
        "short-request", "kernel-wrong-from-the-prefill",
        "kernel-wrong-from-the-first-decode-step",
        "most-requests-part-early", "no-program-line",
        "a-kernel-over-its-bound", "a-sabotage-not-refused",
        "parity-on-cpu"])
def test_one_chip_failures_print_no_ok_line(fakes, capsys, break_it, says):
    break_it(fakes[0], fakes[1], fakes[3])
    rc, lines, err = _run(capsys)
    assert rc != 0
    assert not any('"ok"' in l for l in lines)
    assert "chip_smoke FAILED" in err and says in err


def test_child_over_its_time_limit_is_stopped(fakes, capsys, monkeypatch,
                                              tmp_path):
    sleeper = tmp_path / "sleeper.py"
    sleeper.write_text("import time; time.sleep(600)")
    monkeypatch.setattr(chip_smoke, "TRAIN_SCRIPT", sleeper)
    monkeypatch.setattr(chip_smoke, "TRAIN_LIMIT_S", 1.0)
    rc, lines, err = _run(capsys)
    assert rc != 0 and "limit" in err and not any('"ok"' in l for l in lines)


def test_four_chips_runs_only_the_two_training_children(fakes, capsys):
    rc, lines, _ = _run(capsys, ["--chips", "4"])
    assert rc == 0
    assert fakes[2].read_text().split() == ["fsdp", "single"]   # no serve
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}


@pytest.mark.parametrize("kw,says", [
    (dict(mem=(9, 0, 0, 0)), "all non-zero"),
    (dict(mem=(9, 3, 3, 3)), "spread wider"),
    (dict(collectives=_summary({"all-reduce": 2})), "no parameter all-gather"),
    (dict(collectives=_summary({"all-gather": 25, "all-reduce": 30})),
     "no gradient reduce-scatter"),
    (dict(collectives=_summary({"all-gather": 25, "reduce-scatter-fusion": 1,
                                "all-reduce": 30}, 12_582_912)),
     "a gradient is all-reduced"),
    (dict(losses=[11.9, 11.5, 11.0, 10.5, 10.0, 9.5, 9.0, 8.5]),
     "differ by"),
    (dict(count=1), "want 4 devices"),
], ids=["state-on-one-device", "uneven-shards", "no-gather",
        "no-reduce-scatter", "gradient-all-reduced",
        "trajectory-differs", "one-device-only"])
def test_four_chips_failures(fakes, capsys, kw, says):
    fakes[0]("FSDP_SCRIPT", "fsdp", **{"count": 4, **kw})
    rc, lines, err = _run(capsys, ["--chips", "4"])
    assert rc != 0 and says in err
    assert not any('"ok"' in l for l in lines)


# ---- one compile cache ------------------------------------------------------

@pytest.mark.parametrize("env_dir", [None, "/some/where/else"],
                         ids=["unset", "env-set"])
def test_compile_cache_helper(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR set -> no directory is set in code (JAX
    reads the variable); unset -> the fixed <checkout>/.jax_cache."""
    from distributed_training_guide_tpu.utils import compile_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    if env_dir is None:
        monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    else:
        monkeypatch.setenv(compile_cache.CACHE_ENV, env_dir)
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        lambda fn: updates.__setitem__("listener", fn))
    use = compile_cache.enable_compile_cache()
    if env_dir is None:
        assert use.directory == str(REPO / ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == use.directory
    else:
        assert use.directory == env_dir
        assert "jax_compilation_cache_dir" not in updates
    # hits and misses are JAX's own events, counted per process
    for event in ("/jax/compilation_cache/cache_hits",
                  "/jax/compilation_cache/cache_misses",
                  "/jax/compilation_cache/cache_hits", "/other"):
        updates["listener"](event)
    assert (use.hits, use.misses) == (2, 1)
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_only_the_helper_names_the_cache_directory():
    hits = subprocess.run(
        ["grep", "-rln", "--include=*.py", "jax_compilation_cache_dir",
         "distributed_training_guide_tpu", "chip_smoke.py",
         "tests/conftest.py", "01-single-chip",
         "04-fully-sharded-data-parallel"],
        cwd=REPO, capture_output=True, text=True).stdout.split()
    assert hits == ["distributed_training_guide_tpu/utils/compile_cache.py"]


# ---- no invented peaks, no quiet stand-ins ---------------------------------

@pytest.mark.parametrize("kind,flops", [
    ("TPU v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
    ("TPU v4", 275e12)])
def test_device_peak_flops_known_kinds(kind, flops):
    from distributed_training_guide_tpu.utils.mfu import device_peak_flops

    assert device_peak_flops(device_kind=kind) == flops


@pytest.mark.parametrize("kind", ["cpu", "TPU v9", "", "NVIDIA H100"])
def test_device_peak_flops_unknown_kind_raises(kind):
    from distributed_training_guide_tpu.utils.mfu import (
        device_ici_bandwidth, device_peak_flops)

    with pytest.raises(ValueError, match="no peak"):
        device_peak_flops(device_kind=kind)
    with pytest.raises(ValueError, match="no peak"):
        device_ici_bandwidth(device_kind=kind)


def test_local_cpu_device_has_no_peak_and_no_mfu():
    from distributed_training_guide_tpu.utils.mfu import (compute_mfu,
                                                          device_peak_flops)

    with pytest.raises(ValueError, match="'cpu'"):
        device_peak_flops()
    with pytest.raises(ValueError, match="'cpu'"):
        compute_mfu(1.0, 1.0)


class _Dev:
    def __init__(self, platform, stats):
        self.platform, self._stats = platform, stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def test_memory_stats_zeros_only_on_cpu():
    from distributed_training_guide_tpu.utils.memory import get_mem_stats

    assert get_mem_stats(_Dev("cpu", None))["peak_alloc_gb"] == 0.0
    assert get_mem_stats(_Dev("tpu", {"bytes_in_use": 2e9,
                                      "peak_bytes_in_use": 3e9}))[
        "peak_alloc_gb"] == 3.0
    with pytest.raises(RuntimeError, match="no memory_stats"):
        get_mem_stats(_Dev("tpu", None))
    with pytest.raises(OSError):
        get_mem_stats(_Dev("tpu", OSError("runtime lost")))


def test_interpret_on_a_tpu_backend_is_an_error(monkeypatch):
    from distributed_training_guide_tpu.ops import dispatch

    assert dispatch.resolve_interpret(None) is True        # here: the CPU
    assert dispatch.resolve_interpret(False) is False
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    assert dispatch.resolve_interpret(None) is False
    with pytest.raises(RuntimeError, match="interpret mode on a TPU"):
        dispatch.resolve_interpret(True)


@pytest.mark.parametrize("impl,backend,seq,head_dim,want", [
    ("auto", "tpu", 2048, 128, "flash"),
    ("auto", "tpu", 100, 128, "xla"),
    ("auto", "cpu", 2048, 128, "xla"),
    ("flash", "cpu", 100, 16, "flash"),
    ("xla", "tpu", 2048, 128, "xla"),
])
def test_attention_auto_says_what_it_chose(monkeypatch, impl, backend, seq,
                                           head_dim, want):
    from distributed_training_guide_tpu.ops import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: backend)
    got, reason = attention.resolve_attention_impl(impl, seq, seq, head_dim)
    assert got == want
    assert reason.startswith("auto:" if impl == "auto" else "forced")


def test_attention_record_holds_what_one_trace_took():
    """What the trainer's start-up line reports: the implementations traced
    inside the block, first reason each, nothing from outside it."""
    from distributed_training_guide_tpu.ops import dispatch

    dispatch.note_attention("xla", "no block open: dropped")
    with dispatch.record_attention() as record:
        dispatch.note_attention("flash", "forced")
        dispatch.note_attention("flash", "again")
        dispatch.note_attention("xla", "fallback")
    dispatch.note_attention("ring", "after the block: dropped")
    assert dispatch.describe_attention(record) == ("flash+xla",
                                                   "forced; fallback")
    assert dispatch.describe_attention({})[0] == "none"


def test_forced_flash_raises_on_a_shape_the_kernel_cannot_take():
    import jax.numpy as jnp

    from distributed_training_guide_tpu.ops.flash_attention import (
        flash_attention)

    q = jnp.zeros((1, 100, 2, 16))
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, q, q, interpret=False)


def test_native_loader_build_failure_is_an_error(monkeypatch, tmp_path):
    """--native-loader where the library cannot be built raises; it does not
    warn and carry on with the Python loader."""
    from distributed_training_guide_tpu.data import native_loader

    (tmp_path / "token_loader.cpp").write_text("this is not C++")
    monkeypatch.setattr(native_loader, "_CSRC", tmp_path)
    monkeypatch.setattr(native_loader, "_LIB", None)
    with pytest.raises(RuntimeError, match="native loader build failed"):
        native_loader.get_library()
    assert not list(tmp_path.glob("*.so*"))      # no half-built library left
    assert native_loader.native_available() is False


# ---- one JAX process per TPU host -------------------------------------------

def test_local_launcher_refuses_several_jax_ranks_off_cpu(monkeypatch):
    from distributed_training_guide_tpu.launch.local import launch_gang

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(ValueError, match="one process drives all local"):
        launch_gang([sys.executable, "-c", "pass"], nproc=4)
    # one rank is fine anywhere; several are fine when held to the CPU
    assert launch_gang([sys.executable, "-c", "pass"], nproc=1) == 0
    assert launch_gang([sys.executable, "-c", "pass"], nproc=2,
                       devices_per_proc=1) == 0
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert launch_gang([sys.executable, "-c", "pass"], nproc=2) == 0
