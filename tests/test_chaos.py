"""Chaos drills: deterministic fault injection through the REAL entry points
(ISSUE 1 acceptance criteria).

- SIGKILL a supervised training run at step N; the supervisor restarts it,
  the restart resumes from the last checkpoint, and the stitched loss
  trajectory equals an uninterrupted golden run.
- Corrupt the latest checkpoint after a run; the next resume falls back to
  the previous valid checkpoint via the manifest chain and continues with
  the golden trajectory from there.
- Inject a NaN loss at a chosen step; the `skip` guard policy drops exactly
  that update and finishes, the `abort` policy dies with a machine-readable
  error file naming the step.

Subprocess drills share the multi-process suite's persistent compile cache
and are individually time-bounded; the faults themselves are the env-var
switches documented in ``diagnosing-errors/README.md`` ("Failure drills"),
so these tests are also executable documentation.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from distributed_training_guide_tpu.utils import faults

REPO = Path(__file__).parent.parent
CH02 = REPO / "02-distributed-data-parallel" / "train_llm.py"

pytestmark = pytest.mark.chaos

TRAIN_FLAGS = ["-m", "llama-debug", "-d", "synthetic:60000", "-s", "64",
               "-b", "1", "--num-epochs", "2", "--log-freq", "1"]


def _env(**extra):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # the children share the suite's compile cache: the environment goes
    # through, and utils/compile_cache.py decides the directory
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.update(extra)
    return env


def losses_by_step(text: str) -> dict:
    import ast

    out = {}
    for line in text.splitlines():
        at = line.find("INFO:{")
        if at >= 0:
            try:
                d = ast.literal_eval(line[at + 5:])
            except (ValueError, SyntaxError):
                continue
            if isinstance(d, dict) and "global_step" in d:
                out[d["global_step"]] = d["running_loss"]
    return out


def run_ch02(flags, *, env_extra=None, timeout=420):
    proc = subprocess.run([sys.executable, str(CH02), *TRAIN_FLAGS, *flags],
                          capture_output=True, text=True, timeout=timeout,
                          cwd=REPO, env=_env(**(env_extra or {})))
    return proc.returncode, proc.stdout + proc.stderr


def test_sigkill_restart_resume_matches_uninterrupted(tmp_path):
    """The headline drill: DTG_FAULT_CRASH_STEP SIGKILLs the worker right
    after the step-4 checkpoint publishes; the supervisor restarts it; the
    restart resumes from checkpoint-4 and finishes steps 5-6. The stitched
    per-step losses must EQUAL (not approximate) the uninterrupted run's."""
    rc, golden_text = run_ch02(["--max-steps", "6",
                                "--save-dir", str(tmp_path / "golden")])
    assert rc == 0, golden_text[-3000:]
    golden = losses_by_step(golden_text)
    assert set(golden) == {1, 2, 3, 4, 5, 6}

    work = tmp_path / "work"
    sup_logs = tmp_path / "sup"
    cmd = [sys.executable, "-m",
           "distributed_training_guide_tpu.launch.supervisor",
           "--max-restarts", "2", "--restart-backoff", "0.05",
           "--log-dir", str(sup_logs), "--",
           sys.executable, str(CH02), *TRAIN_FLAGS,
           "--max-steps", "6", "--ckpt-freq", "2",
           "-e", "drill", "--save-dir", str(work)]
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=600, cwd=REPO,
        env=_env(**{faults.ENV_CRASH_STEP: "4"}))
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    assert "attempt 0 failed rc=-9" in proc.stdout     # really SIGKILLed
    assert "attempt 1 exited cleanly" in proc.stdout

    def attempt_text(n):
        d = sup_logs / f"attempt_{n}"
        return ((d / "stdout.log").read_text()
                + (d / "stderr.log").read_text())

    first = losses_by_step(attempt_text(0))
    assert set(first) == {1, 2, 3, 4}                  # died after step 4
    second_text = attempt_text(1)
    assert "Resumed=True" in second_text
    second = losses_by_step(second_text)
    assert set(second) == {5, 6}                       # fast-forwarded
    stitched = {**first, **second}
    for step in golden:
        assert stitched[step] == golden[step], (step, stitched, golden)

    # the supervisor wired a heartbeat file and the loop actually beat it
    hb = json.loads((sup_logs / "attempt_1" / "heartbeat.json").read_text())
    assert hb["step"] >= 5


def test_corrupt_latest_falls_back_and_continues(tmp_path):
    """Run to step 5 with checkpoints at 2 and 4 (keep-n retention), corrupt
    checkpoint-4's shard bytes, then resume: restore must fall back to
    checkpoint-2 via the manifest chain and replay steps 3-5 with the same
    losses the first run logged."""
    exp = ["--ckpt-freq", "2", "-e", "drill", "--save-dir", str(tmp_path)]
    rc, first_text = run_ch02(["--max-steps", "5", *exp])
    assert rc == 0, first_text[-3000:]
    first = losses_by_step(first_text)
    assert set(first) == {1, 2, 3, 4, 5}
    state = json.loads((tmp_path / "drill" / "state.json").read_text())
    assert state["retained"] == ["checkpoint-4", "checkpoint-2"]

    victim = faults.corrupt_checkpoint_dir(tmp_path / "drill" / "checkpoint-4")
    assert victim is not None

    rc, second_text = run_ch02(["--max-steps", "5", *exp])
    assert rc == 0, second_text[-3000:]
    assert "skipping checkpoint checkpoint-4" in second_text
    assert "Resumed=True" in second_text
    second = losses_by_step(second_text)
    assert set(second) == {3, 4, 5}                    # resumed from step 2
    for step in second:
        assert second[step] == first[step], (step, second, first)


def test_corruption_fault_env_var(tmp_path):
    """DTG_FAULT_CORRUPT_CKPT_STEP corrupts the published checkpoint from
    INSIDE the save path (after manifest + state.json) — the operator-facing
    spelling of the drill above."""
    exp = ["--ckpt-freq", "2", "-e", "drill", "--save-dir", str(tmp_path)]
    rc, text = run_ch02(["--max-steps", "4", *exp],
                        env_extra={faults.ENV_CORRUPT_CKPT_STEP: "4"})
    assert rc == 0, text[-3000:]

    from distributed_training_guide_tpu.checkpoint import (load_manifest,
                                                           verify_manifest)

    exp_dir = tmp_path / "drill"
    man = load_manifest(exp_dir, "checkpoint-4")
    assert man is not None
    assert verify_manifest(exp_dir / "checkpoint-4", man)   # really corrupt
    man2 = load_manifest(exp_dir, "checkpoint-2")
    assert verify_manifest(exp_dir / "checkpoint-2", man2) == []


# ---- NaN drills (in-process: the guard work is inside the jitted step) ------

def _nan_args(tmp_path, **over):
    from distributed_training_guide_tpu.train.cli import get_parser

    args = get_parser().parse_args(["-m", "llama-debug"])
    args.dataset_name = "synthetic:60000"
    args.seq_length = 64
    args.batch_size = 1
    args.num_epochs = 1
    args.log_freq = 2
    args.max_steps = 4
    args.save_dir = str(tmp_path)
    for k, v in over.items():
        setattr(args, k, v)
    return args


def test_nan_skip_policy_finishes_run(tmp_path, eight_devices, monkeypatch):
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan
    from distributed_training_guide_tpu.train.cli import run_training

    monkeypatch.setenv(faults.ENV_NAN_LOSS_STEP, "1")
    out = run_training(_nan_args(tmp_path, guard_policy="skip"),
                       lambda: make_plan("ddp", make_mesh()))
    assert out["host_state"]["global_step"] == 4
    assert out["last_info"]["guard_skipped"] == 1      # exactly one skip
    assert np.isfinite(out["last_info"]["running_loss"])


def test_nan_abort_policy_writes_error_file(tmp_path, eight_devices, monkeypatch):
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan
    from distributed_training_guide_tpu.train.cli import run_training
    from distributed_training_guide_tpu.train.guards import NonFiniteLossError

    err = tmp_path / "error.json"
    monkeypatch.setenv("ERROR_FILE", str(err))
    monkeypatch.setenv(faults.ENV_NAN_LOSS_STEP, "1")
    with pytest.raises(NonFiniteLossError, match="step 2"):
        run_training(_nan_args(tmp_path, guard_policy="abort"),
                     lambda: make_plan("ddp", make_mesh()))
    msg = json.loads(err.read_text())["message"]
    assert "NonFiniteLossError" in msg["error"]
    assert "'loss'" in msg["error"]        # offending metrics are recorded
    # the supervisor would classify this as a poison pill: no restart loop
    from distributed_training_guide_tpu.launch.errors import classify_error

    assert classify_error({"message": msg}) == "non-finite"


def test_crash_fault_exception_mode(tmp_path, eight_devices, monkeypatch):
    """DTG_FAULT_CRASH_MODE=exc raises instead of SIGKILL — the drill for
    the @record error-file path (SIGKILL mode can't write one)."""
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan
    from distributed_training_guide_tpu.train.cli import run_training

    monkeypatch.setenv(faults.ENV_CRASH_STEP, "2")
    monkeypatch.setenv(faults.ENV_CRASH_MODE, "exc")
    with pytest.raises(RuntimeError, match="injected fault: crash at global step 2"):
        run_training(_nan_args(tmp_path), lambda: make_plan("ddp", make_mesh()))
