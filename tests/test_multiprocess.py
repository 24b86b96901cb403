"""True multi-process execution tests (VERDICT r3 item 3).

The reference actually runs N processes (``torchrun --standalone``, reference
``02-distributed-data-parallel/README.md:96``); through round 3 every test in
this repo was one process with 8 virtual devices, so ``launch/distributed.py``,
the procguards barriers, per-process shard materialization in
``data/loader.py``, and multihost Orbax save/restore had never run in the
regime they exist for. These tests spawn REAL gangs — 2 processes x 4 virtual
CPU devices, rendezvousing through jax.distributed's TCP coordinator via the
``MASTER_ADDR``/``WORLD_SIZE``/``RANK`` env contract — and drive the real
chapter entry points end to end:

- ddp training whose loss trajectory matches the same config single-process
  (the global computation is process-layout-invariant);
- fsdp (params sharded ACROSS processes) training;
- checkpoint save + cross-restart resume, bit-exact vs uninterrupted;
- process0_first ordering with real barriers;
- supervisor restart-all around a gang where one rank crashes, resuming
  from the last checkpoint (torchrun elasticity, reference
  ``related-topics/elastic-training/README.md:5-16``).

Each gang is a fresh OS process group, so steps are compiled per gang; a
shared persistent XLA compile cache keeps the suite's wall time sane.
"""
import ast
import json
import os
import subprocess
import sys
from pathlib import Path

import jax

REPO = Path(__file__).parent.parent
CH02 = REPO / "02-distributed-data-parallel" / "train_llm.py"
CH04 = REPO / "04-fully-sharded-data-parallel" / "train_llm.py"

TRAIN_FLAGS = ["-m", "llama-debug", "-d", "synthetic:60000", "-s", "64",
               "-b", "1", "--num-epochs", "2", "--log-freq", "1"]


def _clean_env(**extra) -> dict:
    """Worker env: the launcher overrides the conftest's 8-device XLA_FLAGS
    with per-process counts; the compile cache is the suite's own
    (utils/compile_cache.py decides the directory in every process)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


def run_gang(worker_cmd: list, *, nproc: int = 2, devices: int = 4,
             timeout: int = 600, env: dict | None = None,
             log_dir: str | None = None) -> tuple:
    """Launch a gang via the real ``launch.local`` CLI; returns
    (rc, rank0_text, [rankN_text...])."""
    cmd = [sys.executable, "-m", "distributed_training_guide_tpu.launch.local",
           "--nproc", str(nproc), "--devices-per-proc", str(devices)]
    if log_dir:
        cmd += ["--log-dir", log_dir]
    cmd += ["--"] + worker_cmd
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout,
                          env=env or _clean_env(), cwd=REPO)
    rank0 = proc.stdout + proc.stderr
    others = []
    if log_dir:
        for rank in range(1, nproc):
            text = ""
            for suffix in ("out", "err"):
                p = Path(log_dir) / f"rank{rank}.{suffix}"
                if p.exists():
                    text += p.read_text()
            others.append(text)
    return proc.returncode, rank0, others


def parse_info_lines(text: str) -> list:
    """The training loop logs metric dicts (``INFO:{'global_step': ...}``);
    pull them back out of the process logs."""
    infos = []
    for line in text.splitlines():
        at = line.find("INFO:{")
        if at >= 0:
            try:
                d = ast.literal_eval(line[at + 5:])
            except (ValueError, SyntaxError):
                continue
            if isinstance(d, dict) and "global_step" in d:  # skip env dumps
                infos.append(d)
    return infos


def losses_by_step(text: str) -> dict:
    return {i["global_step"]: i["running_loss"] for i in parse_info_lines(text)}


def mp_results(text: str) -> list:
    return [json.loads(line.split("MPRESULT ", 1)[1])
            for line in text.splitlines() if line.startswith("MPRESULT ")]


def single_process_losses(script, flags: list, save_dir) -> dict:
    """Golden: the same chapter entry on 1 process x 8 virtual devices."""
    sp = subprocess.run(
        [sys.executable, str(script), *flags, "--save-dir", str(save_dir)],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env=_clean_env(JAX_PLATFORMS="cpu",
                       XLA_FLAGS="--xla_force_host_platform_device_count=8"))
    assert sp.returncode == 0, (sp.stdout + sp.stderr)[-3000:]
    return losses_by_step(sp.stdout + sp.stderr)


def test_gang_ddp_matches_single_process(tmp_path):
    """2 procs x 4 devices and 1 proc x 8 devices build the same dp=8 mesh
    over the same global batch: the logged loss trajectory must agree. This
    is the process-layout invariance the reference gets from DDP's defined
    semantics — here it also proves the loader's per-process shard
    materialization feeds the same global arrays."""
    worker = [sys.executable, str(CH02), *TRAIN_FLAGS, "--max-steps", "6",
              "--save-dir", str(tmp_path / "mp")]
    rc, rank0, (rank1,) = run_gang(worker, log_dir=str(tmp_path / "logs"))
    assert rc == 0, rank0[-3000:]
    mp_losses = losses_by_step(rank0)
    assert set(mp_losses) == {1, 2, 3, 4, 5, 6}

    # ranks log the same (replicated) loss values
    rank1_losses = losses_by_step(rank1)
    assert rank1_losses == mp_losses

    # single-process golden at the same global config
    sp_losses = single_process_losses(
        CH02, [*TRAIN_FLAGS, "--max-steps", "6"], tmp_path / "sp")
    assert set(sp_losses) == set(mp_losses)
    for step, loss in mp_losses.items():
        # identical global math; only collective reduction order may differ
        assert abs(loss - sp_losses[step]) < 1e-4, (step, loss, sp_losses[step])


def test_gang_fence_every_matches_per_step(tmp_path):
    """--fence-every across a REAL process boundary: each process banks its
    own device-loss reads and drains at the (log-freq) boundary; the logged
    running_loss windows must equal a per-step-fenced single-process run.
    log-freq 3 (not 1) so the fence group actually runs at depth 3."""
    assert TRAIN_FLAGS[-2:] == ["--log-freq", "1"]
    flags = TRAIN_FLAGS[:-1] + ["3"]
    worker = [sys.executable, str(CH02), *flags, "--max-steps", "6",
              "--fence-every", "3", "--save-dir", str(tmp_path / "mp")]
    rc, rank0, (rank1,) = run_gang(worker, log_dir=str(tmp_path / "logs"))
    assert rc == 0, rank0[-3000:]
    mp_losses = losses_by_step(rank0)
    assert set(mp_losses) == {3, 6}
    assert losses_by_step(rank1) == mp_losses

    sp_losses = single_process_losses(
        CH02, [*flags, "--max-steps", "6"], tmp_path / "sp")
    assert set(sp_losses) == set(mp_losses)
    for step, loss in mp_losses.items():
        assert abs(loss - sp_losses[step]) < 1e-4, (step, loss, sp_losses)


def test_gang_fsdp_trains_with_cross_process_shards(tmp_path):
    """fsdp shards every parameter over all 8 devices, i.e. ACROSS the two
    processes: init, step collectives, and the loader all have to handle
    arrays where each process owns only half the shards."""
    worker = [sys.executable, str(CH04), *TRAIN_FLAGS, "--max-steps", "4",
              "--checkpoint-activations", "--save-dir", str(tmp_path / "out")]
    rc, rank0, (rank1,) = run_gang(worker, log_dir=str(tmp_path / "logs"))
    assert rc == 0, rank0[-3000:]
    losses = losses_by_step(rank0)
    assert set(losses) == {1, 2, 3, 4}
    # 4 steps at the default lr is noise-level: assert sane, not "learning"
    assert all(5.0 < v < 7.5 for v in losses.values()), losses
    assert losses_by_step(rank1) == losses
    assert "strategy=fsdp" in rank0


def test_gang_tp_spans_process_boundary(tmp_path):
    """tp=8 on a 2-process x 4-device gang: every tensor-parallel group
    crosses the process boundary, so the per-layer megatron all-reduces run
    over the inter-process transport (the DCN analogue) — the sharding
    regime chapter 6 documents but no single-process test can produce."""
    worker = [sys.executable, str(REPO / "06-tensor-parallel" / "train_llm.py"),
              *TRAIN_FLAGS, "--max-steps", "3", "--tensor-parallel", "8",
              "--save-dir", str(tmp_path / "out")]
    rc, rank0, (rank1,) = run_gang(worker, log_dir=str(tmp_path / "logs"))
    assert rc == 0, rank0[-3000:]
    losses = losses_by_step(rank0)
    assert set(losses) == {1, 2, 3}
    assert all(5.0 < v < 7.5 for v in losses.values()), losses
    assert losses_by_step(rank1) == losses
    assert "'tp': 8" in rank0


def test_gang_ring_cp_spans_process_boundary(tmp_path):
    """cp=8 on a 2-process x 4-device gang: the zigzag ring's ppermute hops
    cross the process boundary every cycle — the long-context regime a
    real pod runs (ring over ICI/DCN), never reachable single-process."""
    worker = [sys.executable, str(REPO / "08-context-parallel" / "train_llm.py"),
              *TRAIN_FLAGS, "--max-steps", "3", "--context-parallel", "8",
              "--attn-impl", "xla", "--save-dir", str(tmp_path / "out")]
    rc, rank0, (rank1,) = run_gang(worker, log_dir=str(tmp_path / "logs"))
    assert rc == 0, rank0[-3000:]
    losses = losses_by_step(rank0)
    assert set(losses) == {1, 2, 3}
    assert all(5.0 < v < 7.5 for v in losses.values()), losses
    assert losses_by_step(rank1) == losses
    assert "'cp': 8" in rank0


def test_gang_pipeline_stage_per_process(tmp_path):
    """pp=2 on a 2-process x 4-device gang with the pp axis outermost:
    each pipeline stage lives on one process, so every 1F1B activation /
    cotangent handoff crosses the process boundary — how a pod actually
    runs pipeline parallelism (stages over DCN)."""
    worker = [sys.executable, str(REPO / "09-pipeline-parallel" / "train_llm.py"),
              *TRAIN_FLAGS, "-b", "4",   # microbatch (gb/4) must cover dp=4
              "--max-steps", "3", "--pipeline-parallel", "2",
              "--save-dir", str(tmp_path / "out")]
    rc, rank0, (rank1,) = run_gang(worker, log_dir=str(tmp_path / "logs"))
    assert rc == 0, rank0[-3000:]
    losses = losses_by_step(rank0)
    assert set(losses) == {1, 2, 3}
    assert all(5.0 < v < 7.5 for v in losses.values()), losses
    assert losses_by_step(rank1) == losses
    assert "'pp': 2" in rank0


def test_gang_moe_ep_spans_process_boundary(tmp_path):
    """ep=8 on a 2-process x 4-device gang: the MoE token all-to-all
    dispatches across the process boundary (each process hosts half the
    experts). With ddp/fsdp (all-reduce/all-gather), tp (per-layer
    reductions), and ring cp (ppermute) above, this completes the
    cross-process coverage of every collective family the framework emits."""
    worker = [sys.executable,
              str(REPO / "10-mixture-of-experts" / "train_llm.py"),
              "-m", "moe-debug", "-d", "synthetic:60000", "-s", "64",
              "-b", "1", "--num-epochs", "2", "--log-freq", "1",
              "--max-steps", "3", "--expert-parallel", "8",
              "--save-dir", str(tmp_path / "out")]
    rc, rank0, (rank1,) = run_gang(worker, log_dir=str(tmp_path / "logs"))
    assert rc == 0, rank0[-3000:]
    losses = losses_by_step(rank0)
    assert set(losses) == {1, 2, 3}
    assert all(5.0 < v < 7.5 for v in losses.values()), losses
    assert losses_by_step(rank1) == losses
    assert "'ep': 8" in rank0


def test_gang_checkpoint_resume_bitexact(tmp_path):
    """Multihost Orbax save (every process writes its shards, process 0
    swings state.json behind a barrier) + restore in a FRESH gang, compared
    bit-exact against an uninterrupted run — the reference's resume contract
    (01:94) upgraded to the multi-process regime."""
    exp = ["--ckpt-freq", "3", "-e", "resume", "--save-dir", str(tmp_path)]

    worker3 = [sys.executable, str(CH02), *TRAIN_FLAGS, "--max-steps", "3", *exp]
    rc, out3, _ = run_gang(worker3, log_dir=str(tmp_path / "l1"))
    assert rc == 0, out3[-3000:]
    assert "Resumed=False" in out3
    assert (tmp_path / "resume" / "state.json").exists()

    worker6 = [sys.executable, str(CH02), *TRAIN_FLAGS, "--max-steps", "6", *exp]
    rc, out6, _ = run_gang(worker6, log_dir=str(tmp_path / "l2"))
    assert rc == 0, out6[-3000:]
    assert "Resumed=True" in out6
    resumed = losses_by_step(out6)
    assert set(resumed) == {4, 5, 6}      # fast-forwarded past steps 1-3

    # uninterrupted 6-step gang in a fresh experiment dir
    gold = [sys.executable, str(CH02), *TRAIN_FLAGS, "--max-steps", "6",
            "--ckpt-freq", "3", "-e", "gold", "--save-dir", str(tmp_path)]
    rc, outg, _ = run_gang(gold, log_dir=str(tmp_path / "l3"))
    assert rc == 0, outg[-3000:]
    golden = losses_by_step(outg)
    for step in (4, 5, 6):
        assert resumed[step] == golden[step], (step, resumed[step], golden[step])


def test_gang_procguards_ordering(tmp_path):
    """process0_first over real processes: rank 1 must observe the file rank
    0 wrote inside the guard, despite rank 0 sleeping first."""
    worker = [sys.executable, str(REPO / "tests" / "mp_worker.py"), "guard",
              "--dir", str(tmp_path)]
    rc, rank0, (rank1,) = run_gang(worker, log_dir=str(tmp_path / "logs"))
    assert rc == 0, rank0[-3000:]
    results = {r["rank"]: r for r in mp_results(rank0) + mp_results(rank1)}
    assert results[0]["world"] == 2
    assert results[1]["saw_marker_on_entry"] is True


def test_gang_loader_materializes_only_local_shards(tmp_path):
    """The per-host data-footprint claim, measured: over a full epoch each
    process fetches exactly its 1/nproc share of every batch's rows from the
    corpus (so a disk-backed corpus costs each host ~batch/nproc RAM), and
    every addressable shard's content matches direct corpus indexing."""
    worker = [sys.executable, str(REPO / "tests" / "mp_worker.py"), "loader",
              "--dir", str(tmp_path)]
    rc, rank0, (rank1,) = run_gang(worker, log_dir=str(tmp_path / "logs"))
    assert rc == 0, rank0[-3000:]
    results = {r["rank"]: r for r in mp_results(rank0) + mp_results(rank1)}
    assert set(results) == {0, 1}
    for r in results.values():
        assert r["content_ok"] is True
        assert r["n_batches"] > 50
        # exactly half of every batch's rows, never the global batch
        assert r["rows_fetched"] == r["n_batches"] * r["global_batch"] // 2


def test_supervisor_restarts_gang_and_resumes(tmp_path):
    """The torchrun-elasticity loop end to end: rank 1 crashes after the
    step-3 checkpoint; fail-fast takes the gang down; the supervisor
    restarts it as a unit; the restarted gang resumes from the checkpoint
    and finishes. Also pins the @record error-file contract per rank."""
    work = tmp_path / "work"
    work.mkdir()
    sup_logs = tmp_path / "sup"
    cmd = [sys.executable, "-m",
           "distributed_training_guide_tpu.launch.supervisor",
           "--max-restarts", "2", "--log-dir", str(sup_logs), "--",
           sys.executable, "-m", "distributed_training_guide_tpu.launch.local",
           "--nproc", "2", "--devices-per-proc", "4",
           "--log-dir", str(tmp_path / "ranks"), "--",
           sys.executable, str(REPO / "tests" / "mp_worker.py"),
           "crash_train", "--dir", str(work)]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                          env=_clean_env(), cwd=REPO)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    assert "attempt 0 failed" in proc.stdout
    assert "attempt 1 exited cleanly" in proc.stdout

    # rank 1's injected failure was captured machine-readably (@record)
    err_file = sup_logs / "attempt_0" / "error.json.rank1"
    assert err_file.exists()
    payload = json.loads(err_file.read_text())
    assert "injected failure" in payload["message"]["error"]
    assert payload["message"]["process_index"] == 1

    # the restarted gang resumed from the step-3 checkpoint and finished
    attempt1_out = (sup_logs / "attempt_1" / "stdout.log").read_text() + \
        (sup_logs / "attempt_1" / "stderr.log").read_text()
    assert "Resumed=True" in attempt1_out
    results = mp_results(attempt1_out)
    assert results and results[0]["global_step"] == 8
