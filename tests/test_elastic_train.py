"""Elastic training runtime: mesh-resharding restore with loud
incompatible-layout failures (checkpoint/reshard.py), the world-agreement
protocol, and the supervisor's slice-loss renegotiation drill
(launch/elastic.py + launch/supervisor.py)."""
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.checkpoint import (
    CheckpointIO, ReshardIncompatibleError, abstract_train_state,
    check_reshard_compatibility, describe_layout, mesh_descriptor,
    restore_train_state, stamp_host_state)
from distributed_training_guide_tpu.launch import elastic as el
from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.parallel import make_mesh, make_plan
from distributed_training_guide_tpu.train import Trainer, adamw_cosine
from distributed_training_guide_tpu.train.precision import PrecisionPolicy
from distributed_training_guide_tpu.train.state import host_state_dict
from distributed_training_guide_tpu.utils import faults

pytestmark = pytest.mark.elastic

REPO = Path(__file__).parent.parent


# ---------------------------------------------------------------------------
# reshard compatibility (unit level: pure descriptors)
# ---------------------------------------------------------------------------

def _desc(**over):
    base = {"axes": {"fsdp": 8}, "device_count": 8, "strategy": "fsdp",
            "pp_stages": 1, "quant_block": None}
    base.update(over)
    return base


def test_compat_same_layout_is_silent():
    assert check_reshard_compatibility(_desc(), _desc()) is False


def test_compat_unstamped_checkpoint_allowed():
    assert check_reshard_compatibility(None, _desc()) is False
    assert check_reshard_compatibility({}, _desc()) is False


def test_compat_mesh_refactorization_is_a_reshard():
    target = _desc(axes={"fsdp": 4}, device_count=4)
    assert check_reshard_compatibility(_desc(), target) is True
    # tp <-> fsdp refactorization at the same device count too
    target = _desc(axes={"tp": 4, "fsdp": 2}, strategy="tp_fsdp")
    assert check_reshard_compatibility(_desc(), target) is True


def test_compat_pp_stage_split_fails_naming_both():
    saved = _desc(axes={"pp": 2, "fsdp": 4}, strategy="pp_fsdp",
                  pp_stages=2)
    with pytest.raises(ReshardIncompatibleError) as exc:
        check_reshard_compatibility(saved, _desc())
    msg = str(exc.value)
    assert "2-stage" in msg and "1 stage" in msg
    assert describe_layout(saved) in msg and describe_layout(_desc()) in msg
    assert exc.value.saved == saved and exc.value.target == _desc()


def test_compat_quant_block_tiling_fails_naming_both():
    saved = _desc(quant_block=64)
    target = _desc(quant_block=128)
    with pytest.raises(ReshardIncompatibleError) as exc:
        check_reshard_compatibility(saved, target)
    msg = str(exc.value)
    assert "block size 64" in msg and "block size 128" in msg
    # one side unquantized is NOT a tiling mismatch (the precision-policy
    # stamp owns that failure mode)
    assert check_reshard_compatibility(_desc(quant_block=None),
                                       target) is False


def test_mesh_descriptor_reads_trainer(eight_devices):
    bundle = get_model("llama-debug", dtype=jnp.float32)
    t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                plan=make_plan("fsdp", make_mesh(fsdp=8)), donate=False)
    d = mesh_descriptor(t)
    assert d["axes"] == {"fsdp": 8}
    assert d["device_count"] == 8
    assert d["strategy"] == "fsdp"
    assert d["pp_stages"] == 1 and d["quant_block"] is None
    host = stamp_host_state({"global_step": 3}, t)
    assert host["mesh"] == d and host["precision_policy"] == "fp32"


# ---------------------------------------------------------------------------
# reshard restore through the policy-aware entry point
# ---------------------------------------------------------------------------

def _step_n(t, state, ids, n):
    batch = {k: jax.device_put(ids, t.batch_shardings()[k])
             for k in ("input_ids", "labels")}
    losses = []
    for _ in range(n):
        state, m = t.step_fn(state, batch)
        losses.append(float(m["loss"]))
    return state, losses


def test_reshard_restore_trajectory_via_entry_point(tmp_path, eight_devices):
    """The elastic acceptance pin, through ``restore_train_state`` (the
    stamped, policy- and mesh-aware entry point): save on mesh A
    (fsdp=8), restore on mesh B (fsdp=4, half the devices — a different
    dp/fsdp factorization), continue — the stitched trajectory equals the
    uninterrupted 8-device run at the documented tolerance, and the
    cross-mesh restore announces itself instead of silently resharding."""
    bundle = get_model("llama-debug", dtype=jnp.float32)
    opt = adamw_cosine(1e-3)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (8, 16)))

    tg = Trainer(bundle=bundle, optimizer=opt,
                 plan=make_plan("fsdp", make_mesh(fsdp=8)), donate=False)
    _, golden = _step_n(tg, tg.init_state(0), ids, 4)

    t8 = Trainer(bundle=bundle, optimizer=opt,
                 plan=make_plan("fsdp", make_mesh(fsdp=8)), donate=False)
    state, first = _step_n(t8, t8.init_state(0), ids, 2)
    io = CheckpointIO(tmp_path / "exp")
    host = host_state_dict()
    host["global_step"] = 2
    io.save(state, stamp_host_state(host, t8))

    t4 = Trainer(bundle=bundle, optimizer=opt,
                 plan=make_plan("fsdp",
                                make_mesh(devices=jax.devices()[:4],
                                          fsdp=4)),
                 donate=False)
    import logging

    records = []
    handler = logging.Handler()
    handler.emit = lambda rec: records.append(rec.getMessage())
    logging.getLogger(
        "distributed_training_guide_tpu.checkpoint.orbax_io"
    ).addHandler(handler)
    try:
        restored, host2 = restore_train_state(io, t4)
    finally:
        logging.getLogger(
            "distributed_training_guide_tpu.checkpoint.orbax_io"
        ).removeHandler(handler)
    assert any("cross-mesh restore" in m and "fsdp=8" in m and "fsdp=4" in m
               for m in records), records
    assert host2["global_step"] == 2
    assert host2["mesh"]["axes"] == {"fsdp": 8}   # the stamp round-trips
    leaf = jax.tree.leaves(restored.params)[0]
    assert len(leaf.sharding.mesh.devices.ravel()) == 4
    _, cont = _step_n(t4, restored, ids, 2)
    np.testing.assert_allclose(first + cont, golden, rtol=2e-4)


def test_quant_block_tiling_restore_fails_loudly(tmp_path):
    """adam8bit moments tiled at block 64 restored into a block-128
    policy: the per-block scale arrays have different shapes, so restore
    must refuse NAMING BOTH TILINGS — not die inside TensorStore, not
    fall back through the retention chain."""
    bundle = get_model("llama-debug", dtype=jnp.float32)
    p64 = PrecisionPolicy(name="adam8bit", quantize_moments=True,
                          block_size=64)
    t64 = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                  precision=p64, donate=False)
    state = t64.init_state(0)
    io = CheckpointIO(tmp_path / "exp")
    host = host_state_dict()
    host["global_step"] = 1
    io.save(state, stamp_host_state(host, t64))

    t128 = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                   precision="adam8bit", donate=False)
    with pytest.raises(ReshardIncompatibleError, match="block size 64"):
        restore_train_state(io, t128)
    with pytest.raises(ReshardIncompatibleError, match="block size 128"):
        restore_train_state(io, t128)
    # the matching tiling restores fine
    t64b = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3),
                   precision=p64, donate=False)
    restored, host2 = restore_train_state(io, t64b)
    assert host2["global_step"] == 1


def test_pp_stage_split_stamp_fails_loudly(tmp_path):
    """A checkpoint stamped under a 2-stage pipeline split refuses to
    restore into a 1-stage run, naming both layouts (the stage-owned
    layer layout is not reshard-compatible)."""
    bundle = get_model("llama-debug", dtype=jnp.float32)
    t = Trainer(bundle=bundle, optimizer=adamw_cosine(1e-3), donate=False)
    state = t.init_state(0)
    io = CheckpointIO(tmp_path / "exp")
    host = stamp_host_state({**host_state_dict(), "global_step": 1}, t)
    host["mesh"] = {"axes": {"pp": 2, "fsdp": 4}, "device_count": 8,
                    "strategy": "pp_fsdp", "pp_stages": 2,
                    "quant_block": None}
    io.save(state, host)
    with pytest.raises(ReshardIncompatibleError,
                       match="2-stage pipeline split"):
        restore_train_state(io, t)


def test_fp32_fallback_reencode_under_mesh_change(tmp_path, eight_devices):
    """The fp32->policy re-encode path re-verified under a mesh change:
    an fp32 checkpoint saved on fsdp=8 restores into an adam8bit run on
    fsdp=4 — re-encoded into quantized storage with the logged warning,
    on the NEW mesh, and immediately trainable."""
    bundle = get_model("llama-debug", dtype=jnp.float32)
    opt = adamw_cosine(1e-3)
    t8 = Trainer(bundle=bundle, optimizer=opt,
                 plan=make_plan("fsdp", make_mesh(fsdp=8)), donate=False)
    state = t8.init_state(0)
    io = CheckpointIO(tmp_path / "exp")
    host = host_state_dict()
    host["global_step"] = 1
    io.save(state, stamp_host_state(host, t8))

    t4 = Trainer(bundle=bundle, optimizer=opt,
                 plan=make_plan("fsdp",
                                make_mesh(devices=jax.devices()[:4],
                                          fsdp=4)),
                 precision="adam8bit", donate=False)
    restored, host2 = restore_train_state(io, t4)
    assert host2["global_step"] == 1
    from distributed_training_guide_tpu.train.precision import Quantized

    quant_leaves = [x for x in jax.tree.leaves(
        restored.opt_state, is_leaf=lambda x: isinstance(x, Quantized))
        if isinstance(x, Quantized)]
    assert quant_leaves, "moments were not re-encoded into int8 storage"
    leaf = jax.tree.leaves(restored.params)[0]
    assert len(leaf.sharding.mesh.devices.ravel()) == 4
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (8, 16)))
    _, losses = _step_n(t4, restored, ids, 1)
    assert np.isfinite(losses[0])


# ---------------------------------------------------------------------------
# world agreement protocol (pure files, no jax)
# ---------------------------------------------------------------------------

def test_membership_liveness(tmp_path):
    a = el.SliceMember(tmp_path, "a")
    b = el.SliceMember(tmp_path, "b")
    a.beat()
    b.beat()
    assert el.live_members(tmp_path, 5.0) == ["a", "b"]
    # a stale payload timestamp ages out; retire removes immediately
    assert el.live_members(tmp_path, 5.0,
                           now=time.time() + 10) == []
    b.retire()
    assert el.live_members(tmp_path, 5.0) == ["a"]


def test_world_agreement_barrier(tmp_path):
    a = el.WorldNegotiator(tmp_path, "a", ack_timeout_s=5.0)
    b = el.WorldNegotiator(tmp_path, "b")
    got = {}
    t = threading.Thread(target=lambda: got.update(b=b.follow(0, 5.0)))
    t.start()
    world = a.propose_and_agree(["a", "b"], "start")
    t.join()
    assert world["world_id"] == 1 and world["members"] == ["a", "b"]
    assert got["b"]["world_id"] == 1
    events = el.read_events(tmp_path)
    assert len(events) == 1
    assert events[0]["event"] == "renegotiated"
    assert events[0]["old_world"] is None
    assert events[0]["new_world"]["members"] == ["a", "b"]
    assert events[0]["trigger"] == "start"
    assert "wall_time" in events[0]


def test_world_agreement_drops_stragglers(tmp_path):
    """A proposed member that never acks is presumed dead: the leader
    re-proposes without it under a fresh world_id — the renegotiation a
    dead slice triggered is never wedged by that same dead slice."""
    a = el.WorldNegotiator(tmp_path, "a", ack_timeout_s=0.3)
    b = el.WorldNegotiator(tmp_path, "b")
    t = threading.Thread(target=lambda: b.follow(0, 5.0))
    t.start()
    world = a.propose_and_agree(["a", "b", "ghost"], "start")
    t.join()
    assert world["members"] == ["a", "b"]
    assert world["world_id"] >= 2          # the ghost cost one round


def test_world_agreement_single_member(tmp_path):
    a = el.WorldNegotiator(tmp_path, "a", ack_timeout_s=0.2)
    world = a.propose_and_agree(["a"], "slice_lost")
    assert world["members"] == ["a"] and world["world_id"] == 1


def test_stale_ack_is_id_fenced(tmp_path):
    """An ack file left by a previous incarnation names an old world_id
    and cannot satisfy a newer proposal's barrier."""
    a = el.WorldNegotiator(tmp_path, "a", ack_timeout_s=0.3)
    # publish world 1 so the next proposal is id 2
    a.propose_and_agree(["a"], "start")
    # preset a stale ack for b naming world 1
    el._write_json_atomic(tmp_path / "world.ack.b.json",
                          {"world_id": 1, "member": "b"})
    world = a.propose_and_agree(["a", "b"], "slice_joined")
    # b never acked id >= 2, so it was dropped despite the stale file
    assert world["members"] == ["a"]


def test_fenced_out_member_raises(tmp_path):
    a = el.WorldNegotiator(tmp_path, "a", ack_timeout_s=0.2)
    a.propose_and_agree(["a"], "slice_lost")     # world excludes b
    b = el.WorldNegotiator(tmp_path, "b")
    with pytest.raises(el.FencedOutError):
        b.follow(0, 0.5)


def test_member_helper_slice_loss_fault(tmp_path, monkeypatch):
    """DTG_FAULT_SLICE_LOSS kills the member helper WITHOUT retiring its
    file — the no-cleanup slice loss the liveness timeout ages out."""
    monkeypatch.setenv(faults.ENV_SLICE_LOSS, "b@3")
    rc = el.run_member(tmp_path, "b", interval_s=0.01, max_beats=50)
    assert rc == 1
    payload = json.loads(
        (tmp_path / el.MEMBERS_DIR / "b.json").read_text())
    assert payload["beats"] == 3                  # died at its 3rd beat
    # the file is still there (no cleanup): only liveness age removes it
    assert el.live_members(tmp_path, 60.0) == ["b"]
    assert el.live_members(tmp_path, 0.0, now=time.time() + 1) == []


def test_member_helper_fenced_out_exits_cleanly(tmp_path):
    """A member the fleet once HELD exits when a newer world excludes
    it; a stale world that PREDATES the member's join must NOT fence it
    (the joiner keeps beating until the leader admits it)."""
    # stale world excluding b: the joiner is not fenced, runs out its
    # beats and retires normally
    el._write_json_atomic(tmp_path / el.WORLD_FILE,
                          {"world_id": 5, "members": ["a"]})
    rc = el.run_member(tmp_path, "b", interval_s=0.001, max_beats=20)
    assert rc == 0
    assert not (tmp_path / el.MEMBERS_DIR / "b.json").exists()  # retired
    # now b becomes a member, then the fleet moves on without it
    el._write_json_atomic(tmp_path / el.WORLD_FILE,
                          {"world_id": 6, "members": ["a", "b"]})
    done = {}
    t = threading.Thread(target=lambda: done.update(
        rc=el.run_member(tmp_path, "b", interval_s=0.01, max_beats=500)))
    t.start()
    time.sleep(0.1)                       # b observes its membership
    el._write_json_atomic(tmp_path / el.WORLD_FILE,
                          {"world_id": 7, "members": ["a"]})
    t.join(timeout=10)
    assert done.get("rc") == 0
    assert not (tmp_path / el.MEMBERS_DIR / "b.json").exists()  # retired


# ---------------------------------------------------------------------------
# worker re-exec rendering
# ---------------------------------------------------------------------------

def test_render_worker_cmd_tokens():
    cmd = ["python", "train.py", "-b", "{world_batch}",
           "--note", "world={world_devices}"]
    out = el.render_worker_cmd(cmd, 4, global_batch=8)
    assert out == ["python", "train.py", "-b", "2", "--note", "world=4"]
    with pytest.raises(ValueError, match="elastic-global-batch"):
        el.render_worker_cmd(["-b", "{world_batch}"], 4)
    with pytest.raises(ValueError, match="not divisible"):
        el.render_worker_cmd(["-b", "{world_batch}"], 3, global_batch=8)


def test_worker_world_env_forces_device_count():
    env = {"XLA_FLAGS": "--xla_foo=1 "
                        "--xla_force_host_platform_device_count=8"}
    world = {"world_id": 3, "members": ["a", "b"]}
    el.worker_world_env(env, world, 4)
    assert env["XLA_FLAGS"] == \
        "--xla_foo=1 --xla_force_host_platform_device_count=4"
    assert env["DTG_WORLD_ID"] == "3"
    assert env["DTG_WORLD_MEMBERS"] == "a,b"
    assert env["DTG_WORLD_DEVICES"] == "4"


# ---------------------------------------------------------------------------
# the supervisor slice-loss chaos drill (subprocess; slow: two training
# incarnations at different device counts + a golden run)
# ---------------------------------------------------------------------------

CH02 = REPO / "02-distributed-data-parallel" / "train_llm.py"
TRAIN_FLAGS = ["-m", "llama-debug", "-d", "synthetic:60000", "-s", "64",
               "--num-epochs", "2", "--log-freq", "1"]


def _losses_by_step(text: str) -> dict:
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


def _drill_env(**extra):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(JAX_PLATFORMS="cpu")
    env.update(extra)
    return env


@pytest.mark.slow
@pytest.mark.chaos
def test_supervisor_slice_loss_renegotiates_and_resumes(tmp_path):
    """THE slice-loss drill: a 2-slice world (4 devices each, global
    batch held at 8 via {world_batch}) loses its peer slice mid-run
    (DTG_FAULT_SLICE_LOSS kills the member helper without cleanup); the
    supervisor notices via membership liveness, SIGTERMs the worker,
    renegotiates to the 1-slice world (barrier'd world.json), re-execs
    the worker with 4 forced devices, and the run resumes from the last
    checkpoint ONTO THE SMALLER MESH — no manual intervention. Every
    step logged by any incarnation must match the uninterrupted golden
    trajectory (rtol covers the cross-mesh reduction-order change), and
    elastic.jsonl must record the 2->1 membership timeline."""
    n_steps = 60        # checkpoint-every-2 pacing keeps the run long
    # golden: uninterrupted 8-device run at global batch 8 (no -e, so no
    # checkpoint I/O — pure trajectory)
    golden_proc = subprocess.run(
        [sys.executable, str(CH02), *TRAIN_FLAGS, "-b", "1",
         "--max-steps", str(n_steps),
         "--save-dir", str(tmp_path / "golden")],
        capture_output=True, text=True, timeout=420, cwd=REPO,
        env=_drill_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=8"))
    assert golden_proc.returncode == 0, \
        (golden_proc.stdout + golden_proc.stderr)[-3000:]
    golden = _losses_by_step(golden_proc.stdout + golden_proc.stderr)
    assert set(golden) == set(range(1, n_steps + 1))

    coord = tmp_path / "coord"
    sup_logs = tmp_path / "sup"
    work = tmp_path / "work"
    # the peer slice: beats until killed. The drill kills it with
    # SIGKILL — the same no-cleanup death DTG_FAULT_SLICE_LOSS injects
    # (unit-pinned above) — but ANCHORED to the step-2 checkpoint
    # publishing, so the loss always lands where the resume has
    # something to resume from whatever this machine's compile time is.
    member = subprocess.Popen(
        [sys.executable, "-m",
         "distributed_training_guide_tpu.launch.elastic",
         "--member", "slice1", "--dir", str(coord),
         "--interval", "0.1", "--max-beats", "100000"],
        env=_drill_env(),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)

    def kill_member_after_checkpoint():
        deadline = time.time() + 400
        ckpt = work / "drill" / "checkpoint-2"
        while time.time() < deadline and not ckpt.exists():
            time.sleep(0.2)
        time.sleep(0.5)                    # let state.json publish too
        member.kill()                      # SIGKILL: the slice is gone

    killer = threading.Thread(target=kill_member_after_checkpoint,
                              daemon=True)
    try:
        killer.start()
        cmd = [sys.executable, "-m",
               "distributed_training_guide_tpu.launch.supervisor",
               "--max-restarts", "2", "--restart-backoff", "0.05",
               "--log-dir", str(sup_logs),
               "--elastic-dir", str(coord), "--slice-name", "slice0",
               "--devices-per-slice", "4", "--liveness-timeout", "1.5",
               "--elastic-global-batch", "8", "--",
               sys.executable, str(CH02), *TRAIN_FLAGS,
               "-b", "{world_batch}", "--max-steps", str(n_steps),
               "--ckpt-freq", "2", "-e", "drill",
               "--save-dir", str(work)]
        # pace the worker with the slow-NFS fault (0.25s per checkpoint
        # save): the slice loss lands at checkpoint-2 and detection takes
        # ~2x the liveness timeout — a warm-cache run without pacing can
        # finish all its steps inside that window and the drill would
        # race instead of drilling
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=540, cwd=REPO,
            env=_drill_env(**{faults.ENV_SAVE_LATENCY_S: "0.25"}))
    finally:
        member.kill()
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]

    # the membership timeline: world 2 members -> world 1 member
    events = el.read_events(coord)
    assert events, "no elastic.jsonl events recorded"
    assert events[0]["new_world"]["members"] == ["slice0", "slice1"]
    lost = [e for e in events
            if e["new_world"]["members"] == ["slice0"]]
    assert lost, events
    assert lost[0]["old_world"]["members"] == ["slice0", "slice1"]
    assert lost[0]["trigger"] == "slice_lost"
    assert "renegotiation (slice_lost)" in out

    # both worlds really ran: 8 forced devices then 4, batch 1 then 2
    attempts = sorted(sup_logs.glob("attempt_*"))
    assert len(attempts) >= 2
    assert "world 1 agreed" in out and "8 devices" in out
    assert "4 devices" in out

    # trajectory: every step any incarnation logged matches golden
    stitched = {}
    for d in attempts:
        text = (d / "stdout.log").read_text() \
            + (d / "stderr.log").read_text()
        stitched.update(_losses_by_step(text))
    last = (attempts[-1] / "stdout.log").read_text() \
        + (attempts[-1] / "stderr.log").read_text()
    assert "Resumed=True" in last          # the shrink resumed, not reran
    assert set(stitched) == set(range(1, n_steps + 1))
    for step, loss in stitched.items():
        np.testing.assert_allclose(loss, golden[step], rtol=2e-4,
                                   err_msg=f"step {step}")
