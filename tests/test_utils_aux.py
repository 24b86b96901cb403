"""Fast unit tests for aux subsystems: supervisor, monitor, data pipeline,
loss masking, LR schedule host mirror, error files, the HLO text parser."""
import json
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np

import pytest

from distributed_training_guide_tpu.utils import hlo as hlo_util

REPO = Path(__file__).parent.parent


# ---- loss ------------------------------------------------------------------

def test_loss_ignore_index():
    from distributed_training_guide_tpu.ops.cross_entropy import causal_lm_loss

    logits = jnp.zeros((1, 4, 8))
    labels = jnp.asarray([[1, 2, -100, 3]])
    loss = float(causal_lm_loss(logits, labels))
    # uniform logits -> log(8) per counted position, ignore masked
    np.testing.assert_allclose(loss, np.log(8), rtol=1e-6)


# ---- lr schedule host mirror ----------------------------------------------

def test_lr_at_step_matches_optax():
    import jax

    from distributed_training_guide_tpu.train.optimizer import (cosine_schedule,
                                                                lr_at_step)

    sched = cosine_schedule(3e-4, t_max=100, eta_min_ratio=0.01, warmup_steps=10)
    for step in [0, 5, 10, 50, 100, 500]:
        # device schedule computes cos in fp32; host mirror in fp64
        np.testing.assert_allclose(float(sched(step)),
                                   lr_at_step(step, 3e-4, 100, 0.01, 10),
                                   rtol=1e-3, atol=1e-10)


# ---- data pipeline ---------------------------------------------------------

def test_pipeline_local_file(tmp_path):
    from distributed_training_guide_tpu.data import (ByteTokenizer,
                                                     load_and_preprocess_data)

    corpus = tmp_path / "corpus.txt"
    corpus.write_text("hello tpu world " * 200)
    data = load_and_preprocess_data(str(corpus), ByteTokenizer(), 32)
    assert data.shape[1] == 32
    assert data.dtype == np.int32
    assert len(data) > 50


def test_pipeline_seq_clamp():
    from distributed_training_guide_tpu.data import (ByteTokenizer,
                                                     load_and_preprocess_data)

    data = load_and_preprocess_data("synthetic:10000", ByteTokenizer(), 4096,
                                    max_position_embeddings=64)
    assert data.shape[1] == 64


# ---- supervisor + error files (C19) ----------------------------------------

def test_supervisor_restarts_and_error_files(tmp_path):
    """Crash twice, then succeed — supervisor must produce per-attempt dirs,
    error.json for failures, and exit 0 overall. No jax involved."""
    worker = tmp_path / "worker.py"
    worker.write_text(f"""
import json, os, sys
sys.path.insert(0, {str(REPO)!r})
from distributed_training_guide_tpu.launch.errors import record

state = {str(tmp_path)!r} + "/count.json"
n = json.load(open(state))["n"] if os.path.exists(state) else 0
json.dump({{"n": n + 1}}, open(state, "w"))

@record
def main():
    if n < 2:
        raise RuntimeError(f"injected fault attempt {{n}}")
    print("success")

main()
""")
    result = subprocess.run(
        [sys.executable, "-m", "distributed_training_guide_tpu.launch.supervisor",
         "--max-restarts", "3", "--log-dir", str(tmp_path / "logs"), "--",
         sys.executable, str(worker)],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env={"PATH": "/usr/bin:/bin:/usr/local/bin", "JAX_PLATFORMS": "cpu"})
    assert result.returncode == 0, result.stdout + result.stderr
    err0 = json.loads((tmp_path / "logs/attempt_0/error.json").read_text())
    assert "injected fault attempt 0" in err0["message"]["error"]
    assert (tmp_path / "logs/attempt_2/stdout.log").read_text().strip() == "success"
    assert not (tmp_path / "logs/attempt_2/error.json").exists()


def test_supervisor_exhausts_restarts(tmp_path):
    result = subprocess.run(
        [sys.executable, "-m", "distributed_training_guide_tpu.launch.supervisor",
         "--max-restarts", "1", "--log-dir", str(tmp_path / "logs"), "--",
         sys.executable, "-c", "raise SystemExit(3)"],
        capture_output=True, text=True, cwd=REPO, timeout=60)
    assert result.returncode == 3
    assert (tmp_path / "logs/attempt_1").exists()
    assert not (tmp_path / "logs/attempt_2").exists()


# ---- cluster monitor (C21) -------------------------------------------------

def test_top_cluster_local():
    result = subprocess.run(
        [sys.executable, "-m", "distributed_training_guide_tpu.monitor.top_cluster",
         "--local"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env={"PATH": "/usr/bin:/bin:/usr/local/bin", "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    stats = json.loads(result.stdout.strip().splitlines()[-1])
    assert len(stats["devices"]) == 8
    assert all("hbm_gb" in d for d in stats["devices"])


# ---- cluster monitor stall detection (reference hang heuristic, C21) -------

def _host_stats(host, num_allocs, hbm=4.0):
    return {"host": host, "devices": [
        {"id": 0, "kind": "fake", "hbm_gb": hbm, "hbm_peak_gb": hbm,
         "hbm_limit_gb": 16.0, "num_allocs": num_allocs}]}


def test_monitor_flags_stalled_host():
    from distributed_training_guide_tpu.monitor.top_cluster import (
        ClusterWatch, format_row)

    watch = ClusterWatch(alert_after=2)
    # busy host: allocator counters move every poll -> ok forever
    for i in range(5):
        row = watch.update(_host_stats("busy", num_allocs=100 + i))
        assert row["status"] == "ok"
    # wedged host: resident memory but frozen counters -> stalled after N
    statuses = [watch.update(_host_stats("wedged", num_allocs=42))["status"]
                for _ in range(4)]
    assert statuses == ["ok", "ok", "stalled", "stalled"]
    assert "STALLED" in format_row(watch.update(_host_stats("wedged", 42)))
    # idle host: no resident memory, frozen counters -> idle, not stalled
    for _ in range(4):
        row = watch.update(_host_stats("empty", num_allocs=0, hbm=0.0))
    assert row["status"] == "idle"
    # recovery: counters move again -> back to ok
    assert watch.update(_host_stats("wedged", num_allocs=43))["status"] == "ok"


def test_monitor_error_row():
    from distributed_training_guide_tpu.monitor.top_cluster import (
        ClusterWatch, format_row)

    row = ClusterWatch().update({"host": "gone", "error": "timeout"})
    assert row["status"] == "error"
    assert "ERROR" in format_row(row)


def test_multi_slice_mesh_fallback(eight_devices):
    """Forcing multi_slice on CPU devices (no slice_index metadata) must fall
    back to the flat mesh, not crash — the degradation path a real pod hits
    when DCN topology metadata is missing."""
    import jax

    from distributed_training_guide_tpu.parallel import make_mesh

    mesh = make_mesh(fsdp=4, multi_slice=True)
    assert mesh.shape["fsdp"] == 4 and mesh.shape["dp"] == 2
    assert mesh.devices.size == len(jax.devices())


# ---- utils/hlo.py parser units (no device work) ---------------------------

_SYNTH = """\
HloModule synth

%loop_body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %ag.9 = f32[32] all-gather(f32[8] %x9), dimensions={0}
  ROOT %t = (s32[], f32[8]) tuple(%i, %y)
}

%cond (p: (s32[], f32[8])) -> pred[] {
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[16,8]) -> f32[] {
  %ag-start.1 = (f32[16,8]{1,0}, f32[64,8]{1,0}) all-gather-start(f32[16,8] %a), dimensions={0}
  %fusion.1 = f32[16,8] fusion(f32[16,8] %a), kind=kLoop, calls=%fc
  %ag-done.1 = f32[64,8]{1,0} all-gather-done((f32[16,8], f32[64,8]) %ag-start.1)
  %w = (s32[], f32[8]) while((s32[], f32[8]) %init), condition=%cond, body=%loop_body
  %rs.2 = f32[4,8] reduce-scatter(f32[16,8] %fusion.1), dimensions={0}
  ROOT %r = f32[] constant(0)
}
"""


def test_hlo_parser_units():
    cols = hlo_util.find_collectives(_SYNTH)
    kinds = sorted(c.kind for c in cols)
    assert kinds == ["all-gather", "all-gather", "all-gather",
                     "reduce-scatter"]
    assert hlo_util.while_body_computations(_SYNTH) >= {"%loop_body",
                                                        "%cond"}
    # which collectives move an array of a given size, and from inside a loop?
    def moving(elements, **kw):
        return [(c.name, in_loop) for c, in_loop in
                hlo_util.collectives_moving(_SYNTH, elements, **kw)]

    assert moving(64 * 8) == [("%ag-start.1", False)]   # the -done is left out
    assert moving(32) == [("%ag.9", True), ("%rs.2", False)]
    assert moving(128, shards=4) == [("%ag-start.1", False), ("%rs.2", False)]

    assert hlo_util.has_aval(_SYNTH, "f32", (16, 8))
    assert hlo_util.has_aval("tensor<16x8xf32>", "f32", (16, 8))
    assert not hlo_util.has_aval(_SYNTH, "f32", (16, 9))
    assert hlo_util.has_shape_run("tensor<4x16x8xbf16>", (16, 8))
    assert not hlo_util.has_shape_run("tensor<116x8xbf16>", (16, 8))


# lines as the chip's compiler prints them (a described-v5e compile of ch04's
# step): tiled layouts nest parentheses inside tuple result types, and a
# reduce-scatter is a custom fusion around an all-reduce
_CHIP = """\
HloModule chip

%all-reduce-scatter (input: f32[8,2048,1024]) -> f32[4104,8,128] {
  %all-reduce.41 = f32[16416,8,128]{2,1,0:T(8,128)} all-reduce(%pad.225), channel_id=200, replica_groups={{0,1,2,3}}, to_apply=%add
}

ENTRY %main (a: f32[16,8]) -> f32[] {
  %collective-permute-start = (bf16[1,2,128,1024]{3,2,1,0:T(8,128)(2,1)S(1)}, bf16[1,2,128,1024]{3,2,1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%x), source_target_pairs={{0,1}}
  %collective-permute-done = bf16[1,2,128,1024]{3,2,1,0:T(8,128)(2,1)S(1)} collective-permute-done(%collective-permute-start)
  %all-gather.82 = bf16[1,1024,3072]{2,1,0:T(8,128)(2,1)} all-gather(%p), replica_groups=[1,4]<=[4], dimensions={0}
  %all-reduce.51 = (f32[8,128]{1,0:T(8,128)S(1)}, f32[8,128,1]{1,0,2:T(8,128)S(1)}) all-reduce(%a, %b), replica_groups=[1,4]<=[4], to_apply=%add
  %fusion.10 = f32[4104,8,128]{2,1,0:T(8,128)S(1)} fusion(%sel), kind=kCustom, calls=%all-reduce-scatter
  ROOT %r = f32[] constant(0)
}
"""


def test_hlo_parser_reads_chip_layouts():
    """Tuple results with tiled layouts parse (they were skipped whole:
    every collective-permute and tuple all-reduce of a chip program), and
    the summary keeps the fused reduce-scatter apart from real all-reduces."""
    kinds = sorted(c.kind for c in hlo_util.find_collectives(_CHIP)
                   if not c.is_done)
    assert kinds == ["all-gather", "all-reduce", "all-reduce",
                     "collective-permute"]
    assert hlo_util.collective_summary(_CHIP) == {
        "counts": {"collective-permute": 1, "all-gather": 1,
                   "all-reduce": 1, "reduce-scatter-fusion": 1},
        "largest_all_reduce_bytes": 2 * 8 * 128 * 4}
    # an explicit reduce-scatter op (the CPU compiler's form) is counted too
    assert hlo_util.collective_summary(_SYNTH)["counts"] == {
        "all-gather": 2, "reduce-scatter": 1}
