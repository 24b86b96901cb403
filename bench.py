"""Benchmark: train-step throughput + MFU on the local device(s).

Prints ONE JSON line: {"metric","value","unit","vs_baseline",...}.

Baseline anchor: the reference's headline number is the Llama-405B run,
~30 s/step on 64xH100 (BASELINE.md) = 6*405e9*(4096*64) FLOP / 30 s / 64 GPUs
~= 332 TFLOP/s/GPU ~= 33.5% MFU on H100 bf16 peak (989 TFLOP/s).
vs_baseline = achieved_mfu / 0.335 — MFU-vs-MFU is the only fair
cross-hardware comparison.

Process design: the top-level process NEVER touches JAX, so it never
holds the chip. It runs each benchmark configuration ("rung") in a
kill-able subprocess with its own time budget, one at a time, walking a
degradation ladder (full-size model -> smaller seq -> debug model) and
retrying a stalled rung once (cheap thanks to the persistent XLA
compilation cache, utils/compile_cache.py). Children emit a partial JSON
line after every timed step. Every rung launch is gated on a device probe
child. Each complete result is persisted to `.bench_last_good.json`; any
emitted line it beats carries it as `detail.last_good`.
`--sweep` runs the queued tuning experiments (SWEEP_QUEUE) the same
probe-gated way, resumably, appending to `.bench_experiments.jsonl`.
"""
from __future__ import annotations

import argparse
import functools
import gc
import hashlib
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
LAST_GOOD_PATH = os.path.join(REPO, ".bench_last_good.json")
FLASH_GOOD_PATH = os.path.join(REPO, ".bench_flash_good.json")
SWEEP_LOG_PATH = os.path.join(REPO, ".bench_experiments.jsonl")
BASELINE_MFU = 0.335


def _default_watchdog() -> int:
    try:
        return int(os.environ.get("BENCH_TIMEOUT", 1500))
    except ValueError:
        return 1500


def _emit(result: dict) -> None:
    print(json.dumps(result), flush=True)


# ---------------------------------------------------------------------------
# last-good evidence cache: every complete result is persisted and
# re-emitted as detail.last_good on any later line it beats.
# ---------------------------------------------------------------------------

def _load_last_good() -> dict | None:
    try:
        with open(LAST_GOOD_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _load_flash_good() -> dict | None:
    try:
        with open(FLASH_GOOD_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _save_flash_good(record: dict, device: str | None) -> None:
    """Persist a clean flash A/B record (commit-stamped, like the headline
    cache) so a later stalled check can still present healthy evidence.
    A completed-but-FAILING numerics check (ok=false) is a real result the
    fresh emission reports, but it must never become the cached 'healthy
    evidence' that backs a stalled run."""
    if not record or record.get("error") or record.get("ok") is not True:
        return
    rec = {**record, "ts": round(time.time(), 1),
           "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
           "git_commit": _git_head(),
           # nested under config so _cache_provenance_ok reads it the same
           # way it reads the headline cache's device stamp
           "config": {"device": device}}
    try:
        tmp = FLASH_GOOD_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(rec, f)
        os.replace(tmp, FLASH_GOOD_PATH)
    except OSError:
        pass


# both memoized: the watchdog timeout handler runs these with a hard kill
# looming — at most one short git wait per process, never one per emission
@functools.lru_cache(maxsize=1)
def _git_head() -> str | None:
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"], cwd=REPO,
                             capture_output=True, text=True, timeout=5)
        return (out.stdout.strip() or None) if out.returncode == 0 else None
    except Exception:
        return None


@functools.lru_cache(maxsize=16)
def _commit_in_history(commit: str) -> bool:
    try:
        out = subprocess.run(["git", "merge-base", "--is-ancestor", commit,
                              "HEAD"], cwd=REPO, capture_output=True, timeout=5)
        return out.returncode == 0
    except Exception:
        return False


def _cache_provenance_ok(rec: dict, cur_device: str | None) -> bool:
    """A cache record is trustworthy evidence only if its measurement commit
    is in this tree's history AND (when both sides know their device kind) it
    was measured on the same hardware. Unstamped legacy records fail closed."""
    commit = rec.get("git_commit")
    if not commit or not _commit_in_history(commit):
        return False
    rec_dev = (rec.get("config") or {}).get("device")
    if cur_device and rec_dev and cur_device != rec_dev:
        return False
    return True


def _save_last_good(final: dict) -> dict | None:
    """Keep the BEST healthy-window result (a later degraded-rung number must
    not clobber the headline evidence). Returns the cache record.

    Partial (mid-kill) measurements are never persisted: a noisy few-step
    number must not become the durable best-evidence record. The record is
    stamped with the git HEAD at measurement time so `_attach_last_good` can
    verify the cache belongs to this tree's history.

    A cached record stamped with a commit OUTSIDE this tree's history could
    never attach anywhere here, so it is displaced even by a lower value —
    letting it block real measurements would wedge the evidence system. A
    record from DIFFERENT HARDWARE with a valid commit is the opposite case:
    it is still the best evidence for the hardware it was measured on (the
    driver's TPU bench), so a run on other hardware (e.g. a CPU dev box)
    neither displaces it nor gets persisted itself."""
    prev = _load_last_good()
    if final.get("value", 0) <= 0 or final.get("partial"):
        return prev
    if prev:
        commit = prev.get("git_commit")
        if not commit or not _commit_in_history(commit):
            prev = None   # unattachable anywhere in this tree: displace
    cur_dev = final.get("detail", {}).get("device")
    prev_dev = ((prev or {}).get("config") or {}).get("device")
    if prev and cur_dev and prev_dev and cur_dev != prev_dev:
        return prev       # other-hardware run: keep the headline untouched
    if prev and prev.get("value", 0) >= final["value"]:
        return prev
    detail = final.get("detail", {})
    rec = {
        "value": final["value"], "unit": final.get("unit"),
        "vs_baseline": final.get("vs_baseline"),
        "ts": round(time.time(), 1),
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        "config": {k: detail[k] for k in
                   ("model", "seq", "global_batch", "step_ms", "remat",
                    "remat_policy", "optimizer", "param_dtype", "precision",
                    "loss_chunks", "fence_every", "offload_opt_state",
                    "sliding_window", "overlap_schedule",
                    "xla_scheduler_flags", "xla_flags_env", "n_chips",
                    "device", "steps_timed", "tokens_per_s_per_chip")
                   if k in detail},
    }
    try:
        tmp = LAST_GOOD_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(rec, f)
        os.replace(tmp, LAST_GOOD_PATH)
    except OSError:
        pass
    return rec


def _attach_last_good(out: dict) -> dict:
    """Attach cached evidence whenever it beats the line being emitted —
    but only when its provenance checks out: the recorded measurement commit
    must be in this tree's history (a cache file carried into an unrelated
    clone never attaches), and when both the cache and the current line know
    their device kind, they must agree (a cache moved to different hardware
    never attaches). Unstamped legacy records fail closed."""
    lg = _load_last_good()
    if not lg or lg.get("value", 0) <= out.get("value", 0):
        return out
    if not _cache_provenance_ok(lg, out.get("detail", {}).get("device")):
        return out
    out.setdefault("detail", {})["last_good"] = lg
    return out


# ---------------------------------------------------------------------------
# child: one benchmark rung (runs in a subprocess; may be killed by parent)
# ---------------------------------------------------------------------------

def _configure_jax_cache() -> None:
    from distributed_training_guide_tpu.utils.compile_cache import \
        enable_compile_cache

    enable_compile_cache()


def run_rung(rung: dict) -> None:
    """Benchmark one (model, batch, seq) config; print partial JSON lines as
    progress is made and a final (non-partial) line on completion."""
    _configure_jax_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_training_guide_tpu.models import get_model
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan
    from distributed_training_guide_tpu.train import Trainer
    from distributed_training_guide_tpu.train.optimizer import OPTIMIZERS
    from distributed_training_guide_tpu.utils import (
        compute_mfu, device_peak_flops, transformer_flops_per_token)

    devices = jax.devices()
    n = len(devices)
    overrides = {}
    if rung.get("param_dtype"):  # e.g. "bfloat16": pure-low-precision state
        overrides["param_dtype"] = getattr(jnp, rung["param_dtype"])
    if rung.get("max_position"):  # raise the RoPE table past the preset's
        overrides["max_position_embeddings"] = rung["max_position"]
    if rung.get("sliding_window"):  # banded flash kernel (SWA) rungs
        overrides["sliding_window"] = rung["sliding_window"]
    if rung.get("moe_dispatch"):  # "ragged" = dropless sorted dispatch rungs
        overrides["moe_dispatch"] = rung["moe_dispatch"]
    bundle = get_model(rung["model"], **overrides)
    cfg = bundle.config
    seq = min(rung["seq"], cfg.max_position_embeddings)
    batch = rung["batch"]
    remat = rung.get("remat", True)

    if n > 1:
        mesh = make_mesh(fsdp=n, devices=devices)
        plan = make_plan("fsdp", mesh)
    else:
        plan = make_plan("single", make_mesh(devices=devices[:1]))

    from distributed_training_guide_tpu.ops.overlap import (
        RECOMMENDED_XLA_FLAGS)

    make_opt = OPTIMIZERS[rung.get("optimizer", "adamw")]
    trainer = Trainer(bundle=bundle, optimizer=make_opt(3e-4), plan=plan,
                      remat=remat, remat_policy=rung.get("remat_policy", "all"),
                      attn_impl=rung.get("attn_impl", "auto"),
                      loss_chunks=rung.get("loss_chunks", 0),
                      offload_opt_state=rung.get("offload_opt_state", False),
                      precision=rung.get("precision", "fp32"),
                      overlap_schedule=rung.get("overlap", False))
    state = trainer.init_state(0)

    global_batch = batch * plan.data_parallel_size
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (global_batch, seq))
    shardings = trainer.batch_shardings()
    batch_arrays = {k: jax.device_put(jnp.asarray(ids), shardings[k])
                    for k in ("input_ids", "labels")}

    fpt = transformer_flops_per_token(bundle.num_active_params(), cfg.num_layers,
                                      cfg.hidden_size, seq, vocab_size=cfg.vocab_size)
    # no peak is on record for the CPU the fallback rungs run on
    # (utils/mfu.py raises on an unknown device kind): those rungs report
    # their step time and no utilization
    try:
        peak = device_peak_flops(devices[0])
    except ValueError:
        peak = None
    # banded preflight pricing for windowed configs (uniform or per-layer):
    # MFU above keeps the conventional dense-causal count so the column stays
    # comparable across rungs — this reports the honest O(S*window) cost
    # beside it (attn_kv_len = mean keys/query; matches preflight's roofline)
    from distributed_training_guide_tpu.utils.mfu import (
        banded_attention_kv_length)

    attn_kv = banded_attention_kv_length(cfg, seq)

    def result(dt: float, loss: float, steps_timed: int, partial: bool) -> dict:
        tokens_per_s = global_batch * seq / dt
        # the ladder reads a numeric "value" and takes 0.0 for "nothing
        # measured" (its failure lines carry the same); without a peak the
        # unit says so, so the line cannot be read as a utilization of 0
        mfu = (compute_mfu(tokens_per_s, fpt, n_chips=n,
                           peak_flops_per_chip=peak) if peak else None)
        out = {
            "metric": "mfu",
            "value": round(mfu, 4) if peak else 0.0,
            "unit": ("fraction_of_peak_bf16" if peak else
                     f"not measured: no peak on record for "
                     f"{devices[0].device_kind!r}"),
            "vs_baseline": round(mfu / BASELINE_MFU, 3) if peak else None,
            "detail": {
                "model": rung["model"], "seq": seq, "global_batch": global_batch,
                "tokens_per_s_per_chip": round(tokens_per_s / n, 1),
                "step_ms": round(1000 * dt, 2), "n_chips": n,
                "device": getattr(devices[0], "device_kind", devices[0].platform),
                "remat": remat,
                "remat_policy": rung.get("remat_policy", "all"),
                "optimizer": rung.get("optimizer", "adamw"),
                **({"param_dtype": rung["param_dtype"]}
                   if rung.get("param_dtype") else {}),
                **({"precision": rung["precision"]}
                   if rung.get("precision") else {}),
                **({"loss_chunks": rung["loss_chunks"]}
                   if rung.get("loss_chunks") else {}),
                **({"fence_every": rung["fence_every"]}
                   if rung.get("fence_every", 1) > 1 else {}),
                **({"offload_opt_state": True}
                   if rung.get("offload_opt_state") else {}),
                **({"sliding_window": rung["sliding_window"]}
                   if rung.get("sliding_window") else {}),
                **({"attn_impl": rung["attn_impl"]}
                   if rung.get("attn_impl") else {}),
                **({"attn_kv_len": attn_kv,
                    "banded_flops_per_token": int(
                        transformer_flops_per_token(
                            bundle.num_active_params(), cfg.num_layers,
                            cfg.hidden_size, seq, vocab_size=cfg.vocab_size,
                            attn_kv_len=attn_kv))}
                   if attn_kv < seq else {}),
                **({"moe_dispatch": rung["moe_dispatch"]}
                   if rung.get("moe_dispatch") else {}),
                # the overlap rungs record their scheduler config: a
                # measured number without the XLA flags it ran under is
                # not reproducible evidence (the latency-hiding scheduler
                # is what turns the explicit collectives into async pairs)
                **({"overlap_schedule": True,
                    "xla_scheduler_flags": " ".join(RECOMMENDED_XLA_FLAGS),
                    "xla_flags_env": os.environ.get("XLA_FLAGS", "")}
                   if rung.get("overlap") else {}),
                "loss": round(loss, 4),
                "steps_timed": steps_timed,
            },
        }
        try:
            stats = devices[0].memory_stats() or {}
        except Exception:  # some backends raise instead of returning None
            stats = {}
        if stats.get("peak_bytes_in_use"):
            # GiB (2**30), matching preflight's budget math and the chip's
            # "16 GB HBM" spec — decimal GB would read ~7% low vs both
            out["detail"]["peak_hbm_gib"] = round(
                stats["peak_bytes_in_use"] / 2**30, 2)
        if partial:
            out["partial"] = True
        return out

    # fence = host-read of the loss (device_get). On the remote-pool TPU
    # platforms used for CI, block_until_ready can return early and deep
    # dispatch-ahead queues stall, so steps are synchronized and timed in
    # groups of fence_every (default 1: every step individually); the median
    # is robust to pool-latency outliers. fence_every>1 lets the host run
    # ahead within a group — the chip never idles on dispatch latency — while
    # the group's last loss read is still a hard fence (each step consumes
    # the previous state, so reading step N's loss forces steps 1..N).
    fence = max(1, rung.get("fence_every", 1))
    warmup_times = []
    for i in range(rung.get("warmup", 2)):
        t0 = time.perf_counter()
        state, metrics = trainer.step_fn(state, batch_arrays)
        loss = float(metrics["loss"])
        warmup_times.append(time.perf_counter() - t0)
        if i > 0:  # step 0 includes compile; later warmups estimate step time
            _emit(result(min(warmup_times[1:]), loss, 0, partial=True))

    times = []  # per-step times (group walltime / group size)
    total, done = rung.get("steps", 10), 0
    while done < total:
        g = min(fence, total - done)  # short last group; never exceeds steps
        t0 = time.perf_counter()
        for _ in range(g):
            state, metrics = trainer.step_fn(state, batch_arrays)
        loss = float(metrics["loss"])
        times.append((time.perf_counter() - t0) / g)
        done += g
        _emit(result(float(np.median(times)), loss, done, partial=done < total))


def run_probe() -> None:
    """Report the platform without compiling anything (a child process, so
    the parent stays off JAX and never holds the chip)."""
    import jax

    d = jax.devices()[0]
    mem = (d.memory_stats() or {}).get("bytes_limit", 0) if d.platform == "tpu" else 0
    _emit({"platform": d.platform, "n_devices": len(jax.devices()),
           "device_kind": getattr(d, "device_kind", d.platform),
           "mem_gb": round(1e-9 * mem, 1)})


def run_flash_check() -> None:
    """On-chip Pallas flash kernel validation: numerics vs the XLA einsum
    reference and per-call walltime for both (fwd+bwd). Shapes match the
    llama-650m attention the headline bench exercises."""
    _configure_jax_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_training_guide_tpu.ops.attention import multihead_attention

    # the llama-650m headline attention shape, GQA included
    B, S, Hq, Hkv, D = 8, 2048, 12, 4, 128
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.bfloat16)

    def make(impl):
        @jax.jit
        def f(q, k, v):
            def loss(q):
                return jnp.sum(multihead_attention(q, k, v, causal=True,
                                                   impl=impl).astype(jnp.float32))
            out, grad = jax.value_and_grad(loss)(q)
            return out, grad
        return f

    results = {}
    outs = {}
    for impl in ("xla", "flash"):
        f = make(impl)
        out, grad = f(q, k, v)  # compile + first run
        float(out)
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            out, grad = f(q, k, v)
            float(out)  # host-read fence (block_until_ready unreliable here)
            times.append(time.perf_counter() - t0)
        outs[impl] = (np.asarray(grad, dtype=np.float32), float(out))
        results[f"{impl}_ms"] = round(1000 * float(np.median(times)), 2)
        _emit({**results, "partial": True})  # survives a stall mid-check

    grad_diff = float(np.max(np.abs(outs["flash"][0] - outs["xla"][0])))
    sum_rel = abs(outs["flash"][1] - outs["xla"][1]) / max(1.0, abs(outs["xla"][1]))
    results.update({
        "shape": [B, S, Hq, Hkv, D], "dtype": "bfloat16",
        "grad_max_abs_diff": round(grad_diff, 5),
        "out_sum_rel_diff": round(sum_rel, 6),
        "ok": bool(grad_diff < 0.1 and sum_rel < 1e-2),
    })
    _emit(results)


def run_decode_check(only: str = None) -> None:
    """Serving rungs: decode tokens/sec through the continuous-batching
    paged-KV engine (serve/) on llama-debug — the inference trajectory
    recorded next to the training MFU rungs.

    - slots1 / slots8: the PR-4 rungs (latency floor vs full-occupancy
      batching), unchanged workload so the history stays comparable.
    - prefix_shared8: n_slots 8 over a common 192-token prefix (the
      system-prompt shape; llama-debug's 256-position table caps the
      512-token nominal) — prefill amortization + refcounted residency.
    - mixed_chunked: one 192-token prompt admitted while 4 decodes are
      resident, prefill_chunk=32 — records the resident decodes' max
      iteration gap, the number chunked prefill exists to bound.
    - decode_sharded_tp2 (queued sweep rung): the slots8 workload on a
      tp=2 mesh with the KV pool sharded on the kv-head axis
      (serve/sharding.py) — needs >= 2 devices.
    - disagg_prefill192_decode4 (queued sweep rung): the mixed workload
      through the DISAGGREGATED pair (serve/disagg.py). One host thread
      drives both engines serially, so the iteration gap still CONTAINS
      the chunk forward while the prompt prefills — what this rung
      isolates vs mixed_chunked is the split's overhead (handoff, two
      schedulers, the decode engine's own occupancy/TTFT) and the
      zero-copy handoff counters; removing the interference itself
      needs concurrent executors (the multi-host seam, future work).
    - spec_ngram8 / spec_draft8: speculative decoding (serve/spec.py) on
      a lookup-friendly prompt (repeated block; its greedy continuation
      cycles), 8 slots, k=8, with the spec-off CONTROL measured on the
      identical workload inside the rung — speedup, acceptance rate,
      and tokens-per-iteration in detail. On CPU the win is fewer
      iterations (per-iteration fixed cost amortizes over the accepted
      run); the TPU rungs (queued) add the weight-read amortization the
      feature exists for.
    - spec_flash8 (queued sweep rung): the spec_ngram8 workload with the
      whole engine on the FLASH family (block_q=T kernel: flash decode
      + flash verify) vs the in-rung GATHER-family control on the
      identical workload — one new variable, the attend family.
      Acceptance and tokens/iteration recorded beside tok/s both ways
      (the family must not change them: spec identity is pinned in CI).
      On CPU the flash leg runs the interpret-mode kernel — a
      correctness emulation, expected slower — so the CPU number prices
      the emulation, not the kernel; the rung exists for the TPU pool.
    - chunk_flash (queued sweep rung): the mixed_chunked workload with
      the chunk program on the multi-token kernel vs the in-rung gather
      control — iteration-gap p50/max both ways (same CPU interpret
      caveat as spec_flash8; on TPU the kernel reads the context once
      per chunk instead of the ~3x gather round-trip).
    - kvq_int8_slots8 (queued sweep rung): the slots8 workload on an
      int8-quantized page pool (serve/kv_pages.py kv_dtype="int8") with
      its fp32-KV control measured in-rung — tokens/sec both ways, the
      pool byte ratio (scales included), and the first greedy-divergence
      position per request (the coarse quality meter). The capacity win
      (~3x pages per pool byte) is the point; on TPU the same ratio cuts
      the bandwidth-bound decode read.
    - kvq_spec_accept (queued sweep rung): the spec_ngram8 workload run
      int8-KV vs fp32-KV, recording the ACCEPTANCE-RATE delta — spec
      acceptance is a sensitive function of KV fidelity (cache error
      perturbs the verify logits and breaks drafted runs long before
      evals move), so this is the serving plane's built-in quality
      meter for quantized pages. Target: |delta| <= 0.02.
    - wq_int8_slots8 (queued sweep rung): the slots8 workload with the
      WEIGHTS block-quantized (serve/weights.py weight_dtype="int8",
      dequantized in-kernel by ops/quantized_matmul.py) vs the
      fp32-weight control in-rung — tok/s both ways, the resident
      weight byte ratio with scales included (~0.28x on llama-debug,
      the >= 1.9x-smaller claim; the publish payload shrinks by the
      same ratio), and the greedy-divergence positions.
    - wq_spec_accept (queued sweep rung): the spec_ngram8 workload's
      ACCEPTANCE-RATE meter pointed at weight fidelity — int8 weights
      vs the SNAPPED-FP control (the identical int8-rounded policy in
      fp storage, post.qlora_base), so the storage+dequant path is the
      one new variable; gate |delta| <= 0.02. The raw-fp acceptance
      rides along ungated (the rounding's own effect — visible on this
      random-init toy, noise on trained models).
    - multilora_slots8 (queued sweep rung): 8 slots serving 4 LoRA
      tenants CO-RESIDENT (requests carry adapter_id; one ragged
      grouped GEMM per target projection applies every tenant's delta
      in the batched decode step) vs two in-rung controls on the
      identical workload — base-only (the lora-path overhead) and one
      MERGED engine per tenant stepped serially (the pool-less
      dedicated-replica world). Headline: the consolidation factor,
      mixed tok/s over the per-tenant serial aggregate.
    - multilora_publish (queued sweep rung): adapter-slot republish
      latency (adapter-sized payload through one cached jit with a
      traced slot index) vs full publish_params on the same engine —
      the tenant-churn price; jit caches must stay flat across both.
    - router_fleet2 (queued sweep rung): 16 requests in two shared-
      prefix groups over a 2-replica fleet behind the router
      (serve/router.py) vs one identical single engine in-rung — prices
      the routing layer + affinity hit rate (one host thread steps both
      replicas serially, so this is NOT a parallel-host speedup claim).
    - handoff_crossproc (queued sweep rung): the disaggregated pair on
      transport='cross_host' (every handoff ships the real serialized
      k/v payload through the socket protocol) vs the same-host 0-byte
      control in-rung, plus a raw wire microbench across a REAL process
      boundary (subprocess echo endpoint, payload sha256 must match,
      MiB/s recorded).
    - tiered_prefix8 (queued sweep rung): 8 requests alternating two
      96-token prefixes on a one-chain pool, tiered engine (host-RAM
      spill + restore, serve/tiering.py) vs the no-tier
      eviction-recompute control in-rung — prefill calls saved, restore
      hits, direct 6-page spill->restore round-trip latency + bytes.
    - directory_pull2 (queued sweep rung): 2-replica fleet where the
      warm replica drains and the cold sibling pulls the committed
      prefix pages through the router's directory over the handoff wire
      vs the cold re-prefill control in-rung — dst prefill calls, pull
      hits, TTFT both ways.
    - multistep_k4_slots8 / multistep_k8_slots8 (queued sweep rungs):
      the slots8 workload with decode_horizon=K — K decode iterations
      fused into ONE compiled program, double-buffered against host
      booking — vs the in-rung K=1 control (one new variable, the
      horizon). Records tok/s, dispatches/token and dispatches/step,
      greedy token-identity vs the control, and per-token-tap itl_p99
      (the K·step burst the amortization costs).

    ``only``: comma-separated rung names (sweep-queue children select the
    new rungs explicitly; the default ladder set keeps its PR-6 cost).
    """
    _configure_jax_cache()
    import jax
    import jax.numpy as jnp

    from distributed_training_guide_tpu.models import get_model
    from distributed_training_guide_tpu.serve.api import (generate_many,
                                                          throughput_stats)
    from distributed_training_guide_tpu.serve.engine import ServeEngine
    from distributed_training_guide_tpu.serve.scheduler import Request

    rungs = (set(only.split(",")) if only
             else {"slots", "prefix_shared8", "mixed_chunked"})
    bundle = get_model("llama-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    out = {"metric": "decode_tput", "model": "llama-debug",
           "unit": "tokens_per_s", "value": 0.0}

    # shared workload definitions: the A/B rungs (spec_flash8,
    # chunk_flash, kvq_spec_accept) claim to run the spec_ngram8 /
    # mixed_chunked workloads — enforced by construction, one definition
    # per workload, instead of by copies that could silently drift
    spec_prompt = ([7, 11, 13, 17, 19, 23, 29, 31] * 12)[:96]

    def spec_workload(engine):
        """The lookup-friendly speculation workload: 8 slots, 96 new
        tokens each, a repeated-block prompt whose greedy continuation
        cycles. Warmed on the WORKLOAD's own shape — the same prefill
        bucket and a continuation long enough that the drafter actually
        drafts; a trivial warm-up would leave the verify program's first
        touch inside the timed window (the PR-10 lesson). Returns
        (results, throughput stats)."""
        from distributed_training_guide_tpu.serve.spec import \
            new_spec_counters

        generate_many(engine, [Request(prompt_ids=spec_prompt + [39],
                                       max_new_tokens=16)])
        engine.decode_steps = engine.decode_tokens = 0
        engine.spec.update(new_spec_counters())
        reqs = [Request(prompt_ids=spec_prompt + [40 + i],
                        max_new_tokens=96, seed=i) for i in range(8)]
        t0 = time.perf_counter()
        results = generate_many(engine, reqs)
        return results, throughput_stats(results,
                                         time.perf_counter() - t0, engine)

    def mixed_chunk_gaps(engine):
        """The mixed chunked-prefill workload: one 192-token prompt
        admitted while 4 decodes are resident — returns the SORTED
        per-iteration gaps (the resident decodes' latency, the number
        chunked prefill exists to bound)."""
        generate_many(engine, [Request(prompt_ids=[3, 17],
                                       max_new_tokens=4)])
        residents = [Request(prompt_ids=[5 + i, 6], max_new_tokens=96,
                             seed=i) for i in range(4)]
        for r in residents:
            engine.submit(r)
        engine.step()
        engine.submit(Request(
            prompt_ids=[3 + (i % 200) for i in range(192)],
            max_new_tokens=8, seed=99))
        gaps, t_prev = [], time.perf_counter()
        while engine.has_work:
            engine.step()
            now = time.perf_counter()
            gaps.append(now - t_prev)
            t_prev = now
        gaps.sort()
        return gaps
    for n_slots in (1, 8) if "slots" in rungs else ():
        engine = ServeEngine(bundle, params, n_slots=n_slots, page_size=16,
                             max_len=128)
        # compile outside the timed window, then zero the step counters so
        # occupancy reflects only the measured batch
        generate_many(engine, [Request(prompt_ids=[3, 17, 42],
                                       max_new_tokens=4)])
        engine.decode_steps = engine.decode_tokens = 0
        reqs = [Request(prompt_ids=[3 + i, 17, 42], max_new_tokens=64,
                        seed=i) for i in range(8)]
        t0 = time.perf_counter()
        results = generate_many(engine, reqs)
        stats = throughput_stats(results, time.perf_counter() - t0, engine)
        out[f"slots{n_slots}"] = stats
        out["value"] = stats["tokens_per_s"]   # headline: the last (8-slot)
        _emit({**out, "partial": True})        # survives a stall mid-check

    if "prefix_shared8" in rungs:
        # prefix-shared rung: 8 slots, common 192-token prefix
        prefix = [3 + (i % 200) for i in range(192)]
        engine = ServeEngine(bundle, params, n_slots=8, page_size=16,
                             max_len=256, prefill_chunk=64)
        generate_many(engine, [Request(prompt_ids=prefix + [7],
                                       max_new_tokens=4)])  # warm+register
        engine.decode_steps = engine.decode_tokens = 0
        reqs = [Request(prompt_ids=prefix + [10 + i], max_new_tokens=32,
                        seed=i) for i in range(8)]
        pool = engine.scheduler.pool
        for r in reqs:
            engine.submit(r)
        results, peak = [], 0
        t0 = time.perf_counter()
        while engine.has_work:
            results.extend(engine.step())
            # peak sampled DURING co-residency — end-state would only show
            # the cache-held pages after every slot has drained
            peak = max(peak, pool.capacity - pool.n_free)
        stats = throughput_stats(results, time.perf_counter() - t0, engine)
        out["prefix_shared8"] = {
            **stats,
            "prefix_hits": engine.scheduler.stats["prefix_hits"],
            "prefix_tokens_shared":
                engine.scheduler.stats["prefix_tokens_shared"],
            "resident_pages_peak": peak,
            "unshared_pages_equivalent":
                8 * (-(-(len(prefix) + 1 + 32) // 16)),
        }
        _emit({**out, "partial": True})

    if "mixed_chunked" in rungs:
        # mixed rung: long prefill chunked against resident decodes — the
        # per-iteration decode gap is the latency chunking bounds
        gaps = mixed_chunk_gaps(ServeEngine(bundle, params, n_slots=5,
                                            page_size=16, max_len=256,
                                            prefill_chunk=32))
        out["mixed_chunked"] = {
            "prefill_chunk": 32,
            "iterations": len(gaps),
            "iter_ms_p50": round(1000 * gaps[len(gaps) // 2], 2),
            "iter_ms_max": round(1000 * gaps[-1], 2),
        }

    if "decode_sharded_tp2" in rungs:
        # the slots8 workload with the KV pool SHARDED on the kv-head
        # axis over a tp=2 mesh (serve/sharding.py): params + pool split,
        # attend shard_map'd per chip — vs the replicated-pool slots8
        # history this isolates the sharded-pool variable
        if len(jax.devices()) < 2:
            out["decode_sharded_tp2"] = {"skipped": "needs >= 2 devices"}
        else:
            from distributed_training_guide_tpu.parallel import (make_mesh,
                                                                 make_plan)

            plan = make_plan("tp", make_mesh(tp=2,
                                             devices=jax.devices()[:2]))
            engine = ServeEngine(bundle, params, n_slots=8, page_size=16,
                                 max_len=128, plan=plan, shard_kv=True)
            generate_many(engine, [Request(prompt_ids=[3, 17, 42],
                                           max_new_tokens=4)])
            engine.decode_steps = engine.decode_tokens = 0
            reqs = [Request(prompt_ids=[3 + i, 17, 42], max_new_tokens=64,
                            seed=i) for i in range(8)]
            t0 = time.perf_counter()
            results = generate_many(engine, reqs)
            stats = throughput_stats(results, time.perf_counter() - t0,
                                     engine)
            out["decode_sharded_tp2"] = {**stats,
                                         **engine.kv_report()}
            out["value"] = stats["tokens_per_s"]
        _emit({**out, "partial": True})

    if "spec_ngram8" in rungs or "spec_draft8" in rungs:
        # speculative decoding rungs (serve/spec.py): 8 slots over a
        # repeated-block prompt whose greedy continuation cycles — the
        # prompt-lookup best case ("lookup-friendly"). The spec-off
        # CONTROL runs the identical workload inside the rung, so the
        # recorded speedup isolates the one new variable (the drafter);
        # acceptance rate and tokens-per-iteration land in detail.
        from distributed_training_guide_tpu.serve.spec import \
            DraftModelDrafter

        _, base = spec_workload(ServeEngine(bundle, params, n_slots=8,
                                            page_size=16, max_len=256))
        for name in ("spec_ngram8", "spec_draft8"):
            if name not in rungs:
                continue
            speculate = ("ngram" if name == "spec_ngram8"
                         else DraftModelDrafter(bundle, params, n_slots=8,
                                                max_len=256, k=8,
                                                page_size=16))
            eng = ServeEngine(bundle, params, n_slots=8, page_size=16,
                              max_len=256, speculate=speculate, spec_k=8)
            _, stats = spec_workload(eng)
            out[name] = {
                **stats,
                "spec_k": 8,
                "spec_off_tokens_per_s": base["tokens_per_s"],
                "speedup_vs_spec_off": (
                    round(stats["tokens_per_s"] / base["tokens_per_s"], 3)
                    if base["tokens_per_s"] else 0.0),
            }
            out["value"] = stats["tokens_per_s"]
            _emit({**out, "partial": True})

    if "spec_flash8" in rungs:
        # the kernel-family A/B: ngram speculation with EVERY forward
        # (decode + verify + empty-draft fallback) on the flash family
        # vs the gather family, identical workload in-rung. Tokens must
        # not change (spec identity is family-internal by construction);
        # what the rung prices is the attend family itself.
        ctl_res, ctl = spec_workload(ServeEngine(
            bundle, params, n_slots=8, page_size=16, max_len=256,
            speculate="ngram", spec_k=8, attend_impl="xla"))
        res, stats = spec_workload(ServeEngine(
            bundle, params, n_slots=8, page_size=16, max_len=256,
            speculate="ngram", spec_k=8, attend_impl="flash"))
        identical = all(a.token_ids == b.token_ids
                        for a, b in zip(res, ctl_res))
        out["spec_flash8"] = {
            **stats,
            "spec_k": 8,
            "attend_impl": "flash",
            "gather_tokens_per_s": ctl["tokens_per_s"],
            "gather_acceptance": ctl["spec_acceptance_rate"],
            "gather_tokens_per_step": ctl["decode_tokens_per_step"],
            "speedup_vs_gather": (
                round(stats["tokens_per_s"] / ctl["tokens_per_s"], 3)
                if ctl["tokens_per_s"] else 0.0),
            "token_identity_vs_gather": identical,
            "cpu_interpret_kernel": jax.default_backend() != "tpu",
        }
        out["value"] = stats["tokens_per_s"]
        _emit({**out, "partial": True})

    if "chunk_flash" in rungs:
        # the chunk program's family A/B on the mixed workload: one long
        # prompt chunked against resident decodes, chunk attend on the
        # multi-token kernel vs the gather view
        def chunk_leg(impl):
            gaps = mixed_chunk_gaps(ServeEngine(
                bundle, params, n_slots=5, page_size=16, max_len=256,
                prefill_chunk=32, attend_impl=impl))
            return {"iterations": len(gaps),
                    "iter_ms_p50": round(1000 * gaps[len(gaps) // 2], 2),
                    "iter_ms_max": round(1000 * gaps[-1], 2)}

        ctl = chunk_leg("xla")
        res = chunk_leg("flash")
        out["chunk_flash"] = {
            "prefill_chunk": 32,
            "attend_impl": "flash",
            **res,
            "gather_iter_ms_p50": ctl["iter_ms_p50"],
            "gather_iter_ms_max": ctl["iter_ms_max"],
            "gather_iterations": ctl["iterations"],
            "cpu_interpret_kernel": jax.default_backend() != "tpu",
        }
        # this is a latency rung — the sweep's done-gate needs a
        # positive `value` on the decode_tput metric line or the entry
        # re-runs every pass (the reshard_restore convention)
        if not out["value"]:
            out["value"] = round(1000.0 / max(res["iter_ms_p50"], 1e-6), 3)
        _emit({**out, "partial": True})

    if "kvq_int8_slots8" in rungs:
        # int8 KV pages: the slots8 workload with the pool quantized and
        # the fp32-KV control measured in-rung on the identical workload
        # (one new variable — the storage dtype). The greedy divergence
        # positions are the coarse quality meter beside kvq_spec_accept's
        # acceptance delta; -1 means token-identical over all 64 steps.
        def kvq_workload(engine):
            generate_many(engine, [Request(prompt_ids=[3, 17, 42],
                                           max_new_tokens=4)])
            engine.decode_steps = engine.decode_tokens = 0
            reqs = [Request(prompt_ids=[3 + i, 17, 42], max_new_tokens=64,
                            seed=i) for i in range(8)]
            t0 = time.perf_counter()
            results = generate_many(engine, reqs)
            return results, throughput_stats(
                results, time.perf_counter() - t0, engine)

        ctl_eng = ServeEngine(bundle, params, n_slots=8, page_size=16,
                              max_len=128)
        ctl_res, ctl = kvq_workload(ctl_eng)
        eng = ServeEngine(bundle, params, n_slots=8, page_size=16,
                          max_len=128, kv_dtype="int8")
        res, stats = kvq_workload(eng)
        div = []
        for a, b in zip(res, ctl_res):
            n = next((j for j, (x, y) in enumerate(
                zip(a.generated_ids, b.generated_ids)) if x != y), -1)
            div.append(n)
        out["kvq_int8_slots8"] = {
            **stats,
            "pool_dtype": "int8",
            "pool_bytes": eng.kv_cache_bytes(),
            "fp32_pool_bytes": ctl_eng.kv_cache_bytes(),
            "bytes_vs_fp32": round(
                eng.kv_cache_bytes() / ctl_eng.kv_cache_bytes(), 4),
            "fp32_kv_tokens_per_s": ctl["tokens_per_s"],
            "speedup_vs_fp32_kv": (
                round(stats["tokens_per_s"] / ctl["tokens_per_s"], 3)
                if ctl["tokens_per_s"] else 0.0),
            "greedy_divergence_positions": div,
        }
        out["value"] = stats["tokens_per_s"]
        _emit({**out, "partial": True})

    if "kvq_spec_accept" in rungs:
        # the KV-quality meter: n-gram speculation on the lookup-friendly
        # workload, int8 pool vs fp32 pool — acceptance rate is the
        # sensitive function of cache fidelity (a perturbed verify logit
        # breaks a drafted run immediately), so the delta is the rung's
        # headline. tests/test_kv_quant.py pins |delta| <= 0.02 in CI.
        def accept_workload(engine):
            _, st = spec_workload(engine)
            return st["tokens_per_s"], st["spec_acceptance_rate"]

        tps32, acc32 = accept_workload(ServeEngine(
            bundle, params, n_slots=8, page_size=16, max_len=256,
            speculate="ngram", spec_k=8))
        tps8, acc8 = accept_workload(ServeEngine(
            bundle, params, n_slots=8, page_size=16, max_len=256,
            speculate="ngram", spec_k=8, kv_dtype="int8"))
        out["kvq_spec_accept"] = {
            "spec_k": 8,
            "tokens_per_s": tps8,
            "fp32_kv_tokens_per_s": tps32,
            "acceptance_int8": acc8,
            "acceptance_fp32": acc32,
            "acceptance_delta": round(acc8 - acc32, 4),
        }
        out["value"] = tps8
        _emit({**out, "partial": True})

    if "wq_int8_slots8" in rungs:
        # int8 WEIGHTS: the slots8 workload with the params block-
        # quantized (serve/weights.py weight_dtype="int8", dequantized
        # inside the matmul by ops/quantized_matmul.py) and the
        # fp32-weight control measured in-rung on the identical workload
        # — one new variable, the weight storage dtype. Beside tok/s the
        # headline is the byte ratio: resident weight bytes AND the
        # publish/swap payload shrink together (scales included), the
        # >= 1.9x-vs-fp32 claim tests/test_weight_quant.py pins. Greedy
        # divergence positions are the coarse quality meter beside
        # wq_spec_accept's acceptance delta; -1 = token-identical.
        def wq_workload(engine):
            generate_many(engine, [Request(prompt_ids=[3, 17, 42],
                                           max_new_tokens=4)])
            engine.decode_steps = engine.decode_tokens = 0
            reqs = [Request(prompt_ids=[3 + i, 17, 42], max_new_tokens=64,
                            seed=i) for i in range(8)]
            t0 = time.perf_counter()
            results = generate_many(engine, reqs)
            return results, throughput_stats(
                results, time.perf_counter() - t0, engine)

        ctl_eng = ServeEngine(bundle, params, n_slots=8, page_size=16,
                              max_len=128)
        ctl_res, ctl = wq_workload(ctl_eng)
        eng = ServeEngine(bundle, params, n_slots=8, page_size=16,
                          max_len=128, weight_dtype="int8")
        res, stats = wq_workload(eng)
        div = []
        for a, b in zip(res, ctl_res):
            n = next((j for j, (x, y) in enumerate(
                zip(a.generated_ids, b.generated_ids)) if x != y), -1)
            div.append(n)
        rep = eng.weight_report()
        out["wq_int8_slots8"] = {
            **stats,
            "weight_dtype": "int8",
            "weight_bytes": eng.weight_bytes(),
            "fp32_weight_bytes": ctl_eng.weight_bytes(),
            "bytes_vs_fp32": round(
                eng.weight_bytes() / ctl_eng.weight_bytes(), 4),
            "publish_payload_bytes": rep["publish_payload_bytes"],
            "fp_publish_payload_bytes": rep["publish_payload_bytes_fp"],
            "fp32_weight_tokens_per_s": ctl["tokens_per_s"],
            "speedup_vs_fp32_weights": (
                round(stats["tokens_per_s"] / ctl["tokens_per_s"], 3)
                if ctl["tokens_per_s"] else 0.0),
            "greedy_divergence_positions": div,
        }
        out["value"] = stats["tokens_per_s"]
        _emit({**out, "partial": True})

    if "wq_spec_accept" in rungs:
        # the WEIGHT-quality meter: n-gram speculation on the
        # lookup-friendly workload, kvq_spec_accept's methodology
        # pointed at weight fidelity. The GATED delta (|delta| <= 0.02,
        # pinned in tests) is int8 vs the SNAPPED-FP control — the same
        # int8-rounded policy served from fp storage through fp matmuls
        # (post.qlora_base), so the storage dtype + in-kernel dequant
        # path is the one new variable and the serving plane must not
        # perturb acceptance beyond it. The raw-fp acceptance is
        # recorded beside it ungated: on THIS random-init debug model
        # the rounding itself moves acceptance (~-0.10; near-uniform
        # logits flip under any perturbation), a toy-model artifact a
        # trained model's confident logits don't share — splitting the
        # two deltas is what keeps the meter honest about which half
        # the serve plane owns.
        def wq_accept_workload(engine):
            _, st = spec_workload(engine)
            return st["tokens_per_s"], st["spec_acceptance_rate"]

        from distributed_training_guide_tpu.post import qlora_base

        wq_kw = dict(n_slots=8, page_size=16, max_len=256,
                     speculate="ngram", spec_k=8)
        tps_fp, acc_fp = wq_accept_workload(ServeEngine(
            bundle, params, **wq_kw))
        tps_snap, acc_snap = wq_accept_workload(ServeEngine(
            bundle, qlora_base(params), **wq_kw))
        tps8, acc8 = wq_accept_workload(ServeEngine(
            bundle, params, weight_dtype="int8", **wq_kw))
        out["wq_spec_accept"] = {
            "spec_k": 8,
            "tokens_per_s": tps8,
            "fp32_weight_tokens_per_s": tps_fp,
            "acceptance_int8": acc8,
            "acceptance_snapped_fp": acc_snap,
            "acceptance_fp32": acc_fp,
            "acceptance_delta": round(acc8 - acc_snap, 4),
            "rounding_delta_ungated": round(acc_snap - acc_fp, 4),
        }
        out["value"] = tps8
        _emit({**out, "partial": True})

    if "multilora_slots8" in rungs:
        # batched multi-LoRA: 8 slots serving 4 TENANTS co-resident —
        # requests carry adapter_id and each decode step applies every
        # tenant's delta through one ragged grouped GEMM (gather-sorted
        # by adapter, group_sizes from the batch histogram). Controls
        # in-rung on the identical workload: base-only (the lora
        # overhead row — same engine shape, no pool) and the pool-less
        # world (one MERGED engine per tenant, each batching only its
        # own 2 requests, stepped serially — dedicated-replica serving).
        # The headline is the CONSOLIDATION factor: mixed tok/s over the
        # per-tenant serial aggregate — multi-LoRA's reason to exist is
        # that tenants share the batch, so occupancy stays at 8 where
        # dedicated engines idle 6 of 8 slots each (S-LoRA/Punica's
        # claim, priced on this engine).
        from distributed_training_guide_tpu.models.lora import (lora_bundle,
                                                                merge_lora)

        ml_lb = lora_bundle(bundle, rank=8)
        tenants = [jax.tree.map(lambda x: x * 0.05,
                                ml_lb.init(ml_lb.config,
                                           jax.random.key(100 + i))["lora"])
                   for i in range(4)]

        def ml_workload(engine, adapter_ids):
            generate_many(engine, [Request(prompt_ids=[3, 17, 42],
                                           max_new_tokens=4,
                                           adapter_id=adapter_ids[0])])
            engine.decode_steps = engine.decode_tokens = 0
            reqs = [Request(prompt_ids=[3 + i, 17, 42], max_new_tokens=64,
                            seed=i,
                            adapter_id=adapter_ids[i % len(adapter_ids)])
                    for i in range(8)]
            t0 = time.perf_counter()
            results = generate_many(engine, reqs)
            return results, throughput_stats(
                results, time.perf_counter() - t0, engine)

        ml_eng = ServeEngine(bundle, params, n_slots=8, page_size=16,
                             max_len=128, max_adapters=8, adapter_rank=8)
        ml_slots = [ml_eng.publish_adapter(t, name=f"tenant-{i}")
                    for i, t in enumerate(tenants)]
        _, mixed = ml_workload(ml_eng, ml_slots)
        _, base_only = ml_workload(
            ServeEngine(bundle, params, n_slots=8, page_size=16,
                        max_len=128), [0])
        # dedicated-replica control: build + warm the merged engines
        # OUTSIDE the timed window (compile is not a serving cost both
        # worlds pay per request), then serve each tenant's slice
        merged_engines = []
        for i, t in enumerate(tenants):
            m_eng = ServeEngine(
                bundle, merge_lora(ml_lb, {"base": params, "lora": t}),
                n_slots=8, page_size=16, max_len=128)
            generate_many(m_eng, [Request(prompt_ids=[3, 17, 42],
                                          max_new_tokens=4)])
            m_eng.decode_steps = m_eng.decode_tokens = 0
            merged_engines.append(m_eng)
        t0 = time.perf_counter()
        merged_tokens = 0
        for i, m_eng in enumerate(merged_engines):
            res = generate_many(m_eng, [
                Request(prompt_ids=[3 + j, 17, 42], max_new_tokens=64,
                        seed=j) for j in range(8) if j % 4 == i])
            merged_tokens += sum(len(r.generated_ids) for r in res)
        merged_wall = time.perf_counter() - t0
        merged_tps = (round(merged_tokens / merged_wall, 1)
                      if merged_wall > 0 else 0.0)
        out["multilora_slots8"] = {
            **mixed,
            "n_adapters": len(ml_slots),
            "adapter_rank": 8,
            "base_only_tokens_per_s": base_only["tokens_per_s"],
            "lora_overhead_vs_base": (
                round(mixed["tokens_per_s"] / base_only["tokens_per_s"], 3)
                if base_only["tokens_per_s"] else 0.0),
            "merged_serial_tokens_per_s": merged_tps,
            "consolidation_factor": (
                round(mixed["tokens_per_s"] / merged_tps, 3)
                if merged_tps else 0.0),
        }
        out["value"] = mixed["tokens_per_s"]
        _emit({**out, "partial": True})

    if "multilora_publish" in rungs:
        # tenant churn pricing: republishing an adapter into its pool
        # slot (one cached jit, traced slot index, adapter-sized
        # payload) vs a full publish_params (whole-model payload) on the
        # same engine — the ratio is what makes per-tenant policy
        # updates cheap enough to ride every post-training boundary.
        # Both loops block on the result; jit caches must stay FLAT
        # across the churn (the retrace-free contract, pinned in tests).
        from distributed_training_guide_tpu.models.lora import lora_bundle

        mp_lb = lora_bundle(bundle, rank=8)
        mp_eng = ServeEngine(bundle, params, n_slots=2, page_size=16,
                             max_len=64, max_adapters=8, adapter_rank=8)
        payloads = [jax.tree.map(lambda x: x * 0.05,
                                 mp_lb.init(mp_lb.config,
                                            jax.random.key(200 + i))["lora"])
                    for i in range(6)]
        slot = mp_eng.publish_adapter(payloads[0], name="churn")  # warm
        mp_eng.publish_params(params)                             # warm
        jax.block_until_ready(mp_eng.programs.adapter_stacks)
        caches_before = dict(mp_eng.programs.jit_cache_sizes())
        t0 = time.perf_counter()
        for p in payloads:
            mp_eng.publish_adapter(p, slot=slot)
        jax.block_until_ready(mp_eng.programs.adapter_stacks)
        insert_ms = 1000 * (time.perf_counter() - t0) / len(payloads)
        t0 = time.perf_counter()
        for _ in payloads:
            mp_eng.publish_params(params)
        jax.block_until_ready(mp_eng.programs.params)
        publish_ms = 1000 * (time.perf_counter() - t0) / len(payloads)
        rep = mp_eng.adapter_report()
        out["multilora_publish"] = {
            "adapter_insert_ms": round(insert_ms, 3),
            "publish_params_ms": round(publish_ms, 3),
            "insert_speedup": (round(publish_ms / insert_ms, 2)
                               if insert_ms > 0 else 0.0),
            "adapter_payload_bytes": rep["publish_payload_bytes"],
            "pool_bytes": rep["pool_bytes"],
            "retrace_free": (dict(mp_eng.programs.jit_cache_sizes())
                             == caches_before),
        }
        out["value"] = out.get("value") or 0.0
        _emit({**out, "partial": True})

    if "tiered_prefix8" in rungs:
        # tiered KV (serve/tiering.py): 8 requests alternating between
        # two 96-token prefixes on a pool that holds only ONE committed
        # chain at a time — every switch evicts the cold chain. The
        # CONTROL (no host tier) pays eviction-recompute: the evicted
        # prefix re-prefills from HBM-scratch. The tiered engine spills
        # evicted pages to host RAM and restores them (scatter + seat)
        # when the prefix comes back; chunked prefill (prefill_chunk=16)
        # makes the avoided work visible as prefill-call counts. The
        # tier is the only new variable. detail also prices one direct
        # spill->restore round-trip (gather/put/take/scatter of a
        # 6-page chain) — the per-restore latency and bytes.
        import dataclasses

        pre_a = [3 + (i % 200) for i in range(96)]
        pre_b = [7 + (i % 190) for i in range(96)]
        tier_reqs = [Request(
            prompt_ids=(pre_a if i % 2 else pre_b) + [10 + i],
            max_new_tokens=16, seed=i) for i in range(8)]

        def tier_workload(host_tier_bytes):
            eng = ServeEngine(bundle, params, n_slots=1, page_size=16,
                              n_pages=12, max_len=128, prefill_chunk=16,
                              host_tier_bytes=host_tier_bytes)
            generate_many(eng, [Request(prompt_ids=pre_a + [7],
                                        max_new_tokens=4),
                                Request(prompt_ids=pre_b + [9],
                                        max_new_tokens=4)])  # warm+commit
            eng.decode_steps = eng.decode_tokens = 0
            pc0 = eng.programs.prefill_calls
            t0 = time.perf_counter()
            results = generate_many(
                eng, [dataclasses.replace(r, request_id=None)
                      for r in tier_reqs], max_iterations=5000)
            stats = throughput_stats(results, time.perf_counter() - t0,
                                     eng)
            toks = {tuple(r.prompt_ids): list(r.generated_ids)
                    for r in results}
            return eng, stats, eng.programs.prefill_calls - pc0, toks

        t_eng, t_stats, t_pc, t_toks = tier_workload(1 << 22)
        _, c_stats, c_pc, c_toks = tier_workload(None)
        ts = t_eng.stats()  # before the microbench touches the counters
        # direct round-trip microbench: one committed 6-page chain
        # through the tier, host copy both ways
        rt_pages = list(range(1, 7))
        rt_ns, rt_bytes = 5, 0
        t0 = time.perf_counter()
        for i in range(rt_ns):
            payload = t_eng.gather_pages(rt_pages)
            t_eng.host_tier.put(("bench", i), payload, pages=len(rt_pages))
            rec = t_eng.host_tier.take(("bench", i))
            t_eng.scatter_pages(rt_pages, rec.payload)
            rt_bytes = rec.nbytes
        jax.block_until_ready(t_eng.pages)
        rt_ms = 1000 * (time.perf_counter() - t0) / rt_ns
        out["tiered_prefix8"] = {
            **t_stats,
            "prefill_calls": t_pc,
            "restore_hits": ts["restore_hits"],
            "restore_misses": ts["restore_misses"],
            "spilled_pages": ts["spilled_pages"],
            "host_tier_bytes": ts["host_tier_bytes"],
            "tier_bytes_restored": ts["tier_bytes_restored"],
            "control_no_tier": {
                "tokens_per_s": c_stats["tokens_per_s"],
                "prefill_calls": c_pc},
            "prefill_calls_saved": c_pc - t_pc,
            "restore_roundtrip_ms_6pages": round(rt_ms, 3),
            "restore_roundtrip_bytes": rt_bytes,
            "tokens_identical": t_toks == c_toks,
        }
        out["value"] = t_stats["tokens_per_s"]
        _emit({**out, "partial": True})

    if "directory_pull2" in rungs:
        # fleet prefix directory (serve/tiering.py pull_prefix via
        # serve/router.py): 2 replicas with INDEPENDENT programs (the
        # prefill-call counters must be per-replica), r-warm serves a
        # 96-token shared prefix then DRAINS — the next request for that
        # prefix must route to the cold sibling, whose affinity miss
        # consults the router's directory and pulls the committed pages
        # over the handoff wire instead of re-prefilling them. The
        # CONTROL is the identical fleet with nothing warmed (plain cold
        # re-prefill on the same replica) — the pull is the only new
        # variable. Chunked prefill makes the saved forwards countable.
        from distributed_training_guide_tpu.serve.router import local_fleet

        dir_prefix = [3 + (i % 200) for i in range(96)]
        fleet_kw = dict(n_slots=2, page_size=16, max_len=128,
                        prefill_chunk=16, host_tier_bytes=1 << 22,
                        share_programs=False)

        def pull_leg(warm):
            fleet = local_fleet(bundle, params, 2, **fleet_kw)
            generate_many(fleet, [Request(prompt_ids=dir_prefix + [7],
                                          max_new_tokens=4)])
            fleet.step()  # publish stats -> directory refresh
            warm_names = [n for n, (_, keys) in fleet._directory.items()
                          if keys]
            if warm:
                fleet.replicas[warm_names[0]].drain()
            else:
                # control: drop the directory so the pull cannot fire,
                # and drain the SAME replica so routing is identical
                fleet._directory.clear()
                fleet.replicas[warm_names[0]].drain()
                fleet._refresh_directory = lambda: None
            pc0 = {n: r.engine.programs.prefill_calls
                   for n, r in fleet.replicas.items()}
            t0 = time.perf_counter()
            results = generate_many(
                fleet, [Request(prompt_ids=dir_prefix + [8],
                                max_new_tokens=24, seed=1)],
                max_iterations=5000)
            wall = time.perf_counter() - t0
            dst = [n for n, r in fleet.replicas.items()
                   if not r.draining][0]
            return {
                "tokens_per_s": round(
                    sum(len(r.generated_ids) for r in results)
                    / max(wall, 1e-9), 1),
                "ttft_s": round(results[0].ttft_s, 4),
                "dst_prefill_calls": (
                    fleet.replicas[dst].engine.programs.prefill_calls
                    - pc0[dst]),
                "directory_pulls": fleet.counters["directory_pulls"],
                "directory_pull_hits": fleet.counters[
                    "directory_pull_hits"],
                "tokens": [list(r.generated_ids) for r in results],
            }

        pull = pull_leg(warm=True)
        ctl = pull_leg(warm=False)
        out["directory_pull2"] = {
            "tokens_per_s": pull["tokens_per_s"],
            "ttft_s": pull["ttft_s"],
            "dst_prefill_calls": pull["dst_prefill_calls"],
            "directory_pulls": pull["directory_pulls"],
            "directory_pull_hits": pull["directory_pull_hits"],
            "control_cold_reprefill": {
                "tokens_per_s": ctl["tokens_per_s"],
                "ttft_s": ctl["ttft_s"],
                "dst_prefill_calls": ctl["dst_prefill_calls"]},
            "prefill_calls_saved": (ctl["dst_prefill_calls"]
                                    - pull["dst_prefill_calls"]),
            "tokens_identical": pull["tokens"] == ctl["tokens"],
        }
        out["value"] = pull["tokens_per_s"]
        _emit({**out, "partial": True})

    if "disagg_prefill192_decode4" in rungs:
        # the mixed workload through the DISAGGREGATED pair (serial
        # facade — see the docstring: this prices the split's overhead
        # and the handoff, not interference removal)
        from distributed_training_guide_tpu.serve.disagg import DisaggEngine

        engine = DisaggEngine(bundle, params, n_slots=4, n_prefill_slots=1,
                              page_size=16, max_len=256, prefill_chunk=32)
        generate_many(engine, [Request(prompt_ids=[3, 17],
                                       max_new_tokens=4)])
        residents = [Request(prompt_ids=[5 + i, 6], max_new_tokens=96,
                             seed=i) for i in range(4)]
        for r in residents:
            engine.submit(r)
        engine.step()
        long_req = Request(prompt_ids=[3 + (i % 200) for i in range(192)],
                           max_new_tokens=8, seed=99)
        engine.submit(long_req)
        results, gaps, t_prev = [], [], time.perf_counter()
        t0 = t_prev
        while engine.has_work:
            results.extend(engine.step())
            now = time.perf_counter()
            gaps.append(now - t_prev)
            t_prev = now
        gaps.sort()
        stats = throughput_stats(results, time.perf_counter() - t0, engine)
        long_res = [r for r in results
                    if r.prompt_ids == long_req.prompt_ids][0]
        out["disagg_prefill192_decode4"] = {
            **stats,
            "prefill_chunk": 32,
            "iterations": len(gaps),
            "iter_ms_p50": round(1000 * gaps[len(gaps) // 2], 2),
            "iter_ms_max": round(1000 * gaps[-1], 2),
            "long_prompt_ttft_s": round(long_res.ttft_s, 4),
            **{f"handoff_{k}": v for k, v in engine.handoff.stats.items()},
        }
        out["value"] = stats["tokens_per_s"]

    if "router_fleet2" in rungs:
        # the fleet rung: 2 ServeEngine replicas (4 slots each, shared
        # compiled programs) behind the router, 16 requests in two
        # 64-token shared-prefix groups — affinity should land each
        # group on one replica where its PrefixCache pages are. The
        # CONTROL is one identical single engine on the same workload
        # in-rung (the router + second replica are the only new
        # variables); one host thread steps both replicas serially, so
        # this prices the routing layer's overhead + the affinity hit
        # rate, not parallel-host speedup (that's the multi-host rung).
        import dataclasses

        from distributed_training_guide_tpu.serve.router import local_fleet

        pre_a = [3 + (i % 200) for i in range(64)]
        pre_b = [7 + (i % 190) for i in range(64)]
        reqs = [Request(prompt_ids=(pre_a if i % 2 else pre_b) + [10 + i],
                        max_new_tokens=32, seed=i) for i in range(16)]

        def fleet_workload(eng):
            generate_many(eng, [Request(prompt_ids=pre_a + [7],
                                        max_new_tokens=4),
                                Request(prompt_ids=pre_b + [9],
                                        max_new_tokens=4)])   # warm+register
            t0 = time.perf_counter()
            results = generate_many(
                eng, [dataclasses.replace(r, request_id=None)
                      for r in reqs], max_iterations=5000)
            return throughput_stats(results, time.perf_counter() - t0, eng)

        ctl_eng = ServeEngine(bundle, params, n_slots=4, page_size=16,
                              max_len=128, prefill_chunk=32)
        ctl = fleet_workload(ctl_eng)
        router = local_fleet(bundle, params, 2, n_slots=4, page_size=16,
                             max_len=128, prefill_chunk=32)
        stats = fleet_workload(router)
        rs = router.stats()
        out["router_fleet2"] = {
            **stats,
            "control_single_engine": {
                "tokens_per_s": ctl["tokens_per_s"],
                "prefix_hits": ctl["prefix_hits"]},
            "speedup_vs_single": round(
                stats["tokens_per_s"] / max(ctl["tokens_per_s"], 1e-9), 3),
            "affinity_routed": rs["affinity_routed"],
            "spillovers": rs["spillovers"],
            "prefix_hits_fleet": rs["prefix_hits"],
            "live_replicas": rs["live_replicas"],
        }
        out["value"] = stats["tokens_per_s"]
        _emit({**out, "partial": True})

    if "handoff_crossproc" in rungs:
        # the cross-host handoff rung, two legs: (a) the disagg pair on
        # transport='cross_host' — every prefill->decode transfer moves
        # the real serialized k/v payload through the socket protocol —
        # with the same-host (0-byte refcount move) pair as the in-rung
        # control, transport the only variable; (b) a raw wire
        # microbench across a REAL process boundary: a subprocess echo
        # endpoint (python -m ...serve.transport --echo) receives the
        # same per-sequence frames over TCP and returns a payload
        # digest, pinning cross-process bitwise integrity + MB/s.
        import socket as socket_mod

        import numpy as np

        from distributed_training_guide_tpu.serve.disagg import DisaggEngine
        from distributed_training_guide_tpu.serve import transport as twire

        def disagg_workload(eng):
            generate_many(eng, [Request(prompt_ids=[3, 17],
                                        max_new_tokens=4)])
            reqs = [Request(prompt_ids=[3 + (j % 200)
                                        for j in range(64)] + [10 + i],
                            max_new_tokens=32, seed=i) for i in range(8)]
            t0 = time.perf_counter()
            results = generate_many(eng, reqs, max_iterations=5000)
            stats = throughput_stats(results, time.perf_counter() - t0, eng)
            return stats, eng.stats()

        ctl_stats, ctl_es = disagg_workload(DisaggEngine(
            bundle, params, n_slots=4, n_prefill_slots=1, page_size=16,
            max_len=128, prefill_chunk=32))
        ch_eng = DisaggEngine(bundle, params, n_slots=4, n_prefill_slots=1,
                              page_size=16, max_len=128, prefill_chunk=32,
                              transport="cross_host")
        ch_stats, ch_es = disagg_workload(ch_eng)

        # leg (b): ship one real sequence payload N times cross-process
        payload = twire.gather_payload(
            ch_eng.pages, list(range(1, min(5, ch_eng.pool.n_pages))))
        n_frames, digest = 32, hashlib.sha256()
        proc = subprocess.Popen(
            [sys.executable, "-m",
             "distributed_training_guide_tpu.serve.transport",
             "--echo", "--expect", str(n_frames)],
            stdout=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        port = json.loads(proc.stdout.readline())["port"]
        sock = socket_mod.create_connection(("127.0.0.1", port))
        sender = twire.HandoffSender(sock, ack_timeout_s=10.0)
        wire_bytes = 0
        t0 = time.perf_counter()
        for i in range(n_frames):
            frame = twire.encode_frame(i, {"seq": i}, payload)
            assert sender.send(frame, i) == "delivered"
            wire_bytes += len(frame)
        wall = time.perf_counter() - t0
        # close OUR end first: the echo server waits for the peer's EOF
        # before printing its digest and exiting (reading its stdout
        # while still holding the socket open would deadlock into the
        # server's join timeout)
        sock.close()
        for _ in range(n_frames):
            for name in twire.pool_leaf_names(ch_eng.pages):
                digest.update(np.ascontiguousarray(payload[name]).tobytes())
        echo = json.loads(proc.stdout.readlines()[-1])
        proc.wait(timeout=30)
        ch_eng.close()
        out["handoff_crossproc"] = {
            **ch_stats,
            "control_same_host": {
                "tokens_per_s": ctl_stats["tokens_per_s"],
                "handoff_bytes_copied": ctl_es["handoff_bytes_copied"]},
            "tokens_per_s_vs_same_host": round(
                ch_stats["tokens_per_s"]
                / max(ctl_stats["tokens_per_s"], 1e-9), 3),
            "handoff_bytes_copied": ch_es["handoff_bytes_copied"],
            "handoff_delivered": ch_es["handoff_delivered"],
            "crossproc_frames": echo["frames"],
            "crossproc_digest_match":
                echo["sha256"] == digest.hexdigest(),
            "crossproc_wire_mib_s": round(
                wire_bytes / 2**20 / max(wall, 1e-9), 2),
        }
        out["value"] = ch_stats["tokens_per_s"]

    if "multistep_k4_slots8" in rungs or "multistep_k8_slots8" in rungs:
        # fused decode horizons: the slots8 workload with K decode
        # iterations compiled into ONE device program + double-buffered
        # dispatch, vs the in-rung K=1 control on the identical workload
        # (one new variable — the horizon). dispatches/token is the
        # headline (the host round-trip, not math, is the serve plane's
        # CPU wall — the PR-6 finding this rung finally amortizes);
        # itl_p99_ms prices the K·step emission burst the amortization
        # costs, from PER-TOKEN tap timestamps (a per-request mean would
        # hide it — the loadgen honest-ITL rule applied in-rung).
        def horizon_warm(engine):
            # warm on the WORKLOAD's own shape (the spec_workload rule):
            # 8 co-resident slots, long enough for several dispatches —
            # the decode/horizon program compiles a second variant on its
            # first donated-output re-entry, and a 1-slot warm-up would
            # leave that compile inside the timed window
            generate_many(engine, [Request(prompt_ids=[3 + i, 17, 42],
                                           max_new_tokens=24, seed=i)
                                   for i in range(8)])

        def horizon_rep(engine):
            # ONE rep of the slots8 workload. decode tok/s excludes the
            # prefill every arm pays identically (the TTFT/ITL split:
            # this is a DECODE rung, and ~20ms of shared prefill would
            # dilute the ratio it measures). The first step() carries
            # admission, and each of the first 8 steps ONE prompt's
            # prefill chunk (a chunk a step is the budget) beside its
            # decode dispatch; their prefill share is their duration
            # minus the median steady dispatch, subtracted from the
            # wall. GC is
            # parked during the timed window (a collection pause lands
            # on whichever arm is mid-rep — symmetric noise, but noise).
            engine.decode_steps = engine.decode_tokens = 0
            engine.host_dispatches = engine.horizon_ksum = 0
            for i in range(8):
                engine.submit(Request(prompt_ids=[3 + i, 17, 42],
                                      max_new_tokens=64, seed=i))
            tok_times, results, step_ts = {}, [], []
            gc.collect()
            gc.disable()
            try:
                t0 = time.perf_counter()
                while engine.has_work:
                    ts0 = time.perf_counter()
                    fin = engine.step()
                    now = time.perf_counter()
                    step_ts.append(now - ts0)
                    for rid, toks in engine.partial_tokens().items():
                        times = tok_times.setdefault(rid, [])
                        times.extend([now] * (len(toks) - len(times)))
                    for res in fin:  # final block leaves partial_tokens
                        times = tok_times.setdefault(res.request_id, [])
                        times.extend([now] * (len(res.generated_ids)
                                              - len(times)))
                    results.extend(fin)
                wall = time.perf_counter() - t0
            finally:
                gc.enable()
            steady = sorted(step_ts[8:])
            prefill_s = max(0.0, sum(step_ts[:8])
                            - 8 * (steady[len(steady) // 2] if steady
                                   else 0.0))
            decode_wall = max(wall - prefill_s, 1e-9)
            gaps = sorted(g for ts in tok_times.values()
                          for g in (b - a for a, b in zip(ts, ts[1:])))
            st = engine.stats()
            row = {
                "tokens_per_s": round(
                    engine.decode_tokens / decode_wall, 2),
                "host_dispatches": st["host_dispatches"],
                "dispatches_per_step": round(
                    st["host_dispatches"]
                    / max(1, engine.decode_steps), 4),
                "dispatches_per_token": round(
                    st["host_dispatches"]
                    / max(1, engine.decode_tokens), 4),
                "tokens_per_dispatch": st["tokens_per_dispatch"],
                "horizon_effective": st["horizon_effective"],
                "itl_p99_ms": (round(
                    1000 * gaps[min(len(gaps) - 1,
                                    int(round(0.99 * (len(gaps) - 1))))],
                    3) if gaps else 0.0),
            }
            return row, {r.request_id: r.generated_ids for r in results}

        def _median_row(rows):
            rows = sorted(rows, key=lambda r: r["tokens_per_s"])
            return rows[len(rows) // 2]

        # PAIRED reps: within each rep the control and every K arm run
        # back-to-back, so a pair shares the same host weather and the
        # speedup is the median of per-rep ratios — arm-block designs
        # (all control reps, then all K reps) let minutes of host drift
        # land entirely on the ratio. Median-of-reps per the autotune
        # convention: best-of would keep each arm's luckiest host
        # wakeups, and the K=1 arm's 63 dispatch round-trips are exactly
        # where the typical-case latency this rung amortizes lives.
        arms = [("k1", 1)] + [(name, k)
                              for name, k in (("multistep_k4_slots8", 4),
                                              ("multistep_k8_slots8", 8))
                              if name in rungs]
        engines, rows, toks_by_arm = {}, {}, {}
        for name, k in arms:
            engines[name] = ServeEngine(
                bundle, params, n_slots=8, page_size=16, max_len=128,
                **({"decode_horizon": k} if k > 1 else {}))
            horizon_warm(engines[name])
            rows[name] = []
        for _ in range(5):
            for name, _k in arms:
                row, toks = horizon_rep(engines[name])
                rows[name].append(row)
                toks_by_arm[name] = toks
        ctl = _median_row(rows["k1"])
        for name, k in arms[1:]:
            ratios = sorted(r["tokens_per_s"] / max(c["tokens_per_s"], 1e-9)
                            for r, c in zip(rows[name], rows["k1"]))
            st = _median_row(rows[name])
            out[name] = {
                **st,
                "decode_horizon": k,
                "k1_control": ctl,
                "speedup_vs_k1": round(ratios[len(ratios) // 2], 3),
                # same submission order on fresh engines => matching ids;
                # the workload is greedy, so this is the identity gate
                "token_identity_vs_k1": toks_by_arm[name] == toks_by_arm["k1"],
            }
            out["value"] = st["tokens_per_s"]
            _emit({**out, "partial": True})
    _emit(out)


def run_elastic_check(only: str = None) -> None:
    """Elastic-fleet rungs (serve/elastic.py + checkpoint/reshard.py),
    each with its in-rung STATIC control per the one-new-variable policy:

    - engine_swap_midstream: the slots4 decode workload with a LIVE
      engine-generation swap (n_slots 4 -> 8, pool regrown) injected
      after 4 iterations, vs the identical workload on a static 4-slot
      engine in-rung — the swap is the only variable. Records tokens/s
      both ways, the swap pause (drain + payload move + seat), pages/
      bytes moved, seated-vs-requeued split, and the token-identity
      check against the control results (identical == the swap was
      invisible to every stream).
    - reshard_restore: save a 2-step llama-debug run on mesh A (fsdp=8,
      CPU-forced devices), then restore TWICE: onto the identical mesh
      (the static control — same save, same bytes, no reshard) and onto
      mesh B (fsdp=4, half the devices — a different dp/fsdp
      factorization through the same stamped entry point). Records both
      restore walls and the 2-step continued-trajectory deviation vs an
      uninterrupted golden run — the honest price of "shrink and
      continue".
    """
    _configure_jax_cache()
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_training_guide_tpu.models import get_model
    from distributed_training_guide_tpu.serve.api import (generate_many,
                                                          throughput_stats)
    from distributed_training_guide_tpu.serve.elastic import swap_engine
    from distributed_training_guide_tpu.serve.engine import ServeEngine
    from distributed_training_guide_tpu.serve.scheduler import Request

    rungs = (set(only.split(",")) if only
             else {"engine_swap_midstream", "reshard_restore"})
    bundle = get_model("llama-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    out = {"metric": "elastic", "model": "llama-debug", "value": 0.0}

    if "engine_swap_midstream" in rungs:
        reqs = [Request(prompt_ids=[3 + i, 17, 42], max_new_tokens=64,
                        seed=i) for i in range(8)]

        def workload(engine, swap_at=None):
            generate_many(engine, [Request(prompt_ids=[3, 17, 42],
                                           max_new_tokens=4)])
            if swap_at is not None:
                # compile-outside-the-timed-window, both generations: the
                # post-swap [8]-slot decode program warms through a
                # throwaway engine sharing the SAME ModelPrograms (its jit
                # cache), so the rung prices the swap itself — drain +
                # payload move + seat — not a first-touch compile that a
                # production swap pre-warms before draining
                warm = ServeEngine(bundle, params, n_slots=8, page_size=16,
                                   max_len=128, programs=engine.programs)
                generate_many(warm, [Request(prompt_ids=[3, 17, 42],
                                             max_new_tokens=4)])
            engine.decode_steps = engine.decode_tokens = 0
            ids = [engine.submit(dataclasses.replace(r, request_id=None))
                   for r in reqs]
            done, it, swap_stats, pause = {}, 0, None, 0.0
            t0 = time.perf_counter()
            while engine.has_work:
                if it == swap_at:
                    t_swap = time.perf_counter()
                    engine, evicted, swap_stats = swap_engine(
                        engine, n_slots=8)
                    pause = time.perf_counter() - t_swap
                    assert not evicted
                for res in engine.step():
                    done[res.request_id] = res
                it += 1
            stats = throughput_stats(list(done.values()),
                                     time.perf_counter() - t0, engine)
            return [done[i] for i in ids], stats, swap_stats, pause

        ctl_res, ctl, _, _ = workload(
            ServeEngine(bundle, params, n_slots=4, page_size=16,
                        max_len=128))
        res, stats, swap_stats, pause = workload(
            ServeEngine(bundle, params, n_slots=4, page_size=16,
                        max_len=128), swap_at=4)
        identical = all(a.generated_ids == b.generated_ids
                        for a, b in zip(res, ctl_res))
        out["engine_swap_midstream"] = {
            "tokens_per_s": stats["tokens_per_s"],
            "control_no_swap_tokens_per_s": ctl["tokens_per_s"],
            "tokens_per_s_vs_no_swap": round(
                stats["tokens_per_s"] / max(ctl["tokens_per_s"], 1e-9), 3),
            "swap_pause_ms": round(1000 * pause, 2),
            "token_identity_vs_no_swap": identical,
            **{f"swap_{k}": v for k, v in (swap_stats or {}).items()},
        }
        out["value"] = stats["tokens_per_s"]
        _emit({**out, "partial": True})

    if "reshard_restore" in rungs:
        import tempfile

        from distributed_training_guide_tpu.checkpoint import (
            CheckpointIO, restore_train_state, stamp_host_state)
        from distributed_training_guide_tpu.parallel import (make_mesh,
                                                             make_plan)
        from distributed_training_guide_tpu.train import (Trainer,
                                                          adamw_cosine)
        from distributed_training_guide_tpu.train.state import \
            host_state_dict

        n_dev = len(jax.devices())
        if n_dev < 2:
            out["reshard_restore"] = {"skipped": "needs >= 2 devices"}
        else:
            half = n_dev // 2
            ids = jnp.asarray(
                np.random.RandomState(0).randint(0, 512, (8, 16)))

            def steps(t, state, n):
                batch = {k: jax.device_put(ids, t.batch_shardings()[k])
                         for k in ("input_ids", "labels")}
                losses = []
                for _ in range(n):
                    state, m = t.step_fn(state, batch)
                    losses.append(float(m["loss"]))
                return state, losses

            def trainer(n):
                return Trainer(bundle=bundle,
                               optimizer=adamw_cosine(1e-3),
                               plan=make_plan("fsdp", make_mesh(
                                   devices=jax.devices()[:n], fsdp=n)),
                               donate=False)

            tg = trainer(n_dev)
            _, golden = steps(tg, tg.init_state(0), 4)
            t_a = trainer(n_dev)
            state, _ = steps(t_a, t_a.init_state(0), 2)
            with tempfile.TemporaryDirectory() as tmp:
                io = CheckpointIO(tmp)
                host = host_state_dict()
                host["global_step"] = 2
                io.save(state, stamp_host_state(host, t_a))
                t0 = time.perf_counter()
                restore_train_state(io, trainer(n_dev))
                same_mesh_s = time.perf_counter() - t0
                t_b = trainer(half)
                t0 = time.perf_counter()
                restored, _ = restore_train_state(io, t_b)
                reshard_s = time.perf_counter() - t0
                _, cont = steps(t_b, restored, 2)
            dev = max(abs(c - g) / abs(g)
                      for c, g in zip(cont, golden[2:]))
            out["reshard_restore"] = {
                "mesh_a": f"fsdp={n_dev}", "mesh_b": f"fsdp={half}",
                "restore_same_mesh_s": round(same_mesh_s, 3),
                "restore_resharded_s": round(reshard_s, 3),
                "reshard_overhead_x": round(
                    reshard_s / max(same_mesh_s, 1e-9), 3),
                "continued_traj_max_rel_dev": float(dev),
                "within_2e4": bool(dev < 2e-4),
            }
            if not out["value"]:
                out["value"] = round(1.0 / max(reshard_s, 1e-9), 3)
    _emit(out)


def run_post_check(only: str = None) -> None:
    """Post-training loop rung (post/): rollout → score → update →
    publish on llama-debug, with the in-rung FROZEN-POLICY control per
    the one-new-variable policy.

    - post_loop_cpu: 5 loop iterations of REINFORCE-with-baseline on the
      dense synthetic band reward (fraction of sampled tokens with
      id < 64 — ~0.125 at init), 24 same-prompt rollouts x 16 new tokens
      through an 8-slot engine, full-parameter policy at lr 0.1 (the
      config tests/test_post.py pins as measurably learning). The
      control is the IDENTICAL loop with ``frozen=True`` — rollout +
      score only, no update, no publish — so the update+publish half is
      the only new variable: its reward trajectory stays at the init
      band rate and its rollout tok/s prices the engine alone.
      Records per-arm reward trajectories, warm rollout tok/s (iteration
      0 carries the compiles — reported separately), publish latency ms,
      and step time.
    - post_qlora_cpu (queued sweep rung): the QLoRA shape
      (arXiv:2305.14314) of the same loop — an int8-SNAPPED frozen base
      (post.qlora_base) + fp LoRA adapters rolling out through a
      weight_dtype="int8" engine, so the adapters learn residuals of
      the policy the serve plane actually runs. The in-rung control is
      the IDENTICAL lora_only loop on the untouched fp base + fp
      engine: the quantized base is the only new variable, and the gate
      is the reward trajectory tracking the control's. Every publish is
      the normal fp merge — the engine re-quantizes through one
      compiled program, pinned retrace-free (jit cache sizes flat)."""
    _configure_jax_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_training_guide_tpu.models import get_model
    from distributed_training_guide_tpu.post import (PostTrainingLoop,
                                                     ProgrammaticScorer,
                                                     band_reward,
                                                     merged_params)
    from distributed_training_guide_tpu.serve.engine import ServeEngine
    from distributed_training_guide_tpu.train.optimizer import adamw_cosine
    from distributed_training_guide_tpu.train.step import Trainer

    rungs = set(only.split(",")) if only else {"post_loop_cpu"}
    out = {"metric": "post_loop", "model": "llama-debug", "value": 0.0}
    if "post_loop_cpu" in rungs:
        bundle = get_model("llama-debug", dtype=jnp.float32)
        n_iter = 5

        def arm(frozen: bool):
            trainer = Trainer(bundle=bundle, optimizer=adamw_cosine(0.1),
                              guard_policy="skip")
            state = trainer.init_state(0)
            engine = ServeEngine(bundle, merged_params(trainer, state),
                                 n_slots=8, page_size=16, max_len=64)
            loop = PostTrainingLoop(
                trainer, engine, ProgrammaticScorer(band_reward(64)),
                [[3, 10, 17]] * 24, state=state, max_new_tokens=16,
                temperature=1.0, base_seed=0, frozen=frozen)
            hist = loop.run(n_iter)
            warm = hist[1:]          # iteration 0 pays the compiles
            return {
                "reward_trajectory": [round(m["reward_mean"], 4)
                                      for m in hist],
                "rollout_tokens_per_s": round(float(np.mean(
                    [m["rollout_tokens_per_s"] for m in warm])), 1),
                "rollout_tokens_per_s_cold": hist[0][
                    "rollout_tokens_per_s"],
                "publish_ms_mean": round(float(np.mean(
                    [m["publish_ms"] for m in warm])), 2),
                "step_s_mean": round(float(np.mean(
                    [m["step_s"] for m in warm])), 4),
                "publishes": loop.publishes,
            }

        live = arm(frozen=False)
        ctl = arm(frozen=True)
        traj = live["reward_trajectory"]
        out["post_loop_cpu"] = {
            "iterations": n_iter,
            **live,
            "reward_delta": round(traj[-1] - traj[0], 4),
            "control_frozen": ctl,
            "control_reward_delta": round(
                ctl["reward_trajectory"][-1]
                - ctl["reward_trajectory"][0], 4),
        }
        out["value"] = live["rollout_tokens_per_s"]

    if "post_qlora_cpu" in rungs:
        # QLoRA (arXiv:2305.14314): int8-snapped frozen base + fp LoRA,
        # rollouts through an int8-weights engine; control = the same
        # lora_only loop on the fp base + fp engine (one new variable —
        # the quantized base). The merge→publish path re-quantizes
        # inside the engine's one compiled requant program; the cache
        # sizes recorded per arm pin it retrace-free.
        from distributed_training_guide_tpu.models.lora import lora_bundle
        from distributed_training_guide_tpu.post import qlora_base

        base = get_model("llama-debug", dtype=jnp.float32)
        n_iter = 5

        def qlora_arm(quantized: bool):
            wrapped = lora_bundle(base, rank=8, alpha=16.0)
            init = wrapped.init(wrapped.config, jax.random.key(0))
            if quantized:
                init = {"base": qlora_base(init["base"]),
                        "lora": init["lora"]}
            trainer = Trainer(bundle=wrapped, optimizer=adamw_cosine(0.1),
                              lora_only=True, guard_policy="skip")
            state = trainer.init_state_from_params(init)
            engine = ServeEngine(
                base, merged_params(trainer, state), n_slots=8,
                page_size=16, max_len=64,
                weight_dtype="int8" if quantized else None)
            loop = PostTrainingLoop(
                trainer, engine, ProgrammaticScorer(band_reward(64)),
                [[3, 10, 17]] * 24, state=state, max_new_tokens=16,
                temperature=1.0, base_seed=0)
            hist = loop.run(1)            # iteration 0 pays the compiles
            sizes0 = engine.programs.jit_cache_sizes()
            hist += loop.run(n_iter - 1)
            warm = hist[1:]
            return {
                "reward_trajectory": [round(m["reward_mean"], 4)
                                      for m in hist],
                "rollout_tokens_per_s": round(float(np.mean(
                    [m["rollout_tokens_per_s"] for m in warm])), 1),
                "publish_ms_mean": round(float(np.mean(
                    [m["publish_ms"] for m in warm])), 2),
                "publishes": loop.publishes,
                "weight_bytes": engine.weight_bytes(),
                "retrace_free": (
                    engine.programs.jit_cache_sizes() == sizes0),
            }

        q = qlora_arm(quantized=True)
        fp = qlora_arm(quantized=False)
        qt, ft = q["reward_trajectory"], fp["reward_trajectory"]
        out["post_qlora_cpu"] = {
            "iterations": n_iter,
            **{f"qlora_{k}": v for k, v in q.items()},
            "qlora_reward_delta": round(qt[-1] - qt[0], 4),
            "control_fp_lora": fp,
            "control_reward_delta": round(ft[-1] - ft[0], 4),
            "weight_bytes_vs_fp": round(
                q["weight_bytes"] / fp["weight_bytes"], 4),
            "reward_final_gap_vs_fp": round(qt[-1] - ft[-1], 4),
        }
        out["value"] = q["rollout_tokens_per_s"]
    _emit(out)


def run_load_check(only: str = None) -> None:
    """Open-loop load rungs (serve/loadgen.py + serve/controller.py):
    the first serve numbers measured under traffic the engine does NOT
    control — arrivals on a wall-clock schedule, goodput (deadline-met
    completions/s, the DistServe metric) instead of raw tok/s.

    - load_saturation: the saturation curve on one llama-debug engine —
      Poisson arrivals at climbing rates, goodput + p50/p99 TTFT/ITL
      tails per point. The knee where goodput stops following offered
      load is the engine's capacity, a number a closed-loop bench
      structurally cannot produce.
    - load_controller_ab: the SAME seeded burst trace (steady Poisson
      base + a packed flash crowd) through a STATIC 1-replica fleet
      (the in-rung control) and an identical fleet under the SLO
      controller allowed to scale to 2 replicas — the controller is the
      only variable. The static arm's small admission queue refuses the
      burst overflow; the controller arm absorbs it by scaling up, so
      its goodput must match or beat the control on the identical
      trace. Records both arms, the win, and the measured cold start.
    """
    _configure_jax_cache()
    import dataclasses

    import jax
    import jax.numpy as jnp

    from distributed_training_guide_tpu.models import get_model
    from distributed_training_guide_tpu.serve.controller import (Controller,
                                                                 SLO)
    from distributed_training_guide_tpu.serve.engine import (ModelPrograms,
                                                             ServeEngine)
    from distributed_training_guide_tpu.serve.loadgen import (
        build_schedule, default_scenarios, poisson_arrivals, run_open_loop,
        saturation_sweep, trace_arrivals)
    from distributed_training_guide_tpu.serve.router import Replica, Router

    rungs = (set(only.split(",")) if only
             else {"load_saturation", "load_controller_ab"})
    bundle = get_model("llama-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    vocab = int(bundle.config.vocab_size)
    # ONE ModelPrograms for every engine in both rungs (and for the
    # controller's spawn_like clones): the programs compile once, so the
    # rungs price scheduling + control, not jit
    programs = ModelPrograms(bundle, params)
    kw = dict(n_slots=2, page_size=4, max_len=32)
    scenarios = default_scenarios(max_len=32, page_size=4, vocab=vocab,
                                  deadline_s=2.0, seed=0)
    out = {"metric": "load", "model": "llama-debug", "value": 0.0}

    if "load_saturation" in rungs:
        sweep = saturation_sweep(
            lambda: ServeEngine(bundle, params, programs=programs,
                                max_queue=16, **kw),
            [1.0, 4.0, 16.0], duration_s=4.0, scenarios=scenarios,
            vocab=vocab, seed=0, max_wall_s=60.0)
        knee = max(sweep, key=lambda p: p["goodput_rps"])
        out["load_saturation"] = {
            "points": [{k: p[k] for k in (
                "rate_rps", "offered", "completed", "refused",
                "deadline_missed", "goodput_rps", "offered_rps",
                "ttft_p50_s", "ttft_p99_s", "itl_p50_s", "itl_p99_s",
                "refusal_rate", "wall_s", "timed_out")} for p in sweep],
            "peak_goodput_rps": knee["goodput_rps"],
            "peak_at_rate_rps": knee["rate_rps"],
        }
        out["value"] = knee["goodput_rps"]
        _emit({**out, "partial": True})

    if "load_controller_ab" in rungs:
        # one deterministic burst trace, replayed against both arms: a
        # 2 rps base over 8 s with ~24 extra arrivals packed into the
        # third second — the flash crowd a static small-queue fleet
        # must refuse and an elastic one can absorb
        base = poisson_arrivals(2.0, 8.0, seed=0)
        burst = [2.0 + t for t in poisson_arrivals(24.0, 1.0, seed=1)]
        trace = trace_arrivals(base + burst)
        schedule = build_schedule(trace, scenarios, vocab=vocab, seed=0)

        def arm(managed: bool) -> dict:
            engine = ServeEngine(bundle, params, programs=programs,
                                 max_queue=4, **kw)
            router = Router([Replica("r0", engine)])
            controller = None
            if managed:
                controller = Controller(
                    router, slo=SLO(queue_high=2.0), min_replicas=1,
                    max_replicas=2, hold_up=2, hold_down=10_000,
                    cooldown_s=0.25)
            # fresh Request copies per arm: engines stamp request_id
            sched = [(t, dataclasses.replace(r, request_id=None))
                     for t, r in schedule]
            report = run_open_loop(router, sched, controller=controller,
                                   max_wall_s=90.0)
            res = {k: getattr(report, k) for k in (
                "goodput_rps", "offered", "completed", "refused",
                "deadline_missed", "resubmit_exhausted", "ttft_p50_s",
                "ttft_p99_s", "itl_p99_s", "refusal_rate", "wall_s",
                "timed_out")}
            res["final_replicas"] = len(router.replicas)
            if controller is not None:
                cs = controller.stats()
                res["controller"] = {k: cs[k] for k in (
                    "state", "observations", "stale_snapshots",
                    "scale_up", "scale_down", "spawn_failed", "shed_on",
                    "backpressure_on")}
                res["cold_start_s"] = [round(c, 4)
                                       for c in cs["cold_start_s"]]
            router.close()
            return res

        static = arm(managed=False)
        managed = arm(managed=True)
        out["load_controller_ab"] = {
            "trace_arrivals": len(trace),
            "static": static,
            "controller": managed,
            "goodput_win_rps": round(
                managed["goodput_rps"] - static["goodput_rps"], 3),
        }
        out["value"] = managed["goodput_rps"]
    _emit(out)


# ---------------------------------------------------------------------------
# parent: ladder orchestration (never touches the TPU itself)
# ---------------------------------------------------------------------------

# Tuning experiments queued behind the headline (BENCH.md "levers already in
# the tree"), likeliest headline-beaters first. `--sweep` runs them
# probe-gated whenever the pool allows; complete results update the
# last-good cache so the best number found becomes official evidence.
SWEEP_QUEUE = [
    dict(name="attn_mlp", model="llama-650m", batch=8, seq=2048,
         remat=True, remat_policy="attn_mlp"),
    dict(name="adafactor_b16", model="llama-650m", batch=16, seq=2048,
         remat=True, remat_policy="attn", optimizer="adafactor"),
    dict(name="adafactor_b8", model="llama-650m", batch=8, seq=2048,
         remat=True, remat_policy="attn", optimizer="adafactor"),
    dict(name="adafactor_b24", model="llama-650m", batch=24, seq=2048,
         remat=True, remat_policy="attn", optimizer="adafactor"),
    # cross-products: adafactor's freed 5.2 GB can pay for the attn_mlp
    # policy's bigger saved set at a bigger batch — the likeliest
    # combination to beat both single-lever results
    dict(name="adafactor_attnmlp_b16", model="llama-650m", batch=16,
         seq=2048, remat=True, remat_policy="attn_mlp",
         optimizer="adafactor"),
    dict(name="adafactor_attnmlp_b8", model="llama-650m", batch=8, seq=2048,
         remat=True, remat_policy="attn_mlp", optimizer="adafactor"),
    # pure bf16 state (params + Adam moments in bf16): frees ~3.9 GB of the
    # 650M fp32 state — the deepest memory lever, at a documented numerics
    # trade (the reference's MixedPrecisionPolicy keeps fp32 shards)
    dict(name="bf16_params_b16", model="llama-650m", batch=16, seq=2048,
         remat=True, remat_policy="attn", param_dtype="bfloat16"),
    dict(name="lion_b16", model="llama-650m", batch=16, seq=2048,
         remat=True, remat_policy="attn", optimizer="lion"),
    dict(name="loss_chunks8", model="llama-650m", batch=8, seq=2048,
         remat=True, remat_policy="attn", loss_chunks=8),
    # long-context single-chip rungs: the flash kernel's O(S) memory is the
    # whole story at seq 8k (the 2026-07-29 sweep measured 47.5% at 4096/b4).
    # max_position raises llama-650m's RoPE table past its 4096 preset —
    # without it run_rung's seq = min(seq, max_position_embeddings) clamp
    # would silently re-measure 4096 under an 8k name
    dict(name="seq8k_b2", model="llama-650m", batch=2, seq=8192,
         max_position=8192, remat=True, remat_policy="attn"),
    dict(name="seq8k_adafactor_b4", model="llama-650m", batch=4, seq=8192,
         max_position=8192, remat=True, remat_policy="attn",
         optimizer="adafactor"),
    dict(name="tinyllama_adafactor_lc8", model="tinyllama-1.1b", batch=8,
         seq=2048, remat=True, remat_policy="attn", optimizer="adafactor",
         loss_chunks=8),
    # offload A/B (VERDICT r3 item 8): step time with --offload-opt-state at
    # the headline config; the without-offload side is the headline itself
    # (695 ms). Measures the whole-state pinned_host<->HBM round-trip the
    # reference's 405B recipe pays ~4 s/step for (its README:274).
    dict(name="offload_opt_b8", model="llama-650m", batch=8, seq=2048,
         remat=True, remat_policy="attn", offload_opt_state=True),
    # --- round-4 follow-ups, informed by the 2026-07-31 on-chip results:
    # adafactor fits b16 (52.8%) but OOMs at b24; attn_mlp+adafactor fits b8
    # (52.4%) but OOMs at b16; bf16 state fits b16 (53.1%). Probe the
    # boundaries and the remaining crosses.
    dict(name="adafactor_b20", model="llama-650m", batch=20, seq=2048,
         remat=True, remat_policy="attn", optimizer="adafactor"),
    dict(name="adafactor_attnmlp_b12", model="llama-650m", batch=12, seq=2048,
         remat=True, remat_policy="attn_mlp", optimizer="adafactor"),
    dict(name="bf16_adafactor_b24", model="llama-650m", batch=24, seq=2048,
         remat=True, remat_policy="attn", optimizer="adafactor",
         param_dtype="bfloat16"),
    dict(name="bf16_b20", model="llama-650m", batch=20, seq=2048,
         remat=True, remat_policy="attn", param_dtype="bfloat16"),
    dict(name="seq4k_adafactor_b8", model="llama-650m", batch=8, seq=4096,
         remat=True, remat_policy="attn", optimizer="adafactor"),
    dict(name="lion_b8", model="llama-650m", batch=8, seq=2048,
         remat=True, remat_policy="attn", optimizer="lion"),
    # beyond-parity: single-chip MoE throughput (the reference has no MoE
    # chapter at all). MFU here is vs *active* params (num_active_params),
    # the standard MoE accounting.
    dict(name="moe1b_adafactor_b8", model="moe-1b-8e", batch=8, seq=2048,
         remat=True, remat_policy="attn", optimizer="adafactor"),
    # --- precision-policy rungs (train/precision.py; unmeasured, so they sit
    # ahead of the fence entries per the fence4 ordering note below).
    # bf16-master = 8 B/param total state (fp32-computed update, bf16
    # storage) — vs param_dtype=bfloat16's bf16-computed update at the same
    # memory, this is the same batch budget with better numerics; adam8bit
    # frees ~3.7 GB of 650M fp32 Adam moments, paying int8 (de)quantize
    # compute inside the fused step — the measurement decides whether the
    # bigger batch wins it back.
    dict(name="bf16master_b16", model="llama-650m", batch=16, seq=2048,
         remat=True, remat_policy="attn", precision="bf16-master"),
    dict(name="bf16master_b24", model="llama-650m", batch=24, seq=2048,
         remat=True, remat_policy="attn", precision="bf16-master"),
    dict(name="adam8bit_b16", model="llama-650m", batch=16, seq=2048,
         remat=True, remat_policy="attn", precision="adam8bit"),
    dict(name="bf16master_adam8bit_b24", model="llama-650m", batch=24,
         seq=2048, remat=True, remat_policy="attn",
         precision="bf16-master+adam8bit"),
    dict(name="bf16master_adam8bit_attnmlp_b16", model="llama-650m",
         batch=16, seq=2048, remat=True, remat_policy="attn_mlp",
         precision="bf16-master+adam8bit"),
    # --- dropless MoE A/B (models/moe.py moe_dispatch="ragged": sorted
    # dispatch + grouped GEMMs, no [E, C, D] capacity padding). Same shape
    # as the 20.0%-MFU moe1b_adafactor_b8 rung so the pair is a direct
    # dense-vs-ragged measurement; queued ahead of the fence entries (the
    # fence4 ordering note below) so the next healthy window prices it.
    # Ragged is the ONLY new variable here — the fence cross lives further
    # down beside its dense sibling, per the one-new-variable stall policy.
    dict(name="moe1b_ragged_adafactor_b8", model="moe-1b-8e", batch=8,
         seq=2048, remat=True, remat_policy="attn", optimizer="adafactor",
         moe_dispatch="ragged"),
    # --- latency-hiding schedule A/B (ops/overlap.py --overlap-schedule:
    # unrolled explicit fsdp all-gather prefetch + per-layer grad
    # reduce-scatter, ring EP exchange, fused hidden->loss kernel). Queued
    # ahead of the fence entries per the one-new-variable policy: overlap
    # is the ONLY variable vs its control, measured in the same window so
    # pool drift can't masquerade as a schedule win. detail records the
    # XLA latency-hiding-scheduler flags the schedule relies on — on a
    # multi-chip fsdp mesh set XLA_FLAGS from detail.xla_scheduler_flags.
    dict(name="fsdp_overlap_b8", model="llama-650m", batch=8, seq=2048,
         remat=True, remat_policy="attn", overlap=True),
    dict(name="fsdp_base_b8_ab", model="llama-650m", batch=8, seq=2048,
         remat=True, remat_policy="attn"),
    # ragged MoE + ring EP double-buffer vs its same-shape non-overlap
    # sibling (moe1b_ragged_adafactor_b8 above) — overlap the only delta
    dict(name="moe1b_ragged_overlap_adafactor_b8", model="moe-1b-8e",
         batch=8, seq=2048, remat=True, remat_policy="attn",
         optimizer="adafactor", moe_dispatch="ragged", overlap=True),
    # --- distributed serving plane (serve/ PR 9; queued ahead of the
    # fence entries per the one-new-variable policy — TPU pool still
    # down, recorded queued). decode_sharded_tp2 = the slots8 decode
    # workload with the KV pool kv-head-sharded over tp=2 (its control is
    # the replicated-pool slots8 history in every healthy window);
    # disagg_prefill192_decode4 = the mixed_chunked workload through the
    # disaggregated prefill/decode pair (its control is mixed_chunked;
    # disaggregation the new variable, MINUS one decode slot — the pair
    # runs 4+1 where the monolith ran 5). NOTE the facade is one serial
    # host thread, so this prices the split's overhead + the zero-copy
    # handoff, not prefill-interference removal (that needs concurrent
    # executors — the multi-host seam).
    dict(name="decode_sharded_tp2", decode_rungs="decode_sharded_tp2"),
    dict(name="disagg_prefill192_decode4",
         decode_rungs="disagg_prefill192_decode4"),
    # --- speculative decoding (serve/spec.py, PR 10; one new variable
    # each: the drafter — both rungs run the identical lookup-friendly
    # workload whose spec-off control is measured inside the rung).
    # spec_ngram8 = prompt-lookup drafting, 8 slots, k=8; spec_draft8 =
    # the self-draft-model drafter on the same workload (prices the
    # drafter's own k sequential forwards per iteration against the
    # verify amortization — on CPU the draft loop is the bottleneck,
    # on TPU the weight-read amortization is the point).
    dict(name="spec_ngram8", decode_rungs="spec_ngram8"),
    dict(name="spec_draft8", decode_rungs="spec_draft8"),
    # spec_flash8 / chunk_flash = the block_q=T kernel family A/B: the
    # spec_ngram8 and mixed_chunked workloads re-run with every paged
    # forward (decode + verify + chunk) on the flash kernel vs the
    # in-rung gather-family control — one new variable each (the attend
    # family). CPU legs price the interpret emulation honestly; the TPU
    # pool is where the O(context)-vs-3x read claim gets its number.
    dict(name="spec_flash8", decode_rungs="spec_flash8"),
    dict(name="chunk_flash", decode_rungs="chunk_flash"),
    # --- quantized KV pages (serve/kv_pages.py kv_dtype="int8"; one new
    # variable each — both rungs measure their fp32-KV control in-rung).
    # kvq_int8_slots8 = the slots8 decode workload on the int8 pool:
    # tput, the pool byte ratio with scales included (~0.31x at
    # llama-debug's head_dim 16), per-request greedy divergence
    # positions. kvq_spec_accept = the spec_ngram8 workload int8-vs-fp32
    # recording the acceptance-rate delta — the sensitive KV-fidelity
    # meter (gate |delta| <= 0.02, also pinned in tests). On TPU the
    # byte ratio is also the decode-read ratio on the bandwidth-bound
    # path — these rungs make the capacity claim honest on CPU first.
    dict(name="kvq_int8_slots8", decode_rungs="kvq_int8_slots8"),
    dict(name="kvq_spec_accept", decode_rungs="kvq_spec_accept"),
    # router_fleet2 = 2 replicas behind serve/router.py on a shared-
    # prefix workload; the in-rung control is ONE identical engine, so
    # the router layer (+ second replica's schedulers) is the only new
    # variable. handoff_crossproc = disagg on transport='cross_host'
    # (real serialized payload over the socket protocol) with the
    # same-host 0-byte pair as the in-rung control — transport the only
    # variable — plus the cross-process wire digest/MiB/s leg.
    dict(name="router_fleet2", decode_rungs="router_fleet2"),
    dict(name="handoff_crossproc", decode_rungs="handoff_crossproc"),
    # --- elastic fleet (serve/elastic.py + checkpoint/reshard.py, PR 13;
    # one new variable each, with the static control measured IN-RUNG).
    # engine_swap_midstream = the slots4 workload with a live
    # n_slots 4->8 generation swap injected mid-stream vs the identical
    # no-swap control (records the swap pause, pages/bytes moved, and
    # the token-identity bit — the swap must be invisible to every
    # stream). reshard_restore = restore a stamped checkpoint onto the
    # SAME mesh (control) then onto a half-size fsdp mesh (the elastic
    # shrink), recording both restore walls and the continued-trajectory
    # deviation vs an uninterrupted golden.
    dict(name="engine_swap_midstream", elastic_rungs="engine_swap_midstream"),
    dict(name="reshard_restore", elastic_rungs="reshard_restore"),
    # --- post-training loop (post/, PR 15): rollout→score→update→publish
    # on llama-debug with the IN-RUNG frozen-policy control (rollout +
    # score only — the update/publish half is the one new variable).
    # Records reward trajectories both arms (live must climb, frozen must
    # not), warm rollout tok/s, publish latency, step time. CPU rung by
    # design: the loop is host-driven scheduling + debug-size compute;
    # the TPU story is the trainer/engine rungs it composes.
    dict(name="post_loop_cpu", post_rungs="post_loop_cpu"),
    # --- open-loop load harness + SLO control plane (serve/loadgen.py +
    # serve/controller.py, PR 16). load_saturation = the goodput-vs-
    # offered-rate curve on one llama-debug engine (the capacity knee a
    # closed-loop bench cannot see). load_controller_ab = one seeded
    # burst trace through a static 1-replica fleet (in-rung control) vs
    # the SLO controller allowed to scale to 2 — the controller is the
    # only variable and must match or beat the static arm's goodput.
    dict(name="load_saturation", load_rungs="load_saturation"),
    dict(name="load_controller_ab", load_rungs="load_controller_ab"),
    # --- int8 serve-plane WEIGHTS (serve/weights.py weight_dtype="int8",
    # dequantized in-kernel by ops/quantized_matmul.py; one new variable
    # each, fp control in-rung). wq_int8_slots8 = the slots8 decode
    # workload on block-quantized params: tok/s, the resident-weight AND
    # publish-payload byte ratio (~0.28x on llama-debug — the >= 1.9x
    # claim), greedy divergence positions. wq_spec_accept = the
    # kvq_spec_accept acceptance-delta methodology pointed at weight
    # fidelity — int8 vs the snapped-fp control (same rounded policy,
    # fp storage) gated |delta| <= 0.02 and pinned in tests, raw-fp
    # acceptance recorded ungated beside it. post_qlora_cpu =
    # the post_loop_cpu shape with an int8-snapped frozen base + fp LoRA
    # (QLoRA) rolling out through an int8-weights engine vs the fp
    # lora_only control — reward trajectory must track the control's,
    # publishes stay retrace-free through the requant program.
    dict(name="wq_int8_slots8", decode_rungs="wq_int8_slots8"),
    dict(name="wq_spec_accept", decode_rungs="wq_spec_accept"),
    dict(name="post_qlora_cpu", post_rungs="post_qlora_cpu"),
    # multi-LoRA rungs: multilora_slots8 = 8 slots serving 4 co-resident
    # tenants through the ragged grouped-GEMM decode path, with the
    # base-only and dedicated-merged-engine controls in-rung — the
    # consolidation factor (mixed tok/s over per-tenant serial) is the
    # headline, S-LoRA/Punica's claim priced on this engine.
    # multilora_publish = adapter insert latency (one cached jit,
    # traced slot index) vs a full publish_params on the same engine,
    # jit caches pinned flat across the churn.
    dict(name="multilora_slots8", decode_rungs="multilora_slots8"),
    dict(name="multilora_publish", decode_rungs="multilora_publish"),
    # tiered-KV rungs (serve/tiering.py; queued ahead of the fence
    # entries per the one-new-variable policy, controls in-rung).
    # tiered_prefix8 = host-RAM spill/restore vs eviction-recompute on
    # a one-chain pool; directory_pull2 = the fleet prefix directory's
    # warm-sibling page pull vs cold re-prefill. Both record the
    # prefill calls saved — the unit the tier exists to avoid.
    dict(name="tiered_prefix8", decode_rungs="tiered_prefix8"),
    dict(name="directory_pull2", decode_rungs="directory_pull2"),
    # fused decode horizons (serve/engine.py decode_horizon=K; queued
    # ahead of the fence entries per the one-new-variable policy, K=1
    # control in-rung). multistep_k4/k8 = the slots8 workload with K
    # iterations per compiled dispatch + double-buffered host booking —
    # dispatches/token, tok/s vs control, greedy token-identity, and
    # the per-token itl_p99 the burst costs. On CPU the host round-trip
    # is the whole wall; on the TPU pool these same rungs price the
    # dispatch-latency amortization the fence4 entries measure on the
    # training side.
    dict(name="multistep_k4_slots8", decode_rungs="multistep_k4_slots8"),
    dict(name="multistep_k8_slots8", decode_rungs="multistep_k8_slots8"),
    # LAST on purpose: fence_every=4 dispatches 4 steps ahead, the exact
    # pattern this pool's documented failure mode punishes — its first
    # attempt (2026-07-31 03:50) stalled and the pool went down with it.
    # Keep it queued (the lever matters on healthy pods) but never let it
    # run ahead of unmeasured experiments again.
    dict(name="fence4", model="llama-650m", batch=8, seq=2048,
         remat=True, remat_policy="attn", fence_every=4),
    # --- fence cross-products, informed by the 2026-07-31 06:41 result:
    # fence_every=4 alone took the b8 headline 695 -> 637 ms (55.1% MFU) —
    # dispatch latency was ~8% of the per-step-fenced number. Cross it with
    # the other winning levers. (Ordering: likeliest headline-beaters first;
    # all configs below already measured OK without the fence, so the fence
    # is the only new variable and a stall costs one retry, not a window.)
    dict(name="fence4_adafactor_b16", model="llama-650m", batch=16, seq=2048,
         remat=True, remat_policy="attn", optimizer="adafactor",
         fence_every=4),
    dict(name="fence4_bf16_b16", model="llama-650m", batch=16, seq=2048,
         remat=True, remat_policy="attn", param_dtype="bfloat16",
         fence_every=4),
    dict(name="fence8_b8", model="llama-650m", batch=8, seq=2048,
         remat=True, remat_policy="attn", fence_every=8),
    dict(name="fence4_adafactor_attnmlp_b8", model="llama-650m", batch=8,
         seq=2048, remat=True, remat_policy="attn_mlp",
         optimizer="adafactor", fence_every=4),
    dict(name="fence4_seq8k_adafactor_b4", model="llama-650m", batch=4,
         seq=8192, max_position=8192, remat=True, remat_policy="attn",
         optimizer="adafactor", fence_every=4),
    dict(name="fence4_bf16_adafactor_b24", model="llama-650m", batch=24,
         seq=2048, remat=True, remat_policy="attn", optimizer="adafactor",
         param_dtype="bfloat16", fence_every=4),
    # --- crosses around the 06:47 winner (fence4 + adafactor + attn_mlp at
    # b8 = 56.8%): push the same recipe to long context, and see whether
    # bf16 params buy the batch that fp32 attn_mlp+adafactor couldn't fit
    dict(name="fence4_seq8k_adafactor_attnmlp_b4", model="llama-650m",
         batch=4, seq=8192, max_position=8192, remat=True,
         remat_policy="attn_mlp", optimizer="adafactor", fence_every=4),
    dict(name="fence4_bf16_adafactor_attnmlp_b16", model="llama-650m",
         batch=16, seq=2048, remat=True, remat_policy="attn_mlp",
         optimizer="adafactor", param_dtype="bfloat16", fence_every=4),
    dict(name="fence4_bf16_adafactor_attnmlp_b12", model="llama-650m",
         batch=12, seq=2048, remat=True, remat_policy="attn_mlp",
         optimizer="adafactor", param_dtype="bfloat16", fence_every=4),
    dict(name="fence4_adafactor_attnmlp_seq4k_b8", model="llama-650m",
         batch=8, seq=4096, remat=True, remat_policy="attn_mlp",
         optimizer="adafactor", fence_every=4),
    # --- tinyllama diagnosis: 1.1b measured a suspicious 33.6%
    # (tinyllama_adafactor_lc8) where a bigger model should have HIGHER
    # arithmetic intensity than 650m. Hypothesis: fp32 params (4.4 GB) +
    # fp32 grads + activations sit at the 16 GB ceiling -> XLA spills.
    # bf16 params halve the resident params; attn_mlp shrinks activations;
    # chunked CE already on. If the 1.1b recipe beats 56.8%, it becomes
    # the headline candidate for round 5.
    dict(name="tinyllama_bf16_adafactor_attnmlp_fence4_b8",
         model="tinyllama-1.1b", batch=8, seq=2048, remat=True,
         remat_policy="attn_mlp", optimizer="adafactor",
         param_dtype="bfloat16", fence_every=4, loss_chunks=8),
    dict(name="tinyllama_bf16_adafactor_fence4_b4",
         model="tinyllama-1.1b", batch=4, seq=2048, remat=True,
         remat_policy="attn", optimizer="adafactor",
         param_dtype="bfloat16", fence_every=4, loss_chunks=8),
    dict(name="tinyllama_adafactor_fence4_b4", model="tinyllama-1.1b",
         batch=4, seq=2048, remat=True, remat_policy="attn",
         optimizer="adafactor", fence_every=4, loss_chunks=8),
    # --- no-remat rungs: remat trades FLOPs for memory; at a batch small
    # enough to hold ALL activations the backward recomputes nothing. MFU
    # counts model FLOPs (6ND), so if ms/token drops below the b8 attn_mlp
    # recipe this wins the headline outright.
    dict(name="fence4_noremat_adafactor_b4", model="llama-650m", batch=4,
         seq=2048, remat=False, optimizer="adafactor", fence_every=4),
    dict(name="fence4_noremat_adafactor_b6", model="llama-650m", batch=6,
         seq=2048, remat=False, optimizer="adafactor", fence_every=4),
    dict(name="fence4_noremat_b4", model="llama-650m", batch=4, seq=2048,
         remat=False, fence_every=4),
    # --- gather-only MoE dispatch (models/moe.py, 2026-07-31): same config
    # as the 20%-MFU moe1b_adafactor_b8 measurement but the row scatters are
    # gone (dispatch = int32 slot-map inversion + row gather; combine =
    # reshape+sum). New name so the resumable queue re-measures it.
    dict(name="moe1b_adafactor_b8_gather", model="moe-1b-8e", batch=8,
         seq=2048, remat=True, remat_policy="attn", optimizer="adafactor"),
    dict(name="moe1b_adafactor_fence4_b8_gather", model="moe-1b-8e", batch=8,
         seq=2048, remat=True, remat_policy="attn", optimizer="adafactor",
         fence_every=4),
    # ragged x fence cross, beside its dense sibling above: by the time the
    # queue reaches here both the plain ragged rung and the dense fence4
    # rung have measured, so the fence is again the only new variable
    dict(name="moe1b_ragged_adafactor_fence4_b8", model="moe-1b-8e", batch=8,
         seq=2048, remat=True, remat_policy="attn", optimizer="adafactor",
         moe_dispatch="ragged", fence_every=4),
    # --- the head-dim experiment: llama-1b-hd128 is tinyllama's size with
    # 16x128 heads instead of 32x64. If the 33.6% tinyllama measurement was
    # the half-width MXU tiles, these should land near the 650m numbers —
    # and a 1B model at ~55% would be a stronger headline than 650m.
    dict(name="l1bhd128_adafactor_fence4_b4", model="llama-1b-hd128",
         batch=4, seq=2048, remat=True, remat_policy="attn",
         optimizer="adafactor", fence_every=4),
    dict(name="l1bhd128_bf16_adafactor_attnmlp_fence4_b8",
         model="llama-1b-hd128", batch=8, seq=2048, remat=True,
         remat_policy="attn_mlp", optimizer="adafactor",
         param_dtype="bfloat16", fence_every=4, loss_chunks=8),
    dict(name="l1bhd128_adafactor_attnmlp_fence4_b4",
         model="llama-1b-hd128", batch=4, seq=2048, remat=True,
         remat_policy="attn_mlp", optimizer="adafactor", fence_every=4,
         loss_chunks=8),
    # --- single-chip long-context ceiling: flash's O(S) memory + the attn
    # policy carried 8k at 55.9%; push to 16k/32k (same token budget per
    # step as the 8k rungs, longer rows). max_position raises the RoPE
    # table; loss_chunks caps the [B,S,V] logits at 32k.
    dict(name="fence4_seq16k_adafactor_b2", model="llama-650m", batch=2,
         seq=16384, max_position=16384, remat=True, remat_policy="attn",
         optimizer="adafactor", fence_every=4),
    dict(name="fence4_seq32k_adafactor_b1_lc8", model="llama-650m", batch=1,
         seq=32768, max_position=32768, remat=True, remat_policy="attn",
         optimizer="adafactor", fence_every=4, loss_chunks=8),
    # --- sliding-window rungs (round 5: the banded flash kernel skips kv
    # tiles below the band, O(S*window) attention). A/B against the measured
    # full-causal rows at the same shape: fence4_seq8k_adafactor_b4 (55.9%)
    # and fence4_seq16k_adafactor_b2 (queued above). MFU here still counts
    # full dense-causal attention FLOPs (the conventional accounting), so
    # compare step_ms, not the MFU column, for the kernel-speedup claim.
    dict(name="fence4_seq8k_swa2k_adafactor_b4", model="llama-650m", batch=4,
         seq=8192, max_position=8192, sliding_window=2048, remat=True,
         remat_policy="attn", optimizer="adafactor", fence_every=4),
    dict(name="fence4_seq16k_swa2k_adafactor_b2", model="llama-650m",
         batch=2, seq=16384, max_position=16384, sliding_window=2048,
         remat=True, remat_policy="attn", optimizer="adafactor",
         fence_every=4),
    # --- Gemma-2 flash-vs-xla A/B (round 6: softcap, query_pre_attn_scalar
    # and the alternating per-layer windows now run IN the Pallas kernel —
    # the force-xla guard is gone). Same shape both rungs, attn_impl the
    # ONLY variable (one-new-variable stall policy); the xla twin is the
    # O(S^2)-memory program every Gemma-2 run compiled before this round.
    # seq 8192 > the 4096 window so the even layers genuinely band (the
    # banded O(S*window) pricing rides the result detail as attn_kv_len /
    # banded_flops_per_token, matching preflight's roofline); bf16 state +
    # adafactor + attn remat to fit the 2.6B model on one chip.
    dict(name="gemma2_2b_flash_fence4_b1", model="gemma2-2b", batch=1,
         seq=8192, attn_impl="flash", remat=True, remat_policy="attn",
         optimizer="adafactor", param_dtype="bfloat16", fence_every=4,
         loss_chunks=8),
    dict(name="gemma2_2b_xla_fence4_b1", model="gemma2-2b", batch=1,
         seq=8192, attn_impl="xla", remat=True, remat_policy="attn",
         optimizer="adafactor", param_dtype="bfloat16", fence_every=4,
         loss_chunks=8),
]


def _append_sweep_log(rec: dict) -> None:
    """Durably record + emit one sweep-log line (best-effort on disk)."""
    try:
        with open(SWEEP_LOG_PATH, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError:
        pass
    _emit(rec)


def _exp_hash(exp: dict) -> str:
    """Stable fingerprint of a sweep experiment's config (name excluded):
    sweep-log records bind to it so results/OOMs from an older config under
    a reused name never satisfy or retire the current experiment."""
    spec = {k: v for k, v in exp.items() if k != "name"}
    blob = json.dumps(spec, sort_keys=True, default=str)
    return hashlib.sha1(blob.encode()).hexdigest()[:12]


def run_sweep(watchdog: int) -> None:
    """Probe-gated experiment queue. Resumable: an experiment is skipped when
    SWEEP_LOG_PATH holds a complete result for its (name, config hash), or is
    retired (`retired_oom`) after two recorded device-OOMs at that exact
    hash; a rung that stalls mid-run is retried once after the pool answers
    a probe again, and bare pool-capacity rejections back off on their own
    budget without consuming either attempt."""
    deadline = time.time() + (watchdog if watchdog else 7 * 86400)
    done = set()
    oom_counts = {}
    try:
        with open(SWEEP_LOG_PATH) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                res = rec.get("result") or {}
                # all skip decisions key by (name, config hash): a record
                # from an older config under a reused name must not satisfy
                # or retire the new experiment. (Every record in the log
                # carries a hash — pre-hash-era records were backfilled from
                # their then-current configs, 2026-07-31.)
                key = (rec.get("name"), rec.get("config_hash"))
                if res.get("value", 0) > 0 and not res.get("partial"):
                    done.add(key)
                elif rec.get("kind") == "oom":
                    oom_counts[key] = oom_counts.get(key, 0) + 1
    except OSError:
        pass

    def pool_up() -> bool:
        budget = min(75, max(5, deadline - time.time()))
        lines, kind = _run_child(["--probe"], budget=budget)
        return kind == "ok" and bool(lines)

    for exp in SWEEP_QUEUE:
        h = _exp_hash(exp)
        if (exp["name"], h) in done:
            continue
        # an OOM at fixed config is deterministic (compile-time HBM
        # exhaustion): two recorded OOM attempts at THIS exact config settle
        # the experiment — don't re-burn healthy window re-proving it on
        # every worker relaunch. Emit the decision so the log distinguishes
        # "retired by policy" from "never reached".
        if oom_counts.get((exp["name"], h), 0) >= 2:
            _emit({"sweep": exp["name"], "status": "retired_oom",
                   "config_hash": h})
            continue
        attempt, backoffs = 0, 0
        while attempt < 2:
            while time.time() < deadline and not pool_up():
                _emit({"sweep": exp["name"], "status": "pool_down",
                       "utc": time.strftime("%H:%M:%SZ", time.gmtime())})
                time.sleep(min(300, max(1, deadline - time.time())))
            if time.time() >= deadline:
                return
            # serving/elastic rungs dispatch their check children instead
            # of a training rung; their result metrics differ
            metric = ("decode_tput" if exp.get("decode_rungs")
                      else "elastic" if exp.get("elastic_rungs")
                      else "post_loop" if exp.get("post_rungs")
                      else "load" if exp.get("load_rungs")
                      else "mfu")
            if exp.get("decode_rungs"):
                child_args = ["--check-decode",
                              "--decode-rungs", exp["decode_rungs"]]
            elif exp.get("elastic_rungs"):
                child_args = ["--check-elastic",
                              "--elastic-rungs", exp["elastic_rungs"]]
            elif exp.get("post_rungs"):
                child_args = ["--check-post",
                              "--post-rungs", exp["post_rungs"]]
            elif exp.get("load_rungs"):
                child_args = ["--check-load",
                              "--load-rungs", exp["load_rungs"]]
            else:
                spec = {k: v for k, v in exp.items() if k != "name"}
                spec.setdefault("steps", 10)
                spec.setdefault("warmup", 2)
                child_args = ["--rung", json.dumps(spec)]
            # clamp to the remaining watchdog window (the ladder path does
            # the same): a child launched near the deadline must not overrun
            # it by its full 700s — an external kill at the deadline would
            # lose the in-flight result entirely
            budget = min(700, deadline - time.time())
            if budget < 90:
                return
            lines, kind = _run_child(child_args, budget=budget)
            if kind == "pool_exhausted" and not any(
                    r.get("metric") == metric and r["value"] > 0
                    for r in lines):
                # transient pool-capacity rejection (NOT device OOM, NOT a
                # crash): the tiny --probe child can pass while a full rung's
                # allocation is refused, so the pool_up() gate never engages.
                # Back off on a budget of its own — a backoff must neither
                # consume one of the two real attempts nor starve them.
                backoffs += 1
                if backoffs > 4:
                    _append_sweep_log(
                        {"name": exp["name"], "kind": "gave_up_pool_exhausted",
                         "config_hash": h, "attempts_used": attempt,
                         "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                              time.gmtime()),
                         "result": None})
                    break
                _emit({"sweep": exp["name"], "status": "pool_exhausted_backoff",
                       "utc": time.strftime("%H:%M:%SZ", time.gmtime())})
                time.sleep(min(180, max(1, deadline - time.time())))
                continue
            attempt += 1
            results = [r for r in lines
                       if r.get("metric") == metric and r["value"] > 0]
            best = results[-1] if results else None
            _append_sweep_log(
                {"name": exp["name"], "attempt": attempt, "kind": kind,
                 "config_hash": h,
                 "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                 "result": best})
            if best is not None and not best.get("partial"):
                if metric == "mfu":   # last-good cache is the MFU headline
                    _save_last_good(best)
                break   # complete result: next experiment
            if kind == "ok":
                break   # clean exit without a number: don't burn a retry
        # two stalled/crashed attempts, or gave up on capacity — move on

def _run_child(mode_args: list, budget: float) -> tuple:
    """Run this script in child mode; return (parsed JSON lines from stdout,
    failure kind). Lines may be empty if the child stalled (killed at budget),
    crashed (OOM etc.), or the pool ate it."""
    # the environment goes through unchanged: where JAX_COMPILATION_CACHE_DIR
    # is set the children honour it, otherwise they all use the checkout's
    # .jax_cache (utils/compile_cache.py)
    proc = subprocess.Popen([sys.executable, os.path.abspath(__file__)] + mode_args,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, cwd=REPO)
    try:
        out, err = proc.communicate(timeout=budget)
        if proc.returncode == 0:
            kind = "ok"
        elif ("Out of memory" in err or "Largest program allocations" in err
                or "Error allocating device buffer" in err):
            # device HBM exhaustion only, by XLA's canonical markers:
            # compile-time OOM carries an allocation dump, runtime buffer
            # OOM says "Error allocating device buffer". Deliberately
            # strict — an oom record can permanently retire a sweep config
            # (>=2 rule in run_sweep), so a transient pool-capacity
            # RESOURCE_EXHAUSTED must never land here; the reverse
            # misclassification only costs a retry.
            kind = "oom"
        elif "RESOURCE_EXHAUSTED" in err:
            kind = "pool_exhausted"
        else:
            kind = f"crashed_rc_{proc.returncode}"
    except subprocess.TimeoutExpired:
        proc.kill()
        out, err = proc.communicate()
        kind = "stalled"
    if err:
        sys.stderr.write(err[-2000:])
    parsed = []
    for line in out.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return parsed, kind


class _Best:
    """Best-so-far result + ladder/probe logs, shared with the watchdog."""
    result: dict | None = None
    ladder: list = []
    probes: list = []
    emitted: bool = False


def _install_parent_watchdog(seconds: float) -> None:
    import threading

    def on_timeout():
        if _Best.emitted:
            os._exit(0)  # main thread already printed the final line
        if _Best.result is not None:
            final = dict(_Best.result)
            _save_last_good(final)  # no-op when the best-so-far is partial
            final.pop("partial", None)
            final["detail"] = {**final.get("detail", {}),
                               "ladder": _Best.ladder,
                               "watchdog_fired": True}
            _emit(_attach_last_good(final))
            os._exit(0)
        _emit(_attach_last_good(
            {"metric": "mfu", "value": 0.0, "unit": "fraction_of_peak_bf16",
             "vs_baseline": 0.0,
             "detail": {"error": f"watchdog: no result within {seconds:.0f}s "
                                 f"(TPU pool unresponsive)",
                        "ladder": _Best.ladder,
                        "probes": _Best.probes}}))
        os._exit(2)

    timer = threading.Timer(seconds, on_timeout)
    timer.daemon = True
    timer.start()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default=None)
    parser.add_argument("--batch", type=int, default=None)
    parser.add_argument("--seq", type=int, default=None)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--warmup", type=int, default=2)
    parser.add_argument("--remat", action="store_true", default=None)
    parser.add_argument("--no-remat", dest="remat", action="store_false")
    parser.add_argument("--attn-impl", default="auto")
    parser.add_argument("--remat-policy", default=None,
                        choices=["all", "dots", "attn", "attn_mlp"])
    parser.add_argument("--optimizer", default=None,
                        choices=["adamw", "adafactor", "lion"])
    parser.add_argument("--loss-chunks", type=int, default=None)
    parser.add_argument("--fence-every", type=int, default=None,
                        help="time steps in groups of N with one host-read "
                             "fence per group (default 1: per-step fence)")
    parser.add_argument("--watchdog", type=int, default=_default_watchdog())
    parser.add_argument("--skip-flash-check", action="store_true")
    parser.add_argument("--sweep", action="store_true",
                        help="run the queued tuning experiments (probe-gated, "
                             "resumable) instead of the ladder")
    # child modes
    parser.add_argument("--rung", default=None, help=argparse.SUPPRESS)
    parser.add_argument("--probe", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--check-flash", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--check-decode", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--decode-rungs", default=None, help=argparse.SUPPRESS)
    parser.add_argument("--check-elastic", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--elastic-rungs", default=None, help=argparse.SUPPRESS)
    parser.add_argument("--check-post", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--post-rungs", default=None, help=argparse.SUPPRESS)
    parser.add_argument("--check-load", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--load-rungs", default=None, help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.remat is False and args.remat_policy:
        parser.error("--no-remat contradicts --remat-policy "
                     "(the policy only applies under remat)")

    if args.rung:
        return run_rung(json.loads(args.rung))
    if args.probe:
        return run_probe()
    if args.check_flash:
        return run_flash_check()
    if args.check_decode:
        return run_decode_check(args.decode_rungs)
    if args.check_elastic:
        return run_elastic_check(args.elastic_rungs)
    if args.check_post:
        return run_post_check(args.post_rungs)
    if args.check_load:
        return run_load_check(args.load_rungs)
    if args.sweep:
        return run_sweep(args.watchdog)

    if args.watchdog:
        deadline = time.time() + args.watchdog - 40
        _install_parent_watchdog(args.watchdog - 15)
    else:  # --watchdog 0: no time limit
        deadline = time.time() + 86400

    # Pool-health gate: a rung burns minutes of budget compiling before its
    # first step can stall, so NEVER launch one into a dead pool. The probe
    # (device enumeration in a kill-able child) is the cheap health signal;
    # while it fails, sleep-poll — the budget is spent waiting, not stalling.
    probe_log = _Best.probes = []
    t_start = time.time()

    def _probe_pool() -> tuple:
        budget = min(75, max(5, deadline - time.time()))
        lines, kind = _run_child(["--probe"], budget=budget)
        info = lines[-1] if lines else None
        ok = kind == "ok" and info is not None
        probe_log.append({"t": int(time.time() - t_start), "ok": ok})
        return info, ok

    def ensure_pool() -> tuple:
        """Probe; while dead, sleep-poll until healthy or near the deadline.
        Returns (probe_info, healthy)."""
        info, ok = _probe_pool()
        while not ok and deadline - time.time() > 180:
            time.sleep(min(45, max(1, deadline - time.time() - 170)))
            info, ok = _probe_pool()
        return info, ok

    probe_info, pool_ok = ensure_pool()
    platform = probe_info.get("platform", "tpu") if probe_info else "tpu"

    if (args.model is not None or args.batch is not None
            or args.seq is not None or args.remat_policy is not None
            or args.optimizer is not None or args.loss_chunks is not None
            or args.fence_every is not None):
        on_tpu = platform == "tpu"
        ladder = [dict(model=args.model or ("llama-650m" if on_tpu else "llama-debug"),
                       batch=args.batch or (8 if on_tpu else 2),
                       seq=args.seq or (2048 if on_tpu else 128),
                       steps=args.steps, warmup=args.warmup,
                       # an explicit policy implies remat (a policy without
                       # remat would silently measure the no-remat program)
                       remat=(args.remat if args.remat is not None
                              else on_tpu or args.remat_policy is not None),
                       attn_impl=args.attn_impl, budget=deadline - time.time(),
                       **({"remat_policy": args.remat_policy}
                          if args.remat_policy else {}),
                       **({"optimizer": args.optimizer}
                          if args.optimizer else {}),
                       **({"loss_chunks": args.loss_chunks}
                          if args.loss_chunks else {}),
                       **({"fence_every": args.fence_every}
                          if args.fence_every else {}))]
    elif platform == "tpu":
        # headline: `--fence-every 4` + adafactor + remat_policy=attn_mlp at
        # b8 — 56.8% MFU on v5e, 2026-07-31 06:47 (sweep
        # `fence4_adafactor_attnmlp_b8`, 618 ms/step vs the per-step-fenced
        # adamw/attn 695 ms). The group fence is still hard (each step
        # consumes the previous state, so 4-step groups measure real
        # throughput); this is how a production loop runs — dispatch ahead,
        # fence at the log interval. fp32 params + fp32 factored adafactor
        # state, i.e. reference-comparable numerics (the bf16-state crosses
        # stay documented levers, BENCH.md). Degradation rungs keep the
        # per-step fence: on a sick pool dispatch-ahead is the documented
        # stall pattern, so the fallbacks are the stall-proof recipes —
        # 52.8% adafactor_b16, 50.5% adamw/b8, 48.5% policy "all".
        ladder = [
            dict(model="llama-650m", batch=8, seq=2048, steps=args.steps,
                 warmup=args.warmup, remat=True, remat_policy="attn_mlp",
                 optimizer="adafactor", fence_every=4,
                 attn_impl=args.attn_impl, budget=600),
            dict(model="llama-650m", batch=16, seq=2048, steps=args.steps,
                 warmup=args.warmup, remat=True, remat_policy="attn",
                 optimizer="adafactor", attn_impl=args.attn_impl, budget=540),
            dict(model="llama-650m", batch=8, seq=2048, steps=args.steps,
                 warmup=args.warmup, remat=True, remat_policy="attn",
                 attn_impl=args.attn_impl, budget=480),
            dict(model="llama-650m", batch=8, seq=2048, steps=args.steps,
                 warmup=args.warmup, remat=True, attn_impl=args.attn_impl,
                 budget=420),
            dict(model="llama-650m", batch=4, seq=1024, steps=6, warmup=2,
                 remat=True, attn_impl=args.attn_impl, budget=360),
            dict(model="llama-debug", batch=8, seq=512, steps=6, warmup=2,
                 remat=False, attn_impl=args.attn_impl, budget=180),
        ]
    else:
        ladder = [dict(model="llama-debug", batch=2, seq=128, steps=args.steps,
                       warmup=args.warmup, remat=False, attn_impl=args.attn_impl,
                       budget=deadline - time.time())]

    ladder_log = _Best.ladder = []
    _Best.result, _Best.emitted = None, False  # fresh per main() call (tests)
    final = None

    # gate rung launches on pool health: set initially when the startup
    # probe loop gave up with the pool still down (launching into a
    # known-dead pool would burn the remaining window stalling in compile),
    # and again whenever a rung stalls
    need_gate = not pool_ok

    def try_rung(rung, attempt):
        """Run one rung; returns its (possibly partial) result dict or None."""
        nonlocal final, need_gate
        if need_gate:
            _, ok = ensure_pool()   # sleep-polls while the pool is dead
            need_gate = not ok
            if not ok:
                ladder_log.append({"model": rung["model"], "seq": rung["seq"],
                                   "status": "skipped_pool_down"})
                return None
        budget = min(rung["budget"], deadline - time.time())
        if budget < 90:
            ladder_log.append({"model": rung["model"], "seq": rung["seq"],
                               "status": "skipped_no_time"})
            return None
        spec = {k: v for k, v in rung.items() if k != "budget"}
        lines, kind = _run_child(["--rung", json.dumps(spec)], budget)
        if kind == "stalled":
            need_gate = True
        results = [r for r in lines if r.get("metric") == "mfu" and r["value"] > 0]
        entry = {"model": rung["model"], "seq": rung["seq"],
                 **({"remat_policy": rung["remat_policy"]}
                    if "remat_policy" in rung else {})}
        if not results:
            if kind == "ok":  # exited clean but produced no usable number
                kind = "no_result"
            ladder_log.append({**entry, "status": f"{kind}_attempt_{attempt}"})
            return None
        best = results[-1]
        status = "ok" if not best.get("partial") else "partial"
        if kind != "ok":  # produced numbers, then crashed/stalled mid-rung
            status = f"{status}_then_{kind}"
        ladder_log.append({**entry, "status": status,
                           "steps_timed": best["detail"]["steps_timed"]})
        if _Best.result is None or best["value"] > _Best.result["value"]:
            _Best.result = dict(best)
        if final is None:
            final = dict(best)
        return best

    # pass 1: one attempt per rung, stopping at the first full success —
    # on a sick pool a smaller config may finish where the big one stalls
    top_rung_ok = False
    for n, rung in enumerate(ladder):
        res = try_rung(rung, attempt=1)
        if res is not None and not res.get("partial"):
            top_rung_ok = n == 0
            break
    # pass 2: nothing landed at all — spend what remains retrying (compile
    # cache makes retries cheap if the pool has recovered)
    if final is None:
        for rung in ladder:
            if try_rung(rung, attempt=2) is not None:
                break

    # bonus pass: the HEADLINE rung fully succeeded (pool is demonstrably
    # healthy) — measure the min-memory "all" policy rung so every healthy
    # run records the remat-policy delta. Selected by predicate, NOT by
    # ladder index: rung order changes with each retuned headline. ("dots"
    # is NOT retried: BENCH.md records it OOMing at this shape on the 16 GB
    # chip.) Only the A/B run's own COMPLETE result may displace the
    # verified one.
    ab_rung = next((r for r in ladder[1:]
                    if "remat_policy" not in r and r["model"] == "llama-650m"),
                   None)
    if (top_rung_ok and platform == "tpu" and ab_rung is not None
            and deadline - time.time() > 420):
        tuned_res = try_rung(dict(ab_rung, budget=360), attempt=1)
        if (tuned_res is not None and not tuned_res.get("partial")
                and tuned_res["value"] > final["value"]):
            final = dict(tuned_res)

    if final is None:
        final = _Best.result  # a later partial is better than nothing
    if final is None:
        _emit(_attach_last_good(
            {"metric": "mfu", "value": 0.0, "unit": "fraction_of_peak_bf16",
             "vs_baseline": 0.0,
             "detail": {"error": ("pool unresponsive: no healthy probe"
                                  if not pool_ok else "all ladder rungs stalled"),
                        "ladder": ladder_log, "probes": probe_log,
                        "probe": probe_info}}))
        sys.exit(2)

    _save_last_good(final)  # before the pop: a partial fallback never persists
    final.pop("partial", None)
    final["detail"]["ladder"] = ladder_log
    if any(not p["ok"] for p in probe_log):   # record outage evidence
        final["detail"]["probes"] = probe_log
    if platform == "tpu" and not args.skip_flash_check:
        remaining = deadline - time.time()
        if remaining > 120:
            flash, kind = _run_child(["--check-flash"], budget=min(420, remaining))
            record = flash[-1] if flash else {}
            if kind != "ok":
                record = {**record, "error": kind}
                # the flash A/B runs LAST on whatever budget the ladder left,
                # so it is the likeliest child to stall on a slow pool (it
                # did in the 2026-07-31 dress rehearsal) — back a failed run
                # with the cached healthy record, same provenance gates as
                # the headline cache (commit-in-history + device match)
                cached = _load_flash_good()
                if cached and _cache_provenance_ok(
                        cached, final.get("detail", {}).get("device")):
                    record["last_good"] = cached
            else:
                _save_flash_good(record, final.get("detail", {}).get("device"))
            final["detail"]["flash_check"] = record
    # serving rung (any platform — llama-debug): decode tokens/sec at
    # n_slots 1 vs 8 through serve/'s paged engine, recorded beside the
    # training rungs so the BENCH_*.json history tracks inference too
    remaining = deadline - time.time()
    if remaining > 60:
        dec, kind = _run_child(["--check-decode"], budget=min(300, remaining))
        record = dec[-1] if dec else {}
        if kind != "ok":
            record = {**record, "error": kind}
        final["detail"]["decode_tput"] = record
    _Best.result = dict(final)
    _Best.emitted = True
    _emit(_attach_last_good(final))


if __name__ == "__main__":
    main()
