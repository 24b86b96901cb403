"""Shared chapter CLI + training loop.

The reference duplicates ~300 lines of loop/parser/data code into every
chapter's ``train_llm.py`` so each chapter's *diff* is the lesson
(``02-distributed-data-parallel/README.md:3``). The TPU build keeps the same
CLI surface (flags from ``01-single-gpu/train_llm.py:289-303``) and the same
host-state/logging/checkpoint contract, but factors the loop here; a chapter
script is then just "build a mesh + plan, call ``run_training``" — the diff
between chapters is the *sharding plan*, which is the lesson on TPU.

Phase timing note: the reference times data/forward/backward/update separately
(``01:113``, eager phases). Under XLA forward+backward+update is one fused
program by design, so the honest split is data / step; per-op attribution
lives in the profiler (``jax.profiler.trace``, chapter "diagnosing-errors").
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import time
from pathlib import Path
from typing import Callable, Optional

import jax
import numpy as np

LOGGER = logging.getLogger(__name__)


def _positive_int(s: str) -> int:
    v = int(s)
    if v < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {v}")
    return v


def get_parser() -> argparse.ArgumentParser:
    """Flag surface of the reference parser (``01-single-gpu/train_llm.py:289-303``)."""
    parser = argparse.ArgumentParser()
    parser.add_argument("-e", "--experiment-name", default=None)
    parser.add_argument("-d", "--dataset-name", default="synthetic", required=False)
    parser.add_argument("--dataset-subset", default=None)
    parser.add_argument("-m", "--model-name", default=None, required=True)
    parser.add_argument("--save-dir", default="../outputs")
    parser.add_argument("--seed", default=0, type=int)
    parser.add_argument("--num-epochs", default=100, type=int)
    parser.add_argument("--lr", default=3e-5, type=float)
    parser.add_argument("--optimizer", default="adamw",
                        choices=["adamw", "adafactor", "lion"],
                        help="adamw = reference parity (fused AdamW, 2x-fp32 "
                             "moments); adafactor = factored second moment, "
                             "~0 optimizer memory (the TPU-native lever for "
                             "fitting big models without CPU offload); lion = "
                             "one momentum slot, sign updates (use ~3-10x "
                             "lower lr / higher weight decay than adamw)")
    parser.add_argument("-b", "--batch-size", default=1, type=int,
                        help="per-data-parallel-replica batch size (reference semantics)")
    parser.add_argument("--log-freq", default=10, type=int)
    parser.add_argument("--ckpt-freq", default=500, type=int)
    parser.add_argument("-s", "--seq-length", default=1024, type=int)
    parser.add_argument("--steps-per-epoch", default=None, type=int,
                        help="cap steps per epoch (smoke runs)")
    parser.add_argument("--grad-accum", default=1, type=int)
    parser.add_argument("--checkpoint-activations", action="store_true",
                        help="remat decoder layers (reference 05:163-178)")
    parser.add_argument("--remat-policy", default="all", choices=["all", "dots", "attn", "attn_mlp"],
                        help="what survives forward under remat: all=recompute "
                             "everything (min memory); dots=keep matmul outputs "
                             "(most memory); attn=keep attention outputs + flash "
                             "lse so backward never re-runs the attention kernel "
                             "(a small memory cost); attn_mlp="
                             "attn plus the [B,S,I] MLP inner activations "
                             "(also skips the gate/up matmul recompute)")
    parser.add_argument("--attn-impl", default="auto", choices=["auto", "xla", "flash"])
    parser.add_argument("--context-impl", default="ring",
                        choices=["ring", "ulysses"],
                        help="cp>1 attention scheme: ring = zigzag ppermute "
                             "ring (any head count, any length); ulysses = "
                             "all-to-all head sharding during attention "
                             "(cheaper comms, needs kv_heads %% (cp*tp) == 0)")
    parser.add_argument("--cp-hop-loop", default="auto",
                        choices=["auto", "scan", "unrolled"],
                        help="ring hop-loop form: scan = O(1) program size "
                             "(auto at cp >= 8), unrolled = O(cp); per hop "
                             "the two are op-for-op identical")
    parser.add_argument("--max-steps", default=None, type=int)
    parser.add_argument("--guard-policy", default="off",
                        choices=["off", "skip", "abort"],
                        help="non-finite loss/grad-norm policy (train/"
                             "guards.py): skip = drop the poisoned update "
                             "(params/opt state revert in-step), abort past "
                             "--guard-max-skips consecutive; abort = fail "
                             "fast, writing the step + metrics to the "
                             "torchelastic-style error file. off (default) "
                             "= reference behavior (NaNs propagate)")
    parser.add_argument("--guard-max-skips", default=5, type=_positive_int,
                        metavar="N",
                        help="with --guard-policy skip: abort after N "
                             "consecutive non-finite steps (a divergent run "
                             "must not spin forever)")
    parser.add_argument("--pretrained", default=None, metavar="DIR",
                        help="directory produced by convert_llama.py / "
                             "convert_hf_checkpoint: start from these weights "
                             "instead of random init (the reference's "
                             "from_pretrained default, 01:57); pairs with "
                             "-m hf:<hf-dir> for checkpoints without a preset")
    parser.add_argument("--native-loader", action="store_true",
                        help="assemble batches with the C++ mmap/prefetch loader (csrc/)")
    parser.add_argument("--mmap-data", default=None, metavar="DIR",
                        help="spill the token array to a raw token file under "
                             "DIR (built once, reused across runs) and train "
                             "from a read-only memmap: host RAM holds only "
                             "each batch's local shard rows, not the corpus; "
                             "--native-loader then mmaps the same file "
                             "zero-copy")
    parser.add_argument("--async-checkpoint", action="store_true",
                        help="overlap checkpoint writes with training (Orbax "
                             "async; state.json publishes when the write commits)")
    parser.add_argument("--keep-checkpoints", default=2, type=_positive_int,
                        metavar="N",
                        help="retain the N newest checkpoints (manifest-"
                             "verified on restore; a corrupt latest falls "
                             "back to the next-oldest). 1 = the old "
                             "delete-all-but-latest behavior")
    parser.add_argument("--loss-chunks", type=int, default=0,
                        help=">0: compute the loss in sequence chunks, never "
                             "materializing full [B,S,V] logits (big-vocab "
                             "memory saver)")
    parser.add_argument("--wandb", action="store_true",
                        help="log the info dict to wandb (reference C27; "
                             "process-0 single run by default, resumable via "
                             "a run id stored beside state.json)")
    parser.add_argument("--wandb-project", default=None)
    parser.add_argument("--wandb-per-host", action="store_true",
                        help="grouped per-host runs instead of one process-0 "
                             "run (wandb-configurations pattern 2)")
    parser.add_argument("--lora-rank", default=0, type=int, metavar="R",
                        help="train LoRA adapters of rank R on a FROZEN "
                             "base model instead of full parameters "
                             "(llama family; composes with --pretrained "
                             "and every sharding plan). 0 = off")
    parser.add_argument("--lora-alpha", default=16.0, type=float,
                        help="LoRA scale numerator (delta = alpha/R * A@B)")
    parser.add_argument("--lora-targets", default="wq,wv",
                        help="comma list of adapted projections "
                             "(wq,wk,wv,wo,gate,up,down)")
    parser.add_argument("--moe-dispatch", default=None,
                        choices=["dense", "ragged"],
                        help="MoE expert-dispatch backend (MoE models only): "
                             "dense = static [E, C, D] capacity buffers "
                             "(Switch/GShard; overflow tokens drop to the "
                             "residual), ragged = dropless sort-based "
                             "dispatch + grouped GEMMs over the [kT, D] "
                             "sorted buffer (MegaBlocks) — no padding "
                             "compute, no capacity knob, moe_dropped_frac "
                             "identically 0. Default: the model config's "
                             "moe_dispatch (dense)")
    parser.add_argument("--checkpoint-full-crc", action="store_true",
                        help="CRC32 every checkpoint file in full when "
                             "writing integrity manifests. Default: files "
                             "beyond a size threshold get a deterministic "
                             "sampled CRC (head + tail + strided interior "
                             "windows), keeping the per-save manifest cost "
                             "bounded instead of O(checkpoint bytes) over "
                             "the shared FS at pod scale")
    parser.add_argument("--sliding-window", default=None, type=int,
                        metavar="W",
                        help="sliding-window attention: each token attends "
                             "the previous W tokens only (banded flash "
                             "kernel, O(S*W) attention). Overrides the "
                             "model config; hf: checkpoints with "
                             "sliding_window set enable this automatically")
    parser.add_argument("--precision-policy", default="fp32",
                        metavar="POLICY",
                        help="storage-precision policy (train/precision.py): "
                             "fp32 (default, the reference's mixed-precision "
                             "layout, bit-identical to before the flag "
                             "existed); bf16-master = bf16 param/moment/"
                             "accum storage with the optimizer update "
                             "computed in fp32 (8 B/param instead of 16); "
                             "adam8bit = int8 block-quantized Adam moments "
                             "with per-block fp32 scales (Dettmers et al.; "
                             "opt state ~3.9x smaller); policies compose "
                             "with '+', e.g. bf16-master+adam8bit. "
                             "--preflight prices the chosen policy")
    parser.add_argument("--param-dtype", default="float32",
                        choices=["float32", "bfloat16"],
                        help="parameter STORAGE dtype (compute is bf16 "
                             "either way). bfloat16 halves resident param "
                             "memory and also stores the optimizer moments "
                             "in bf16 (the numerics trade: "
                             "train/precision.py); fp32 (default) is the "
                             "reference's mixed-precision policy")
    parser.add_argument("--fence-every", type=_positive_int, default=1,
                        metavar="N",
                        help="host-read the loss every N steps instead of "
                             "every step. 1 (default) is the reference's "
                             "per-step `.item()` sync (01:163); N>1 lets the "
                             "host dispatch N steps ahead so the chip never "
                             "idles on dispatch latency (not measured on "
                             "this tree). The group fence is still hard: "
                             "each step consumes the previous state on "
                             "device")
    parser.add_argument("--profile-dir", default=None,
                        help="capture a jax.profiler trace of steps 10-15 into this dir "
                             "(view with xprof/tensorboard; see diagnosing-errors/)")
    parser.add_argument("--preflight", action="store_true",
                        help="don't train: abstractly trace + SPMD-lower the "
                             "full step for this (model, mesh, flags) and "
                             "print the per-device HBM budget + ICI comm "
                             "roofline + the serving-side KV-page pricing "
                             "(bytes per decode slot at this context, "
                             "related-topics/serving/), then exit — catches "
                             "sharding/divisibility/fit problems without "
                             "touching an accelerator")
    parser.add_argument("--preflight-target", default=None, metavar="KIND",
                        help="chip kind the comm roofline prices (e.g. v5p, "
                             "v5e) when preflighting a pod plan from a "
                             "non-TPU host; default: local device on TPU, "
                             "v5p otherwise")
    return parser


def _compile_step(lowered, n_chips: int, trainer):
    """Compile the lowered step ONCE; the executable that comes back takes
    every step of the run, and the JSON line printed here describes it: the
    compile seconds and, on a multi-device mesh, the collectives it holds
    (``utils/hlo.collective_summary``) and, under a chunked loss, whether
    the output matrix is gathered once a step or once a chunk
    (``Trainer.head_gather``)."""
    t0 = time.perf_counter()
    compiled = lowered.compile()
    program = {"compile_s": round(time.perf_counter() - t0, 3)}
    if n_chips > 1:
        from ..utils.hlo import collective_summary

        program["collectives"] = collective_summary(compiled.as_text())
        if trainer.loss_chunks and trainer.plan.mesh.shape["pp"] == 1:
            program["loss_head"] = trainer.head_gather["why"]
    if jax.process_index() == 0:
        print(json.dumps({"step_program": program}), flush=True)
    return compiled


def _print_device_memory() -> None:
    """Every local device's ``bytes_in_use``, as one JSON line: after the
    first step of a sharded run it shows the state really is spread."""
    print(json.dumps({"device_memory": [
        {"id": d.id, "bytes_in_use": (d.memory_stats() or {}).get(
            "bytes_in_use")} for d in jax.local_devices()]}), flush=True)


def run_training(args, plan_factory: Callable, *, extra_log: Optional[dict] = None,
                 pretrained_dir: Optional[str] = None,
                 offload_opt_state: bool = False,
                 offload_params: bool = False,
                 pp_microbatches: Optional[int] = None) -> dict:
    """The chapter-invariant training loop. Returns final metrics (for tests).

    ``plan_factory() -> ShardingPlan`` is the one thing chapters customize.
    """
    # reject bad knobs before any resource (loader/tracker/progress) exists:
    # failing later would strand an unfinished wandb run and leak the loader
    if getattr(args, "fence_every", 1) < 1:
        raise SystemExit(f"--fence-every must be >= 1, got {args.fence_every}")
    from ..utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    from ..checkpoint import CheckpointIO, restore_train_state
    from ..data import ShardedBatchLoader, get_tokenizer, load_and_preprocess_data
    from ..models import get_model
    from ..train import Trainer
    from ..train.optimizer import OPTIMIZERS, lr_at_step
    from ..train.state import host_state_dict
    from ..utils.trace import install_gc_span, span
    from ..utils import (LocalTimer, compute_mfu, get_mem_stats, init_logging,
                         is_process0, transformer_flops_per_token)
    from ..utils.logging import print_device_line
    from ..utils.mfu import device_peak_flops

    init_logging(jax.process_index(), jax.process_count())
    LOGGER.info({k: v for k, v in os.environ.items() if k.startswith(("JAX", "XLA", "TPU"))})
    LOGGER.info(vars(args))
    pretrained_dir = pretrained_dir or getattr(args, "pretrained", None)

    plan = plan_factory()
    overrides = {}
    if getattr(args, "param_dtype", None) and args.param_dtype != "float32":
        import jax.numpy as jnp
        overrides["param_dtype"] = {"bfloat16": jnp.bfloat16,
                                    "float32": jnp.float32}[args.param_dtype]
    if getattr(args, "sliding_window", None):
        overrides["sliding_window"] = args.sliding_window
    if getattr(args, "moe_dispatch", None):
        overrides["moe_dispatch"] = args.moe_dispatch
    try:
        bundle = get_model(args.model_name, **overrides)
    except TypeError as exc:
        if "moe_dispatch" in overrides:
            raise SystemExit(
                f"--moe-dispatch is only valid for MoE models; "
                f"{args.model_name!r} rejected it ({exc})")
        raise
    cfg = bundle.config
    optimizer = OPTIMIZERS[args.optimizer](args.lr)
    lora_rank = getattr(args, "lora_rank", 0)
    if lora_rank:
        from ..models.lora import lora_bundle, mask_optimizer, num_trainable_params

        bundle = lora_bundle(bundle, rank=lora_rank,
                             alpha=getattr(args, "lora_alpha", 16.0),
                             targets=tuple(
                                 getattr(args, "lora_targets",
                                         "wq,wv").split(",")))
        optimizer = mask_optimizer(optimizer)
        LOGGER.info(f"LoRA: rank {lora_rank}, "
                    f"{num_trainable_params(bundle):,} trainable adapter "
                    f"params over a frozen {bundle.num_params():,}-param base")
    LOGGER.info(f"Training {bundle.num_params():,} model parameters "
                f"on mesh {dict(plan.mesh.shape)} strategy={plan.strategy}")

    seq_length = min(args.seq_length, cfg.max_position_embeddings)
    trainer = Trainer(
        bundle=bundle,
        optimizer=optimizer,
        plan=plan,
        grad_accum=args.grad_accum,
        remat=args.checkpoint_activations,
        remat_policy=args.remat_policy,
        loss_chunks=args.loss_chunks,
        attn_impl=args.attn_impl,
        context_impl=getattr(args, "context_impl", "ring"),
        cp_hop_loop=getattr(args, "cp_hop_loop", "auto"),
        guard_policy=getattr(args, "guard_policy", "off"),
        offload_opt_state=offload_opt_state,
        offload_params=offload_params,
        pp_microbatches=pp_microbatches,
        precision=getattr(args, "precision_policy", "fp32"),
    )
    from .guards import GuardMonitor

    guard = GuardMonitor(getattr(args, "guard_policy", "off"),
                         getattr(args, "guard_max_skips", 5))

    global_batch = args.batch_size * plan.data_parallel_size * args.grad_accum

    if getattr(args, "preflight", False):
        from .preflight import run_preflight

        return run_preflight(trainer, global_batch=global_batch,
                             seq_length=seq_length,
                             target_device=getattr(args, "preflight_target",
                                                   None))

    # Trace and lower the step now, before anything is loaded or placed: the
    # start-up line then names the attention implementation(s) the step
    # TRACED (ops/dispatch.record_attention), not what the flags were expected
    # to resolve to, and a shape or sharding the step cannot take fails here
    from ..ops.dispatch import describe_attention, record_attention
    from .step import lower_step

    with record_attention() as traced:
        lowered, _ = lower_step(trainer, global_batch=global_batch,
                                seq_length=seq_length)
    if is_process0():
        print_device_line("attention", describe_attention(traced),
                          cache.directory)

    tokenizer = get_tokenizer(args.model_name)
    dataset = load_and_preprocess_data(
        args.dataset_name, tokenizer, seq_length,
        dataset_subset=args.dataset_subset,
        max_position_embeddings=cfg.max_position_embeddings, seed=args.seed,
        mmap_dir=getattr(args, "mmap_data", None))
    LOGGER.info(f"{len(dataset)} training sequences of length {seq_length}")
    loader = ShardedBatchLoader(
        dataset, global_batch,
        trainer.batch_shardings()["input_ids"],
        grad_accum=args.grad_accum, seed=args.seed,
        native=getattr(args, "native_loader", False))
    steps_per_epoch = len(loader)
    if args.steps_per_epoch:
        steps_per_epoch = min(steps_per_epoch, args.steps_per_epoch)
    LOGGER.info(f"{steps_per_epoch} batches per epoch (global batch {global_batch})")

    # ---- experiment dir + resume (reference 01:80-110) ----------------------
    exp_dir = Path(args.save_dir)
    is_experiment = args.experiment_name is not None
    if is_experiment:
        exp_dir = exp_dir / args.experiment_name
    io = (CheckpointIO(exp_dir, async_save=args.async_checkpoint,
                       keep_n=getattr(args, "keep_checkpoints", 2),
                       full_crc=getattr(args, "checkpoint_full_crc", False))
          if is_experiment else None)

    host_state = host_state_dict()
    if io is not None and io.can_resume():
        # policy-aware: an fp32 checkpoint restored into a precision-policy
        # run is re-encoded (re-quantized) with a logged warning
        state, host_state = restore_train_state(io, trainer)
        LOGGER.info(f"Resumed=True | {host_state}")
    elif pretrained_dir:
        LOGGER.info(f"Loading pretrained weights from {pretrained_dir}")
        if lora_rank:
            from ..models.lora import load_pretrained_lora

            params = load_pretrained_lora(bundle, trainer.param_shardings,
                                          pretrained_dir, seed=args.seed)
        else:
            from ..models.hf_convert import load_pretrained

            params = load_pretrained(bundle, trainer.param_shardings,
                                     pretrained_dir)
        state = trainer.init_state_from_params(params, args.seed)
        if is_experiment:
            LOGGER.info(f"Resumed=False | {host_state}")
    else:
        state = trainer.init_state(args.seed)
        if is_experiment:
            LOGGER.info(f"Resumed=False | {host_state}")
    if is_experiment:
        exp_dir.mkdir(parents=True, exist_ok=True)
    # stamped into every manifest's host_state: restore_train_state fails
    # loudly when a run drops/changes its --precision-policy, and checks the
    # mesh descriptor for reshard compatibility on elastic restarts
    from ..checkpoint import stamp_host_state

    stamp_host_state(host_state, trainer)

    from ..utils.tracking import make_tracker

    tracker = make_tracker(
        args, mode="per-host" if getattr(args, "wandb_per_host", False) else "process0",
        exp_dir=exp_dir if is_experiment else None, config=vars(args))

    install_gc_span()
    # each timer is also the host span dtg.train.<k> (utils/trace.py)
    timers = {k: LocalTimer(name=f"train.{k}") for k in ["data", "step"]}
    flops_per_token = transformer_flops_per_token(
        bundle.num_active_params(), cfg.num_layers, cfg.hidden_size, seq_length,
        vocab_size=cfg.vocab_size)
    n_chips = plan.mesh.size
    # MFU is reported only against a peak that is on record for this device
    # kind; elsewhere (the CPU included) the field is absent
    try:
        peak_flops = device_peak_flops()
    except ValueError:
        peak_flops = None
    tok_per_step = trainer.tokens_per_step(args.batch_size, seq_length)
    last_info: dict = {}

    progress = None
    if is_process0():
        try:
            import tqdm

            progress = tqdm.tqdm(total=steps_per_epoch * args.num_epochs, disable=None)
        except ImportError:
            pass

    from ..utils.faults import maybe_crash
    from ..utils.heartbeat import HeartbeatWriter

    heartbeat = HeartbeatWriter()  # no-op unless $HEARTBEAT_FILE is set

    profile_started = profile_done = False
    profile_start_step = 0
    # One executable for the whole run, compiled ahead of time from the
    # program lowered above. Under host offload step_fn is a Python wrapper
    # around its jit (transfers outside it) and compiles on its first call.
    first_step = host_state["global_step"]
    step_fn = trainer.step_fn
    if not hasattr(step_fn, "jitted"):
        step_fn = _compile_step(lowered, n_chips, trainer)
    del lowered
    done = False
    pending_losses = []  # (step, loss, notfinite) banked between fences

    def drain_losses():
        if not pending_losses:
            return
        with span("train.fence", step=pending_losses[-1][0]):
            for step_no, l, flag in pending_losses:
                # host read = hard fence. The guard monitor sees every
                # step's flag (abort may thus surface a fence group late —
                # the error file still names the offending step); skipped
                # steps stay out of running_loss so one NaN doesn't poison
                # every later window
                if flag is not None and guard.observe(
                        float(flag), step_no, {"loss": float(l)}):
                    continue
                host_state["running_loss"] += float(l)
            pending_losses.clear()
    try:
        for epoch in range(host_state["epoch"], args.num_epochs):
            host_state["epoch"] = epoch
            loader.set_epoch(epoch)
            LOGGER.info(f"Begin epoch {epoch} at step {host_state['epoch_step']}")
            batches = loader.epoch_batches(start_step=host_state["epoch_step"])

            for i_step in range(host_state["epoch_step"], steps_per_epoch):
                step_no = host_state["global_step"] + 1
                with timers["data"](step=step_no):
                    batch = next(batches)
                with timers["step"](step=step_no):
                    state, metrics = step_fn(state, batch)
                    # --fence-every 1 (default): force sync now, like the
                    # reference's per-step loss.item() (01:163). N>1: bank
                    # the device scalar and let the host dispatch ahead;
                    # drain_losses() materializes the bank at every point
                    # where running_loss is observed (fence, log boundary,
                    # checkpoint save, end of run). A log boundary drains
                    # HERE, inside the step timer, so the awaited device
                    # work of the whole group is charged to time/step —
                    # draining after the timer closed would let untimed
                    # compute inflate tokens_per_s/MFU.
                    pending_losses.append(
                        (host_state["global_step"] + 1, metrics["loss"],
                         metrics.get("notfinite") if guard.enabled else None))
                    if (len(pending_losses) >= args.fence_every
                            or (host_state["global_step"] + 1)
                            % args.log_freq == 0):
                        drain_losses()
                        # the step's integer counts (a sparse family's
                        # moe_pairs_held, models/laguna.py TRAIN_METRICS) as
                        # the span's statistics: the fence above has landed,
                        # so reading them waits for nothing
                        counts = {k: v for k, v in metrics.items()
                                  if np.issubdtype(v.dtype, np.integer)}
                        if counts:
                            timers["step"].set_metadata(**{
                                k: int(v) for k, v in
                                jax.device_get(counts).items()})

                host_state["global_step"] += 1
                host_state["epoch_step"] += 1
                heartbeat.beat(host_state["global_step"])
                if (n_chips > 1 and host_state["global_step"] == first_step + 1
                        and is_process0()):
                    _print_device_memory()
                if progress:
                    progress.update(1)

                if args.profile_dir:  # trace a ~5-step steady-state window (C22)
                    if not profile_started and host_state["global_step"] >= 10:
                        jax.profiler.start_trace(args.profile_dir)
                        profile_started = True
                        profile_start_step = host_state["global_step"]
                    elif profile_started and not profile_done and \
                            host_state["global_step"] >= profile_start_step + 5:
                        jax.profiler.stop_trace()
                        profile_done = True
                        LOGGER.info(f"profiler trace written to {args.profile_dir}")

                if host_state["global_step"] % args.log_freq == 0:
                    with span("train.log", step=host_state["global_step"]):
                        drain_losses()  # no-op: the in-timer drain above fired
                        ms_per_step = sum(t.avg_elapsed_ms() for t in timers.values())
                        tokens_per_s = 1000 * tok_per_step / max(ms_per_step, 1e-9)
                        info = {
                            "global_step": host_state["global_step"],
                            "lr": lr_at_step(host_state["global_step"], args.lr),
                            "running_loss": host_state["running_loss"] / args.log_freq,
                            "grad_norm": float(metrics["grad_norm"]),
                            **{k: float(v) for k, v in metrics.items()
                               if k not in ("loss", "grad_norm")},
                            "epoch": epoch,
                            "epoch_progress": host_state["epoch_step"] / steps_per_epoch,
                            "num_batches_remaining": steps_per_epoch - i_step,
                            **get_mem_stats(),
                            "tokens_per_s": tokens_per_s,
                            **({"mfu": compute_mfu(tokens_per_s, flops_per_token,
                                                   n_chips, peak_flops)}
                               if peak_flops else {}),
                            "time/total": ms_per_step,
                            **{f"time/{k}": t.avg_elapsed_ms() for k, t in timers.items()},
                            **({"guard_skipped": guard.total_skipped}
                               if guard.enabled else {}),
                            **(extra_log or {}),
                        }
                        LOGGER.info(info)
                        tracker.log(info, step=host_state["global_step"])
                        last_info = info
                        host_state["running_loss"] = 0.0
                        for t in timers.values():
                            t.reset()

                if io is not None and host_state["global_step"] % args.ckpt_freq == 0:
                    # host_state is about to be persisted. Timing caveat
                    # (deliberate): with --fence-every > 1 this drain runs
                    # OUTSIDE the step timer while the log-boundary drain is
                    # inside it — when ckpt_freq isn't a multiple of
                    # log_freq, the awaited device work of this fence group
                    # is untimed and that window's tokens_per_s/MFU reads
                    # slightly high. Align ckpt_freq to log_freq for
                    # benchmark-grade numbers
                    drain_losses()
                    LOGGER.info("Saving checkpoint.")
                    with span("train.ckpt", step=host_state["global_step"]):
                        io.save(state, host_state)

                # after the checkpoint block: an injected crash at step N
                # leaves the step-N checkpoint (if any) published, matching
                # the "died right after saving" drill the docs describe
                maybe_crash(host_state["global_step"])

                if args.max_steps and host_state["global_step"] >= args.max_steps:
                    done = True
                    break

            drain_losses()  # epoch boundary (or early break) observes the bank
            host_state["epoch_step"] = 0
            if done:
                break

    finally:
        if profile_started and not profile_done:
            jax.profiler.stop_trace()
            LOGGER.info(f"profiler trace written to {args.profile_dir} "
                        f"(run ended inside the trace window)")
        if io is not None:
            io.close()  # finalize any in-flight async checkpoint
        tracker.finish()
        loader.close()
        if progress:
            progress.close()
    if is_process0():
        cache.print_line()
    return {"host_state": host_state, "last_info": last_info, "state": state}
