"""Config-driven training engine facade.

Capability parity with the reference's DeepSpeed chapter
(``alternative-frameworks/deepspeed/train_llm.py``): there, a JSON config
(``ds_config.json``) drives ZeRO stage, batch sizes, grad accumulation and
precision, and the engine owns backward/step/checkpoint
(``model_engine.backward(loss); model_engine.step()``). The TPU-native engine
keeps the config-file surface (similar keys where they make sense) but maps
stages to sharding plans:

    stage 0 -> ddp, stage 1 -> zero1 (opt state sharded),
    stage 2 -> zero2 (opt state + grads sharded, params replicated),
    stage 3 -> fsdp (params sharded too)

and covers the WHOLE strategy space beyond the reference's engine:
``tensor_parallel``, ``pipeline_parallel`` (+ ``pp_microbatches``),
``context_parallel`` (+ ``context_impl``: "ring"/"ulysses"),
``expert_parallel``, ``moe_dispatch`` ("dense" capacity buffers / "ragged"
dropless sorted dispatch, MoE models only), ``attn_impl``, ``loss_chunks``,
and ``activation_checkpointing`` as a bool or
``{"enabled": true, "policy": "attn"}`` (a REMAT_POLICIES key). Storage
precision is a named policy (``train/precision.py``): spell it
``optimizer.params.precision`` (DeepSpeed-style, next to lr/betas) or
top-level ``precision`` — "fp32" (default, bit-identical to the seed),
"bf16-master", "adam8bit", or a "+" composition. ``bf16.enabled`` keeps its
original meaning (model COMPUTE dtype).

Eager ``backward()``/``step()`` calls make no sense under XLA — the engine's
``train_batch(batch)`` is the whole fused step (what DeepSpeed's pair does,
minus the Python boundary in the middle).

Example config (see ``alternative-frameworks/engine/config.json``)::

    {
      "model": "llama-3.1-8b",
      "zero_optimization": {"stage": 3},
      "tensor_parallel": 1,
      "train_micro_batch_size_per_gpu": 8,
      "gradient_accumulation_steps": 1,
      "optimizer": {"type": "AdamW",
                    "params": {"lr": 3e-5, "weight_decay": 0.01,
                               "precision": "adam8bit"}},
      "scheduler": {"t_max": 1000, "eta_min_ratio": 0.01, "warmup_steps": 0},
      "bf16": {"enabled": true},
      "activation_checkpointing": true,
      "offload_optimizer": false
    }
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Optional

import jax
import numpy as np

_STAGE_TO_STRATEGY = {0: "ddp", 1: "zero1", 2: "zero2", 3: "fsdp"}


def _ds_offload_enabled(v) -> bool:
    """DeepSpeed offload values: bool, or {"device": "cpu"/"nvme"/"none"}
    — the dict with device "none" is the canonical DISABLE spelling."""
    if isinstance(v, dict):
        return v.get("device", "none") not in ("none", None)
    return bool(v)


class TrainingEngine:
    def __init__(self, config: dict | str | Path):
        from ..models import get_model
        from ..parallel import make_mesh, make_plan
        from .optimizer import adafactor_cosine, adamw_cosine, lion_cosine
        from .step import Trainer

        if not isinstance(config, dict):
            with open(config) as fp:
                config = json.load(fp)
        self.config = config

        import jax.numpy as jnp

        bf16 = config.get("bf16", {}).get("enabled", True)
        overrides = {"dtype": jnp.bfloat16 if bf16 else jnp.float32}
        if config.get("moe_dispatch"):
            # "dense" (capacity buffers) | "ragged" (dropless sorted dispatch
            # + grouped GEMMs, models/moe.py) — MoE families only
            overrides["moe_dispatch"] = config["moe_dispatch"]
        try:
            bundle = get_model(config["model"], **overrides)
        except TypeError as exc:
            if "moe_dispatch" not in overrides:
                raise
            raise ValueError(
                f"moe_dispatch={config['moe_dispatch']!r} is only valid "
                f"for MoE models; {config['model']!r} rejected it ({exc})")

        # {"rank": 8, "alpha": 16, "targets": ["wq","wv"]} — wrap the model
        # in LoRA adapters (models/lora.py) and restrict the optimizer to
        # them (lora_only below: base updates zeroed, moments only for the
        # adapter leaves) — the parameter-efficient finetune/post-training
        # configuration, config-file spelled like everything else here
        lora_cfg = config.get("lora")
        if lora_cfg:
            from ..models.lora import DEFAULT_TARGETS, lora_bundle

            bundle = lora_bundle(
                bundle, rank=lora_cfg.get("rank", 8),
                alpha=lora_cfg.get("alpha", 16.0),
                targets=tuple(lora_cfg.get("targets", DEFAULT_TARGETS)))

        stage = config.get("zero_optimization", {}).get("stage", 0)
        tp = config.get("tensor_parallel", 1)
        pp = config.get("pipeline_parallel", 1)
        cp = config.get("context_parallel", 1)
        ep = config.get("expert_parallel", 1)
        n = len(jax.devices())
        if ep > 1 and (tp > 1 or pp > 1):
            raise ValueError(
                "expert_parallel composes with data/fsdp axes only (the ep "
                "plans); drop tensor_parallel/pipeline_parallel or ep")
        if stage in (1, 2) and (pp > 1 or ep > 1):
            raise ValueError(
                "ZeRO stage 1/2 shards optimizer/grad state over the data "
                "axes of ddp/tp plans; with pipeline_parallel or "
                "expert_parallel use stage 0 or 3")
        denom = tp * pp * cp * ep
        if n % denom:
            raise ValueError(f"{n} devices not divisible by tensor x "
                             f"pipeline x context x expert = {denom}")
        fsdp_like = stage == 3
        if ep > 1:
            strategy = "ep_fsdp" if fsdp_like else "ep"
        elif pp > 1:
            strategy = ("pp_tp_fsdp" if tp > 1 and fsdp_like
                        else "pp_tp" if tp > 1
                        else "pp_fsdp" if fsdp_like else "pp")
        elif tp > 1:
            strategy = "tp_fsdp" if fsdp_like else "tp"
        else:
            strategy = _STAGE_TO_STRATEGY[stage]
        mesh_kw = {k: v for k, v in
                   dict(tp=tp, pp=pp, cp=cp, ep=ep).items() if v > 1}
        if fsdp_like:
            mesh_kw["fsdp"] = n // denom
        mesh = make_mesh(**mesh_kw)
        # ZeRO-1/2 sharding is orthogonal to tp: keep the optimizer-state
        # (and for stage 2 the gradient-buffer) sharding when the strategy
        # string was rewritten for tensor_parallel
        plan = make_plan(strategy, mesh, zero1=(stage in (1, 2)) or None,
                         zero2=(stage == 2) or None)

        opt_type = config.get("optimizer", {}).get("type", "AdamW").lower()
        opt_cfg = dict(config.get("optimizer", {}).get("params", {}))
        # precision policy (train/precision.py): the DeepSpeed-ish nested
        # spelling optimizer.params.precision, or top-level "precision" —
        # both name a policy ("fp32" | "bf16-master" | "adam8bit" | a '+'
        # composition). The bf16 block stays what it always was here: the
        # model COMPUTE dtype. Conflicting spellings fail loudly.
        nested_precision = opt_cfg.pop("precision", None)
        top_precision = config.get("precision")
        if (nested_precision and top_precision
                and nested_precision != top_precision):
            raise ValueError(
                f"optimizer.params.precision={nested_precision!r} conflicts "
                f"with top-level precision={top_precision!r}; set one")
        precision = nested_precision or top_precision or "fp32"
        known = {"adamw": {"lr", "betas", "eps", "weight_decay"},
                 "adam": {"lr", "betas", "eps", "weight_decay"},
                 "adafactor": {"lr", "weight_decay"},
                 "lion": {"lr", "betas", "weight_decay"}}.get(opt_type)
        unknown = set(opt_cfg) - known if known is not None else set()
        if unknown:
            # silently dropping e.g. betas for Adafactor would run different
            # dynamics than the (likely AdamW-ported) config implies
            raise ValueError(
                f"optimizer.params {sorted(unknown)} are not supported for "
                f"optimizer.type {opt_type!r} (supported: {sorted(known)}); "
                f"remove them or switch type")
        sched = config.get("scheduler", {})
        if "type" in sched or "params" in sched:
            # canonical DeepSpeed spelling (the reference's ds_config.json:
            # {"type": "WarmupCosineLR", "params": {total_num_steps,
            # warmup_num_steps, cos_min_ratio}}). Fail-loud policy, same as
            # optimizer.params: only the cosine schedule exists here, and a
            # param this engine would drop (e.g. warmup_max_lr) means the
            # run would use different dynamics than the config states.
            stype = sched.get("type", "WarmupCosineLR")
            if stype not in ("WarmupCosineLR", "WarmupDecayLR"):
                raise ValueError(
                    f"scheduler.type {stype!r} is not supported "
                    f"(WarmupCosineLR or WarmupDecayLR); or use the flat "
                    f"native spelling {{t_max, eta_min_ratio, warmup_steps,"
                    f" decay}}")
            p = sched.get("params", {})
            known = {"total_num_steps", "warmup_num_steps"}
            if stype == "WarmupCosineLR":
                known.add("cos_min_ratio")
            unknown = set(p) - known
            if unknown:
                raise ValueError(
                    f"scheduler.params {sorted(unknown)} are not supported "
                    f"for {stype} (supported: {sorted(known)}); remove them "
                    f"or port the values to the flat native spelling")
            total = p.get("total_num_steps", 1000)
            warmup = p.get("warmup_num_steps", 0)
            # DS semantics: the decay ENDS at total_num_steps. The native
            # schedule decays over t_max steps AFTER warmup, so the DS
            # spelling maps to t_max = total - warmup (keeping t_max=total
            # would hit the floor warmup steps late, at a shallower slope)
            sched = {"t_max": max(total - warmup, 1),
                     "warmup_steps": warmup,
                     # WarmupDecayLR decays LINEARLY to zero in DeepSpeed
                     "eta_min_ratio": (p.get("cos_min_ratio", 0.01)
                                       if stype == "WarmupCosineLR" else 0.0),
                     "decay": ("cosine" if stype == "WarmupCosineLR"
                               else "linear")}
        self.scheduler_config = sched  # post-normalization (tests pin this)
        common = dict(
            weight_decay=opt_cfg.get("weight_decay", 0.01),
            t_max=sched.get("t_max", 1000),
            eta_min_ratio=sched.get("eta_min_ratio", 0.01),
            warmup_steps=sched.get("warmup_steps", 0),
            decay=sched.get("decay", "cosine"),
            grad_clip=config.get("gradient_clipping"),
        )
        if opt_type in ("adamw", "adam"):
            optimizer = adamw_cosine(
                opt_cfg.get("lr", 3e-5),
                b1=opt_cfg.get("betas", [0.9, 0.999])[0],
                b2=opt_cfg.get("betas", [0.9, 0.999])[1],
                eps=opt_cfg.get("eps", 1e-8),
                **common)
        elif opt_type == "adafactor":
            optimizer = adafactor_cosine(opt_cfg.get("lr", 3e-5), **common)
        elif opt_type == "lion":
            optimizer = lion_cosine(
                opt_cfg.get("lr", 1e-5),
                b1=opt_cfg.get("betas", [0.9, 0.99])[0],
                b2=opt_cfg.get("betas", [0.9, 0.99])[1],
                **common)
        else:
            raise ValueError(f"unknown optimizer.type {opt_type!r}; "
                             f"use AdamW, Adafactor, or Lion")

        # bool (DeepSpeed-style) or {"enabled": bool, "policy": <REMAT key>}
        ac = config.get("activation_checkpointing", False)
        if isinstance(ac, dict):
            remat, remat_policy = ac.get("enabled", True), ac.get("policy", "all")
        else:
            remat, remat_policy = bool(ac), "all"

        # {"policy": "off"|"skip"|"abort", "max_consecutive_skips": N} —
        # train/guards.py; detection compiles into the step, enforcement
        # happens on the metrics train_batch already host-reads
        from .guards import GuardMonitor

        sg = config.get("step_guards", {})
        guard_policy = sg.get("policy", "off")
        self._guard = GuardMonitor(guard_policy,
                                   sg.get("max_consecutive_skips", 5))

        self.trainer = Trainer(
            bundle=bundle,
            optimizer=optimizer,
            lora_only=bool(lora_cfg),
            plan=plan,
            grad_accum=config.get("gradient_accumulation_steps", 1),
            remat=remat,
            remat_policy=remat_policy,
            attn_impl=config.get("attn_impl", "auto"),
            context_impl=config.get("context_impl", "ring"),
            cp_hop_loop=config.get("cp_hop_loop", "auto"),
            guard_policy=guard_policy,
            loss_chunks=config.get("loss_chunks", 0),
            pp_microbatches=config.get("pp_microbatches"),
            precision=precision,
            # both spellings: our top-level key, and DeepSpeed's nested
            # zero_optimization.offload_optimizer/offload_param — there a
            # bool, or a dict whose device decides ({"device": "none"} is
            # the canonical DISABLE spelling, so bool(dict) would invert it)
            offload_opt_state=bool(
                config.get("offload_optimizer", False)
                or _ds_offload_enabled(
                    config.get("zero_optimization", {}).get(
                        "offload_optimizer", False))),
            offload_params=bool(
                config.get("offload_params", False)
                or _ds_offload_enabled(
                    config.get("zero_optimization", {}).get(
                        "offload_param", False))),
        )
        self.state = self.trainer.init_state(config.get("seed", 0))
        # host-side mirror of state.step: train_batch/save_checkpoint must
        # not jax.device_get the device counter every call (that host sync
        # blocks the dispatch pipeline; see train_batch)
        self._step = 0
        self._ios: dict[str, Any] = {}  # save_dir/tag -> CheckpointIO

    # ---- deepspeed-surface methods ----------------------------------------
    @property
    def micro_batch_size(self) -> int:
        return self.config.get("train_micro_batch_size_per_gpu", 1)

    @property
    def global_batch_size(self) -> int:
        return (self.micro_batch_size * self.trainer.plan.data_parallel_size
                * self.trainer.grad_accum)

    def train_batch(self, batch: dict) -> dict:
        """fwd + bwd + optimizer step (= model_engine.backward + step).

        Returns the metric dict with DEVICE scalars: nothing here forces a
        host sync, so the host can dispatch the next step(s) while this one
        still runs (the CLI's banked-loss pattern; a per-step ``float(v)``
        here would put the host's dispatch latency between steps). Each
        value materializes lazily when the caller reads it — the caller's
        logging cadence IS the fence cadence. With step guards enabled the
        per-step host read comes back by construction: the skip/abort policy
        is enforced on the host against this step's flag.
        """
        self.state, metrics = self.trainer.step_fn(self.state, batch)
        self._step += 1
        if self._guard.enabled:
            out = {k: float(v) for k, v in metrics.items()}
            skipped = self._guard.observe(
                out.get("notfinite", 0.0), step=self._step, metrics=out)
            out["guard_skipped"] = float(skipped)
            return out
        return dict(metrics)

    def _io_for(self, save_dir: str | Path, tag: Optional[str]):
        """One CheckpointIO per destination, reused across calls and closed
        by ``close()`` — retention state and any in-flight async save live on
        the IO object, so a throwaway per call would leak its Orbax
        resources and re-run the orphan sweep on every save."""
        from ..checkpoint import CheckpointIO

        key = str(Path(save_dir) / (tag or ""))
        io = self._ios.get(key)
        if io is None:
            io = self._ios[key] = CheckpointIO(key)
        return io

    def save_checkpoint(self, save_dir: str | Path, tag: Optional[str] = None) -> None:
        from .state import host_state_dict

        host = host_state_dict()
        host["global_step"] = self._step  # host mirror: no device sync
        from ..checkpoint import stamp_host_state

        stamp_host_state(host, self.trainer)
        self._io_for(save_dir, tag).save(self.state, host)

    def load_checkpoint(self, save_dir: str | Path, tag: Optional[str] = None) -> dict:
        from ..checkpoint import restore_train_state

        io = self._io_for(save_dir, tag)
        self.state, host = restore_train_state(io, self.trainer)
        self._step = int(host.get("global_step", 0))
        return host

    def close(self) -> None:
        """Flush + release every CheckpointIO this engine opened."""
        for io in self._ios.values():
            io.close()
        self._ios.clear()


def initialize(config: dict | str | Path) -> TrainingEngine:
    """``deepspeed.initialize`` analogue."""
    return TrainingEngine(config)
