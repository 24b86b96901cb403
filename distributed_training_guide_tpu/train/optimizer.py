"""Optimizer + LR schedule.

Parity with the reference's fused AdamW + CosineAnnealingLR
(``01-single-gpu/train_llm.py:73-78``): ``optax.adamw`` under jit compiles to
fully fused XLA update kernels (the reference needs torch's hand-written fused
CUDA kernels and even ``torch.compile(optimizer.step)``,
``05-training-llama-405b/train_llm.py:202-204`` — under XLA this is free).

Schedule matches CosineAnnealingLR(T_max=1000, eta_min=lr*1e-2) semantics:
cosine from lr to lr/100 over t_max steps, then flat. Optional linear warmup
(the LR-scaling recipes in ``related-topics/effective-batch-size-and-lr``).
"""
from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp
import optax


def make_schedule(lr: float, t_max: int = 1000, eta_min_ratio: float = 0.01,
                  warmup_steps: int = 0,
                  decay: str = "cosine") -> optax.Schedule:
    """Warmup + decay-to-``lr*eta_min_ratio`` over ``t_max`` steps, flat
    after. ``decay``: "cosine" (the reference's CosineAnnealingLR shape) or
    "linear" (DeepSpeed's WarmupDecayLR shape — pair with
    ``eta_min_ratio=0.0`` for its decay-to-zero semantics)."""
    if decay not in ("cosine", "linear"):
        raise ValueError(f"decay must be cosine|linear, got {decay!r}")
    eta_min = lr * eta_min_ratio

    def schedule(step):
        warm = jnp.minimum(step / max(warmup_steps, 1), 1.0) if warmup_steps else 1.0
        t = jnp.clip(step - warmup_steps, 0, t_max)
        if decay == "cosine":
            val = eta_min + (lr - eta_min) * 0.5 * (1 + jnp.cos(jnp.pi * t / t_max))
        else:
            val = eta_min + (lr - eta_min) * (1 - t / t_max)
        return warm * val

    return schedule


def cosine_schedule(lr: float, t_max: int = 1000, eta_min_ratio: float = 0.01,
                    warmup_steps: int = 0) -> optax.Schedule:
    return make_schedule(lr, t_max, eta_min_ratio, warmup_steps, "cosine")


def adamw_cosine(
    lr: float,
    *,
    t_max: int = 1000,
    eta_min_ratio: float = 0.01,
    warmup_steps: int = 0,
    weight_decay: float = 0.01,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    grad_clip: Optional[float] = None,
    decay: str = "cosine",
) -> optax.GradientTransformation:
    tx = optax.adamw(
        learning_rate=make_schedule(lr, t_max, eta_min_ratio, warmup_steps,
                                    decay),
        b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
    )
    if grad_clip:
        tx = optax.chain(optax.clip_by_global_norm(grad_clip), tx)
    return tx


def adafactor_cosine(
    lr: float,
    *,
    t_max: int = 1000,
    eta_min_ratio: float = 0.01,
    warmup_steps: int = 0,
    weight_decay: float = 0.01,
    grad_clip: Optional[float] = None,
    min_dim_size_to_factor: int = 128,
    decay: str = "cosine",
) -> optax.GradientTransformation:
    """Adafactor with the same cosine schedule as ``adamw_cosine``.

    The TPU-native memory lever the reference doesn't have: the second
    moment is stored FACTORED (row + column accumulators, Shazeer & Stern
    2018) and the first moment is dropped, so optimizer state is ~1/1000 of
    AdamW's 2x-fp32 (e.g. ~5.2 GB -> ~7 MB for the ``llama-650m`` preset) —
    often the difference between fitting a model on a chip with the Adam
    recipe (reference ``05:69-72``'s CPU offload) and just training it.

    Built as an explicit chain rather than ``optax.adafactor`` because the
    canned version appends ``add_decayed_weights`` AFTER the learning-rate
    scaling — i.e. decay of ``wd * p`` per step regardless of lr, ~1e4x
    stronger than AdamW's decoupled ``lr * wd * p``. Here decay sits before
    ``scale_by_learning_rate`` so the update is ``-lr_t * (rms_grad + wd*p)``,
    matching ``optax.adamw``'s semantics and schedule exactly.
    """
    schedule = make_schedule(lr, t_max, eta_min_ratio, warmup_steps, decay)
    steps = [
        optax.scale_by_factored_rms(min_dim_size_to_factor=min_dim_size_to_factor),
        optax.clip_by_block_rms(1.0),
        optax.add_decayed_weights(weight_decay) if weight_decay else None,
        optax.scale_by_learning_rate(schedule),
    ]
    tx = optax.chain(*[s for s in steps if s is not None])
    if grad_clip:
        tx = optax.chain(optax.clip_by_global_norm(grad_clip), tx)
    return tx


def lion_cosine(
    lr: float,
    *,
    t_max: int = 1000,
    eta_min_ratio: float = 0.01,
    warmup_steps: int = 0,
    weight_decay: float = 0.01,
    b1: float = 0.9,
    b2: float = 0.99,
    grad_clip: Optional[float] = None,
    decay: str = "cosine",
) -> optax.GradientTransformation:
    """Lion (Chen et al. 2023) with the shared cosine schedule.

    The middle point of the optimizer-memory ladder: one momentum slot
    (AdamW keeps two, adafactor ~none), and sign-based updates whose
    magnitude is set purely by ``lr`` — the usual recipe is ~3-10x lower lr
    and ~3-10x higher weight decay than AdamW. ``optax.lion`` already
    applies decay decoupled and before the lr scaling (same semantics as
    ``optax.adamw``), so no re-chaining is needed here.
    """
    tx = optax.lion(
        learning_rate=make_schedule(lr, t_max, eta_min_ratio, warmup_steps,
                                    decay),
        b1=b1, b2=b2, weight_decay=weight_decay,
    )
    if grad_clip:
        tx = optax.chain(optax.clip_by_global_norm(grad_clip), tx)
    return tx


# name -> constructor, the dispatch shared by the chapter CLI (--optimizer)
# and the benchmark's runners; the engine facade adds its own config mapping
OPTIMIZERS = {"adamw": adamw_cosine, "adafactor": adafactor_cosine,
              "lion": lion_cosine}


def lr_at_step(step: int, lr: float, t_max: int = 1000, eta_min_ratio: float = 0.01,
               warmup_steps: int = 0) -> float:
    """Host-side mirror of the schedule for logging (reference logs
    ``lr_scheduler.get_last_lr()``, ``01:160``)."""
    eta_min = lr * eta_min_ratio
    warm = min(step / max(warmup_steps, 1), 1.0) if warmup_steps else 1.0
    t = min(max(step - warmup_steps, 0), t_max)
    return warm * (eta_min + (lr - eta_min) * 0.5 * (1 + math.cos(math.pi * t / t_max)))
