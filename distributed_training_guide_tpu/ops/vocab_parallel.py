"""Vocab-parallel embedding + cross-entropy (manual-collective forms).

Megatron-style vocab parallelism for use inside ``shard_map`` regions where
``tp`` is a *manual* axis (the pipeline schedule, ``parallel/pipeline.py``):
each tp member owns a contiguous vocab shard of the embedding table / output
projection and the collectives are written explicitly instead of inserted by
GSPMD. The reference documents the auto-partitioned analogue as
``loss_parallel`` (``06-tensor-parallel/README.md:241-271``) but ships with
replicated logits; the GSPMD version of that idea lives in
``plans.ShardingPlan.logits_sharding``.

All functions are no-ops over the axis when its size is 1, so callers can use
one code path for tp and no-tp meshes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .collectives import psum as _psum
from .cross_entropy import IGNORE_INDEX


def vocab_parallel_embed(table_local: jnp.ndarray, input_ids: jnp.ndarray,
                         axis: str) -> jnp.ndarray:
    """Embedding lookup from a vocab-sharded table: mask out-of-shard ids,
    gather locally, psum partial rows across the axis.

    table_local: [V/axis_size, E]; input_ids: [...]; returns [..., E].
    """
    v_local = table_local.shape[0]
    offset = jax.lax.axis_index(axis) * v_local
    local = input_ids - offset
    in_shard = (local >= 0) & (local < v_local)
    rows = jnp.take(table_local, jnp.clip(local, 0, v_local - 1), axis=0)
    rows = jnp.where(in_shard[..., None], rows, 0)
    return _psum(rows, axis)


def vocab_parallel_causal_lm_loss(logits_local: jnp.ndarray,
                                  labels: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Mean next-token cross-entropy over vocab-sharded logits.

    Same semantics as ``cross_entropy.causal_lm_loss`` (shift inside, -100
    ignored) but the vocab dim stays sharded throughout: the logsumexp is a
    local reduce + psum and the target logit a masked local gather + psum, so
    full [B, S, V] logits never exist on any device.

    logits_local: [B, S, V/axis_size]; labels: [B, S] (replicated on axis).
    """
    logits = logits_local[:, :-1, :].astype(jnp.float32)
    targets = labels[:, 1:]
    valid = targets != IGNORE_INDEX

    logz = sharded_logsumexp(logits, axis)
    picked = sharded_pick(logits, targets, valid, axis)

    nll = (logz - picked) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)


def sharded_logsumexp(logits: jnp.ndarray, axis: str) -> jnp.ndarray:
    """logsumexp over a vocab-sharded last dim: local reduce + psum.

    The stabilizing max is constant w.r.t. AD (the exact gradient of
    logsumexp doesn't depend on the shift); pmax has no JVP rule, so the
    cross-shard max rides an all_gather of the (tiny) per-shard maxes.
    logits: [..., V/axis_size] fp32 -> [...]."""
    m = jax.lax.stop_gradient(jnp.max(
        jax.lax.all_gather(jnp.max(logits, axis=-1), axis), axis=0))
    sumexp = jax.lax.psum(
        jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), axis)
    return jnp.log(sumexp) + m


def sharded_pick(logits: jnp.ndarray, targets: jnp.ndarray,
                 valid: jnp.ndarray, axis: str) -> jnp.ndarray:
    """The target's logit out of a vocab-sharded last dim: masked local
    gather + psum. logits [..., V/axis], targets/valid [...] -> [...]."""
    v_local = logits.shape[-1]
    # GLOBAL target ids -> ids clipped into this member's vocab slice
    local_t = jnp.where(valid, targets, 0) - jax.lax.axis_index(axis) * v_local
    in_shard = (local_t >= 0) & (local_t < v_local)
    safe = jnp.clip(local_t, 0, v_local - 1)
    picked_local = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    return jax.lax.psum(jnp.where(in_shard, picked_local, 0.0), axis)
