"""Latency-hiding training schedules: explicit shard_map collectives the
XLA scheduler can slide across layer boundaries.

The unscheduled train step leaves communication to GSPMD: FSDP parameter
all-gathers are inserted *at use* inside the ``lax.scan`` over layers, grad
reduce-scatters materialize at the optimizer boundary, and both serialize
against compute — a collective inside a scan iteration structurally cannot
start during the previous iteration, whatever the latency-hiding scheduler
would like (the reference gets the overlap for free from FSDP2's implicit
prefetch + eager frees, ``04-fully-sharded-data-parallel/train_llm.py`` /
arXiv:2304.11277; ZeRO's byte accounting is arXiv:1910.02054).

``--overlap-schedule`` swaps that for an explicit schedule
(:class:`LayerSchedule`):

- the layer loop is UNROLLED into a flat program, so the scheduler may
  issue layer i+1's collectives while layer i computes;
- each layer's fsdp-sharded weights are all-gathered by a manual
  ``shard_map`` collective (``ops/collectives.all_gather``) with a custom
  VJP whose backward is a per-layer grad reduce-scatter
  (``psum_scatter`` with the cotangent widened to fp32 first, matching
  GSPMD's reduction dtype) — so layer i's reduce-scatter is issued inside
  layer i's backward cell and overlaps layer i-1's backward compute;
- every cell is ``jax.checkpoint``-wrapped; gather outputs are tagged
  ``fsdp_gather`` and excluded from every save policy, so the backward
  *re-gathers* each layer's weights (FSDP semantics — sharded params are
  the only persistent copy) and those re-gathers likewise overlap.

On TPU the overlap shows up as async ``all-gather-start``/``done`` pairs
spanning compute (pinned by tests/test_overlap.py via utils/hlo.py); the
flags below make the scheduler aggressive about it. Off-TPU the collectives
lower synchronously but the program is numerically identical — parity vs
the unscheduled path is the other half of the pin.

``make_fused_loss`` is the same idea applied to the loss: one hidden->loss
kernel (``ops.cross_entropy.fused_linear_cross_entropy``) under a manual
shard_map, composing the chunked loss with the tp/fsdp vocab shard so the
``[B*S, vocab]`` fp32 logits never exist on any device.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from .collectives import all_gather as _all_gather
from .collectives import gather_with_reduce_scatter_vjp as _gather_with_rs_vjp
from .collectives import psum as _psum

# XLA flags the schedule relies on to turn the flat program's collectives
# into async start/done pairs hoisted across layer compute (TPU; harmless
# elsewhere). Recorded in bench detail so measured numbers carry their
# scheduler config; documented in related-topics/performance-tuning.
RECOMMENDED_XLA_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
)

_GATHER_NAME = "fsdp_gather"


def _is_axes_leaf(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None)))
                                        for e in x)


class LayerSchedule:
    """Explicit per-layer prefetch/reduce-scatter schedule for a model's
    stacked layer parameters (built by :func:`make_layer_schedule`; threaded
    into the families' ``apply(..., layer_schedule=...)``).

    Call as ``schedule(block, carry, layers, wins)`` in place of the layer
    ``lax.scan``: ``block(carry, layer_params[, window_override=w])`` is the
    family's block function; ``layers`` the stacked param tree; ``wins`` the
    optional per-layer window column.
    """

    def __init__(self, mesh, gather_specs: Sequence[Optional[tuple]],
                 *, axis: str, remat: bool, remat_policy: Any,
                 manual: Optional[set] = None):
        # gather_specs: per layer-tree leaf, None (pass through) or the
        # leaf's full per-layer PartitionSpec entries with ``axis`` on the
        # dim to gather (other entries — e.g. a tp shard — stay put)
        self._gather_idx = [i for i, s in enumerate(gather_specs)
                            if s is not None]
        self.axis = axis
        self.n_gathered = len(self._gather_idx)
        if remat:
            # the user's policy decides what survives; none of the named
            # policies save the (untagged-by-them) fsdp_gather outputs, so
            # backward re-gathers either way
            self._policy = remat_policy
        else:
            # no user remat: save everything EXCEPT gathered weights — the
            # sharded params stay the only persistent copy (FSDP semantics)
            # and backward re-gathers layer by layer
            self._policy = jax.checkpoint_policies.save_anything_except_these_names(
                _GATHER_NAME)
        if not self._gather_idx:
            self._sm = None
            return
        gathers = []
        in_specs = []
        out_specs = []
        for i in self._gather_idx:
            entries = list(gather_specs[i])
            dim = next(j for j, e in enumerate(entries)
                       if axis in ((e,) if isinstance(e, str) else (e or ())))
            in_specs.append(P(*entries))
            out = list(entries)
            out[dim] = (None if isinstance(out[dim], str) else
                        tuple(a for a in out[dim] if a != axis) or None)
            out_specs.append(P(*out))  # gathered on ``axis``; e.g. a tp
            gathers.append(_gather_with_rs_vjp(axis, dim))  # shard stays

        def body(*shards):
            return tuple(g(p) for g, p in zip(gathers, shards))

        # the manual set covers every ACTIVE data axis and every axis a
        # leaf spec names, not just the gather axis: (a) jax 0.4.37's
        # partitioner rejects programs mixing manual subgroups of different
        # shapes (the EP dispatch and fused-loss regions are manual over
        # all data axes + tp), and (b) with dp/ep manual and unnamed in the
        # weight specs, shard_map's transpose psums the weight cotangent
        # over them PER LAYER — the data-parallel grad reduction issued
        # layer by layer in backward instead of in bulk at the optimizer
        # boundary
        self._sm = jax.shard_map(
            body, mesh=mesh, axis_names=manual or {axis}, check_vma=False,
            in_specs=tuple(in_specs), out_specs=tuple(out_specs))

    def gather_layer(self, layer):
        """All-gather one layer's fsdp-sharded leaves (manual collectives);
        pass every other leaf through untouched. Outputs are tagged so remat
        policies drop them (backward re-gathers)."""
        if self._sm is None:
            return layer
        flat, treedef = jax.tree_util.tree_flatten(layer)
        gathered = self._sm(*[flat[i] for i in self._gather_idx])
        for i, g in zip(self._gather_idx, gathered):
            flat[i] = checkpoint_name(g, _GATHER_NAME)
        return jax.tree_util.tree_unflatten(treedef, flat)

    def __call__(self, block, carry, layers, wins=None):
        leaves = jax.tree.leaves(layers)
        n_layers = leaves[0].shape[0]

        def cell(carry, layer, w):
            layer = self.gather_layer(layer)
            if w is None:
                return block(carry, layer)
            return block(carry, layer, window_override=w)

        # prevent_cse=True (the default): in a flat program CSE would merge
        # the backward recompute with the forward, resurrecting the gathered
        # weights the policy just dropped
        cell = jax.checkpoint(cell, policy=self._policy)
        for i in range(n_layers):
            layer_i = jax.tree.map(lambda p: p[i], layers)
            w = None if wins is None else wins[i]
            carry = cell(carry, layer_i, w)
        return carry


def make_layer_schedule(plan, layer_axes, layer_shapes, *, remat: bool,
                        remat_policy: Any, axis: str = "fsdp"
                        ) -> LayerSchedule:
    """Build the schedule for a plan's stacked layer params.

    ``layer_axes`` / ``layer_shapes``: the ``params["layers"]`` subtrees of
    the bundle's logical axes and shape trees (leading axis "layers" — the
    unrolled dim). Leaves whose spec puts ``axis`` on a dim get the manual
    gather; everything else passes through, so plans with no fsdp-sharded
    params (ddp/zero1/ep) still get the flat unrolled program (collectives
    free to slide) with zero gathers.
    """
    from ..parallel.plans import spec_for_leaf

    mesh = plan.mesh
    ax_leaves = jax.tree.leaves(layer_axes, is_leaf=_is_axes_leaf)
    sd_leaves = jax.tree.leaves(layer_shapes)
    assert len(ax_leaves) == len(sd_leaves)
    specs: list[Optional[tuple]] = []
    manual = {axis} | {a for a in plan.data_axes if mesh.shape.get(a, 1) > 1}
    sharded = mesh.shape.get(axis, 1) > 1
    ep_active = mesh.shape.get("ep", 1) > 1
    for ax, sd in zip(ax_leaves, sd_leaves):
        leaf_spec = None
        if sharded and not (ep_active and "experts" in ax):
            # expert-stacked weights under an active ep axis are gathered
            # INSIDE the EP dispatch region (make_ragged_ep_dispatch's
            # embed_axis path) — gathering them out here would feed one
            # partial-manual region's output into another, which the jax
            # 0.4.37 partitioner rejects outright
            spec = spec_for_leaf(mesh, ax, sd.shape, plan.rules)
            entries = list(spec) + [None] * (len(sd.shape) - len(spec))
            entries = entries[1:]  # drop the leading stacked "layers" dim
            names = set()
            for e in entries:
                names.update((e,) if isinstance(e, str) else (e or ()))
            if axis in names:
                leaf_spec = tuple(entries)
                manual |= names  # e.g. tp: the shard rides through the
                #                  region; manual sets must agree program-wide
        specs.append(leaf_spec)
    return LayerSchedule(mesh, specs, axis=axis, remat=remat,
                         remat_policy=remat_policy, manual=manual)


# ---------------------------------------------------------------------------
# fused hidden -> loss
# ---------------------------------------------------------------------------

def make_fused_loss(plan, *, num_chunks: int = 8):
    """One hidden->loss kernel for the plan: a manual shard_map around
    ``fused_linear_cross_entropy`` composing the chunked loss with the
    plan's vocab shard, so full ``[B*S, V]`` fp32 logits never exist.

    - vocab on **tp** (megatron loss-parallel): the kernel runs the
      vocab-parallel logsumexp/pick with explicit tp psums; under sequence
      parallelism the tp-sharded seq dim is all-gathered first (its
      transpose reduce-scatters the hidden cotangent — the SP backward).
    - vocab on **fsdp** (the fsdp plan's lm_head): the weight shard is
      all-gathered inside the region (transpose = the lm_head grad
      reduce-scatter, the same schedule story as the layers) and each
      member runs the full-vocab chunked kernel on its batch rows.
    - unsharded vocab: pure local chunked kernel.

    Returns ``loss(hidden [B,S,E], w_out [E,V], labels [B,S]) -> scalar``.
    """
    from .cross_entropy import fused_linear_cross_entropy

    mesh = plan.mesh
    data_axes = tuple(a for a in plan.data_axes if mesh.shape.get(a, 1) > 1)
    vocab_rule = plan.rules.get("vocab")

    def _sharded(rule_axis):
        return vocab_rule == rule_axis and mesh.shape.get(rule_axis, 1) > 1

    tp_vocab = _sharded("tp")
    fsdp_vocab = _sharded("fsdp")
    seq_tp = plan.sequence_sharded and mesh.shape.get("tp", 1) > 1

    manual = set(data_axes)
    if tp_vocab or seq_tp:
        manual.add("tp")
    if fsdp_vocab:
        manual.add("fsdp")
    if not manual:
        def local_loss(hidden, w_out, labels):
            nll, cnt = fused_linear_cross_entropy(hidden, w_out, labels,
                                                  num_chunks=num_chunks)
            return nll / jnp.maximum(cnt, 1.0)

        return local_loss

    hidden_spec = P(data_axes or None, "tp" if seq_tp else None, None)
    w_spec = P(None, "tp" if tp_vocab else ("fsdp" if fsdp_vocab else None))
    labels_spec = P(data_axes or None, None)
    w_gather = _gather_with_rs_vjp("fsdp", 1) if fsdp_vocab else None

    def body(hidden, w_out, labels):
        if seq_tp:
            # SP: pull the full sequence in; the transpose reduce-scatters
            # the hidden cotangent back onto the tp seq shards
            hidden = _all_gather(hidden, "tp", dim=1)
        if w_gather is not None:
            w_out = checkpoint_name(w_gather(w_out), _GATHER_NAME)
        nll, cnt = fused_linear_cross_entropy(
            hidden, w_out, labels, num_chunks=num_chunks,
            vocab_axis="tp" if tp_vocab else None)
        if data_axes:
            # global mean: sum over the batch-owning axes only (tp members
            # hold the SAME rows post-psum — summing over tp would double
            # count)
            nll = _psum(nll, data_axes)
            cnt = _psum(cnt, data_axes)
        return nll / jnp.maximum(cnt, 1.0)

    return jax.shard_map(body, mesh=mesh, axis_names=manual, check_vma=False,
                         in_specs=(hidden_spec, w_spec, labels_spec),
                         out_specs=P())


def fused_loss_supported(plan, config, family_mod, loss_fn) -> Optional[str]:
    """Why the fused hidden->loss path can NOT run for this setup (None =
    supported). The Trainer falls back to the standard loss branches on a
    reason rather than silently changing semantics."""
    from .cross_entropy import causal_lm_loss

    if not hasattr(family_mod, "output_weights"):
        return "family has no output_weights"
    if loss_fn is not causal_lm_loss:
        return "custom loss_fn"
    if getattr(config, "final_logit_softcap", None):
        return "final_logit_softcap is applied by lm_head_logits, which the "\
               "fused hidden->loss kernel bypasses"
    if plan.mesh.shape.get("cp", 1) > 1:
        return "cp-sharded sequence"
    vocab_rule = plan.rules.get("vocab")
    if vocab_rule not in (None, "tp", "fsdp"):
        return f"vocab sharded on unsupported axis {vocab_rule!r}"
    if vocab_rule is not None:
        size = plan.mesh.shape.get(vocab_rule, 1)
        if size > 1 and config.vocab_size % size:
            return (f"vocab_size {config.vocab_size} not divisible by "
                    f"{vocab_rule}={size}")
    return None
