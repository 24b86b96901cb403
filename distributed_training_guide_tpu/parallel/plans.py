"""Sharding plans: every parallelism strategy as a logical->mesh axis mapping.

This file replaces four different torch wrapper APIs from the reference with
one mechanism. The reference needs:

- ``DistributedDataParallel``            (``02-distributed-data-parallel/train_llm.py:66-68``)
- ``ZeroRedundancyOptimizer``            (``02:87-89``)
- ``fully_shard`` (FSDP2)                (``04-fully-sharded-data-parallel/train_llm.py:83-95``)
- ``tp.parallelize_module`` Colwise/Rowwise/SequenceParallel plans (``06:79-121``)
- both at once on a 2-D mesh             (``07-2d-parallel/train_llm.py:77-123``)

Here each of those is a *rules table* mapping the model's logical parameter
axes (vocab/embed/heads/kv/mlp) to mesh axes (dp/fsdp/tp/cp). GSPMD then
inserts exactly the collectives the reference implements by hand in CUDA:
grad psum over dp/fsdp (DDP all-reduce), per-layer all-gather/reduce-scatter
of fsdp-sharded params (FSDP), and the TP all-gather / reduce-scatter pairs
from the reference's forward walk (SURVEY.md section 3.3).

A dimension that is not divisible by its assigned mesh axis falls back to
replication on that axis (torch DTensor errors instead; replication is always
correct, just less sharded).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axes, per strategy. A value may be a single mesh axis
# name or a tuple of them (sharded over both).
STRATEGIES: dict[str, dict[str, Any]] = {
    # chapter 01: one device
    "single": {},
    # chapter 02: replicated params, data sharded over (dp, fsdp)
    "ddp": {},
    # chapter 02 + ZeRO-1: params replicated, *optimizer state* sharded (the
    # optimizer-state rules below are applied by train/optimizer.py)
    "zero1": {},
    # ZeRO-2 (deepspeed stage 2): params replicated, optimizer state AND the
    # gradient-accumulation buffer sharded over the data axes — the grads'
    # reduce-scatter replaces DDP's all-reduce, and full grads never persist
    "zero2": {},
    # chapter 04: FULL_SHARD — every weight matrix sharded on its embed dim
    "fsdp": {
        "embed": "fsdp",
        "vocab": "fsdp",  # the embedding table ("vocab", "embed") shards its
                          # vocab dim (embed comes second: fsdp is taken);
                          # an untied lm_head ("embed", "vocab") shards embed
                          # like every other matrix (spec_for_leaf gives an
                          # axis to the FIRST dim that asks for it)
    },
    # chapter 06: megatron TP + sequence parallelism for activations.
    # *_vector axes are the gpt2 biases — a column-parallel projection's
    # bias shards with its columns
    "tp": {
        "heads": "tp",
        "kv": "tp",
        "mlp": "tp",
        "vocab": "tp",
        "heads_vector": "tp",
        "kv_vector": "tp",
        "mlp_vector": "tp",
    },
    # chapter 07: 2-D = FSDP x TP on orthogonal axes
    "tp_fsdp": {
        "heads": "tp",
        "kv": "tp",
        "mlp": "tp",
        "vocab": "tp",
        "heads_vector": "tp",
        "kv_vector": "tp",
        "mlp_vector": "tp",
        "embed": "fsdp",
    },
    # chapter 09 (beyond the reference): pipeline stages own layer slices;
    # the stacked layer dim is the sharded one (parallel/pipeline.py)
    "pp": {"layers": "pp"},
    "pp_fsdp": {"layers": "pp", "embed": "fsdp", "vocab": "fsdp"},
    "pp_tp": {"layers": "pp", "heads": "tp", "kv": "tp", "mlp": "tp",
              "vocab": "tp", "heads_vector": "tp", "kv_vector": "tp",
              "mlp_vector": "tp"},
    # pp x tp x fsdp: tp is manual inside the pipeline shard_map (megatron
    # shards + vocab-parallel embed/head), fsdp stays auto on the embed dim
    "pp_tp_fsdp": {"layers": "pp", "heads": "tp", "kv": "tp", "mlp": "tp",
                   "vocab": "tp", "heads_vector": "tp", "kv_vector": "tp",
                   "mlp_vector": "tp",
                   "embed": "fsdp"},
    # chapter 10 (beyond the reference): MoE expert parallelism — the expert
    # dim of stacked expert weights lives on ep. With moe_dispatch="dense"
    # GSPMD derives the token all-to-all from the static capacity
    # dispatch/combine einsums; with "ragged" (dropless sorted dispatch) the
    # sort is data-dependent, so the Trainer threads a manual shard_map over
    # the data axes that exchanges sorted expert groups instead
    # (models/moe.py make_ragged_ep_dispatch) — same rules table either way
    "ep": {"experts": "ep"},
    "ep_fsdp": {"experts": "ep", "embed": "fsdp", "vocab": "fsdp"},
}

# logical axes that shard the optimizer state only (ZeRO-1, reference C3):
ZERO1_RULES = {"embed": ("dp", "fsdp"), "vocab": ("dp", "fsdp")}


def _dim_divisible(mesh: Mesh, axes, dim: int) -> bool:
    if axes is None:
        return True
    names = (axes,) if isinstance(axes, str) else tuple(axes)
    size = int(np.prod([mesh.shape[a] for a in names]))
    return size > 0 and dim % size == 0


def spec_for_leaf(mesh: Mesh, logical_axes: tuple, shape: tuple, rules: dict) -> P:
    """PartitionSpec for one parameter leaf; replicates non-divisible dims."""
    entries = []
    used: set = set()
    for ax_name, dim in zip(logical_axes, shape):
        mesh_axes = rules.get(ax_name)
        if mesh_axes is not None:
            names = (mesh_axes,) if isinstance(mesh_axes, str) else tuple(mesh_axes)
            if any(n in used for n in names) or not _dim_divisible(mesh, names, dim):
                mesh_axes = None
            else:
                used.update(names)
        entries.append(mesh_axes)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """Everything the train-step builder needs to lay out one strategy."""

    mesh: Mesh
    strategy: str
    rules: dict
    sequence_sharded: bool = False  # SP: shard the seq dim of activations on tp
    zero1: bool = False             # shard optimizer state over the data axes
    zero2: bool = False             # zero1 + shard persistent gradients too

    # ---- batch / data ------------------------------------------------------
    @property
    def data_axes(self) -> tuple:
        """Mesh axes that partition the global batch dim. ``ep`` is a data
        axis: tokens shard over it, and it is precisely the combination
        (tokens over ep) x (experts over ep) that makes GSPMD partition the
        MoE dispatch/combine einsums into the token all-to-all (GShard)."""
        return ("dp", "fsdp", "ep")

    def batch_spec(self, ndim: int = 2) -> P:
        seq = ("cp",) if self.mesh.shape["cp"] > 1 else None
        if ndim == 1:
            return P(self.data_axes)
        extra = [seq[0] if seq else None] + [None] * (ndim - 2)
        return P(self.data_axes, *extra)

    def batch_sharding(self, ndim: int = 2) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec(ndim))

    @property
    def data_parallel_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.data_axes]))

    def active_axes(self) -> tuple:
        """Mesh axes with size > 1. The serve-side sharded page pool
        (serve/sharding.py) keys its full-manual-region validation on
        this: its rules table mirrors the ``kv``->tp mapping above, and
        the pool is only shardable when tp is the sole active axis."""
        return tuple(a for a in self.mesh.axis_names
                     if int(self.mesh.shape[a]) > 1)

    # ---- activations -------------------------------------------------------
    def activation_sharding(self) -> Optional[NamedSharding]:
        """Residual-stream constraint [B, S, E] between blocks.

        With SP (reference's SequenceParallel norms, ``06:90,101,115``) the
        sequence dim is sharded on tp so norms/elementwise run on 1/tp of the
        tokens; XLA inserts the same all-gather before attention/mlp and
        reduce-scatter after that DTensor does.
        """
        if self.mesh.shape["cp"] > 1:
            # context parallelism: seq dim lives on cp everywhere; attention
            # crosses shards via the ring (ops/ring_attention.py)
            return NamedSharding(self.mesh, P(self.data_axes, "cp", None))
        if self.sequence_sharded and self.mesh.shape["tp"] > 1:
            return NamedSharding(self.mesh, P(self.data_axes, "tp", None))
        if self.strategy == "single":
            return None
        return NamedSharding(self.mesh, P(self.data_axes, None, None))

    def logits_sharding(self) -> Optional[NamedSharding]:
        """Loss-parallel layout [B, S, V]: keep the vocab dim tp-sharded
        through the cross-entropy (logsumexp becomes local-reduce + psum)
        instead of all-gathering full logits. The reference documents this as
        ``loss_parallel`` but ships with ``Replicate()``
        (``06-tensor-parallel/README.md:241-271``, ``06:117``)."""
        if self.rules.get("vocab") == "tp" and self.mesh.shape["tp"] > 1:
            seq = "cp" if self.mesh.shape["cp"] > 1 else None
            return NamedSharding(self.mesh, P(self.data_axes, seq, "tp"))
        return None

    # ---- params / optimizer state -----------------------------------------
    def param_shardings(self, logical_axes_tree, shape_tree) -> Any:
        """NamedSharding pytree for params (shape_tree: ShapeDtypeStructs)."""
        is_ax = lambda x: isinstance(x, tuple)
        return jax.tree.map(
            lambda ax, sd: NamedSharding(self.mesh, spec_for_leaf(self.mesh, ax, sd.shape, self.rules)),
            logical_axes_tree, shape_tree,
            is_leaf=is_ax,
        )

    def optimizer_state_rules(self) -> dict:
        """Rules for optimizer-state leaves (adds ZeRO-1 on top of params)."""
        if self.zero1:
            return {**self.rules, **ZERO1_RULES}
        return self.rules

    def grad_shardings(self, logical_axes_tree, shape_tree) -> Any:
        """Shardings for *persistent* gradient buffers (ZeRO-2): grads follow
        the optimizer-state layout, so under zero2 the accumulation buffer is
        reduce-scattered across the data axes instead of living replicated."""
        rules = self.optimizer_state_rules() if self.zero2 else self.rules
        is_ax = lambda x: isinstance(x, tuple)
        return jax.tree.map(
            lambda ax, sd: NamedSharding(self.mesh, spec_for_leaf(self.mesh, ax, sd.shape, rules)),
            logical_axes_tree, shape_tree,
            is_leaf=is_ax,
        )

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


def make_plan(strategy: str, mesh: Mesh, *, sequence_sharded: Optional[bool] = None,
              zero1: Optional[bool] = None,
              zero2: Optional[bool] = None) -> ShardingPlan:
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}; choose from {sorted(STRATEGIES)}")
    if sequence_sharded is None:
        sequence_sharded = strategy in ("tp", "tp_fsdp")
    if zero2 is None:
        zero2 = strategy == "zero2"
    if zero1 is None:
        zero1 = strategy == "zero1" or zero2
    return ShardingPlan(mesh=mesh, strategy=strategy, rules=STRATEGIES[strategy],
                        sequence_sharded=sequence_sharded, zero1=zero1,
                        zero2=zero2)
