"""The jitted train step: one compiled program per strategy.

This replaces the reference's eager hot loop (forward / backward / optimizer
step as separate host-driven phases with hook-driven NCCL collectives,
``02-distributed-data-parallel/train_llm.py:140-159``). Under XLA the whole
step — forward, backward, grad all-reduce, optimizer update — is a single
compiled program; GSPMD inserts collectives from the in/out shardings and the
latency-hiding scheduler overlaps them with compute (the reference needs
manual bucketing / ``set_modules_to_forward_prefetch`` for the same effect,
``05-training-llama-405b/train_llm.py:148-161``).

Gradient accumulation (reference C24, ``related-topics/gradient-accumulation``)
is a ``lax.scan`` over a leading microbatch axis — the analogue of ``no_sync``:
the grad psum happens once, at the optimizer boundary, because that is simply
where the sharded->replicated transition sits in the compiled program.
"""
from __future__ import annotations

import dataclasses
from functools import cached_property
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import llama
from ..models.registry import ModelBundle
from ..ops.cross_entropy import causal_lm_loss
from ..parallel.mesh import make_mesh
from ..parallel.plans import ShardingPlan, make_plan, spec_for_leaf
from .guards import apply_step_guard, validate_guard_policy
from .precision import resolve_policy
from .state import TrainState


REMAT_POLICIES = {
    # "all": recompute everything (min memory, the reference's
    # apply_activation_checkpointing semantics, 05:163-178)
    "all": jax.checkpoint_policies.nothing_saveable,
    # "dots": keep matmul outputs, recompute elementwise — the usual best
    # MFU/memory trade on TPU (matmuls are the expensive recompute)
    "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    # "attn": keep only the attention outputs (+ the flash kernel's lse
    # residual) so backward never re-runs the attention kernel; everything
    # else (projections, mlp) is recomputed. ~o(B*S*H*D) extra bytes per
    # layer vs "all" — far less than "dots"
    "attn": jax.checkpoint_policies.save_only_these_names(
        "attn_out", "flash_out", "flash_lse"),
    # "attn_mlp": additionally keep the MLP inner activation ([B,S,I] per
    # layer — the big one) so backward also skips the gate/up matmuls;
    # between "attn" and "dots" on the memory/time curve
    "attn_mlp": jax.checkpoint_policies.save_only_these_names(
        "attn_out", "flash_out", "flash_lse", "mlp_act"),
}


def _is_axes_leaf(x):
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)


def _keystr(path) -> tuple:
    return tuple(str(k) for k in path)


def _opt_state_shardings(plan: ShardingPlan, opt_shape_tree, axes_tree, param_shape_tree):
    """Shardings for optimizer state by structural match against params.

    optax state (mu/nu for adamw) mirrors the params pytree, so each opt leaf
    whose key-path suffix + shape matches a param gets that param's sharding —
    computed with the plan's *optimizer-state* rules, which for ZeRO-1 shard
    states across (dp, fsdp) even though params stay replicated (reference C3,
    ``02:87-89``). Scalars (step counts) replicate.
    """
    rules = plan.optimizer_state_rules()
    p_leaves = jax.tree_util.tree_flatten_with_path(
        axes_tree, is_leaf=_is_axes_leaf)[0]
    shape_leaves = jax.tree.leaves(param_shape_tree)
    by_path = [
        (_keystr(path), ax, sd.shape)
        for (path, ax), sd in zip(p_leaves, shape_leaves)
    ]

    def leaf_sharding(path, leaf):
        ks = _keystr(path)
        # block-quantized moments (train/precision.py Quantized containers)
        # flatten into a ``.q``/``.scale`` pair under the moment's own path:
        # the int8 payload keeps the param's shape and shards identically;
        # the per-block scales take the same spec, with the block axis
        # replicated whenever the (possibly ragged) block tiling would not
        # align with the payload's shards
        field = None
        if ks and ks[-1] in (".q", ".scale"):
            field, ks = ks[-1], ks[:-1]
        if leaf.ndim == 0:
            return NamedSharding(plan.mesh, P())
        for ppath, ax, shape in by_path:
            if len(ks) < len(ppath) or ks[-len(ppath):] != ppath:
                continue
            if field == ".scale":
                if (leaf.ndim != len(shape)
                        or tuple(leaf.shape[:-1]) != tuple(shape[:-1])):
                    continue
                spec = spec_for_leaf(plan.mesh, ax, leaf.shape, rules)
                bs = -(-shape[-1] // leaf.shape[-1])
                if bs * leaf.shape[-1] != shape[-1] and len(spec) == leaf.ndim:
                    spec = P(*spec[:-1])  # ragged tiling: replicate block axis
                return NamedSharding(plan.mesh, spec)
            if tuple(leaf.shape) == tuple(shape):
                return NamedSharding(plan.mesh, spec_for_leaf(plan.mesh, ax, leaf.shape, rules))
        return NamedSharding(plan.mesh, P())

    flat, treedef = jax.tree_util.tree_flatten_with_path(opt_shape_tree)
    return jax.tree_util.tree_unflatten(treedef, [leaf_sharding(p, l) for p, l in flat])


@dataclasses.dataclass
class Trainer:
    """Builds sharded init + train-step functions for a (model, plan) pair.

    Chapters construct one of these and then run the same loop — matching the
    reference's core design property that the loop body never changes between
    chapters (SURVEY.md section 1, L3).
    """

    bundle: ModelBundle
    optimizer: optax.GradientTransformation
    plan: Optional[ShardingPlan] = None
    grad_accum: int = 1
    remat: bool = False
    remat_policy: str = "all"  # REMAT_POLICIES key (what survives under remat)
    loss_chunks: int = 0  # >0: chunked CE from hidden states (no [B,S,V] logits)
    attn_impl: str = "auto"
    context_impl: str = "ring"  # cp>1 attention: "ring" or "ulysses"
    cp_hop_loop: str = "auto"  # ring hop loop: "auto"/"scan"/"unrolled"
    loss_fn: Callable = causal_lm_loss
    donate: bool = True
    guard_policy: str = "off"  # "off" | "skip" | "abort" (train/guards.py)
    offload_opt_state: bool = False
    offload_params: bool = False  # params live in host memory between steps
    pp_microbatches: Optional[int] = None  # pipeline microbatches (default 2*pp)
    # storage-precision policy (train/precision.py): name, '+'-composition,
    # or a PrecisionPolicy. The optimizer handed in stays the single entry
    # point — the policy wraps it here, so fp32 runs are bit-identical
    precision: Any = "fp32"
    # LoRA-param-only optimizer path (models/lora.py): the bundle must be
    # lora_bundle-wrapped; the optimizer is mask_optimizer-wrapped here so
    # base updates are ZEROED and moments exist only for the adapter
    # leaves — what makes post-training updates cheap enough that publish
    # frequency is a knob (post/loop.py), and what any LoRA finetune wants
    lora_only: bool = False

    def __post_init__(self):
        validate_guard_policy(self.guard_policy)
        self.precision = resolve_policy(self.precision)
        if self.lora_only:
            from ..models.lora import mask_optimizer

            if getattr(self.bundle, "lora_base", None) is None:
                raise ValueError(
                    "lora_only=True needs a lora_bundle-wrapped bundle "
                    "(models/lora.py) — this bundle has no adapters to "
                    "restrict the optimizer to")
            # masked BEFORE base_optimizer is captured: the checkpoint
            # fallback layout and preflight baseline must price the
            # masked (adapter-moments-only) state, not a phantom full
            # set of base moments
            self.optimizer = mask_optimizer(self.optimizer)
        # keep the unwrapped optimizer reachable: preflight prices the fp32
        # baseline with it, and checkpoint restore uses its (fp32) state
        # layout as the fallback target for pre-policy checkpoints
        self.base_optimizer = self.optimizer
        self.optimizer = self.precision.wrap(self.optimizer)
        if self.plan is None:
            self.plan = make_plan("single", make_mesh(devices=jax.devices()[:1]))
        # seq-dependent rope types (dynamic NTK, longrope) trace their
        # frequencies from max(positions)+1. Under context parallelism that
        # max runs in GSPMD-land OUTSIDE the attention shard_maps — positions
        # are a global array, so the reduction is a global (cp-collective)
        # max and every sequence shard derives the SAME frequencies; pinned
        # by the dynamic-rope cp parity test (tests/test_rope_scaling.py)
        # that replaced the old blanket rejection here.
        if getattr(self.bundle.config, "layer_windows", None) and (
                self.plan.mesh.shape.get("pp", 1) > 1):
            # cp composes (the kernels' dynamic band operand + the CP
            # wrappers' per-call window); the pipeline's manual region is
            # the one place the traced per-layer window is still unplumbed
            raise ValueError(
                "per-layer sliding-window patterns (Gemma-2 layer_windows) "
                "are not implemented under pipeline parallelism; "
                "use dp/fsdp/tp/cp plans")
        if callable(self.attn_impl) and (
                getattr(self.bundle.config, "attn_logit_softcap", None)
                is not None
                or getattr(self.bundle.config, "query_pre_attn_scalar", None)
                or ((getattr(self.bundle.config, "layer_windows", None)
                     or getattr(self.bundle.config, "sliding_window", None))
                    and not getattr(self.attn_impl, "accepts_window",
                                    False))):
            # a user-supplied callable's contract carries no softcap/scale
            # (the Trainer-built wrappers bake them in from the config), so
            # Gemma-2 extras would be SILENTLY dropped; windows (uniform or
            # per-layer) alone are fine when the callable declares
            # accepts_window (the model passes window= per call, like the
            # built wrappers)
            raise ValueError(
                "a user-supplied attn_impl callable cannot receive the "
                "configured attention extras (attn_logit_softcap / "
                "query_pre_attn_scalar / sliding_window / layer_windows) — "
                "they would be silently dropped; use attn_impl='auto' or "
                "'xla', or set accepts_window=True on a callable that "
                "takes the per-call window")
        moe_dispatch = getattr(self.bundle.config, "moe_dispatch", None)
        if moe_dispatch is not None:
            from ..models.moe import MOE_DISPATCH_MODES

            if moe_dispatch not in MOE_DISPATCH_MODES:
                raise ValueError(
                    f"unknown moe_dispatch {moe_dispatch!r}; choose from "
                    f"{MOE_DISPATCH_MODES}")
            if (moe_dispatch == "ragged"
                    and self.plan.mesh.shape.get("cp", 1) > 1):
                raise ValueError(
                    "moe_dispatch='ragged' under context parallelism is "
                    "not implemented (the sorted-group dispatch is manual "
                    "over the data axes and would need cp-aware row "
                    "layouts); use moe_dispatch='dense' or cp=1")
            if (moe_dispatch == "ragged"
                    and self.plan.mesh.shape.get("pp", 1) > 1):
                # the pipeline's manual region can't nest the data-axes
                # shard_map the ragged backend needs, and handing the
                # data-dependent sort to GSPMD instead is exactly the
                # replication/all-gather trap ch.10 documents
                raise ValueError(
                    "moe_dispatch='ragged' under pipeline parallelism is "
                    "not implemented (the sorted-group dispatch's "
                    "data-axes shard_map cannot nest in the pp-manual "
                    "region); use moe_dispatch='dense' or pp=1")
            if (moe_dispatch == "ragged"
                    and self.plan.mesh.shape.get("tp", 1) > 1):
                # tp plans shard gate/up/down on the mlp dim; the grouped
                # GEMMs would need tp-aware partial sums the shard_map does
                # not implement, and outside it the data-dependent sort
                # lands in GSPMD auto-partitioning (the same trap as above)
                raise ValueError(
                    "moe_dispatch='ragged' under tensor parallelism is "
                    "not implemented (grouped GEMMs over mlp-sharded "
                    "expert weights); use moe_dispatch='dense' or tp=1")
        if (getattr(self.bundle.config, "experts_held", None) is not None
                and self.plan.mesh.size > 1):
            # a held share is one chip's partial sum (models/moe.py
            # _ragged_dispatch): across chips the partial sums of the
            # shares would have to be exchanged, which no plan does yet
            raise ValueError(
                f"experts_held (models/moe.py: one chip's share of an "
                f"expert-parallel layer) trains on one device; plan "
                f"{self.plan.strategy!r} over {self.plan.mesh.size} devices "
                f"would need the exchange of the shares' partial sums, "
                f"which is not implemented: use make_plan('single') or a "
                f"one-device mesh, or drop experts_held")
        if self.offload_opt_state or self.offload_params:
            kinds = {m.kind for m in jax.local_devices()[0].addressable_memories()}
            if "pinned_host" not in kinds:
                raise ValueError(
                    f"host offload needs a backend with pinned_host memory "
                    f"(this one has {sorted(kinds)})")

    # ---- shapes & shardings ------------------------------------------------
    @cached_property
    def param_shapes(self):
        return jax.eval_shape(lambda: self.precision.cast_params(
            self.bundle.init(self.bundle.config, jax.random.key(0))))

    @cached_property
    def fp32_param_shapes(self):
        """Param shapes with every float leaf fp32 — the pre-policy storage
        layout, used as the baseline for preflight's byte accounting and as
        the restore target for checkpoints written by fp32 runs."""
        from .precision import cast_floats

        return jax.eval_shape(lambda: cast_floats(
            self.bundle.init(self.bundle.config, jax.random.key(0)),
            jnp.float32))

    @cached_property
    def logical_axes(self):
        return self.bundle.param_logical_axes(self.bundle.config)

    @cached_property
    def param_shardings(self):
        return self.plan.param_shardings(self.logical_axes, self.param_shapes)

    @cached_property
    def opt_shardings_device(self):
        opt_shapes = jax.eval_shape(self.optimizer.init, self.param_shapes)
        return _opt_state_shardings(self.plan, opt_shapes, self.logical_axes,
                                    self.param_shapes)

    @cached_property
    def state_shardings(self) -> TrainState:
        opt_sh = self.opt_shardings_device
        if self.offload_opt_state:
            # reference C5 (CPUOffloadPolicy, 04:85 / 05:69-72): Adam moments
            # live in pinned host memory; XLA streams them in/out around the
            # (fused) update.
            opt_sh = jax.tree.map(lambda s: s.with_memory_kind("pinned_host"), opt_sh)
        param_sh = self.param_shardings
        if self.offload_params:
            # full C5: parameter storage is pinned host too — the step fetches
            # them to HBM, computes, and the updated params stream back out
            param_sh = jax.tree.map(lambda s: s.with_memory_kind("pinned_host"),
                                    param_sh)
        return TrainState(
            step=NamedSharding(self.plan.mesh, P()),
            params=param_sh,
            opt_state=opt_sh,
            rng=NamedSharding(self.plan.mesh, P()),
        )

    @cached_property
    def fp32_state_shardings(self) -> TrainState:
        """Shardings for the PRE-policy (fp32, unwrapped-optimizer) state
        layout — the restore target when a checkpoint written by an fp32 run
        is loaded into a policy run, and preflight's byte baseline."""
        opt_shapes = jax.eval_shape(self.base_optimizer.init,
                                    self.fp32_param_shapes)
        return TrainState(
            step=NamedSharding(self.plan.mesh, P()),
            params=self.param_shardings,
            opt_state=_opt_state_shardings(self.plan, opt_shapes,
                                           self.logical_axes,
                                           self.fp32_param_shapes),
            rng=NamedSharding(self.plan.mesh, P()),
        )

    def encode_fp32_state(self, state: TrainState) -> TrainState:
        """Re-encode an fp32-layout TrainState into this trainer's precision
        policy (cast params, quantize/downcast the optimizer moments) — the
        checkpoint-restore fallback path for pre-policy checkpoints."""
        pol = self.precision

        def encode(s):
            return TrainState(step=s.step, params=pol.cast_params(s.params),
                              opt_state=pol.store_opt_state(s.opt_state),
                              rng=s.rng)

        jitted = jax.jit(encode, out_shardings=self._device_state_shardings)
        return self._place(jitted(state))

    def _output_matrix_spec(self):
        """PartitionSpec of the family's [E, V] ``output_weights`` under the
        plan, or None where it cannot be read off ONE leaf: the leaf the
        function reads (found in its jaxpr; tied heads read the embedding
        table transposed) gives its dims by logical name."""
        from ..models.registry import family_module

        mod, cfg = family_module(self.bundle.family), self.bundle.config
        jaxpr = jax.make_jaxpr(lambda p: mod.output_weights(cfg, p))(
            self.param_shapes).jaxpr
        used = {id(v) for e in jaxpr.eqns for v in e.invars}
        used.update(id(v) for v in jaxpr.outvars)
        read = [i for i, v in enumerate(jaxpr.invars) if id(v) in used]
        axes = jax.tree.leaves(self.logical_axes, is_leaf=_is_axes_leaf)
        shapes = jax.tree.leaves(self.param_shapes)
        if len(read) != 1 or sorted(axes[read[0]]) != ["embed", "vocab"]:
            return None
        leaf_axes, shape = axes[read[0]], shapes[read[0]].shape
        spec = tuple(spec_for_leaf(self.plan.mesh, leaf_axes, shape,
                                   self.plan.rules)) + (None, None)
        return P(spec[leaf_axes.index("embed")],
                 spec[leaf_axes.index("vocab")])

    @cached_property
    def head_gather(self) -> dict:
        """How the chunked loss meets an output matrix that the plan shards
        over data axes (FSDP's head): ``{"once": bool, "why": str, "spec"}``.

        ``once``: the matrix is gathered ONCE a step around the chunk loop
        and its gradient reduce-scattered once after it
        (``ops/cross_entropy.make_gathered_chunked_loss``). Otherwise the
        loss is left to GSPMD, which gathers the matrix and reduce-scatters
        its gradient once a CHUNK. The choice reads the plan, the shapes and
        the device's memory, nothing else:

        - every mesh axis in use is a data axis (no tp / cp / pp: a vocab- or
          sequence-parallel chunk body is not built inside the region), and
          the matrix is sharded on exactly one dim, over data axes;
        - the whole matrix in the compute dtype and this chip's fp32 partial
          gradient of it fit the device beside its shard of params,
          optimizer state and gradients (``preflight.priced_state_bytes``).
          A device that reports no memory (the CPU) is taken to fit.
        """
        from .preflight import device_bytes_limit, priced_state_bytes

        mesh, data = self.plan.mesh, set(self.plan.data_axes)
        if not set(self.plan.active_axes()) <= data:
            return {"once": False, "why": "a mesh axis in use is no data axis"}
        spec = self._output_matrix_spec()
        named = [e for e in (spec or ()) if e is not None]
        axes = {a for e in named for a in ((e,) if isinstance(e, str) else e)}
        if (len(named) != 1 or not axes <= data
                or all(mesh.shape[a] == 1 for a in axes)):
            return {"once": False,
                    "why": "the output matrix is not sharded over data axes"}
        cfg = self.bundle.config
        e, v = cfg.hidden_size, cfg.vocab_size
        need = e * v * (jnp.dtype(cfg.dtype).itemsize + 4)
        limit = device_bytes_limit(mesh.devices.flat[0])
        if limit is not None and priced_state_bytes(self) + need > limit:
            return {"once": False,
                    "why": f"the gathered matrix and its gradient "
                           f"({need / 2**30:.2f} GiB) do not fit the "
                           f"device's {limit / 2**30:.2f} GiB beside its "
                           f"state"}
        return {"once": True, "spec": spec,
                "why": f"output matrix sharded {spec}: one gather, one "
                       f"reduce-scatter a step"}

    def batch_shardings(self, batch_ndim: int = 2):
        ndim = batch_ndim + (1 if self.grad_accum > 1 else 0)
        if self.grad_accum > 1:
            spec = self.plan.batch_spec(batch_ndim)
            spec = P(None, *spec)  # leading microbatch axis is scanned, unsharded
            sharding = NamedSharding(self.plan.mesh, spec)
        else:
            sharding = self.plan.batch_sharding(batch_ndim)
        return {"input_ids": sharding, "labels": sharding}

    # ---- init --------------------------------------------------------------
    def _fresh_state(self, params, train_rng) -> TrainState:
        """The single definition of a step-0 TrainState (shared by random init
        and pretrained load, so the two paths can't drift). Applies the
        precision policy's param storage dtype, so both init paths land in
        policy storage."""
        params = self.precision.cast_params(params)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=self.optimizer.init(params),
                          rng=jax.random.key_data(train_rng))

    @cached_property
    def _device_state_shardings(self) -> TrainState:
        """state_shardings with default (device) memory kinds — the jit-init
        target; XLA rejects mixed-memory out_shardings on the init program, so
        offloaded storage is established by a device_put after init."""
        default_kind = jax.local_devices()[0].default_memory().kind
        return jax.tree.map(lambda s: s.with_memory_kind(default_kind),
                            self.state_shardings)

    def _place(self, state: TrainState) -> TrainState:
        if self.offload_opt_state or self.offload_params:
            return jax.device_put(state, self.state_shardings)
        return state

    @cached_property
    def init_state(self) -> Callable[[jax.Array], TrainState]:
        """Returns jitted (seed) -> TrainState, materialized *sharded* — big
        models never exist unsharded anywhere (the reference needs meta-device
        init + per-rank materialization for this, ``04:76-95``)."""

        def make(seed):
            init_rng, train_rng = jax.random.split(jax.random.key(seed))
            params = self.bundle.init(self.bundle.config, init_rng)
            return self._fresh_state(params, train_rng)

        jitted = jax.jit(make, out_shardings=self._device_state_shardings)
        return lambda seed: self._place(jitted(jnp.asarray(seed, jnp.uint32)))

    def init_state_from_params(self, params, seed: int = 0) -> TrainState:
        """Fresh optimizer state around externally-loaded (pretrained) params
        — the reference's set_model_state_dict path (``05:118-126``)."""

        def make(params, seed):
            _, train_rng = jax.random.split(jax.random.key(seed))
            return self._fresh_state(params, train_rng)

        jitted = jax.jit(make, in_shardings=(self.param_shardings, None),
                         out_shardings=self._device_state_shardings)
        return self._place(jitted(params, jnp.asarray(seed, jnp.uint32)))

    # ---- the step ----------------------------------------------------------
    @cached_property
    def step_fn(self) -> Callable:
        cfg = self.bundle.config
        apply = self.bundle.apply
        act_sharding = self.plan.activation_sharding()

        attn_impl = self.attn_impl
        # under pp the attention wrapper runs INSIDE the pp-manual region:
        # heads arrive pre-sharded as manual megatron shards (declare no tp
        # axis there), and its shard_map nests against the context mesh —
        # the one head-sharding policy for the CP and flash branches below
        under_pp = self.plan.mesh.shape["pp"] > 1
        plan_head_axis = ("tp" if not under_pp
                          and self.plan.rules.get("heads") == "tp" else None)
        window = getattr(cfg, "sliding_window", None)
        # Gemma-2 attention extras: the score-scale override and tanh logit
        # cap are baked into whichever wrapper is built below (flash, ring,
        # ulysses — all thread them into the kernel with the (1 - tanh^2)
        # backward term); per-layer windows ride each wrapper's per-call
        # window argument from the families' layer scans
        attn_scale, attn_softcap = llama.attention_extras(cfg)
        if self.plan.mesh.shape["cp"] > 1 and not callable(attn_impl):
            if self.context_impl == "ulysses":
                # all-to-all CP: heads shard over cp (x tp) during
                # attention, full sequence per device — see
                # ops/ulysses_attention.py for the ring-vs-ulysses trade.
                # Inside the pipeline only the shard_map (flash) path can
                # nest — the xla path's sharding constraints name the
                # concrete mesh, which a manual region rejects
                from ..ops.ulysses_attention import make_ulysses_attention

                if under_pp and attn_impl == "xla":
                    raise ValueError(
                        "attn_impl='xla' cannot run Ulysses inside the "
                        "pipeline: the constraint-based xla path names the "
                        "concrete mesh, which the pp-manual region rejects. "
                        "Drop --attn-impl (the flash wrapper nests), or use "
                        "--context-impl ring")
                attn_impl = make_ulysses_attention(
                    self.plan.mesh, data_axes=self.plan.data_axes,
                    head_axis=plan_head_axis, window=window,
                    scale=attn_scale, logit_softcap=attn_softcap,
                    impl="flash" if under_pp else attn_impl)
            elif self.context_impl == "ring":
                # cp carries the ring's ppermutes; batch/head axes are
                # manual too (local Pallas calls — GSPMD would gather
                # them), with heads manual only when this plan actually
                # tp-shards them. The window (uniform or per-layer) rides
                # the banded ring: every live chunk pair runs the kernel
                # with its GLOBAL offsets, dead pairs skip at the hop level
                from ..ops.ring_attention import make_ring_attention

                attn_impl = make_ring_attention(
                    self.plan.mesh, data_axes=self.plan.data_axes,
                    head_axis=plan_head_axis, hop_loop=self.cp_hop_loop,
                    window=window, scale=attn_scale,
                    logit_softcap=attn_softcap)
            else:
                raise ValueError(f"unknown context_impl "
                                 f"{self.context_impl!r}; use 'ring' or "
                                 f"'ulysses'")
        elif (not callable(attn_impl)
              and (attn_impl == "flash"
                   or (attn_impl == "auto"
                       and jax.default_backend() == "tpu"))):
            # GSPMD cannot partition the Mosaic custom call (it all-gathers
            # q/k/v and runs the full kernel on every device); wrap the flash
            # path in a batch/head-manual shard_map so the kernel stays local.
            # Inside the pipeline's pp-manual region the wrapper nests as a
            # dp/fsdp-manual sub-region (built against the context mesh);
            # heads there arrive pre-sharded as manual megatron shards, so
            # only the batch axes are declared. Skipped under "auto" off-TPU
            # (the dispatcher resolves to the partitionable XLA path).
            from ..ops.flash_attention import make_sharded_flash_attention

            wrapped = make_sharded_flash_attention(
                self.plan.mesh, batch_axes=self.plan.data_axes,
                head_axis=plan_head_axis, window=window,
                scale=attn_scale, logit_softcap=attn_softcap,
                forced=attn_impl == "flash")
            if wrapped is not None:
                attn_impl = wrapped

        logits_sharding = self.plan.logits_sharding()
        if self.remat_policy not in REMAT_POLICIES:
            raise ValueError(f"unknown remat_policy {self.remat_policy!r}; "
                             f"choose from {sorted(REMAT_POLICIES)}")
        policy = REMAT_POLICIES[self.remat_policy]

        chunked_ce = None
        if self.loss_chunks > 0 and self.plan.mesh.shape["pp"] == 1:
            from ..models.registry import family_module
            from ..ops.cross_entropy import (chunked_causal_lm_loss,
                                             validate_chunked_loss_support)

            chunk_mod = family_module(self.bundle.family)
            validate_chunked_loss_support(chunk_mod, self.bundle.family,
                                          self.loss_fn)

            if self.head_gather["once"]:
                from ..ops.cross_entropy import make_gathered_chunked_loss

                gathered = make_gathered_chunked_loss(
                    self.plan.mesh, self.head_gather["spec"],
                    self.plan.data_axes, num_chunks=self.loss_chunks)

                @jax.named_scope("loss_head")
                def chunked_ce(params, hidden, labels):
                    # cast to the compute dtype on the shard, before the gather
                    w_out = chunk_mod.output_weights(cfg, params)
                    return gathered(hidden, w_out, labels)
            else:
                @jax.named_scope("loss_head")
                def chunked_ce(params, hidden, labels):
                    w_out = chunk_mod.output_weights(cfg, params)
                    return chunked_causal_lm_loss(
                        hidden, w_out, labels, num_chunks=self.loss_chunks,
                        logits_sharding=logits_sharding)

        # every loss branch returns (loss, extras) where extras is a dict of
        # auxiliary scalar metrics with the static key set ``extra_reduce``
        # (name -> how microbatches join it: "mean", "sum" or "max")
        grad_fn = None
        extra_reduce: dict = {}
        if self.plan.mesh.shape["pp"] > 1:
            from ..parallel.pipeline import make_pipeline_value_and_grad

            # the pipeline hand-differentiates its 1F1B schedule (cotangents
            # ride the reverse ppermute), so it IS the value-and-grad
            pp_vag = make_pipeline_value_and_grad(
                self.bundle, self.plan, microbatches=self.pp_microbatches,
                remat=self.remat, remat_policy=policy, attn_impl=attn_impl,
                loss_fn=self.loss_fn, loss_chunks=self.loss_chunks)

            def grad_fn(params, mb):
                loss, grads = pp_vag(params, mb)
                return (loss, {}), grads
        elif self.bundle.apply_with_aux is not None:
            apply_aux = self.bundle.apply_with_aux
            aux_coef = getattr(cfg, "router_aux_coef", 0.0)
            from ..models.registry import family_module

            # the family's extra metrics and how microbatches join them
            extra_reduce = getattr(family_module(self.bundle.family),
                                   "TRAIN_METRICS",
                                   {"moe_dropped_frac": "mean"})
            # ragged dropless dispatch on a sharded mesh: the sorted-group
            # dispatch runs in a manual shard_map over the data axes (GSPMD
            # cannot partition the data-dependent sort the way it does the
            # dense path's static capacity einsums), built once here against
            # the plan's mesh and threaded to every layer. ep > 1 adds the
            # gather/reduce-scatter group exchange; plain dp/fsdp meshes get
            # a collective-free local body. None on single-shard meshes.
            moe_ep = None
            if (getattr(cfg, "moe_dispatch", "dense") == "ragged"
                    and self.plan.mesh.shape.get("pp", 1) == 1):
                from ..models.moe import make_ragged_ep_dispatch

                embed_axis = (self.plan.rules.get("embed")
                              if self.plan.mesh.shape.get("fsdp", 1) > 1
                              else None)
                moe_ep = make_ragged_ep_dispatch(
                    self.plan.mesh, cfg, data_axes=self.plan.data_axes,
                    embed_axis=embed_axis)

            def loss_on_microbatch(params, mb):
                out, aux, moe_metrics = apply_aux(
                    cfg, params, mb["input_ids"],
                    positions=mb.get("positions"),
                    remat=self.remat, remat_policy=policy,
                    attn_impl=attn_impl,
                    activation_sharding=act_sharding, return_metrics=True,
                    return_hidden=chunked_ce is not None, moe_ep=moe_ep)
                if chunked_ce is not None:
                    ce = chunked_ce(params, out, mb["labels"])
                else:
                    if logits_sharding is not None:
                        out = jax.lax.with_sharding_constraint(out, logits_sharding)
                    with jax.named_scope("loss_head"):
                        ce = self.loss_fn(out, mb["labels"])
                return ce + aux_coef * aux, jax.lax.stop_gradient(moe_metrics)
        elif chunked_ce is not None:
            def loss_on_microbatch(params, mb):
                hidden = apply(cfg, params, mb["input_ids"],
                               positions=mb.get("positions"),
                               remat=self.remat, remat_policy=policy,
                               attn_impl=attn_impl,
                               activation_sharding=act_sharding,
                               return_hidden=True)
                return chunked_ce(params, hidden, mb["labels"]), {}
        else:
            def loss_on_microbatch(params, mb):
                logits = apply(cfg, params, mb["input_ids"],
                               positions=mb.get("positions"),
                               remat=self.remat, remat_policy=policy,
                               attn_impl=attn_impl,
                               activation_sharding=act_sharding)
                if logits_sharding is not None:  # loss-parallel (vocab sharded)
                    logits = jax.lax.with_sharding_constraint(logits, logits_sharding)
                with jax.named_scope("loss_head"):
                    return self.loss_fn(logits, mb["labels"]), {}

        if grad_fn is None:
            grad_fn = jax.value_and_grad(loss_on_microbatch, has_aux=True)

        # deterministic NaN fault (utils/faults.py), resolved at build time so
        # the injected branch compiles into the step only when the drill is on
        from ..utils.faults import active_faults

        nan_fault_step = active_faults().nan_loss_step

        def train_step(state: TrainState, batch: dict):
            params = state.params
            opt_state = state.opt_state
            if self.grad_accum > 1:
                grad_sh = (self.plan.grad_shardings(self.logical_axes,
                                                    self.param_shapes)
                           if self.plan.zero2 else None)

                def accum(carry, mb):
                    loss_sum, extras_sum, grads_sum = carry
                    (loss, extras), grads = grad_fn(params, mb)
                    # the buffer dtype is the policy's accum_dtype — cast the
                    # microbatch grads INTO it so promotion can't silently
                    # re-widen a bf16 buffer back to fp32
                    grads_sum = jax.tree.map(
                        lambda a, g: a + g.astype(a.dtype), grads_sum, grads)
                    if grad_sh is not None:
                        # ZeRO-2: the persistent accum buffer stays sharded
                        # over the data axes (reduce-scatter per microbatch)
                        grads_sum = jax.lax.with_sharding_constraint(
                            grads_sum, grad_sh)
                    extras_sum = {
                        k: (jnp.maximum if extra_reduce[k] == "max"
                            else jnp.add)(extras_sum[k], v)
                        for k, v in extras.items()}
                    return (loss_sum + loss, extras_sum, grads_sum), None

                accum_dtype = self.precision.accum_dtype
                zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype),
                                     params)
                zero_extras = {
                    k: jnp.zeros((), jnp.float32 if how == "mean"
                                 else jnp.int32)
                    for k, how in extra_reduce.items()}
                (loss_sum, extras, grads), _ = jax.lax.scan(
                    accum, (jnp.zeros((), jnp.float32), zero_extras, zeros), batch)
                loss = loss_sum / self.grad_accum
                extras = {k: v / self.grad_accum
                          if extra_reduce[k] == "mean" else v
                          for k, v in extras.items()}
                grads = jax.tree.map(lambda g: (g / self.grad_accum).astype(jnp.float32), grads)
            else:
                (loss, extras), grads = grad_fn(params, batch)

            if nan_fault_step is not None:
                loss = jnp.where(state.step == nan_fault_step, jnp.nan, loss)

            # clipping, the update and the precision policy's casts (the
            # policy wraps the optimizer), and the gradient norm it logs
            with jax.named_scope("optimizer"):
                updates, new_opt = self.optimizer.update(grads, opt_state,
                                                         params)
                new_params = optax.apply_updates(params, updates)
                grad_norm = optax.global_norm(grads).astype(jnp.float32)
            metrics = {
                "loss": loss.astype(jnp.float32),
                "grad_norm": grad_norm,
                # counts stay int32 (moe_pairs_held), fractions are float32
                **{k: v if jnp.issubdtype(v.dtype, jnp.integer)
                   else v.astype(jnp.float32) for k, v in extras.items()},
            }
            new_state = TrainState(step=state.step + 1, params=new_params,
                                   opt_state=new_opt, rng=state.rng)
            if self.guard_policy != "off":
                # flags non-finite loss/grad-norm; under "skip" the params/
                # opt-state revert to the (donated) inputs via a predicated
                # select — all inside this compiled program, no host sync
                new_state, metrics = apply_step_guard(
                    self.guard_policy, state, new_state, metrics)
            return new_state, metrics

        metric_sharding = {"loss": self.plan.replicated(),
                           "grad_norm": self.plan.replicated(),
                           **({"notfinite": self.plan.replicated()}
                              if self.guard_policy != "off" else {}),
                           **{k: self.plan.replicated() for k in extra_reduce}}
        offloading = self.offload_params or self.offload_opt_state
        jitted = jax.jit(
            train_step,
            in_shardings=(self._device_state_shardings, self.batch_shardings()),
            out_shardings=(self._device_state_shardings, metric_sharding),
            donate_argnums=(0,) if self.donate else (),
        )
        if not offloading:
            return jitted

        # Offloaded storage is managed OUTSIDE the jit: pinned_host -> HBM
        # before the step, HBM -> pinned_host after, both async device_puts.
        # In-jit memory-kind boundaries would let XLA stream leaf-by-leaf;
        # re-verified blocked on jax 0.9 (round 4) in every variant: (a)
        # replicated/scalar outputs lose sharding on their placement
        # annotation (spmd_partitioner.cc:5743 RET_CHECK "Side-effect HLO
        # must have sharding") whether the metrics are device- or host-
        # placed; (b) tiling the metrics over the mesh instead trips
        # "Side-effect ops cannot be replicated" on the host-placed state
        # outputs; (c) a 1-device mesh sidesteps SPMD but the CPU backend
        # has no runtime for annotate_device_placement, so the path is
        # untestable off-TPU. Whole-state transfers match the reference's
        # CPU offload semantics anyway (full grad D2H + host optimizer.step,
        # 05/README.md:191-224); HBM still only holds params/opt state for
        # the duration of the step (the round trip's cost on the chip is
        # not measured on this tree).
        def step_and_offload(state, batch):
            state = jax.device_put(state, self._device_state_shardings)
            new_state, metrics = jitted(state, batch)
            return self._place(new_state), metrics

        # the compiled core, for ahead-of-time inspection (train/preflight.py)
        step_and_offload.jitted = jitted
        return step_and_offload

    # ---- accounting --------------------------------------------------------
    def tokens_per_step(self, per_device_batch: int, seq_len: int) -> int:
        """Global tokens per optimizer step (reference's ``tok_per_step``,
        ``02:167`` — world_size*batch*seq; here data-parallel size*batch*seq)."""
        return self.plan.data_parallel_size * per_device_batch * seq_len * self.grad_accum


def lower_step(trainer: "Trainer", *, global_batch: int, seq_length: int):
    """Trace and lower the train step against ABSTRACT inputs — the state
    layout a restore would target, one global batch of token ids — and
    return ``(lowered, state_avals)``. Nothing is placed on a device.

    The training loop compiles the returned program once and takes every
    step with that executable (so what it logs about the program — compile
    seconds, collectives — describes the program that runs); the preflight
    reads its memory analysis. Under host offload ``step_fn`` is a Python
    wrapper (transfers outside the jit): its compiled core is lowered, against
    the device-resident shardings it expects."""
    from ..checkpoint import abstract_train_state

    state = abstract_train_state(trainer)
    if global_batch % trainer.grad_accum:
        # a silent floor-div here would lower a SMALLER step than training
        # runs, making both the budget and the "it lowers" signal wrong
        raise ValueError(
            f"global batch {global_batch} is not divisible by "
            f"gradient accumulation {trainer.grad_accum}")
    if trainer.grad_accum > 1:  # leading scanned microbatch axis
        shape = (trainer.grad_accum, global_batch // trainer.grad_accum,
                 seq_length)
    else:
        shape = (global_batch, seq_length)
    batch = {k: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sh)
             for k, sh in trainer.batch_shardings().items()}
    step = trainer.step_fn
    if hasattr(step, "jitted"):
        step = step.jitted
        state = jax.tree.map(
            lambda sds, sh: jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                                 sharding=sh),
            state, trainer._device_state_shardings)
    return step.lower(state, batch), state  # raises on sharding bugs


# ---------------------------------------------------------------------------
# post-training: masked ragged rollout objectives (post/loop.py's update step)
# ---------------------------------------------------------------------------

POST_OBJECTIVES = ("reinforce", "distill_kl")
POST_BASELINES = ("batch", "group", "none")


def _pack_ragged(values, prompt_lens, group_sizes, s):
    """Pack per-token values of B ragged continuations into ONE [M, 1]
    buffer in group order — the ``ops/grouped_matmul.py`` row layout.

    ``values`` is [B, S] (a value per SOURCE position: the logits row
    that predicts the next token); continuation g occupies packed rows
    ``offs[g-1]:offs[g]``, reading source positions
    ``prompt_lens[g]-1 .. prompt_lens[g]-1+group_sizes[g]-1``. Rows past
    ``sum(group_sizes)`` are zeroed — exactly the tail contract
    ``grouped_matmul`` guarantees zeros (and zero grads) for, so the
    static worst-case packed width B*(S-1) carries no pad FLOPs into the
    objective. Returns (packed [M, 1], group index per row [M], valid
    mask [M])."""
    b = values.shape[0]
    m_pad = b * (s - 1)
    offs = jnp.cumsum(group_sizes)
    starts = offs - group_sizes
    rows = jnp.arange(m_pad, dtype=group_sizes.dtype)
    g = jnp.searchsorted(offs, rows, side="right").clip(0, b - 1)
    j = rows - starts[g]
    valid = rows < offs[-1]
    src = jnp.clip(prompt_lens[g] - 1 + j, 0, s - 2)
    packed = jnp.where(valid, values.reshape(-1)[g * s + src], 0.0)
    return packed[:, None], g, valid


def post_loss(logits, tokens, prompt_lens, total_lens, *,
              objective: str = "reinforce", advantages=None,
              teacher_logprobs=None, gmm_impl: str = "auto"):
    """The one post-training loss seam: REINFORCE-with-baseline and
    distillation-KL over RAGGED variable-length rollouts.

    The masked-loss contract: ``tokens`` is [B, S] (prompt + sampled
    continuation, zero-padded); position p carries gradient iff it is a
    SAMPLED continuation token — source positions
    ``prompt_lens[b]-1 <= p < total_lens[b]-1`` — so prompt tokens and
    the pad tail contribute exactly zero loss AND zero gradient (pinned
    in tests/test_post.py by differentiating w.r.t. the logits). The
    ragged packing runs through ``ops/grouped_matmul.py``: per-token
    values pack into one [M, 1] buffer with ``group_sizes`` = per-rollout
    continuation lengths, and the per-sequence scalar (the REINFORCE
    advantage, or the KL's 1/length normalizer) rides ``rhs`` [B, 1, 1] —
    one grouped GEMM broadcasts it onto its ragged token block, with the
    tail-rows-are-zero contract covering the pad.

    - ``reinforce``: loss = -(1/B) sum_b adv_b * sum_t log pi(y_t | ...)
      (advantages are data — stop-gradiented here; the baseline that
      produced them lives in ``make_post_step``).
    - ``distill_kl``: loss = (1/B) sum_b (1/|y_b|) sum_t
      KL(teacher_t || student_t) with full-vocab teacher log-probs
      aligned at source positions (``teacher_logprobs`` [B, S, V]) —
      on-policy distillation over the student's own rollouts.

    Returns (loss, extras) with static extras keys
    (``post_tokens``, ``post_logprob_mean``)."""
    from ..ops.grouped_matmul import grouped_matmul

    if objective not in POST_OBJECTIVES:
        raise ValueError(f"unknown post objective {objective!r}; choose "
                         f"from {POST_OBJECTIVES}")
    b, s, _ = logits.shape
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    group_sizes = (total_lens - prompt_lens).astype(jnp.int32)
    # token logprob at source position p (predicting tokens[:, p+1]);
    # the last column has no next token — padded zero, never packed
    tok_lp = jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None],
                                 axis=-1)[..., 0]
    tok_lp = jnp.pad(tok_lp, ((0, 0), (0, 1)))
    packed_lp, _, valid = _pack_ragged(tok_lp, prompt_lens, group_sizes, s)
    n_tok = jnp.maximum(group_sizes.sum(), 1)
    extras = {
        "post_tokens": group_sizes.sum().astype(jnp.float32),
        "post_logprob_mean": (packed_lp.sum() / n_tok).astype(jnp.float32),
    }
    if objective == "reinforce":
        if advantages is None:
            raise ValueError("objective='reinforce' needs advantages")
        adv = jax.lax.stop_gradient(advantages.astype(jnp.float32))
        out = grouped_matmul(packed_lp, adv[:, None, None], group_sizes,
                             impl=gmm_impl)
        return -out.sum() / b, extras
    # distill_kl
    if teacher_logprobs is None:
        raise ValueError("objective='distill_kl' needs teacher_logprobs "
                         "[B, S, V] aligned at source positions")
    t_lp = jax.lax.stop_gradient(teacher_logprobs.astype(jnp.float32))
    kl_tok = jnp.sum(jnp.exp(t_lp) * (t_lp - logp), axis=-1)  # [B, S]
    packed_kl, _, _ = _pack_ragged(kl_tok, prompt_lens, group_sizes, s)
    inv_len = 1.0 / jnp.maximum(group_sizes.astype(jnp.float32), 1.0)
    out = grouped_matmul(packed_kl, inv_len[:, None, None], group_sizes,
                         impl=gmm_impl)
    return out.sum() / b, extras


def make_post_step(trainer: Trainer, *, objective: str = "reinforce",
                   baseline: str = "batch", gmm_impl: str = "auto"):
    """Build the jitted POST-TRAINING step for a Trainer: one compiled
    program consuming a packed rollout batch —

        {"tokens" [B, S] int32, "prompt_lens" [B], "total_lens" [B],
         "rewards" [B] fp32, "group_ids" [B] int32 (baseline='group'),
         "teacher_logprobs" [B, S, V] fp32 (objective='distill_kl')}

    — and returning ``(new_state, metrics)`` exactly like ``step_fn``:
    same optimizer (LoRA-masked under ``lora_only``), same precision
    policy, same in-jit guard detect+revert (``--guard-policy skip`` is
    what lets a NaN update revert instead of poisoning the publishing
    engine — post/loop.py gates the publish on the ``notfinite`` flag).

    ``baseline``: "batch" subtracts the batch-mean reward; "group" is
    the GRPO form (arXiv:2402.03300) — advantages are group-relative,
    (r - mean_g) / (std_g + eps) over rollouts sharing a prompt
    (``group_ids``); "none" uses raw rewards."""
    if objective not in POST_OBJECTIVES:
        raise ValueError(f"unknown post objective {objective!r}; choose "
                         f"from {POST_OBJECTIVES}")
    if baseline not in POST_BASELINES:
        raise ValueError(f"unknown post baseline {baseline!r}; choose "
                         f"from {POST_BASELINES}")
    if trainer.plan.mesh.shape.get("pp", 1) > 1:
        raise ValueError(
            "post-training steps are not implemented under pipeline "
            "parallelism (the hand-differentiated 1F1B schedule has no "
            "ragged-objective form); use dp/fsdp/tp plans")
    if callable(trainer.attn_impl):
        raise ValueError(
            "post-training steps do not support a user-supplied callable "
            "attn_impl — silently substituting 'auto' would optimize a "
            "different model function than the one generating the "
            "rollouts; use a named attn_impl on the Trainer")
    cfg = trainer.bundle.config
    apply = trainer.bundle.apply
    act_sharding = trainer.plan.activation_sharding()
    from ..utils.faults import active_faults

    nan_fault_step = active_faults().nan_loss_step

    def advantages_of(batch):
        rewards = batch["rewards"].astype(jnp.float32)
        if baseline == "batch":
            return rewards - rewards.mean()
        if baseline == "group":
            gids = batch["group_ids"]
            b = rewards.shape[0]
            onehot = (gids[:, None] == jnp.arange(b)[None, :]) \
                .astype(jnp.float32)                       # [B, G<=B]
            cnt = jnp.maximum(onehot.sum(axis=0), 1.0)
            mean_g = (rewards @ onehot) / cnt
            var_g = ((rewards ** 2) @ onehot) / cnt - mean_g ** 2
            return ((rewards - mean_g[gids])
                    / (jnp.sqrt(jnp.maximum(var_g[gids], 0.0)) + 1e-4))
        return rewards

    def post_step(state: TrainState, batch: dict):
        adv = advantages_of(batch)

        def loss_fn(params):
            logits = apply(cfg, params, batch["tokens"],
                           remat=trainer.remat,
                           remat_policy=REMAT_POLICIES[trainer.remat_policy],
                           attn_impl=trainer.attn_impl,
                           activation_sharding=act_sharding)
            return post_loss(
                logits, batch["tokens"], batch["prompt_lens"],
                batch["total_lens"], objective=objective, advantages=adv,
                teacher_logprobs=batch.get("teacher_logprobs"),
                gmm_impl=gmm_impl)

        (loss, extras), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        if nan_fault_step is not None:
            loss = jnp.where(state.step == nan_fault_step, jnp.nan, loss)
        updates, new_opt = trainer.optimizer.update(grads, state.opt_state,
                                                    state.params)
        new_params = optax.apply_updates(state.params, updates)
        metrics = {
            "loss": loss.astype(jnp.float32),
            "grad_norm": optax.global_norm(grads).astype(jnp.float32),
            "reward_mean": batch["rewards"].mean().astype(jnp.float32),
            "advantage_std": adv.std().astype(jnp.float32),
            **{k: v.astype(jnp.float32) for k, v in extras.items()},
        }
        new_state = TrainState(step=state.step + 1, params=new_params,
                               opt_state=new_opt, rng=state.rng)
        if trainer.guard_policy != "off":
            new_state, metrics = apply_step_guard(
                trainer.guard_policy, state, new_state, metrics)
        return new_state, metrics

    metric_keys = ("loss", "grad_norm", "reward_mean", "advantage_std",
                   "post_tokens", "post_logprob_mean") + (
        ("notfinite",) if trainer.guard_policy != "off" else ())
    return jax.jit(
        post_step,
        out_shardings=(trainer._device_state_shardings,
                       {k: trainer.plan.replicated() for k in metric_keys}),
        donate_argnums=(0,) if trainer.donate else ())
