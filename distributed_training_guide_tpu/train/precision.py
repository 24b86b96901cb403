"""Precision policies: what dtype each piece of training state is STORED in.

Chapter 05's memory math is the constraint that gates the north star: with the
default policy every parameter costs 4 B of storage + 8 B of fp32 Adam moments
+ 4 B of grad-accum buffer = 16 B/param, so HBM — not FLOPs — caps the
micro-batch. The reference's DeepSpeed track exposes this as config
(``bf16``/``fp16`` blocks, ``ds_config.json``); here the same lever is a named
**precision policy** applied as an optax gradient-transformation wrapper, so
``adamw_cosine`` stays the single optimizer entry point and every strategy
(ddp/zero/fsdp/tp/pp/cp/ep) inherits the policy through the sharding-plan
machinery unchanged.

Per-parameter storage (the table 05-training-llama-405b/README.md reproduces):

    policy        params  opt state           grad accum   total
    fp32          4 B     8 B (fp32 mu+nu)    4 B          16 B
    bf16-master   2 B     4 B (bf16 mu+nu)    2 B           8 B   (2.0x)
    adam8bit      4 B     ~2.06 B (int8+scales) 4 B        ~10 B  (opt 3.9x)

- ``fp32``: the seed behavior, bit-for-bit — the wrapper is a no-op and the
  optimizer state mirrors the params in fp32.
- ``bf16-master``: params, Adam moments, and the grad-accum buffer are stored
  bf16; the optimizer UPDATE runs entirely in fp32 — params/moments are
  upcast to an fp32 master copy inside the fused step, Adam's arithmetic and
  the weight-decay/apply addition happen in fp32, and only the results are
  rounded back to bf16 storage (``optax.apply_updates`` computes ``p + u`` in
  the promoted fp32 before casting to the param dtype). The master is
  therefore materialized transiently per step by XLA rather than persisted —
  that is what makes the policy a 2x memory win instead of a loss. The trade:
  per-step updates smaller than ~2^-8 of a weight round away (no stochastic
  rounding).
- ``adam8bit`` (Dettmers et al., 8-bit Optimizers via Block-wise
  Quantization): params stay fp32 (they ARE the master copy), but both Adam
  moments are stored as int8 with one fp32 scale per block of ~128
  consecutive elements of the trailing axis. Block-wise absmax keeps the
  quantization dynamic range local, so one outlier only costs its own block
  precision. ``nu`` (the second moment, an EMA of g^2 with twice the dynamic
  range) is quantized in the sqrt domain: an element survives quantization
  in ``nu`` exactly when it survives in ``mu`` — quantizing g^2 linearly
  would zero ``nu`` for elements whose ``mu`` survives, and
  ``mu/(sqrt(0)+eps)`` explodes.

Policies compose with ``+`` (e.g. ``bf16-master+adam8bit``: bf16 params +
int8 moments), and the grad-accum-buffer dtype rides along
(``accum_dtype``). The ZeRO sharding of the quantized leaves (int8 payload
sharded exactly like the moment it encodes, per-block scales alongside their
blocks) is handled by ``train/step.py``'s optimizer-state sharding match.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax


class Quantized(NamedTuple):
    """Block-quantized tensor: int8 payload + one fp32 scale per block.

    ``q`` keeps the SOURCE tensor's shape (so sharding plans can lay it out
    exactly like the moment it encodes); blocks tile the trailing axis.
    ``scale`` has shape ``q.shape[:-1] + (nblocks,)``. The block size is
    recoverable from the two shapes (``ceil(d / nblocks)``), so the container
    needs no static metadata and round-trips through Orbax like any pytree.
    """

    q: jax.Array      # int8, same shape as the dequantized tensor
    scale: jax.Array  # fp32, trailing axis = number of blocks


def block_geometry(d: int, block_size: int) -> tuple[int, int]:
    """(nblocks, effective block size) for a trailing axis of length ``d``.

    The effective size is the fixed point of ``ceil(d / ceil(d / bs))`` so
    that dequantize can re-derive it from shapes alone.
    """
    nblocks = -(-d // max(block_size, 1))
    bs = -(-d // nblocks)
    return -(-d // bs), bs


def quantize_blockwise(x: jax.Array, block_size: int = 128,
                       sqrt_domain: bool = False) -> Any:
    """Absmax int8 quantization per block of the trailing axis.

    ``sqrt_domain=True`` quantizes ``sqrt(x)`` (for non-negative tensors like
    Adam's ``nu``): halving the exponent range aligns the survival threshold
    with the linear quantization of ``mu``. 0-d tensors pass through in fp32
    (nothing to block over).
    """
    x = x.astype(jnp.float32)
    if x.ndim == 0:
        return x
    if sqrt_domain:
        x = jnp.sqrt(x)
    d = x.shape[-1]
    nblocks, bs = block_geometry(d, block_size)
    pad = nblocks * bs - d
    xb = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = xb.reshape(*x.shape[:-1], nblocks, bs)
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scale = jnp.where(amax > 0, amax, 1.0) / 127.0
    q = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127).astype(jnp.int8)
    q = q.reshape(*x.shape[:-1], nblocks * bs)[..., :d]
    return Quantized(q=q, scale=scale.astype(jnp.float32))


def dequantize_blockwise(qt: Quantized, sqrt_domain: bool = False,
                         dtype: Any = jnp.float32) -> jax.Array:
    d = qt.q.shape[-1]
    bs = -(-d // qt.scale.shape[-1])
    scale = jnp.repeat(qt.scale, bs, axis=-1)[..., :d]
    x = qt.q.astype(jnp.float32) * scale
    if sqrt_domain:
        x = x * x
    return x.astype(dtype)


def cast_floats(tree, dtype):
    """Cast inexact (float) leaves to ``dtype``; integer leaves (Adam's step
    count, schedule counters) pass through untouched."""
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.inexact)
        else x, tree)


def _is_adam(node) -> bool:
    return isinstance(node, optax.ScaleByAdamState)


def _is_quantized(node) -> bool:
    return isinstance(node, Quantized)


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """One storage policy for the whole TrainState.

    ``param_dtype=None`` means "inherit the model's storage dtype" (so the
    default policy composes with the existing ``--param-dtype`` lever instead
    of silently overriding it).
    """

    name: str
    param_dtype: Optional[Any] = None    # TrainState param storage dtype
    moment_dtype: Any = jnp.float32      # stored dtype of optimizer moments
    quantize_moments: bool = False       # int8 block quantization of mu/nu
    block_size: int = 128
    accum_dtype: Any = jnp.float32       # grad-accumulation buffer dtype

    # ---- classification ----------------------------------------------------
    @property
    def is_noop(self) -> bool:
        """True when the policy changes nothing (the seed fp32 behavior)."""
        return (self.param_dtype is None and not self.quantize_moments
                and self.moment_dtype == jnp.float32)

    # ---- params ------------------------------------------------------------
    def cast_params(self, params):
        if self.param_dtype is None:
            return params
        return cast_floats(params, self.param_dtype)

    # ---- optimizer state storage <-> fp32 compute form ---------------------
    def store_opt_state(self, state):
        """fp32 optimizer state -> storage form (quantized / downcast)."""
        def store(node):
            if _is_adam(node):
                if self.quantize_moments:
                    bs = self.block_size
                    mu = jax.tree.map(
                        lambda x: quantize_blockwise(x, bs), node.mu)
                    nu = jax.tree.map(
                        lambda x: quantize_blockwise(x, bs, sqrt_domain=True),
                        node.nu)
                else:
                    mu = cast_floats(node.mu, self.moment_dtype)
                    nu = cast_floats(node.nu, self.moment_dtype)
                return node._replace(mu=mu, nu=nu)
            return cast_floats(node, self.moment_dtype)

        return jax.tree.map(store, state, is_leaf=_is_adam)

    def load_opt_state(self, state):
        """Storage form -> the fp32 state the wrapped optimizer computes in."""
        def load_moment(tree, sqrt_domain):
            return jax.tree.map(
                lambda x: (dequantize_blockwise(x, sqrt_domain=sqrt_domain)
                           if _is_quantized(x) else cast_floats(x, jnp.float32)),
                tree, is_leaf=_is_quantized)

        def load(node):
            if _is_adam(node):
                return node._replace(mu=load_moment(node.mu, False),
                                     nu=load_moment(node.nu, True))
            return cast_floats(node, jnp.float32)

        return jax.tree.map(load, state, is_leaf=_is_adam)

    # ---- the optax wrapper -------------------------------------------------
    def wrap(self, tx: optax.GradientTransformation) -> optax.GradientTransformation:
        """Wrap ``tx`` so its state is STORED under this policy while its
        update math runs in fp32 (the transient master copy: params, grads
        and state are upcast inside the fused step, ``tx`` computes in fp32,
        and results are rounded back to storage dtypes on the way out)."""
        if self.is_noop:
            return tx

        def init_fn(params):
            state = tx.init(cast_floats(params, jnp.float32))
            if self.quantize_moments and not any(
                    _is_adam(n) for n in
                    jax.tree.leaves(state, is_leaf=_is_adam)):
                raise ValueError(
                    f"precision policy {self.name!r} quantizes Adam moments "
                    f"but the optimizer has no ScaleByAdamState (use adamw, "
                    f"or drop the adam8bit policy)")
            return self.store_opt_state(state)

        def update_fn(updates, state, params=None):
            g32 = cast_floats(updates, jnp.float32)
            p32 = None if params is None else cast_floats(params, jnp.float32)
            u, new_state = tx.update(g32, self.load_opt_state(state), p32)
            # u stays fp32: optax.apply_updates computes p + u in the
            # promoted fp32 and casts to the param storage dtype after —
            # the fp32-master write-back for bf16 params
            return u, self.store_opt_state(new_state)

        return optax.GradientTransformation(init_fn, update_fn)


POLICIES: dict[str, PrecisionPolicy] = {
    "fp32": PrecisionPolicy(name="fp32"),
    "bf16-master": PrecisionPolicy(
        name="bf16-master", param_dtype=jnp.bfloat16,
        moment_dtype=jnp.bfloat16, accum_dtype=jnp.bfloat16),
    "adam8bit": PrecisionPolicy(name="adam8bit", quantize_moments=True),
}


def resolve_policy(spec) -> PrecisionPolicy:
    """Name, ``+``-composition of names, or an explicit PrecisionPolicy.

    ``bf16-master+adam8bit`` composes storage dtypes and quantization: bf16
    params/accum with int8 moments — the deepest memory rung.
    """
    if isinstance(spec, PrecisionPolicy):
        return spec
    if spec is None:
        return POLICIES["fp32"]
    parts = [p.strip() for p in str(spec).split("+") if p.strip()]
    unknown = [p for p in parts if p not in POLICIES]
    if unknown or not parts:
        raise ValueError(
            f"unknown precision policy {spec!r}; use one of "
            f"{sorted(POLICIES)} or a '+' composition of them")
    merged = POLICIES[parts[0]]
    for name in parts[1:]:
        nxt = POLICIES[name]
        merged = PrecisionPolicy(
            name="+".join(parts),
            param_dtype=nxt.param_dtype or merged.param_dtype,
            moment_dtype=(nxt.moment_dtype
                          if nxt.moment_dtype != jnp.float32
                          else merged.moment_dtype),
            quantize_moments=merged.quantize_moments or nxt.quantize_moments,
            block_size=merged.block_size,
            accum_dtype=(nxt.accum_dtype if nxt.accum_dtype != jnp.float32
                         else merged.accum_dtype),
        )
    return merged
