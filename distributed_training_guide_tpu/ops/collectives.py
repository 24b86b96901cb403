"""Manual-collective helpers shared by shard_map regions.

All helpers carry the same environment guard: jaxlib's non-TPU runtimes
abort on sub-fp32 collectives (observed on this container's CPU backend as
a hlo_instruction.cc CHECK "Invalid binary instruction opcode copy" on a
bf16 all-reduce), which would otherwise kill the virtual-mesh test suite.
``sub_fp32_guard`` factors that upcast-around-the-collective into one
decorator: off-TPU, bf16/fp16 operands are widened to fp32 for the
collective and narrowed back; on TPU the native low-precision collective
runs (half the ICI bytes). The guard is exact for the data-movement
collective (all-gather) and changes only the reduction arithmetic width for
psum / psum_scatter — fp32 accumulation off-TPU, never worse than native.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def sub_fp32_guard(collective):
    """Decorate a collective ``f(x, axis, **kw)`` with the non-TPU sub-fp32
    upcast: run the wrapped op in fp32 and cast back when ``x`` is bf16/fp16
    and the backend is not TPU."""

    @functools.wraps(collective)
    def guarded(x: jnp.ndarray, axis, **kw):
        if (jax.default_backend() != "tpu"
                and x.dtype in (jnp.bfloat16, jnp.float16)):
            return collective(x.astype(jnp.float32), axis, **kw).astype(x.dtype)
        return collective(x, axis, **kw)

    return guarded


@sub_fp32_guard
def psum(x: jnp.ndarray, axis) -> jnp.ndarray:
    return jax.lax.psum(x, axis)


@sub_fp32_guard
def psum_scatter(x: jnp.ndarray, axis, *, scatter_dimension: int = 0) -> jnp.ndarray:
    """``jax.lax.psum_scatter(tiled=True)`` under the shared guard."""
    return jax.lax.psum_scatter(x, axis, scatter_dimension=scatter_dimension,
                                tiled=True)


@sub_fp32_guard
def all_gather(x: jnp.ndarray, axis, *, dim: int = 0) -> jnp.ndarray:
    """Tiled all-gather along ``dim`` under the shared guard."""
    return jax.lax.all_gather(x, axis, axis=dim, tiled=True)


def gather_with_reduce_scatter_vjp(axis, dim: int):
    """All-gather along ``dim`` over ``axis`` whose backward is an explicit
    reduce-scatter. The cotangent is widened to fp32 for the reduction and
    narrowed back to the parameter dtype — the same accumulate-wide /
    store-narrow contract GSPMD applies to its grad reductions, so a
    hand-spelled gather stays bit-comparable to GSPMD's own. The gathered
    array comes out in fp32 (an exact widening), so what the caller's
    backward sums against it stays fp32 until the reduction has taken it
    (the loss head's one gather, ops/cross_entropy.py)."""

    @jax.custom_vjp
    def gather(p):
        return all_gather(p, axis, dim=dim).astype(jnp.float32)

    def fwd(p):
        return gather(p), jnp.zeros((0,), p.dtype)  # the parameter's dtype

    def bwd(like, ct):
        return (psum_scatter(ct.astype(jnp.float32), axis,
                             scatter_dimension=dim).astype(like.dtype),)

    gather.defvjp(fwd, bwd)
    return gather
