"""What the kernels in ``ops/`` share: how ``interpret`` is resolved, a log
line that says once which path a program took, the record of which attention
implementations a program traced, and the one in-kernel idiom
the chip's compiler forced on the int8 matmul (``lane_column``).

A Pallas kernel runs compiled (Mosaic) on a TPU backend and interpreted
everywhere else — the interpreter is how the CPU test suite checks kernel
numerics. On the chip nothing runs interpreted: a kernel asked to interpret
while ``jax.default_backend() == "tpu"`` is an error, not a slow stand-in.
"""
from __future__ import annotations

import contextlib
import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp

LOGGER = logging.getLogger(__name__)


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> interpret exactly when the backend is not a TPU; an
    explicit ``True`` on a TPU backend raises."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise RuntimeError(
            "a Pallas kernel was asked to run in interpret mode on a TPU "
            "backend; on the chip every kernel runs compiled")
    return bool(interpret)


@functools.lru_cache(maxsize=None)
def note_choice(op: str, impl: str, reason: str) -> None:
    """Log ``op -> impl (reason)`` once per process for each distinct
    triple, so a run's log says which implementation an ``auto`` dispatch
    took and why, without a line per traced call."""
    LOGGER.info(f"dispatch: {op} -> {impl} ({reason})")


# impl -> reason in the order first traced, while a record_attention()
# block is open; None outside one
_ATTENTION_RECORD: Optional[dict] = None


def note_attention(impl: str, reason: str) -> None:
    """Every attention path calls this where it commits to a program; inside
    a ``record_attention()`` block the call is recorded (first reason per
    implementation), so what a run reports is what its step traced, not what
    its flags were expected to resolve to."""
    if _ATTENTION_RECORD is not None:
        _ATTENTION_RECORD.setdefault(impl, reason)


@contextlib.contextmanager
def record_attention():
    """Collect which attention implementations are traced inside the block
    (wrap the trace of ONE program: a jit already traced records nothing).
    Yields the record; ``describe_attention`` turns it into a report."""
    global _ATTENTION_RECORD
    outer, _ATTENTION_RECORD = _ATTENTION_RECORD, {}
    try:
        yield _ATTENTION_RECORD
    finally:
        _ATTENTION_RECORD = outer


def describe_attention(record: dict) -> tuple[str, str]:
    """``(impl, reason)`` of a ``record_attention()`` record; several
    implementations join with ``+`` (an ``auto`` run whose shapes split
    between the kernel and a fallback says so)."""
    if not record:
        return "none", "no attention call was traced"
    return "+".join(record), "; ".join(record.values())


def lane_column(table, index):
    """Column ``index`` (a traced scalar) of a ``[rows, cols]`` table held in
    VMEM, as a ``[rows, 1]`` vector — for use INSIDE a kernel. A one-lane
    block of the table is not a shape Mosaic tiles, so the table is DMA'd
    whole and the column picked with a masked lane reduction, which needs
    no dynamic lane slice. (The per-block scale of an int8 weight.)"""
    lane = jax.lax.broadcasted_iota(jnp.int32, table.shape, 1)
    return jnp.sum(jnp.where(lane == index, table, 0.0), axis=1,
                   keepdims=True)
