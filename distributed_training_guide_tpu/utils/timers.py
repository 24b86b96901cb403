"""Phase timers with honest device synchronization.

Capability parity with the reference's ``LocalTimer``
(``01-single-gpu/train_llm.py:260-286``): a context manager that measures
wall-time of a phase, forcing a device sync on entry and exit so the
measurement is not polluted by async dispatch. On TPU the sync primitive is
``jax.block_until_ready`` on the arrays the phase produced (CUDA's
``torch.cuda.synchronize`` has no direct analogue — JAX dispatch is async per
array, so we block on outputs rather than a global device fence).
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import jax

from .trace import span


def device_sync() -> None:
    """Device fence for ``LocalTimer(sync_fn=...)`` — the reference C17
    semantics (``01-single-gpu/train_llm.py:260-286``, cuda.synchronize).

    Enqueues a trivial computation on every local device and blocks on it
    with ``jax.block_until_ready``: the runtime executes programs in launch
    order per device, so the fence completes only after all previously
    dispatched work. ``--timer-sync`` puts it on both edges of every phase
    timer."""
    import jax.numpy as jnp

    jax.block_until_ready([jnp.zeros((), jnp.int32, device=d) + 1
                           for d in jax.local_devices()])


def _default_sync() -> None:
    # A no-op: JAX has no global device fence (dispatch queues are
    # per-array), so a timed region is honest when it ends by waiting for
    # its own outputs. The training loop's ``float(metrics["loss"])`` inside
    # the step timer is that wait, exactly like the reference's
    # ``loss.item()`` (``02-distributed-data-parallel/train_llm.py:163``).
    # Callers that time a region with no such read pass ``device_sync``.
    return None


class LocalTimer:
    """Measures average wall-time of a repeated phase (data/forward/step/...).

    Usage::

        timers = {k: LocalTimer(name=f"train.{k}") for k in ["data", "step"]}
        with timers["step"](step=n):
            loss = train_step(state, batch)   # async dispatch
            # sync happens on __exit__

    With a ``name`` the phase is also the host span ``dtg.<name>``
    (``utils/trace.py``) over exactly the timed interval, so a profiler
    session sees what the timer measured; calling the timer binds the span's
    arguments for the next entry.
    """

    def __init__(self, sync_fn: Optional[Callable[[], None]] = None,
                 name: Optional[str] = None):
        self.synchronize = sync_fn or _default_sync
        self.name = name
        self.measurements: list[float] = []
        self.start_time: Optional[float] = None
        self._span_args: dict = {}
        self._span = None

    def __call__(self, **span_args) -> "LocalTimer":
        self._span_args = span_args
        return self

    def __enter__(self) -> "LocalTimer":
        self.synchronize()
        if self.name is not None:
            self._span = span(self.name, **self._span_args)
            self._span.__enter__()
        self.start_time = time.perf_counter()
        return self

    def __exit__(self, exc_type, value, traceback) -> None:
        if traceback is None:
            self.synchronize()
            self.measurements.append(time.perf_counter() - self.start_time)
        if self._span is not None:
            self._span.__exit__(exc_type, value, traceback)
            self._span = None
        self.start_time = None

    def set_metadata(self, **stats) -> None:
        """Statistics known only inside the phase, onto its open span."""
        if self._span is not None:
            self._span.set_metadata(**stats)

    def avg_elapsed_ms(self) -> float:
        if not self.measurements:
            return 0.0
        return 1000.0 * (sum(self.measurements) / len(self.measurements))

    def reset(self) -> None:
        self.measurements = []
        self.start_time = None
