"""Phase timers.

Capability parity with the reference's ``LocalTimer``
(``01-single-gpu/train_llm.py:260-286``): a context manager that measures
wall-time of a phase. The reference forces ``torch.cuda.synchronize`` on
entry and exit; JAX has no global device fence (dispatch queues are
per-array), so a timed region is honest when it ends by waiting for its own
outputs. The training loop's ``float(metrics["loss"])`` inside the step
timer is that wait, exactly like the reference's ``loss.item()``
(``02-distributed-data-parallel/train_llm.py:163``).
"""
from __future__ import annotations

import time
from typing import Optional

from .trace import span


class LocalTimer:
    """Measures average wall-time of a repeated phase (data/forward/step/...).

    Usage::

        timers = {k: LocalTimer(name=f"train.{k}") for k in ["data", "step"]}
        with timers["step"](step=n):
            state, metrics = train_step(state, batch)   # async dispatch
            loss = float(metrics["loss"])     # the wait that ends the phase

    With a ``name`` the phase is also the host span ``dtg.<name>``
    (``utils/trace.py``) over exactly the timed interval, so a profiler
    session sees what the timer measured; calling the timer binds the span's
    arguments for the next entry.
    """

    def __init__(self, name: Optional[str] = None):
        self.name = name
        self.measurements: list[float] = []
        self.start_time: Optional[float] = None
        self._span_args: dict = {}
        self._span = None

    def __call__(self, **span_args) -> "LocalTimer":
        self._span_args = span_args
        return self

    def __enter__(self) -> "LocalTimer":
        if self.name is not None:
            self._span = span(self.name, **self._span_args)
            self._span.__enter__()
        self.start_time = time.perf_counter()
        return self

    def __exit__(self, exc_type, value, traceback) -> None:
        if traceback is None:
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
