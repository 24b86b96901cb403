"""Device memory statistics.

Parity with the reference's ``get_mem_stats`` (``01-single-gpu/train_llm.py:248-257``),
which reports current/peak allocated+reserved GB from the CUDA caching
allocator. On TPU the runtime exposes ``Device.memory_stats()`` and a failure
to read it is an error. The CPU backend reports nothing, and only there the
fields are zeros so the log dict keeps its keys.
"""
from __future__ import annotations

from typing import Optional

import jax


def get_mem_stats(device: Optional[jax.Device] = None) -> dict:
    device = device or jax.local_devices()[0]
    stats = device.memory_stats()
    if stats is None:
        if device.platform != "cpu":
            raise RuntimeError(
                f"{device} ({device.platform}) returned no memory_stats()")
        stats = {}
    gb = 1e-9
    return {
        "total_gb": gb * stats.get("bytes_limit", 0),
        "curr_alloc_gb": gb * stats.get("bytes_in_use", 0),
        "peak_alloc_gb": gb * stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0)),
        "curr_resv_gb": gb * stats.get("bytes_reserved", 0),
        "peak_resv_gb": gb * stats.get("peak_bytes_reserved", 0),
    }
