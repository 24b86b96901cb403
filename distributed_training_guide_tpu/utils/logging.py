"""Process-prefixed logging.

Parity with the reference's rank-prefixed stdlib logging
(``02-distributed-data-parallel/train_llm.py:43-46``). JAX is one process per
*host* (not per chip), so the prefix is ``jax.process_index()``.
"""
from __future__ import annotations

import json
import logging


def init_logging(process_index: int = 0, process_count: int = 1, level=logging.INFO) -> None:
    logging.basicConfig(
        format=f"[%(asctime)s] [proc {process_index}/{process_count}] %(levelname)s:%(message)s",
        level=level,
        force=True,
    )


def print_device_line(impl_key: str, impl: tuple[str, str],
                      cache_dir: str) -> None:
    """The one JSON line an entry point prints at start-up: the device JAX
    runs on (as ``jax.devices()`` reports it), which implementation the
    flags resolved to under ``impl_key`` ("attention" for the trainer,
    "attend" for the server) and why, and the compile cache in use. What
    reads a run's output (``chip_smoke.py``) holds the run to this line."""
    import jax

    dev = jax.devices()[0]
    print(json.dumps({
        "device": {"platform": dev.platform, "device_kind": dev.device_kind,
                   "count": len(jax.devices())},
        impl_key: {"impl": impl[0], "reason": impl[1]},
        "compile_cache": cache_dir}), flush=True)


def log_dict(logger: logging.Logger, info: dict) -> None:
    logger.info({k: (round(v, 6) if isinstance(v, float) else v) for k, v in info.items()})
