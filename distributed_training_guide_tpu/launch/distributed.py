"""Multi-host runtime initialization.

The reference's process bootstrap is torchrun + ``dist.init_process_group``
reading ``RANK``/``WORLD_SIZE``/``MASTER_ADDR`` (``02-distributed-data-parallel/
train_llm.py:36-41``, ``03-job-launchers/README.md``). JAX is one process per
*host*; on TPU pods the runtime discovers coordinator/process-id/process-count
from the TPU metadata, so ``jax.distributed.initialize()`` needs no arguments.
For CPU/GPU clusters (or explicit control) we honor the same env contract the
reference uses, mapped to JAX names.

Env contract (all optional on TPU pods):
    COORDINATOR_ADDRESS (or MASTER_ADDR:MASTER_PORT)
    NUM_PROCESSES       (or WORLD_SIZE)
    PROCESS_ID          (or RANK)
"""
from __future__ import annotations

import logging
import os

import jax

LOGGER = logging.getLogger(__name__)


def maybe_initialize_distributed() -> None:
    """Idempotent; no-op for single-process runs.

    Three cases, in order: an explicit env contract (coordinator, process
    count and id given — JAX then looks nothing up); a TPU pod of SEVERAL
    worker hosts (zero-argument auto-discovery); neither — one process,
    which on a TPU host drives all its local chips and needs no distributed
    runtime (a single-host slice sets ``TPU_WORKER_HOSTNAMES`` too, to one
    name: that is not a pod). A rendezvous that was asked for and fails
    raises; the run does not carry on as a single process.

    NB: must not touch ``jax.devices()``/``jax.process_count()`` before
    deciding — querying them initializes the local backend, after which
    ``jax.distributed.initialize`` raises.
    """
    if jax.distributed.is_initialized():
        return

    coord = os.environ.get("COORDINATOR_ADDRESS")
    if coord is None and os.environ.get("MASTER_ADDR"):
        coord = f"{os.environ['MASTER_ADDR']}:{os.environ.get('MASTER_PORT', '8476')}"
    nproc = os.environ.get("NUM_PROCESSES") or os.environ.get("WORLD_SIZE")
    pid = os.environ.get("PROCESS_ID") or os.environ.get("RANK")
    workers = [h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
               if h.strip()]

    if coord and nproc is not None and pid is not None:
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=int(nproc),
                                   process_id=int(pid))
        LOGGER.info(f"distributed: initialized process {pid}/{nproc} via {coord}")
    elif len(workers) > 1 or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
        jax.distributed.initialize()  # TPU pod auto-discovery
        LOGGER.info(
            f"distributed: TPU pod auto-init, process "
            f"{jax.process_index()}/{jax.process_count()}")
