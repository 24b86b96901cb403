"""distributed_training_guide_tpu — a TPU-native distributed-training framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of
LambdaLabsML/distributed-training-guide (mounted read-only at /root/reference).
The reference is a chapter-per-directory pedagogical guide built on
torch + NCCL; this package provides the same capability surface the
TPU-native way:

- one ``jax.sharding.Mesh`` + NamedSharding plans instead of wrapper classes
  (DDP / ZeRO-1 / FSDP / TP / SP / 2D are *sharding plans*, not engines)
- a single jitted train step instead of eager autograd hooks
- XLA collectives over ICI/DCN instead of NCCL (reference C11,
  SURVEY.md section 2)
- Orbax/TensorStore sharded checkpoints instead of torch DCP
- a Pallas flash-attention kernel instead of the flash-attn CUDA wheel

Package layout:
    models/      pure-JAX model zoo (GPT-2, Llama) with logical-axis metadata
    ops/         compute kernels: XLA reference attention + Pallas flash attention
    parallel/    mesh construction + sharding plans + grad accumulation + remat
    data/        data pipeline (HF-compatible + hermetic synthetic), per-host sharding
    train/       train-state, optimizer, jitted step builder, config-driven engine
    checkpoint/  Orbax sharded checkpoint + state.json + RNG persistence
    utils/       timers, memory stats, MFU, rank-ordered guards, logging
    launch/      pod launchers, elastic supervisor, error capture
    monitor/     cluster monitor (top-cluster equivalent)
    csrc/        native C++ components (token-shard data loader)
"""

__version__ = "0.1.0"
