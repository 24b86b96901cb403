"""Operations and bytes that the ``brumby`` family's two power-retention
computations REQUIRE, from shapes and the run's counters alone (``flops.py``'s
rule: nothing a kernel happens to execute, pad or re-read is counted), the
same whatever implements them.

The state is counted AS PUBLISHED: ``D = d (d + 1) / 2`` rows of the exact
symmetric feature map (8,256 at ``d`` = 128) and the normaliser ``z [D]``, in
float32. The program stores a blocked layout of 9,216 rows and the normaliser
as a ``[d, d]`` matrix (``ops/retention.py``): those 12% count AGAINST the
kernels, as do the passes a float32 product takes on the MXU (``peaks.json``
holds the bfloat16 rate).

One token on one kv head: the update ``S += phi(k) v^T``, ``z += phi(k)`` is
``2 D (d + 1)`` flops; one query head's read-out ``S^T phi(q)``, ``z .
phi(q)`` the same. A causal pair inside a chunk in the ``(q . k)^2`` form is
``4 d`` flops a query head (the score and the value's sum).
"""
from __future__ import annotations

STATE_BYTES = 4     # the state class is float32: a constant of the yardstick,
#                     NOT read from the configuration (flops_jamba.py's note:
#                     it does not guard the state's precision either)
ROW_BYTES = 2       # q, k, v rows arrive in the compute dtype
OUT_BYTES = 4       # the normalised output leaves in float32


def feature_rows(cfg: dict) -> int:
    d = cfg["head_dim"]
    return d * (d + 1) // 2


def state_bytes(cfg: dict) -> int:
    """One sequence's ``S`` and ``z`` in one layer (34,080,768 B as
    published: 8 x 8,256 x 128 + 8 x 8,256 floats)."""
    return (cfg["num_key_value_heads"] * feature_rows(cfg)
            * (cfg["head_dim"] + 1) * STATE_BYTES)


def head_flops(cfg: dict) -> float:
    """One head's update or read-out of one token (2,130,048 as published)."""
    return 2.0 * feature_rows(cfg) * (cfg["head_dim"] + 1)


def row_bytes(cfg: dict) -> int:
    """One token's q, k, v rows in and its output rows out, one layer."""
    d, hq, hkv = (cfg["head_dim"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    return (hq + 2 * hkv) * d * ROW_BYTES + hq * d * OUT_BYTES


def retention_step(cfg: dict, slot_steps: int) -> dict:
    """Decode steps' recurrence, every layer: each live slot's state is read
    once and written once a layer a step, one update a kv head and one
    read-out a query head; ``slot_steps``: live slots summed over the
    steps."""
    layers = cfg["num_hidden_layers"]
    heads = cfg["num_attention_heads"] + cfg["num_key_value_heads"]
    return {"flops": head_flops(cfg) * heads * layers * slot_steps,
            "bytes": float(2 * state_bytes(cfg) + row_bytes(cfg))
            * layers * slot_steps}


def retention_chunk(cfg: dict, chunks: list) -> dict:
    """Prefill chunks' retention, every layer. ``chunks``: ``(real tokens,
    carried)`` a chunk, ``carried`` false for a sequence's first chunk, whose
    state is zero: it is neither read out nor read in. Per real token the
    update a kv head, the carried state's read-out a query head where there
    is one, ``4 d`` a query head a causal pair of the chunk; the rows; the
    state out once a chunk, and in once where it is carried."""
    layers, d = cfg["num_hidden_layers"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    flops = nbytes = 0.0
    for tokens, carried in chunks:
        flops += head_flops(cfg) * tokens * (hkv + (hq if carried else 0))
        flops += 4.0 * d * hq * tokens * (tokens + 1) / 2
        nbytes += float(row_bytes(cfg)) * tokens
        nbytes += float(state_bytes(cfg)) * (2 if carried else 1)
    return {"flops": flops * layers, "bytes": nbytes * layers}
