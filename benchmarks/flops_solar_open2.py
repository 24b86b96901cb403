"""Operations and bytes that the ``solar_open2`` family's two KDA
computations and its GQA layers' paged attend REQUIRE, from shapes and the
run's counters alone (``flops.py``'s rule: nothing a kernel happens to
execute, pad or re-read is counted).

The recurrence of one token in one head, on a state ``S [d_k, d_v]``: the
decay (1 flop an element), ``k^T S'`` (2), the rank-one write (2), ``S^T q``
(2) = ``7 d_k d_v`` flops, in float32.
"""
from __future__ import annotations

from benchmarks.weights_solar_open2 import kda_sizes as _kda_sizes

STATE_BYTES = 4     # the state class is float32: a constant of the yardstick,
#                     NOT read from the configuration, so a state stored
#                     narrower reads over 100% (113% in bfloat16 on the chip)
ROW_BYTES = 4       # the recurrence's rows arrive in float32


def kda_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - len(cfg["gqa_layers"])


def kda_sizes(cfg: dict) -> tuple:
    """``(heads, d_k = d_v)``."""
    return _kda_sizes(cfg)[:2]


def state_bytes(cfg: dict) -> int:
    """One sequence's state in one KDA layer (4,194,304 B as published)."""
    heads, d = kda_sizes(cfg)
    return heads * d * d * STATE_BYTES


def token_flops(cfg: dict) -> float:
    """The recurrence for one token in one layer (7.34 MFLOP as published)."""
    heads, d = kda_sizes(cfg)
    return 7.0 * heads * d * d


def row_bytes(cfg: dict) -> int:
    """One token's q, k, v, g and o rows and its beta, one layer."""
    heads, d = kda_sizes(cfg)
    return (5 * heads * d + heads) * ROW_BYTES


def kda_step(cfg: dict, slot_steps: int) -> dict:
    """Decode steps' recurrence, every KDA layer: each live slot's state is
    read once and written once a layer a step, its rows go in and out;
    ``slot_steps``: live slots summed over the steps."""
    layers = kda_layers(cfg)
    return {"flops": token_flops(cfg) * layers * slot_steps,
            "bytes": float(2 * state_bytes(cfg) + row_bytes(cfg))
            * layers * slot_steps}


def kda_chunk(cfg: dict, tokens: int, chunks: int) -> dict:
    """Prefill chunks' recurrence, every KDA layer: ``tokens`` tokens'
    required flops (a blocked form executes more), their rows, and each
    chunk's state in once and out once."""
    layers = kda_layers(cfg)
    return {"flops": token_flops(cfg) * layers * tokens,
            "bytes": float(row_bytes(cfg)) * layers * tokens
            + float(2 * state_bytes(cfg)) * layers * chunks}


def gqa_attend(cfg: dict, context_tokens: int, slot_steps: int,
               kv_bytes: int = 2) -> dict:
    """Decode steps' attention over the paged pool, over the GQA layers alone
    (``flops.paged_attend`` multiplies by ``num_hidden_layers``): every live
    context token's k and v is read once (8 kv heads x 128 x 2 x 2 B = 4,096 B
    a token a layer as published), each slot's query and output rows move
    once; FLOPs are 4 per (query head, key, head_dim). ``context_tokens`` and
    ``slot_steps``: live context and live slots summed over the steps."""
    d, hq, hkv = (cfg["head_dim"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    layers = len(cfg["gqa_layers"])
    return {"flops": 4.0 * layers * hq * d * context_tokens,
            "bytes": float(2 * hkv * d * kv_bytes) * layers * context_tokens
            + 2 * 2 * layers * slot_steps * hq * d}
