"""Operations and bytes that the ``jamba`` family's two state-space
computations and its attention layers' paged attend REQUIRE, from shapes and
the run's counters alone (``flops.py``'s rule: nothing a kernel happens to
execute, pad or re-read is counted).

The recurrence of one token on one (channel, state) pair: ``Delta A`` (1
flop), the decay's product with ``h`` (1; the ``exp`` itself is counted as
one more), ``Delta x`` times ``B`` (2), the sum into ``h`` (1), ``C h`` and
its sum over the states (2), the skip's share (1) = 9 flops, in float32.
"""
from __future__ import annotations

from benchmarks.weights_jamba import channels, layers_of

STATE_BYTES = 4     # the state class is float32: a constant of the yardstick,
#                     NOT read from the configuration. It does NOT guard the
#                     state's precision: a state stored in bfloat16 read
#                     103.5% of ``ssm_step_roofline`` on the chip (PR 48),
#                     under the 105% at which a run is refused; tier-1 and
#                     ``ops/ssm.ssm_step``'s refusal of a narrower pool do
ROW_BYTES = 4       # the recurrence's rows arrive in float32
PAIR_FLOPS = 9.0    # a (channel, state) pair a token (module docstring)


def mamba_layers(cfg: dict) -> int:
    return len(layers_of(cfg)["mamba"])


def attention_layers(cfg: dict) -> int:
    return len(layers_of(cfg)["attn"])


def state_bytes(cfg: dict) -> int:
    """One sequence's state in one Mamba layer (327,680 B as published)."""
    return channels(cfg) * cfg["mamba_d_state"] * STATE_BYTES


def token_flops(cfg: dict) -> float:
    """The recurrence for one token in one layer (737,280 as published)."""
    return PAIR_FLOPS * channels(cfg) * cfg["mamba_d_state"]


def row_bytes(cfg: dict) -> int:
    """One token's x, Delta and y rows and its B and C, one layer."""
    return (3 * channels(cfg) + 2 * cfg["mamba_d_state"]) * ROW_BYTES


def ssm_step(cfg: dict, slot_steps: int) -> dict:
    """Decode steps' recurrence, every Mamba layer: each live slot's state is
    read once and written once a layer a step, its rows go in and out;
    ``slot_steps``: live slots summed over the steps."""
    layers = mamba_layers(cfg)
    return {"flops": token_flops(cfg) * layers * slot_steps,
            "bytes": float(2 * state_bytes(cfg) + row_bytes(cfg))
            * layers * slot_steps}


def ssm_chunk(cfg: dict, tokens: int, chunks: int) -> dict:
    """Prefill chunks' recurrence, every Mamba layer: ``tokens`` REAL tokens'
    required flops, their rows, and each chunk's state in once and out once.
    ``peaks.json`` holds the MXU's rate and the memory's, not the VPU's, on
    which this work runs: the floor that comes out is the rows' bytes, and a
    VPU-bound kernel reads well under 100%."""
    layers = mamba_layers(cfg)
    return {"flops": token_flops(cfg) * layers * tokens,
            "bytes": float(row_bytes(cfg)) * layers * tokens
            + float(2 * state_bytes(cfg)) * layers * chunks}


def mqa_attend(cfg: dict, context_tokens: int, slot_steps: int,
               kv_bytes: int = 2) -> dict:
    """Decode steps' attention over the paged pool, over the attention layers
    alone (``flops.paged_attend`` multiplies by ``num_hidden_layers``): every
    live context token's k and v is read once (1 kv head x 128 x 2 x 2 B =
    512 B a token a layer as published), each slot's query and output rows
    move once; FLOPs are 4 per (query head, key, head_dim).
    ``context_tokens`` and ``slot_steps``: live context and live slots summed
    over the steps."""
    d, hq, hkv = (cfg["head_dim"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    layers = attention_layers(cfg)
    return {"flops": 4.0 * layers * hq * d * context_tokens,
            "bytes": float(2 * hkv * d * kv_bytes) * layers * context_tokens
            + 2 * 2 * layers * slot_steps * hq * d}
