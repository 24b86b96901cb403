"""Operations and bytes that the ``mimo_v2`` family's decode step REQUIRES,
from shapes and the run's counters alone (``flops.py``'s rule: nothing a
kernel happens to execute, pad or re-read is counted). The two attention
kinds differ in kv heads and in reach, so nothing multiplies by
``num_hidden_layers``: the layers are counted from ``hybrid_layer_pattern``,
and a cached token costs its PUBLISHED bytes (a 192-wide key and a 128-wide
value a kv head), not the pool's padded rows.
"""
from __future__ import annotations

from benchmarks.weights_mimo_v2 import FULL, WINDOW, kv_heads, layers_of


def token_bytes(cfg: dict, kind: str, kv_bytes: int = 2) -> int:
    """k and v of one token in one layer of ``kind``, as published: 2,560 B
    in a full layer, 5,120 B in a window layer (bf16)."""
    return kv_heads(cfg, kind) * (cfg["head_dim"] + cfg["v_head_dim"]) \
        * kv_bytes


def layers(cfg: dict, kind: str) -> int:
    return len(layers_of(cfg)[f"attn_{kind}"])


def attend(cfg: dict, kind: str, tokens: int, n_slots: int,
           kv_bytes: int = 2) -> dict:
    """The decode steps' attention of the layers of ONE kind over ``tokens``
    positions in reach (summed over slots and steps): each position's k and
    v read once, the queries in and the outputs out; per (query head,
    position) ``2 x head_dim`` FLOPs for the score and ``2 x v_head_dim`` for
    the value sum."""
    n, hq = layers(cfg, kind), cfg["num_attention_heads"]
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    return {"flops": 2.0 * n * hq * (dk + dv) * tokens,
            "bytes": float(n * token_bytes(cfg, kind, kv_bytes)) * tokens
            + 2.0 * n * n_slots * hq * (dk + dv)}


def mixed_attend(cfg: dict, context_tokens: int, n_slots: int,
                 kv_bytes: int = 2) -> dict:
    """Both kinds over decode steps whose live contexts sum to
    ``context_tokens`` over ``n_slots`` (slot, step) pairs: a full layer
    reaches every live position, a window layer the last ``sliding_window``
    of each slot (every context of the cell is longer than the window; the
    sum over slots is capped by the contexts' own sum)."""
    in_window = min(cfg["sliding_window"] * n_slots, context_tokens)
    full = attend(cfg, FULL, context_tokens, n_slots, kv_bytes)
    window = attend(cfg, WINDOW, in_window, n_slots, kv_bytes)
    return {k: full[k] + window[k] for k in ("flops", "bytes")}
