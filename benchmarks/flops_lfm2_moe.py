"""Operations and bytes that the ``lfm2_moe`` family's decode step REQUIRES,
from shapes and the run's counters alone (``flops.py``'s rule: nothing a
kernel happens to execute, pad or re-read is counted). Consecutive layers
differ in kind here, so nothing multiplies by ``num_hidden_layers``: the
attention layers are counted from ``layer_types``.
"""
from __future__ import annotations

from benchmarks.weights_lfm2_moe import head_dim, layers_of


def attention_layers(cfg: dict) -> int:
    return len(layers_of(cfg)["attn"])


def kv_bytes_per_token(cfg: dict, kv_bytes: int = 2) -> int:
    """k and v of one token over the layers that ATTEND (4 KB at the cell's
    2 attention layers, 20 KB at the published 10)."""
    return (attention_layers(cfg) * 2 * cfg["num_key_value_heads"]
            * head_dim(cfg) * kv_bytes)


def paged_attend(cfg: dict, context_tokens: int, n_slots: int,
                 kv_bytes: int = 2) -> dict:
    """Decode steps' attention over the paged pool, over the layers that
    attend: every live context token's k and v is read once (the two kv
    heads a 128-wide pool row holds are both required, so the row's bytes
    are); FLOPs are 4 per (query head, key, head_dim): the products against
    the zero half of a packed query row are the kernel's, not required."""
    d, hq = head_dim(cfg), cfg["num_attention_heads"]
    layers = attention_layers(cfg)
    return {"flops": 4.0 * layers * hq * d * context_tokens,
            "bytes": float(kv_bytes_per_token(cfg, kv_bytes)) * context_tokens
            + 2 * 2 * layers * n_slots * hq * d}


def matmul_params_outside_experts(cfg: dict) -> int:
    """Every matmul parameter a decode step reads whatever it routes: the
    operators, the dense FFNs, the routers and the tied embedding (as the
    head)."""
    e, d = cfg["hidden_size"], head_dim(cfg)
    kinds = layers_of(cfg)
    attn = e * d * (2 * cfg["num_attention_heads"]
                    + 2 * cfg["num_key_value_heads"])
    conv = e * 3 * e + e * e + e * cfg["conv_L_cache"]
    return (len(kinds["attn"]) * attn + len(kinds["conv"]) * conv
            + len(kinds["dense"]) * 3 * e * cfg["intermediate_size"]
            + len(kinds["moe"]) * e * cfg["num_experts"]
            + cfg["vocab_size"] * e)


def decode_step_bytes(cfg: dict, context_tokens: int, experts_touched: int,
                      weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """One decode step: the weights outside the experts once, the three
    matrices of every (expert, layer) pair that has a token, and the live
    k and v; the conv state (two rows a conv layer a slot) is noise."""
    expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return ((matmul_params_outside_experts(cfg) + experts_touched * expert)
            * weight_bytes
            + kv_bytes_per_token(cfg, kv_bytes) * context_tokens)
