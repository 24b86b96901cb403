"""Operations and bytes that the ``mla_moe`` family's two kernels REQUIRE,
from shapes and the run's counters alone (``flops.py``'s rule: nothing a
kernel happens to execute, pad or re-read is counted).
"""
from __future__ import annotations


def latent_row_bytes(cfg: dict, kv_bytes: int = 2) -> int:
    """One cached token in one layer AS PUBLISHED: ``c_kv`` and the one rope
    key, 640 B in bf16. The pool's lane padding of the rope key (resident 768
    B) is not required work."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * kv_bytes


def latent_attend(cfg: dict, context_tokens: int, n_slots: int,
                  kv_bytes: int = 2) -> dict:
    """Decode steps' absorbed latent attention, all layers: every live
    context token's latent row is read ONCE (it is key and value), the
    queries in and the latent outputs out; per (token, head) ``2 x (C + R)``
    FLOPs for the score and ``2 x C`` for the value sum."""
    layers, h = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    c, r = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return {"flops": 2.0 * layers * h * ((c + r) + c) * context_tokens,
            "bytes": float(layers * latent_row_bytes(cfg, kv_bytes))
            * context_tokens + 2.0 * layers * n_slots * h * ((c + r) + c)}


def expert_gmm(cfg: dict, experts_touched: int, pairs: int,
               weight_bytes: int = 2) -> dict:
    """The routed experts' three grouped matmuls over ``pairs`` (token,
    expert) pairs computed here, ``experts_touched`` (expert, layer, step)
    triples having at least one: each touched expert's three matrices are
    read once, each pair's rows go in and out of each product, and a pair
    costs ``2 x 3 x hidden x width`` FLOPs."""
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = (e + f) + (e + f) + (f + e)      # gate, up, down: in + out
    return {"flops": 2.0 * 3 * e * f * pairs,
            "bytes": float(3 * e * f * weight_bytes) * experts_touched
            + float(rows * weight_bytes) * pairs}
