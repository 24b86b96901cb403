"""Operations and bytes that the algorithm requires, from shapes alone.

Nothing here counts what a kernel happens to execute: recomputed forward
passes (remat), masked-out attention blocks and padding are not required work.
A share computed from these can therefore not pass 100% unless the time it is
divided by leaves out part of the work.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul for every token: the layers'
    projections and the output head (tied or not). The embedding lookup is a
    gather and the norm scales are elementwise: neither is counted."""
    e, f, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    hq = cfg["num_attention_heads"] * d
    hkv = cfg["num_key_value_heads"] * d
    per_layer = e * hq + 2 * e * hkv + hq * e + 3 * e * f
    return cfg["num_hidden_layers"] * per_layer + e * cfg["vocab_size"]


def attention_flops_fwd(cfg: dict, batch: int, seq: int) -> float:
    """Causal attention, one layer, forward: QK^T and PV over the lower
    triangle (S*(S+1)/2 query-key pairs), 2 FLOPs per multiply-add."""
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    pairs = seq * (seq + 1) / 2
    return 2 * 2 * batch * hq * pairs


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Required FLOPs per trained token, forward and backward: 6 per matmul
    parameter, and for causal attention the forward's two matmuls once and
    the backward's at twice the forward (3x in all). The five-matmul
    flash backward with its recomputed scores is not counted: recompute is
    not required work."""
    attn = 3 * cfg["num_hidden_layers"] * attention_flops_fwd(cfg, 1, seq) / seq
    return 6 * matmul_params(cfg) + attn


# ---- flash attention kernels (one call = one layer, whole batch) ----------
def flash_fwd(cfg: dict, batch: int, seq: int) -> dict:
    """FLOPs and HBM bytes of one causal forward call: read q, k, v, write o
    (compute dtype, 2 B) and the row log-sum-exp (4 B)."""
    d = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    bytes_ = 2 * batch * seq * d * (2 * hq + 2 * hkv) + 4 * batch * seq * hq
    return {"flops": attention_flops_fwd(cfg, batch, seq), "bytes": bytes_}


def flash_bwd(cfg: dict, batch: int, seq: int) -> dict:
    """The whole backward of one layer (dq and dkv kernels together): five
    matmuls over the lower triangle (scores, dP, dV, dQ, dK) where the forward
    has two; reads q, k, v, o, do, lse and writes dq, dk, dv."""
    d = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    bytes_ = (2 * batch * seq * d * (4 * hq + 4 * hkv)
              + 2 * 4 * batch * seq * hq)
    return {"flops": 2.5 * attention_flops_fwd(cfg, batch, seq), "bytes": bytes_}


# ---- serving --------------------------------------------------------------
def kv_bytes_per_token(cfg: dict, kv_bytes: int = 2) -> int:
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * kv_bytes)


def paged_attend(cfg: dict, context_tokens: int, n_slots: int,
                 kv_bytes: int = 2) -> dict:
    """One decode step's attention over the paged pool, all layers: every
    live context token's k and v is read once (bandwidth-bound at one query
    token a slot); FLOPs are 4 per (query head, key, head_dim)."""
    d, hq = cfg["head_dim"], cfg["num_attention_heads"]
    layers = cfg["num_hidden_layers"]
    return {"flops": 4.0 * layers * hq * d * context_tokens,
            "bytes": float(kv_bytes_per_token(cfg, kv_bytes)) * context_tokens
            + 2 * 2 * layers * n_slots * hq * d}


def decode_step_bytes(cfg: dict, context_tokens: int, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """Weights (every matmul parameter once) plus the live KV, per step."""
    return (matmul_params(cfg) * weight_bytes
            + kv_bytes_per_token(cfg, kv_bytes) * context_tokens)


def least_time(work: dict, peak: dict, n: float = 1.0) -> tuple:
    """(seconds, bound) for ``n`` calls of ``work`` at the chip's peaks."""
    t_flops = n * work["flops"] / peak["bf16_flops_per_s"]
    t_bytes = n * work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
