"""Operations and bytes that a ``laguna`` train step REQUIRES for the cut the
configuration file states, from shapes and the run's counters alone
(``flops.py``'s rule: nothing a kernel happens to execute, pad, re-read or
recompute is counted; a rematted forward is required once).

Where ``flops.py`` counts one head count, every layer full causal and every
layer a dense FFN, the counts here go layer by layer: ``layer_types`` (a
window layer's query sees ``sliding_window`` keys, its own included),
``num_attention_heads_per_layer``, ``mlp_layer_types``, and for the routed
experts the (token, expert) pairs HELD HERE: a pair whose expert another chip
holds costs this chip nothing.
"""
from __future__ import annotations

from benchmarks import weights_laguna
from benchmarks.weights_laguna import is_dense, is_window, router_experts


def sparse_layers(cfg: dict) -> int:
    return len(weights_laguna.sparse_layers(cfg))


def expected_pairs_held_per_token(cfg: dict) -> float:
    """Under uniform routing: ``top-k x held / routed over`` a sparse layer."""
    return (sparse_layers(cfg) * cfg["num_experts_per_tok"]
            * cfg["num_experts"] / router_experts(cfg))


def attended_pairs(cfg: dict, l: int, seq: int) -> float:
    """(query, key) pairs one head of layer ``l`` attends over one sequence:
    the lower triangle, cut to the band in a window layer."""
    if not is_window(cfg, l) or cfg["sliding_window"] >= seq:
        return seq * (seq + 1) / 2
    w = cfg["sliding_window"]
    return w * (w + 1) / 2 + (seq - w) * w


def attention_flops_fwd(cfg: dict, l: int, batch: int, seq: int) -> float:
    """Layer ``l``'s attention, forward: QK^T and PV over the attended pairs,
    2 FLOPs a multiply-add."""
    hq = cfg["num_attention_heads_per_layer"][l] * cfg["head_dim"]
    return 2 * 2 * batch * hq * attended_pairs(cfg, l, seq)


def matmul_params(cfg: dict) -> dict:
    """Parameters that take part in a matmul for EVERY token, by part. The
    routed experts are not here (they are counted by the pair), nor the
    embedding lookup and the norm scales."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * d
    fs = cfg["shared_expert_intermediate_size"]
    out = {"projections": 0, "gate": 0, "dense_ffn": 0, "shared_expert": 0,
           "router": 0, "head": e * cfg["vocab_size"]}
    for l in range(cfg["num_hidden_layers"]):
        hq = cfg["num_attention_heads_per_layer"][l]
        out["projections"] += 2 * e * hq * d + 2 * e * hkv
        out["gate"] += e * hq
        if is_dense(cfg, l):
            out["dense_ffn"] += 3 * e * cfg["intermediate_size"]
        else:
            out["shared_expert"] += 3 * e * fs
            out["router"] += e * router_experts(cfg)
    return out


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def train_flops_by_part(cfg: dict, seq: int,
                        pairs_held_per_token: float | None = None) -> dict:
    """Required FLOPs a trained token, forward and backward, by part: 6 a
    matmul parameter a token (a routed expert's: a HELD pair), and attention
    at three times its forward (``flops.train_flops_per_token``'s rule).
    ``pairs_held_per_token``: what the steps counted, summed over the sparse
    layers; the expectation under uniform routing where it is not given."""
    if pairs_held_per_token is None:
        pairs_held_per_token = expected_pairs_held_per_token(cfg)
    out = {k: 6.0 * v for k, v in matmul_params(cfg).items()}
    out["routed_experts"] = 6.0 * expert_params(cfg) * pairs_held_per_token
    out["attention_full"] = out["attention_window"] = 0.0
    for l in range(cfg["num_hidden_layers"]):
        kind = "attention_window" if is_window(cfg, l) else "attention_full"
        out[kind] += 3 * attention_flops_fwd(cfg, l, 1, seq) / seq
    return out


def train_flops_per_token(cfg: dict, seq: int,
                          pairs_held_per_token: float | None = None) -> float:
    return sum(train_flops_by_part(cfg, seq, pairs_held_per_token).values())


# ---- the flash kernels, a layer at a time ----------------------------------
def flash_fwd(cfg: dict, l: int, batch: int, seq: int) -> dict:
    """One forward call of layer ``l``: read q, k, v, write o (compute dtype,
    2 B) and the row log-sum-exp (4 B). A window layer reads no fewer rows
    (every key is in some query's band), only fewer pairs."""
    d, hkv = cfg["head_dim"], cfg["num_key_value_heads"]
    hq = cfg["num_attention_heads_per_layer"][l]
    bytes_ = 2 * batch * seq * d * (2 * hq + 2 * hkv) + 4 * batch * seq * hq
    return {"flops": attention_flops_fwd(cfg, l, batch, seq), "bytes": bytes_}


def flash_bwd(cfg: dict, l: int, batch: int, seq: int) -> dict:
    """Layer ``l``'s whole backward (dq and dkv kernels together), as
    ``flops.flash_bwd`` counts it: five matmuls where the forward has two;
    reads q, k, v, o, do, lse and writes dq, dk, dv."""
    d, hkv = cfg["head_dim"], cfg["num_key_value_heads"]
    hq = cfg["num_attention_heads_per_layer"][l]
    bytes_ = (2 * batch * seq * d * (4 * hq + 4 * hkv)
              + 2 * 4 * batch * seq * hq)
    return {"flops": 2.5 * attention_flops_fwd(cfg, l, batch, seq),
            "bytes": bytes_}


def _add(*works) -> dict:
    return {k: float(sum(w[k] for w in works)) for k in ("flops", "bytes")}


def banded_flash(cfg: dict, batch: int, seq: int, kind: str | None = None
                 ) -> dict:
    """One step's flash kernels: every layer (or the layers of ``kind``,
    ``"full"`` / ``"window"``) forward once and backward once."""
    layers = [l for l in range(cfg["num_hidden_layers"])
              if kind is None or (kind == "window") == is_window(cfg, l)]
    return _add(*(w for l in layers for w in (flash_fwd(cfg, l, batch, seq),
                                              flash_bwd(cfg, l, batch, seq))))


# ---- the grouped products over the held experts ----------------------------
def held_gmm(cfg: dict, pairs_held: float, layer_steps: float,
             compute_bytes: int = 2, grad_bytes: int = 4) -> dict:
    """The routed experts' grouped products over ``pairs_held`` (token,
    expert) pairs held here, summed over ``layer_steps`` (sparse layer, step)
    couples, forward once and backward once.

    Forward (three ``gmm``): each held expert's three matrices read once, a
    pair's rows in and out of each product, ``2 x 3 x hidden x width`` FLOPs a
    pair. Backward: the row gradients (three ``gmm`` against the transposed
    matrices: the matrices once more, a pair's gradient rows in and out, the
    same FLOPs) and the matrix gradients (three ``tgmm``: a pair's input and
    gradient rows in, each held matrix's gradient written once at
    ``grad_bytes``, the same FLOPs). Rows and matrices at the COMPUTE dtype's
    width: a backward that widens them to fp32 reads more than is required."""
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["num_experts"]
    rows = (e + f) + (e + f) + (f + e)          # gate, up, down: in + out
    matrices = 3 * e * f * held * layer_steps
    return {"flops": 3 * 2.0 * 3 * e * f * pairs_held,
            "bytes": float(2 * matrices * compute_bytes           # fwd, dx
                           + matrices * grad_bytes                # dW out
                           + 3 * rows * compute_bytes * pairs_held)}


# ---- what a reader asks for by name (readers/family_work.py) ---------------
def window_work(name: str, cfg: dict, traffic: dict, steps: int,
                routing: list) -> dict | None:
    """The required work of ``steps`` whole steps for the kernels ``name``
    stands for. ``routing``: the steps' ``(pairs held, pairs routed, fullest
    expert's rows)``."""
    batch, seq = traffic["global_batch"], traffic["seq_len"]
    if name == "banded_flash":
        one = banded_flash(cfg, batch, seq)
        return {k: steps * v for k, v in one.items()}
    if name == "held_gmm":
        if not routing:
            return None
        return held_gmm(cfg, sum(r[0] for r in routing),
                        sparse_layers(cfg) * len(routing))
    raise KeyError(f"flops_laguna has no work named {name!r}")
