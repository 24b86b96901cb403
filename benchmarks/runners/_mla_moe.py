"""The ``mla_moe`` family's adapter, found by ``cfg["family"]``
(``runners/_<family>.py``): the benchmark's configuration and weights handed
to the program in the program's own terms (``models/mla.py``), and the
family's ``weights`` module and plain ``reference``."""
from __future__ import annotations

from benchmarks import weights_mla_moe as weights
from benchmarks.reference import mla_moe as reference  # noqa: F401

ATTN = ("wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo")
MOE = ("router", "router_bias", "gate", "up", "down", "shared_gate_proj",
       "shared_up", "shared_down")


def bundle_for(cfg: dict, name: str):
    from distributed_training_guide_tpu.models import mla
    from distributed_training_guide_tpu.models.registry import ModelBundle
    from distributed_training_guide_tpu.ops.rope import freeze_rope_scaling

    if cfg["family"] != "mla_moe":
        raise ValueError(f"runner knows the mla_moe family, not {cfg['family']!r}")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1 or cfg["first_k_dense_replace"]:
        raise ValueError("group-limited routing and leading dense layers are "
                         "not implemented (the published config has neither)")
    rope = dict(cfg["rope_parameters"])
    theta, beta = rope.pop("rope_theta"), rope.pop("llama_4_scaling_beta", 0.0)
    config = mla.MlaMoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate=(cfg["moe_intermediate_size"]
                                    * cfg["n_shared_experts"]),
        num_experts=cfg["router_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=(cfg.get("experts_held_first", 0), cfg["n_routed_experts"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        rope_theta=float(theta), rope_scaling=freeze_rope_scaling(rope),
        rope_interleave=cfg["rope_interleave"], query_scale_beta=beta,
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=weights.DTYPES[cfg["compute_dtype"]],
        param_dtype=weights.DTYPES[cfg["weights_dtype"]])
    return ModelBundle(name, config, mla.init, mla.apply,
                       mla.param_logical_axes, family="mla_moe")


def to_program(w: dict) -> dict:
    """``weights_mla_moe.stacked_weights`` layout -> ``models/mla.py``'s tree."""
    layers = dict(w["layers"])
    return {
        "embed": {"embedding": w["top"]["embed"]},
        "final_norm": w["top"]["final_norm"],
        "lm_head": w["top"]["lm_head"],
        "layers": {"attn": {k: layers.pop(k) for k in ATTN},
                   "moe": {k: layers.pop(k) for k in MOE}, **layers},
    }


def program_params(cfg: dict, key, dtype=None):
    """Traceable: the program's tree for ``weights.seed_key(seed)``, which the
    one jit around this takes as an operand."""
    return to_program(weights.stacked_weights(cfg, key, dtype))
