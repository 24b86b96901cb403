"""The ``laguna`` family's adapter, found by ``cfg["family"]``
(``runners/_<family>.py``): the benchmark's configuration and weights handed
to the program in the program's own terms (``models/laguna.py``), and the
family's ``weights`` module, plain ``reference`` and ``flops``. The program's
module is imported here at the top, so a checkout without it fails on the
cell's name at once, before any weight is made."""
from __future__ import annotations

from benchmarks import flops_laguna as flops  # noqa: F401
from benchmarks import weights_laguna as weights
from benchmarks.reference import laguna as reference  # noqa: F401
from distributed_training_guide_tpu.models import laguna
from distributed_training_guide_tpu.models.registry import ModelBundle
from distributed_training_guide_tpu.ops.rope import freeze_rope_scaling

ATTN = ("wq", "wk", "wv", "wo", "wg")
# the benchmark's leaf -> the program's, inside its group
MLP = {"dense_gate": "gate", "dense_up": "up", "dense_down": "down"}
MOE = {"router": "router", "gate": "gate", "up": "up", "down": "down",
       "shared_gate": "shared_gate_proj", "shared_up": "shared_up",
       "shared_down": "shared_down"}


def bundle_for(cfg: dict, name: str):
    if cfg["family"] != "laguna":
        raise ValueError(f"runner knows the laguna family, not {cfg['family']!r}")
    if cfg["attention_bias"] or cfg["tie_word_embeddings"]:
        raise ValueError("attention biases and a tied head are not drawn by "
                         "weights_laguna (the published config has neither)")
    if not cfg["gating"] or cfg["moe_apply_router_weight_on_input"]:
        raise ValueError("the program gates every head's output and weights "
                         "the experts' outputs, as published")
    rp = cfg["rope_parameters"]
    full, swa = dict(rp["full_attention"]), dict(rp["sliding_attention"])
    if swa.get("rope_type", "default") != "default":
        raise ValueError("the window layers' rope is plain, as published")
    if full["partial_rotary_factor"] != cfg["partial_rotary_factor"]:
        raise ValueError("partial_rotary_factor is the full layers'")
    n = cfg["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        if len(cfg[key]) != n:
            raise ValueError(f"{key} must name every layer")
    scaling = {k: v for k, v in full.items()
               if k not in ("rope_theta", "partial_rotary_factor")}
    config = laguna.LagunaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        num_heads_per_layer=tuple(cfg["num_attention_heads_per_layer"]),
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"],
        rope_theta=float(full["rope_theta"]),
        rope_scaling=(freeze_rope_scaling(scaling)
                      if full["rope_type"] != "default" else None),
        partial_rotary_factor=full["partial_rotary_factor"],
        swa_rope_theta=float(swa["rope_theta"]),
        swa_partial_rotary_factor=swa["partial_rotary_factor"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg["shared_expert_intermediate_size"],
        num_experts=weights.router_experts(cfg),
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=(cfg.get("experts_held_first", 0), cfg["num_experts"]),
        routed_scaling_factor=cfg["moe_routed_scaling_factor"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=weights.DTYPES[cfg["compute_dtype"]],
        param_dtype=weights.DTYPES[cfg["weights_dtype"]])
    return ModelBundle(name, config, laguna.init, laguna.apply,
                       laguna.param_logical_axes, family="laguna",
                       apply_with_aux=laguna.apply_with_aux)


def to_program(w: dict) -> dict:
    """``weights_laguna.model_weights`` layout -> ``models/laguna.py``'s
    tree: the same per-layer list, a layer's leaves in their groups."""
    def layer(leaves: dict) -> dict:
        out = {"attn_norm": leaves["attn_norm"], "ffn_norm": leaves["ffn_norm"],
               "attn": {k: leaves[k] for k in ATTN}}
        group, names = (("mlp", MLP) if "dense_gate" in leaves
                        else ("moe", MOE))
        out[group] = {prog: leaves[bench] for bench, prog in names.items()}
        return out

    return {"embed": {"embedding": w["top"]["embed"]},
            "final_norm": w["top"]["final_norm"],
            "lm_head": w["top"]["lm_head"],
            "layers": [layer(leaves) for leaves in w["layers"]]}


def from_program(tree: dict) -> dict:
    """The inverse: the program's tree under the benchmark's leaf names (no
    leaf is copied)."""
    def layer(p: dict) -> dict:
        out = {"attn_norm": p["attn_norm"], "ffn_norm": p["ffn_norm"],
               **p["attn"]}
        group, names = ("mlp", MLP) if "mlp" in p else ("moe", MOE)
        out.update({bench: p[group][prog] for bench, prog in names.items()})
        return out

    return {"top": {"embed": tree["embed"]["embedding"],
                    "final_norm": tree["final_norm"],
                    "lm_head": tree["lm_head"]},
            "layers": [layer(p) for p in tree["layers"]]}


def program_params(cfg: dict, key, dtype=None):
    """Traceable: the program's tree for ``weights.seed_key(seed)``, which the
    one jit around this takes as an operand."""
    return to_program(weights.model_weights(cfg, key, dtype))
