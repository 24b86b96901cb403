"""The ``lfm2_moe`` family's adapter, found by ``cfg["family"]``
(``runners/_<family>.py``): the benchmark's configuration and weights handed
to the program in the program's own terms (``models/lfm2.py``), and the
family's ``weights`` module and plain ``reference``. The program's module is
imported here at the top, so a checkout without it fails on the cell's name
at once, before any weight is made."""
from __future__ import annotations

from benchmarks import weights_lfm2_moe as weights
from benchmarks.reference import lfm2_moe as reference  # noqa: F401
from distributed_training_guide_tpu.models import lfm2
from distributed_training_guide_tpu.models.registry import ModelBundle


def bundle_for(cfg: dict, name: str):
    if cfg["family"] != "lfm2_moe":
        raise ValueError(f"runner knows the lfm2_moe family, not {cfg['family']!r}")
    if cfg["conv_bias"]:
        raise ValueError("conv_bias true is not implemented (the published "
                         "config has none)")
    if not cfg["use_expert_bias"]:
        raise ValueError("use_expert_bias false is not tested (the published "
                         "router has the choice bias)")
    if not cfg["tie_word_embeddings"]:
        raise ValueError("an untied head is not drawn by weights_lfm2_moe "
                         "(the family ties)")
    rope = cfg["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not implemented "
                         f"(the published config's is default)")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer")
    config = lfm2.Lfm2MoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=weights.head_dim(cfg),
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        use_expert_bias=cfg["use_expert_bias"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        conv_l_cache=cfg["conv_L_cache"],
        rope_theta=float(rope["rope_theta"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=weights.DTYPES[cfg["compute_dtype"]],
        param_dtype=weights.DTYPES[cfg["weights_dtype"]])
    return ModelBundle(name, config, lfm2.init, lfm2.apply,
                       lfm2.param_logical_axes, family="lfm2_moe")


def to_program(w: dict) -> dict:
    """``weights_lfm2_moe.stacked_weights`` layout -> ``models/lfm2.py``'s
    tree (the taps are drawn ``[E, L]`` as the equations write them; the
    program keeps them ``[L, E]``)."""
    conv = dict(w["conv"])
    conv["taps"] = conv["taps"].swapaxes(-1, -2)
    return {
        "embed": {"embedding": w["top"]["embed"]},
        "final_norm": w["top"]["final_norm"],
        "layers": {
            **w["norms"], "attn": w["attn"], "conv": conv,
            "mlp": {k.removeprefix("dense_"): v for k, v in w["dense"].items()},
            "moe": w["moe"],
        },
    }


def program_params(cfg: dict, key, dtype=None):
    """Traceable: the program's tree for ``weights.seed_key(seed)``, which the
    one jit around this takes as an operand."""
    return to_program(weights.stacked_weights(cfg, key, dtype))
