"""The ``mimo_v2`` family's adapter, found by ``cfg["family"]``
(``runners/_<family>.py``): the benchmark's configuration and weights handed
to the program in the program's own terms (``models/mimo_v2.py``), and the
family's ``weights`` module and plain ``reference``. The program's module is
imported here at the top, so a checkout without it fails on the cell's name
at once, before any weight is made."""
from __future__ import annotations

from benchmarks import weights_mimo_v2 as weights
from benchmarks.reference import mimo_v2 as reference  # noqa: F401
from distributed_training_guide_tpu.models import mimo_v2
from distributed_training_guide_tpu.models.registry import ModelBundle


def bundle_for(cfg: dict, name: str):
    if cfg["family"] != "mimo_v2":
        raise ValueError(f"runner knows the mimo_v2 family, not {cfg['family']!r}")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("group-limited routing is not implemented (the "
                         "published config's groups are the identity)")
    if cfg["n_shared_experts"] or cfg["routed_scaling_factor"] not in (None, 1):
        raise ValueError("a shared expert and a routed scaling factor are "
                         "not implemented (the published config has neither)")
    if cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc":
        raise ValueError("the router is sigmoid scores with a choice bias")
    if cfg["attention_bias"] or cfg["tie_word_embeddings"]:
        raise ValueError("attention biases and a tied head are not drawn by "
                         "weights_mimo_v2 (the published config has neither)")
    if cfg["rope_scaling"].get("rope_type", "default") != "default":
        raise ValueError("rope scaling is not implemented (the published "
                         "config's is default)")
    if not (cfg["sliding_window"] == cfg["sliding_window_size"]
            and cfg["swa_head_dim"] == cfg["head_dim"]
            and cfg["swa_v_head_dim"] == cfg["v_head_dim"]
            and cfg["swa_num_attention_heads"] == cfg["num_attention_heads"]):
        raise ValueError("the window layers share the full layers' head "
                         "widths and query-head count")
    n = cfg["num_hidden_layers"]
    if len(cfg["hybrid_layer_pattern"]) != n or len(cfg["moe_layer_freq"]) != n:
        raise ValueError("hybrid_layer_pattern and moe_layer_freq must name "
                         "every layer")
    config = mimo_v2.MimoV2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        hybrid_layer_pattern=tuple(cfg["hybrid_layer_pattern"]),
        moe_layer_freq=tuple(cfg["moe_layer_freq"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        swa_num_kv_heads=cfg["swa_num_key_value_heads"],
        head_dim=cfg["head_dim"], v_head_dim=cfg["v_head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]),
        swa_rope_theta=float(cfg["swa_rope_theta"]),
        sliding_window=cfg["sliding_window"],
        attention_value_scale=cfg["attention_value_scale"],
        add_swa_attention_sink_bias=cfg["add_swa_attention_sink_bias"],
        add_full_attention_sink_bias=cfg["add_full_attention_sink_bias"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=weights.router_experts(cfg),
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=(cfg.get("experts_held_first", 0),
                      cfg["n_routed_experts"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["layernorm_epsilon"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=weights.DTYPES[cfg["compute_dtype"]],
        param_dtype=weights.DTYPES[cfg["weights_dtype"]],
        # the tests' debug configuration narrows the pool rows
        **({"row_lanes": cfg["row_lanes"]} if "row_lanes" in cfg else {}))
    return ModelBundle(name, config, mimo_v2.init, mimo_v2.apply,
                       mimo_v2.param_logical_axes, family="mimo_v2")


def to_program(w: dict) -> dict:
    """``weights_mimo_v2.stacked_weights`` layout -> ``models/mimo_v2.py``'s
    tree: the attention kinds' and the dense FFNs' leaves a layer each (a
    list), the norms and the routed FFNs stacked."""
    def rows(stack: dict, rename) -> list:
        n = len(next(iter(stack.values())))
        return [{rename(name): leaf[i] for name, leaf in stack.items()}
                for i in range(n)]

    def attn(kind):     # the program stores wq and wk [out, in]
        layers = rows(w[f"attn_{kind}"], lambda name: name.split("_", 1)[1])
        return [{name: leaf.T if name in ("wq", "wk") else leaf
                 for name, leaf in layer.items()} for layer in layers]

    return {
        "embed": {"embedding": w["top"]["embed"]},
        "final_norm": w["top"]["final_norm"],
        "lm_head": w["top"]["lm_head"],
        "layers": {
            **w["norms"], "attn_full": attn("full"),
            "attn_window": attn("window"),
            "mlp": rows(w["dense"], lambda name: name.removeprefix("dense_")),
            "moe": w["moe"],
        },
    }


def program_params(cfg: dict, key, dtype=None):
    """Traceable: the program's tree for ``weights.seed_key(seed)``, which the
    one jit around this takes as an operand."""
    return to_program(weights.stacked_weights(cfg, key, dtype))
