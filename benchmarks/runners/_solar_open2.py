"""The ``solar_open2`` family's adapter, found by ``cfg["family"]``
(``runners/_<family>.py``): the benchmark's configuration and weights handed
to the program in the program's own terms (``models/solar_open2.py``), and
the family's ``weights`` module and plain ``reference``. The program's module
is imported here at the top, so a checkout without it fails on the cell's
name at once, before any weight is made."""
from __future__ import annotations

import jax.numpy as jnp

from benchmarks import weights_solar_open2 as weights
from benchmarks.reference import solar_open2 as reference  # noqa: F401
from distributed_training_guide_tpu.models import solar_open2
from distributed_training_guide_tpu.models.registry import ModelBundle


def bundle_for(cfg: dict, name: str):
    if cfg["family"] != "solar_open2":
        raise ValueError(f"runner knows the solar_open2 family, not "
                         f"{cfg['family']!r}")
    if cfg["use_rope"]:
        raise ValueError("use_rope true is not implemented (the published "
                         "config has no positional encoding)")
    if not (cfg["use_gqa_gate"] and cfg["kda_allow_neg_eigval"]) \
            or cfg["kda_use_full_proj"]:
        raise ValueError("the published form alone is implemented: a gated "
                         "GQA output, beta in (0, 2), low-rank KDA gates")
    if cfg["first_k_dense_replace"] or cfg["tie_word_embeddings"]:
        raise ValueError("a dense FFN layer and a tied head are not drawn by "
                         "weights_solar_open2 (the published config has "
                         "neither)")
    if not cfg["norm_topk_prob"]:
        raise ValueError("norm_topk_prob false is not tested")
    if cfg["state_dtype"] != "float32":
        raise ValueError(f"the state class is float32 in the program, not an "
                         f"option of it: state_dtype {cfg['state_dtype']!r} "
                         f"cannot be run")
    lin = cfg["linear_attn_config"]
    if lin["num_kv_heads"] not in (None, lin["num_heads"]):
        raise ValueError("KDA's k and v have the heads q has")
    config = solar_open2.SolarOpen2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        gqa_layers=tuple(cfg["gqa_layers"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=weights.router_experts(cfg),
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=(cfg.get("experts_held_first", 0),
                      cfg["n_routed_experts"]),
        shared_expert_intermediate=(cfg["n_shared_experts"]
                                    * cfg["moe_intermediate_size"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=weights.DTYPES[cfg["compute_dtype"]],
        param_dtype=weights.DTYPES[cfg["weights_dtype"]])
    return ModelBundle(name, config, solar_open2.init, solar_open2.apply,
                       solar_open2.param_logical_axes, family="solar_open2")


def to_program(w: dict) -> dict:
    """``weights_solar_open2.stacked_weights`` layout ->
    ``models/solar_open2.py``'s tree: the mixers' and the FFN's own leaves a
    layer each (a list), the norms and the held experts stacked. The program
    keeps KDA's three streams as ONE matrix ``[E, 3 C]`` (q, k, v in that
    order) and their taps ``[taps, 3 C]``; the equations write ``[C, taps]``
    a stream."""
    def rows(stack: dict) -> list:
        n = len(next(iter(stack.values())))
        return [{name: leaf[i] for name, leaf in stack.items()}
                for i in range(n)]

    def gqa(p):
        return {name.removeprefix("gqa_"): leaf for name, leaf in p.items()}

    def kda(p):
        p = {name.removeprefix("kda_"): leaf for name, leaf in p.items()}
        streams = [p.pop(f"w{s}") for s in "qkv"]
        taps = [p.pop(f"conv_{s}") for s in "qkv"]
        return {**p, "w_qkv": jnp.concatenate(streams, axis=-1),
                "taps": jnp.concatenate(taps, axis=0).T}

    def ffn(p):
        return {"router": p["router"], "router_bias": p["router_bias"],
                "shared_gate_proj": p["shared_gate"],
                "shared_up": p["shared_up"], "shared_down": p["shared_down"]}

    return {
        "embed": {"embedding": w["top"]["embed"]},
        "final_norm": w["top"]["final_norm"],
        "lm_head": w["top"]["lm_head"],
        "layers": {
            **w["norms"],
            solar_open2.GQA: [gqa(p) for p in rows(w[weights.GQA])],
            solar_open2.KDA: [kda(p) for p in rows(w[weights.KDA])],
            "ffn": [ffn(p) for p in rows(w["ffn"])],
            "moe": {name: w["ffn"][name] for name in weights.EXPERT_LEAVES},
        },
    }


def program_params(cfg: dict, key, dtype=None):
    """Traceable: the program's tree for ``weights.seed_key(seed)``, which the
    one jit around this takes as an operand."""
    return to_program(weights.stacked_weights(cfg, key, dtype))
