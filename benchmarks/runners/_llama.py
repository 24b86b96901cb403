"""The llama family's adapter, found by ``cfg["family"]``
(``runners/_<family>.py``): the benchmark's configuration and weights handed
to the program in the program's own terms (``models/llama.py``), and the
family's ``weights`` module and plain ``reference``."""
from __future__ import annotations

from benchmarks import weights
from benchmarks.reference import decoder as reference  # noqa: F401

ATTN = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
MLP = ("gate", "up", "down")


def bundle_for(cfg: dict, name: str):
    from distributed_training_guide_tpu.models import llama
    from distributed_training_guide_tpu.models.registry import ModelBundle

    if cfg["family"] != "llama":
        raise ValueError(f"runner knows the llama family, not {cfg['family']!r}")
    config = llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        qk_norm={"per_head": True, "flat": "flat", "none": False}[cfg["qk_norm"]],
        post_norm=cfg["wiring"] == "post_norm",
        dtype=weights.DTYPES[cfg["compute_dtype"]],
        param_dtype=weights.DTYPES[cfg["weights_dtype"]])
    return ModelBundle(name, config, llama.init, llama.apply,
                       llama.param_logical_axes, family="llama")


def to_program(w: dict) -> dict:
    """``weights.stacked_weights`` layout -> ``models/llama.py``'s tree."""
    layers = dict(w["layers"])
    tree = {
        "embed": {"embedding": w["top"]["embed"]},
        "final_norm": w["top"]["final_norm"],
        "layers": {
            "attn": {k: layers.pop(k) for k in ATTN if k in layers},
            "mlp": {k: layers.pop(k) for k in MLP},
            **layers,
        },
    }
    if "lm_head" in w["top"]:
        tree["lm_head"] = w["top"]["lm_head"]
    return tree


def from_program(tree: dict) -> dict:
    """The inverse: the program's tree under the benchmark's leaf names."""
    layers = {k: v for k, v in tree["layers"].items()
              if k not in ("attn", "mlp")}
    layers.update(tree["layers"]["attn"])
    layers.update(tree["layers"]["mlp"])
    top = {"embed": tree["embed"]["embedding"], "final_norm": tree["final_norm"]}
    if "lm_head" in tree:
        top["lm_head"] = tree["lm_head"]
    return {"top": top, "layers": layers}


def program_params(cfg: dict, key, dtype=None):
    """Traceable: the program's tree for ``weights.seed_key(seed)``, which the
    one jit around this takes as an operand."""
    return to_program(weights.stacked_weights(cfg, key, dtype))
