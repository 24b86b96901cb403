"""The ``jamba`` family's adapter, found by ``cfg["family"]``
(``runners/_<family>.py``): the benchmark's configuration and weights handed
to the program in the program's own terms (``models/jamba.py``), and the
family's ``weights`` module and plain ``reference``. The program's module is
imported here at the top, so a checkout without it fails on the cell's name
at once, before any weight is made."""
from __future__ import annotations

from benchmarks import weights_jamba as weights
from benchmarks.reference import jamba as reference  # noqa: F401
from distributed_training_guide_tpu.models import jamba
from distributed_training_guide_tpu.models.registry import ModelBundle


def bundle_for(cfg: dict, name: str):
    if cfg["family"] != "jamba":
        raise ValueError(f"runner knows the jamba family, not "
                         f"{cfg['family']!r}")
    if cfg["num_experts"] != 1 or cfg["num_experts_per_tok"] != 1:
        raise ValueError("routed experts are not implemented for this family "
                         "(the published config has one dense FFN a layer)")
    if cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"]:
        raise ValueError("the published form alone is implemented: no bias "
                         "on the Mamba projections, one on its convolution")
    if cfg["sliding_window"] is not None or cfg["hidden_act"] != "silu":
        raise ValueError("a sliding window and another activation than silu "
                         "are not implemented (the published config has "
                         "neither)")
    if not cfg["tie_word_embeddings"]:
        raise ValueError("an untied head is not drawn by weights_jamba (the "
                         "published config ties it)")
    if cfg["state_dtype"] != "float32":
        raise ValueError(f"the state class is float32 in the program, not an "
                         f"option of it: state_dtype {cfg['state_dtype']!r} "
                         f"cannot be run")
    config = jamba.JambaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        attn_layer_period=cfg["attn_layer_period"],
        attn_layer_offset=cfg["attn_layer_offset"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"], mamba_dt_rank=cfg["mamba_dt_rank"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=weights.DTYPES[cfg["compute_dtype"]],
        param_dtype=weights.DTYPES[cfg["weights_dtype"]])
    return ModelBundle(name, config, jamba.init, jamba.apply,
                       jamba.param_logical_axes, family="jamba")


def to_program(w: dict) -> dict:
    """``weights_jamba.stacked_weights`` layout -> ``models/jamba.py``'s
    tree: every matrix a layer each (a list), the two norms stacked. The
    program holds the taps ``[taps, C]`` and ``A_log`` ``[N, C]`` (as the
    state lies, channels on the lanes); the equations write ``[C, taps]`` and
    ``[C, N]``."""
    def rows(stack: dict) -> list:
        n = len(next(iter(stack.values())))
        return [{name: leaf[i] for name, leaf in stack.items()}
                for i in range(n)]

    def attn(p):
        return {name.removeprefix("attn_"): leaf for name, leaf in p.items()}

    def mamba(p):
        p = {name.removeprefix("mamba_"): leaf for name, leaf in p.items()}
        taps = p.pop("conv").T
        return {**p, "taps": taps, "a_log": p["a_log"].T}

    return {
        "embed": {"embedding": w["top"]["embed"]},
        "final_norm": w["top"]["final_norm"],
        "layers": {
            **w["norms"],
            jamba.ATTENTION: [attn(p) for p in rows(w[weights.ATTENTION])],
            jamba.MAMBA: [mamba(p) for p in rows(w[weights.MAMBA])],
            "mlp": rows(w["ffn"]),
        },
    }


def program_params(cfg: dict, key, dtype=None):
    """Traceable: the program's tree for ``weights.seed_key(seed)``, which the
    one jit around this takes as an operand."""
    return to_program(weights.stacked_weights(cfg, key, dtype))
