"""The ``brumby`` family's adapter, found by ``cfg["family"]``
(``runners/_<family>.py``): the benchmark's configuration and weights handed
to the program in the program's own terms (``models/brumby.py``), and the
family's ``weights`` module and plain ``reference``. The program's module is
imported here at the top, so a checkout without it fails on the cell's name
at once, before any weight is made."""
from __future__ import annotations

from benchmarks import weights_brumby as weights
from benchmarks.reference import brumby as reference  # noqa: F401
from distributed_training_guide_tpu.models import brumby
from distributed_training_guide_tpu.models.registry import ModelBundle

MIXER = ("wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm")
MLP = ("gate", "up", "down")


def bundle_for(cfg: dict, name: str):
    if cfg["family"] != "brumby":
        raise ValueError(f"runner knows the brumby family, not "
                         f"{cfg['family']!r}")
    if cfg["attention_bias"] or cfg["hidden_act"] != "silu":
        raise ValueError("the published form alone is implemented: no bias "
                         "on the projections, silu in the FFN")
    if (cfg["sliding_window"] is not None or cfg["use_sliding_window"]
            or cfg["rope_scaling"] is not None):
        raise ValueError("a sliding window and a scaled rope are not "
                         "implemented (the published config has neither)")
    if cfg["tie_word_embeddings"]:
        raise ValueError("a tied head is not drawn by weights_brumby (the "
                         "published config unties it)")
    if cfg["state_dtype"] != "float32":
        raise ValueError(f"the state class is float32 in the program, not an "
                         f"option of it: state_dtype {cfg['state_dtype']!r} "
                         f"cannot be run")
    config = brumby.BrumbyConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=weights.DTYPES[cfg["compute_dtype"]],
        param_dtype=weights.DTYPES[cfg["weights_dtype"]])
    return ModelBundle(name, config, brumby.init, brumby.apply,
                       brumby.param_logical_axes, family="brumby")


def _tree(top: dict, layers: list) -> dict:
    """``models/brumby.py``'s tree from the top leaves and a LIST of layers'
    leaves: every matrix a layer each, the two norms stacked."""
    import jax.numpy as jnp

    return {
        "embed": {"embedding": top["embed"]},
        "final_norm": top["final_norm"],
        "lm_head": top["lm_head"],
        "layers": {
            "mixer_norm": jnp.stack([w["mixer_norm"] for w in layers]),
            "ffn_norm": jnp.stack([w["ffn_norm"] for w in layers]),
            "mixer": [{name: w[name] for name in MIXER} for w in layers],
            "mlp": [{name: w[name] for name in MLP} for w in layers]},
    }


def to_program(w: dict) -> dict:
    """``weights_brumby.stacked_weights`` layout -> the program's tree."""
    n = len(w["layers"]["mixer_norm"])
    return _tree(w["top"], [{name: leaf[i] for name, leaf
                             in w["layers"].items()} for i in range(n)])


def program_params(cfg: dict, key, dtype=None):
    """Traceable: the program's tree for ``weights.seed_key(seed)``, which the
    one jit around this takes as an operand. Drawn a LAYER at a time (the
    counter hash gives the same numbers alone as stacked): a stacked copy of
    8 layers beside the leaves cut from it would be 6.8 GB more than the
    chip has left."""
    return _tree(weights.top_weights(cfg, key, dtype),
                 [weights.layer_weights(cfg, key, l, dtype)
                  for l in range(cfg["num_hidden_layers"])])
