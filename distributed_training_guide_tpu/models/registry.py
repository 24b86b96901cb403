"""Model registry: name -> (config, init, apply, logical axes).

The reference instantiates models by HF hub name through
``AutoModelForCausalLM.from_config`` (``01-single-gpu/train_llm.py:48-49``).
The TPU build keeps the by-name surface but resolves to the in-repo pure-JAX
zoo; HF hub names alias to the matching preset so reference commands port
unchanged (e.g. ``--model-name gpt2`` or ``meta-llama/Llama-3.1-405B``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

from . import (brumby, gpt2, jamba, laguna, lfm2, llama, mimo_v2, mla, moe,
               neox, solar_open2)


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    name: str
    config: Any
    init: Callable          # (config, rng) -> params
    apply: Callable         # (config, params, input_ids, ...) -> logits
    param_logical_axes: Callable  # (config,) -> axes pytree
    family: str
    # MoE models: (config, params, ids, ...) -> (logits, aux_loss); the
    # trainer adds config.router_aux_coef * aux to the loss
    apply_with_aux: Optional[Callable] = None

    def num_params(self) -> int:
        return self.config.num_params()

    def num_active_params(self) -> int:
        """Per-token active params (MoE: k of E experts) for FLOPs/MFU math."""
        fn = getattr(self.config, "num_active_params", None)
        return fn() if fn else self.config.num_params()


_HF_ALIASES = {
    "openai-community/gpt2": "gpt2",
    "tinyllama/tinyllama-1.1b-chat-v1.0": "tinyllama-1.1b",
    "tinyllama/tinyllama_v1.1": "tinyllama-1.1b",
    "meta-llama/llama-3.2-1b": "llama-3.2-1b",
    "meta-llama/llama-3.2-3b": "llama-3.2-3b",
    "meta-llama/llama-3.1-8b": "llama-3.1-8b",
    "meta-llama/meta-llama-3.1-8b": "llama-3.1-8b",
    "meta-llama/llama-3.1-70b": "llama-3.1-70b",
    "meta-llama/llama-3.1-405b": "llama-3.1-405b",
    "meta-llama/meta-llama-3.1-405b": "llama-3.1-405b",
    "eleutherai/pythia-70m": "pythia-70m",
    "eleutherai/pythia-160m": "pythia-160m",
    "eleutherai/pythia-410m": "pythia-410m",
    "eleutherai/pythia-1.4b": "pythia-1.4b",
    "eleutherai/pythia-6.9b": "pythia-6.9b",
    "eleutherai/gpt-neox-20b": "gpt-neox-20b",
    "mistralai/mistral-small-4-119b-2603": "mistral-small-4-119b",
    "liquidai/lfm2-24b-a2b": "lfm2-24b-a2b",
    "xiaomimimo/mimo-v2.5": "mimo-v2.5",
    "poolside/laguna-xs.2": "laguna-xs.2",
    "upstage/solar-open2-250b": "solar-open2-250b",
    "ai21labs/ai21-jamba2-3b": "jamba2-3b",
    "manifestai/brumby-14b-base": "brumby-14b",
}


def family_module(family: str):
    """The module implementing a model family (block/embed/head helpers used
    by the pipeline schedule and chunked losses)."""
    mods = {"llama": llama, "gpt2": gpt2, "moe": moe, "neox": neox,
            "mla_moe": mla, "lfm2_moe": lfm2, "mimo_v2": mimo_v2,
            "laguna": laguna, "solar_open2": solar_open2, "jamba": jamba,
            "brumby": brumby}
    if family not in mods:
        raise KeyError(f"unknown model family {family!r}")
    return mods[family]


def list_models() -> list[str]:
    return (sorted(gpt2.PRESETS) + sorted(llama.PRESETS) + sorted(moe.PRESETS)
            + sorted(neox.PRESETS) + sorted(mla.PRESETS)
            + sorted(lfm2.PRESETS) + sorted(mimo_v2.PRESETS)
            + sorted(laguna.PRESETS) + sorted(solar_open2.PRESETS)
            + sorted(jamba.PRESETS) + sorted(brumby.PRESETS))


def get_model(name: str, **overrides) -> ModelBundle:
    if name.startswith("hf:"):
        # AutoModelForCausalLM analogue (reference 01:57): build the family
        # config from the checkpoint's own config.json (models/auto.py)
        from .auto import config_from_hf

        family, config = config_from_hf(name[3:])
        if overrides:
            config = dataclasses.replace(config, **overrides)
        mod = family_module(family)
        return ModelBundle(
            name, config, mod.init, mod.apply, mod.param_logical_axes,
            family=family,
            **({"apply_with_aux": moe.apply_with_aux} if family == "moe" else {}))
    key = _HF_ALIASES.get(name.lower(), name.lower())
    if key in gpt2.PRESETS:
        config = gpt2.PRESETS[key]
        if overrides:
            config = dataclasses.replace(config, **overrides)
        return ModelBundle(key, config, gpt2.init, gpt2.apply,
                           gpt2.param_logical_axes, family="gpt2")
    if key in llama.PRESETS:
        config = llama.PRESETS[key]
        if overrides:
            config = dataclasses.replace(config, **overrides)
        return ModelBundle(key, config, llama.init, llama.apply,
                           llama.param_logical_axes, family="llama")
    if key in moe.PRESETS:
        config = moe.PRESETS[key]
        if overrides:
            config = dataclasses.replace(config, **overrides)
        return ModelBundle(key, config, moe.init, moe.apply,
                           moe.param_logical_axes, family="moe",
                           apply_with_aux=moe.apply_with_aux)
    if key in neox.PRESETS:
        config = neox.PRESETS[key]
        if overrides:
            config = dataclasses.replace(config, **overrides)
        return ModelBundle(key, config, neox.init, neox.apply,
                           neox.param_logical_axes, family="neox")
    if key in mla.PRESETS:
        config = mla.PRESETS[key]
        if overrides:
            config = dataclasses.replace(config, **overrides)
        return ModelBundle(key, config, mla.init, mla.apply,
                           mla.param_logical_axes, family="mla_moe")
    if key in lfm2.PRESETS:
        config = lfm2.PRESETS[key]
        if overrides:
            config = dataclasses.replace(config, **overrides)
        return ModelBundle(key, config, lfm2.init, lfm2.apply,
                           lfm2.param_logical_axes, family="lfm2_moe")
    if key in mimo_v2.PRESETS:
        config = mimo_v2.PRESETS[key]
        if overrides:
            config = dataclasses.replace(config, **overrides)
        return ModelBundle(key, config, mimo_v2.init, mimo_v2.apply,
                           mimo_v2.param_logical_axes, family="mimo_v2")
    if key in laguna.PRESETS:
        config = laguna.PRESETS[key]
        if overrides:
            config = dataclasses.replace(config, **overrides)
        return ModelBundle(key, config, laguna.init, laguna.apply,
                           laguna.param_logical_axes, family="laguna",
                           apply_with_aux=laguna.apply_with_aux)
    if key in solar_open2.PRESETS:
        config = solar_open2.PRESETS[key]
        if overrides:
            config = dataclasses.replace(config, **overrides)
        return ModelBundle(key, config, solar_open2.init, solar_open2.apply,
                           solar_open2.param_logical_axes,
                           family="solar_open2")
    if key in jamba.PRESETS:
        config = jamba.PRESETS[key]
        if overrides:
            config = dataclasses.replace(config, **overrides)
        return ModelBundle(key, config, jamba.init, jamba.apply,
                           jamba.param_logical_axes, family="jamba")
    if key in brumby.PRESETS:
        config = brumby.PRESETS[key]
        if overrides:
            config = dataclasses.replace(config, **overrides)
        return ModelBundle(key, config, brumby.init, brumby.apply,
                           brumby.param_logical_axes, family="brumby")
    raise ValueError(
        f"Unknown model {name!r}. Available: {', '.join(list_models())} "
        f"(HF aliases: {', '.join(sorted(_HF_ALIASES))})"
    )
