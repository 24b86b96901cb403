"""Minimal text sampling from any model in the zoo — a qualitative check
for trained / converted checkpoints.

Default mode re-runs the FULL forward over a fixed-size buffer per token
(any family, one compile) — the hermetic numerics reference. ``--kv-cache``
delegates to the serving runtime (``serve/``): the continuous-batching
paged-KV engine at n_slots=1 — the prompt through the chunk program, then
one-token decode steps over the paged cache, both the family's
``paged_decode_step`` — for the llama family incl. qwen3/olmo2/gemma2
wirings, gpt2, neox, moe and mla_moe (routed FFN drop-free per decoded
token; same greedy tokens, pinned per family by test). The real serving
path (multi-request, HTTP) lives at
``python -m distributed_training_guide_tpu.serve``.

    # hermetic (no tokenizer): raw token ids in, ids out
    python -m distributed_training_guide_tpu.models.sample \\
        -m llama-debug --prompt-ids 3,17,42 --steps 16
    # with a tokenizer cache: text in, text out
    python -m distributed_training_guide_tpu.models.sample \\
        -m gpt2 --pretrained /ckpts/gpt2-conv --prompt "The TPU" --steps 32
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


def make_sampler(bundle, temperature: float = 0.0, kv_cache: bool = False):
    """One compiled decode step per generation. Two modes:

    - recompute (default, any family): the full forward re-runs over a
      fixed buffer and the token at ``pos`` is written — O(steps x
      forward(prompt+steps));
    - ``kv_cache=True`` (families exporting ``paged_decode_step``: to
      serve, a family exports that one function, and ``pool_layout`` sizes
      its cache rows): the serving engine (serve/engine.py) at n_slots=1 —
      the prompt through the chunk program, then one single-token program
      per step attending over the paged cache — O(forward(prompt) + steps
      x token). Same
      greedy tokens as recompute (pinned per family by tests/test_sample.py);
      at temperature > 0 draws come from the engine's per-request
      fold_in(seed, position) stream (deterministic in ``rng``).

    Greedy when ``temperature == 0`` (a Python constant — each mode is its
    own single compile)."""

    def pick(logit, key):
        if temperature == 0.0:
            return jnp.argmax(logit)
        return jax.random.categorical(key, logit / temperature)

    max_pos = getattr(bundle.config, "max_position_embeddings", None)

    def check_length(n_prompt: int, steps: int) -> None:
        # the guard lives HERE, not only in the CLI main(): as a library,
        # an over-long generation would silently clamp gpt2's learned
        # position table (and the cache's dynamic_update_slice) under jit —
        # garbage tokens with no error
        if max_pos and n_prompt + steps > max_pos:
            raise ValueError(
                f"prompt ({n_prompt}) + steps ({steps}) exceeds the model's "
                f"max_position_embeddings ({max_pos})")

    if kv_cache:
        from .registry import family_module

        mod = family_module(bundle.family)
        if not hasattr(mod, "paged_decode_step"):
            raise ValueError(f"family {bundle.family!r} exports no "
                             f"paged_decode_step (the one hook the serving "
                             f"engine asks of a family); use kv_cache=False")
        engines: dict = {}

        def sample(params, prompt_ids, steps: int,
                   rng: Optional[jax.Array] = None) -> list[int]:
            from ..serve.api import generate_many
            from ..serve.engine import ServeEngine
            from ..serve.scheduler import Request

            rng = rng if rng is not None else jax.random.key(0)
            n = len(prompt_ids)
            check_length(n, steps)
            page = 16
            capacity = -(-(n + steps) // page) * page
            # one engine (== one compiled chunk/decode pair) per page-
            # rounded capacity; the engine holds its params so the id key
            # stays pinned to the live object
            eng = engines.get((id(params), capacity))
            if eng is None:
                eng = ServeEngine(bundle, params, n_slots=1, page_size=page,
                                  max_len=capacity)
                engines[(id(params), capacity)] = eng
            seed = int(jax.random.randint(rng, (), 0, 2**31 - 1))
            res = generate_many(eng, [Request(
                prompt_ids=[int(t) for t in prompt_ids],
                max_new_tokens=steps, temperature=temperature, seed=seed)])
            return res[0].token_ids

        return sample

    @partial(jax.jit, donate_argnums=(1,))
    def recompute_step(params, buf, pos, key):
        logits = bundle.apply(bundle.config, params, buf)
        logit = jax.lax.dynamic_index_in_dim(logits[0], pos - 1, axis=0,
                                             keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            buf, pick(logit, key).astype(buf.dtype)[None], pos, axis=1)

    def sample(params, prompt_ids, steps: int,
               rng: Optional[jax.Array] = None) -> list[int]:
        rng = rng if rng is not None else jax.random.key(0)
        n = len(prompt_ids)
        check_length(n, steps)
        buf = jnp.zeros((1, n + steps), jnp.int32)
        buf = buf.at[0, :n].set(jnp.asarray(prompt_ids, jnp.int32))
        for t in range(n, n + steps):
            rng, key = jax.random.split(rng)
            buf = recompute_step(params, buf, jnp.asarray(t), key)
        return [int(x) for x in buf[0]]

    return sample


def main(argv=None) -> None:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("-m", "--model-name", required=True)
    parser.add_argument("--prompt", default=None,
                        help="text prompt (needs the model's HF tokenizer "
                             "in the local cache)")
    parser.add_argument("--prompt-ids", default=None,
                        help="comma-separated token ids — the hermetic path")
    parser.add_argument("--steps", type=int, default=32)
    parser.add_argument("--temperature", type=float, default=0.0)
    parser.add_argument("--kv-cache", action="store_true",
                        help="prefill + cached one-token decode steps "
                             "(dense families) instead of full recompute")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--pretrained", default=None, metavar="DIR",
                        help="converted checkpoint dir (models/hf_convert); "
                             "random init otherwise")
    args = parser.parse_args(argv)
    if (args.prompt is None) == (args.prompt_ids is None):
        raise SystemExit("pass exactly one of --prompt / --prompt-ids")

    from ..parallel import make_mesh, make_plan
    from .registry import get_model

    bundle = get_model(args.model_name, dtype=jnp.float32)
    tokenizer = None
    if args.prompt is not None:
        from ..data import get_tokenizer

        tokenizer = get_tokenizer(args.model_name)
        prompt_ids = tokenizer(args.prompt)["input_ids"]
        if prompt_ids and isinstance(prompt_ids[0], list):
            prompt_ids = prompt_ids[0]  # batched tokenizers (ByteTokenizer)
    else:
        prompt_ids = [int(t) for t in args.prompt_ids.split(",")]

    # over-long generations are refused by check_length inside the sampler
    # (the library guard) — no CLI copy to drift out of sync

    if args.pretrained:
        from .hf_convert import load_pretrained

        plan = make_plan("single", make_mesh(devices=jax.devices()[:1]))
        shapes = jax.eval_shape(
            lambda: bundle.init(bundle.config, jax.random.key(0)))
        shardings = plan.param_shardings(
            bundle.param_logical_axes(bundle.config), shapes)
        params = load_pretrained(bundle, shardings, args.pretrained)
    else:
        params = bundle.init(bundle.config, jax.random.key(args.seed))

    sample = make_sampler(bundle, temperature=args.temperature,
                          kv_cache=args.kv_cache)
    out = sample(params, prompt_ids, args.steps,
                 rng=jax.random.key(args.seed))
    if tokenizer is not None:
        print(tokenizer.decode(out))
    else:
        print(",".join(str(t) for t in out))


if __name__ == "__main__":
    main()
