"""Sampler correctness: the jit decode step must equal a naive per-step
argmax reference (position indexing into the fixed buffer is where an
off-by-one would hide)."""
import jax
import jax.numpy as jnp
import numpy as np

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.models.sample import make_sampler, main


def _chunk_program_logits(bundle, params, prompt, chunk=16, page=16):
    """The serve path's one prefill, as the engine calls it: the prompt
    padded to ``chunk`` through ``ModelPrograms.chunk_for`` into one slot's
    pages. Returns (last real position's logits [V], the k pool)."""
    from distributed_training_guide_tpu.serve.engine import ModelPrograms

    programs = ModelPrograms(bundle, params)
    pages = programs.init_device_pages(1 + chunk // page, page)
    ids = np.zeros((1, chunk), np.int32)
    ids[0, :len(prompt)] = prompt
    table = jnp.arange(1, 1 + chunk // page, dtype=jnp.int32)[None]
    logit, pools = programs.chunk_for(chunk)(
        programs.params, pages, jnp.asarray(ids),
        jnp.zeros(1, jnp.int32), table,
        jnp.asarray(len(prompt) - 1, jnp.int32),
        jnp.asarray([len(prompt)], jnp.int32))
    return logit, pools["k"]


def test_greedy_matches_naive_reference():
    bundle = get_model("llama-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    prompt = [3, 17, 42]
    steps = 5
    out = make_sampler(bundle)(params, prompt, steps)
    assert out[:3] == prompt and len(out) == len(prompt) + steps

    # naive reference: grow a python list, argmax the last position's
    # logits over the same zero-padded buffer the sampler uses
    ids = list(prompt)
    for t in range(steps):
        buf = np.zeros((1, len(prompt) + steps), np.int32)
        buf[0, :len(ids)] = ids
        logits = np.asarray(bundle.apply(bundle.config, params,
                                         jnp.asarray(buf)))
        ids.append(int(np.argmax(logits[0, len(ids) - 1])))
    assert out == ids


def test_temperature_sampling_is_seeded_and_in_vocab():
    bundle = get_model("gpt2-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(1))
    sample = make_sampler(bundle, temperature=0.8)
    a = sample(params, [5, 6], 6, rng=jax.random.key(7))
    b = sample(params, [5, 6], 6, rng=jax.random.key(7))
    assert a == b                       # same seed, same draw
    assert all(0 <= t < bundle.config.vocab_size for t in a)


def test_kv_cache_matches_recompute():
    """The cached decode (the chunk program + one-token steps over the
    paged cache) must produce the same greedy tokens as the full-recompute
    sampler, and the chunk program's logits must match the plain forward's
    last position."""
    bundle = get_model("llama-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    prompt = [3, 17, 42, 7]
    steps = 6

    slow = make_sampler(bundle)(params, prompt, steps)
    fast = make_sampler(bundle, kv_cache=True)(params, prompt, steps)
    assert fast == slow

    logit, kp = _chunk_program_logits(bundle, params, prompt)
    ids = jnp.asarray(prompt, jnp.int32)[None, :]
    full = bundle.apply(bundle.config, params, ids)
    np.testing.assert_allclose(np.asarray(logit), np.asarray(full[0, -1]),
                               rtol=1e-5, atol=1e-5)
    # the cache is the page pool: the prompt's rows in the slot's page, the
    # chunk's pad tail nowhere in it
    assert kp.shape == (2, 2, 16, bundle.config.num_kv_heads,
                        bundle.config.head_size)
    kp = np.asarray(kp)
    assert np.abs(kp[:, 1, :len(prompt)]).min(axis=(2, 3)).all()
    assert not kp[:, 1, len(prompt):].any()


def test_kv_cache_gqa_qwen_bias_family():
    """The cache path through a GQA + QKV-bias config (the biases ride the
    projections before rope; kv_heads < heads exercises grouped attention
    over the cache)."""
    bundle = get_model("qwen2.5-0.5b", vocab_size=256, hidden_size=64,
                       intermediate_size=128, num_layers=2, num_heads=4,
                       num_kv_heads=2, max_position_embeddings=128,
                       dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(2))
    prompt = [9, 11]
    slow = make_sampler(bundle)(params, prompt, 5)
    fast = make_sampler(bundle, kv_cache=True)(params, prompt, 5)
    assert fast == slow


def test_kv_cache_neox_matches_recompute():
    """The NeoX cache path: parallel-residual blocks and PARTIAL rotary
    (only the first rotary_ndims of each head rotate) through prefill +
    cached decode must reproduce the recompute sampler's greedy tokens."""
    bundle = get_model("neox-debug", dtype=jnp.float32)
    assert 0 < bundle.config.rotary_ndims < bundle.config.head_size
    params = bundle.init(bundle.config, jax.random.key(3))
    prompt = [8, 21, 5]
    slow = make_sampler(bundle)(params, prompt, 6)
    fast = make_sampler(bundle, kv_cache=True)(params, prompt, 6)
    assert fast == slow

    # sequential-residual wiring too
    seq_bundle = get_model("neox-debug", use_parallel_residual=False,
                           dtype=jnp.float32)
    seq_params = seq_bundle.init(seq_bundle.config, jax.random.key(4))
    slow = make_sampler(seq_bundle)(seq_params, prompt, 4)
    fast = make_sampler(seq_bundle, kv_cache=True)(seq_params, prompt, 4)
    assert fast == slow


def test_kv_cache_gpt2_matches_recompute():
    """gpt2's cache path: no rope (the learned position row is added at
    embed, including for the single decode token) — cached greedy tokens
    must equal the recompute sampler's."""
    bundle = get_model("gpt2-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(5))
    prompt = [7, 19]
    slow = make_sampler(bundle)(params, prompt, 6)
    fast = make_sampler(bundle, kv_cache=True)(params, prompt, 6)
    assert fast == slow


def test_kv_cache_qwen3_qk_norm_matches_recompute():
    """Qwen3's per-head q/k RMSNorm rides attention_sublayer, so the cache
    path (k written post-norm+rope, like HF's cache) must reproduce the
    recompute sampler's greedy tokens."""
    bundle = get_model("qwen3-0.6b", vocab_size=256, hidden_size=64,
                       intermediate_size=128, num_layers=2, num_heads=4,
                       num_kv_heads=2, head_dim=16,
                       max_position_embeddings=128, dtype=jnp.float32)
    assert bundle.config.qk_norm
    params = bundle.init(bundle.config, jax.random.key(7))
    prompt = [4, 31]
    slow = make_sampler(bundle)(params, prompt, 5)
    fast = make_sampler(bundle, kv_cache=True)(params, prompt, 5)
    assert fast == slow


def test_kv_cache_olmo2_post_norm_matches_recompute():
    """OLMo-2's post-norm wiring through the cache path: the decode body's
    residuals norm the sublayer OUTPUTS; cached greedy must equal recompute."""
    bundle = get_model("olmo2-7b", vocab_size=256, hidden_size=64,
                       intermediate_size=128, num_layers=2, num_heads=4,
                       num_kv_heads=2, max_position_embeddings=128,
                       dtype=jnp.float32)
    assert bundle.config.post_norm and bundle.config.qk_norm == "flat"
    params = bundle.init(bundle.config, jax.random.key(8))
    prompt = [6, 17, 2]
    slow = make_sampler(bundle)(params, prompt, 5)
    fast = make_sampler(bundle, kv_cache=True)(params, prompt, 5)
    assert fast == slow


def test_kv_cache_gemma2_matches_recompute():
    """Gemma-2's cache path: sandwich norms, softcaps, score scale, and the
    per-layer window column threaded through the decode scans — cached
    greedy must equal the recompute sampler past the sliding window."""
    bundle = get_model("gemma2-2b", vocab_size=256, hidden_size=64,
                       intermediate_size=128, num_layers=2, num_heads=4,
                       num_kv_heads=2, head_dim=16,
                       layer_windows=(8, 0), query_pre_attn_scalar=24.0,
                       max_position_embeddings=128, dtype=jnp.float32)
    assert bundle.config.sandwich_norm and bundle.config.layer_windows
    params = bundle.init(bundle.config, jax.random.key(9))
    prompt = list(range(2, 14))            # prompt longer than the window
    slow = make_sampler(bundle)(params, prompt, 6)
    fast = make_sampler(bundle, kv_cache=True)(params, prompt, 6)
    assert fast == slow


def test_kv_cache_moe_matches_recompute():
    """The MoE cache path: routed FFN per decoded token (drop-free expert
    dispatch in chunk and decode) through the shared cache contract. The
    recompute side uses capacity_factor = num_experts so IT is drop-free
    too — with zero drops on both sides, per-token routing is independent
    of the other buffer rows and cached greedy must equal recompute."""
    bundle = get_model("moe-debug", dtype=jnp.float32, capacity_factor=4.0)
    params = bundle.init(bundle.config, jax.random.key(6))
    prompt = [12, 3, 44]
    slow = make_sampler(bundle)(params, prompt, 6)
    fast = make_sampler(bundle, kv_cache=True)(params, prompt, 6)
    assert fast == slow

    # the chunk program's logits == plain forward last position (router
    # included)
    logit, _ = _chunk_program_logits(bundle, params, prompt)
    ids = jnp.asarray(prompt, jnp.int32)[None, :]
    full = bundle.apply(bundle.config, params, ids)
    np.testing.assert_allclose(np.asarray(logit), np.asarray(full[0, -1]),
                               rtol=1e-5, atol=1e-5)


def test_kv_cache_mla_moe_matches_recompute():
    """The latent-attention family through ``kv_cache=True``: one hook
    (``paged_decode_step``) is all the sampler asks, so the family that
    never had a contiguous cache serves here like the others — cached
    greedy tokens must equal the recompute sampler's."""
    bundle = get_model("mla-moe-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(12))
    prompt = [14, 2, 55, 9]
    slow = make_sampler(bundle)(params, prompt, 6)
    fast = make_sampler(bundle, kv_cache=True)(params, prompt, 6)
    assert fast == slow


def test_kv_cache_qwen2_moe_shared_expert_matches_recompute():
    """The shared expert (+ QKV biases) through the MoE cache path: the
    sigmoid-gated dense branch runs per decoded token alongside the
    drop-free routed dispatch — cached greedy must equal recompute."""
    bundle = get_model("qwen1.5-moe-a2.7b", vocab_size=256, hidden_size=64,
                       intermediate_size=48, shared_expert_intermediate=80,
                       num_layers=2, num_heads=4, num_kv_heads=2,
                       num_experts=4, experts_per_token=2,
                       max_position_embeddings=128, capacity_factor=4.0,
                       dtype=jnp.float32)
    assert bundle.config.shared_expert_intermediate and bundle.config.attn_bias
    params = bundle.init(bundle.config, jax.random.key(11))
    prompt = [9, 40, 3]
    slow = make_sampler(bundle)(params, prompt, 6)
    fast = make_sampler(bundle, kv_cache=True)(params, prompt, 6)
    assert fast == slow


def test_sampler_library_length_guard():
    """make_sampler used as a LIBRARY must refuse prompt+steps past the
    position table (both modes) — the CLI-only check left silent jit
    clamping (ADVICE r4)."""
    import pytest

    bundle = get_model("gpt2-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    max_pos = bundle.config.max_position_embeddings
    for kv in (False, True):
        with pytest.raises(ValueError, match="max_position_embeddings"):
            make_sampler(bundle, kv_cache=kv)(params, [1, 2], max_pos)


def test_cli_hermetic_path(capsys):
    main(["-m", "llama-debug", "--prompt-ids", "1,2,3", "--steps", "4"])
    out = capsys.readouterr().out.strip().split(",")
    assert len(out) == 7 and all(t.isdigit() for t in out)


def test_cli_refuses_past_position_table():
    """The CLI has no guard of its own anymore (it drifted against the
    library's): make_sampler's check_length surfaces through main()."""
    import pytest

    with pytest.raises(ValueError, match="max_position_embeddings"):
        main(["-m", "gpt2-debug", "--prompt-ids", "1,2",
              "--steps", "4000"])


def test_cli_text_prompt_via_byte_tokenizer_fallback(capsys):
    """--prompt with no HF tokenizer cached falls back to ByteTokenizer,
    whose batched [[ids]] output must be unwrapped, not crash."""
    main(["-m", "llama-debug", "--prompt", "hi", "--steps", "2"])
    assert capsys.readouterr().out.strip()
