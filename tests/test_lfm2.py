"""The ``lfm2_moe`` family (``models/lfm2.py``) on the CPU, float32, debug
widths: a stack whose layers differ in kind (dense + conv, experts +
attention, experts + conv), the conv state's life beside the KV pages (a
reused slot, chunks that end inside a page, preemption and resume, a
prefix-cache hit, a fork, the host tier, the fused horizon), 64-wide heads
packed two to a pool row through both attends, what the engine refuses, and
the scope names. ``lfm2.apply`` is the oracle here; it is held to the plain
reference in ``tests/benchmarks/test_benchmark_lfm2_moe.py``."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import get_model, lfm2
from distributed_training_guide_tpu.serve import (Request, ServeEngine,
                                                  kv_pages)
from distributed_training_guide_tpu.serve.api import generate_many

PAGE = 8
LOGIT_TOL = 2e-5


@pytest.fixture(scope="module")
def model():
    """The debug preset with an untied head and matrices 8x the init's: the
    greedy stream then moves with every layer's output, so a state one token
    out of place changes the tokens that follow."""
    bundle = get_model("lfm2-moe-debug", dtype=jnp.float32,
                       tie_word_embeddings=False)
    params = bundle.init(bundle.config, jax.random.key(0))
    params = jax.tree.map(
        lambda a: a * 8 if a.ndim >= 2 and a.shape[-1] > 3 else a, params)
    return bundle, params


def prompts(n, length, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, size=length).tolist() for _ in range(n)]


def request(prompt, n=20, **kw):
    return Request(prompt_ids=prompt, max_new_tokens=n, temperature=0.0, **kw)


def engine(model, **kw):
    bundle, params = model
    kw = {"n_slots": 2, "page_size": PAGE, "max_len": 96, "prefill_chunk": 5,
          **kw}
    return ServeEngine(bundle, params, **kw)


def alone(model, prompt, n=20):
    """The request served by itself: one slot, no prefix cache."""
    eng = engine(model, n_slots=1, prefix_cache=False)
    return list(generate_many(eng, [request(prompt, n)])[0].generated_ids)


def served(eng, reqs):
    return [list(r.generated_ids) for r in generate_many(eng, reqs)]


# ---- the configuration ------------------------------------------------------
def test_layer_order_comes_from_layer_types_and_num_dense_layers():
    cfg = lfm2.PRESETS["lfm2-moe-debug"]
    assert cfg.layer_table() == (("conv", 0, True, 0),
                                 ("full_attention", 0, False, 0),
                                 ("conv", 1, False, 1))
    assert (cfg.num_layers, cfg.num_kv_layers, cfg.num_conv_layers) == (3, 1, 2)
    big = lfm2.PRESETS["lfm2-24b-a2b"]
    kinds = [row[0] for row in big.layer_table()]
    assert (len(kinds), kinds.count("conv"), kinds.count("full_attention")) \
        == (40, 30, 10)
    assert kinds[:7] == ["conv", "conv", "full_attention", "conv", "conv",
                         "conv", "full_attention"]
    assert [row[2] for row in big.layer_table()].count(True) == 2
    assert big.head_size == 64 and big.kv_pack == 2
    assert big.kv_layout() == {"k": (4, 128), "v": (4, 128)}
    assert big.state_layout() == (30, 2, 2048)
    assert 23.5e9 < big.num_params() < 24.5e9
    assert 2.0e9 < big.num_active_params() < 2.6e9
    # another order is another table, not another model name
    flipped = dataclasses.replace(cfg, layer_types=("full_attention", "conv"),
                                  num_dense_layers=0)
    assert flipped.layer_table() == (("full_attention", 0, False, 0),
                                     ("conv", 0, False, 1))
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(cfg, layer_types=("conv", "mamba"))


def test_init_matches_the_logical_axes_and_the_parameter_count():
    cfg = lfm2.PRESETS["lfm2-moe-debug"]
    params = jax.eval_shape(lambda: lfm2.init(cfg, jax.random.key(0)))
    axes = lfm2.param_logical_axes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    for leaf, ax in zip(jax.tree.leaves(params), jax.tree.leaves(
            axes, is_leaf=lambda x: isinstance(x, tuple))):
        assert leaf.ndim == len(ax)
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.num_params()


def test_only_the_attention_layers_have_pages_and_the_state_lies_beside():
    cfg = dataclasses.replace(lfm2.PRESETS["lfm2-moe-debug"],
                              dtype=jnp.float32)
    pages = kv_pages.init_pages(cfg, 5, PAGE)
    assert pages["k"].shape == pages["v"].shape == (1, 5, PAGE, 1, 128)
    assert pages["state"].shape == (2, 5, 2, 64)
    assert kv_pages.pool_nbytes(pages) == kv_pages.kv_page_bytes(
        cfg, page_size=PAGE, n_pages=5)
    assert not kv_pages.is_latent(cfg)
    # the kernel's gate is asked about the pool ROW: 128 wide
    assert kv_pages.resolve_attend_for(cfg, "flash", PAGE)[0] == "flash"
    real = lfm2.PRESETS["lfm2-24b-a2b"]
    # 20 KB of k and v a token published; a page's state 8 KB a conv layer
    assert kv_pages.kv_page_bytes(real, page_size=1, kv_dtype="bf16") \
        == 10 * 2 * 8 * 64 * 2 + 30 * 2 * 2048 * 2


# ---- the paged step against the whole-sequence forward -----------------------
@pytest.mark.parametrize("chunk,impl", [(5, "xla"), (8, "xla"), (13, "xla"),
                                        (5, "flash"), (16, "flash")])
def test_chunks_then_decode_match_the_forward_at_every_served_position(
        model, chunk, impl):
    """A 21-token prompt through chunks that end inside a page (5, 13), on a
    page edge (8) or past the prompt (16), then 9 decode steps: the logits
    at the prompt's last position and at every decoded one are the plain
    forward's. ``flash`` is the kernel's path, interpreted, over the packed
    rows; ``xla`` the gather path over the same rows."""
    bundle, params = model
    cfg = bundle.config
    tokens = prompts(1, 30, seed=3)[0]
    n_prompt = 21
    want = lfm2.apply(cfg, params, jnp.asarray([tokens]))[0]
    pages = kv_pages.init_pages(cfg, 6, PAGE)
    table = jnp.arange(1, 6, dtype=jnp.int32)[None]

    @jax.jit
    def step(pools, ids, pos, nv):
        return lfm2.paged_decode_step(
            cfg, params, ids, pos, pools,
            kv_pages.make_attend(table, pos, impl=impl, n_valid=nv),
            last_index=nv[0] - 1)

    start, got = 0, []
    while start < n_prompt:
        real = min(chunk, n_prompt - start)
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :real] = tokens[start:start + real]
        logits, cache = step(pages, jnp.asarray(ids), jnp.asarray([start]),
                             jnp.asarray([real]))
        cache.pop("routing")
        pages, start = cache, start + real
    got.append(logits[0])
    for pos in range(n_prompt, len(tokens)):
        logits, cache = step(pages, jnp.asarray([[tokens[pos]]]),
                             jnp.asarray([pos]), jnp.asarray([1]))
        routing = cache.pop("routing")
        pages = cache
        got.append(logits[0])
    diff = jnp.max(jnp.abs(jnp.stack(got) - want[n_prompt - 1:]))
    assert float(diff) < LOGIT_TOL * float(jnp.max(jnp.abs(want)))
    # two expert layers x one slot x top-2: [routed, held, touched, fullest]
    assert routing.tolist()[:2] == [4, 4] and 2 <= int(routing[2]) <= 4


def test_write_state_puts_the_state_after_each_pages_last_token():
    """A chunk of 13 tokens from position 5 over pages of 8 writes pages 0,
    1 and 2 (positions 5-7, 8-15, 16-17): each gets the two rows before its
    last valid token's end; the pad tail and untouched columns go to the
    trash page."""
    rows, width, t = 2, 4, 16
    state = jnp.zeros((3, 7, rows, width))
    history = jnp.arange((rows + t) * width, dtype=jnp.float32).reshape(
        1, rows + t, width)             # row r holds g of token r - 2
    tables = jnp.asarray([[4, 2, 6, 5]], jnp.int32)
    out = kv_pages.write_state(state, 1, PAGE, history, tables=tables,
                               lengths=jnp.asarray([5]),
                               n_valid=jnp.asarray([13]))
    after = lambda pos: history[0, pos - 5 + 1: pos - 5 + 3]
    np.testing.assert_array_equal(out[1, 4], after(7))
    np.testing.assert_array_equal(out[1, 2], after(15))
    np.testing.assert_array_equal(out[1, 6], after(17))
    assert not out[1, 5].any() and not out[0].any() and not out[2].any()
    got = kv_pages.read_state(out, 1, PAGE, tables=tables,
                              lengths=jnp.asarray([18]))
    np.testing.assert_array_equal(got[0], after(17))
    # a sequence with nothing cached reads zeros whatever its page holds
    fresh = kv_pages.read_state(out, 1, PAGE, tables=tables,
                                lengths=jnp.asarray([0]))
    assert not fresh.any()


# ---- the state's life through the engine -------------------------------------
def test_engine_serves_the_forwards_greedy_stream(model):
    bundle, params = model
    prompt = prompts(1, 3 * PAGE)[0]
    ids = list(prompt)
    for _ in range(12):
        logits = bundle.apply(bundle.config, params, jnp.asarray([ids]))
        ids.append(int(jnp.argmax(logits[0, -1])))
    assert alone(model, prompt, 12) == ids[len(prompt):]
    assert len(set(ids[len(prompt):])) > 6      # not one token over and over


def test_a_reused_slot_starts_from_zero_and_a_hit_ends_at_a_full_page(model):
    """One slot, three requests in turn: the second shares a page and a half
    with the first (the benchmark's warm-up pair), the third nothing. Each
    is the request served alone; the hit is the one FULL page, and nothing
    is forked (a page's state row is the state at its last token)."""
    a, c = prompts(2, 3 * PAGE)
    b = a[: PAGE + PAGE // 2] + c[:PAGE]
    eng = engine(model, n_slots=1)
    got = [served(eng, [request(p)])[0] for p in (a, b, c)]
    assert got == [alone(model, p) for p in (a, b, c)]
    stats = eng.stats()
    assert (stats["prefix_hits"], stats["prefix_tokens_shared"],
            stats["cow_forks"]) == (1, PAGE, 0)


def test_the_state_left_by_a_slots_last_owner_is_a_fault_the_stream_shows(
        model, monkeypatch):
    """The sabotage of the test above: a state read that does not start a
    new sequence from zero serves the second request another stream."""
    real = kv_pages.read_state

    def stale(state, layer, page, *, tables, lengths):
        return real(state, layer, page, tables=tables,
                    lengths=jnp.maximum(lengths, 1))
    monkeypatch.setattr(kv_pages, "read_state", stale)
    a, c = prompts(1, 3 * PAGE)[0], prompts(1, 3, seed=9)[0]
    eng = engine(model, n_slots=1, prefix_cache=False)
    first, second = (served(eng, [request(p)])[0] for p in (a, c))
    monkeypatch.undo()
    assert first == alone(model, a)         # zero pages: nothing stale yet
    # the stale rows enter the first two tokens of a three-token prompt
    assert second != alone(model, c)
    sound = engine(model, n_slots=1, prefix_cache=False)
    assert [served(sound, [request(p)])[0] for p in (a, c)] == [first, alone(
        model, c)]


def test_preempted_sequences_resume_with_their_state(model):
    ps = prompts(3, 2 * PAGE + 3, seed=1)
    eng = engine(model, n_slots=3, n_pages=13, prefix_cache=False)
    got = served(eng, [request(p, 24) for p in ps])
    assert eng.stats()["preemptions"] >= 1
    assert got == [alone(model, p, 24) for p in ps]


def test_the_host_tier_spills_and_restores_the_state_with_the_pages(model):
    ps = prompts(3, 2 * PAGE + 3, seed=1)
    eng = engine(model, n_slots=3, n_pages=13, host_tier_bytes=1 << 24)
    got = served(eng, [request(p, 24) for p in ps])
    stats = eng.stats()
    assert stats["preemptions"] >= 1 and stats["restore_hits"] >= 1
    assert got == [alone(model, p, 24) for p in ps]


def test_the_fused_horizon_carries_the_state_pool(model):
    ps = prompts(2, 2 * PAGE + 5, seed=2)
    eng = engine(model, decode_horizon=4)
    assert served(eng, [request(p) for p in ps]) == [alone(model, p)
                                                     for p in ps]


def test_a_fork_copies_the_state_row_with_the_k_and_v_pages():
    cfg = dataclasses.replace(lfm2.PRESETS["lfm2-moe-debug"],
                              dtype=jnp.float32)
    rng = np.random.default_rng(4)
    pools = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
        kv_pages.init_pages(cfg, 6, PAGE))
    forked = jax.jit(kv_pages.copy_pages)(pools, jnp.asarray(3),
                                          jnp.asarray(5))
    for name in ("k", "v", "state"):
        np.testing.assert_array_equal(forked[name][:, 5], pools[name][:, 3])
        keep = [0, 1, 2, 3, 4]
        np.testing.assert_array_equal(forked[name][:, keep],
                                      pools[name][:, keep])


def test_the_kernels_path_serves_the_gather_paths_tokens(model):
    """head_dim 64 through ``paged_flash_attend`` (interpreted here): two kv
    heads a 128-wide row, each query head in its half."""
    ps = prompts(2, 2 * PAGE + 5, seed=5)
    want = served(engine(model, attend_impl="xla"), [request(p) for p in ps])
    got = served(engine(model, attend_impl="flash"), [request(p) for p in ps])
    assert got == want == [alone(model, p) for p in ps]


def test_a_query_tile_too_large_for_vmem_goes_in_blocks():
    from distributed_training_guide_tpu.ops import paged_decode

    # the cell's chunk: 1,024 tokens, 8 query heads a packed row, 4 rows
    assert paged_decode._query_block(1024, 8, 4, 128, jnp.bfloat16,
                                     jnp.bfloat16) == 128
    # decode16's chunk and every decode step go whole, as before
    assert paged_decode._query_block(512, 1, 32, 128, jnp.bfloat16,
                                     jnp.bfloat16) == 512
    assert paged_decode._query_block(1, 8, 4, 128, jnp.bfloat16,
                                     jnp.bfloat16) == 1
    rng = np.random.default_rng(6)
    hq, hkv, d, t, page = 4, 1, 128, 2048, 8
    assert paged_decode._query_block(t, hq // hkv, hkv, d, jnp.float32,
                                     jnp.float32) < t
    pool = jnp.asarray(rng.normal(size=(1, 3, page, hkv, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, 16, hq, d)), jnp.float32)
    tables = jnp.asarray([[1, 2]], jnp.int32)
    whole = paged_decode.paged_flash_attend(q, pool, pool, 0, tables,
                                            jnp.asarray([0]), interpret=True)
    halves = jnp.concatenate([
        paged_decode.paged_flash_attend(q[:, i:i + 8], pool, pool, 0, tables,
                                        jnp.asarray([i]), interpret=True)
        for i in (0, 8)], axis=1)
    np.testing.assert_allclose(whole, halves, atol=1e-5)


# ---- what the engine refuses, and what the trace names ------------------------
@pytest.mark.parametrize("option,kwargs", [
    ("kv_dtype='int8'", {"kv_dtype": "int8"}),
    ("weight_dtype='int8'", {"weight_dtype": "int8"}),
    ("max_adapters", {"max_adapters": 2}),
    ("speculate", {"speculate": "ngram"}),
])
def test_engine_refuses_by_the_options_name(model, option, kwargs):
    assert option in lfm2.SERVE_REFUSES
    with pytest.raises(ValueError, match=re.escape(option)):
        engine(model, **kwargs)


def test_the_mesh_paths_are_refused_too(model, eight_devices):
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan
    from distributed_training_guide_tpu.serve.disagg import DisaggEngine

    bundle, params = model
    plan = make_plan("tp", make_mesh(tp=2, devices=eight_devices[:2]))
    with pytest.raises(ValueError, match="plan / shard_kv"):
        engine(model, plan=plan, shard_kv=True)
    with pytest.raises(ValueError, match="disaggregation"):
        DisaggEngine(bundle, params, n_slots=2, page_size=PAGE, max_len=64)


def test_decode_program_names_the_conv_operator_inside_attn(model):
    eng = engine(model)
    arrays = {k: jnp.asarray(v)
              for k, v in eng.scheduler.decode_arrays().items()}
    text = eng._decode_fn.lower(
        eng.params, eng.pages,
        *(arrays[k] for k in ("tokens", "lengths", "tables", "seeds",
                              "temps", "top_ks", "top_ps", "actives"))
    ).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]+)"', text))
    parts = set().union(*(p.split("/") for p in paths))
    assert {"layers", "attn", "conv", "attend", "kv_write", "mlp", "router",
            "experts", "loss_head", "sample"} <= parts
    # the sub-scope lies inside `attn`, and the state's write under kv_write
    assert any("/attn/conv/" in p for p in paths)
    assert not any("/conv/" in p and "/attn/" not in p for p in paths)
    assert any("/attn/kv_write/" in p and "scatter" in p for p in paths)
    assert "module @jit_serve_decode" in text
