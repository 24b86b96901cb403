"""The latent-attention family (``models/mla.py``) on the CPU at a small size
(hidden 64, 4 heads, q_lora 32, kv_lora 16, nope 8 / rope 8 / v 16, 8 experts
top-2 + a shared one, 2 layers, float32): the serve path's logits against the
benchmark's plain reference (``benchmarks/reference/mla_moe.py``, which
imports nothing of the program), the two forms of the attention against each
other, the latent kernel interpreted against the gathered rows, the pool's
layout and bytes, and what ``ServeEngine`` refuses for this family."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import weights_mla_moe as weights  # noqa: E402
from benchmarks.reference import mla_moe as ref  # noqa: E402
from benchmarks.runners import _mla_moe  # noqa: E402
from distributed_training_guide_tpu.models import get_model, mla  # noqa: E402
from distributed_training_guide_tpu.models.registry import list_models  # noqa: E402
from distributed_training_guide_tpu.ops.paged_decode import (  # noqa: E402
    latent_decode_eligible, paged_latent_attend)
from distributed_training_guide_tpu.serve import (Request, ServeEngine,  # noqa: E402
                                                  kv_pages)

LOGIT_TOL = 2e-5     # float32 against float32: summation order alone
PAGE, PROMPT, N_NEW = 16, 70, 12


@pytest.fixture(scope="module")
def model():
    cfg = json.loads((ROOT / "tests" / "benchmarks" / "debug" / "configs"
                      / "debug-mla-moe.json").read_text())
    cfg = dict(cfg, compute_dtype="float32", weights_dtype="float32")
    w = weights.stacked_weights(cfg, weights.seed_key(7), jnp.float32)
    bundle = _mla_moe.bundle_for(cfg, "debug")
    return cfg, w, bundle, _mla_moe.to_program(w)


def paged_logits(bundle, params, tokens, impl, chunk):
    """Teacher-forced through the paged path as the engine drives it: the
    prompt in chunks of ``chunk`` (decompressed), then one token a step
    (absorbed). Returns the logits after the prompt and after each step."""
    config = bundle.config
    n_pages = 2 + -(-len(tokens) // PAGE)
    pages = kv_pages.init_pages(config, n_pages, PAGE)
    table = jnp.arange(1, n_pages, dtype=jnp.int32)[None]
    out, start = [], 0
    step = jax.jit(lambda p, kp, vp, ids, pos, nv: mla.paged_decode_step(
        config, p, ids, pos, {"k": kp, "v": vp},
        kv_pages.make_attend(table, pos, impl=impl, n_valid=nv),
        last_index=nv[0] - 1))
    while start < PROMPT:
        real = min(chunk, PROMPT - start)
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :real] = tokens[start:start + real]
        logits, cache = step(params, pages["k"], pages["v"], jnp.asarray(ids),
                             jnp.asarray([start]), jnp.asarray([real]))
        pages = {"k": cache["k"], "v": cache["v"]}
        start += real
    out.append(logits[0])
    for pos in range(PROMPT, len(tokens) - 1):
        logits, cache = step(params, pages["k"], pages["v"],
                             jnp.asarray([[tokens[pos]]]), jnp.asarray([pos]),
                             jnp.asarray([1]))
        pages = {"k": cache["k"], "v": cache["v"]}
        assert cache["routing"].shape == (4,)
        out.append(logits[0])
    return jnp.stack(out)


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_paged_prefill_then_decode_logits_match_the_reference(model, impl):
    """Prompt 70 > the config's original length of 64: the decode steps run
    past the boundary where g(t) and YaRN's long wavelengths change."""
    cfg, w, bundle, params = model
    tokens = np.random.default_rng(4).integers(
        0, cfg["vocab_size"], PROMPT + N_NEW).astype(np.int32)
    want = ref.forward_logits(cfg, lambda l: jax.tree.map(
        lambda a: a[l], w["layers"]), w["top"], tokens[None])[0]
    got = paged_logits(bundle, params, tokens, impl, chunk=32)
    assert got.shape == (N_NEW, cfg["vocab_size"])
    assert float(jnp.max(jnp.abs(got - want[PROMPT - 1: -1]))) < LOGIT_TOL


def test_a_padded_chunk_writes_the_latent_rows_narrow_chunks_write(model):
    """48 tokens as ONE chunk padded to 64 and as three chunks of 16 (whose
    later chunks attend the rows the earlier ones wrote): the same latent
    rows in the slot's pages, the pad tail nowhere but the trash page, and
    the last token's logits those of the full forward ``mla.apply``."""
    cfg, w, bundle, params = model
    config = bundle.config
    tokens = np.random.default_rng(5).integers(0, cfg["vocab_size"], 48)
    want = mla.apply(config, params, jnp.asarray(tokens[None]))[0, -1]
    table = jnp.arange(1, 6, dtype=jnp.int32)[None]

    def prefill(chunk):
        pages = kv_pages.init_pages(config, 6, PAGE)
        assert pages["k"].shape == (2, 6, PAGE, 1, 128) \
            and pages["v"].shape == (2, 6, PAGE, 1, 16)
        for start in range(0, 48, chunk):
            real = min(chunk, 48 - start)
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :real] = tokens[start:start + real]
            nv = jnp.asarray([real])
            logits, pages = mla.paged_decode_step(
                config, params, jnp.asarray(ids), jnp.asarray([start]), pages,
                kv_pages.make_attend(table, jnp.asarray([start]), impl="xla",
                                     n_valid=nv), last_index=real - 1)
        return logits[0], pages["k"], pages["v"]

    logits, kp, vp = prefill(64)
    assert float(jnp.max(jnp.abs(logits - want))) < LOGIT_TOL
    narrow_logits, nkp, nvp = prefill(16)
    assert float(jnp.max(jnp.abs(narrow_logits - want))) < LOGIT_TOL
    # the rope key's pad columns stay zero; the rows are the narrow chunks'
    assert float(jnp.max(jnp.abs(kp[..., 8:]))) == 0.0
    assert float(jnp.max(jnp.abs(vp[:, 1:4]))) > 1e-3
    assert np.allclose(vp[:, 1:4], nvp[:, 1:4], atol=1e-6)
    assert np.allclose(kp[:, 1:4], nkp[:, 1:4], atol=1e-6)
    # positions 48..63 of the padded chunk went to the trash page: the
    # slot's fourth and fifth pages hold nothing
    assert float(jnp.max(jnp.abs(vp[:, 4:]))) == 0.0
    assert float(jnp.max(jnp.abs(kp[:, 4:]))) == 0.0


def test_absorbed_and_decompressed_attention_are_the_same_sum(model, monkeypatch):
    """One layer (the second of the stacked pools), 3 slots, T = 2 new
    tokens over committed histories: the form the decode step takes against
    the form a chunk takes."""
    cfg, w, bundle, params = model
    config = bundle.config
    layer = jax.tree.map(lambda a: a[1], params["layers"])
    rng = np.random.default_rng(6)
    pages = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype) * 0.5,
        kv_pages.init_pages(config, 16, PAGE))
    pages["k"] = pages["k"].at[..., 8:].set(0)
    tables = jnp.asarray(rng.permutation(np.arange(1, 16))[:15].reshape(3, 5))
    lengths = jnp.asarray([0, 17, 70])
    x = jnp.asarray(rng.normal(size=(3, 2, 64)), jnp.float32)
    pos = lengths[:, None] + jnp.arange(2)[None]

    def run(wide: bool):
        def bound(q, k_new, v_new, **kw):
            assert ("expand" in kw) == wide
            return kv_pages.paged_attend(q, k_new, v_new, pages["k"],
                                         pages["v"], 1, tables, lengths,
                                         impl="xla", **kw)
        return mla.latent_attention_sublayer(
            config, x, layer["attn"], layer["input_norm"], pos, bound)[0]

    absorbed = run(False)
    # a query tile past ROWS_ALL_HEADS (a chunk) takes the decompressed form
    monkeypatch.setattr(mla, "ROWS_ALL_HEADS", 0)
    decompressed = run(True)
    assert float(jnp.max(jnp.abs(absorbed))) > 1e-3
    assert float(jnp.max(jnp.abs(absorbed - decompressed))) < 1e-5


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("t,dtype", [(1, jnp.float32), (2, jnp.float32),
                                     (1, jnp.bfloat16)])
def test_latent_kernel_interpreted_matches_the_gathered_rows(t, dtype, layer):
    """Lengths either side of a page and of a block of the walk (8 pages of
    16), an empty slot and a full table, through shuffled physical pages of
    one layer of stacked pools whose three layers all differ: the kernel and
    the gather path there against the gather path on that layer ALONE, and
    the write leaves the other layers' bytes as they were."""
    rng = np.random.default_rng(8)
    h, c, r, rw, page, cols = 4, 128, 64, 128, 16, 20
    lengths = [0, 1, 15, 16, 17, 127, 128, 129, 200, cols * page - t]
    n = len(lengths)
    q = jnp.asarray(rng.normal(size=(n, t, h, c + r)), dtype)
    kp = jnp.asarray(rng.normal(size=(3, 1 + n * cols, page, 1, rw)), dtype)
    vp = jnp.asarray(rng.normal(size=(3, 1 + n * cols, page, 1, c)), dtype)
    k_new = jnp.asarray(rng.normal(size=(n, t, 1, rw)), dtype)
    v_new = jnp.asarray(rng.normal(size=(n, t, 1, c)), dtype)
    tables = jnp.asarray(rng.permutation(np.arange(1, 1 + n * cols))
                         .reshape(n, cols), jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    kw = dict(scale=0.05, latent_rope=r)
    want, _ = kv_pages.paged_attend(q, k_new, v_new, kp[layer][None],
                                    vp[layer][None], 0, tables, lens,
                                    impl="xla", **kw)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    others = [i for i in range(3) if i != layer]
    for impl in ("flash", "xla"):
        got, (nkp, nvp) = kv_pages.paged_attend(
            q, k_new, v_new, kp, vp, layer, tables, lens, impl=impl, **kw)
        assert got.shape == (n, t, h, c) and got.dtype == dtype
        assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                     - want.astype(jnp.float32)))) < tol
        for before, after in ((kp, nkp), (vp, nvp)):
            assert jnp.array_equal(after[jnp.asarray(others)],
                                   before[jnp.asarray(others)])
            assert not jnp.array_equal(after[layer], before[layer])
    # the rope key's pad columns are not part of the key
    clean = paged_latent_attend(q, kp, vp, layer, tables, lens, scale=0.05)
    dirty = paged_latent_attend(q, kp.at[..., r:].set(7.0), vp, layer, tables,
                                lens, scale=0.05)
    assert jnp.array_equal(clean, dirty)


def test_pool_layout_bytes_and_gate():
    real = mla.PRESETS["mistral-small-4-119b"]
    assert kv_pages.pool_layout(real) == {"k": (1, 128), "v": (1, 256)}
    assert kv_pages.is_latent(real) and not kv_pages.is_latent(
        get_model("llama-debug").config)
    cut = dataclasses.replace(real, num_layers=6, dtype=jnp.bfloat16)
    # published: (256 + 64) x 2 B = 640 B a token a layer; resident 768 B,
    # the rope key padded to one lane tile
    assert (real.kv_lora_rank + real.qk_rope_head_dim) * 2 == 640
    assert kv_pages.kv_page_bytes(cut, page_size=1) == 6 * 768
    assert kv_pages.kv_page_bytes(cut, page_size=128, n_pages=3073) \
        == 3073 * 128 * 6 * 768
    llama = get_model("llama-debug").config
    assert kv_pages.kv_page_bytes(llama, page_size=8, kv_dtype="fp32") \
        == 2 * 8 * 2 * llama.num_kv_heads * llama.head_size * 4
    assert latent_decode_eligible(256, 128, 128, rows=32)
    assert not latent_decode_eligible(256, 128, 128, rows=2048 * 32)
    assert not latent_decode_eligible(256, 64, 128, rows=32)
    assert real.softmax_scale() == pytest.approx(
        128 ** -0.5 * (0.1 * np.log(128) + 1) ** 2)
    # 119 B parameters, 6.5 B of them active (the card's "119B-A6.5B")
    assert real.num_params() == pytest.approx(119e9, rel=0.02)
    assert real.num_active_params() == pytest.approx(6.5e9, rel=0.05)


def test_registry_lists_the_presets_and_the_hub_name():
    assert {"mla-moe-debug", "mistral-small-4-119b"} <= set(list_models())
    bundle = get_model("mistralai/Mistral-Small-4-119B-2603")
    assert bundle.family == "mla_moe" and bundle.config.num_layers == 36
    assert bundle.config.num_experts == 128


def test_resolve_says_which_attend_the_family_takes(monkeypatch):
    config = mla.PRESETS["mistral-small-4-119b"]
    assert kv_pages.resolve_attend_for(config, "auto", 128)[0] == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    impl, reason = kv_pages.resolve_attend_for(config, "auto", 128)
    assert impl == "flash" and "paged latent" in reason
    impl, reason = kv_pages.resolve_attend_for(config, "auto", 8)
    assert impl == "xla" and "page_size % 16" in reason
    with pytest.raises(ValueError, match="paged latent"):
        kv_pages.resolve_attend_for(config, "flash", 8)
    assert "paged flash" in kv_pages.resolve_attend_for(
        get_model("qwen3-0.6b").config, "auto", 16)[1]


@pytest.fixture(scope="module")
def debug_engine_parts():
    bundle = get_model("mla-moe-debug", dtype=jnp.float32)
    return bundle, bundle.init(bundle.config, jax.random.key(0))


@pytest.mark.parametrize("kw,named", [
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"weight_dtype": "int8"}, "weight_dtype='int8'"),
    ({"max_adapters": 2}, "max_adapters"),
    ({"speculate": "ngram"}, "speculate"),
    ({"host_tier_bytes": 1 << 20}, "host_tier_bytes"),
    ({"shard_kv": True}, "plan / shard_kv")])
def test_engine_refuses_what_the_family_does_not_serve(debug_engine_parts, kw,
                                                      named):
    bundle, params = debug_engine_parts
    with pytest.raises(ValueError, match="does not serve with") as exc:
        ServeEngine(bundle, params, n_slots=2, page_size=16, max_len=64, **kw)
    assert named in str(exc.value) and "mla_moe" in str(exc.value)


def test_disaggregated_engine_refuses_the_family(debug_engine_parts):
    from distributed_training_guide_tpu.serve.disagg import DisaggEngine

    bundle, params = debug_engine_parts
    with pytest.raises(ValueError, match="disaggregation"):
        DisaggEngine(bundle, params, n_slots=2, page_size=16, max_len=64)


@pytest.mark.parametrize("engine_kw", [{}, {"prefill_chunk": 16},
                                       {"decode_horizon": 2}],
                         ids=["own-size", "chunked", "horizon2"])
def test_engine_serves_the_recompute_streams_and_counts_routing(
        debug_engine_parts, engine_kw):
    bundle, params = debug_engine_parts
    engine = ServeEngine(bundle, params, n_slots=2, page_size=16, max_len=128,
                         n_pages=9, **engine_kw)
    prompts = [list(range(3, 40)), [9, 1, 30, 2, 77, 4]]
    for i, prompt in enumerate(prompts):
        engine.submit(Request(prompt_ids=prompt, max_new_tokens=10,
                              temperature=0.0, eos_id=None, seed=i))
    done = []
    while engine.has_work:
        done.extend(engine.step())
    got = {r.request_id: list(r.generated_ids) for r in done}
    for rid, prompt in enumerate(prompts):
        cur = list(prompt)
        for _ in range(10):
            logits = bundle.apply(bundle.config, params, jnp.asarray([cur]))
            cur.append(int(jnp.argmax(logits[0, -1])))
        assert got[rid] == cur[len(prompt):]
    routing = engine.stats().get("routing")
    if "decode_horizon" in engine_kw:     # the horizon program keeps no count
        assert routing is None
        return
    # every expert held: each decode step routes 2 slots x top-2 x 2 layers
    assert routing["pairs_routed"] == routing["pairs_held"] \
        == routing["steps"] * 2 * 2 * 2
    assert 0 < routing["experts_touched"] <= routing["steps"] * 2 * 8
    assert 1 <= routing["fullest_expert_pairs"] <= 4


def test_paged_step_reads_the_experts_in_place_or_slices_then_casts():
    """``tests/test_moe.py``'s check on this family's paged step: leaves in
    the compute dtype ride the layer scan whole, fp32 leaves under bf16
    compute are sliced and then cast, and the two agree bit for bit."""
    from tests.test_moe import check_in_place_and_sliced_experts_agree

    check_in_place_and_sliced_experts_agree(
        mla, lambda dtype: get_model("mla-moe-debug", dtype=dtype))


@pytest.mark.parametrize("held", [(0, 8), (2, 4), (5, 3)],
                         ids=["all", "experts-2-5", "experts-5-7"])
def test_in_place_experts_of_a_held_share_serve_the_recompute_tokens(
        debug_engine_parts, held):
    """``gmm`` starts at matrix ``layer * held`` of the stacked leaf, counted
    in HELD experts: a share that begins past expert 0 and an odd count
    serve the tokens the plain forward gives with the same leaves."""
    bundle, params = debug_engine_parts
    first, count = held
    bundle = dataclasses.replace(bundle, config=dataclasses.replace(
        bundle.config, experts_held=held))
    moe_p = params["layers"]["moe"]
    params = {**params, "layers": {**params["layers"], "moe": {
        **moe_p, **{k: moe_p[k][:, first:first + count]
                    for k in ("gate", "up", "down")}}}}
    engine = ServeEngine(bundle, params, n_slots=2, page_size=16, max_len=64,
                         prefill_chunk=16)
    prompts = [list(range(3, 24)), [9, 1, 30, 2, 77, 4]]
    for i, prompt in enumerate(prompts):
        engine.submit(Request(prompt_ids=prompt, max_new_tokens=6,
                              temperature=0.0, eos_id=None, seed=i))
    done = []
    while engine.has_work:
        done.extend(engine.step())
    for r in done:
        cur = list(prompts[r.request_id])
        for _ in range(6):
            logits = bundle.apply(bundle.config, params, jnp.asarray([cur]))
            cur.append(int(jnp.argmax(logits[0, -1])))
        assert list(r.generated_ids) == cur[len(prompts[r.request_id]):]
    routing = engine.stats()["routing"]
    assert (routing["pairs_held"] < routing["pairs_routed"]) == (count < 8)


def test_a_llama_engine_reports_no_routing():
    bundle = get_model("llama-debug")
    params = bundle.init(bundle.config, jax.random.key(0))
    engine = ServeEngine(bundle, params, n_slots=2, page_size=8, max_len=64)
    engine.submit(Request(prompt_ids=[3, 17, 42], max_new_tokens=4,
                          temperature=0.0, eos_id=None))
    while engine.has_work:
        engine.step()
    assert "routing" not in engine.stats()


def test_latent_pools_ride_the_layer_scan_as_carry(debug_engine_parts):
    """The family's decode and chunk programs carry the two stacked latent
    pools through the layer scan whole (the routing counts are its one
    per-layer output) and slice no layer's pool out of them."""
    from distributed_training_guide_tpu.utils import hlo
    from tests.test_paged_decode import serve_program_jaxprs

    bundle, params = debug_engine_parts
    engine = ServeEngine(bundle, params, n_slots=2, page_size=16, max_len=64,
                         attend_impl="flash", prefill_chunk=16)
    # off the chip ``gmm`` is the group scan, which takes its layer of the
    # stacked expert leaves as a slice (fused into its dot): at debug widths
    # one layer's experts outweigh one layer's pool, so they are named here
    experts = {params["layers"]["moe"][leaf].shape[1:]
               for leaf in ("gate", "up", "down")}
    for name, jaxpr in serve_program_jaxprs(engine).items():
        for leaf in ("k", "v"):
            scans = hlo.scans_holding(jaxpr, engine.pages[leaf].shape)
            for scan in scans:
                scan["sliced"] = [x for x in scan["sliced"]
                                  if x[1] not in experts]
            assert scans == [{"carry": 1, "xs": 0, "ys": 0, "sliced": []}], (
                name, leaf, scans)


def test_decode_program_carries_the_new_scopes(debug_engine_parts):
    import re

    bundle, params = debug_engine_parts
    engine = ServeEngine(bundle, params, n_slots=2, page_size=16, max_len=64,
                         attend_impl="flash")
    arrays = {k: jnp.asarray(v)
              for k, v in engine.scheduler.decode_arrays().items()}
    text = engine._decode_fn.lower(
        engine.params, engine.pages,
        *(arrays[k] for k in ("tokens", "lengths", "tables", "seeds",
                              "temps", "top_ks", "top_ps", "actives"))
    ).as_text(debug_info=True)
    found = set()
    for path in re.findall(r'loc\("([^"]+)"', text):
        found |= set(path.split("/"))
    want = {"layers", "attn", "latent_proj", "attend", "kv_write", "router",
            "experts", "shared_expert", "loss_head", "sample",
            "paged_latent_attend"}
    assert want <= found, want - found
    assert "module @jit_serve_decode" in text
