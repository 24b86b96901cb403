"""``models/jamba.py`` through the pool's STATE CLASS on the CPU: prefill in
chunks then decode through the pool against the whole-sequence forward (a
prompt of several chunks carries ``h`` and the conv rows across the chunk
boundary), a reused block read as zeros, a wrong state moving the logits,
the engine end to end, the presets, the counts, and every refusal by name
(the state class's own life in the scheduler: ``tests/test_solar_open2.py``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import jamba, solar_open2
from distributed_training_guide_tpu.models.registry import (family_module,
                                                            get_model)
from distributed_training_guide_tpu.models.state_class import \
    STATE_CLASS_REFUSES
from distributed_training_guide_tpu.serve import (Request, ServeEngine,
                                                  kv_pages)
from distributed_training_guide_tpu.serve.kv_pages import pool_audit

# float32 both ways: two scans over tokens and a paged attend against the
# whole-sequence forward sum in another order: read 1e-6..4e-6
TOL = 3e-5
PAGE, CHUNK, MAX_LEN, N_SLOTS = 8, 16, 96, 3


@pytest.fixture(scope="module")
def model():
    bundle = get_model("jamba-debug", dtype=jnp.float32)
    return bundle, bundle.init(bundle.config, jax.random.key(0))


@pytest.fixture(scope="module")
def whole(model):
    bundle, params = model
    apply = jax.jit(lambda ids: jamba.apply(bundle.config, params, ids))
    return lambda seq: np.asarray(apply(jnp.asarray(seq)[None])[0])


def sequences(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, n).tolist() for n in lengths]


def table_for(block, first_page=1):
    table = np.zeros((1, MAX_LEN // PAGE + 1), np.int32)
    table[0, :-1] = first_page + np.arange(MAX_LEN // PAGE)
    table[0, -1] = block
    return jnp.asarray(table)


class Paged:
    """One slot's sequence through the family's paged hook, teacher-forced,
    with the logits kept: chunks of ``CHUNK`` over the prompt, then one token
    a step, over pools and a table row built by hand."""

    def __init__(self, cfg, params, n_blocks=4):
        self.cfg, self.params = cfg, params
        self.pages = kv_pages.init_pages(cfg, 40, PAGE, n_state_blocks=n_blocks)
        self.step = jax.jit(self._step, static_argnames="t")

    def _step(self, pages, ids, lengths, tables, n_valid, t):
        attend = kv_pages.make_attend(tables, lengths, impl="xla",
                                      n_valid=n_valid, state_class=True)
        return jamba.paged_decode_step(self.cfg, self.params, ids, lengths,
                                       pages, attend, all_logits=True)

    def run(self, seq, n_prompt, block, first_page=1):
        """Logits at every position of ``seq``: ``n_prompt`` tokens in chunks,
        the rest in decode steps; pages ``first_page ..`` and ``block``."""
        table = table_for(block, first_page)
        out, pos = [], 0
        while pos < len(seq):
            t = min(CHUNK, n_prompt - pos) if pos < n_prompt else 1
            width = CHUNK if pos < n_prompt else 1
            ids = np.zeros((1, width), np.int32)
            ids[0, :t] = seq[pos:pos + t]
            logits, self.pages = self.step(
                self.pages, jnp.asarray(ids), jnp.asarray([pos], jnp.int32),
                table, jnp.asarray([t], jnp.int32), t=width)
            out.append(np.asarray(logits[0, :t]))
            pos += t
        return np.concatenate(out)


def test_prefill_in_chunks_then_decode_is_the_whole_forward(model, whole):
    """40 prompt tokens in chunks of 16, 16 and 8 (the second starts from the
    ``h`` and the conv rows the first left, the third ends short of a chunk),
    then 12 decode steps: the logits at every position are the
    whole-sequence forward's."""
    bundle, params = model
    (seq,) = sequences((52,))
    got = Paged(bundle.config, params).run(seq, 40, block=2)
    assert np.max(np.abs(got - whole(seq))) < TOL
    assert np.max(np.abs(got)) > 0.1


def test_the_chunk_boundary_carries_the_state_and_the_conv_rows(model, whole):
    """The same two-chunk prompt with either half of what the first chunk
    left zeroed before the second: the second chunk's logits move, by the
    state ``h`` and by the conv rows each alone."""
    bundle, params = model
    (seq,) = sequences((32,), seed=2)
    want = whole(seq)
    for leaf in kv_pages.SEQUENCE_LEAVES:
        run = Paged(bundle.config, params)
        head = run.run(seq[:16], 16, block=1)
        assert np.max(np.abs(head - want[:16])) < TOL
        pool = np.array(run.pages[leaf])
        assert np.max(np.abs(pool[:, 1])) > 1e-3      # the first chunk's
        pool[:, 1] = 0
        run.pages[leaf] = jnp.asarray(pool)
        ids = jnp.asarray([seq[16:32]], jnp.int32)
        logits, _ = run.step(run.pages, ids, jnp.asarray([16], jnp.int32),
                             table_for(1), jnp.asarray([16], jnp.int32),
                             t=CHUNK)
        assert np.max(np.abs(np.asarray(logits[0]) - want[16:])) > 100 * TOL


def test_a_block_taken_again_starts_from_zeros(model, whole):
    """A second sequence on the first one's block and pages reads zeros where
    its predecessor left a state, through the chunk path, through a chunk of
    one real token (a one-token prompt) and through a decode step at position
    0."""
    bundle, params = model
    first, second, short, bare = sequences((30, 37, 9, 6), seed=3)
    run = Paged(bundle.config, params)
    run.run(first, 20, block=1)
    left = np.asarray(run.pages["seq_state"][:, 1])
    assert np.max(np.abs(left)) > 1e-3         # the predecessor's state
    assert np.max(np.abs(run.run(second, 21, block=1) - whole(second))) < TOL
    assert np.max(np.abs(run.run(short, 1, block=1) - whole(short))) < TOL
    assert np.max(np.abs(run.run(bare, 0, block=1) - whole(bare))) < TOL


def test_a_wrong_state_moves_the_logits(model, whole):
    """Zeroing one Mamba layer's ``h`` in the middle of a sequence moves the
    next logits by far more than the comparison's tolerance: the state is not
    decoration."""
    bundle, params = model
    (seq,) = sequences((40,), seed=5)
    run = Paged(bundle.config, params)
    head = run.run(seq[:32], 32, block=1)
    assert np.max(np.abs(head - whole(seq)[:32])) < TOL
    state = np.array(run.pages["seq_state"])
    state[1, 1] = 0.0
    run.pages["seq_state"] = jnp.asarray(state)
    logits, _ = run.step(run.pages, jnp.asarray([[seq[32]]]),
                         jnp.asarray([32], jnp.int32), table_for(1),
                         jnp.asarray([1], jnp.int32), t=1)
    assert np.max(np.abs(np.asarray(logits[0, 0]) - whole(seq)[32])) > 100 * TOL


def test_the_state_class_is_float32_and_not_an_option(model):
    """h is stored in float32 whatever the pool's dtype (bf16 weights and k /
    v beside it), ``[d_state, channels]`` a block, the conv rows in the
    pool's own; the config class has no field for it and a pool made narrower
    by hand is refused at the step."""
    bundle, params = model
    assert "state_dtype" not in {f.name for f in
                                 dataclasses.fields(bundle.config)}
    cfg = dataclasses.replace(bundle.config, dtype=jnp.bfloat16)
    pages = kv_pages.init_pages(cfg, 4, PAGE, kv_dtype="bf16", n_state_blocks=3)
    assert pages["seq_state"].dtype == jnp.float32
    assert pages["seq_state"].shape == (3, 3, 8, 128)
    assert pages["seq_conv"].dtype == pages["k"].dtype == jnp.bfloat16
    run = Paged(bundle.config, params)
    run.pages["seq_state"] = run.pages["seq_state"].astype(jnp.bfloat16)
    (seq,) = sequences((12,))
    with pytest.raises(TypeError, match="state pool is float32"):
        run.run(seq, 0, block=2)      # decode steps from the first token


def test_the_engine_serves_what_the_forward_says(model, whole):
    """Five requests on three slots through ``ServeEngine`` (prompts of one,
    two and three chunks; slots and blocks reused): every greedy token is the
    whole-sequence forward's argmax, every block is back at the end."""
    bundle, params = model
    eng = ServeEngine(bundle, params, n_slots=N_SLOTS, page_size=PAGE,
                      max_len=MAX_LEN, prefill_chunk=CHUNK)
    assert eng.scheduler.cache is None          # no prefix cache: refused
    assert eng.scheduler.pool.state.n_pages == N_SLOTS + 1
    assert eng.pages["seq_state"].shape == (3, N_SLOTS + 1, 8, 128)
    assert eng.pages["seq_state"].dtype == jnp.float32
    assert eng.pages["seq_conv"].shape == (3, N_SLOTS + 1, 3, 128)
    assert eng.pages["k"].shape[0] == 1 and eng.pages["k"].shape[3] == 1
    prompts = sequences((5, 23, 16, 9, 41), seed=7)
    rids = [eng.submit(Request(prompt_ids=p, max_new_tokens=10,
                               temperature=0.0, eos_id=None))
            for p in prompts]
    done, most = {}, 0
    while eng.has_work:
        for r in eng.step():
            done[r.request_id] = r
        most = max(most, eng.stats()["state_blocks_live"])
        pool_audit(eng.scheduler.pool,
                   [{p: 1 for s in eng.scheduler.slots if s is not None
                     for p in s.pages}],
                   state_holder_maps=[eng.scheduler.state_holders()])
    for rid, prompt in zip(rids, prompts):
        seq = prompt + list(done[rid].generated_ids)
        want = np.argmax(whole(seq), -1)[len(prompt) - 1:-1]
        assert np.array_equal(want, done[rid].generated_ids)
    stats = eng.stats()
    assert most == N_SLOTS and stats["state_blocks_live"] == 0
    assert stats["state_blocks_taken"] == stats["state_blocks_returned"] == 5
    assert eng.kv_cache_bytes() == (
        kv_pages.kv_page_bytes(bundle.config, page_size=PAGE,
                               n_pages=eng.pages["k"].shape[1])
        + kv_pages.sequence_state_bytes(bundle.config, N_SLOTS + 1))


def test_presets_alias_and_counts():
    for name in ("jamba-debug", "jamba2-3b", "ai21labs/AI21-Jamba2-3B"):
        bundle = get_model(name)
        assert bundle.family == "jamba"
        assert family_module(bundle.family) is jamba
    whole = get_model("ai21labs/AI21-Jamba2-3B").config
    # shapes only: nothing of the 3 B is allocated
    assert whole.num_params() == 3_029_337_472
    shapes = jax.eval_shape(lambda: jamba.init(whole, jax.random.key(0)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 3_029_337_472
    table = whole.layer_table()
    assert [l for l, (kind, _) in enumerate(table) if kind == "attn"] == [7, 21]
    assert table[7] == ("attn", 0) and table[8] == ("mamba", 7) \
        and table[27] == ("mamba", 25)
    assert (whole.num_kv_layers, whole.num_mamba_layers) == (2, 26)
    assert whole.head_dim == whole.hidden_size // whole.num_heads == 128
    served = dataclasses.replace(whole, dtype=jnp.bfloat16)
    assert kv_pages.sequence_state_bytes(served) == 9_318_400
    assert kv_pages.kv_page_bytes(served, page_size=1) == 1024
    debug = get_model("jamba-debug")
    assert {kind for kind, _ in debug.config.layer_table()} == {"attn", "mamba"}
    params = debug.init(debug.config, jax.random.key(1))
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == debug.config.num_params()
    axes = jamba.param_logical_axes(debug.config)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, params)) \
        == jax.tree.structure(jax.tree.map(
            lambda x: 0, axes, is_leaf=lambda x: isinstance(x, tuple)))
    with pytest.raises(ValueError, match="attn_layer_offset must lie in"):
        dataclasses.replace(debug.config, attn_layer_offset=3)


@pytest.mark.parametrize("option,kwargs", [
    ("kv_dtype='int8'", {"kv_dtype": "int8"}),
    ("weight_dtype='int8'", {"weight_dtype": "int8"}),
    ("max_adapters", {"max_adapters": 2}),
    ("speculate", {"speculate": "ngram"}),
    ("host_tier_bytes", {"host_tier_bytes": 1 << 20}),
    ("prefix_cache", {"prefix_cache": True}),
    ("decode_horizon", {"decode_horizon": 4}),
    ("plan / shard_kv", {"shard_kv": True}),
])
def test_what_the_family_does_not_serve_is_refused_by_name(model, option,
                                                           kwargs):
    bundle, params = model
    assert option in jamba.SERVE_REFUSES
    with pytest.raises(ValueError, match="does not serve with "
                       + option.replace("(", r"\(")):
        ServeEngine(bundle, params, n_slots=2, page_size=PAGE,
                    max_len=MAX_LEN, **kwargs)


def test_disaggregation_and_an_engine_swap_are_refused_by_name(model):
    from distributed_training_guide_tpu.serve.disagg import DisaggEngine
    from distributed_training_guide_tpu.serve.elastic import new_generation

    bundle, params = model
    with pytest.raises(ValueError, match="does not serve with disaggregation"):
        DisaggEngine(bundle, params, n_slots=2, page_size=PAGE,
                     max_len=MAX_LEN)
    eng = ServeEngine(bundle, params, n_slots=2, page_size=PAGE,
                      max_len=MAX_LEN)
    with pytest.raises(ValueError, match="does not serve with engine swap"):
        new_generation(eng, n_slots=3)


def test_the_state_class_refuses_the_same_for_every_family_that_keeps_one():
    """One statement (``models/state_class.py``), read by both families: the
    same options for the same modules."""
    assert jamba.SERVE_REFUSES is solar_open2.SERVE_REFUSES \
        is STATE_CLASS_REFUSES
    assert set(STATE_CLASS_REFUSES) == {
        "kv_dtype='int8'", "weight_dtype='int8'", "max_adapters", "speculate",
        "host_tier_bytes", "prefix_cache", "decode_horizon",
        "plan / shard_kv", "disaggregation", "engine swap"}
    assert all("KDA" not in why and "Mamba" not in why
               for why in STATE_CLASS_REFUSES.values())


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_programs_carry_the_familys_names(model, monkeypatch, impl):
    """``attn/ssm/ssm_step`` on the decode step's Mamba layers, ``ssm_chunk``
    on a chunk's, whichever form computes them (``pallas``: the kernels,
    interpreted here, as ``auto`` takes them on a TPU), the state's writes
    under ``kv_write``; each program's dispatch notes its choice under its
    own name."""
    import re

    from distributed_training_guide_tpu.ops import ssm
    from distributed_training_guide_tpu.utils import trace

    class OnTpu:    # what ``ops/ssm.py`` alone sees of the backend
        default_backend = staticmethod(lambda: "tpu")

        def __getattr__(self, name):
            return getattr(jax, name)

    noted = []
    if impl == "pallas":
        monkeypatch.setattr(ssm, "jax", OnTpu())
        monkeypatch.setattr(ssm, "resolve_interpret", lambda i: True)
    monkeypatch.setattr(ssm, "note_choice",
                        lambda op, took, why: noted.append((op, took)))
    bundle, params = model
    eng = ServeEngine(bundle, params, n_slots=2, page_size=PAGE,
                      max_len=MAX_LEN, prefill_chunk=CHUNK)
    arr = {k: jnp.asarray(v)
           for k, v in eng.scheduler.decode_arrays().items()}
    decode = eng._decode_fn.lower(
        eng.params, dict(eng.pages), *(arr[k] for k in (
            "tokens", "lengths", "tables", "seeds", "temps", "top_ks",
            "top_ps", "actives"))).as_text(debug_info=True)
    chunk = eng.programs.chunk_for(CHUNK).lower(
        eng.params, dict(eng.pages), jnp.zeros((1, CHUNK), jnp.int32),
        jnp.zeros((1,), jnp.int32), arr["tables"][:1],
        jnp.asarray(3, jnp.int32), jnp.asarray([4], jnp.int32)
    ).as_text(debug_info=True)
    in_decode = set(re.findall(r'loc\("([^"]+)"', decode))
    in_chunk = set(re.findall(r'loc\("([^"]+)"', chunk))
    assert any("attn/ssm/ssm_step/" in f for f in in_decode)
    # (scope paths: a bare "ssm_step" is a Python frame's name, which a
    # cached inner jaxpr carries from the program that traced it first)
    assert not any("/ssm_chunk/" in f for f in in_decode)
    assert any("attn/ssm/ssm_chunk/" in f for f in in_chunk)
    assert not any("/ssm_step/" in f for f in in_chunk)
    assert set(noted) == {("ssm_step", impl), ("ssm_chunk", impl)}
    assert any("attn/kv_write/" in f for f in in_decode)
    assert any("attn/attend/" in f for f in in_decode)
    assert arr["tables"].shape == (2, eng.max_pages + 1)
    assert "ssm" in trace.SUBSCOPES
    assert {"ssm_step", "ssm_chunk"} <= set(trace.KERNELS)
