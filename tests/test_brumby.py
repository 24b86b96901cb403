"""``models/brumby.py`` through the pool's STATE CLASS on the CPU, a family
with NO attending layer: prefill in chunks then decode through the pool
against the whole-sequence forward (a prompt of several chunks carries ``S``
and ``Z`` across the chunk boundary), a reused block read as zeros, a wrong
state moving the logits, the engine end to end with no k or v leaf anywhere
(it admits, preempts nothing and returns its blocks), the presets, the
counts, and every refusal by name."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import brumby, jamba
from distributed_training_guide_tpu.models.registry import (family_module,
                                                            get_model)
from distributed_training_guide_tpu.models.state_class import \
    STATE_CLASS_REFUSES
from distributed_training_guide_tpu.serve import (Request, ServeEngine,
                                                  kv_pages)
from distributed_training_guide_tpu.serve.kv_pages import pool_audit

# float32 both ways: the whole-sequence forward is the blocked scan from
# zeros, the paged path the same scan cut at other places and the step behind
# it: read 2e-6..5e-6 on logits of magnitude 1.5
TOL = 3e-5
PAGE, CHUNK, MAX_LEN, N_SLOTS = 8, 16, 96, 3


@pytest.fixture(scope="module")
def model():
    bundle = get_model("brumby-debug", dtype=jnp.float32)
    return bundle, bundle.init(bundle.config, jax.random.key(0))


@pytest.fixture(scope="module")
def whole(model):
    bundle, params = model
    apply = jax.jit(lambda ids: brumby.apply(bundle.config, params, ids))
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
        return brumby.paged_decode_step(self.cfg, self.params, ids, lengths,
                                        pages, attend, all_logits=True)

    def run(self, seq, n_prompt, block, first_page=1):
        """Logits at every position of ``seq``: ``n_prompt`` tokens in chunks,
        the rest in decode steps; ``block`` (the pages hold nothing)."""
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
    ``S`` and ``Z`` the first left, the third ends short of a chunk), then 12
    decode steps: the logits at every position are the whole-sequence
    forward's."""
    bundle, params = model
    (seq,) = sequences((52,))
    got = Paged(bundle.config, params).run(seq, 40, block=2)
    assert np.max(np.abs(got - whole(seq))) < TOL
    assert np.max(np.abs(got)) > 0.1


def test_the_chunk_boundary_carries_the_state_and_its_normaliser(model, whole):
    """The same two-chunk prompt with either leaf of what the first chunk
    left zeroed before the second: the second chunk's logits move, by ``S``
    and by ``Z`` each alone."""
    bundle, params = model
    (seq,) = sequences((32,), seed=2)
    want = whole(seq)
    assert set(bundle.config.sequence_state_layout()) == {"seq_state",
                                                          "seq_norm"}
    for leaf in bundle.config.sequence_state_layout():
        run = Paged(bundle.config, params)
        head = run.run(seq[:16], 16, block=1)
        assert np.max(np.abs(head - want[:16])) < TOL
        pool = np.array(run.pages[leaf])
        assert np.max(np.abs(pool[:, 1])) > 1e-3      # the first chunk's
        pool[:, 1] = 1.0 if leaf == "seq_norm" else 0.0
        run.pages[leaf] = jnp.asarray(pool)
        ids = jnp.asarray([seq[16:32]], jnp.int32)
        logits, _ = run.step(run.pages, ids, jnp.asarray([16], jnp.int32),
                             table_for(1), jnp.asarray([16], jnp.int32),
                             t=CHUNK)
        assert np.max(np.abs(np.asarray(logits[0]) - want[16:])) > 100 * TOL


def test_a_block_taken_again_starts_from_zeros(model, whole):
    """A second sequence on the first one's block reads zeros where its
    predecessor left a state, through the chunk path, through a chunk of one
    real token (a one-token prompt) and through a decode step at position
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
    """Zeroing one layer's ``S`` in the middle of a sequence moves the next
    logits by far more than the comparison's tolerance: the state is not
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


def test_the_state_class_is_float32_and_the_pool_has_no_k_or_v(model):
    """``S`` and ``Z`` are stored in float32 whatever the pool's dtype (bf16
    weights beside them); the pool dict holds those two leaves and NOTHING
    else, a page costs no byte; the config class has no field for the state's
    dtype and a pool made narrower by hand is refused at the step."""
    bundle, params = model
    assert "state_dtype" not in {f.name for f in
                                 dataclasses.fields(bundle.config)}
    cfg = dataclasses.replace(bundle.config, dtype=jnp.bfloat16)
    pages = kv_pages.init_pages(cfg, 4, PAGE, kv_dtype="bf16", n_state_blocks=3)
    assert set(pages) == {"seq_state", "seq_norm"}
    assert pages["seq_state"].dtype == pages["seq_norm"].dtype == jnp.float32
    assert pages["seq_state"].shape == (2, 3, 2, 3, 256, 32)
    assert pages["seq_norm"].shape == (2, 3, 2, 32, 32)
    assert kv_pages.num_kv_layers(cfg) == 0 and kv_pages.pool_layout(cfg) == {}
    assert kv_pages.kv_page_bytes(cfg, page_size=PAGE, n_pages=1000) == 0
    assert kv_pages.resolve_attend_for(cfg, "auto", PAGE)[0] == "none"
    run = Paged(bundle.config, params)
    run.pages["seq_state"] = run.pages["seq_state"].astype(jnp.bfloat16)
    (seq,) = sequences((12,))
    with pytest.raises(TypeError, match="state pool .* is float32"):
        run.run(seq, 0, block=2)      # decode steps from the first token


def test_an_engine_with_no_attending_layer_serves_what_the_forward_says(
        model, whole):
    """Five requests on three slots through ``ServeEngine`` (prompts of one,
    two and three chunks; slots and blocks reused): the engine admits them
    all, preempts nothing, every greedy token is the whole-sequence forward's
    argmax, every block is back at the end, and what it holds on the device
    is the state class alone: ``max_len`` costs no memory."""
    bundle, params = model
    eng = ServeEngine(bundle, params, n_slots=N_SLOTS, page_size=PAGE,
                      max_len=MAX_LEN, prefill_chunk=CHUNK)
    assert eng.scheduler.cache is None          # no prefix cache: refused
    assert eng.scheduler.pool.state.n_pages == N_SLOTS + 1
    assert set(eng.pages) == {"seq_state", "seq_norm"}
    assert eng.pages["seq_state"].shape == (2, N_SLOTS + 1, 2, 3, 256, 32)
    state_bytes = kv_pages.sequence_state_bytes(bundle.config, N_SLOTS + 1)
    assert eng.kv_cache_bytes() == state_bytes \
        == (N_SLOTS + 1) * 2 * 4 * (2 * 768 * 32 + 2 * 32 * 32)
    longer = ServeEngine(bundle, params, n_slots=N_SLOTS, page_size=PAGE,
                         max_len=4 * MAX_LEN, prefill_chunk=CHUNK)
    assert longer.kv_cache_bytes() == state_bytes
    del longer
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
    assert stats["preemptions"] == 0 and len(done) == 5
    assert most == N_SLOTS and stats["state_blocks_live"] == 0
    assert stats["state_blocks_taken"] == stats["state_blocks_returned"] == 5
    report = stats["kv_report"] if "kv_report" in stats else eng.kv_report()
    assert report["bytes_per_page"] == 0 and report["bytes_vs_fp32"] == 0.0


def test_presets_alias_and_counts():
    for name in ("brumby-debug", "brumby-14b", "manifestai/Brumby-14B-Base"):
        bundle = get_model(name)
        assert bundle.family == "brumby"
        assert family_module(bundle.family) is brumby
    whole = get_model("manifestai/Brumby-14B-Base").config
    # shapes only: nothing of the 14 B is allocated
    assert (whole.num_layers, whole.hidden_size, whole.num_heads,
            whole.num_kv_heads, whole.head_dim, whole.intermediate_size,
            whole.vocab_size) == (40, 5120, 40, 8, 128, 17408, 151936)
    layer = 62_955_776 + 267_386_880 + 10_240
    assert layer == 330_352_896
    assert whole.num_params() == 40 * layer + 1_555_829_760
    cut = dataclasses.replace(whole, num_layers=8)
    assert cut.num_params() == 4_198_652_928
    shapes = jax.eval_shape(lambda: brumby.init(cut, jax.random.key(0)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 4_198_652_928
    assert shapes["lm_head"].shape == (5120, 151936)        # untied
    assert shapes["layers"]["mixer"][0]["wg"].shape == (5120, 8)
    assert whole.num_kv_layers == 0 and whole.kv_layout() == {}
    assert kv_pages.sequence_state_bytes(cut) == 8 * (
        8 * 9216 * 128 * 4 + 8 * 128 * 128 * 4) == 306_184_192
    assert abs(jax.nn.sigmoid(brumby.GATE_SHIFT) - 0.999) < 1e-6
    debug = get_model("brumby-debug")
    params = debug.init(debug.config, jax.random.key(1))
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == debug.config.num_params()
    axes = brumby.param_logical_axes(debug.config)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, params)) \
        == jax.tree.structure(jax.tree.map(
            lambda x: 0, axes, is_leaf=lambda x: isinstance(x, tuple)))
    with pytest.raises(ValueError, match="do not divide"):
        dataclasses.replace(debug.config, num_kv_heads=3)


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
    assert option in brumby.SERVE_REFUSES
    with pytest.raises(ValueError, match="does not serve with "
                       + option.replace("(", r"\(")):
        ServeEngine(bundle, params, n_slots=2, page_size=PAGE,
                    max_len=MAX_LEN, **kwargs)


def test_disaggregation_and_an_engine_swap_are_refused_by_name(model):
    from distributed_training_guide_tpu.serve.disagg import DisaggEngine
    from distributed_training_guide_tpu.serve.elastic import new_generation

    bundle, params = model
    assert brumby.SERVE_REFUSES is jamba.SERVE_REFUSES is STATE_CLASS_REFUSES
    with pytest.raises(ValueError, match="does not serve with disaggregation"):
        DisaggEngine(bundle, params, n_slots=2, page_size=PAGE,
                     max_len=MAX_LEN)
    eng = ServeEngine(bundle, params, n_slots=2, page_size=PAGE,
                      max_len=MAX_LEN)
    with pytest.raises(ValueError, match="does not serve with engine swap"):
        new_generation(eng, n_slots=3)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_programs_carry_the_familys_names(model, monkeypatch, impl):
    """``attn/retention/retention_step`` on the decode step's layers,
    ``retention_chunk`` on a chunk's, whichever form computes them
    (``pallas``: the kernels, interpreted here, as ``auto`` takes them on a
    TPU); no program has an ``attend`` or a ``kv_write`` scope: nothing
    attends and no page is written; each program's dispatch notes its choice
    under its own name."""
    import re

    from distributed_training_guide_tpu.ops import retention
    from distributed_training_guide_tpu.utils import trace

    class OnTpu:    # what ``ops/retention.py`` alone sees of the backend
        default_backend = staticmethod(lambda: "tpu")

        def __getattr__(self, name):
            return getattr(jax, name)

    noted = []
    if impl == "pallas":
        monkeypatch.setattr(retention, "jax", OnTpu())
        monkeypatch.setattr(retention, "resolve_interpret", lambda i: True)
    monkeypatch.setattr(retention, "note_choice",
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
    assert any("attn/retention/retention_step/" in f for f in in_decode)
    assert not any("/retention_chunk/" in f for f in in_decode)
    assert any("attn/retention/retention_chunk/" in f for f in in_chunk)
    assert not any("/retention_step/" in f for f in in_chunk)
    assert set(noted) == {("retention_step", impl), ("retention_chunk", impl)}
    for found in (in_decode, in_chunk):
        assert not any(re.search(r"(^|/)(attend|kv_write)/", f)
                       for f in found)
    assert arr["tables"].shape == (2, eng.max_pages + 1)
    assert "retention" in trace.SUBSCOPES
    assert {"retention_step", "retention_chunk"} <= set(trace.KERNELS)


def test_the_spans_carry_a_blocks_passage_and_a_chunks_start(model, tmp_path):
    """Under a profiler session: the two ``serve.state`` spans of a request
    (its block taken, then returned), and each ``serve.prefill`` span where
    its chunk starts (0: a state that is zero), which
    ``benchmarks/readers/retention_work.py`` reads."""
    from jax.profiler import ProfileData

    from distributed_training_guide_tpu.utils.trace import PREFIX

    bundle, params = model
    eng = ServeEngine(bundle, params, n_slots=2, page_size=PAGE,
                      max_len=MAX_LEN, prefill_chunk=CHUNK)
    with jax.profiler.trace(str(tmp_path)):
        eng.submit(Request(prompt_ids=list(range(3, 23)), max_new_tokens=2,
                           temperature=0.0, eos_id=None))
        while eng.has_work:
            eng.step()
    events = [(e.name[len(PREFIX):], dict(e.stats))
              for plane in ProfileData.from_file(
                  str(next(tmp_path.rglob("*.xplane.pb")))).planes
              for line in plane.lines for e in line.events
              if e.name.startswith(PREFIX)]
    state = [stats for name, stats in events if name == "serve.state"]
    assert [(int(s["taken"]), int(s["returned"])) for s in state] \
        == [(1, 0), (0, 1)]
    prefill = [stats for name, stats in events if name == "serve.prefill"]
    assert [(int(s["start"]), int(s["tokens"])) for s in prefill] \
        == [(0, 16), (16, 4)]
