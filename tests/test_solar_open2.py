"""``models/solar_open2.py`` and the pool's STATE CLASS on the CPU: prefill in
chunks then decode through the pool against the whole-sequence forward, the
state class's life in the scheduler and the engine (taken, read as zeros,
returned, taken again), what the other families' pools still are, the
presets, the counts, and every refusal by name."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import solar_open2
from distributed_training_guide_tpu.models.registry import (family_module,
                                                            get_model)
from distributed_training_guide_tpu.serve import (Request, ServeEngine,
                                                  kv_pages)
from distributed_training_guide_tpu.serve.kv_pages import PagePool, pool_audit
from distributed_training_guide_tpu.serve.scheduler import Scheduler

# float32 both ways: a chunked scan and a paged attend against the
# whole-sequence forward sum in another order: read 2e-6..6e-6
TOL = 3e-5
PAGE, CHUNK, MAX_LEN, N_SLOTS = 8, 16, 96, 3


@pytest.fixture(scope="module")
def model():
    bundle = get_model("solar-open2-debug", dtype=jnp.float32)
    return bundle, bundle.init(bundle.config, jax.random.key(0))


@pytest.fixture(scope="module")
def whole(model):
    bundle, params = model
    apply = jax.jit(lambda ids: solar_open2.apply(bundle.config, params, ids))
    return lambda seq: np.asarray(apply(jnp.asarray(seq)[None])[0])


def sequences(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, n).tolist() for n in lengths]


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
        logits, cache = solar_open2.paged_decode_step(
            self.cfg, self.params, ids, lengths, pages, attend,
            all_logits=True)
        cache.pop("routing")
        return logits, cache

    def run(self, seq, n_prompt, block, first_page=1):
        """Logits at every position of ``seq``: ``n_prompt`` tokens in chunks,
        the rest in decode steps; pages ``first_page ..`` and ``block``."""
        table = np.zeros((1, MAX_LEN // PAGE + 1), np.int32)
        table[0, :-1] = first_page + np.arange(MAX_LEN // PAGE)
        table[0, -1] = block
        out, pos = [], 0
        while pos < len(seq):
            t = min(CHUNK, n_prompt - pos) if pos < n_prompt else 1
            width = CHUNK if pos < n_prompt else 1
            ids = np.zeros((1, width), np.int32)
            ids[0, :t] = seq[pos:pos + t]
            logits, self.pages = self.step(
                self.pages, jnp.asarray(ids), jnp.asarray([pos], jnp.int32),
                jnp.asarray(table), jnp.asarray([t], jnp.int32), t=width)
            out.append(np.asarray(logits[0, :t]))
            pos += t
        return np.concatenate(out)


def test_prefill_in_chunks_then_decode_is_the_whole_forward(model, whole):
    """40 prompt tokens in chunks of 16, 16 and 8 (the second starts from the
    state the first left, the third ends short of a chunk), then 12 decode
    steps: the logits at every position are the whole-sequence forward's."""
    bundle, params = model
    (seq,) = sequences((52,))
    got = Paged(bundle.config, params).run(seq, 40, block=2)
    assert np.max(np.abs(got - whole(seq))) < TOL
    assert np.max(np.abs(got)) > 0.1


def test_a_block_taken_again_starts_from_zeros(model, whole):
    """A second sequence on the first one's block and pages reads zeros where
    its predecessor left a state, through the chunk path and (a one-token
    prompt) through a chunk of one real token."""
    bundle, params = model
    first, second, short = sequences((30, 37, 9), seed=3)
    run = Paged(bundle.config, params)
    run.run(first, 20, block=1)
    left = np.asarray(run.pages["seq_state"][:, 1])
    assert np.max(np.abs(left)) > 1e-3         # the predecessor's state
    assert np.max(np.abs(run.run(second, 21, block=1) - whole(second))) < TOL
    assert np.max(np.abs(run.run(short, 1, block=1) - whole(short))) < TOL


def test_a_wrong_state_moves_the_logits(model, whole):
    """Zeroing one KDA layer's state in the middle of a sequence moves the
    next logits by far more than the comparison's tolerance: the state is
    not decoration."""
    bundle, params = model
    (seq,) = sequences((40,), seed=5)
    run = Paged(bundle.config, params)
    head = run.run(seq[:32], 32, block=1)
    assert np.max(np.abs(head - whole(seq)[:32])) < TOL
    state = np.array(run.pages["seq_state"])
    state[1, 1] = 0.0
    run.pages["seq_state"] = jnp.asarray(state)
    table = np.zeros((1, MAX_LEN // PAGE + 1), np.int32)
    table[0, :-1] = 1 + np.arange(MAX_LEN // PAGE)
    table[0, -1] = 1
    logits, _ = run.step(run.pages, jnp.asarray([[seq[32]]]),
                         jnp.asarray([32], jnp.int32), jnp.asarray(table),
                         jnp.asarray([1], jnp.int32), t=1)
    assert np.max(np.abs(np.asarray(logits[0, 0]) - whole(seq)[32])) > 100 * TOL


def test_the_state_class_is_float32_and_not_an_option(model):
    """S is stored in float32 whatever the pool's dtype (bf16 weights and k /
    v beside it), the conv rows in the pool's own; the config class has no
    field for it and a pool made narrower by hand is refused at the step."""
    bundle, params = model
    assert "state_dtype" not in {f.name for f in
                                 dataclasses.fields(bundle.config)}
    cfg = dataclasses.replace(bundle.config, dtype=jnp.bfloat16)
    pages = kv_pages.init_pages(cfg, 4, PAGE, kv_dtype="bf16", n_state_blocks=3)
    assert pages["seq_state"].dtype == jnp.float32
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
    assert eng.pages["seq_state"].shape == (2, N_SLOTS + 1, 4, 16, 16)
    assert eng.pages["seq_state"].dtype == jnp.float32
    assert eng.pages["seq_conv"].shape == (2, N_SLOTS + 1, 3, 3 * 64)
    assert eng.pages["k"].shape[0] == 1
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
    assert eng.scheduler.pool.state.n_free == N_SLOTS
    assert eng.kv_cache_bytes() == (
        kv_pages.kv_page_bytes(bundle.config, page_size=PAGE,
                               n_pages=eng.pages["k"].shape[1])
        + kv_pages.sequence_state_bytes(bundle.config, N_SLOTS + 1))


def test_a_preempted_sequence_gives_its_block_back_and_is_served_again(
        model, whole):
    """A pool too small for two long replies: the younger sequence is
    preempted (its block returned with its pages), prefilled again on a block
    read as zeros and replayed: its tokens are the forward's all the same."""
    bundle, params = model
    eng = ServeEngine(bundle, params, n_slots=2, page_size=PAGE, max_len=72,
                      prefill_chunk=CHUNK, n_pages=12)
    prompts = sequences((20, 20), seed=9)
    rids = [eng.submit(Request(prompt_ids=p, max_new_tokens=40,
                               temperature=0.0, eos_id=None))
            for p in prompts]
    done = {}
    while eng.has_work:
        for r in eng.step():
            done[r.request_id] = r
    stats = eng.stats()
    assert stats["preemptions"] >= 1
    assert stats["state_blocks_taken"] == 2 + stats["preemptions"] \
        == stats["state_blocks_returned"]
    for rid, prompt in zip(rids, prompts):
        seq = prompt + list(done[rid].generated_ids)
        assert np.array_equal(np.argmax(whole(seq), -1)[19:-1],
                              done[rid].generated_ids)


def test_the_scheduler_books_a_block_a_sequence():
    """Taken at admission, the last column of the slot's table row, returned
    when the sequence leaves, whatever way; an idle slot's row names the
    trash block; ``adopt`` refuses (it seats page ids alone)."""
    pool = PagePool(20, PAGE, n_state_blocks=3)
    sched = Scheduler(n_slots=2, pool=pool, max_len=MAX_LEN,
                      max_pages_per_slot=4, prefix_cache=False)
    assert sched.table_width == 5
    for n in (10, 12, 9):
        sched.submit(Request(prompt_ids=list(range(n)), max_new_tokens=4,
                             temperature=0.0, eos_id=None))
    admitted = sched.try_admit()
    assert len(admitted) == 2 and len(sched.queue) == 1
    blocks = [sched.slots[i].state_block for i in (0, 1)]
    assert sorted(blocks) == [1, 2] and pool.state.n_free == 0
    assert [int(sched.table_row(i)[-1]) for i in (0, 1)] == blocks
    assert sched.state_holders() == {1: 1, 2: 1}
    assert sched.decode_tables().shape == (2, 5)
    assert not sched.decode_tables().any()      # both still prefilling
    sched.preempt(0)
    assert pool.state.n_free == 1 and sched.live_state_blocks() == 1
    assert int(sched.table_row(0)[-1]) == 0
    (again,) = sched.try_admit()
    assert sched.slots[again.slot_idx].state_block == blocks[0]
    assert sched.stats["state_blocks_taken"] == 3
    assert sched.stats["state_blocks_returned"] == 1
    pool_audit(pool, [{p: 1 for s in sched.slots for p in s.pages}],
               state_holder_maps=[sched.state_holders()])
    with pytest.raises(ValueError, match="adopt seats page ids alone"):
        sched.adopt(request=admitted[0].request, pages=[], cache_len=0,
                    generated=[], submitted_at=0.0, admitted_at=0.0)
    with pytest.raises(ValueError, match="a state class serves without"):
        Scheduler(n_slots=2, pool=PagePool(20, PAGE, n_state_blocks=3),
                  max_len=MAX_LEN, max_pages_per_slot=4, prefix_cache=True)


def test_copy_pages_leaves_the_state_class_alone(model):
    bundle, _ = model
    pages = kv_pages.init_pages(bundle.config, 6, PAGE, n_state_blocks=3)
    pages = jax.tree.map(lambda a: jnp.arange(a.size, dtype=a.dtype)
                         .reshape(a.shape), pages)
    out = kv_pages.copy_pages(pages, jnp.int32(2), jnp.int32(4))
    for leaf in bundle.config.sequence_state_layout():
        assert np.array_equal(out[leaf], pages[leaf])
    assert np.array_equal(out["k"][:, 4], pages["k"][:, 2])


@pytest.mark.parametrize("name, leaves", [
    ("llama-debug", {"k", "v"}), ("mla-moe-debug", {"k", "v"}),
    ("lfm2-moe-debug", {"k", "v", "state"}),
    ("mimo-v2-debug", {"k", "v", "k_win", "v_win"})])
def test_the_other_families_build_the_pools_they_built(name, leaves):
    """A page-addressed state stays a row a page, a one-class family's pool k
    and v alone, and no table row gains a column."""
    bundle = get_model(name, dtype=jnp.float32)
    params = jax.tree.map(
        lambda x: jnp.zeros(x.shape, x.dtype),
        jax.eval_shape(lambda: bundle.init(bundle.config, jax.random.key(0))))
    eng = ServeEngine(bundle, params, n_slots=2, page_size=PAGE, max_len=64)
    assert set(eng.pages) == leaves
    assert kv_pages.sequence_state_layout(bundle.config) is None
    assert kv_pages.sequence_state_bytes(bundle.config, 5) == 0
    assert eng.scheduler.pool.state is None
    classes = 2 if "k_win" in leaves else 1
    assert eng.scheduler.decode_tables().shape == (2, classes * eng.max_pages)
    if "state" in leaves:
        layers, rows, width = bundle.config.state_layout()
        assert eng.pages["state"].shape == (layers, eng.pages["k"].shape[1],
                                            rows, width)
    assert eng.stats()["state_blocks_live"] == 0


def test_presets_alias_and_counts():
    for name in ("solar-open2-debug", "solar-open2-250b",
                 "upstage/Solar-Open2-250B"):
        bundle = get_model(name)
        assert bundle.family == "solar_open2"
        assert family_module(bundle.family) is solar_open2
    whole = get_model("upstage/Solar-Open2-250B").config
    assert whole.num_params() == 250_287_810_304
    assert [k for k, _ in whole.layer_table()][:5] == [
        "gqa", "kda", "kda", "kda", "gqa"]
    cut = dataclasses.replace(whole, num_layers=4, gqa_layers=(0,),
                              vocab_size=24576, experts_held=(0, 40))
    assert cut.num_params() == 3_308_353_344
    assert kv_pages.sequence_state_bytes(dataclasses.replace(
        cut, dtype=jnp.bfloat16)) == 13_025_280
    debug = get_model("solar-open2-debug")
    params = debug.init(debug.config, jax.random.key(1))
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == debug.config.num_params()
    axes = solar_open2.param_logical_axes(debug.config)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, params)) \
        == jax.tree.structure(jax.tree.map(
            lambda x: 0, axes, is_leaf=lambda x: isinstance(x, tuple)))
    with pytest.raises(ValueError, match="gqa_layers names layers"):
        dataclasses.replace(debug.config, gqa_layers=(7,))


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
    assert option in solar_open2.SERVE_REFUSES
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
    assert set(solar_open2.SERVE_REFUSES) == {
        "kv_dtype='int8'", "weight_dtype='int8'", "max_adapters", "speculate",
        "host_tier_bytes", "prefix_cache", "decode_horizon",
        "plan / shard_kv", "disaggregation", "engine swap"}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_decode_program_carries_the_familys_names(model, monkeypatch,
                                                      impl):
    """``attn/kda/kda_step`` on the decode step's KDA layers, ``kda_chunk``
    on a chunk's, whichever form computes them (``pallas``: the kernels,
    interpreted here, as ``auto`` takes them on a TPU), the state's writes
    under ``kv_write``; each program's dispatch notes its choice under its
    own name; the scheduler's ``serve.state`` span is in the vocabulary
    (``utils/trace.py``)."""
    import re

    from distributed_training_guide_tpu.ops import kda
    from distributed_training_guide_tpu.utils import trace

    class OnTpu:    # what ``ops/kda.py`` alone sees of the backend
        default_backend = staticmethod(lambda: "tpu")

        def __getattr__(self, name):
            return getattr(jax, name)

    noted = []
    if impl == "pallas":
        monkeypatch.setattr(kda, "jax", OnTpu())
        monkeypatch.setattr(kda, "resolve_interpret", lambda i: True)
    monkeypatch.setattr(kda, "note_choice",
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
    assert any("attn/kda/kda_step/" in f for f in in_decode)
    # no SCOPE of that name: the one string let through is the bare name of
    # a Python frame, `#loc554 = loc("kda_chunk"(#loc302))`, which a cached
    # inner jaxpr carries into whichever program lowers it next (read with
    # tests/test_kda.py run before this test's pallas case in one process)
    assert not any("kda_chunk" in f for f in in_decode if f != "kda_chunk")
    assert any("attn/kda/kda_chunk/" in f for f in in_chunk)
    assert set(noted) == {("kda_step", impl), ("kda_chunk", impl)}
    # the kernel solves (I + A) itself; the ``jnp`` form asks XLA to
    assert ("triangular_solve" in chunk) == (impl == "xla")
    assert any("attn/kv_write/" in f for f in in_decode)
    assert arr["tables"].shape == (2, eng.max_pages + 1)
    assert {"kda"} <= set(trace.SUBSCOPES) and "serve.state" in trace.SPANS
