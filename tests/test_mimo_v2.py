"""The ``mimo_v2`` family (``models/mimo_v2.py``) and the two page classes it
serves from (``serve/kv_pages.py``, ``serve/scheduler.py``), on the CPU at the
debug preset: every kind of layer (dense + full, experts + window twice,
experts + full), keys of 24 in two 16-wide pool rows beside values of 16, a
window of 12 over pages of 8. Logits are compared, never sampled tokens:
float32 program against itself along another path, so only the order of sums
differs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import mimo_v2
from distributed_training_guide_tpu.models.registry import get_model
from distributed_training_guide_tpu.serve import Request, ServeEngine, kv_pages
from distributed_training_guide_tpu.serve.kv_pages import PagePool, pool_audit
from distributed_training_guide_tpu.serve.scheduler import Scheduler

# float32 both ways: a paged step and the whole-sequence forward sum in
# another order (and the interpreted kernel in blocks): read 2e-6..8e-6
TOL = 3e-5
PAGE, CHUNK, MAX_LEN, N_SLOTS = 8, 16, 96, 3


@pytest.fixture(scope="module")
def model():
    bundle = get_model("mimo-v2-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    # sinks and choice biases large enough that leaving one out shows
    for i, layer in enumerate(params["layers"]["attn_window"]):
        layer["sink"] = jax.random.normal(jax.random.key(1 + i),
                                          layer["sink"].shape)
    return bundle.config, params


@pytest.fixture(scope="module")
def whole(model):
    cfg, params = model
    apply = jax.jit(lambda ids: mimo_v2.apply(cfg, params, ids))
    return lambda seq: np.asarray(apply(jnp.asarray(seq)[None])[0])


class Paged:
    """The engine's loop with the logits kept: the scheduler books pages of
    both classes exactly as ``ServeEngine`` has it do, the family's paged hook
    runs under ``make_attend``, and every sequence is TEACHER-FORCED (the
    token recorded after a step is the sequence's own next one), so the
    logits at every position compare with the whole-sequence forward."""

    def __init__(self, cfg, params, impl="xla", n_pages=40):
        self.cfg, self.params, self.impl = cfg, params, impl
        second = cfg.window_kv_layout()
        self.n_win = kv_pages.window_pages_bound(second["window"], PAGE,
                                                 N_SLOTS, CHUNK)
        self.sched = Scheduler(
            n_slots=N_SLOTS, pool=PagePool(n_pages, PAGE, self.n_win),
            max_len=MAX_LEN, max_pages_per_slot=MAX_LEN // PAGE,
            prefix_cache=False, window=second["window"])
        self.pages = kv_pages.init_pages(cfg, n_pages, PAGE,
                                         n_window_pages=self.n_win)
        self.seqs, self.logits, self.pending = {}, {}, {}
        self.step_fn = jax.jit(self._step, static_argnames="t")

    def _step(self, pages, ids, lengths, tables, n_valid, t):
        attend = kv_pages.make_attend(tables, lengths, impl=self.impl,
                                      n_valid=n_valid)
        logits, cache = mimo_v2.paged_decode_step(
            self.cfg, self.params, ids, lengths, pages, attend,
            all_logits=True)
        cache.pop("routing", None)
        return logits, cache

    def submit(self, seq, n_prompt):
        rid = self.sched.submit(Request(
            prompt_ids=list(seq[:n_prompt]),
            max_new_tokens=len(seq) - n_prompt, temperature=0.0,
            eos_id=None))
        self.seqs[rid], self.logits[rid] = list(seq), {}
        return rid

    def audit(self):
        s = self.sched
        pool_audit(s.pool, [{p: 1 for slot in s.slots if slot
                             for p in slot.pages}],
                   window_holder_maps=[s.window_holders()])

    def step(self):
        """One engine iteration: admit, one chunk, grow, one decode step."""
        s = self.sched
        for adm in s.try_admit():
            self.pending[adm.slot_idx] = adm
        for i in s.prefilling_indices()[:1]:
            slot, adm = s.slots[i], self.pending[i]
            start = slot.cache_len
            real = min(CHUNK, slot.target_len - start)
            s.reserve_window(i, start, real)
            ids = np.zeros((1, CHUNK), np.int32)
            ids[0, :real] = adm.tokens[start:start + real]
            logits, self.pages = self.step_fn(
                self.pages, jnp.asarray(ids), jnp.asarray([start], jnp.int32),
                jnp.asarray(s.table_row(i)[None]),
                jnp.asarray([real], jnp.int32), t=CHUNK)
            rid = adm.request.request_id
            for j in range(real):
                self.logits[rid][start + j] = np.asarray(logits[0, j])
            s.commit_tokens(i, real)
            if not s.slots[i].prefilling:
                self.pending.pop(i)
                if not s.slots[i].generated:    # a resumed slot has them
                    s.record_token(i, self.seqs[rid][start + real],
                                   from_decode=False)
        s.grow_for_decode()
        active = s.active_indices()
        if active:
            arr = s.decode_arrays()
            logits, self.pages = self.step_fn(
                self.pages, jnp.asarray(arr["tokens"])[:, None],
                jnp.asarray(arr["lengths"]), jnp.asarray(arr["tables"]),
                jnp.ones((N_SLOTS,), jnp.int32), t=1)
            for i in active:
                slot = s.slots[i]
                rid, pos = slot.request.request_id, int(arr["lengths"][i])
                assert int(arr["tokens"][i]) == self.seqs[rid][pos]
                self.logits[rid][pos] = np.asarray(logits[i, 0])
                s.record_token(i, self.seqs[rid][pos + 1], from_decode=True)
        self.audit()

    def run(self):
        while self.sched.has_work:
            self.step()


def sequences(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, n + 1).tolist() for n in lengths]


def assert_logits(run: Paged, rid, want, first=0):
    got = run.logits[rid]
    last = len(run.seqs[rid]) - 2       # the last position that was an input
    assert sorted(got) == list(range(0, last + 1))
    worst = max(float(np.abs(got[p] - want[p]).max())
                for p in range(first, last + 1))
    assert worst < TOL, worst


@pytest.mark.parametrize("impl", ["flash"])
def test_chunks_then_decode_through_both_classes_match_the_whole_forward(
        model, whole, impl):
    """Sequences of unequal length in one batch (prompts of 5, 37 and 50:
    one to four chunks of 16), decoded past several windows of 12 and several
    page boundaries (to 70 tokens at most), through the interpreted kernel
    (the tests below take the gather path): the logits at EVERY position,
    prompt and reply, are the whole-sequence forward's."""
    cfg, params = model
    seqs = sequences((30, 70, 64))
    run = Paged(cfg, params, impl=impl)
    rids = [run.submit(seq, n) for seq, n in zip(seqs, (5, 37, 50))]
    run.run()
    for rid, seq in zip(rids, seqs):
        assert_logits(run, rid, whole(seq[:-1]))
    stats = run.sched.stats
    assert stats["window_pages_released"] > 0 and stats["preempted"] == 0
    assert run.sched.pool.n_free == run.sched.pool.capacity
    assert run.sched.pool.window.n_free == run.sched.pool.window.capacity


def test_a_window_page_goes_back_the_step_its_last_position_leaves_the_window():
    """Host bookkeeping alone: a slot decoding from position 20 (window 12,
    page 8) holds the pages of positions ``t - 11 .. t`` before each step and
    hands a page back in the very step after which no query can see its last
    position; a chunk holds its own pages while it runs."""
    pool = PagePool(64, PAGE, 32)
    sched = Scheduler(n_slots=1, pool=pool, max_len=MAX_LEN,
                      max_pages_per_slot=MAX_LEN // PAGE, prefix_cache=False,
                      window=12)
    sched.submit(Request(prompt_ids=list(range(20)), max_new_tokens=40,
                         temperature=0.0, eos_id=None))
    (adm,) = sched.try_admit()
    slot = sched.slots[0]
    assert slot.window_pages == {}          # nothing until a chunk runs
    sched.reserve_window(0, 0, 16)
    assert sorted(slot.window_pages) == [0, 1]
    sched.commit_tokens(0, 16)              # next query: position 16 sees 5..
    assert sorted(slot.window_pages) == [0, 1]
    sched.reserve_window(0, 16, 4)
    sched.commit_tokens(0, 4)               # position 20 sees 9..20: page 0 gone
    assert sorted(slot.window_pages) == [1, 2]
    sched.record_token(0, 1, from_decode=False)
    released = sched.stats["window_pages_released"]
    for t in range(20, 40):
        sched.grow_for_decode()
        want = list(kv_pages.window_page_span(t, 1, 12, PAGE))
        assert sorted(slot.window_pages) == want, (t, slot.window_pages)
        sched.record_token(0, 1, from_decode=True)
        # after the step, position t + 1 sees t - 10 ..: a page whose last
        # position is t - 11 is back on the free list NOW
        assert min(slot.window_pages) == max(t + 1 - 11, 0) // PAGE
        assert len(sched.window_holders()) == len(slot.window_pages)
        assert pool.window.n_free == pool.window.capacity - len(
            slot.window_pages)
    assert sched.stats["window_pages_released"] - released == 2
    assert sched.live_pages_by_class() == {"full": 5, "window": 2}


def test_freed_pages_of_both_classes_are_reused_from_clean(model, whole):
    """A pool that only fits one request at a time: the second request takes
    the pages (both classes, LIFO) the first left full of its keys, and its
    logits are those of a run alone."""
    cfg, params = model
    a, b = sequences((60, 44), seed=3)
    run = Paged(cfg, params, n_pages=10)        # 9 pages: 72 tokens
    ra, rb = run.submit(a, 30), run.submit(b, 20)
    run.run()
    assert_logits(run, ra, whole(a[:-1]))
    assert_logits(run, rb, whole(b[:-1]))
    assert run.sched.stats["admission_blocked"] > 0


def test_preempt_then_resume_gives_the_logits_of_an_undisturbed_run(model,
                                                                    whole):
    """A sequence preempted mid-decode (its pages of both classes dropped)
    is prefilled again and replays its recorded tokens through the decode
    step: every logit, before and after, is the undisturbed forward's."""
    cfg, params = model
    (seq,) = sequences((66,), seed=5)
    run = Paged(cfg, params)
    rid = run.submit(seq, 25)
    for _ in range(20):
        run.step()
    assert len(run.sched.slots[0].generated) > 10
    run.sched.preempt(0)
    run.audit()
    assert run.sched.pool.window.n_free == run.sched.pool.window.capacity
    run.run()
    assert_logits(run, rid, whole(seq[:-1]))
    assert run.sched.stats["preempted"] == 1


def test_a_copy_on_write_fork_of_both_classes_decodes_like_the_original(
        model, whole):
    """``copy_pages`` over both classes: every page a running slot holds is
    forked into a fresh page of its class (ids of their own), the slot is
    pointed at the copies and the originals are freed and overwritten with
    another request's keys; the slot decodes on as if nothing had moved."""
    cfg, params = model
    (seq,) = sequences((60,), seed=7)
    run = Paged(cfg, params)
    rid = run.submit(seq, 21)
    for _ in range(12):
        run.step()
    s, slot = run.sched, run.sched.slots[0]
    copy = jax.jit(kv_pages.copy_pages)
    for col, src in enumerate(list(slot.pages)):
        (dst,) = s.pool.alloc(1)
        run.pages = copy(run.pages, jnp.int32(src), jnp.int32(dst))
        slot.pages[col] = dst
        s.pool.free([src])
    for logical, src in list(slot.window_pages.items()):
        (dst,) = s.pool.window.alloc(1)
        # the window leaves alone: the full leaves copy a page onto itself
        run.pages = copy(run.pages, jnp.int32(0), jnp.int32(0),
                         (jnp.int32(src), jnp.int32(dst)))
        slot.window_pages[logical] = dst
        s.pool.window.free([src])
    run.audit()
    other = run.submit(sequences((40,), seed=8)[0], 30)   # takes the originals
    run.run()
    assert_logits(run, rid, whole(seq[:-1]))
    assert len(run.logits[other]) == 40


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
    cfg, params = model
    bundle = get_model("mimo-v2-debug", dtype=jnp.float32)
    assert option in mimo_v2.SERVE_REFUSES
    with pytest.raises(ValueError, match="does not serve with "
                       + option.replace("(", r"\(")):
        ServeEngine(bundle, params, n_slots=2, page_size=PAGE,
                    max_len=MAX_LEN, **kwargs)


def test_disaggregation_is_refused_by_name(model):
    from distributed_training_guide_tpu.serve.disagg import DisaggEngine

    bundle = get_model("mimo-v2-debug", dtype=jnp.float32)
    with pytest.raises(ValueError, match="does not serve with disaggregation"):
        DisaggEngine(bundle, model[1], n_slots=2, page_size=PAGE,
                     max_len=MAX_LEN)


def test_the_engine_sizes_the_window_class_so_that_no_reservation_fails(
        model):
    """The second class holds a bounded few pages a slot and one chunk's
    worth, the trash page beside them: nothing a caller sets."""
    cfg, params = model
    bundle = get_model("mimo-v2-debug", dtype=jnp.float32)
    need = kv_pages.window_pages_bound(12, PAGE, 3, CHUNK)
    assert need == 1 + 3 * 3 + 5
    eng = ServeEngine(bundle, params, n_slots=3, page_size=PAGE,
                      max_len=MAX_LEN, prefill_chunk=CHUNK)
    assert eng.scheduler.pool.window.n_pages == need
    assert eng.scheduler.cache is None          # no prefix cache: refused
    assert eng.pages["k_win"].shape == (2 * 2, need, PAGE, 2, 16)
    assert eng.pages["k"].shape[0] == 2 * 2 and eng.pages["v"].shape[0] == 2
    assert eng.kv_cache_bytes() == (
        kv_pages.kv_page_bytes(cfg, page_size=PAGE, n_pages=eng.pages["k"].shape[1])
        + kv_pages.kv_page_bytes(cfg, page_size=PAGE, n_pages=need,
                                 window_class=True))
    assert eng.stats()["live_pages_by_class"] == {"full": 0, "window": 0}


def test_the_published_preset_is_the_catalog_rows_shape():
    cfg = mimo_v2.PRESETS["mimo-v2.5"]
    table = cfg.layer_table()
    assert cfg.num_layers == 48 and cfg.num_window_layers == 39
    assert table[0] == ("full", 0, True, 0)             # dense + full
    assert table[1] == ("window", 0, False, 0) and table[5][0] == "full"
    assert [k for k, *_ in table[6:12]] == ["window"] * 5 + ["full"]
    assert cfg.rotary_dims == 64 and cfg.key_parts == 2 and cfg.row_width == 128
    assert cfg.kv_layout() == {"k": (4, 128), "v": (4, 128)}
    assert cfg.window_kv_layout() == {"layers": 39, "window": 128,
                                      "k": (8, 128), "v": (8, 128)}
    # resident bytes a token: two 128-wide key rows and the value row a head
    assert kv_pages.kv_page_bytes(cfg, page_size=1) == 9 * 4 * 384 * 2
    assert kv_pages.kv_page_bytes(cfg, page_size=1, window_class=True) \
        == 39 * 8 * 384 * 2
    no_window = dataclasses.replace(cfg, hybrid_layer_pattern=(0, 0),
                                    moe_layer_freq=(0, 1))
    assert no_window.window_kv_layout() is None


def test_auto_takes_the_compiled_kernel_at_the_published_widths(monkeypatch):
    """What the entry points print at start-up (``resolve_attend_for``): on a
    TPU the published rows (128 wide, page 128) pass the kernel's gate for
    BOTH kinds, decode and chunk alike (the gate is T-independent); off one
    ``auto`` says why it gathers."""
    cfg = mimo_v2.PRESETS["mimo-v2.5"]
    assert kv_pages.resolve_attend_for(cfg, "auto", 128)[0] == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    impl, reason = kv_pages.resolve_attend_for(cfg, "auto", 128)
    assert impl == "flash" and "passes" in reason
    assert kv_pages.pool_layout(cfg)["k"] == (4, 128)


KERNEL_CASES = {
    # query heads / kv heads as published (16 and 8 to a kv head), keys of 192
    # in two 128-wide rows, values of 128; windows that end inside a page
    "full-16-to-1": dict(hq=16, hkv=1, window=None, sink=False),
    "window-8-to-1-sink": dict(hq=16, hkv=2, window=12, sink=True),
    "window-ends-inside-a-page": dict(hq=8, hkv=1, window=5, sink=True),
}


@pytest.mark.parametrize("case,t", [
    ("full-16-to-1", 1), ("window-8-to-1-sink", 16),
    ("window-ends-inside-a-page", 1)])
def test_the_interpreted_kernel_matches_the_gather_path(case, t):
    c = KERNEL_CASES[case]
    rng = np.random.default_rng(1)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    n_layers, n_pages, m, d = 2, 20, 6, 128
    k_pages = draw(n_layers * 2, n_pages, PAGE, c["hkv"], d)
    v_pages = draw(n_layers, n_pages, PAGE, c["hkv"], d)
    tables = jnp.asarray(rng.permutation(np.arange(1, n_pages))[:3 * m]
                         .reshape(3, m), jnp.int32)
    lengths = jnp.asarray([0, 13, m * PAGE - t], jnp.int32)
    pad = lambda x: jnp.pad(x, ((0, 0),) * 3 + ((0, 64),))
    q, k_new = pad(draw(3, t, c["hq"], 192)), pad(draw(3, t, c["hkv"], 192))
    v_new = draw(3, t, c["hkv"], d)
    sink = draw(c["hq"]) if c["sink"] else None
    got, want = (kv_pages.paged_attend(
        q, k_new, v_new, k_pages, v_pages, jnp.int32(1), tables, lengths,
        window=c["window"], scale=192 ** -0.5, impl=impl, sink=sink)[0]
        for impl in ("flash", "xla"))
    assert got.shape == (3, t, c["hq"], d)
    assert float(jnp.abs(got - want).max()) < 1e-5
    if sink is not None:    # the sink moves the result: it is not ignored
        bare = kv_pages.paged_attend(
            q, k_new, v_new, k_pages, v_pages, jnp.int32(1), tables, lengths,
            window=c["window"], scale=192 ** -0.5, impl="flash")[0]
        assert float(jnp.abs(bare - want).max()) > 1e-2


# ---- the one-class families keep their programs ------------------------------
# sha256 (first 16 hex) of the lowered decode step and 16-token chunk program
# at the debug size, recorded on the parent commit of the PR that brought the
# second page class (PR 37) and equal on its tree: the one-class layout is the
# case of the same code in which none of the new lines runs. A PR that changes
# one of these programs ON PURPOSE records its hash again here.
PARENT_PROGRAMS = {
    "llama-debug:flash": [
        "326f7f277f972064",
        "b52d503767171747"
    ],
    "lfm2-moe-debug:flash": [
        "2cc82ab4b41aafd0",
        "1ce88270bc954866"
    ],
    "mla-moe-debug:flash": [
        "d1a3b967c81684b6",
        "a006160a03ba5f83"
    ],
    "llama-debug:xla": [
        "6491097fdef7eef1",
        "f29a0914e3151c08"
    ]
}


@pytest.mark.parametrize("case", sorted(PARENT_PROGRAMS))
def test_one_class_families_lower_to_the_programs_they_had(case):
    import hashlib

    name, impl = case.split(":")
    bundle = get_model(name, dtype=jnp.float32)
    params = jax.tree.map(
        lambda x: jnp.zeros(x.shape, x.dtype),
        jax.eval_shape(lambda: bundle.init(bundle.config, jax.random.key(0))))
    eng = ServeEngine(bundle, params, n_slots=4, page_size=16, max_len=64,
                      attend_impl=impl, prefill_chunk=16)
    arr = {k: jnp.asarray(v)
           for k, v in eng.scheduler.decode_arrays().items()}
    decode = eng._decode_fn.lower(
        eng.params, dict(eng.pages), *(arr[k] for k in (
            "tokens", "lengths", "tables", "seeds", "temps", "top_ks",
            "top_ps", "actives"))).as_text()
    chunk = eng.programs.chunk_for(16).lower(
        eng.params, dict(eng.pages), jnp.zeros((1, 16), jnp.int32),
        jnp.zeros((1,), jnp.int32), arr["tables"][:1],
        jnp.asarray(3, jnp.int32), jnp.asarray([4], jnp.int32)).as_text()
    got = [hashlib.sha256(text.encode()).hexdigest()[:16]
           for text in (decode, chunk)]
    assert got == PARENT_PROGRAMS[case]


def test_an_engine_swap_is_refused_by_name(model):
    from distributed_training_guide_tpu.serve.elastic import new_generation

    bundle = get_model("mimo-v2-debug", dtype=jnp.float32)
    eng = ServeEngine(bundle, model[1], n_slots=2, page_size=PAGE,
                      max_len=MAX_LEN)
    with pytest.raises(ValueError, match="does not serve with engine swap"):
        new_generation(eng, n_slots=3)
