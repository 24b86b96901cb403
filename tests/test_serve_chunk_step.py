"""A step that completes a prefill on the monolith's plain path dispatches
its two programs back to back (``ServeEngine.step``): the first token is
sampled and seated on the device, the decode arrays go up behind the chunk
program, and the host reads once the decode is enqueued. What that order may
not change is a single token; what rides on it is the shape of the step's
spans, which ``benchmarks/readers/step_waterfall.py`` cuts, and two counters.
"""
import contextlib
import dataclasses
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.models.lora import lora_bundle
from distributed_training_guide_tpu.serve import Request, ServeEngine
from distributed_training_guide_tpu.serve import engine as engine_mod
from distributed_training_guide_tpu.serve import scheduler as scheduler_mod
from distributed_training_guide_tpu.serve.api import (generate_many,
                                                      throughput_stats)
from distributed_training_guide_tpu.serve.engine import ModelPrograms
from distributed_training_guide_tpu.serve.router import Router

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.readers import program_span, step_waterfall  # noqa: E402

pytestmark = pytest.mark.serve

CHUNK, PAGE, MAX_LEN = 8, 4, 48


@pytest.fixture(scope="module")
def llama():
    bundle = get_model("llama-debug", dtype=jnp.float32)
    return bundle, bundle.init(bundle.config, jax.random.key(0))


@pytest.fixture(scope="module")
def programs(llama):
    """One program cache for every engine of a case and its reference: the
    order under test is the host's, and the compiles are the slow part."""
    return ModelPrograms(*llama)


@pytest.fixture(scope="module")
def lora_programs(llama):
    """A pool of two tenant adapters, one published (both factors random:
    the training init's zero B would decode like the base model)."""
    bundle, params = llama
    progs = ModelPrograms(bundle, params, max_adapters=3, adapter_rank=4)
    wrapped = lora_bundle(bundle, rank=4)
    shapes = jax.eval_shape(
        lambda: wrapped.init(wrapped.config, jax.random.key(0)))["lora"]
    leaves, treedef = jax.tree.flatten(shapes)
    keys = jax.random.split(jax.random.key(7), len(leaves))
    slot = progs.publish_adapter(jax.tree.unflatten(treedef, [
        0.2 * jax.random.normal(k, leaf.shape, jnp.float32)
        for k, leaf in zip(keys, leaves)]), name="tenant")
    return progs, slot


def engine_of(llama, programs, **kw):
    kw = {"n_slots": 3, "page_size": PAGE, "max_len": MAX_LEN,
          "prefill_chunk": CHUNK, **kw}
    return ServeEngine(*llama, programs=programs, **kw)


def read_first(eng):
    """``eng`` held to the order before this one: every first token read off
    the prefill logits before anything else of the step."""
    def on_complete(adm, logit):
        eng.drop_dev("prefilled")
        eng._step_prefills += 1
        if adm.resumed:
            return None
        token = eng.programs.sample_one(logit, adm.request, len(adm.tokens))
        return eng.scheduler.record_token(adm.slot_idx, token,
                                          from_decode=False)
    eng._on_prefill_complete = on_complete
    return eng


def serial_reference(llama, programs, reqs):
    """One request at a time through one slot, in the older order."""
    ref = read_first(engine_of(llama, programs, n_slots=1))
    out = generate_many(ref, [dataclasses.replace(r) for r in reqs],
                        max_iterations=3000)
    assert ref.stats()["chunk_steps_overlapped"] == 0
    return out


def both(prompt, n_new, **kw):
    """The request greedy and seeded."""
    return [Request(prompt_ids=list(prompt), max_new_tokens=n_new, **kw),
            Request(prompt_ids=list(prompt), max_new_tokens=n_new,
                    temperature=0.8, top_k=40, top_p=0.9,
                    seed=11 + len(prompt), **kw)]


def prompt_of(n, start=3):
    return [start + (5 * i) % 90 for i in range(n)]


def run_case(eng, reqs):
    return generate_many(eng, [dataclasses.replace(r) for r in reqs],
                         max_iterations=3000)


# what each case's engine must say of itself afterwards: every chunk step
# overlapped, none, or some (a session that also preempts and resumes)
ALL, NONE, SOME = "all", "none", "some"


def case_one_chunk(llama, programs, monkeypatch):
    reqs = both(prompt_of(5), 6) + both(prompt_of(8, 9), 5)
    return engine_of(llama, programs), reqs, ALL


def case_three_chunks(llama, programs, monkeypatch):
    reqs = both(prompt_of(20), 6) + both(prompt_of(3, 40), 9)
    return engine_of(llama, programs), reqs, ALL


def case_eos_first(llama, programs, monkeypatch):
    """The first token IS the request's eos: the decode ran that lane for
    nothing, and the request beside it reads none of it."""
    plain = both(prompt_of(6), 5)
    first = [r.generated_ids[0]
             for r in serial_reference(llama, programs, plain)]
    reqs = [dataclasses.replace(r, eos_id=t) for r, t in zip(plain, first)]
    return engine_of(llama, programs), reqs + both(prompt_of(7, 20), 7), ALL


def case_one_token(llama, programs, monkeypatch):
    return engine_of(llama, programs), both(prompt_of(6), 1), NONE


def case_prefix_fork(llama, programs, monkeypatch):
    """The second prompt shares a page and a half with the first: a
    copy-on-write fork runs in front of its chunk."""
    base = prompt_of(12)
    reqs = both(base, 5) + both(base[:6] + prompt_of(5, 50), 5)
    return engine_of(llama, programs, n_slots=1), reqs, ALL


def case_resumed(llama, programs, monkeypatch):
    """A pool the requests outgrow: sequences are preempted, re-admitted
    and replayed. A step whose growth will preempt reads its first token
    before it grows (the victim takes it along), every other one after."""
    reqs = [r for i in range(4)
            for r in both(prompt_of(1 + i % 3, 3 + i), 8 + i)]
    eng = engine_of(llama, programs, n_slots=4, max_len=16, n_pages=7)
    preempt = eng.scheduler.preempt

    def checked(slot_idx):
        slot = eng.scheduler.slots[slot_idx]
        assert slot.prefilling or slot.generated, \
            "a victim left its first token on the device"
        preempt(slot_idx)
    monkeypatch.setattr(eng.scheduler, "preempt", checked)
    return eng, reqs, SOME


def case_two_prefills(llama, programs, monkeypatch):
    """The chunk budget lets ONE prefill complete in a step; widened here
    to two, each first token is seated in its own lane."""
    advance = engine_mod.advance_prefill_chunks
    monkeypatch.setattr(engine_mod, "advance_prefill_chunks",
                        lambda *a: advance(*a) + advance(*a))
    reqs = both(prompt_of(5), 6) + both(prompt_of(7, 30), 6)
    return engine_of(llama, programs, n_slots=4), reqs, ALL


def case_lora_lane(llama, lora_programs, monkeypatch):
    progs, slot = lora_programs
    reqs = both(prompt_of(6), 6, adapter_id=slot) + both(prompt_of(6), 6)
    return engine_of(llama, progs), reqs, ALL


def case_horizon4(llama, programs, monkeypatch):
    reqs = both(prompt_of(5), 9) + both(prompt_of(11, 9), 7)
    return engine_of(llama, programs, decode_horizon=4), reqs, NONE


def case_drafter(llama, programs, monkeypatch):
    reqs = both([9, 8, 7, 9, 8, 7, 9], 8) + both([9, 8, 7, 9, 8], 6)
    return (engine_of(llama, programs, speculate="ngram", spec_k=3), reqs,
            NONE)


CASES = [case_one_chunk, case_three_chunks, case_eos_first, case_one_token,
         case_prefix_fork, case_resumed, case_two_prefills, case_lora_lane,
         case_horizon4, case_drafter]


@pytest.mark.parametrize("case", CASES,
                         ids=lambda c: c.__name__[len("case_"):])
def test_chunk_step_order_changes_no_token(case, llama, programs,
                                           lora_programs, monkeypatch):
    """Greedy and seeded, every stream equals the one-request-at-a-time
    reference in the older order, token for token and with the same reason
    to end; and the engine says which order its chunk steps took."""
    progs = lora_programs if case is case_lora_lane else programs
    eng, reqs, overlapped = case(llama, progs, monkeypatch)
    got = run_case(eng, reqs)
    ref_progs = progs[0] if case is case_lora_lane else progs
    want = serial_reference(llama, ref_progs, reqs)
    for g, w, r in zip(got, want, reqs):
        assert g.generated_ids == w.generated_ids, r
        assert g.finish_reason == w.finish_reason, r
        assert g.first_token_at >= g.submitted_at
    stats = eng.stats()
    steps, over = stats["chunk_steps"], stats["chunk_steps_overlapped"]
    assert steps > 0
    if overlapped == ALL:
        assert over == steps, (over, steps)
    elif overlapped == NONE:
        assert over == 0, over
    else:
        assert 0 < over < steps, (over, steps)
        assert stats["preemptions"] > 0
    if case is case_eos_first:
        assert [g.finish_reason for g in got[:2]] == ["eos", "eos"]
        assert all(len(g.generated_ids) == 1 for g in got[:2])
    if case is case_prefix_fork:
        assert stats["cow_forks"] >= 1
    if case is case_two_prefills:
        assert steps < len(reqs)        # a step completed two
    # nothing left on the device between steps, no page left held
    assert eng._first == [] and not eng.has_work
    sched = eng.scheduler
    assert sched.pool.n_free + sched.cache_pages_held() == sched.pool.capacity


# ---- the order, by the step's own spans -----------------------------------

class _Recorded(contextlib.AbstractContextManager):
    """``utils.trace.span`` for a test: the span as the readers take it,
    ``(name, start_ns, end_ns, thread, stats)``, appended when it closes."""

    def __init__(self, into, name, args):
        self.into, self.name, self.args = into, name, dict(args)

    def __enter__(self):
        self.start = time.perf_counter_ns()
        return self

    def set_metadata(self, **args):
        self.args.update(args)

    def __exit__(self, *exc):
        self.into.append((self.name, self.start, time.perf_counter_ns(),
                          "python3", self.args))
        return False


def record_spans(monkeypatch):
    spans = []
    for mod in (engine_mod, scheduler_mod):
        monkeypatch.setattr(
            mod, "span", lambda name, **args: _Recorded(spans, name, args))
    return spans


def named_children(children, name):
    return sorted((c for c in children if c[0] == name), key=lambda c: c[1])


def test_a_chunk_step_has_one_dispatch_one_wait_and_reads_after_both(
        llama, programs, monkeypatch):
    eng = engine_of(llama, programs)
    run_case(eng, both(prompt_of(5), 3))           # compile outside the record
    spans = record_spans(monkeypatch)
    run_case(eng, both(prompt_of(6, 12), 6) + both(prompt_of(20, 30), 5))
    steps = program_span.steps_with_children(spans, 0, 2 ** 63)
    chunk = [(s, c) for s, c in steps
             if s[4]["overlapped"] and named_children(c, "serve.prefill")]
    assert len(chunk) == 4 == sum(s[4]["overlapped"] for s, _ in steps)
    modules = []
    for step, children in chunk:
        (dispatch,) = named_children(children, "serve.dispatch")
        (wait,) = named_children(children, "serve.wait")
        (build,) = named_children(children, "serve.build")
        prefill = named_children(children, "serve.prefill")[-1]
        launch, read = named_children(children, "serve.sample")
        assert dispatch[4]["program"] == "serve_decode"
        # one whole build, for the reasons a build had before
        assert build[4]["reason"] in ("admitted", "prefilled", "left")
        (upload,) = named_children(children, "serve.upload")
        assert upload[4]["arrays"] == 11
        # the chunk's call, the sampler's launch, the whole build, the
        # decode's call, and only then the two reads
        assert prefill[2] <= launch[1] <= launch[2] <= build[1]
        assert build[2] <= dispatch[1] < dispatch[2] <= read[1]
        assert dispatch[1] < read[2] <= wait[1]
        assert launch[4]["request_id"] == read[4]["request_id"]
        assert named_children(children, "serve.book")[0][1] >= wait[2]
        # a made-up device line: the decode runs behind the chunk program,
        # from after its dispatch to just before the wait's end
        modules.append(("jit_serve_decode(1)", dispatch[2] + 1, wait[2] - 1))
    # the reader keeps every such step, as a chunk step, and cuts it whole
    joined, skipped = step_waterfall.join(chunk, modules)
    assert skipped == 0 and len(joined) == len(chunk)
    for step, children, dispatch, wait, run, _ in joined:
        cut = step_waterfall.cut(step, children, dispatch, wait, run)
        assert cut["chunk"] and cut["rebuilt"]
        assert sum(cut["phases"].values()) == cut["ns"]
    assert not program_span.decode_steps(chunk)
    # and the steps that complete no prefill are what they were
    for step, children in steps:
        if not named_children(children, "serve.prefill"):
            assert step[4]["overlapped"] == 0
            assert not named_children(children, "serve.sample")


# ---- the counters ----------------------------------------------------------

def test_the_counters_and_the_step_span_agree(llama, programs, monkeypatch):
    """``chunk_steps`` counts every step that completed a prefill,
    ``chunk_steps_overlapped`` and the ``serve.step`` span's ``overlapped``
    those that took the new order: a request of one token (the older order,
    known beforehand) counts in the first alone."""
    spans = record_spans(monkeypatch)
    eng = engine_of(llama, programs)
    reqs = both(prompt_of(5), 4) + both(prompt_of(6, 9), 1)
    t0 = time.perf_counter()
    done = run_case(eng, reqs)
    stats = eng.stats()
    assert stats["chunk_steps"] == 4
    assert stats["chunk_steps_overlapped"] == 2
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == stats["stats_seq"]
    assert sum(s[4]["overlapped"] for s in steps) == 2
    assert all(s[4]["overlapped"] in (0, 1) and s[4]["cpu_ms"] >= 0
               for s in steps)
    # the same two numbers wherever the engine's stats are passed on
    summary = throughput_stats(done, time.perf_counter() - t0, eng)
    assert (summary["chunk_steps"],
            summary["chunk_steps_overlapped"]) == (4, 2)
    assert {"chunk_steps", "chunk_steps_overlapped"} <= set(Router._SUM_KEYS)
