"""The plain decode step's two orders (``ServeEngine.step``): with a decode
program in flight and the step quiet, the program for token n+1 is enqueued
BEFORE the host reads token n; everything else reads first and runs the
boundary in the next step. What the order may not change is a single token
or, without an eos, the step in which anything is booked or admitted; what
rides on it is the shape of a step's spans, which
``benchmarks/readers/step_waterfall.py`` cuts, and one counter.

Every case is held to the SYNCHRONOUS order, forced in the test (an engine
whose quiet test never passes: the parent's step, link for link), one request
at a time through one slot where the case says batch-1.
"""
import contextlib
import dataclasses
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.serve import Request, ServeEngine
from distributed_training_guide_tpu.serve import engine as engine_mod
from distributed_training_guide_tpu.serve import scheduler as scheduler_mod
from distributed_training_guide_tpu.serve.engine import ModelPrograms
from distributed_training_guide_tpu.serve.kv_pages import (pool_audit,
                                                           window_page_span)

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.readers import program_span, step_waterfall  # noqa: E402
from distributed_training_guide_tpu.utils.trace import (  # noqa: E402
    ADMIT_BLOCKS, NOT_QUIET, STEP_ORDERS)

pytestmark = pytest.mark.serve

PAGE, CHUNK, MAX_LEN = 8, 16, 96

# llama serves with the prefix cache on (its default); the state-class and
# the two-class families refuse it
FAMILIES = {"llama": "llama-debug", "jamba": "jamba-debug",
            "mimo": "mimo-v2-debug"}


@pytest.fixture(scope="module")
def programs():
    """One program cache a family for every engine of the module: the order
    under test is the host's, and the compiles are the slow part."""
    made = {}

    def of(family):
        if family not in made:
            bundle = get_model(FAMILIES[family], dtype=jnp.float32)
            made[family] = ModelPrograms(
                bundle, bundle.init(bundle.config, jax.random.key(0)))
        return made[family]
    return of


def engine_of(progs, synchronous=False, **kw):
    kw = {"n_slots": 3, "page_size": PAGE, "max_len": MAX_LEN,
          "prefill_chunk": CHUNK, **kw}
    eng = ServeEngine(progs.bundle, progs.params, programs=progs, **kw)
    if synchronous:     # the parent's order: no step is ever quiet
        eng._ahead = lambda pending_k, first=(), resident=None: (None, "")
    return eng


def prompt_of(n, start=3):
    return [start + (5 * i) % 90 for i in range(n)]


def request(n_prompt, n_new, i=0, **kw):
    """Request ``i``: greedy where ``i`` is even, seeded otherwise."""
    sampling = {} if i % 2 == 0 else {
        "temperature": 0.8, "top_k": 40, "top_p": 0.9, "seed": 11 + i}
    return Request(prompt_ids=prompt_of(n_prompt, 3 + 7 * i),
                   max_new_tokens=n_new, **sampling, **kw)


def audit(eng):
    sched = eng.scheduler
    holders = [{}]
    for s in sched.slots:
        if s is not None:
            for p in s.pages:
                holders[0][p] = holders[0].get(p, 0) + 1
    if sched.cache is not None:
        cached, stack = {}, list(sched.cache._roots.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node.page is not None:
                cached[node.page] = 1
        holders.append(cached)
    pool_audit(sched.pool, holders,
               window_holder_maps=(None if sched.window is None
                                   else [sched.window_holders()]),
               state_holder_maps=(None if sched.pool.state is None
                                  else [sched.state_holders()]))


def run(eng, reqs, clients=None, on_step=None):
    """Drive ``eng`` as the benchmark's closed loop does: ``clients``
    requests in flight (a function of the steps taken so far where callers
    arrive as the run goes), the next one submitted when one finishes.
    Returns the results in request order and a row a step: ``(tokens booked
    by request, requests admitted, pipelined)``."""
    todo = [dataclasses.replace(r) for r in reqs]
    clients = len(todo) if clients is None else clients
    order, done, rows = [], {}, []
    have: dict[int, int] = {}
    live = 0
    for _ in range(3000):
        while todo and live < (clients(len(rows)) if callable(clients)
                               else clients):
            order.append(eng.submit(todo.pop(0)))
            live += 1
        if not eng.has_work:
            break
        admitted = eng.scheduler.stats["admitted"]
        pipelined = eng.decode_steps_pipelined
        finished = eng.step()
        now = {rid: len(t) for rid, t in eng.partial_tokens().items()}
        for res in finished:
            now[res.request_id] = len(res.generated_ids)
            done[res.request_id] = res
            live -= 1
        booked = {order.index(rid): n - have.get(rid, 0)
                  for rid, n in now.items() if n != have.get(rid, 0)}
        have.update(now)
        rows.append((booked, eng.scheduler.stats["admitted"] - admitted,
                     eng.decode_steps_pipelined - pipelined))
        audit(eng)
        if on_step is not None:
            on_step(eng, finished)
    assert not eng.has_work and not todo
    return [done[rid] for rid in order], rows


def one_token_a_step(rows):
    """No step books two decode tokens of one slot, or none for a slot that
    was decoding: a request that had a token before a step has exactly one
    more after it, until it is done (its first step books its first token
    and, where its prefill completed there, the decode's beside it)."""
    seen: dict[int, int] = {}
    for booked, _, _ in rows:
        for i in seen:
            if i not in booked:
                seen[i] = -1            # done: never booked again
        for i, n in booked.items():
            assert seen.get(i, 0) >= 0, (i, "booked after it was done")
            assert n == 1 if seen.get(i) else n in (1, 2), (i, n)
            seen[i] = seen.get(i, 0) + n
    live_rows = [set(b) for b, _, _ in rows]
    for i in seen:      # booked in every step from its first to its last
        at = [j for j, b in enumerate(live_rows) if i in b]
        assert at == list(range(at[0], at[-1] + 1)), (i, at)


def batch1(progs, reqs):
    """One request at a time through one slot, in the synchronous order."""
    ref = engine_of(progs, synchronous=True, n_slots=1)
    out, _ = run(ref, reqs, clients=1)
    assert ref.stats()["decode_steps_pipelined"] == 0
    return out


def same_tokens(got, want, reqs):
    for g, w, r in zip(got, want, reqs):
        assert g.generated_ids == w.generated_ids, r
        assert g.finish_reason == w.finish_reason, r


# ---- (a) (d) a quiet run ----------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_a_quiet_run_is_pipelined_and_changes_no_token(programs, family):
    """Three replies of 44 tokens decode side by side across five page
    boundaries each with nothing queued: from the step that completes the
    last prefill to the one that ends the first reply, every step enqueues
    ahead, and every token is the one-at-a-time engine's."""
    progs = programs(family)
    reqs = [request(5 + 4 * i, 44, i) for i in range(3)]
    eng = engine_of(progs)
    got, rows = run(eng, reqs)
    same_tokens(got, batch1(progs, reqs), reqs)
    stats = eng.stats()
    # one prefill completes a step: steps 1 and 2 have prefills pending,
    # step 3 enters, steps 4 to 42 are pipelined, step 43 reads the first
    # reply's last token (its budget's end, known a step ahead: a drain),
    # and the two steps left each end a reply
    assert [p for *_, p in rows] == [0] * 3 + [1] * 39 + [0] * 3
    assert stats["decode_steps_pipelined"] == 39
    # a decode program a booked token of each slot, and none beside them
    assert stats["decode_steps"] == 45
    assert stats["decode_tokens"] == 3 * 43
    one_token_a_step(rows)
    assert eng._inflight is None and eng._first == []
    sched = eng.scheduler
    assert sched.pool.n_free + sched.cache_pages_held() == sched.pool.capacity


# ---- (b) (d) budgets that end at different steps -----------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_budgets_end_at_different_steps_and_the_schedule_is_the_parents(
        programs, family):
    """A closed loop of three clients over nine replies of 5 to 21 tokens:
    the step in which each token is booked and each request admitted is the
    synchronous order's, step for step, and no step books two decode tokens
    of one slot or none."""
    progs = programs(family)
    reqs = [request(3 + (5 * i) % 17, 5 + (7 * i) % 17, i) for i in range(9)]
    eng = engine_of(progs)
    got, rows = run(eng, reqs, clients=3)
    sync = engine_of(progs, synchronous=True)
    want, sync_rows = run(sync, reqs, clients=3)
    same_tokens(got, want, reqs)
    same_tokens(got, batch1(progs, reqs), reqs)
    assert [(b, a) for b, a, _ in rows] == [(b, a) for b, a, _ in sync_rows]
    one_token_a_step(rows)
    one_token_a_step(sync_rows)
    assert all(r.finish_reason == "length" for r in got)
    stats = eng.stats()
    assert 0 < stats["decode_steps_pipelined"] < stats["decode_steps"]
    assert sync.stats()["decode_steps_pipelined"] == 0
    # a budget's end is a boundary, known a step ahead: no program ever ran
    # a lane too many, so both engines ran the same number
    assert stats["decode_steps"] == sync.stats()["decode_steps"]
    assert stats["decode_tokens"] == sync.stats()["decode_tokens"]


# ---- (c) (d) an eos that arrives mid-pipeline --------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_an_eos_mid_pipeline_books_nothing_of_the_lane_too_many(
        programs, family):
    """Two replies end by an eos that the host reads while the program for
    the token after it is already enqueued: that lane's token is not booked,
    the pages and the state block the slot freed go to the next admission,
    and every stream is the one-at-a-time engine's."""
    progs = programs(family)
    plain = [request(6 + 3 * i, 30, i) for i in range(5)]
    free_run = batch1(progs, plain)
    # the eos of the two seeded requests: a token of the free-running reply,
    # eight or more in, that did not occur earlier in it
    reqs, ends = list(plain), {}
    for i in (1, 3):
        tokens = free_run[i].generated_ids
        at = next(j for j in range(8 + i, 28) if tokens[j] not in tokens[:j])
        reqs[i] = dataclasses.replace(plain[i], eos_id=tokens[at])
        ends[i] = at + 1
    eng = engine_of(progs, n_slots=2)
    behind = []

    def on_step(eng, finished):
        for res in finished:
            if res.finish_reason == "eos":
                # the program after the eos is in flight, that lane in it
                behind.append(eng._inflight is not None)
    got, rows = run(eng, reqs, clients=2, on_step=on_step)
    same_tokens(got, batch1(progs, reqs), reqs)
    assert [r.finish_reason for r in got] == [
        "eos" if i in ends else "length" for i in range(5)]
    assert {i: len(got[i].generated_ids) for i in ends} == ends
    assert len(behind) == 2 and any(behind), behind
    one_token_a_step(rows)
    stats = eng.stats()
    # a program or two ran a lane too many; their tokens are in no count
    assert stats["decode_tokens"] == sum(len(r.generated_ids) - 1 for r in got)
    assert stats["decode_steps_pipelined"] > 0
    assert eng._inflight is None
    sched = eng.scheduler
    assert sched.pool.n_free + sched.cache_pages_held() == sched.pool.capacity
    if sched.pool.state is not None:
        assert sched.stats["state_blocks_taken"] == 5 \
            == sched.stats["state_blocks_returned"]


# ---- what is released at booking is not what the enqueued program reads ------

def test_a_window_page_released_at_booking_is_none_the_next_program_reads(
        programs, monkeypatch):
    """The window class (pages of 8 under a window of 12): every decode
    program, when it is enqueued and again after the step has booked and
    released, finds the pages its query sees held by its slot and off the
    free list. The host's length at a booking is the enqueued program's own:
    a release reckoned from it is late for the device, never early."""
    progs = programs("mimo")
    eng = engine_of(progs)
    sched = eng.scheduler
    window, n_full = sched.window, sched.max_pages
    decode = progs._decode_fn
    inflight, checked = [], [0, 0]

    def reads(tables, lengths):
        """``{slot: physical window pages}`` a program's queries see."""
        out = {}
        for i in sched.active_indices():
            span = window_page_span(int(lengths[i]), 1, window, PAGE)
            out[i] = [int(tables[i, n_full + p]) for p in span]
        return out

    def held(pages_by_slot):
        for i, pages in pages_by_slot.items():
            slot = sched.slots[i]
            if slot is None:            # left: its lane ran for nothing
                continue
            assert 0 not in pages, (i, pages)
            assert set(pages) <= set(slot.window_pages.values()), (i, pages)
            assert not set(pages) & sched.pool.window._free_set, (i, pages)

    def checked_decode(params, pools, tokens, lengths, tables, *rest):
        pages = reads(np.asarray(tables), np.asarray(lengths))
        held(pages)
        inflight.append(pages)
        checked[0] += 1
        return decode(params, pools, tokens, lengths, tables, *rest)

    def on_step(eng, finished):
        if eng._inflight is not None:   # after this step's release
            held(inflight[-1])
            checked[1] += 1
    monkeypatch.setattr(progs, "_decode_fn", checked_decode)
    reqs = [request(9 + 5 * i, 40, i) for i in range(3)]
    got, _ = run(eng, reqs, on_step=on_step)
    monkeypatch.undo()
    same_tokens(got, batch1(progs, reqs), reqs)
    assert sched.stats["window_pages_released"] >= 9
    assert checked[0] >= 40 and checked[1] >= 30
    assert eng.stats()["decode_steps_pipelined"] >= 30


# ---- (e) memory pressure ------------------------------------------------------

@pytest.mark.parametrize("family", ["llama", "jamba"])
def test_growth_that_would_preempt_drains_first(programs, family,
                                                monkeypatch):
    """A pool the replies outgrow: the page of write n+1 is not there, so
    the step is not quiet, the token in flight is booked, and growth
    preempts in the next step, on the host's state whole: the victim goes
    back with every token it has, and every stream is batch-1's."""
    progs = programs(family)
    reqs = [request(4 + i, 30 + i, i) for i in range(3)]
    eng = engine_of(progs, n_pages=9, max_len=48)
    preempt = eng.scheduler.preempt
    victims = []

    def checked(slot_idx):
        assert eng._inflight is None, "preempted under a program in flight"
        victims.append(len(eng.scheduler.slots[slot_idx].generated))
        preempt(slot_idx)
    monkeypatch.setattr(eng.scheduler, "preempt", checked)
    got, rows = run(eng, reqs)
    same_tokens(got, batch1(progs, reqs), reqs)
    stats = eng.stats()
    assert stats["preemptions"] > 0 and any(victims)
    assert stats["decode_steps_pipelined"] > 0
    sched = eng.scheduler
    assert sched.pool.n_free + sched.cache_pages_held() == sched.pool.capacity


# ---- (f) what needs the host's state whole ------------------------------------

def act_publish(eng, progs):
    """A forced mid-stream publish books what is in flight at once."""
    eng.publish_params(jax.tree.map(jnp.copy, progs.params), force=True)
    return eng, "settled"


def act_swap(eng, progs):
    """An engine swap books what is in flight before its export reads the
    scheduler; the sequences go on in the new generation."""
    from distributed_training_guide_tpu.serve.elastic import swap_engine

    new, evicted, stats = swap_engine(eng)
    assert evicted == [] and stats["seated"] + stats["requeued"] == 2
    assert not eng.has_work
    return new, "settled"


def act_drain(eng, progs):
    """A flag (another thread may set it): the steps go on as they were."""
    eng.drain()
    assert eng.draining
    return eng, "flying"


def act_gather(eng, progs):
    """A read of the pool, ordered behind the program on the device."""
    slot = next(s for s in eng.scheduler.slots if s is not None)
    assert eng.gather_pages(slot.pages[:1])
    return eng, "flying"


def act_publish_refused(eng, progs):
    with pytest.raises(RuntimeError, match="in flight"):
        eng.publish_params(jax.tree.map(jnp.copy, progs.params))
    return eng, "flying"


def act_horizon(eng, progs):
    """Seen by the next step's quiet test, which drains."""
    assert eng.set_decode_horizon(2) == 2
    assert eng._inflight is not None
    assert eng.step() == [] and eng._inflight is None   # the plain drain
    return eng, "drained"


@pytest.mark.parametrize("act", [act_publish, act_swap, act_drain,
                                 act_gather, act_publish_refused,
                                 act_horizon],
                         ids=lambda a: a.__name__[len("act_"):])
def test_what_needs_the_host_state_whole_finds_it_whole(programs, act):
    """``partial_tokens`` is what has been BOOKED, one token a step and
    request, while a program is in flight. A swap and a forced publish book
    that program at once, outside a step; a horizon switched on is seen by
    the next step, which drains; a drain flag, a refused publish and a page
    gather leave it flying. The streams go on to batch-1's tokens."""
    progs = programs("llama")
    reqs = [request(5 + 3 * i, 24, i) for i in range(2)]
    eng = engine_of(progs)
    rids = [eng.submit(dataclasses.replace(r)) for r in reqs]
    for n in range(1, 9):
        assert eng.step() == []
        # a prefill completes a step: the first token and n more, and the
        # second request a step behind
        assert [len(t) for t in eng.partial_tokens().values()] \
            == [n + 1, n][:n]
    assert eng._inflight is not None and eng.has_work
    steps = eng.stats()["decode_steps"]
    eng, state = act(eng, progs)
    booked = [len(t) for t in eng.partial_tokens().values()]
    if state == "settled":
        assert eng._inflight is None                # booked, outside a step
        assert booked == [10, 9]
        assert eng.stats()["decode_steps"] in (0, steps)    # none enqueued
    elif state == "flying":
        assert eng._inflight is not None and booked == [9, 8]
        assert eng.stats()["decode_steps"] == steps
    else:
        assert booked == [10, 9]
    done = {}
    while eng.has_work:
        done.update((r.request_id, r) for r in eng.step())
    same_tokens([done[rid] for rid in rids], batch1(progs, reqs), reqs)


def test_has_work_holds_while_the_last_lane_ran_for_nothing(programs):
    """The only request ends by an eos read behind an enqueued program: the
    scheduler is empty, the engine is not, and the next step books nothing
    and returns nothing; ``settle`` at that instant books it outside a
    step."""
    progs = programs("llama")
    tokens = batch1(progs, [request(6, 20, 1)])[0].generated_ids
    at = next(j for j in range(6, 18) if tokens[j] not in tokens[:j])
    req = request(6, 20, 1, eos_id=tokens[at])
    for settle in (False, True):
        eng = engine_of(progs)
        eng.submit(dataclasses.replace(req))
        # the step of the prefill books two tokens, every other one one
        (res,) = [r for _ in range(at) for r in eng.step()]
        assert res.finish_reason == "eos"
        assert res.generated_ids == tokens[:at + 1]
        assert not eng.scheduler.has_work and eng.has_work
        if settle:
            eng.settle()
            assert eng.take_settled() == []
        else:
            assert eng.step() == []
        assert not eng.has_work and eng._inflight is None
        assert eng.stats()["decode_tokens"] == at
        sched = eng.scheduler
        assert sched.pool.n_free + sched.cache_pages_held() \
            == sched.pool.capacity


# ---- (g) the order, by the step's own spans -----------------------------------

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


def named_children(children, name):
    return sorted((c for c in children if c[0] == name), key=lambda c: c[1])


def recorded(monkeypatch):
    """Every span of the engine and the scheduler from here on, as the
    benchmark's readers take them."""
    spans = []
    for mod in (engine_mod, scheduler_mod):
        monkeypatch.setattr(
            mod, "span", lambda name, **args: _Recorded(spans, name, args))
    return spans


def order_by_structure(step, children):
    """The order a step took, from the shape of its spans alone: what the
    file derived before ``serve.step`` said it."""
    seq = step[4]["seq"]
    dispatches = named_children(children, "serve.dispatch")
    waits = named_children(children, "serve.wait")
    # one of each at most, whichever order the step took
    assert len(dispatches) <= 1 and len(waits) <= 1, seq
    if not dispatches and not waits:
        return "idle"               # prefill chunks alone, or nothing
    if not dispatches:
        # the read of the step before's program, and the wait says so
        assert waits[0][4]["waits_for"] == seq - 1, seq
        return "drain"
    (dispatch,) = dispatches
    if not waits:
        return "enter"              # a horizon's first block, left unread
    (wait,) = waits
    if wait[2] <= dispatch[1]:
        # a horizon's drain: the step before's block read, the boundary,
        # the next block
        assert wait[4]["waits_for"] == seq - 1, seq
        return "drain"
    assert dispatch[2] <= wait[1], seq
    waits_for = wait[4].get("waits_for", seq)    # a speculative step: its own
    if waits_for == seq - 1:
        return "pipelined"
    assert waits_for == seq, seq                # its own program
    return "enter" if dispatch[4].get("programs") == 2 else "sync"


def first_held(children):
    """``held_by`` of the step's first quiet test that failed, or None."""
    return next((q[4]["held_by"] for q in named_children(children,
                                                          "serve.quiet")
                 if q[4].get("held_by")), None)


def counts_close(eng, spans, before):
    """The sums ISSUE 53 holds a traced run to, on the CPU engine: every
    step under one order of the vocabulary, the counters what the spans
    say, and a cause for each ``sync`` and ``drain`` step and no other. And
    ISSUE 54's: ``admission_held`` is the ``serve.admit`` spans with ``held``
    1, one a pipelined step that went ahead past a refused head, each with
    that refusal's cause and nothing reckoned."""
    steps = program_span.steps_with_children(spans, 0, 2 ** 63)
    stats = eng.stats()
    orders = dict.fromkeys(STEP_ORDERS, 0)
    causes = dict.fromkeys(NOT_QUIET, 0)
    held_steps = 0
    for step, children in steps:
        order = step[4]["order"]
        assert order == order_by_structure(step, children), step[4]
        assert "pipelined" not in step[4]
        orders[order] += 1
        admits = named_children(children, "serve.admit")
        was_held = [a[4] for a in admits if a[4].get("held")]
        if was_held:
            # the only attempt-shaped span of its step, which pipelined
            assert order == "pipelined" and len(admits) == 1, step[4]
            assert set(was_held[0]) == {"request_id", "queue_ms", "admitted",
                                        "blocked_by", "held"}, was_held
            assert was_held[0]["admitted"] == 0 and was_held[0]["held"] == 1
            assert was_held[0]["blocked_by"] in ADMIT_BLOCKS
            held_steps += 1
        else:
            assert order != "pipelined" or not admits, step[4]
        held = first_held(children)
        quiet = named_children(children, "serve.quiet")
        assert all(q[4]["held_by"] in ("", *NOT_QUIET) for q in quiet)
        if order in ("sync", "drain"):
            assert held is not None, (step[4], children)
            causes[held] += 1
        elif order == "idle":
            assert not quiet
        else:                       # a horizon's first block asks nothing
            assert held is None and (quiet or eng.decode_horizon > 1)
    assert orders == {k: v - before["steps_by_order"][k]
                      for k, v in stats["steps_by_order"].items()}
    assert causes == {k: v - before["not_quiet"][k]
                      for k, v in stats["not_quiet"].items()}
    assert sum(orders.values()) == len(steps) \
        == stats["stats_seq"] - before["stats_seq"]
    assert sum(causes.values()) == orders["sync"] + orders["drain"]
    assert stats["decode_steps_pipelined"] \
        == stats["steps_by_order"]["pipelined"]
    assert held_steps == stats["admission_held"] - before["admission_held"]
    return steps, orders, causes


def test_a_pipelined_step_dispatches_then_waits_for_the_step_before(
        programs, monkeypatch):
    progs = programs("llama")
    eng = engine_of(progs)
    reqs = [request(5, 20, 0), request(9, 12, 1), request(7, 16, 2)]
    run(eng, reqs[:1])                          # compile outside the record
    before = eng.stats()
    spans = recorded(monkeypatch)
    run(eng, reqs, clients=2)
    steps, orders, causes = counts_close(eng, spans, before)
    assert orders["pipelined"] > 10 and orders["enter"] >= 2
    assert orders["drain"] >= 2 and orders["sync"] >= 1, orders
    # two clients over three requests: a reply's end is a budget's end, the
    # third request waits for a slot, its prompt is one chunk
    assert causes["budget"] >= 2 and set(
        k for k, v in causes.items() if v) <= {"budget", "queued", "arrays",
                                               "prefill"}, causes
    modules = []
    for step, children in steps:
        seq, order = step[4]["seq"], step[4]["order"]
        waits = named_children(children, "serve.wait")
        if not waits:
            continue                # a step of prefill chunks alone
        (wait,) = waits
        (book,) = named_children(children, "serve.book")
        assert wait[2] <= book[1]
        if order == "pipelined":
            # enqueued BEFORE the read, of what the step before enqueued;
            # nothing of the boundary in between
            (dispatch,) = named_children(children, "serve.dispatch")
            assert dispatch[4] == {"program": "serve_decode", "programs": 1}
            assert not {c[0] for c in children} & {
                "serve.expire", "serve.admit", "serve.prefill",
                "serve.sample"}
            builds = named_children(children, "serve.build")
            assert all(b[4]["reason"] == "lookahead" and b[2] <= dispatch[1]
                       for b in builds)
            # the quiet test in front of it all, the reservation its child
            (quiet,) = named_children(children, "serve.quiet")
            assert quiet[4] == {"held_by": ""}
            assert quiet[2] <= dispatch[1]
            (reserve,) = named_children(children, "serve.reserve")
            assert quiet[1] <= reserve[1] and reserve[2] <= quiet[2]
            # a made-up device line: the program in flight was running
            # when this step enqueued the next, and ran into its wait
            modules.append(("jit_serve_decode(1)", dispatch[1] - 1000,
                            wait[2] - 1))
        elif order == "drain":
            # the read of the step before's program, and the step is over;
            # the boundary is the next step's
            assert {c[0] for c in children} <= {
                "serve.quiet", "serve.reserve", "serve.wait", "serve.book",
                "serve.release", "serve.state"}
            (quiet,) = named_children(children, "serve.quiet")
            assert quiet[2] <= wait[1]
    # the benchmark's reader keeps a pipelined step (one dispatch, one wait
    # after it) and joins it with the program that was in flight
    piped = [(s, c) for s, c in steps if s[4]["order"] == "pipelined"]
    joined, skipped = step_waterfall.join(piped, modules)
    assert skipped == 0 and len(joined) == len(piped)
    for step, children, dispatch, wait, device, _ in joined:
        cut = step_waterfall.cut(step, children, dispatch, wait, device)
        assert sum(cut["phases"].values()) == cut["ns"]
        assert cut["phases"]["launch"] == 0     # it ran before the dispatch


def test_a_horizon_takes_its_orders_from_the_same_vocabulary(programs,
                                                            monkeypatch):
    """``decode_horizon`` 4: the first block ENTERS (enqueued, never read in
    its step, and no quiet test asked), the blocks after it are pipelined,
    and a drain goes on into the boundary in the same step. The counts close
    as they do for the plain program."""
    progs = programs("llama")
    eng = engine_of(progs, decode_horizon=4, n_slots=2)
    reqs = [request(5 + 3 * i, 30 + 7 * i, i) for i in range(3)]
    run(eng, reqs[:1])                          # compile outside the record
    want = batch1(progs, reqs)
    before = eng.stats()
    spans = recorded(monkeypatch)
    got, _ = run(eng, reqs, clients=2)
    same_tokens(got, want, reqs)
    steps, orders, causes = counts_close(eng, spans, before)
    assert orders["enter"] >= 1 and orders["pipelined"] >= 3, orders
    assert orders["drain"] >= 2 and orders["sync"] == 0, orders
    # a horizon's drain runs the boundary, and may enqueue the next block,
    # in the same step
    assert any(named_children(c, "serve.dispatch") for s, c in steps
               if s[4]["order"] == "drain")


# ---- (h) the first thing that kept a step from pipelining ----------------------

def held_queued(progs, monkeypatch):
    """A second caller arrives under a program in flight: no attempt has
    refused it, so it might get in, and the step drains for it. (A head that
    a full attempt refused is no cause while that refusal stands: the tests
    of the standing refusal, below.)"""
    eng = engine_of(progs, n_slots=2)
    return eng, [request(5 + i, 12, i) for i in range(2)], {
        "clients": lambda steps: 1 if steps < 5 else 2}


def held_prefill(progs, monkeypatch):
    """A prompt of three chunks beside a slot that decodes."""
    eng = engine_of(progs, n_slots=2)
    return eng, [request(5, 12, 0), request(40, 6, 2)], {}


def held_budget(progs, monkeypatch):
    """A reply's last token is known a step ahead."""
    return engine_of(progs), [request(5, 9, 0)], {}


def held_arrays(progs, monkeypatch):
    """A lane that left by an eos, read behind the program enqueued after
    it: the arrays on the device name it still."""
    plain = [request(6, 30, 0), request(9, 30, 1)]
    tokens = batch1(progs, plain)[1].generated_ids
    at = next(j for j in range(8, 28) if tokens[j] not in tokens[:j])
    reqs = [plain[0], dataclasses.replace(plain[1], eos_id=tokens[at])]
    return engine_of(progs, n_slots=2), reqs, {}


def held_pages(progs, monkeypatch):
    """A pool too full to reserve the write ahead; the victim of the
    preemption that follows comes back and replays what it had."""
    eng = engine_of(progs, n_pages=9, max_len=48)
    return eng, [request(4 + i, 30 + i, i) for i in range(3)], {
        "also": ("replaying",)}


def held_deadline(progs, monkeypatch):
    """A deadline that falls due while the slot decodes."""
    eng = engine_of(progs)
    now = [0.0]
    eng.scheduler._clock = lambda: now[0]

    def on_step(eng, finished):
        if eng.stats()["stats_seq"] % 100 == 8:
            now[0] += 100.0
    return eng, [request(5, 30, 0, deadline_s=50.0)], {
        "on_step": on_step, "unfinished": True}


def held_drafter(progs, monkeypatch):
    """What a drafter proposes comes from the host's tokens."""
    eng = engine_of(progs, speculate="ngram", spec_k=2)
    return eng, [request(5, 12, 0)], {}


def held_kind(progs, monkeypatch):
    """A horizon switched on under a plain program in flight."""
    eng = engine_of(progs)

    def on_step(eng, finished):
        if eng.stats()["stats_seq"] % 100 == 6:
            eng.set_decode_horizon(2)
    return eng, [request(5, 20, 0)], {"on_step": on_step}


def held_inactive(progs, monkeypatch):
    """The only reply ends by an eos read behind an enqueued program: the
    next step has a program to read and no slot that decodes."""
    tokens = batch1(progs, [request(6, 20, 1)])[0].generated_ids
    at = next(j for j in range(6, 18) if tokens[j] not in tokens[:j])
    return engine_of(progs), [request(6, 20, 1, eos_id=tokens[at])], {}


@pytest.mark.parametrize("cause,scene", [
    ("queued", held_queued), ("prefill", held_prefill),
    ("budget", held_budget), ("arrays", held_arrays), ("pages", held_pages),
    ("deadline", held_deadline), ("drafter", held_drafter),
    ("kind", held_kind), ("inactive", held_inactive)])
def test_a_step_that_does_not_pipeline_names_the_first_thing_in_its_way(
        programs, monkeypatch, cause, scene):
    """One case a cause a CPU engine can reach: the ``serve.quiet`` span of
    the step says ``held_by``, ``stats()["not_quiet"]`` counts it, and the
    counts close over the run (``counts_close``)."""
    assert cause in NOT_QUIET
    progs = programs("llama")
    eng, reqs, how = scene(progs, monkeypatch)
    base = 100 * (1 + eng.stats()["stats_seq"] // 100)
    eng.stats_seq = base            # the scenes count steps from here
    before = eng.stats()
    spans = recorded(monkeypatch)
    got, _ = run(eng, reqs, clients=how.get("clients"),
                 on_step=how.get("on_step"))
    steps, orders, causes = counts_close(eng, spans, before)
    for want in (cause, *how.get("also", ())):
        assert causes[want] >= 1, (want, causes)
        assert eng.stats()["not_quiet"][want] == causes[want]
    if not how.get("unfinished"):
        assert all(r.finish_reason in ("length", "eos") for r in got)
    # the step the cause held drained, or stayed synchronous: it never
    # enqueued ahead
    for step, children in steps:
        if first_held(children) == cause:
            assert step[4]["order"] in ("sync", "drain")


# ---- (i) a queue head whose refusal still stands -------------------------------

def scene_pages(progs, **kw):
    """Three slots over a pool of 16 pages that two replies nearly fill: a
    prompt is five pages, and the headroom rule (its pages and one a running
    decode, free) refuses the third caller until a reply ENDS."""
    eng = engine_of(progs, n_slots=3, n_pages=16, max_len=64, **kw)
    return eng, [request(40, 12 + 4 * (i % 3), i) for i in range(6)]


def scene_slots(progs, **kw):
    """Three callers on two slots, pages to spare: the third waits for a
    slot."""
    eng = engine_of(progs, n_slots=2, **kw)
    return eng, [request(5 + 2 * i, 14 + 3 * (i % 3), i) for i in range(6)]


def arriving(steps):
    """Two callers from the start, a third once they decode."""
    return 2 if steps < 7 else 3


@pytest.mark.parametrize("horizon", [1, 4], ids=["plain", "horizon4"])
@pytest.mark.parametrize("scene", [scene_pages, scene_slots],
                         ids=["pages", "slots"])
def test_steps_go_ahead_past_a_refused_head_and_the_schedule_is_the_parents(
        programs, monkeypatch, scene, horizon):
    """The head's refusal stands for a reply's length: those steps pipeline
    (each says that the head waited: ``held`` 1), every token and the step of
    every admission are those of the same run with the predicate forced
    False, the parent's ``queued``, and the counts close."""
    progs = programs("llama")
    eng, reqs = scene(progs, decode_horizon=horizon)
    before = eng.stats()
    spans = recorded(monkeypatch)
    got, rows = run(eng, reqs, clients=arriving)
    _, orders, causes = counts_close(eng, spans, before)
    stats = eng.stats()

    parent, _ = scene(progs, decode_horizon=horizon)
    parent.scheduler.head_refusal_stands = lambda: False
    want, parent_rows = run(parent, reqs, clients=arriving)
    same_tokens(got, want, reqs)
    if horizon == 1:
        same_tokens(got, batch1(progs, reqs), reqs)
        one_token_a_step(rows)
    assert [(b, a) for b, a, _ in rows] == [(b, a) for b, a, _ in parent_rows]
    assert stats["preemptions"] == 0 == parent.stats()["preemptions"]

    # the parent drained at every step with a request queued; here only an
    # arrival under a program in flight does, and a reply's end (`budget`)
    was = parent.stats()
    assert was["admission_held"] == 0
    assert was["not_quiet"]["queued"] >= (20 if horizon == 1 else 6)
    assert causes["queued"] <= 4, causes
    assert stats["admission_held"] - before["admission_held"] \
        >= (15 if horizon == 1 else 1)
    assert orders["pipelined"] > was["steps_by_order"]["pipelined"]
    # a real attempt refreshes the memo, a held step makes none: the pool's
    # refusals are counted where they happen, and far fewer than the parent's
    assert stats["admission_blocked"] <= was["admission_blocked"]
    if scene is scene_slots:
        assert stats["admission_blocked"] == 0
    sched = eng.scheduler
    assert sched.pool.n_free + sched.cache_pages_held() == sched.pool.capacity


# what ends a standing refusal: (name, the cause the draining step names)
def ends_budget(eng, ctx):
    """Nothing done: a running reply's last token comes due."""


def ends_eos(eng, ctx):
    """The eos set on a running reply is read behind an enqueued program."""


def ends_priority(eng, ctx):
    eng.submit(request(5, 4, 6, priority=1))


def ends_front(eng, ctx):
    """An entry put in front of its class, as a preempted sequence is."""
    eng.resubmit(request(5, 4, 6))


def ends_deadline(eng, ctx):
    ctx["now"][0] += 100.0


def ends_headroom(eng, ctx):
    ctx["extra"][0] = 0


def ends_tier(eng, ctx):
    from distributed_training_guide_tpu.serve.tiering import HostTier

    eng.scheduler.attach_tier(HostTier(1 << 20), eng.gather_pages)


ENDS = [("pages", ends_budget, "budget"), ("slots", ends_budget, "budget"),
        ("pages", ends_eos, "queued"), ("slots", ends_eos, "queued"),
        ("pages", ends_priority, "queued"), ("slots", ends_priority, "queued"),
        ("pages", ends_front, "queued"), ("slots", ends_front, "queued"),
        ("pages", ends_deadline, "deadline"),
        ("slots", ends_deadline, "deadline"),
        ("pages", ends_headroom, "queued"),
        ("pages", ends_tier, "queued"), ("slots", ends_tier, "queued")]


@pytest.mark.parametrize(
    "blocked_by,event,cause", ENDS,
    ids=[f"{e.__name__[len('ends_'):]}-{b}" for b, e, _ in ENDS])
def test_what_ends_a_standing_refusal_drains_and_then_a_real_attempt_runs(
        programs, monkeypatch, blocked_by, event, cause):
    """Two replies decode, two callers wait, the head refused by
    ``blocked_by`` and held for steps. Then the event: the first step that
    does not pipeline is a DRAIN that names ``cause``, makes no attempt and
    holds nothing, and the step after it runs a REAL attempt (a
    ``serve.admit`` without ``held``, which refreshes the memo)."""
    progs = programs("llama")
    now, extra = [0.0], [0]
    ctx = {"now": now, "extra": extra}
    running = [request(32 if blocked_by == "pages" else 6, 30, i)
               for i in range(2)]
    if event is ends_eos:
        tokens = batch1(progs, running[1:])[0].generated_ids
        at = next(j for j in range(14, 28) if tokens[j] not in tokens[:j])
        running[1] = dataclasses.replace(running[1], eos_id=tokens[at])
    if event is ends_budget:
        running[1] = dataclasses.replace(running[1], max_new_tokens=16)
    if event is ends_headroom:
        # pages to spare, and a sibling's decodes that the hook reports
        eng = engine_of(progs, n_slots=3, n_pages=40, max_len=64)
        eng.scheduler._headroom_fn = lambda: extra[0]
    else:
        eng, _ = (scene_pages if blocked_by == "pages" else scene_slots)(progs)
    sched = eng.scheduler
    sched._clock = lambda: now[0]
    for r in running:
        eng.submit(dataclasses.replace(r))
    while len(sched.active_indices()) < 2:
        assert eng.step() == [] and eng.stats_seq < 20
    extra[0] = 100
    waiting = [request(32 if blocked_by == "pages" else 6, 4, 2 + i,
                       deadline_s=50.0 if event is ends_deadline and i == 0
                       else None) for i in range(2)]
    rids = [eng.submit(w) for w in waiting]
    spans = recorded(monkeypatch)
    before = eng.stats()
    while sched.stats["admission_held"] < 3:
        assert eng.step() == [] and eng.stats_seq < 40
    assert eng._inflight is not None and sched.head_refusal_stands()
    assert sched._refusal.blocked_by == blocked_by
    assert sched._refusal.request_id == rids[0]
    assert sched.stats["admitted"] == 2

    event(eng, ctx)
    del spans[:]
    drains = eng.steps_by_order["drain"]
    finished = []
    while eng.steps_by_order["drain"] == drains:
        held = sched.stats["admission_held"]
        finished += eng.step()
        assert eng.stats_seq < 60
        if eng.steps_by_order["drain"] == drains:
            # it went ahead past the head once more, and said so
            assert sched.stats["admission_held"] == held + 1
    assert eng._inflight is None
    attempts = sched.stats["admitted"], sched.stats["admission_blocked"]
    finished += eng.step()                      # the boundary's step
    steps = program_span.steps_with_children(spans, 0, 2 ** 63)
    (drain, drained), (boundary, bounded) = steps[-2:]
    assert drain[4]["order"] == "drain" and first_held(drained) == cause
    assert not named_children(drained, "serve.admit")
    assert all(s[4]["order"] == "pipelined" for s, _ in steps[:-2])
    assert boundary[4]["order"] in ("sync", "enter")
    real = [a[4] for a in named_children(bounded, "serve.admit")]
    assert real and not any("held" in a for a in real), real
    # only an event the step ahead cannot see costs the head steps
    assert len(steps) - 2 <= (0 if event not in (ends_budget, ends_eos)
                              else 20), len(steps)
    if event is ends_eos:
        assert [r.finish_reason for r in finished] == ["eos"]
        assert steps[-3][0][4]["order"] == "pipelined"  # read behind D(n+1)
    if event is ends_budget:
        assert [r.finish_reason for r in finished] == ["length"]
    if event in (ends_budget, ends_eos, ends_headroom):
        assert real[0] == {"request_id": rids[0], "admitted": 1,
                           "queue_ms": real[0]["queue_ms"]}
    if event is ends_deadline:
        assert [r.finish_reason for r in finished] == ["deadline"]
        assert real[0]["request_id"] == rids[1]
    if event in (ends_priority, ends_front):
        assert real[0]["request_id"] not in rids
    if event is ends_tier:
        # today's behaviour from here on: an attempt a step, nothing held
        assert real[0]["request_id"] == rids[0] and not real[0]["admitted"]
        held = sched.stats["admission_held"]
        for _ in range(3):
            eng.step()
        assert sched.stats["admission_held"] == held
        assert eng.not_quiet["queued"] >= before["not_quiet"]["queued"] + 3
    assert (sched.stats["admitted"], sched.stats["admission_blocked"]) \
        != attempts or blocked_by == "slots"
    while eng.has_work:
        eng.step()
    assert sched.pool.n_free + sched.cache_pages_held() == sched.pool.capacity


@pytest.mark.parametrize("shares", [True, False], ids=["shared", "unrelated"])
def test_a_registered_prefix_ends_the_refusal_where_the_head_shares_it(
        programs, shares):
    """Two callers wait behind two replies: the first fits, the second is
    refused for pages in the same attempt, and then the first one's prompt
    registers in the prefix cache. Where the refused head shares its first
    page, its match may grow: the step does not enter the pipeline and the
    next runs a real attempt. An unrelated prompt changes nothing for the
    head, and the chunk step enters with the head held behind it."""
    progs = programs("llama")
    eng = engine_of(progs, n_slots=4, n_pages=16, max_len=64)
    sched = eng.scheduler
    for i in range(2):
        eng.submit(request(32, 30, i))
    while len(sched.active_indices()) < 2:
        assert eng.step() == []
    big = request(32, 4, 3)
    small = Request(prompt_ids=(big.prompt_ids[:PAGE] if shares
                                else prompt_of(PAGE, 77)) + [1, 2],
                    max_new_tokens=4)
    eng.submit(small)
    rid = eng.submit(big)
    while sched.stats["admitted"] < 3:
        eng.step()
    # the step that admitted `small` refused `big` and completed the prefill
    assert sched._refusal.request_id == rid
    assert sched._refusal.blocked_by == "pages"
    assert len(sched.active_indices()) == 3
    assert sched.head_refusal_stands() is (not shares)
    assert (eng._inflight is not None) is (not shares)
    blocked = sched.stats["admission_blocked"]
    eng.step()
    if shares:      # a real attempt, which finds the shared page
        assert sched.stats["admission_blocked"] == blocked + 1
        assert sched.stats["admission_held"] == 0
    else:
        assert sched.stats["admission_blocked"] == blocked
        assert sched.stats["admission_held"] == 1
    while eng.has_work:
        eng.step()
    assert sched.pool.n_free + sched.cache_pages_held() == sched.pool.capacity


# ---- the predicate alone, on a scheduler without an engine ---------------------

def refused_scheduler(blocked_by, **kw):
    """Two sequences decode and a third is the queue's head, refused by
    ``blocked_by``: pages of 4, a prompt of 8, a pool of 9 pages of which
    the two hold 6 and the head wants 2 and 2 of headroom, one more than are
    there (``pages``), or two slots both taken (``slots``)."""
    from distributed_training_guide_tpu.serve import PagePool, Scheduler

    pool = PagePool(n_pages=10 if blocked_by == "pages" else 17, page_size=4)
    sched = Scheduler(n_slots=3 if blocked_by == "pages" else 2, pool=pool,
                      max_len=32, max_pages_per_slot=8, **kw)
    for i in range(2):
        sched.submit(Request(prompt_ids=prompt_of(8, 3 + 7 * i),
                             max_new_tokens=9, deadline_s=50.0 + 100 * i))
        (adm,) = sched.try_admit()
        sched.commit_tokens(adm.slot_idx, 8)
        assert sched.record_token(adm.slot_idx, 5, from_decode=False) is None
    assert sched.grow_for_decode() == (2, 0)        # the page of write 9
    rid = sched.submit(Request(prompt_ids=prompt_of(8, 40), max_new_tokens=4))
    assert sched.try_admit() == []
    assert sched._refusal.blocked_by == blocked_by
    assert sched._refusal.request_id == rid and sched.head_refusal_stands()
    return sched


def came_back_end(sched):
    assert sched.record_token(0, 9, from_decode=True) is None
    for _ in range(7):
        done = sched.record_token(0, 9, from_decode=True)
    assert done.finish_reason == "length"


def came_back_preempt(sched):
    sched.preempt(1)            # and its entry is the head now


def came_back_expired(sched):
    (gone,) = sched.expire_deadlines(now=sched._clock() + 60.0)
    assert gone.finish_reason == "deadline"


def came_back_released(sched):
    slot, _ = sched.release_slot(0)
    sched.pool.free(slot.pages)


def another_head_priority(sched):
    sched.submit(Request(prompt_ids=[1, 2, 3], max_new_tokens=2, priority=1))


def another_head_front(sched):
    sched.requeue(Request(prompt_ids=[1, 2, 3], max_new_tokens=2), [7])


def another_head_drained(sched):
    assert len(sched.drain_queue()) == 1


def tier_attached(sched):
    sched.attach_tier(object(), lambda pages: {})


def stays_growth(sched):
    for i in (0, 1):
        for _ in range(4):
            sched.record_token(i, 9, from_decode=True)
    assert sched.grow_for_decode() == (2, 0)


def stays_write_ahead(sched):
    assert sched.reserve_horizon(6) == (6, 2)


def stays_arrival_behind(sched):
    sched.submit(Request(prompt_ids=[1, 2, 3], max_new_tokens=2))


def stays_headroom_rose(sched):
    sched._headroom_fn = lambda: 3


ENDS_MEMO = [came_back_end, came_back_preempt, came_back_expired,
             came_back_released, another_head_priority,
             another_head_front, another_head_drained, tier_attached]
KEEPS_MEMO = [stays_growth, stays_write_ahead, stays_arrival_behind,
              stays_headroom_rose]


@pytest.mark.parametrize("blocked_by", ["pages", "slots"])
@pytest.mark.parametrize("event", ENDS_MEMO + KEEPS_MEMO,
                         ids=lambda e: e.__name__)
def test_the_refusal_stands_until_something_comes_back_or_the_head_changes(
        blocked_by, event):
    """``head_refusal_stands`` by itself: what returns a slot or a page of
    the main class, another head and a host tier end it; pages TAKEN, an
    arrival behind the head and more headroom do not, and there a real
    attempt is refused again, which is what the predicate promised."""
    sched = refused_scheduler(blocked_by)
    rid = sched._refusal.request_id
    event(sched)
    assert sched.head_refusal_stands() is (event in KEEPS_MEMO)
    if event in KEEPS_MEMO:
        blocked = sched.stats["admission_blocked"]
        assert sched.try_admit() == []
        assert sched.queue[0].request.request_id == rid
        assert sched.stats["admission_blocked"] - blocked \
            == (blocked_by == "pages")
        assert sched.head_refusal_stands()


def test_a_sibling_schedulers_decodes_falling_end_a_pages_refusal():
    """The disaggregated prefill side counts the decode side's running
    replies through ``admission_headroom``: they can fall without this pool
    seeing anything, and the refusal was held to them."""
    from distributed_training_guide_tpu.serve import PagePool, Scheduler

    there = [6]
    sched = Scheduler(n_slots=2, pool=PagePool(n_pages=9, page_size=4),
                      max_len=32, max_pages_per_slot=8,
                      admission_headroom=lambda: there[0])
    sched.submit(Request(prompt_ids=prompt_of(12), max_new_tokens=4))
    assert sched.try_admit() == []              # 3 pages + 6 of 8
    assert sched._refusal.headroom == 6 and sched.head_refusal_stands()
    there[0] = 7
    assert sched.head_refusal_stands()          # more of them: stands
    there[0] = 5
    assert not sched.head_refusal_stands()
    (adm,) = sched.try_admit()
    assert adm.slot_idx == 0 and not sched.head_refusal_stands()
