"""The program names its own work (``utils/trace.py``): host spans under a
profiler session on the CPU, nothing without one, and the scope, program and
kernel names in the lowered programs. The kernels' names where the chip's
compiler lowers them are in ``tests/test_chip_compile.py``; the readers of
these names are under ``tests/benchmarks/``."""
import contextlib
import gc
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from distributed_training_guide_tpu.models import get_model
from distributed_training_guide_tpu.parallel import make_mesh, make_plan
from distributed_training_guide_tpu.serve import Request, ServeEngine
from distributed_training_guide_tpu.train import Trainer
from distributed_training_guide_tpu.train.step import lower_step
from distributed_training_guide_tpu.utils import trace as trace_mod
from distributed_training_guide_tpu.utils.trace import (ADMIT_BLOCKS, KERNELS,
                                                        NOT_QUIET, PREFIX,
                                                        PROGRAMS,
                                                        REBUILD_REASONS,
                                                        SCOPES, SPANS,
                                                        STEP_ORDERS,
                                                        SUBSCOPES, named, span)

SERVE_CHILDREN = {s for s in SPANS if s.startswith("serve.")} - {"serve.step"}


def program_events(trace_dir):
    """``(name, start_ns, end_ns, thread, stats)`` of every ``dtg.`` host
    event of the one trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    path = next(trace_dir.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name[len(PREFIX):], e.start_ns,
                                e.start_ns + e.duration_ns, line.name,
                                dict(e.stats)))
    return out


def covered(intervals):
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


@pytest.fixture(scope="module")
def debug_model():
    bundle = get_model("llama-debug")
    return bundle, bundle.init(bundle.config, jax.random.key(0))


def tight_engine(debug_model, **kw):
    """Two slots over a pool that holds one and a half requests: the second
    request's growth preempts, so the run has prefill, decode, a preemption
    and the re-admission."""
    bundle, params = debug_model
    return ServeEngine(bundle, params, n_slots=2, page_size=8, max_len=64,
                       n_pages=7, **kw)


def run_requests(engine, n_new=20):
    for i, prompt in enumerate(([3, 17, 42, 5, 6, 9, 11], [8, 1, 30, 2])):
        engine.submit(Request(prompt_ids=prompt, max_new_tokens=n_new,
                              temperature=0.0, eos_id=None, seed=i))
    done = []
    while engine.has_work:
        done.extend(engine.step())
    return {r.request_id: list(r.generated_ids) for r in done}


# ---- (a) the span tree under a session --------------------------------------

@pytest.mark.parametrize("engine_kw", [{}, {"prefill_chunk": 4},
                                       {"decode_horizon": 4}],
                         ids=["own-size", "chunked", "horizon4"])
def test_serve_span_tree(debug_model, tmp_path, engine_kw):
    engine = tight_engine(debug_model, **engine_kw)
    run_requests(engine, n_new=4)          # compile outside the session
    jax.profiler.start_trace(str(tmp_path))
    try:
        tokens = run_requests(engine)
    finally:
        jax.profiler.stop_trace()
    assert len(tokens) == 2 and all(len(t) == 20 for t in tokens.values())
    assert engine.stats()["preemptions"] >= 1
    events = program_events(tmp_path)
    names = {e[0] for e in events}
    assert names <= set(SPANS), names - set(SPANS)
    want = {"serve.step", "serve.expire", "serve.admit", "serve.prefill",
            "serve.sample", "serve.quiet", "serve.reserve", "serve.build",
            "serve.dispatch", "serve.wait", "serve.book"}
    assert want <= names, want - names
    steps = [e for e in events if e[0] == "serve.step"]
    children = [e for e in events if e[0] in SERVE_CHILDREN]
    # every iteration number once, in order
    seqs = [s[4]["seq"] for s in sorted(steps, key=lambda s: s[1])]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    # containment: every child lies inside exactly one step, on its thread
    for name, a, b, thread, _ in children:
        inside = [s for s in steps
                  if s[3] == thread and s[1] <= a and b <= s[2]]
        assert len(inside) == 1, (name, a, b)
    # the children cover the steps but for a stated remainder: the engine's
    # own glue between them (slot lists, counters, the latency meter),
    # under a quarter of the step time at debug width on the CPU
    step_ns = sum(b - a for _, a, b, _, _ in steps)
    child_ns = covered((a, b) for _, a, b, _, _ in children)
    assert child_ns >= 0.75 * step_ns, (child_ns, step_ns)
    # the per-request spans carry the request, admission its queue wait
    admits = [e for e in events if e[0] == "serve.admit"]
    assert {e[4]["request_id"] for e in admits} == set(tokens)
    assert all(e[4]["queue_ms"] >= 0 for e in admits)
    assert len(admits) >= 3                # two requests and a re-admission
    assert all("request_id" in e[4] for e in events
               if e[0] in ("serve.prefill", "serve.sample"))
    # one prefill program, whatever the engine was asked for: the chunk
    # program at the engine's resolved size
    assert {e[4]["program"] for e in events if e[0] == "serve.prefill"} \
        == {f"serve_chunk_t{engine.prefill_chunk}"}
    assert all(e[4]["program"].startswith("serve_") for e in events
               if e[0] == "serve.dispatch")
    reserves = [e[4] for e in events if e[0] == "serve.reserve"]
    assert sum(r.get("preempted", 0) for r in reserves) >= 1
    # every step says which order it took and every quiet test what held
    # it, both of the closed sets; an attempt at admission says how it ended
    assert {s[4]["order"] for s in steps} <= set(STEP_ORDERS)
    assert all("pipelined" not in s[4] for s in steps)
    quiet = [e[4] for e in events if e[0] == "serve.quiet"]
    # (a quiet test's empty `held_by` does not reach a trace)
    assert quiet and {q["held_by"] for q in quiet if "held_by" in q} \
        <= set(NOT_QUIET)
    assert any("held_by" not in q for q in quiet)
    assert {1} <= {e[4]["admitted"] for e in admits} <= {0, 1}
    assert {e[4]["blocked_by"] for e in admits if not e[4]["admitted"]} \
        <= set(ADMIT_BLOCKS)
    assert all("blocked_by" not in e[4] for e in admits if e[4]["admitted"])
    assert set().union(*(e[4] for e in admits)) <= ADMIT_STATS
    stats = engine.stats()
    assert set(stats["steps_by_order"]) == set(STEP_ORDERS)
    assert set(stats["not_quiet"]) == set(NOT_QUIET)
    assert sum(stats["steps_by_order"].values()) == stats["stats_seq"]


# every statistic a `serve.admit` span may carry (utils/trace.py's docstring)
ADMIT_STATS = {"request_id", "queue_ms", "admitted", "blocked_by", "need",
               "free", "headroom", "held"}


def test_a_step_that_goes_ahead_past_a_refused_head_says_held(debug_model,
                                                              tmp_path):
    """Three requests on two slots, under a session: the third is refused
    for a slot once, and the steps that pipeline past it while that refusal
    stands each leave a ``serve.admit`` with ``held`` 1: ``admitted`` 0, the
    refusal's ``blocked_by``, nothing reckoned; ``stats()`` counts them."""
    bundle, params = debug_model
    engine = ServeEngine(bundle, params, n_slots=2, page_size=8, max_len=64)
    run_requests(engine, n_new=4)          # compile outside the session

    def three():
        return [engine.submit(Request(prompt_ids=[3 + i, 17, 42, 5],
                                      max_new_tokens=12))
                for i in range(3)]
    before = engine.stats()["admission_held"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        rids = three()
        while engine.has_work:
            engine.step()
    finally:
        jax.profiler.stop_trace()
    events = program_events(tmp_path)
    admits = [e[4] for e in events if e[0] == "serve.admit"]
    assert set().union(*admits) <= ADMIT_STATS
    held = [a for a in admits if a.get("held")]
    assert len(held) >= 5
    assert len(held) == engine.stats()["admission_held"] - before
    for a in held:
        assert set(a) == {"request_id", "queue_ms", "admitted", "blocked_by",
                          "held"}
        assert a["admitted"] == 0 and a["held"] == 1
        assert a["blocked_by"] == "slots" and a["blocked_by"] in ADMIT_BLOCKS
        assert a["request_id"] == rids[2]
    # the held steps pipelined: each lies in a step whose order says so
    steps = {e[4]["seq"]: e for e in events if e[0] == "serve.step"}
    spans = [e for e in events if e[0] == "serve.admit" and e[4].get("held")]
    for _, a, b, _, _ in spans:
        (step,) = [s for s in steps.values() if s[1] <= a and b <= s[2]]
        assert step[4]["order"] == "pipelined"
    # a real refusal by the same cause set the memo they repeat (one a
    # synchronous step: the two steps of the prompts' chunks), and they are
    # many more than it
    real = [a for a in admits if not a["admitted"] and not a.get("held")]
    assert {a["blocked_by"] for a in real} == {"slots"}
    assert 1 <= len(real) <= 3 < len(held)


def test_the_vocabularys_text_says_what_queued_means_now():
    """``utils/trace.py`` beside ``NOT_QUIET`` and ``ADMIT_BLOCKS``, and its
    docstring on ``serve.admit``: a head whose refusal stands is no cause,
    and the step that goes ahead past it says ``held``."""
    import inspect

    source = inspect.getsource(trace_mod)
    beside_causes = source.split("NOT_QUIET = (")[0].rsplit("\n\n", 1)[1]
    assert "head_refusal_stands" in " ".join(beside_causes.split())
    assert "`held` 1" in " ".join(beside_causes.split())
    beside_blocks = source.split("ADMIT_BLOCKS = (")[0].rsplit("\n\n", 1)[1]
    assert "`held` 1" in " ".join(beside_blocks.split())
    said = " ".join(trace_mod.__doc__.split())
    assert "``held`` 1" in said and "Scheduler.hold_head" in said


def test_the_disaggregated_pairs_step_is_the_one_without_an_order(
        debug_model, tmp_path):
    """``serve/disagg.py`` opens a ``serve.step`` of its own, exempted here
    by name as ``utils/trace.py`` says beside ``STEP_ORDERS``: ``cpu_ms``
    and ``seq`` as before, no ``order`` and no ``serve.quiet``."""
    from distributed_training_guide_tpu.serve import DisaggEngine

    bundle, params = debug_model
    engine = DisaggEngine(bundle, params, n_slots=2, n_prefill_slots=1,
                          page_size=8, max_len=64)
    run_requests(engine, n_new=4)          # compile outside the session
    jax.profiler.start_trace(str(tmp_path))
    try:
        tokens = run_requests(engine, n_new=6)
    finally:
        jax.profiler.stop_trace()
    assert len(tokens) == 2
    events = program_events(tmp_path)
    steps = [e for e in events if e[0] == "serve.step"]
    assert steps and all(set(s[4]) == {"seq", "cpu_ms"} for s in steps)
    assert "serve.quiet" not in {e[0] for e in events}


def test_train_loop_span_tree(tmp_path, eight_devices):
    from distributed_training_guide_tpu.train.cli import (get_parser,
                                                          run_training)

    args = get_parser().parse_args(["-m", "llama-debug"])
    args.dataset_name, args.seq_length, args.batch_size = "synthetic:60000", 64, 1
    args.num_epochs, args.log_freq, args.max_steps = 1, 1, 3
    args.save_dir, args.experiment_name, args.ckpt_freq = str(tmp_path), "t", 3
    trace_dir = tmp_path / "trace"
    jax.profiler.start_trace(str(trace_dir))
    try:
        out = run_training(args, lambda: make_plan("ddp", make_mesh()))
    finally:
        jax.profiler.stop_trace()
    assert out["host_state"]["global_step"] == 3
    events = program_events(trace_dir)
    names = {e[0] for e in events}
    assert names <= set(SPANS), names - set(SPANS)
    by_name = lambda n: sorted((e for e in events if e[0] == n),
                               key=lambda e: e[1])
    for name in ("train.data", "train.step", "train.fence", "train.log"):
        assert [e[4]["step"] for e in by_name(name)] == [1, 2, 3], name
    assert [e[4]["step"] for e in by_name("train.ckpt")] == [3]
    # the fence (the host read of the loss) lies inside its step's span, the
    # loader's work inside the data span or ahead of it (prefetch)
    for fence, step in zip(by_name("train.fence"), by_name("train.step")):
        assert step[1] <= fence[1] and fence[2] <= step[2]
    puts, assembles = by_name("data.put"), by_name("data.assemble")
    assert len(puts) >= 3 and len(assembles) >= len(puts)
    for a in assembles:
        assert any(p[1] <= a[1] and a[2] <= p[2] for p in puts)
    # data, step, log and checkpoint follow each other with nothing else of
    # the loop between them: they cover the loop from the first step's data
    # to the last step's log but for the heartbeat and the progress bar
    loop = [e for e in events if e[0] in ("train.data", "train.step",
                                          "train.log", "train.ckpt")]
    lo, hi = min(e[1] for e in loop), max(e[2] for e in loop)
    assert covered((e[1], e[2]) for e in loop) >= 0.75 * (hi - lo)


# ---- (a2) serve.build says why and what it uploaded -------------------------

def roomy_engine(debug_model, page_size):
    bundle, params = debug_model
    return ServeEngine(bundle, params, n_slots=2, page_size=page_size,
                       max_len=64)


def request(prompt, n_new, seed=0):
    return Request(prompt_ids=prompt, max_new_tokens=n_new, temperature=0.0,
                   eos_id=None, seed=seed)


def until_resident(engine):
    """Step until the decode arrays are on the device and a step has run
    on them: whatever drops them next is the first cause since a build."""
    while engine._dev["kind"] != "plain":
        engine.step()
    engine.step()
    assert engine._dev["kind"] == "plain"


def provoke_left(engine):
    """Two slots decode, one reply ends: the other's next step rebuilds."""
    engine.submit(request([3, 17, 42, 5], 4))
    engine.submit(request([8, 1, 30, 2], 14, seed=1))
    until_resident(engine)


def provoke_grown(engine):
    """One slot decodes across the end of its first page of 8."""
    engine.submit(request([3, 17, 42, 5], 14))
    until_resident(engine)


def provoke_grown_synchronous(engine):
    """The same under the SYNCHRONOUS order of a plain step, forced here (no
    step is quiet): the page of the next write comes from
    ``grow_for_decode``, in the step that writes it. In the pipelined order,
    which this session takes by itself, it comes a step earlier, from the
    reservation for the program enqueued ahead: ``lookahead``."""
    engine._ahead = lambda pending_k, first=(), resident=None: (None, "")
    provoke_grown(engine)


def provoke_admitted(engine):
    """One slot decodes; a second request arrives inside the session."""
    engine.submit(request([3, 17, 42, 5], 14))
    until_resident(engine)
    return request([8, 1, 30, 2], 3, seed=1)


@pytest.mark.parametrize("reason,page_size,provoke", [
    ("left", 32, provoke_left), ("grown", 8, provoke_grown_synchronous),
    ("lookahead", 8, provoke_grown), ("admitted", 32, provoke_admitted)])
def test_build_says_why_and_what_it_uploaded(debug_model, tmp_path, reason,
                                             page_size, provoke):
    engine = roomy_engine(debug_model, page_size)
    run_requests(engine, n_new=4)          # compile outside the session
    late = provoke(engine)
    jax.profiler.start_trace(str(tmp_path))
    try:
        if late is not None:
            engine.submit(late)
        while engine.has_work:
            engine.step()
    finally:
        jax.profiler.stop_trace()
    events = program_events(tmp_path)
    by_name = lambda n: sorted((e for e in events if e[0] == n),
                               key=lambda e: e[1])
    builds = by_name("serve.build")
    assert builds, "the session saw no rebuild"
    # the FIRST cause since the last build, of the closed set
    assert builds[0][4]["reason"] == reason
    assert {b[4]["reason"] for b in builds} <= set(REBUILD_REASONS)
    # the two children lie inside every build, arrays before upload, and
    # leave it next to nothing of its own
    arrays, uploads = by_name("serve.arrays"), by_name("serve.upload")
    assert len(arrays) == len(uploads) == len(builds)
    for b, a, u in zip(builds, arrays, uploads):
        assert b[1] <= a[1] <= a[2] <= u[1] <= u[2] <= b[2]
        assert a[3] == u[3] == b[3]
    # what went up: the block tables alone where a slot only grew a page,
    # the scheduler's eleven arrays, byte for byte, for every other reason
    host = engine.scheduler.decode_arrays()
    assert len(host) == 11
    for b, u in zip(builds, uploads):
        if b[4]["reason"] in ("grown", "lookahead"):
            assert u[4]["arrays"] == 1
            assert u[4]["bytes"] == host["tables"].nbytes
        else:
            assert u[4]["arrays"] == 11
            assert u[4]["bytes"] == sum(v.nbytes for v in host.values())
    if reason in ("grown", "lookahead"):    # 4 + 14 tokens cross two
        assert [b[4]["reason"] for b in builds].count(reason) == 2  # pages
        pipelined = engine.stats()["decode_steps_pipelined"]
        assert (pipelined > 0) == (reason == "lookahead")
    # the thread's own CPU time on every step, inside its wall time
    steps = by_name("serve.step")
    assert steps and all(
        0 <= s[4]["cpu_ms"] <= (s[2] - s[1]) / 1e6 + 1.0 for s in steps)


def test_a_collection_is_a_span_under_a_session_and_nothing_without(
        debug_model, tmp_path):
    roomy_engine(debug_model, 32)          # the engine installs the hook
    from distributed_training_guide_tpu.utils.trace import install_gc_span

    before = list(gc.callbacks)
    install_gc_span()                      # once a process, whoever asks
    assert gc.callbacks == before
    gc.collect()                           # no session: nothing is recorded
    was_enabled = gc.isenabled()
    gc.disable()                           # no collection of the runtime's own
    try:
        jax.profiler.start_trace(str(tmp_path / "quiet"))
        jnp.zeros(4).block_until_ready()
        jax.profiler.stop_trace()
        jax.profiler.start_trace(str(tmp_path / "collected"))
        garbage = [[] for _ in range(100)]
        for g in garbage:
            g.append(g)                    # cycles only a collection frees
        del garbage, g
        gc.collect()
        jax.profiler.stop_trace()
    finally:
        if was_enabled:
            gc.enable()
    assert [e for e in program_events(tmp_path / "quiet")
            if e[0] == "gc"] == []
    (event,) = [e for e in program_events(tmp_path / "collected")
                if e[0] == "gc"]
    assert event[4]["generation"] == 2 and event[4]["collected"] >= 100
    assert event[2] > event[1]


def test_no_engine_drops_the_decode_arrays_by_assignment():
    """Every event that takes ``_dev`` off the device, or leaves its tables
    stale, says which it is, through ``DecodeArrays.drop_dev`` or
    ``DecodeArrays.stale_tables``; the reasons in the source are the closed
    set, no more and no fewer."""
    from pathlib import Path

    serve = Path(trace_mod.__file__).resolve().parents[1] / "serve"
    said = set()
    for path in serve.glob("*.py"):
        src = path.read_text()
        assert not re.search(r"_dev\s*(?::[^=\n]+)?=\s*None", src), path.name
        assert not re.search(r'\["stale"\]\s*=[^=]', src) \
            or path.name == "engine.py", path.name
        for call in re.findall(
                r"\b(?:drop_dev|no_dev|stale_tables)\(([^)]*)\)", src):
            said |= set(re.findall(r'"(\w+)"', call))
    # the two that the builder says itself: it finds the arrays resident,
    # but another program's set, or refreshes the tables of its own after
    # its caller's reservation (speculation); a reservation for a program
    # enqueued AHEAD says ``lookahead`` as an event too
    # (``DecodeArrays.reserve_ahead``: its pages may wait a step for a build)
    import inspect

    from distributed_training_guide_tpu.serve import engine

    built = set(re.findall(r'"(\w+)"', inspect.getsource(
        engine.upload_decode_arrays).split('"""')[2])) & set(REBUILD_REASONS)
    assert built == {"kind", "lookahead"} and built & said == {"lookahead"}
    assert said | built == set(REBUILD_REASONS)
    assert len(REBUILD_REASONS) == len(set(REBUILD_REASONS))


def test_the_orders_and_the_causes_in_the_source_are_the_closed_sets():
    """As ``REBUILD_REASONS`` above: what ``serve/engine.py`` says of a
    step's ``order``, what the quiet test returns as ``held_by`` and what
    admission says blocked it are the three tuples of ``utils/trace.py``, no
    more and no fewer."""
    import inspect

    from distributed_training_guide_tpu.serve import engine, scheduler

    said = set()
    for line in inspect.getsource(engine).splitlines():
        if re.search(r"\border(, held_by)? = ", line):
            said |= set(re.findall(r'"(\w+)"', line))
    assert said == set(STEP_ORDERS), said
    quiet = inspect.getsource(engine.ServeEngine._quiet).split('"""')[2]
    steady = inspect.getsource(
        engine.ServeEngine._pipeline_steady).split('"""')[2]
    returned = r'(?:return None, |return |else \(None, )"(\w+)"'
    # in the order the checks run: the first that fails is the one named
    # (`budget` twice: the plain program's before the reservation, a
    # horizon's, all lanes ending inside the pending block, after it)
    assert re.findall(returned, quiet)[:1] == ["kind"]
    assert tuple(re.findall(returned, quiet)[:1] + re.findall(returned, steady)
                 + re.findall(returned, quiet)[1:-1]) == NOT_QUIET
    assert re.findall(returned, quiet)[-1] == "budget"
    # a refusal is written in ONE place, which also keeps the memo
    blocks = set(re.findall(r'self\._refuse_head\(entry, sp, "(\w+)"',
                            inspect.getsource(scheduler)))
    assert blocks == set(ADMIT_BLOCKS)
    assert not re.findall(r'blocked_by="', inspect.getsource(scheduler))
    for closed in (STEP_ORDERS, NOT_QUIET, ADMIT_BLOCKS):
        assert len(closed) == len(set(closed))


# ---- (b) no session: nothing recorded, nothing changed ---------------------

def test_span_records_nothing_without_a_session(debug_model, tmp_path):
    engine = tight_engine(debug_model)
    with span("serve.step", seq=0):
        run_requests(engine, n_new=4)
    jax.profiler.start_trace(str(tmp_path))
    jnp.zeros(4).block_until_ready()
    jax.profiler.stop_trace()
    assert program_events(tmp_path) == []


class _NullSpan(contextlib.nullcontext):
    def __enter__(self):
        return self

    def set_metadata(self, **_):
        pass


def test_tokens_do_not_depend_on_the_spans(debug_model, monkeypatch):
    with_spans = run_requests(tight_engine(debug_model))
    from distributed_training_guide_tpu.serve import engine, scheduler

    for mod in (engine, scheduler):
        monkeypatch.setattr(mod, "span", lambda name, **args: _NullSpan())
    assert run_requests(tight_engine(debug_model)) == with_spans


# ---- (c) names in the lowered programs -------------------------------------

def scope_components(text):
    """Every component of every location path in a lowered program's debug
    text, wrappers (``transpose(jvp(attn))``) taken off."""
    out = set()
    for path in re.findall(r'loc\("([^"]+)"', text):
        for part in path.split("/"):
            out.add(re.sub(r"^(?:[\w.\-]+\()*([^()]*)\)*$", r"\1", part))
    return out


def test_train_step_carries_scopes_and_module_name():
    plan = make_plan("single", make_mesh(devices=jax.devices()[:1]))
    trainer = Trainer(bundle=get_model("llama-debug"),
                      optimizer=optax.adamw(1e-3), plan=plan, remat=True,
                      loss_chunks=4)
    lowered, _ = lower_step(trainer, global_batch=2, seq_length=64)
    text = lowered.as_text(debug_info=True)
    assert "module @jit_train_step" in text
    want = {"embed", "layers", "attn", "mlp", "final_norm", "loss_head",
            "optimizer"}
    assert want <= scope_components(text), want - scope_components(text)
    assert want <= set(SCOPES)


@pytest.mark.parametrize("strategy,gathers", [("fsdp", True), ("ddp", False)])
def test_head_gather_is_written_inside_loss_head(strategy, gathers):
    """The sub-scope names the loss head's one gather, forward and (as the
    region's transpose) backward, where the plan shards the output matrix
    over data axes; a replicated head has no such scope."""
    devices = jax.devices()[:4]
    mesh = (make_mesh(fsdp=4, devices=devices) if strategy == "fsdp"
            else make_mesh(dp=4, devices=devices))
    trainer = Trainer(bundle=get_model("llama-debug"),
                      optimizer=optax.adamw(1e-3),
                      plan=make_plan(strategy, mesh), loss_chunks=4)
    lowered, _ = lower_step(trainer, global_batch=4, seq_length=32)
    assert ("head_gather" in scope_components(
        lowered.as_text(debug_info=True))) is gathers
    # the whole path is in the compiled program's op names
    paths = [p for p in re.findall(r'op_name="([^"]+)"',
                                   lowered.compile().as_text())
             if "/head_gather/" in p]
    assert all("loss_head" in p.split("/head_gather/")[0] for p in paths)
    assert any(p.endswith("/all_gather") for p in paths) is gathers
    assert any("transpose(jvp(loss_head))" in p for p in paths) is gathers
    assert "head_gather" in SUBSCOPES


def test_moe_step_carries_router_and_experts():
    plan = make_plan("single", make_mesh(devices=jax.devices()[:1]))
    trainer = Trainer(bundle=get_model("moe-debug"),
                      optimizer=optax.adamw(1e-3), plan=plan)
    lowered, _ = lower_step(trainer, global_batch=2, seq_length=32)
    found = scope_components(lowered.as_text(debug_info=True))
    assert {"router", "experts", "attn", "layers", "loss_head"} <= found


def test_sparse_walked_step_carries_a_scope_a_layer_kind():
    """``models/laguna.py``: the attention sublayer of a full / a window
    layer lies under ``attn_full`` / ``attn_window`` INSIDE ``attn``, the
    dense layer's FFN under ``mlp``, a sparse layer's under ``experts`` with
    ``router`` and ``shared_expert`` inside, forward and backward."""
    plan = make_plan("single", make_mesh(devices=jax.devices()[:1]))
    trainer = Trainer(bundle=get_model("laguna-debug"), remat=True,
                      optimizer=optax.adamw(1e-3), plan=plan, loss_chunks=4)
    lowered, _ = lower_step(trainer, global_batch=2, seq_length=32)
    text = lowered.as_text(debug_info=True)
    assert {"attn", "attn_full", "attn_window", "mlp", "experts", "router",
            "shared_expert", "layers", "loss_head", "optimizer"} \
        <= scope_components(text)
    fragments = re.findall(r'loc\("([^"]+)"', text)
    for kind in ("attn_full", "attn_window"):
        inside = [f for f in fragments if f"/{kind}/" in f or
                  f"({kind})" in f]
        assert inside and all("attn" in scope_components(f'loc("{f}"')
                              for f in inside), kind
        assert any("transpose(" in f for f in inside), kind   # the backward
    assert {"attn_full", "attn_window"} <= set(SUBSCOPES)


@pytest.mark.parametrize("family", ["gpt2-debug", "neox-debug"])
def test_other_families_carry_the_shared_scopes(family):
    bundle = get_model(family)
    params = jax.eval_shape(lambda: bundle.init(bundle.config,
                                                jax.random.key(0)))
    ids = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = jax.jit(lambda p, x: bundle.apply(bundle.config, p, x)).lower(
        params, ids).as_text(debug_info=True)
    assert {"embed", "attn", "mlp", "final_norm",
            "loss_head"} <= scope_components(text)


def test_serve_programs_have_stable_names_and_scopes(debug_model):
    bundle, params = debug_model
    engine = ServeEngine(bundle, params, n_slots=2, page_size=8, max_len=64,
                         prefill_chunk=4, speculate="ngram", spec_k=2)
    run_requests(engine, n_new=6)
    plain = tight_engine(debug_model, decode_horizon=2)
    run_requests(plain, n_new=6)
    programs = engine.programs
    arrays = {k: jnp.asarray(v)
              for k, v in engine.scheduler.decode_arrays().items()}
    text = programs._decode_fn.lower(
        programs.params, engine.pages,
        *(arrays[k] for k in ("tokens", "lengths", "tables", "seeds",
                              "temps", "top_ks", "top_ps", "actives"))
    ).as_text(debug_info=True)
    assert "module @jit_serve_decode" in text
    want = {"embed", "layers", "attn", "attend", "kv_write", "mlp",
            "final_norm", "loss_head", "sample"}
    assert want <= scope_components(text), want - scope_components(text)
    # every jitted program of the two engines, by the name jit gave it
    fns = [programs._decode_fn, programs._copy_fn, programs._sample_one,
           plain.programs._seat_fn, *programs._chunk_fns.values(),
           *programs._verify_fns.values(),
           *plain.programs._chunk_fns.values(),
           *plain.programs._horizon_fns.values()]
    got = {fn.__name__ for fn in fns}
    # the engine that names no chunk size prefills through the chunk program
    # at its own size: one slot's capacity here (64 < the ceiling of 512)
    assert {"serve_decode", "serve_copy", "serve_sample_one",
            "serve_seat_token", "serve_chunk_t4", "serve_chunk_t64",
            "serve_verify_t3_greedy", "serve_horizon_k2"} <= got
    for name in got:
        assert name != "fn" and "lambda" not in name
        assert any(name == p or (name.startswith(p) and p[-1] in "kt")
                   for p in PROGRAMS), name


def test_latent_family_decode_carries_its_subscopes_and_kernel():
    """``models/mla.py`` through ``ServeEngine``: the sub-scopes sit inside
    their parents (``attn/latent_proj``, ``experts/shared_expert``), so the
    scope table still adds up, and the attend is the latent kernel's."""
    bundle = get_model("mla-moe-debug", dtype=jnp.float32)
    params = bundle.init(bundle.config, jax.random.key(0))
    engine = ServeEngine(bundle, params, n_slots=2, page_size=16, max_len=64,
                         attend_impl="flash")
    arrays = {k: jnp.asarray(v)
              for k, v in engine.scheduler.decode_arrays().items()}
    text = engine._decode_fn.lower(
        engine.params, engine.pages,
        *(arrays[k] for k in ("tokens", "lengths", "tables", "seeds",
                              "temps", "top_ks", "top_ps", "actives"))
    ).as_text(debug_info=True)
    found = scope_components(text)
    want = {"attn", "latent_proj", "attend", "paged_latent_attend", "experts",
            "shared_expert", "router", "kv_write", "layers", "loss_head"}
    assert want <= found, want - found
    # a sub-scope is written inside its parent's
    fragments = re.findall(r'loc\("([^"]+)"', text)
    assert any(f.startswith("experts/shared_expert/") for f in fragments)
    assert any(f.startswith("attn/latent_proj/") for f in fragments)
    assert "paged_latent_attend" in KERNELS and set(SUBSCOPES) == {
        "latent_proj", "shared_expert", "conv", "attend_full",
        "attend_window", "head_gather", "attn_full", "attn_window", "kda",
        "ssm", "retention"}


def test_named_gives_jit_the_name():
    fn = jax.jit(named(lambda x: x + 1, "serve_copy"))
    assert "module @jit_serve_copy" in fn.lower(jnp.zeros(2)).as_text()


def test_the_vocabulary_is_what_the_package_uses():
    """``grep`` over the package: every ``span("...")``, ``named_scope("...")``
    and ``pallas_call`` name is in the tuples, and ``TraceAnnotation`` is
    used by ``utils/trace.py`` alone. A computation that is a kernel on the
    chip and plain ``jnp`` elsewhere (``ops/kda.py``, ``ops/ssm.py``,
    ``ops/retention.py``) carries
    its KERNELS name as a ``named_scope`` too, whatever implements it."""
    from pathlib import Path

    root = Path(trace_mod.__file__).resolve().parents[1]
    spans, scopes, kernels, annot = set(), set(), set(), []
    for path in root.rglob("*.py"):
        src = path.read_text()
        if "TraceAnnotation" in src:
            annot.append(path.name)
        spans |= set(re.findall(r'\bspan\(\s*"([\w.]+)"', src))
        scopes |= set(re.findall(r'named_scope\("(\w+)"\)', src))
        if path.parent.name == "ops":
            kernels |= set(re.findall(r'^\s+name="(\w+)",$', src, re.M))
    assert annot == ["trace.py"]
    assert spans | {"train.data", "train.step"} == set(SPANS)
    assert scopes - set(KERNELS) == set(SCOPES) | set(SUBSCOPES)
    assert not set(SCOPES) & set(SUBSCOPES)
    assert scopes & set(KERNELS) == {"kda_step", "kda_chunk", "ssm_step",
                                     "ssm_chunk", "retention_step",
                                     "retention_chunk"}
    assert kernels | (scopes & set(KERNELS)) == set(KERNELS)
