"""The program's own names in a profiler trace.

One mechanism and no switch. On the host, :func:`span` is a
``jax.profiler.TraceAnnotation`` named ``dtg.<name>``: without a profiler
session it is a flag test and records nothing; inside one
(``--profile-dir``, ``benchmarks/run.py --trace 1``, an operator's
``jax.profiler.start_trace``) it lands on a host thread's line on the same
clock as the device's ``XLA Ops`` line. Spans nest by containment on one
thread. Arguments known only when the work is done go on with
``set_metadata`` before the ``with`` block ends::

    with span("serve.reserve") as s:
        grown, preempted = sched.grow_for_decode()
        s.set_metadata(grown=grown, preempted=preempted)

Statistics that say what a span cannot show by its length: ``serve.build``
carries ``reason`` (one of :data:`REBUILD_REASONS`: why the decode arrays on
the device could not be used as they stood), its child ``serve.upload``
carries ``arrays`` and ``bytes`` (what went up: the whole set, or the block
tables alone), ``serve.step`` carries ``cpu_ms`` (the thread's own CPU time
over the iteration: a long step with little of it was waiting or
descheduled; where that clock ticks every 10 ms, as on the v5e hosts, only a
long step says anything) and ``overlapped`` (1 where the step completed a
prefill and dispatched its decode BEFORE it read the first token, so the
chunk program and the decode program ran back to back: such a step has a
``serve.sample`` in front of its ``serve.dispatch``, the sampler's launch,
and one behind it, the token's read; 0 in every other step) and ``order``
(one of :data:`STEP_ORDERS`: which of ``ServeEngine.step``'s orders the
iteration took; in a ``pipelined`` step the ``serve.dispatch`` ends before
the ``serve.wait`` starts as in every step, but the program that ran under
the wait is the step before's), ``serve.quiet`` (the quiet test where it
runs, ``ServeEngine._ahead``: may the NEXT decode program be enqueued before
the pending one is read) carries ``held_by`` (one of :data:`NOT_QUIET`: the
FIRST check that kept the step from pipelining, as ``serve.build``'s
``reason`` names the first event; ``""`` where the step is quiet, and the
profiler keeps no empty statistic: a quiet test's span arrives with none);
the one-write-ahead ``serve.reserve`` is its child. ``serve.wait`` carries
``waits_for`` (the ``seq`` of
the ``serve.step`` that enqueued the program it read: its own step's, or the
one before in a pipelined step and in one that drains; a reader that joins a
wait with a device program follows this, not the dispatch beside it),
``serve.admit`` carries ``request_id``, ``queue_ms`` (how long the queue's
head has waited since its submit) and ``admitted`` (1: the head took a slot
and its pages, and ``queue_ms`` is the wait it paid; 0: it stays queued, and
``blocked_by`` says by what, one of :data:`ADMIT_BLOCKS`, for ``pages`` with
``need``, ``free`` and ``headroom`` in pages: one span an attempt, so a head
that waits ten steps leaves ten spans with ``admitted`` 0 and one with 1;
``held`` 1 on a span with ``admitted`` 0 marks a step that made NO attempt:
it went ahead past a head whose last refusal still stands,
``Scheduler.hold_head``, and repeats that refusal's ``blocked_by`` with
nothing reckoned, so no ``need``, ``free`` or ``headroom``),
``serve.dispatch`` carries ``program`` and, for the plain decode program,
``programs`` (2 where a synchronous step entered the pipeline and enqueued
the next step's program behind its own, else 1), and ``gc``
carries ``generation`` and ``collected`` (:func:`install_gc_span`: one span
over each garbage collection).

On the device the names are HLO metadata and cost nothing at run time:
``jax.named_scope`` per model part (:data:`SCOPES`, :data:`SUBSCOPES`), a stable ``__name__`` on
every jitted program (:data:`PROGRAMS`; the trace's ``XLA Modules`` line then
reads ``jit_train_step`` or ``jit_serve_decode``) and ``name=`` on every
``pallas_call`` (:data:`KERNELS`). The tuples below are the whole vocabulary;
tests hold the package to them and the benchmark's readers match on them.
"""
from __future__ import annotations

import gc

import jax

PREFIX = "dtg."

# host spans, without the prefix (parent first within a family)
SPANS = (
    "serve.step", "serve.expire", "serve.restore", "serve.admit",
    "serve.fork", "serve.prefill", "serve.sample", "serve.draft",
    "serve.quiet", "serve.reserve", "serve.build", "serve.arrays", "serve.upload",
    "serve.dispatch", "serve.wait", "serve.book", "serve.release",
    "serve.state",
    "data.assemble", "data.put",
    "train.data", "train.step", "train.fence", "train.log", "train.ckpt",
    "gc",
)

# `reason` of a serve.build span: the FIRST event since the last build that
# left the decode arrays on the device unfit for the next decode
# (`serve/engine.py::DecodeArrays`). `first`: nothing did, the engine has not
# built any yet; `kind`: resident, but another decode program's set (plain /
# spec / horizon). Two reasons mean the same ONE transfer, the block tables
# alone, for different causes: `grown` (`grow_for_decode` gave a slot the page
# of its next write) and `lookahead` (a reservation gave it pages ahead of
# that: speculation's, a horizon's, or the one write ahead of a plain program
# enqueued behind the one in flight). Every other build carries the whole set;
# `serve.upload`'s `arrays` tells the two apart
REBUILD_REASONS = (
    "first", "grown", "preempted", "admitted", "prefilled", "left", "expired",
    "restored", "drained", "kind", "speculation", "swapped", "lookahead",
)

# `order` of a serve.step span: which of `ServeEngine.step`'s orders the
# iteration took. `sync`: nothing in flight at its start, the decode program
# enqueued AND read in the step (a chunk step that decodes too; a speculative
# step). `enter`: as `sync`, but the step ended quiet and LEFT a program in
# flight: the plain program for the token after its own, behind its own under
# the one serve.dispatch (`programs=2`), or a horizon's first block, which is
# never read in the step that enqueues it. `pipelined`: a program in flight
# and the step quiet, the next enqueued BEFORE the one in flight was read.
# `drain`: in flight and not quiet, waited and booked (the plain program's
# drain returns there; a horizon's goes on into the boundary in the same
# step). `idle`: no decoding slot, so no decode program (prefill chunks
# alone, or nothing). The `serve.step` of `serve/disagg.py`'s pair carries no
# `order` (and the pair emits no serve.quiet): no cell, reader or `stats()` key
# observes the pair, its plain program is always synchronous and its horizon's
# quiet test is its own, inline; `tests/test_trace_names.py` exempts it by name
# until a cell measures it
STEP_ORDERS = ("sync", "enter", "pipelined", "drain", "idle")

# `held_by` of a serve.quiet span, in the order the checks run; the FIRST
# that fails is named. `kind`: the program in flight is not the one the
# engine would enqueue now (plain / horizon: the knob moved); `inactive`: no
# slot decodes; `drafter`: what it proposes comes from the host's tokens;
# `queued`: a request waits for admission AND might get in (a head whose last
# refusal still stands, `Scheduler.head_refusal_stands`, is no cause: the step
# goes ahead past it and says so with a `serve.admit` of `held` 1); `prefill`:
# an admitted prompt has chunks to run; `replaying`: a slot consumes recorded
# tokens, from the host; `deadline`: an expiry is due; `budget`: a reply ends
# with a pending token (the plain program masks no lane; a horizon too while a
# refused head waits for what that reply returns), or every lane ends inside
# the pending block (a horizon); `arrays`: the resident set went (a lane left,
# or the reservation's growth dropped it); `pages`: the reservation for the
# writes ahead covered too little. A quiet step has `held_by` ""
NOT_QUIET = ("kind", "inactive", "drafter", "queued", "prefill", "replaying",
             "deadline", "budget", "arrays", "pages")

# `blocked_by` of a serve.admit span with `admitted` 0: the pool could not
# grant the head's pages and keep its headroom (`need`, `free`, `headroom`
# ride with it), or no slot is free. A span with `held` 1 repeats the cause of
# the refusal that still stands (`queued` above), without those three
ADMIT_BLOCKS = ("pages", "slots")

# jax.named_scope names: model parts (`layers` is the layer scan's own work,
# outside any sublayer), the train step's tail, the paged serve path. The
# benchmark's scope table (`readers/scope_time.py`) is keyed by these: an
# operation goes to the innermost of them in its path
SCOPES = (
    "embed", "layers", "attn", "mlp", "final_norm", "loss_head", "optimizer",
    "router", "experts", "attend", "kv_write", "sample",
)

# named_scope names INSIDE a scope above, which split it without leaving it:
# `latent_proj` (in `attn`: MLA's low-rank projections and absorbed products),
# `shared_expert` (in `experts`), `conv` (in `attn`, which for a hybrid
# family is the operator sublayer whatever its kind: LFM2's gated short
# convolution, its norm and projections included) and `attend_full` /
# `attend_window` (in `attend`: the read half of a two-class family's full
# and window layers, `serve/kv_pages.py`), `attn_full` / `attn_window` (in
# `attn`: the WHOLE attention sublayer of a full / a window layer of a family
# whose layers differ in kind on the train path, `models/laguna.py`: norm,
# projections, rope, the flash kernels, gate and output) and `head_gather` (in `loss_head`:
# the ONE all-gather of a data-sharded output matrix around the chunked loss
# and, as `transpose(jvp(loss_head))/.../head_gather`, the one reduce-scatter
# of its gradient, `ops/cross_entropy.py`; a plan that leaves the loss to
# GSPMD has no such events) and `kda` (in `attn`: a KDA layer's whole mixer,
# `models/solar_open2.py`: norm, projections, the short convolutions, the
# `kda_step` / `kda_chunk` recurrence, the gated read-out and `W_o`; the
# state's writes are under `kv_write`) and `ssm` (in `attn`: a Mamba layer's
# whole mixer, `models/jamba.py`: norm, `W_in`, the convolution, `W_x`, the
# inner norms, `W_dt`, the `ssm_step` / `ssm_chunk` recurrence, the gate and
# `W_out`; the state's writes under `kv_write` again) and `retention` (in
# `attn`: a power-retention layer's whole mixer, `models/brumby.py`: norm,
# projections, the gate, QK-norm and rope, the `retention_step` /
# `retention_chunk` recurrence and `W_o`; the state is updated where it lies,
# inside those two). A reader that knows
# only SCOPES counts their time under the parent;
# `readers/path_component.py` reads one alone
SUBSCOPES = ("latent_proj", "shared_expert", "conv", "attend_full",
             "attend_window", "head_gather", "attn_full", "attn_window", "kda",
             "ssm", "retention")

# pallas_call names (ops/); `kda_step` and `kda_chunk` name the two forms of
# the KDA recurrence whatever implements them: on a TPU each is ONE Pallas
# kernel a KDA layer (a decode program shows three `kda_step` events, a chunk
# program three `kda_chunk`: the chunk's whole gated delta rule, its state in
# VMEM from the first block to the last), elsewhere plain jnp; both sit
# under a `named_scope` of the same name, so a path reads
# `attn/kda/kda_chunk/...` either way (`ops/kda.py`); `ssm_step` and
# `ssm_chunk` likewise name the two forms of Mamba's selective scan
# (`ops/ssm.py`: one kernel a Mamba layer on a TPU, `attn/ssm/ssm_step/...`),
# `retention_step` and `retention_chunk` the two forms of power retention
# (`ops/retention.py`: `attn/retention/retention_step/...`; the scope holds
# the kernel and, for the step, XLA's feature rows and normaliser beside it)
KERNELS = ("flash_fwd", "flash_dq", "flash_dkv", "paged_attend",
           "paged_latent_attend", "gmm", "tgmm", "qmm", "kda_step",
           "kda_chunk", "ssm_step", "ssm_chunk", "retention_step",
           "retention_chunk")

# jitted programs; a name ending in _k or _t takes the static size that
# keys the program (serve_horizon_k4, serve_chunk_t64, serve_verify_t5 and
# its all-greedy twin serve_verify_t5_greedy)
PROGRAMS = (
    "train_step", "serve_decode", "serve_horizon_k", "serve_chunk_t",
    "serve_verify_t", "serve_copy", "serve_sample_one", "serve_seat_token",
    "serve_adapter_insert", "serve_snapshot", "serve_requant",
    "serve_draft_step", "serve_draft_catchup",
)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``dtg.<name>`` with ``args`` as its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


class _GcSpan:
    """The ``gc.callbacks`` hook: ``dtg.gc`` from a collection's start to its
    stop, on the thread that collects (collections do not nest)."""

    def __init__(self):
        self._open = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._open = span("gc", generation=info["generation"])
            self._open.__enter__()
        elif self._open is not None:
            self._open.set_metadata(collected=info["collected"])
            self._open.__exit__(None, None, None)
            self._open = None


_gc_span = _GcSpan()


def install_gc_span() -> None:
    """Hook the collector once a process (the serve engine and the train
    loop both call this): nothing runs unless a collection does, and outside
    a profiler session the span is a flag test like any other."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)


def named(fn, name: str):
    """``fn`` (called positionally) under a stable ``__name__``:
    ``jax.jit(named(fn, "x"))`` compiles a module called ``jit_x`` whatever
    the closure, lambda or bound method was called."""
    def program(*args):
        return fn(*args)

    program.__name__ = program.__qualname__ = name
    return program
