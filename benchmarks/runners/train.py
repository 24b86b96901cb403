"""Runner for training cells: drives ``train/step.py``'s compiled step with
batches from ``data/loader.py``, as ``train/cli.py``'s loop does.

Set-up builds ONE object, the compiled step with its state, drives it through
its first ``check_steps`` steps through the window's own call and feed, and
hands the same object to the window. After the window the program's state is
freed and the plain reference follows the same first steps from the same
seed-made weights on the same batches.
"""
from __future__ import annotations

import json
import math
import statistics
import time

import numpy as np

from benchmarks import weights
from benchmarks.reference import decoder as ref
from benchmarks.runners import _llama
from benchmarks.traffic import generate


def _find_mu(opt_state):
    """The Adam first moment inside an optax state, wherever it is chained."""
    found = []

    def visit(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
            return
        if isinstance(node, (tuple, list)):
            for child in node:
                visit(child)
        elif isinstance(node, dict):
            for child in node.values():
                visit(child)

    visit(opt_state)
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0]


def worst_leaf_gap(prog: dict, want: dict) -> tuple:
    """Largest |program's norm - reference's norm| over leaves (one entry per
    layer of a stacked leaf), against the reference's norm of that leaf or of
    the median leaf, whichever is larger. Returns (gap, leaf)."""
    flat_w = {f"{k}[{i}]": float(x) for k, v in want.items()
              for i, x in enumerate(np.atleast_1d(v))}
    flat_p = {f"{k}[{i}]": float(x) for k, v in prog.items()
              for i, x in enumerate(np.atleast_1d(v))}
    median = statistics.median(flat_w.values())
    worst, where = 0.0, ""
    for k, w in flat_w.items():
        gap = abs(flat_p[k] - w) / max(w, median)
        if not math.isfinite(gap):
            return math.inf, k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def global_norm_gap(prog: dict, want: dict) -> float:
    """|program's norm - reference's| / reference's, of all leaves together."""
    total = lambda t: math.sqrt(sum(float(np.sum(np.square(v)))
                                    for v in t.values()))
    return abs(total(prog) - total(want)) / total(want)


def build(ctx):
    """The program's objects for this cell: trainer, loader, compiled step."""
    import jax

    from distributed_training_guide_tpu.data import ShardedBatchLoader
    from distributed_training_guide_tpu.parallel import make_mesh, make_plan
    from distributed_training_guide_tpu.train import Trainer
    from distributed_training_guide_tpu.train.optimizer import OPTIMIZERS
    from distributed_training_guide_tpu.train.step import lower_step

    cfg, traffic, job, seed = (ctx["config"], ctx["traffic"], ctx["job"],
                               ctx["seed"])
    plan = make_plan(job["plan"]["strategy"],
                     make_mesh(**job["plan"].get("mesh", {}),
                               devices=ctx["devices"]))
    opt = job["optimizer"]
    optimizer = OPTIMIZERS[opt["name"]](
        opt["lr"], t_max=opt["t_max"], eta_min_ratio=opt["eta_min_ratio"],
        weight_decay=opt["weight_decay"], b1=opt["b1"], b2=opt["b2"],
        eps=opt["eps"])
    trainer = Trainer(
        bundle=_llama.bundle_for(cfg, ctx["cell"]["config"]),
        optimizer=optimizer, plan=plan, remat=job["remat"],
        remat_policy=job.get("remat_policy", "all"),
        loss_chunks=job["loss_chunks"], attn_impl=job["attn_impl"],
        precision=job["precision"])
    gb, seq = traffic["global_batch"], traffic["seq_len"]
    ctx["phase"]("trainer built")
    lowered, _ = lower_step(trainer, global_batch=gb, seq_length=seq)
    ctx["phase"]("step lowered")
    step = lowered.compile()
    del lowered
    ctx["phase"]("step compiled or loaded")

    # the seed is an operand: a new seed compiles nothing
    make = jax.jit(lambda key: _llama.program_params(cfg, key),
                   out_shardings=trainer.param_shardings)
    params = make(weights.seed_key(seed))
    state = trainer.init_state_from_params(params, seed & 0x7FFFFFFF)
    del params
    ctx["phase"]("weights made, state placed")

    dataset = generate.train_dataset(traffic, cfg["vocab_size"], seed)
    loader = ShardedBatchLoader(dataset, gb,
                                trainer.batch_shardings()["input_ids"],
                                seed=seed & 0x7FFFFFFF)
    return trainer, step, state, loader


def batches_forever(loader):
    epoch = 0
    while True:
        loader.set_epoch(epoch)
        yield from loader.epoch_batches()
        epoch += 1


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    cfg, traffic, job, seed = (ctx["config"], ctx["traffic"], ctx["job"],
                               ctx["seed"])
    spans, checks = ctx["spans"], ctx["checks"]
    chips = len(ctx["devices"])
    ctx["phase"]("imports done, device checked")
    trainer, step, state, loader = build(ctx)
    batches = batches_forever(loader)

    # ---- the first steps, through the window's own call and feed ----------
    n_check = job["check"]["steps"]
    norms = jax.jit(lambda t: ref.leaf_norms(_llama.from_program(t)))
    delta = jax.jit(lambda p, key: ref.leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        _llama.from_program(p),
        _llama.from_program(_llama.program_params(cfg, key)))))
    seen, prog = [], {"losses": []}
    for i in range(n_check):
        with spans.span("data"):
            batch = next(batches)
        seen.append(np.asarray(batch["input_ids"]))
        with spans.span("step"):
            state, metrics = step(state, batch)
            prog["losses"].append(float(metrics["loss"]))
        if i == 0:
            b1 = job["optimizer"]["b1"]
            mu = jax.device_get(norms(_find_mu(state.opt_state)))
            prog["grad_norms"] = {k: v / (1.0 - b1) for k, v in mu.items()}
    prog["delta_norms"] = jax.device_get(delta(state.params,
                                               weights.seed_key(seed)))
    ctx["phase"]("first steps driven and read")
    for rows in seen:   # rows that all differ
        assert len({r.tobytes() for r in rows}) == len(rows)

    # ---- the window --------------------------------------------------------
    tokens_per_step = traffic["global_batch"] * traffic["seq_len"]
    trace_s = min(ctx["seconds"], job.get("trace_seconds", 10.0))
    compiles_before = ctx["compiles"].snapshot()
    tracing = ctx["trace_dir"] is not None
    if tracing:
        jax.profiler.start_trace(str(ctx["trace_dir"]))
    t0 = time.perf_counter()
    setup_s = time.monotonic() - ctx["t_process_start"]
    deadline = t0 + ctx["seconds"]
    attempted = failed = done = 0
    last_end, losses, trace_window = t0, [], None
    while True:
        attempted += 1
        try:
            with spans.span("data"):
                batch = next(batches)
            with spans.span("step"):
                state, metrics = step(state, batch)
                loss = float(metrics["loss"])   # host read: the step's fence
        except Exception as exc:  # a failed step is counted, not hidden
            print(json.dumps({"step_failed": repr(exc)}), flush=True)
            failed += 1
            break
        now = time.perf_counter()
        if tracing and now - t0 >= trace_s:
            jax.profiler.stop_trace()
            tracing, trace_window = False, (t0, now)
        if now > deadline:
            attempted -= 1      # ended outside the window: not this run's
            break
        if not math.isfinite(loss):
            failed += 1
        else:
            done += 1
            last_end = now
            losses.append(loss)
    if tracing:
        jax.profiler.stop_trace()
        trace_window = (t0, time.perf_counter())
    compiles_after = ctx["compiles"].snapshot()
    from benchmarks.harness import memory_peak_bytes
    peak = memory_peak_bytes(ctx["devices"])
    loader.close()
    window_s = last_end - t0
    rate = done * tokens_per_step / window_s / chips if done else 0.0
    print(json.dumps({"window": {
        "steps_finished": done, "steps_attempted": attempted,
        "seconds_to_last_step_end": window_s, "tokens_per_step": tokens_per_step,
        "first_loss": prog["losses"][0], "window_first_loss": losses[:1],
        "window_last_loss": losses[-1:]}}), flush=True)

    # ---- free the program's state, then the plain reference ---------------
    del state, step, batch, metrics
    t_ref = time.perf_counter()
    want = reference_steps(ctx, seen)
    ref_s = time.perf_counter() - t_ref
    lim = job["check"]["limits"]
    for name, value, note, limit in gap_rows(prog, want):
        checks.add(name, value, lim[limit], "max", note)
    if losses:
        checks.add("window_loss_rise_over_first", max(losses) - prog["losses"][0],
                   lim["loss_rise_max"], "max", "finite and not diverging")
    checks.add("steps_finished_in_window", done, 1, "min")
    print(json.dumps({"reference_seconds": ref_s}), flush=True)

    return {
        "end_to_end": {"train.tokens_per_s_per_chip": rate, "setup_s": setup_s},
        "attempted": attempted, "failed": failed,
        "compiles_in_window": [b - a for a, b in
                               zip(compiles_before, compiles_after)],
        "memory_peak_bytes": peak, "window": (t0, last_end),
        "trace_window": trace_window,
        "checked": {"seen": seen, "want": want},
        "counters": {"steps": done, "tokens_per_step": tokens_per_step},
    }


def gap_rows(prog: dict, want: dict) -> list:
    """Every number compared with the reference, as rows of
    (check, value, note, the limit's key in the workload file)."""
    rows = [(f"loss_step{i}_rel_gap", abs(a - b) / abs(b),
             f"program {a!r} reference {b!r}", "loss_rel_gap")
            for i, (a, b) in enumerate(zip(prog["losses"], want["losses"]))]
    rows.append(("first_grad_norm_worst_leaf_gap", *worst_leaf_gap(
        prog["grad_norms"], want["grad_norms"]), "grad_norm_worst_leaf_gap"))
    rows.append(("first_grad_global_norm_gap", global_norm_gap(
        prog["grad_norms"], want["grad_norms"]), "", "grad_global_norm_gap"))
    rows.append(("param_change_norm_worst_leaf_gap", *worst_leaf_gap(
        prog["delta_norms"], want["delta_norms"]),
        "param_change_norm_worst_leaf_gap"))
    return rows


def control(ctx, mode: str) -> dict:
    """``controls.py`` only, after ``run``: the reference in a lower precision
    put in the program's place, over the batches the run checked."""
    checked = ctx["checked"]
    ctrl = reference_steps(ctx, checked["seen"], mode=mode)
    return {name: value for name, value, _, _ in
            gap_rows(ctrl, checked["want"])}


def reference_steps(ctx, seen, mode="highest") -> dict:
    """The plain reference over the batches the program saw. Its float32
    parameters and moments are spread over the cell's chips where there are
    several (the code is the same plain ``jax.numpy``)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg, job, seed = ctx["config"], ctx["job"], ctx["seed"]
    devices = ctx["devices"]
    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("x",))

    def spread(shape):
        if n > 1:
            for axis in sorted(range(len(shape)), key=lambda a: -shape[a]):
                if shape[axis] % n == 0 and shape[axis] >= 1024:
                    spec = [None] * len(shape)
                    spec[axis] = "x"
                    return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    import jax.numpy as jnp
    key = weights.seed_key(seed)
    make = lambda key: weights.stacked_weights(cfg, key, jnp.float32)
    shardings = jax.tree.map(lambda s: spread(s.shape),
                             jax.eval_shape(make, key))
    place = lambda fn: jax.jit(fn, out_shardings=shardings)
    rows = job["check"]["reference_rows_per_block"]
    batches = [jax.device_put(b, NamedSharding(mesh, P())) for b in seen]
    return ref.train_steps(
        cfg, job["optimizer"], make, key, batches, rows, mode, place=place,
        moments_on_host=job["check"].get("reference_moments_on_host", False))
