"""Runner for the training cells of a family that is NOT ``llama``:
``runners/train.py``'s set-up, window and check, with the family's adapter
found by the configuration's ``family`` (``runners/_<family>.py``:
``bundle_for``, ``program_params``, ``from_program``, its ``weights`` module
and its plain ``reference`` with ``leaf_norms`` and ``train_steps``), as
``runners/serve.py`` finds a serve family's. ``runners/train.py`` is wired to
``_llama`` and ``reference/decoder.py`` by import and may not be edited by
the PR that added this file; the two loops are one loop twice, and PERF.md
section 7 asks the next ``benchmark`` PR to merge them.

A sparse family's step also reports what its routed layers counted
(``moe_pairs_held``, ``moe_pairs_routed``, ``moe_fullest_expert_rows``:
``models/laguna.py TRAIN_METRICS``); they are read with the loss, in the
step's one host read, and kept a step (``counters["routing_steps"]``) and in
sum.
"""
from __future__ import annotations

import importlib
import json
import math
import time

import numpy as np

from benchmarks.runners.train import _find_mu, batches_forever, gap_rows
from benchmarks.traffic import generate

ROUTING = ("moe_pairs_held", "moe_pairs_routed", "moe_fullest_expert_rows")


def family_of(cfg: dict):
    """``runners/_<family>.py``: a checkout without the family's module in
    the program fails here, on the name, before any weight is made."""
    return importlib.import_module(f"benchmarks.runners._{cfg['family']}")


def build(ctx, fam):
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
        bundle=fam.bundle_for(cfg, ctx["cell"]["config"]),
        optimizer=optimizer, plan=plan, remat=job["remat"],
        remat_policy=job.get("remat_policy", "all"),
        loss_chunks=job["loss_chunks"], attn_impl=job["attn_impl"],
        precision=job["precision"])
    gb, seq = traffic["global_batch"], traffic["seq_len"]
    print(json.dumps({"parameters_held": fam.weights.num_params(cfg)}),
          flush=True)
    ctx["phase"]("trainer built")
    lowered, _ = lower_step(trainer, global_batch=gb, seq_length=seq)
    ctx["phase"]("step lowered")
    step = lowered.compile()
    del lowered
    ctx["phase"]("step compiled or loaded")
    analysis = step.memory_analysis()
    if analysis is not None:
        print(json.dumps({"step_memory_analysis": {
            "arguments_bytes": analysis.argument_size_in_bytes,
            "temporaries_bytes": analysis.temp_size_in_bytes}}), flush=True)

    # the seed is an operand: a new seed compiles nothing
    make = jax.jit(lambda key: fam.program_params(cfg, key),
                   out_shardings=trainer.param_shardings)
    params = make(fam.weights.seed_key(seed))
    state = trainer.init_state_from_params(params, seed & 0x7FFFFFFF)
    del params
    ctx["phase"]("weights made, state placed")

    dataset = generate.train_dataset(traffic, cfg["vocab_size"], seed)
    loader = ShardedBatchLoader(dataset, gb,
                                trainer.batch_shardings()["input_ids"],
                                seed=seed & 0x7FFFFFFF)
    return trainer, step, state, loader


def read_step(metrics) -> tuple:
    """The step's one host read: the loss (the fence) and, where the family
    counts them, the routed layers' counts ``(held, routed, fullest)``."""
    import jax

    got = jax.device_get({k: metrics[k] for k in ("loss", *ROUTING)
                          if k in metrics})
    routing = (tuple(int(got[k]) for k in ROUTING)
               if all(k in got for k in ROUTING) else None)
    return float(got["loss"]), routing


def routing_counters(cfg: dict, rows: list) -> dict:
    """``rows``: ``(wall clock, held, routed, fullest)`` a finished step."""
    if not rows:
        return {}
    held = sum(r[1] for r in rows)
    routed = sum(r[2] for r in rows)
    sparse = sum(t != "dense" for t in cfg["mlp_layer_types"])
    # a step's fullest held group of any layer over its mean held group
    fullest = [r[3] / (r[1] / (sparse * cfg["num_experts"])) for r in rows
               if r[1]]
    return {"routing_steps": rows, "pairs_held": held, "pairs_routed": routed,
            "fullest_expert_rows": max(r[3] for r in rows),
            "expert_pairs_held_pct": 100.0 * held / routed,
            "expert_rows_fullest_over_mean": (sum(fullest) / len(fullest)
                                              if fullest else None)}


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    cfg, traffic, job, seed = (ctx["config"], ctx["traffic"], ctx["job"],
                               ctx["seed"])
    spans, checks = ctx["spans"], ctx["checks"]
    chips = len(ctx["devices"])
    fam = family_of(cfg)
    ref = fam.reference
    ctx["phase"]("imports done, device checked")
    trainer, step, state, loader = build(ctx, fam)
    batches = batches_forever(loader)

    # ---- the first steps, through the window's own call and feed ----------
    n_check = job["check"]["steps"]
    norms = jax.jit(lambda t: ref.leaf_norms(fam.from_program(t)))
    delta = jax.jit(lambda p, key: ref.leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        fam.from_program(p),
        fam.from_program(fam.program_params(cfg, key)))))
    seen, prog = [], {"losses": []}
    for i in range(n_check):
        with spans.span("data"):
            batch = next(batches)
        seen.append(np.asarray(batch["input_ids"]))
        with spans.span("step"):
            state, metrics = step(state, batch)
            prog["losses"].append(read_step(metrics)[0])
        if i == 0:
            b1 = job["optimizer"]["b1"]
            mu = jax.device_get(norms(_find_mu(state.opt_state)))
            prog["grad_norms"] = {k: v / (1.0 - b1) for k, v in mu.items()}
    prog["delta_norms"] = jax.device_get(delta(state.params,
                                               fam.weights.seed_key(seed)))
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
    last_end, losses, routing, trace_window = t0, [], [], None
    while True:
        attempted += 1
        try:
            with spans.span("data"):
                batch = next(batches)
            with spans.span("step"):
                state, metrics = step(state, batch)
                loss, counts = read_step(metrics)    # the step's fence
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
            if counts is not None:
                routing.append((now, *counts))
    if tracing:
        jax.profiler.stop_trace()
        trace_window = (t0, time.perf_counter())
    compiles_after = ctx["compiles"].snapshot()
    from benchmarks.harness import memory_peak_bytes
    peak = memory_peak_bytes(ctx["devices"])
    loader.close()
    window_s = last_end - t0
    rate = done * tokens_per_step / window_s / chips if done else 0.0
    counters = {"steps": done, "tokens_per_step": tokens_per_step,
                **routing_counters(cfg, routing)}
    print(json.dumps({"window": {
        "steps_finished": done, "steps_attempted": attempted,
        "seconds_to_last_step_end": window_s, "tokens_per_step": tokens_per_step,
        "first_loss": prog["losses"][0], "window_first_loss": losses[:1],
        "window_last_loss": losses[-1:],
        **{k: v for k, v in counters.items() if k not in (
            "routing_steps", "steps", "tokens_per_step")}}}), flush=True)

    # ---- free the program's state, then the plain reference ---------------
    del state, step, batch, metrics
    t_ref = time.perf_counter()
    want = reference_steps(ctx, seen)
    ref_s = time.perf_counter() - t_ref
    lim = job["check"]["limits"]
    for name, value, note, limit in gap_rows(prog, want):
        if limit in lim:    # a limit the cell does not set is not compared
            checks.add(name, value, lim[limit], "max", note)
    if losses and "loss_rise_max" in lim:
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
        "counters": counters,
    }


def control(ctx, mode: str) -> dict:
    """``controls.py`` only, after ``run``: the reference in a lower precision
    put in the program's place, over the batches the run checked."""
    checked = ctx["checked"]
    ctrl = reference_steps(ctx, checked["seen"], mode=mode)
    lim = ctx["job"]["check"]["limits"]
    return {name: value for name, value, _, limit in
            gap_rows(ctrl, checked["want"]) if limit in lim}


def reference_steps(ctx, seen, mode="highest") -> dict:
    """The family's plain reference over the batches the program saw, on the
    cell's one chip."""
    import jax
    import jax.numpy as jnp

    cfg, job, seed = ctx["config"], ctx["job"], ctx["seed"]
    if len(ctx["devices"]) != 1:
        raise ValueError("runners/train_family.py places its reference on "
                         "one chip; a cell across chips needs "
                         "runners/train.py's spread")
    fam = family_of(cfg)
    make = lambda key: fam.weights.model_weights(cfg, key, jnp.float32)
    batches = [jax.device_put(b, ctx["devices"][0]) for b in seen]
    return fam.reference.train_steps(
        cfg, job["optimizer"], make, fam.weights.seed_key(seed), batches,
        job["check"]["reference_rows_per_block"], mode,
        moments_on_host=job["check"].get("reference_moments_on_host", False))
