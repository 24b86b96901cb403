"""Runner for serving cells of every family: drives ``serve/engine.py``'s
``ServeEngine`` (submit / step) under the traffic mix's loop, from one thread.
The family's adapter is found by name, ``runners/_<cfg["family"]>.py``: it
gives ``bundle_for`` and ``program_params`` and names the family's ``weights``
module and plain ``reference``, so a new family is new files alone.

Set-up warms every program the traffic uses with two requests of its own,
then starts the closed loop and lets it run ``ramp_steps`` engine steps (every
client's first prompt is prefilled by then), so the window opens on the state
the mix holds. All clocks are the benchmark's own. After the window the
engine is freed and the plain reference runs once, teacher-forced, over a
seeded sample of the requests the window served tokens to, finished or still
in flight at its close (the longest among them).
"""
from __future__ import annotations

import gc
import json
import random
import time

import numpy as np

from benchmarks import harness
from benchmarks.traffic import generate

KV_BYTES = {"bf16": 2, "fp32": 4, "int8": 1}


class Client:
    def __init__(self):
        self.rid = None


def family_of(cfg: dict):
    """``runners/_<family>.py``, by the configuration's ``family``."""
    return harness.load_module("runners", f"_{cfg['family']}")


def run(ctx) -> dict:
    import jax

    from distributed_training_guide_tpu.serve import (RefusalError, Request,
                                                      ServeEngine)

    cfg, traffic, job, seed = (ctx["config"], ctx["traffic"], ctx["job"],
                               ctx["seed"])
    if traffic["loop"] != "closed":
        raise ValueError("this runner drives closed loops; an open loop "
                         "(traffic.loop = 'open') is a later PR's runner")
    spans, checks = ctx["spans"], ctx["checks"]
    ctx["phase"]("imports done, device checked")
    eng = job["engine"]
    family = family_of(cfg)
    # the seed is an operand: a new seed compiles nothing
    params = jax.jit(lambda key: family.program_params(cfg, key))(
        family.weights.seed_key(seed))
    engine = ServeEngine(family.bundle_for(cfg, ctx["cell"]["config"]),
                         params, **eng)
    del params
    ctx["phase"]("weights made, engine built")
    n_slots = eng["n_slots"]
    stream = generate.RequestStream(traffic, cfg["vocab_size"], seed)
    clients = [Client() for _ in range(traffic["clients"])]
    tap = generate.TokenTap()
    live: dict[int, dict] = {}      # rid -> record of a request in flight
    done: list[dict] = []           # completed, in order
    refused = 0
    occupancy: list[float] = []
    decode_context: list[tuple] = []
    routing_steps: list[tuple] = []   # (stamp, pairs held, experts touched)
    # a routing family's counters, which its decode program fills with its
    # tokens; every other family's stay at zero
    routing = getattr(engine.programs, "routing", None) or {"steps": 0}
    tracing = False

    def submit_idle(now):
        nonlocal refused
        for c in clients:
            if c.rid is not None:
                continue
            prompt, n_out = next(stream)
            try:
                rid = engine.submit(Request(
                    prompt_ids=prompt, max_new_tokens=n_out, temperature=0.0,
                    eos_id=None, seed=stream.issued))
            except RefusalError as exc:
                refused += 1
                done.append({"refused": str(exc), "t_done": now, "ok": False})
                continue
            c.rid = rid
            live[rid] = {"rid": rid, "prompt": prompt, "n_out": n_out,
                         "t_submit": now, "client": c}

    def iterate(sample_stats: bool):
        now = time.perf_counter()
        submit_idle(now)
        calls = engine.programs.prefill_calls
        routed = dict(routing)
        with spans.span("engine.step"):
            finished = engine.step()
        t0, t1 = spans.items["engine.step"][-1]
        # the step again under the kind of program it ran: a step that ran
        # a prefill chunk (and its decode step after it), or decode alone
        kind = ("engine.step.prefill"
                if engine.programs.prefill_calls > calls else "engine.step.decode")
        spans.items.setdefault(kind, []).append((t0, t1))
        with spans.span("client"):
            for rid, toks in engine.partial_tokens().items():
                tap.stamp(rid, len(toks), t1)
            for res in finished:
                tap.stamp(res.request_id, len(res.generated_ids), t1)
                rec = live.pop(res.request_id)
                rec["client"].rid = None
                tokens = list(res.generated_ids)
                rec.update(tokens=tokens, reason=res.finish_reason, t_done=t1,
                           ok=(res.finish_reason == "length"
                               and len(tokens) == rec["n_out"]))
                del rec["client"]
                done.append(rec)
            if sample_stats and len(decode_context) % 8 == 0:
                # every eighth step, from the scheduler itself: stats() also
                # exports the prefix cache's keys (half a second at 2,048
                # cached pages), which a traced run reads as device idle time
                occupancy.append(len(engine.scheduler.active_indices())
                                 / n_slots)
            # live context of the slots this step decoded for: the benchmark
            # knows it from what it sent and what came back
            ctx_tokens = n_dec = 0
            for rec in live.values():
                n_gen = len(tap.times.get(rec["rid"], ()))
                if n_gen:
                    ctx_tokens += len(rec["prompt"]) + n_gen
                    n_dec += 1
            decode_context.append((t1, ctx_tokens, n_dec))
            if routing["steps"] > routed["steps"]:
                routing_steps.append((
                    t1, routing["pairs_held"] - routed["pairs_held"],
                    routing["experts_touched"] - routed["experts_touched"]))
        return t1

    # ---- warm-up and ramp: part of set-up -----------------------------------
    # Two requests alone first: the second shares a page and a half with the
    # first, so that the copy-on-write fork (which two unrelated prompts need
    # as soon as their first tokens agree; a match may end inside a page only
    # if that page is a full, committed page of the earlier prompt) is
    # compiled with the chunk and decode programs before the clients start.
    # Four tokens each: the first decode step takes arrays built on the host
    # (its slot has just grown a page), the next ones take the step's own
    # device outputs back, which is a second signature of the decode program.
    page = eng["page_size"]
    rng = np.random.default_rng([int(seed), 0x7761726D])
    warm = rng.integers(0, cfg["vocab_size"], size=3 * page).tolist()
    for prompt in (warm, warm[: page + page // 2] + warm[::-1][: page]):
        engine.submit(Request(prompt_ids=prompt, max_new_tokens=4,
                              temperature=0.0, eos_id=None))
        while engine.has_work:
            engine.step()
    ctx["phase"]("programs warmed")
    # then the closed loop itself, for a fixed number of engine steps: it
    # takes the clients out of lock-step, so the window opens on a steady state
    for _ in range(job["ramp_steps"]):
        iterate(False)
    n_ramp = len(done)
    ctx["phase"]("ramp done")

    # ---- the window --------------------------------------------------------
    stats0 = engine.stats()
    routing0 = dict(routing)
    compiles_before = ctx["compiles"].snapshot()
    trace_s = min(ctx["seconds"], job.get("trace_seconds", 8.0))
    trace_window = None
    if ctx["trace_dir"] is not None:
        jax.profiler.start_trace(str(ctx["trace_dir"]))
        tracing = True
    t0 = time.perf_counter()
    setup_s = time.monotonic() - ctx["t_process_start"]
    deadline = t0 + ctx["seconds"]
    sample = ctx["trace_dir"] is not None
    while True:
        now = iterate(sample)
        if tracing and now - t0 >= trace_s:
            jax.profiler.stop_trace()
            tracing, trace_window = False, (t0, now)
        if now >= deadline:
            break
    t1 = now
    if tracing:
        jax.profiler.stop_trace()
        trace_window = (t0, t1)
    stats1 = engine.stats()
    routing1 = dict(routing)
    compiles_after = ctx["compiles"].snapshot()
    peak = harness.memory_peak_bytes(ctx["devices"])

    in_window = [r for r in done[n_ramp:] if r["t_done"] <= t1]
    completed = [r for r in in_window if "tokens" in r]
    failed = sum(1 for r in in_window if not r["ok"])
    # requests still in flight at the close, with the tokens served so far
    partial = engine.partial_tokens()
    in_flight = [dict(rec, tokens=list(partial[rid]))
                 for rid, rec in live.items() if partial.get(rid)]
    served = completed + in_flight
    # the rate is over ALL the work of the window: every output token the tap
    # stamped inside it, of requests finished or still in flight
    out_tokens = sum(1 for times in tap.times.values()
                     for t in times if t0 < t <= t1)
    completed_tokens = sum(len(r["tokens"]) for r in completed)
    ttfts, censored = [], 0
    for r in completed + list(live.values()):
        if r["t_submit"] < t0:
            continue
        stamps = tap.times.get(r["rid"], [])
        if stamps and stamps[0] <= t1:
            ttfts.append(1e3 * (stamps[0] - r["t_submit"]))
        else:
            censored += 1
    gaps = [1e3 * g for g in tap.gaps(t0, t1)]
    in_steps = [row for row in decode_context if t0 < row[0] <= t1]
    ends = [r["t_done"] for r in completed]
    e2e = {"setup_s": setup_s,
           "serve.out_tokens_per_s": out_tokens / (t1 - t0)}
    if gaps:
        e2e["serve.itl_p95_ms"] = generate.percentile(gaps, 0.95)
    print(json.dumps({"window": {
        "seconds": t1 - t0, "completed": len(completed), "refused": refused,
        "failed": failed, "in_flight_at_close": len(live),
        "served_tokens_to": len(served),
        "out_tokens": out_tokens, "out_tokens_of_completed": completed_tokens,
        "ttft_samples": len(ttfts),
        "ttft_without_first_token_at_close": censored,
        "ttft_p50_ms": generate.percentile(ttfts, 0.5) if ttfts else None,
        "itl_samples": len(gaps),
        "itl_p50_ms": generate.percentile(gaps, 0.5) if gaps else None,
        "mean_live_context": (sum(c for t, c, n in in_steps)
                              / max(1, sum(n for t, c, n in in_steps))),
        "ramp_requests": n_ramp,
        "most_completions_in_one_step": max(map(ends.count, ends), default=0),
        "prefill_step_share_pct": 100.0 * sum(spans.durations_ms(
            "engine.step.prefill", t0, t1)) / 1e3 / (t1 - t0),
        "preemptions": stats1["preemptions"] - stats0["preemptions"],
        "prefill_calls": stats1["prefill_calls"] - stats0["prefill_calls"],
        "prefix_hits": stats1.get("prefix_hits", 0) - stats0.get("prefix_hits", 0),
        "admitted": stats1.get("admitted", 0) - stats0.get("admitted", 0),
    }}), flush=True)

    # mean live context, for the paged-attend roofline
    counters = {
        "preemptions": stats1["preemptions"] - stats0["preemptions"],
        "prefill_calls": stats1["prefill_calls"] - stats0["prefill_calls"],
        "n_slots": n_slots,
        "kv_bytes": KV_BYTES[engine.kv_dtype],
        "decode_context": decode_context,
    }
    if ttfts:   # only where requests are submitted inside the window
        counters["ttft_p50_ms"] = generate.percentile(ttfts, 0.5)
        counters["ttft_samples"] = len(ttfts)
    if occupancy:
        counters["batch_occupancy_pct"] = 100.0 * sum(occupancy) / len(occupancy)
    steps = routing1["steps"] - routing0["steps"]
    if steps:
        counters["routing_steps"] = routing_steps
        routed_pairs = routing1["pairs_routed"] - routing0["pairs_routed"]
        held_pairs = routing1["pairs_held"] - routing0["pairs_held"]
        touched = routing1["experts_touched"] - routing0["experts_touched"]
        counters["expert_pairs_held_pct"] = 100.0 * held_pairs / routed_pairs
        counters["experts_touched_pct"] = 100.0 * touched / (
            steps * cfg["num_hidden_layers"] * cfg["n_routed_experts"])
        print(json.dumps({"routing": {
            "decode_steps": steps, "pairs_routed_a_step": routed_pairs / steps,
            "pairs_held_a_step": held_pairs / steps,
            "experts_touched_a_step_a_layer":
                touched / steps / cfg["num_hidden_layers"],
            "fullest_expert_pairs": routing1["fullest_expert_pairs"]}}),
            flush=True)

    # ---- free the engine, then the plain reference -------------------------
    del engine, stats0, stats1, partial
    live.clear()
    gc.collect()
    t_ref = time.perf_counter()
    sample = pick_sample(served, seed, job["check"]["sample_requests"])
    compare(ctx, sample, checks)
    print(json.dumps({"reference_seconds": time.perf_counter() - t_ref}),
          flush=True)
    checks.add("output_tokens_in_window", out_tokens, 1, "min")

    # attempted: every request the window finished, refused or was still
    # serving at its close; failed: those refused or not completed in full
    return {
        "end_to_end": e2e, "attempted": len(in_window) + len(in_flight),
        "failed": failed,
        "compiles_in_window": [b - a for a, b in
                               zip(compiles_before, compiles_after)],
        "memory_peak_bytes": peak, "window": (t0, t1),
        "trace_window": trace_window, "counters": counters,
        "checked": {"sample": sample},
    }


def pick_sample(served: list, seed: int, n: int) -> list:
    """A sample drawn from the seed, with the longest request in it."""
    if not served:
        return []
    longest = max(served, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in served if r is not longest]
    random.Random(f"{int(seed)}:sample").shuffle(rest)
    return [longest] + rest[: max(0, n - 1)]


def reference_weights(ctx):
    """The reference's own weights from the seed: the top leaves, and a
    function that makes layer ``l``'s (the float32 model is never held whole)."""
    import jax

    cfg = ctx["config"]
    weights = family_of(cfg).weights
    key = weights.seed_key(ctx["seed"])
    top = jax.jit(lambda key: weights.top_weights(cfg, key))(key)
    layer = jax.jit(lambda key, l: weights.layer_weights(cfg, key, l))
    return top, lambda l: layer(key, np.uint32(l))


def sample_gaps(ctx, sample, control_mode=None) -> dict:
    """Over the sample's served tokens, the gap by which each one's reference
    logit lies below the reference's best (with ``control_mode``: the gap of
    the token that a pass in that lower precision puts first at the same
    position): the widest, where it is, and the mean over all of them. The
    widest is the number that catches one bad token; the mean is the steady
    one, which the lower precision moves most."""
    cfg = ctx["config"]
    ref = family_of(cfg).reference
    top, layer_fn = reference_weights(ctx)
    widest, total, n_tokens, where = 0.0, 0.0, 0, ""
    for r in sample:
        tokens = np.asarray(r["prompt"] + r["tokens"], np.int32)
        gaps = ref.served_token_gaps(cfg, layer_fn, top, tokens,
                                     len(r["prompt"]),
                                     control_mode=control_mode)
        n_tokens += len(gaps)
        total += float(gaps.sum())
        if len(gaps) and float(gaps.max()) > widest:
            widest = float(gaps.max())
            where = f"request {r['rid']} token {int(gaps.argmax())}"
    return {"widest": widest, "mean": total / n_tokens if n_tokens else
            float("inf"), "where": where, "served_tokens": n_tokens}


def compare(ctx, sample, checks):
    """The sample's served tokens against the reference, each number beside
    its limit."""
    limits = ctx["job"]["check"]["limits"]
    got = sample_gaps(ctx, sample)
    print(json.dumps({"compared_sample": {
        "requests": len(sample), "served_tokens": got["served_tokens"],
        "longest": max((len(r["prompt"]) + len(r["tokens"]) for r in sample),
                       default=0)}}), flush=True)
    checks.add("served_token_widest_logit_gap",
               got["widest"] if sample else float("inf"),
               limits["served_token_logit_gap"], "max", got["where"])
    checks.add("served_token_mean_logit_gap", got["mean"],
               limits["served_token_mean_logit_gap"], "max")


def control(ctx, mode: str) -> dict:
    """``controls.py`` only, after ``run``: at each position of the sample's
    prompts and served tokens, the gap of the token that a lower-precision
    pass of the reference puts first."""
    got = sample_gaps(ctx, ctx["checked"]["sample"], control_mode=mode)
    return {"served_token_widest_logit_gap": got["widest"],
            "served_token_mean_logit_gap": got["mean"]}
