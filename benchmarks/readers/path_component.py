"""Device time of the ``XLA Ops`` events whose ``tf_op`` path has a given
COMPONENT (or one of ``components``): a Pallas kernel's ``name=``
(``.../attend/paged_latent_attend/pallas_call:``, ``.../attn/flash_fwd/
pallas_call:``) or a ``jax.named_scope`` that ``scope_time``'s fixed list
does not know (``latent_proj``). The path is matched, not the instruction's
name (which takes a transform's wrapper where no scope encloses the call) and
not the call's target (every Pallas kernel is a ``tpu_custom_call``):
``_xplane.components``. With ``program`` only the events that ran under that
jitted program count (``serve_decode``: a prefill chunk runs the same
``paged_attend`` kernel at its own tile, under ``jit_serve_chunk_t512``).

``as: "ms_per_step"``: the events' self time, mean over devices, in ms per
execution of ``program``, or per ``step_span`` span of the window where no
program is named. ``as: "roofline"``: the least time the chip could take for
the work ``work`` names (``flops.py``, ``flops_mla_moe.py``: what the
algorithm REQUIRES, from the run's counters inside the traced window; a
forward pass that remat runs twice is required once) over that time, in
percent.

A program that carries no such component (the parent of the PR that added
it, another family's cell, a run with no device plane) gives ``None``.
"""
import json

from benchmarks import flops, flops_mla_moe, trace_reduce
from benchmarks.readers import _xplane, scope_time


def component_seconds(device_ops: dict, op_paths: dict, component,
                      lo: int, hi: int):
    """Mean over devices of the self time, in seconds, of the events inside
    [lo, hi] whose path has ``component`` (a name, or several: any of them);
    None where no event has it."""
    wanted = {component} if isinstance(component, str) else set(component)
    has = {name: not wanted.isdisjoint(_xplane.components(path))
           for name, path in op_paths.items()}
    total, found = 0.0, False
    for ops in device_ops.values():
        for name, self_ns, a, b in trace_reduce.self_times(ops):
            if a >= lo and b <= hi and self_ns and has.get(name):
                total += self_ns
                found = True
    return total / 1e9 / len(device_ops) if found else None


def _in_window(ctx, rows):
    t0, t1 = ctx["trace_window"]
    return [row for row in rows if t0 <= row[0] <= t1]


def _latent_attend(ctx):
    rows = _in_window(ctx, ctx["counters"].get("decode_context") or ())
    if not rows:
        return None
    return flops_mla_moe.latent_attend(
        ctx["config"], sum(c for _, c, _ in rows), sum(n for _, _, n in rows),
        ctx["counters"]["kv_bytes"])


def _expert_gmm(ctx):
    rows = _in_window(ctx, ctx["counters"].get("routing_steps") or ())
    if not rows:
        return None
    return flops_mla_moe.expert_gmm(
        ctx["config"], sum(touched for _, _, touched in rows),
        sum(held for _, held, _ in rows))


def _paged_attend(ctx):
    """The decode steps' attention over the live contexts the runner counted
    (``counters["decode_context"]``: one wall-clock stamped row a step)."""
    rows = _in_window(ctx, ctx["counters"].get("decode_context") or ())
    if not rows:
        return None
    return flops.paged_attend(
        ctx["config"], sum(c for _, c, _ in rows), sum(n for _, _, n in rows),
        ctx["counters"]["kv_bytes"])


def _flash_attention(ctx):
    """Forward once and backward once per layer and whole step in the window;
    each device takes its share of the batch's rows."""
    cfg, traffic, trace = ctx["config"], ctx["traffic"], ctx["trace"]
    steps = trace_reduce.spans_inside(trace, "step")
    if not steps:
        return None
    batch, seq = traffic["global_batch"], traffic["seq_len"]
    calls = steps * cfg["num_hidden_layers"] / len(trace["device_ops"])
    fwd, bwd = flops.flash_fwd(cfg, batch, seq), flops.flash_bwd(cfg, batch, seq)
    return {k: calls * (fwd[k] + bwd[k]) for k in ("flops", "bytes")}


WORK = {"latent_attend": _latent_attend, "expert_gmm": _expert_gmm,
        "paged_attend": _paged_attend, "flash_attention": _flash_attention}


def read(ctx, params):
    found = _xplane.traced(ctx)
    if found is None:
        return None
    trace, path = found
    lo, hi = trace["lo_ns"], trace["hi_ns"]
    component = params.get("components") or params["component"]
    if "program" in params:
        device_ops, steps = trace_reduce.program_ops(trace, params["program"])
    else:
        device_ops, steps = trace["device_ops"], None
    seconds = component_seconds(device_ops, scope_time.op_paths_of(path),
                                component, lo, hi)
    if not seconds:
        return None
    if params["as"] == "ms_per_step":
        if steps is None:
            steps = trace_reduce.spans_inside(trace, params["step_span"])
        return 1e3 * seconds / steps if steps else None
    if ctx.get("peak") is None:
        return None
    work = WORK[params["work"]](ctx)
    if work is None:
        return None
    least_s, bound = flops.least_time(work, ctx["peak"])
    print(json.dumps({"roofline": {
        "kernel": component, "bound": bound, "least_s": least_s,
        "kernel_s": seconds}}), flush=True)
    return 100.0 * least_s / seconds
