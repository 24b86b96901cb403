#!/usr/bin/env python3
"""Walk the lever ladder (README.md here) on YOUR model, measuring each step,
and report the winning flag set.

The reference tunes these knobs by hand, chapter by chapter (batch in its
``02``, activation checkpointing + offload in ``04``/``05``); this walks
them automatically: every probe in
a kill-able subprocess (an OOM or a pool stall costs one probe, never the
walk), keep a lever only if measured time-per-token improves, re-walk batch
last because every earlier lever moves the HBM knee.

    python related-topics/performance-tuning/autotune.py -m llama-650m -s 2048
    python related-topics/performance-tuning/autotune.py -m hf:/ckpts/my-model --dry-run

Output: one JSON line per probe, then a final ``best`` line whose ``flags``
paste directly onto any chapter's ``train_llm.py`` command.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUNNER = os.path.join(REPO, "01-single-chip", "train_llm.py")

# the ladder in the README's order; each entry: (name, extra flags)
REMAT_LADDER = ["all", "attn", "attn_mlp"]


def parse_step_ms(out: str) -> float | None:
    """Median of the post-compile log windows (the loop logs
    `'time/total': <ms>` per window; the FIRST window carries compile +
    warmup and is dropped — the median over the rest is what is robust to
    a single slow window on a jittery pool)."""
    hits = [float(h) for h in re.findall(r"'time/total': ([0-9.]+)", out)]
    windows = hits[1:] if len(hits) > 1 else hits
    if not windows:
        return None
    return float(statistics.median(windows))


def parse_mfu(out: str) -> float | None:
    hits = re.findall(r"'mfu': ([0-9.eE+-]+)", out)
    return float(hits[-1]) if hits else None


def classify_failure(err: str) -> str:
    """By XLA's canonical markers: device HBM exhaustion is
    retire-the-config, pool-capacity rejection is retryable."""
    if ("Out of memory" in err or "Largest program allocations" in err
            or "Error allocating device buffer" in err):
        return "oom"
    if "RESOURCE_EXHAUSTED" in err:
        return "pool_exhausted"
    return "failed"


def probe_cmd(args, batch: int, flags: list[str], save_dir: str) -> list[str]:
    tokens = batch * args.seq * (args.steps + 2)
    # log-freq 4 everywhere: the loop drains banked losses at every log
    # boundary, so a smaller log window would silently cap --fence-every 4
    # at depth 2 — the probe must RUN at the depth it recommends
    return [sys.executable, RUNNER, "-m", args.model,
            "-d", f"synthetic:{max(tokens * 2, 20000)}",
            "-s", str(args.seq), "-b", str(batch),
            "--num-epochs", "1", "--max-steps", str(args.steps),
            "--log-freq", "4", "--save-dir", save_dir, *flags]


def run_probe(args, batch: int, flags: list[str]) -> dict:
    """One config in a kill-able subprocess -> {ms, mfu} | {error}."""
    with tempfile.TemporaryDirectory() as d:
        t0 = time.time()
        try:
            proc = subprocess.run(
                probe_cmd(args, batch, flags, d), capture_output=True,
                text=True, timeout=args.budget)
        except subprocess.TimeoutExpired:
            return {"error": "stalled"}
        out = proc.stdout + proc.stderr
        if proc.returncode != 0:
            return {"error": classify_failure(out)}
        ms = parse_step_ms(out)
        if ms is None:
            return {"error": "no_result"}
        return {"ms": ms, "mfu": parse_mfu(out),
                "wall_s": round(time.time() - t0, 1)}


def plan_walk(args) -> list[dict]:
    """The probe sequence, data only (what --dry-run prints). Each entry:
    {name, batch, flags}. The walk evaluates them statefully — a lever is
    kept only if it improved — so later entries here show the flags they
    would add, not the final composition."""
    steps = [{"name": "baseline", "batch": args.batch, "flags": []}]
    steps.append({"name": "fence4", "batch": args.batch,
                  "flags": ["--fence-every", "4"]})
    for policy in REMAT_LADDER:
        steps.append({"name": f"remat_{policy}", "batch": args.batch,
                      "flags": ["--checkpoint-activations",
                                "--remat-policy", policy]})
    steps.append({"name": "adafactor", "batch": args.batch,
                  "flags": ["--optimizer", "adafactor"]})
    # re-walk the remat ladder AFTER adafactor: fence4 + adafactor +
    # attn_mlp is only reachable this way — attn_mlp's bigger saved set
    # needs the HBM adafactor frees, so its first probe (AdamW still
    # active) can OOM and must get a second chance.
    # The walk skips any retry whose composed config it already measured.
    for policy in REMAT_LADDER[1:]:
        steps.append({"name": f"remat_{policy}_after_adafactor",
                      "batch": args.batch,
                      "flags": ["--checkpoint-activations",
                                "--remat-policy", policy]})
    steps.append({"name": "loss_chunks8", "batch": args.batch,
                  "flags": ["--loss-chunks", "8"]})
    b = args.batch
    while b < args.batch * 4:
        b *= 2
        steps.append({"name": f"batch_{b}", "batch": b, "flags": []})
    return steps


def compose_flags(kept: list[str], step_name: str,
                  step_flags: list[str]) -> list[str]:
    """Compose a probe's flag set from the kept levers plus the step's.

    Remat rungs REPLACE the kept policy, not stack with it: strip the kept
    3-token segment (``--checkpoint-activations --remat-policy <p>``)
    wherever it sits and keep everything around it — truncating at the
    segment would silently drop levers kept after it (e.g. adafactor,
    turning the post-adafactor attn_mlp retry into a mislabeled re-probe
    of the config that already OOMed)."""
    if step_name.startswith("remat_") and "--remat-policy" in kept:
        i = kept.index("--checkpoint-activations")
        return kept[:i] + kept[i + 3:] + step_flags
    return kept + step_flags


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-m", "--model", required=True)
    p.add_argument("-s", "--seq", type=int, default=2048)
    p.add_argument("-b", "--batch", type=int, default=8)
    p.add_argument("--steps", type=int, default=12,
                   help="training steps per probe; the LAST 4-step log "
                        "window is what gets measured (post-compile, "
                        "post-warmup), so keep this a multiple of 4 >= 12")
    p.add_argument("--budget", type=int, default=600,
                   help="seconds per probe before it is killed (compile "
                        "included)")
    p.add_argument("--dry-run", action="store_true")
    args = p.parse_args()

    plan = plan_walk(args)
    if args.dry_run:
        for s in plan:
            print(json.dumps(s))
        return

    def emit(rec):
        print(json.dumps(rec), flush=True)

    best = None        # (time-per-token, record)
    kept_flags: list[str] = []
    kept_batch = args.batch

    def tpt(ms, batch):
        return ms / (batch * args.seq)

    probed = set()
    for step in plan:
        name, batch = step["name"], max(step["batch"], kept_batch)
        if step["name"].startswith("batch_"):
            batch = step["batch"]
        flags = compose_flags(kept_flags, name, step["flags"])
        key = (tuple(flags), batch)
        if key in probed:   # e.g. a post-adafactor remat retry that already won
            emit({"probe": name, "status": "skipped_already_measured"})
            continue
        probed.add(key)
        res = run_probe(args, batch, flags)
        if res.get("error") in ("pool_exhausted", "stalled"):
            # transient pool conditions, not properties of the config
            # (classify_failure's distinction): one retry after a pause
            emit({"probe": name, "batch": batch, "flags": flags, **res,
                  "retrying": True})
            time.sleep(30)
            res = run_probe(args, batch, flags)
        rec = {"probe": name, "batch": batch, "flags": flags, **res}
        emit(rec)
        if "error" in res:
            continue
        score = tpt(res["ms"], batch)
        if best is None or score < best[0]:
            best = (score, rec)
            kept_flags, kept_batch = flags, batch
    if best is None:
        emit({"best": None, "error": "no probe produced a result"})
        sys.exit(2)
    emit({"best": best[1]["probe"], "batch": best[1]["batch"],
          "flags": " ".join(best[1]["flags"]),
          "step_ms": best[1]["ms"], "mfu": best[1].get("mfu")})


if __name__ == "__main__":
    main()
