"""The control behind ``correct``: what the comparison reads for sound runs of
the program and for a lower precision put in its place, over several seeds in
one process. Never part of a benchmark run; PERF.md records what it printed
on the chip, and the limits in ``workloads/<cell>.json`` were set from it.

    python3 benchmarks/controls.py --workload <cell> --seeds 1,2,3 \\
        [--seconds 2] [--control int8] [--override job.precision=bf16-master]

``--control`` has the cell's runner compute the plain reference again in that
precision (``int8`` or ``bf16``) in the program's place, on what the run just
checked. ``--override`` edits the cell's data as loaded (a dotted path into
config, traffic or job), which switches on a lower-precision path of the
program's own: the run's compared numbers are then the control's.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def edited(load_cell, overrides: dict):
    """``harness.load_cell`` with ``overrides`` written over what it read."""
    def load(*args, **kwargs):
        cell = load_cell(*args, **kwargs)
        for path, value in overrides.items():
            *parents, leaf = path.split(".")
            node = cell
            for key in parents:
                node = node[key]
            node[leaf] = value
        return cell
    return load


def main(argv) -> int:
    from benchmarks import harness

    parser = argparse.ArgumentParser(prog="benchmarks/controls.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=2.0)
    parser.add_argument("--control", default=None)
    parser.add_argument("--override", action="append", default=[])
    args = parser.parse_args(argv)
    overrides = dict(kv.split("=", 1) for kv in args.override)
    if overrides:
        harness.load_cell = edited(harness.load_cell, overrides)
    sound, control = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        result = harness.run_cell(
            root=ROOT, workload=args.workload, seed=seed, seconds=args.seconds,
            trace=False, t_process_start=time.monotonic())
        for row in result["compared"]:
            sound.setdefault(row["check"], []).append(row["value"])
        if args.control:
            ctx = result["ctx"]
            runner = harness.load_module("runners", ctx["job"]["runner"])
            rows = runner.control(ctx, args.control)
            print(json.dumps({"control": {"mode": args.control, **rows}}),
                  flush=True)
            for name, value in rows.items():
                control.setdefault(name, []).append(value)
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          "metrics": result["metrics"]}), flush=True)
        del result
    summary = {name: {"program_max": max(vals), "program": vals,
                      "control_min": min(control[name]) if name in control else None,
                      "control": control.get(name)}
               for name, vals in sound.items()}
    print(json.dumps({"controls_summary": {
        "workload": args.workload, "control": args.control,
        "override": overrides or None, "checks": summary}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
