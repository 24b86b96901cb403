#!/usr/bin/env python3
"""Device events under one ``jax.named_scope`` in a cell's newest trace, per
chip: how many a step and how long (not collected by pytest; run it after a
``benchmarks/run.py --workload <cell> --trace 1`` in the same checkout).

    python tests/onchip/scope_events.py olmo2-7b-l8.train.fsdp4.seq4096 head_gather

``head_gather`` (utils/trace.py: SUBSCOPES) is the use it was written for:
where the FSDP loss head gathers the output matrix once a step
(``Trainer.head_gather``) a chip shows one ``all_gather`` and one
``reduce_scatter`` event a step under it; where the loss is left to GSPMD it
shows none, and the per-chunk gathers sit under ``loss_head`` alone.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from jax.profiler import ProfileData  # noqa: E402

from benchmarks.readers import _xplane  # noqa: E402


def main(cell: str, scope: str, program: str = "train_step") -> None:
    path = max(Path(".bench_out/trace", cell).rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime)
    op_paths = _xplane.metadata_stat(path)
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        paths = op_paths.get(plane.name, {})
        runs, found = 0, {}
        for line in plane.lines:
            if line.name == "XLA Modules":
                runs = sum(1 for e in line.events if program in e.name)
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                p = paths.get(e.name, "")
                if scope in _xplane.components(p):
                    key = (e.name.split(" = ")[0], p.split(f"jit({program})/")[-1])
                    n, ns = found.get(key, (0, 0))
                    found[key] = (n + 1, ns + e.duration_ns)
        print(f"{plane.name}: {runs} executions of {program}")
        for (name, p), (n, ns) in sorted(found.items()):
            print(f"   {name:36.36s} {n / max(runs, 1):6.2f} events/step "
                  f"{ns / 1e6 / max(runs, 1):8.3f} ms/step  {p[:100]}")


if __name__ == "__main__":
    main(*sys.argv[1:4])
