"""``python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one cell, once, in the one process that holds its chips.
Fails, and prints no result, when JAX finds no TPU or fewer chips than the
cell asks for. The last line of standard output is the result."""
import time

T_PROCESS_START = time.monotonic()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

if __name__ == "__main__":
    from benchmarks import harness

    sys.exit(harness.main(sys.argv[1:], t_process_start=T_PROCESS_START,
                          root=ROOT))
