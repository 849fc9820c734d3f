"""Chip benchmark entry point: one cell of ``BENCHMARK.json``, one process.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The run loads the cell's configuration
and traffic, warms every shape the traffic uses (set-up), measures for
``--seconds``, checks what the timed path produced against a plain
reference, and prints one JSON object as the last line of standard output.
It exits non-zero and prints no result where JAX finds no TPU, or fewer
chips than the cell asks for.
"""

import time

_T0 = time.perf_counter()  # set-up is timed from here, before JAX loads

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t0=_T0))
