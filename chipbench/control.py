"""Readings that the limits of ``correct`` are set from, in one process.

    python3 chipbench/control.py --workload <cell> --seeds 1 2 3 --seconds 2 [--program]

For each seed it sets up the cell, runs a short window at the cell's own
sizes and load, and prints the numbers ``correct`` compares, one JSON line
per seed. By default the control runs in the program's place: the
reference computed in float8 (``check.py``). It has to come out not
correct. ``--program`` runs the program itself, for the lower readings.
The benchmark's own runs never run this.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from chipbench import harness  # noqa: E402


def readings(cell, seeds, seconds, control, root=harness.ROOT,
             require_tpu=True, peaks=None):
    """One dict of compared numbers per seed."""
    devices = harness.chips_or_raise(cell.chips, require_tpu)
    harness.configure_compile_cache()
    out = []
    for seed in seeds:
        session = harness.open_session(cell, seed, devices,
                                       peaks or harness.load_peaks(root),
                                       root, control=control)
        session.setup()
        rec = session.window(seconds, None)
        session.release()
        checks, wrong = session.check(cell.limits)
        out.append({"seed": seed, "control": control,
                    "attempted": rec.completed, "failed": rec.failed + wrong,
                    "readings": {c.name: c.value for c in checks},
                    "limits": {c.name: c.limit for c in checks}})
        del session
    return out


def main(argv):
    ap = argparse.ArgumentParser(prog="chipbench/control.py",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--program", action="store_true",
                    help="run the program, not the control")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    with harness.fresh_program_dirs():
        for row in readings(cell, args.seeds, args.seconds,
                            control=not args.program):
            print(json.dumps(row), flush=True)
    print(f"control.py: {time.perf_counter() - _T0:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
