"""Readings that a cell's limits are set from, on the chip.

    python3 benchmarks/chip/control.py --workload overlap3d-32k.replay \\
        --seconds 3 --seeds 11 12 13 ... --control-seeds 11 12 13

For each seed, one run of the cell through the benchmark's own path with a
short window, in this one process: its compared numbers are the program's
readings (the lower ones).  For the ``--control-seeds`` the same run also
puts the lower-precision control in the program's place (``control`` of
the cell's driver) and reads the same comparison (the upper readings),
with ``control_correct``: whether the control passes the cell's limits.
One JSON line per seed on standard output; the benchmark's runs never run
the control.  Like ``run.py`` it refuses to run off a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    if harness.chip_devices("control.py", cell.chips) is None:
        return 2
    for seed in args.seeds:
        r = harness.run_cell(cell, seed, args.seconds, False,
                             time.perf_counter(),
                             control=seed in args.control_seeds)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "checks": r["checks"],
                          "control": r.get("control"),
                          "control_correct": r.get("control_correct"),
                          "window": r["window"]}), flush=True)
        del r
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
