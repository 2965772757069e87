"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload overlap3d-32k.replay \\
        --seed 1234 --seconds 10 --trace 0

The cell, its configuration, its traffic and its metrics come from
``BENCHMARK.json`` and the files it names (see ``harness.py``).  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics with ``--trace 0``, the
per-layer ones with ``--trace 1``), ``device`` and, traced, ``breakdown``;
its last key, ``checks``, gives each number compared with its limit, and
the same lines end standard error.  The run refuses to start unless JAX's
first device is a TPU and JAX sees as many as the cell asks for.  JAX's
persistent compilation cache lives in ``.jax_cache`` at the checkout's
root, so only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import harness, peaks

    cell = harness.load_cell(args.workload)
    devices = harness.chip_devices(f"run.py {args.workload}", cell.chips)
    if devices is None:
        return 2
    table = peaks.peaks_for(devices[0].device_kind)

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START, peaks=table)
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    print(f"correct={result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
