"""Record the small profiler trace kept in ``testdata/`` for the tests.

    python3 benchmarks/chip/record_trace.py OUT.xplane.pb

Runs the replay cell's traffic through the benchmark's own traced path at
n = 512 (4 x 4 blocks of 128) for a fraction of a second on the chip, copies
the trace to ``OUT.xplane.pb`` and prints its planes and lines with a few
events of each, to show what the device's operations are called.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]

SMALL = {"n_per_dim": 8, "n": 512, "leaf_n": 256, "bs": 128}


def main(argv=None) -> int:
    out = pathlib.Path((argv or sys.argv[1:])[0])
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import harness, xtrace

    if harness.chip_devices("record_trace.py") is None:
        return 2
    from jax.profiler import ProfileData
    cell = harness.load_cell("overlap3d-32k.replay")
    cell.config.update(SMALL)
    out.parent.mkdir(parents=True, exist_ok=True)
    r = harness.run_cell(cell, 1, 0.2, True, time.perf_counter(),
                         peaks={"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
                         keep_trace=out)
    print(json.dumps(r))
    pd = ProfileData.from_serialized_xspace(out.read_bytes())
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for e in events[:6]:
                print("    ", repr(e.name), e.start_ns, e.duration_ns,
                      {k: str(v)[:80] for k, v in e.stats})
    print("reduced:", xtrace.reduce_xplane(out))
    print("bytes:", out.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())
