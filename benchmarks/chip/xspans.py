"""The device's idle time, charged to the program span open over it.

    python3 benchmarks/chip/xspans.py --workload overlap3d-32k.replay \\
        --seed 1234 --seconds 10 [--keep TRACE.xplane.pb]

A recording ``repro.obs`` tracer mirrors each program span as a
``jax.profiler.TraceAnnotation`` of its name, so a profiler trace holds the
program's spans on its host plane, on the device's clock.
:func:`idle_by_span` cuts the first device's idle intervals inside the
``bench.window`` annotation at the boundaries of those spans and charges
each piece to the innermost program span open over it (``""`` where none
is: harness glue and the per-op garbage collection).  One long gap that
covers packing, upload, download and unpacking is split among them, not
given whole to the span open at its midpoint.

Run as a script, it makes one traced run of a cell through the
benchmark's own harness, keeps the trace, and prints the result line and
then one line ``{"idle_by_span_ms": {span: ms per op}}``, largest first.
It refuses to start off a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import bisect  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]

#: name prefixes of the program's spans (``repro.obs``), as against the
#: benchmark's own ``bench.*`` annotations and JAX's host events
PROGRAM_PREFIXES = ("plan.", "qt.", "engine.", "kernel.", "serve.",
                    "session.")


def _read(path):
    """``(window, program spans, first device's op intervals)``, in ns."""
    from jax.profiler import ProfileData

    from benchmarks.chip import xtrace

    path = pathlib.Path(path)
    if path.is_dir():
        path = xtrace.xplane_file(path)
    pd = ProfileData.from_serialized_xspace(path.read_bytes())
    windows, spans, ops = [], [], {}
    for plane in pd.planes:
        m = xtrace.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == xtrace.OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not m and plane.name.startswith("/host:"):
                for e in line.events:
                    iv = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name == xtrace.WINDOW:
                        windows.append(iv)
                    elif e.name.startswith(PROGRAM_PREFIXES):
                        spans.append(iv)
    if len(windows) != 1:
        raise ValueError(f"expected one {xtrace.WINDOW!r} annotation in "
                         f"the trace, found {len(windows)}")
    w0, w1, _ = windows[0]
    used = [d for d in sorted(ops)
            if any(b > w0 and a < w1 for a, b in ops[d])]
    device = ops[used[0]] if used else []
    return (w0, w1), spans, device


def idle_by_span(path) -> dict:
    """``{innermost program span name or "": idle seconds}`` of the first
    device with operations in the window (the whole window where none
    ran); the values sum to the window less the device's busy time."""
    from benchmarks.chip import xtrace

    (w0, w1), spans, device = _read(path)
    busy = xtrace._union((max(a, w0), min(b, w1)) for a, b in device
                         if b > w0 and a < w1)
    edges = [w0] + [t for ab in busy for t in ab] + [w1]
    cuts = sorted({t for a, b, _ in spans for t in (a, b) if w0 < t < w1})
    # innermost = latest start, then earliest end, among the open spans
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    starts = [s[0] for s in spans]
    out: dict = {}
    for a, b in zip(edges[::2], edges[1::2]):
        lo, hi = bisect.bisect_right(cuts, a), bisect.bisect_left(cuts, b)
        pts = [a] + cuts[lo:hi] + [b]
        for x, y in zip(pts, pts[1:]):
            if y <= x:
                continue
            mid = (x + y) / 2
            label = ""
            for s in reversed(spans[:bisect.bisect_right(starts, mid)]):
                if s[1] > mid:
                    label = s[2]
                    break
            out[label] = out.get(label, 0.0) + (y - x) * 1e-9
    return out


def main(argv=None) -> int:
    import argparse
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", help="copy the trace to this path")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import harness, peaks

    cell = harness.load_cell(args.workload)
    devices = harness.chip_devices(f"xspans.py {args.workload}", cell.chips)
    if devices is None:
        return 2
    keep = args.keep or str(pathlib.Path(tempfile.mkdtemp()) / "t.xplane.pb")
    result = harness.run_cell(cell, args.seed, args.seconds, True, T_START,
                              peaks=peaks.peaks_for(devices[0].device_kind),
                              keep_trace=keep)
    print(json.dumps(result), flush=True)
    ops = result["window"]["ops"]
    idle = sorted(idle_by_span(keep).items(), key=lambda kv: -kv[1])
    print(json.dumps({"idle_by_span_ms": {k: 1e3 * v / ops
                                          for k, v in idle}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
