"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

The benchmark records the measured window under ``jax.profiler`` and wraps
its own calls into the program in ``TraceAnnotation``s (``bench.*``), so
that the host's activity and the device's operations share the profiler's
clock.  On a TPU v5e each chip is a plane ``/device:TPU:<i>`` whose line
``XLA Modules`` holds one event per executed program (``jit_<name>(<id>)``)
and whose line ``XLA Ops`` holds one event per HLO instruction, named by
its HLO text (``%bsmm_pairs.1 = f32[...] custom-call(...),
custom_call_target="tpu_custom_call", ...``).  Control flow nests: a
``while`` event spans the ops of its body.  From the trace:

* the window is the ``bench.window`` annotation on a host thread;
* busy time is the union of the ``XLA Ops`` intervals inside the window,
  per device, averaged over the devices that ran anything in it;
* device time by operation is each op's self time (its interval less the
  ops nested in it), keyed ``<module>:<instruction>``;
* a kernel's time is the self time of the Pallas (Mosaic) calls,
  ``custom_call_target="tpu_custom_call"``, inside the modules whose name
  holds the kernel's name;
* the idle gaps of the first device are labelled with the innermost
  ``bench.*`` annotation open on the host at the gap's midpoint.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MOSAIC = 'custom_call_target="tpu_custom_call"'
WINDOW = "bench.window"
LABEL_PREFIX = "bench."


@dataclasses.dataclass
class DeviceTrace:
    window_s: float
    busy_s: float                   # averaged over the devices used
    devices: int                    # devices with operations in the window
    op_s: dict                      # "<module>:<instruction>" -> self s
    mosaic_s: dict                  # module -> self s of its Mosaic calls
    gaps: list                      # [(label, seconds)], longest first

    def kernel_s(self, name: str):
        """Device seconds of the Pallas calls in the modules whose name
        holds ``name``; None where no such call ran."""
        hits = [s for mod, s in self.mosaic_s.items() if name in mod]
        return sum(hits) if hits else None

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def xplane_file(log_dir) -> pathlib.Path:
    """The one ``.xplane.pb`` under a profiler log directory."""
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(found)}")
    return found[0]


def _union(intervals) -> list:
    """Merged, sorted ``[[t0, t1]]``."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(events: list) -> list:
    """``[(t0, t1, name, self_ns)]``: each event less its nested events."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    self_ns = [e[1] - e[0] for e in events]
    stack: list = []
    for i, (a, b, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= b - a
        stack.append(i)
    return [(a, b, name, s) for (a, b, name), s in zip(events, self_ns)]


def _module_of(modules: list, t: float) -> str:
    """Name of the module event (sorted ``(t0, t1, name)``) covering t."""
    lo, hi = 0, len(modules)
    while lo < hi:                          # last module starting <= t
        mid = (lo + hi) // 2
        if modules[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and modules[lo - 1][1] >= t:
        return modules[lo - 1][2]
    return "?"


def reduce_xplane(path) -> DeviceTrace:
    """Reduce the trace at ``path`` (a file or a profiler log directory)."""
    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.is_dir():
        path = xplane_file(path)
    pd = ProfileData.from_serialized_xspace(path.read_bytes())

    labels: list = []           # (t0, t1, name) of bench.* annotations
    ops: dict = {}              # device -> [(t0, t1, hlo text)]
    modules: dict = {}          # device -> [(t0, t1, module name)]
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dst = ops if line.name == OPS_LINE else modules
                dst.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events)
            elif not m and plane.name.startswith("/host:"):
                labels.extend((e.start_ns, e.start_ns + e.duration_ns,
                               e.name) for e in line.events
                              if e.name.startswith(LABEL_PREFIX))
    windows = [(a, b) for a, b, name in labels if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} annotation in the "
                         f"trace, found {len(windows)}")
    w0, w1 = windows[0]

    op_s: dict = {}
    mosaic_s: dict = {}
    busy: dict = {}
    for dev, events in sorted(ops.items()):
        inside = [e for e in events if e[1] > w0 and e[0] < w1]
        if not inside:
            continue
        mods = sorted((a, b, name.split("(")[0])
                      for a, b, name in modules.get(dev, []))
        for a, b, text, self_ns in _self_times(inside):
            clip = max(0.0, min(b, w1) - max(a, w0)) / max(b - a, 1e-9)
            sec = self_ns * clip * 1e-9
            mod = _module_of(mods, a)
            key = f"{mod}:{text.split(' = ')[0].lstrip('%')}"
            op_s[key] = op_s.get(key, 0.0) + sec
            if MOSAIC in text:
                mosaic_s[mod] = mosaic_s.get(mod, 0.0) + sec
        busy[dev] = _union((max(a, w0), min(b, w1)) for a, b, _ in inside)
    if not busy:
        return DeviceTrace((w1 - w0) * 1e-9, 0.0, 0, {}, {}, [])

    spans = busy[min(busy)]
    edges = [w0] + [t for ab in spans for t in ab] + [w1]
    inner = [lab for lab in labels if lab[2] != WINDOW]
    gaps = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        open_ = [lab for lab in inner if lab[0] <= mid < lab[1]]
        # innermost: the latest-starting annotation open at the midpoint
        label = max(open_)[2] if open_ else WINDOW
        gaps.append((label, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    busy_s = sum(sum(b - a for a, b in iv) for iv in busy.values())
    return DeviceTrace(window_s=(w1 - w0) * 1e-9,
                       busy_s=busy_s * 1e-9 / len(busy), devices=len(busy),
                       op_s=op_s, mosaic_s=mosaic_s, gaps=gaps)
