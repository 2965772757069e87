"""One run of one cell: set up, measure a window, check, report.

Everything a cell needs is found by name: its entry in ``BENCHMARK.json``,
``configs/<config>.json``, ``traffic/<traffic>.json``, the traffic's
driver (built into ``drivers.py`` or ``drive/<driver>.py``) and, for each
per-layer metric, ``metrics/<metric>.py`` or, where there is none, the
reader of its layer's quantity, ``metrics/<metric without its cell
suffix>.py`` (``api_ms.replay`` falls back to ``api_ms.py``).
:func:`run_cell` does the run and returns the result line; the entry
scripts first call :func:`chip_devices`.

The window: ops start while less than ``seconds`` have passed since the
first one started, and it closes when the last one finishes.  The cell's
time per op (the traffic's ``op_metric``) is the window over the ops
completed; ``setup_s`` runs from process start to the first op.  Each op
ends with a garbage collection, inside its time.  With
``trace`` the program's spans are on (``repro.obs``) and the window runs
under ``jax.profiler``; those runs report the per-layer metrics.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(path) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    """A cell of ``BENCHMARK.json`` with its files loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list            # the benchmark's metric entries that
    per_layer: list             # this cell reports


def applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root=ROOT) -> Cell:
    """Find a cell and everything it names, or raise ``KeyError``."""
    bench = load_json(pathlib.Path(root) / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    return Cell(
        name=name, chips=w["chips"],
        config=load_json(HERE / "configs" / f"{w['config']}.json"),
        traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name)])


def chip_devices(who: str, chips: int = 1):
    """JAX's devices, with the persistent compilation cache in
    ``.jax_cache`` at the checkout's root; None, with the reason on
    standard error, unless the first device is a TPU and there are at
    least ``chips`` of them."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"{who}: needs a TPU, but JAX's first device is on platform "
              f"{devices[0].platform!r} ({devices[0].device_kind})",
              file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"{who}: needs {chips} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return None
    return devices


def load_reader(metric: str):
    """The ``read(window)`` function of ``metrics/<metric>.py``, or of
    ``metrics/<metric less its last dotted part>.py`` where the first is
    not there."""
    path = HERE / "metrics" / f"{metric}.py"
    if not path.exists() and "." in metric:
        path = HERE / "metrics" / f"{metric.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@contextlib.contextmanager
def count_compiles():
    """Count compile requests inside the block (persistent-cache hits
    included): ``with ... as c: c[0]``."""
    import jax

    count = [0]

    def on_event(event: str, duration: float, **_) -> None:
        if event == _COMPILE_EVENT:
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield count
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


@dataclasses.dataclass
class Window:
    """What a per-layer metric's reader reads about the measured window.

    Span times are seconds on the program tracer's clock; ``op_times``
    are the ops' ``(start, end)`` on the same clock.  ``device`` is the
    reduced profiler trace (:class:`xtrace.DeviceTrace`) and ``work`` the
    least flops and bytes of one op, where the cell has them.
    """
    ops: int
    window_s: float
    compiles: int
    spans: list = dataclasses.field(default_factory=list)
    op_times: list = dataclasses.field(default_factory=list)
    device: object = None
    work: dict = None
    peaks: dict = None

    def per_op_ms(self, seconds):
        """``seconds`` over the window's ops, in ms; None stays None."""
        return None if seconds is None else 1e3 * seconds / self.ops

    def self_s(self, prefixes: tuple):
        """Summed self time of the spans whose name starts with a prefix:
        each span's duration less what its direct children cover; None
        where no such span was recorded."""
        child = [0.0] * len(self.spans)
        stack: list = []
        order = sorted(range(len(self.spans)),
                       key=lambda i: (self.spans[i].t0, self.spans[i].depth))
        for i in order:
            s = self.spans[i]
            while stack and self.spans[stack[-1]].depth >= s.depth:
                stack.pop()
            if stack:
                child[stack[-1]] += s.duration
            stack.append(i)
        hits = [s.duration - c for s, c in zip(self.spans, child)
                if s.name.startswith(prefixes)]
        return sum(hits) if hits else None

    def total_s(self, names: tuple):
        hits = [s.duration for s in self.spans if s.name in names]
        return sum(hits) if hits else None

    def outside_spans_s(self):
        """Op time not covered by any top-level span of the program; None
        where the program recorded no span."""
        tops = sorted((s.t0, s.t1) for s in self.spans if s.depth == 0)
        if not tops:
            return None
        out = 0.0
        for a, b in self.op_times:
            covered, end = 0.0, a
            for t0, t1 in tops:
                t0, t1 = max(t0, end), min(t1, b)
                if t1 > t0:
                    covered += t1 - t0
                    end = t1
            out += (b - a) - covered
        return out


def device_info(devices, n_used: int) -> dict:
    d = devices[0]
    peak = 0
    for dev in devices[:n_used]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: dict = None, keep_trace=None,
             control: bool = False) -> dict:
    """Run one cell once; returns the result line as a dict.

    ``keep_trace`` copies the profiler's ``.xplane.pb`` to that path;
    ``control`` adds the lower-precision control's readings of the same
    comparison under ``"control"``, and under ``"control_correct"`` whether
    they pass the same limits (``control.py``; never in a benchmark run).
    """
    import jax

    from . import drivers, xtrace

    tracer = None
    if trace:
        from repro.obs.tracer import Tracer
        tracer = Tracer()
    driver = drivers.make_driver(cell.config, cell.traffic, seed, tracer,
                                 chips=cell.chips)
    with count_compiles() as setup_compiles:
        driver.setup()
    gc.collect()                # set-up's garbage is set-up's cost

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    op_times = []
    with count_compiles() as compiles:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        with jax.profiler.TraceAnnotation(xtrace.WINDOW):
            while time.perf_counter() - t0 < seconds:
                a = time.perf_counter()
                driver.op(len(op_times))
                # what the op dropped is freed inside its own time, not
                # in a collection at a random later point
                gc.collect()
                op_times.append((a, time.perf_counter()))
        t1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
    ops, window_s = len(op_times), t1 - t0
    device = device_info(jax.devices(), cell.chips)
    driver.release()

    result = {"correct": None, "attempted": ops, "failed": 0,
              "metrics": {}, "device": device}
    if trace:
        dev = xtrace.reduce_xplane(log_dir)
        if keep_trace is not None:
            shutil.copy(xtrace.xplane_file(log_dir), keep_trace)
        shutil.rmtree(log_dir, ignore_errors=True)
        epoch = tracer.epoch
        win = Window(ops=ops, window_s=window_s, compiles=compiles[0],
                     spans=[s for s in tracer.spans
                            if s.t0 >= t0 - epoch and s.t1 <= t1 - epoch],
                     op_times=[(a - epoch, b - epoch) for a, b in op_times],
                     device=dev, work=getattr(driver, "work", None),
                     peaks=peaks)
        for m in cell.per_layer:
            value = load_reader(m["name"])(win)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        device.update(busy_s=dev.busy_s, window_s=dev.window_s)
        result["breakdown"] = dev.breakdown()
    else:
        per_op = window_s / ops
        values = {cell.traffic["op_metric"]: per_op, "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    result["window"] = {"ops": ops, "seconds": window_s,
                        "compiles": compiles[0], "setup_s": setup_s,
                        "setup_phases": driver.phases,
                        "setup_compiles": setup_compiles[0],
                        "op_s": [b - a for a, b in op_times]}
    checks = driver.check(cell.config["limits"], ops)
    result["correct"] = all(v <= lim for v, lim in checks.values())
    if control:
        readings = driver.control()
        result["control"] = readings
        result["control_correct"] = all(
            readings[k] <= lim for k, (_, lim) in checks.items())
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result
