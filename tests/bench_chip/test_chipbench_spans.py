"""The benchmark's readers of the spans inside a wave, and the device's
idle time charged to the program span open over it (``xspans.py``).

The readers are checked on a synthetic ``Window``; ``idle_by_span`` on a
trace recorded on the chip (``testdata/spans.xplane.pb``, by
``record_trace.py`` once the program mirrored its spans into the
profiler) and on one recorded here on the CPU, where the device plane is
missing and the whole window is idle.
"""
import json
import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import harness, xspans, xtrace  # noqa: E402

SPANS_TRACE = ROOT / "benchmarks/chip/testdata/spans.xplane.pb"
SMALL_TRACE = ROOT / "benchmarks/chip/testdata/small.xplane.pb"
QUANTITIES = ("pack_ms", "unpack_ms", "host_fill_ms", "upload_ms",
              "kernel_wait_ms", "download_ms", "h2d_mb", "d2h_mb")


def _window():
    from repro.obs.tracer import Span

    spans = [Span("plan.run", 0.0, 10.0, depth=0),
             Span("plan.replay", 1.0, 9.0, depth=1),
             Span("engine.flush", 1.0, 9.0, depth=2),
             Span("engine.wave", 1.0, 7.0, depth=3),
             Span("engine.wave.pack", 1.0, 2.0, depth=4),
             Span("kernel.dispatch", 2.0, 5.5, depth=4),
             Span("kernel.upload", 2.0, 2.5, depth=5,
                  attrs={"bytes": 3_000_000}),
             Span("kernel.run", 2.5, 3.0, depth=5),
             Span("kernel.download", 3.0, 5.0, depth=5,
                  attrs={"bytes": 5_000_000}),
             Span("engine.wave.unpack", 5.5, 6.5, depth=4),
             Span("engine.flush.host", 7.0, 8.5, depth=3,
                  attrs={"adds": 4, "transposes": 0, "scales": 0,
                         "blocks": 9})]
    return harness.Window(ops=2, window_s=10.0, compiles=0, spans=spans,
                          op_times=[(0.0, 5.0), (5.0, 10.0)])


def test_wave_readers_on_a_synthetic_window():
    w = _window()
    read = {q: harness.load_reader(f"{q}.replay")(w) for q in QUANTITIES}
    assert read == pytest.approx({
        "pack_ms": 500.0, "unpack_ms": 500.0, "host_fill_ms": 750.0,
        "upload_ms": 250.0, "kernel_wait_ms": 250.0,
        "download_ms": 1000.0, "h2d_mb": 1.5, "d2h_mb": 2.5})
    # the engine's children leave its self time's split, not its sum:
    # wave 6 - pack 1 - dispatch 3.5 - unpack 1 = 0.5, flush 8 - wave 6
    # - host 1.5 = 0.5, and the three children's own 1 + 1 + 1.5
    engine = harness.load_reader("engine_ms.replay")(w)
    assert engine == pytest.approx(1e3 * (0.5 + 0.5 + 1 + 1 + 1.5) / 2)
    dispatch = harness.load_reader("dispatch_ms.replay")(w)
    assert dispatch == pytest.approx(
        read["upload_ms"] + read["kernel_wait_ms"] + read["download_ms"]
        + 250.0)


def test_wave_readers_read_nothing_without_the_spans():
    """A program that records no spans inside its waves
    reports none of their metrics, and raises nothing."""
    from repro.obs.tracer import Span

    w = harness.Window(ops=1, window_s=2.0, compiles=0,
                       spans=[Span("engine.wave", 0.0, 1.0),
                              Span("kernel.dispatch", 0.2, 0.8, depth=1)])
    for q in QUANTITIES:
        assert harness.load_reader(f"{q}.symsq")(w) is None


def test_every_wave_metric_is_declared_for_both_cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in bench["per_layer"]}
    for cell, moves in (("replay", "replay_s"), ("symsq", "symsq_s")):
        for q in QUANTITIES:
            m = declared[f"{q}.{cell}"]
            assert m["moves"] == moves
            assert m["workloads"] == [f"overlap3d-32k.{cell}"]
            assert m["layer"] == ("engine" if q in (
                "pack_ms", "unpack_ms", "host_fill_ms")
                else "transfer and dispatch")
            assert m["source"] == ("program_counter" if q.endswith("_mb")
                                   else "program_span")


def test_idle_by_span_cuts_gaps_at_span_edges(monkeypatch):
    """One idle gap under several spans is split among them, each piece
    to the innermost span open over it; busy time is charged nowhere."""
    window = (0, 100)
    spans = [(10, 90, "plan.run"), (20, 80, "engine.flush"),
             (20, 40, "engine.wave.pack"), (60, 70, "kernel.download"),
             (5, 8, "bench.flush")]     # not a program span: dropped
    spans = [s for s in spans if s[2].startswith(xspans.PROGRAM_PREFIXES)]
    busy = [(45, 50), (48, 55), (95, 120)]
    monkeypatch.setattr(xspans, "_read", lambda path: (window, spans, busy))
    idle = xspans.idle_by_span("unused")
    assert idle == pytest.approx({
        "": (10 + 5) * 1e-9, "plan.run": (10 + 10) * 1e-9,
        "engine.wave.pack": 20e-9, "engine.flush": (5 + 5 + 10) * 1e-9,
        "kernel.download": 10e-9})
    assert sum(idle.values()) == pytest.approx((100 - 10 - 5) * 1e-9)


def test_idle_by_span_on_a_recorded_chip_trace():
    dev = xtrace.reduce_xplane(SPANS_TRACE)
    idle = xspans.idle_by_span(SPANS_TRACE)
    assert dev.devices == 1 and 0.0 < dev.busy_s < dev.window_s
    assert sum(idle.values()) + dev.busy_s == pytest.approx(dev.window_s,
                                                            rel=1e-6)
    assert idle["engine.wave.pack"] > 0.0 and idle["kernel.download"] > 0.0
    assert all(v > 0.0 for v in idle.values())
    assert all(k == "" or k.startswith(xspans.PROGRAM_PREFIXES)
               for k in idle)


def test_idle_by_span_on_a_trace_without_program_spans():
    """The trace recorded before the program mirrored its spans: all
    idle time is charged to no span."""
    dev = xtrace.reduce_xplane(SMALL_TRACE)
    idle = xspans.idle_by_span(SMALL_TRACE)
    assert set(idle) == {""}
    assert idle[""] == pytest.approx(dev.window_s - dev.busy_s, rel=1e-6)


@pytest.mark.pallas
def test_idle_by_span_on_a_cpu_trace(tmp_path):
    """A traced tiny run here: no device plane, so the whole window is
    idle, split among the program's spans and the glue between them."""
    cell = harness.load_cell("overlap3d-32k.replay")
    cell.config.update(n_per_dim=4, n=64, leaf_n=16, bs=8)
    keep = tmp_path / "cpu.xplane.pb"
    r = harness.run_cell(cell, 2**31 + 13, 0.3, True, time.perf_counter(),
                         peaks={"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
                         keep_trace=keep)
    assert r["correct"] is True
    for q in QUANTITIES:
        assert r["metrics"][f"{q}.replay"]["value"] > 0.0
    idle = xspans.idle_by_span(keep)
    assert sum(idle.values()) == pytest.approx(r["device"]["window_s"],
                                               rel=1e-6)
    assert {"engine.wave.pack", "kernel.upload", "kernel.download",
            "engine.wave.unpack", "plan.rebind"} <= set(idle)
