"""The four-chip mesh cell and the drivers found by name, on the CPU.

``drive/mesh_replay.py`` is found by its file name, with no entry in
``drivers.DRIVERS``.  The cell ``overlap3d-64k.mesh-replay`` is built from
its files (``mesh_cell.py``, with its ``BENCHMARK.json`` entry's metrics
once that file names it) and runs end to end through ``harness.run_cell``
at a tiny size, in this process on its one host device and in a
subprocess on four forced host devices (``mesh_cell.py``), untraced,
traced and with the control in the program's place; with its timed path
broken underneath, ``correct`` comes out false.  Its readers are checked
on synthetic windows, and its communication readers against the engine's
own counters.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT), str(HERE)]

import mesh_cell  # noqa: E402
from benchmarks.chip import drivers, harness, xtrace  # noqa: E402
from benchmarks.chip.drive import mesh_replay  # noqa: E402

_end_to_end, _per_layer = mesh_cell.metric_lists()
E2E = {m["name"] for m in _end_to_end}
# what a CPU trace holds: no device plane, so no kernel or collective time
TRACED = {m["name"] for m in _per_layer} - {
    "mesh_wave_ms.mesh", "mesh_wave_roofline.mesh", "ppermute_ms.mesh"}


# -- drivers by name ---------------------------------------------------------

def test_driver_is_found_by_its_file_name():
    assert "mesh_replay" not in drivers.DRIVERS
    assert (drivers.DRIVE / "mesh_replay.py").is_file()
    assert drivers.driver_class("mesh_replay") is mesh_replay.Driver
    assert drivers.driver_class("plan_replay") is drivers.PlanReplay


def test_unknown_driver_lists_both_kinds():
    with pytest.raises(ValueError) as err:
        drivers.make_driver({}, {"driver": "no_such_driver"}, 1)
    msg = str(err.value)
    assert "no_such_driver" in msg
    assert "plan_replay" in msg and "mesh_replay" in msg


def test_make_driver_tells_the_chip_count():
    mesh = mesh_cell.tiny_cell(4)
    d = drivers.make_driver(mesh.config, mesh.traffic, 5, chips=4)
    assert isinstance(d, mesh_replay.Driver) and d.chips == 4
    assert mesh_replay.Driver(mesh.config, mesh.traffic, 5).chips == 1
    # the one-chip driver is built as before; the count is only set on it
    cell = harness.load_cell("overlap3d-32k.replay")
    d = drivers.make_driver(cell.config, cell.traffic, 5, chips=1)
    ref = drivers.PlanReplay(cell.config, cell.traffic, 5)
    assert type(d) is drivers.PlanReplay and d.widths == ref.widths


def test_mesh_traffic_rebinds_both_operands_every_op():
    cell = mesh_cell.tiny_cell(4)
    d = mesh_replay.Driver(cell.config, cell.traffic, 2**40 + 3)
    assert len(d.widths) == cell.traffic["value_sets"] + 2 == 4
    lo, hi = cell.traffic["width_range"]
    assert all(lo <= w <= hi for w in d.widths)
    binds = [(2 + k % d.sets, 2 + (k + 1) % d.sets)
             for k in range(-1, 5)]     # the warm replay, then the ops
    for (a0, b0), (a1, b1) in zip(binds, binds[1:]):
        assert a1 != a0 and b1 != b0 and a1 != b1
    assert all(v >= 2 for ab in binds for v in ab)


def test_mesh_work_reads_both_operands():
    import numpy as np

    rows = np.array([0, 0, 9, 12])
    cols = np.array([0, 12, 9, 12])
    w = mesh_replay.product_work(rows, cols, 4)
    # blocks (0,0), (0,3), (2,2), (3,3); triples (0,0,0), (0,0,3),
    # (0,3,3), (2,2,2), (3,3,3); outputs (0,0), (0,3), (2,2), (3,3)
    assert (w["pairs"], w["in_blocks"], w["out_blocks"]) == (5, 4, 4)
    assert w["flops"] == 2.0 * 4 ** 3 * 5
    assert w["bytes"] == (2 * 4 + 4) * 4 * 4 * 4


# -- the cell on one host device, in this process ----------------------------

@pytest.mark.parametrize("trace", [False, True])
def test_mesh_cell_runs_on_one_device(trace):
    r = mesh_cell.run(1, trace=trace)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["window"]["compiles"] == 0
    assert list(r)[-1] == "checks"
    if trace:
        assert set(r["metrics"]) == TRACED
        assert r["metrics"]["compiles.mesh"]["value"] == 0
        assert r["metrics"]["staged_mb.mesh"]["value"] > 0
        # one device ships nothing
        assert r["metrics"]["fetch_mb.mesh"]["value"] == 0
        assert "breakdown" in r
    else:
        assert set(r["metrics"]) == E2E
        assert all(m["value"] > 0 for m in r["metrics"].values())
    json.dumps(r)


def test_mesh_control_fails_the_comparison():
    r = mesh_cell.run(1, control=True)
    assert r["correct"] is True and r["control_correct"] is False
    c = r["checks"]["row_err"]
    assert r["control"]["row_err"] > 3 * c["limit"] > 3 * c["value"]


@pytest.mark.parametrize("fault", ["stale_state", "half_batch",
                                   "altered_answer"])
def test_mesh_broken_path_is_not_correct(fault):
    with mesh_cell.FAULTS[fault]():
        r = mesh_cell.run(1, seconds=0.2)
    assert r["correct"] is False, r["checks"]


# -- the cell on four forced host devices, in a subprocess -------------------

@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "mesh_cell.py")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_devices_run_untraced_and_traced(four):
    assert four["devices"] == 4
    untraced, traced = four["runs"]["untraced"], four["runs"]["traced"]
    assert untraced["correct"] is True and traced["correct"] is True
    assert untraced["device"]["count"] == 4
    assert set(untraced["metrics"]) == E2E
    assert set(traced["metrics"]) == TRACED
    assert untraced["compiles"] == traced["compiles"] == 0
    assert traced["metrics"]["fetch_mb.mesh"]["value"] > 0
    assert traced["metrics"]["collective_mb.mesh"]["value"] >= \
        traced["metrics"]["fetch_mb.mesh"]["value"]


def test_four_devices_control_fails_the_comparison(four):
    r = four["runs"]["control"]
    assert r["correct"] is True and r["control_correct"] is False
    assert r["control"]["row_err"] > r["checks"]["row_err"]["limit"]


@pytest.mark.parametrize("fault", sorted(mesh_cell.FAULTS))
def test_four_devices_broken_path_is_not_correct(four, fault):
    assert four["faults"][fault] is False


@pytest.mark.parametrize("quantity", ["fetch_mb", "collective_mb"])
def test_four_devices_readers_equal_the_engine_counters(four, quantity):
    got = four["readers_vs_stats"][quantity]
    assert got["reader"] == pytest.approx(got["stats"], rel=1e-12)
    assert got["reader"] > 0
    first, *rest = got["per_replay"]
    assert all(r == first for r in rest)     # every replay moves the same


# -- the readers on synthetic windows ----------------------------------------

def _device(op_s=None, mosaic_s=None, devices=4):
    return xtrace.DeviceTrace(window_s=10.0, busy_s=1.0, devices=devices,
                              op_s=op_s or {}, mosaic_s=mosaic_s or {},
                              gaps=[])


def _spans():
    from repro.obs.tracer import Span

    wave = lambda t0, fetched, coll, packed: Span(
        "engine.wave", t0, t0 + 1.0, depth=1, attrs={
            "bytes_packed": packed, "fetched_bytes_by_dev": fetched,
            "collective_bytes_by_dev": coll})
    return [Span("engine.flush", 0.0, 4.0),
            wave(0.0, [0, 1_000_000, 2_000_000, 3_000_000], [4e6] * 4, 7e6),
            wave(2.0, [5_000_000, 0, 2_000_000, 0], [1e6] * 4, 3e6)]


def read(metric, w):
    return harness.load_reader(metric)(w)


def test_mesh_readers_on_a_synthetic_window():
    dev = _device(
        op_s={"jit_mesh_wave:closed_call.3": 8.0,
              "jit_mesh_wave:collective-permute-start.1": 0.4,
              "jit_mesh_wave:collective-permute-done.1": 1.2,
              "jit_mesh_wave:collective-permute.2": 0.4,
              "jit_mesh_wave:fusion.7": 3.0,
              "jit_other:collective-permute-done.9": 5.0},
        mosaic_s={"jit_mesh_wave": 8.0, "jit_bsmm_pairs": 5.0})
    w = harness.Window(ops=2, window_s=10.0, compiles=0, spans=_spans(),
                       device=dev, peaks={"flops_per_s": 1e12,
                                          "hbm_bytes_per_s": 1e9},
                       work={"flops": 8e12, "bytes": 2e9})
    # per device 5, 1, 4 and 3 MB over 2 ops: the largest, device 0
    assert read("fetch_mb.mesh", w) == pytest.approx(5.0 / 2)
    assert read("collective_mb.mesh", w) == pytest.approx(5.0 / 2)
    assert read("staged_mb.mesh", w) == pytest.approx(10.0 / 2)
    # 8 s over 4 devices and 2 ops
    assert read("mesh_wave_ms.mesh", w) == pytest.approx(1000.0)
    # (0.4 + 1.2 + 0.4) s over 4 devices and 2 ops
    assert read("ppermute_ms.mesh", w) == pytest.approx(250.0)
    # least: max(8e12 / 4e12, 2e9 / 4e9) = 2 s against 1 s per op
    assert read("mesh_wave_roofline.mesh", w) == pytest.approx(200.0)
    w.work = {"flops": 1e12, "bytes": 2e9}
    assert read("mesh_wave_roofline.mesh", w) == pytest.approx(50.0)


def test_mesh_readers_read_nothing_without_their_sources():
    """A one-chip wave (no per-device counters) and a trace without the
    mesh module give none of the mesh metrics, and raise nothing."""
    from repro.obs.tracer import Span

    dev = _device(op_s={"jit_bsmm_pairs:closed_call.1": 1.0},
                  mosaic_s={"jit_bsmm_pairs": 1.0}, devices=1)
    w = harness.Window(ops=1, window_s=2.0, compiles=0, device=dev,
                       spans=[Span("engine.wave", 0.0, 1.0,
                                   attrs={"pairs": 3})],
                       peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
                       work={"flops": 1.0, "bytes": 1.0})
    for q in ("fetch_mb", "collective_mb", "staged_mb", "mesh_wave_ms",
              "mesh_wave_roofline", "ppermute_ms"):
        assert read(f"{q}.mesh", w) is None, q


def test_mesh_cell_files_and_readers():
    """The cell's configuration is the 32k one at 40^3 particles, its
    traffic names the driver found by name and an end-to-end metric that
    ``BENCHMARK.json`` defines, and every metric it reports has a reader;
    where ``BENCHMARK.json`` names the cell, its entry names these files
    on four chips."""
    cfg = harness.load_json(mesh_cell.CONFIG)
    base = harness.load_cell("overlap3d-32k.replay").config
    assert set(cfg) == set(base)
    diff = {k for k in base if cfg[k] != base[k]}
    assert diff == {"name", "source", "n_per_dim", "n", "assumed"}
    assert (cfg["n_per_dim"], cfg["n"], cfg["leaf_n"], cfg["bs"]) == \
        (40, 65536, 1024, 128)
    cell = mesh_cell.tiny_cell(4)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert cell.traffic["driver"] == "mesh_replay"
    assert cell.traffic["op_metric"] in E2E
    assert cell.traffic["op_metric"] in {m["name"]
                                         for m in bench["end_to_end"]}
    for m in cell.per_layer:
        assert callable(harness.load_reader(m["name"]))
    # once admitted, the cell's per-layer metrics above are its entry's
    entry = {w["name"]: w for w in bench["workloads"]}.get(mesh_cell.CELL)
    if entry is not None:
        assert (entry["config"], entry["traffic"], entry["chips"]) == \
            ("overlap3d-64k", "mesh-replay", 4)
