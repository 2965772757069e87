"""The mesh cell at a tiny size on the CPU, for ``test_chipbench_mesh.py``.

The cell ``overlap3d-64k.mesh-replay`` (``configs/overlap3d-64k.json``,
``traffic/mesh-replay.json``, ``drive/mesh_replay.py``) is built from its
files by :func:`tiny_cell`.  Its metrics are those of its entry in
``BENCHMARK.json`` where that file names the cell, and otherwise the ones
it would report, ``END_TO_END`` and ``PER_LAYER``; so the tests hold before
the cell is admitted and after.  Imported, this module gives the tiny cell,
one run of it through the harness, the faults that break its timed path
underneath, and the comparison of the communication readers with the
engine's own counters.  Run as a script under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``, it does all of that
on four forced host devices and prints one JSON line.
"""
import contextlib
import json
import pathlib
import sys
import time
from unittest import mock

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import drivers, harness  # noqa: E402

CELL = "overlap3d-64k.mesh-replay"
CONFIG = harness.HERE / "configs" / "overlap3d-64k.json"
TRAFFIC = harness.HERE / "traffic" / "mesh-replay.json"
END_TO_END = {"replay_s": "s", "setup_s": "s"}
PER_LAYER = {"api_ms.mesh": "ms", "engine_ms.mesh": "ms",
             "dispatch_ms.mesh": "ms", "staged_mb.mesh": "MB",
             "mesh_wave_ms.mesh": "ms", "mesh_wave_roofline.mesh": "%",
             "ppermute_ms.mesh": "ms", "fetch_mb.mesh": "MB",
             "collective_mb.mesh": "MB", "compiles.mesh": "count",
             "idle_pct.mesh": "%"}
TINY = dict(n_per_dim=5, n=128, leaf_n=16, bs=8)
SEED = 2**31 + 13
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def metric_lists() -> tuple:
    """The cell's end-to-end and per-layer metric entries: its
    ``BENCHMARK.json`` entry's where there is one, else ``END_TO_END``'s
    and ``PER_LAYER``'s."""
    try:
        cell = harness.load_cell(CELL)
    except KeyError:
        metrics = lambda units: [{"name": k, "unit": u}
                                 for k, u in units.items()]
        return metrics(END_TO_END), metrics(PER_LAYER)
    return cell.end_to_end, cell.per_layer


def tiny_cell(chips: int) -> harness.Cell:
    config = harness.load_json(CONFIG)
    config.update(TINY)
    end_to_end, per_layer = metric_lists()
    return harness.Cell(name=CELL, chips=chips, config=config,
                        traffic=harness.load_json(TRAFFIC),
                        end_to_end=end_to_end, per_layer=per_layer)


def run(chips: int, seconds=0.3, trace=False, control=False) -> dict:
    return harness.run_cell(tiny_cell(chips), SEED, seconds, trace,
                            time.perf_counter(), peaks=PEAKS,
                            control=control)


# -- the timed path broken underneath: correct must come out false -----------

def _wrap_wave(fault):
    from repro.launch import mesh_exec
    real = mesh_exec.mesh_wave

    def broken(own_pool, sa, sb, seg, sels, **kw):
        return fault(real, own_pool, sa, sb, seg, sels, kw)
    return mock.patch.object(mesh_exec, "mesh_wave", broken)


def stale_state():
    """A replay that keeps its state: rebinding leaves the inputs as
    they were."""
    from repro.api import plan
    return mock.patch.object(plan.Plan, "_rebind", lambda self, by: None)


def half_batch():
    """Every other pair of each device left out, the rest doubled to keep
    the mean."""
    def fault(real, own_pool, sa, sb, seg, sels, kw):
        seg = np.array(seg)
        seg[:, 1::2] = kw["cap_c"]
        order = np.argsort(seg, axis=1, kind="stable")
        take = lambda x: np.take_along_axis(np.asarray(x), order, axis=1)
        return 2.0 * real(own_pool, take(sa), take(sb), take(seg), sels,
                          **kw)
    return _wrap_wave(fault)


def altered_answer():
    """One element of one output block altered by 1% where it is made."""
    def fault(real, own_pool, sa, sb, seg, sels, kw):
        import jax

        c = real(own_pool, sa, sb, seg, sels, **kw)
        host = np.array(c)
        host[0, 0, 0, 0] += 0.01 * (abs(host[0, 0, 0, 0]) + 1e-3)
        return jax.device_put(host, c.sharding)
    return _wrap_wave(fault)


@contextlib.contextmanager
def no_exchange():
    """The ring shifts deliver zeros: no block crosses between devices."""
    import jax
    import jax.numpy as jnp
    from repro.launch import mesh_exec

    mesh_exec.mesh_wave.clear_cache()
    try:
        with mock.patch.object(jax.lax, "ppermute",
                               lambda x, *a, **k: jnp.zeros_like(x)):
            yield
    finally:
        mesh_exec.mesh_wave.clear_cache()


FAULTS = {"stale_state": stale_state, "half_batch": half_batch,
          "altered_answer": altered_answer, "no_exchange": no_exchange}


# -- the communication readers against the engine's counters -----------------

def readers_vs_stats(chips: int, ops: int = 3) -> dict:
    """``fetch_mb`` and ``collective_mb`` of ``ops`` replays, and the
    same from ``MeshEngine.stats()``: the largest device's counter delta
    over the replays, and each replay's per-device deltas."""
    from repro.obs.tracer import Tracer

    cell = tiny_cell(chips)
    tracer = Tracer()
    d = drivers.make_driver(cell.config, cell.traffic, SEED, tracer,
                            chips=chips)
    d.setup()
    first = len(tracer.spans)
    snaps = [d.engine.stats()]
    for k in range(ops):
        d.op(k)
        snaps.append(d.engine.stats())
    w = harness.Window(ops=ops, window_s=1.0, compiles=0,
                       spans=tracer.spans[first:])
    out = {}
    for quantity, key in (("fetch_mb", "fetched_bytes"),
                          ("collective_mb", "collective_bytes")):
        deltas = [np.subtract(b[key], a[key]).tolist()
                  for a, b in zip(snaps, snaps[1:])]
        out[quantity] = {
            "reader": harness.load_reader(f"{quantity}.mesh")(w),
            "stats": 1e-6 * max(np.sum(deltas, axis=0)) / ops,
            "per_replay": deltas}
    return out


def main() -> int:
    import jax

    chips = len(jax.devices())
    out = {"devices": chips, "runs": {}, "faults": {}}
    for label, kw in (("untraced", {}), ("traced", {"trace": True}),
                      ("control", {"control": True})):
        r = run(chips, **kw)
        out["runs"][label] = {k: r.get(k) for k in (
            "correct", "metrics", "checks", "control", "control_correct",
            "device")}
        out["runs"][label]["compiles"] = r["window"]["compiles"]
    for name, fault in FAULTS.items():
        with fault():
            out["faults"][name] = run(chips, seconds=0.2)["correct"]
    out["readers_vs_stats"] = readers_vs_stats(chips)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
