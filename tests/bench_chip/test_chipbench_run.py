"""Whole runs of the chip benchmark's cells, on the CPU at tiny sizes.

``harness.run_cell`` is what ``run.py`` calls once it has found a TPU; here
it runs each cell's traffic end to end with the Pallas kernels in interpret
mode, untraced and traced, and then with the timed path broken underneath,
where ``correct`` has to come out false.  ``run.py`` itself must refuse a
CPU-only JAX and print no result.
"""
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import harness  # noqa: E402

pytestmark = pytest.mark.pallas

TINY_OVERLAP = dict(n_per_dim=4, n=64, leaf_n=16, bs=8)
TINY = {"overlap3d-32k.replay": TINY_OVERLAP,
        "overlap3d-32k.symsq": TINY_OVERLAP}
SEED = 2**31 + 11
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def tiny_cell(name):
    cell = harness.load_cell(name)
    cell.config.update(TINY[name])
    return cell


def run(name, seconds=0.3, trace=False, control=False):
    return harness.run_cell(tiny_cell(name), SEED, seconds, trace,
                            time.perf_counter(), peaks=PEAKS, control=control)


def test_run_py_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/chip/run.py"),
         "--workload", "overlap3d-32k.replay", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert "correct" not in p.stdout


@pytest.mark.parametrize("name", sorted(TINY))
def test_cell_runs_end_to_end(name):
    r = run(name)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    cell = tiny_cell(name)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["window"]["compiles"] == 0
    assert list(r)[-1] == "checks"
    json.dumps(r)


@pytest.mark.parametrize("name", sorted(TINY))
def test_cell_runs_traced(name):
    r = run(name, trace=True)
    assert r["correct"] is True
    spans = {m for m in r["metrics"] if m.split(".")[0] in (
        "api_ms", "engine_ms", "dispatch_ms")}
    assert spans and all(r["metrics"][m]["value"] > 0 for m in spans)
    assert r["metrics"][f"compiles.{name.split('.')[1]}"]["value"] == 0
    # the CPU has no TPU plane: no kernel time, and no roofline read as 0
    assert not any(m.startswith("bsmm_pairs") for m in r["metrics"])
    assert r["device"]["window_s"] > 0 and "breakdown" in r


@pytest.mark.parametrize("name", sorted(TINY))
def test_control_fails_the_harness_comparison(name):
    """The lower-precision control, put in the program's place, is judged
    by the run's own limits and comes out not correct."""
    r = run(name, control=True)
    assert r["correct"] is True
    assert r["control_correct"] is False
    for k, c in r["checks"].items():
        assert r["control"][k] > c["limit"] > c["value"]
    assert list(r)[-1] == "checks"


# -- the timed path broken underneath: correct must come out false -----------

def _stale_rebind(monkeypatch):
    """A replay that keeps its state: rebinding leaves the inputs as
    they were."""
    from repro.api import plan
    monkeypatch.setattr(plan.Plan, "_rebind", lambda self, by_slot: None)


def _wrap_kernel(monkeypatch, fault):
    from repro.kernels import ops
    real = ops.bsmm_pairs

    def broken(a, b, sa, sb, seg, *, cap_c, **kw):
        return fault(real, a, b, sa, sb, seg, cap_c, kw)
    monkeypatch.setattr(ops, "bsmm_pairs", broken)


def _half_batch(monkeypatch):
    """Every other pair left out, the rest doubled to keep the mean."""
    def fault(real, a, b, sa, sb, seg, cap_c, kw):
        seg = np.asarray(seg).copy()
        seg[1::2] = cap_c
        order = np.argsort(seg, kind="stable")
        return 2.0 * real(a, b, np.asarray(sa)[order], np.asarray(sb)[order],
                          seg[order], cap_c=cap_c, **kw)
    _wrap_kernel(monkeypatch, fault)


def _altered_answer(monkeypatch):
    """One element of one output block altered by 1% where it is made."""
    def fault(real, a, b, sa, sb, seg, cap_c, kw):
        c = real(a, b, sa, sb, seg, cap_c=cap_c, **kw)
        return c.at[0, 0, 0].add(0.01 * (abs(c[0, 0, 0]) + 1e-3))
    _wrap_kernel(monkeypatch, fault)


FAULTS = {"stale_state": _stale_rebind, "half_batch": _half_batch,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", sorted(TINY))
def test_broken_path_is_not_correct(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    r = run(name, seconds=0.2)
    assert r["correct"] is False, r["checks"]
