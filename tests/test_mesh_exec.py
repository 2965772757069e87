"""Mesh-executor equivalence suite (launch/mesh_exec.py, DESIGN.md §7).

Pins the mesh leaf backend three ways:

1. **Numerical equivalence** — ``Session(engine="mesh")`` matches the
   numpy reference engine over banded/random/symmetric patterns,
   including NIL quadrants, folded transposes, and the truncated
   multiply, in-process on the ambient (single) jax device.
2. **Device-count invariance** — the same program run under 1, 4 and 8
   forced host devices produces identical results (subprocess scenarios:
   XLA device count must be set before jax initialises) with monotone
   per-device communication counters, and the SpSUMMA baseline fails
   fast on the non-square p=6.
3. **Lifecycle** — ``Session.free`` drops the executor's device-resident
   buffers and ownership/residency bookkeeping (free-then-reuse), and
   plan rebinds bump block versions so stale device copies are
   re-pushed, never silently reused.
4. **Wave plan** — ``plan_mesh_wave`` splits the one-chip numbering of a
   wave over 1-4 devices, on the host and with no devices: each device
   runs exactly the one-chip rows of its tasks, holds every block it
   reads once, and counts a fetch per remote block it reads.
"""
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

from repro import Session
from repro.core.engine import _pack_wave, number_wave
from repro.core.patterns import (banded_mask, divide_space_order,
                                 overlap_mask, particle_cloud, random_mask,
                                 random_symmetric_mask, values_for_mask)

N, LEAF_N, BS = 64, 16, 4
TOL = dict(atol=1e-4)          # mesh packs float32; numpy is float64

_SCRIPT = pathlib.Path(__file__).parent / "dist_scenarios.py"


def _run(scenario: str, n_dev: int):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = str(pathlib.Path(__file__).parents[1] / "src")
    res = subprocess.run([sys.executable, str(_SCRIPT), scenario],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, \
        f"{scenario} failed:\n{res.stdout}\n{res.stderr}"
    assert f"OK {scenario}" in res.stdout
    return res.stdout


def _pair(engine="mesh"):
    mesh = Session(engine=engine, leaf_n=LEAF_N, bs=BS)
    ref = Session(engine="numpy", leaf_n=LEAF_N, bs=BS)
    return mesh, ref


class TestEquivalence:
    """mesh == numpy engine, in-process (ambient device count)."""

    PATTERNS = {
        "banded": lambda: values_for_mask(banded_mask(N, 5), seed=1),
        "random": lambda: values_for_mask(random_mask(N, 0.1, seed=2),
                                          seed=2),
        "nil_quadrant": lambda: np.triu(
            values_for_mask(banded_mask(N, 9), seed=3)),
    }

    @pytest.mark.parametrize("pattern", sorted(PATTERNS))
    def test_multiply(self, pattern):
        a = self.PATTERNS[pattern]()
        b = values_for_mask(banded_mask(N, 7), seed=4)
        mesh, ref = _pair()
        got = (mesh.from_dense(a) @ mesh.from_dense(b)).to_dense()
        want = (ref.from_dense(a) @ ref.from_dense(b)).to_dense()
        np.testing.assert_allclose(got, want, **TOL)

    @pytest.mark.parametrize("case", ["at_b", "a_bt", "at_bt"])
    def test_transposes(self, case):
        a = values_for_mask(banded_mask(N, 5), seed=5)
        b = values_for_mask(random_mask(N, 0.15, seed=6), seed=6)
        op = {"at_b": lambda A, B: A.T @ B,
              "a_bt": lambda A, B: A @ B.T,
              "at_bt": lambda A, B: (B @ A).T}[case]
        mesh, ref = _pair()
        got = op(mesh.from_dense(a), mesh.from_dense(b)).to_dense()
        want = op(ref.from_dense(a), ref.from_dense(b)).to_dense()
        np.testing.assert_allclose(got, want, **TOL)

    def test_sym_square(self):
        s = values_for_mask(random_symmetric_mask(N, 0.15, seed=7),
                            seed=7, symmetric=True)
        mesh, ref = _pair()
        got = mesh.from_dense(s, upper=True).sym_square().to_dense()
        want = ref.from_dense(s, upper=True).sym_square().to_dense()
        np.testing.assert_allclose(got, want, **TOL)

    def test_truncated_multiply_same_structure(self):
        """tau prunes identically on both engines (structure comes from
        leaf_task_pairs on both), numbers agree on the surviving work."""
        idx = np.arange(N)
        decay = np.exp(-np.abs(idx[:, None] - idx[None, :]) / 3.0)
        rng = np.random.default_rng(8)
        a = rng.standard_normal((N, N)) * decay
        mesh, ref = _pair()
        gm = mesh.from_dense(a).multiply(mesh.from_dense(a), tau=1e-2)
        gr = ref.from_dense(a).multiply(ref.from_dense(a), tau=1e-2)
        np.testing.assert_allclose(gm.to_dense(), gr.to_dense(), **TOL)
        assert abs(gm.error_bound - gr.error_bound) < 1e-10

    def test_nil_stays_nil(self):
        """An all-zero quadrant product is NIL on the mesh engine too."""
        a = np.zeros((N, N))
        a[: N // 2, : N // 2] = values_for_mask(
            banded_mask(N // 2, 5), seed=9)
        mesh, ref = _pair()
        got = (mesh.from_dense(a) @ mesh.from_dense(a))
        want = (ref.from_dense(a) @ ref.from_dense(a))
        assert mesh.graph.is_nil(got.node) == ref.graph.is_nil(want.node)
        np.testing.assert_allclose(got.to_dense(), want.to_dense(), **TOL)

    def test_task_graph_identical_to_numpy(self):
        """Structure (task kinds/counts) is engine-independent."""
        a = values_for_mask(banded_mask(N, 5), seed=1)
        mesh, ref = _pair()
        (mesh.from_dense(a) @ mesh.from_dense(a)).to_dense()
        (ref.from_dense(a) @ ref.from_dense(a)).to_dense()
        assert mesh.task_counts() == ref.task_counts()


class TestLifecycle:
    def test_free_then_reuse(self):
        """Session.free drops device-resident buffers + residency; the
        session keeps computing correctly afterwards."""
        rng = np.random.default_rng(0)
        a = rng.standard_normal((N, N)) * 0.1
        sess = Session(engine="mesh", leaf_n=LEAF_N, bs=BS)
        M = sess.from_dense(a)
        P = M @ M
        P.to_dense()
        st1 = sess.engine_stats()
        assert st1["device_leaves"] > 0
        sess.free(P)
        st2 = sess.engine_stats()
        assert st2["device_leaves"] < st1["device_leaves"]
        assert st2["device_blocks"] < st1["device_blocks"]
        # counters never go backwards on free
        assert st2["fetched_bytes"] == st1["fetched_bytes"]
        assert st2["pushed_bytes"] == st1["pushed_bytes"]
        Q = M @ M.T
        np.testing.assert_allclose(Q.to_dense(), a @ a.T, **TOL)

    def test_rebind_bumps_version_and_repushes(self):
        """A plan rebind refills input leaves in place: device copies go
        stale (version bump) and are re-pushed, not silently reused."""
        rng = np.random.default_rng(1)
        a = rng.standard_normal((N, N)) * 0.1
        sess = Session(engine="mesh", leaf_n=LEAF_N, bs=BS, lazy=True)
        X = sess.from_dense(a, name="X")
        plan = sess.compile(X @ X)
        Y = plan.run()
        np.testing.assert_allclose(Y.to_dense(), a @ a, **TOL)
        st1 = sess.engine_stats()
        a2 = rng.standard_normal((N, N)) * 0.1
        Z = plan.run(X=a2)
        np.testing.assert_allclose(Z.to_dense(), a2 @ a2, **TOL)
        st2 = sess.engine_stats()
        assert sum(st2["pushed_bytes"]) > sum(st1["pushed_bytes"])

    def test_plan_replay_compiles_nothing(self):
        """Waves of one shape reuse one jitted executable: a rebound plan
        replay triggers no XLA compile."""
        import jax

        from repro.launch.mesh_exec import MeshEngine

        rng = np.random.default_rng(2)
        a = rng.standard_normal((N, N)) * 0.1
        sess = Session(engine=MeshEngine(), leaf_n=LEAF_N,
                       bs=BS, lazy=True)
        X = sess.from_dense(a, name="X")
        plan = sess.compile(X @ X)
        plan.run().to_dense()
        compiles = []

        def on_event(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(duration)

        jax.monitoring.register_event_duration_secs_listener(on_event)
        try:
            a2 = rng.standard_normal((N, N)) * 0.1
            out = plan.run(X=a2).to_dense()
        finally:
            jax.monitoring.unregister_event_duration_listener(on_event)
        np.testing.assert_allclose(out, a2 @ a2, **TOL)
        assert compiles == []

    def test_engine_stats_shape(self):
        a = values_for_mask(banded_mask(N, 5), seed=1)
        sess = Session(engine="mesh", leaf_n=LEAF_N, bs=BS)
        (sess.from_dense(a) @ sess.from_dense(a)).to_dense()
        st = sess.engine_stats()
        assert st["backend"] == "mesh"
        n = st["n_dev"]
        assert n >= 1
        for key in ("fetched_bytes", "fetched_blocks", "pushed_bytes",
                    "collective_bytes"):
            assert len(st[key]) == n
            assert all(v >= 0 for v in st[key])
        assert st["waves"] == len(st["comm_log"]) > 0


class TestWavePlan:
    """``plan_mesh_wave`` against ``_pack_wave``, without devices."""

    @staticmethod
    def _wave(n_dev, product):
        """The ready tasks of one overlap-pattern product, not run."""
        from repro.launch.mesh_exec import MeshEngine

        coords = particle_cloud(4, 3, seed=7)          # 64 basis functions
        mask = overlap_mask(coords, 4.0, order=divide_space_order(coords))
        x_val = values_for_mask(mask, seed=7, symmetric=True)
        sess = Session(engine=MeshEngine(n_dev=n_dev), leaf_n=LEAF_N,
                       bs=BS)
        x = sess.from_dense(x_val, upper=product == "sym_square")
        _ = x.sym_square() if product == "sym_square" else x @ x
        (tasks,) = sess.graph.engine.ready_wave().values()
        return tasks

    @pytest.mark.parametrize("product", ["matmul", "sym_square"])
    @pytest.mark.parametrize("n_dev", [1, 2, 3, 4])
    def test_plan_splits_the_one_chip_rows(self, n_dev, product):
        from repro.launch.mesh_exec import plan_mesh_wave

        tasks = self._wave(n_dev, product)
        slots, sa, sb, seg, n_slots = number_wave(tasks)
        table, psa, psb, pseg, _, _ = _pack_wave(tasks)
        plan = plan_mesh_wave(slots, sa, sb, seg,
                              [len(t.out.blocks) for t in tasks], n_dev,
                              {}, [dict() for _ in range(n_dev)])
        # the contiguous balanced split of the tasks
        assert (np.diff(plan.task_dev) >= 0).all()
        per_dev = np.bincount(plan.task_dev, minlength=n_dev)
        assert per_dev.max() - per_dev.min() <= 1
        assert plan.n_out.sum() == n_slots
        lo = 0
        for d in range(n_dev):
            pool = plan.pool_slot[d]
            n = int((plan.seg[d] < plan.cap_c).sum())
            rows = slice(lo, lo + n)
            lo += n
            # in order, the one-chip rows of this device's tasks: of the
            # whole wave in its numbering, and of these tasks alone
            np.testing.assert_array_equal(pool[plan.sa[d, :n]], psa[rows])
            np.testing.assert_array_equal(pool[plan.sb[d, :n]], psb[rows])
            np.testing.assert_array_equal(plan.seg[d, :n] + plan.base[d],
                                          pseg[rows])
            mine = [t for t, dev in zip(tasks, plan.task_dev) if dev == d]
            if mine:
                t_d, sa_d, sb_d, seg_d, _, _ = _pack_wave(mine)
                np.testing.assert_array_equal(plan.seg[d, :n], seg_d)
                assert table[pool[plan.sa[d, :n]]].tobytes() == \
                    t_d[sa_d].tobytes()
                assert table[pool[plan.sb[d, :n]]].tobytes() == \
                    t_d[sb_d].tobytes()
            # every slot it reads is its own or received, exactly once
            read = np.unique(np.concatenate([psa[rows], psb[rows]]))
            held = pool[pool >= 0]
            assert len(np.unique(held)) == len(held)
            assert np.isin(read, held).all()
            own = pool[:plan.cap_own]
            assert (plan.home[own[own >= 0]] == d).all()
            assert (plan.home[pool[plan.cap_own:][
                pool[plan.cap_own:] >= 0]] != d).all()
            # with nothing resident yet, every remote read is one fetch
            assert plan.fetched[d] == (plan.home[read] != d).sum()
        assert lo == len(pseg)
        assert plan.pushed.sum() == len(slots)
        # the ring shifts deliver what the pools say they hold
        off = plan.cap_own
        for k, sel in zip(plan.shifts, plan.sels):
            for src in range(n_dev):
                got = plan.pool_slot[src, sel[src]]
                want = plan.pool_slot[(src + k) % n_dev,
                                      off:off + sel.shape[1]]
                np.testing.assert_array_equal(got[want >= 0],
                                              want[want >= 0])
            off += sel.shape[1]
        assert off == plan.pool_slot.shape[1]
        # a leaf lives on the first device that reads any of its blocks
        pair_dev = np.repeat(np.arange(n_dev), [
            (plan.seg[d] < plan.cap_c).sum() for d in range(n_dev)])
        leaf_of = np.array([id(leaf) for leaf, _, _ in slots])
        for lid in set(leaf_of.tolist()):
            mine = np.flatnonzero(leaf_of == lid)
            readers = pair_dev[np.isin(psa, mine) | np.isin(psb, mine)]
            assert (plan.home[mine] == readers.min()).all()


@pytest.mark.slow
class TestDeviceCounts:
    """Forced-host-device runs (subprocess: XLA device count is fixed at
    jax init, so the main pytest process can't host them)."""

    @pytest.mark.parametrize("n_dev", [1, 4, 8])
    def test_equivalence(self, n_dev):
        _run("mesh_engine_equivalence", n_dev)

    def test_identical_results_across_device_counts(self):
        sums = set()
        for n_dev in (1, 4, 8):
            out = _run("mesh_engine_equivalence", n_dev)
            m = re.search(r"CHECKSUM (.*)", out)
            assert m, out
            sums.add(m.group(1).strip())
        assert len(sums) == 1, f"results differ across device counts: {sums}"

    @pytest.mark.parametrize("n_dev", [1, 4])
    def test_counters(self, n_dev):
        _run("mesh_engine_counters", n_dev)

    def test_summa_p6_fails_fast(self):
        _run("summa_pgrid_validation", 6)
