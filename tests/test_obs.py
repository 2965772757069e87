"""Observability layer (DESIGN.md §8): span tracer, unified metrics,
Perfetto export, plan profiles — and its two load-bearing contracts:
the no-op path changes nothing, and the unified counters carry the
legacy values verbatim (bit-for-bit against the published artifacts).
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro import Session
from repro.core.patterns import banded_mask, values_for_mask
from repro.obs import (NOOP, Counter, MetricSet, Tracer, as_tracer,
                       chrome_trace, from_engine_stats, from_sim_report,
                       from_truncation, mesh_stats_events, sim_trace_events,
                       span_events, text_report, validate_metrics,
                       write_chrome_trace)
from repro.runtime.trace import TaskEvent, Trace, critical_path

_ROOT = pathlib.Path(__file__).parents[1]
# benchmarks/ is a repo-root package (for benchmarks._artifact)
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))


def _banded(n=64, d=9, seed=1):
    return values_for_mask(banded_mask(n, d), seed=seed)


class TestTracer:
    def test_as_tracer(self):
        assert as_tracer(None) is NOOP
        assert as_tracer(False) is NOOP
        assert isinstance(as_tracer(True), Tracer)
        tr = Tracer()
        assert as_tracer(tr) is tr
        with pytest.raises(ValueError):
            as_tracer("yes")

    def test_noop_is_inert(self):
        assert not NOOP.enabled
        assert NOOP.spans == ()
        with NOOP.span("x", track="t", k=1) as sp:
            sp.set(more=2)          # chainable, records nothing
        assert NOOP.spans == ()
        assert len(NOOP.find("x")) == 0

    def test_nesting_depth_and_attrs(self):
        tr = Tracer()
        with tr.span("outer", track="a", k=1) as so:
            with tr.span("inner", track="b") as si:
                si.set(q=2)
            so.set(done=True)
        outer, = tr.find("outer")
        inner, = tr.find("inner")
        assert outer.depth == 0 and inner.depth == 1
        assert outer.attrs == {"k": 1, "done": True}
        assert inner.attrs == {"q": 2}
        assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
        # ordered() sorts by start time; spans list is close order
        assert [s.name for s in tr.ordered()] == ["outer", "inner"]
        assert [s.name for s in tr.spans] == ["inner", "outer"]
        assert len(tr) == 2
        tr.clear()
        assert len(tr) == 0


class TestSessionSpans:
    def test_numpy_engine_taxonomy(self):
        a = _banded()
        sess = Session(trace=True, leaf_n=32, bs=8)
        A = sess.from_dense(a)
        C = A @ A
        sess.simulate(p=4)
        names = {s.name for s in sess.tracer.spans}
        assert {"qt.from_dense", "qt.multiply",
                "session.simulate"} <= names
        mul, = sess.tracer.find("qt.multiply")
        assert mul.track == "graph"
        assert mul.attrs["n"] == 64 and mul.attrs["tasks"] > 0
        sim, = sess.tracer.find("session.simulate")
        assert sim.attrs["tasks"] > 0 and sim.attrs["makespan_s"] > 0
        np.testing.assert_allclose(C.to_dense(), a @ a, rtol=1e-9)

    @pytest.mark.pallas
    def test_pallas_engine_wave_spans(self):
        a = _banded()
        sess = Session(engine="pallas", trace=True, leaf_n=32, bs=8)
        A = sess.from_dense(a)
        got = (A @ A).to_dense()
        np.testing.assert_allclose(got, a @ a, rtol=1e-3, atol=1e-5)
        waves = sess.tracer.find("engine.wave")
        assert waves and all(w.track == "engine" for w in waves)
        w = waves[0]
        assert w.attrs["kernel"] and w.attrs["bs"] == 8
        assert w.attrs["pairs"] > 0 and w.attrs["bytes_packed"] > 0
        disp = sess.tracer.find("kernel.dispatch")
        assert disp and all(d.depth > w.depth or d.t0 >= w.t0
                            for d in disp)
        # dispatch spans nest inside their wave span
        assert any(w.t0 <= d.t0 and d.t1 <= w.t1 for d in disp)

    def test_tracing_context_manager(self):
        a = _banded()
        sess = Session(leaf_n=32, bs=8)
        assert sess.tracer is NOOP
        with sess.tracing() as tr:
            A = sess.from_dense(a)
            _ = A @ A
        assert sess.tracer is NOOP
        assert sess.graph.tracer is NOOP
        assert tr.find("qt.multiply")
        # exception still restores the previous tracer
        with pytest.raises(RuntimeError):
            with sess.tracing():
                raise RuntimeError("boom")
        assert sess.tracer is NOOP


def _replayed(product, trace=True):
    """A compiled X @ X + X or X.sym_square() + X on the pallas engine,
    run once and replayed once with tracing on only for the replay (the
    shape of one benchmark op); returns the session and the replay's
    tracer.  The kernel sums the product's own partials; the ``+ X`` has
    a data leaf for an input, so it cannot fold and stays a host fill."""
    a = _banded()
    a = (a + a.T) / 2
    sess = Session(engine="pallas", lazy=True, leaf_n=16, bs=8)
    X = sess.from_dense(a, upper=product == "sym_square", name="X")
    plan = sess.compile((X.sym_square() if product == "sym_square"
                         else X @ X) + X)
    plan.run()
    sess.flush()
    tr = Tracer() if trace else NOOP
    with sess.tracing(tr):
        plan.run(X=X, flush=False)
        sess.flush()
    return sess, tr


def _parents(tr) -> list:
    """``(name, parent name or None)`` of every span, by nesting."""
    out, stack = [], []
    for s in sorted(tr.spans, key=lambda s: (s.t0, s.depth)):
        while stack and stack[-1].depth >= s.depth:
            stack.pop()
        out.append((s.name, stack[-1].name if stack else None))
        stack.append(s)
    return out


@pytest.mark.pallas
@pytest.mark.parametrize("product", ["matmul", "sym_square"])
class TestWaveSpans:
    """The spans inside a wave: pack, host fill, upload, kernel,
    download, unpack — under the parents the benchmark's readers
    assume, with byte counters that match what crossed the bus."""

    NEW = {"engine.wave.pack": "engine.wave",
           "engine.wave.unpack": "engine.wave",
           "engine.flush.host": "engine.flush",
           "kernel.upload": "kernel.dispatch",
           "kernel.run": "kernel.dispatch",
           "kernel.download": "kernel.dispatch"}

    def test_each_new_span_sits_under_its_parent(self, product):
        _, tr = _replayed(product)
        pairs = _parents(tr)
        seen = {name for name, _ in pairs}
        assert set(self.NEW) <= seen
        for name, parent in pairs:
            if name in self.NEW:
                assert parent == self.NEW[name], (name, parent)
        pack, = tr.find("engine.wave.pack")
        assert pack.attrs["pairs"] > 0 and pack.attrs["unique_blocks"] > 0
        run, = tr.find("kernel.run")
        assert run.attrs["pairs"] == pack.attrs["pairs"]
        unpack, = tr.find("engine.wave.unpack")
        assert run.attrs["cap_c"] == unpack.attrs["c_blocks"] > 0
        host = tr.find("engine.flush.host")
        assert all(h.attrs["adds"] + h.attrs["transposes"]
                   + h.attrs["scales"] > 0 for h in host)
        assert sum(h.attrs["blocks"] for h in host) > 0

    def test_byte_counters_equal_the_arrays_moved(self, product,
                                                  monkeypatch):
        from repro.core import engine

        packed = []
        pack = engine._pack_wave

        def spy(tasks):
            out = pack(tasks)
            packed.append(out)
            return out

        monkeypatch.setattr(engine, "_pack_wave", spy)
        _, tr = _replayed(product)
        (table, sa, sb, seg, n_slots, _), = packed[-1:]
        up, = tr.find("kernel.upload")
        down, = tr.find("kernel.download")
        # the operand table serves both sides and goes up once
        assert up.attrs["bytes"] == sum(
            x.nbytes for x in (table, sa, sb, seg))
        assert down.attrs["bytes"] == n_slots * 8 * 8 * 4

    def test_existing_layer_sums_unchanged_by_the_children(self, product):
        from benchmarks.chip.harness import Window

        _, tr = _replayed(product)
        spans = list(tr.spans)
        old = [s for s in spans if s.name not in self.NEW]
        assert len(old) < len(spans)

        def layers(sp):
            w = Window(ops=1, window_s=1.0, compiles=0, spans=sp)
            return (w.self_s(("engine.flush", "engine.wave")),
                    w.total_s(("kernel.dispatch",)),
                    w.self_s(("plan.", "qt.")))

        for new, before in zip(layers(spans), layers(old)):
            assert new == pytest.approx(before, rel=1e-9, abs=1e-12)

    def test_untraced_records_nothing_and_never_syncs(self, product,
                                                      monkeypatch):
        import jax

        calls = []
        sync = jax.block_until_ready

        def counting(x):
            calls.append(1)
            return sync(x)

        monkeypatch.setattr(jax, "block_until_ready", counting)
        sess, tr = _replayed(product, trace=False)
        assert tr is NOOP and NOOP.spans == () and sess.tracer is NOOP
        assert calls == []
        _replayed(product)
        assert len(calls) >= 2      # the patch is live: traced runs sync


@pytest.mark.pallas
def test_round_trip_spans_on_the_other_dispatch_paths():
    """The triangular solve wave splits its dispatch into the same three
    children as the pairs wave, with the same counters."""
    a = _banded()
    spd = a @ a.T + 64 * np.eye(64)
    sess = Session(engine="pallas", leaf_n=16, bs=8)
    with sess.tracing() as tr:
        z = sess.from_dense(spd, upper=True).inv_chol().to_dense()
    np.testing.assert_allclose(z.T @ spd @ z, np.eye(64), rtol=1e-3,
                               atol=1e-4)
    pairs = _parents(tr)
    kids = [name for name, parent in pairs if parent == "kernel.dispatch"]
    assert kids and set(kids) == {"kernel.upload", "kernel.run",
                                  "kernel.download"}
    assert all(s.attrs["bytes"] > 0 for s in tr.spans
               if s.name in ("kernel.upload", "kernel.download"))
    assert any(s.attrs["kernel"] == "inv_chol"
               for s in tr.find("kernel.dispatch"))


@pytest.mark.pallas
def test_program_spans_reach_the_profiler_trace(tmp_path):
    """Under jax.profiler, each recording span is a host-plane event of
    the same name, on the profiler's clock."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        _, tr = _replayed("matmul")
        tr.instant("probe.instant")
    finally:
        jax.profiler.stop_trace()
    found, = list(tmp_path.rglob("*.xplane.pb"))
    pd = ProfileData.from_serialized_xspace(found.read_bytes())
    host = {e.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    assert {s.name for s in tr.spans} <= host
    assert {"plan.run", "engine.wave.pack", "kernel.upload", "kernel.run",
            "kernel.download", "engine.wave.unpack",
            "engine.flush.host", "probe.instant"} <= host


class TestNoopInert:
    """Tracing off vs on: identical task program and schedule."""

    def _run(self, trace):
        a = _banded(128, 12)
        sess = Session(leaf_n=32, bs=8, trace=trace, seed=0)
        A = sess.from_dense(a)
        B = sess.from_dense(a)
        _ = A @ B
        rep = sess.simulate(p=4)
        return sess, rep

    def test_graph_and_schedule_identical(self):
        s_off, r_off = self._run(False)
        s_on, r_on = self._run(True)
        assert s_off.task_counts() == s_on.task_counts()
        assert len(s_off.graph.nodes) == len(s_on.graph.nodes)
        assert r_off.trace.schedule() == r_on.trace.schedule()
        assert r_off.makespan == r_on.makespan
        assert list(r_off.bytes_received) == list(r_on.bytes_received)


class TestMetrics:
    def test_counter_invariants(self):
        c = Counter("x", "B", [1, 2, 3])
        assert c.total == 6 and c.max == 3
        d = c.to_dict()
        assert d == {"name": "x", "unit": "B", "per_worker": [1, 2, 3],
                     "total": 6}

    def test_metricset_mapping_and_validation(self):
        ms = MetricSet("test")
        ms.add("a", "B", [1, 2])
        ms.add("b", "s", 0.5)               # scalar -> one-element list
        assert "a" in ms and ms["a"].total == 3
        assert ms["b"].per_worker == [0.5]
        assert set(ms.names()) == {"a", "b"}
        doc = ms.to_dict()
        validate_metrics(doc)
        assert MetricSet.from_dict(doc).to_dict() == doc
        doc["counters"][0]["total"] = 999
        with pytest.raises(ValueError):
            validate_metrics(doc)

    def test_sim_report_counters_equal_legacy(self):
        a = _banded(128, 12)
        sess = Session(leaf_n=32, bs=8, seed=0)
        A = sess.from_dense(a)
        _ = A @ A
        rep = sess.simulate(p=4)
        ms = rep.to_metrics()
        assert ms.source == "simulator"
        validate_metrics(ms.to_dict())
        assert ms["bytes_received"].per_worker == list(rep.bytes_received)
        assert ms["bytes_pushed"].per_worker == list(rep.bytes_pushed)
        assert ms["tasks_executed"].per_worker == list(rep.tasks_per_worker)
        assert ms["steals"].total == rep.steals
        assert ms["makespan"].per_worker == [rep.makespan]
        assert from_sim_report(rep).to_dict() == ms.to_dict()

    @pytest.mark.pallas
    def test_engine_stats_counters_equal_legacy(self):
        a = _banded()
        sess = Session(engine="pallas", leaf_n=32, bs=8)
        A = sess.from_dense(a)
        _ = (A @ A).to_dense()
        st = sess.engine_stats()
        ms = from_engine_stats(st)
        assert ms.source == "engine:pallas"
        validate_metrics(ms.to_dict())
        assert ms["waves"].total == st["waves"]
        assert ms["batched_pairs"].total == st["batched_pairs"]
        assert ms["bytes_packed"].total == st["bytes_packed"]

    def test_truncation_counters(self):
        a = _banded(128, 12)
        sess = Session(leaf_n=32, bs=8)
        A = sess.from_dense(a)
        M = A.multiply(A, tau=1e-3)
        rep = M.truncation
        ms = from_truncation(rep)
        validate_metrics(ms.to_dict())
        assert ms["pruned_leaf_pairs"].total == rep.pruned_leaf_pairs
        assert ms["error_bound"].total == rep.error_bound

    def test_session_metrics_sources(self):
        a = _banded()
        sess = Session(leaf_n=32, bs=8)
        A = sess.from_dense(a)
        _ = A @ A
        sess.simulate(p=2)
        sources = [ms.source for ms in sess.metrics()]
        assert sources == ["engine:numpy", "simulator"]
        report = text_report(*sess.metrics())
        assert "bytes_received" in report and "simulator" in report


class TestExport:
    def _sim(self):
        a = _banded(128, 12)
        sess = Session(leaf_n=32, bs=8, seed=0)
        A = sess.from_dense(a)
        _ = A @ A
        return sess, sess.simulate(p=4)

    @staticmethod
    def _assert_monotone(doc):
        ts = [e["ts"] for e in doc["traceEvents"] if e["ph"] != "M"]
        assert ts == sorted(ts)
        assert all(t >= 0 for t in ts)

    def test_sim_trace_chrome_export(self, tmp_path):
        sess, rep = self._sim()
        doc = chrome_trace(sim_trace_events(rep.trace))
        # valid JSON, monotone timestamps, workers as named threads
        doc = json.loads(json.dumps(doc))
        self._assert_monotone(doc)
        evs = doc["traceEvents"]
        names = {e["args"]["name"] for e in evs if e["ph"] == "M"
                 and e["name"] == "thread_name"}
        assert {"worker 0", "worker 3"} <= names
        slices = [e for e in evs if e["ph"] == "X"]
        assert len(slices) == len(rep.trace.events)
        # cumulative received-bytes counters end at the legacy totals
        last = {}
        for e in evs:
            if e["ph"] == "C":
                last[e["name"]] = e["args"]["bytes"]
        assert sum(last.values()) == sum(rep.bytes_received)
        out = tmp_path / "sim.trace.json"
        write_chrome_trace(out, sim_trace_events(rep.trace))
        assert "traceEvents" in json.loads(out.read_text())

    def test_span_events_export(self, tmp_path):
        a = _banded()
        sess = Session(trace=True, leaf_n=32, bs=8)
        A = sess.from_dense(a)
        _ = A @ A
        sess.simulate(p=2)
        doc = chrome_trace(span_events(sess.tracer))
        self._assert_monotone(doc)
        slices = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert {"qt.multiply", "session.simulate"} <= slices
        # combined export: spans + simulator on distinct pid tracks
        both = chrome_trace(span_events(sess.tracer),
                            sim_trace_events(sess._last_report.trace))
        self._assert_monotone(both)
        pids = {e["pid"] for e in both["traceEvents"]}
        assert len(pids) == 2

    def test_mesh_stats_events_from_log(self):
        # synthetic stats dict in MeshEngine.stats() shape: the exporter
        # itself needs no devices
        st = {"n_dev": 2,
              "wave_log": [{"kernel": "k", "bs": 8, "tasks": 3,
                            "pairs": 5, "padded_pairs": 6, "c_blocks": 4,
                            "dispatch_s": 0.25}] * 2,
              "comm_log": [
                  {"fetched_bytes_by_dev": [256, 0],
                   "pushed_bytes_by_dev": [0, 512],
                   "collective_bytes_by_dev": [256, 0]},
                  {"fetched_bytes_by_dev": [0, 128],
                   "pushed_bytes_by_dev": [64, 0],
                   "collective_bytes_by_dev": [0, 128]},
              ]}
        doc = chrome_trace(mesh_stats_events(st))
        self._assert_monotone(doc)
        fetched = [e for e in doc["traceEvents"] if e["ph"] == "C"
                   and e["name"].startswith("fetched_bytes")]
        finals = {}
        for e in fetched:       # cumulative: last value per device wins
            finals[e["tid"]] = e["args"]["bytes"]
        assert finals == {0: 256, 1: 128}
        slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(slices) == 4     # 2 waves x 2 devices
        assert {e["dur"] for e in slices} == {0.25 * 1e6}


class TestPinnedArtifacts:
    """Unified counters reproduce the published BENCH values bit-for-bit."""

    def test_sim_cache_miss_bytes_match_comm_scaling(self):
        doc = json.loads((_ROOT / "BENCH_comm_scaling.json").read_text())
        assert doc["schema"] == 1 and doc["bench"] == "comm_scaling"
        rec = [r for r in doc["records"]
               if r["pattern"] == "banded"
               and r["placement"] == "parent-worker" and r["p"] == 4][0]
        # re-run that record's exact cell (bench_comm_scaling.run_banded
        # at the quick sizes) and compare through the unified schema
        n = rec["n"]
        a = values_for_mask(banded_mask(n, 24), seed=1, symmetric=True)
        sess = Session(leaf_n=32, bs=8, placement="parent-worker", seed=0)
        A = sess.from_dense(a)
        B = sess.from_dense(a)
        sess.simulate(p=4)
        _ = A @ B
        rep = sess.simulate(fresh_stats=True)
        ms = rep.to_metrics()
        assert ms["bytes_received"].max == int(round(rec["max_MB"] * 1e6))
        total = sum(rep.bytes_received)
        assert ms["bytes_received"].total == total
        assert abs(total / len(rep.bytes_received)
                   - rec["avg_MB"] * 1e6) < 0.5

    @pytest.mark.slow
    def test_mesh_fetched_bytes_match_mesh_comm(self):
        # subprocess: XLA device count must be set before jax init
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = str(_ROOT / "src")
        res = subprocess.run(
            [sys.executable, str(_ROOT / "tests" / "dist_scenarios.py"),
             "obs_mesh_pinned"],
            capture_output=True, text=True, env=env, timeout=600)
        assert res.returncode == 0, \
            f"obs_mesh_pinned failed:\n{res.stdout}\n{res.stderr}"
        assert "OK obs_mesh_pinned" in res.stdout


class TestPlanProfile:
    def test_profile_shape_and_metrics(self):
        a = _banded(128, 12)
        sess = Session(lazy=True, leaf_n=32, bs=8)
        X = sess.from_dense(a, name="X")
        plan = sess.compile(X @ X)
        plan.run()
        plan.run()                          # zero-task replay
        prof = plan.profile()
        assert prof["schema"] == 1
        assert prof["inputs"] == ["X"]
        assert prof["runs"] == 2 and prof["n_tasks"] > 0
        assert prof["compile_s"] > 0
        assert len(prof["replay_s"]) == 1
        assert prof["waves"] == []          # immediate numpy backend
        for ms in prof["metrics"]:
            validate_metrics(ms)
        assert prof["metrics"][0]["source"] == "engine:numpy"
        assert json.loads(json.dumps(prof)) == prof

    @pytest.mark.pallas
    def test_profile_waves_on_pallas(self):
        a = _banded(128, 12)
        sess = Session(engine="pallas", lazy=True, leaf_n=32, bs=8)
        X = sess.from_dense(a, name="X")
        plan = sess.compile(X @ X)
        plan.run()
        sess.flush()
        prof = plan.profile()
        assert prof["waves"], "pallas plan should record waves"
        w = prof["waves"][0]
        assert w["bs"] == 8 and w["pairs"] > 0
        assert 0.0 <= w["padding_waste"] < 1.0
        assert w["bytes_packed"] > 0


class TestTraceRegressions:
    """Satellite fixes in runtime/trace.py."""

    def test_gantt_zero_duration_tail_event(self):
        tr = Trace(2)
        tr.append(TaskEvent(nid=0, kind="a", worker=0, start=0.0, end=1.0))
        # zero-duration event exactly at the makespan: start * scale
        # lands on column `width` — must clamp, not IndexError
        tr.append(TaskEvent(nid=1, kind="b", worker=1, start=1.0, end=1.0))
        chart = tr.gantt(width=10)
        lines = chart.splitlines()
        assert lines[0].startswith("w0")
        assert "#" in lines[1]          # the tail event still renders

    def test_gantt_empty_trace(self):
        assert Trace(2).gantt() == "(empty trace)"

    def test_critical_path_empty_trace(self):
        sess = Session(leaf_n=32, bs=8)
        cp = critical_path(sess.graph, Trace(2))
        assert cp.work_s == 0.0 and cp.length_s == 0.0
        assert cp.path == [] and cp.n_tasks == 0

    def test_critical_path_all_done_before(self):
        a = _banded()
        sess = Session(leaf_n=32, bs=8)
        A = sess.from_dense(a)
        _ = A @ A
        rep = sess.simulate(p=2)
        done = {ev.nid for ev in rep.trace.events}
        # a later phase that re-simulates nothing: empty trace + full
        # done_before set must yield the zero path, not raise
        cp = critical_path(sess.graph, Trace(2), done)
        assert cp.length_s == 0.0 and cp.n_tasks == 0


class TestArtifactEnvelope:
    def test_envelope_and_validation(self, tmp_path):
        from benchmarks._artifact import (artifact, validate_artifact,
                                          write_artifact)
        doc = artifact("x", {"v": 1}, params={"p": 2})
        assert doc == {"schema": 1, "bench": "x", "params": {"p": 2},
                       "v": 1}
        validate_artifact(doc)
        with pytest.raises(ValueError):
            validate_artifact({"bench": "x"})
        out = write_artifact(tmp_path / "a.json", "y", {"k": [1, 2]})
        loaded = json.loads(pathlib.Path(out).read_text())
        assert loaded["bench"] == "y" and loaded["k"] == [1, 2]

    def test_published_artifacts_carry_envelope(self):
        for name in ("BENCH_comm_scaling.json", "BENCH_mesh_comm.json"):
            p = _ROOT / name
            if not p.exists():
                pytest.skip(f"{name} not present")
            doc = json.loads(p.read_text())
            assert doc["schema"] == 1
            assert doc["bench"] == name[6:-5]
            assert isinstance(doc["params"], dict)
