"""The chip benchmark's own parts, on the CPU at tiny sizes.

Its copies of the generators and float64 references agree with the ones
in ``chip_smoke.py``; its structural pair count equals the engine's, in
general and in symmetric upper storage; its control reads above the
configured limit; its trace reduction reads the small trace recorded on a
TPU v5e in ``testdata/``; and ``BENCHMARK.json`` names only files and
readers that exist.
"""
import importlib.util
import json
import math
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import data, harness, peaks, reference, xtrace  # noqa

CHIP = ROOT / "benchmarks" / "chip"
TESTDATA = CHIP / "testdata" / "small.xplane.pb"
TINY_OVERLAP = dict(n_per_dim=4, n=64, leaf_n=16, bs=8)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke_for_bench", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def overlap_cfg(**over):
    cfg = harness.load_cell("overlap3d-32k.replay").config
    cfg.update(TINY_OVERLAP, **over)
    return cfg


def test_overlap_problem_matches_chip_smoke(smoke):
    pts, rows, cols, n = data.overlap_problem(overlap_cfg())
    s_pts, s_rows, s_cols, s_n = smoke.overlap_problem(4, 0)
    assert n == s_n == 64
    np.testing.assert_array_equal(pts, s_pts)
    assert set(zip(rows.tolist(), cols.tolist())) == \
        set(zip(s_rows.tolist(), s_cols.tolist()))


def test_product_reference_and_error_match_chip_smoke(smoke):
    from repro import Session

    pts, rows, cols, n = data.overlap_problem(overlap_cfg())
    vf = data.overlap_values(pts, 4.0)
    x = reference.sparse_matrix(rows, cols, n, vf)
    assert (x != smoke.reference(rows, cols, n,
                                 smoke.pair_values(pts, 4.0))).nnz == 0
    sess = Session(engine="numpy", leaf_n=16, bs=8)
    a = sess.from_pattern(rows, cols, n, value_fn=vf)
    c = a @ a
    ref = reference.product_reference(x)
    got = reference.block_errors(smoke.stored_blocks(c), ref, 8)
    assert got["rel_err"] == pytest.approx(smoke.rel_error(c, ref),
                                           rel=1e-9)
    assert got["row_err"] >= got["rel_err"]


@pytest.mark.parametrize("upper", [False, True])
def test_block_errors_see_a_missing_block(upper):
    x = reference.sparse_matrix(np.array([0, 9, 1]), np.array([0, 9, 12]),
                                16, lambda r, c: np.ones(len(r)))
    blocks = reference.csr_blocks(x, 8, upper=upper)
    assert reference.block_errors(blocks, x, 8, upper=upper)["row_err"] == 0
    del blocks[1]
    assert math.isinf(
        reference.block_errors(blocks, x, 8, upper=upper)["row_err"])


def test_upper_errors_match_chip_smoke(smoke):
    """The upper-storage comparison reads what chip_smoke's does."""
    from repro import Session

    pts, rows, cols, n = data.overlap_problem(overlap_cfg())
    vf = data.overlap_values(pts, 4.0)
    sess = Session(engine="numpy", leaf_n=16, bs=8)
    s2 = sess.from_pattern(rows, cols, n, value_fn=vf, upper=True)
    s2 = s2.sym_square()
    ref = reference.product_reference(reference.sparse_matrix(
        rows, cols, n, vf))
    got = reference.block_errors(smoke.stored_blocks(s2), ref, 8, upper=True)
    assert got["rel_err"] == pytest.approx(smoke.rel_error(s2, ref),
                                           rel=1e-9)
    assert got["row_err"] < 1e-12


def test_product_work_counts_block_triples():
    rng = np.random.default_rng(3)
    dense = rng.random((6, 6)) < 0.4
    dense = dense | dense.T | np.eye(6, dtype=bool)
    r, c = np.nonzero(dense)
    want = sum(1 for i in range(6) for k in range(6) for j in range(6)
               if dense[i, k] and dense[k, j])
    want_up = sum(1 for i in range(6) for k in range(6) for j in range(i, 6)
                  if dense[i, k] and dense[k, j])
    sq = dense.astype(int) @ dense.astype(int) > 0
    full = reference.product_work(r * 4, c * 4, 4)
    up = reference.product_work(r * 4, c * 4, 4, upper=True)
    assert (full["pairs"], up["pairs"]) == (want, want_up)
    assert full["in_blocks"] == dense.sum()
    assert up["in_blocks"] == np.triu(dense).sum()
    assert up["out_blocks"] == np.triu(sq).sum()
    assert up["bytes"] == (up["in_blocks"] + up["out_blocks"]) * 64


def test_count_compiles_matches_chip_smoke(smoke):
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda v: v * 3.0 + 1.0)
    v = jnp.ones(7).block_until_ready()
    with harness.count_compiles() as ours, smoke.count_compiles() as theirs:
        fn(v).block_until_ready()
        fn(v).block_until_ready()
    assert ours[0] == theirs[0] == 1


@pytest.mark.pallas
@pytest.mark.parametrize("upper", [False, True])
def test_structural_pairs_equal_the_engine_pairs(upper):
    from repro import Session

    pts, rows, cols, n = data.overlap_problem(overlap_cfg())
    sess = Session(lazy=True, engine="pallas", leaf_n=16, bs=8)
    x = sess.from_pattern(rows, cols, n, upper=upper, name="X",
                          value_fn=data.overlap_values(pts, 4.0))
    sess.compile(x.sym_square() if upper else x @ x).run()
    stats = sess.engine_stats()
    work = reference.product_work(rows, cols, 8, upper=upper)
    assert work["pairs"] == stats["batched_pairs"] > 0
    ref = reference.product_reference(reference.sparse_matrix(
        rows, cols, n, data.overlap_values(pts, 4.0)))
    assert work["out_blocks"] == sum(
        len(b) for b in reference.csr_blocks(ref, 8, upper=upper).values())
    assert work["flops"] == 2.0 * 8 ** 3 * work["pairs"]


def test_product_control_reads_above_the_limit():
    pts, rows, cols, n = data.overlap_problem(overlap_cfg())
    x = reference.sparse_matrix(rows, cols, n, data.overlap_values(pts, 4.0))
    ref = reference.product_reference(x)
    limit = overlap_cfg()["limits"]["row_err"]
    x32 = x.copy()
    x32.data = x32.data.astype(np.float32).astype(np.float64)
    program_like = reference.csr_blocks(reference.product_reference(x32), 8)
    ctrl = reference.csr_blocks(reference.product_control(x), 8)
    assert reference.block_errors(program_like, ref, 8)["row_err"] < limit
    assert reference.block_errors(ctrl, ref, 8)["row_err"] > 3 * limit


def test_reader_falls_back_to_the_layer_quantity():
    """``api_ms.<cell>`` reads with ``metrics/api_ms.py``; a reader of the
    metric's own name comes first."""
    w = harness.Window(ops=1, window_s=1.0, compiles=3)
    assert not (CHIP / "metrics" / "compiles.replay.py").exists()
    assert harness.load_reader("compiles.replay")(w) == 3
    assert harness.load_reader("compiles.symsq")(w) == 3
    with pytest.raises(FileNotFoundError):
        harness.load_reader("no_such_quantity.replay")


def test_peaks_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "source" in v5e
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")


def test_trace_reduction_on_a_recorded_chip_trace():
    dev = xtrace.reduce_xplane(TESTDATA)
    assert dev.devices == 1
    assert 0.0 < dev.busy_s < dev.window_s
    assert dev.kernel_s("bsmm_pairs") > 0.0
    assert sum(dev.op_s.values()) >= dev.busy_s * (1 - 1e-9)
    idle = dev.window_s - dev.busy_s
    assert sum(g for _, g in dev.gaps) == pytest.approx(idle, rel=1e-6)
    assert {label for label, _ in dev.gaps} <= {
        "bench.window", "bench.plan_run", "bench.flush"}
    out = dev.breakdown()
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert out["idle_gaps"][0][1] == max(g for _, g in dev.gaps)


def test_span_self_time():
    from repro.obs.tracer import Span

    spans = [Span("plan.run", 0.0, 10.0, depth=0),
             Span("plan.rebind", 0.0, 2.0, depth=1),
             Span("plan.replay", 2.0, 9.0, depth=1),
             Span("engine.flush", 3.0, 8.0, depth=2),
             Span("engine.wave", 3.0, 7.0, depth=3),
             Span("kernel.dispatch", 4.0, 6.0, depth=4),
             Span("qt.from_dense", 11.0, 12.0, depth=0)]
    w = harness.Window(ops=2, window_s=20.0, compiles=0, spans=spans,
                       op_times=[(0.0, 10.5), (10.5, 13.0)])
    # plan.run 1 + rebind 2 + replay 2 + qt 1
    assert w.self_s(("plan.", "qt.")) == pytest.approx(6.0)
    assert w.self_s(("engine.",)) == pytest.approx(1.0 + 2.0)
    assert w.total_s(("kernel.dispatch",)) == pytest.approx(2.0)
    assert w.outside_spans_s() == pytest.approx(0.5 + 1.5)
    assert w.per_op_ms(w.total_s(("kernel.dispatch",))) == 1000.0
    assert w.self_s(("serve.",)) is None


def test_benchmark_json_names_what_exists():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and all(m["bound"] <= 0.25 for m in e2e.values())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert cell.chips == w["chips"]
        assert cell.traffic["op_metric"] in e2e
        reported = {m["name"] for m in cell.end_to_end}
        assert reported == {"setup_s", cell.traffic["op_metric"]}
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in reported
            assert callable(harness.load_reader(m["name"]))
    # at most half the cells, rounded down, on four chips; one always may
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
