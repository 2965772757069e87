"""Leaf execution engine: numpy reference vs Pallas batched backend.

Every quadtree operation is run through both backends on the paper's pattern
families (random, banded, and the S2 electronic-structure overlap pattern)
and checked against dense numpy.  The pallas backend runs the actual kernel
bodies in interpret mode on CPU, with cross-leaf batched waves.
"""
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.core.engine import (LeafPayload, NumpyEngine, PallasEngine,
                               leaf_task_pairs, make_engine)
from repro.core.leaf import LeafMatrix, alloc_structure, unpack_blocks
from repro.core.multiply import (count_tasks_per_level, qt_add, qt_multiply,
                                 qt_sym_multiply, qt_sym_square, qt_syrk,
                                 total_flops, total_multiply_tasks)
from repro.core.patterns import (banded_mask, divide_space_order,
                                 overlap_mask, particle_cloud, random_mask,
                                 random_symmetric_mask, values_for_mask)
from repro.core.quadtree import QTParams, qt_from_dense, qt_to_dense
from repro.core.tasks import ClusterSim, CTGraph

PARAMS = QTParams(n=64, leaf_n=16, bs=4)
TOL = dict(atol=1e-4, rtol=1e-4)   # pallas packs float32; numpy is float64


def _s2_mask(n=64):
    """The paper's §6.2 application pattern: 3-D particle-cloud overlap
    matrix in recursive divide-space ordering (symmetric by construction)."""
    coords = particle_cloud(4, 3, seed=7)          # 64 basis functions
    order = divide_space_order(coords)
    return overlap_mask(coords, 4.0, order=order)


PATTERNS = {
    "random": lambda: random_mask(64, 0.12, seed=3),
    "banded": lambda: banded_mask(64, 6),
    "s2": _s2_mask,
}
ENGINES = ["pallas-pairs", "pallas-gemm"]


def _engine(spec):
    if spec == "pallas-pairs":
        return PallasEngine(kernel="pairs")
    if spec == "pallas-gemm":
        return PallasEngine(kernel="gemm")
    return make_engine(spec)


def _both(build, check):
    """Run ``build(g) -> root id`` under each backend and check results."""
    outs = {}
    graphs = {}
    for spec in ["numpy"] + ENGINES:
        g = CTGraph(engine=_engine(spec))
        rc = build(g)
        outs[spec] = qt_to_dense(g, rc, PARAMS)
        graphs[spec] = g
    for spec in ENGINES:
        np.testing.assert_allclose(outs[spec], outs["numpy"], **TOL)
    check(outs["numpy"])
    return graphs


@pytest.mark.pallas
class TestMultiplyEquivalence:
    @pytest.mark.parametrize("pattern", sorted(PATTERNS))
    def test_multiply(self, pattern):
        a = values_for_mask(PATTERNS[pattern](), seed=1)
        b = values_for_mask(PATTERNS[pattern](), seed=2)

        def build(g):
            ra = qt_from_dense(g, a, PARAMS)
            rb = qt_from_dense(g, b, PARAMS)
            return qt_multiply(g, PARAMS, ra, rb)

        _both(build, lambda out: np.testing.assert_allclose(out, a @ b,
                                                            atol=1e-10))

    @pytest.mark.parametrize("ta,tb", [(True, False), (False, True),
                                       (True, True)])
    def test_multiply_transposes(self, ta, tb):
        a = values_for_mask(banded_mask(64, 5), seed=4)
        b = values_for_mask(random_mask(64, 0.1, seed=5), seed=5)

        def build(g):
            ra = qt_from_dense(g, a, PARAMS)
            rb = qt_from_dense(g, b, PARAMS)
            return qt_multiply(g, PARAMS, ra, rb, ta=ta, tb=tb)

        want = (a.T if ta else a) @ (b.T if tb else b)
        _both(build, lambda out: np.testing.assert_allclose(out, want,
                                                            atol=1e-10))

    def test_add(self):
        a = values_for_mask(banded_mask(64, 4), seed=6)
        b = values_for_mask(random_mask(64, 0.08, seed=7), seed=7)

        def build(g):
            ra = qt_from_dense(g, a, PARAMS)
            rb = qt_from_dense(g, b, PARAMS)
            return qt_add(g, PARAMS, ra, rb)

        _both(build, lambda out: np.testing.assert_allclose(out, a + b,
                                                            atol=1e-12))

    def test_all_zero_leaves_and_nil_quadrants(self):
        # middle band of rows zero -> whole leaf rows NIL; only the upper-left
        # quadrant of B occupied -> three root children NIL
        a = values_for_mask(banded_mask(64, 6), seed=8)
        a[16:48, :] = 0.0
        b = np.zeros((64, 64))
        b[:32, :32] = values_for_mask(random_mask(32, 0.3, seed=9), seed=9)

        def build(g):
            ra = qt_from_dense(g, a, PARAMS)
            rb = qt_from_dense(g, b, PARAMS)
            return qt_multiply(g, PARAMS, ra, rb)

        _both(build, lambda out: np.testing.assert_allclose(out, a @ b,
                                                            atol=1e-10))

    def test_disjoint_product_is_structurally_nil(self):
        a = np.zeros((64, 64)); a[:16, 48:] = 1.0
        b = np.zeros((64, 64)); b[:16, :16] = 1.0
        for spec in ["numpy"] + ENGINES:
            g = CTGraph(engine=_engine(spec))
            ra = qt_from_dense(g, a, PARAMS)
            rb = qt_from_dense(g, b, PARAMS)
            rc = qt_multiply(g, PARAMS, ra, rb)
            assert rc is None or np.allclose(qt_to_dense(g, rc, PARAMS), 0)


@pytest.mark.pallas
class TestSymmetricEquivalence:
    @pytest.mark.parametrize("pattern", sorted(PATTERNS))
    def test_sym_square(self, pattern):
        mask = PATTERNS[pattern]()
        s = values_for_mask(mask | mask.T, seed=11, symmetric=True)

        def build(g):
            rs = qt_from_dense(g, s, PARAMS, upper=True)
            return qt_sym_square(g, PARAMS, rs)

        _both(build, lambda out: np.testing.assert_allclose(out, s @ s,
                                                            atol=1e-10))

    @pytest.mark.parametrize("trans", [False, True])
    def test_syrk(self, trans):
        a = values_for_mask(banded_mask(64, 6), seed=12)

        def build(g):
            ra = qt_from_dense(g, a, PARAMS)
            return qt_syrk(g, PARAMS, ra, trans=trans)

        want = a.T @ a if trans else a @ a.T
        _both(build, lambda out: np.testing.assert_allclose(out, want,
                                                            atol=1e-10))

    @pytest.mark.parametrize("side", ["left", "right"])
    def test_sym_multiply(self, side):
        s = values_for_mask(random_symmetric_mask(64, 0.1, seed=13),
                            seed=13, symmetric=True)
        b = values_for_mask(banded_mask(64, 5), seed=14)

        def build(g):
            rs = qt_from_dense(g, s, PARAMS, upper=True)
            rb = qt_from_dense(g, b, PARAMS)
            return qt_sym_multiply(g, PARAMS, rs, rb, side=side)

        want = s @ b if side == "left" else b @ s
        _both(build, lambda out: np.testing.assert_allclose(out, want,
                                                            atol=1e-10))


@pytest.mark.pallas
class TestGraphInvariance:
    """The executor refactor must not change the task graph: structure,
    counts and flop attribution are backend-independent."""

    def _graphs(self):
        a = values_for_mask(banded_mask(64, 5), seed=20)
        b = values_for_mask(random_mask(64, 0.1, seed=21), seed=21)

        def build(g):
            ra = qt_from_dense(g, a, PARAMS)
            rb = qt_from_dense(g, b, PARAMS)
            return qt_multiply(g, PARAMS, ra, rb)

        return _both(build, lambda out: None)

    def test_task_counts_and_flops_match(self):
        graphs = self._graphs()
        ref = graphs["numpy"]
        for spec in ENGINES:
            g = graphs[spec]
            assert total_multiply_tasks(g) == total_multiply_tasks(ref)
            assert count_tasks_per_level(g) == count_tasks_per_level(ref)
            assert total_flops(g) == pytest.approx(total_flops(ref))
            assert g.count_kinds() == ref.count_kinds()

    def test_wave_stats_account_for_all_pairs(self):
        graphs = self._graphs()
        for spec in ENGINES:
            g = graphs[spec]
            st_ = g.engine.stats()
            assert st_["waves"] >= 1
            bs = PARAMS.bs
            # every structural pair ran in a batched wave, exactly once
            assert st_["batched_pairs"] == total_flops(g) / (2.0 * bs ** 3)
            assert st_["padded_pairs"] >= st_["batched_pairs"]
            assert st_["dispatch_s"] > 0.0

    def test_cluster_sim_equivalent_across_backends(self):
        """Same task graph + flops => same simulated schedule; makespans
        agree to the (small) fetch-time delta from pallas chunks being
        float32 (half the bytes of numpy's float64 leaves)."""
        a = values_for_mask(banded_mask(64, 5), seed=22)
        results = {}
        for spec in ["numpy", "pallas-pairs"]:
            g = CTGraph(engine=_engine(spec))
            ra = qt_from_dense(g, a, PARAMS)
            rb = qt_from_dense(g, a, PARAMS)
            sim = ClusterSim(4, seed=0)
            sim.run(g)
            sim.reset_stats()
            qt_multiply(g, PARAMS, ra, rb)
            results[spec] = sim.run(g)
        ref, got = results["numpy"], results["pallas-pairs"]
        assert sum(got.tasks_per_worker) == sum(ref.tasks_per_worker)
        assert got.makespan == pytest.approx(ref.makespan, rel=0.02)


@pytest.mark.pallas
class TestEngineUnit:
    def test_make_engine_specs(self):
        assert isinstance(make_engine(None), NumpyEngine)
        assert isinstance(make_engine("numpy"), NumpyEngine)
        assert isinstance(make_engine("pallas"), PallasEngine)
        e = PallasEngine(kernel="gemm")
        assert make_engine(e) is e
        with pytest.raises(ValueError):
            make_engine("cuda")

    def test_leaf_task_pairs_matches_leafstats(self):
        """Structural pair count == the numpy backend's block_multiplies."""
        from repro.core.leaf import LeafStats, leaf_multiply, leaf_sym_square
        a = LeafMatrix.from_dense(
            values_for_mask(random_mask(16, 0.4, seed=30), seed=30), 4)
        b = LeafMatrix.from_dense(
            values_for_mask(random_mask(16, 0.4, seed=31), seed=31), 4)
        stats = LeafStats()
        leaf_multiply(a, b, stats=stats)
        pairs, upper = leaf_task_pairs(LeafPayload("multiply"), a, b)
        assert not upper and len(pairs) == stats.block_multiplies

        s = values_for_mask(random_symmetric_mask(16, 0.4, seed=32),
                            seed=32, symmetric=True)
        su = LeafMatrix.from_dense(s, 4, upper=True)
        stats = LeafStats()
        leaf_sym_square(su, stats=stats)
        pairs, upper = leaf_task_pairs(LeafPayload("sym_square"), su, None)
        assert upper and len(pairs) == stats.block_multiplies

    def test_structure_matches_compute_c_structure(self):
        """Pure-Python output structure == the bsmm boolean-matmul structure
        (validate_structure cross-checks every leaf task at registration)."""
        a = values_for_mask(random_mask(64, 0.15, seed=40), seed=40)
        s = values_for_mask(random_symmetric_mask(64, 0.15, seed=41),
                            seed=41, symmetric=True)
        g = CTGraph(engine=PallasEngine(validate_structure=True))
        ra = qt_from_dense(g, a, PARAMS)
        rb = qt_from_dense(g, a, PARAMS)
        qt_multiply(g, PARAMS, ra, rb, tb=True)
        rs = qt_from_dense(g, s, PARAMS, upper=True)
        qt_sym_square(g, PARAMS, rs)
        g.flush()   # would have asserted on any structure mismatch

    @pytest.mark.parametrize("spec", ["numpy"] + ENGINES)
    def test_upper_operand_to_plain_multiply_rejected(self, spec):
        """Both backends refuse a plain multiply on upper-storage leaves
        (the host-library contract) instead of silently dropping the
        mirrored lower triangle."""
        s = values_for_mask(random_symmetric_mask(64, 0.2, seed=35),
                            seed=35, symmetric=True)
        b = values_for_mask(banded_mask(64, 4), seed=36)
        g = CTGraph(engine=_engine(spec))
        rs = qt_from_dense(g, s, PARAMS, upper=True)
        rb = qt_from_dense(g, b, PARAMS)
        with pytest.raises(AssertionError):
            qt_multiply(g, PARAMS, rs, rb)

    def test_alloc_unpack_roundtrip(self):
        a = LeafMatrix.from_dense(
            values_for_mask(banded_mask(16, 3), seed=33), 4)
        keys = list(a.blocks)
        out = alloc_structure(16, 4, keys)
        assert list(out.blocks) == keys
        assert all(np.all(blk == 0) for blk in out.blocks.values())
        held = [out.blocks[k] for k in keys]    # downstream references
        unpack_blocks(out, keys, np.stack([a.blocks[k] for k in keys]))
        np.testing.assert_allclose(out.to_dense(), a.to_dense())
        # in-place fill: previously-taken references see the new data
        assert all(h is out.blocks[k] for h, k in zip(held, keys))

    def test_engine_instance_bound_to_one_graph(self):
        a = values_for_mask(banded_mask(64, 3), seed=34)
        e = PallasEngine()
        g1 = CTGraph(engine=e)
        ra = qt_from_dense(g1, a, PARAMS)
        qt_multiply(g1, PARAMS, ra, ra)
        g2 = CTGraph(engine=e)
        rb = qt_from_dense(g2, a, PARAMS)
        with pytest.raises(ValueError, match="one engine per graph"):
            qt_multiply(g2, PARAMS, rb, rb)


@pytest.mark.pallas
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), fill=st.floats(0.05, 0.4),
       kernel=st.sampled_from(["pairs", "gemm"]))
def test_property_engine_equivalence(seed, fill, kernel):
    a = values_for_mask(random_mask(64, fill, seed=seed), seed=seed)
    b = values_for_mask(random_mask(64, fill, seed=seed + 1), seed=seed + 1)
    outs = {}
    for eng in ("numpy", kernel):
        spec = "numpy" if eng == "numpy" else PallasEngine(kernel=kernel)
        g = CTGraph(engine=spec)
        ra = qt_from_dense(g, a, PARAMS)
        rb = qt_from_dense(g, b, PARAMS)
        rc = qt_multiply(g, PARAMS, ra, rb)
        outs[eng] = qt_to_dense(g, rc, PARAMS)
    np.testing.assert_allclose(outs[kernel], outs["numpy"], **TOL)
    np.testing.assert_allclose(outs["numpy"], a @ b, atol=1e-10)
