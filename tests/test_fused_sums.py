"""Partial sums in the kernel (``core/engine.py`` ``fold_roots``).

A product's leaf-level add tree is folded into the wave that computes its
partials: every multiply under a root add writes the root's output leaf,
so all partials of one output block share one wave segment and
``bsmm_pairs`` sums them.  These tests hold the folded engine to the
numpy engine, count the slots and the folded adds, and check the adds
that must not fold: a partial with a second reader, a host-kind input, a
result a ``Matrix`` holds, and every add on the mesh engine.
"""
import numpy as np
import pytest

from repro import Session
from repro.core import engine as eng_mod
from repro.core.engine import KERNEL_KINDS, LeafPayload, PallasEngine
from repro.core.multiply import qt_add, qt_multiply
from repro.core.patterns import banded_mask, values_for_mask
from repro.core.quadtree import QTParams, qt_from_dense, qt_to_dense
from repro.core.tasks import Alias, CTGraph, Dep
from repro.obs.tracer import Tracer
from repro.serve import WaveCoalescer

pytestmark = pytest.mark.pallas

N, LEAF, BS = 128, 16, 8        # 8 x 8 leaves, three quadtree levels


def _sym(seed, n=N, band=13):
    a = values_for_mask(banded_mask(n, band), seed=seed)
    return (a + a.T) / 2


def _rel(got, want):
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def _square(sess, product, a):
    x = sess.from_dense(a, upper=product == "sym_square", name="X")
    return x, sess.compile(x.sym_square() if product == "sym_square"
                           else x @ x)


def _waves(sess):
    return sess.graph.engine.stats()["wave_log"]


@pytest.mark.parametrize("product", ["matmul", "sym_square"])
class TestFoldedSquare:

    def test_agrees_with_numpy_engine(self, product):
        a = _sym(1)
        outs = {}
        for engine in ("numpy", "pallas"):
            sess = Session(engine=engine, lazy=True, leaf_n=LEAF, bs=BS)
            _, plan = _square(sess, product, a)
            outs[engine] = plan.run().to_dense()
        assert _rel(outs["pallas"], outs["numpy"]) < 1e-6

    def test_one_slot_per_output_block(self, product):
        sess = Session(engine="pallas", lazy=True, leaf_n=LEAF, bs=BS)
        _, plan = _square(sess, product, _sym(1))
        out = plan.run()
        sess.flush()
        wave, = _waves(sess)
        assert wave["c_blocks"] == out.nnz_blocks()
        assert wave["fused_adds"] > 0
        assert wave["fused_adds"] == sess.n_add_tasks - _internal_adds(
            sess)

    def test_replays_run_no_host_fill(self, product):
        sess = Session(engine="pallas", lazy=True, leaf_n=LEAF, bs=BS)
        _, plan = _square(sess, product, _sym(1))
        plan.run()
        sess.flush()
        tr = Tracer()
        with sess.tracing(tr):
            plan.run(X=_sym(2), flush=False)
            sess.flush()
        assert tr.find("engine.flush.host") == []
        wave, = tr.find("engine.wave")
        assert wave.attrs["fused_adds"] > 0
        run, = tr.find("kernel.run")
        assert run.attrs["cap_c"] == wave.attrs["c_blocks"]

    def test_two_replays_on_new_values(self, product):
        sess = Session(engine="pallas", lazy=True, leaf_n=LEAF, bs=BS)
        _, plan = _square(sess, product, _sym(1))
        plan.run()
        for seed in (2, 3):
            b = _sym(seed)
            got = plan.run(X=b).to_dense()
            assert _rel(got, b @ b) < 1e-6

    def test_replays_reuse_the_first_decision(self, product, monkeypatch):
        sess = Session(engine="pallas", lazy=True, leaf_n=LEAF, bs=BS)
        _, plan = _square(sess, product, _sym(1))
        plan.run()
        sess.flush()
        calls = []
        walk = eng_mod.fold_roots
        monkeypatch.setattr(eng_mod, "fold_roots",
                            lambda g, cand: calls.append(1) or walk(g, cand))
        plan.run(X=_sym(2))
        assert calls == []
        assert _waves(sess)[-1]["fused_adds"] == _waves(sess)[0]["fused_adds"]


def _internal_adds(sess):
    """Add tasks above the leaf level (identifier shuffling, no payload)."""
    return sum(1 for n in sess.graph.nodes
               if n.kind == "add" and n.payload is None)


def test_partial_read_twice_is_not_folded():
    """P = X @ X feeds both the add and the product P @ X: its leaves
    are filled, and the add of P + P @ X stays a host add."""
    a = _sym(1)
    sess = Session(engine="pallas", lazy=True, leaf_n=LEAF, bs=BS)
    x = sess.from_dense(a, name="X")
    p = x @ x
    plan = sess.compile(p + p @ x)
    tr = Tracer()
    with sess.tracing(tr):
        got = plan.run().to_dense()
    want = a @ a + a @ a @ a
    assert _rel(got, want) < 1e-6
    assert sum(h.attrs["adds"] for h in tr.find("engine.flush.host")) > 0
    assert all(w["fused_adds"] > 0 for w in _waves(sess))


def _program(g, body):
    """Register ``body`` as the child program of one top-level task, so its
    results are intermediates no caller holds."""
    return g.register_task("program", lambda: Alias(body()), [])


def _leaf_graph():
    g = CTGraph(engine=PallasEngine())
    params = QTParams(LEAF, LEAF, BS)
    a, b = _sym(1, LEAF, 5), _sym(2, LEAF, 5) * 0.5
    return g, params, a, b, (qt_from_dense(g, a, params),
                             qt_from_dense(g, b, params))


def test_hand_registered_single_reader_folds():
    g, params, a, b, (x, y) = _leaf_graph()
    top = _program(g, lambda: qt_add(g, params, qt_multiply(g, params, x, y),
                                     qt_multiply(g, params, y, x)))
    got = qt_to_dense(g, top, params)
    assert _rel(got, a @ b + b @ a) < 1e-6
    wave, = g.engine.stats()["wave_log"]
    assert wave["tasks"] == 2 and wave["fused_adds"] == 1
    assert wave["c_blocks"] == len(g.value_of(top).leaf.blocks)


def test_hand_registered_second_reader_keeps_the_add():
    g, params, a, b, (x, y) = _leaf_graph()

    def body():
        p = qt_multiply(g, params, x, y)
        return qt_add(g, params, p, qt_multiply(g, params, p, x))

    top = _program(g, body)
    got = qt_to_dense(g, top, params)
    assert _rel(got, a @ b + a @ b @ a) < 1e-6
    assert g.engine._root_of == {}
    assert all(w["fused_adds"] == 0 for w in g.engine.stats()["wave_log"])


def test_host_kind_input_keeps_the_add():
    """SP2's 2X - X^2: the scale output is a host-kind input."""
    a = _sym(1) * 0.1
    sess = Session(engine="pallas", lazy=True, leaf_n=LEAF, bs=BS)
    x = sess.from_dense(a, name="X")
    plan = sess.compile(2.0 * x - x @ x)
    tr = Tracer()
    with sess.tracing(tr):
        got = plan.run().to_dense()
    assert _rel(got, 2.0 * a - a @ a) < 1e-6
    assert sum(h.attrs["adds"] for h in tr.find("engine.flush.host")) > 0
    # the square's own add tree still folds
    assert _waves(sess)[0]["fused_adds"] > 0


def test_a_held_result_is_never_folded_away():
    """Eager P = X @ X, then R = P + Y @ Y in the same flush: P's leaves
    feed only R's adds, but a Matrix holds them, so they are filled."""
    a, b = _sym(1), _sym(2)
    sess = Session(engine="pallas", leaf_n=LEAF, bs=BS)
    x, y = sess.from_dense(a), sess.from_dense(b)
    p = x @ x
    r = p + y @ y
    assert _rel(r.to_dense(), a @ a + b @ b) < 1e-6
    assert _rel(p.to_dense(), a @ a) < 1e-6


@pytest.mark.parametrize("n", [LEAF, N])
def test_a_raw_top_level_result_is_never_folded_away(n):
    """A raw caller's P = X @ X, registered at top level before the
    program that reads it once: P's whole tree is newer than P's own
    node, and the flush that fills the program still fills P."""
    g = CTGraph(engine=PallasEngine())
    params = QTParams(n, LEAF, BS)
    a, b = _sym(1, n, 5), _sym(2, n, 5) * 0.5
    x, y = qt_from_dense(g, a, params), qt_from_dense(g, b, params)
    p = qt_multiply(g, params, x, x)
    top = _program(g, lambda: qt_add(g, params, p,
                                     qt_multiply(g, params, y, y)))
    assert _rel(qt_to_dense(g, top, params), a @ a + b @ b) < 1e-6
    assert _rel(qt_to_dense(g, p, params), a @ a) < 1e-6


def test_a_flush_inside_a_registration_folds_nothing_it_holds():
    """A SpAMM product flushes at its root entry, inside the program:
    P's leaves then feed one add, but the open program reads P again
    after the flush, so P must have been filled."""
    g = CTGraph(engine=PallasEngine())
    params = QTParams(N, LEAF, BS)
    a, b = _sym(1, N, 5), _sym(2, N, 5) * 0.5
    x, y = qt_from_dense(g, a, params), qt_from_dense(g, b, params)

    def body():
        p = qt_multiply(g, params, x, x)
        r = qt_add(g, params, p, qt_multiply(g, params, y, y))
        qt_multiply(g, params, y, y, tau=1e-30)
        return qt_add(g, params, r, p)

    top = _program(g, body)
    assert _rel(qt_to_dense(g, top, params), 2 * a @ a + b @ b) < 1e-6


def test_folded_partials_stay_unfilled_and_a_reader_raises():
    sess = Session(engine="pallas", lazy=True, leaf_n=LEAF, bs=BS)
    _, plan = _square(sess, "matmul", _sym(1))
    plan.run()
    sess.flush()
    g, e = sess.graph, sess.graph.engine
    pid = next(nid for nid in e._root_of
               if g.nodes[nid].kind in KERNEL_KINDS)
    assert e.is_unfilled(g.value_of(pid).leaf)
    with pytest.raises(RuntimeError, match="never computed"):
        qt_to_dense(g, pid, sess.params_for(N))
    g.register_task("scale", None, [Dep(pid)],
                    payload=LeafPayload("scale", a=pid, alpha=2.0))
    with pytest.raises(RuntimeError, match="deadlock"):
        g.flush()


def test_coalesced_folded_waves_are_bitwise_the_solo_ones():
    def pending():
        sess = Session(engine="pallas", lazy=True, leaf_n=LEAF, bs=BS)
        _, plan = _square(sess, "matmul", _sym(1))
        plan.run()
        sess.flush()
        return sess, plan.run(X=_sym(2), flush=False)

    solo_sess, solo = pending()
    want = solo.to_dense()
    pairs = [pending() for _ in range(2)]
    co = WaveCoalescer()
    co.flush([s.graph for s, _ in pairs])
    assert co.merged_waves == 1
    for sess, out in pairs:
        assert out.to_dense().tobytes() == want.tobytes()
        assert _waves(sess)[-1]["fused_adds"] == \
            _waves(solo_sess)[-1]["fused_adds"] > 0
        assert _waves(sess)[-1]["c_blocks"] == out.nnz_blocks()


def test_mesh_waves_keep_per_task_slots():
    from repro.launch.mesh_exec import MeshEngine

    a = _sym(1)
    sess = Session(engine=MeshEngine(n_dev=1), leaf_n=LEAF, bs=BS)
    x = sess.from_dense(a)
    out = x @ x
    assert _rel(out.to_dense(), a @ a) < 1e-6
    g = sess.graph
    per_task = sum(len(g.value_of(n.nid).leaf.blocks) for n in g.nodes
                   if n.payload is not None
                   and n.payload.kind in KERNEL_KINDS)
    wave, = _waves(sess)
    assert wave["c_blocks"] == per_task > out.nnz_blocks()
    assert "fused_adds" not in wave
    assert g.engine._root_of == {}
