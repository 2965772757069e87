"""One operand table per wave (``core/engine.py`` ``_pack_wave``).

Both sides of a wave's pairs index one float32 table with a slot per
distinct (leaf, key, transpose) block, filled by a cast on copy.  These
tests hold it to the packing it replaced — separate A and B stacks of
float64 blocks, then ``astype(float32)``, kept below as the oracle — bit
for bit, and check the ``shared_blocks`` counter that says how often the
sharing engages.
"""
import numpy as np
import pytest

from repro import Session
from repro.core import engine
from repro.core.patterns import banded_mask, values_for_mask
from repro.serve import WaveCoalescer

pytestmark = pytest.mark.pallas

PRODUCTS = ["matmul", "sym_square", "disjoint"]


def _oracle_pack(tasks):
    """The per-side packing: ``(a_pack, b_pack, sa, sb, seg, n_slots)``."""
    n_pairs = sum(len(t.pairs) for t in tasks)
    a_slots, b_slots = {}, {}
    a_list, b_list = [], []

    def slot_of(slots, lst, leaf, key, tr):
        sk = (id(leaf), key, tr)
        s = slots.get(sk)
        if s is None:
            s = len(lst)
            slots[sk] = s
            blk = leaf.blocks[key]
            lst.append(blk.T if tr else blk)
        return s

    sa = np.empty((n_pairs,), np.int32)
    sb = np.empty((n_pairs,), np.int32)
    seg = np.empty((n_pairs,), np.int32)
    p = 0
    n_slots = 0
    out_slots = {}      # tasks of a folded product share their out leaf
    for t in tasks:
        if id(t.out) not in out_slots:
            out_slots[id(t.out)] = {key: n_slots + i
                                    for i, key in enumerate(t.out.blocks)}
            n_slots += len(t.out.blocks)
        key_slot = out_slots[id(t.out)]
        srcs = {"a": t.a_leaf, "b": t.b_leaf}
        for src_a, ka, tra, src_b, kb, trb, out_key in t.pairs:
            sa[p] = slot_of(a_slots, a_list, srcs[src_a], ka, tra)
            sb[p] = slot_of(b_slots, b_list, srcs[src_b], kb, trb)
            seg[p] = key_slot[out_key]
            p += 1
    a_pack = np.stack(a_list).astype(np.float32)
    b_pack = np.stack(b_list).astype(np.float32)
    order = np.argsort(seg, kind="stable")
    return a_pack, b_pack, sa[order], sb[order], seg[order], n_slots


def _oracle_as_table(tasks):
    """The oracle's two stacks laid end to end as one table, so the
    dispatch feeds the kernel exactly the oracle's operands."""
    a_pack, b_pack, sa, sb, seg, n_slots = _oracle_pack(tasks)
    return (np.concatenate([a_pack, b_pack]), sa, sb + len(a_pack), seg,
            n_slots, 0)


def _banded(seed):
    a = values_for_mask(banded_mask(64, 9), seed=seed)
    return (a + a.T) / 2


def _replay(product, sess=None, flush=True):
    """Compile ``product`` on a pallas session, run it, rebind new values
    and replay; returns ``(session, output matrix)``.  ``flush=False``
    leaves the replay's waves pending."""
    sess = sess or Session(engine="pallas", lazy=True, leaf_n=16, bs=8)
    x = sess.from_dense(_banded(1), upper=product == "sym_square",
                        name="X")
    if product == "disjoint":
        y = sess.from_dense(_banded(2) @ np.diag(np.arange(1.0, 65.0)),
                            name="Y")
        plan = sess.compile(x @ y)
        rebind = {"X": _banded(3), "Y": _banded(4)}
    else:
        plan = sess.compile(x.sym_square() if product == "sym_square"
                            else x @ x)
        rebind = {"X": _banded(3)}
    plan.run()
    sess.flush()
    out = plan.run(**rebind, flush=flush)
    return sess, out


@pytest.fixture
def packs(monkeypatch):
    """Record ``(tasks, new packing, oracle packing)`` of every wave."""
    seen = []
    pack = engine._pack_wave

    def spy(tasks):
        got = pack(tasks)
        seen.append((tasks, got, _oracle_pack(tasks)))
        return got

    monkeypatch.setattr(engine, "_pack_wave", spy)
    return seen


def _assert_same_operands(got, want):
    table, sa, sb, seg, n_slots, _ = got
    a_pack, b_pack, osa, osb, oseg, on_slots = want
    assert table.dtype == np.float32 and table.flags.c_contiguous
    assert n_slots == on_slots
    np.testing.assert_array_equal(seg, oseg)
    assert table[sa].tobytes() == a_pack[osa].tobytes()
    assert table[sb].tobytes() == b_pack[osb].tobytes()


def _touched_leaves(tasks):
    leaves = {id(lf): lf for t in tasks for lf in (t.a_leaf, t.b_leaf)
              if lf is not None}
    return leaves.values()


@pytest.mark.parametrize("product", PRODUCTS)
def test_table_gathers_the_oracle_operands(product, packs):
    _replay(product)
    assert packs
    for _, got, want in packs:
        _assert_same_operands(got, want)


def test_coalesced_two_engine_wave_gathers_the_oracle_operands(packs):
    sessions = [_replay("matmul", flush=False)[0] for _ in range(2)]
    packs.clear()
    co = WaveCoalescer()
    assert co.flush([s.graph for s in sessions]) >= 1
    assert co.merged_waves >= 1
    (tasks, got, want), = packs
    _assert_same_operands(got, want)
    table, shared = got[0], got[5]
    # id(leaf) keys keep the two engines' equal-valued blocks apart
    assert len(table) == shared == sum(
        len(lf.blocks) for lf in _touched_leaves(tasks))
    assert co.waves[0]["shared_blocks"] == shared


@pytest.mark.parametrize("product", PRODUCTS)
def test_replayed_product_is_bitwise_the_oracle_packings(product,
                                                         monkeypatch):
    want = _replay(product)[1].to_dense()
    monkeypatch.setattr(engine, "_pack_wave", _oracle_as_table)
    got = _replay(product)[1].to_dense()
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("product", PRODUCTS)
def test_shared_blocks_counter(product, packs):
    sess, _ = _replay(product)
    wave = sess.graph.engine.stats()["wave_log"][-1]
    tasks, (table, *_, shared), (a_pack, b_pack, *_) = packs[-1]
    assert wave["unique_blocks"] == len(table)
    assert wave["shared_blocks"] == shared
    assert wave["bytes_packed"] == table.nbytes + wave["c_blocks"] * 256
    if product == "disjoint":
        # nothing to share: the table holds both sides whole
        assert shared == 0
        assert len(table) == len(a_pack) + len(b_pack)
    else:
        # X @ X and X.sym_square() read every orientation on both sides
        assert shared == len(table) == len(a_pack) == len(b_pack)
    if product == "matmul":
        assert len(table) == sum(
            len(lf.blocks) for lf in _touched_leaves(tasks))


@pytest.mark.parametrize("product", ["matmul", "disjoint"])
def test_pack_span_carries_the_counters(product):
    sess = Session(engine="pallas", lazy=True, leaf_n=16, bs=8)
    with sess.tracing() as tr:
        _replay(product, sess=sess)
    pack = tr.find("engine.wave.pack")[-1]
    wave = sess.graph.engine.stats()["wave_log"][-1]
    assert pack.attrs["unique_blocks"] == wave["unique_blocks"]
    assert pack.attrs["shared_blocks"] == wave["shared_blocks"]
    assert (pack.attrs["shared_blocks"] == 0) == (product == "disjoint")
