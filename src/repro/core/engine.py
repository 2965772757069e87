"""Leaf execution engine: pluggable backends for leaf-level multiply work.

The paper's performance story (§4.1) is that leaf-level multiplication work
is *batched* and offloaded: "in case GPUs are available, both CPUs and GPUs
are used for leaf-level multiplication work", with the small block GEMMs
mapped onto the cuBLAS batched-gemm API.  This module is the repo's rendering
of that pluggable leaf engine:

* :class:`NumpyEngine` — the reference backend; executes each leaf task
  immediately with the host library (core/leaf.py), preserving the original
  per-task semantics exactly.
* :class:`PallasEngine` — the accelerator backend.  Leaf multiply/syrk/
  sym_square/sym_multiply tasks are *not* executed at registration: their
  output **structure** is computed up front (via
  :func:`repro.core.bsmm.compute_c_structure` on the leaf occupancy masks —
  the create-from-ids tree collapsed to one boolean matmul) and zero
  placeholder blocks are allocated, while the numeric work is deferred.  At
  flush time the engine harvests *all* pending leaf tasks across the whole
  quadtree, packs every surviving block pair of every leaf into one
  ``(P, bs, bs)`` operand stream, and executes **one fused kernel call per
  wave** — ``kernels.bsmm_pairs`` (gather-GEMM-scatter) or
  ``kernels.batched_gemm`` + host scatter-add.  This lifts the paper's Fig 2
  outer-product batching from per-leaf to per-graph: cross-leaf batching.

Correctness of deferral rests on a structural fact both backends share: the
*occupancy* of every leaf result is determined by the operand masks alone
(einsum over structurally-present pairs), so NIL propagation — and therefore
the task graph, task counts and flop attribution — is identical across
backends; only the numeric fill is deferred.  Numerically the backends agree
to float32 precision: the pallas backend packs operands as float32 and its
kernels accumulate in float32, so its result leaves are float32 even when
the inputs are float64 (see the PallasEngine docstring).

Flop/byte attribution: each task's ``node.flops`` is set at registration
from its structural pair count (identical formula to the numpy backend's
LeafStats), so :class:`~repro.core.tasks.ClusterSim` sees per-task work
regardless of backend; the fused-wave reality (kernel wall time, pair and
padding counts, bytes packed) is recorded in :meth:`PallasEngine.stats`.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Any, Optional

import numpy as np

from .leaf import (LeafMatrix, LeafStats, alloc_structure, inv_chol_keys,
                   leaf_add, leaf_inv_chol, leaf_multiply, leaf_scale,
                   leaf_sym_multiply, leaf_sym_square, leaf_syrk,
                   leaf_tri_solve, tri_solve_keys, unpack_blocks)
from .quadtree import MatrixChunk
from repro.obs.tracer import NOOP

#: leaf-payload kinds executed host-side (no kernel wave)
HOST_KINDS = ("add", "transpose", "scale")

#: leaf-payload kinds dispatched through the batched triangular kernels
#: (kernels/tri.py) — their own wave family, never mixed into GEMM waves
SOLVE_KINDS = ("tri_solve", "inv_chol")

#: leaf-payload kinds a GEMM wave computes (their pairs go to the kernel)
KERNEL_KINDS = ("multiply", "sym_square", "syrk", "sym_multiply")


@dataclasses.dataclass(frozen=True)
class LeafPayload:
    """Batchable description of a leaf task (replaces opaque closures).

    ``a``/``b`` are producer *node ids* in the registering CTGraph; the
    engine resolves them to chunks at execution time.  Only the fields
    relevant to ``kind`` are meaningful.
    """
    # multiply|sym_square|syrk|sym_multiply|add|transpose|scale
    # |tri_solve|inv_chol
    kind: str
    a: Optional[int] = None
    b: Optional[int] = None
    ta: bool = False                # multiply: transpose A
    tb: bool = False                # multiply: transpose B
    trans: bool = False             # syrk: A^T A instead of A A^T
    side: str = "left"              # sym_multiply: S B vs B S
    tau: float = 0.0                # multiply: SpAMM block-pair threshold
    alpha: float = 1.0              # scale: C = alpha * A
    # TruncationReport accumulating pruned-pair bounds; excluded from
    # eq/hash (it is an accumulator identity, not part of the task's value)
    trunc: Any = dataclasses.field(default=None, compare=False)


class EngineRebindError(RuntimeError, ValueError):
    """A stateful engine instance was bound to a second CTGraph.

    Deferred waves and flop/bytes stats are per-graph state: silently
    rebinding would flush foreign work as a side effect and conflate the
    reports.  Subclasses ValueError for backwards compatibility with code
    that caught the original exception type.
    """


class LeafEngine:
    """Backend interface consumed by :class:`~repro.core.tasks.CTGraph`."""

    name = "abstract"
    #: observability hook; stateful backends resolve the bound graph's
    #: tracer instead (see PallasEngine.tracer)
    tracer = NOOP

    def execute(self, g, node, payload: LeafPayload) -> Optional[MatrixChunk]:
        """Execute (or defer) one leaf task; returns its chunk or None=NIL."""
        raise NotImplementedError

    def reexecute(self, g, node, payload: LeafPayload) -> None:
        """Recompute an already-executed leaf task's numbers *in place*.

        Compiled-Plan replay (api/plan.py): the task's output chunk
        already exists with its final block structure; only the numbers
        are refreshed from the (rebound) operand chunks.  Must register
        no tasks and allocate no chunks.  Truncated multiplies replay the
        block-pair list frozen on ``node.replay`` at first execution so
        the program — not the norms of the new values — decides the
        structure.
        """
        raise NotImplementedError

    def flush(self, g) -> None:
        """Run all deferred work; afterwards every chunk holds real numbers."""

    def free_chunks(self, g, nids) -> None:
        """Drop engine-side state tied to these chunks (Session.free).

        Stateless backends keep nothing per chunk; the mesh executor
        overrides this to release device-resident block buffers and
        ownership/residency bookkeeping for the freed leaves.
        """

    def has_pending_for(self, leaf_ids) -> bool:
        """Whether any deferred task reads or writes one of these leaves.

        ``leaf_ids`` is a set of ``id(LeafMatrix)`` values.  Immediate
        backends keep nothing deferred; the batched backends override
        this so callers that overwrite leaf values in place (the plan
        rebind hooks) can flush *only when their target is actually
        entangled with pending work* — leaving unrelated deferred waves
        intact for cross-plan coalescing (DESIGN.md §9).
        """
        return False

    def is_unfilled(self, leaf) -> bool:
        """Whether a flushed leaf's numbers were never computed.

        Immediate backends fill every leaf they make; the Pallas engine
        leaves the partials it folds into a root's wave unfilled for good.
        """
        return False

    def stats(self) -> dict:
        return {}


def make_engine(spec: Any) -> LeafEngine:
    """Resolve an engine spec: None/'numpy', 'pallas', or an instance."""
    if spec is None or spec == "numpy":
        return NumpyEngine()
    if spec == "pallas":
        return PallasEngine()
    if spec == "mesh":
        # lazy import: the mesh executor pulls in jax device state, which
        # must stay out of processes that only simulate
        from repro.launch.mesh_exec import MeshEngine
        return MeshEngine()
    if isinstance(spec, LeafEngine):
        return spec
    raise ValueError(f"unknown leaf engine spec: {spec!r}")


# ---------------------------------------------------------------------------
# Structure enumeration shared by both backends' bookkeeping
# ---------------------------------------------------------------------------

def _plain_items(leaf: LeafMatrix, trans: bool):
    """(row, col, stored_key, transpose_flag) of op(A), op in {id, T}."""
    for (i, j) in leaf.blocks:
        if trans:
            yield j, i, (i, j), True
        else:
            yield i, j, (i, j), False


def _full_items(leaf: LeafMatrix):
    """Full symmetric structure view of an upper-storage leaf."""
    for (i, j) in leaf.blocks:
        yield i, j, (i, j), False
        if i != j:
            yield j, i, (i, j), True


def leaf_task_pairs(payload: LeafPayload, a_leaf: LeafMatrix,
                    b_leaf: Optional[LeafMatrix]):
    """All surviving block GEMMs of one leaf task.

    Returns ``(pairs, upper_out)`` where each pair is
    ``(src_a, key_a, trans_a, src_b, key_b, trans_b, out_key)`` with src in
    {'a', 'b'} naming which operand leaf the stored block comes from.  The
    pair count equals the numpy backend's LeafStats.block_multiplies.
    """
    k = payload.kind
    if k == "multiply":
        assert not a_leaf.upper and not b_leaf.upper  # host-library contract
        first = ("a", _plain_items(a_leaf, payload.ta))
        second = ("b", _plain_items(b_leaf, payload.tb))
        upper = False
    elif k == "sym_square":
        assert a_leaf.upper
        first = ("a", _full_items(a_leaf))
        second = ("a", _full_items(a_leaf))
        upper = True
    elif k == "syrk":
        assert not a_leaf.upper
        if payload.trans:   # C = A^T A
            first = ("a", _plain_items(a_leaf, True))
            second = ("a", _plain_items(a_leaf, False))
        else:               # C = A A^T
            first = ("a", _plain_items(a_leaf, False))
            second = ("a", _plain_items(a_leaf, True))
        upper = True
    elif k == "sym_multiply":
        assert a_leaf.upper and not b_leaf.upper
        if payload.side == "left":      # C = S B
            first = ("a", _full_items(a_leaf))
            second = ("b", _plain_items(b_leaf, False))
        else:                            # C = B S
            first = ("b", _plain_items(b_leaf, False))
            second = ("a", _full_items(a_leaf))
        upper = False
    else:
        raise ValueError(f"not a multiply-kind payload: {k}")

    cols: dict[int, list] = {}
    for i, kk, key, tr in first[1]:
        cols.setdefault(kk, []).append((i, first[0], key, tr))
    rows: dict[int, list] = {}
    for kk, j, key, tr in second[1]:
        rows.setdefault(kk, []).append((j, second[0], key, tr))

    pairs = []
    for kk in cols.keys() & rows.keys():
        for i, sa, ka, tra in cols[kk]:
            for j, sb, kb, trb in rows[kk]:
                if upper and i > j:
                    continue        # lower triangle skipped: symmetry saving
                pairs.append((sa, ka, tra, sb, kb, trb, (i, j)))

    if payload.tau > 0.0 and k == "multiply":
        # SpAMM pruning inside the leaf (DESIGN.md §5): a block pair whose
        # norm product is below tau is dropped *structurally* — both
        # backends take their structure from this list, so pruned pairs
        # never enter a Pallas wave and never touch the host library.
        # Block norms are transpose-invariant: the stored key's cached
        # norm is valid for either orientation.
        srcs = {"a": a_leaf, "b": b_leaf}
        flops_each = 2.0 * a_leaf.bs ** 3
        kept = []
        for pr in pairs:
            sa, ka, _, sb, kb, _, _ = pr[:7]
            bound = math.sqrt(srcs[sa].block_norm2(ka)
                              * srcs[sb].block_norm2(kb))
            if bound < payload.tau:
                if payload.trunc is not None:
                    payload.trunc.record_leaf_pair(bound, flops_each)
            else:
                kept.append(pr)
        pairs = kept
    return pairs, upper


# ---------------------------------------------------------------------------
# Reference backend
# ---------------------------------------------------------------------------

def execute_pairs_host(a_leaf: LeafMatrix, b_leaf: Optional[LeafMatrix],
                       pairs: list, upper: bool,
                       stats: Optional[LeafStats] = None) -> LeafMatrix:
    """Evaluate a leaf task from its (possibly pruned) block-pair list.

    This is the host-side twin of the Pallas wave: the structure comes
    from :func:`leaf_task_pairs`, so a truncated multiply produces the
    same block occupancy on both backends by construction.
    """
    dtype = a_leaf.dtype if b_leaf is None \
        else np.result_type(a_leaf.dtype, b_leaf.dtype)
    out = LeafMatrix(a_leaf.n, a_leaf.bs, upper=upper, dtype=dtype)
    srcs = {"a": a_leaf, "b": b_leaf}
    for sa, ka, tra, sb, kb, trb, out_key in pairs:
        ab = srcs[sa].blocks[ka]
        bb = srcs[sb].blocks[kb]
        prod = (ab.T if tra else ab) @ (bb.T if trb else bb)
        cur = out.blocks.get(out_key)
        if cur is None:
            out.blocks[out_key] = prod
        else:
            cur += prod
    if stats is not None:
        stats.block_multiplies += len(pairs)
        stats.flops += 2.0 * len(pairs) * a_leaf.bs ** 3
        stats.batches += 1 if pairs else 0
    return out


class NumpyEngine(LeafEngine):
    """Immediate per-task execution with the host leaf library (§4.1)."""

    name = "numpy"

    def _compute(self, g, node, payload: LeafPayload,
                 av: MatrixChunk, bv: Optional[MatrixChunk], st: LeafStats
                 ) -> tuple[LeafMatrix, bool]:
        """The numeric work of one leaf task; shared by execute/reexecute."""
        k = payload.kind
        if k == "multiply" and payload.tau > 0.0:
            # truncated path: structure (incl. SpAMM pair pruning) comes
            # from leaf_task_pairs — identical to the pallas backend's —
            # and the surviving pairs are evaluated with the host library.
            # The pair list is frozen on the node so a Plan replay re-runs
            # the same program instead of re-pruning against new norms.
            if node.replay is None:
                node.replay = leaf_task_pairs(payload, av.leaf, bv.leaf)
            pairs, upper = node.replay
            res = execute_pairs_host(av.leaf, bv.leaf, pairs, upper, st)
        elif k == "multiply":
            res = leaf_multiply(av.leaf, bv.leaf, ta=payload.ta,
                                tb=payload.tb, stats=st)
            upper = False
        elif k == "sym_square":
            res = leaf_sym_square(av.leaf, stats=st)
            upper = True
        elif k == "syrk":
            res = leaf_syrk(av.leaf, trans=payload.trans, stats=st)
            upper = True
        elif k == "sym_multiply":
            res = leaf_sym_multiply(av.leaf, bv.leaf, side=payload.side,
                                    stats=st)
            upper = False
        elif k == "add":
            res = leaf_add(av.leaf, bv.leaf)
            upper = av.upper
        elif k == "transpose":
            res = av.leaf.transpose()
            upper = False
        elif k == "scale":
            res = leaf_scale(av.leaf, payload.alpha)
            upper = av.upper
        elif k == "inv_chol":
            res = leaf_inv_chol(av.leaf, stats=st)
            upper = False
        elif k == "tri_solve":
            res = leaf_tri_solve(av.leaf, bv.leaf, stats=st)
            upper = False
        else:
            raise ValueError(f"unknown leaf payload kind: {k}")
        return res, upper

    def execute(self, g, node, payload: LeafPayload) -> Optional[MatrixChunk]:
        av: MatrixChunk = g.value_of(payload.a)
        bv: Optional[MatrixChunk] = (
            g.value_of(payload.b) if payload.b is not None else None)
        st = LeafStats()
        res, upper = self._compute(g, node, payload, av, bv, st)
        node.flops = st.flops
        # multiply kinds prune structurally-empty results to NIL; adds of
        # two non-NIL leaves always produce a chunk (Alg 2 semantics) —
        # matching the pallas backend's structural behavior exactly.
        # Solve kinds always produce a chunk: their structure is the
        # deterministic inv_chol_keys/tri_solve_keys set, never empty.
        if payload.kind not in HOST_KINDS \
                and payload.kind not in SOLVE_KINDS and res.is_zero():
            return None
        return MatrixChunk(av.n, leaf=res, upper=upper)

    def reexecute(self, g, node, payload: LeafPayload) -> None:
        av: MatrixChunk = g.value_of(payload.a)
        bv: Optional[MatrixChunk] = (
            g.value_of(payload.b) if payload.b is not None else None)
        res, _ = self._compute(g, node, payload, av, bv, LeafStats())
        out: MatrixChunk = g.value_of(node.nid)
        dst = out.leaf
        if set(res.blocks) != set(dst.blocks):   # pragma: no cover - guard
            raise RuntimeError(
                "replay structure drift: leaf block occupancy changed "
                "between plan compilation and replay")
        for key, blk in res.blocks.items():
            dst.blocks[key][...] = blk
        dst.invalidate_norms()
        out.norm2 = None
        out.trace = None


# ---------------------------------------------------------------------------
# Batched accelerator backend
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Pending:
    nid: int
    payload: LeafPayload
    out: LeafMatrix
    a_leaf: LeafMatrix
    b_leaf: Optional[LeafMatrix]
    pairs: Optional[list] = None    # multiply kinds only
    # folded product (fold_roots): nid of the root add whose output leaf
    # ``out`` now is; the kernel sums this task's pairs into it, and a
    # folded add rides its root's wave without running
    root: Optional[int] = None


class PallasEngine(LeafEngine):
    """Deferred, cross-leaf-batched execution through the Pallas kernels.

    Precision contract: operands are packed float32 and the kernels
    accumulate in float32 with full-precision MXU passes
    (``Precision.HIGHEST``; jax runs without x64 here), so engine-produced
    leaves are float32 regardless of input dtype — expect ~1e-7 relative
    agreement with the float64 numpy backend, not bitwise equality.

    kernel   : 'pairs' -> one fused kernels.bsmm_pairs gather-GEMM-scatter
               call per wave; 'gemm' -> one kernels.batched_gemm call per
               wave + host scatter-add (the cuBLAS-batched-gemm shape).
    interpret: None -> auto (compiled on TPU, interpret mode on CPU, any
               other backend raises; resolved by kernels.ops.resolve and
               recorded in every wave record with the result's platform).
    block_t  : batch tile of the batched_gemm kernel, which zero-pads the
               wave to a multiple of it internally.
    validate_structure : cross-check the pure-Python output structure of
               every leaf task against bsmm.compute_c_structure (the boolean
               matmul the TPU path uses).  Costs one eager JAX call per leaf
               task; meant for tests.

    Partial sums in the kernel: the leaf-level add tree of a product
    (Algorithm 1's ``C_mn = A_m0 B_0n + A_m1 B_1n`` at every level) is
    folded into the wave that computes its partials.  Every multiply
    under a root add writes the root's output leaf, so all partials of one
    output block share one wave segment and ``bsmm_pairs`` sums them in
    VMEM; the folded adds never run on the host.  Which adds fold is
    decided once per task (:func:`fold_roots`, at the first flush that
    sees it) and reused by every replay.  The partial leaves under a root
    stay unfilled for good: a pending task that read one would never be
    ready, so the flush raises instead of reading zeros.
    """

    name = "pallas"
    # fold products' add trees into their waves (the mesh executor splits
    # a wave's tasks across chips, so it keeps per-task outputs)
    _FOLD_SUMS = True

    @property
    def tracer(self):
        """The bound graph's tracer (NOOP until bound / when tracing off)."""
        g = self._graph
        return getattr(g, "tracer", NOOP) if g is not None else NOOP

    def __init__(self, kernel: str = "pairs",
                 interpret: Optional[bool] = None, block_t: int = 8,
                 validate_structure: bool = False):
        assert kernel in ("pairs", "gemm")
        self.kernel = kernel
        self.interpret = interpret
        self.block_t = block_t
        self.validate_structure = validate_structure
        self._pending: list[_Pending] = []
        self._unfilled: set[int] = set()     # id() of placeholder out leaves
        self._waves: list[dict] = []
        # fold decisions (fold_roots): the nids decided so far, and the
        # root add of every folded nid
        self._decided: set[int] = set()
        self._root_of: dict[int, int] = {}
        self._graph = None                   # bound CTGraph (one per engine)

    # -- registration-time: structure only ----------------------------------
    def _bind(self, g) -> None:
        """One engine instance serves one graph: pending waves and stats are
        per-graph state, so sharing would flush foreign work as a side
        effect and conflate the flop/bytes report."""
        if g is None:
            return
        if self._graph is None:
            self._graph = g
        elif g is not self._graph:
            raise EngineRebindError(
                "this PallasEngine instance is already bound to another "
                "CTGraph; create one engine per graph")

    def execute(self, g, node, payload: LeafPayload) -> Optional[MatrixChunk]:
        self._bind(g)
        av: MatrixChunk = g.value_of(payload.a)
        bv: Optional[MatrixChunk] = (
            g.value_of(payload.b) if payload.b is not None else None)
        a_leaf = av.leaf
        b_leaf = bv.leaf if bv is not None else None

        if payload.kind == "add":
            # adds run host-side (no kernel), so input precision is kept:
            # float64 for original data, float32 when fed by kernel results
            out = alloc_structure(
                a_leaf.n, a_leaf.bs,
                list(dict.fromkeys(list(a_leaf.blocks) + list(b_leaf.blocks))),
                upper=a_leaf.upper,
                dtype=np.result_type(a_leaf.dtype, b_leaf.dtype))
            self._defer(_Pending(node.nid, payload, out, a_leaf, b_leaf))
            return MatrixChunk(av.n, leaf=out, upper=av.upper)

        if payload.kind == "transpose":
            # host-side like add; deferred so it orders after the wave that
            # fills its input (structure is final at registration)
            out = alloc_structure(a_leaf.n, a_leaf.bs,
                                  [(j, i) for (i, j) in a_leaf.blocks],
                                  upper=False, dtype=a_leaf.dtype)
            self._defer(_Pending(node.nid, payload, out, a_leaf, None))
            return MatrixChunk(av.n, leaf=out)

        if payload.kind == "scale":
            # host-side like add/transpose: same structure, scaled numbers
            out = alloc_structure(a_leaf.n, a_leaf.bs, list(a_leaf.blocks),
                                  upper=a_leaf.upper, dtype=a_leaf.dtype)
            self._defer(_Pending(node.nid, payload, out, a_leaf, None))
            return MatrixChunk(av.n, leaf=out, upper=av.upper)

        if payload.kind in SOLVE_KINDS:
            # structure is a function of the operand structure alone
            # (deterministic keys, zero blocks kept — see core/leaf.py),
            # so deferral is safe exactly like the multiply kinds; the
            # numeric fill joins a batched triangular wave at flush
            if payload.kind == "inv_chol":
                keys = inv_chol_keys(a_leaf.grid)
            else:
                keys = tri_solve_keys(b_leaf.blocks, a_leaf.grid)
            node.flops = float(a_leaf.n) ** 3
            out = alloc_structure(a_leaf.n, a_leaf.bs, keys, upper=False,
                                  dtype=self._out_dtype(a_leaf, b_leaf))
            self._defer(_Pending(node.nid, payload, out, a_leaf, b_leaf))
            return MatrixChunk(av.n, leaf=out, upper=False)

        pairs, upper = leaf_task_pairs(payload, a_leaf, b_leaf)
        if payload.tau > 0.0:
            # freeze the surviving pairs for Plan replay (see qt_replay):
            # the norm test must not re-evaluate against rebound values
            node.replay = (pairs, upper)
        node.flops = 2.0 * len(pairs) * a_leaf.bs ** 3
        # output occupancy in row-major slot order (the same order
        # bsmm.compute_c_structure assigns; see validate_structure)
        keys = sorted({p[6] for p in pairs})
        if self.validate_structure:
            oracle = self._c_keys(payload, a_leaf, b_leaf, upper)
            if payload.tau > 0.0:
                # the jnp oracle evaluates the tau test in float32; allow
                # it to disagree only on pairs within f32 rounding of the
                # boundary by bracketing with slightly shifted taus
                def keys_at(t):
                    probe = dataclasses.replace(payload, tau=t, trunc=None)
                    prs, _ = leaf_task_pairs(probe, a_leaf, b_leaf)
                    return {p[6] for p in prs}
                strict = keys_at(payload.tau * (1 + 1e-5))
                loose = keys_at(payload.tau * (1 - 1e-5))
                assert strict <= set(oracle) <= loose
            else:
                assert keys == oracle
        if not keys:
            return None
        out = alloc_structure(a_leaf.n, a_leaf.bs, keys, upper=upper,
                              dtype=self._out_dtype(a_leaf, b_leaf))
        self._defer(_Pending(node.nid, payload, out, a_leaf, b_leaf, pairs))
        return MatrixChunk(av.n, leaf=out, upper=upper)

    @staticmethod
    def _out_dtype(a_leaf, b_leaf):
        # kernels compute in float32 (f32 accumulation on the MXU, and jax
        # runs without x64 here): engine-produced leaves are float32 so the
        # stored dtype and bytes accounting are truthful about precision
        _ = a_leaf, b_leaf
        return np.float32

    def _defer(self, entry: _Pending) -> None:
        self._pending.append(entry)
        self._unfilled.add(id(entry.out))

    def _fold_fresh(self) -> None:
        """Decide the fold of every pending task not decided before.

        Runs before any wave or host pass can fill a fresh task's output;
        the decision is kept per nid, so a replay (:meth:`reexecute`)
        applies it without walking the graph again.
        """
        fresh = [t for t in self._pending if t.nid not in self._decided]
        if not fresh:
            return
        self._decided.update(t.nid for t in fresh)
        if not self._FOLD_SUMS:
            return
        cand = {t.nid: t for t in fresh
                if t.payload.kind == "add" or t.payload.kind in KERNEL_KINDS}
        roots = fold_roots(self._graph, cand) if cand else {}
        for nid, root in roots.items():
            cand[nid].root = root
            cand[nid].out = cand[root].out
        self._root_of.update(roots)

    def _c_keys(self, payload, a_leaf, b_leaf, upper) -> list:
        """Output occupancy via the one-shot boolean matmul of bsmm.

        The operand masks are the op-applied structure views; the C keys come
        back in compute_c_structure's row-major slot order, which fixes the
        packed output slot numbering of the flush wave.  A truncated
        multiply (``payload.tau > 0``) cross-checks against the
        norm-weighted structure (:func:`~repro.core.bsmm
        .compute_c_structure_norms`) instead: a C block survives only if
        some inner pair's norm product clears tau.
        """
        from .bsmm import compute_c_structure, compute_c_structure_norms
        import jax.numpy as jnp

        grid = a_leaf.grid
        if payload.kind == "multiply" and payload.tau > 0.0:
            na = np.zeros((grid, grid))
            nb = np.zeros((grid, grid))
            for i, k, key, _ in _plain_items(a_leaf, payload.ta):
                na[i, k] = math.sqrt(a_leaf.block_norm2(key))
            for k, j, key, _ in _plain_items(b_leaf, payload.tb):
                nb[k, j] = math.sqrt(b_leaf.block_norm2(key))
            crows, ccols, _, cnt = compute_c_structure_norms(
                jnp.asarray(na), jnp.asarray(nb), payload.tau,
                cap_c=grid * grid)
            cnt = int(cnt)
            return [(int(r), int(c)) for r, c
                    in zip(np.asarray(crows)[:cnt], np.asarray(ccols)[:cnt])]

        ma = np.zeros((grid, grid), bool)
        mb = np.zeros((grid, grid), bool)
        kfirst = payload.kind
        if kfirst == "multiply":
            for i, k, _, _ in _plain_items(a_leaf, payload.ta):
                ma[i, k] = True
            for k, j, _, _ in _plain_items(b_leaf, payload.tb):
                mb[k, j] = True
        elif kfirst == "sym_square":
            for i, k, _, _ in _full_items(a_leaf):
                ma[i, k] = True
            mb = ma
        elif kfirst == "syrk":
            for i, k, _, _ in _plain_items(a_leaf, payload.trans):
                ma[i, k] = True
            mb = ma.T
        elif kfirst == "sym_multiply":
            if payload.side == "left":
                for i, k, _, _ in _full_items(a_leaf):
                    ma[i, k] = True
                for k, j, _, _ in _plain_items(b_leaf, False):
                    mb[k, j] = True
            else:
                for i, k, _, _ in _plain_items(b_leaf, False):
                    ma[i, k] = True
                for k, j, _, _ in _full_items(a_leaf):
                    mb[k, j] = True
        crows, ccols, _, cnt = compute_c_structure(
            jnp.asarray(ma), jnp.asarray(mb), cap_c=grid * grid)
        cnt = int(cnt)
        keys = [(int(r), int(c)) for r, c
                in zip(np.asarray(crows)[:cnt], np.asarray(ccols)[:cnt])]
        if upper:
            keys = [k for k in keys if k[0] <= k[1]]
        return keys

    # -- flush: batched waves ------------------------------------------------
    def _ready(self, t: _Pending) -> bool:
        if id(t.a_leaf) in self._unfilled:
            return False
        return t.b_leaf is None or id(t.b_leaf) not in self._unfilled

    def batch_key(self, t: _Pending) -> tuple:
        """Wave-compatibility key of a deferred kernel task.

        Tasks agreeing on ``(kernel, leaf_n, bs, dtype)`` may share one
        fused dispatch — within this engine's waves and, through the
        serving layer's cross-plan coalescer (:mod:`repro.serve`),
        across engines of different sessions.
        """
        return (self.kernel, t.out.n, t.out.bs,
                np.dtype(t.out.dtype).name)

    def is_unfilled(self, leaf) -> bool:
        return id(leaf) in self._unfilled

    def has_pending_for(self, leaf_ids) -> bool:
        for t in self._pending:
            if id(t.out) in leaf_ids or id(t.a_leaf) in leaf_ids or \
                    (t.b_leaf is not None and id(t.b_leaf) in leaf_ids):
                return True
        return False

    def ready_wave(self) -> dict:
        """Ready deferred kernel tasks, grouped by :meth:`batch_key`.

        A folded product is ready whole or not at all: its multiplies
        join a wave together, with its folded adds, which retire with the
        wave's :meth:`commit_tasks`.  Nothing is executed or committed
        here.  The cross-plan coalescer merges groups with equal keys
        across engines before dispatching; :meth:`flush` consumes the
        same grouping locally.
        """
        self._fold_fresh()
        blocked = {t.root for t in self._pending
                   if t.root is not None and t.pairs is not None
                   and not self._ready(t)}
        groups: dict[tuple, list[_Pending]] = {}
        for t in self._pending:
            if t.root is not None:
                if t.root in blocked:
                    continue
            elif t.payload.kind in HOST_KINDS \
                    or t.payload.kind in SOLVE_KINDS or not self._ready(t):
                continue
            groups.setdefault(self.batch_key(t), []).append(t)
        return groups

    def solve_wave(self) -> dict:
        """Ready deferred triangular-solve tasks, grouped for batching.

        Solve kinds never join GEMM waves: they dispatch through
        kernels/tri.py one batched call per ``(kind, leaf_n, bs)`` group.
        """
        groups: dict[tuple, list[_Pending]] = {}
        for t in self._pending:
            if t.payload.kind in SOLVE_KINDS and self._ready(t):
                key = (t.payload.kind, t.out.n, t.out.bs)
                groups.setdefault(key, []).append(t)
        return groups

    def run_solve_ready(self) -> bool:
        """Dispatch every ready batched triangular wave; True if any ran."""
        progressed = False
        for key, tasks in sorted(self.solve_wave().items()):
            kind, n, bs = key
            tr = self.tracer
            if tr.enabled:
                with tr.span("engine.wave", track="engine") as sp:
                    self._waves.append(dispatch_solve_wave(
                        tasks, kind=kind, n=n, bs=bs, tracer=tr))
                    sp.set(**self._wave_span_attrs())
            else:
                self._waves.append(dispatch_solve_wave(
                    tasks, kind=kind, n=n, bs=bs))
            self._waves[-1].setdefault("batch_key", list(key))
            self.commit_tasks(tasks)
            progressed = True
        return progressed

    def run_host_ready(self) -> bool:
        """Execute every ready host-side fill (add/transpose/scale).

        Returns True if anything ran — the progress signal both
        :meth:`flush` and the coalescer's drain loop use.  Traced, a pass
        that runs at least one fill is one ``engine.flush.host`` span;
        folded adds never run here.
        """
        self._fold_fresh()
        tr = self.tracer
        if tr.enabled and any(self._host_ready(t) for t in self._pending):
            with tr.span("engine.flush.host", track="engine") as sp:
                return self._host_pass(sp)
        return self._host_pass(None)

    def _host_pass(self, span) -> bool:
        done = {"add": 0, "transpose": 0, "scale": 0}
        blocks = 0
        rest = []
        for t in self._pending:
            if self._host_ready(t):
                if t.payload.kind == "add":
                    self._run_add(t)
                elif t.payload.kind == "scale":
                    self._run_scale(t)
                else:
                    self._run_transpose(t)
                self._unfilled.discard(id(t.out))
                done[t.payload.kind] += 1
                blocks += len(t.out.blocks)
            else:
                rest.append(t)
        self._pending = rest
        if span is not None:
            span.set(adds=done["add"], transposes=done["transpose"],
                     scales=done["scale"], blocks=blocks)
        return any(done.values())

    def _host_ready(self, t: _Pending) -> bool:
        return t.payload.kind in HOST_KINDS and t.root is None \
            and self._ready(t)

    def commit_tasks(self, tasks: list, wave_record: Optional[dict] = None
                     ) -> None:
        """Retire externally executed tasks (cross-engine coalescer).

        The coalescer packs this engine's share of a merged wave into one
        dispatch it runs itself, then commits the share here so the next
        flush does not re-run it.  ``wave_record`` (this engine's slice
        of the merged wave's accounting) lands in the wave log.
        """
        done = {id(t) for t in tasks}
        for t in tasks:
            self._unfilled.discard(id(t.out))
        self._pending = [t for t in self._pending if id(t) not in done]
        if wave_record is not None:
            self._waves.append(wave_record)

    def flush(self, g=None) -> None:
        # tasks leave self._pending only after their wave succeeded, so a
        # kernel failure leaves the deferred work intact and a later flush
        # retries it (block fills are idempotent in-place assignments)
        self._bind(g)
        while self._pending:
            groups = self.ready_wave()
            if groups:
                self._run_wave(groups)   # commits per group (see below)
            progressed = bool(groups)
            progressed |= self.run_host_ready()
            progressed |= self.run_solve_ready()
            if self._pending and not progressed:
                raise RuntimeError(
                    "leaf engine deadlock: unresolvable leaf dependencies")

    @staticmethod
    def _run_add(t: _Pending) -> None:
        for key, blk in t.out.blocks.items():
            a = t.a_leaf.blocks.get(key)
            b = t.b_leaf.blocks.get(key)
            if a is None:
                blk[...] = b
            elif b is None:
                blk[...] = a
            else:
                np.add(a, b, out=blk, casting="unsafe")
        t.out.invalidate_norms()

    @staticmethod
    def _run_transpose(t: _Pending) -> None:
        for (i, j), blk in t.a_leaf.blocks.items():
            t.out.blocks[(j, i)][...] = blk.T
        t.out.invalidate_norms()

    @staticmethod
    def _run_scale(t: _Pending) -> None:
        for key, blk in t.a_leaf.blocks.items():
            np.multiply(blk, t.payload.alpha, out=t.out.blocks[key],
                        casting="unsafe")
        t.out.invalidate_norms()

    def reexecute(self, g, node, payload: LeafPayload) -> None:
        """Re-defer an already-executed leaf task against its existing
        output chunk; the next flush re-runs the batched waves/host fills
        in dependency order, writing the same placeholder blocks."""
        self._bind(g)
        av: MatrixChunk = g.value_of(payload.a)
        bv: Optional[MatrixChunk] = (
            g.value_of(payload.b) if payload.b is not None else None)
        a_leaf = av.leaf
        b_leaf = bv.leaf if bv is not None else None
        out: MatrixChunk = g.value_of(node.nid)
        # every wave, host fill and solve wave assigns (not scatter-adds)
        # each output block it owns, so re-deferring without zeroing is
        # exact: a wave's slots each get at least one pair
        entry = _Pending(node.nid, payload, out.leaf, a_leaf, b_leaf)
        if payload.kind in KERNEL_KINDS:
            if payload.tau > 0.0:
                entry.pairs, _ = node.replay    # frozen at first execution
            else:
                probe = dataclasses.replace(payload, trunc=None)
                entry.pairs, _ = leaf_task_pairs(probe, a_leaf, b_leaf)
        root = self._root_of.get(node.nid)
        if root is not None:                    # the fold decided at first
            entry.root = root
            entry.out = g.value_of(root).leaf
        self._defer(entry)
        out.norm2 = None
        out.trace = None

    def _wave_span_attrs(self) -> dict:
        """Attributes of the just-committed wave for its engine.wave span."""
        w = self._waves[-1]
        return {k: w[k] for k in ("kernel", "bs", "tasks", "pairs",
                                  "padded_pairs", "c_blocks", "fused_adds",
                                  "bytes_packed")
                if k in w}

    def _run_wave(self, groups: dict[tuple, list[_Pending]]) -> None:
        tr = self.tracer
        for key, tasks in sorted(groups.items()):
            if tr.enabled:
                with tr.span("engine.wave", track="engine") as sp:
                    self._run_group(key[2], tasks)
                    sp.set(**self._wave_span_attrs())
            else:
                self._run_group(key[2], tasks)
            self._waves[-1].setdefault("batch_key", list(key))
            # commit this group immediately: a failure in a *later* group
            # must not leave these tasks pending, or a retrying flush would
            # re-run them and double-count their wave record in stats()
            self.commit_tasks(tasks)

    def _run_group(self, bs: int, tasks: list[_Pending]) -> None:
        """Pack every block pair of every leaf task into one kernel call."""
        self._waves.append(dispatch_packed_wave(
            tasks, bs, kernel=self.kernel, block_t=self.block_t,
            interpret=self.interpret, tracer=self.tracer))

    # -- reporting -----------------------------------------------------------
    def stats(self) -> dict:
        return {
            "backend": self.name,
            "kernel": self.kernel,
            "waves": len(self._waves),
            "batched_pairs": sum(w["pairs"] for w in self._waves),
            "padded_pairs": sum(w["padded_pairs"] for w in self._waves),
            "c_blocks": sum(w["c_blocks"] for w in self._waves),
            "dispatch_s": sum(w["dispatch_s"] for w in self._waves),
            "bytes_packed": sum(w["bytes_packed"] for w in self._waves),
            "wave_log": list(self._waves),
        }


def dispatch_solve_wave(tasks: list[_Pending], *, kind: str, n: int,
                        bs: int, tracer=NOOP) -> dict:
    """One batched triangular-kernel call for every ready solve leaf.

    Leaves are densified host-side (symmetric upper storage expands to
    full), stacked ``(P, n, n)`` in float32, run through
    :mod:`repro.kernels.tri`, and scattered back into each task's
    pre-allocated deterministic block structure.  Returns a wave record
    with the same accounting fields as the GEMM waves (``pairs`` counts
    leaves here — one "pair" of dense operands per task).
    """
    from repro.kernels import tri as ktri

    a_pack = np.stack([t.a_leaf.to_dense() for t in tasks]).astype(np.float32)
    b_pack = None if kind == "inv_chol" else np.stack(
        [t.b_leaf.to_dense() for t in tasks]).astype(np.float32)
    t0 = time.perf_counter()
    with tracer.span("kernel.dispatch", track="engine", kernel=kind,
                     bs=bs, pairs=len(tasks)):
        res_dev, res = _round_trip(
            tracer, [x for x in (a_pack, b_pack) if x is not None],
            ktri.batched_inv_chol if kind == "inv_chol"
            else ktri.batched_tri_solve, pairs=len(tasks))
    dispatch = time.perf_counter() - t0
    b_bytes = 0 if b_pack is None else b_pack.nbytes

    c_blocks = 0
    for t, x in zip(tasks, res):
        keys = list(t.out.blocks)
        data = np.stack([np.ascontiguousarray(
            x[i * bs:(i + 1) * bs, j * bs:(j + 1) * bs]) for i, j in keys])
        unpack_blocks(t.out, keys, data)
        c_blocks += len(keys)
    return {
        "kernel": kind, "bs": bs, "tasks": len(tasks),
        "pairs": len(tasks), "padded_pairs": len(tasks),
        "c_blocks": int(c_blocks), "dispatch_s": dispatch,
        "bytes_packed": int(a_pack.nbytes + b_bytes
                            + res.astype(np.float32).nbytes),
        # XLA-native triangular ops: no Pallas kernel on either backend
        **ran_on(res_dev, use_pallas=False, interpret=False),
    }


def _round_trip(tracer, host, call, fetch=np.asarray, **run_attrs):
    """Upload ``host`` arrays, run ``call`` on them, fetch the result.

    Returns ``(device result, fetch(device result))``, each step in its
    span: ``kernel.upload`` (``bytes`` sent), ``kernel.run`` (the jitted
    call, ``run_attrs``) and ``kernel.download`` (``bytes`` read back).
    Only a recording tracer waits for the device between the steps, so
    each span times its own work; untraced, the calls into JAX are the
    asynchronous uploads, the call and the blocking read-back.
    """
    import jax
    import jax.numpy as jnp

    with tracer.span("kernel.upload", track="engine") as sp:
        operands = [jnp.asarray(x) for x in host]
        if tracer.enabled:
            jax.block_until_ready(operands)
            sp.set(bytes=sum(int(x.nbytes) for x in operands))
    with tracer.span("kernel.run", track="engine", **run_attrs):
        out = call(*operands)
        if tracer.enabled:
            jax.block_until_ready(out)
    with tracer.span("kernel.download", track="engine") as sp:
        res = fetch(out)
        sp.set(bytes=int(out.nbytes))
    return out, res


def ran_on(out, *, use_pallas: bool, interpret: bool) -> dict:
    """Wave-record fields naming how and where a wave's kernel ran.

    ``platform`` is read off the result array itself, so a record cannot
    claim a device the work never reached.
    """
    (platform,) = {d.platform for d in out.devices()}
    return {"use_pallas": use_pallas, "interpret": interpret,
            "platform": platform}


def dispatch_packed_wave(tasks: list[_Pending], bs: int, *, kernel: str,
                         block_t: int, interpret: bool,
                         tracer=NOOP) -> dict:
    """Pack every block pair of every leaf task into one kernel call.

    Module-level so the cross-plan coalescer (:mod:`repro.serve.coalesce`)
    can merge same-``batch_key`` tasks *from several engines* into one
    dispatch.  Fills each task's output leaf in place and returns the wave
    record (the caller appends it to the owning engine's wave log).

    Both sides of every pair read one operand table (``_pack_wave``): it
    is uploaded once and passed as both ``a_blocks`` and ``b_blocks`` of
    ``bsmm_pairs``; the ``gemm`` path gathers ``table[sa]`` and
    ``table[sb]`` from it.

    Output slots are numbered once per distinct output leaf, so the
    partials of a folded product (tasks sharing their root's leaf) share
    one segment per output block and the kernel sums them; folded adds in
    ``tasks`` carry no pairs and are only counted (``fused_adds``).

    Numerical identity with per-engine dispatch: output slots are numbered
    leaf-by-leaf in structure order and pairs are sorted by a *stable*
    argsort on segment id, so every output block accumulates its products
    in the same order — task registration order, then pair order —
    regardless of which other tasks share the wave.
    """
    from repro.kernels import ops as kops

    fused = sum(t.pairs is None for t in tasks)
    tasks = [t for t in tasks if t.pairs is not None]
    with tracer.span("engine.wave.pack", track="engine") as sp:
        table, sa, sb, seg, n_slots, shared = _pack_wave(tasks)
        n_pairs = len(seg)
        sp.set(pairs=int(n_pairs), unique_blocks=len(table),
               shared_blocks=shared)

    _, interpret = kops.resolve(True, interpret)
    run_attrs = {"pairs": int(n_pairs), "cap_c": int(n_slots)}
    t0 = time.perf_counter()
    with tracer.span("kernel.dispatch", track="engine",
                     kernel=kernel, bs=bs,
                     pairs=int(n_pairs), c_blocks=int(n_slots)):
        if kernel == "pairs":
            def pairs_call(blocks, *index):
                return kops.bsmm_pairs(blocks, blocks, *index,
                                       cap_c=n_slots, use_pallas=True,
                                       interpret=interpret)

            c_dev, c = _round_trip(tracer, (table, sa, sb, seg),
                                   pairs_call, **run_attrs)
            padded = n_pairs
        else:
            # host gather feeds the cuBLAS-shaped batch (uploaded as it
            # is gathered); batched_gemm zero-pads to a block_t multiple
            # internally; the scatter-add joins the read-back
            def scatter_add(prods_dev):
                out = np.zeros((n_slots, bs, bs), np.float32)
                np.add.at(out, seg, np.asarray(prods_dev))
                return out

            c_dev, c = _round_trip(
                tracer, (table[idx] for idx in (sa, sb)),
                functools.partial(kops.batched_gemm, block_t=block_t,
                                  use_pallas=True, interpret=interpret),
                fetch=scatter_add, **run_attrs)
            padded = n_pairs + (-n_pairs) % block_t
    dispatch = time.perf_counter() - t0

    record = {
        "kernel": kernel, "bs": bs, "tasks": len(tasks),
        "pairs": int(n_pairs), "padded_pairs": int(padded),
        "unique_blocks": len(table), "shared_blocks": shared,
        "c_blocks": int(n_slots), "fused_adds": fused,
        "dispatch_s": dispatch,
        "bytes_packed": int(table.nbytes + c.nbytes),
        **ran_on(c_dev, use_pallas=True, interpret=interpret),
    }
    with tracer.span("engine.wave.unpack", track="engine",
                     c_blocks=int(n_slots)):
        base = 0
        for leaf in _wave_outputs(tasks):
            keys = list(leaf.blocks)
            unpack_blocks(leaf, keys, c[base:base + len(keys)])
            base += len(keys)
    return record


def _wave_outputs(tasks: list[_Pending]) -> list[LeafMatrix]:
    """Distinct output leaves of a wave's kernel tasks, in first-use order.

    The multiplies of a folded product share their root's leaf; it gets
    its output slots once.
    """
    outs: dict[int, LeafMatrix] = {}
    for t in tasks:
        outs.setdefault(id(t.out), t.out)
    return list(outs.values())


def wave_counts(tasks: list[_Pending]) -> dict:
    """``tasks``, ``pairs``, ``c_blocks`` and ``fused_adds`` of a wave's
    share of tasks, counted as :func:`dispatch_packed_wave` counts them."""
    kernel = [t for t in tasks if t.pairs is not None]
    return {"tasks": len(kernel),
            "pairs": sum(len(t.pairs) for t in kernel),
            "c_blocks": sum(len(lf.blocks) for lf in _wave_outputs(kernel)),
            "fused_adds": len(tasks) - len(kernel)}


def fold_roots(g, cand: dict) -> dict:
    """The adds the kernel can absorb, as ``{nid: root add nid}``.

    ``cand`` maps nid to the pending multiply-kind and add tasks whose
    fold is undecided.  An add is *fusable* when each of its inputs is
    such a multiply-kind output or another fusable add's output, and is
    read by nothing else: one fetch consumer in the graph's dependency
    lists (this add), and no place in the tree of a top-level result —
    a task registered with no parent, which a ``Matrix``, a ``Plan``
    output or a raw caller may hold.  A *root* is a fusable add whose
    output feeds no fusable add.  The result maps every fusable add and
    every multiply-kind task under one to its root (a root to itself);
    an add with any other input — a scale, a transpose, a data leaf, a
    partial read twice — stays a host add.
    """
    lo = min(cand)
    # Top-level tasks register one after another, each whole before the
    # next starts, and a chunk names only nodes that existed when it was
    # made.  So every node older than ``top``, the top-level task under
    # which the oldest candidate was registered, holds no candidate, and
    # the scan starts at ``top`` and prunes below it.
    top = lo
    while g.nodes[top].parent is not None:
        top = g.nodes[top].parent
    uses: dict[int, int] = {}       # fetch consumers of a candidate
    user: dict[int, int] = {}       # ... and the last of them
    held: set[int] = set()          # candidates a top-level result holds
    seen: set[int] = set()
    for node in g.nodes[top:]:
        for d in node.deps:
            if d.fetch and d.nid is not None:
                r = g.resolve(d.nid)
                if r in cand:
                    uses[r] = uses.get(r, 0) + 1
                    user[r] = node.nid
        if node.parent is not None:
            continue
        stack = [node.nid]
        while stack:
            r = g.resolve(stack.pop())
            if r is None or r < top or r in seen:
                continue
            seen.add(r)
            if r in cand:
                held.add(r)
                continue
            n = g.nodes[r]
            if n.value is None:
                # no chunk yet: a registration still open (a flush
                # inside it) may come to name whatever its subtasks made
                stack.extend(n.children)
            elif not getattr(n.value, "is_leaf", True):
                stack.extend(c for c in n.value.children if c is not None)

    def alone(r: int) -> bool:
        return r in cand and uses.get(r) == 1 and r not in held

    fusable: set[int] = set()
    for nid in sorted(cand):
        p = cand[nid].payload
        if p.kind == "add" and all(
                alone(r) and (cand[r].payload.kind != "add" or r in fusable)
                for r in (g.resolve(p.a), g.resolve(p.b))):
            fusable.add(nid)
    roots: dict[int, int] = {}
    for nid in sorted(fusable, reverse=True):     # consumers first
        up = user.get(nid)
        roots[nid] = roots[up] if up in fusable else nid
    for nid, t in cand.items():
        if t.payload.kind != "add" and user.get(nid) in fusable \
                and alone(nid):
            roots[nid] = roots[user[nid]]
    return roots


def _pack_wave(tasks: list[_Pending]) -> tuple:
    """Host packing of one wave: ``(table, sa, sb, seg, n_slots, shared)``.

    Output slots are numbered leaf-by-leaf in structure order, once per
    distinct output leaf (:func:`_wave_outputs`).  Operands
    are packed *uniquely* into one float32 ``table`` that both sides
    share — one slot per distinct (leaf, key, transpose) block, so a block
    that is an A operand of one pair and a B operand of another is packed
    once — and pairs address it through ``sa``/``sb``, which is exactly
    the slot-indexed gather the bsmm_pairs scalar-prefetch kernel is built
    around.  Each slot is filled by a cast on copy from its leaf block
    (the rounding of ``astype``, with no float64 staging); ``shared``
    counts the slots both sides read.  Pairs come back in ascending
    segment order (the bsmm_pairs accumulation contract), by a stable
    sort.
    """
    n_pairs = sum(len(t.pairs) for t in tasks)
    slots: dict[tuple, int] = {}
    blocks: list[np.ndarray] = []

    def slot_of(leaf, key, tr):
        sk = (id(leaf), key, tr)
        s = slots.get(sk)
        if s is None:
            s = slots[sk] = len(blocks)
            blk = leaf.blocks[key]
            blocks.append(blk.T if tr else blk)
        return s

    sa = np.empty((n_pairs,), np.int32)
    sb = np.empty((n_pairs,), np.int32)
    seg = np.empty((n_pairs,), np.int32)
    out_slots: dict[int, dict] = {}
    n_slots = 0
    for leaf in _wave_outputs(tasks):
        out_slots[id(leaf)] = {key: n_slots + i
                               for i, key in enumerate(leaf.blocks)}
        n_slots += len(leaf.blocks)
    p = 0
    for t in tasks:
        key_slot = out_slots[id(t.out)]
        srcs = {"a": t.a_leaf, "b": t.b_leaf}
        for src_a, ka, tra, src_b, kb, trb, out_key in t.pairs:
            sa[p] = slot_of(srcs[src_a], ka, tra)
            sb[p] = slot_of(srcs[src_b], kb, trb)
            seg[p] = key_slot[out_key]
            p += 1
    table = np.empty((len(blocks),) + blocks[0].shape, np.float32)
    for s, blk in enumerate(blocks):
        table[s] = blk
    shared = len(np.intersect1d(sa, sb))

    order = np.argsort(seg, kind="stable")
    return table, sa[order], sb[order], seg[order], n_slots, shared
