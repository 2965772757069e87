"""Driver ``mesh_replay``: compiled ``A @ B`` replayed through ``MeshEngine``.

A and B share an ``overlap3d`` configuration's pattern and differ in their
values.  The session runs on ``MeshEngine(n_dev=<the cell's chips>)`` with
its default ``"pairs"`` kernel: each wave's tasks are split over the
devices, operand blocks are homed on their producer and shipped between
devices by ring ``ppermute``s, and every device runs its share of the
block pairs (``launch/mesh_exec.py``).

Widths 0 and 1 fill the compiled inputs ``A`` and ``B``; the traffic's
``value_sets`` more are the values the ops bind.  A rebind copies values
into the compiled input's own leaves, so the compiled inputs are never
bound themselves.  Op ``k`` binds ``A = V[k % S]`` and ``B = V[(k + 1) %
S]`` (``S`` value sets), so both operands take new values in every op.

``check`` compares the last op's stored blocks with ``A @ B`` in float64
(``scipy.sparse``); ``control`` puts the three-pass bf16 product of the
same operands in the program's place.  ``work`` counts both operands'
stored blocks as read, unlike ``reference.product_work``, whose ``X @ X``
reads one.
"""
from __future__ import annotations

import numpy as np

from .. import data, reference
from ..drivers import annotate, stored_blocks, timed


def product_work(rows, cols, bs: int, itemsize: int = 4) -> dict:
    """Least work of ``A @ B`` with A and B on the pattern of ``(rows,
    cols)``: ``2 bs^3`` flops per block triple, each stored block of both
    operands read once and each output block written once."""
    work = reference.product_work(rows, cols, bs, itemsize=itemsize)
    blk = bs * bs * itemsize
    work["bytes"] = float((2 * work["in_blocks"] + work["out_blocks"]) * blk)
    return work


def product_control(a, b):
    """``A @ B`` as a three-pass bf16 product (``Precision.HIGH``)."""
    a_hi, a_lo = reference.split3(a)
    b_hi, b_lo = reference.split3(b)
    c = a_hi @ b_hi + a_hi @ b_lo + a_lo @ b_hi
    c.data = c.data.astype(np.float32).astype(np.float64)
    return c.tocsr()


class Driver:
    """Replays of a compiled ``A @ B`` on the device mesh."""

    chips = 1

    def __init__(self, cfg: dict, traffic: dict, seed: int, tracer=None):
        self.cfg = cfg
        self.trace = False if tracer is None else tracer
        self.phases: dict = {}
        self.sets = traffic["value_sets"]
        lo, hi = traffic["width_range"]
        self.widths = data.rng_of(seed).uniform(lo, hi,
                                                self.sets + 2).tolist()
        self.last = None

    def setup(self) -> None:
        from repro import Session
        from repro.launch.mesh_exec import MeshEngine

        cfg, ph = self.cfg, self.phases
        with timed(ph, "pattern"):
            self.pts, self.rows, self.cols, self.n = \
                data.overlap_problem(cfg)
            self.work = product_work(self.rows, self.cols, cfg["bs"])
        self.engine = MeshEngine(n_dev=self.chips)
        self.session = Session(lazy=True, engine=self.engine,
                               leaf_n=cfg["leaf_n"], bs=cfg["bs"],
                               trace=self.trace)
        with timed(ph, "value_sets"):
            self.ms = [self.session.from_pattern(
                self.rows, self.cols, self.n,
                value_fn=data.overlap_values(self.pts, w),
                name=("A", "B")[k] if k < 2 else None)
                for k, w in enumerate(self.widths)]
        with timed(ph, "first_run"):    # lowers, registers, compiles
            self.plan = self.session.compile(self.ms[0] @ self.ms[1])
            self.out = self.plan.run()
            self.session.flush()
        with timed(ph, "warm_replay"):  # the last op of a cycle
            self.op(self.sets - 1)

    def op(self, k: int) -> None:
        a, b = 2 + k % self.sets, 2 + (k + 1) % self.sets
        with annotate("bench.plan_run"):
            self.out = self.plan.run(A=self.ms[a], B=self.ms[b], flush=False)
        with annotate("bench.flush"):
            self.session.flush()
        self.last = (a, b)

    def release(self) -> None:
        """Keep the last result's host blocks; drop everything else."""
        self.got = stored_blocks(self.out)
        del self.plan, self.ms, self.out, self.session, self.engine

    def _operands(self):
        return [reference.sparse_matrix(
            self.rows, self.cols, self.n,
            data.overlap_values(self.pts, self.widths[v]))
            for v in self.last]

    def check(self, limits: dict, ops: int) -> dict:
        """The last op's result against ``A @ B`` in float64."""
        a, b = self._operands()
        errs = reference.block_errors(self.got, (a @ b).tocsr(),
                                      self.cfg["bs"])
        return {"row_err": (errs["row_err"], limits["row_err"])}

    def control(self) -> dict:
        """The same comparison with the control in the program's place."""
        bs = self.cfg["bs"]
        a, b = self._operands()
        ctrl = reference.csr_blocks(product_control(a, b), bs)
        return {"row_err": reference.block_errors(
            ctrl, (a @ b).tocsr(), bs)["row_err"]}
