"""The traffic generator: one driver per kind of operation a mix asks for.

A traffic file (``traffic/<mix>.json``) names its ``driver`` and gives its
parameters; the configuration file gives the sizes.  A driver makes its
inputs from the seed, sets up and warms every shape in ``setup``, runs one
operation in ``op``, and after the window compares what the timed path
produced with the float64 reference in ``check``.  ``control`` puts the
lower-precision control in the program's place for the same comparison
(``control.py`` reads it; the benchmark's runs never do).

A driver is found by name: one of :data:`DRIVERS`, or else the class
``Driver`` of ``drive/<driver>.py``, so that a new driver is a new file.
:func:`make_driver` sets the driver's ``chips`` to the cell's chip count
after building it; a driver that runs on one device may ignore it.

Built-in drivers:

* ``plan_replay`` — a compiled ``Plan`` of the square of an ``overlap3d``
  configuration's matrix, replayed with rebound value sets; one op is
  ``plan.run(X=<next value set>)`` and the flush of its waves.  The
  traffic's ``product`` picks the square: ``matmul`` (``X @ X``, general
  storage) or ``sym_square`` (``X.sym_square()``, symmetric upper
  storage, arXiv:1501.07800 §3.3).

The program is imported inside the drivers only, so that the rest of the
benchmark loads without it.
"""
from __future__ import annotations

import contextlib
import importlib
import pathlib
import time

from . import data, reference

__all__ = ["DRIVERS", "driver_class", "make_driver"]

DRIVE = pathlib.Path(__file__).resolve().parent / "drive"


def annotate(label: str):
    """A host span on the profiler's clock (labels the device's gaps)."""
    import jax
    return jax.profiler.TraceAnnotation(label)


@contextlib.contextmanager
def timed(phases: dict, name: str):
    """Record the seconds of a set-up phase under ``phases[name]``."""
    t0 = time.perf_counter()
    yield
    phases[name] = time.perf_counter() - t0


def stored_blocks(m) -> dict:
    """``{block row: [(block col, block)]}`` of a result's stored blocks.

    Walks the result's quadtree as the host holds it after a flush,
    without densifying.
    """
    g, bs = m.session.graph, m.params.bs
    out: dict[int, list] = {}

    def walk(nid, r0: int, c0: int) -> None:
        chunk = g.value_of(nid)
        if chunk is None:
            return
        if chunk.is_leaf:
            for (i, j), blk in chunk.leaf.blocks.items():
                out.setdefault(r0 // bs + i, []).append((c0 // bs + j, blk))
            return
        h = chunk.n // 2
        for q, child in enumerate(chunk.children):
            walk(child, r0 + (q // 2) * h, c0 + (q % 2) * h)

    walk(m.node, 0, 0)
    for blocks in out.values():
        blocks.sort(key=lambda cb: cb[0])
    return out


class PlanReplay:
    """Replays of a compiled square over rebound overlap matrices."""

    PRODUCTS = ("matmul", "sym_square")

    def __init__(self, cfg: dict, traffic: dict, seed: int, tracer=None):
        self.cfg, self.traffic = cfg, traffic
        self.trace = False if tracer is None else tracer
        product = traffic.get("product", "matmul")
        if product not in self.PRODUCTS:
            raise ValueError(f"unknown product {product!r}; "
                             f"known: {self.PRODUCTS}")
        self.upper = product == "sym_square"
        self.phases: dict = {}
        lo, hi = traffic["width_range"]
        # value set 0 is the compiled input slot; ops cycle over 1..V
        self.widths = data.rng_of(seed).uniform(
            lo, hi, traffic["value_sets"] + 1).tolist()
        self.last = None

    def setup(self) -> None:
        from repro import Session

        cfg, ph = self.cfg, self.phases
        with timed(ph, "pattern"):
            self.pts, self.rows, self.cols, self.n = \
                data.overlap_problem(cfg)
            self.work = reference.product_work(self.rows, self.cols,
                                               cfg["bs"], upper=self.upper)
        self.session = Session(lazy=True, engine="pallas",
                               leaf_n=cfg["leaf_n"], bs=cfg["bs"],
                               trace=self.trace)
        with timed(ph, "value_sets"):
            self.xs = [self.session.from_pattern(
                self.rows, self.cols, self.n,
                value_fn=data.overlap_values(self.pts, w), upper=self.upper,
                name="X" if k == 0 else None)
                for k, w in enumerate(self.widths)]
        with timed(ph, "first_run"):    # lowers, registers, compiles
            x = self.xs[0]
            self.plan = self.session.compile(
                x.sym_square() if self.upper else x @ x)
            self.out = self.plan.run()
            self.session.flush()
        with timed(ph, "warm_replay"):  # one replay of the last set
            self.op(len(self.widths) - 2)

    def op(self, k: int) -> None:
        v = 1 + k % (len(self.widths) - 1)
        with annotate("bench.plan_run"):
            self.out = self.plan.run(X=self.xs[v], flush=False)
        with annotate("bench.flush"):
            self.session.flush()
        self.last = v

    def release(self) -> None:
        """Keep the last result's host blocks; drop everything else."""
        self.got = stored_blocks(self.out)
        del self.plan, self.xs, self.out, self.session

    def _matrix(self, v: int):
        return reference.sparse_matrix(
            self.rows, self.cols, self.n,
            data.overlap_values(self.pts, self.widths[v]))

    def check(self, limits: dict, ops: int) -> dict:
        """The last op's result against ``X @ X`` in float64."""
        x = self._matrix(self.last)
        ref = reference.product_reference(x)
        errs = reference.block_errors(self.got, ref, self.cfg["bs"],
                                      upper=self.upper)
        return {"row_err": (errs["row_err"], limits["row_err"])}

    def control(self) -> dict:
        """The same comparison with the control in the program's place."""
        bs = self.cfg["bs"]
        x = self._matrix(self.last)
        ref = reference.product_reference(x)
        ctrl = reference.csr_blocks(reference.product_control(x), bs,
                                    upper=self.upper)
        return {"row_err": reference.block_errors(
            ctrl, ref, bs, upper=self.upper)["row_err"]}


DRIVERS = {"plan_replay": PlanReplay}


def driver_class(name: str):
    """The built-in driver ``name``, else ``Driver`` of ``drive/<name>.py``."""
    if name in DRIVERS:
        return DRIVERS[name]
    if (DRIVE / f"{name}.py").is_file():
        return importlib.import_module(f"{__package__}.drive.{name}").Driver
    files = sorted(p.stem for p in DRIVE.glob("*.py") if p.stem != "__init__")
    raise ValueError(f"unknown traffic driver {name!r}; built in: "
                     f"{sorted(DRIVERS)}; in {DRIVE.name}/: {files}")


def make_driver(cfg: dict, traffic: dict, seed: int, tracer=None,
                chips: int = 1):
    driver = driver_class(traffic["driver"])(cfg, traffic, seed, tracer)
    driver.chips = chips
    return driver
