"""Inputs of the benchmark's cells, made from a configuration and a seed.

The generators are the benchmark's own copies, so that the inputs of a
cell stay fixed when the program changes: ``particle_cloud``,
``divide_space_order`` and ``overlap_pairs`` follow ``repro.core.patterns``
— hydrogen-like particles on a jittered 3-D grid in recursive divide-space
(Ergo) order, and the element pairs closer than the cutoff: the overlap
pattern of arXiv:1501.07800, §6.2.

The geometry is fixed by the configuration (its ``cloud_seed``); the run's
``--seed`` draws values only, so every seed runs the same shapes.
"""
from __future__ import annotations

import itertools
import math

import numpy as np


def rng_of(seed: int) -> np.random.Generator:
    """A generator for any whole-number seed, negative or above 2**63."""
    return np.random.default_rng(seed % (1 << 64))


# -- 3-D overlap pattern -----------------------------------------------------

def particle_cloud(n_per_dim: int, dim: int, spacing: float = 2.0,
                   jitter: float = 1.0, seed: int = 0) -> np.ndarray:
    """Particles on a ``dim``-D grid with uniform random jitter."""
    rng = np.random.default_rng(seed)
    axes = [np.arange(n_per_dim, dtype=np.float64) * spacing] * dim
    grid = np.stack(np.meshgrid(*axes, indexing="ij"), axis=-1)
    grid = grid.reshape(-1, dim)
    return grid + rng.uniform(-jitter, jitter, size=grid.shape)


def divide_space_order(coords: np.ndarray) -> np.ndarray:
    """Recursive halving at the median of the widest axis."""
    order: list[int] = []

    def rec(idx: np.ndarray) -> None:
        if len(idx) <= 1:
            order.extend(idx.tolist())
            return
        pts = coords[idx]
        axis = int(np.argmax(pts.max(axis=0) - pts.min(axis=0)))
        mid = len(idx) // 2
        part = np.argpartition(pts[:, axis], mid - 1)
        rec(idx[part[:mid]])
        rec(idx[part[mid:]])

    rec(np.arange(len(coords)))
    return np.asarray(order, dtype=np.int64)


def overlap_pairs(pts: np.ndarray, radius: float
                  ) -> tuple[np.ndarray, np.ndarray]:
    """``(rows, cols)`` of the point pairs closer than ``radius``.

    Cell-list search; the indices are positions in ``pts``, self pairs
    included.
    """
    dim = pts.shape[1]
    cid = np.floor((pts - pts.min(axis=0)) / radius).astype(np.int64)
    ncell = cid.max(axis=0) + 1
    mult = np.cumprod(np.concatenate([[1], ncell[:-1]]))
    lin = cid @ mult
    by_cell = np.argsort(lin, kind="stable")
    starts = np.searchsorted(lin[by_cell], np.arange(int(ncell.prod()) + 1))
    rows, cols = [], []
    for off in itertools.product((-1, 0, 1), repeat=dim):
        nb = cid + np.asarray(off)
        ok = np.all((nb >= 0) & (nb < ncell), axis=1)
        src = np.nonzero(ok)[0]
        nb_lin = nb[ok] @ mult
        s, e = starts[nb_lin], starts[nb_lin + 1]
        cnt = e - s
        if cnt.sum() == 0:
            continue
        rep = np.repeat(src, cnt)
        idx = np.concatenate([by_cell[a:b] for a, b in zip(s, e)])
        keep = ((pts[rep] - pts[idx]) ** 2).sum(axis=1) < radius * radius
        rows.append(rep[keep])
        cols.append(idx[keep])
    return np.concatenate(rows), np.concatenate(cols)


def overlap_problem(cfg: dict):
    """``(pts, rows, cols, n)`` of an ``overlap3d`` configuration.

    ``pts`` are in divide-space order and ``n`` is the next power of two
    at or above the particle count.
    """
    coords = particle_cloud(cfg["n_per_dim"], cfg["dim"], cfg["spacing"],
                            cfg["jitter"], seed=cfg["cloud_seed"])
    pts = coords[divide_space_order(coords)]
    rows, cols = overlap_pairs(pts, cfg["cutoff"])
    n = 1 << int(math.ceil(math.log2(len(pts))))
    return pts, rows, cols, n


def overlap_values(pts: np.ndarray, width: float):
    """Element values ``exp(-|xi - xj|^2 / width)`` for index arrays."""
    def value_fn(r, c):
        return np.exp(-((pts[r] - pts[c]) ** 2).sum(-1) / width)
    return value_fn
