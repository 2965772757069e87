"""Sparse SUMMA baseline (Buluc & Gilbert [46]) on a shard_map process grid.

The comparison target of the paper (Table 1, Figs 12-14): a static 2D
sqrt(p) x sqrt(p) decomposition where each device owns one panel of A, B
and C; stage-free formulation via all_gather of the A row-slab along the
process-grid columns and the B col-slab along the rows, then a local
sparse multiply.  Communication per device is the whole row/col slab:
(sqrt(p)-1)/sqrt(p) * (|A_row| + |B_col|) bytes — eq (15)'s 2mN/sqrt(p)
elements — growing as sqrt(p) in weak scaling, with or without data
locality in the pattern.

An optional host-side **random permutation** of block rows/cols mimics the
load-balancing maneuver of [21, 22] that the paper argues *destroys*
locality (Fig 1).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .blocksparse import enumerate_pairs_flat


def summa_pgrid(p: int) -> int:
    """sqrt(p), validated: SpSUMMA runs on a square process grid.

    A non-square device count used to fall through ``int(np.sqrt(p))``
    and silently shard onto a smaller sub-grid (p=6 -> 2x2, two devices
    idle and every measured slab-byte count wrong).  Fail fast instead.
    """
    p = int(p)
    if p < 1:
        raise ValueError(f"SpSUMMA needs at least one device, got p={p}")
    pgrid = int(round(p ** 0.5))
    if pgrid * pgrid != p:
        raise ValueError(
            f"SpSUMMA needs a perfect-square device count for its "
            f"sqrt(p) x sqrt(p) process grid; got p={p}. Use p in "
            f"{{1, 4, 9, 16, ...}} or the parent-worker mesh engine "
            f"(Session(engine='mesh')), which accepts any device count.")
    return pgrid


@dataclasses.dataclass(frozen=True)
class SummaPlan:
    grid: int              # global block grid
    bs: int
    pgrid: int             # process grid is pgrid x pgrid
    cap_panel: int         # max nonzero blocks in any owned panel
    cap_c_panel: int
    cap_pairs: int         # local multiply pair capacity

    @property
    def n_dev(self) -> int:
        return self.pgrid ** 2

    @property
    def panel(self) -> int:        # blocks per panel side
        return self.grid // self.pgrid


def plan_summa(mask_a: np.ndarray, mask_b: np.ndarray, bs: int,
               pgrid: int, slack: float = 1.3, round_to: int = 8
               ) -> SummaPlan:
    grid = mask_a.shape[0]
    summa_pgrid(pgrid * pgrid)      # pgrid must be a positive integer
    if grid % pgrid != 0:
        raise ValueError(
            f"SpSUMMA panel split needs the block grid ({grid}) to be "
            f"divisible by pgrid ({pgrid}); pad the matrix or pick a "
            f"device count whose sqrt divides the grid.")
    panel = grid // pgrid
    ma, mb = np.asarray(mask_a), np.asarray(mask_b)
    mc = (ma.astype(np.int64) @ mb.astype(np.int64)) > 0

    def _panels(m):
        return m.reshape(pgrid, panel, pgrid, panel).sum(axis=(1, 3))

    def _cap(x):
        return max(round_to, int(np.ceil(x * slack / round_to)) * round_to)

    cap_panel = _cap(int(max(_panels(ma).max(), _panels(mb).max())))
    cap_c_panel = _cap(int(_panels(mc).max()))
    # local pairs: row-slab of A x col-slab of B restricted to own panel
    worst = 0
    for r in range(pgrid):
        for c in range(pgrid):
            a_slab = ma[r * panel:(r + 1) * panel, :].astype(np.int64)
            b_slab = mb[:, c * panel:(c + 1) * panel].astype(np.int64)
            worst = max(worst, int((a_slab.sum(0) * b_slab.sum(1)).sum()))
    cap_pairs = _cap(worst)
    return SummaPlan(grid=grid, bs=bs, pgrid=pgrid, cap_panel=cap_panel,
                     cap_c_panel=cap_c_panel, cap_pairs=cap_pairs)


def random_block_permutation(grid: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).permutation(grid)


def distribute_panels(dense: np.ndarray, bs: int, plan: SummaPlan,
                      perm: Optional[np.ndarray] = None
                      ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pack a dense matrix into (n_dev, cap_panel, bs, bs) 2D-panel shards.

    Coordinates are *global* block indices (after the optional random
    permutation), padding == grid.  Device order is row-major over the
    process grid.
    """
    grid, pgrid, panel, cap = plan.grid, plan.pgrid, plan.panel, \
        plan.cap_panel
    if perm is not None:
        gp = np.repeat(perm, bs) * bs + np.tile(np.arange(bs), grid)
        dense = dense[np.ix_(gp, gp)]
    tiles = dense.reshape(grid, bs, grid, bs).transpose(0, 2, 1, 3)
    occ = np.abs(tiles).max(axis=(2, 3)) > 0
    n_dev = plan.n_dev
    blocks = np.zeros((n_dev, cap, bs, bs), dense.dtype)
    rows = np.full((n_dev, cap), grid, np.int32)
    cols = np.full((n_dev, cap), grid, np.int32)
    fill = np.zeros(n_dev, np.int64)
    for i, j in zip(*np.nonzero(occ)):
        d = (i // panel) * pgrid + (j // panel)
        s = fill[d]
        assert s < cap
        blocks[d, s] = tiles[i, j]
        rows[d, s] = i
        cols[d, s] = j
        fill[d] += 1
    return blocks, rows, cols


def summa_spmm(mesh: Mesh, axes: tuple[str, str], plan: SummaPlan,
               a_blocks, a_rows, a_cols, b_blocks, b_rows, b_cols):
    """C = A @ B via SpSUMMA all_gathers on a (pr, pc) process grid.

    Arrays carry a leading n_dev axis laid out row-major over (pr, pc) and
    sharded over both mesh axes.  Returns (c_blocks, c_rows, c_cols,
    n_pairs) with the same leading layout.
    """
    g, bs, pgrid = plan.grid, plan.bs, plan.pgrid
    cap_c, cap_pairs = plan.cap_c_panel, plan.cap_pairs
    ax_r, ax_c = axes

    def body(ab, ar, ac, bb, br, bc):
        ab, ar, ac = ab[0], ar[0], ac[0]
        bb, br, bc = bb[0], br[0], bc[0]
        pr = jax.lax.axis_index(ax_r)
        pc = jax.lax.axis_index(ax_c)

        # the SpSUMMA communication: row-slab of A, col-slab of B
        A = jax.lax.all_gather(ab, ax_c).reshape(-1, bs, bs)
        Ar = jax.lax.all_gather(ar, ax_c).reshape(-1)
        Ac = jax.lax.all_gather(ac, ax_c).reshape(-1)
        B = jax.lax.all_gather(bb, ax_r).reshape(-1, bs, bs)
        Br = jax.lax.all_gather(br, ax_r).reshape(-1)
        Bc = jax.lax.all_gather(bc, ax_r).reshape(-1)

        slot_a = jnp.full((g + 1, g + 1), -1, jnp.int32).at[Ar, Ac].set(
            jnp.arange(Ar.shape[0], dtype=jnp.int32))
        slot_a = slot_a.at[g, :].set(-1).at[:, g].set(-1)
        slot_b = jnp.full((g + 1, g + 1), -1, jnp.int32).at[Br, Bc].set(
            jnp.arange(Br.shape[0], dtype=jnp.int32))
        slot_b = slot_b.at[g, :].set(-1).at[:, g].set(-1)
        mask_a = slot_a[:g, :g] >= 0
        mask_b = slot_b[:g, :g] >= 0

        panel = g // pgrid
        r_idx = jax.lax.broadcasted_iota(jnp.int32, (g, g), 0)
        c_idx = jax.lax.broadcasted_iota(jnp.int32, (g, g), 1)
        owned = ((r_idx // panel == pr) & (c_idx // panel == pc))
        mask_c = (jnp.matmul(mask_a.astype(jnp.int32),
                             mask_b.astype(jnp.int32)) > 0) & owned

        crows, ccols = jnp.nonzero(mask_c, size=cap_c, fill_value=g)
        crows, ccols = crows.astype(jnp.int32), ccols.astype(jnp.int32)
        cslot = jnp.full((g + 1, g + 1), -1, jnp.int32).at[crows, ccols].set(
            jnp.arange(cap_c, dtype=jnp.int32))
        cslot = cslot.at[g, :].set(-1).at[:, g].set(-1)

        m3 = mask_a[:, :, None] & mask_b[None, :, :] & mask_c[:, None, :]
        pi, pk, pj = jnp.nonzero(m3, size=cap_pairs, fill_value=g)
        n_pairs = jnp.sum(m3).astype(jnp.int32)
        sa, sb, sc = slot_a[pi, pk], slot_b[pk, pj], cslot[pi, pj]
        pvalid = (sa >= 0) & (sb >= 0) & (sc >= 0)
        prods = jnp.einsum(
            "pik,pkj->pij", A[jnp.maximum(sa, 0)], B[jnp.maximum(sb, 0)],
            preferred_element_type=jnp.float32).astype(A.dtype)
        prods = jnp.where(pvalid[:, None, None], prods, 0)
        seg = jnp.where(pvalid, sc, cap_c)
        cb = jax.ops.segment_sum(prods, seg, num_segments=cap_c + 1)[:cap_c]
        return cb[None], crows[None], ccols[None], n_pairs[None]

    spec = P((ax_r, ax_c))
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 6,
                       out_specs=(spec,) * 4, check_vma=False)
    return fn(a_blocks, a_rows, a_cols, b_blocks, b_rows, b_cols)
