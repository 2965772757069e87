"""Float64 references, lower-precision controls and the structural work count.

Nothing here imports the program.  The reference that decides ``correct``
is the product ``X @ X`` of an overlap matrix in float64 (``scipy.sparse``),
compared block row by block row; a result in symmetric upper storage is
compared on its upper block triangle, which holds every independent entry.

The control is the same product in the nearest precision below the
configurations' float32 at ``Precision.HIGHEST``: ``HIGH``, three bf16
passes.  ``split3`` writes a float32 value as ``hi + lo`` in bfloat16 and
a product as ``hi*hi + hi*lo + lo*hi``, which is what a three-pass MXU
product computes; it is spelled out so that the control reads the same on
the CPU, whose float32 products ignore the precision flag.
"""
from __future__ import annotations

import math

import ml_dtypes
import numpy as np
import scipy.sparse as sp

BF16 = ml_dtypes.bfloat16


# -- products -----------------------------------------------------------------

def sparse_matrix(rows, cols, n: int, value_fn) -> sp.csr_matrix:
    """The float64 matrix with the given pattern and values."""
    return sp.csr_matrix((value_fn(rows, cols), (rows, cols)), shape=(n, n))


def product_reference(x: sp.csr_matrix) -> sp.csr_matrix:
    """``X @ X`` in float64."""
    return (x @ x).tocsr()


def split3(x: sp.csr_matrix) -> tuple[sp.csr_matrix, sp.csr_matrix]:
    """``(hi, lo)``: the bfloat16 head and tail of float32 ``x``."""
    data = x.data.astype(np.float32)
    hi = data.astype(BF16).astype(np.float32)
    lo = (data - hi).astype(BF16).astype(np.float32)
    mk = lambda d: sp.csr_matrix((d.astype(np.float64), x.indices,
                                  x.indptr), shape=x.shape)
    return mk(hi), mk(lo)


def product_control(x: sp.csr_matrix) -> sp.csr_matrix:
    """``X @ X`` as a three-pass bf16 product (``Precision.HIGH``)."""
    hi, lo = split3(x)
    c = hi @ hi + hi @ lo + lo @ hi
    c.data = c.data.astype(np.float32).astype(np.float64)
    return c.tocsr()


def csr_blocks(m: sp.csr_matrix, bs: int, upper: bool = False) -> dict:
    """``{block row: [(block col, float32 block)]}`` of nonzero blocks;
    with ``upper`` only those of the upper block triangle."""
    out: dict[int, list] = {}
    for bi in range(m.shape[0] // bs):
        strip = m[bi * bs:(bi + 1) * bs]
        if strip.nnz == 0:
            continue
        dense = strip.toarray()
        cols = np.unique(strip.indices // bs)
        if upper:
            cols = cols[cols >= bi]
        out[bi] = [(int(bj), dense[:, bj * bs:(bj + 1) * bs]
                    .astype(np.float32)) for bj in cols]
    return out


def block_errors(got: dict, ref: sp.csr_matrix, bs: int,
                 upper: bool = False) -> dict:
    """Relative Frobenius errors of stored blocks against a reference.

    ``got`` maps a block row to its ``[(block col, block)]``; with
    ``upper`` it holds the upper block triangle and is compared with the
    reference's.  Returns ``{"rel_err": ||G - R||_F / ||R||_F, "row_err":
    the worst block row's ||G_i - R_i||_F / ||R_i||_F}``, streamed one block
    row at a time; a block row that the reference holds and ``got`` lacks,
    or the reverse, reads as infinitely wrong.
    """
    err2 = ref2 = 0.0
    worst = 0.0
    n = ref.shape[0]
    for bi in range(n // bs):
        off = bi * bs if upper else 0
        row = ref[bi * bs:(bi + 1) * bs, off:]
        blocks = got.get(bi, [])
        if not blocks and row.nnz == 0:
            continue
        if not blocks or row.nnz == 0:
            worst = math.inf
            continue
        cols = [bj * bs - off for bj, _ in blocks]
        lo = min(cols + [int(row.indices.min())])
        hi = max([c + bs for c in cols] + [int(row.indices.max()) + 1])
        dense = row[:, lo:hi].toarray()
        r2 = float(np.square(dense).sum())
        for c, (_, blk) in zip(cols, blocks):
            dense[:, c - lo:c - lo + bs] -= blk
        e2 = float(np.square(dense).sum())
        err2, ref2 = err2 + e2, ref2 + r2
        worst = max(worst, math.sqrt(e2 / r2))
    return {"rel_err": math.sqrt(err2 / ref2), "row_err": worst}


def product_work(rows, cols, bs: int, upper: bool = False,
                 itemsize: int = 4) -> dict:
    """Least work of ``X @ X`` on the block pattern of ``(rows, cols)``.

    ``flops`` counts ``2 bs^3`` for every block triple (i, k, j) with
    X_ik and X_kj stored; ``bytes`` reads each stored block of X once and
    writes each output block once.  With ``upper`` (a symmetric X in upper
    storage, its square likewise) only the triples with i <= j count, and
    only the blocks of the upper triangles are read and written.  Nothing
    here depends on how the program enumerates or packs its pairs.
    """
    br, bc = np.asarray(rows) // bs, np.asarray(cols) // bs
    nb = int(max(br.max(), bc.max())) + 1
    pat = sp.csr_matrix((np.ones(len(br)), (br, bc)), shape=(nb, nb))
    pat.data[:] = 1.0                   # duplicate coordinates were summed
    csc = pat.tocsc()
    triples = 0
    for k in range(nb):
        i_k = np.sort(csc.indices[csc.indptr[k]:csc.indptr[k + 1]])
        j_k = pat.indices[pat.indptr[k]:pat.indptr[k + 1]]
        # pairs (i, j) with X_ik, X_kj stored (and i <= j when upper)
        triples += int(np.searchsorted(i_k, j_k, side="right").sum()
                       if upper else len(i_k) * len(j_k))
    out, inp = pat @ pat, pat
    if upper:
        out, inp = sp.triu(out), sp.triu(inp)
    out_blocks, in_blocks = int(out.nnz), int(inp.nnz)
    blk = bs * bs * itemsize
    return {"pairs": triples, "flops": 2.0 * bs ** 3 * triples,
            "bytes": float((in_blocks + out_blocks) * blk),
            "in_blocks": in_blocks, "out_blocks": out_blocks}
