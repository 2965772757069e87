"""Pallas TPU kernel: batched small-GEMM (the paper's leaf engine, §4.1).

The paper maps block-sparse leaf multiplication onto the cuBLAS *batched*
gemm API because individual 16-64 blocks are too small to fill a GPU.  The
TPU analogue: the MXU is a 128x128 systolic array, so we (a) retune the
default block size toward 128 and (b) tile the batch dimension so each grid
step feeds the MXU a (T*bs, bs) x (bs, bs)-shaped stream of work from VMEM.

BlockSpec layout: each grid step owns a (T, bs, bs) slab of A, B and C in
VMEM.  VMEM budget: 3 * T * bs^2 * 4B; with T=8, bs=128 that is 1.5 MiB —
comfortably inside the ~16 MiB VMEM of a TPU core while leaving room for
double buffering (the pipeline overlaps the HBM->VMEM copy of slab i+1 with
compute on slab i, which is exactly the paper's "overlap data transfers with
computation", §4.2, achieved structurally by the Pallas pipeline).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _precision(dtype):
    # float32 operands: full-precision MXU passes (the engines' documented
    # ~1e-7 relative agreement with float64); narrower dtypes: the default
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _kernel(a_ref, b_ref, o_ref):
    o_ref[...] = jax.lax.dot_general(
        a_ref[...], b_ref[...],
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        precision=_precision(a_ref.dtype),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def batched_gemm(a: jax.Array, b: jax.Array, *, block_t: int = 8,
                 interpret: bool = False) -> jax.Array:
    """C[p] = A[p] @ B[p] for p in [0, P).

    a, b : (P, bs, bs); returns (P, bs, bs) in a's dtype.  Batches that do
    not divide by ``block_t`` are zero-padded up to the next multiple (the
    padding feeds the MXU zero work and is sliced off) — shapes are static
    under jit, so the pad is resolved at trace time.
    """
    p, bs, _ = a.shape
    assert a.shape == b.shape and a.shape[1] == a.shape[2]
    if p == 0:
        return a
    pad = (-p) % block_t
    if pad:
        zeros = jnp.zeros((pad, bs, bs), a.dtype)
        a = jnp.concatenate([a, zeros])
        b = jnp.concatenate([b, zeros])
    out = pl.pallas_call(
        _kernel,
        grid=((p + pad) // block_t,),
        in_specs=[
            pl.BlockSpec((block_t, bs, bs), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_t, bs, bs), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, bs, bs), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((p + pad, bs, bs), a.dtype),
        interpret=interpret,
    )(a, b)
    return out[:p]
