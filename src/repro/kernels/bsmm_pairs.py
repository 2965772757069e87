"""Pallas TPU kernel: gather-GEMM-scatter over block pairs (DESIGN.md §3).

This is the fused TPU rendering of steps 2-4 of the block-sparse multiply
(core/bsmm.py): for every surviving (A-slot, B-slot, C-slot) triple, gather
the two bs x bs blocks from the packed HBM arrays, multiply on the MXU, and
accumulate into the C slot.

TPU adaptation of the paper's leaf engine (§4.1): instead of cuBLAS batched
gemm + host-side scatter, we use **scalar prefetch** — the slot-id arrays
arrive in SMEM *before* the kernel body runs, and the BlockSpec index maps
read them to steer the HBM->VMEM DMA of each grid step.  Gather therefore
costs exactly one block DMA per pair (no materialized gathered copy in HBM),
and the Pallas pipeline overlaps pair p+1's DMA with pair p's MXU work —
the paper's §4.2 transfer/compute overlap, structurally.

Accumulation requirement: ``seg`` (output slot per pair) must be sorted
ascending, so all writes to one C block are consecutive grid steps; the
kernel seeds the VMEM accumulator on first visit (pl.when) and the final
value is flushed to HBM when the output index map moves on.  Invalid /
padding pairs carry seg == cap_c: they sort to the tail, address the last
C block (so its run simply continues) and add nothing.  C therefore needs
no trailing garbage block, and the result is the kernel's own buffer, not
a sliced copy of it — which halves the device memory of a wave.

SMEM bound: the three prefetched slot tables take 12 bytes per pair, and a
v5e core has 1 MiB of SMEM, so one call takes at most ``MAX_PAIRS`` pairs.
Longer pair streams run as a loop of calls over one C buffer that every
call updates in place (``input_output_aliases``): each call seeds a C block
from the buffer instead of from zero, so a segment split across two calls
keeps accumulating in the same order — the result is bitwise that of one
call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .batched_gemm import _precision

#: pairs per kernel call: 3 int32 tables x 32768 = 384 KiB of the 1 MiB
#: SMEM of a TPU v5e core (100k pairs in one call is refused at compile)
MAX_PAIRS = 1 << 15


def _kernel(sa_ref, sb_ref, seg_ref, a_ref, b_ref, c_ref, o_ref, *,
            cap_c: int):
    p = pl.program_id(0)
    seg_here = seg_ref[p]
    slot_here = jnp.minimum(seg_here, cap_c - 1)
    slot_prev = jnp.minimum(seg_ref[jnp.maximum(p - 1, 0)], cap_c - 1)
    first_visit = jnp.logical_or(p == 0, slot_here != slot_prev)

    @pl.when(first_visit)
    def _init():
        o_ref[...] = c_ref[...]

    @pl.when(seg_here < cap_c)
    def _accumulate():
        prod = jax.lax.dot_general(
            a_ref[0], b_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=_precision(a_ref.dtype),
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)
        o_ref[...] += prod[None]


def _call(sa, sb, seg, a_blocks, b_blocks, c, *, interpret: bool):
    """One call: C[seg[p]] += A[sa[p]] @ B[sb[p]], C updated in place."""
    bs, cap_c = a_blocks.shape[1], c.shape[0]
    index = lambda p, sa_r, sb_r, seg_r: (
        jnp.minimum(seg_r[p], cap_c - 1), 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(sa.shape[0],),
        in_specs=[
            pl.BlockSpec((1, bs, bs),
                         lambda p, sa_r, sb_r, seg_r: (sa_r[p], 0, 0)),
            pl.BlockSpec((1, bs, bs),
                         lambda p, sa_r, sb_r, seg_r: (sb_r[p], 0, 0)),
            pl.BlockSpec((1, bs, bs), index),
        ],
        out_specs=pl.BlockSpec((1, bs, bs), index),
    )
    return pl.pallas_call(
        functools.partial(_kernel, cap_c=cap_c),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(c.shape, c.dtype),
        input_output_aliases={5: 0},
        interpret=interpret,
    )(sa, sb, seg, a_blocks, b_blocks, c)


@functools.partial(jax.jit, static_argnames=("cap_c", "interpret"))
def bsmm_pairs(a_blocks: jax.Array, b_blocks: jax.Array,
               sa: jax.Array, sb: jax.Array, seg: jax.Array, *,
               cap_c: int, interpret: bool = False) -> jax.Array:
    """Accumulate C[seg[p]] += A[sa[p]] @ B[sb[p]] over all pairs.

    a_blocks : (capA, bs, bs); b_blocks : (capB, bs, bs)
    sa, sb   : (P,) int32 slot ids (clamped to the valid range here)
    seg      : (P,) int32 ascending; cap_c marks invalid pairs
    returns  : (cap_c, bs, bs) accumulated C blocks (a_blocks.dtype);
               slots no pair reaches are zero
    """
    return _in_calls(a_blocks, b_blocks, sa, sb, seg, cap_c=cap_c,
                     interpret=interpret, max_pairs=MAX_PAIRS)


def _in_calls(a_blocks, b_blocks, sa, sb, seg, *, cap_c: int,
              interpret: bool, max_pairs: int):
    """:func:`bsmm_pairs` as calls of at most ``max_pairs`` pairs each."""
    (p_cnt,) = sa.shape
    bs = a_blocks.shape[1]
    c = jnp.zeros((cap_c, bs, bs), a_blocks.dtype)
    if p_cnt == 0 or cap_c == 0:     # static under jit: all-zero C
        return c
    sa = jnp.clip(sa, 0, a_blocks.shape[0] - 1)
    sb = jnp.clip(sb, 0, b_blocks.shape[0] - 1)
    seg = jnp.minimum(seg, cap_c)
    n_calls = -(-p_cnt // max_pairs)
    size = -(-p_cnt // n_calls)
    pad = n_calls * size - p_cnt
    if pad:            # trailing invalid pairs keep seg ascending
        sa = jnp.concatenate([sa, jnp.zeros((pad,), sa.dtype)])
        sb = jnp.concatenate([sb, jnp.zeros((pad,), sb.dtype)])
        seg = jnp.concatenate([seg, jnp.full((pad,), cap_c, seg.dtype)])
    run = functools.partial(_call, a_blocks=a_blocks, b_blocks=b_blocks,
                            interpret=interpret)
    if n_calls == 1:
        c = run(sa, sb, seg, c=c)
    else:
        sa, sb, seg = (x.reshape(n_calls, size) for x in (sa, sb, seg))
        c = jax.lax.fori_loop(
            0, n_calls, lambda k, c: run(sa[k], sb[k], seg[k], c=c), c)
    return c
