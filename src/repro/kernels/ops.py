"""Jit'd public wrappers around the Pallas kernels with XLA fallbacks.

``use_pallas=None`` (default) selects the Pallas kernel on TPU and the XLA
reference on CPU; ``interpret=None`` compiles the kernel on TPU and runs
its body in Python on CPU (how kernels are validated in this repo's
tests).  Any other backend raises: there is no silent fallback that would
hide which device ran the work.  The contract of each op is defined by
kernels/ref.py.
"""
from __future__ import annotations

from typing import Optional

import jax

from . import ref
from .batched_gemm import batched_gemm as _batched_gemm_kernel
from .block_attention import banded_attention as _banded_attention_kernel
from .bsmm_pairs import bsmm_pairs as _bsmm_pairs_kernel


def resolve(use_pallas: Optional[bool] = None,
            interpret: Optional[bool] = None) -> tuple[bool, bool]:
    """The ``(use_pallas, interpret)`` pair a kernel call will run with.

    ``None`` entries resolve from the default backend: ``tpu`` -> compiled
    Pallas, ``cpu`` -> XLA reference / interpret mode.  Any other backend
    raises ``RuntimeError``, so a run can never fall back without saying.
    """
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"no kernel path for the {backend!r} backend: the Pallas kernels "
            f"compile for 'tpu' and run interpreted on 'cpu' only")
    on_tpu = backend == "tpu"
    if use_pallas is None:
        use_pallas = on_tpu
    if interpret is None:
        interpret = not on_tpu
    return bool(use_pallas), bool(interpret)


def batched_gemm(a: jax.Array, b: jax.Array, *, block_t: int = 8,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """C[p] = A[p] @ B[p]; (P, bs, bs) each.

    ``use_pallas=True`` with ``interpret=None`` exercises the kernel body
    everywhere (compiled on TPU, interpreted on CPU).  The kernel
    zero-pads batches to a multiple of ``block_t`` internally.
    """
    use_pallas, interpret = resolve(use_pallas, interpret)
    if not use_pallas:
        return ref.batched_gemm_ref(a, b)
    return _batched_gemm_kernel(a, b, block_t=block_t, interpret=interpret)


def bsmm_pairs(a_blocks: jax.Array, b_blocks: jax.Array, sa: jax.Array,
               sb: jax.Array, seg: jax.Array, *, cap_c: int,
               use_pallas: Optional[bool] = None,
               interpret: Optional[bool] = None) -> jax.Array:
    """C[seg[p]] += A[sa[p]] @ B[sb[p]]; seg ascending, cap_c = invalid.

    Slots no pair reaches come back zero on both paths.
    """
    use_pallas, interpret = resolve(use_pallas, interpret)
    if not use_pallas:
        return ref.bsmm_pairs_ref(a_blocks, b_blocks, sa, sb, seg, cap_c)
    return _bsmm_pairs_kernel(a_blocks, b_blocks, sa, sb, seg,
                              cap_c=cap_c, interpret=interpret)


def banded_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     window: int, block_q: int = 128, block_kv: int = 128,
                     causal: bool = True,
                     use_pallas: Optional[bool] = None,
                     interpret: bool = False) -> jax.Array:
    """Sliding-window attention, (H, S, D) -> (H, S, D)."""
    use_pallas, _ = resolve(use_pallas)
    if not use_pallas:
        return ref.banded_attention_ref(q, k, v, window, causal=causal)
    return _banded_attention_kernel(
        q, k, v, window=window, block_q=block_q, block_kv=block_kv,
        causal=causal, interpret=interpret)
