"""Quickstart: locality-aware block-sparse matmul through the Session API.

    PYTHONPATH=src python examples/quickstart.py

1. Build banded matrices as sparse quadtrees of chunks (paper §3) with a
   :class:`repro.Session`, multiply with ``C = A @ B`` on a simulated
   8-worker cluster, and report the communication statistics that make
   the paper's point (locality => tiny comm per worker).
2. Re-run the multiply in a **pallas-engine session**
   (``Session(engine="pallas")``): leaf work across the whole quadtree is
   batched into fused Pallas kernel waves (paper §4.1 batched leaf-level
   work), and the flop/bytes report shows what was batched.
3. Run the same multiply through the static TPU engine (mask-pyramid
   enumeration + capacity-bounded gather-GEMM-scatter, DESIGN.md §3) and
   check everything against dense numpy.
"""
import numpy as np
import jax.numpy as jnp

from repro import Session
from repro.core import blocksparse as bsp
from repro.core.bsmm import bsmm
from repro.core.patterns import (banded_mask, block_mask_from_element_mask,
                                 values_for_mask)


def main() -> None:
    n, bs, d = 512, 8, 16
    a = values_for_mask(banded_mask(n, d), seed=1).astype(np.float32)
    b = values_for_mask(banded_mask(n, d), seed=2).astype(np.float32)
    want = a @ b

    # --- 1. the paper's library on a simulated cluster ------------------
    sess = Session(leaf_n=64, bs=bs, p=8, seed=0)
    A = sess.from_dense(a)
    B = sess.from_dense(b)
    sess.simulate()                    # construction task program places inputs
    C = A @ B
    res = sess.simulate(fresh_stats=True)
    np.testing.assert_allclose(C.to_dense(), want, atol=1e-3)
    print("quadtree multiply: OK")
    print(f"  multiply tasks: {sess.n_multiply_tasks}, "
          f"add tasks: {sess.n_add_tasks} (mult > add, paper §5)")
    print(f"  virtual makespan: {res.makespan*1e3:.2f} ms on 8 workers, "
          f"steals: {res.steals}")
    mb = np.asarray(res.bytes_received) / 1e6
    print(f"  comm per worker: avg {mb.mean():.2f} MB, max {mb.max():.2f}"
          " MB  <- locality keeps this flat as the cluster grows")

    # --- 2. same multiply, pallas leaf backend (batched kernel waves) ---
    sess2 = Session(engine="pallas", leaf_n=64, bs=bs)
    C2 = sess2.from_dense(a) @ sess2.from_dense(b)
    np.testing.assert_allclose(C2.to_dense(), want, atol=1e-3)
    st = sess2.engine_stats()
    print('leaf backend engine="pallas": OK (matches engine="numpy")')
    print(f"  flop/bytes report: {sess2.flops:.3g} useful flops in "
          f"{st['waves']} fused wave(s); {st['batched_pairs']} block pairs "
          f"batched ({st['padded_pairs'] - st['batched_pairs']} padding), "
          f"{st['bytes_packed'] / 1e6:.2f} MB packed, "
          f"kernel {st['kernel']} dispatched in {st['dispatch_s'] * 1e3:.1f} ms")

    # --- 3. the TPU engine (jit, static shapes) -------------------------
    ma = block_mask_from_element_mask(np.abs(a) > 0, bs)
    mb_ = block_mask_from_element_mask(np.abs(b) > 0, bs)
    caps = bsp.plan_caps(ma, mb_)
    A_ = bsp.from_dense(jnp.asarray(a), bs, int(ma.sum()) + 8)
    B_ = bsp.from_dense(jnp.asarray(b), bs, int(mb_.sum()) + 8)
    c, info = bsmm(A_, B_, pair_caps=caps, cap_c=bsp.plan_c_cap(ma, mb_))
    np.testing.assert_allclose(np.asarray(bsp.to_dense(c)), want,
                               atol=1e-2)
    print("TPU block-sparse engine: OK")
    print(f"  surviving block pairs: {int(info['n_pairs'])} "
          f"(the paper's leaf-level task count), "
          f"C blocks: {int(info['n_c_blocks'])}")


if __name__ == "__main__":
    main()
