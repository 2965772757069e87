"""Paper Figs 5-8: leaf block-sparse multiply throughput vs fill factor.

Default mode — host leaf engine (sum-of-outer-products batching, Fig 2
structure) on randomly occupied block matrices, blocksizes 16/32/64, fill
sweep.  CSV: bs,fill,gflops,block_multiplies,batches,useful_fraction.

``--compare-backends`` — run the same quadtree multiply once per leaf
backend (numpy reference vs pallas batched waves, both kernel modes) and
emit one JSON record with per-backend wall time and batched-pair counts:

    PYTHONPATH=src python benchmarks/bench_leaf_multiply.py \
        --compare-backends [--n 256] [--pattern banded|random]
"""
import argparse
import json
import time

import numpy as np

from repro.core.leaf import LeafMatrix, LeafStats, leaf_multiply


def csv_mode() -> None:
    print("bs,fill,gflops,block_multiplies,batches,useful_fraction")
    n = 1024
    rng = np.random.default_rng(0)
    for bs in (16, 32, 64):
        g = n // bs
        for fill in (0.01, 0.05, 0.2, 0.5, 1.0):
            mask = rng.random((g, g)) < fill
            a = LeafMatrix(n, bs)
            b = LeafMatrix(n, bs)
            for i, j in zip(*np.nonzero(mask)):
                a.blocks[(i, j)] = rng.standard_normal((bs, bs))
            mask_b = rng.random((g, g)) < fill
            for i, j in zip(*np.nonzero(mask_b)):
                b.blocks[(i, j)] = rng.standard_normal((bs, bs))
            st = LeafStats()
            t0 = time.perf_counter()
            c = leaf_multiply(a, b, stats=st)
            dt = time.perf_counter() - t0
            dense_flops = 2.0 * n ** 3
            useful = st.flops / dense_flops
            print(f"{bs},{fill},{st.flops / dt / 1e9:.2f},"
                  f"{st.block_multiplies},{st.batches},{useful:.4f}")
            assert not np.isnan(st.flops)
            del c


def compare_backends(n: int, pattern: str, leaf_n: int, bs: int,
                     seed: int) -> dict:
    """Quadtree multiply through every leaf backend; JSON-able record."""
    from repro import Session
    from repro.core.engine import PallasEngine
    from repro.core.patterns import banded_mask, random_mask, values_for_mask

    if pattern == "banded":
        mask = banded_mask(n, max(n // 32, 4))
    else:
        mask = random_mask(n, 0.08, seed=seed)
    a = values_for_mask(mask, seed=seed)
    b = values_for_mask(mask, seed=seed + 1)

    # engine instances bind to one graph, so each timed run gets a fresh one
    backends = {
        "numpy": lambda: "numpy",
        "pallas-pairs": lambda: PallasEngine(kernel="pairs"),
        "pallas-gemm": lambda: PallasEngine(kernel="gemm"),
    }
    record = {
        "mode": "compare-backends", "n": n, "leaf_n": leaf_n, "bs": bs,
        "pattern": pattern, "seed": seed, "backends": {},
    }
    ref = None
    for name, mk_engine in backends.items():
        # run twice: the first pays one-time jit trace/compile (reported as
        # wall_s_cold), the second is the steady-state comparison number
        walls = []
        for _ in range(2):
            sess = Session(engine=mk_engine(), leaf_n=leaf_n, bs=bs)
            A = sess.from_dense(a)
            B = sess.from_dense(b)
            t0 = time.perf_counter()
            C = A @ B
            sess.flush()
            walls.append(time.perf_counter() - t0)
        out = C.to_dense()
        if ref is None:
            ref = out
        else:
            np.testing.assert_allclose(out, ref, atol=1e-3, rtol=1e-3)
        entry = {
            "wall_s": walls[-1],
            "wall_s_cold": walls[0],
            "multiply_tasks": sess.n_multiply_tasks,
            "flops": sess.flops,
        }
        stats = sess.engine_stats()
        if stats:
            entry.update({
                "kernel": stats.get("kernel"),
                "waves": stats.get("waves"),
                "batched_pairs": stats.get("batched_pairs"),
                "padded_pairs": stats.get("padded_pairs"),
                "c_blocks": stats.get("c_blocks"),
                "dispatch_s": stats.get("dispatch_s"),
                "bytes_packed": stats.get("bytes_packed"),
            })
        record["backends"][name] = entry
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--compare-backends", action="store_true",
                    help="JSON backend comparison instead of the CSV sweep")
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--leaf-n", type=int, default=64)
    ap.add_argument("--bs", type=int, default=16)
    ap.add_argument("--pattern", choices=("banded", "random"),
                    default="banded")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.compare_backends:
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        print(json.dumps(compare_backends(args.n, args.pattern, args.leaf_n,
                                          args.bs, args.seed), indent=2))
    else:
        csv_mode()


if __name__ == "__main__":
    main()
