"""Table 1 shape on the *executing* mesh engine: measured fetch volume.

Weak scaling (N proportional to p) of a banded quadtree multiply through
``Session(engine="mesh")`` — the parent-worker placement promoted to a
real device-sharded executor (launch/mesh_exec.py).  The reported metric
is the worst per-device **fetched bytes counter of the executor itself**
(blocks actually shipped between devices by the ring collectives, counted
once per resident block) — measured communication, not the simulator's
cost model and not parsed HLO.

The comparison target is the SpSUMMA baseline at the same weak-scaling
sizes, whose per-device slab all_gather volume is parsed from the
compiled SPMD module (the roofline methodology; SpSUMMA's traffic is
uniform by construction so the HLO number *is* the per-device number).

Expected Table-1 shape: parent-worker stays roughly flat with p on a
banded (local) pattern; SpSUMMA grows ~sqrt(p).

A virtual-device study: it runs itself in subprocesses pinned to the CPU
(``JAX_PLATFORMS=cpu``) with 2-16 forced host devices, since the device
count must be set before jax initialises and a chip host has fewer
devices than that.  Writes ``BENCH_mesh_comm.json`` at the repo root (or
``--out``).
"""
import argparse
import json
import os
import pathlib
import subprocess
import sys

try:
    from benchmarks._artifact import write_artifact
except ImportError:                     # run directly from benchmarks/
    from _artifact import write_artifact

_CHILD = "_child"
#: env var naming the path the mesh child writes its Perfetto trace to
_TRACE_ENV = "BENCH_MESH_TRACE"

MESH_PS = (2, 4, 8)
SUMMA_PS = (4, 16)


def child(scheme: str, p: int, n: int) -> None:
    import numpy as np

    bs = 8
    if scheme == "mesh":
        from repro import Session
        from repro.core.patterns import banded_mask, values_for_mask
        from repro.launch.mesh_exec import MeshEngine

        a = values_for_mask(banded_mask(n, 12), seed=1)
        b = values_for_mask(banded_mask(n, 7), seed=2)
        sess = Session(engine=MeshEngine(n_dev=p), leaf_n=32, bs=bs)
        A, B = sess.from_dense(a), sess.from_dense(b)
        C = A @ B
        np.testing.assert_allclose(C.to_dense(), a @ b, atol=1e-3)
        st = sess.engine_stats()
        trace_out = os.environ.get(_TRACE_ENV)
        if trace_out:
            from repro.obs import mesh_stats_events, write_chrome_trace
            write_chrome_trace(trace_out, mesh_stats_events(st))
        rec = {
            "scheme": "mesh", "p": p, "n": n,
            "max_fetched_bytes_per_dev": max(st["fetched_bytes"]),
            "sum_fetched_blocks": sum(st["fetched_blocks"]),
            "max_pushed_bytes_per_dev": max(st["pushed_bytes"]),
            "max_collective_bytes_per_dev": max(st["collective_bytes"]),
            "waves": st["waves"],
        }
    else:
        import jax
        import jax.numpy as jnp
        from repro.core import spsumma
        from repro.core.patterns import (banded_mask,
                                         block_mask_from_element_mask,
                                         values_for_mask)
        from repro.launch import roofline

        a = values_for_mask(banded_mask(n, 12), seed=1).astype(np.float32)
        ma = block_mask_from_element_mask(np.abs(a) > 0, bs)
        pg = spsumma.summa_pgrid(p)
        sp = spsumma.plan_summa(ma, ma, bs, pg)
        ab, ar, ac = spsumma.distribute_panels(a, bs, sp)
        mesh = jax.make_mesh((pg, pg), ("pr", "pc"))

        def run(*xs):
            return spsumma.summa_spmm(mesh, ("pr", "pc"), sp, *xs)

        args = [jnp.asarray(x) for x in (ab, ar, ac, ab, ar, ac)]
        compiled = jax.jit(run).lower(*args).compile()
        rec = {
            "scheme": "summa", "p": p, "n": n,
            "coll_bytes_per_dev": roofline.collective_bytes(
                compiled.as_text()),
            "pgrid": pg,
        }
    print("JSON " + json.dumps(rec))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="smaller weak-scaling sizes (CI)")
    ap.add_argument("--out", default="BENCH_mesh_comm.json")
    args = ap.parse_args()

    scale = 64 if args.quick else 128
    runs = [("mesh", p, scale * p) for p in MESH_PS] + \
           [("summa", p, scale * p) for p in SUMMA_PS]
    records = []
    root = pathlib.Path(__file__).parents[1]
    for scheme, p, n in runs:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={p}"
        if scheme == "mesh" and p == max(MESH_PS):
            # largest mesh run also emits its per-wave device trace
            env[_TRACE_ENV] = str(root / "mesh_comm.trace.json")
        res = subprocess.run(
            [sys.executable, __file__, _CHILD, scheme, str(p), str(n)],
            capture_output=True, text=True, env=env, timeout=1800)
        if res.returncode:
            print(f"{scheme} p={p} n={n} FAILED:\n{res.stderr[-500:]}")
            return 1
        line = [l for l in res.stdout.splitlines()
                if l.startswith("JSON ")][-1]
        rec = json.loads(line[5:])
        records.append(rec)
        print(rec, flush=True)

    mesh = {r["p"]: r for r in records if r["scheme"] == "mesh"}
    summa = {r["p"]: r for r in records if r["scheme"] == "summa"}
    lo, hi = min(MESH_PS), max(MESH_PS)
    f_lo = max(1, mesh[lo]["max_fetched_bytes_per_dev"])
    f_hi = mesh[hi]["max_fetched_bytes_per_dev"]
    mesh_growth = f_hi / f_lo
    s_lo, s_hi = min(SUMMA_PS), max(SUMMA_PS)
    summa_growth = (summa[s_hi]["coll_bytes_per_dev"]
                    / max(1, summa[s_lo]["coll_bytes_per_dev"]))
    out = {
        "metric": "max per-device fetched bytes (mesh engine counters) "
                  "vs per-device HLO collective bytes (SpSUMMA)",
        "quick": bool(args.quick),
        "records": records,
        "mesh_fetch_growth_2_to_8": mesh_growth,
        "flat_2_to_8": mesh_growth <= 2.0,
        "summa_coll_growth_4_to_16": summa_growth,
    }
    path = write_artifact(
        root / args.out, "mesh_comm", out,
        params={"quick": bool(args.quick), "scale": scale, "bs": 8,
                "leaf_n": 32, "mesh_ps": list(MESH_PS),
                "summa_ps": list(SUMMA_PS)})
    print(f"\nparent-worker fetch growth {lo}->{hi} devs: "
          f"{mesh_growth:.2f}x (flat within 2x: {out['flat_2_to_8']})")
    print(f"SpSUMMA collective growth {s_lo}->{s_hi} devs: "
          f"{summa_growth:.2f}x")
    print(f"wrote {path}")
    return 0 if out["flat_2_to_8"] else 1


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == _CHILD:
        child(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
    else:
        sys.exit(main())
