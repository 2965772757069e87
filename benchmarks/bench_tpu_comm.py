"""Paper Fig 14 analogue on the TPU engine: compiled-HLO collective bytes.

Weak scaling (N proportional to p) of the banded distributed multiply:
Morton-locality halo exchange (core/distributed.py) vs SpSUMMA
all_gather (core/spsumma.py).  Collective bytes per device are parsed
from the optimized SPMD module — the dry-run methodology end-to-end.

A virtual-device study: it runs itself in subprocesses pinned to the CPU
(``JAX_PLATFORMS=cpu``) with 4-64 forced host devices, since the device
count must be set before jax initialises and a chip host has fewer
devices than that.  The collective bytes come from the CPU backend's SPMD
partitioning of the same program.
CSV: scheme,p,N,coll_bytes_per_dev,halo_hops_or_pgrid.
"""
import os
import subprocess
import sys

_CHILD = "_child"


def child(scheme: str, p: int, n: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import distributed as dist, spsumma
    from repro.core.patterns import banded_mask, values_for_mask, \
        block_mask_from_element_mask
    from repro.launch import roofline

    bs = 8
    a = values_for_mask(banded_mask(n, 12), seed=1).astype(np.float32)
    ma = block_mask_from_element_mask(np.abs(a) > 0, bs)
    if scheme == "halo":
        plan = dist.plan_distribution(ma, ma, bs, p)
        ab, ar, ac = dist.distribute_morton(a, bs, plan)
        mesh = jax.make_mesh((p,), ("dev",))
        fn = dist.make_halo_spmm(mesh, "dev", plan)
        args = [jnp.asarray(x) for x in (ab, ar, ac, ab, ar, ac)]
        compiled = fn.lower(*args).compile()
        extra = plan.halo_hops
    elif scheme == "demand":
        dplan = dist.plan_demand(ma, ma, bs, p)
        base = dist.plan_distribution(ma, ma, bs, p)
        ab, ar, ac = dist.distribute_morton(a, bs, base)
        mesh = jax.make_mesh((p,), ("dev",))
        fn = dist.make_demand_spmm(mesh, "dev", dplan)
        args = [jnp.asarray(x) for x in (ab, ar, ac, ab, ar, ac)]
        compiled = fn.lower(*args).compile()
        extra = len(dplan.shifts)
    else:
        pg = int(np.sqrt(p))
        sp = spsumma.plan_summa(ma, ma, bs, pg)
        ab, ar, ac = spsumma.distribute_panels(a, bs, sp)
        mesh = jax.make_mesh((pg, pg), ("pr", "pc"))

        def run(*xs):
            return spsumma.summa_spmm(mesh, ("pr", "pc"), sp, *xs)

        args = [jnp.asarray(x) for x in (ab, ar, ac, ab, ar, ac)]
        compiled = jax.jit(run).lower(*args).compile()
        extra = pg
    coll = roofline.collective_bytes(compiled.as_text())
    print(f"{scheme},{p},{n},{coll},{extra}")


def main() -> None:
    print("scheme,p,N,coll_bytes_per_dev,halo_hops_or_pgrid")
    sys.stdout.flush()
    for p in (4, 16, 64):
        n = 256 * p
        for scheme in ("halo", "demand", "summa"):
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env["XLA_FLAGS"] = \
                f"--xla_force_host_platform_device_count={p}"
            res = subprocess.run(
                [sys.executable, __file__, _CHILD, scheme, str(p),
                 str(n)], capture_output=True, text=True, env=env,
                timeout=1800)
            if res.returncode:
                print(f"{scheme},{p},{n},FAILED,{res.stderr[-200:]}")
            else:
                print(res.stdout.strip().splitlines()[-1])
            sys.stdout.flush()


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == _CHILD:
        child(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
    else:
        main()
