"""On-chip smoke run of the library's main path, checked against float64.

    python chip_smoke.py              # multiply, replay and scf on one TPU
    python chip_smoke.py --chips 4    # the mesh engine on four TPU chips

Each phase drives the library through the entry points a user calls
(``Session``, ``Plan``, ``scf_density``, ``MeshEngine``) at a size a
deployment would call real, and compares the result with a float64
reference built with ``scipy.sparse`` or numpy, never with this
library's engines:

* ``multiply`` — the paper's §6.2 workload: the overlap matrix S of a
  3-D particle cloud in divide-space order (n = 32,768 at the defaults),
  ``S.sym_square()`` in symmetric upper storage and one general product
  ``A @ B`` on full-storage operands of the same pattern;
* ``replay`` — a compiled ``Plan`` for ``X @ X`` run once, then replayed
  with three rebound inputs: zero task registrations and zero XLA
  compiles are required in the replays;
* ``scf`` — ``scf_density`` on a lazy session (n = 4,096), the one phase
  that reaches the triangular solve waves, against an ``eigh`` projector;
* ``mesh`` (``--chips 4`` only) — ``A @ B`` through ``MeshEngine`` over
  four devices at about twice the one-chip n, against the reference and
  the same product on ``engine="pallas"``; every device must own output.

Every phase prints one ``phase=... key=value`` line: sizes, multiply
tasks / waves / batched pairs from ``Session.engine_stats()``, the XLA
compiles counted in the phase, wall seconds (ending in host readback) and
the worst relative Frobenius error.  Every engine wave must have run
compiled Pallas on the device JAX reports.  The last stdout line is
``{"ok": true, "device": {...}}``; a failed check raises before it.
``main`` refuses to run unless JAX's first device is a TPU.  The phase
functions take their sizes as arguments, so the tests run them tiny on
the CPU, in Pallas interpret mode.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]

import jax  # noqa: E402
import numpy as np  # noqa: E402
import scipy.sparse as sp  # noqa: E402

from repro import Session  # noqa: E402
from repro.core.patterns import (  # noqa: E402
    divide_space_order, overlap_pairs, particle_cloud)
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh_exec import MeshEngine  # noqa: E402

#: relative Frobenius error limits against the float64 references, about
#: 15x and 5x the errors measured on a TPU v5e (6.6e-8 for the products
#: at n = 32,768; 9.2e-6 for D at n = 4,096 after 64 SP2 iterations)
MULTIPLY_TOL = 1e-6
SCF_TOL = 5e-5
#: SP2 exit threshold on ||X^2 - X||_F for the float32 engines
SP2_TOL = 1e-4

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeCheckError(AssertionError):
    """A phase's result failed one of its checks."""


@contextlib.contextmanager
def count_compiles():
    """Count XLA backend compiles inside the block: ``with ... as c: c[0]``."""
    count = [0]

    def on_event(event: str, duration: float, **_) -> None:
        if event == _COMPILE_EVENT:
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield count
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def report(phase: str, fields: dict, checks: dict) -> dict:
    """Print the phase line, then raise if any check failed."""
    failed = [name for name, ok in checks.items() if not ok]
    line = " ".join(f"{k}={v!r}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in {"phase": phase, **fields}.items())
    print(line + (f" FAILED={','.join(failed)}" if failed else ""),
          flush=True)
    if failed:
        raise SmokeCheckError(f"{phase}: failed checks {failed}")
    return fields


# -- inputs and references ---------------------------------------------------

def overlap_problem(n_per_dim: int, seed: int):
    """Particle cloud in divide-space order and its overlap pattern.

    Returns ``(pts, rows, cols, n)`` with ``n`` the next power of two at or
    above the particle count (the quadtree pads with zero rows/columns).
    """
    coords = particle_cloud(n_per_dim, 3, seed=seed)
    order = divide_space_order(coords)
    rows, cols = overlap_pairs(coords, 4.5, order=order)
    n = 1 << int(math.ceil(math.log2(len(coords))))
    return coords[order], rows, cols, n


def pair_values(pts: np.ndarray, width: float, oscillate: bool = False):
    """Element values ``exp(-|xi - xj|^2 / width)`` for index arrays.

    ``width=4`` gives the overlap values of the §6.2 example
    (``examples/s2_electronic_structure.py``); ``oscillate=True`` adds a
    sign-changing factor so that products cancel.
    """
    def value_fn(r, c):
        d2 = ((pts[r] - pts[c]) ** 2).sum(-1)
        v = np.exp(-d2 / width)
        return v * np.cos(d2) if oscillate else v
    return value_fn


def reference(rows, cols, n: int, value_fn) -> sp.csr_matrix:
    """The full float64 matrix with the given pattern and values."""
    return sp.csr_matrix((value_fn(rows, cols), (rows, cols)), shape=(n, n))


def stored_blocks(m):
    """``{block row: [(block col, block)]}`` of a matrix's stored blocks.

    Walks the quadtree without densifying; symmetric upper storage yields
    its upper block triangle only.
    """
    m.stats()                               # forces and flushes
    g, bs = m.session.graph, m.params.bs
    out: dict[int, list] = {}

    def walk(nid, r0: int, c0: int) -> None:
        chunk = g.value_of(nid)
        if chunk is None:
            return
        if chunk.is_leaf:
            for (i, j), blk in chunk.leaf.blocks.items():
                out.setdefault(r0 // bs + i, []).append((c0 // bs + j, blk))
            return
        h = chunk.n // 2
        for q, child in enumerate(chunk.children):
            walk(child, r0 + (q // 2) * h, c0 + (q % 2) * h)

    walk(m.node, 0, 0)
    return out


def rel_error(m, ref: sp.csr_matrix) -> float:
    """``||M - ref||_F / ||ref||_F``, streamed one block row at a time.

    For symmetric upper storage the comparison covers the upper block
    triangle, which holds every independent entry.
    """
    bs, got = m.params.bs, stored_blocks(m)
    err2 = ref2 = 0.0
    for bi in range(m.n // bs):
        off = bi * bs if m.upper else 0
        row = ref[bi * bs:(bi + 1) * bs, off:]
        blocks = got.get(bi, [])
        if not blocks and row.nnz == 0:
            continue
        cols = [bj * bs - off for bj, _ in blocks]
        lo = min(cols + ([int(row.indices.min())] if row.nnz else []))
        hi = max([c + bs for c in cols] +
                 ([int(row.indices.max()) + 1] if row.nnz else []))
        dense = row[:, lo:hi].toarray()
        ref2 += float(np.square(dense).sum())
        for c, blk in zip(cols, (b for _, b in blocks)):
            dense[:, c - lo:c - lo + bs] -= blk
        err2 += float(np.square(dense).sum())
    return math.sqrt(err2 / ref2)


def rel_diff(m, ref_m) -> float:
    """``||M - R||_F / ||R||_F`` of two matrices with equal block structure."""
    got, want = stored_blocks(m), stored_blocks(ref_m)
    err2 = ref2 = 0.0
    for r in set(got) | set(want):
        g, w = dict(got.get(r, [])), dict(want.get(r, []))
        if g.keys() != w.keys():
            return math.inf
        for c, blk in w.items():
            ref2 += float(np.square(blk, dtype=np.float64).sum())
            err2 += float(np.square(g[c] - blk, dtype=np.float64).sum())
    return math.sqrt(err2 / ref2)


def wave_summary(sess, first: int = 0) -> tuple[dict, dict]:
    """Counters of the engine waves from ``first`` on, and their checks.

    Every multiply wave must have run the Pallas kernel, interpreted only
    on the CPU, and every wave's result must live on the default backend.
    """
    st = sess.engine_stats()
    waves = st["wave_log"][first:]
    backend = jax.default_backend()
    gemm = [w for w in waves if w["kernel"] in ("pairs", "gemm")]
    fields = {"tasks": sum(w["tasks"] for w in gemm), "waves": len(waves),
              "pairs": sum(w["pairs"] for w in gemm)}
    checks = {
        "waves_on_" + backend: all(w["platform"] == backend for w in waves),
        "waves_pallas": all(w["use_pallas"] and
                            w["interpret"] == (backend == "cpu")
                            for w in gemm),
    }
    return fields, checks


# -- phases ------------------------------------------------------------------

def phase_multiply(n_per_dim: int = 32, leaf_n: int = 1024, bs: int = 128,
                   seed: int = 0) -> dict:
    """S^2 (symmetric upper storage) and a general A @ B, eager pallas."""
    pts, rows, cols, n = overlap_problem(n_per_dim, seed)
    s_val = pair_values(pts, 4.0)
    b_val = pair_values(pts, 8.0, oscillate=True)
    with count_compiles() as compiles:
        sess = Session(engine="pallas", leaf_n=leaf_n, bs=bs)
        t0 = time.perf_counter()
        S = sess.from_pattern(rows, cols, n, value_fn=s_val, upper=True)
        A = sess.from_pattern(rows, cols, n, value_fn=s_val)
        B = sess.from_pattern(rows, cols, n, value_fn=b_val)
        build_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        S2 = S.sym_square()
        sess.flush()
        sym_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        C = A @ B
        sess.flush()
        gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    s_ref = reference(rows, cols, n, s_val)
    err_s2 = rel_error(S2, s_ref @ s_ref)
    err_ab = rel_error(C, s_ref @ reference(rows, cols, n, b_val))
    ref_s = time.perf_counter() - t0
    fields, checks = wave_summary(sess)
    fields = {"n": n, "bs": bs, "leaf_n": leaf_n, **fields,
              "compiles": compiles[0], "wall_s": sym_s + gen_s,
              "sym_square_s": sym_s, "general_s": gen_s,
              "build_s": build_s, "reference_s": ref_s,
              "s_blocks": S.nnz_blocks(), "s2_blocks": S2.nnz_blocks(),
              "c_blocks": C.nnz_blocks(),
              "rel_err": max(err_s2, err_ab), "rel_err_s2": err_s2,
              "rel_err_ab": err_ab, "tol": MULTIPLY_TOL}
    checks["rel_err"] = fields["rel_err"] <= MULTIPLY_TOL
    return report("multiply", fields, checks)


def phase_replay(n_per_dim: int = 32, leaf_n: int = 1024, bs: int = 128,
                 seed: int = 0, replays: int = 3) -> dict:
    """Compiled ``X @ X`` plan: one run, then rebound zero-compile replays."""
    pts, rows, cols, n = overlap_problem(n_per_dim, seed)
    widths = [4.0] + [4.0 + k for k in range(1, replays + 1)]
    sess = Session(lazy=True, engine="pallas", leaf_n=leaf_n, bs=bs)
    xs = [sess.from_pattern(rows, cols, n, value_fn=pair_values(pts, w),
                            name="X" if k == 0 else None)
          for k, w in enumerate(widths)]
    with count_compiles() as first_compiles:
        plan = sess.compile(xs[0] @ xs[0])
        t0 = time.perf_counter()
        out = plan.run()
        sess.flush()
        first_s = time.perf_counter() - t0
    refs = [reference(rows, cols, n, pair_values(pts, w)) for w in widths]
    errs = [rel_error(out, refs[0] @ refs[0])]
    wave0 = len(sess.engine_stats()["wave_log"])
    tasks0 = len(sess.graph.nodes)
    replay_s, compiles = [], 0
    for x, ref in zip(xs[1:], refs[1:]):
        with count_compiles() as c:
            t0 = time.perf_counter()
            out = plan.run(X=x)
            sess.flush()
            replay_s.append(time.perf_counter() - t0)
        compiles += c[0]
        errs.append(rel_error(out, ref @ ref))
    new_tasks = len(sess.graph.nodes) - tasks0
    fields, checks = wave_summary(sess, wave0)
    fields = {"n": n, "bs": bs, "leaf_n": leaf_n, **fields,
              "compiles": compiles, "new_tasks": new_tasks,
              "wall_s": sum(replay_s), "replay_s": replay_s,
              "first_run_s": first_s,
              "first_run_compiles": first_compiles[0],
              "rel_err": max(errs), "tol": MULTIPLY_TOL}
    checks.update(rel_err=max(errs) <= MULTIPLY_TOL,
                  zero_compiles=compiles == 0, zero_new_tasks=new_tasks == 0)
    return report("replay", fields, checks)


def phase_scf(n: int = 4096, leaf_n: int = 512, bs: int = 128,
              seed: int = 0) -> dict:
    """``scf_density`` (S -> Z -> Z^T F Z -> SP2 -> D) on a lazy session."""
    from inverse_factor_scf import make_fock, make_overlap
    from repro.solvers import scf_density

    s, f = make_overlap(n, seed), make_fock(n, seed + 1)
    n_occ = n // 4
    sess = Session(lazy=True, engine="pallas", leaf_n=leaf_n, bs=bs)
    with count_compiles() as compiles:
        t0 = time.perf_counter()
        D, rep = scf_density(sess, f, s, n_occ, tol=SP2_TOL, max_iters=100)
        d = D.to_dense()
        wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    z = np.linalg.solve(np.linalg.cholesky(s).T, np.eye(n))
    _, v = np.linalg.eigh(z.T @ f @ z)
    d_ref = z @ v[:, :n_occ] @ v[:, :n_occ].T @ z.T
    err = float(np.linalg.norm(d - d_ref) / np.linalg.norm(d_ref))
    ref_s = time.perf_counter() - t0
    fields, checks = wave_summary(sess)
    solve = [w for w in sess.engine_stats()["wave_log"]
             if w["kernel"] in ("inv_chol", "tri_solve")]
    fields = {"n": n, "bs": bs, "leaf_n": leaf_n, **fields,
              "solve_waves": len(solve), "compiles": compiles[0],
              "wall_s": wall, "sp2_iterations": rep.sp2_iterations,
              "idempotency": rep.idempotency,
              "replay_tasks": rep.replay_tasks,
              "reference_s": ref_s, "rel_err": err, "tol": SCF_TOL}
    checks.update(converged=rep.converged, solve_waves=bool(solve),
                  zero_replay_tasks=rep.replay_tasks == 0,
                  rel_err=err <= SCF_TOL)
    return report("scf", fields, checks)


def phase_mesh(n_per_dim: int = 40, leaf_n: int = 1024, bs: int = 128,
               n_dev: int = 4, seed: int = 0) -> dict:
    """``A @ B`` through ``MeshEngine`` against pallas and float64."""
    pts, rows, cols, n = overlap_problem(n_per_dim, seed)
    a_val = pair_values(pts, 4.0)
    b_val = pair_values(pts, 8.0, oscillate=True)
    results = {}
    # the one-chip product first, while device 0 is empty: it holds the
    # whole output on one device, the mesh a quarter of it on each
    for name, engine in (("pallas", "pallas"),
                         ("mesh", MeshEngine(n_dev=n_dev))):
        sess = Session(engine=engine, leaf_n=leaf_n, bs=bs)
        A = sess.from_pattern(rows, cols, n, value_fn=a_val)
        B = sess.from_pattern(rows, cols, n, value_fn=b_val)
        with count_compiles() as compiles:
            t0 = time.perf_counter()
            C = A @ B
            sess.flush()
            wall = time.perf_counter() - t0
        results[name] = (sess, C, wall, compiles[0])
    sess, C, wall, compiles = results["mesh"]
    ref = reference(rows, cols, n, a_val) @ reference(rows, cols, n, b_val)
    err = rel_error(C, ref)
    err_vs_pallas = rel_diff(C, results["pallas"][1])
    st = sess.engine_stats()
    out_blocks = np.sum([c["c_blocks_by_dev"] for c in st["comm_log"]],
                        axis=0).tolist()
    # per-device wave sizes: pairs (padded) and output slots of the largest
    cap_p = max(w["padded_pairs"] // st["n_dev"] for w in st["wave_log"])
    cap_c = max(c["cap_c"] for c in st["comm_log"])
    fields, checks = wave_summary(sess)
    checks.update({f"pallas_{k}": ok for k, ok
                   in wave_summary(results["pallas"][0])[1].items()})
    fields = {"n": n, "bs": bs, "leaf_n": leaf_n, "n_dev": st["n_dev"],
              **fields, "compiles": compiles, "wall_s": wall,
              "pallas_wall_s": results["pallas"][2],
              "pallas_compiles": results["pallas"][3],
              "fetched_bytes": st["fetched_bytes"],
              "pushed_bytes": st["pushed_bytes"],
              "out_blocks": out_blocks, "cap_p": cap_p, "cap_c": cap_c,
              "rel_err": err,
              "rel_err_vs_pallas": err_vs_pallas, "tol": MULTIPLY_TOL}
    checks.update(n_dev=st["n_dev"] == n_dev,
                  every_device_owns_output=all(b > 0 for b in out_blocks),
                  rel_err=err <= MULTIPLY_TOL,
                  matches_pallas=err_vs_pallas <= MULTIPLY_TOL)
    return report("mesh", fields, checks)


# -- entry point -------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: multiply, replay, scf; 4: the mesh phase only")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the particle cloud and the SCF matrices")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is on "
              f"platform {dev.platform!r} ({dev.device_kind})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    use_compile_cache()
    if args.chips == 4:
        phase_mesh(n_dev=4, seed=args.seed)
    else:
        phase_multiply(seed=args.seed)
        phase_replay(seed=args.seed)
        phase_scf(seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
