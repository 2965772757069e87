"""Serving throughput vs coalesced batch size, with tail latency.

    PYTHONPATH=src python benchmarks/bench_serve.py [--quick] \
        [--out BENCH_serve.json]

Measures the plan-serving subsystem (DESIGN.md §9) on a repeated-shape
multiply workload:

* **Cache-hit rate** — after one warmup pass per distinct request shape,
  every request must rebind-replay an existing replica: the bench
  asserts a >= 90% shared-cache hit rate on the measured workload and
  **zero new task registrations** after warmup.
* **Throughput vs batch size** — the same request stream served with
  ``max_inflight`` in {1, 2, 4, 8}: coalescing more plans per fused
  kernel dispatch amortizes per-dispatch overhead, so requests/s at the
  best coalesced batch size must beat ``max_inflight=1``.  (The curve
  peaks and flattens once same-shape requests outnumber replicas.)
* **Tail latency** — p50/p95/p99 of per-request submit-to-done latency
  per batch-size point.
* **Correctness** — every served result is pinned (bitwise, float32
  readback tolerance) to the same request served alone, so coalescing
  is an execution detail, not a numerics change.

The artifact (``BENCH_serve.json``) carries one row per batch size:
``{max_inflight, requests, requests_per_s, p50_ms, p95_ms, p99_ms,
hit_rate, merged_waves, solo_waves}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from _artifact import write_artifact  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))
from repro.serve import PlanServer, Request  # noqa: E402


def percentile_ms(lat_s: list, q: float) -> float:
    return float(np.percentile(np.asarray(lat_s), q) * 1e3)


def make_operands(n: int, n_mats: int, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    return {f"M{i}": rng.standard_normal((n, n)) for i in range(n_mats)}


def request_stream(names: list, count: int) -> list:
    """A repeated-shape workload: products cycling over registered pairs."""
    reqs = []
    for i in range(count):
        a = names[i % len(names)]
        b = names[(i + 1) % len(names)]
        reqs.append(Request.multiply(a, b))
    return reqs


def serve_point(mats: dict, reqs: list, max_inflight: int, *, n_sessions: int,
                leaf_n: int, bs: int, reps: int = 1) -> tuple:
    """Serve the stream at one batch size; returns (row, results).

    The measured pass runs ``reps`` times against the warm server and the
    fastest pass is reported — single-pass wall times on a shared CPU are
    too noisy to pin a ~20% dispatch-amortization effect.
    """
    srv = PlanServer(engine="pallas", n_sessions=n_sessions,
                     max_inflight=max_inflight,
                     max_queue=max(len(reqs), 4), leaf_n=leaf_n, bs=bs)
    for name, a in mats.items():
        srv.register(name, a)

    # warmup: serve the stream once — this compiles every replica the
    # measured pass will touch (including the extra per-session replicas
    # concurrent same-shape requests need) and pays the one-time jit of
    # the fused kernels
    for r in reqs:
        srv.submit(r)
    srv.drain()
    tasks_after_warmup = srv.task_count()
    hits0 = srv.cache.counters()["hits"]
    misses0 = srv.cache.counters()["misses"]

    wall, tickets = None, None
    for _ in range(reps):
        t0 = time.perf_counter()
        cand = [srv.submit(r) for r in reqs]
        srv.drain()
        w = time.perf_counter() - t0
        assert all(t.done for t in cand), \
            [t.error for t in cand if not t.done]
        if wall is None or w < wall:
            wall, tickets = w, cand
    assert srv.task_count() == tasks_after_warmup, (
        f"warm serving registered tasks: {tasks_after_warmup} -> "
        f"{srv.task_count()}")
    c = srv.cache.counters()
    hits = c["hits"] - hits0
    misses = c["misses"] - misses0
    hit_rate = hits / max(hits + misses, 1)
    lat = [t.latency_s for t in tickets]
    return {
        "max_inflight": max_inflight,
        "requests": len(reqs),
        "wall_s": wall,
        "requests_per_s": len(reqs) / wall,
        "p50_ms": percentile_ms(lat, 50),
        "p95_ms": percentile_ms(lat, 95),
        "p99_ms": percentile_ms(lat, 99),
        "hit_rate": hit_rate,
        "merged_waves": srv.coalescer.merged_waves,
        "solo_waves": srv.coalescer.solo_waves,
        "tasks": tasks_after_warmup,
    }, [t.result for t in tickets]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="smaller matrices and request count (CI)")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()

    # serving-typical regime: many small repeated-shape products, where
    # per-dispatch overhead is a real cost to amortize.  (At much larger
    # waves the interpret-mode kernel dominates and grows superlinearly
    # with packed size, so coalescing is neutral there — the win this
    # bench pins is dispatch amortization, not kernel speedup.)  The full
    # run spreads replicas over 4 sessions so concurrent same-shape
    # requests coalesce cleanly instead of entangling on shared
    # same-session templates.
    n = 32
    leaf_n, bs = 16, 4
    n_mats = 3
    count = 8 if args.quick else 32
    batch_sizes = [1, 2, 4] if args.quick else [1, 2, 4, 8]
    n_sessions = 2 if args.quick else 4
    reps = 2 if args.quick else 3

    mats = make_operands(n, n_mats)
    names = sorted(mats)
    reqs = request_stream(names, count)

    # serial reference: every request served alone (max_inflight=1 in a
    # fresh server) — the numerical pin for every coalesced point
    print(f"bench_serve: n={n} requests={count} shapes={n_mats} "
          f"batch sizes={batch_sizes}")
    ref_row, ref_results = serve_point(
        mats, reqs, 1, n_sessions=1, leaf_n=leaf_n, bs=bs, reps=reps)

    rows = []
    for mi in batch_sizes:
        row, results = serve_point(mats, reqs, mi, n_sessions=n_sessions,
                                   leaf_n=leaf_n, bs=bs, reps=reps)
        for got, want in zip(results, ref_results):
            np.testing.assert_array_equal(
                got, want,
                err_msg=f"coalesced serving (max_inflight={mi}) diverged "
                        f"from serial execution")
        assert row["hit_rate"] >= 0.90, (
            f"cache-hit rate {row['hit_rate']:.2f} < 0.90 at "
            f"max_inflight={mi}")
        rows.append(row)
        print(f"  max_inflight={mi}: {row['requests_per_s']:.2f} req/s  "
              f"p50={row['p50_ms']:.1f}ms p95={row['p95_ms']:.1f}ms "
              f"p99={row['p99_ms']:.1f}ms hit_rate={row['hit_rate']:.2f} "
              f"merged_waves={row['merged_waves']}")

    # coalescing must buy throughput over serial serving at its sweet
    # spot; past it, replica stalls (same-shape requests outnumbering
    # replicas) and same-session template entanglement flatten the curve,
    # so the claim is about the best coalesced point, not the largest
    thr = {r["max_inflight"]: r["requests_per_s"] for r in rows}
    best = max((mi for mi in thr if mi > 1), key=lambda mi: thr[mi],
               default=None)
    assert best is not None and thr[best] > thr[1], (
        f"coalesced serving never beat serial: {thr[1]:.2f} req/s at "
        f"max_inflight=1 vs {thr}")

    doc_params = {"quick": args.quick, "n": n, "leaf_n": leaf_n, "bs": bs,
                  "n_mats": n_mats, "requests": count,
                  "n_sessions": n_sessions, "reps": reps}
    path = write_artifact(args.out, "serve",
                          {"rows": rows, "serial_reference": ref_row},
                          params=doc_params)
    print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
