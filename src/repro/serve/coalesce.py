"""Cross-plan wave coalescer: one fused kernel call for many plans.

The deferred engines already batch *within* a graph: every pending leaf
task whose operands are final joins one fused ``bsmm_pairs`` dispatch at
flush.  A serving front end runs several
plans per batch — possibly in *different* sessions, each with its own
engine — and flushing them one by one would dispatch one undersized wave
per plan.  :class:`WaveCoalescer` instead:

1. asks every engine for its ready kernel tasks grouped by
   :meth:`~repro.core.engine.PallasEngine.batch_key`
   (``(leaf_n, bs, dtype)``),
2. merges groups with equal keys and interpret modes across engines,
3. packs each merged group through the same
   :func:`~repro.core.engine.dispatch_packed_wave` the engines use
   themselves — one kernel call per key per round, whose operands of
   every engine and both sides share one float32 table (slots are keyed
   by ``id(leaf)``, so blocks of different engines never share a slot)
   — and
4. commits each engine's share back so its wave log and pending set stay
   consistent.

Numerical identity with per-plan flushing is structural, not accidental:
output slots are numbered leaf-by-leaf (a folded product's partials share
their root's), pair order within a task is preserved, and the segment
sort is stable — so every output block
accumulates exactly the pair products it would have accumulated alone,
in the same order, in float32 (see ``dispatch_packed_wave``).  Tests pin
this bitwise.

Only plain :class:`~repro.core.engine.PallasEngine` instances merge;
the mesh executor (device-resident buffers, counted collectives) and the
immediate numpy backend flush through their own paths.
"""
from __future__ import annotations

from repro.core.engine import (PallasEngine, dispatch_packed_wave,
                               wave_counts)
from repro.obs.metrics import MetricSet
from repro.obs.tracer import NOOP

__all__ = ["WaveCoalescer"]


class WaveCoalescer:
    """Merge compatible ready waves across engines, dispatch once."""

    def __init__(self, tracer=NOOP):
        self.tracer = tracer
        # merged-wave log: one record per fused dispatch this coalescer ran
        self.waves: list[dict] = []
        self.merged_waves = 0       # dispatches serving >1 engine
        self.solo_waves = 0         # dispatches serving exactly 1 engine
        self.merged_tasks = 0       # tasks that shared a cross-engine wave

    # -- the batch flush ------------------------------------------------------
    def flush(self, graphs) -> int:
        """Drain all deferred work of ``graphs``, coalescing across them.

        Returns the number of fused dispatches run.  Engines that cannot
        merge (mesh, numpy/immediate) are flushed through their own
        ``flush`` unchanged.
        """
        mergeable: list[tuple] = []     # (graph, engine)
        rest: list = []
        for g in graphs:
            eng = g._engine
            # exactly PallasEngine: subclasses (the mesh executor) own
            # device state a foreign dispatch would bypass
            if type(eng) is PallasEngine:
                mergeable.append((g, eng))
            else:
                rest.append(g)
        for g in rest:
            g.flush()
        dispatches = 0
        while True:
            progressed = False
            for g, eng in mergeable:
                eng._bind(g)
                progressed |= eng.run_host_ready()
                # solve waves (triangular kinds) stay per-engine: they
                # dispatch dense stacked leaves, not GEMM pair streams
                progressed |= eng.run_solve_ready()
            merged: dict = {}
            for _, eng in mergeable:
                for key, tasks in eng.ready_wave().items():
                    # the interpret mode must also agree for the shares
                    # to be dispatch-compatible
                    mk = (key, eng.interpret)
                    merged.setdefault(mk, []).append((eng, tasks))
            for (key, interpret), parts in sorted(
                    merged.items(), key=lambda kv: kv[0][0]):
                self._dispatch(key, interpret, parts)
                dispatches += 1
                progressed = True
            if not any(eng._pending for _, eng in mergeable):
                break
            if not progressed:
                raise RuntimeError(
                    "wave coalescer deadlock: unresolvable leaf "
                    "dependencies across in-flight plans")
        return dispatches

    def _dispatch(self, key: tuple, interpret: bool, parts: list) -> None:
        _, bs, _ = key
        all_tasks = [t for _, tasks in parts for t in tasks]
        with self.tracer.span("serve.wave", track="serve",
                              engines=len(parts), tasks=len(all_tasks),
                              kernel="pairs", bs=bs):
            record = dispatch_packed_wave(all_tasks, bs, interpret=interpret,
                                          tracer=self.tracer)
        record["batch_key"] = list(key)
        record["engines"] = len(parts)
        self.waves.append(record)
        if len(parts) > 1:
            self.merged_waves += 1
            self.merged_tasks += len(all_tasks)
        else:
            self.solo_waves += 1
        # each engine keeps its own share of the accounting: pair/task/
        # block counts are exact, wall time and bytes are attributed
        # proportionally by pair count so per-engine stats() still sum
        # to (approximately) the merged wave
        total_pairs = max(record["pairs"], 1)
        for eng, tasks in parts:
            counts = wave_counts(tasks)
            share = counts["pairs"] / total_pairs
            eng.commit_tasks(tasks, wave_record={
                "kernel": "pairs", "bs": bs, **counts,
                "padded_pairs": counts["pairs"],
                "dispatch_s": record["dispatch_s"] * share,
                "bytes_packed": int(record["bytes_packed"] * share),
                "batch_key": list(key), "coalesced": len(parts),
                **{k: record[k] for k in ("use_pallas", "interpret",
                                          "platform")},
            })

    # -- reporting ------------------------------------------------------------
    def counters(self) -> dict:
        return {"merged_waves": self.merged_waves,
                "solo_waves": self.solo_waves,
                "merged_tasks": self.merged_tasks,
                "dispatches": len(self.waves)}

    def metrics(self) -> MetricSet:
        ms = MetricSet(source="serve-coalescer")
        for k, v in self.counters().items():
            ms.add(k, "count", [v])
        return ms

    def __repr__(self) -> str:
        return (f"WaveCoalescer(dispatches={len(self.waves)}, "
                f"merged={self.merged_waves}, solo={self.solo_waves})")
