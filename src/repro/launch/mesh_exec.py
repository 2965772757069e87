"""Real device-mesh executor for the quadtree multiply (DESIGN.md §7).

:class:`MeshEngine` promotes the simulator's parent-worker placement into
an executing backend: every deferred leaf-engine wave is partitioned over
the devices of a 1-D jax mesh (``launch.mesh.make_spmm_mesh``), operand
blocks move between devices through explicit, *counted* ring collectives
(``jax.lax.ppermute``), and the per-device block GEMMs run as one
``shard_map``-sharded :func:`~repro.kernels.ops.bsmm_pairs` dispatch per
wave.  The per-device communication volume reported by :meth:`stats` is
therefore *measured from the shipments actually performed*, not derived
from the simulator's cost model.

One wave numbering: a wave's operand slots, output slots and pair order
are the one-chip engine's (:func:`~repro.core.engine.number_wave`).
:func:`plan_mesh_wave` derives the per-device pools, ship lists and pair
tables from that numbering with array operations, on the host and
without devices.

Ownership (the paper's parent-worker rendering, §6/Table 1):

* each wave's tasks are split contiguously over the devices in
  registration order (the quadtree's DFS order, which is Morton/locality
  order for the leaves) using the same closed-form balanced split as
  ``core.distributed``;
* a leaf produced by a task lives on the device that ran the task;
* an input leaf is homed on the first device that touches it.

Data movement model per wave:

* **push** — host -> home device upload of an operand block not already
  device-resident at its current ``LeafMatrix._version`` (first touch, or
  stale after a plan rebind refilled the leaf);
* **fetch** — a remote operand block a device needs, shipped from its
  home by a ring shift; counted once per (block, version, device) — a
  re-used resident block costs nothing, which is exactly the locality the
  parent-worker placement is supposed to buy;
* **collective** — the raw padded payload the ring shifts move (SPMD
  shipping is rectangular: every device sends the same padded count per
  shift, so this is an upper envelope of fetch).

Devices are whatever jax exposes: the four chips of a TPU v5e host (``python
chip_smoke.py --chips 4`` runs ``A @ B`` at n = 65,536 there, every device
owning output and the result bitwise equal to the one-chip
``engine="pallas"`` product) or forced host devices in CPU tests
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``). What is not
device-resident yet: wave staging and unpacking still round-trip through the
host like the parent :class:`~repro.core.engine.PallasEngine` does. The
sharding, collectives and per-device counters are real.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.engine import (PallasEngine, _Pending, fill_slots,
                               number_wave, ran_on)
from repro.core.leaf import unpack_blocks
from repro.kernels import ops as kops


@functools.partial(jax.jit, static_argnames=(
    "mesh", "shifts", "cap_c", "use_pallas", "interpret"))
def mesh_wave(own_pool, sa, sb, seg, sels, *, mesh, shifts: tuple,
              cap_c: int, use_pallas: bool, interpret: bool):
    """One device-sharded wave: ring-shift the halos, run ``bsmm_pairs``.

    Every array is stacked over the mesh's ``"dev"`` axis: ``own_pool``
    ``(n_dev, cap_own, bs, bs)`` holds each device's home blocks;
    ``sels[i]`` ``(n_dev, cnt_i)`` picks the blocks each device sends on
    ring shift ``shifts[i]``; ``sa``/``sb``/``seg`` ``(n_dev, cap_p)``
    address the per-device pool (own segment, then one receive segment
    per shift).  Returns ``(n_dev, cap_c, bs, bs)`` output slots.

    Module-level and jitted on its static signature (mesh, shifts,
    caps) plus the argument shapes, so waves of one shape, such as a plan
    replay, reuse one executable.
    """
    n_dev = mesh.shape["dev"]

    def body(own, sa_, sb_, seg_, *sels_):
        own = own[0]
        parts = [own]
        for shift, sel in zip(shifts, sels_):
            perm = [(r, (r + shift) % n_dev) for r in range(n_dev)]
            parts.append(jax.lax.ppermute(own[sel[0]], "dev", perm))
        pool = jnp.concatenate(parts, axis=0) if len(parts) > 1 \
            else parts[0]
        c = kops.bsmm_pairs(pool, pool, sa_[0], sb_[0], seg_[0],
                            cap_c=cap_c, use_pallas=use_pallas,
                            interpret=interpret)
        return c[None]

    spec = P("dev")
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(spec,) * (4 + len(sels)),
                         out_specs=spec, check_vma=False)(
        own_pool, sa, sb, seg, *sels)


@dataclasses.dataclass
class MeshWavePlan:
    """Host plan of one mesh wave (:func:`plan_mesh_wave`).

    ``pool_slot[d, i]`` is the wave's operand slot at position ``i`` of
    device ``d``'s pool (its own segment, ``cap_own`` long, then one
    receive segment per ring shift), -1 for padding.  ``sa``/``sb``
    address that pool and ``seg`` the device's local output slots;
    device ``d``'s output slot ``j`` is the wave's slot ``base[d] + j``.
    """
    task_dev: np.ndarray    # (tasks,) device that runs each task
    base: np.ndarray        # (n_dev,) first wave output slot of each device
    n_out: np.ndarray       # (n_dev,) output slots of each device
    home: np.ndarray        # (slots,) device that holds each operand slot
    cap_own: int
    pool_slot: np.ndarray   # (n_dev, pool_len)
    shifts: tuple           # ring shifts that carry blocks, ascending
    sels: tuple             # per shift (n_dev, cnt): own-pool rows sent
    sa: np.ndarray          # (n_dev, cap_p) int32, padding 0
    sb: np.ndarray          # (n_dev, cap_p) int32, padding 0
    seg: np.ndarray         # (n_dev, cap_p) int32, padding cap_c
    cap_c: int
    shipped: int            # blocks the shifts carry, unpadded
    pushed: np.ndarray      # (n_dev,) blocks uploaded host -> home
    fetched: np.ndarray     # (n_dev,) received blocks not yet resident


def plan_mesh_wave(slots, sa, sb, seg, out_counts, n_dev: int,
                   owner: dict, resident: list) -> MeshWavePlan:
    """Split one numbered wave over ``n_dev`` devices, on the host.

    ``slots``/``sa``/``sb``/``seg`` are the wave's :func:`number_wave`
    numbering and ``out_counts`` the output slots of each task, in task
    order (one output leaf per task: the mesh does not fold).  Tasks go
    to devices by the contiguous balanced split, so the wave's output
    slots are the devices' local slots laid end to end, and the pairs —
    sorted by ``seg`` — are one contiguous run per device, already in
    the order each device's kernel sums them.

    An operand slot lives on its leaf's owner (``owner``: leaf id ->
    device; a leaf without one is homed on the first device that reads
    it, and recorded).  ``resident[d]`` maps a slot key ``(id(leaf),
    key, transpose)`` to the leaf version device ``d`` holds: a home slot
    at another version counts as pushed, a received one as fetched, and
    both maps are brought up to date.
    """
    nt = len(out_counts)
    task_dev = ((np.arange(nt, dtype=np.int64) + 1) * n_dev - 1) // nt
    n_out = np.bincount(task_dev, weights=out_counts,
                        minlength=n_dev).astype(np.int64)
    base = np.cumsum(n_out) - n_out
    bounds = np.searchsorted(seg, np.append(base, n_out.sum()))
    pair_dev = np.repeat(np.arange(n_dev), np.diff(bounds))

    # slots are numbered in first-use order and tasks run in device
    # order, so the first reader of a slot is its lowest reading device
    first = np.full(len(slots), n_dev, np.int64)
    np.minimum.at(first, sa, pair_dev)
    np.minimum.at(first, sb, pair_dev)
    keys = [(id(leaf), key, tr) for leaf, key, tr in slots]
    vers = [getattr(leaf, "_version", 0) for leaf, _, _ in slots]
    home = np.array([owner.setdefault(id(leaf), f) for (leaf, _, _), f
                     in zip(slots, first.tolist())], np.int64)

    def stale(d, s):
        if resident[d].get(keys[s]) == vers[s]:
            return False
        resident[d][keys[s]] = vers[s]
        return True

    pushed = np.zeros(n_dev, np.int64)
    for s, h in enumerate(home.tolist()):
        pushed[h] += stale(h, s)
    own = [np.flatnonzero(home == d) for d in range(n_dev)]
    own_pos = np.empty(len(slots), np.int64)
    for rows in own:
        own_pos[rows] = np.arange(len(rows))

    # the remote slots each device reads, grouped by ring shift
    # (dst - home) mod n_dev; a (shift, src) pair has one destination
    fetched = np.zeros(n_dev, np.int64)
    ship: dict[int, list] = {}
    for d in range(n_dev):
        lo, hi = bounds[d], bounds[d + 1]
        need = np.unique(np.concatenate([sa[lo:hi], sb[lo:hi]]))
        far = need[home[need] != d]
        fetched[d] = sum(stale(d, s) for s in far.tolist())
        shift = (d - home[far]) % n_dev
        for k in np.unique(shift).tolist():
            lists = ship.setdefault(k, [far[:0]] * n_dev)
            lists[(d - k) % n_dev] = far[shift == k]
    shifts = tuple(sorted(ship))
    cnts = [max(len(x) for x in ship[k]) for k in shifts]

    cap_own = max(1, max(len(rows) for rows in own))
    pool_slot = np.full((n_dev, cap_own + sum(cnts)), -1, np.int64)
    for d, rows in enumerate(own):
        pool_slot[d, :len(rows)] = rows
    sels = []
    off = cap_own
    for k, cnt in zip(shifts, cnts):
        sel = np.zeros((n_dev, cnt), np.int32)
        for src, rows in enumerate(ship[k]):
            sel[src, :len(rows)] = own_pos[rows]
            pool_slot[(src + k) % n_dev, off:off + len(rows)] = rows
        sels.append(sel)
        off += cnt

    cap_p = max(1, int(np.diff(bounds).max()))
    cap_c = max(1, int(n_out.max()))
    dsa = np.zeros((n_dev, cap_p), np.int32)
    dsb = np.zeros((n_dev, cap_p), np.int32)
    dseg = np.full((n_dev, cap_p), cap_c, np.int32)
    pos = np.empty(len(slots), np.int64)
    for d in range(n_dev):
        at = np.flatnonzero(pool_slot[d] >= 0)
        pos[pool_slot[d, at]] = at
        lo, hi = bounds[d], bounds[d + 1]
        dsa[d, :hi - lo] = pos[sa[lo:hi]]
        dsb[d, :hi - lo] = pos[sb[lo:hi]]
        dseg[d, :hi - lo] = seg[lo:hi] - base[d]
    return MeshWavePlan(
        task_dev=task_dev, base=base, n_out=n_out, home=home,
        cap_own=cap_own, pool_slot=pool_slot, shifts=shifts,
        sels=tuple(sels), sa=dsa, sb=dsb, seg=dseg, cap_c=cap_c,
        shipped=int((pool_slot[:, cap_own:] >= 0).sum()),
        pushed=pushed, fetched=fetched)


class MeshEngine(PallasEngine):
    """Device-sharded leaf backend: ``Session(engine="mesh")``.

    Parameters
    ----------
    n_dev : devices to shard over (default: all visible jax devices).
    use_pallas / interpret : forwarded to :mod:`repro.kernels.ops`;
        ``None`` auto-selects (compiled Pallas on TPU, the XLA
        reference on CPU; other backends raise).

    Inherits the deferral machinery, NIL/structure semantics, host-side
    add/transpose/scale fills, the wave numbering and the float32
    precision contract of :class:`~repro.core.engine.PallasEngine`; only
    wave *execution* (and the communication bookkeeping that comes with
    it) is replaced: each device runs ``bsmm_pairs`` on its share of the
    wave (:func:`mesh_wave`), planned by :func:`plan_mesh_wave`.
    """

    name = "mesh"
    # a wave's tasks are split across chips by ownership, so a product's
    # partials cannot share one segment: no fold of add trees
    _FOLD_SUMS = False

    def __init__(self, n_dev: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 use_pallas: Optional[bool] = None):
        super().__init__(interpret=interpret)
        self.use_pallas = use_pallas
        self._n_dev_req = n_dev
        self._mesh = None
        self.n_dev = 0                      # resolved at first wave
        # leaf id -> owning device (parent-worker: producer owns)
        self._owner: dict[int, int] = {}
        # per-device residency: slot key (leaf_id, block_key, trans) ->
        # LeafMatrix._version present on that device
        self._resident: list[dict] = []
        # leaf id -> device-side output shard reference (jax.Array) kept
        # so produced blocks stay device-resident between waves;
        # Session.free drops these through free_chunks
        self._dev_out: dict[int, object] = {}
        self._fetched_bytes = np.zeros(0, np.int64)
        self._fetched_blocks = np.zeros(0, np.int64)
        self._pushed_bytes = np.zeros(0, np.int64)
        self._collective_bytes = np.zeros(0, np.int64)
        self._comm_log: list[dict] = []

    # -- mesh ----------------------------------------------------------------
    def _ensure_mesh(self):
        if self._mesh is None:
            from .mesh import make_spmm_mesh

            avail = jax.device_count()
            n = self._n_dev_req or avail
            if n > avail:
                raise ValueError(
                    f"MeshEngine: n_dev={n} requested but only {avail} "
                    f"jax devices are visible (force host devices with "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count=N "
                    f"before jax initialises)")
            self.n_dev = n
            self._mesh = make_spmm_mesh(n)
            z = lambda: np.zeros(n, np.int64)
            self._fetched_bytes = z()
            self._fetched_blocks = z()
            self._pushed_bytes = z()
            self._collective_bytes = z()
            self._resident = [dict() for _ in range(n)]
        return self._mesh

    # -- wave execution ------------------------------------------------------
    def _run_group(self, bs: int, tasks: list[_Pending]) -> None:
        """One device-sharded dispatch for every block pair of the wave."""
        mesh = self._ensure_mesh()
        n_dev = self.n_dev
        bsz = bs * bs * 4               # float32 wire format
        t0 = time.perf_counter()
        slots, sa, sb, seg, _ = number_wave(tasks)
        counts = [len(t.out.blocks) for t in tasks]
        plan = plan_mesh_wave(slots, sa, sb, seg, counts, n_dev,
                              self._owner, self._resident)
        task_dev = plan.task_dev.tolist()
        for t, dev in zip(tasks, task_dev):
            self._owner[id(t.out)] = dev
        own_pool = np.zeros((n_dev, plan.cap_own, bs, bs), np.float32)
        for d in range(n_dev):
            rows = plan.pool_slot[d, :plan.cap_own]
            fill_slots(own_pool[d], [slots[s] for s in rows[rows >= 0]])
        cnts = [sel.shape[1] for sel in plan.sels]
        fetched = plan.fetched * bsz
        pushed = plan.pushed * bsz
        collective = np.full(n_dev, sum(cnts) * bsz, np.int64)
        self._fetched_bytes += fetched
        self._fetched_blocks += plan.fetched
        self._pushed_bytes += pushed
        self._collective_bytes += collective   # every device receives

        # the sharded dispatch: ring-shift the halos, run the kernel
        use_pallas, interpret = kops.resolve(self.use_pallas, self.interpret)
        tr = self.tracer
        if tr.enabled and plan.shifts:
            tr.instant("collective.ppermute", track="engine",
                       shifts=len(plan.shifts),
                       shipped_blocks=plan.shipped,
                       padded_shipped_blocks=int(sum(cnts) * n_dev))
        t_dispatch = time.perf_counter()
        with tr.span("kernel.dispatch", track="engine", kernel="pairs",
                     bs=bs, n_dev=n_dev, pairs=len(seg)):
            c_dev = mesh_wave(own_pool, plan.sa, plan.sb, plan.seg,
                              plan.sels, mesh=mesh, shifts=plan.shifts,
                              cap_c=plan.cap_c, use_pallas=use_pallas,
                              interpret=interpret)
            c_np = np.asarray(c_dev)
        dispatch = time.perf_counter() - t_dispatch

        # scatter into the placeholder out leaves; produced blocks are
        # now resident on their owner (backed by the retained shard ref)
        start = 0
        for t, dev, n in zip(tasks, task_dev, counts):
            keys = list(t.out.blocks)
            lo = start - plan.base[dev]
            unpack_blocks(t.out, keys, c_np[dev, lo:lo + n])
            start += n
            self._dev_out[id(t.out)] = c_dev
            ver = getattr(t.out, "_version", 0)
            for key in keys:
                self._resident[dev][(id(t.out), key, False)] = ver

        wall = time.perf_counter() - t0
        self._waves.append({
            "kernel": "pairs", "bs": bs, "tasks": len(tasks),
            "pairs": len(seg), "padded_pairs": int(plan.sa.size),
            "unique_blocks": len(slots), "c_blocks": int(plan.n_out.sum()),
            "dispatch_s": dispatch,
            "bytes_packed": int(own_pool.nbytes + c_np.nbytes),
            **ran_on(c_dev, use_pallas=use_pallas, interpret=interpret),
        })
        self._comm_log.append({
            "bs": bs, "n_dev": n_dev, "tasks": len(tasks), "pairs": len(seg),
            "shifts": len(plan.shifts), "shipped_blocks": plan.shipped,
            "padded_shipped_blocks": int(sum(cnts) * n_dev),
            "fetched_blocks": int(plan.fetched.sum()),
            "pool_len": int(plan.pool_slot.shape[1]),
            "cap_c": plan.cap_c, "c_blocks_by_dev": plan.n_out.tolist(),
            "wall_s": wall,
            # this wave's measured per-device counter deltas (exported as
            # Perfetto counter tracks; see obs/export.mesh_stats_events)
            "fetched_bytes_by_dev": fetched.tolist(),
            "fetched_blocks_by_dev": plan.fetched.tolist(),
            "pushed_bytes_by_dev": pushed.tolist(),
            "collective_bytes_by_dev": collective.tolist(),
        })

    def _wave_span_attrs(self) -> dict:
        """Wave span attrs: batch shape plus this wave's per-device comm
        deltas (the Table-1 metric, measured)."""
        attrs = super()._wave_span_attrs()
        c = self._comm_log[-1]
        attrs.update({k: c[k] for k in
                      ("n_dev", "shifts", "shipped_blocks",
                       "fetched_bytes_by_dev", "pushed_bytes_by_dev",
                       "collective_bytes_by_dev")})
        return attrs

    # -- lifecycle -----------------------------------------------------------
    def free_chunks(self, g, nids) -> None:
        """Drop ownership, residency and device shard refs of freed leaves."""
        freed: set[int] = set()
        for nid in nids:
            chunk = g.value_of(nid)
            leaf = getattr(chunk, "leaf", None)
            if leaf is not None:
                freed.add(id(leaf))
        if not freed:
            return
        for lid in freed:
            self._owner.pop(lid, None)
            self._dev_out.pop(lid, None)
        for res in self._resident:
            for sk in [sk for sk in res if sk[0] in freed]:
                del res[sk]

    # -- reporting -----------------------------------------------------------
    def stats(self) -> dict:
        out = super().stats()
        out.update({
            "n_dev": self.n_dev,
            "fetched_bytes": self._fetched_bytes.tolist(),
            "fetched_blocks": self._fetched_blocks.tolist(),
            "pushed_bytes": self._pushed_bytes.tolist(),
            "collective_bytes": self._collective_bytes.tolist(),
            "device_blocks": sum(len(r) for r in self._resident),
            "device_leaves": len(self._dev_out),
            "comm_log": list(self._comm_log),
        })
        return out
