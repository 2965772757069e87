"""Real device-mesh executor for the quadtree multiply (DESIGN.md §7).

:class:`MeshEngine` promotes the simulator's parent-worker placement into
an executing backend: every deferred leaf-engine wave is partitioned over
the devices of a 1-D jax mesh (``launch.mesh.make_spmm_mesh``), operand
blocks move between devices through explicit, *counted* ring collectives
(``jax.lax.ppermute``), and the per-device block GEMMs run as one
``shard_map``-sharded :func:`repro.kernels.ops.batched_gemm` /
:func:`~repro.kernels.ops.bsmm_pairs` dispatch per wave.  The per-device
communication volume reported by :meth:`stats` is therefore *measured
from the shipments actually performed*, not derived from the simulator's
cost model.

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

The default kernel is ``"pairs"``, which gathers operand blocks by DMA.
``"gemm"`` gathers both operands of every pair and their products into
``(pairs, bs, bs)`` arrays, 192 KiB per pair at bs = 128: at the n = 65,536
wave above the TPU compiler refuses it for lack of HBM
(``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.engine import PallasEngine, _Pending, ran_on
from repro.core.leaf import unpack_blocks
from repro.kernels import ops as kops


@functools.partial(jax.jit, static_argnames=(
    "mesh", "kernel", "shifts", "cap_c", "block_t", "use_pallas",
    "interpret"))
def mesh_wave(own_pool, sa, sb, seg, sels, *, mesh, kernel: str,
              shifts: tuple, cap_c: int, block_t: int, use_pallas: bool,
              interpret: bool):
    """One device-sharded wave: ring-shift the halos, run the kernel.

    Every array is stacked over the mesh's ``"dev"`` axis: ``own_pool``
    ``(n_dev, cap_own, bs, bs)`` holds each device's home blocks;
    ``sels[i]`` ``(n_dev, cnt_i)`` picks the blocks each device sends on
    ring shift ``shifts[i]``; ``sa``/``sb``/``seg`` ``(n_dev, cap_p)``
    address the per-device pool (own segment, then one receive segment
    per shift).  Returns ``(n_dev, cap_c, bs, bs)`` output slots.

    Module-level and jitted on its static signature (mesh, kernel,
    shifts, caps) plus the argument shapes, so waves of one shape, such as
    a plan replay, reuse one executable.
    """
    n_dev = mesh.shape["dev"]

    def body(own, sa_, sb_, seg_, *sels_):
        own = own[0]
        sa1, sb1, seg1 = sa_[0], sb_[0], seg_[0]
        parts = [own]
        for shift, sel in zip(shifts, sels_):
            perm = [(r, (r + shift) % n_dev) for r in range(n_dev)]
            parts.append(jax.lax.ppermute(own[sel[0]], "dev", perm))
        pool = jnp.concatenate(parts, axis=0) if len(parts) > 1 \
            else parts[0]
        if kernel == "pairs":
            c = kops.bsmm_pairs(pool, pool, sa1, sb1, seg1, cap_c=cap_c,
                                use_pallas=use_pallas, interpret=interpret)
        else:
            prods = kops.batched_gemm(pool[sa1], pool[sb1], block_t=block_t,
                                      use_pallas=use_pallas,
                                      interpret=interpret)
            prods = jnp.where((seg1 < cap_c)[:, None, None], prods, 0)
            c = jax.ops.segment_sum(
                prods.astype(jnp.float32), jnp.minimum(seg1, cap_c),
                num_segments=cap_c + 1)[:cap_c]
        return c[None]

    spec = P("dev")
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(spec,) * (4 + len(sels)),
                         out_specs=spec, check_vma=False)(
        own_pool, sa, sb, seg, *sels)


class MeshEngine(PallasEngine):
    """Device-sharded leaf backend: ``Session(engine="mesh")``.

    Parameters
    ----------
    n_dev : devices to shard over (default: all visible jax devices).
    kernel : ``"pairs"`` (the fused bsmm_pairs gather-GEMM-scatter, the
        default) or ``"gemm"`` (batched_gemm + segment_sum scatter, which
        materialises the gathered operands; small waves only).
    use_pallas / interpret : forwarded to :mod:`repro.kernels.ops`;
        ``None`` auto-selects (compiled Pallas on TPU, the XLA
        reference on CPU; other backends raise).
    block_t : batch tile of the batched_gemm kernel.

    Inherits the deferral machinery, NIL/structure semantics, host-side
    add/transpose/scale fills and the float32 precision contract of
    :class:`~repro.core.engine.PallasEngine`; only wave *execution* (and
    the communication bookkeeping that comes with it) is replaced.
    """

    name = "mesh"

    def __init__(self, n_dev: Optional[int] = None, kernel: str = "pairs",
                 interpret: Optional[bool] = None,
                 use_pallas: Optional[bool] = None, block_t: int = 8):
        super().__init__(kernel=kernel, interpret=interpret,
                         block_t=block_t)
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
            import jax

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
        # per-device counter snapshot: the wave's comm_log entry (and its
        # engine.wave span) carries this wave's deltas, not the running sums
        fetched0 = self._fetched_bytes.copy()
        fblocks0 = self._fetched_blocks.copy()
        pushed0 = self._pushed_bytes.copy()
        coll0 = self._collective_bytes.copy()

        # 1. task ownership: contiguous balanced split in registration
        # (quadtree DFS ~ Morton) order — core.distributed's closed form
        nt = len(tasks)
        owners = ((np.arange(nt, dtype=np.int64) + 1) * n_dev - 1) // nt
        owners = owners.astype(np.int32)

        # 2. operand slots: one per distinct (leaf, key, transpose),
        # homed on the leaf's owning device (producer, else first touch)
        slot_home: dict[tuple, int] = {}
        slot_val: dict[tuple, np.ndarray] = {}
        slot_ver: dict[tuple, int] = {}
        needs: list[dict] = [dict() for _ in range(n_dev)]  # ordered sets
        for t, dev in zip(tasks, owners):
            dev = int(dev)
            self._owner[id(t.out)] = dev
            srcs = {"a": t.a_leaf, "b": t.b_leaf}
            for src_a, ka, tra, src_b, kb, trb, _ in t.pairs:
                for src, kk, tr in ((src_a, ka, tra), (src_b, kb, trb)):
                    leaf = srcs[src]
                    sk = (id(leaf), kk, tr)
                    if sk not in slot_home:
                        home = self._owner.setdefault(id(leaf), dev)
                        slot_home[sk] = home
                        blk = leaf.blocks[kk]
                        slot_val[sk] = np.asarray(
                            blk.T if tr else blk, np.float32)
                        slot_ver[sk] = getattr(leaf, "_version", 0)
                    needs[dev].setdefault(sk)

        # 3. per-device own pools (+ push accounting: host -> home device
        # uploads of blocks not resident at their current version)
        own_keys: list[list] = [[] for _ in range(n_dev)]
        own_pos: dict[tuple, int] = {}
        for sk, h in slot_home.items():
            own_pos[sk] = len(own_keys[h])
            own_keys[h].append(sk)
            if self._resident[h].get(sk) != slot_ver[sk]:
                self._resident[h][sk] = slot_ver[sk]
                self._pushed_bytes[h] += bsz
        cap_own = max(1, max((len(k) for k in own_keys), default=1))
        own_pool = np.zeros((n_dev, cap_own, bs, bs), np.float32)
        for d in range(n_dev):
            for i, sk in enumerate(own_keys[d]):
                own_pool[d, i] = slot_val[sk]

        # 4. shipments grouped by ring shift s = (dst - home) mod n_dev;
        # SPMD tables: per shift every device sends the same padded count
        ship: dict[int, list[list]] = {}    # shift -> per-src slot keys
        fetched_now = 0
        for d in range(n_dev):
            for sk in needs[d]:
                h = slot_home[sk]
                if h == d:
                    continue
                s = (d - h) % n_dev
                ship.setdefault(s, [[] for _ in range(n_dev)])[h].append(sk)
                if self._resident[d].get(sk) != slot_ver[sk]:
                    self._resident[d][sk] = slot_ver[sk]
                    self._fetched_bytes[d] += bsz
                    self._fetched_blocks[d] += 1
                    fetched_now += 1
        shifts = sorted(ship)
        cnts = [max(len(lst) for lst in ship[s]) for s in shifts]
        # pool position of slot sk as seen by device d: the own segment,
        # then one recv segment per shift at a static offset
        recv_pos: dict[tuple, int] = {}     # (shift, slot) -> pool pos
        sels = []
        off = cap_own
        for s, cnt in zip(shifts, cnts):
            sel = np.zeros((n_dev, cnt), np.int32)
            for src in range(n_dev):
                for i, sk in enumerate(ship[s][src]):
                    sel[src, i] = own_pos[sk]
                    recv_pos[(s, sk)] = off + i
            sels.append(sel)
            off += cnt
        pool_len = off

        def pos_on(d: int, sk: tuple) -> int:
            h = slot_home[sk]
            if h == d:
                return own_pos[sk]
            return recv_pos[((d - h) % n_dev, sk)]

        # 5. per-device pair tables (sa/sb into the halo'd pool, seg into
        # the device-local output slots; cap-padded, seg=cap_c invalid)
        out_base: list[int] = []
        n_out = [0] * n_dev
        for t, dev in zip(tasks, owners):
            out_base.append(n_out[int(dev)])
            n_out[int(dev)] += len(t.out.blocks)
        cap_c = max(1, max(n_out))
        dev_pairs: list[list] = [[] for _ in range(n_dev)]
        n_pairs = 0
        for t, dev, base in zip(tasks, owners, out_base):
            dev = int(dev)
            key_slot = {key: base + i
                        for i, key in enumerate(t.out.blocks)}
            srcs = {"a": t.a_leaf, "b": t.b_leaf}
            for src_a, ka, tra, src_b, kb, trb, out_key in t.pairs:
                dev_pairs[dev].append(
                    (pos_on(dev, (id(srcs[src_a]), ka, tra)),
                     pos_on(dev, (id(srcs[src_b]), kb, trb)),
                     key_slot[out_key]))
                n_pairs += 1
        cap_p = max(1, max(len(p) for p in dev_pairs))
        sa = np.zeros((n_dev, cap_p), np.int32)
        sb = np.zeros((n_dev, cap_p), np.int32)
        seg = np.full((n_dev, cap_p), cap_c, np.int32)
        for d in range(n_dev):
            # ascending output slots (bsmm_pairs accumulation contract;
            # the cap_c padding sorts to the tail)
            for i, (pa, pb, pc) in enumerate(
                    sorted(dev_pairs[d], key=lambda x: x[2])):
                sa[d, i], sb[d, i], seg[d, i] = pa, pb, pc

        # 6. the sharded dispatch: ring-shift the halos, run the kernel
        use_pallas, interpret = kops.resolve(self.use_pallas, self.interpret)
        tr = self.tracer
        if tr.enabled and shifts:
            tr.instant("collective.ppermute", track="engine",
                       shifts=len(shifts),
                       shipped_blocks=int(sum(len(lst) for s in shifts
                                              for lst in ship[s])),
                       padded_shipped_blocks=int(sum(cnts) * n_dev))
        with tr.span("kernel.dispatch", track="engine", kernel=self.kernel,
                     bs=bs, n_dev=n_dev, pairs=int(n_pairs)):
            c_dev = mesh_wave(own_pool, sa, sb, seg, tuple(sels), mesh=mesh,
                              kernel=self.kernel, shifts=tuple(shifts),
                              cap_c=cap_c, block_t=self.block_t,
                              use_pallas=use_pallas, interpret=interpret)
            c_np = np.asarray(c_dev)

        # 7. scatter into the placeholder out leaves; produced blocks are
        # now resident on their owner (backed by the retained shard ref)
        for t, dev, base in zip(tasks, owners, out_base):
            dev = int(dev)
            keys = list(t.out.blocks)
            unpack_blocks(t.out, keys, c_np[dev, base:base + len(keys)])
            self._dev_out[id(t.out)] = c_dev
            ver = getattr(t.out, "_version", 0)
            for key in keys:
                self._resident[dev][(id(t.out), key, False)] = ver

        wall = time.perf_counter() - t0
        shipped = sum(len(lst) for s in shifts for lst in ship[s])
        padded_ship = sum(cnts) * n_dev
        self._collective_bytes += sum(cnts) * bsz   # every device receives
        self._waves.append({
            "kernel": self.kernel, "bs": bs, "tasks": nt,
            "pairs": int(n_pairs), "padded_pairs": int(cap_p * n_dev),
            "unique_blocks": len(slot_home), "c_blocks": int(sum(n_out)),
            "wall_s": wall,
            "bytes_packed": int(own_pool.nbytes + c_np.nbytes),
            **ran_on(c_dev, use_pallas=use_pallas, interpret=interpret),
        })
        self._comm_log.append({
            "bs": bs, "n_dev": n_dev, "tasks": nt, "pairs": int(n_pairs),
            "shifts": len(shifts), "shipped_blocks": int(shipped),
            "padded_shipped_blocks": int(padded_ship),
            "fetched_blocks": int(fetched_now),
            "pool_len": int(pool_len), "cap_c": int(cap_c),
            "c_blocks_by_dev": list(n_out),
            "wall_s": wall,
            # this wave's measured per-device counter deltas (exported as
            # Perfetto counter tracks; see obs/export.mesh_stats_events)
            "fetched_bytes_by_dev": (self._fetched_bytes - fetched0).tolist(),
            "fetched_blocks_by_dev": (self._fetched_blocks - fblocks0).tolist(),
            "pushed_bytes_by_dev": (self._pushed_bytes - pushed0).tolist(),
            "collective_bytes_by_dev": (self._collective_bytes - coll0).tolist(),
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
