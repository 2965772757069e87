"""collectives (launch/mesh_exec.py): operand bytes a device fetched from
other devices' homes, the ``fetched_bytes_by_dev`` counters of the
program's ``engine.wave`` spans summed per device, the largest device's,
MB (1e6 B) per op: the measured per-worker traffic of arXiv:1501.07800,
Table 1."""


def read(w):
    per_dev = [s.attrs["fetched_bytes_by_dev"] for s in w.spans
               if s.name == "engine.wave"
               and "fetched_bytes_by_dev" in s.attrs]
    return 1e-6 * max(map(sum, zip(*per_dev))) / w.ops if per_dev else None
