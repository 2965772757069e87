"""collectives (launch/mesh_exec.py): the padded payload the ring shifts
deliver to a device, the ``collective_bytes_by_dev`` counters of the
program's ``engine.wave`` spans summed per device, the largest device's,
MB (1e6 B) per op."""


def read(w):
    per_dev = [s.attrs["collective_bytes_by_dev"] for s in w.spans
               if s.name == "engine.wave"
               and "collective_bytes_by_dev" in s.attrs]
    return 1e-6 * max(map(sum, zip(*per_dev))) / w.ops if per_dev else None
