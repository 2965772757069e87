"""collectives (launch/mesh_exec.py): device self time of the
collective-permute operations (``collective-permute``, ``-start`` and
``-done``: the ring shifts of ``jax.lax.ppermute``) in the
``jit_mesh_wave`` modules in the profiler trace, the mean over the
devices that ran in the window, ms per op."""

MODULE, OP = "mesh_wave", "collective-permute"


def read(w):
    hits = [s for key, s in w.device.op_s.items()
            for mod, op in [key.split(":", 1)]
            if MODULE in mod and op.startswith(OP)]
    return w.per_op_ms(sum(hits) / w.device.devices) if hits else None
