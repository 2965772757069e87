"""kernels (launch/mesh_exec.py): device time of the Pallas (Mosaic)
calls in the ``jit_mesh_wave`` modules in the profiler trace, the mean
over the devices that ran in the window, ms per op."""


def read(w):
    kernel_s = w.device.kernel_s("mesh_wave")
    return None if kernel_s is None else \
        w.per_op_ms(kernel_s / w.device.devices)
