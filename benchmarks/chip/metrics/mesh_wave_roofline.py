"""kernels (launch/mesh_exec.py): the least time of one op's product on
the devices used over ``mesh_wave_ms``, the mesh kernel's mean device
time per op, %.

The least time is the larger of the product's flops over the devices'
summed peak and its bytes over their summed HBM bandwidth; both come from
the operands' block pattern (the driver's ``work``), not from the
program.  The bf16 peak bounds a float32 product from above.
"""


def read(w):
    kernel_s = w.device.kernel_s("mesh_wave")
    if not kernel_s or not w.work:
        return None
    n = w.device.devices
    least = max(w.work["flops"] / (n * w.peaks["flops_per_s"]),
                w.work["bytes"] / (n * w.peaks["hbm_bytes_per_s"]))
    return 100.0 * least / (kernel_s / n / w.ops)
