"""kernels (kernels/bsmm_pairs.py): device time of the ``bsmm_pairs``
kernel's operations in the profiler trace, ms per op."""


def read(w):
    return w.per_op_ms(w.device.kernel_s("bsmm_pairs"))
