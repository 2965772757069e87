"""kernels (kernels/bsmm_pairs.py): the least time of one op's product
over the kernel's device time per op, %.

The least time is the larger of the product's flops over the chip's peak
and its bytes over the HBM bandwidth; both come from the operands' block
pattern (``reference.product_work``), not from the program.  The bf16 peak
bounds a float32 product from above.
"""


def read(w):
    kernel_s = w.device.kernel_s("bsmm_pairs")
    if not kernel_s or not w.work:
        return None
    least = max(w.work["flops"] / w.peaks["flops_per_s"],
                w.work["bytes"] / w.peaks["hbm_bytes_per_s"])
    return 100.0 * least * w.ops / kernel_s
