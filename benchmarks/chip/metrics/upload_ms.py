"""transfer and dispatch: the program's ``kernel.upload`` spans (a wave's
operands copied to the device, until they are there), ms per op."""


def read(w):
    return w.per_op_ms(w.total_s(("kernel.upload",)))
