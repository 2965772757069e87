"""transfer and dispatch: the program's ``kernel.download`` spans (a
wave's result copied back to the host), ms per op."""


def read(w):
    return w.per_op_ms(w.total_s(("kernel.download",)))
