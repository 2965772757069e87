"""transfer and dispatch: the program's ``kernel.run`` spans (the jitted
kernel call, until its result is ready: launch and device time), ms per
op."""


def read(w):
    return w.per_op_ms(w.total_s(("kernel.run",)))
