"""transfer and dispatch: the program's ``kernel.dispatch`` spans (upload,
kernel, blocking download), ms per op."""


def read(w):
    return w.per_op_ms(w.total_s(("kernel.dispatch",)))
