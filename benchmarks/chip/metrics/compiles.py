"""XLA: backend compiles inside the measured window (0 when every shape
was warmed in set-up)."""


def read(w):
    return w.compiles
