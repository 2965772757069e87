"""engine layer (core/engine.py): self time of the program's
``engine.flush`` and ``engine.wave`` spans (packing, unpacking, host
fills), ms per op."""


def read(w):
    return w.per_op_ms(w.self_s(("engine.flush", "engine.wave")))
