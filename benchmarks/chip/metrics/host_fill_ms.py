"""engine layer (core/engine.py): self time of the program's
``engine.flush.host`` spans (the host adds, transposes and scales that
join partial products), ms per op."""


def read(w):
    return w.per_op_ms(w.self_s(("engine.flush.host",)))
