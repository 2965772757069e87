"""engine layer (core/engine.py): self time of the program's
``engine.wave.unpack`` spans (a wave's result blocks copied into their
leaves), ms per op."""


def read(w):
    return w.per_op_ms(w.self_s(("engine.wave.unpack",)))
