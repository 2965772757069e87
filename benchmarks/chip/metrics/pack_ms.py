"""engine layer (core/engine.py): self time of the program's
``engine.wave.pack`` spans (output slot numbering, the pair loop, the
wave's one float32 operand table and the segment sort), ms per op."""


def read(w):
    return w.per_op_ms(w.self_s(("engine.wave.pack",)))
