"""engine layer (core/engine.py): self time of the program's
``engine.wave.pack`` spans (output slot numbering, the pair loop, the
operand stacks and the segment sort of each wave), ms per op."""


def read(w):
    return w.per_op_ms(w.self_s(("engine.wave.pack",)))
