"""api layer (api/plan.py, core/quadtree.py, core/multiply.py): self
time of the program's ``plan.*`` and ``qt.*`` spans, ms per op."""


def read(w):
    return w.per_op_ms(w.self_s(("plan.", "qt.")))
