"""transfer and dispatch: bytes a wave stages between host and devices,
the ``bytes_packed`` counters of the program's ``engine.wave`` spans
(operands up, the padded result down), MB (1e6 B) per op."""


def read(w):
    hits = [s.attrs["bytes_packed"] for s in w.spans
            if s.name == "engine.wave" and "bytes_packed" in s.attrs]
    return 1e-6 * sum(hits) / w.ops if hits else None
