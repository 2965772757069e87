"""transfer and dispatch: device-to-host bytes, the ``bytes`` counters of
the program's ``kernel.download`` spans, MB (1e6 B) per op."""


def read(w):
    hits = [s.attrs["bytes"] for s in w.spans
            if s.name == "kernel.download"]
    return 1e-6 * sum(hits) / w.ops if hits else None
