"""transfer and dispatch: host-to-device bytes, the ``bytes`` counters of
the program's ``kernel.upload`` spans, MB (1e6 B) per op."""


def read(w):
    hits = [s.attrs["bytes"] for s in w.spans if s.name == "kernel.upload"]
    return 1e-6 * sum(hits) / w.ops if hits else None
