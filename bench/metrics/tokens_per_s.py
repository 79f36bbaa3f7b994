"""Output tokens the client saw inside the window, over the window."""
from bench.window import tokens_in


def read(w):
    n = tokens_in(w.stamps, w.t0, w.t1)
    return n / w.seconds if n else None
