"""Window length over the engine iterations in it (host clock)."""


def read(w):
    it = w.delta("iterations")
    return 1e3 * w.seconds / it if it > 0 else None
