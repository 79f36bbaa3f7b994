"""Process start to the window's start: imports, weights, server, warm-up
of this cell's programs and, for a closed loop, filling the batch."""


def read(w):
    return w.setup_s
