"""Device idle while the engine thread runs Algorithm 1's decision
(``core/scheduler``), as a share (%) of the traced window."""
from bench.idle_by_span import idle_share

SPANS = ("apex.schedule",)


def read(w):
    return idle_share(w.trace, SPANS)
