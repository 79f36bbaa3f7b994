"""Device idle while the engine thread admits, places, moves between
tiers or retires requests (``serving/lifecycle``), as a share (%) of the
traced window."""
from bench.idle_by_span import idle_share

SPANS = ("apex.admit", "apex.rebalance", "apex.tier_move", "apex.retire")


def read(w):
    return idle_share(w.trace, SPANS)
