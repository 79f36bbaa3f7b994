"""Median of every gap between consecutive tokens of a request, over all
requests, with both tokens inside the window, where the later token was
made by the device tier: the pace of a device-tier stream, set by the
decode-only step and the engine-thread work around it."""
from bench.window import gaps_in, percentile


def read(w):
    p = percentile(gaps_in(w.stamps, w.t0, w.t1, w.token_tiers, "device"), 50)
    return None if p is None else 1e3 * p
