"""95th percentile of every gap between consecutive tokens of a request,
over all requests, with both tokens inside the window, where the later
token was made by the host tier.  A host row advances one layer per
iteration, so its gaps are an order of magnitude longer than a device
row's and are read apart.  A per-layer metric: a window holds some 30
(1.8B) to 80 (20b-8l) such gaps, so the 95th percentile is the second to
fourth longest, and a tier move or a program compiled for a new length
inside the window decides it (PERF.md)."""
from bench.window import gaps_in, percentile


def read(w):
    p = percentile(gaps_in(w.stamps, w.t0, w.t1, w.token_tiers, "host"), 95)
    return None if p is None else 1e3 * p
