"""95th percentile of every gap between consecutive tokens of a request,
over all requests, with both tokens inside the window, where the later
token was made by the device tier.  The two tiers' gaps differ by an order
of magnitude (a host row advances one layer per iteration), so each
tier's tail is read on its own: pooled, the 95th percentile would sit on
whichever side the host tier's share of tokens puts it."""
from bench.window import gaps_in, percentile


def read(w):
    p = percentile(gaps_in(w.stamps, w.t0, w.t1, w.token_tiers, "device"), 95)
    return None if p is None else 1e3 * p
