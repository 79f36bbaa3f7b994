"""99th percentile of every gap between consecutive tokens of a request,
over all requests, with both tokens inside the window, where the later
token was made by the device tier.  A window holds some thousands of such
gaps, so more than ten lie beyond it: the tail a device-tier stream
feels, from steps that run a prefill chunk, move a request between tiers
or demote prefix-cache entries beside the decode."""
from bench.window import gaps_in, percentile


def read(w):
    p = percentile(gaps_in(w.stamps, w.t0, w.t1, w.token_tiers, "device"), 99)
    return None if p is None else 1e3 * p
