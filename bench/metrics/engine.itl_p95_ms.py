"""``itl_p95_ms.device`` in the cells that report no ``itl_p99_ms.device``
end to end, so that it moves ``tokens_per_s`` there: the 95th percentile of
every device-tier gap between consecutive tokens inside the window, on the
knee between decode-only and chunk steps."""
from bench.window import gaps_in, percentile


def read(w):
    p = percentile(gaps_in(w.stamps, w.t0, w.t1, w.token_tiers, "device"), 95)
    return None if p is None else 1e3 * p
