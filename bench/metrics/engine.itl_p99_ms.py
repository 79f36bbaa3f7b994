"""``itl_p99_ms.device`` read as a per-layer metric, in the cells where that
tail is not steady enough from run to run to stand end to end: the 99th
percentile of every device-tier gap between consecutive tokens inside the
window.  On InternLM2-1.8B it falls among the few hundred gaps of the
engine's longest steps (chunk steps beside a retire, prefix-cache
demotions), whose number changes from run to run of one seed."""
from bench.window import gaps_in, percentile


def read(w):
    p = percentile(gaps_in(w.stamps, w.t0, w.t1, w.token_tiers, "device"), 99)
    return None if p is None else 1e3 * p
