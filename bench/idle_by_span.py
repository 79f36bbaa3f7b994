"""Put the device's idle time down to the serving program's own spans.

    python3 bench/idle_by_span.py --workload <cell> --seed <n> --seconds 51

serves one traced window of the cell, as ``bench/run.py --trace 1`` does:
it prints the idle seconds by span, the longest idle gaps with their
pieces, and a result line whose metrics hold the layers' idle shares.

``trace_reduce.reduce`` calls ``attribute`` on every traced window: each
idle interval is split at the edges of the spans on the engine's thread
(the host line that holds the ``bench.step`` spans) and each piece is
credited to the innermost span covering it, or to ``no span``.  The
program names its spans ``apex.<phase>`` (``repro.core.spans``); spans on
other lines, such as the host-attention workers', never label device
idle.

A layer's idle share is a reader of its own,
``metrics/<layer>.idle_share.py``, that names the layer's spans and reads
them with ``idle_share``.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

NO_SPAN = "no span"

Span = Tuple[str, float, float]   # (name, start_ns, end_ns)


def engine_spans(lines: Sequence[Tuple[str, float, float, int]]) -> List[Span]:
    """The spans of the line that holds the most ``bench.step`` spans;
    ``lines`` are (name, start_ns, end_ns, line) of every host span."""
    steps: Dict[int, int] = defaultdict(int)
    for name, _, _, line in lines:
        if name == "bench.step":
            steps[line] += 1
    if not steps:
        return []
    engine = max(steps, key=steps.get)
    return [(name, a, b) for name, a, b, line in lines if line == engine]


def segments(spans: Sequence[Span], lo: float,
             hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut at every span edge, each piece labelled with the
    shortest (innermost) span covering it, or ``no span``."""
    cuts = sorted({lo, hi} | {t for _, a, b in spans for t in (a, b)
                              if lo < t < hi})
    order = sorted(spans, key=lambda s: s[1])
    out, active, k = [], [], 0
    for a, b in zip(cuts[:-1], cuts[1:]):
        while k < len(order) and order[k][1] <= a:
            active.append(order[k])
            k += 1
        active = [s for s in active if s[2] >= b]
        best = min(active, key=lambda s: s[2] - s[1], default=None)
        out.append((a, b, best[0] if best else NO_SPAN))
    return out


def split(gap: Tuple[float, float],
          segs: Sequence[Tuple[float, float, str]],
          ends: Sequence[float]) -> Dict[str, float]:
    """Nanoseconds of one idle gap under each label; ``ends`` are the
    segments' ends, for the search."""
    out: Dict[str, float] = defaultdict(float)
    i = bisect.bisect_right(ends, gap[0])
    while i < len(segs) and segs[i][0] < gap[1]:
        a, b, name = segs[i]
        out[name] += min(b, gap[1]) - max(a, gap[0])
        i += 1
    return out


def attribute(spans: Sequence[Span],
              device_gaps: Sequence[Sequence[Tuple[float, float]]],
              lo: float, hi: float, top: int = 8) -> Dict:
    """Idle seconds of the window [lo, hi] (ns) by the innermost of the
    engine line's ``spans``, averaged over the devices (one list of idle
    gaps each, as ``trace_reduce.gaps`` gives them), largest first; and
    the ``top`` longest gaps with their pieces."""
    segs = segments(spans, lo, hi)
    ends = [b for _, b, _ in segs]
    by_span: Dict[str, float] = defaultdict(float)
    longest = []
    for dev_gaps in device_gaps:
        for gap in dev_gaps:
            pieces = split(gap, segs, ends)
            for name, ns in pieces.items():
                by_span[name] += ns
            longest.append((gap[1] - gap[0], gap[0] - lo, pieces))
    ns = 1e-9
    longest.sort(key=lambda g: -g[0])
    return {
        "idle_by_span": {k: v * ns / len(device_gaps) for k, v in
                         sorted(by_span.items(), key=lambda kv: -kv[1])},
        "longest_gaps": [
            {"s": d * ns, "at_s": at * ns,
             "pieces": {k: v * ns for k, v in
                        sorted(p.items(), key=lambda kv: -kv[1])}}
            for d, at, p in longest[:top]],
    }


def idle_share(trace: Optional[Dict], names: Sequence[str]) -> Optional[float]:
    """Idle under the spans ``names`` as a share (%) of the traced window;
    None without a trace, or where the engine's line holds no program
    span (a program without spans has nothing to read)."""
    if trace is None or not trace.get("program_spans"):
        return None
    by_span = trace["idle_by_span"]
    return 100.0 * sum(by_span.get(n, 0.0) for n in names) / trace["window_s"]


if __name__ == "__main__":
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from bench import run

    sys.exit(run.main(sys.argv[1:] + ["--trace", "1"]))
