"""Put the device's idle time down to the serving program's own spans.

    python3 bench/idle_by_span.py --workload <cell> --seed <n> --seconds 51 \
        [--out result.json]

Serves one traced window of the cell as ``bench/run.py --trace 1`` does,
then reduces the trace twice: with ``trace_reduce`` (busy and window
seconds, from the benchmark's ``bench.*`` spans) and here, where every
idle interval of the window is split at the edges of the spans on the
engine's thread (the host line that holds the ``bench.step`` spans) and
each piece is credited to the innermost span covering it, or to
``no span``.  Prints one JSON object: idle seconds by span, the idle
shares of the program's layers, the longest idle gaps with their pieces,
and the cell's end-to-end and per-layer metrics as a run reads them.

The program names its spans ``apex.<phase>`` (``repro.core.spans``);
spans on other lines, such as the host-attention workers', never label
device idle.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Sequence, Tuple  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace_reduce as tr  # noqa: E402

PROGRAM_PREFIX = "apex."
NO_SPAN = "no span"
# each layer's idle share and the program's spans it reads; idle under
# a host job on the engine thread (a synchronous recompute) is a wait
# for the host tier
SHARES = {
    "lifecycle.idle_share": ("apex.admit", "apex.rebalance",
                             "apex.tier_move", "apex.retire"),
    "sched.idle_share": ("apex.schedule",),
    "engine.idle_share": ("apex.step", "apex.dispatch", "apex.sync",
                          "apex.host_token", "apex.finish_chunks"),
    "host.wait_idle_share": ("apex.host_wait", "apex.host_job",
                             "apex.host_job.device_wait",
                             "apex.host_job.copy", "apex.host_job.attention"),
}
# idle under the benchmark's own spans or under none
REST = "rest.idle_share"

Span = Tuple[str, float, float, int]   # (name, start_ns, end_ns, line)


def span_name(name: str) -> str:
    """A span's name without the ``#key=value#`` metadata a profiler may
    append to it."""
    return name.split("#", 1)[0]


def load(path: str) -> Dict:
    """``trace_reduce.load``'s events, and every ``bench.*`` and
    ``apex.*`` span of the host planes with the line (thread) it is on,
    numbered in the order the trace lists them."""
    from jax.profiler import ProfileData

    events = tr.load(path)
    lines: List[Span] = []
    n = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            lines += [(span_name(e.name), e.start_ns, e.end_ns, n)
                      for e in line.events
                      if e.name.startswith((tr.SPAN_PREFIX, PROGRAM_PREFIX))]
            n += 1
    events["lines"] = lines
    return events


def engine_spans(events: Dict) -> List[Tuple[str, float, float]]:
    """The spans of the line that holds the ``bench.step`` spans."""
    steps = defaultdict(int)
    for name, _, _, line in events["lines"]:
        if name == "bench.step":
            steps[line] += 1
    if not steps:
        return []
    engine = max(steps, key=steps.get)
    return [(name, a, b) for name, a, b, line in events["lines"]
            if line == engine]


def segments(spans: Sequence[Tuple[str, float, float]], lo: float,
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


def attribute(events: Dict, top: int = 8) -> Dict:
    """Idle seconds of the traced window by the innermost engine-thread
    span, averaged over the devices that ran anything, as ``busy_s`` is;
    the window is ``trace_reduce``'s, from the ``bench.*`` spans alone."""
    spans = events["spans"]
    if not spans:
        raise ValueError("the trace holds no benchmark spans")
    lo = min(a for _, a, _ in spans)
    hi = max(b for _, _, b in spans)
    segs = segments(engine_spans(events), lo, hi)
    ends = [b for _, b, _ in segs]
    by_span: Dict[str, float] = defaultdict(float)
    longest = []
    n_dev = 0
    for dev in events["devices"].values():
        ops = tr.clip([(a, b) for _, a, b in dev["ops"]], lo, hi)
        if not ops:
            continue
        n_dev += 1
        for gap in tr.gaps(tr.union(ops), lo, hi):
            pieces = split(gap, segs, ends)
            for name, ns in pieces.items():
                by_span[name] += ns
            longest.append((gap[1] - gap[0], gap[0] - lo, pieces))
    if not n_dev:
        raise ValueError("no operation ran on a device in the traced window")
    ns = 1e-9
    longest.sort(key=lambda g: -g[0])
    return {
        "idle_by_span": {k: v * ns / n_dev for k, v in
                         sorted(by_span.items(), key=lambda kv: -kv[1])},
        "longest_gaps": [
            {"s": d * ns, "at_s": at * ns,
             "pieces": {k: v * ns for k, v in
                        sorted(p.items(), key=lambda kv: -kv[1])}}
            for d, at, p in longest[:top]],
    }


def layer_shares(idle_by_span: Dict[str, float],
                 window_s: float) -> Dict[str, float]:
    """Idle under each layer's spans as a share (%) of the window, and
    the rest: idle under ``bench.*`` spans and under no span."""
    out = {share: 100.0 * sum(idle_by_span.get(n, 0.0)
                              for n in names) / window_s
           for share, names in SHARES.items()}
    named = {n for names in SHARES.values() for n in names}
    out[REST] = 100.0 * sum(
        v for k, v in idle_by_span.items() if k not in named) / window_s
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from bench.peaks import peak_for
    from bench.registry import Registry, read_metric

    if jax.devices()[0].platform != "tpu":
        print("idle_by_span: needs a TPU", file=sys.stderr)
        return 2
    harness.configure_jax()
    registry = Registry()
    cell = registry.cell(args.workload)
    trace_dir = harness.new_trace_dir()
    try:
        run = harness.serve_window(
            registry.config(cell["config"]), registry.mix(cell["traffic"]),
            args.seed, args.seconds, t_process=T_PROCESS,
            trace_dir=trace_dir)
        events = load(tr.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    w = run.window
    w.peak = peak_for(jax.devices()[0].device_kind)
    w.trace = tr.reduce(events)
    row = {"cell": cell["name"], "seed": args.seed,
           "busy_s": w.trace["busy_s"], "window_s": w.trace["window_s"],
           "device_idle_s": w.trace["window_s"] - w.trace["busy_s"],
           "iterations": w.delta("iterations"),
           "metrics": {}}
    for kind in ("end_to_end", "per_layer"):
        for entry in registry.metric_entries(cell["name"], kind):
            m = read_metric(registry, entry, w)
            if m is not None:
                row["metrics"][entry["name"]] = m["value"]
    row.update(attribute(events))
    row["layer_shares"] = layer_shares(row["idle_by_span"],
                                       w.trace["window_s"])
    row["notes"] = run.notes
    line = json.dumps(row)
    print(line)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
