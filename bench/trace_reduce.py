"""From a profiler trace to device busy time, idle share, top device
programs and idle gaps labelled by what the host was doing.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``
into plain (name, start_ns, end_ns) tuples first, so that the reduction
itself is pure arithmetic and testable on recorded events.

* Device planes are the chips', ``/device:TPU:<n>``; a profiler's other
  ``/device:`` planes (the CPU, a ``CUSTOM`` one such as the Megascale
  trace that loading the TPU library adds) are not chips.  Their
  ``XLA Ops`` line holds the operations; ``XLA Modules`` names the
  program each ran in.  Busy time is the union of the operations'
  intervals, averaged over the devices that ran anything.
* Host spans are the benchmark's own ``TraceAnnotation``s (names starting
  with ``bench.``) on the host plane.  The traced window runs from the
  first such span's start to the last one's end.
* An idle gap is an interval of the window with no operation on the
  device; it is labelled by the host span that covers its midpoint, or
  ``no span``.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[str, float, float]   # (name, start_ns, end_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def load(path: str) -> Dict:
    """Events of a trace file: per device its ops and modules, and the
    host's benchmark spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Interval]]] = {}
    spans: List[Interval] = []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            lines = {line.name: line for line in plane.lines}
            ops = lines.get(OPS_LINE)
            mods = lines.get(MODULES_LINE)
            devices[plane.name] = {
                "ops": [(e.name, e.start_ns, e.end_ns) for e in ops.events]
                if ops is not None else [],
                "modules": [(e.name, e.start_ns, e.end_ns)
                            for e in mods.events] if mods is not None else []}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float):
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label(spans: Sequence[Interval], t: float) -> str:
    """The innermost benchmark span covering time ``t``."""
    best = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else "no span"


def reduce(events: Dict, top: int = 10) -> Dict:
    """Busy and window seconds, and the breakdown the result line carries."""
    spans = events["spans"]
    if not spans:
        raise ValueError("the trace holds no benchmark spans")
    lo = min(a for _, a, _ in spans)
    hi = max(b for _, _, b in spans)
    busy_per_dev, gap_list = [], []
    by_module: Dict[str, float] = defaultdict(float)
    n_dev = 0
    for dev in events["devices"].values():
        ops = clip([(a, b) for _, a, b in dev["ops"]], lo, hi)
        if not ops:
            continue
        n_dev += 1
        merged = union(ops)
        busy_per_dev.append(sum(b - a for a, b in merged))
        gap_list += gaps(merged, lo, hi)
        for name, a, b in dev["modules"]:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                by_module[name] += b - a
    if not n_dev:
        raise ValueError("no operation ran on a device in the traced window")
    by_label: Dict[str, float] = defaultdict(float)
    longest = []
    for a, b in gap_list:
        name = label(spans, (a + b) / 2)
        by_label[name] += b - a
        longest.append((b - a, name))
    longest.sort(reverse=True)
    ns = 1e-9
    idle = [[f"total {k}", v * ns] for k, v in
            sorted(by_label.items(), key=lambda kv: -kv[1])][: top // 2]
    idle += [[f"longest {k}", v * ns] for v, k in longest[: top - len(idle)]]
    ops = sorted(by_module.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy_per_dev) / n_dev * ns,
            "window_s": (hi - lo) * ns,
            "devices": n_dev,
            "breakdown": {"device_ops": [[k, v * ns] for k, v in ops],
                          "idle_gaps": idle}}
