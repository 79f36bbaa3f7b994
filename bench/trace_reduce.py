"""From a profiler trace to device busy time, idle share, top device
programs, and idle time put down to what the host was doing.

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
  with ``bench.``) and the serving program's (``apex.``) on the host
  planes, each with the line (thread) it is on.  The traced window runs
  from the first benchmark span's start to the last one's end.
* An idle gap is an interval of the window with no operation on the
  device; ``idle_by_span.attribute`` splits it at the edges of the spans
  on the engine's thread and credits each piece to the innermost span
  covering it, or to ``no span``.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

from bench import idle_by_span

Interval = Tuple[str, float, float]   # (name, start_ns, end_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "apex."


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def span_name(name: str) -> str:
    """A span's name without the ``#key=value#`` metadata a profiler may
    append to it."""
    return name.split("#", 1)[0]


def load(path: str) -> Dict:
    """Events of a trace file: per device its ops and modules; the host's
    benchmark spans (``spans``); and every benchmark and program span of
    the host planes with the line it is on, numbered in the order the
    trace lists them (``lines``)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Interval]]] = {}
    lines: List[Tuple[str, float, float, int]] = []
    n = 0
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            named = {line.name: line for line in plane.lines}
            ops = named.get(OPS_LINE)
            mods = named.get(MODULES_LINE)
            devices[plane.name] = {
                "ops": [(e.name, e.start_ns, e.end_ns) for e in ops.events]
                if ops is not None else [],
                "modules": [(e.name, e.start_ns, e.end_ns)
                            for e in mods.events] if mods is not None else []}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                lines += [(span_name(e.name), e.start_ns, e.end_ns, n)
                          for e in line.events
                          if e.name.startswith((SPAN_PREFIX, PROGRAM_PREFIX))]
                n += 1
    spans = [(name, a, b) for name, a, b, _ in lines
             if name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans, "lines": lines}


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


def reduce(events: Dict, top: int = 10) -> Dict:
    """Busy and window seconds, idle seconds by the innermost engine-thread
    span (averaged over the devices that ran anything, as busy time is)
    with the longest idle gaps, and the breakdown the result line
    carries.  Events without ``lines`` (a recorded trace of the benchmark's
    spans alone) count those spans as the engine's line."""
    spans = events["spans"]
    if not spans:
        raise ValueError("the trace holds no benchmark spans")
    lo = min(a for _, a, _ in spans)
    hi = max(b for _, _, b in spans)
    busy_per_dev, device_gaps = [], []
    by_module: Dict[str, float] = defaultdict(float)
    for dev in events["devices"].values():
        ops = clip([(a, b) for _, a, b in dev["ops"]], lo, hi)
        if not ops:
            continue
        merged = union(ops)
        busy_per_dev.append(sum(b - a for a, b in merged))
        device_gaps.append(gaps(merged, lo, hi))
        for name, a, b in dev["modules"]:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                by_module[name] += b - a
    if not device_gaps:
        raise ValueError("no operation ran on a device in the traced window")
    lines = events.get("lines") or [(n, a, b, 0) for n, a, b in spans]
    engine = idle_by_span.engine_spans(lines)
    idle = idle_by_span.attribute(engine, device_gaps, lo, hi, top=top)
    by_span = idle["idle_by_span"]
    breakdown_idle = [[f"total {k}", v] for k, v in
                      list(by_span.items())[: top // 2]]
    breakdown_idle += [
        [f"longest {max(g['pieces'], key=g['pieces'].get)}", g["s"]]
        for g in idle["longest_gaps"][: top - len(breakdown_idle)]]
    ns = 1e-9
    ops = sorted(by_module.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy_per_dev) / len(busy_per_dev) * ns,
            "window_s": (hi - lo) * ns,
            "devices": len(busy_per_dev),
            "idle_by_span": by_span,
            "longest_gaps": idle["longest_gaps"],
            "program_spans": sum(1 for n, _, _ in engine
                                 if n.startswith(PROGRAM_PREFIX)),
            "breakdown": {"device_ops": [[k, v * ns] for k, v in ops],
                          "idle_gaps": breakdown_idle}}
