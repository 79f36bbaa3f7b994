"""What one measured window saw, from the client's side and from the
program's counters, and the arithmetic the metric readers share.

All times are host ``time.perf_counter()`` seconds.  A token's stamp is
the moment the client's loop saw it, after the engine step that made it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class StepRecord:
    """One engine step inside the window: when it ended, the live context
    of each device row that decoded in it, tokens emitted by host rows,
    prompt tokens prefilled in it, and the change in it of each engine
    counter the configuration's family names in ``STEP_COUNTERS``."""
    t_end: float
    device_contexts: List[int]
    host_tokens: int
    prompt_tokens: int
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    setup_s: float
    model: Dict                       # the configuration's model sizes
    engine: Dict                      # the configuration's engine geometry
    stamps: Dict[int, List[float]]    # request id -> token stamps
    token_tiers: Dict[int, List[str]]  # request id -> tier of each token
    failed: List[int]                 # request ids that failed
    counters0: Dict[str, float]
    counters1: Dict[str, float]
    steps: List[StepRecord]
    peak: object = None               # bench.peaks.Peak of the device
    trace: Optional[Dict] = None      # bench.trace_reduce.reduce() output
    family: object = None             # the configuration's family module

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def delta(self, name: str) -> float:
        return self.counters1[name] - self.counters0[name]


def tokens_in(stamps: Dict[int, Sequence[float]], t0: float,
              t1: float) -> int:
    return sum(int(np.count_nonzero((np.asarray(s) >= t0)
                                    & (np.asarray(s) <= t1)))
               for s in stamps.values() if len(s))


def gaps_in(stamps: Dict[int, Sequence[float]], t0: float, t1: float,
            tiers: Optional[Dict[int, Sequence[str]]] = None,
            tier: Optional[str] = None) -> np.ndarray:
    """Every gap between consecutive tokens of one request, both of whose
    tokens fall inside the window; with ``tier``, only the gaps that end
    on a token that tier made."""
    out = []
    for rid, s in stamps.items():
        s = np.asarray(s, float)
        if len(s) < 2:
            continue
        keep = (s[1:] >= t0) & (s[:-1] >= t0) & (s[1:] <= t1)
        if tier is not None:
            keep &= np.asarray(tiers[rid][1:]) == tier
        out.append(np.diff(s)[keep])
    return np.concatenate(out) if out else np.zeros(0)


def percentile(x: np.ndarray, q: float) -> Optional[float]:
    return float(np.percentile(x, q)) if len(x) else None
