"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes its requests from ``--seed``.

Every seed gets the same requests in the same order; the seed draws only
their token ids.  Lengths are lognormal (the shapes of
``repro.serving.workloads``, copied here so that program changes cannot
move them), drawn as stratified quantiles, paired and ordered by one
constant permutation.  The order is not left to the seed because it
changes the work: it decides which stream's prefill finishes first, and
that decides the whole run (PERF.md).

Mix keys:
  loop         "closed": a fixed number of streams, each replaced by the
               next request when it finishes (the only loop there is)
  streams      "slots": one stream per device and host slot; the first
               requests of the streams are sent when the window opens
  prompt       {"mean", "cv", "min", "max"} lognormal prompt lengths
  output       {"mean", "cv", "min", "max"} lognormal output lengths, also
               capped at cache_len - prompt - 1
  pool         number of requests in the fixed set
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
# the fixed pairing and order of prompt and output quantiles (not the
# run's seed)
_PAIRING_SEED = 20250603


@dataclasses.dataclass
class Spec:
    """One request as the generator makes it."""
    prompt: List[int]
    max_new_tokens: int


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> Dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"traffic mix {name!r}: no file {path}")
    return json.loads(path.read_text())


def lognormal_quantiles(dist: Dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles ((i + 0.5) / n) of a lognormal with the
    given mean and coefficient of variation, rounded and clipped."""
    sigma2 = math.log(1.0 + dist["cv"] ** 2)
    mu = math.log(dist["mean"]) - sigma2 / 2.0
    nd = NormalDist(mu, math.sqrt(sigma2))
    x = np.exp([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(x).astype(np.int64), dist["min"], dist["max"])


def _pairs(mix: Dict, n: int, cache_len: int) -> np.ndarray:
    """(n, 2) prompt and output lengths: a fixed set in a fixed order for
    a given n (quantiles paired and shuffled by a constant seed)."""
    fixed = np.random.default_rng(_PAIRING_SEED)
    p = lognormal_quantiles(mix["prompt"], n)
    o = lognormal_quantiles(mix["output"], n)[fixed.permutation(n)]
    p = np.minimum(p, cache_len - 2)
    o = np.maximum(np.minimum(o, cache_len - p - 1), 1)
    return np.stack([p, o], axis=1)[fixed.permutation(n)]


class Traffic:
    """A mix's requests for one seed, in the order they are sent."""

    def __init__(self, mix: Dict, seed: int, *, vocab: int, cache_len: int,
                 streams: int) -> None:
        if mix["loop"] != "closed":
            raise ValueError(f"unknown loop {mix['loop']!r}; the generator "
                             f"makes closed loops only")
        if streams <= 0:
            raise ValueError("a closed loop needs a number of streams")
        self.mix = mix
        self._rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
        self.vocab = vocab
        self.streams = streams
        first = _pairs(mix, streams, cache_len)
        self.initial = [self._make(int(p), int(o)) for p, o in first]
        self.queue = [self._make(int(p), int(o))
                      for p, o in _pairs(mix, int(mix["pool"]), cache_len)]

    def _make(self, plen: int, olen: int) -> Spec:
        """A request of ``plen`` random prompt tokens and ``olen`` output
        tokens."""
        toks = self._rng.integers(0, self.vocab, plen)
        return Spec(prompt=[int(t) for t in toks], max_new_tokens=olen)

    def next(self) -> Optional[Spec]:
        return self.queue.pop(0) if self.queue else None
