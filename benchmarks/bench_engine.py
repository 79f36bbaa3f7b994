"""Decode hot-path benchmark: the engine perf numbers each PR is held to.

Measures, on the container's CPU backend:

  * ``decode`` — offload-heavy serving (the APEX regime: more requests
    than device slots, host tier carrying cohorts): decode iterations/s,
    tokens/s, host-overlap efficiency (host busy / wall).
  * ``prefill`` — admission over many distinct prompt lengths: jit
    compile count (bucketing bounds it), mean admission latency, and
    p50/p95 time-to-first-token / inter-token latency.
  * ``preemption`` (all modes) — mixed-priority arrivals with the host
    pool too small for the urgent prompt: reports urgent TTFT p95 with
    and without preemptive admission plus ``deadline_misses`` (the CI
    smoke gate asserts zero, and >= 1 preemption).
  * ``hybrid_decode`` (all modes) — a hybrid (Mamba+attention) stack on
    the serving fast paths: cold admission latency over distinct prompt
    lengths under bucketed+chunked prefill vs the whole-prompt
    per-request path hybrids used to be gated onto, and decode co-run
    while a long hybrid prompt is mid-prefill; the CI gate asserts the
    admission ratio <= HYBRID_ADMISSION_RATIO_MAX and
    ``chunk_co_run_iterations`` > 0.
  * ``multi_turn_chat`` (all modes) — chat sessions over a shared long
    system prompt, replayed with the cross-request prefix cache off
    (cold) and on (warm): follow-up-turn TTFT both ways, the cache hit
    rate, and a bit-identity check against the cache-disabled run; the
    CI gate asserts a nonzero hit rate, warm TTFT <=
    CHAT_WARM_TTFT_RATIO_MAX of cold, and identical tokens.
  * ``long_context`` (full mode) — a long prompt arriving mid-decode:
    chunked prefill must co-run with decode (``chunk_co_run_iterations``
    > 0) instead of stalling it, and a host-tier long must migrate to a
    freed device slot (``migrations`` >= 1, tokens bit-identical to a
    rebalancing-disabled run); reports decode progress during the
    prefill window.
  * ``asym_heavy`` (full mode) — 1 device slot vs a large host cohort
    at long context: the regime where Algorithm 1 leans hybrid; reports
    the strategy mix and throughput.
  * ``arrival_sweep`` (full mode) — open-loop Poisson replay at several
    arrival rates through ``InferenceServer.serve``; reports TTFT
    percentiles per rate.
  * ``http_serving`` (all modes) — end-to-end through the HTTP/SSE
    gateway over real sockets (2 engine replicas): closed-loop TTFT/ITL
    percentiles per concurrency level, open-loop Poisson (full mode),
    and the 429/503 shed rate when a tiny bounded gateway queue is
    overloaded; the CI gate asserts its smoke flags.
  * ``fault_soak`` (all modes) — a closed-loop run under a deterministic
    chaos plan (host worker deaths + stalls, pool allocation failures,
    latency spikes) plus a blocked-swap preemption that must take the
    recompute escape hatch; the CI gate asserts every request completes
    bit-identical to a fault-free run, the watchdog fallback and
    recompute both engaged, and zero pool pages / host slots leak.
  * ``host_capacity`` (all modes) — the quantized host KV tier at a
    fixed RAM budget: resident requests before shed at fp32 vs int8
    page storage, the host->device migration gather time per dtype,
    and offload-heavy decode throughput per dtype; the CI gate asserts
    resident_ratio >= CAPACITY_RESIDENT_RATIO_MIN and decode_ratio >=
    CAPACITY_DECODE_RATIO_MIN.  ``multi_turn_chat`` and ``fault_soak``
    additionally rerun once with ``host_kv_dtype=int8``: chaos
    recovery must stay bit-identical with zero leaks (a true invariant
    — chaos and fault-free runs quantize identically, so any mismatch
    is a scale-table leak, not drift), and the smoke chat gate asserts
    warm==cold token identity (at full geometry host-pool hits are
    drift-bounded per the documented accuracy contract; the scenario
    reports ``tokens_match_fraction`` alongside the flag).

Emits ``BENCH_engine.json`` at the repo root (CI uploads it as an
artifact so the perf trajectory accumulates per PR).  The JSON carries
two reference blocks: ``baseline`` (the pre-parallel-hot-path engine,
commit d66a15b) and ``pr3_baseline`` (the pre-chunked-prefill engine,
commit 9154eac) — both measured on this same container in full mode.

``--check`` (used by CI after ``--smoke``) compares decode iters/s and
host-overlap efficiency against the committed ``SMOKE_BASELINE`` block
and exits non-zero on a >30% drop.

    PYTHONPATH=src python benchmarks/bench_engine.py [--smoke] [--check] \
        [--out BENCH_engine.json]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import configure_compile_cache
from repro.models import init_params
from repro.serving.engine import Engine, EngineConfig
from repro.serving.request import Request, make_synthetic_request

# Pre-PR reference: this same scenario (full mode) measured on the
# engine before the parallel host runtime / non-blocking handoff /
# bucketed prefill landed, on the 2-vCPU container CI runs on.
PRE_PR_BASELINE = {
    "commit": "d66a15b",
    "decode_iters_per_s": 10.67,
    "tokens_per_s": 15.82,
    "host_overlap_efficiency": 0.051,
    "admission_latency_ms": 17326.0,
}

# The engine as of PR 3 (parallel host runtime, bucketed prefill, but
# whole-prompt prefill serialized before decode), full mode, this
# container — the bar the chunked-prefill work is held to.
PR3_BASELINE = {
    "commit": "9154eac",
    "decode_iters_per_s": 58.96,
    "tokens_per_s": 117.17,
    "host_overlap_efficiency": 0.392,
    "admission_latency_ms": 3352.0,
    "prefill_wall_s": 6.86,
}

# Committed smoke-mode numbers on the 2-vCPU reference container: the
# CI regression gate (--check) fails the job when a fresh --smoke run
# drops more than REGRESSION_TOLERANCE below these.  decode_iters_per_s
# is hardware-dependent — if the CI runner class changes, re-record
# with `--smoke --record-baseline` there and update this block
# (host_overlap_efficiency is a ratio and travels better).
SMOKE_BASELINE = {
    # re-recorded on the current 1-vCPU container (the old block came
    # from a 2-vCPU runner, where host attention gets its own core and
    # overlap efficiency runs ~4x higher)
    "decode_iters_per_s": 168.6,
    # on 1 vCPU the overlap ratio is scheduling noise in a 0.05-0.10
    # band run-to-run; baseline the band floor so the gate only trips
    # on a real collapse (overlap broken -> ~0), not on which side of
    # the band a given run lands
    "host_overlap_efficiency": 0.05,
}
REGRESSION_TOLERANCE = 0.30

# hybrid_decode gate: cold admission under the fast paths must land at
# or below this fraction of the whole-prompt per-request path's latency
# (a ratio of two same-process measurements, so it travels across
# runner classes in a way absolute iters/s numbers don't).  0.75, not
# 0.6: plan_chunks now caps every grant at chunk_tokens so the chunk
# buffer keeps one compiled geometry (the prefix cache's warm==cold
# bit-identity requires it) — idle admissions take more iterations
# than the old whole-backlog burst, which costs most in the short
# smoke scenario (full mode still measures ~0.45).  A geometry-stable
# kernel would earn the 0.6 bar back (ROADMAP open item 3).
HYBRID_ADMISSION_RATIO_MAX = 0.75
HYBRID_ARCH = "jamba-1.5-large-398b"

# multi_turn_chat gate: warm follow-up turns (history prefix served
# from the cache) must land at or below this fraction of the cold TTFT
# (again a same-process ratio, portable across runner classes).
CHAT_WARM_TTFT_RATIO_MAX = 0.5

# host_capacity gates: at a fixed host RAM budget the int8 pool must
# hold at least this many times more resident requests than fp32
# (quantized pages are ~4x denser; 1.5 leaves headroom for the fp32
# scale rows), and int8 decode throughput must stay within 10% of
# fp32's at the same offload-heavy geometry (the dequant is fused into
# the host attention kernel, so it rides the same GEMM pass).  Both
# are same-process ratios, portable across runner classes.
CAPACITY_RESIDENT_RATIO_MIN = 1.5
CAPACITY_DECODE_RATIO_MIN = 0.9


def _engine_config(**kw) -> EngineConfig:
    """Build an EngineConfig from whatever knobs this engine version
    has (lets the script record baselines on pre-PR checkouts)."""
    names = {f.name for f in dataclasses.fields(EngineConfig)}
    return EngineConfig(**{k: v for k, v in kw.items() if k in names})


def _fresh(protos):
    return [Request(prompt=list(r.prompt), max_new_tokens=r.max_new_tokens)
            for r in protos]


def _lat(stats, prefix: str = "") -> dict:
    """Latency-distribution fields (ms), None-safe on old engines.
    ``prefix`` namespaces them so scenario blocks merged into one
    payload never clobber each other's distributions."""
    out = {}
    for name in ("ttft_p50", "ttft_p95", "itl_p50", "itl_p95"):
        v = getattr(stats, name, None)
        out[f"{prefix}{name}_ms"] = None if v is None else 1e3 * v
    return out


def bench_decode(cfg, params, *, smoke: bool, host_workers: int) -> dict:
    """Offload-heavy serving run; warmup run first so jit compiles and
    the profiler never land in the timed window."""
    n_req = 6 if smoke else 10
    out_len = 8 if smoke else 32
    # tier_rebalance pinned off: this scenario MEASURES the host tier
    # (overlap efficiency = host busy / wall), and rebalancing would
    # deliberately drain host residents into freed device slots —
    # migration behaviour has its own long_context/preemption metrics.
    # prefix_cache pinned off too: the timed pass replays the warmup's
    # prompts, so a cache would turn it into an all-hit replay that no
    # longer measures the prefill+offload mix — cache performance has
    # its own multi_turn_chat scenario
    ecfg = _engine_config(device_slots=2, host_slots=n_req, cache_len=128,
                          page_size=32, host_pool_pages=512,
                          perf_model="analytic", host_workers=host_workers,
                          tier_rebalance=False, prefix_cache=False)
    eng = Engine(cfg, params, ecfg)
    rng = np.random.default_rng(0)
    protos = [make_synthetic_request(rng, prompt_len=12, output_len=out_len,
                                     vocab=cfg.vocab_size)
              for _ in range(n_req)]
    try:
        eng.run(_fresh(protos))                      # warmup: compiles
        it0, wall0 = eng.stats.iterations, eng.stats.wall_time
        host0 = eng._executor.busy_time if eng._executor else 0.0
        dev0, h0 = eng.stats.device_tokens, eng.stats.host_tokens
        ov0 = eng.stats.strategy_counts.get("async_overlap", 0)
        eng.run(_fresh(protos))                      # timed
        iters = eng.stats.iterations - it0
        wall = eng.stats.wall_time - wall0
        host_busy = (eng._executor.busy_time if eng._executor else 0.0) - host0
        toks = (eng.stats.device_tokens + eng.stats.host_tokens) - dev0 - h0
        overlap = eng.stats.strategy_counts.get("async_overlap", 0) - ov0
        resolved_workers = getattr(eng.stats, "host_workers", host_workers)
    finally:
        eng.shutdown()
    return {
        "decode_iters_per_s": iters / max(wall, 1e-9),
        "tokens_per_s": toks / max(wall, 1e-9),
        "host_overlap_efficiency": host_busy / max(wall, 1e-9),
        "iterations": iters,
        "host_tokens": eng.stats.host_tokens - h0,
        "async_overlap_iterations": overlap,
        "host_workers_resolved": resolved_workers,
        # lifecycle counters (rebalance pinned off here, so migrations
        # stay 0 by construction; occupancy is the utilization signal)
        "migrations": getattr(eng.stats, "migrations", 0),
        "preemptions": getattr(eng.stats, "preemptions", 0),
        "deadline_misses": getattr(eng.stats, "deadline_misses", 0),
        "device_occupancy": getattr(eng.stats, "device_occupancy", None),
        "host_occupancy": getattr(eng.stats, "host_occupancy", None),
        **_lat(eng.stats, prefix="decode_"),
    }


def bench_prefill(cfg, params, *, smoke: bool, host_workers: int) -> dict:
    """Admission/prefill over many distinct prompt lengths: compile
    count (bucketing bounds it) and admission latency distribution."""
    n_req = 8 if smoke else 16
    lengths = list(range(3, 3 + n_req))              # all distinct
    # prefix_cache off: retire-time publication at 16 distinct prompt
    # lengths would add one-time copy compiles to the measured wall
    ecfg = _engine_config(device_slots=n_req + 1, host_slots=0,
                          enable_offload=False, cache_len=128,
                          perf_model="analytic", host_workers=host_workers,
                          prefix_cache=False)
    eng = Engine(cfg, params, ecfg)
    rng = np.random.default_rng(1)
    reqs = [Request(prompt=list(rng.integers(0, cfg.vocab_size, n)),
                    max_new_tokens=2) for n in lengths]
    try:
        t0 = time.perf_counter()
        eng.run(reqs)
        wall = time.perf_counter() - t0
    finally:
        eng.shutdown()
    ttfts = [r.first_token_time - r.arrival_time for r in reqs
             if r.first_token_time is not None]
    return {
        "prefill_compilations": getattr(eng.stats, "prefill_compilations",
                                        -1),
        "distinct_prompt_lengths": n_req,
        "admission_latency_ms": 1e3 * float(np.mean(ttfts)) if ttfts else None,
        "prefill_wall_s": wall,
        **_lat(eng.stats),
    }


def bench_hybrid_decode(*, smoke: bool, host_workers: int) -> dict:
    """Hybrid stack (Mamba+attention) on the serving fast paths.

    Two measurements on a reduced Jamba period (7 Mamba + 1 attention
    layer):

      * cold admission over many distinct prompt lengths, fast paths on
        (bucketed + chunked) vs the whole-prompt per-request path the
        engine used to force hybrids onto.  Cold on purpose: per-length
        jit recompiles are a real recurring cost of the whole-prompt
        path (prompt lengths are unbounded in serving), and bounding
        them is half of what bucketing buys.  The whole-prompt engine
        runs second, so shared decode shapes are already compiled for
        it — the bias runs against the fast path.
      * a 100-token hybrid prompt landing while two shorts decode:
        chunked prefill must advance it without stalling their tokens
        (``chunk_co_run_iterations`` counts the co-runs).
    """
    cfg = get_config(HYBRID_ARCH).reduced(layers=8, d_model=128, vocab=256)
    params = init_params(jax.random.PRNGKey(3), cfg)
    n_req = 6 if smoke else 12
    lengths = [5 + 3 * i for i in range(n_req)]          # all distinct
    rng = np.random.default_rng(5)
    protos = [Request(prompt=list(rng.integers(1, cfg.vocab_size, n)),
                      max_new_tokens=2) for n in lengths]
    # prefix_cache off: the admission comparison must price whole
    # prompts on both paths
    base_kw = dict(device_slots=n_req + 1, host_slots=0,
                   enable_offload=False, cache_len=128,
                   perf_model="analytic", host_workers=host_workers,
                   prefix_cache=False)

    def admission(**kw):
        eng = Engine(cfg, params, _engine_config(**base_kw, **kw))
        reqs = _fresh(protos)
        try:
            t0 = time.perf_counter()
            eng.run(reqs)
            wall = time.perf_counter() - t0
        finally:
            eng.shutdown()
        ttfts = [r.first_token_time - r.arrival_time for r in reqs
                 if r.first_token_time is not None]
        return (1e3 * float(np.mean(ttfts)) if ttfts else None, wall,
                getattr(eng.stats, "prefill_compilations", -1))

    lat_fast, wall_fast, compiles = admission(chunk_tokens=8)
    lat_whole, wall_whole, _ = admission(bucketed_prefill=False,
                                         chunk_tokens=0)
    ratio = lat_fast / lat_whole if lat_fast and lat_whole else None

    eng = Engine(cfg, params, _engine_config(
        device_slots=3, cache_len=256, enable_offload=False,
        chunk_tokens=8, perf_model="analytic", host_workers=host_workers,
        prefix_cache=False))
    rng = np.random.default_rng(6)
    short = [Request(prompt=list(rng.integers(1, cfg.vocab_size, 4)),
                     max_new_tokens=64) for _ in range(2)]
    try:
        for r in short:
            eng.submit(r)
        eng.step()                          # prefill the shorts
        eng.step()                          # they decode
        long_req = Request(prompt=list(rng.integers(1, cfg.vocab_size, 100)),
                           max_new_tokens=4)
        eng.submit(long_req)
        before = [len(r.output) for r in short]
        it0 = eng.stats.iterations
        t0 = time.perf_counter()
        while long_req.first_token_time is None \
                and eng.stats.iterations < it0 + 200:
            eng.step()
        long_prefill_wall = time.perf_counter() - t0
        co_run = eng.stats.chunk_co_run_iterations
        gained = sum(len(r.output) - b for r, b in zip(short, before))
    finally:
        eng.shutdown()
    return {
        "hybrid_arch": cfg.name,
        "hybrid_admission_latency_ms": lat_fast,
        "hybrid_admission_latency_whole_prompt_ms": lat_whole,
        "hybrid_admission_latency_ratio": ratio,
        "hybrid_prefill_wall_s": wall_fast,
        "hybrid_prefill_wall_whole_prompt_s": wall_whole,
        "hybrid_prefill_compilations": compiles,
        "hybrid_long_prefill_wall_s": long_prefill_wall,
        "chunk_co_run_iterations": int(co_run),
        "decode_tokens_during_prefill": int(gained),
    }


def bench_multi_turn_chat(cfg, params, *, smoke: bool, host_workers: int,
                          host_kv_dtype: str = "fp32") -> dict:
    """Cross-request prefix cache on the workload it exists for:
    chat sessions sharing a long system prompt, each follow-up turn
    resending the full history.  The same session schedule runs twice
    — prefix cache off (cold) then on (warm) — and the scenario
    reports mean follow-up-turn TTFT both ways plus the cache hit
    rate.  Outputs must be bit-identical between the two runs (the
    cache is exact, not approximate); the CI gate asserts that, a
    nonzero smoke hit rate, and warm TTFT <= CHAT_WARM_TTFT_RATIO_MAX
    of cold."""
    n_sessions = 2 if smoke else 4
    n_turns = 3
    sys_len, user_len = 96, 6
    out_len = 6 if smoke else 10
    rng = np.random.default_rng(11)
    sys_prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, sys_len)]
    # pre-draw every user turn so both runs replay identical sessions
    user_turns = [[[int(t) for t in rng.integers(1, cfg.vocab_size,
                                                 user_len)]
                   for _ in range(n_turns)] for _ in range(n_sessions)]

    def run(prefix_cache: bool) -> dict:
        ecfg = _engine_config(device_slots=4, host_slots=4, cache_len=512,
                              page_size=32, host_pool_pages=512,
                              chunk_tokens=32, perf_model="analytic",
                              host_workers=host_workers,
                              host_kv_dtype=host_kv_dtype,
                              prefix_cache=prefix_cache,
                              prefix_cache_slots=2)
        eng = Engine(cfg, params, ecfg)
        try:
            followup_ttfts, outputs = [], []
            for phase in ("warmup", "timed"):    # warmup amortizes jit
                followup_ttfts, outputs = [], []
                lk0 = getattr(eng.stats, "prefix_lookups", 0)
                hit0 = getattr(eng.stats, "prefix_hits", 0)
                htok0 = getattr(eng.stats, "prefix_hit_tokens", 0)
                for turns in user_turns:
                    history = list(sys_prompt)
                    for k, user in enumerate(turns):
                        req = Request(prompt=history + user,
                                      max_new_tokens=out_len)
                        eng.run([req])
                        if k > 0 and req.first_token_time is not None:
                            followup_ttfts.append(req.first_token_time
                                                  - req.arrival_time)
                        outputs.append(list(req.output))
                        history = list(req.prompt) + list(req.output)
            lookups = getattr(eng.stats, "prefix_lookups", 0) - lk0
            hits = getattr(eng.stats, "prefix_hits", 0) - hit0
            hit_tokens = getattr(eng.stats, "prefix_hit_tokens", 0) - htok0
        finally:
            eng.shutdown()
        return {
            "followup_ttft_ms": (1e3 * float(np.mean(followup_ttfts))
                                 if followup_ttfts else None),
            "lookups": lookups, "hits": hits, "hit_tokens": hit_tokens,
            "outputs": outputs,
        }

    warm = run(prefix_cache=True)
    cold = run(prefix_cache=False)
    ratio = (warm["followup_ttft_ms"] / cold["followup_ttft_ms"]
             if warm["followup_ttft_ms"] and cold["followup_ttft_ms"]
             else None)
    # positional token agreement between the runs: 1.0 when
    # bit-identical.  At fp32 identity is a hard bar in every mode; at
    # int8 it holds at the smoke geometry (all entries fit the device
    # cache rows, whose publication is a bit-exact copy) and the CI
    # gate asserts it there, while at full geometry LRU demotes
    # entries to the quantized host pool and host hits are
    # drift-bounded rather than bit-exact (the documented accuracy
    # contract), so the fraction contextualizes a False flag.
    wf = [t for o in warm["outputs"] for t in o]
    cf = [t for o in cold["outputs"] for t in o]
    matched = sum(1 for a, b in zip(wf, cf) if a == b)
    match_fraction = matched / max(len(cf), 1)
    return {
        "sessions": n_sessions, "turns_per_session": n_turns,
        "system_prompt_len": sys_len, "host_kv_dtype": host_kv_dtype,
        "cold_followup_ttft_ms": cold["followup_ttft_ms"],
        "warm_followup_ttft_ms": warm["followup_ttft_ms"],
        "warm_ttft_ratio": ratio,
        "prefix_lookups": warm["lookups"],
        "prefix_hits": warm["hits"],
        "prefix_hit_tokens": warm["hit_tokens"],
        "hit_rate": warm["hits"] / max(warm["lookups"], 1),
        "tokens_bit_identical_to_no_cache":
            warm["outputs"] == cold["outputs"],
        "tokens_match_fraction": match_fraction,
    }


def bench_long_context(cfg, params, *, host_workers: int) -> dict:
    """The decode stall chunked prefill kills, plus tier rebalancing:
    long prompts arrive while short requests are decoding; one long
    lands on the host tier and must visibly migrate to a device slot
    once the shorts retire (migrations >= 1), with tokens bit-identical
    to a rebalancing-disabled run.  Reports how far decode advanced
    during the prefill window and the chunk co-run count."""
    rng = np.random.default_rng(2)
    short_protos = [make_synthetic_request(rng, prompt_len=8, output_len=24,
                                           vocab=cfg.vocab_size)
                    for _ in range(3)]
    long_protos = [make_synthetic_request(rng, prompt_len=192, output_len=48,
                                          vocab=cfg.vocab_size)
                   for _ in range(2)]

    def run(rebalance: bool) -> dict:
        ecfg = _engine_config(device_slots=4, host_slots=4, cache_len=512,
                              perf_model="analytic",
                              host_workers=host_workers, chunk_tokens=32,
                              tier_rebalance=rebalance,
                              prefix_cache=False)
        eng = Engine(cfg, params, ecfg)
        try:
            short = _fresh(short_protos)
            longs = _fresh(long_protos)
            eng.run(short, max_iterations=3)         # shorts decoding
            before = sum(len(r.output) for r in short)
            it0 = eng.stats.iterations
            t0 = time.perf_counter()
            for r in longs:
                eng.submit(r)
            while any(r.first_token_time is None for r in longs) \
                    and eng.stats.iterations < it0 + 500:
                eng.step()
            prefill_window_s = time.perf_counter() - t0
            window_iters = eng.stats.iterations - it0
            decode_during = sum(len(r.output) for r in short) - before
            while eng.has_work and eng.stats.iterations < it0 + 4000:
                eng.step()
        finally:
            eng.shutdown()
        return {
            "outputs": [list(r.output) for r in short + longs],
            "prefill_window_s": prefill_window_s,
            "prefill_window_iterations": window_iters,
            "decode_tokens_during_prefill": decode_during,
            "chunk_co_run_iterations": getattr(eng.stats,
                                               "chunk_co_run_iterations", 0),
            "prefill_chunks": getattr(eng.stats, "prefill_chunks", 0),
            "migrations": getattr(eng.stats, "migrations", 0),
            "lat": _lat(eng.stats),
        }

    with_rb = run(rebalance=True)
    without_rb = run(rebalance=False)
    return {
        "long_prompt_len": 192,
        "chunk_tokens": 32,
        "prefill_window_s": with_rb["prefill_window_s"],
        "prefill_window_iterations": with_rb["prefill_window_iterations"],
        "decode_tokens_during_prefill":
            with_rb["decode_tokens_during_prefill"],
        "chunk_co_run_iterations": with_rb["chunk_co_run_iterations"],
        "prefill_chunks": with_rb["prefill_chunks"],
        # tier rebalancing: a host-tier long must migrate to a freed
        # device slot, and migration must be bit-invisible in tokens
        "migrations": with_rb["migrations"],
        "tokens_bit_identical_to_no_rebalance":
            with_rb["outputs"] == without_rb["outputs"],
        **with_rb["lat"],
    }


def bench_preemption(cfg, params, *, smoke: bool, host_workers: int) -> dict:
    """SLO-aware preemptive admission: urgent long-context requests
    (priority 1, TTFT deadline) arrive while low-priority jobs hold
    every device slot and the host pool is too small to take the
    urgent prompt.  Without preemption the urgent request queues until
    a device resident finishes; with preemption a low-priority
    resident is demoted to the paged pool and the urgent request takes
    its slot.  Reports urgent TTFT p95 both ways plus deadline misses
    (the CI smoke gate asserts zero with preemption on)."""
    n_low = 2
    out_low = 16 if smoke else 48
    n_urgent = 1 if smoke else 2
    deadline = 60.0
    rng = np.random.default_rng(5)
    low_protos = [make_synthetic_request(rng, prompt_len=12,
                                         output_len=out_low,
                                         vocab=cfg.vocab_size)
                  for _ in range(2 * n_low)]
    urgent_protos = [make_synthetic_request(rng, prompt_len=200,
                                            output_len=8,
                                            vocab=cfg.vocab_size,
                                            deadline=deadline, priority=1)
                     for _ in range(n_urgent)]

    def run(preemption: bool) -> dict:
        # pool sized so a low-priority context fits (ceil(28/32) pages
        # x layers) but the 200-token urgent prompt cannot — the host
        # tier is no escape hatch, preemption is the only fast path
        # prefix_cache off: the timed phase replays the warmup's
        # prompts, and an urgent-prompt cache hit would skip the long
        # prefill this scenario exists to preempt around
        ecfg = _engine_config(device_slots=n_low, host_slots=4,
                              cache_len=256, page_size=32,
                              host_pool_pages=16, perf_model="analytic",
                              host_workers=host_workers,
                              preemption=preemption, prefix_cache=False)
        eng = Engine(cfg, params, ecfg)
        try:
            outputs = []
            for phase in ("warmup", "timed"):   # warmup amortizes jit
                lows = _fresh(low_protos)
                urgents = [Request(prompt=list(r.prompt),
                                   max_new_tokens=r.max_new_tokens,
                                   deadline=r.deadline, priority=r.priority)
                           for r in urgent_protos]
                eng.run(lows[:n_low], max_iterations=4)  # lows decoding
                for r in urgents:
                    eng.submit(r)
                eng.run(lows[n_low:], max_iterations=4000)
                outputs = [list(r.output) for r in lows + urgents]
            ttfts = [r.first_token_time - r.arrival_time for r in urgents
                     if r.first_token_time is not None]
        finally:
            eng.shutdown()
        return {
            "urgent_ttft_p95_ms": (1e3 * float(np.percentile(ttfts, 95))
                                   if ttfts else None),
            "urgent_ttft_mean_ms": (1e3 * float(np.mean(ttfts))
                                    if ttfts else None),
            "preemptions": getattr(eng.stats, "preemptions", 0),
            "deadline_misses": getattr(eng.stats, "deadline_misses", 0),
            "outputs": outputs,
        }

    on = run(preemption=True)
    off = run(preemption=False)
    return {
        "urgent_requests": n_urgent,
        "deadline_s": deadline,
        "urgent_ttft_p95_ms_with_preemption": on["urgent_ttft_p95_ms"],
        "urgent_ttft_p95_ms_without_preemption": off["urgent_ttft_p95_ms"],
        "preemptions": on["preemptions"],
        "deadline_misses": on["deadline_misses"],
        "tokens_bit_identical_to_no_preemption":
            on["outputs"] == off["outputs"],
    }


def bench_fault_soak(cfg, params, *, smoke: bool, host_workers: int,
                     host_kv_dtype: str = "fp32") -> dict:
    """Chaos soak (all modes): a deterministic fault plan — a host
    worker death, a wedged host worker stalled past the watchdog
    deadline, a failed pool allocation and a latency spike — runs
    against the offload-heavy decode mix, then a blocked-swap
    preemption exercises the recompute-from-scratch escape hatch.  The
    CI gate asserts zero lost/hung requests, >= 1 watchdog fallback,
    >= 1 recompute preemption, bit-identical tokens vs a fault-free
    run at the same geometry, and zero leaked pool pages / host
    slots."""
    n_req = 6 if smoke else 10
    out_len = 8 if smoke else 24
    plan = "host_error@2,host_stall@4:1.5,pool_alloc@2,latency_spike@3:0.05"
    rng = np.random.default_rng(9)
    protos = [make_synthetic_request(rng, prompt_len=12, output_len=out_len,
                                     vocab=cfg.vocab_size)
              for _ in range(n_req)]

    # fault-free reference at the SAME geometry (the control that
    # isolates the recovery machinery — device-vs-host tier exactness
    # is tier-1's bar, tests/test_overlap.py)
    ref_eng = Engine(cfg, params, _engine_config(
        device_slots=2, host_slots=n_req, cache_len=128, page_size=32,
        host_pool_pages=512, perf_model="analytic",
        host_workers=host_workers, tier_rebalance=False,
        host_kv_dtype=host_kv_dtype, prefix_cache=False))
    try:
        ref = _fresh(protos)
        ref_eng.run(ref)
    finally:
        ref_eng.shutdown()
    ref_by_prompt = {tuple(r.prompt): list(r.output) for r in ref}

    # chaos soak: offload-heavy, the plan firing mid-run
    eng = Engine(cfg, params, _engine_config(
        device_slots=2, host_slots=n_req, cache_len=128, page_size=32,
        host_pool_pages=512, perf_model="analytic",
        host_workers=host_workers, tier_rebalance=False,
        host_kv_dtype=host_kv_dtype, prefix_cache=False, fault_plan=plan))
    try:
        reqs = _fresh(protos)
        t0 = time.perf_counter()
        eng.run(reqs, max_iterations=20000)     # bounded: a hang shows
        soak_wall = time.perf_counter() - t0    # up as completed < n
        stats = eng.stats
        completed = sum(r.done and not r.failed for r in reqs)
        identical = all(list(r.output) == ref_by_prompt[tuple(r.prompt)]
                        for r in reqs)
        fired = eng._faults.snapshot()["fired"] if eng._faults else {}
        pool = eng._executor.pool if eng._executor else None
        pages_leaked = (pool.pages.shape[1] - pool.num_free) if pool else 0
        host_slots_leaked = len(eng.lc.host_requests)
        degradation = stats.degradation()
    finally:
        eng.shutdown()

    # blocked-swap preemption: the one-page pool cannot take the
    # victim, so the urgent admission must recompute it from scratch
    eng2 = Engine(cfg, params, _engine_config(
        device_slots=1, host_slots=1, cache_len=256, page_size=32,
        host_pool_pages=1, perf_model="analytic",
        host_workers=host_workers, host_kv_dtype=host_kv_dtype,
        prefix_cache=False))
    try:
        resident = Request(prompt=[1] * 12, max_new_tokens=16)
        eng2.submit(resident)
        eng2.step()
        urgent = Request(prompt=[2] * 200, max_new_tokens=4, priority=1)
        eng2.submit(urgent)
        it0 = eng2.stats.iterations
        while eng2.has_work and eng2.stats.iterations < it0 + 4000:
            eng2.step()
        recomputes = eng2.stats.preemption_recomputes
        preempt_done = (resident.done and not resident.failed
                        and urgent.done and not urgent.failed)
    finally:
        eng2.shutdown()

    return {
        "fault_plan": plan,
        "host_kv_dtype": host_kv_dtype,
        "requests": n_req,
        "completed": int(completed),
        "soak_wall_s": soak_wall,
        "host_fallbacks": stats.host_fallbacks,
        "host_breaker_trips": stats.host_breaker_trips,
        "faults_fired": dict(fired),
        "preemption_recomputes": int(recomputes),
        "preemption_requests_completed": bool(preempt_done),
        "tokens_bit_identical_to_fault_free": bool(identical),
        "pool_pages_leaked": int(pages_leaked),
        "host_slots_leaked": int(host_slots_leaked),
        "degradation_after_soak": degradation,
    }


def bench_host_capacity(cfg, params, *, smoke: bool,
                        host_workers: int) -> dict:
    """The quantized host tier's headline claim: at a fixed host RAM
    budget, how many resident requests fit before admission sheds, at
    fp32 vs int8 page storage?  Capacity is measured at the pool level
    (size each pool to the same byte budget, admit fixed-context
    requests until ``can_admit`` says no), migration cost as the wall
    time to gather a full context out of the pool (the host->device
    promotion payload, dequant included), and decode cost by rerunning
    the offload-heavy decode mix at each dtype.  The CI gate asserts
    resident_ratio >= CAPACITY_RESIDENT_RATIO_MIN and decode_ratio >=
    CAPACITY_DECODE_RATIO_MIN."""
    from repro.models.kv_cache import PagedKVPool

    ctx = 64
    page_size = 32
    budget_bytes = 4 << 20                       # 4 MiB of host KV RAM

    def pool_side(dt: str) -> dict:
        probe = PagedKVPool(1, page_size, cfg.num_attn_layers,
                            cfg.num_kv_heads, cfg.resolved_head_dim,
                            host_kv_dtype=dt)
        pb = probe.page_bytes
        num_pages = max(1, budget_bytes // pb)
        pool = PagedKVPool(num_pages, page_size, cfg.num_attn_layers,
                           cfg.num_kv_heads, cfg.resolved_head_dim,
                           host_kv_dtype=dt)
        residents = 0
        while pool.can_admit(ctx):
            pool.allocate(residents, ctx)
            residents += 1
        # migration payload: fill one resident with real rows, then
        # time gathering its full context across every layer (what a
        # host->device promotion materializes)
        rng = np.random.default_rng(0)
        rows = rng.standard_normal(
            (ctx, cfg.num_kv_heads, cfg.resolved_head_dim)).astype(
                np.float32)
        for layer in range(pool.num_layers):
            pool.write_prompt(0, layer, rows, rows,
                              advance=layer == pool.num_layers - 1)
        best = float("inf")
        for _ in range(3):                       # best-of-3 damps noise
            t0 = time.perf_counter()
            for layer in range(pool.num_layers):
                pool.gather(0, layer)
            best = min(best, time.perf_counter() - t0)
        return {"page_bytes": pb, "pool_pages": num_pages,
                "resident_requests": residents,
                "migration_gather_ms": 1e3 * best}

    n_req, out_len = (4, 6) if smoke else (8, 16)
    rng = np.random.default_rng(0)
    protos = [make_synthetic_request(rng, prompt_len=12, output_len=out_len,
                                     vocab=cfg.vocab_size)
              for _ in range(n_req)]

    def decode_engine(dt: str) -> Engine:
        return Engine(cfg, params, _engine_config(
            device_slots=2, host_slots=n_req, cache_len=128,
            page_size=page_size, host_pool_pages=512,
            perf_model="analytic", host_workers=host_workers,
            tier_rebalance=False, prefix_cache=False, host_kv_dtype=dt))

    def timed_pass(eng: Engine) -> float:
        it0, wall0 = eng.stats.iterations, eng.stats.wall_time
        eng.run(_fresh(protos))
        iters = eng.stats.iterations - it0
        wall = eng.stats.wall_time - wall0
        return iters / max(wall, 1e-9)

    fp32 = pool_side("fp32")
    int8 = pool_side("int8")
    # decode at each dtype: the timed passes are interleaved (fp32 then
    # int8, three rounds, best-of) so transient container load lands on
    # both dtypes instead of skewing the ratio one way
    engs = {dt: decode_engine(dt) for dt in ("fp32", "int8")}
    best = {dt: 0.0 for dt in engs}
    try:
        for eng in engs.values():
            eng.run(_fresh(protos))              # warmup: compiles
        for _ in range(3):
            for dt, eng in engs.items():
                best[dt] = max(best[dt], timed_pass(eng))
    finally:
        for eng in engs.values():
            eng.shutdown()
    fp32_iters, int8_iters = best["fp32"], best["int8"]
    return {
        "context_tokens": ctx,
        "host_ram_budget_bytes": budget_bytes,
        "fp32": fp32,
        "int8": int8,
        "resident_ratio": (int8["resident_requests"]
                           / max(fp32["resident_requests"], 1)),
        "fp32_decode_iters_per_s": fp32_iters,
        "int8_decode_iters_per_s": int8_iters,
        "decode_ratio": int8_iters / max(fp32_iters, 1e-9),
        "migration_gather_ratio": (int8["migration_gather_ms"]
                                   / max(fp32["migration_gather_ms"],
                                         1e-9)),
    }


def bench_asym_heavy(cfg, params, *, host_workers: int) -> dict:
    """1 device slot vs a large host cohort at long context — the
    regime where Algorithm 1 leans hybrid.  Reports the strategy mix."""
    n_host = 8
    # rebalance pinned off for the same reason as bench_decode: this
    # scenario measures the hybrid strategy mix at a fixed cohort
    ecfg = _engine_config(device_slots=1, host_slots=n_host, cache_len=256,
                          page_size=32, host_pool_pages=1024,
                          perf_model="analytic", host_workers=host_workers,
                          tier_rebalance=False, prefix_cache=False)
    eng = Engine(cfg, params, ecfg)
    rng = np.random.default_rng(3)
    reqs = [make_synthetic_request(rng, prompt_len=96, output_len=12,
                                   vocab=cfg.vocab_size)
            for _ in range(n_host + 1)]
    try:
        t0 = time.perf_counter()
        stats = eng.run(reqs)
        wall = time.perf_counter() - t0
    finally:
        eng.shutdown()
    return {
        "strategy_counts": dict(stats.strategy_counts),
        "asym_pipeline_iterations": stats.strategy_counts.get(
            "asym_pipeline", 0),
        "host_tokens": stats.host_tokens,
        "tokens_per_s": (stats.device_tokens + stats.host_tokens)
        / max(wall, 1e-9),
        **_lat(stats),
    }


def bench_arrival_sweep(cfg, params, *, host_workers: int) -> dict:
    """Open-loop Poisson replay at increasing arrival rates: TTFT
    percentiles under real arrival pressure."""
    from repro.serving.api import InferenceServer, ServerConfig
    sweep = {}
    for rate in (4.0, 16.0):
        scfg = ServerConfig(device_slots=2, host_slots=6, cache_len=128,
                            perf_model="analytic",
                            host_workers=host_workers, prefix_cache=False,
                            num_requests=10, arrival_rate=rate,
                            prompt_len=12, output_len=12)
        server = InferenceServer(cfg, params, scfg)
        try:
            reqs = scfg.build_requests(vocab=cfg.vocab_size)
            server.serve(reqs, realtime=True)
            stats = server.stats
            sweep[f"rate_{rate:g}"] = {
                "tokens_per_s": stats.throughput,
                **_lat(stats),
            }
        finally:
            server.shutdown()
    return sweep


def bench_http_serving(cfg, params, *, smoke: bool, host_workers: int) -> dict:
    """Serving through the HTTP/SSE gateway over real sockets: a
    closed-loop concurrency sweep (TTFT/ITL percentiles per level), an
    open-loop Poisson sweep (full mode), and an overload burst against
    a tiny bounded queue (429/503 shed rate at the edge).  Smoke mode
    also reports the pass/fail flags the CI gateway gate asserts."""
    import threading

    from repro.serving.api import InferenceServer, ServerConfig
    from repro.serving.gateway import EngineReplicaPool, serve_in_thread
    from repro.serving.gateway.client import get_json, get_text, sse_chat

    out_len = 6 if smoke else 16
    scfg = ServerConfig(device_slots=2, host_slots=4, cache_len=128,
                        perf_model="analytic", host_workers=host_workers,
                        output_len=out_len)

    def factory():
        return InferenceServer(cfg, params, dataclasses.replace(scfg))

    rng = np.random.default_rng(7)

    def burst(port, *, clients, per_client, rate=None):
        """closed loop (each client fires sequentially), or open loop
        when ``rate`` is set (exponential gaps across all clients)."""
        results, lock = [], threading.Lock()
        gaps = (rng.exponential(1.0 / rate, clients * per_client)
                if rate else None)

        def client(ci):
            for k in range(per_client):
                if gaps is not None:
                    time.sleep(float(gaps[ci * per_client + k]))
                prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 8)]
                r = sse_chat("127.0.0.1", port, prompt,
                             max_new_tokens=out_len)
                with lock:
                    results.append(r)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300.0)
        wall = time.perf_counter() - t0
        return results, wall

    def summarize(results, wall):
        ok = [r for r in results if r["status"] == 200 and not r["error"]]
        shed = [r for r in results if r["status"] in (429, 503)]
        ttfts = [r["ttft_s"] for r in ok if r["ttft_s"] is not None]
        itls = [g for r in ok for g in r["itl_s"]]
        toks = sum(len(r["tokens"]) for r in ok)
        pct = lambda xs, q: (1e3 * float(np.percentile(xs, q))  # noqa: E731
                             if xs else None)
        return {
            "requests": len(results), "completed": len(ok),
            "shed": len(shed),
            "shed_rate": len(shed) / max(len(results), 1),
            "tokens_per_s": toks / max(wall, 1e-9),
            "ttft_p50_ms": pct(ttfts, 50), "ttft_p95_ms": pct(ttfts, 95),
            "itl_p50_ms": pct(itls, 50), "itl_p95_ms": pct(itls, 95),
        }

    out = {"replicas": 2, "output_len": out_len}
    pool = EngineReplicaPool(factory, replicas=2)
    try:
        gw, stop = serve_in_thread(pool, port=0, max_queue_depth=64)
        try:
            # closed-loop sweep: C concurrent clients, R requests each
            levels = (1, 4) if smoke else (1, 4, 8)
            per_client = 2 if smoke else 3
            closed = {}
            for c in levels:
                results, wall = burst(gw.port, clients=c,
                                      per_client=per_client)
                closed[f"concurrency_{c}"] = summarize(results, wall)
            out["closed_loop"] = closed
            streams_ok = all(s["completed"] == s["requests"]
                             for s in closed.values())
            if not smoke:
                # open-loop Poisson over the same sockets
                open_loop = {}
                for rate in (4.0, 16.0):
                    results, wall = burst(gw.port, clients=4, per_client=3,
                                          rate=rate)
                    open_loop[f"rate_{rate:g}"] = summarize(results, wall)
                out["open_loop"] = open_loop
            health = get_json("127.0.0.1", gw.port, "/health")
            metrics = get_text("127.0.0.1", gw.port, "/metrics")
            health_ok = (health["status"] == 200
                         and health["body"]["status"] == "ok")
            metrics_ok = (metrics["status"] == 200
                          and "apex_replica_up" in metrics["body"]
                          and "apex_engine_iterations_total"
                          in metrics["body"])
        finally:
            stop()

        # overload burst: bounded queue of 1 — the depth check admits
        # one stream and sheds the concurrent rest with 503 at the edge
        gw2, stop2 = serve_in_thread(pool, port=0, max_queue_depth=1)
        try:
            results, wall = burst(gw2.port, clients=8, per_client=1)
            out["overload"] = {"max_queue_depth": 1,
                               **summarize(results, wall)}
        finally:
            stop2()
    finally:
        pool.shutdown()
    out["flags"] = {
        "sse_streams_nonempty": streams_ok,
        "health_ok": health_ok,
        "metrics_parseable": metrics_ok,
        "overload_shed": out["overload"]["shed"] > 0,
    }
    return out


def check_regression(decode: dict, preempt: dict, http: dict,
                     hybrid: dict, chat: dict, soak: dict,
                     capacity: dict, chat_int8: dict,
                     soak_int8: dict) -> int:
    """CI gate: fail on a >REGRESSION_TOLERANCE drop vs the committed
    smoke baseline on decode throughput or overlap efficiency, on any
    deadline miss in the smoke preemption sub-scenario (urgent requests
    carry a generous TTFT SLO that preemption must keep), on the
    hybrid fast-path guarantees (admission ratio, chunk co-run), on
    the prefix-cache guarantees (nonzero hit rate, warm follow-up TTFT
    ratio, bit-identical tokens), or on the fault-soak guarantees
    (zero lost requests, fallback + recompute engaged, bit-identical
    under chaos, zero leaked pool pages)."""
    failures = []
    for key, base in SMOKE_BASELINE.items():
        got = decode.get(key)
        floor = base * (1.0 - REGRESSION_TOLERANCE)
        if got is None or got < floor:
            failures.append(f"{key}: {got} < {floor:.3g} "
                            f"(baseline {base}, tol {REGRESSION_TOLERANCE})")
    misses = preempt.get("deadline_misses")
    if misses != 0:
        failures.append(f"deadline_misses: {misses} != 0 in the smoke "
                        f"preemption sub-scenario")
    if preempt.get("preemptions", 0) < 1:
        failures.append("preemptions: expected >= 1 in the smoke "
                        "preemption sub-scenario")
    for flag, ok in (http.get("flags") or {}).items():
        if not ok:
            failures.append(f"http_serving flag {flag} is false")
    ratio = hybrid.get("hybrid_admission_latency_ratio")
    if ratio is None or ratio > HYBRID_ADMISSION_RATIO_MAX:
        failures.append(f"hybrid_admission_latency_ratio: {ratio} > "
                        f"{HYBRID_ADMISSION_RATIO_MAX} (fast paths must "
                        f"beat the whole-prompt hybrid path)")
    if hybrid.get("chunk_co_run_iterations", 0) < 1:
        failures.append("chunk_co_run_iterations: expected >= 1 in the "
                        "hybrid_decode sub-scenario (decode must co-run "
                        "with hybrid chunked prefill)")
    if not chat.get("hit_rate"):
        failures.append(f"multi_turn_chat hit_rate: "
                        f"{chat.get('hit_rate')} — the smoke chat "
                        f"workload must hit the prefix cache")
    warm_ratio = chat.get("warm_ttft_ratio")
    if warm_ratio is None or warm_ratio > CHAT_WARM_TTFT_RATIO_MAX:
        failures.append(f"multi_turn_chat warm_ttft_ratio: {warm_ratio} "
                        f"> {CHAT_WARM_TTFT_RATIO_MAX} (cached history "
                        f"must cut follow-up TTFT)")
    if not chat.get("tokens_bit_identical_to_no_cache"):
        failures.append("multi_turn_chat tokens_bit_identical_to_no_cache "
                        "is false (the prefix cache must be exact)")
    if soak.get("completed") != soak.get("requests"):
        failures.append(f"fault_soak: {soak.get('completed')}/"
                        f"{soak.get('requests')} requests completed — a "
                        f"lost or hung request under injected faults")
    if soak.get("host_fallbacks", 0) < 1:
        failures.append("fault_soak host_fallbacks: expected >= 1 (the "
                        "watchdog must absorb the injected host faults)")
    if soak.get("preemption_recomputes", 0) < 1:
        failures.append("fault_soak preemption_recomputes: expected >= 1 "
                        "(the blocked swap must recompute its victim)")
    if not soak.get("preemption_requests_completed"):
        failures.append("fault_soak: the recompute-preemption requests "
                        "did not all complete cleanly")
    if not soak.get("tokens_bit_identical_to_fault_free"):
        failures.append("fault_soak tokens_bit_identical_to_fault_free is "
                        "false (recovery must be exact)")
    if soak.get("pool_pages_leaked", 0) or soak.get("host_slots_leaked", 0):
        failures.append(f"fault_soak leaks: "
                        f"{soak.get('pool_pages_leaked')} pool pages, "
                        f"{soak.get('host_slots_leaked')} host slots")
    rr = capacity.get("resident_ratio")
    if rr is None or rr < CAPACITY_RESIDENT_RATIO_MIN:
        failures.append(f"host_capacity resident_ratio: {rr} < "
                        f"{CAPACITY_RESIDENT_RATIO_MIN} (int8 must hold "
                        f"proportionally more residents at equal RAM)")
    dr = capacity.get("decode_ratio")
    if dr is None or dr < CAPACITY_DECODE_RATIO_MIN:
        failures.append(f"host_capacity decode_ratio: {dr} < "
                        f"{CAPACITY_DECODE_RATIO_MIN} (fused dequant must "
                        f"keep int8 decode within 10% of fp32)")
    # the quantized reruns hold the same exactness bars as fp32: the
    # prefix cache stays warm==cold bit-identical and the chaos plan
    # recovers bit-identically with zero leaks (a scale-table leak
    # would show up here as leaked pool pages)
    if not chat_int8.get("hit_rate"):
        failures.append("multi_turn_chat[int8] hit_rate is zero")
    if not chat_int8.get("tokens_bit_identical_to_no_cache"):
        failures.append("multi_turn_chat[int8] warm run is not "
                        "bit-identical to its cache-disabled run")
    if soak_int8.get("completed") != soak_int8.get("requests"):
        failures.append(f"fault_soak[int8]: {soak_int8.get('completed')}/"
                        f"{soak_int8.get('requests')} requests completed")
    if not soak_int8.get("tokens_bit_identical_to_fault_free"):
        failures.append("fault_soak[int8] is not bit-identical to its "
                        "fault-free int8 reference")
    if soak_int8.get("pool_pages_leaked", 0) \
            or soak_int8.get("host_slots_leaked", 0):
        failures.append(f"fault_soak[int8] leaks: "
                        f"{soak_int8.get('pool_pages_leaked')} pool pages, "
                        f"{soak_int8.get('host_slots_leaked')} host slots")
    if failures:
        print("REGRESSION GATE FAILED:")
        for f in failures:
            print(f"  {f}")
        return 1
    print(f"regression gate OK (tolerance {REGRESSION_TOLERANCE:.0%}): "
          + ", ".join(f"{k}={decode[k]:.3g} vs baseline {v}"
                      for k, v in SMOKE_BASELINE.items())
          + f"; preemption deadline_misses=0 "
            f"(preemptions={preempt.get('preemptions')}); "
          + "http_serving flags all green; "
          + f"hybrid admission ratio {ratio:.2f} <= "
            f"{HYBRID_ADMISSION_RATIO_MAX} "
            f"({hybrid['chunk_co_run_iterations']} co-run iterations); "
          + f"chat warm/cold TTFT {warm_ratio:.2f} <= "
            f"{CHAT_WARM_TTFT_RATIO_MAX} at hit rate "
            f"{chat['hit_rate']:.0%} (bit-identical)")
    return 0


def main() -> None:
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small/fast variant for CI (decode + prefill "
                         "scenarios only)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero on a >30%% drop vs the committed "
                         "smoke baseline (CI regression gate; requires "
                         "--smoke — the baseline is smoke-mode)")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: BENCH_engine.json at "
                         "the repo root)")
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--host-workers", type=int, default=0,
                    help="HostExecutor worker threads (0 = auto)")
    ap.add_argument("--record-baseline", action="store_true",
                    help="print the metrics dict for embedding as a "
                         "pre-change baseline instead of writing JSON")
    args = ap.parse_args()
    if args.check and not args.smoke:
        ap.error("--check compares against the smoke-mode baseline; "
                 "run it with --smoke")

    cfg = get_config(args.arch).reduced(layers=4, d_model=128, vocab=256)
    params = init_params(jax.random.PRNGKey(0), cfg)

    decode = bench_decode(cfg, params, smoke=args.smoke,
                          host_workers=args.host_workers)
    prefill = bench_prefill(cfg, params, smoke=args.smoke,
                            host_workers=args.host_workers)
    # the preemption sub-scenario runs in smoke mode too: the CI gate
    # asserts zero deadline misses (and >= 1 preemption) there
    preempt = bench_preemption(cfg, params, smoke=args.smoke,
                               host_workers=args.host_workers)
    # gateway serving over real sockets runs in smoke mode too: the CI
    # gate asserts its pass/fail flags (SSE non-empty, health green,
    # metrics parseable, overload sheds at the edge)
    http = bench_http_serving(cfg, params, smoke=args.smoke,
                              host_workers=args.host_workers)
    # hybrid stacks ride the same fast paths since the length-masked
    # scan landed: the gate holds chunked+bucketed hybrid admission to
    # <= HYBRID_ADMISSION_RATIO_MAX of the old whole-prompt path and
    # requires decode to co-run with hybrid chunked prefill
    hybrid = bench_hybrid_decode(smoke=args.smoke,
                                 host_workers=args.host_workers)
    # the chat sub-scenario runs in smoke mode too: the CI gate asserts
    # a nonzero prefix-cache hit rate, the warm-TTFT ratio, and tokens
    # bit-identical to a cache-disabled run
    chat = bench_multi_turn_chat(cfg, params, smoke=args.smoke,
                                 host_workers=args.host_workers)
    # the fault-soak sub-scenario runs in smoke mode too: the CI gate
    # asserts every request survives the chaos plan bit-identical to a
    # fault-free run, the blocked swap recomputes its victim, and the
    # engine leaks no pool pages or host slots
    soak = bench_fault_soak(cfg, params, smoke=args.smoke,
                            host_workers=args.host_workers)
    # the quantized host tier runs in smoke mode too: the CI gate
    # asserts int8 packs >= 1.5x the residents at equal host RAM with
    # decode within 10% of fp32, and that the chat + soak exactness
    # bars hold unchanged when the pool stores int8
    capacity = bench_host_capacity(cfg, params, smoke=args.smoke,
                                   host_workers=args.host_workers)
    chat_int8 = bench_multi_turn_chat(cfg, params, smoke=args.smoke,
                                      host_workers=args.host_workers,
                                      host_kv_dtype="int8")
    soak_int8 = bench_fault_soak(cfg, params, smoke=args.smoke,
                                 host_workers=args.host_workers,
                                 host_kv_dtype="int8")
    scenarios = {"preemption": preempt, "http_serving": http,
                 "hybrid_decode": hybrid, "multi_turn_chat": chat,
                 "fault_soak": soak, "host_capacity": capacity,
                 "multi_turn_chat_int8": chat_int8,
                 "fault_soak_int8": soak_int8}
    if not args.smoke:
        scenarios["long_context"] = bench_long_context(
            cfg, params, host_workers=args.host_workers)
        scenarios["asym_heavy"] = bench_asym_heavy(
            cfg, params, host_workers=args.host_workers)
        scenarios["arrival_sweep"] = bench_arrival_sweep(
            cfg, params, host_workers=args.host_workers)

    payload = {
        "bench": "engine_hot_path",
        "mode": "smoke" if args.smoke else "full",
        "arch": cfg.name,
        "backend": jax.default_backend(),
        "host_workers": decode.get("host_workers_resolved",
                                   args.host_workers),
        **decode,
        **prefill,
        "baseline": PRE_PR_BASELINE,
        "pr3_baseline": PR3_BASELINE,
    }
    if scenarios:
        payload["scenarios"] = scenarios
    if not args.smoke:
        payload["speedup_vs_baseline"] = (
            decode["decode_iters_per_s"]
            / PRE_PR_BASELINE["decode_iters_per_s"])
        payload["decode_iters_vs_pr3"] = (
            decode["decode_iters_per_s"]
            / PR3_BASELINE["decode_iters_per_s"])
        if prefill["admission_latency_ms"]:
            payload["admission_latency_vs_pr3"] = (
                prefill["admission_latency_ms"]
                / PR3_BASELINE["admission_latency_ms"])
    if args.record_baseline:
        print(json.dumps({k: decode[k] for k in
                          ("decode_iters_per_s", "tokens_per_s",
                           "host_overlap_efficiency")}
                         | {"admission_latency_ms":
                            prefill["admission_latency_ms"]}, indent=1))
        return
    out = args.out or os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_engine.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {out}")
    for k in ("decode_iters_per_s", "tokens_per_s",
              "host_overlap_efficiency", "prefill_compilations",
              "admission_latency_ms", "ttft_p50_ms", "ttft_p95_ms"):
        print(f"  {k}: {payload.get(k)}")
    if "speedup_vs_baseline" in payload:
        print(f"  speedup_vs_baseline: "
              f"{payload['speedup_vs_baseline']:.2f}x")
    if "decode_iters_vs_pr3" in payload:
        print(f"  decode_iters_vs_pr3: {payload['decode_iters_vs_pr3']:.2f}x"
              f" (1.0 = PR-3; within noise expected)")
    if "admission_latency_vs_pr3" in payload:
        print(f"  admission_latency_vs_pr3: "
              f"{payload['admission_latency_vs_pr3']:.2f}x (lower is better)")
    if scenarios.get("long_context"):
        lc = scenarios["long_context"]
        print(f"  long_context: {lc['decode_tokens_during_prefill']} decode "
              f"tokens during prefill, "
              f"{lc['chunk_co_run_iterations']} co-run iterations, "
              f"{lc['migrations']} migrations (bit-identical: "
              f"{lc['tokens_bit_identical_to_no_rebalance']})")
    def _ms(v):
        return "n/a" if v is None else f"{v:.0f}ms"
    print(f"  preemption: urgent TTFT p95 "
          f"{_ms(preempt['urgent_ttft_p95_ms_with_preemption'])} with vs "
          f"{_ms(preempt['urgent_ttft_p95_ms_without_preemption'])} "
          f"without ({preempt['preemptions']} preemptions, "
          f"{preempt['deadline_misses']} deadline misses)")
    peak = sorted(http["closed_loop"])[-1]
    hs = http["closed_loop"][peak]
    print(f"  http_serving: {hs['completed']}/{hs['requests']} streams at "
          f"{peak}, TTFT p95 {_ms(hs['ttft_p95_ms'])}, overload shed rate "
          f"{http['overload']['shed_rate']:.0%}, flags {http['flags']}")
    ratio = hybrid["hybrid_admission_latency_ratio"]
    print(f"  hybrid_decode: admission "
          f"{_ms(hybrid['hybrid_admission_latency_ms'])} fast-path vs "
          f"{_ms(hybrid['hybrid_admission_latency_whole_prompt_ms'])} "
          f"whole-prompt (ratio "
          f"{'n/a' if ratio is None else f'{ratio:.2f}'}), "
          f"{hybrid['chunk_co_run_iterations']} co-run iterations, "
          f"{hybrid['decode_tokens_during_prefill']} decode tokens during "
          f"the long prefill")
    wr = chat["warm_ttft_ratio"]
    print(f"  multi_turn_chat: follow-up TTFT "
          f"{_ms(chat['warm_followup_ttft_ms'])} warm vs "
          f"{_ms(chat['cold_followup_ttft_ms'])} cold (ratio "
          f"{'n/a' if wr is None else f'{wr:.2f}'}), hit rate "
          f"{chat['hit_rate']:.0%} ({chat['prefix_hit_tokens']} prompt "
          f"tokens served from cache, bit-identical: "
          f"{chat['tokens_bit_identical_to_no_cache']})")
    print(f"  fault_soak: {soak['completed']}/{soak['requests']} survived "
          f"'{soak['fault_plan']}' ({soak['host_fallbacks']} fallbacks, "
          f"{soak['host_breaker_trips']} breaker trips, "
          f"{soak['preemption_recomputes']} recomputes, bit-identical: "
          f"{soak['tokens_bit_identical_to_fault_free']}, leaks: "
          f"{soak['pool_pages_leaked']} pages / "
          f"{soak['host_slots_leaked']} slots, degradation "
          f"'{soak['degradation_after_soak']}')")
    print(f"  host_capacity: {capacity['int8']['resident_requests']} int8 "
          f"vs {capacity['fp32']['resident_requests']} fp32 residents in "
          f"{capacity['host_ram_budget_bytes'] >> 20} MiB "
          f"({capacity['resident_ratio']:.2f}x), decode ratio "
          f"{capacity['decode_ratio']:.2f}, migration gather ratio "
          f"{capacity['migration_gather_ratio']:.2f}")
    print(f"  int8 reruns: chat bit-identical "
          f"{chat_int8['tokens_bit_identical_to_no_cache']} (hit rate "
          f"{chat_int8['hit_rate']:.0%}, token match "
          f"{chat_int8['tokens_match_fraction']:.0%}), soak "
          f"{soak_int8['completed']}/{soak_int8['requests']} bit-identical "
          f"{soak_int8['tokens_bit_identical_to_fault_free']}, leaks "
          f"{soak_int8['pool_pages_leaked']} pages / "
          f"{soak_int8['host_slots_leaked']} slots")
    if args.check:
        sys.exit(check_regression(decode, preempt, http, hybrid, chat,
                                  soak, capacity, chat_int8, soak_int8))


if __name__ == "__main__":
    main()
