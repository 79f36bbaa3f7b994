"""One run of one cell: set-up, the measured window, the correctness
check, and the result line.  ``bench/run.py`` is the command; this module
holds the parts that tests and the calibration script drive too.

The window drives ``InferenceServer.step()`` from the benchmark's own
loop, a closed loop: a fixed number of streams, each replaced by the next
request when it finishes.  After every step the loop reads each in-flight
request's output length on the host clock, and the tier that holds it.
Spans around the calls (``bench.step``, ``bench.client``) go into the
profiler's trace when one is taken.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import flops
from bench.registry import ROOT, Registry
from bench.traffic import Spec, Traffic
from bench.window import StepRecord, Window

sys.path.insert(0, str(ROOT / "src"))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a step longer than this is reported with what happened in it
LONG_STEP_S = 0.25
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
STRATEGIES = ("gpu_only", "async_overlap", "asym_pipeline")
# the warm-up waves: prompt tokens of each request, and output tokens of
# a device-tier and a host-tier request
WARM_PROMPT = 16
WARM_DEVICE_TOKENS = 32
WARM_TOKENS = 3
SETUP_LIMIT_S = 900.0


class CompileCounter:
    """Programs JAX compiled or loaded from its persistent cache, through
    its monitoring hooks: ``requests`` counts both, ``loads`` the loads;
    ``programs`` holds (end time, seconds, name) of each."""

    def __init__(self) -> None:
        import jax

        self._lock = threading.Lock()
        self.requests = 0
        self.loads = 0
        self.programs: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.requests += 1
                self.programs.append((time.perf_counter(), duration,
                                      str(kw.get("fun_name", "?"))))

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.loads += 1

    def mark(self):
        with self._lock:
            return self.requests, self.loads


def model_config(c: Dict):
    """The serving engine's ``ModelConfig`` for a configuration file."""
    from repro.models.config import BlockKind, FFNKind, ModelConfig

    m = c["model"]
    if m.get("tie_word_embeddings") or m.get("bias"):
        raise ValueError(f"{c['name']}: tied embeddings or biases are not "
                         f"served by this harness")
    return ModelConfig(
        name=c["name"], num_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        block_pattern=(BlockKind.ATTN,), ffn_kind=FFNKind.DENSE,
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        max_seq_len=m["max_position_embeddings"])


def server_config(c: Dict, mcfg):
    """``ServerConfig`` from the configuration's engine geometry; the host
    pool holds every host slot at full ``cache_len``."""
    from repro.serving import ServerConfig

    e = dict(c["engine"])
    pages = (e["host_slots"] * -(-e["cache_len"] // e.get("page_size", 32))
             * mcfg.num_attn_layers)
    return ServerConfig(host_pool_pages=pages, perf_model="analytic", **e)


def counters(stats) -> Dict[str, float]:
    """Every numeric ``EngineStats`` field, and the Algorithm-1 strategy
    counts as ``strategy.<name>``: what metric readers can take deltas of."""
    out = {f.name: float(getattr(stats, f.name))
           for f in dataclasses.fields(stats)
           if isinstance(getattr(stats, f.name), (int, float))
           and not isinstance(getattr(stats, f.name), bool)}
    for k in STRATEGIES:
        out[f"strategy.{k}"] = float(stats.strategy_counts.get(k, 0))
    return out


class Client:
    """The benchmark's side of the server: submits requests and watches
    their outputs grow."""

    def __init__(self, server, traffic: Traffic) -> None:
        from jax.profiler import TraceAnnotation

        self.server = server
        self.traffic = traffic
        self.span = TraceAnnotation
        self.reqs: Dict[int, object] = {}      # every request sent
        self.active: Dict[int, object] = {}    # not yet finished
        self.stamps: Dict[int, List[float]] = {}
        # per token, the tier that made it: "prefill" for the first token
        # (the chunked prefill's), then the request's tier as its step ends
        self.token_tiers: Dict[int, List[str]] = {}
        self.recording: Optional[List[StepRecord]] = None
        self._prompt_seen = 0.0
        # steps longer than LONG_STEP_S while recording: (start, seconds,
        # counters that changed, seconds of garbage collection in it)
        self.long_steps: List[tuple] = []
        self._gc_s = 0.0
        self._gc_t = 0.0

    def _gc_clock(self, phase: str, _info) -> None:
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self._gc_s += time.perf_counter() - self._gc_t

    # --- sending ---------------------------------------------------------
    def send(self, spec: Spec):
        from repro.serving.request import Request

        r = Request(prompt=spec.prompt, max_new_tokens=spec.max_new_tokens)
        self.server.submit(r)
        self.reqs[r.request_id] = r
        self.active[r.request_id] = r
        self.stamps[r.request_id] = []
        self.token_tiers[r.request_id] = []
        return r

    def host_flags(self, rid: int) -> List[bool]:
        """One flag per served token of a request: made by the host tier."""
        return [t == "host" for t in self.token_tiers[rid]]

    def refill(self) -> None:
        """Closed loop: one new request for each stream that finished."""
        while len(self.active) < self.traffic.streams:
            spec = self.traffic.next()
            if spec is None:
                raise RuntimeError("closed loop ran out of requests; raise "
                                   "the mix's pool")
            self.send(spec)

    # --- one step --------------------------------------------------------
    def step(self) -> None:
        from repro.serving.request import Phase

        stats = self.server.engine.stats
        before = counters(stats) if self.recording is not None else None
        gc0, t_start = self._gc_s, time.perf_counter()
        with self.span("bench.step"):
            self.server.step()
        if before is not None and time.perf_counter() - t_start > LONG_STEP_S:
            after = counters(stats)
            self.long_steps.append((
                t_start, time.perf_counter() - t_start,
                {k: after[k] - before[k] for k in after
                 if after[k] != before[k] and k != "wall_time"},
                self._gc_s - gc0))
        with self.span("bench.client"):
            now = time.perf_counter()
            contexts: List[int] = []
            host_new = 0
            for rid, r in list(self.active.items()):
                seen = self.stamps[rid]
                n = len(r.output)
                if n > len(seen):
                    tiers = self.token_tiers[rid]
                    if not seen:
                        tiers.append("prefill")
                    elif r.tier == "device":
                        contexts.append(r.prompt_len + n - 2)
                    else:
                        host_new += n - len(seen)
                    tiers.extend([r.tier or "unknown"] * (n - len(seen)
                                                       - (not seen)))
                    seen.extend([now] * (n - len(seen)))
                if r.phase is Phase.FINISHED:
                    del self.active[rid]
            prompt = stats.chunked_prefill_tokens
            if self.recording is not None:
                self.recording.append(StepRecord(
                    t_end=now, device_contexts=contexts, host_tokens=host_new,
                    prompt_tokens=int(prompt - self._prompt_seen)))
            self._prompt_seen = prompt

    def drive_until(self, done: Callable[[], bool], limit: float, *,
                    refill: bool = True) -> None:
        while not done():
            if time.perf_counter() > limit:
                raise TimeoutError("the server made too little progress")
            if refill:
                self.refill()
            if self.server.engine.has_work:
                self.step()


def warm_up(client: Client, seed: int, engine: Dict, vocab: int) -> None:
    """Compile and run every program this cell's traffic uses, in waves
    of short requests: the device rows prefill alone (the chunk program)
    and decode; one host row prefills beside them (decode fused with a
    chunk); the other host rows prefill beside its host-tier decode
    (decode, host cohort and chunk in one program); then all finish,
    device rows first, so host rows move up to device slots."""
    limit = time.perf_counter() + SETUP_LIMIT_S
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))

    def send(n: int, tokens: int) -> List[object]:
        return [client.send(Spec(prompt=[int(x) for x in rng.integers(
            0, vocab, WARM_PROMPT)], max_new_tokens=tokens))
            for _ in range(n)]

    def emitting(reqs) -> bool:
        return all(r.output for r in reqs)

    burst = send(engine["device_slots"], WARM_DEVICE_TOKENS)
    client.drive_until(lambda: emitting(burst), limit, refill=False)
    first = send(1, WARM_TOKENS)
    client.drive_until(lambda: emitting(first), limit, refill=False)
    burst += first + send(engine["host_slots"] - 1, WARM_TOKENS)
    client.drive_until(lambda: all(r.request_id not in client.active
                                   for r in burst), limit, refill=False)
    for r in burst:
        for d in (client.reqs, client.stamps, client.token_tiers):
            d.pop(r.request_id, None)


@dataclasses.dataclass
class RunResult:
    window: Window
    client: Client
    compiles_in_window: int
    cache_loads_in_window: int
    programs: List[tuple]     # (seconds into the window, seconds, name)
    notes: List[str]
    memory_peak_bytes: Optional[int]
    attempted: int
    failed: int


def serve_window(cfgfile: Dict, mix: Dict, seed: int, seconds: float, *,
                 t_process: float, trace_dir: Optional[str] = None,
                 log=print) -> RunResult:
    """Set up, warm up and measure one window; the server is shut down
    and its device memory freed before this returns."""
    import jax

    from repro.serving import InferenceServer
    from bench.weights import program_params

    compiles = CompileCounter()
    mcfg = model_config(cfgfile)
    engine = cfgfile["engine"]
    params = program_params(seed, cfgfile["model"])
    log(f"weights made: {flops.param_count(cfgfile['model']) / 1e9:.3f}B "
        f"parameters, {time.perf_counter() - t_process:.2f}s since start")
    server = InferenceServer(mcfg, params, server_config(cfgfile, mcfg))
    client = None
    try:
        traffic = Traffic(mix, seed, vocab=mcfg.vocab_size,
                          cache_len=engine["cache_len"],
                          streams=engine["device_slots"] + engine["host_slots"])
        client = Client(server, traffic)
        warm_up(client, seed, engine, mcfg.vocab_size)
        c0 = counters(server.stats)
        k0 = compiles.mark()
        gc.callbacks.append(client._gc_clock)
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # the benchmark's spans only
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        client.recording = []
        t0 = time.perf_counter()
        setup_s = t0 - t_process
        for spec in traffic.initial:     # a closed loop's streams
            client.send(spec)
        t_stop = t0 + seconds
        client.drive_until(lambda: time.perf_counter() >= t_stop,
                           t_stop + 120.0)
        t1 = client.recording[-1].t_end if client.recording else t0
        if trace_dir is not None:
            jax.profiler.stop_trace()
        c1 = counters(server.stats)
        k1 = compiles.mark()
        gc.callbacks.remove(client._gc_clock)
        programs = [(t - t0, d, name) for t, d, name in compiles.programs
                    if t0 <= t <= t1]
        steps, client.recording = client.recording, None
        memory = peak_memory()
        failed = sorted(rid for rid, r in client.reqs.items() if r.failed)
        attempted = sum(1 for s in client.stamps.values()
                        if any(t0 <= x <= t1 for x in s))
        window = Window(
            t0=t0, t1=t1, setup_s=setup_s, model=cfgfile["model"],
            engine=engine, stamps=client.stamps,
            token_tiers=client.token_tiers, failed=failed, counters0=c0,
            counters1=c1, steps=steps)
        return RunResult(window=window, client=client,
                         compiles_in_window=(k1[0] - k1[1]) - (k0[0] - k0[1]),
                         cache_loads_in_window=k1[1] - k0[1],
                         programs=programs,
                         notes=window_notes(window, client, programs),
                         memory_peak_bytes=memory, attempted=attempted,
                         failed=len(failed))
    finally:
        if client is not None and client._gc_clock in gc.callbacks:
            gc.callbacks.remove(client._gc_clock)
        server.shutdown()
        if client is not None:
            client.server = None      # the reference needs the memory
        del server, params
        gc.collect()


def window_notes(w: Window, client: Client,
                 programs: List[tuple]) -> List[str]:
    """What held the window up: the programs compiled or loaded in it,
    the longest steps and what changed in them, and the longest gaps
    that end on a host-tier token; times from the window's start."""
    from bench.window import gaps_in

    out = [f"programs compiled or loaded in window: {len(programs)}, "
           f"{sum(p[1] for p in programs):.3f}s"]
    for t, d, name in sorted(programs, key=lambda p: -p[1])[:10]:
        out.append(f"  program +{t:.2f}s {d:.3f}s {name}")
    out.append(f"steps over {LONG_STEP_S}s: {len(client.long_steps)}")
    for t, d, changed, gc_s in sorted(client.long_steps,
                                      key=lambda x: -x[1])[:8]:
        moved = " ".join(f"{k}+{v:g}" for k, v in sorted(changed.items()))
        out.append(f"  step +{t - w.t0:.2f}s {d:.3f}s gc {gc_s:.3f}s: "
                   f"{moved}")
    ends = []
    for rid, s in w.stamps.items():
        tiers = w.token_tiers[rid]
        for i in range(1, len(s)):
            if (tiers[i] == "host" and w.t0 <= s[i - 1]
                    and s[i] <= w.t1):
                ends.append((s[i] - s[i - 1], s[i] - w.t0, rid))
    gaps = gaps_in(w.stamps, w.t0, w.t1, w.token_tiers, "host")
    out.append(f"host-tier gaps: {len(gaps)}; longest (ms, ending at, "
               f"request): " + ", ".join(
                   f"{g * 1e3:.0f} +{t:.2f}s r{rid}"
                   for g, t, rid in sorted(ends, reverse=True)[:10]))
    return out


def peak_memory() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def sample_for_check(client: Client, seed: int, n: int) -> List[int]:
    """Request ids whose served tokens the reference judges: the one with
    the most served tokens, up to half with tokens the host tier made, and
    the rest drawn from the seed."""
    served = [rid for rid, s in client.stamps.items()
              if s and not client.reqs[rid].failed]
    if not served:
        return []
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    longest = max(served, key=lambda rid: len(client.reqs[rid].output))
    rest = [rid for rid in served if rid != longest]
    host = [rid for rid in rest if any(client.host_flags(rid))]
    pick = [longest]
    k = min(len(host), max(1, n // 2))
    if k:
        pick += [host[i] for i in rng.choice(len(host), k, replace=False)]
    others = [rid for rid in rest if rid not in pick]
    k = min(len(others), n - len(pick))
    if k > 0:
        pick += [others[i] for i in rng.choice(len(others), k, replace=False)]
    return pick


def check(registry: Registry, cfgfile: Dict, client: Client, seed: int,
          run_failed: int, *, control: bool = False) -> Dict:
    """Judge the served tokens against the plain reference.  Returns the
    compared numbers, each with its limit, and the verdict.  With
    ``control`` the tokens judged are those the reference computed in
    int8 puts first at the same positions: the verdict a program in that
    precision would get, which has to come out false."""
    lim = cfgfile["correct"]
    ids = sample_for_check(client, seed, int(lim["sample_requests"]))
    seqs = [(list(client.reqs[rid].prompt), list(client.reqs[rid].output))
            for rid in ids]
    host = [client.host_flags(rid) for rid in ids]
    pad = max(int(lim["sample_requests"]) - len(seqs), 0)
    seqs += [([0], [])] * pad
    host += [[]] * pad
    ref = registry.reference(cfgfile["reference"])
    g = ref.logit_gaps(seed, cfgfile["model"], seqs,
                       cfgfile["engine"]["cache_len"], control=control,
                       host=host)
    compared = {
        "mean_logit_gap": {"value": g["mean"], "limit": lim["mean_logit_gap"]},
        "tokens_judged": {"value": g["judged"],
                          "limit": lim["min_tokens_judged"]},
        "failed_requests": {"value": run_failed, "limit": 0},
    }
    correct = (g["mean"] <= lim["mean_logit_gap"]
               and g["judged"] >= lim["min_tokens_judged"] and run_failed == 0)
    # the host tier's tokens on their own, where the configuration sets a
    # limit for them (one that separates the program from the control at
    # the number of host-tier tokens its cells judge)
    if lim.get("host_mean_logit_gap") is not None:
        compared["host_mean_logit_gap"] = {
            "value": g["host_mean"], "limit": lim["host_mean_logit_gap"]}
        compared["host_tokens_judged"] = {
            "value": g["host_judged"], "limit": lim["min_host_tokens_judged"]}
        correct = (correct and g["host_mean"] <= lim["host_mean_logit_gap"]
                   and g["host_judged"] >= lim["min_host_tokens_judged"])
    return {"correct": bool(correct), "compared": compared,
            "host_mean_logit_gap": g["host_mean"],
            "host_tokens_judged": g["host_judged"],
            "widest_logit_gap": float(np.max(g["widest"])) if ids else None,
            "requests_judged": len(ids)}


def configure_jax() -> str:
    """The program's compile cache, kept for every program however small
    (the serving engine compiles small eager operations as it meets new
    shapes); returns the cache directory."""
    import jax

    from repro.launch.compile_cache import configure_compile_cache

    cache = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def device_info() -> Dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def new_trace_dir() -> str:
    return tempfile.mkdtemp(prefix="bench-trace-",
                            dir=os.environ.get("TMPDIR") or None)
