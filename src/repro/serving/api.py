"""Unified serving surface: scheduler-driven ``InferenceServer``.

One facade replaces the ad-hoc ``EngineConfig`` wiring previously
duplicated across ``launch/serve.py``, ``examples/serve_chat.py`` and
``benchmarks``:

    server = InferenceServer(cfg, params, ServerConfig(device_slots=2,
                                                       host_slots=6))
    handle = server.submit([5, 42, 7], max_new_tokens=16)
    for tok in handle.tokens():      # per-token streaming; drives the
        print(tok)                   # engine's continuous-batching loop

Three drivers, all over the same continuous-batching iteration:

  * ``step()``            — one engine iteration (admit -> Algorithm 1
    -> dispatch -> retire); the unit the streaming iterators pump.
  * ``run_until_idle()``  — drain everything submitted (closed loop).
  * ``serve(requests)``   — open-loop replay: each request's
    ``arrival_time`` is a *relative offset* from serve start (what
    ``repro.serving.workloads.generate`` emits); offsets are rebased
    onto the wall clock and requests submitted as they become due.

``ServerConfig`` groups the engine capacity knobs, the Algorithm-1
scheduler knobs and the workload knobs in one structured config; the
legacy ``EngineConfig`` remains as the engine-internal subset.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Iterable, Iterator, List, Optional, Sequence, Union

from repro.core.scheduler import ApexScheduler
from repro.models.config import ModelConfig
from repro.serving.engine import Engine, EngineConfig, EngineStats
from repro.serving.request import Phase, Request

_DRIVE_LIMIT = 1_000_000     # runaway guard for handle-driven stepping


@dataclasses.dataclass
class ServerConfig:
    """Structured serving configuration: engine + scheduler + workload."""

    # --- engine capacity -------------------------------------------------
    device_slots: int = 8
    host_slots: int = 8
    cache_len: int = 256
    page_size: int = 32
    host_pool_pages: int = 512
    max_queue: int = 1024
    temperature: float = 0.0
    enable_offload: bool = True
    # host-tier worker threads sharding each host-attention job's rows
    # (0 = auto: cpu_count - 1) and the bucketed-prefill fast path (see
    # EngineConfig; docs/serving_api.md "Performance")
    host_workers: int = 0
    bucketed_prefill: bool = True
    # host KV tier precision ("fp32" | "int8") and cold-page
    # compression idle threshold in seconds (0 = off); see
    # docs/serving_api.md "Host KV precision and compression"
    host_kv_dtype: str = "fp32"
    cold_page_compress_after: float = 0.0
    # chunked prefill co-scheduled with decode: each prefilling row's
    # prompt tokens per iteration, and the width of the chunk buffer
    # (every chunk iteration fills it, up to staging rows x
    # chunk_tokens); 0 = whole-prompt prefill before decode (the
    # pre-chunking behaviour).  See docs/serving_api.md "Chunked
    # prefill".
    chunk_tokens: int = 64
    # --- request lifecycle (docs/serving_api.md "Request lifecycle,
    # migration, and SLOs") -----------------------------------------
    # host→device migration when a device slot frees and the shared
    # drain-time predicate (repro.core.placement) says it pays off
    tier_rebalance: bool = True
    # SLO-aware preemptive admission: urgent requests may demote a
    # strictly lower-priority device resident to the host tier
    preemption: bool = True
    # default TTFT deadline (seconds from arrival) stamped onto
    # build_requests() workloads; None = no SLO.  Per-request
    # deadlines passed to submit() override this.
    deadline: Optional[float] = None
    # --- Algorithm-1 scheduler ------------------------------------------
    # perf-model spec (repro.core.perf_model.PerfModelProvider):
    # "analytic" | "analytic:<platform>" | "measured" | "file:<path>".
    # "measured" profiles the real backends once at server startup
    # (cached at profile_cache when set) — the profiling-informed mode
    # for real deployments; "analytic" keeps the platform calibration
    # (instant startup, the simulation/default mode).  Either way the
    # engine wraps the model in an OnlineCalibrator refined from
    # observed iteration timings.
    perf_model: str = "analytic"
    profile_cache: Optional[str] = None
    profile_grid: Optional[dict] = None     # override startup profile points
    # analytic perf-model calibration; None = the attached device's
    # (repro.core.perf_model.device_platform)
    platform: Optional[str] = None
    host_min_ratio: float = 0.0      # §4.2 admission threshold
    max_pipeline_sub_batch: int = 256
    use_scheduler: bool = True
    # admission-throttling overrides (None = derive from capacity)
    device_kv_budget_tokens: Optional[int] = None
    host_kv_budget_tokens: Optional[int] = None
    # cross-request prefix cache (docs/serving_api.md "Prefix cache"):
    # retired prompts publish their KV across both tiers; admissions
    # matching a cached prefix prefill only the suffix
    prefix_cache: bool = True
    prefix_cache_slots: int = 2
    # --- fault tolerance (docs/serving_api.md "Failure handling") --------
    # deterministic chaos plan (repro.serving.faults.FaultPlan, a plan
    # string, or None): injected host-worker faults, pool exhaustion,
    # driver crashes, latency spikes — the same matrix tests and the
    # fault_soak bench run
    fault_plan: Optional[object] = None
    # host-job watchdog: deadline = predicted t_catt x slack (floored
    # at min_timeout); an expired or crashed job is recomputed exactly
    # on the engine thread
    host_job_slack: float = 8.0
    host_job_min_timeout: float = 0.25
    # False restores the legacy contract: host faults fail the engine
    # loudly and blocked swaps requeue instead of recompute-preempting
    recompute_fallback: bool = True
    # consecutive watchdog fallbacks tripping the GPU_ONLY breaker, and
    # its base cooldown (doubles per trip, resets on a healthy job)
    host_breaker_threshold: int = 3
    host_breaker_cooldown: float = 1.0
    # sliding window (seconds) for the /health degradation-ladder level
    degradation_window: float = 5.0
    # --- workload --------------------------------------------------------
    workload: Optional[str] = None   # azure-conv | livebench | dolphin-r1 | osc
    num_requests: int = 12
    arrival_rate: Optional[float] = None    # req/s Poisson; None = closed loop
    prompt_len: int = 16             # synthetic length / workload prompt cap
    output_len: int = 24             # synthetic length / workload output cap
    seed: int = 0

    def engine_config(self) -> EngineConfig:
        # ServerConfig is a superset of EngineConfig; copy by field
        # name so new engine knobs can never be silently dropped
        return EngineConfig(**{f.name: getattr(self, f.name)
                               for f in dataclasses.fields(EngineConfig)})

    def build_requests(self, *, vocab: int) -> List[Request]:
        """Sample the configured workload trace (or a synthetic one),
        capped to lengths that fit this server's KV cache."""
        from repro.serving import workloads
        prompt_cap = min(self.prompt_len, max(self.cache_len - 2, 1))
        output_cap = min(self.output_len,
                         max(self.cache_len - prompt_cap - 1, 1))
        if self.workload is None:
            import numpy as np
            from repro.serving.request import make_synthetic_request
            rng = np.random.default_rng(self.seed)
            reqs = [make_synthetic_request(rng, prompt_len=prompt_cap,
                                           output_len=output_cap,
                                           vocab=vocab)
                    for _ in range(self.num_requests)]
            if self.arrival_rate:
                offsets = workloads.poisson_offsets(
                    rng, self.arrival_rate, self.num_requests)
                for r, a in zip(reqs, offsets):
                    r.arrival_time = a
            return self._stamp_slo(reqs)
        reqs = workloads.generate(
            self.workload, num_requests=self.num_requests, vocab=vocab,
            arrival_rate=self.arrival_rate, seed=self.seed)
        for r in reqs:   # cap trace lengths to the engine's cache
            r.prompt = r.prompt[:prompt_cap]
            r.max_new_tokens = min(r.max_new_tokens, output_cap)
        return self._stamp_slo(reqs)

    def _stamp_slo(self, reqs: List[Request]) -> List[Request]:
        if self.deadline is not None:
            for r in reqs:
                if r.deadline is None:
                    r.deadline = self.deadline
        return reqs


class RequestHandle:
    """Streaming view of one submitted request.

    ``tokens()`` yields tokens as the engine produces them; pulling the
    iterator drives ``server.step()``, so every in-flight request keeps
    advancing (continuous batching) while you stream this one.
    """

    def __init__(self, server: "InferenceServer", request: Request) -> None:
        self._server = server
        self.request = request

    @property
    def request_id(self) -> int:
        return self.request.request_id

    @property
    def phase(self) -> Phase:
        return self.request.phase

    @property
    def done(self) -> bool:
        return self.request.phase == Phase.FINISHED

    @property
    def failed(self) -> bool:
        """True when the request was rejected (submit or admission)."""
        return self.request.failed

    @property
    def error(self) -> Optional[str]:
        return self.request.error

    @property
    def output(self) -> List[int]:
        return self.request.output

    def tokens(self) -> Iterator[int]:
        """Per-token stream; lazily steps the server until this request
        finishes.  Safe to interleave across handles."""
        sent = 0
        driven = 0
        while True:
            out = self.request.output
            while sent < len(out):
                yield out[sent]
                sent += 1
            if self.request.phase == Phase.FINISHED:
                return
            if not self._server.engine.has_work:
                raise RuntimeError(
                    f"request {self.request_id} not finished but the "
                    f"engine is idle (was it submitted?)")
            self._server.step()
            driven += 1
            if driven > _DRIVE_LIMIT:
                raise RuntimeError("token stream stalled: engine made no "
                                   f"progress in {_DRIVE_LIMIT} iterations")

    def result(self) -> List[int]:
        """Block (drive the engine) until finished; returns all tokens."""
        for _ in self.tokens():
            pass
        return self.request.output

    def time_to_first_token(self) -> Optional[float]:
        return self.request.time_to_first_token()

    def per_token_latency(self) -> Optional[float]:
        return self.request.per_token_latency()


class InferenceServer:
    """Scheduler-driven serving facade over the APEX engine."""

    def __init__(self, cfg: ModelConfig, params, config:
                 Optional[ServerConfig] = None,
                 scheduler: Optional[ApexScheduler] = None) -> None:
        self.config = config or ServerConfig()
        self.engine = Engine(cfg, params, self.config.engine_config(),
                             scheduler=scheduler)
        # one engine iteration at a time: the engine's per-iteration
        # bookkeeping (admission, cohort, staging) is not re-entrant,
        # but every RequestHandle.tokens() iterator drives step() — two
        # iterators pulled from different threads used to race the
        # engine.  submit() shares the lock (it mutates the admission
        # queue the step reads).  The gateway's replica driver threads
        # rely on this: they pump step() while gateway worker threads
        # submit concurrently.
        self._step_lock = threading.RLock()

    # --- submission ----------------------------------------------------------
    def submit(self, request: Union[Request, Sequence[int]],
               max_new_tokens: Optional[int] = None, *,
               deadline: Optional[float] = None,
               priority: int = 0) -> RequestHandle:
        """Submit a Request (or a raw token prompt); arrival is stamped
        now unless the request already carries a wall-clock stamp.

        ``deadline`` is a TTFT SLO in seconds from arrival (admission
        rejects it outright when it is already impossible);
        ``priority`` orders the admission queue and — with
        ``ServerConfig.preemption`` — lets the request demote a
        strictly lower-priority device resident.  Both apply only when
        constructing the request from a raw prompt; a ``Request``
        instance carries its own."""
        if not isinstance(request, Request):
            request = Request(prompt=[int(t) for t in request],
                              max_new_tokens=(self.config.output_len
                                              if max_new_tokens is None
                                              else max_new_tokens),
                              deadline=(deadline if deadline is not None
                                        else self.config.deadline),
                              priority=priority)
        if request.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {request.max_new_tokens} "
                f"(the prefill itself emits the first token)")
        reason = Engine.prompt_reject_reason(request.prompt_len,
                                             self.config.cache_len)
        if reason is not None:
            # no room for the prompt plus at least one generated token:
            # reject as a failed handle (Phase.FINISHED, error set)
            # rather than raising, so open-loop trace replay survives
            # one oversized request; longer *outputs* are merely
            # clamped to the cache (max-model-len) at admission
            if request.arrival_time is None:
                request.arrival_time = time.perf_counter()
            Engine.reject(request, reason)
            return RequestHandle(self, request)
        with self._step_lock:
            if len(self.engine.queue) >= self.config.max_queue:
                raise RuntimeError(f"queue full ({self.config.max_queue})")
            self.engine.submit(request)
        return RequestHandle(self, request)

    # --- drivers -------------------------------------------------------------
    def step(self) -> None:
        """One continuous-batching iteration: admit -> Algorithm 1 ->
        dispatch (GPU_ONLY / ASYNC_OVERLAP / ASYM_PIPELINE) -> retire.
        Re-entrant-safe: concurrent callers (interleaved token
        iterators, a pool driver thread) serialize on the step lock."""
        with self._step_lock:
            self.engine.step()

    def cancel(self, request_id: int) -> bool:
        """Abort a live request and free its resources (see
        ``Engine.cancel``).  Serialized with step()/submit() on the
        step lock so a gateway disconnect can abort safely while a
        driver thread is mid-iteration."""
        with self._step_lock:
            return self.engine.cancel(request_id)

    def run_until_idle(self, *, max_iterations: int = 100000) -> EngineStats:
        it = 0
        while self.engine.has_work and it < max_iterations:
            self.step()
            it += 1
        return self.stats

    def serve(self, requests: Iterable[Request], *, realtime: bool = True,
              max_iterations: int = 1_000_000) -> List[RequestHandle]:
        """Open-loop replay with continuous batching.

        ``arrival_time`` on each request is a relative offset from
        serve start (``None`` = immediately).  ``realtime=True`` honors
        the offsets on the wall clock — the engine keeps iterating on
        whatever is in flight while later arrivals are still due;
        ``realtime=False`` collapses the trace to a closed loop.
        """
        order = sorted(requests, key=lambda r: r.arrival_time or 0.0)
        handles = []
        start = time.perf_counter()
        i = 0
        it = 0
        while (i < len(order) or self.engine.has_work) \
                and it < max_iterations:
            now = time.perf_counter() - start
            while i < len(order):
                offset = order[i].arrival_time or 0.0
                if realtime and offset > now:
                    break
                if len(self.engine.queue) >= self.config.max_queue:
                    break       # backpressure: drain before admitting more
                r = order[i]
                # rebase the relative offset onto the wall clock (or
                # let submit() stamp "now" in closed-loop replay)
                r.arrival_time = start + offset if realtime else None
                handles.append(self.submit(r))
                i += 1
                now = time.perf_counter() - start
            if self.engine.has_work:
                self.step()
                it += 1
            elif i < len(order):
                # idle until the next arrival is due
                next_due = start + (order[i].arrival_time or 0.0)
                time.sleep(max(0.0, min(next_due - time.perf_counter(),
                                        0.01)))
        return handles

    # --- introspection -------------------------------------------------------
    @property
    def stats(self) -> EngineStats:
        if self.engine._executor is not None:
            self.engine.stats.host_busy_time = \
                self.engine._executor.busy_time
            self.engine.stats.host_transfer_time = \
                self.engine._executor.transfer_time
            self.engine.stats.host_device_wait_time = \
                self.engine._executor.device_wait_time
        self.engine._refresh_host_pool_gauges()
        return self.engine.stats

    @property
    def pending(self) -> int:
        return len(self.engine.queue)

    @property
    def active(self) -> int:
        return (sum(r is not None for r in self.engine.slots)
                + len(self.engine.host_requests))

    def shutdown(self) -> None:
        self.engine.shutdown()

    def __enter__(self) -> "InferenceServer":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
