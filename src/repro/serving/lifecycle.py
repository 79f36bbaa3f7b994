"""Request-lifecycle subsystem: state machine, admission, placement.

Carved out of the Engine monolith so the engine shrinks to an
orchestrator of jitted execution while everything about *which request
is where, and why* lives here:

  * the per-request **state machine**

        QUEUED → PREFILL → DECODE_DEVICE ─┬→ FINISHED
                     │          │ (preempt)
                     │          └→ PREEMPTED → DECODE_HOST
                     └→ DECODE_HOST ─┬→ FINISHED
                                     └→ MIGRATING → DECODE_DEVICE

    (mid-prefill tier retargeting passes through MIGRATING back to
    PREFILL; recompute-from-scratch preemption takes DECODE_DEVICE →
    RECOMPUTE → PREFILL — the victim's KV is dropped and it re-enters
    the queue).  ``transition`` enforces the legal edges.

  * ``AdmissionQueue`` — the waiting line as a priority queue:
    higher ``Request.priority`` first, earliest ``deadline`` next
    (EDF within a priority class), then arrival order.

  * ``TierPlacer`` — per-iteration placement policy.  It folds the
    shared ``AdmissionController`` budgets, the structural slot/pool
    constraints, and the ``OnlineCalibrator``'s corrected per-tier
    timings into three decisions: where a new request goes (rule 1,
    GPU-first), whether a host resident should migrate to a freed
    device slot (the drain-time predicate shared with the simulator
    via ``repro.core.placement``), and which device resident — if
    any — to demote for an urgent admission.

  * ``RequestLifecycle`` — the registries (device slots, host
    residents, in-flight prefills) plus admission, retirement, SLO
    accounting and occupancy counters.  It decides; the Engine
    executes (KV moves, jitted steps) through narrow callbacks.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import placement
from repro.core.scheduler import AdmissionController, Decision
from repro.serving.request import Phase, Request

def pow2_ceil(n: int) -> int:
    """Smallest power of two >= n — THE bucket rule bounding jit
    retraces for prefill lengths, batch sizes and chunk widths alike
    (one definition; the log2(cache_len) retrace bound depends on
    every caller using the same rule)."""
    return 1 << max(n - 1, 0).bit_length()


# ---------------------------------------------------------------------------
# Engine configuration (the engine-internal subset of ServerConfig;
# capacity + lifecycle-policy + scheduler knobs in one place)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EngineConfig:
    device_slots: int = 8
    host_slots: int = 8
    cache_len: int = 256
    page_size: int = 32
    host_pool_pages: int = 512
    max_queue: int = 1024
    temperature: float = 0.0
    # host-tier parallelism: worker threads sharding each host-attention
    # job's cohort rows (0 = auto: cpu_count - 1, leaving a core for the
    # device dispatch thread)
    host_workers: int = 0
    # bucketed/batched prefill fast path (every stack): prompt lengths
    # padded to powers of two so jit retraces stay <= log2(cache_len),
    # same-bucket admissions prefilled in one device call.  Exact for
    # hybrid (recurrent) stacks too — the length-masked scan freezes
    # state past each row's true length.
    bucketed_prefill: bool = True
    # chunked prefill co-scheduled with decode: prompts advance in
    # token-budgeted chunks INSIDE the continuous-batching loop (one
    # fused device step runs the decode batch and one prefill chunk).
    # 0 disables chunking (whole-prompt prefill before decode);
    # ``bucketed_prefill=False`` also falls back to whole-prompt.
    chunk_tokens: int = 64
    # offload policy: fraction of device KV that must be claimed before
    # requests go to the host tier (GPU-first rule)
    enable_offload: bool = True
    # --- request-lifecycle policy ------------------------------------
    # host→device tier rebalancing: when a device slot frees and the
    # drain-time predicate (repro.core.placement, shared with the
    # simulator) says the move pays off, promote a host resident — or
    # retarget a mid-prefill host admission — into the freed slot
    tier_rebalance: bool = True
    # SLO-aware preemptive admission: an urgent request (higher
    # Request.priority) may demote a strictly lower-priority device
    # resident to the host tier and take its slot
    preemption: bool = True
    # Algorithm-1 scheduling: the perf-model spec resolved by
    # PerfModelProvider ("analytic" | "analytic:<platform>" |
    # "measured" | "file:<path>"), the platform backing the analytic
    # specs (None = the attached device's, by device_kind), and the
    # §4.2 knobs passed to ApexScheduler.  "measured"
    # runs the OfflineProfiler once at engine startup (loading/saving
    # profile_cache when set); the resolved model is wrapped in an
    # OnlineCalibrator that refines it from observed iteration timings.
    perf_model: str = "analytic"
    profile_cache: Optional[str] = None
    profile_grid: Optional[Dict[str, tuple]] = None
    platform: Optional[str] = None
    host_min_ratio: float = 0.0
    max_pipeline_sub_batch: int = 256
    use_scheduler: bool = True
    # optional KV-budget overrides for the AdmissionController; None
    # derives them from slot capacity (then the structural constraints
    # — free slot, paged pool — bind first).  Set tighter values to
    # throttle admission below the engine's physical capacity.
    device_kv_budget_tokens: Optional[int] = None
    host_kv_budget_tokens: Optional[int] = None
    # cross-request prefix cache (repro.serving.prefix_cache): retired
    # requests publish their KV, admissions matching a cached prefix
    # resume chunked prefill at the uncached suffix.  Bit-identical
    # tokens either way; rides the chunked-prefill path, so
    # chunk_tokens == 0 or bucketed_prefill=False disables it too.
    prefix_cache: bool = True
    # device-resident cache entries (dedicated StackState rows); hot
    # prefixes hit from here without touching the host pool.  0 keeps
    # the cache host-pool-only (still exact, one upload per hit).
    prefix_cache_slots: int = 2
    # --- fault tolerance / chaos -------------------------------------
    # deterministic fault injection (repro.serving.faults): a FaultPlan
    # instance or its compact parse string ("host_stall@3x2:0.5,...");
    # None = no injection.  Tests and the fault_soak bench feed the
    # same plans through here so they exercise identical chaos.
    fault_plan: Optional[Any] = None
    # host-job watchdog: a submitted host-attention job must land within
    # max(calibrated t_catt prediction * host_job_slack,
    # host_job_min_timeout) seconds or the engine abandons it and
    # recomputes the cohort's attention on-device (bit-identical —
    # same numpy kernel, idempotent KV writes)
    host_job_slack: float = 8.0
    host_job_min_timeout: float = 0.25
    # master switch for both recompute escape hatches: the watchdog's
    # GPU fallback above, and recompute-from-scratch preemption when a
    # swap has no host capacity.  False restores the pre-chaos
    # behavior: host faults propagate, blocked swaps requeue the
    # urgent request (preemption_requeues).
    recompute_fallback: bool = True
    # host-tier circuit breaker: this many consecutive watchdog
    # fallbacks pin the scheduler to GPU_ONLY (no new host jobs or host
    # placements) for a cooldown that doubles per trip
    # (RestartPolicy backoff) and resets after a healthy host job
    host_breaker_threshold: int = 3
    host_breaker_cooldown: float = 1.0
    # sliding window (seconds) over pressure events for the
    # graceful-degradation ladder level reported on /health
    degradation_window: float = 5.0
    # --- host-KV precision and cold-page compression -----------------
    # stored dtype of the paged host pool: "fp32" (exact, the device
    # dtype) or "int8" (symmetric per-token quantization with fp32
    # scales; ~4x more resident tokens per byte of host RAM,
    # proportionally cheaper migrations, and the perf model prices
    # t_catt/t_migrate at the stored size).  int8 keeps tokens
    # identical on the pinned tier-1 workloads; logits drift within
    # the bounded-drift test's envelope.
    host_kv_dtype: str = "fp32"
    # seconds a host-pool owner may sit untouched before its pages are
    # zstd-compressed in place (transparently decompressed on next
    # touch; the reclaim path also prefers compressing evictable
    # owners' pages over evicting them).  0 disables compression.
    cold_page_compress_after: float = 0.0


# ---------------------------------------------------------------------------
# State machine
# ---------------------------------------------------------------------------

LEGAL_TRANSITIONS: Dict[Phase, Tuple[Phase, ...]] = {
    Phase.QUEUED: (Phase.PREFILL, Phase.FINISHED),
    Phase.PREFILL: (Phase.DECODE_DEVICE, Phase.DECODE_HOST,
                    Phase.MIGRATING, Phase.FINISHED),
    Phase.DECODE_DEVICE: (Phase.PREEMPTED, Phase.RECOMPUTE, Phase.FINISHED),
    Phase.DECODE_HOST: (Phase.MIGRATING, Phase.FINISHED),
    Phase.MIGRATING: (Phase.DECODE_DEVICE, Phase.PREFILL),
    Phase.PREEMPTED: (Phase.DECODE_HOST,),
    # a recompute-preempted victim waits in the admission queue and
    # re-prefills on re-admission (FINISHED covers a cancel while queued)
    Phase.RECOMPUTE: (Phase.PREFILL, Phase.FINISHED),
    Phase.FINISHED: (),
}


def transition(req: Request, to: Phase) -> None:
    """Move a request along a legal state-machine edge (raises on an
    illegal one — a lifecycle bug, not a recoverable condition)."""
    if to not in LEGAL_TRANSITIONS[req.phase]:
        raise RuntimeError(
            f"illegal lifecycle transition {req.phase.value} -> {to.value} "
            f"for request {req.request_id}")
    req.phase = to


def reject(req: Request, reason: str) -> None:
    """Fail a request without admitting it: FINISHED with ``error``
    set (surfaced as ``RequestHandle.failed``)."""
    req.error = reason
    transition(req, Phase.FINISHED)
    req.finish_time = time.perf_counter()


# ---------------------------------------------------------------------------
# Stats
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EngineStats:
    device_tokens: int = 0
    host_tokens: int = 0
    iterations: int = 0
    wall_time: float = 0.0
    # resolved host-tier worker count the HostExecutor actually runs
    # with (the config knob may be 0 = auto); 0 when offload is off
    host_workers: int = 0
    # host-executor busy split: compute (KV append + paged attention)
    # vs device->host QKV transfer; busy = compute + transfer.  Only
    # the compute share feeds the calibrator's t_catt correction.
    host_busy_time: float = 0.0
    host_transfer_time: float = 0.0
    # the part of the transfer spent waiting for the device step that
    # makes a job's QKV (span apex.host_job.device_wait), and the
    # engine thread's seconds blocked on the host tier: the
    # ASYM_PIPELINE wait, breaker-open synchronous jobs and watchdog
    # recomputes (span apex.host_wait)
    host_device_wait_time: float = 0.0
    host_wait_time: float = 0.0
    # jit traces taken by the bucketed/chunked prefill fast paths
    prefill_compilations: int = 0
    # chunked prefill: chunks executed, prompt tokens prefilled through
    # chunks, and iterations where a chunk co-ran with active decode
    prefill_chunks: int = 0
    chunked_prefill_tokens: int = 0
    chunk_co_run_iterations: int = 0
    # iterations whose step ran the chunk program (fused or alone), and
    # the token positions of the chunk buffer those calls computed
    chunk_iterations: int = 0
    chunk_buffer_tokens: int = 0
    # --- tier rebalancing / SLO admission ---------------------------
    # host→device promotions (including mid-prefill retargets) and
    # device→host demotions executed by the engine
    migrations: int = 0
    preemptions: int = 0
    # swap-to-queue fallbacks: urgent requests whose preemptive
    # admission found a strictly lower-priority victim but no host
    # slot / paged-pool room to demote it into — the urgent request
    # stays queued at its EDF position and retries as capacity frees
    # (counted once per request, not once per blocked iteration)
    preemption_requeues: int = 0
    # recompute-from-scratch preemptions: blocked swaps (or mid-flight
    # pool-allocation failures) that dropped the victim's KV and sent
    # it back through the queue on the RECOMPUTE edge
    preemption_recomputes: int = 0
    # --- host-tier fault tolerance ----------------------------------
    # host jobs abandoned by the watchdog (timeout or worker exception)
    # and recomputed on-device, and breaker trips (consecutive-fallback
    # threshold reached -> GPU_ONLY pin for a cooldown window)
    host_fallbacks: int = 0
    host_breaker_trips: int = 0
    # what each watchdog fallback recovered from: "timeout", or the
    # worker's exception as "<type>: <message>"
    host_fallback_causes: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    # requests aborted by the client (gateway disconnects,
    # PoolHandle.cancel, Engine.cancel) with their resources freed
    cancelled: int = 0
    # TTFT SLO outcomes: first tokens that landed after arrival +
    # deadline, and requests rejected at admission because the
    # deadline was already impossible (backpressure, not a miss)
    deadline_misses: int = 0
    deadline_rejections: int = 0
    # per-tier occupancy: slot-iterations accumulated each engine
    # iteration (mean occupancy = counter / iterations)
    device_slot_iterations: int = 0
    host_slot_iterations: int = 0
    # --- cross-request prefix cache ---------------------------------
    # admission-time lookups, hits, and prompt tokens served from the
    # cache (skipped prefill work); evictions count entries leaving
    # the index (LRU drops, pool reclaims, supersessions) while
    # demotions count device→host tier moves (the entry survives).
    # The byte gauges track resident cached KV per tier.
    prefix_lookups: int = 0
    prefix_hits: int = 0
    prefix_hit_tokens: int = 0
    prefix_evictions: int = 0
    prefix_demotions: int = 0
    prefix_device_bytes: int = 0
    prefix_host_bytes: int = 0
    # --- host-pool byte accounting (quantized KV tier) ---------------
    # stored bytes resident in the paged host pool by state (hot =
    # occupied physical pages, compressed = cold zstd blobs, free =
    # unoccupied physical pages), the pool's stored bytes per KV
    # element (1 = int8, 4 = fp32), and cold-page compression activity
    # (counters + lossless-codec ratio EWMA, None until the first
    # compression).  The engine refreshes these from
    # ``PagedKVPool.byte_stats()`` each stats sync.
    host_pool_hot_bytes: int = 0
    host_pool_compressed_bytes: int = 0
    host_pool_free_bytes: int = 0
    host_kv_dtype_bytes: int = 0
    host_pages_compressed: int = 0
    host_pages_decompressed: int = 0
    host_compressed_ratio_ewma: Optional[float] = None
    # latency distributions over retired requests: time-to-first-token
    # and per-request mean inter-token latency (seconds)
    ttft_samples: List[float] = dataclasses.field(default_factory=list)
    itl_samples: List[float] = dataclasses.field(default_factory=list)
    # per-iteration Algorithm-1 outcomes: StrategyKind.value -> count
    strategy_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    last_decision: Optional[Decision] = None
    # scheduling accuracy: per-iteration model-predicted step times vs
    # the measured wall time of those same (decided) iterations, plus
    # the OnlineCalibrator's EWMA of the per-step relative error
    perf_model_spec: str = ""
    predicted_time: float = 0.0
    observed_time: float = 0.0
    step_error_ewma: Optional[float] = None
    # --- graceful-degradation ladder --------------------------------
    # last time (perf_counter) each ladder rung's action fired; the
    # reported level is the most severe rung active within
    # ``degradation_window`` seconds (engine copies the config knob in)
    pressure_marks: Dict[str, float] = dataclasses.field(default_factory=dict)
    degradation_window: float = 5.0

    def note_pressure(self, rung: str) -> None:
        self.pressure_marks[rung] = time.perf_counter()

    def degradation(self, window: Optional[float] = None) -> str:
        """Current rung of ``placement.DEGRADATION_LADDER`` ("ok" when
        no pressure action fired within the window)."""
        w = self.degradation_window if window is None else window
        now = time.perf_counter()
        recent = {rung: (now - t) <= w
                  for rung, t in self.pressure_marks.items()}
        return placement.degradation_level(recent)

    def record_decision(self, decision: Decision) -> None:
        key = decision.strategy.value
        self.strategy_counts[key] = self.strategy_counts.get(key, 0) + 1
        self.last_decision = decision

    @property
    def throughput(self) -> float:
        return (self.device_tokens + self.host_tokens) / max(self.wall_time,
                                                             1e-9)

    @property
    def device_occupancy(self) -> float:
        """Mean occupied device slots per iteration."""
        return self.device_slot_iterations / max(self.iterations, 1)

    @property
    def host_occupancy(self) -> float:
        return self.host_slot_iterations / max(self.iterations, 1)

    @staticmethod
    def _pct(samples: List[float], q: float) -> Optional[float]:
        if not samples:
            return None
        return float(np.percentile(np.asarray(samples, float), q))

    @property
    def ttft_p50(self) -> Optional[float]:
        return self._pct(self.ttft_samples, 50)

    @property
    def ttft_p95(self) -> Optional[float]:
        return self._pct(self.ttft_samples, 95)

    @property
    def itl_p50(self) -> Optional[float]:
        return self._pct(self.itl_samples, 50)

    @property
    def itl_p95(self) -> Optional[float]:
        return self._pct(self.itl_samples, 95)

    def snapshot(self) -> Dict[str, Optional[float]]:
        """Flat metric-name → value view of the serving counters — the
        stats-export surface the gateway's Prometheus ``/metrics``
        endpoint renders and the HTTP bench embeds.  ``None`` marks a
        distribution with no samples yet (exporters skip those)."""
        return {
            "iterations": float(self.iterations),
            "device_tokens": float(self.device_tokens),
            "host_tokens": float(self.host_tokens),
            "wall_time_seconds": self.wall_time,
            "decode_iters_per_s": self.iterations / max(self.wall_time,
                                                        1e-9),
            "tokens_per_s": self.throughput,
            "migrations": float(self.migrations),
            "preemptions": float(self.preemptions),
            "preemption_requeues": float(self.preemption_requeues),
            "preemption_recomputes": float(self.preemption_recomputes),
            "host_fallbacks": float(self.host_fallbacks),
            "host_wait_seconds": self.host_wait_time,
            "host_device_wait_seconds": self.host_device_wait_time,
            "host_breaker_trips": float(self.host_breaker_trips),
            "cancelled": float(self.cancelled),
            "degradation_level": float(
                placement.DEGRADATION_LADDER.index(self.degradation())),
            "deadline_misses": float(self.deadline_misses),
            "deadline_rejections": float(self.deadline_rejections),
            "device_occupancy": self.device_occupancy,
            "host_occupancy": self.host_occupancy,
            "prefill_chunks": float(self.prefill_chunks),
            "prefix_lookups": float(self.prefix_lookups),
            "prefix_hits": float(self.prefix_hits),
            "prefix_hit_tokens": float(self.prefix_hit_tokens),
            "prefix_evictions": float(self.prefix_evictions),
            "prefix_demotions": float(self.prefix_demotions),
            "prefix_device_bytes": float(self.prefix_device_bytes),
            "prefix_host_bytes": float(self.prefix_host_bytes),
            "host_pool_hot_bytes": float(self.host_pool_hot_bytes),
            "host_pool_compressed_bytes": float(
                self.host_pool_compressed_bytes),
            "host_pool_free_bytes": float(self.host_pool_free_bytes),
            "host_kv_dtype_bytes": float(self.host_kv_dtype_bytes),
            "host_pages_compressed": float(self.host_pages_compressed),
            "host_pages_decompressed": float(self.host_pages_decompressed),
            "host_compressed_ratio_ewma": self.host_compressed_ratio_ewma,
            "ttft_p50_seconds": self.ttft_p50,
            "ttft_p95_seconds": self.ttft_p95,
            "itl_p50_seconds": self.itl_p50,
            "itl_p95_seconds": self.itl_p95,
        }

    @property
    def prediction_error(self) -> Optional[float]:
        """Aggregate |predicted - observed| / observed over decided
        iterations (None until the first decision lands).  Includes
        one-off jit-compile iterations by construction — it is the true
        total gap; ``step_error_ewma`` is the outlier-robust view of
        current scheduling accuracy."""
        if self.observed_time <= 0.0:
            return None
        return abs(self.predicted_time - self.observed_time) \
            / self.observed_time


# ---------------------------------------------------------------------------
# In-flight prefill bookkeeping (chunked-prefill staging)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class InflightPrefill:
    """One admission advancing chunk-by-chunk through the staging state."""

    req: Request
    tier: str                        # "device" | "host"
    slot: int                        # device slot / host slot index
    consumed: int = 0                # prompt tokens already prefilled

    @property
    def remaining(self) -> int:
        return self.req.prompt_len - self.consumed


@dataclasses.dataclass
class ChunkPlan:
    """This iteration's chunk assignment over staging rows."""

    rows: List[int]                  # staging rows advancing (FIFO order)
    lens: List[int]                  # real tokens granted per row
    tokens: np.ndarray               # (P, C) right-padded chunk tokens
    clens: np.ndarray                # (P,) per-row chunk length (0 = idle)


# ---------------------------------------------------------------------------
# Admission queue (priority + EDF)
# ---------------------------------------------------------------------------


class AdmissionQueue:
    """The waiting line, ordered by (priority desc, deadline asc,
    arrival asc): urgent requests jump the queue, and within a
    priority class the earliest deadline goes first (EDF).  ``push``
    is O(1); ordering is applied lazily at ``pop``."""

    def __init__(self) -> None:
        self._q: List[Request] = []
        self._sorted = True

    @staticmethod
    def _key(r: Request):
        arrival = r.arrival_time if r.arrival_time is not None else 0.0
        # EDF wants absolute due time (arrival + relative deadline) —
        # ordering by the relative deadline alone would rank a
        # late-arriving slack request ahead of an earlier one already
        # close to its due time
        due = arrival + r.deadline if r.deadline is not None \
            else float("inf")
        return (-r.priority, due, arrival, r.request_id)

    def push(self, req: Request) -> None:
        self._q.append(req)
        self._sorted = False

    def _sort(self) -> None:
        if not self._sorted:
            self._q.sort(key=self._key)
            self._sorted = True

    def peek(self) -> Optional[Request]:
        self._sort()
        return self._q[0] if self._q else None

    def pop(self) -> Request:
        self._sort()
        return self._q.pop(0)

    def remove(self, request_id: int) -> Optional[Request]:
        """Pull a specific request out of the line (client cancel
        before admission); None when it is not queued."""
        for i, r in enumerate(self._q):
            if r.request_id == request_id:
                return self._q.pop(i)
        return None

    def __len__(self) -> int:
        return len(self._q)

    def __bool__(self) -> bool:
        return bool(self._q)

    def __iter__(self):
        self._sort()
        return iter(list(self._q))

    def snapshot(self) -> List[Request]:
        """Point-in-time copy of the queued requests, *without*
        sorting.  Safe to call from a thread other than the engine
        driver (the gateway's predicted-wait estimate does): a plain
        list copy never mutates ordering state under the driver."""
        return list(self._q)


# ---------------------------------------------------------------------------
# Tier placement policy
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TierPlacer:
    """Placement policy over the shared budgets and the calibrated
    perf model.  Pure decisions — the engine executes the KV moves.

    ``perf_model`` is the engine's ``OnlineCalibrator`` (or any object
    with ``timings``/``t_catt``/``t_migrate``/``t_prefill``); ``None``
    degrades gracefully to structural rules (no drain-time model, no
    deadline prediction).
    """

    admission: AdmissionController
    perf_model: Any = None
    iters_per_host_token: int = 1    # num_attn_layers + 1 under overlap
    # prefix-cache probe: prompt -> cached-prefix length (0 = miss).
    # The engine wires ``PrefixCache.match_len`` here so deadline
    # backpressure prices only the uncached suffix — a long prompt
    # whose prefix is cached is NOT impossible.
    cached_prefix_probe: Optional[Callable[[Sequence[int]], int]] = None

    # --- admission-time placement (rule 1) ----------------------------
    def place(self, need_tokens: int, *, device_ok: bool,
              host_ok: bool) -> Optional[str]:
        return self.admission.place(need_tokens, device_ok=device_ok,
                                    host_ok=host_ok)

    # --- per-tier decode-time estimates -------------------------------
    def tier_token_times(self, *, device_batch: int, host_batch: int,
                         context: float
                         ) -> Tuple[Optional[float], Optional[float]]:
        """(device, host) seconds-per-token at the current operating
        point, from the calibrator-corrected timings: one device token
        per iteration; one host token per ``iters_per_host_token``
        iterations — each iteration as wide as the slower of the
        device step and the cohort's one-layer host attention."""
        pm = self.perf_model
        if pm is None:
            return None, None
        t = pm.timings(max(device_batch, 1), max(context, 1.0))
        iter_time = t.t_glinear + t.t_gatt
        t_host_layer = pm.t_catt(max(host_batch, 1), max(context, 1.0),
                                 layers=1)
        host_time = self.iters_per_host_token * max(iter_time, t_host_layer)
        return iter_time, host_time

    def migration_cost(self, n_tokens: int) -> float:
        if self.perf_model is None:
            return 0.0
        return float(self.perf_model.t_migrate(n_tokens))

    # --- rebalance (host → device) ------------------------------------
    def rebalance_candidate(self, candidates: List[Request], *,
                            waiting: int, device_slot_free: bool,
                            device_batch: int) -> Optional[Request]:
        """The host resident to promote into a freed device slot, or
        None.  Candidate choice and the pays-off predicate both come
        from ``repro.core.placement`` — the same rule the simulator
        runs, so sim and engine cannot drift."""
        cand = placement.pick_rebalance_candidate(candidates)
        if cand is None:
            return None
        remaining = cand.max_new_tokens - cand.tokens_generated
        dev_s, host_s = self.tier_token_times(
            device_batch=device_batch, host_batch=len(candidates),
            context=float(cand.total_len))
        # a mid-prefill retarget moves no KV (the staging state already
        # holds it on device) — charging t_migrate would refuse a free
        # promotion; only decoding residents pay the transfer
        cost = (0.0 if cand.phase is Phase.PREFILL
                else self.migration_cost(cand.total_len))
        ok = placement.should_rebalance_to_device(
            waiting=waiting, device_slot_free=device_slot_free,
            device_kv_headroom=self.admission.headroom("device"),
            need_tokens=cand.kv_reserved, remaining_tokens=remaining,
            migration_cost=cost,
            device_s_per_token=dev_s, host_s_per_token=host_s)
        return cand if ok else None

    # --- preemption (device → host) -----------------------------------
    def preemption_victim(self, residents: List[Request], *,
                          urgent: Request, host_slot_free: bool,
                          pool_ok: Callable[[int], bool]
                          ) -> Optional[Request]:
        """The device resident to demote so ``urgent`` can take its
        slot, or None when preemption cannot help: no strictly
        lower-priority resident, no host slot / paged-pool room for
        the victim, or the freed device budget still would not fit
        the urgent request."""
        if not host_slot_free:
            return None
        victim = placement.pick_preemption_victim(
            residents, urgent_priority=urgent.priority)
        if victim is None:
            return None
        if not pool_ok(victim.kv_demand()):
            return None
        if self.admission.headroom("host") < victim.kv_reserved:
            return None
        if self.admission.headroom("device") + victim.kv_reserved \
                < urgent.kv_demand():
            return None
        return victim

    def prefer_recompute(self, victim: Request) -> bool:
        """Swap-vs-recompute pricing for a *feasible* swap: True when
        dropping the victim's KV and replaying it later is predicted
        cheaper than moving its KV to the host tier.  Without a perf
        model, swap (the side that preserves work) wins."""
        pm = self.perf_model
        if pm is None:
            return False
        return placement.should_recompute_instead_of_swap(
            t_swap=self.migration_cost(victim.total_len),
            t_recompute=float(pm.t_recompute(victim.prompt_len,
                                             victim.tokens_generated)))

    # --- SLO backpressure ---------------------------------------------
    def deadline_impossible(self, req: Request, *, now: float) -> bool:
        """Reject-on-impossible-deadline: the time already burned in
        the queue plus the model-predicted prefill exceeds the TTFT
        SLO.  Without a perf model the check degrades to 'deadline
        already passed'."""
        if req.deadline is None:
            return False
        elapsed = (now - req.arrival_time
                   if req.arrival_time is not None else 0.0)
        predicted = 0.0
        if self.perf_model is not None:
            cached = (self.cached_prefix_probe(req.prompt)
                      if self.cached_prefix_probe is not None else 0)
            charge = placement.chargeable_prefill_tokens(
                req.prompt_len, cached)
            suffix = getattr(self.perf_model, "t_prefill_suffix", None)
            if suffix is not None and charge < req.prompt_len:
                predicted = float(suffix(charge, req.prompt_len))
            else:
                predicted = float(self.perf_model.t_prefill(
                    charge, req.prompt_len))
        return placement.deadline_impossible(
            elapsed=elapsed, deadline=req.deadline, predicted_ttft=predicted)


# ---------------------------------------------------------------------------
# Lifecycle registries + admission/retirement
# ---------------------------------------------------------------------------


class RequestLifecycle:
    """Owns the request registries and every lifecycle decision.

    ``e`` is the engine config (duck-typed: only the capacity and
    policy knobs are read).  KV movement is the engine's job; the two
    execution callbacks it hands ``admit`` keep the split clean:
    ``demote(urgent) -> Optional[int]`` performs a preemption and
    returns the freed device slot.
    """

    def __init__(self, e: Any, *, stats: EngineStats,
                 placer: TierPlacer) -> None:
        self.e = e
        self.stats = stats
        self.placer = placer
        self.admission = placer.admission
        self.queue = AdmissionQueue()
        self.slots: List[Optional[Request]] = [None] * e.device_slots
        self.host_requests: Dict[int, Request] = {}
        self.host_slot_owner: Dict[int, int] = {}    # host slot -> request_id
        # chunked-prefill staging registry (rows claimed by admissions)
        self.staging: List[Optional[InflightPrefill]] = []
        self.staging_order: List[int] = []           # rows in admission order
        # urgent requests already counted as a swap-to-queue fallback
        # (preemption attempted, no victim capacity) — dedups the
        # EngineStats.preemption_requeues counter across retries
        self._preempt_noted: set = set()

    # --- submission ----------------------------------------------------
    def submit(self, req: Request) -> None:
        if req.arrival_time is None:
            req.arrival_time = time.perf_counter()
        req.phase = Phase.QUEUED
        self.queue.push(req)

    # --- slot scans ----------------------------------------------------
    def free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slots):
            if r is None:
                return i
        return None

    def free_host_slot(self) -> Optional[int]:
        for i in range(self.e.host_slots):
            if i not in self.host_slot_owner:
                return i
        return None

    @property
    def has_work(self) -> bool:
        return bool(self.queue or any(r is not None for r in self.slots)
                    or self.host_requests)

    def decoding_hosts(self) -> List[Request]:
        """Host residents actually decoding (mid-prefill and retiring
        requests excluded) — the scheduler's decode_cpu snapshot and
        the rebalance candidate pool."""
        return [r for r in self.host_requests.values()
                if not r.done and r.phase is Phase.DECODE_HOST]

    def schedule_snapshots(self, admitted: List[Request],
                           active_rows: List[int], *, chunked: bool
                           ) -> Tuple[List[Request], List[Request],
                                      List[Request], int]:
        """Algorithm 1's queue snapshots for this iteration:
        (prefill_q, decode_gpu, decode_cpu, chunk_backlog_tokens).

        Device requests admitted this iteration are the prefill queue,
        not decodes.  Host requests stay in decode_cpu even when just
        admitted: at engine granularity their cohort decode runs in
        this same step, and the strategy choice must see them
        (decode_cpu empty <=> GPU_ONLY must match the dispatch).
        Chunked mode snapshots every in-flight prefill instead (the
        scheduler grants the chunk budget from the backlog)."""
        new_ids = {r.request_id for r in admitted}
        decode_gpu = [r for r in (self.slots[i] for i in active_rows)
                      if r.request_id not in new_ids]
        decode_cpu = self.decoding_hosts()
        if chunked:
            inflight = [self.staging[row] for row in self.staging_order]
            prefill_q = [e.req for e in inflight]
            backlog = sum(e.remaining for e in inflight)
        else:
            prefill_q = admitted
            backlog = 0
        return prefill_q, decode_gpu, decode_cpu, backlog

    # --- admission (rule 1 + SLO backpressure + preemption) -------------
    def admit(self, *, pool: Any,
              demote: Optional[Callable[[Request], Optional[int]]],
              prompt_reject_reason: Callable[[int, int], Optional[str]],
              ) -> List[Tuple[Request, str, int]]:
        """Pop the priority queue into tier placements until the first
        request that cannot be placed.  Returns (req, tier, slot)
        placements with slots/budgets/pool chains already reserved;
        the engine prefills (or stages) them after."""
        placements: List[Tuple[Request, str, int]] = []
        now = time.perf_counter()
        while self.queue:
            req = self.queue.peek()
            reason = prompt_reject_reason(req.prompt_len, self.e.cache_len)
            if reason is not None:
                reject(self.queue.pop(), reason)
                self._preempt_noted.discard(req.request_id)
                continue
            # a recompute-preempted victim has already streamed tokens
            # its consumer is holding — rejecting it on a now-stale
            # TTFT prediction would lose committed output, so the
            # deadline gate applies to fresh admissions only
            if req.phase is not Phase.RECOMPUTE \
                    and self.placer.deadline_impossible(req, now=now):
                self.stats.deadline_rejections += 1
                self._preempt_noted.discard(req.request_id)
                reject(self.queue.pop(),
                       f"deadline {req.deadline:.3f}s impossible: queue "
                       f"wait + predicted prefill already exceeds it")
                continue
            if req.prompt_len + req.max_new_tokens >= self.e.cache_len:
                req.max_new_tokens = self.e.cache_len - req.prompt_len - 1
            need = req.kv_demand()
            slot = self.free_slot()
            hslot = self.free_host_slot() if self.e.enable_offload else None
            tier = self.placer.place(
                need, device_ok=slot is not None,
                host_ok=(hslot is not None and pool is not None
                         and pool.can_admit(need)))
            if tier is None and demote is not None and slot is None:
                # SLO-aware preemption: an urgent request may demote a
                # strictly lower-priority device resident to the host
                # tier and take its slot
                slot = demote(req)
                if slot is not None:
                    tier = self.placer.place(need, device_ok=True,
                                             host_ok=False)
                elif any(r is not None and not r.done
                         and r.phase is Phase.DECODE_DEVICE
                         and r.priority < req.priority
                         for r in self.slots):
                    # swap-to-queue fallback: a strictly lower-priority
                    # victim exists but the demote found no host slot /
                    # paged-pool room to move it into.  The urgent
                    # request was only peeked, never popped — it keeps
                    # its EDF position at the head of the queue and
                    # retries next iteration when capacity may have
                    # freed, instead of the demote failing silently.
                    if req.request_id not in self._preempt_noted:
                        self._preempt_noted.add(req.request_id)
                        self.stats.preemption_requeues += 1
            if tier is None:
                break
            req = self.queue.pop()
            self._preempt_noted.discard(req.request_id)
            req.tier = tier
            req.kv_reserved = need
            if tier == "device":
                self.slots[slot] = req          # reserve before prefill
                req.slot = slot
                placements.append((req, "device", slot))
            else:
                # reserve host slot, pool chains and request map now so
                # later placements in this round see them taken
                try:
                    pool.allocate(req.request_id, req.prompt_len)
                except MemoryError:
                    # can_admit is advisory: an in-flight host job
                    # extended a chain between the check and this
                    # reservation — undo the budget claim, retry later
                    self.admission.release("host", need)
                    req.tier = None
                    req.kv_reserved = 0
                    self.queue.push(req)
                    break
                self.host_slot_owner[hslot] = req.request_id
                self.host_requests[req.request_id] = req
                req.slot = hslot
                placements.append((req, "host", hslot))
        return placements

    # --- chunked-prefill staging ----------------------------------------
    def stage(self, placements: List[Tuple[Request, str, int]]) -> List[int]:
        """Claim a staging row per admission: prompts prefill there
        chunk-by-chunk inside the engine's fused device step.  Returns
        the claimed rows — recycled rows carry the previous occupant's
        recurrent state, which the engine must re-zero for hybrids."""
        rows: List[int] = []
        for req, tier, s in placements:
            row = self.staging.index(None)
            transition(req, Phase.PREFILL)
            self.staging[row] = InflightPrefill(req=req, tier=tier, slot=s)
            self.staging_order.append(row)
            rows.append(row)
        return rows

    def staging_backlog(self) -> int:
        return sum(self.staging[r].remaining for r in self.staging_order)

    def plan_chunks(self, budget: int) -> Optional[ChunkPlan]:
        """Assign this iteration's chunk budget over in-flight
        prefills — priority classes first (an urgent request that
        preempted its way in must not starve behind an earlier-staged
        low-priority backlog), admission (FIFO) order within a class.
        The chunk call is one batched device step over all advancing
        staging rows.  Every grant is capped at ``chunk_tokens`` and
        the token buffer is always ``pow2_ceil(chunk_tokens)`` wide:
        XLA specializes reduction order to buffer shape, so a
        variable-width buffer would make a token's KV depend on how
        the prompt happened to be chunked — the prefix cache's
        exactness bar needs one program geometry for every chunk call
        (a 29-token and a 39-token prompt must produce bit-identical
        KV for their shared prefix)."""
        if budget <= 0:
            return None
        rows: List[int] = []
        lens: List[int] = []
        left = budget
        order = sorted(self.staging_order,       # stable: FIFO inside class
                       key=lambda row: -self.staging[row].req.priority)
        for row in order:
            if left <= 0:
                break
            c = min(self.staging[row].remaining, left, self.e.chunk_tokens)
            if c <= 0:
                continue
            rows.append(row)
            lens.append(c)
            left -= c
        if not rows:
            return None
        cbucket = pow2_ceil(self.e.chunk_tokens)
        p = len(self.staging)
        toks = np.zeros((p, cbucket), np.int32)
        clens = np.zeros((p,), np.int32)
        for row, c in zip(rows, lens):
            ent = self.staging[row]
            toks[row, :c] = ent.req.prompt[ent.consumed:ent.consumed + c]
            clens[row] = c
        return ChunkPlan(rows=rows, lens=lens, tokens=toks, clens=clens)

    def release_staging_row(self, row: int) -> None:
        self.staging[row] = None
        self.staging_order.remove(row)

    # --- tier-move bookkeeping ------------------------------------------
    def note_migrated(self, req: Request, slot: int, *,
                      to_prefill: bool = False) -> None:
        """Registry flip for a host→device promotion the engine just
        executed (``to_prefill``: a mid-prefill retarget — the request
        returns to PREFILL in its staging row instead of decoding)."""
        self.host_requests.pop(req.request_id, None)
        if req.slot is not None:
            self.host_slot_owner.pop(req.slot, None)
        self.admission.transfer("host", "device", req.kv_reserved)
        self.slots[slot] = req
        req.slot = slot
        req.tier = "device"
        transition(req, Phase.PREFILL if to_prefill
                   else Phase.DECODE_DEVICE)
        self.stats.migrations += 1

    def note_preempted(self, victim: Request, hslot: int) -> None:
        """Registry flip for a device→host demotion."""
        self.slots[victim.slot] = None
        self.admission.transfer("device", "host", victim.kv_reserved)
        self.host_slot_owner[hslot] = victim.request_id
        self.host_requests[victim.request_id] = victim
        victim.slot = hslot
        victim.tier = "host"
        transition(victim, Phase.DECODE_HOST)
        self.stats.preemptions += 1
        self.stats.note_pressure("demote")

    def note_recomputed(self, victim: Request) -> None:
        """Registry flip for a recompute-from-scratch preemption: the
        engine already dropped the victim's KV; here it loses its slot
        and budget and re-enters the admission queue on the RECOMPUTE
        edge.  ``output.clear()`` is IN PLACE on purpose — token
        streams hold the same list object and only forward tokens past
        their high-water mark, so the deterministic replay (re-prefill
        the original prompt, re-decode) regenerates indices below the
        mark bit-identically without the consumer seeing duplicates."""
        transition(victim, Phase.RECOMPUTE)
        self.slots[victim.slot] = None
        self.admission.release("device", victim.kv_reserved)
        victim.slot = None
        victim.tier = None
        victim.kv_reserved = 0
        victim.output.clear()
        victim.layer_progress = 0
        self.queue.push(victim)
        self.stats.preemption_recomputes += 1
        self.stats.note_pressure("recompute")

    # --- per-iteration accounting ---------------------------------------
    def note_iteration(self) -> None:
        self.stats.device_slot_iterations += sum(
            r is not None for r in self.slots)
        self.stats.host_slot_iterations += len(self.host_requests)

    # --- retirement ------------------------------------------------------
    def _latency_sample(self, r: Request) -> None:
        if r.arrival_time is None or r.first_token_time is None:
            return
        ttft = r.first_token_time - r.arrival_time
        self.stats.ttft_samples.append(ttft)
        if r.deadline is not None and ttft > r.deadline:
            self.stats.deadline_misses += 1
        if r.finish_time is not None and len(r.output) > 1:
            self.stats.itl_samples.append(
                (r.finish_time - r.first_token_time) / (len(r.output) - 1))

    def retire(self, *, free_host: Callable[[int], None],
               publish: Optional[Callable[[Request], bool]] = None) -> None:
        """Scan both tiers for done requests: finish them, release
        budgets/slots, sample latencies and SLO outcomes.  ``publish``
        (the prefix cache's retirement hook) sees each request while
        its KV is still live; a True return means the cache ADOPTED a
        host retiree's pool chains, so ``free_host`` is skipped."""
        now = time.perf_counter()
        for i, r in enumerate(self.slots):
            if r is not None and r.done:
                if publish is not None:
                    publish(r)         # device slots always still free
                transition(r, Phase.FINISHED)
                r.finish_time = now
                self.admission.release("device", r.kv_reserved)
                self.slots[i] = None
                self._latency_sample(r)
        done_hosts = [rid for rid, r in self.host_requests.items() if r.done]
        for rid in done_hosts:
            r = self.host_requests.pop(rid)
            adopted = publish(r) if publish is not None else False
            transition(r, Phase.FINISHED)
            r.finish_time = now
            self.admission.release("host", r.kv_reserved)
            if not adopted:
                free_host(rid)
            self.host_slot_owner.pop(r.slot, None)
            self._latency_sample(r)
