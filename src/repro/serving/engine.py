"""Online serving engine — execution orchestrator of the APEX design.

The engine owns *execution*: the jitted model step functions, the
Asynchronous Overlap runtime (OverlapController + HostExecutor), KV
movement between tiers, and the per-iteration dispatch of the
Algorithm-1 ``Decision``:

  * ``GPU_ONLY``       — device-only decode (no host-designated rows).
  * ``ASYNC_OVERLAP``  — deferred sync: the previous iteration's host
    job is *polled*; late host rows ride along (the §3.4 re-check).
  * ``ASYM_PIPELINE``  — two-sub-step variant: host attention is
    *synchronized* (blocking) between consecutive device sub-steps.

Everything about *which request is where, and why* lives in
``repro.serving.lifecycle``: the per-request state machine, the
priority/EDF admission queue with SLO backpressure, and the
``TierPlacer`` that re-evaluates placement every iteration.  The
engine executes the placer's decisions:

  * **host→device migration** — when a device slot frees and the
    drain-time predicate (shared with the simulator through
    ``repro.core.placement``) says it pays off, a host resident's
    paged KV is gathered, uploaded into the freed slot, and decode
    continues on-device; an in-flight host *prefill* retargets by pure
    bookkeeping (its KV already lives in the staging state).
  * **device→host preemption** — an urgent admission may demote a
    strictly lower-priority device resident: its contiguous KV is
    demoted to the paged pool and the cohort picks it up at the next
    token boundary.

Both moves are exact (bit-identical tokens to a never-migrating run,
tests/test_lifecycle.py) and costed through the perf model's
``t_migrate`` term.  Static-shape discipline is unchanged: one
decode compile per (device_slots, host_slots) pair.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import placement
from repro.core.overlap_engine import (Cohort, HostExecutor,
                                       OverlapController,
                                       stack_row_kv_to_pool_layers)
from repro.core.perf_model import OnlineCalibrator, resolve_perf_model
from repro.core.scheduler import (AdmissionController, ApexScheduler,
                                  Decision, StrategyKind, chunk_budget)
from repro.core.spans import span, spanned, step_span
from repro.distributed.fault_tolerance import RestartPolicy
from repro.serving.faults import FaultInjector
from repro.models import (HostIO, ModelParams, decode_step,
                          decode_with_chunked_prefill, init_decode_state,
                          prefill_bucketed, prefill_chunk)
from repro.models.config import ModelConfig
from repro.models.kv_cache import PagedKVPool, StackState
from repro.serving.lifecycle import (ChunkPlan, EngineConfig, EngineStats,
                                     RequestLifecycle, TierPlacer, reject,
                                     transition)
from repro.serving.prefill_exec import (finish_chunks, prefill_batched,
                                        prefill_into_slot, prefill_to_host,
                                        seed_prefix_hits)
from repro.serving.prefix_cache import PrefixCache, publish_retired
from repro.serving.request import Phase, Request
from repro.serving.sampler import sample
from repro.serving.tiermove import (demote_slot_to_host_row,
                                    upload_host_kv_to_slot,
                                    zero_recurrent_rows)

__all__ = ["Engine", "EngineConfig", "EngineStats"]


class Engine:
    def __init__(self, cfg: ModelConfig, params: ModelParams,
                 ecfg: Optional[EngineConfig] = None,
                 scheduler: Optional[ApexScheduler] = None) -> None:
        self.cfg = cfg
        self.params = params
        self.e = ecfg or EngineConfig()
        if not cfg.has_kv_cache:
            self.e.enable_offload = False   # APEX inapplicable (DESIGN §5)
        self.state = init_decode_state(
            cfg, device_batch=self.e.device_slots,
            host_batch=self.e.host_slots if self.e.enable_offload else 0,
            cache_len=self.e.cache_len)
        self.stats = EngineStats()
        self.stats.degradation_window = self.e.degradation_window
        self.scheduler = scheduler
        # deterministic chaos (None when no plan is configured); the
        # injector threads through the executor, the paged pool and the
        # replica driver so tests/bench run one coherent fault matrix
        self._faults = FaultInjector.from_config(self.e.fault_plan)
        self._calibrator: Optional[OnlineCalibrator] = None
        # injected schedulers predating chunked prefill keep working:
        # the engine only forwards the chunk kwargs (and trusts
        # Decision.chunk_tokens) when schedule() accepts them
        self._sched_chunk_aware = False
        if self.scheduler is None and self.e.use_scheduler:
            base = resolve_perf_model(
                self.e.perf_model, cfg, platform=self.e.platform,
                profile_cache=self.e.profile_cache,
                profile_grid=self.e.profile_grid,
                host_kv_dtype=self.e.host_kv_dtype)
            self._calibrator = OnlineCalibrator(base)
            self.stats.perf_model_spec = self.e.perf_model
            self.scheduler = ApexScheduler(
                self._calibrator,
                host_min_ratio=self.e.host_min_ratio,
                max_pipeline_sub_batch=self.e.max_pipeline_sub_batch)
        if self.scheduler is not None:
            import inspect
            self._sched_chunk_aware = "chunk_capacity" in \
                inspect.signature(self.scheduler.schedule).parameters
        device_budget = (self.e.device_kv_budget_tokens
                         if self.e.device_kv_budget_tokens is not None
                         else self.e.device_slots * self.e.cache_len)
        host_budget = 0
        if self.e.enable_offload:
            host_budget = (self.e.host_kv_budget_tokens
                           if self.e.host_kv_budget_tokens is not None
                           else self.e.host_pool_pages * self.e.page_size)
        self.admission = AdmissionController(
            device_kv_budget_tokens=device_budget,
            host_kv_budget_tokens=host_budget)
        # the request-lifecycle subsystem: state machine, priority/EDF
        # admission queue, and the per-iteration tier placer steering
        # migration/preemption off the calibrator's corrected timings
        placer = TierPlacer(
            admission=self.admission, perf_model=self._calibrator,
            iters_per_host_token=cfg.num_attn_layers + 1)
        self.lc = RequestLifecycle(self.e, stats=self.stats, placer=placer)
        self._decode_fn = jax.jit(self._decode_traced)
        # hybrid (recurrent-state) stacks ride the same fast paths as
        # attention-only stacks: the length-masked scan (models.ssm)
        # freezes state past each row's true length, so bucketed and
        # chunked prefill stay exact for every architecture
        self._hybrid = cfg.has_recurrent
        self._bucketed_prefill = self.e.bucketed_prefill
        self._prefill_compiles = 0
        self._prefill_jit = jax.jit(self._prefill_traced)
        self._splice_jit = jax.jit(self._splice_device_row,
                                   donate_argnums=(0,))
        # chunked prefill co-scheduled with decode rides on bucketing;
        # chunk_tokens == 0 turns it off explicitly
        self._chunked = self.e.chunk_tokens > 0 and self._bucketed_prefill
        if self._chunked:
            # one staging row per admissible request: prompts prefill
            # here chunk-by-chunk, then splice (device) / finish
            # streaming to the paged pool (host) on completion
            n_staging = self.e.device_slots + (
                self.e.host_slots if self.e.enable_offload else 0)
            self._staging_state = init_decode_state(
                cfg, device_batch=n_staging, cache_len=self.e.cache_len)
            self.lc.staging = [None] * n_staging
            self._chunk_jit = jax.jit(self._chunk_traced,
                                      donate_argnums=(3,))
            self._decode_chunk_jit = jax.jit(self._decode_chunk_traced,
                                             donate_argnums=(5,))
            self._decode_overlap_chunk_jit = jax.jit(
                self._decode_overlap_chunk_traced, donate_argnums=(6,))
        self._overlap = None
        self._executor = None
        if self.e.enable_offload:
            self._overlap = OverlapController(cfg)
            pool = PagedKVPool(
                self.e.host_pool_pages, self.e.page_size,
                cfg.num_attn_layers, cfg.num_kv_heads,
                cfg.resolved_head_dim,
                host_kv_dtype=self.e.host_kv_dtype,
                cold_page_compress_after=self.e.cold_page_compress_after)
            pool.fault_hook = (self._faults.on_pool_alloc
                               if self._faults is not None else None)
            self._executor = HostExecutor(cfg, pool,
                                          workers=self.e.host_workers,
                                          faults=self._faults)
            # the *resolved* worker count (0 = auto expands inside the
            # executor) — what the host tier actually runs with
            self.stats.host_workers = self._executor.workers
            self._cohort: Optional[Cohort] = None
            self._idle_io: Optional[HostIO] = None
            self._pending_job: Optional[int] = None
            self._pending_host_pred = 0.0   # predicted time of pending job
            self._host_compute_seen = 0.0   # executor compute_time watermark
            self._job_ids = iter(range(1, 1 << 30))
            # host-job watchdog: submit stashes the pending job's full
            # argument set (deadline too) so a stalled or crashed job
            # can be abandoned and recomputed exactly on this thread
            self._pending_meta: Optional[dict] = None
            self._pending_deadline = 0.0
            self._pending_timeout = 0.0
            # circuit breaker over consecutive watchdog fallbacks: while
            # open (now < _breaker_until) no host jobs are submitted and
            # no new host placements/demotions happen — GPU_ONLY pin;
            # RestartPolicy doubles the cooldown per trip and a healthy
            # host job resets it
            self._fallback_streak = 0
            self._breaker_until = 0.0
            self._breaker = RestartPolicy(
                max_restarts=1 << 30,
                backoff_base=max(self.e.host_breaker_cooldown, 1e-3),
                backoff_cap=max(self.e.host_breaker_cooldown, 1e-3) * 32)
            self._decode_overlap_fn = jax.jit(self._decode_overlap_traced)
        # cross-request prefix cache: retired requests publish their KV
        # (device cache rows, overflowing to the paged host pool) and
        # admissions matching a cached prefix resume chunked prefill at
        # the uncached suffix.  Rides the chunked path — without it
        # there is no mid-prompt continuation to resume.
        self._prefix: Optional[PrefixCache] = None
        self._prefix_state: Optional[StackState] = None
        if self.e.prefix_cache and self._chunked:
            n_rows = max(self.e.prefix_cache_slots, 0)
            self._prefix = PrefixCache(device_rows=n_rows,
                                       hybrid=self._hybrid)
            if n_rows > 0:
                # a DEDICATED state for cached rows: decode_step writes
                # K/V at position ``lengths`` for every row each step,
                # so cached prefixes must live where decode never runs
                self._prefix_state = init_decode_state(
                    cfg, device_batch=n_rows, cache_len=self.e.cache_len)
            placer.cached_prefix_probe = self._prefix.match_len
            if self._executor is not None:
                self._executor.pool.on_evict = self._on_pool_evict

    def _on_pool_evict(self, owner: int) -> None:
        """Pool LRU reclaimed a cached prefix chain — rung 1 of the
        degradation ladder (the cheapest pressure response)."""
        self._prefix.forget_owner(owner, self.stats)
        self.stats.note_pressure("prefix_evict")

    # --- lifecycle views ---------------------------------------------------
    @property
    def queue(self):
        return self.lc.queue

    @property
    def slots(self) -> List[Optional[Request]]:
        return self.lc.slots

    @property
    def host_requests(self) -> Dict[int, Request]:
        return self.lc.host_requests

    @property
    def has_work(self) -> bool:
        return self.lc.has_work

    def submit(self, request: Request) -> None:
        self.lc.submit(request)

    @staticmethod
    def reject(request: Request, reason: str) -> None:
        """Fail a request without admitting it: Phase.FINISHED with
        ``error`` set (surfaced as RequestHandle.failed)."""
        reject(request, reason)

    @staticmethod
    def prompt_reject_reason(prompt_len: int,
                             cache_len: int) -> Optional[str]:
        """The single degenerate-prompt predicate shared by API submit
        and engine admission: None when the prompt is non-empty and
        leaves room to generate at least one token, else the rejection
        reason."""
        if prompt_len < 1:
            return "empty prompt"
        if prompt_len < cache_len - 1:
            return None
        return (f"prompt of {prompt_len} tokens does not fit "
                f"cache_len={cache_len} with room to generate")

    # --- prefill ----------------------------------------------------------
    def _prefill_traced(self, params: ModelParams, tokens, plens):
        # trace-count probe: the body runs only when jit (re)traces,
        # i.e. once per new (bucket_len, batch_bucket) shape pair —
        # surfaced as EngineStats.prefill_compilations
        self._prefill_compiles += 1
        return prefill_bucketed(params, self.cfg, tokens, plens,
                                cache_len=self.e.cache_len)

    # --- decode -------------------------------------------------------------
    def _decode_traced(self, params: ModelParams, tokens, state):
        return decode_step(params, self.cfg, tokens, state)

    def _decode_overlap_traced(self, params: ModelParams, tokens, state,
                               host):
        return decode_step(params, self.cfg, tokens, state, host)

    # --- chunked prefill (fused with decode) ------------------------------
    def _chunk_traced(self, params: ModelParams, ctoks, clens, cstate):
        self._prefill_compiles += 1
        return prefill_chunk(params, self.cfg, ctoks, clens, cstate)

    def _decode_chunk_traced(self, params: ModelParams, tokens, state,
                             ctoks, clens, cstate):
        self._prefill_compiles += 1
        return decode_with_chunked_prefill(params, self.cfg, tokens, state,
                                           None, ctoks, clens, cstate)

    def _decode_overlap_chunk_traced(self, params: ModelParams, tokens,
                                     state, host, ctoks, clens, cstate):
        self._prefill_compiles += 1
        return decode_with_chunked_prefill(params, self.cfg, tokens, state,
                                           host, ctoks, clens, cstate)

    def _splice_device_row(self, state: StackState, sub_entries,
                           row, slot, plen) -> StackState:
        """Scatter one prefilled sub-state row into the shared batch
        state via dynamic_update on donated buffers — no full-state
        copy per admission."""
        def upd(big, small):
            r = jax.lax.dynamic_index_in_dim(small, row, axis=1,
                                             keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(
                big, r.astype(big.dtype), slot, axis=1)
        new_entries = tuple(
            jax.tree.map(upd, entry, sub)
            for entry, sub in zip(state.per_entry, sub_entries))
        lengths = jax.lax.dynamic_update_index_in_dim(
            state.lengths, plen.astype(state.lengths.dtype), slot, axis=0)
        return StackState(per_entry=new_entries, lengths=lengths)

    # --- admission (rule 1: GPU-first + SLO backpressure) -------------------
    def _admit(self) -> List[Request]:
        """Admit queued requests through the lifecycle subsystem:
        KV budgets, slot availability, deadline backpressure and
        preemption are one placement decision.  Returns the requests
        placed this iteration (the scheduler's prefill snapshot)."""
        # breaker open: the host tier is suspect, so new admissions and
        # demotions stay device-only until the cooldown re-probe
        host_ok = self._executor is not None and not self._breaker_open()
        demote = None
        if self.e.preemption and host_ok:
            demote = self._preempt_to_host
        placements = self.lc.admit(
            pool=self._executor.pool if host_ok else None,
            demote=demote, prompt_reject_reason=self.prompt_reject_reason)
        if placements:
            if self._chunked:
                rows = self.lc.stage(placements)
                if self._hybrid:
                    # recycled staging rows still hold the previous
                    # occupant's recurrent carry; stale KV is masked by
                    # length, but a chunk continuation would resume it
                    self._staging_state = zero_recurrent_rows(
                        self.cfg, self._staging_state, rows)
                if self._prefix is not None:
                    seed_prefix_hits(self, placements, rows)
            elif self._bucketed_prefill:
                prefill_batched(self, placements)
            else:
                for req, tier, s in placements:
                    if tier == "device":
                        prefill_into_slot(self, req, s)
                    else:
                        prefill_to_host(self, req, s)
            self.stats.prefill_compilations = self._prefill_compiles
        return [p[0] for p in placements]

    # --- tier moves (the placer decides; the engine moves the KV) ----------
    @spanned("tier_move")
    def _migrate_host_to_device(self, req: Request, slot: int) -> None:
        """Promote a host resident into a freed device slot: gather its
        paged KV through the executor, upload into the slot's
        contiguous cache, and splice recurrent-state rows (hybrids)
        from the host row.  Runs only at cohort token boundaries (or
        for requests outside the in-flight cohort), so no host job can
        touch the chains mid-gather."""
        transition(req, Phase.MIGRATING)
        n = self._executor.pool.lengths[req.request_id]
        self.state = upload_host_kv_to_slot(
            self.cfg, self.state, self._executor.gather_request(
                req.request_id), slot, n,
            host_row=self.e.device_slots + req.slot)
        self._executor.free(req.request_id)
        self.lc.note_migrated(req, slot)

    @spanned("tier_move")
    def _retarget_staging(self, req: Request, slot: int) -> None:
        """Mid-prefill host→device retarget: the staging row's KV
        already lives on device, so the move is pure bookkeeping —
        free the pool chains holding the already-streamed chunks and
        flip the entry's tier; completion will splice into the device
        slot instead of activating a host row."""
        ent = next(self.lc.staging[row] for row in self.lc.staging_order
                   if self.lc.staging[row].req is req)
        transition(req, Phase.MIGRATING)
        self._executor.free(req.request_id)
        self.lc.note_migrated(req, slot, to_prefill=True)
        ent.tier = "device"
        ent.slot = slot

    def _rebalance(self) -> None:
        """Host→device tier rebalancing (NEO's load-aware rule in the
        real engine): promote host residents into freed device slots
        while the shared drain-time predicate says each move pays off.
        Cohort members move only at token boundaries (mid-journey
        attention state cannot migrate)."""
        if not (self.e.tier_rebalance and self._executor is not None):
            return
        lc = self.lc
        while True:
            slot = lc.free_slot()
            if slot is None or lc.queue:
                return
            boundary = self._cohort is None or self._cohort.attn_ptr == -1
            mid_journey = (set(self._cohort.slot_rids)
                           if self._cohort is not None and not boundary
                           else set())
            candidates = [r for r in lc.decoding_hosts()
                          if r.request_id not in mid_journey]
            if self._chunked:
                candidates += [lc.staging[row].req
                               for row in lc.staging_order
                               if lc.staging[row].tier == "host"]
            cand = lc.placer.rebalance_candidate(
                candidates, waiting=len(lc.queue), device_slot_free=True,
                device_batch=sum(r is not None for r in lc.slots))
            if cand is None:
                return
            if cand.phase is Phase.PREFILL:
                self._retarget_staging(cand, slot)
            else:
                self._migrate_host_to_device(cand, slot)

    @spanned("tier_move")
    def _preempt_to_host(self, urgent: Request) -> Optional[int]:
        """Demote the placer-chosen lowest-priority device resident to
        the host tier (the inverse migration: contiguous KV demoted to
        the paged pool, recurrent state spliced into the host row) and
        return its freed device slot; None when preemption cannot
        help the urgent request.

        When the swap cannot progress — no host slot / pool room, a
        lost allocation race — or the perf model prices a replay below
        the KV move, the recompute-from-scratch escape hatch drops the
        victim's KV instead: it re-enters the EDF queue on the
        RECOMPUTE edge and replays prefill + its already-emitted
        tokens deterministically (bit-identical stream)."""
        lc = self.lc
        hslot = lc.free_host_slot()
        residents = [r for r in lc.slots
                     if r is not None and not r.done
                     and r.phase is Phase.DECODE_DEVICE]
        victim = lc.placer.preemption_victim(
            residents, urgent=urgent, host_slot_free=hslot is not None,
            pool_ok=self._executor.pool.can_admit)
        if victim is None:
            return self._recompute_preempt(urgent, residents)
        if self.e.recompute_fallback and lc.placer.prefer_recompute(victim):
            return self._recompute_victim(victim)
        slot = victim.slot
        n = victim.total_len - 1           # cached positions in the slot
        try:
            self._executor.pool.allocate(victim.request_id, n)
        except MemoryError:
            # advisory can_admit lost a race (or the chaos plan failed
            # this allocation mid-flight) — recompute instead of
            # stranding the urgent request behind a full pool
            return self._recompute_victim(victim)
        transition(victim, Phase.PREEMPTED)
        self._executor.migrate_prompt(
            victim.request_id,
            stack_row_kv_to_pool_layers(self.cfg, self.state, slot, n))
        self.state = demote_slot_to_host_row(
            self.cfg, self.state, slot,
            host_row=self.e.device_slots + hslot)
        self.lc.note_preempted(victim, hslot)
        # the cohort picks the demoted request up at the next boundary
        return slot

    def _recompute_preempt(self, urgent: Request,
                           residents: List[Request]) -> Optional[int]:
        """Swap found no victim capacity: pick the structural victim
        (lowest priority, smallest KV) and recompute-preempt it, if
        the escape hatch is enabled and a strictly-lower-priority
        resident exists at all."""
        if not self.e.recompute_fallback:
            return None
        victim = placement.pick_preemption_victim(
            residents, urgent_priority=urgent.priority)
        if victim is None:
            return None
        return self._recompute_victim(victim)

    def _recompute_victim(self, victim: Request) -> Optional[int]:
        """Drop a device resident's KV and requeue it on the RECOMPUTE
        edge; returns its freed slot.  The slot's cache rows need no
        scrub — lengths hygiene zeroes empty slots each step and the
        re-admission prefills fresh KV."""
        slot = victim.slot
        self.lc.note_recomputed(victim)
        return slot

    # --- waits on the host tier ---------------------------------------------
    @contextlib.contextmanager
    def _host_waiting(self):
        """The engine thread blocks on the host tier: span
        ``apex.host_wait``, its seconds counted in ``host_wait_time``."""
        t = time.perf_counter()
        try:
            with span("host_wait"):
                yield
        finally:
            self.stats.host_wait_time += time.perf_counter() - t

    # --- host-tier fault tolerance ------------------------------------------
    def _breaker_open(self) -> bool:
        """True while the host-tier circuit breaker holds the engine in
        GPU_ONLY: no host-job submits, no host placements or demotions,
        until the cooldown elapses and a re-probe is allowed."""
        return (self._executor is not None
                and time.perf_counter() < self._breaker_until)

    def _host_fallback(self, cause: str) -> np.ndarray:
        """Watchdog recovery: abandon the pending host job (stalled
        past its deadline or died with an exception) and rerun it
        synchronously on the engine thread through the executor's
        injection-free path.  ``append_rows`` writes KV at explicit
        positions and never advances lengths, so the rerun is
        idempotent even when the abandoned worker already wrote (or
        later writes) the same rows — tokens stay bit-identical with a
        fault-free run.  Consecutive fallbacks trip the breaker with an
        exponentially growing cooldown."""
        meta = self._pending_meta
        self._executor.cancel(self._pending_job)
        out = self._executor.execute_sync(
            next(self._job_ids), meta["layer"], meta["request_ids"],
            meta["q"], meta["k"], meta["v"], meta["positions"],
            rows=meta["rows"])
        self.stats.host_fallbacks += 1
        causes = self.stats.host_fallback_causes
        causes[cause] = causes.get(cause, 0) + 1
        self._fallback_streak += 1
        # the recovery's wall time says nothing about a healthy host
        # tier — never feed it to the calibrator
        self._pending_host_pred = 0.0
        if self._fallback_streak >= self.e.host_breaker_threshold:
            self._fallback_streak = 0
            delay = self._breaker.next_delay() or self.e.host_breaker_cooldown
            self._breaker_until = time.perf_counter() + delay
            self.stats.host_breaker_trips += 1
        return out

    # --- client aborts ------------------------------------------------------
    def cancel(self, request_id: int) -> bool:
        """Abort a live request wherever it sits — queue, staging row,
        device slot, or host tier — releasing every resource it holds
        (KV budget, slot, pool chains, staging row).  The request
        finishes with ``error='cancelled'``.  Host residents inside an
        in-flight cohort journey defer to the next token boundary
        (membership is frozen mid-journey); everything else is freed
        inline.  Returns True when the request was found live."""
        lc = self.lc
        req = lc.queue.remove(request_id)
        if req is not None:                       # queued (or RECOMPUTE wait)
            reject(req, "cancelled")
            self.stats.cancelled += 1
            return True
        for row in list(lc.staging_order):        # mid-chunked-prefill
            ent = lc.staging[row]
            if ent.req.request_id != request_id:
                continue
            lc.release_staging_row(row)
            req = ent.req
            if ent.tier == "device":
                lc.slots[ent.slot] = None
                lc.admission.release("device", req.kv_reserved)
            else:
                self._executor.free(request_id)
                lc.host_slot_owner.pop(ent.slot, None)
                lc.host_requests.pop(request_id, None)
                lc.admission.release("host", req.kv_reserved)
            req.kv_reserved = 0
            req.slot = None
            reject(req, "cancelled")
            self.stats.cancelled += 1
            return True
        for i, r in enumerate(lc.slots):          # device resident
            if r is not None and r.request_id == request_id and not r.done:
                reject(r, "cancelled")
                lc.admission.release("device", r.kv_reserved)
                lc.slots[i] = None
                r.slot = None
                self.stats.cancelled += 1
                return True
        req = lc.host_requests.get(request_id)    # host resident: deferred
        if req is not None and not req.done:
            req.cancel_requested = True
            self._apply_host_cancels()
            return True
        return False

    def _apply_host_cancels(self) -> None:
        """Finish host residents whose cancel was deferred — safe only
        at a cohort token boundary (attn_ptr == -1), where no host job
        is pending and no recurrent commit is mid-journey.  Runs at the
        top of every step and inline from cancel() (the boundary may
        already hold)."""
        if self._executor is None:
            return
        if self._cohort is not None and self._cohort.attn_ptr != -1:
            return
        lc = self.lc
        doomed = [rid for rid, r in lc.host_requests.items()
                  if r.cancel_requested and not r.done]
        for rid in doomed:
            r = lc.host_requests.pop(rid)
            reject(r, "cancelled")
            lc.admission.release("host", r.kv_reserved)
            self._executor.free(rid)
            lc.host_slot_owner.pop(r.slot, None)
            r.slot = None
            r.kv_reserved = 0
            self.stats.cancelled += 1

    def _refresh_prefix_gauges(self) -> None:
        """Resident-byte gauges of the prefix cache, per tier — kept
        current on every cache mutation (publish/seed/evict/demote) so
        snapshot() never walks the cache itself."""
        if self._prefix is None:
            return
        self.stats.prefix_device_bytes = self._prefix.device_bytes(self)
        self.stats.prefix_host_bytes = self._prefix.host_bytes(self)

    def _refresh_host_pool_gauges(self) -> None:
        """Host-pool byte accounting (hot / compressed / free at the
        pool's *stored* dtype) plus the cold-page compression counters,
        copied onto the stats surface for snapshot()//metrics."""
        if self._executor is None:
            return
        pool = self._executor.pool
        b = pool.byte_stats()
        self.stats.host_pool_hot_bytes = b["hot"]
        self.stats.host_pool_compressed_bytes = b["compressed"]
        self.stats.host_pool_free_bytes = b["free"]
        self.stats.host_kv_dtype_bytes = pool.kv_dtype_bytes
        self.stats.host_pages_compressed = pool.pages_compressed
        self.stats.host_pages_decompressed = pool.pages_decompressed
        self.stats.host_compressed_ratio_ewma = pool.compressed_ratio_ewma

    # --- cohort management ------------------------------------------------
    def _ensure_cohort(self) -> Optional[Cohort]:
        """(Re)build the host cohort — ONLY at token boundaries
        (attn_ptr == -1): recurrent-state commits are not idempotent, so
        membership must stay frozen mid-journey."""
        c = self._cohort
        if c is not None and c.attn_ptr != -1:
            return c
        # done requests (e.g. clamped to one token, satisfied by the
        # prefill) retire this step — never enroll them in a journey;
        # chunked admissions still mid-prefill aren't decoding yet
        hosts = self.lc.host_requests
        slot_rids = [rid if rid >= 0
                     and not hosts[rid].done
                     and hosts[rid].phase is Phase.DECODE_HOST
                     else -1
                     for rid in (self.lc.host_slot_owner.get(i, -1)
                                 for i in range(self.e.host_slots))]
        last_tokens = [hosts[rid].output[-1] if rid >= 0 else 0
                       for rid in slot_rids]
        positions = [hosts[rid].total_len - 1 if rid >= 0 else 0
                     for rid in slot_rids]
        self._cohort = self._overlap.build_cohort(
            self.params.embedding["embed"], slot_rids, last_tokens,
            positions)
        return self._cohort

    # --- Algorithm 1 ---------------------------------------------------------
    def _schedule(self, admitted: List[Request],
                  active_rows: List[int]) -> Optional[Decision]:
        """Build queue snapshots and run Algorithm 1 for this iteration."""
        if self.scheduler is None:
            return None
        prefill_q, decode_gpu, decode_cpu, backlog = \
            self.lc.schedule_snapshots(admitted, active_rows,
                                       chunked=self._chunked)
        if self._chunked:
            # a chunk-aware scheduler prices the chunk inside schedule().
            # A legacy injected scheduler never sees the chunk kwargs, so
            # hand it the chunk capacity the device will compute —
            # otherwise predicted_time omits the chunk work and skews the
            # calibrator low on every staging iteration.
            prefill_tokens = (self._chunk_capacity()
                              if prefill_q and not self._sched_chunk_aware
                              else 0)
        else:
            prefill_tokens = sum(r.prompt_len for r in admitted)
        if not (prefill_q or decode_gpu or decode_cpu):
            return None                      # idle iteration: nothing to decide
        contexts = [r.total_len for r in decode_gpu + decode_cpu]
        mean_context = float(np.mean(contexts)) if contexts else 1.0
        kw = {}
        if self._sched_chunk_aware:
            kw = dict(chunk_backlog_tokens=backlog,
                      chunk_capacity=(self._chunk_capacity()
                                      if self._chunked else 0))
        decision = self.scheduler.schedule(
            prefill_q, decode_gpu, decode_cpu,
            mean_context=max(mean_context, 1.0),
            prefill_tokens=prefill_tokens, **kw)
        self.stats.record_decision(decision)
        return decision

    # --- chunked-prefill planning -------------------------------------------
    def _chunk_capacity(self) -> int:
        """The most one chunk call advances: ``chunk_tokens`` on every
        staging row, which is what the fused chunk program's pinned
        buffer holds (``plan_chunks``)."""
        return len(self.lc.staging) * self.e.chunk_tokens

    # --- one engine iteration ------------------------------------------------
    def step(self) -> None:
        with step_span(self.stats.iterations):
            self._step()

    def _step(self) -> None:
        if self._executor is not None and self.e.cold_page_compress_after > 0:
            # outside the timed section: compression is pool maintenance,
            # not iteration work the calibrator should learn from
            self._executor.pool.maybe_compress_cold()
        t0 = time.perf_counter()
        if self._faults is not None:
            spike = self._faults.on_engine_step()
            if spike is not None:
                # after t0 on purpose: the spike lands inside the timed
                # section so the calibrator sees it like a real stall
                time.sleep(spike)
        with span("admit"):
            self._apply_host_cancels()
            admitted = self._admit()
        with span("rebalance"):
            self._rebalance()
        with span("schedule"):
            # rows whose request already reached max_new_tokens (possible
            # straight out of prefill when the clamp left room for exactly
            # one token) must not ride this iteration's decode batch — they
            # retire at the end of the step without over-generating.
            # Chunked admissions still mid-prefill aren't decoding either.
            active_rows = [i for i, r in enumerate(self.lc.slots)
                           if r is not None and not r.done
                           and r.phase is Phase.DECODE_DEVICE]
            decision = self._schedule(admitted, active_rows)
            plan = None
            if self._chunked and self.lc.staging_order:
                budget = (decision.chunk_tokens
                          if decision is not None and self._sched_chunk_aware
                          else chunk_budget(self.lc.staging_backlog(),
                                            self._chunk_capacity()))
                plan = self.lc.plan_chunks(budget)
        with span("dispatch"):
            tokens = np.zeros((self.e.device_slots,), np.int32)
            for i in active_rows:
                tokens[i] = self.lc.slots[i].output[-1]
            # lengths hygiene for empty slots
            mask = np.zeros((self.e.device_slots,), bool)
            mask[active_rows] = True
            lengths = jnp.where(jnp.asarray(mask), self.state.lengths, 0)
            self.state = StackState(per_entry=self.state.per_entry,
                                    lengths=lengths)

            cohort = self._ensure_cohort() if self.e.enable_offload else None
            if cohort is not None:
                wait = (decision is not None
                        and decision.strategy == StrategyKind.ASYM_PIPELINE)
                self._step_overlap(jnp.asarray(tokens), cohort, active_rows,
                                   wait=wait, plan=plan)
            elif active_rows or plan is not None:
                self._step_device_only(jnp.asarray(tokens), active_rows, plan)
        if plan is not None:
            self.stats.prefill_chunks += len(plan.rows)
            self.stats.chunked_prefill_tokens += sum(plan.lens)
            self.stats.chunk_iterations += 1
            self.stats.chunk_buffer_tokens += plan.tokens.size
            if active_rows or cohort is not None:
                self.stats.chunk_co_run_iterations += 1
            self.stats.prefill_compilations = self._prefill_compiles
        self.stats.iterations += 1
        self.lc.note_iteration()
        dt = time.perf_counter() - t0
        self.stats.wall_time += dt
        predicted = getattr(decision, "predicted_time", 0.0) \
            if decision is not None else 0.0
        if predicted > 0.0:
            self.stats.predicted_time += predicted
            self.stats.observed_time += dt
            if self._calibrator is not None:
                self._calibrator.observe_step(predicted, dt)
                self.stats.step_error_ewma = self._calibrator.step_error_ewma
        with span("retire"):
            self.lc.retire(free_host=(self._executor.free
                                      if self._executor is not None
                                      else lambda rid: None),
                           publish=((lambda r: publish_retired(self, r))
                                    if self._prefix is not None else None))
        # the cohort rebuilds itself at the next token boundary
        # (_ensure_cohort); completions always leave attn_ptr == -1

    @spanned("sync")
    def _commit_device(self, logits, active_rows) -> None:
        toks = sample(logits[: self.e.device_slots],
                      temperature=self.e.temperature)
        toks = np.asarray(toks)
        now = time.perf_counter()
        for i in active_rows:
            r = self.lc.slots[i]
            r.output.append(int(toks[i]))
            self.stats.device_tokens += 1
            if r.first_token_time is None:
                r.first_token_time = now

    def _idle_host_io(self):
        """A no-cohort HostIO (all rows invalid, no emit/consume/commit
        window): hybrid stacks with offload enabled must decode through
        the unified overlap step even with no live cohort — their
        recurrent state spans the host rows, and the host=None path
        only carries device-batch activations.  Constant per config,
        so it is built once and cached."""
        if self._idle_io is None:
            bc = self.e.host_slots
            emb = self.params.embedding["embed"]
            self._idle_io = HostIO(
                x_carry=jnp.zeros((bc, self.cfg.d_model), emb.dtype),
                positions=jnp.zeros((bc,), jnp.int32),
                attn_in=jnp.zeros((bc, self.cfg.num_heads,
                                   self.cfg.resolved_head_dim), jnp.float32),
                consume_layer=jnp.int32(-1), emit_layer=jnp.int32(-1),
                window_start=jnp.int32(0), window_end=jnp.int32(0),
                row_valid=jnp.zeros((bc,), bool))
        return self._idle_io

    def _step_device_only(self, tokens, active_rows,
                          plan: Optional[ChunkPlan] = None) -> None:
        if plan is None:
            if self._executor is not None and self._hybrid:
                logits, self.state, _, _ = self._decode_overlap_fn(
                    self.params, tokens, self.state, self._idle_host_io())
            else:
                logits, self.state, _, _ = self._decode_fn(
                    self.params, tokens, self.state)
            self._commit_device(logits, active_rows)
            return
        if not active_rows:
            clogits, self._staging_state = self._chunk_jit(
                self.params, jnp.asarray(plan.tokens),
                jnp.asarray(plan.clens), self._staging_state)
            finish_chunks(self, plan, clogits)
            return
        # fused step: the decode batch and the prefill chunk compile
        # and dispatch as ONE device program
        if self._executor is not None and self._hybrid:
            # same routing as the plan-less branch: recurrent state
            # spans the host rows, so decode must take the unified
            # overlap step even with no live cohort
            logits, self.state, _, _, clogits, self._staging_state = \
                self._decode_overlap_chunk_jit(
                    self.params, tokens, self.state, self._idle_host_io(),
                    jnp.asarray(plan.tokens), jnp.asarray(plan.clens),
                    self._staging_state)
        else:
            logits, self.state, _, _, clogits, self._staging_state = \
                self._decode_chunk_jit(self.params, tokens, self.state,
                                       jnp.asarray(plan.tokens),
                                       jnp.asarray(plan.clens),
                                       self._staging_state)
        self._commit_device(logits, active_rows)
        finish_chunks(self, plan, clogits)

    def _step_overlap(self, tokens, cohort: Cohort, active_rows,
                      *, wait: bool = False,
                      plan: Optional[ChunkPlan] = None) -> None:
        """One hybrid iteration (paper §3.3).

        ``wait=False`` — Asynchronous Overlap: poll the pending host
        job; if late, host rows ride along untouched (the §3.4
        re-check).  ``wait=True`` — Asymmetric Pipelining at engine
        granularity: block until the host result is ready, putting host
        attention between the two device sub-steps (on the critical
        path) so every cycle advances the cohort one layer.

        The handoff is non-blocking end to end: the host job is
        submitted with the *device* QKV arrays straight from the jitted
        step (the device→host transfer happens inside the executor
        worker, overlapped with this iteration's logits sync and the
        next device dispatch) — the engine never forces a sync on QKV.
        """
        ctl = self._overlap
        valid = cohort.valid_slots
        if self._pending_job is not None:
            fell_back = False
            try:
                if wait:
                    timeout = 120.0
                    if self.e.recompute_fallback and self._pending_deadline:
                        timeout = max(
                            self._pending_deadline - time.perf_counter(),
                            0.001)
                    with self._host_waiting():
                        out = self._executor.result(self._pending_job,
                                                    timeout=timeout)
                else:
                    out = self._executor.poll(self._pending_job)
                    if out is None and self.e.recompute_fallback \
                            and self._pending_deadline \
                            and time.perf_counter() > self._pending_deadline:
                        raise TimeoutError(
                            f"host job {self._pending_job} missed its "
                            "watchdog deadline")
            except (RuntimeError, TimeoutError) as exc:
                # worker exception (RuntimeError via _unwrap) or
                # watchdog expiry: abandon the job and recompute its
                # attention exactly on this thread.  Without the
                # fallback the legacy contract holds — host faults
                # fail the engine loudly.
                if not self.e.recompute_fallback:
                    raise
                err = exc.__cause__
                with self._host_waiting():
                    out = self._host_fallback(
                        "timeout" if err is None
                        else f"{type(err).__name__}: {err}"[:160])
                fell_back = True
            if out is None:
                host_idle = ctl.host_io(cohort)._replace(
                    consume_layer=jnp.int32(-1), emit_layer=jnp.int32(-1),
                    window_start=jnp.int32(0), window_end=jnp.int32(0))
                if plan is not None:
                    logits, self.state, _, xf, clogits, \
                        self._staging_state = self._decode_overlap_chunk_jit(
                            self.params, tokens, self.state, host_idle,
                            jnp.asarray(plan.tokens), jnp.asarray(plan.clens),
                            self._staging_state)
                else:
                    logits, self.state, _, xf = self._decode_overlap_fn(
                        self.params, tokens, self.state, host_idle)
                self._commit_device(logits, active_rows)
                if plan is not None:
                    finish_chunks(self, plan, clogits)
                return
            buf = np.zeros(cohort.attn_in.shape, np.float32)
            buf[np.asarray(valid, np.int64)] = out
            cohort.attn_in = jnp.asarray(buf)
            self._executor.recycle(out)
            self._pending_job = None
            self._pending_meta = None
            self._pending_deadline = 0.0
            if not fell_back:
                # a healthy consume closes the fallback streak and
                # resets the breaker's exponential cooldown
                self._fallback_streak = 0
                self._breaker.record_success()
            # host-side calibration against the executor's *compute*
            # time only — the device→host transfer share is accounted
            # separately so t_catt stays an attention-cost estimate
            if self._calibrator is not None and self._pending_host_pred > 0:
                observed = (self._executor.compute_time
                            - self._host_compute_seen)
                self._calibrator.observe_host(self._pending_host_pred,
                                              observed)
            self._host_compute_seen = self._executor.compute_time
            self._pending_host_pred = 0.0

        io = ctl.host_io(cohort)
        emit_layer = ctl.emit_layer(cohort)
        completes = ctl.completes_token(cohort)
        clogits = None
        if plan is not None:
            # fused: decode batch + host-cohort ride-along + prefill
            # chunk in ONE device program — host attention overlaps
            # the chunk's compute too (the widened rule-3 window)
            logits, self.state, qkv, x_final, clogits, \
                self._staging_state = self._decode_overlap_chunk_jit(
                    self.params, tokens, self.state, io,
                    jnp.asarray(plan.tokens), jnp.asarray(plan.clens),
                    self._staging_state)
        else:
            logits, self.state, qkv, x_final = self._decode_overlap_fn(
                self.params, tokens, self.state, io)
        if emit_layer >= 0 and self._breaker_open():
            # breaker open: the async host tier is suspect, so compute
            # this layer's host attention synchronously at the emit
            # point (ASYM_PIPELINE semantics, injection-free path) —
            # in-flight cohort journeys finish exactly without trusting
            # a worker that just stalled or died
            idx = np.asarray(valid, np.int64)
            with self._host_waiting():
                out = self._executor.execute_sync(
                    next(self._job_ids), emit_layer, cohort.request_ids,
                    qkv.q, qkv.k, qkv.v, cohort.positions[idx], rows=idx)
            buf = np.zeros(cohort.attn_in.shape, np.float32)
            buf[idx] = out
            cohort.attn_in = jnp.asarray(buf)
            self._executor.recycle(out)
            # keep the calibrator's compute-time watermark current so
            # the next async consume doesn't attribute this sync work
            self._host_compute_seen = self._executor.compute_time
        elif emit_layer >= 0:
            # submit BEFORE the logits sync in _commit_device: the
            # worker materializes QKV and computes host attention while
            # the engine is still waiting on device logits
            job = next(self._job_ids)
            idx = np.asarray(valid, np.int64)
            positions = cohort.positions[idx]
            self._executor.submit(
                job, emit_layer, cohort.request_ids,
                qkv.q, qkv.k, qkv.v, positions, rows=idx)
            self._pending_job = job
            # watchdog stash: everything needed to abandon this job and
            # recompute it exactly on the engine thread
            self._pending_meta = dict(
                layer=emit_layer, request_ids=cohort.request_ids,
                q=qkv.q, k=qkv.k, v=qkv.v, positions=positions, rows=idx)
            pred = 0.0
            if self._calibrator is not None:
                mean_pos = float(np.mean(positions + 1))
                pred = self._calibrator.t_catt(len(valid), mean_pos,
                                               layers=1)
                self._pending_host_pred = pred
            # the watchdog clock starts below, once this step's outputs
            # (the job's inputs) exist: the device step's own time is
            # never charged to the host job
            self._pending_timeout = max(pred * self.e.host_job_slack,
                                        self.e.host_job_min_timeout)
            self._pending_deadline = 0.0
        self._commit_device(logits, active_rows)
        if self._pending_job is not None and not self._pending_deadline:
            self._pending_deadline = time.perf_counter() \
                + self._pending_timeout
        cohort.x_carry = x_final[self.e.device_slots:]
        if completes:
            with span("host_token"):
                row_idx = [self.e.device_slots + i for i in valid]
                toks = np.asarray(sample(logits[jnp.asarray(row_idx)],
                                         temperature=self.e.temperature))
                emb = self.params.embedding["embed"]
                for j, i in enumerate(valid):
                    r = self.lc.host_requests[cohort.slot_rids[i]]
                    r.output.append(int(toks[j]))
                    self.stats.host_tokens += 1
                    cohort.positions[i] += 1
                # one stacked gather+scatter for the cohort's fresh
                # embeddings (vs bc separate .at[i].set dispatches)
                cohort.x_carry = cohort.x_carry.at[jnp.asarray(valid)].set(
                    jnp.take(emb, jnp.asarray(toks), axis=0
                             ).astype(cohort.x_carry.dtype))
                self._executor.advance_token(cohort.request_ids)
                cohort.attn_in = jnp.zeros_like(cohort.attn_in)
        for rid in cohort.request_ids:
            self.lc.host_requests[rid].layer_progress = \
                ctl.layer_progress(cohort)
        ctl.advance(cohort)
        if plan is not None:
            finish_chunks(self, plan, clogits)

    # --- driver -------------------------------------------------------------
    def run(self, requests: List[Request], *, max_iterations: int = 100000
            ) -> EngineStats:
        for r in requests:
            self.submit(r)
        it = 0
        while self.has_work and it < max_iterations:
            self.step()
            it += 1
        if self._executor is not None:
            self.stats.host_busy_time = self._executor.busy_time
            self.stats.host_transfer_time = self._executor.transfer_time
            self.stats.host_device_wait_time = \
                self._executor.device_wait_time
        self._refresh_host_pool_gauges()
        return self.stats

    def shutdown(self) -> None:
        if self._executor is not None:
            self._executor.shutdown()
