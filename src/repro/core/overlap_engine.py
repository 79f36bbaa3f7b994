"""Asynchronous Overlap runtime (paper §3.3 + §4.2).

Two pieces:

  * ``OverlapController`` — the deferred-synchronization state machine.
    A *cohort* of host-offloaded requests advances one attention layer
    per engine iteration: it consumes the host-computed attention for
    layer k (produced during the previous iteration), commits every
    device-computable layer in [k, next_attn(k)), and emits fresh
    Q/K/V at next_attn(k).  Layers between attention layers (Mamba/FFN
    in hybrids) commit on-device in the same window — the host stalls
    only attention.  A token completes every (num_attn_layers + 1)
    iterations.
  * ``HostExecutor`` — the parallel host attention runtime (the
    paper's Pybind11/GIL-release runtime, rendered as a dispatcher
    thread plus a worker pool whose numpy/BLAS kernels release the GIL
    natively).  It owns the host paged KV pool, performs the
    device→host QKV transfer *inside* the worker (non-blocking
    handoff), appends each emitted K/V with one vectorized write,
    shards a job's cohort rows across workers, and buffers results for
    the next iteration.

``scratch/validate_overlap.py``-style equivalence (host-offloaded rows
produce bit-identical tokens to device rows) is enforced in
tests/test_overlap.py.
"""
from __future__ import annotations

import dataclasses
import os
import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spans import span
from repro.kernels.ops import host_paged_attention_numpy
from repro.models.config import BlockKind, ModelConfig
from repro.models.kv_cache import PagedKVPool
from repro.models.transformer import HostIO


@dataclasses.dataclass
class Cohort:
    """A set of host-offloaded requests progressing in lockstep.

    Rows are *stable host slots*: slot i occupies unified-batch row
    device_slots + i, and its recurrent states live at that row in the
    device state — so membership may only change at token boundaries
    (attn_ptr == -1), and empty slots carry rid -1 with row_valid False.
    """

    slot_rids: List[int]             # (Bc,) request id per slot, -1 = empty
    positions: np.ndarray            # (Bc,) position of the token in flight
    x_carry: jnp.ndarray             # (Bc, d) residual carry
    attn_in: jnp.ndarray             # (Bc, H, D) host result for consume layer
    attn_ptr: int = -1               # index into attn_layers; -1 = token start

    @property
    def valid_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_rids) if r >= 0]

    @property
    def request_ids(self) -> List[int]:
        return [r for r in self.slot_rids if r >= 0]

    @property
    def size(self) -> int:
        return len(self.request_ids)

    def row_valid(self) -> np.ndarray:
        return np.asarray([r >= 0 for r in self.slot_rids], bool)


class OverlapController:
    """Computes per-iteration HostIO windows and advances cohorts."""

    def __init__(self, cfg: ModelConfig) -> None:
        self.cfg = cfg
        self.attn_layers: Tuple[int, ...] = cfg.attn_layer_indices
        if not self.attn_layers:
            raise ValueError(
                f"{cfg.name}: no attention layers — APEX offload inapplicable")
        self.num_layers = cfg.num_layers

    @property
    def iterations_per_token(self) -> int:
        return len(self.attn_layers) + 1

    def host_io(self, cohort: Cohort) -> HostIO:
        a = self.attn_layers
        if cohort.attn_ptr < 0:
            consume, ws, we = -1, 0, a[0]
            emit = a[0]
        else:
            consume = a[cohort.attn_ptr]
            ws = consume
            nxt = (a[cohort.attn_ptr + 1]
                   if cohort.attn_ptr + 1 < len(a) else self.num_layers)
            we = nxt
            emit = nxt if cohort.attn_ptr + 1 < len(a) else -1
        return HostIO(
            x_carry=cohort.x_carry,
            positions=jnp.asarray(cohort.positions, jnp.int32),
            attn_in=cohort.attn_in,
            consume_layer=jnp.int32(consume), emit_layer=jnp.int32(emit),
            window_start=jnp.int32(ws), window_end=jnp.int32(we),
            row_valid=jnp.asarray(cohort.row_valid()))

    def emit_layer(self, cohort: Cohort) -> int:
        """Absolute layer whose QKV this iteration emits (-1 = none)."""
        a = self.attn_layers
        if cohort.attn_ptr < 0:
            return a[0]
        if cohort.attn_ptr + 1 < len(a):
            return a[cohort.attn_ptr + 1]
        return -1

    def completes_token(self, cohort: Cohort) -> bool:
        """True if this iteration commits the final layer window."""
        return cohort.attn_ptr == len(self.attn_layers) - 1

    def advance(self, cohort: Cohort) -> None:
        cohort.attn_ptr = (-1 if self.completes_token(cohort)
                           else cohort.attn_ptr + 1)

    def layer_progress(self, cohort: Cohort) -> int:
        """Layers completed for the in-flight token (scheduler rule 4)."""
        if cohort.attn_ptr < 0:
            return 0
        a = self.attn_layers
        return (a[cohort.attn_ptr + 1]
                if cohort.attn_ptr + 1 < len(a) else self.num_layers)

    def build_cohort(self, emb: jnp.ndarray, slot_rids: List[int],
                     last_tokens: Sequence[int],
                     positions: Sequence[int]) -> Optional[Cohort]:
        """Assemble a fresh token-boundary cohort from per-slot
        membership: ``slot_rids[i] = -1`` marks an empty host slot,
        and ``last_tokens``/``positions`` carry the valid slots'
        in-flight token state.  Returns None for an all-empty set."""
        if all(r < 0 for r in slot_rids):
            return None
        bc = len(slot_rids)
        valid_mask = np.asarray([r >= 0 for r in slot_rids], bool)
        # one stacked gather for the whole cohort (a per-row .at[i].set
        # loop dispatches bc separate device ops); empty rows stay zero
        x_carry = jnp.where(
            jnp.asarray(valid_mask)[:, None],
            jnp.take(emb, jnp.asarray(np.asarray(last_tokens, np.int32)),
                     axis=0),
            jnp.zeros((), emb.dtype)).astype(emb.dtype)
        return Cohort(
            slot_rids=list(slot_rids),
            positions=np.asarray(positions, np.int64), x_carry=x_carry,
            attn_in=jnp.zeros((bc, self.cfg.num_heads,
                               self.cfg.resolved_head_dim), jnp.float32))


@dataclasses.dataclass
class _Job:
    job_id: int
    layer: int                       # absolute layer index of the QKV
    request_ids: List[int]
    q: Any                           # (Bc, H, D)  — jax or numpy; the
    k: Any                           # (Bc, KV, D)   device→host transfer
    v: Any                           #               happens in the worker
    positions: np.ndarray            # (n,) token positions of valid rows
    rows: Optional[np.ndarray]       # (n,) valid row indices into q/k/v
    # False for watchdog-fallback / breaker-open synchronous runs: the
    # recovery path must not re-enter fault injection
    inject: bool = True


def stack_row_kv_to_pool_layers(cfg: ModelConfig, state: Any, row: int,
                                plen: int, start: int = 0) -> List[tuple]:
    """Host (numpy) copies of one state row's attention-KV span
    ``[start, plen)``, as the per-attention-layer [(k, v), ...] list
    ``HostExecutor.migrate_prompt`` expects, in absolute
    attention-layer order.

    ``state`` is any ``StackState``-shaped object (the engine's shared
    decode state or its chunked-prefill staging state); ``start > 0``
    extracts one chunk of an in-progress prefill.  This is the gather
    side of every device→host KV move: post-prefill migration, chunk
    streaming, and decode-time preemption.
    """
    per_layer = []
    for j, kind in enumerate(cfg.block_pattern):
        if kind != BlockKind.ATTN:
            continue
        k = np.asarray(state.per_entry[j].k[:, row, start:plen], np.float32)
        v = np.asarray(state.per_entry[j].v[:, row, start:plen], np.float32)
        for g in range(cfg.num_groups):
            per_layer.append((k[g], v[g]))
    # per_layer is grouped by entry then g; reorder to absolute
    # attention-layer order
    ordered: List[Any] = [None] * cfg.num_attn_layers
    idx = 0
    for j, kind in enumerate(cfg.block_pattern):
        if kind != BlockKind.ATTN:
            continue
        for g in range(cfg.num_groups):
            abs_layer = g * cfg.pattern_period + j
            ordered[cfg.attn_layer_indices.index(abs_layer)] = per_layer[idx]
            idx += 1
    return ordered


def _as_f32(a) -> np.ndarray:
    """Materialize on host as float32 — a no-op (no copy) when the
    input already is a float32 numpy array; for jax arrays this is the
    device→host transfer and belongs on the worker thread."""
    if isinstance(a, np.ndarray) and a.dtype == np.float32:
        return a
    return np.asarray(a, np.float32)


class HostExecutor:
    """Parallel host-attention runtime owning the paged KV pool.

    ``submit`` is non-blocking and accepts device (jax) arrays: the
    device→host transfer runs inside the worker, overlapped with the
    engine's *next* device dispatch — the engine never syncs on QKV.
    A job's cohort rows are sharded across ``workers`` threads
    (numpy/BLAS releases the GIL, so shards genuinely run in parallel)
    into disjoint views of a preallocated per-job output buffer.
    ``result`` blocks only if the host is genuinely the straggler, in
    which case the engine's re-check semantics (paper §3.4 end) apply.

    Host-busy accounting is split so the calibrator's ``t_catt`` stays
    honest: ``transfer_time`` (device→host materialization, of which
    ``device_wait_time`` waits for the device step that makes the QKV)
    vs ``compute_time`` (KV append + paged attention); ``busy_time`` is
    their sum.  Callers may hand consumed result buffers back through
    ``recycle`` — unreturned buffers are simply allocated per job.
    """

    def __init__(self, cfg: ModelConfig, pool: PagedKVPool,
                 *, synchronous: bool = False, workers: int = 0,
                 faults: Any = None) -> None:
        self.cfg = cfg
        self.pool = pool
        self.page_size = pool.page_size
        self.synchronous = synchronous
        # duck-typed FaultInjector (repro.serving.faults) or None; only
        # its on_host_job() hook is called, from _execute
        self.faults = faults
        if workers <= 0:     # leave a core for the device dispatch thread
            workers = max(1, (os.cpu_count() or 2) - 1)
        self.workers = workers
        self._shards: Optional[ThreadPoolExecutor] = (
            ThreadPoolExecutor(max_workers=workers,
                               thread_name_prefix="host-attn")
            if workers > 1 else None)
        self._results: Dict[int, np.ndarray] = {}
        self._abandoned: set = set()
        self._lock = threading.Lock()
        self._done = threading.Condition(self._lock)
        self._queue: "queue.Queue[Optional[_Job]]" = queue.Queue()
        self._free_bufs: Dict[tuple, List[np.ndarray]] = {}
        self._transfer_time = 0.0
        self._device_wait_time = 0.0
        self._compute_time = 0.0
        self._worker: Optional[threading.Thread] = None
        if not synchronous:
            self._worker = threading.Thread(target=self._run, daemon=True)
            self._worker.start()

    # --- layer index mapping -------------------------------------------------
    def _pool_layer(self, abs_layer: int) -> int:
        """Host pool indexes attention layers densely (0..n_attn-1)."""
        return self.cfg.attn_layer_indices.index(abs_layer)

    # --- API -------------------------------------------------------------------
    def submit(self, job_id: int, layer: int, request_ids: Sequence[int],
               q, k, v, positions, *, rows=None) -> None:
        """Enqueue one layer's host attention for a cohort.

        q/k/v may be jax device arrays covering the *full* cohort;
        ``rows`` selects the valid slots after the worker materializes
        them on host.  positions: (len(request_ids),) — already
        restricted to valid rows.
        """
        job = _Job(job_id, layer, list(request_ids), q, k, v,
                   np.asarray(positions),
                   None if rows is None else np.asarray(rows, np.int64))
        if self.synchronous:
            self._execute(job)
        else:
            self._queue.put(job)

    @staticmethod
    def _unwrap(job_id: int, out):
        # a failed job publishes its exception as the result so the
        # engine fails loudly at the next poll instead of treating the
        # job as forever-late (silent ride-along livelock)
        if isinstance(out, BaseException):
            raise RuntimeError(f"host job {job_id} failed") from out
        return out

    def result(self, job_id: int, timeout: Optional[float] = None
               ) -> np.ndarray:
        with self._done:
            while job_id not in self._results:
                if not self._done.wait(timeout):
                    raise TimeoutError(f"host job {job_id} not ready")
            return self._unwrap(job_id, self._results.pop(job_id))

    def poll(self, job_id: int) -> Optional[np.ndarray]:
        """Non-blocking readiness check (the paper's GPU re-check)."""
        with self._lock:
            return self._unwrap(job_id, self._results.pop(job_id, None))

    def cancel(self, job_id: int) -> None:
        """Abandon a submitted job: an already-published result is
        discarded (buffer recycled), a still-in-flight job's eventual
        publish is dropped at the publish site.  Safe even when the
        abandoned worker is mid-write — ``append_rows`` writes at
        explicit positions, so the watchdog's fallback recompute
        rewrites the very same values (idempotent)."""
        with self._done:
            out = self._results.pop(job_id, None)
            if out is not None:
                if isinstance(out, np.ndarray):
                    self._free_bufs.setdefault(out.shape, []).append(out)
                return
            self._abandoned.add(job_id)

    def execute_sync(self, job_id: int, layer: int,
                     request_ids: Sequence[int], q, k, v, positions,
                     *, rows=None) -> np.ndarray:
        """Run one cohort-layer attention job on the CALLER's thread
        and return its output buffer directly (caller recycles it).

        This is the watchdog's exact GPU-side* recovery path and the
        breaker-open emit path: same transfer, same idempotent KV
        append, same paged-attention kernel as the async route — so
        the tokens are bit-identical by construction — but fault
        injection is bypassed (the recovery path must not fail the
        recovery).  (*engine-thread; the KV source of truth is the
        paged pool either way.)"""
        job = _Job(job_id, layer, list(request_ids), q, k, v,
                   np.asarray(positions),
                   None if rows is None else np.asarray(rows, np.int64),
                   inject=False)
        self._execute(job)
        with self._done:
            return self._unwrap(job_id, self._results.pop(job_id))

    def recycle(self, buf: np.ndarray) -> None:
        """Return a consumed result buffer for reuse by later jobs."""
        with self._lock:
            self._free_bufs.setdefault(buf.shape, []).append(buf)

    def migrate_prompt(self, request_id: int, per_layer_kv) -> None:
        """Move a prefilled request's KV to the host pool.

        per_layer_kv: list over attention layers of (k, v) arrays of
        shape (T, KV, D).  The request's chains may already be
        reserved (the engine allocates at placement time).
        """
        t = per_layer_kv[0][0].shape[0]
        if request_id not in self.pool.lengths:
            self.pool.allocate(request_id, t)
        n_layers = len(per_layer_kv)
        for li, (k, v) in enumerate(per_layer_kv):
            self.pool.write_prompt(request_id, li, _as_f32(k), _as_f32(v),
                                   advance=(li == n_layers - 1))

    def gather_request(self, request_id: int) -> List[tuple]:
        """Materialize a resident request's full per-attention-layer
        [(K, V), ...] from the paged pool (dense attention-layer
        order) — the gather side of a host→device migration.  Safe
        only when no in-flight job can touch this request's chains
        (the engine migrates at cohort token boundaries)."""
        return [self.pool.gather(request_id, li)
                for li in range(self.cfg.num_attn_layers)]

    def free(self, request_id: int) -> None:
        self.pool.free(request_id)

    def shutdown(self) -> None:
        if self._worker is not None:
            self._queue.put(None)
            self._worker.join(timeout=5)
        if self._shards is not None:
            self._shards.shutdown(wait=False)

    @property
    def busy_time(self) -> float:
        return self._transfer_time + self._compute_time

    @property
    def transfer_time(self) -> float:
        """Seconds spent materializing device QKV on the host: waiting
        for the device step that makes it, then copying it."""
        return self._transfer_time

    @property
    def device_wait_time(self) -> float:
        """The part of ``transfer_time`` spent waiting for the device
        step that makes a job's QKV."""
        return self._device_wait_time

    @property
    def compute_time(self) -> float:
        """Seconds of actual host attention work (append + paged attn)."""
        return self._compute_time

    # --- worker -------------------------------------------------------------
    def _run(self) -> None:
        while True:
            job = self._queue.get()
            if job is None:
                return
            try:
                self._execute(job)
            except BaseException as e:          # noqa: BLE001 — surfaced
                # publish the failure as the job's result (see _unwrap)
                # and keep the dispatcher alive for subsequent jobs
                with self._done:
                    if job.job_id in self._abandoned:
                        self._abandoned.discard(job.job_id)
                    else:
                        self._results[job.job_id] = e
                    self._done.notify_all()

    def _out_buffer(self, shape: tuple) -> np.ndarray:
        with self._lock:
            free = self._free_bufs.get(shape)
            if free:
                return free.pop()
        return np.empty(shape, np.float32)

    def _execute(self, job: _Job) -> None:
        import time
        if job.inject and self.faults is not None:
            self.faults.on_host_job()
        with span("host_job", job=job.job_id, layer=job.layer):
            t0 = time.perf_counter()
            # device→host transfer (no-op for float32 numpy inputs): doing
            # it here — not at submit — is the non-blocking handoff; the
            # engine is already dispatching the next device step.  Device
            # QKV first has to exist: the wait for the step that makes it
            # is kept apart from the copy itself.
            with span("host_job.device_wait"):
                jax.block_until_ready([a for a in (job.q, job.k, job.v)
                                       if isinstance(a, jax.Array)])
            t_ready = time.perf_counter()
            with span("host_job.copy"):
                q, k, v = _as_f32(job.q), _as_f32(job.k), _as_f32(job.v)
                if job.rows is not None:
                    q, k, v = q[job.rows], k[job.rows], v[job.rows]
            t1 = time.perf_counter()
            with span("host_job.attention"):
                out = self._attend(job, q, k, v)
            t2 = time.perf_counter()
        with self._done:
            if job.job_id in self._abandoned:
                # watchdog gave up on this job; its (identical) output
                # was recomputed already — drop the late publish
                self._abandoned.discard(job.job_id)
                self._free_bufs.setdefault(out.shape, []).append(out)
            else:
                self._results[job.job_id] = out
            self._device_wait_time += t_ready - t0
            self._transfer_time += t1 - t0
            self._compute_time += t2 - t1
            self._done.notify_all()

    def _attend(self, job: _Job, q, k, v) -> np.ndarray:
        """Append the job's fresh K/V to the pool and run paged attention
        for its rows into one output buffer."""
        li = self._pool_layer(job.layer)
        n = len(job.request_ids)
        # append the fresh token's K/V for this layer — one vectorized
        # write for the whole cohort (length advances only when the
        # token's final layer is written: the shared counter must
        # reflect *completed* positions)
        self.pool.append_rows(job.request_ids, li, job.positions, k, v)

        # paged attention over [0, pos] inclusive, rows sharded across
        # the worker pool into disjoint slices of one output buffer.
        # Chains must be hot (physical page ids) before snapshotting
        # them into the int32 table; writes above rehydrate this
        # layer's tail page but a long-idle request's earlier pages
        # may still be cold.
        if self.pool.has_compressed:
            for rid in job.request_ids:
                self.pool.ensure_hot(rid)
        chains = [self.pool.page_tables[(rid, li)]
                  for rid in job.request_ids]
        max_pages = max(len(c) for c in chains)
        pt = np.zeros((n, max_pages), np.int32)
        for i, c in enumerate(chains):
            pt[i, :len(c)] = c
        lengths = job.positions.astype(np.int32) + 1
        scales = self.pool.scales
        out = self._out_buffer(q.shape)
        if self._shards is None or n < 2:
            host_paged_attention_numpy(q, self.pool.pages, pt, lengths,
                                       page_size=self.page_size,
                                       scales=scales, out=out)
        else:
            bounds = np.linspace(0, n, min(self.workers, n) + 1).astype(int)
            futs = [
                self._shards.submit(
                    host_paged_attention_numpy, q[a:b], self.pool.pages,
                    pt[a:b], lengths[a:b], page_size=self.page_size,
                    scales=scales, out=out[a:b])
                for a, b in zip(bounds[:-1], bounds[1:]) if b > a]
            for f in futs:
                f.result()
        return out

    def advance_token(self, request_ids: Sequence[int]) -> None:
        """Bump pool lengths after a cohort completes a token."""
        for rid in request_ids:
            self.pool.lengths[rid] += 1
