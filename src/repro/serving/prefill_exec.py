"""Admission-time prefill execution paths (the engine delegates here).

Three ways a placed request's prompt becomes cached state:

  * ``prefill_into_slot`` / ``prefill_to_host`` — the exact
    per-request reference paths (also what runs when bucketing is
    disabled in config).
  * ``prefill_batched`` — the fast path for every stack: prompt
    lengths bucket to powers of two and same-bucket admissions
    prefill in ONE jitted device call (jit retraces bounded by
    log2(cache_len) x log2(2*device_slots) shape pairs).  Hybrid
    (Mamba/xLSTM) rows are exact here too: the length-masked scan
    freezes recurrent state past each row's true length.

All three take the engine as their execution context (its jitted
entry points, shared state and host executor); request state-machine
edges go through ``lifecycle.transition``.  The chunked-prefill path
(admissions advancing inside the continuous-batching loop) lives in
the engine itself — it is fused with decode dispatch.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.overlap_engine import stack_row_kv_to_pool_layers
from repro.core.spans import spanned
from repro.models import init_decode_state, prefill
from repro.models.kv_cache import StackState
from repro.serving.lifecycle import pow2_ceil, transition
from repro.serving.request import Phase, Request
from repro.serving.sampler import sample
from repro.serving.tiermove import (copy_state_row, set_recurrent_row,
                                    snapshot_recurrent_row,
                                    splice_recurrent_rows,
                                    write_prefix_into_row)


def seed_prefix_hits(eng, placements: List[Tuple[Request, str, int]],
                     rows: List[int]) -> None:
    """Prefix-cache admission matching for freshly staged requests:
    find the longest cached prefix of each prompt, seed the staging
    row with its KV (and recurrent carry for hybrids), and advance
    ``InflightPrefill.consumed`` to the hit length — chunked prefill
    then resumes at the suffix (``prefill_chunk`` queries at absolute
    position ``lengths``), and the scheduler's chunk backlog prices
    only the uncached tokens.  Host-tier placements additionally get
    the prefix into their pool chains: a fork (refcount++, zero
    copies) when the entry is host-resident, a device→pool write when
    it is not.  Every move is bit-exact, so tokens match a cache-off
    run exactly."""
    cache = eng._prefix
    for (req, tier, slot), row in zip(placements, rows):
        eng.stats.prefix_lookups += 1
        hit = cache.match(req.prompt)
        if hit is None:
            continue
        entry, n = hit
        pool = eng._executor.pool if eng._executor is not None else None
        if entry.tier == "host":
            # fallible pool reads FIRST: the pool's LRU may reclaim the
            # entry from the host-executor thread between match and
            # here — bail before touching any staging state and the
            # admission degrades to a plain miss
            try:
                per_layer = [pool.gather(entry.owner, li, n)
                             for li in range(eng.cfg.num_attn_layers)]
                pool.touch(entry.owner)
            except KeyError:
                continue
            eng._staging_state = write_prefix_into_row(
                eng.cfg, eng._staging_state, per_layer, row, n)
            if eng._hybrid and entry.carry is not None:
                eng._staging_state = set_recurrent_row(
                    eng.cfg, eng._staging_state, row, entry.carry)
        else:
            eng._staging_state = copy_state_row(
                eng.cfg, eng._staging_state, eng._prefix_state,
                entry.row, row, n)
        if tier == "host":
            # the request's chains must hold the prefix too (host
            # decode gathers the full sequence from the pool): drop the
            # admission-time reservation, then fork the cached chains
            # (host entry) or write the device rows out (device entry)
            pool.free(req.request_id)
            if entry.tier == "host":
                try:
                    pool.fork(entry.owner, req.request_id, n)
                except KeyError:
                    # entry evicted between gather and fork: rebuild
                    # chains from the gathered arrays (pages we just
                    # freed more than cover the prefix)
                    pool.allocate(req.request_id, req.prompt_len)
                    for li, (kk, vv) in enumerate(per_layer):
                        pool.write_prompt(
                            req.request_id, li, kk, vv,
                            advance=(li == eng.cfg.num_attn_layers - 1))
            else:
                eng._executor.migrate_prompt(
                    req.request_id,
                    stack_row_kv_to_pool_layers(eng.cfg, eng._prefix_state,
                                                entry.row, n))
        eng.lc.staging[row].consumed = n
        eng.stats.prefix_hits += 1
        eng.stats.prefix_hit_tokens += n
    eng._refresh_prefix_gauges()


def prefill_into_slot(eng, req: Request, slot: int) -> None:
    """Per-request prefill on device into this slot of the shared
    state (the exact reference path)."""
    transition(req, Phase.PREFILL)
    prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
    sub = init_decode_state(eng.cfg, device_batch=1,
                            cache_len=eng.e.cache_len)
    logits, sub = prefill(eng.params, eng.cfg, {"tokens": prompt}, sub)
    tok = int(sample(logits, temperature=eng.e.temperature)[0])
    req.output.append(tok)
    if req.first_token_time is None:
        req.first_token_time = time.perf_counter()
    # splice the single-row state into the shared batch state — the
    # same row-assignment works for every entry kind (attention KV
    # and recurrent states share the batch-axis layout)
    new_entries = [
        jax.tree.map(lambda big, small: big.at[:, slot].set(small[:, 0]),
                     entry, sub.per_entry[j])
        for j, entry in enumerate(eng.state.per_entry)
    ]
    lengths = eng.state.lengths.at[slot].set(req.prompt_len)
    eng.state = StackState(per_entry=tuple(new_entries), lengths=lengths)
    eng.lc.slots[slot] = req
    req.slot = slot
    transition(req, Phase.DECODE_DEVICE)


def prefill_to_host(eng, req: Request, host_slot: int) -> None:
    """Per-request prefill on device, migrating attention KV to the
    host pool (paper §3.1: device prefills; host owns decode
    attention).  Recurrent (Mamba/xLSTM) states stay ON-DEVICE,
    spliced into the unified state's host row — only attention
    stalls on the host."""
    transition(req, Phase.PREFILL)
    prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
    sub = init_decode_state(eng.cfg, device_batch=1,
                            cache_len=eng.e.cache_len)
    logits, sub = prefill(eng.params, eng.cfg, {"tokens": prompt}, sub)
    tok = int(sample(logits, temperature=eng.e.temperature)[0])
    req.output.append(tok)
    if req.first_token_time is None:
        req.first_token_time = time.perf_counter()
    row = eng.e.device_slots + host_slot
    eng.state = splice_recurrent_rows(eng.cfg, eng.state, sub.per_entry,
                                      0, row)
    eng._executor.migrate_prompt(
        req.request_id,
        stack_row_kv_to_pool_layers(eng.cfg, sub, 0, req.prompt_len))
    req.slot = host_slot
    transition(req, Phase.DECODE_HOST)
    # the cohort picks the new member up at the next token boundary


@spanned("finish_chunks")
def finish_chunks(eng, plan, clogits) -> None:
    """Post-chunk bookkeeping for the chunked-prefill path: stream
    host-tier chunks' KV into the paged pool, and graduate completed
    prefills — sample the first token, splice device rows into the
    shared decode state / activate host rows for the next cohort,
    free the staging row."""
    staging = eng.lc.staging
    done_rows = [row for row, c in zip(plan.rows, plan.lens)
                 if staging[row].consumed + c >= staging[row].req.prompt_len]
    toks: Dict[int, int] = {}
    if done_rows:
        picked = clogits[jnp.asarray(done_rows)]
        sampled = np.asarray(sample(picked, temperature=eng.e.temperature))
        toks = {row: int(t) for row, t in zip(done_rows, sampled)}
    now = time.perf_counter()
    freed: List[int] = []
    for row, c in zip(plan.rows, plan.lens):
        ent = staging[row]
        start = ent.consumed
        ent.consumed += c
        if ent.tier == "host":
            # KV streams to the paged pool at chunk granularity — no
            # whole-prompt migration on completion
            eng._executor.migrate_prompt(
                ent.req.request_id,
                stack_row_kv_to_pool_layers(eng.cfg, eng._staging_state,
                                            row, ent.consumed, start=start))
        if ent.consumed >= ent.req.prompt_len:
            req = ent.req
            if eng._prefix is not None and eng._hybrid:
                # the staging row's carry right now is the prompt-end
                # carry — the only moment it exists before decode
                # advances it; prefix-cache publication needs it to
                # stay bit-exact (decode and prefill kernels reduce
                # floats in different orders)
                req._prefix_carry = snapshot_recurrent_row(
                    eng.cfg, eng._staging_state, row)
            req.output.append(toks[row])
            if req.first_token_time is None:
                req.first_token_time = now
            if ent.tier == "device":
                eng.state = eng._splice_jit(
                    eng.state, eng._staging_state.per_entry,
                    jnp.int32(row), jnp.int32(ent.slot),
                    jnp.int32(req.prompt_len))
                transition(req, Phase.DECODE_DEVICE)
            else:
                if eng._hybrid:
                    # recurrent state stays on-device in the unified
                    # host row; only attention KV lives in the pool
                    eng.state = splice_recurrent_rows(
                        eng.cfg, eng.state, eng._staging_state.per_entry,
                        row, eng.e.device_slots + ent.slot)
                transition(req, Phase.DECODE_HOST)
                # the cohort picks it up at the next token boundary
            eng.lc.release_staging_row(row)
            freed.append(row)
    if freed:
        # one batched scatter for every graduated row (a per-row
        # .at[i].set loop dispatches len(freed) device ops)
        lengths = eng._staging_state.lengths.at[
            jnp.asarray(freed, jnp.int32)].set(0)
        eng._staging_state = StackState(
            per_entry=eng._staging_state.per_entry, lengths=lengths)


def prefill_batched(eng, placements: List[Tuple[Request, str, int]]) -> None:
    """The prefill fast path (every stack — padding is length-masked):
    bucket prompt lengths to powers of two and prefill each bucket's
    admissions in ONE jitted device call."""
    groups: Dict[int, list] = {}
    for p in placements:
        groups.setdefault(pow2_ceil(p[0].prompt_len), []).append(p)
    for blen in sorted(groups):
        group = groups[blen]
        bb = pow2_ceil(len(group))
        tokens = np.zeros((bb, blen), np.int32)
        plens = np.ones((bb,), np.int32)   # padded rows: discarded
        for j, (req, _, _) in enumerate(group):
            transition(req, Phase.PREFILL)
            tokens[j, :req.prompt_len] = req.prompt
            plens[j] = req.prompt_len
        logits, sub = eng._prefill_jit(eng.params, jnp.asarray(tokens),
                                       jnp.asarray(plens))
        toks = np.asarray(sample(logits, temperature=eng.e.temperature))
        now = time.perf_counter()
        for j, (req, tier, slot) in enumerate(group):
            req.output.append(int(toks[j]))
            if req.first_token_time is None:
                req.first_token_time = now
            if tier == "device":
                eng.state = eng._splice_jit(
                    eng.state, sub.per_entry, jnp.int32(j),
                    jnp.int32(slot), jnp.int32(req.prompt_len))
                transition(req, Phase.DECODE_DEVICE)
            else:
                if eng._hybrid:
                    eng.state = splice_recurrent_rows(
                        eng.cfg, eng.state, sub.per_entry, j,
                        eng.e.device_slots + slot)
                eng._executor.migrate_prompt(
                    req.request_id,
                    stack_row_kv_to_pool_layers(eng.cfg, sub, j,
                                                req.prompt_len))
                transition(req, Phase.DECODE_HOST)
