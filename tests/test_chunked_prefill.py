"""Chunked prefill co-scheduled with decode: exactness + liveness.

Chunking is a pure performance feature — splitting a prompt into
token-budgeted chunks that advance inside the continuous-batching loop
must be bit-invisible in the emitted tokens (causality makes chunk
boundaries mathematically inert), across both tiers and every chunk
size including the degenerate ones (chunk == prompt, chunk == 1).
Liveness is the point of the feature: decode iterations must keep
producing tokens while a long prompt is mid-prefill.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_decode_state, init_params, prefill, prefill_chunk
from repro.serving.engine import Engine, EngineConfig
from repro.serving.request import Phase, Request


def _dense_cfg():
    return get_config("internlm2-1.8b").reduced(layers=None, d_model=64,
                                                vocab=64)


def _requests(seed, n, *, vocab, out_len=5, lo=1, hi=20):
    rng = np.random.default_rng(seed)
    lengths = rng.integers(lo, hi, n)
    return [Request(prompt=list(rng.integers(0, vocab, int(ln))),
                    max_new_tokens=out_len) for ln in lengths]


def _clone(reqs):
    return [Request(prompt=list(r.prompt), max_new_tokens=r.max_new_tokens)
            for r in reqs]


# ---------------------------------------------------------------------------
# Model-level: prefill_chunk == whole-prompt prefill, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [1, 4, 19])
def test_prefill_chunk_bitwise_equals_whole_prefill(chunk):
    """Chunk-by-chunk advance through a staging row must reproduce the
    whole-prompt prefill bit-for-bit: last-token logits AND the KV it
    leaves in the cache (chunk == prompt covers the one-shot edge,
    chunk == 1 the token-at-a-time edge)."""
    cfg = _dense_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    plen, cache = 19, 64
    prompt = rng.integers(0, cfg.vocab_size, plen)

    st = init_decode_state(cfg, device_batch=1, cache_len=cache)
    ref_logits, ref_state = prefill(params, cfg,
                                    {"tokens": jnp.asarray(prompt)[None]}, st)

    p = 3                                   # staging batch; row 1 is ours
    stg = init_decode_state(cfg, device_batch=p, cache_len=cache)
    consumed = 0
    logits = None
    while consumed < plen:
        c = min(chunk, plen - consumed)
        cb = 1 << max(c - 1, 0).bit_length()      # power-of-two bucket
        toks = np.zeros((p, cb), np.int32)
        lens = np.zeros((p,), np.int32)
        toks[1, :c] = prompt[consumed:consumed + c]
        lens[1] = c
        logits, stg = prefill_chunk(params, cfg, jnp.asarray(toks),
                                    jnp.asarray(lens), stg)
        consumed += c
    np.testing.assert_array_equal(np.asarray(stg.lengths), [0, plen, 0])
    np.testing.assert_array_equal(np.asarray(ref_logits[0]),
                                  np.asarray(logits[1]))
    for j, entry in enumerate(ref_state.per_entry):
        if hasattr(entry, "k"):
            np.testing.assert_array_equal(
                np.asarray(entry.k[:, 0, :plen], np.float32),
                np.asarray(stg.per_entry[j].k[:, 1, :plen], np.float32))
            np.testing.assert_array_equal(
                np.asarray(entry.v[:, 0, :plen], np.float32),
                np.asarray(stg.per_entry[j].v[:, 1, :plen], np.float32))


# ---------------------------------------------------------------------------
# Engine-level: tokens identical across tiers and chunk sizes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [1, 4, 64])
def test_chunked_engine_tokens_identical_device_tier(chunk):
    """Device-tier serving with chunking (including chunk == 1 and a
    chunk covering every prompt whole) == the whole-prompt engine."""
    cfg = _dense_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    protos = _requests(4, 8, vocab=cfg.vocab_size)

    legacy = Engine(cfg, params, EngineConfig(
        device_slots=9, cache_len=64, enable_offload=False, chunk_tokens=0))
    a = _clone(protos)
    legacy.run(a)
    legacy.shutdown()

    eng = Engine(cfg, params, EngineConfig(
        device_slots=9, cache_len=64, enable_offload=False,
        chunk_tokens=chunk))
    b = _clone(protos)
    stats = eng.run(b)
    eng.shutdown()
    assert stats.prefill_chunks > 0
    for x, y in zip(a, b):
        assert x.output == y.output


def test_chunked_engine_tokens_identical_host_tier():
    """Offload config: host-tier prompts stream their KV to the paged
    pool at chunk granularity and must emit the same tokens as the
    whole-prompt engine (which migrates KV once, post-prefill)."""
    cfg = _dense_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    protos = _requests(5, 8, vocab=cfg.vocab_size)

    legacy = Engine(cfg, params, EngineConfig(
        device_slots=2, host_slots=8, cache_len=64, chunk_tokens=0))
    a = _clone(protos)
    sa = legacy.run(a)
    legacy.shutdown()
    assert sa.host_tokens > 0

    eng = Engine(cfg, params, EngineConfig(
        device_slots=2, host_slots=8, cache_len=64, chunk_tokens=4))
    b = _clone(protos)
    sb = eng.run(b)
    eng.shutdown()
    assert sb.host_tokens > 0
    for x, y in zip(a, b):
        assert x.output == y.output


def test_recurrent_archs_ride_chunked_prefill():
    """Hybrid stacks advance chunk-by-chunk like everyone else: the
    chunk-continuation path resumes carried recurrent state and the
    length-masked scan keeps padding out of it (bit-identity:
    tests/test_hybrid_fastpath.py)."""
    cfg = get_config("jamba-1.5-large-398b").reduced(layers=None, d_model=64,
                                                     vocab=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, EngineConfig(device_slots=2, cache_len=64,
                                           chunk_tokens=16))
    assert eng._chunked is True
    eng.shutdown()


# ---------------------------------------------------------------------------
# Chunk grant under a live host cohort: the pinned buffer fills
# ---------------------------------------------------------------------------

_CHUNK = 8
_STAGED = (20, 13, 30)          # prompts staged while the cohort decodes


def _host_cohort_scenario():
    """One device row and one host row decoding, then three host-tier
    prompts staged beside them.  Returns the requests, the engine's
    stats, the chunk iterations before the staging, and per chunk
    iteration after it {request: (consumed before, after)} for every
    staged prompt with prompt left, whether the host cohort was
    decoding, and whether it emitted a token."""
    cfg = _dense_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    eng = Engine(cfg, params, EngineConfig(
        device_slots=1, host_slots=4, cache_len=128,
        chunk_tokens=_CHUNK, host_job_min_timeout=600.0))
    first = [Request(prompt=list(rng.integers(0, cfg.vocab_size, n)),
                     max_new_tokens=out) for n, out in ((6, 60), (5, 40))]
    staged = [Request(prompt=list(rng.integers(0, cfg.vocab_size, n)),
                      max_new_tokens=6) for n in _STAGED]
    steps = []
    try:
        for r in first:
            eng.submit(r)
        while not (first[1].phase is Phase.DECODE_HOST
                   and eng.stats.host_tokens > 0):
            eng.step()
        assert first[0].phase is Phase.DECODE_DEVICE
        chunks0 = eng.stats.chunk_iterations
        for r in staged:
            eng.submit(r)
        while eng.has_work:
            before = {r.request_id: _consumed(eng, r) for r in staged}
            chunks, host = eng.stats.chunk_iterations, eng.stats.host_tokens
            hosts_decoding = bool(eng.lc.decoding_hosts())
            eng.step()
            if eng.stats.chunk_iterations > chunks:
                advanced = {r.request_id: (before[r.request_id],
                                           _consumed(eng, r))
                            for r in staged
                            if before[r.request_id] < r.prompt_len}
                steps.append((advanced, hosts_decoding,
                              eng.stats.host_tokens > host))
        return first + staged, eng.stats, chunks0, steps
    finally:
        eng.shutdown()


def _consumed(eng, req):
    """Prompt tokens of ``req`` prefilled so far."""
    if req.phase is Phase.QUEUED:
        return 0
    for row in eng.lc.staging_order:
        if eng.lc.staging[row].req is req:
            return eng.lc.staging[row].consumed
    return req.prompt_len


@pytest.fixture(scope="module")
def host_cohort_run():
    return _host_cohort_scenario()


def test_chunk_iterations_advance_every_row_by_chunk_tokens(host_cohort_run):
    """With a host cohort decoding, every chunk iteration advances each
    staged row with prompt left by min(remaining, chunk_tokens): the
    grant fills the pinned buffer instead of trickling 1-2 tokens."""
    reqs, _, _, steps = host_cohort_run
    prompt_len = {r.request_id: r.prompt_len for r in reqs}
    staged_steps = [s for s in steps if s[0]]
    assert staged_steps
    for advanced, hosts_decoding, _ in staged_steps:
        assert hosts_decoding
        assert len(advanced) >= 1
        for rid, (b, a) in advanced.items():
            assert a - b == min(prompt_len[rid] - b, _CHUNK), (rid, b, a)


def test_prompt_completes_in_ceil_p_over_chunk_iterations(host_cohort_run):
    """A P-token prompt staged under a live cohort prefills in exactly
    ceil(P / chunk_tokens) chunk iterations, and the cohort keeps
    emitting while the prompts prefill."""
    reqs, _, _, steps = host_cohort_run
    for r in reqs[2:]:
        n = sum(1 for advanced, _, _ in steps if r.request_id in advanced)
        assert n == -(-r.prompt_len // _CHUNK), (r.prompt_len, n)
        assert r.output
    staging_iters = -(-max(_STAGED) // _CHUNK)
    assert any(emitted for _, _, emitted in steps[:staging_iters])


def test_full_buffer_grant_tokens_identical_to_whole_prompt(host_cohort_run):
    """The same requests through the whole-prompt engine (no chunking)
    emit the same greedy tokens."""
    reqs, _, _, _ = host_cohort_run
    cfg = _dense_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    legacy = Engine(cfg, params, EngineConfig(
        device_slots=1, host_slots=4, cache_len=128, chunk_tokens=0,
        host_job_min_timeout=600.0))
    ref = _clone(reqs)
    try:
        legacy.run(ref)
    finally:
        legacy.shutdown()
    for x, y in zip(ref, reqs):
        assert x.output == y.output


def test_chunk_counters(host_cohort_run):
    """``chunk_iterations`` counts every iteration that ran the chunk
    program; ``chunk_buffer_tokens`` the buffer positions it computed
    (staging rows x pow2_ceil(chunk_tokens) a call), so their ratio
    with ``chunked_prefill_tokens`` is the buffer's fill."""
    reqs, stats, chunks0, steps = host_cohort_run
    assert chunks0 >= 1 and steps
    assert stats.chunk_iterations == chunks0 + len(steps)
    rows = 1 + 4                              # device + host slots
    assert stats.chunk_buffer_tokens == stats.chunk_iterations * rows * _CHUNK
    assert stats.chunked_prefill_tokens == sum(r.prompt_len for r in reqs)
    assert stats.chunked_prefill_tokens < stats.chunk_buffer_tokens


# ---------------------------------------------------------------------------
# Liveness: decode proceeds while a long prompt is mid-prefill
# ---------------------------------------------------------------------------


def test_decode_not_starved_by_long_prefill():
    """The decode stall this feature kills: with a long prompt arriving
    mid-serve, decode requests must keep gaining tokens every iteration
    the prefill is in progress, and those iterations must be recorded
    as chunk co-runs."""
    cfg = _dense_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    eng = Engine(cfg, params, EngineConfig(
        device_slots=3, cache_len=256, enable_offload=False, chunk_tokens=8))
    short = [Request(prompt=list(rng.integers(0, cfg.vocab_size, 4)),
                     max_new_tokens=64) for _ in range(2)]
    try:
        for r in short:
            eng.submit(r)
        eng.step()                          # prefill the shorts
        eng.step()                          # they decode
        long_req = Request(prompt=list(rng.integers(0, cfg.vocab_size, 100)),
                           max_new_tokens=4)
        eng.submit(long_req)
        before = [len(r.output) for r in short]
        it0 = eng.stats.iterations
        while long_req.first_token_time is None \
                and eng.stats.iterations < it0 + 100:
            eng.step()
        prefill_iters = eng.stats.iterations - it0
        gained = [len(r.output) - b for r, b in zip(short, before)]
        # 100-token prompt at budget 8 spans many iterations...
        assert prefill_iters >= 100 // 8
        # ...and decode advanced through every one of them
        assert all(g >= prefill_iters - 1 for g in gained), \
            (gained, prefill_iters)
        assert eng.stats.chunk_co_run_iterations >= prefill_iters - 1
        assert eng.stats.ttft_samples == []   # nothing retired yet
    finally:
        eng.shutdown()


def test_latency_percentiles_recorded():
    """Retired requests feed the TTFT / inter-token distributions."""
    cfg = _dense_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, EngineConfig(device_slots=4, cache_len=64,
                                           enable_offload=False))
    reqs = _requests(9, 4, vocab=cfg.vocab_size, out_len=3)
    stats = eng.run(reqs)
    eng.shutdown()
    assert len(stats.ttft_samples) == 4
    assert len(stats.itl_samples) == 4
    assert stats.ttft_p50 is not None and stats.ttft_p95 >= stats.ttft_p50
    assert stats.itl_p50 is not None and stats.itl_p95 >= stats.itl_p50
    assert stats.host_workers == 0           # offload off: no executor
