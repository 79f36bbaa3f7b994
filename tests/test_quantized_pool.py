"""Quantized host KV tier: int8 pool round-trips, COW/fork scale
preservation, fused-dequant kernel agreement, cold-page compression,
stored-byte capacity accounting, and the engine-level accuracy
contract — token identity across the lifecycle matrix with
quantization on vs off, plus a bounded-logit-drift assertion for the
tie-prone hybrid geometry."""
import functools

import jax
import numpy as np
import pytest

import repro.serving.engine as engine_mod
from repro.configs import get_config
from repro.distributed.compression import (dequantize_kv_rows,
                                           quantize_kv_rows)
from repro.kernels.ops import (host_paged_attention,
                               host_paged_attention_numpy)
from repro.models import init_params
from repro.models.kv_cache import PagedKVPool
from repro.serving.engine import Engine, EngineConfig
from repro.serving.request import Request, make_synthetic_request


def _pool(host_kv_dtype="int8", **kw):
    kw.setdefault("num_pages", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_layers", 2)
    return PagedKVPool(kv_heads=1, head_dim=2, host_kv_dtype=host_kv_dtype,
                       **kw)


def _rows(rng, n, kv=1, d=2):
    # spread magnitudes over decades so per-row scaling actually matters
    mags = np.logspace(-2, 2, max(n, 1))[:n, None, None]
    return (rng.standard_normal((n, kv, d)) * mags).astype(np.float32)


def _fill(pool, rid, k, v):
    pool.allocate(rid, len(k))
    for layer in range(pool.num_layers):
        pool.write_prompt(rid, layer, k, v,
                          advance=(layer == pool.num_layers - 1))


# --- quantization helpers -------------------------------------------------

def test_quantize_roundtrip_bounded_and_requant_stable():
    """Per-row symmetric int8: error within half a quantization step,
    and requantizing the dequantized rows reproduces the identical
    codes AND scales (gather -> write chains are stable)."""
    rng = np.random.default_rng(0)
    x = _rows(rng, 16, 4, 8)
    q, s = quantize_kv_rows(x)
    deq = dequantize_kv_rows(q, s)
    err = np.abs(deq - x).max(axis=(1, 2))
    assert np.all(err <= s * 0.5 + 1e-12)
    q2, s2 = quantize_kv_rows(deq)
    np.testing.assert_array_equal(q, q2)
    np.testing.assert_array_equal(s, s2)


# --- quantized pool -------------------------------------------------------

def test_quantized_pool_roundtrip_and_dtypes():
    pool = _pool()
    assert pool.pages.dtype == np.int8
    assert pool.kv_dtype_bytes == 1
    rng = np.random.default_rng(1)
    k, v = _rows(rng, 10), _rows(rng, 10)
    _fill(pool, 1, k, v)
    gk, gv = pool.gather(1, 0)
    assert gk.dtype == np.float32 and gv.dtype == np.float32
    _, sk = quantize_kv_rows(k)
    _, sv = quantize_kv_rows(v)
    assert np.all(np.abs(gk - k).max(axis=(1, 2)) <= sk * 0.5 + 1e-12)
    assert np.all(np.abs(gv - v).max(axis=(1, 2)) <= sv * 0.5 + 1e-12)


def test_empty_gather_returns_logical_dtype():
    """The empty-chain path hands back the logical (dequantized) dtype,
    not the stored int8."""
    pool = _pool()
    pool.allocate(1, 4)
    k, v = pool.gather(1, 0)
    assert k.shape == (0, 1, 2)
    assert k.dtype == np.float32 and v.dtype == np.float32


def test_append_matches_bulk_write_quantized():
    """Streaming appends and the bulk prompt write quantize each token
    row identically (write-pattern invariance)."""
    rng = np.random.default_rng(2)
    k, v = _rows(rng, 9), _rows(rng, 9)
    bulk, stream = _pool(), _pool()
    _fill(bulk, 1, k, v)
    stream.allocate(1, 9)
    for t in range(9):
        for layer in range(2):
            stream.append(1, layer, k[t], v[t], advance=(layer == 1))
    for layer in range(2):
        bk, bv = bulk.gather(1, layer)
        sk, sv = stream.gather(1, layer)
        np.testing.assert_array_equal(bk, sk)
        np.testing.assert_array_equal(bv, sv)


def test_fork_cow_preserves_scales():
    """COW under quantization: an appended row lands in a private copy
    carrying the original page's scale rows; the cached owner's
    dequantized view stays byte-identical."""
    pool = _pool()
    rng = np.random.default_rng(3)
    k, v = _rows(rng, 6), _rows(rng, 6)
    _fill(pool, 1, k, v)
    pool.fork(1, -5, 6)
    cached = [pool.gather(-5, layer) for layer in range(2)]
    tok = (rng.standard_normal((1, 2)) * 50).astype(np.float32)
    for layer in range(2):
        pool.append(1, layer, tok, tok, advance=(layer == 1))
    for layer in range(2):
        ck, cv = pool.gather(-5, layer)
        np.testing.assert_array_equal(ck, cached[layer][0])
        np.testing.assert_array_equal(cv, cached[layer][1])
        lk, _ = pool.gather(1, layer)
        np.testing.assert_array_equal(lk[:6], cached[layer][0])
        _, s = quantize_kv_rows(tok[None])
        assert np.abs(lk[6] - tok[0]).max() <= s[0] * 0.5 + 1e-12


def test_page_bytes_charges_stored_bytes():
    """Capacity predicates price the stored element size: an int8 page
    (plus its fp32 scale rows) is 4x smaller than the fp32 page minus
    the scale overhead."""
    fp, q = _pool("fp32"), _pool("int8")
    ps, kv, d = 4, 1, 2
    assert fp.page_bytes == 2 * ps * kv * d * 4
    assert q.page_bytes == 2 * ps * kv * d * 1 + 2 * ps * 4
    assert q.page_bytes < fp.page_bytes
    stats = q.byte_stats()
    assert stats["free"] == 32 * q.page_bytes
    assert stats["hot"] == 0 and stats["compressed"] == 0


# --- cold-page compression ------------------------------------------------

def test_cold_compression_roundtrip_frees_pages():
    """Idle pages compress in place (physical pages return to the free
    list — the capacity win), decompress transparently on gather, and
    the round trip is bit-exact at the stored codes."""
    pool = _pool(cold_page_compress_after=1e-6)
    rng = np.random.default_rng(4)
    k, v = _rows(rng, 8), _rows(rng, 8)
    _fill(pool, 1, k, v)
    before = [pool.gather(1, layer) for layer in range(2)]
    free_before = pool.num_free
    n = pool.maybe_compress_cold(now=1e9)       # force "idle forever"
    assert n > 0 and pool.pages_compressed == n
    assert pool.num_free > free_before          # physical pages freed
    assert pool.has_compressed
    stats = pool.byte_stats()
    assert stats["compressed"] > 0
    assert pool.compressed_ratio_ewma is not None
    for layer in range(2):                      # transparent rehydrate
        gk, gv = pool.gather(1, layer)
        np.testing.assert_array_equal(gk, before[layer][0])
        np.testing.assert_array_equal(gv, before[layer][1])
    assert pool.pages_decompressed > 0
    assert not pool.has_compressed


def test_reclaim_prefers_compression_over_eviction():
    """Allocation pressure compresses an evictable owner's pages before
    evicting it: the cheaper degradation rung keeps the cached entry
    alive."""
    pool = _pool(num_pages=8, page_size=4, num_layers=1,
                 cold_page_compress_after=1e-6)
    rng = np.random.default_rng(5)
    evicted = []
    pool.on_evict = evicted.append
    k, v = _rows(rng, 8), _rows(rng, 8)
    _fill(pool, -1, k, v)                       # 2 pages, evictable
    pool.mark_evictable(-1)
    snap = pool.gather(-1, 0)
    _fill(pool, 1, _rows(rng, 16), _rows(rng, 16))  # 4 pages live
    pool.allocate(2, 16)                        # needs 4: compress -1
    assert evicted == [] and pool.evictions == 0
    assert pool.pages_compressed >= 2
    assert (-1, 0) in pool.page_tables
    pool.free(2)                                # headroom to rehydrate
    gk, gv = pool.gather(-1, 0)                 # entry survived intact
    np.testing.assert_array_equal(gk, snap[0])
    np.testing.assert_array_equal(gv, snap[1])


def test_compression_also_works_fp32():
    """The cold rung is orthogonal to quantization: an fp32 pool
    compresses and rehydrates bit-identically too."""
    pool = _pool("fp32", cold_page_compress_after=1e-6)
    rng = np.random.default_rng(6)
    k, v = _rows(rng, 8), _rows(rng, 8)
    _fill(pool, 1, k, v)
    assert pool.maybe_compress_cold(now=1e9) > 0
    gk, gv = pool.gather(1, 0)
    np.testing.assert_array_equal(gk, k)
    np.testing.assert_array_equal(gv, v)


# --- fused-dequant kernels ------------------------------------------------

def _paged_setup(rng, batch=3, ctx=10, page_size=4, kv=2, d=8, heads=4):
    pages_per = -(-ctx // page_size)
    npages = batch * pages_per
    kf = (rng.standard_normal((2, npages * page_size, kv, d))
          .astype(np.float32))
    q8 = np.zeros((2, npages, page_size, kv, d), np.int8)
    scales = np.zeros((2, npages, page_size), np.float32)
    fp_pages = np.zeros((2, npages, page_size, kv, d), np.float32)
    for side in range(2):
        codes, s = quantize_kv_rows(kf[side])
        q8[side] = codes.reshape(npages, page_size, kv, d)
        scales[side] = s.reshape(npages, page_size)
        fp_pages[side] = dequantize_kv_rows(codes, s).reshape(
            npages, page_size, kv, d)
    pt = np.arange(npages, dtype=np.int32).reshape(batch, pages_per)
    lengths = rng.integers(1, ctx + 1, batch).astype(np.int32)
    qq = rng.standard_normal((batch, heads, d)).astype(np.float32)
    return qq, q8, scales, fp_pages, pt, lengths


def test_fused_dequant_numpy_matches_dequantized_reference():
    """The fused int8 path computes exactly what attention over
    pre-dequantized fp32 pages computes."""
    rng = np.random.default_rng(7)
    q, q8, scales, fp_pages, pt, lengths = _paged_setup(rng)
    fused = host_paged_attention_numpy(q, q8, pt, lengths, page_size=4,
                                       scales=scales)
    ref = host_paged_attention_numpy(q, fp_pages, pt, lengths, page_size=4)
    np.testing.assert_allclose(fused, ref, rtol=1e-5, atol=1e-6)


def test_fused_dequant_jax_matches_numpy():
    rng = np.random.default_rng(8)
    q, q8, scales, _, pt, lengths = _paged_setup(rng)
    fused_np = host_paged_attention_numpy(q, q8, pt, lengths, page_size=4,
                                          scales=scales)
    fused_jax = np.asarray(host_paged_attention(
        q, q8, pt, lengths, page_size=4, scales=scales))
    np.testing.assert_allclose(fused_jax, fused_np, rtol=2e-5, atol=2e-5)


# --- engine-level accuracy contract ---------------------------------------

@functools.lru_cache(maxsize=None)
def _model(arch, vocab=64):
    cfg = get_config(arch).reduced(layers=None, d_model=64, vocab=vocab)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _run_engine(cfg, params, reqs, ecfg_kw, max_iterations=100000):
    eng = Engine(cfg, params, EngineConfig(**ecfg_kw))
    try:
        stats = eng.run(reqs, max_iterations=max_iterations)
    finally:
        eng.shutdown()
    return [r.output for r in reqs], stats


def _scenario_offload(arch, dt):
    cfg, params = _model(arch)
    rng = np.random.default_rng(1)
    reqs = [make_synthetic_request(rng, prompt_len=7, output_len=4,
                                   vocab=cfg.vocab_size) for _ in range(5)]
    outs, stats = _run_engine(cfg, params, reqs, dict(
        device_slots=2, host_slots=5, cache_len=64, host_kv_dtype=dt))
    return outs, stats.host_tokens > 0


def _scenario_migration(arch, dt):
    cfg, params = _model(arch)
    rng = np.random.default_rng(2)
    reqs = [Request(prompt=list(rng.integers(0, 64, 6)), max_new_tokens=2)]
    reqs += [Request(prompt=list(rng.integers(0, 64, 6)), max_new_tokens=4)
             for _ in range(2)]
    outs, stats = _run_engine(cfg, params, reqs, dict(
        device_slots=1, host_slots=2, cache_len=64, preemption=False,
        host_kv_dtype=dt))
    return outs, stats.migrations >= 1


def _scenario_preemption(arch, dt):
    cfg, params = _model(arch)
    rng = np.random.default_rng(6)
    lows = [Request(prompt=list(rng.integers(0, 64, 8)), max_new_tokens=6)
            for _ in range(2)]
    urgent = Request(prompt=list(rng.integers(0, 64, 100)),
                     max_new_tokens=3, priority=1, deadline=120.0)
    # size the host pool so the urgent prompt cannot fit there (4 pages
    # x L layers > pool) but a demoted low (1 page x L) can
    L = len(cfg.attn_layer_indices)
    eng = Engine(cfg, params, EngineConfig(
        device_slots=2, host_slots=4, cache_len=128, page_size=32,
        host_pool_pages=2 * L, preemption=True, host_kv_dtype=dt))
    try:
        eng.run(lows, max_iterations=4)
        eng.submit(urgent)
        it = 0
        while eng.has_work and it < 3000:
            eng.step()
            it += 1
        stats = eng.stats
    finally:
        eng.shutdown()
    return [r.output for r in lows + [urgent]], stats.preemptions >= 1


def _scenario_prefix_host_hit(arch, dt):
    cfg, params = _model(arch, vocab=128)
    eng = Engine(cfg, params, EngineConfig(
        device_slots=2, host_slots=4, cache_len=128, page_size=16,
        host_pool_pages=256, chunk_tokens=16, enable_offload=True,
        prefix_cache=True, prefix_cache_slots=0, host_kv_dtype=dt))
    try:
        rng = np.random.default_rng(2)
        history = [int(t) for t in rng.integers(1, cfg.vocab_size, 24)]
        outs = []
        for _ in range(2):
            user = [int(t) for t in rng.integers(1, cfg.vocab_size, 5)]
            req = Request(prompt=history + user, max_new_tokens=4)
            eng.run([req])
            outs.append(list(req.output))
            history = list(req.prompt) + list(req.output)
        hits = eng.stats.prefix_hits
    finally:
        eng.shutdown()
    return outs, hits > 0


_SCENARIOS = {
    "offload": _scenario_offload,
    "migration": _scenario_migration,
    "preemption": _scenario_preemption,
    "prefix_host_hit": _scenario_prefix_host_hit,
}


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "jamba-1.5-large-398b"])
@pytest.mark.parametrize("scenario", sorted(_SCENARIOS))
def test_token_identity_quantized_matrix(arch, scenario):
    """The accuracy gate: every lifecycle scenario emits token-identical
    greedy outputs with the host tier quantized vs fp32, on both the
    dense and the hybrid stack — and the scenario actually engaged."""
    run = _SCENARIOS[scenario]
    fp_out, fp_engaged = run(arch, "fp32")
    q_out, q_engaged = run(arch, "int8")
    assert fp_engaged and q_engaged, f"{scenario} never engaged"
    assert fp_out == q_out, f"int8 divergence in {scenario} on {arch}"


def test_compression_keeps_tokens_identical():
    """The cold rung is lossless end to end: an int8 engine with
    aggressive cold-page compression emits the same tokens as one
    without."""
    cfg, params = _model("internlm2-1.8b")
    rng = np.random.default_rng(1)

    def reqs():
        r = np.random.default_rng(9)
        return [make_synthetic_request(r, prompt_len=7, output_len=4,
                                       vocab=cfg.vocab_size)
                for _ in range(5)]

    base_kw = dict(device_slots=2, host_slots=5, cache_len=64,
                   host_kv_dtype="int8")
    plain, s1 = _run_engine(cfg, params, reqs(), base_kw)
    comp, s2 = _run_engine(cfg, params, reqs(), dict(
        base_kw, cold_page_compress_after=1e-9))
    assert plain == comp
    assert s1.host_tokens > 0


def test_bounded_logit_drift_int8(monkeypatch):
    """Where ULP-scale ties could flip greedy (the hybrid stack's
    recurrence amplifies drift), the contract is a bounded logit delta:
    every decode-step logit under int8 stays within a small envelope of
    the fp32 run's.  Host jobs run synchronously so both runs take the
    same iterations (a late async job rides along for an extra step,
    which would misalign the per-step comparison under CPU load)."""
    cfg, params = _model("jamba-1.5-large-398b")
    monkeypatch.setattr(engine_mod, "HostExecutor", functools.partial(
        engine_mod.HostExecutor, synchronous=True))
    real = engine_mod.sample

    def run(dt):
        rec = []

        def spy(logits, **kw):
            rec.append(np.asarray(logits, np.float32).copy())
            return real(logits, **kw)

        engine_mod.sample = spy
        try:
            rng = np.random.default_rng(1)
            reqs = [make_synthetic_request(rng, prompt_len=7, output_len=4,
                                           vocab=cfg.vocab_size)
                    for _ in range(5)]
            eng = Engine(cfg, params, EngineConfig(
                device_slots=2, host_slots=5, cache_len=64,
                host_kv_dtype=dt))
            eng.run(reqs)
            eng.shutdown()
        finally:
            engine_mod.sample = real
        return rec, [r.output for r in reqs]

    fp_logits, fp_out = run("fp32")
    q_logits, q_out = run("int8")
    assert fp_out == q_out                     # same trajectory: aligned
    assert len(fp_logits) == len(q_logits)
    drift = max(float(np.abs(a - b).max())
                for a, b in zip(fp_logits, q_logits))
    assert 0.0 < drift < 0.75, f"logit drift {drift} out of envelope"
