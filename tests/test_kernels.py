"""Per-kernel correctness sweeps: shapes x dtypes vs the pure-jnp
oracles in repro.kernels.ref (interpret=True executes the Pallas body
on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.host_paged_attention import (host_paged_attention,
                                                host_paged_attention_numpy)
from repro.kernels.prefill_attention import prefill_attention

DECODE_SWEEP = [
    # (B, H, KV, D, S, block_s)
    (1, 4, 4, 64, 128, 64),        # MHA
    (2, 8, 2, 64, 512, 256),       # GQA 4:1
    (3, 8, 1, 128, 384, 128),      # MQA, non-pow2 batch, pad path
    (2, 16, 8, 128, 1024, 512),    # wide
]


@pytest.mark.parametrize("b,h,kv,d,s,bs", DECODE_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(b, h, kv, d, s, bs, dtype, key):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, d), dtype)
    lengths = jax.random.randint(ks[3], (b,), 1, s + 1)
    out = decode_attention(q, k, v, lengths, block_s=bs, interpret=True)
    expect = ref.decode_attention_ref(q, k, v, lengths)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


PREFILL_SWEEP = [
    # (B, T, H, KV, D, BQ, BK, causal)
    (1, 128, 4, 4, 64, 64, 64, True),
    (2, 256, 8, 2, 64, 128, 128, True),
    (1, 200, 4, 1, 64, 128, 64, True),     # padding path
    (2, 128, 4, 4, 64, 64, 128, False),    # encoder (hubert)
]


@pytest.mark.parametrize("b,t,h,kv,d,bq,bk,causal", PREFILL_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefill_attention_matches_ref(b, t, h, kv, d, bq, bk, causal,
                                       dtype, key):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, t, h, d), dtype)
    k = jax.random.normal(ks[1], (b, t, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, t, kv, d), dtype)
    prefix = jax.random.randint(ks[3], (b,), 0, t // 2)
    out = prefill_attention(q, k, v, prefix, causal=causal,
                            block_q=bq, block_k=bk, interpret=True)
    expect = ref.prefill_attention_ref(q, k, v, prefix, causal=causal)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


CHUNK_SWEEP = [
    # (B, T_chunk, S_cache, H, KV, D, BQ, BK)
    (2, 64, 160, 4, 2, 64, 32, 64),
    (1, 32, 96, 4, 1, 64, 32, 32),        # MQA, offset near cache end
]


@pytest.mark.parametrize("b,t,s,h,kv,d,bq,bk", CHUNK_SWEEP)
def test_prefill_attention_chunked_offset_matches_ref(b, t, s, h, kv, d,
                                                      bq, bk, key):
    """Chunked prefill: a T-token query chunk at per-row absolute
    offsets against an S-position KV span (S >= T) must match the
    oracle — causality on absolute positions, junk cache columns
    beyond a row's chunk end invisible."""
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    offset = jax.random.randint(ks[3], (b,), 0, s - t + 1)
    out = prefill_attention(q, k, v, None, offset, block_q=bq, block_k=bk,
                            interpret=True)
    expect = ref.prefill_attention_ref(q, k, v, None, offset)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=1e-5, rtol=1e-5)


def test_prefill_prefix_lm_visibility(key):
    """Prefix tokens must see each other bidirectionally."""
    b, t, h, d = 1, 64, 2, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, h, d))
    v = jax.random.normal(ks[2], (b, t, h, d))
    no_prefix = prefill_attention(q, k, v, jnp.array([0]), interpret=True,
                                  block_q=32, block_k=32)
    with_prefix = prefill_attention(q, k, v, jnp.array([16]), interpret=True,
                                    block_q=32, block_k=32)
    # token 0 attends [0] vs [0..15]: must differ
    assert not np.allclose(np.asarray(no_prefix[0, 0]),
                           np.asarray(with_prefix[0, 0]))


@pytest.mark.parametrize("b,pages,page_size", [(2, 8, 16), (3, 12, 32)])
def test_host_paged_attention_backends_agree(b, pages, page_size, rng):
    kv, h, d = 2, 8, 64
    pg = rng.standard_normal((2, pages, page_size, kv, d)).astype(np.float32)
    per = pages // b
    pt = rng.permutation(pages)[: b * per].reshape(b, per).astype(np.int32)
    lengths = rng.integers(1, per * page_size + 1, b).astype(np.int32)
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    o_jit = np.asarray(host_paged_attention(q, pg, pt, lengths,
                                            page_size=page_size))
    o_np = host_paged_attention_numpy(q, pg, pt, lengths,
                                      page_size=page_size)
    o_ref = ref.host_paged_attention_ref(q, pg, pt, lengths,
                                         page_size=page_size)
    np.testing.assert_allclose(o_jit, o_ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(o_np, o_ref, atol=2e-5, rtol=2e-5)


def test_chunked_attention_oracle_matches_dense(key):
    """The model's XLA chunked path == dense attention (layers oracle)."""
    from repro.models.attention import chunked_gqa_attention
    from repro.models.layers import gqa_attention
    b, t, h, kv, d = 2, 300, 8, 2, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, kv, d))
    v = jax.random.normal(ks[2], (b, t, kv, d))
    pos = jnp.arange(t)[None].repeat(b, 0)
    out = chunked_gqa_attention(q, k, v, q_positions=pos, kv_positions=pos,
                                causal=True, q_chunk=64, kv_chunk=128)
    expect = gqa_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


MAMBA_SWEEP = [
    # (B, T, I, N, block_i)
    (1, 16, 64, 8, 64),
    (2, 33, 128, 16, 64),     # odd T
    (2, 64, 256, 16, 128),
]


@pytest.mark.parametrize("b,t,i,n,bi", MAMBA_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba_selective_scan_matches_ref(b, t, i, n, bi, dtype, key):
    from repro.kernels.mamba_scan import (mamba_selective_scan,
                                          mamba_selective_scan_ref)
    ks = jax.random.split(key, 6)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, t, i), dtype))
    x = jax.random.normal(ks[1], (b, t, i), dtype)
    bb = jax.random.normal(ks[2], (b, t, n), dtype)
    cc = jax.random.normal(ks[3], (b, t, n), dtype)
    a_neg = -jnp.exp(jax.random.normal(ks[4], (i, n), jnp.float32))
    d_skip = jax.random.normal(ks[5], (i,), jnp.float32)
    h0 = jnp.zeros((b, i, n), jnp.float32)
    y, hT = mamba_selective_scan(dt, x, bb, cc, a_neg, d_skip, h0,
                                 block_i=bi, interpret=True)
    y_ref, hT_ref = mamba_selective_scan_ref(dt, x, bb, cc, a_neg, d_skip, h0)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_ref),
                               atol=tol, rtol=tol)


def test_mamba_scan_carries_state_across_calls(key):
    """Chunked invocation (h0 threading) == one long scan."""
    from repro.kernels.mamba_scan import (mamba_selective_scan,
                                          mamba_selective_scan_ref)
    b, t, i, n = 1, 32, 64, 8
    ks = jax.random.split(key, 6)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, t, i)))
    x = jax.random.normal(ks[1], (b, t, i))
    bb = jax.random.normal(ks[2], (b, t, n))
    cc = jax.random.normal(ks[3], (b, t, n))
    a_neg = -jnp.exp(jax.random.normal(ks[4], (i, n)))
    d_skip = jax.random.normal(ks[5], (i,))
    h0 = jnp.zeros((b, i, n), jnp.float32)
    y_full, _ = mamba_selective_scan_ref(dt, x, bb, cc, a_neg, d_skip, h0)
    half = t // 2
    y1, h_mid = mamba_selective_scan(dt[:, :half], x[:, :half], bb[:, :half],
                                     cc[:, :half], a_neg, d_skip, h0,
                                     block_i=64, interpret=True)
    y2, _ = mamba_selective_scan(dt[:, half:], x[:, half:], bb[:, half:],
                                 cc[:, half:], a_neg, d_skip, h_mid,
                                 block_i=64, interpret=True)
    np.testing.assert_allclose(np.concatenate([y1, y2], 1),
                               np.asarray(y_full), atol=2e-5, rtol=2e-5)


def test_ops_on_tpu_never_interpret_nor_reference(monkeypatch):
    """On a TPU the public wrappers always run the compiled Pallas
    kernel: neither interpret mode nor the jnp reference, whatever
    REPRO_INTERPRET_KERNELS says."""
    from repro.kernels import ops
    calls = {}

    def record(name):
        return lambda *a, **kw: calls.setdefault(name, kw)

    def refuse(*a, **kw):
        raise AssertionError("reference path taken on a TPU")

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setenv("REPRO_INTERPRET_KERNELS", "0")
    monkeypatch.setattr(ops._dec, "decode_attention", record("decode"))
    monkeypatch.setattr(ops._pre, "prefill_attention", record("prefill"))
    monkeypatch.setattr(ops._ref, "decode_attention_ref", refuse)
    monkeypatch.setattr(ops._ref, "prefill_attention_ref", refuse)
    ops.decode_attention(None, None, None, None)
    ops.prefill_attention(None, None, None)
    assert calls["decode"]["interpret"] is False
    assert calls["prefill"]["interpret"] is False
