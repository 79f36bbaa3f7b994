"""Pallas TPU kernel: causal flash attention for prefill.

Standard flash-attention-2 schedule adapted to the TPU grid model:
grid = (batch, head, q_block, kv_block) with the kv_block axis
innermost and accumulated sequentially in VMEM scratch.  Causality is
enforced with an index mask; tiles entirely in the future contribute
nothing (their scores are -inf) and are additionally skipped for
compute via ``pl.when`` (the DMA still runs — on TPU the schedule is
static, so a roofline counts causal FLOPs at 0.5x).

Supports prefix-LM masking (PaliGemma) via ``prefix_len``, and
*chunked prefill* via ``q_offset``: queries are a T-token chunk whose
row b starts at absolute position ``q_offset[b]`` while k/v cover the
whole accumulated cache span (S >= T).  The causal mask compares
absolute positions (``k_idx <= q_offset[b] + q_idx``), so a chunk
attends to every prior chunk's KV plus its own causal triangle —
junk cache columns beyond a row's chunk end are in the strict future
of all its queries and masked by the same predicate.

VMEM per step at BQ=256, BS=512, D=128, fp32: q 128 KB + k/v 512 KB +
acc 128 KB + m/l 256 KB ≈ 1 MB.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _prefill_kernel(prefix_ref, qoff_ref, q_ref, k_ref, v_ref, o_ref,
                    m_ref, l_ref, acc_ref, *,
                    block_q: int, block_k: int, scale: float, causal: bool):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    num_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # tiles strictly in the future of the whole q block are skipped
    # (chunked prefill: the block's absolute positions start at q_offset)
    run = jnp.logical_or(
        jnp.array(not causal),
        ki * block_k <= qoff_ref[b] + qi * block_q + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)             # (BQ, D)
        k = k_ref[0].astype(jnp.float32)             # (BK, D)
        v = v_ref[0].astype(jnp.float32)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale           # (BQ, BK)
        if causal:
            q_idx = qoff_ref[b] + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 0)
            k_idx = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 1)
            mask = k_idx <= q_idx
            prefix = prefix_ref[b]
            mask = mask | (k_idx < prefix)
            scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_ref[:, :1]
        m_blk = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ki == num_k - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_q", "block_k", "causal", "interpret"))
def prefill_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      prefix_len: jnp.ndarray | None = None,
                      q_offset: jnp.ndarray | None = None, *,
                      causal: bool = True, block_q: int = 256,
                      block_k: int = 512, interpret: bool = False
                      ) -> jnp.ndarray:
    """Causal (or full) flash attention.

    q: (B, T, H, D); k, v: (B, S, KV, D) with S >= T; prefix_len: (B,)
    optional prefix-LM boundary; q_offset: (B,) optional absolute
    position of each row's first query (chunked prefill — k/v then
    cover the accumulated cache span, causality is enforced on
    absolute positions).  Returns (B, T, H, D).  Compiled on TPU, D
    must be a multiple of 128 (or H == KV == 1); interpret mode takes
    any shape.
    """
    b, t, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    block_q = min(block_q, t)
    block_k = min(block_k, s)
    tq = -(-t // block_q) * block_q
    tk = -(-s // block_k) * block_k
    # heads folded into the lane axis — (B, T, H*D) and (B, S, KV*D) —
    # so head ``hi`` is column block ``hi`` of width D and every block's
    # trailing dims are (block, D): the (8, 128) tiling the TPU
    # lowering requires when D % 128 == 0
    qp = jnp.pad(q, ((0, 0), (0, tq - t), (0, 0), (0, 0))).reshape(b, tq, h * d)
    kp = jnp.pad(k, ((0, 0), (0, tk - s), (0, 0), (0, 0))).reshape(b, tk, kv * d)
    vp = jnp.pad(v, ((0, 0), (0, tk - s), (0, 0), (0, 0))).reshape(b, tk, kv * d)
    if prefix_len is None:
        prefix_len = jnp.zeros((b,), jnp.int32)
    if q_offset is None:
        q_offset = jnp.zeros((b,), jnp.int32)
    scale = 1.0 / math.sqrt(d)

    grid = (b, h, tq // block_q, tk // block_k)
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, block_q=block_q, block_k=block_k,
                          scale=scale, causal=causal),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda bi, hi, qi, ki, *_: (bi, qi, hi)),
                pl.BlockSpec((1, block_k, d),
                             lambda bi, hi, qi, ki, *_, g_=g: (bi, ki, hi // g_)),
                pl.BlockSpec((1, block_k, d),
                             lambda bi, hi, qi, ki, *_, g_=g: (bi, ki, hi // g_)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda bi, hi, qi, ki, *_: (bi, qi, hi)),
            scratch_shapes=[
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, tq, h * d), q.dtype),
        interpret=interpret,
    )(prefix_len, q_offset, qp, kp, vp)
    # rows past t attended nothing (l=0, guarded divide) — slice away
    return out.reshape(b, tq, h, d)[:, :t]
