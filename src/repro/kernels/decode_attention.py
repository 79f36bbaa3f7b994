"""Pallas TPU kernel: GQA decode attention (flash-decoding schedule).

One fresh query token per sequence attends over a contiguous KV cache
with per-row valid lengths.  The kernel is the device-side attention
hot-spot of the APEX serving path — the operation whose *host-side*
twin (``host_paged_attention``) the paper offloads.

TPU adaptation (DESIGN.md §2): instead of a CUDA warp-per-row split,
the grid walks (batch, kv_head, kv_block) with the kv_block axis
innermost and *sequentially accumulated* in VMEM scratch — the
flash-decoding online-softmax schedule expressed in the TPU's
grid-sequential execution model.  Block shapes keep the MXU fed:
the (G, D) query tile (G = heads per kv head) multiplies (BLOCK_S, D)
key tiles with D = head_dim (typically 128, MXU-aligned).

VMEM budget per step: q (G·D·4) + k,v blocks (2·BLOCK_S·D·4) + scratch
(G·D·4 + 2·G·128·4) ≈ 0.6 MB at BLOCK_S=512, D=128 — comfortably
inside the ~16 MB v5e VMEM, leaving room for double buffering.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, block_s: int, scale: float):
    b = pl.program_id(0)
    s = pl.program_id(2)
    num_s = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
    k = k_ref[0].astype(jnp.float32)               # (BS, D)
    v = v_ref[0].astype(jnp.float32)               # (BS, D)

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale              # (G, BS)
    idx = s * block_s + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(idx < length, scores, NEG_INF)

    m_prev = m_ref[:, :1]                                         # (G, 1)
    m_blk = jnp.max(scores, axis=-1, keepdims=True)               # (G, 1)
    m_new = jnp.maximum(m_prev, m_blk)
    p = jnp.exp(scores - m_new)                                   # (G, BS)
    correction = jnp.exp(m_prev - m_new)                          # (G, 1)

    l_prev = l_ref[:, :1]
    l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(s == num_s - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     lengths: jnp.ndarray, *, block_s: int = 512,
                     interpret: bool = False) -> jnp.ndarray:
    """Flash-decoding GQA attention.

    q: (B, H, D) fresh-token queries; k, v: (B, S, KV, D) contiguous
    cache; lengths: (B,) valid token counts (the fresh token's K/V must
    already be written at index lengths-1).  Returns (B, H, D).
    Compiled on TPU, D must be a multiple of 128 (or KV == 1) and
    block_s a multiple of 8; interpret mode takes any shape.
    """
    b, h, d = q.shape
    _, s, kv, _ = k.shape
    g = h // kv
    block_s = min(block_s, s)
    if s % block_s:
        pad = block_s - s % block_s
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        s += pad
    qg = q.reshape(b, kv, g, d)
    # K/V viewed as (B, S, KV*D): kv head ``hi`` is column block ``hi``
    # of width D, so every block's trailing dims are (block_s, D) — the
    # (8, 128) tiling the TPU lowering requires when D % 128 == 0
    k = k.reshape(b, s, kv * d)
    v = v.reshape(b, s, kv * d)
    scale = 1.0 / math.sqrt(d)

    grid = (b, kv, s // block_s)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda bi, hi, si, _: (bi, hi, 0, 0)),
                pl.BlockSpec((1, block_s, d),
                             lambda bi, hi, si, _: (bi, si, hi)),
                pl.BlockSpec((1, block_s, d),
                             lambda bi, hi, si, _: (bi, si, hi)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda bi, hi, si, _: (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 128), jnp.float32),   # running max
                pltpu.VMEM((g, 128), jnp.float32),   # running denominator
                pltpu.VMEM((g, d), jnp.float32),     # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        interpret=interpret,
    )(lengths, qg, k, v)
    return out.reshape(b, h, d)
