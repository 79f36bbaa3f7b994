"""Host-tier paged attention — the paper's Llamafile-kernel analogue.

The paper replaces NEO's ISPC CPU paged-attention with Llamafile GEMM
kernels and reports ~2x at large batch (§4.1).  On a TPU host the
equivalent is a *blocked, cache-friendly* paged-attention running on
the host CPU.  Two implementations live here:

  * ``host_paged_attention`` — jax-cpu jit of a page-gather +
    flash-style blocked attention.  This is the "kernel" the host
    backend dispatches; XLA:CPU vectorizes the GEMMs (the Llamafile
    role) and releases the GIL while executing (the Pybind11 role).
  * ``host_paged_attention_numpy`` — dependency-free numpy fallback
    used by the threaded executor for very small batches where jit
    dispatch overhead dominates, and as a second oracle.

Layout: pages (2, P, page_size, KV, D) — index 0 keys, 1 values — with
page tables (B, max_pages) and per-row lengths, matching
``repro.models.kv_cache.PagedKVPool``.

Both kernels take an optional ``scales`` operand, (2, P, page_size)
fp32: when given, ``pages`` holds symmetric int8 and each slot's row
is dequantized *inside* the kernel during the per-request page gather
(``k = q_int8 * scale`` fused into the existing ``astype`` step) — a
full-precision copy of the pool is never materialized.  ``scales=None``
is the legacy full-precision path, bit-identical to before.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_CPU = None


def _cpu_device():
    global _CPU
    if _CPU is None:
        _CPU = jax.devices("cpu")[0]
    return _CPU


def _attention_core(q, k, v, lengths, s):
    """Shared blocked-softmax core.  k, v: (B, S, KV, D) f32."""
    b, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, kv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k) * scale
    idx = jnp.arange(s)[None, None, None, :]
    scores = jnp.where(idx < lengths[:, None, None, None], scores, -1e30)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p / jnp.maximum(l, 1e-30), v)
    return out.reshape(b, h, d)


@functools.partial(jax.jit, static_argnames=("page_size",))
def _paged_attention_impl(q, pages, page_table, lengths, *, page_size: int):
    """q: (B, H, D); pages: (2, P, page_size, KV, D);
    page_table: (B, MP) int32; lengths: (B,).  Returns (B, H, D) f32."""
    b = q.shape[0]
    d = q.shape[2]
    kv = pages.shape[3]
    mp = page_table.shape[1]
    s = mp * page_size

    # gather this batch's pages: (B, MP, page_size, KV, D)
    k = pages[0][page_table].reshape(b, s, kv, d).astype(jnp.float32)
    v = pages[1][page_table].reshape(b, s, kv, d).astype(jnp.float32)
    return _attention_core(q, k, v, lengths, s)


@functools.partial(jax.jit, static_argnames=("page_size",))
def _paged_attention_quant_impl(q, pages, scales, page_table, lengths, *,
                                page_size: int):
    """Quantized variant: pages int8, scales (2, P, page_size) fp32 —
    dequant is fused into the page gather (no fp32 pool copy)."""
    b = q.shape[0]
    d = q.shape[2]
    kv = pages.shape[3]
    mp = page_table.shape[1]
    s = mp * page_size

    sk = scales[0][page_table].reshape(b, s, 1, 1)
    sv = scales[1][page_table].reshape(b, s, 1, 1)
    k = pages[0][page_table].reshape(b, s, kv, d).astype(jnp.float32) * sk
    v = pages[1][page_table].reshape(b, s, kv, d).astype(jnp.float32) * sv
    return _attention_core(q, k, v, lengths, s)


def host_paged_attention(q, pages, page_table, lengths, *, page_size: int,
                         scales=None):
    """Host (CPU-tier) paged attention.  Always executes on the CPU
    backend regardless of the default device: the inputs are placed on
    the CPU device, and jit runs where its inputs live.  ``scales``
    selects the fused-dequant int8 path (see module docstring)."""
    cpu = _cpu_device()
    if scales is None:
        args = jax.device_put((q, pages, page_table, lengths), cpu)
        return _paged_attention_impl(*args, page_size=page_size)
    args = jax.device_put((q, pages, scales, page_table, lengths), cpu)
    return _paged_attention_quant_impl(*args, page_size=page_size)


def host_paged_attention_numpy(q: np.ndarray, pages: np.ndarray,
                               page_table: np.ndarray, lengths: np.ndarray,
                               *, page_size: int,
                               scales: Optional[np.ndarray] = None,
                               out: Optional[np.ndarray] = None) -> np.ndarray:
    """Blocked numpy implementation (GIL released inside BLAS calls).

    ``out`` (B, H, D) float32, written in place when given — lets the
    threaded executor shard rows of one job across workers into
    disjoint views of a preallocated per-job buffer.  ``scales``
    enables the fused-dequant int8 path: only each request's own chain
    is dequantized, inside the existing per-row ``astype`` gather.
    """
    b, h, d = q.shape
    kv = pages.shape[3]
    g = h // kv
    scale = 1.0 / math.sqrt(d)
    if out is None:
        out = np.empty((b, h, d), np.float32)
    for i in range(b):
        n = int(lengths[i])
        npages = -(-n // page_size) if n else 0
        chain = page_table[i, :npages]
        k = pages[0, chain].reshape(-1, kv, d)[:n].astype(np.float32)
        v = pages[1, chain].reshape(-1, kv, d)[:n].astype(np.float32)
        if scales is not None:
            k *= scales[0, chain].reshape(-1)[:n, None, None]
            v *= scales[1, chain].reshape(-1)[:n, None, None]
        qi = q[i].reshape(kv, g, d).astype(np.float32)
        scores = np.einsum("kgd,skd->kgs", qi, k) * scale
        m = scores.max(-1, keepdims=True)
        p = np.exp(scores - m)
        p /= np.maximum(p.sum(-1, keepdims=True), 1e-30)
        out[i] = np.einsum("kgs,skd->kgd", p, v).reshape(h, d)
    return out
