"""Compression primitives shared by the trainer and the serving tiers.

Two families live here:

  * **Gradient compression** — per-tensor int8 quantization with error
    feedback (1-bit-Adam-family technique, adapted to int8).  On a
    multi-pod mesh the cross-pod ("pod" axis) all-reduce is the
    slowest collective; quantizing gradients to int8 cuts its bytes 4x
    (vs fp32 accumulators) while the error-feedback residual keeps the
    optimizer unbiased over time.
  * **Host-KV quantization + cold-page codec** — numpy-side symmetric
    int8 with one scale per token row (``quantize_kv_rows``), used by
    the paged host pool to store KV at 1 byte/element, and a lossless
    zstd byte codec that the pool uses to squeeze cold pages further.
    Per-row scaling makes requantization of dequantized rows exact:
    the max-magnitude element of a row always maps back to ±127, so
    the recomputed scale equals the original and int8 codes round-trip
    bit-identically through gather → write_prompt chains.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import zstandard


def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-tensor int8.  Returns (q, scale)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# Host-KV row quantization (numpy — the paged pool lives on the host)
# ---------------------------------------------------------------------------


def quantize_kv_rows(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric int8 over the trailing axes, one scale per leading row.

    ``x``: (T, kv_heads, head_dim) float.  Returns ``(q, scales)`` with
    ``q`` int8 of the same shape and ``scales`` (T,) float32.  Matches
    ``quantize_int8`` semantics per row (scale floored at 1e-12 so
    all-zero rows stay exactly zero).
    """
    xf = np.asarray(x, np.float32)
    amax = np.abs(xf).max(axis=(-2, -1)) if xf.size else \
        np.zeros(xf.shape[0], np.float32)
    scales = (np.maximum(amax, 1e-12) / 127.0).astype(np.float32)
    q = np.clip(np.rint(xf / scales[:, None, None]), -127, 127)
    return q.astype(np.int8), scales


def dequantize_kv_rows(q: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """Inverse of ``quantize_kv_rows``: (T, kv, d) int8 × (T,) → fp32."""
    return q.astype(np.float32) * np.asarray(scales,
                                             np.float32)[:, None, None]


# ---------------------------------------------------------------------------
# Lossless page codec (cold host-KV pages) — zstd
# ---------------------------------------------------------------------------


def compress_page_bytes(raw: bytes) -> bytes:
    """Losslessly compress one page blob.  zstd is bit-exact on
    decompress, so compressed cold pages never change tokens."""
    return zstandard.ZstdCompressor(level=3).compress(raw)


def decompress_page_bytes(blob: bytes) -> bytes:
    return zstandard.ZstdDecompressor().decompress(blob)


def compress_decompress_with_feedback(grads: Any, error_feedback: Optional[Any]
                                      ) -> Tuple[Any, Any]:
    """Apply int8 round-trip with error feedback.

    new_grad = dequant(quant(grad + residual)); residual' = input - new_grad.
    """
    if error_feedback is None:
        error_feedback = jax.tree.map(
            lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    def one(g, ef):
        corrected = g.astype(jnp.float32) + ef
        q, scale = quantize_int8(corrected)
        deq = dequantize_int8(q, scale)
        return deq, corrected - deq

    gl, treedef = jax.tree_util.tree_flatten(grads)
    efl = treedef.flatten_up_to(error_feedback)
    results = [one(g, ef) for g, ef in zip(gl, efl)]
    new_grads = treedef.unflatten([r[0] for r in results])
    new_ef = treedef.unflatten([r[1] for r in results])
    return new_grads, new_ef
