"""Operations and bytes of the model's work, computed from its shapes.

Counts are of the work a token needs, not of what a program happens to
execute: a decode token reads every layer's weights and the live keys
and values of its own row, and nothing past its length.
"""
from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2


def dims(m: Dict) -> Dict[str, int]:
    d, h = m["hidden_size"], m["num_attention_heads"]
    dh = d // h
    kv = m["num_key_value_heads"]
    return dict(d=d, h=h, dh=dh, kv=kv, f=m["intermediate_size"],
                v=m["vocab_size"], layers=m["num_hidden_layers"])


def layer_params(m: Dict) -> int:
    x = dims(m)
    attn = 2 * x["d"] * x["h"] * x["dh"] + 2 * x["d"] * x["kv"] * x["dh"]
    return attn + 3 * x["d"] * x["f"] + 2 * x["d"]


def param_count(m: Dict) -> int:
    """All parameters: layers, embedding, output head, final norm."""
    x = dims(m)
    return x["layers"] * layer_params(m) + 2 * x["v"] * x["d"] + x["d"]


def matmul_params(m: Dict) -> int:
    """Parameters a token multiplies through: every layer's matrices and
    the output head (the embedding is a lookup)."""
    x = dims(m)
    return x["layers"] * (layer_params(m) - 2 * x["d"]) + x["d"] * x["v"]


def flops_per_token(m: Dict, context: float = 0.0) -> float:
    """Forward operations of one token that attends over ``context``
    earlier positions: 2 per matrix parameter plus QK^T and PV."""
    x = dims(m)
    attn = 4.0 * x["layers"] * x["h"] * x["dh"] * context
    return 2.0 * matmul_params(m) + attn


def prompt_flops_per_token(m: Dict) -> float:
    """A prompt token's matrix operations, without the output head (the
    prefill takes logits of the last prompt token only); its attention
    is left out, so the count is a lower bound."""
    x = dims(m)
    return 2.0 * (matmul_params(m) - x["d"] * x["v"])


def kv_bytes_per_token(m: Dict) -> int:
    x = dims(m)
    return 2 * x["layers"] * x["kv"] * x["dh"] * BF16


def weight_bytes(m: Dict) -> int:
    """Bytes of the weights one step reads: matrices, output head, norms
    (embedding rows are gathered, so they are left out)."""
    x = dims(m)
    return BF16 * (matmul_params(m) + (2 * x["layers"] + 1) * x["d"])


def step_cost(m: Dict, device_contexts: Iterable[int], other_tokens: int,
              prompt_tokens: int) -> tuple:
    """(operations, bytes) of one engine step: decode rows on the device
    at their live contexts, tokens of host rows (their matrices run on the
    device, their attention on the host) and prompt tokens."""
    ctx = list(device_contexts)
    flops = sum(flops_per_token(m, c) for c in ctx)
    flops += other_tokens * flops_per_token(m, 0)
    flops += prompt_tokens * prompt_flops_per_token(m)
    kv = kv_bytes_per_token(m)
    nbytes = weight_bytes(m) + sum(kv * (c + 1) for c in ctx)
    return flops, nbytes
