"""The InternLM2 family (arXiv:2403.17297): dense grouped-query attention
with rotary embeddings, a SwiGLU MLP, RMSNorm before each, an untied
output head, no biases; the head size is ``hidden_size /
num_attention_heads``.

A family module is what a configuration file's ``"family"`` key names
(``bench/families/<family>.py``; a file without the key is InternLM2).
It gives the benchmark the serving engine's ``ModelConfig``, the seeded
weights in the engine's layout and, one layer at a time, for the plain
reference, and the operations and bytes of an engine step.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.weights import grid, norm_scale, seed_key

BF16 = 2
# engine counters whose change in each step the client records into
# ``StepRecord.counters``; this family's counts need none
STEP_COUNTERS = ()

# one fold_in domain per kind of tensor, so the layers' keys never meet
_EMBED_DOMAIN = 1
_LAYER_DOMAIN = 1_000


def model_config(c: Dict):
    """The serving engine's ``ModelConfig`` for a configuration file."""
    from repro.models.config import BlockKind, FFNKind, ModelConfig

    m = c["model"]
    if m.get("tie_word_embeddings") or m.get("bias"):
        raise ValueError(f"{c['name']}: tied embeddings or biases are not "
                         f"in the InternLM2 family")
    return ModelConfig(
        name=c["name"], num_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        block_pattern=(BlockKind.ATTN,), ffn_kind=FFNKind.DENSE,
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        max_seq_len=m["max_position_embeddings"])


# --- weights --------------------------------------------------------------

def layer_weights(key, layer, m: Dict) -> Dict[str, jax.Array]:
    """One decoder layer's tensors (bfloat16), ``layer`` may be traced."""
    d, h, kv = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    dh = d // h
    f = m["intermediate_size"]
    ks = jax.random.split(jax.random.fold_in(key, _LAYER_DOMAIN + layer), 9)
    return {
        "ln1": norm_scale(ks[0], d),
        "wq": grid(ks[1], (d, h * dh), 1.0 / math.sqrt(d)),
        "wk": grid(ks[2], (d, kv * dh), 1.0 / math.sqrt(d)),
        "wv": grid(ks[3], (d, kv * dh), 1.0 / math.sqrt(d)),
        "wo": grid(ks[4], (h * dh, d), 1.0 / math.sqrt(h * dh)),
        "ln2": norm_scale(ks[5], d),
        "w_gate": grid(ks[6], (d, f), 1.0 / math.sqrt(d)),
        "w_up": grid(ks[7], (d, f), 1.0 / math.sqrt(d)),
        "w_down": grid(ks[8], (f, d), 1.0 / math.sqrt(f)),
    }


def outer_weights(key, m: Dict) -> Dict[str, jax.Array]:
    """Embedding, output head and final norm gain (bfloat16)."""
    d, v = m["hidden_size"], m["vocab_size"]
    ks = jax.random.split(jax.random.fold_in(key, _EMBED_DOMAIN), 3)
    return {"embed": grid(ks[0], (v, d), 0.02),
            "unembed": grid(ks[1], (d, v), 1.0 / math.sqrt(d)),
            "final_norm": norm_scale(ks[2], d)}


def program_params(seed: int, m: Dict):
    """The served weights in the serving engine's ``ModelParams`` layout,
    made by one jitted call on the default device."""
    from repro.models.model import ModelParams

    n_layers = m["num_hidden_layers"]

    def make(key):
        o = outer_weights(key, m)
        lw = jax.lax.map(lambda i: layer_weights(key, i, m),
                         jnp.arange(n_layers, dtype=jnp.int32))
        block = {"ln1": {"scale": lw["ln1"]},
                 "attn": {n: lw[n] for n in ("wq", "wk", "wv", "wo")},
                 "ln2": {"scale": lw["ln2"]},
                 "ffn": {n: lw[n] for n in ("w_gate", "w_up", "w_down")}}
        return ModelParams(
            embedding={"embed": o["embed"], "unembed": o["unembed"]},
            blocks=(block,), final_norm={"scale": o["final_norm"]})

    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))


# --- operations and bytes -------------------------------------------------
# Counts are of the work a token needs, not of what a program happens to
# execute: a decode token reads every layer's weights and the live keys
# and values of its own row, and nothing past its length.

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


def step_cost(m: Dict, step) -> tuple:
    """(operations, bytes) of one engine step (a ``StepRecord``).
    Operations: decode rows on the device at their live contexts, tokens
    of host rows (their matrices run on the device, their attention on the
    host) and prompt tokens.  Bytes: the weights once, and each device
    row's live keys and values."""
    ctx = list(step.device_contexts)
    flops = sum(flops_per_token(m, c) for c in ctx)
    flops += step.host_tokens * flops_per_token(m, 0)
    flops += step.prompt_tokens * prompt_flops_per_token(m)
    kv = kv_bytes_per_token(m)
    nbytes = weight_bytes(m) + sum(kv * (c + 1) for c in ctx)
    return flops, nbytes
