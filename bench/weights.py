"""Random weights from a seed, made on the device in the type they are served in.

Every value is ``k * 2**e`` with ``k`` a uniform random integer of at most
eight bits, so it is exact in bfloat16 and comes out bit-identical however
XLA batches or fuses the generator.  The served weights (built as one
stacked pytree by ``program_params``) and the plain reference (which
regenerates one layer at a time with ``layer_weights``) therefore hold the
same numbers without the reference taking anything the program made.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# one fold_in domain per kind of tensor, so the layers' keys never meet
_EMBED_DOMAIN = 1
_LAYER_DOMAIN = 1_000


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number (seeds may exceed 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    key = jax.random.PRNGKey(int(words[0] >> 1))
    return jax.random.fold_in(key, int(words[1] >> 1))


def _grid(key, shape, std: float):
    """Uniform values on the grid ``k * 2**e``, ``|k| <= kmax < 256``,
    with standard deviation close to ``std``; exact in bfloat16."""
    e = math.floor(math.log2(std * math.sqrt(3.0) / 255.0)) + 1
    step = 2.0 ** e
    kmax = max(1, min(255, round(math.sqrt(3.0) * std / step)))
    k = jax.random.randint(key, shape, -kmax, kmax + 1, dtype=jnp.int32)
    return (k.astype(jnp.float32) * step).astype(jnp.bfloat16)


def _norm_scale(key, dim: int):
    """RMSNorm gains ``1 + k/128``, ``|k| <= 8``: exact in bfloat16."""
    k = jax.random.randint(key, (dim,), -8, 9, dtype=jnp.int32)
    return (1.0 + k.astype(jnp.float32) / 128.0).astype(jnp.bfloat16)


def layer_weights(key, layer, m: Dict) -> Dict[str, jax.Array]:
    """One decoder layer's tensors (bfloat16), ``layer`` may be traced."""
    d, h, kv = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    dh = d // h
    f = m["intermediate_size"]
    ks = jax.random.split(jax.random.fold_in(key, _LAYER_DOMAIN + layer), 9)
    return {
        "ln1": _norm_scale(ks[0], d),
        "wq": _grid(ks[1], (d, h * dh), 1.0 / math.sqrt(d)),
        "wk": _grid(ks[2], (d, kv * dh), 1.0 / math.sqrt(d)),
        "wv": _grid(ks[3], (d, kv * dh), 1.0 / math.sqrt(d)),
        "wo": _grid(ks[4], (h * dh, d), 1.0 / math.sqrt(h * dh)),
        "ln2": _norm_scale(ks[5], d),
        "w_gate": _grid(ks[6], (d, f), 1.0 / math.sqrt(d)),
        "w_up": _grid(ks[7], (d, f), 1.0 / math.sqrt(d)),
        "w_down": _grid(ks[8], (f, d), 1.0 / math.sqrt(f)),
    }


def outer_weights(key, m: Dict) -> Dict[str, jax.Array]:
    """Embedding, output head and final norm gain (bfloat16)."""
    d, v = m["hidden_size"], m["vocab_size"]
    ks = jax.random.split(jax.random.fold_in(key, _EMBED_DOMAIN), 3)
    return {"embed": _grid(ks[0], (v, d), 0.02),
            "unembed": _grid(ks[1], (d, v), 1.0 / math.sqrt(d)),
            "final_norm": _norm_scale(ks[2], d)}


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
