"""A test family: grouped-query attention whose head size is the
configuration's ``head_dim``, not ``hidden_size / num_attention_heads``;
otherwise as InternLM2 (rotary embeddings, SwiGLU MLP, RMSNorm, untied
head).  Its operation count reads the prompt tokens of each step from an
engine counter it names in ``STEP_COUNTERS``."""
import math

import jax
import jax.numpy as jnp

from bench.weights import grid, norm_scale, seed_key

STEP_COUNTERS = ("chunked_prefill_tokens",)


def model_config(c):
    from repro.models.config import ModelConfig

    m = c["model"]
    return ModelConfig(
        name=c["name"], num_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        max_seq_len=m["max_position_embeddings"])


def layer_weights(key, layer, m):
    d, h, kv = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    dh, f = m["head_dim"], m["intermediate_size"]
    ks = jax.random.split(jax.random.fold_in(key, 1_000 + layer), 9)
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


def outer_weights(key, m):
    d, v = m["hidden_size"], m["vocab_size"]
    ks = jax.random.split(jax.random.fold_in(key, 1), 3)
    return {"embed": grid(ks[0], (v, d), 0.02),
            "unembed": grid(ks[1], (d, v), 1.0 / math.sqrt(d)),
            "final_norm": norm_scale(ks[2], d)}


def program_params(seed, m):
    from repro.models.model import ModelParams

    def make(key):
        o = outer_weights(key, m)
        lw = jax.lax.map(lambda i: layer_weights(key, i, m),
                         jnp.arange(m["num_hidden_layers"], dtype=jnp.int32))
        block = {"ln1": {"scale": lw["ln1"]},
                 "attn": {n: lw[n] for n in ("wq", "wk", "wv", "wo")},
                 "ln2": {"scale": lw["ln2"]},
                 "ffn": {n: lw[n] for n in ("w_gate", "w_up", "w_down")}}
        return ModelParams(
            embedding={"embed": o["embed"], "unembed": o["unembed"]},
            blocks=(block,), final_norm={"scale": o["final_norm"]})

    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))


def _matrices(m):
    d, h, kv = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    dh = m["head_dim"]
    return (2 * d * h * dh + 2 * d * kv * dh
            + 3 * d * m["intermediate_size"]) * m["num_hidden_layers"]


def param_count(m):
    d, v = m["hidden_size"], m["vocab_size"]
    return _matrices(m) + (2 * m["num_hidden_layers"] + 1) * d + 2 * v * d


def step_cost(m, step):
    d, v = m["hidden_size"], m["vocab_size"]
    attn = 4.0 * m["num_hidden_layers"] * m["num_attention_heads"] * m["head_dim"]
    token = 2.0 * (_matrices(m) + d * v)
    decode = len(step.device_contexts) + step.host_tokens
    flops = (decode * token + attn * sum(step.device_contexts)
             + 2.0 * _matrices(m) * step.counters["chunked_prefill_tokens"])
    kv = 2 * m["num_hidden_layers"] * m["num_key_value_heads"] * m["head_dim"] * 2
    nbytes = 2 * (_matrices(m) + d * v) + sum(kv * (c + 1)
                                              for c in step.device_contexts)
    return flops, nbytes
