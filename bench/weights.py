"""Random weights from a seed, made on the device in the type they are
served in; shared by every family (``bench/families/<family>.py``).

Every value is ``k * 2**e`` with ``k`` a uniform random integer of at most
eight bits, so it is exact in bfloat16 and comes out bit-identical however
XLA batches or fuses the generator.  A family's served weights (built as
one stacked pytree by its ``program_params``) and its plain reference
(which regenerates one layer at a time with its ``layer_weights``)
therefore hold the same numbers without the reference taking anything the
program made.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number (seeds may exceed 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    key = jax.random.PRNGKey(int(words[0] >> 1))
    return jax.random.fold_in(key, int(words[1] >> 1))


def grid(key, shape, std: float):
    """Uniform values on the grid ``k * 2**e``, ``|k| <= kmax < 256``,
    with standard deviation close to ``std``; exact in bfloat16."""
    e = math.floor(math.log2(std * math.sqrt(3.0) / 255.0)) + 1
    step = 2.0 ** e
    kmax = max(1, min(255, round(math.sqrt(3.0) * std / step)))
    k = jax.random.randint(key, shape, -kmax, kmax + 1, dtype=jnp.int32)
    return (k.astype(jnp.float32) * step).astype(jnp.bfloat16)


def norm_scale(key, dim: int):
    """RMSNorm gains ``1 + k/128``, ``|k| <= 8``: exact in bfloat16."""
    k = jax.random.randint(key, (dim,), -8, 9, dtype=jnp.int32)
    return (1.0 + k.astype(jnp.float32) / 128.0).astype(jnp.bfloat16)
