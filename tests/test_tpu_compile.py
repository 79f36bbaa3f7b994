"""Compiles for a described TPU v5e chip — the only chip compiles in
the suite.  Nothing runs: the TPU compiler installed with JAX lowers
each program for a chip that is described, not attached, and refuses
what the chip would refuse (Pallas block shapes off the (8, 128)
tiling, programs that do not fit HBM).  All such compiles live in this
one file: the topology is described inside a module fixture, so only
the worker that runs this file loads the TPU library."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.mamba_scan import mamba_selective_scan
from repro.kernels.prefill_attention import prefill_attention
from repro.models import (abstract_params, decode_step,
                          decode_with_chunked_prefill, init_decode_state)
from repro.models.transformer import HostIO

V5E_HBM_BYTES = 16 * 1024 ** 3
# the chip smoke geometry (chip_smoke.py): 8 device + 8 host slots
SLOTS, HOST_SLOTS, CACHE_LEN, CHUNK = 8, 8, 2048, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Single-chip sharding, with the persistent compilation cache off:
    a described-chip compile is written to the cache but can never be
    read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), tree)


def _peak_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.fixture(scope="module")
def internlm2():
    return get_config("internlm2-1.8b")


def test_decode_attention_compiles_at_internlm2_widths(one_chip, internlm2):
    cfg = internlm2
    d = cfg.resolved_head_dim
    kv = _spec(one_chip, (SLOTS, CACHE_LEN, cfg.num_kv_heads, d),
               jnp.bfloat16)
    compiled = jax.jit(decode_attention).lower(
        _spec(one_chip, (SLOTS, cfg.num_heads, d), jnp.bfloat16), kv, kv,
        _spec(one_chip, (SLOTS,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("chunked", [False, True])
def test_prefill_attention_compiles_at_internlm2_widths(one_chip, internlm2,
                                                        chunked):
    cfg = internlm2
    d = cfg.resolved_head_dim
    if chunked:     # a staging chunk against the whole cache span
        b, t, s = SLOTS + HOST_SLOTS, CHUNK, CACHE_LEN
    else:           # a whole prompt
        b, t, s = 2, 512, 512
    q = _spec(one_chip, (b, t, cfg.num_heads, d), jnp.bfloat16)
    kv = _spec(one_chip, (b, s, cfg.num_kv_heads, d), jnp.bfloat16)
    off = _spec(one_chip, (b,), jnp.int32)
    compiled = jax.jit(
        lambda q, k, v, o: prefill_attention(q, k, v, None, o)
    ).lower(q, kv, kv, off).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mamba_scan_compiles_at_jamba_widths(one_chip):
    cfg = get_config("jamba-1.5-large-398b")
    inner = cfg.mamba.expand * cfg.d_model
    n, t = cfg.mamba.state_dim, CHUNK
    f32 = jnp.float32
    compiled = jax.jit(mamba_selective_scan).lower(
        _spec(one_chip, (1, t, inner), f32), _spec(one_chip, (1, t, inner), f32),
        _spec(one_chip, (1, t, n), f32), _spec(one_chip, (1, t, n), f32),
        _spec(one_chip, (inner, n), f32), _spec(one_chip, (inner,), f32),
        _spec(one_chip, (1, inner, n), f32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def full_width(one_chip, internlm2):
    cfg = internlm2
    params = _on(one_chip, abstract_params(cfg))
    state = _on(one_chip, jax.eval_shape(lambda: init_decode_state(
        cfg, device_batch=SLOTS, host_batch=HOST_SLOTS,
        cache_len=CACHE_LEN)))
    return cfg, params, state


def test_decode_step_fits_v5e_at_full_width(one_chip, full_width):
    cfg, params, state = full_width
    tokens = _spec(one_chip, (SLOTS,), jnp.int32)
    compiled = jax.jit(lambda p, t, s: decode_step(p, cfg, t, s)).lower(
        params, tokens, state).compile()
    assert _peak_bytes(compiled) < V5E_HBM_BYTES


def test_fused_chunk_step_fits_v5e_at_full_width(one_chip, full_width):
    """The largest program of the offload path: decode batch + host
    cohort + one prefill chunk over every staging row, with the staging
    state donated as the engine does."""
    cfg, params, state = full_width
    n_staging = SLOTS + HOST_SLOTS
    staging = _on(one_chip, jax.eval_shape(lambda: init_decode_state(
        cfg, device_batch=n_staging, cache_len=CACHE_LEN)))
    s = lambda shape, dt: _spec(one_chip, shape, dt)   # noqa: E731
    i32 = jnp.int32
    host = HostIO(
        x_carry=s((HOST_SLOTS, cfg.d_model), jnp.bfloat16),
        positions=s((HOST_SLOTS,), i32),
        attn_in=s((HOST_SLOTS, cfg.num_heads, cfg.resolved_head_dim),
                  jnp.float32),
        consume_layer=s((), i32), emit_layer=s((), i32),
        window_start=s((), i32), window_end=s((), i32),
        row_valid=s((HOST_SLOTS,), bool))
    compiled = jax.jit(
        lambda p, t, st, h, ct, cl, cs: decode_with_chunked_prefill(
            p, cfg, t, st, h, ct, cl, cs), donate_argnums=(6,)
    ).lower(params, s((SLOTS,), i32), state, host,
            s((n_staging, CHUNK), i32), s((n_staging,), i32),
            staging).compile()
    assert _peak_bytes(compiled) < V5E_HBM_BYTES
