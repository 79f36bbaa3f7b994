"""Entry-point plumbing: which model the launchers serve, how they size
the host pool, and where they put JAX's compilation cache."""
import argparse
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.launch.compile_cache import (DEFAULT_CACHE_DIR,
                                        configure_compile_cache)
from repro.launch.serve import add_model_args, host_pool_pages, model_config

REPO = Path(__file__).resolve().parents[1]


def _args(*argv):
    ap = argparse.ArgumentParser()
    add_model_args(ap)
    return ap.parse_args(list(argv))


def test_launchers_serve_published_widths_unless_reduced():
    published = model_config(_args("--arch", "internlm2-1.8b"))
    assert published == get_config("internlm2-1.8b")
    assert (published.num_layers, published.d_model, published.num_heads,
            published.num_kv_heads, published.vocab_size) \
        == (24, 2048, 16, 8, 92544)
    reduced = model_config(_args("--arch", "internlm2-1.8b",
                                 "--layers", "4", "--d-model", "128"))
    assert (reduced.num_layers, reduced.d_model, reduced.num_heads,
            reduced.vocab_size) == (4, 128, 4, 512)
    # either flag alone asks for the reduced model
    assert model_config(_args("--layers", "2")).d_model == 128
    assert model_config(_args("--d-model", "64")).num_layers == 4


def test_host_pool_holds_every_host_slot_at_full_cache():
    cfg = get_config("internlm2-1.8b")
    pages = host_pool_pages(cfg, host_slots=8, cache_len=2048)
    assert pages == 8 * 64 * 24          # 32-token pages, per attention layer
    assert host_pool_pages(cfg, host_slots=1, cache_len=33) == 2 * 24


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_directory(monkeypatch, tmp_path, from_env):
    was = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            jax.config.update("jax_compilation_cache_dir", str(tmp_path))
            assert configure_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert configure_compile_cache() == str(REPO / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == str(
                DEFAULT_CACHE_DIR)
            ignored = (REPO / ".gitignore").read_text().split()
            assert ".jax_cache/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
