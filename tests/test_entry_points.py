"""The entry points' config resolution and compile-cache helper (CPU only;
nothing here builds a model)."""
import dataclasses
import os

import jax
import pytest

from repro.configs import all_arch_ids, get_config, resolve_config
from repro.launch import compile_cache


@pytest.mark.parametrize("arch", all_arch_ids())
def test_layers_without_reduced_keeps_every_width(arch):
    full = get_config(arch)
    depth = full.n_enc_layers + 2
    cfg = resolve_config(arch, layers=depth)
    assert cfg.n_layers == depth
    assert cfg == dataclasses.replace(full, n_layers=depth)


def test_layers_without_reduced_qwen3_published_widths():
    cfg = resolve_config("qwen3-1.7b", layers=4)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, cfg.d_ff, cfg.vocab, cfg.qk_norm) == \
        (4, 2048, 16, 8, 128, 6144, 151936, True)
    assert resolve_config("qwen3-1.7b") == get_config("qwen3-1.7b")


def test_reduced_layers_unchanged():
    cfg = resolve_config("qwen3-1.7b", reduced=True, layers=3, d_model=128,
                         seq=32)
    assert cfg == get_config("qwen3-1.7b").reduced(n_layers=3, d_model=128,
                                                   seq=32)
    assert resolve_config("qwen3-1.7b", reduced=True) == \
        get_config("qwen3-1.7b").reduced(n_layers=4, d_model=256, seq=64)


def test_full_width_rejects_reduced_only_options():
    with pytest.raises(ValueError, match="d_model"):
        resolve_config("qwen3-1.7b", d_model=128)
    whisper = get_config("whisper-base")
    with pytest.raises(ValueError, match="encoder"):
        resolve_config("whisper-base", layers=whisper.n_enc_layers)
    from repro.launch import train
    with pytest.raises(SystemExit):
        train.main(["--arch", "qwen3-1.7b", "--d-model", "128"])


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_respects_env(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax-cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_in_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == second == os.path.join(checkout, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
