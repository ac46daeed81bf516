"""Entry-point plumbing: compile-cache placement, the arch registry's
published and reduced names, and the synthetic LM stream at a published
vocabulary."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro import compile_cache
from repro.configs import (ALL_ARCHS, REDUCED_ARCHS, get_config,
                           get_reduced_config)
from repro.data import BigramLM


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_var_is_not_overridden(monkeypatch, tmp_path,
                                                 restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = compile_cache.DEFAULT_DIR.parent
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert (root / "src" / "repro" / "compile_cache.py").exists()
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.enable_compile_cache() == got     # never moves


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_registry_published_and_reduced_names(arch):
    full = get_config(arch)
    reduced = get_config(arch + "-reduced")
    assert full.name == arch
    assert reduced is get_reduced_config(arch)
    assert reduced.name == arch + "-reduced"
    assert reduced.name in REDUCED_ARCHS


def test_registry_rejects_unknown_arch():
    with pytest.raises(KeyError):
        get_config("smollm-360m-tiny")


def test_bigram_stream_at_published_vocab_is_sparse_and_deterministic():
    V = get_config("smollm-360m").vocab_size
    a, b = BigramLM(V, seed=3), BigramLM(V, seed=3)
    assert a.P.shape[0] == V and a.P.shape[1] < V      # no V x V matrix
    ba, bb = a.batch(2, 256), b.batch(2, 256)
    np.testing.assert_array_equal(ba["tokens"], bb["tokens"])
    np.testing.assert_array_equal(ba["tokens"][:, 1:], ba["labels"][:, :-1])
    assert 0 <= ba["tokens"].min() and ba["tokens"].max() < V
    # every move follows the chain: the next token is one of the
    # current token's fixed successors
    t = ba["tokens"][0]
    assert all(t[i + 1] in a.succ[t[i]] for i in range(len(t) - 1))
    assert 0.0 < a.entropy_floor() < np.log(a.P.shape[1])
