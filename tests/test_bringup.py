"""Bring-up guards (fast tier): the compile cache is placed from
outside or at one fixed path, and an accelerator that cannot report
its memory stops the engine instead of being budgeted by assumption."""

import os
import types

import jax
import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine
from kaito_tpu.models import get_model_by_name
from kaito_tpu.utils.platform import DEFAULT_CACHE_DIR, enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_env_placement_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_path(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        picked = []
        for cwd in (tmp_path, "/"):
            monkeypatch.chdir(cwd)
            assert enable_compile_cache() == DEFAULT_CACHE_DIR
            picked.append(jax.config.jax_compilation_cache_dir)
        # inside the checkout, whatever the working directory
        assert picked == [os.path.join(REPO, ".jax_cache")] * 2
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


class _MuteChip:
    """An accelerator whose runtime cannot report memory."""

    platform = "tpu"
    process_index = 0

    def memory_stats(self):
        raise RuntimeError("memory_stats unimplemented")


def test_derive_max_pages_raises_without_memory_stats(monkeypatch):
    md = get_model_by_name("tiny-llama-test")
    cfg = EngineConfig(model=md.name, max_model_len=256)
    eng = types.SimpleNamespace(
        mesh=None, pp_exec=None, md=md, cfg=cfg, params={},
        pages_per_seq=cfg.pages_per_seq)
    monkeypatch.setattr(jax, "local_devices", lambda: [_MuteChip()])
    with pytest.raises(RuntimeError, match="memory_stats"):
        InferenceEngine._derive_max_pages(eng)
    # ... and one that answers with nothing is refused the same way
    monkeypatch.setattr(_MuteChip, "memory_stats", lambda self: None)
    with pytest.raises(RuntimeError, match="no memory_stats"):
        InferenceEngine._derive_max_pages(eng)
