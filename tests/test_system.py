"""End-to-end system tests through the public CLI drivers."""
import numpy as np
import pytest

from repro.launch import serve as serve_cli
from repro.launch import train as train_cli


@pytest.mark.slow
def test_train_cli_end_to_end(tmp_path):
    sup = train_cli.main([
        "--arch", "qwen2-7b", "--reduced", "--steps", "12",
        "--batch", "4", "--seq", "64", "--ckpt-dir", str(tmp_path),
        "--ckpt-every", "5", "--lr", "1e-3"])
    losses = [h["loss"] for h in sup.history]
    assert len(losses) == 12
    assert losses[-1] < losses[0]
    assert all(np.isfinite(l) for l in losses)


@pytest.mark.slow
def test_train_cli_int8_opt(tmp_path):
    sup = train_cli.main([
        "--arch", "starcoder2-3b", "--reduced", "--steps", "6",
        "--batch", "2", "--seq", "64", "--ckpt-dir", str(tmp_path),
        "--opt-compression", "int8"])
    assert sup.history[-1]["loss"] < sup.history[0]["loss"]


@pytest.mark.slow
def test_serve_cli_end_to_end():
    done = serve_cli.main([
        "--arch", "qwen2-7b", "--reduced", "--requests", "5",
        "--slots", "2", "--max-len", "48", "--max-new", "4",
        "--kv-mode", "int8"])
    assert len(done) == 5
    assert all(len(r.out) >= 1 for r in done)


def test_compile_cache_dir_env_or_checkout(monkeypatch, tmp_path):
    """The persistent compile cache lives where JAX_COMPILATION_CACHE_DIR
    says (nothing set in code), else at one fixed path in the checkout."""
    import pathlib
    import jax
    from repro.launch.compile_cache import REPO_CACHE_DIR, \
        enable_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
        repo = pathlib.Path(__file__).resolve().parents[1]
        assert REPO_CACHE_DIR == repo / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
