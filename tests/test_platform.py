"""What decides where the program runs and what it keeps: the kernels'
interpret switch, the one-device mesh, the compile cache's directory, and
the failures that must not pass silently (a benchmark section that
raised)."""

from __future__ import annotations

from pathlib import Path

import jax
import pytest

from repro.kernels.platform import resolve_interpret
from repro.launch import cache
from repro.launch.mesh import make_test_mesh


def test_interpret_resolves_from_the_backend():
    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True


def test_test_mesh_takes_one_device():
    mesh = make_test_mesh(devices=jax.devices()[:1])
    assert dict(mesh.shape) == {"data": 1, "model": 1}


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_fixed_dir_in_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = cache.enable_compile_cache()
    assert got == str(cache.CACHE_DIR)
    assert cache.CACHE_DIR.parent == Path(__file__).resolve().parents[1]
    assert jax.config.jax_compilation_cache_dir == got


def test_compile_cache_env_left_to_jax(monkeypatch, cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    jax.config.update("jax_compilation_cache_dir", None)
    assert cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir is None


def test_benchmark_run_exits_nonzero_on_a_failed_section(monkeypatch,
                                                         capsys):
    from benchmarks import run

    def broken(*, smoke):
        raise ValueError("section broke")

    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    monkeypatch.setitem(run.SECTIONS, "broken", broken)
    assert run.main(["--only", "broken"]) == 1
    assert "broken/ERROR" in capsys.readouterr().out
