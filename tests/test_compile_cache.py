"""Where the persistent compilation cache lives is decided in one place
(``quiver_tpu/utils/compile_cache.py``) and, when the process was started
with ``JAX_COMPILATION_CACHE_DIR``, from outside: no code then sets
``jax_compilation_cache_dir``."""

import os
import re
from pathlib import Path

import jax
import pytest

from quiver_tpu.recovery.registry import ProgramRegistry
from quiver_tpu.utils import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Whatever a test does to the cache directory is undone."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_variable_places_the_cache(monkeypatch, cache_config):
    monkeypatch.setenv(compile_cache.ENV, "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.cache_dir() == "/some/dir"
    assert compile_cache.enable() == "/some/dir"
    # JAX took the path from the variable at import; nothing set it here
    assert jax.config.jax_compilation_cache_dir == before


def test_without_the_variable_it_is_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.cache_dir() == want
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_enable_persistent_cache_defers_to_the_variable(
        monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv(compile_cache.ENV, "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    reg = ProgramRegistry()
    assert reg.enable_persistent_cache(str(tmp_path / "pcache"))
    assert jax.config.jax_compilation_cache_dir == before
    assert reg._pcache_dir == "/some/dir"
    assert not (tmp_path / "pcache").exists()


def test_enable_persistent_cache_uses_its_argument_otherwise(
        monkeypatch, tmp_path, cache_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    reg = ProgramRegistry()
    assert reg.enable_persistent_cache(str(tmp_path / "pcache"))
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "pcache")


def _sources():
    for root in ("quiver_tpu", "benchmarks", "examples", "tests"):
        yield from (REPO / root).rglob("*.py")
    yield from (REPO / n for n in ("bench.py", "chip_smoke.py",
                                   "__graft_entry__.py", "Makefile",
                                   "pytest.ini"))


def test_only_the_helper_and_the_registry_set_the_cache_dir():
    setter = re.compile(r"update\(\s*[\"']jax_compilation_cache_dir")
    hits = {str(p.relative_to(REPO)) for p in _sources()
            if p != Path(__file__) and setter.search(p.read_text())}
    assert hits <= {"quiver_tpu/utils/compile_cache.py",
                    "quiver_tpu/recovery/registry.py",
                    "tests/test_compile_cache.py"}, hits
    # and neither entry script writes the variable any more
    writes = re.compile(r"environ(\[|\.setdefault\()\s*[\"']"
                        + compile_cache.ENV)
    for name in ("bench.py", "chip_smoke.py"):
        assert not writes.search((REPO / name).read_text()), name


def test_libtpu_lock_override_is_set_nowhere():
    name = "ALLOW_MULTIPLE_" + "LIBTPU_LOAD"
    hits = [str(p.relative_to(REPO)) for p in _sources()
            if name in p.read_text()]
    assert hits == [], hits
