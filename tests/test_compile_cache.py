"""The compile-cache rule: one helper places JAX's persistent cache, only
entry points call it, and it never moves the cache between runs; and
``compile_cache.off`` keeps the cache out of a block wherever the block
falls in the process."""
from __future__ import annotations

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_dir_is_left_to_jax(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/cache")
    assert compile_cache.enable() == "/somewhere/cache"
    assert calls == []


def test_default_dir_is_fixed_in_the_checkout(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == str(REPO / ".jax_cache") == compile_cache.enable()
    assert calls == [("jax_compilation_cache_dir", path)] * 2
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_only_the_helper_sets_a_cache_dir():
    pat = re.compile(r"jax_compilation_cache_dir|set_cache_dir")
    offenders = [
        str(p.relative_to(REPO))
        for sub in ("src", "benchmarks", "examples", "scripts")
        for p in sorted((REPO / sub).rglob("*.py"))
        if pat.search(p.read_text(encoding="utf-8"))
        and p != REPO / "src/repro/launch/compile_cache.py"]
    assert offenders == []
    assert not pat.search((REPO / "chip_smoke.py").read_text())


@pytest.fixture
def cache_in(tmp_path):
    """The persistent cache on, in ``tmp_path``, for every program; JAX's
    settings and the cache's state are restored afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs")
    was = {n: getattr(jax.config, n) for n in names}
    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        yield tmp_path
    finally:
        for n, v in was.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


def _cache_hits_of_a_compile() -> int:
    """Compile one program anew; how many persistent-cache hits that
    took."""
    hits = []

    def listen(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    jax.monitoring.register_event_listener(listen)
    try:
        jax.clear_caches()
        jax.jit(lambda v: jnp.sin(v) * 3.0 + 0.25).lower(
            jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
    finally:
        jax.monitoring.unregister_event_listener(listen)
    return len(hits)


def test_off_keeps_a_cache_in_use_out_of_the_block(cache_in):
    assert _cache_hits_of_a_compile() == 0          # writes the program
    assert any(cache_in.iterdir())
    assert _cache_hits_of_a_compile() == 1          # the cache is in use
    entries = sorted(cache_in.iterdir())
    with compile_cache.off():
        assert _cache_hits_of_a_compile() == 0
        assert _cache_hits_of_a_compile() == 0      # nor was it written
    assert sorted(cache_in.iterdir()) == entries
    assert _cache_hits_of_a_compile() == 1


def test_off_around_the_first_compile_leaves_the_cache_on_after(cache_in):
    with compile_cache.off():
        assert _cache_hits_of_a_compile() == 0
    assert not any(cache_in.iterdir())
    assert _cache_hits_of_a_compile() == 0          # writes the program
    assert _cache_hits_of_a_compile() == 1
