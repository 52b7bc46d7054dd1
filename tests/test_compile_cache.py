"""First dedicated coverage for utils/compile_cache.py.

Pins three behaviors that previously had no test of their own:

- enable/fallback: a usable dir enables the persistent cache, a falsy or
  unusable one disables it (and clears the env-var-injected default)
  WITHOUT failing startup;
- in-process re-point: jax latches its cache singleton on first compile,
  so changing the dir must go through jax's public ``reset_cache()``
  for later compiles to land in the new dir;
- counters + structured log: the jax monitoring hooks count compiles /
  persistent-cache hits / misses / persists and emit one
  ``tpumlops.compile`` line per compilation.
"""

import logging

import jax
import jax.numpy as jnp
import pytest

from tpumlops.utils import compile_cache as cc


@pytest.fixture(autouse=True)
def _restore_cache_config():
    """Leave the process-wide cache config the way each test found it."""
    prior = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prior)
    cc._reset_jax_cache_singleton()


def _unique_fn(tag: float):
    """A jit whose jaxpr differs per tag — guaranteed fresh cache key."""
    return jax.jit(lambda x: x * tag + (tag + 1.0))


def test_enable_returns_true_and_points_jax_at_dir(tmp_path):
    d = tmp_path / "cache"
    assert cc.enable_persistent_compile_cache(str(d)) is True
    assert jax.config.jax_compilation_cache_dir == str(d)
    assert d.is_dir()  # created on demand


def test_falsy_dir_disables_even_with_env_default(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cc.enable_persistent_compile_cache("") is False
    assert cc.enable_persistent_compile_cache(None) is False
    assert jax.config.jax_compilation_cache_dir is None


def test_unusable_dir_falls_back_without_raising(tmp_path, caplog):
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("file where the cache dir should go")
    with caplog.at_level(logging.WARNING, logger="tpumlops.compile_cache"):
        assert cc.enable_persistent_compile_cache(str(blocker)) is False
    assert jax.config.jax_compilation_cache_dir is None
    assert any("unusable" in r.getMessage() for r in caplog.records)


def test_in_process_repoint_takes_effect(tmp_path):
    """The PR 1 ``reset_cache()`` fix: without it, jax's singleton latches
    the FIRST dir at the first compile and silently ignores every later
    config update — entries would keep landing in d1."""
    d1, d2 = tmp_path / "one", tmp_path / "two"
    assert cc.enable_persistent_compile_cache(str(d1)) is True
    _unique_fn(3.5)(jnp.ones((16, 16))).block_until_ready()
    n1 = cc.cache_entry_count(str(d1))
    assert n1 >= 1  # the first dir took writes

    assert cc.enable_persistent_compile_cache(str(d2)) is True
    _unique_fn(7.25)(jnp.ones((16, 16))).block_until_ready()
    assert cc.cache_entry_count(str(d2)) >= 1, (
        "re-pointed dir took no writes: the cache singleton was not reset"
    )
    assert cc.cache_entry_count(str(d1)) == n1  # old dir no longer written


def test_repoint_resets_through_the_public_api(tmp_path, monkeypatch):
    """Every (re-)point goes through jax's PUBLIC ``reset_cache`` — one
    installation, no private-module probing — and a failure there is
    raised, not swallowed: a re-point that did not take would keep
    writing the previous dir while looking successful."""
    from jax.experimental.compilation_cache import compilation_cache as pub

    calls = []
    real = pub.reset_cache
    monkeypatch.setattr(
        pub, "reset_cache", lambda: (calls.append(1), real())[1]
    )
    assert cc.enable_persistent_compile_cache(str(tmp_path / "a")) is True
    assert cc.enable_persistent_compile_cache(str(tmp_path / "b")) is True
    assert len(calls) == 2

    def boom():
        raise RuntimeError("reset refused")

    monkeypatch.setattr(pub, "reset_cache", boom)
    with pytest.raises(RuntimeError, match="reset refused"):
        cc.enable_persistent_compile_cache(str(tmp_path / "c"))


def test_counters_and_one_structured_line_per_compile(tmp_path, caplog):
    cc.install_compile_listeners()
    assert cc.enable_persistent_compile_cache(str(tmp_path / "c")) is True
    before = cc.counters_snapshot()
    with caplog.at_level(logging.INFO, logger="tpumlops.compile"):
        # Fresh jaxpr: a persistent-cache MISS that persists an entry.
        _unique_fn(11.5)(jnp.ones((8, 8))).block_until_ready()
        # Identical jaxpr under a NEW jit object: jax's in-memory jit
        # cache cannot serve it, so the compile request goes to the
        # persistent cache — a HIT.
        _unique_fn(11.5)(jnp.ones((8, 8))).block_until_ready()
    after = cc.counters_snapshot()
    assert after["compiles"] > before["compiles"]
    assert after["compile_seconds"] > before["compile_seconds"]
    assert after["misses"] >= before["misses"] + 1
    assert after["persists"] >= before["persists"] + 1
    assert after["hits"] >= before["hits"] + 1
    lines = [
        r.getMessage() for r in caplog.records if r.name == "tpumlops.compile"
    ]
    assert any(line.startswith("compiled op=") for line in lines)
    # Record attributes ride along for the JSON log format.
    recs = [r for r in caplog.records if r.name == "tpumlops.compile"]
    assert any(hasattr(r, "compile_op") for r in recs)


def test_misses_without_cache_dir_do_not_count_persists():
    cc.install_compile_listeners()
    assert cc.enable_persistent_compile_cache("") is False
    before = cc.counters_snapshot()
    _unique_fn(17.25)(jnp.ones((8, 8))).block_until_ready()
    after = cc.counters_snapshot()
    assert after["compiles"] > before["compiles"]
    assert after["persists"] == before["persists"]


def test_detach_observatory_stops_attribution():
    """Server shutdown unbinds its observatory: later compiles stop
    feeding the retired object (and its metrics registry)."""

    class _Obs:
        def __init__(self):
            self.events = []

        def current_op(self):
            return "x"

        def on_event(self, kind, seconds=0.0):
            self.events.append(kind)

    obs = _Obs()
    cc.install_compile_listeners(observatory=obs)
    try:
        _unique_fn(23.5)(jnp.ones((8, 8))).block_until_ready()
        assert "compile" in obs.events
        n = len(obs.events)
        cc.detach_observatory(obs)
        _unique_fn(29.25)(jnp.ones((8, 8))).block_until_ready()
        assert len(obs.events) == n  # no further attribution
        # Detaching a non-registered object is a no-op.
        cc.detach_observatory(object())
    finally:
        cc.detach_observatory(obs)
