"""Persistent XLA compilation cache (SURVEY §7 hard part 3).

TPU cold-start is the canary killer: the first request into a freshly
scheduled 10%-traffic predictor triggers a 20–40 s XLA compile, which lands
in the Prometheus latency window and fails the promotion gate before the
model has served a single steady-state request.  The reference never faces
this (its Seldon ``MLFLOW_SERVER`` pods are interpreted CPU Python,
``mlflow_operator.py:198``); a TPU data plane must solve it.

Two layers of defense:

1. **Warmup before readiness** — the server compiles every batch bucket
   before answering the readiness probe (``server/app.py``), so no live
   request ever pays a compile.
2. **This module** — persists compiled executables to a node-local
   directory (the manifest builder mounts a ``hostPath`` volume, so the
   cache survives pod restarts and is shared between the stable and canary
   pods scheduled on the same TPU host).  Warmup on a warm node then takes
   ~100 ms of cache deserialization instead of tens of seconds of XLA work,
   which keeps time-to-ready — and therefore time-to-100%-traffic, the
   north-star metric — low.

JAX's own defaults are tuned for big training jobs: entries below 1 s of
compile time are not persisted.  Canary models (iris, xgboost, small BERT
buckets) compile faster than that, so we lower both floors to zero —
a cache miss on *any* bucket is a readiness-latency regression here.
"""

from __future__ import annotations

import logging
import os
import threading
from pathlib import Path

_log = logging.getLogger("tpumlops.compile_cache")
# One structured line per compilation (see install_compile_listeners).
_compile_log = logging.getLogger("tpumlops.compile")

# Process-wide compile/cache counters, fed by jax's monitoring events
# (install_compile_listeners).  "hits"/"misses" are persistent-cache
# outcomes of compile requests; "persists" counts misses taken while a
# cache dir was active (with our min-entry floors at zero, every such
# miss writes an entry); "compiles" counts backend compilations and
# "compile_seconds" their summed wall.
COUNTERS = {
    "hits": 0, "misses": 0, "persists": 0,
    "compiles": 0, "compile_seconds": 0.0,
}
_counters_lock = threading.Lock()
_listeners_installed = False
_observatory = None  # server.device_telemetry.CompileObservatory | None

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# Where the cache lives when nobody placed it from outside: one fixed
# path inside the checkout (the path is part of jax's cache key, so a
# directory that moves — a tempdir, a pid, a timestamp — never hits).
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_compile_cache")


def resolve_compile_cache_dir(requested: str | None = None) -> str:
    """THE answer to "where does this process keep its compile cache",
    for every entry point (server CLI, chip_smoke.py, scripts).

    ``JAX_COMPILATION_CACHE_DIR`` set → that directory and no other:
    whoever runs the program placed the cache, and a flag or a default
    of ours does not move it.  Unset → ``requested`` when given, else
    :data:`DEFAULT_CACHE_DIR`.  An explicit empty ``requested`` means
    "no persistent cache" and stays empty either way (nothing is written
    anywhere)."""
    if requested == "":
        return ""
    placed = os.environ.get(CACHE_DIR_ENV)
    if placed:
        if requested and requested != placed:
            _log.warning(
                "%s=%s places the compile cache; ignoring requested %s",
                CACHE_DIR_ENV, placed, requested,
            )
        return placed
    return requested or DEFAULT_CACHE_DIR


def install_compile_listeners(observatory=None) -> None:
    """Hook jax's monitoring stream: persistent-cache hit/miss events and
    backend compile durations feed :data:`COUNTERS`, one structured
    ``tpumlops.compile`` log line fires per compilation, and — when a
    :class:`~..server.device_telemetry.CompileObservatory` is supplied —
    each event is attributed to the engine op that triggered it.

    Idempotent for the listeners (first call wins); the observatory
    reference is refreshed on every call so a server rebuild re-binds."""
    global _listeners_installed, _observatory
    if observatory is not None:
        _observatory = observatory
    if _listeners_installed:
        return
    from jax import monitoring

    monitoring.register_event_listener(_on_jax_event)
    monitoring.register_event_duration_secs_listener(_on_jax_duration)
    _listeners_installed = True


def detach_observatory(observatory) -> None:
    """Unbind a CompileObservatory (server shutdown): the jax listeners
    stay (they are process-global and cheap) but stop attributing into
    a retired server's observatory — whose metrics hooks would
    otherwise keep incrementing a dead registry and pin the whole
    server object graph for the life of the process."""
    global _observatory
    if _observatory is observatory:
        _observatory = None


def _on_jax_event(name: str, **kwargs) -> None:
    if name == "/jax/compilation_cache/cache_hits":
        kind = "cache_hit"
        with _counters_lock:
            COUNTERS["hits"] += 1
    elif name == "/jax/compilation_cache/cache_misses":
        kind = "cache_miss"
        import jax

        with _counters_lock:
            COUNTERS["misses"] += 1
            if jax.config.jax_compilation_cache_dir:
                COUNTERS["persists"] += 1
    else:
        return
    if _observatory is not None:
        _observatory.on_event(kind)


def _on_jax_duration(name: str, duration: float, **kwargs) -> None:
    if name != "/jax/core/compile/backend_compile_duration":
        return
    with _counters_lock:
        COUNTERS["compiles"] += 1
        COUNTERS["compile_seconds"] += duration
        hits, misses = COUNTERS["hits"], COUNTERS["misses"]
    op = _observatory.current_op() if _observatory is not None else "other"
    _compile_log.info(
        "compiled op=%s wall_ms=%.1f cache_hits=%d cache_misses=%d",
        op, duration * 1000.0, hits, misses,
        extra={"compile_op": op, "compile_wall_s": duration},
    )
    if _observatory is not None:
        _observatory.on_event("compile", duration)


def counters_snapshot() -> dict:
    with _counters_lock:
        return dict(COUNTERS)


def enable_persistent_compile_cache(
    cache_dir: str | None,
    *,
    min_compile_time_secs: float = 0.0,
    max_size_bytes: int = 10 * 1024**3,
) -> bool:
    """Point JAX's persistent compilation cache at ``cache_dir``.

    Returns True when enabled.  ``cache_dir`` falsy → disabled (returns
    False); an unwritable directory logs a warning and disables rather
    than failing server startup — a cold compile is slow, not fatal.
    Must run before the first ``jit`` trace to cover warmup compiles.

    ``max_size_bytes`` caps the directory with JAX's LRU eviction: the
    hostPath volume outlives every pod and cache keys change with each
    model version, so without a cap the node disk would fill with dead
    versions' executables until kubelet disk-pressure evicts the very
    predictors the cache protects.
    """
    import jax

    # Counters + the per-compile tpumlops.compile log line are a
    # compile-cache feature, not a telemetry-gated one: every server that
    # configures caching (the CLI default) gets them; DeviceTelemetry
    # re-binds its observatory for per-op attribution on top.
    install_compile_listeners()
    if not cache_dir:
        # JAX reads JAX_COMPILATION_CACHE_DIR as this option's import-time
        # default; clear it so "disabled" really disables, even when the
        # manifest exported the env var.
        jax.config.update("jax_compilation_cache_dir", None)
        return False
    try:
        os.makedirs(cache_dir, exist_ok=True)
        probe = os.path.join(cache_dir, ".tpumlops-probe")
        with open(probe, "w"):
            pass
        os.remove(probe)
    except OSError as exc:
        _log.warning(
            "compile cache dir %s unusable (%s); continuing without "
            "persistent cache",
            cache_dir,
            exc,
        )
        # The manifest also exports JAX_COMPILATION_CACHE_DIR, which JAX
        # reads as this option's default at import — clear it so "disabled"
        # really means disabled, not "retry cache I/O on every compile".
        jax.config.update("jax_compilation_cache_dir", None)
        return False

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Persist every executable regardless of size/compile time: canary
    # buckets are small and fast to compile but still too slow for a
    # latency-gated readiness window.
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_time_secs
    )
    jax.config.update("jax_compilation_cache_max_size", max_size_bytes)
    _reset_jax_cache_singleton()
    _log.info("persistent compile cache at %s", cache_dir)
    return True


def _reset_jax_cache_singleton() -> None:
    """Drop jax's latched cache object so the new dir takes effect.

    jax initializes its persistent-cache singleton on the FIRST compile
    and never re-reads ``jax_compilation_cache_dir`` afterwards — if any
    jit ran before this helper (or the helper runs twice with different
    dirs), the config update is silently ignored without this reset."""
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


def cache_entry_count(cache_dir: str) -> int:
    """Number of persisted executables (for tests and the warm-start metric)."""
    try:
        return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except OSError:
        return 0
