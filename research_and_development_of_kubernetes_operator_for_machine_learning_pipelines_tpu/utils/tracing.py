"""Host-time spans: the one way the program marks what a thread is doing.

``with tracer.span("engine.admit"): ...`` accumulates, per name, the
count, the total and largest duration, and the SELF time (the duration
less what child spans opened on the same thread covered).  Spans are
always on; there is no switch.  Three sinks read the one primitive:

- ``Tracer.stats()`` / ``as_dict()``: ``GET /debug/spans`` on the server
  and on the operator's metrics listener;
- ``/metrics``: ``tpumlops_span_*`` families rendered from ``stats()`` at
  scrape time (``server/metrics.py``), nothing on the hot path;
- the profiler: a tracer built with ``profiler=True`` (the server's; it
  imports jax, which the operator must not) also enters a
  ``jax.profiler.TraceAnnotation`` while a capture is running, so the
  span lands on its thread's line of the ``/host:CPU`` plane of the same
  ``.xplane.pb`` as the device's ops, on the profiler's clock.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from time import perf_counter


@dataclass
class SpanStats:
    count: int = 0
    total_s: float = 0.0
    self_s: float = 0.0
    max_s: float = 0.0

    @property
    def mean_s(self) -> float:
        return self.total_s / self.count if self.count else 0.0


class _ThreadState:
    """One thread's open-span stack (a linked list through ``top``) and
    its own accumulators: the owning thread is the only writer, so a span
    takes no lock."""

    __slots__ = ("top", "stats", "thread")

    def __init__(self):
        self.top: _Span | None = None
        # name -> (count, total_s, self_s, max_s); replaced whole on every
        # exit, so a reader on another thread never sees a torn record.
        self.stats: dict[str, tuple] = {}
        self.thread = threading.current_thread()


_ZERO = (0, 0.0, 0.0, 0.0)


class _Span:
    __slots__ = ("_state", "_name", "_annotation", "_parent", "_child_s", "_t0")

    def __init__(self, state: _ThreadState, name: str, annotation):
        self._state = state
        self._name = name
        self._annotation = annotation

    def __enter__(self):
        state = self._state
        self._parent = state.top
        state.top = self
        self._child_s = 0.0
        ann = self._annotation
        if ann is not None:
            # TraceMe records only what starts inside a capture; asking
            # first keeps the idle cost to one call.
            if ann.is_enabled():
                ann = self._annotation = ann(self._name)
                ann.__enter__()
            else:
                self._annotation = None
        self._t0 = perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        state = self._state
        parent = state.top = self._parent
        if parent is not None:
            parent._child_s += dt
        stats = state.stats
        count, total_s, self_s, max_s = stats.get(self._name, _ZERO)
        stats[self._name] = (
            count + 1,
            total_s + dt,
            self_s + dt - self._child_s,
            dt if dt > max_s else max_s,
        )
        return False


class Tracer:
    def __init__(self, profiler: bool = False):
        self._annotation = None
        if profiler:
            import jax

            self._annotation = jax.profiler.TraceAnnotation
        self._local = threading.local()
        self._lock = threading.Lock()  # guards _threads and _retired
        self._threads: list[_ThreadState] = []
        self._retired: dict[str, tuple] = {}

    def span(self, name: str) -> _Span:
        try:
            state = self._local.state
        except AttributeError:
            state = self._register_thread()
        return _Span(state, name, self._annotation)

    def _register_thread(self) -> _ThreadState:
        state = self._local.state = _ThreadState()
        with self._lock:
            # Fold what finished threads left, so churning threads do not
            # grow the list.
            live = []
            for other in self._threads:
                if other.thread.is_alive():
                    live.append(other)
                else:
                    _merge(self._retired, other.stats)
            live.append(state)
            self._threads = live
        return state

    def stats(self) -> dict[str, SpanStats]:
        """Point-in-time snapshot over every thread, as copies: a caller
        holding one never sees it move."""
        with self._lock:
            merged = dict(self._retired)
            for state in self._threads:
                _merge(merged, state.stats)
        return {name: SpanStats(*rec) for name, rec in merged.items()}

    def as_dict(self) -> dict[str, dict]:
        """JSON-ready stats (the ``/debug/spans`` payload shape on both
        the server and the operator's metrics listener)."""
        return {
            name: {
                "count": s.count,
                "total_s": round(s.total_s, 6),
                "self_s": round(s.self_s, 6),
                "mean_ms": round(s.mean_s * 1e3, 3),
                "max_ms": round(s.max_s * 1e3, 3),
            }
            for name, s in sorted(self.stats().items())
        }


def _merge(into: dict[str, tuple], stats: dict[str, tuple]) -> None:
    for name, (count, total_s, self_s, max_s) in list(stats.items()):
        c, t, s, m = into.get(name, _ZERO)
        into[name] = (c + count, t + total_s, s + self_s, max(m, max_s))


GLOBAL_TRACER = Tracer()
span = GLOBAL_TRACER.span
