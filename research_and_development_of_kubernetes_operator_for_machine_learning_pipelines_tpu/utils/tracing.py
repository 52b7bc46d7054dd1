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

An :class:`IntervalAccount` (``tracer.account(name)``) keeps, for
intervals one thread opens and closes, their seconds and count by the
label each was closed under and the same seconds by the span whose SELF
time covered them.  The generation engine's ``device_starved`` account
is the one user: open where the chip was seen to run out of programs,
closed where it is handed the next.
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


def _open_self(state: _ThreadState, now: float) -> list[tuple[str, float]]:
    """(name, self time so far) of every span open on ``state``'s thread."""
    out = []
    node, child_t0 = state.top, now
    while node is not None:
        # What an open child has covered is not in ``_child_s`` yet.
        out.append((node._name, child_t0 - node._t0 - node._child_s))
        node, child_t0 = node._parent, node._t0
    return out


class IntervalAccount:
    """Intervals of ONE thread (the only writer, so no lock), each closed
    under a label: ``by_label`` holds (seconds, intervals), ``by_span``
    the same seconds by the span whose self time covered them.  A reader
    on another thread copies a dict and never sees a torn record; between
    the two dicts it may see one interval half booked."""

    __slots__ = ("_tracer", "_state", "mark", "by_label", "by_span")

    def __init__(self, tracer: Tracer):
        self._tracer = tracer
        self._state: _ThreadState | None = None  # the writer's, at its first open
        self.mark: tuple | None = None  # None: no interval is open
        self.by_label: dict[str, tuple[float, int]] = {}
        self.by_span: dict[str, float] = {}

    def open(self) -> None:
        """An interval begins now: mark the thread's self-time account
        (the closed spans' records, which are replaced whole on every
        exit, and what every open span has so far)."""
        state = self._state
        if state is None:
            state = self._state = self._tracer._thread_state()
        now = perf_counter()
        self.mark = (now, state.stats.copy(), _open_self(state, now))

    def drop(self) -> None:
        """Forget an open interval (what it waited for was lost)."""
        self.mark = None

    def close(self, label: str, rest: str, leave_out: tuple = ()) -> None:
        """Close the open interval, if any, under ``label``, and book its
        seconds by span: a span that closed since the mark gives the self
        time it closed with less what it had at the mark, a span still
        open what it has so far.  Time no span covered goes to ``rest``;
        the spans named in ``leave_out`` are no part of the interval, and
        their time comes off its length.  One pass, no table in between:
        this runs on the engine's hot path."""
        mark = self.mark
        if mark is None:
            return
        self.mark = None
        t0, stats0, open0 = mark
        state = self._state
        now = perf_counter()
        by_span = self.by_span
        covered = left_out = 0.0
        for name, d in _open_self(state, now):
            covered += d
            if name in leave_out:
                left_out += d
            else:
                by_span[name] = by_span.get(name, 0.0) + d
        for name, rec in state.stats.items():
            was = stats0.get(name)
            if rec is not was:
                d = rec[2] - (was[2] if was else 0.0)
                covered += d
                if name in leave_out:
                    left_out += d
                else:
                    by_span[name] = by_span.get(name, 0.0) + d
        for name, d in open0:
            covered -= d
            if name in leave_out:
                left_out -= d
            else:
                by_span[name] = by_span.get(name, 0.0) - d
        length = now - t0
        by_span[rest] = by_span.get(rest, 0.0) + length - covered
        seconds, count = self.by_label.get(label, (0.0, 0))
        self.by_label[label] = (seconds + length - left_out, count + 1)

    def as_dict(self) -> dict:
        """JSON-ready totals (the ``/debug/spans`` shape)."""
        return {
            "by_label": {
                label: {"seconds": round(s, 6), "intervals": n}
                for label, (s, n) in sorted(self.by_label.copy().items())
            },
            "by_span_s": {
                name: round(s, 6) for name, s in sorted(self.by_span.copy().items())
            },
        }


class Tracer:
    def __init__(self, profiler: bool = False):
        self._annotation = None
        if profiler:
            import jax

            self._annotation = jax.profiler.TraceAnnotation
        self._local = threading.local()
        self._lock = threading.Lock()  # guards _threads, _retired, _accounts
        self._threads: list[_ThreadState] = []
        self._retired: dict[str, tuple] = {}
        self._accounts: dict[str, IntervalAccount] = {}

    def _thread_state(self) -> _ThreadState:
        try:
            return self._local.state
        except AttributeError:
            return self._register_thread()

    def span(self, name: str) -> _Span:
        try:  # the hot path: ``_thread_state`` inlined
            state = self._local.state
        except AttributeError:
            state = self._register_thread()
        return _Span(state, name, self._annotation)

    def account(self, name: str) -> IntervalAccount:
        """The interval account of this name, made on first asking: its
        writer and its readers (``/metrics``, ``/debug/spans``) meet
        here as a span's do."""
        with self._lock:
            account = self._accounts.get(name)
            if account is None:
                account = self._accounts[name] = IntervalAccount(self)
        return account

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
