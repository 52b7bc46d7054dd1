"""Host-time spans (utils/tracing.py) and the engine loop's ``engine.*``
phases: nesting and self time, the nine phases over every tick kind, the
profiler sink on the capture's clock, the prefill-token counter, where the
loop takes the wait for a prefill chunk (behind the step's dispatch, with
or without a recorder watching), and the operator staying off jax.

Engines here are tiny and start WITHOUT the warm-up sweep (each program
compiles on first use, a few seconds a mode), so the cases run in the
fast tranche; every wait has its own timeout.
"""

import glob
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumlops.models import llama
from tpumlops.server.generation import GenerationEngine
from tpumlops.utils.tracing import Tracer

ROOT = "engine.iteration"
PHASES = {
    "engine.wait_work", "engine.admit", "engine.prefill_dispatch",
    "engine.prefill_sync", "engine.decode_assemble", "engine.decode_dispatch",
    "engine.decode_readback", "engine.emit", "engine.journal",
}
PROMPTS = [list(range(3, 3 + n)) for n in (5, 11, 19, 8, 25)]


def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


@pytest.mark.parametrize("threads", [1, 2])
def test_span_nesting_and_self_time(threads):
    """A span's self time is its duration less what its children on the
    SAME thread covered; two threads nest independently and their stats
    add up."""
    tr = Tracer()
    barrier = threading.Barrier(threads)

    def work():
        barrier.wait(timeout=10)
        with tr.span("root"):
            _spin(0.01)
            with tr.span("child"):
                _spin(0.02)
                with tr.span("leaf"):
                    _spin(0.01)
            with tr.span("child"):
                _spin(0.01)

    pool = [threading.Thread(target=work) for _ in range(threads)]
    for t in pool:
        t.start()
    for t in pool:
        t.join(timeout=30)
        assert not t.is_alive()
    st = tr.stats()
    assert st["root"].count == threads and st["child"].count == 2 * threads
    assert st["leaf"].count == threads
    assert st["leaf"].self_s == pytest.approx(st["leaf"].total_s)
    assert st["child"].self_s == pytest.approx(
        st["child"].total_s - st["leaf"].total_s
    )
    assert st["root"].self_s == pytest.approx(
        st["root"].total_s - st["child"].total_s
    )
    # Self times partition the root: nothing is counted twice.
    assert sum(s.self_s for s in st.values()) == pytest.approx(
        st["root"].total_s
    )
    assert st["root"].self_s >= 0.009 * threads
    assert st["child"].max_s >= 0.029 and st["child"].max_s <= st["child"].total_s
    # A span that raises still closes, and pops itself off the stack.
    with pytest.raises(ValueError):
        with tr.span("root"):
            with tr.span("child"):
                raise ValueError("boom")
    with tr.span("after"):
        pass
    st = tr.stats()
    assert st["child"].count == 2 * threads + 1
    assert st["after"].self_s == pytest.approx(st["after"].total_s)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny(max_seq=64)
    return llama.init(jax.random.key(0), cfg, dtype=jnp.float32), cfg


def _engine(tiny, **kw):
    params, cfg = tiny
    eng = GenerationEngine(params, cfg, max_slots=4, dtype=jnp.float32, **kw)
    eng.start(warmup=False)
    return eng


def _watchers(cpu_peaks) -> dict:
    """What ``traceRing`` and ``deviceTelemetry`` hand the engine."""
    from tpumlops.server.device_telemetry import DeviceTelemetry
    from tpumlops.server.flight_recorder import FlightRecorder

    return dict(
        recorder=FlightRecorder(4096),
        telemetry=DeviceTelemetry(peaks=cpu_peaks),
    )


def _serve(eng, prompts=PROMPTS, new=12):
    futs = [eng.submit(p, new) for p in prompts]
    return [f.result(timeout=300) for f in futs]


def _delta(before, after):
    out = {}
    for name, s in after.items():
        b = before.get(name)
        out[name] = (
            s.count - (b.count if b else 0),
            s.total_s - (b.total_s if b else 0.0),
            s.self_s - (b.self_s if b else 0.0),
        )
    return out


# mode -> (engine kwargs, tick kinds whose dispatch is read back as a
# decode step, phases the mode never enters)
MODES = {
    "chunked": (dict(prefill_chunk=8), ("decode",), set()),
    "packed": (dict(prefill_chunk=8, prefill_batch=4), ("decode",), set()),
    # Unified: prefill chunks ride the super-step dispatch, so without a
    # cached-prefix seed there is no prefill dispatch of its own.
    "unified": (
        dict(prefill_chunk=8, prefill_batch=4, unified_step=True,
             decode_steps=2),
        ("superstep",), {"engine.prefill_dispatch"},
    ),
    "fused": (dict(decode_steps=4), ("decode", "multistep"), set()),
}


@pytest.mark.parametrize("watched", [False, True], ids=["bare", "watched"])
@pytest.mark.parametrize("mode", list(MODES))
def test_engine_phases_cover_the_loop(tiny, cpu_peaks, mode, watched):
    kw, step_kinds, absent = MODES[mode]
    eng = _engine(tiny, **kw, **(_watchers(cpu_peaks) if watched else {}))
    uncovered = []
    try:
        _serve(eng)  # compiles every program the later batches will use
        first = before = eng.tracer.stats()
        d0 = dict(eng.dispatches_total)
        p0 = eng.prefill_tokens
        for _ in range(3):
            _serve(eng)
            after = eng.tracer.stats()
            d = _delta(before, after)
            # What no phase covers, against the loop's busy time.  Self
            # times are whole at each span's close, so the ratio holds even
            # where a snapshot cuts an idle wait from the pass around it.
            busy = sum(
                s for n, (_c, _t, s) in d.items() if n != "engine.wait_work"
            )
            uncovered.append(d[ROOT][2] / busy)
            before = after
        d = _delta(first, after)
        steps = sum(
            eng.dispatches_total.get(k, 0) - d0.get(k, 0) for k in step_kinds
        )
        prefilled = eng.prefill_tokens - p0
    finally:
        eng.shutdown()
    present = {n for n, (count, _t, _s) in d.items() if count > 0}
    assert present == ({ROOT} | PHASES) - absent
    # One blocking read-back per decode-kind dispatch: the step count the
    # benchmark's loop_period_ms divides by.
    assert steps > 0 and d["engine.decode_readback"][0] == steps
    # A phase left out of the spans would show in every batch; a batch is
    # some 30 ms of a toy model here, so one thread switch can cost a
    # batch a few percent, and the best of three is taken.
    assert d[ROOT][0] > 0 and min(uncovered) < 0.05, uncovered
    assert prefilled == 3 * sum(len(p) for p in PROMPTS)


class _LoggedSpan:
    def __init__(self, inner, name, log):
        self.inner, self.name, self.log = inner, name, log

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.inner.__enter__()
        return self

    def __exit__(self, *exc):
        out = self.inner.__exit__(*exc)
        self.log.append((self.name, self.t0, time.perf_counter()))
        return out


class _LoggingTracer(Tracer):
    """A tracer that also keeps every span's (name, open, close), in the
    order they closed."""

    def __init__(self):
        super().__init__()
        self.log = []

    def span(self, name):
        return _LoggedSpan(super().span(name), name, self.log)


def _spy_ticks(eng) -> list:
    """Every journaled tick as (kind, start, wall), on perf_counter."""
    ticks, record = [], eng._record_tick

    def spy(kind, t0, wall_s, **fields):
        ticks.append((kind, t0, wall_s))
        return record(kind, t0, wall_s, **fields)

    eng._record_tick = spy
    return ticks


def _first_token_then(eng, prompt, new):
    """Submit and return (future, event set at the first token)."""
    started = threading.Event()
    return eng.submit(prompt, new, on_token=lambda _t: started.set()), started


LONG = list(range(3, 43))  # five chunks of 8


def test_the_wait_for_a_chunk_is_taken_behind_the_steps_dispatch(
    tiny, cpu_peaks
):
    """A pass with an active slot and a chunk that is not the prompt's
    last: chunk dispatch, step dispatch, THEN the wait for the chunk and
    the step's read-back.  The journaled walls are taken in completion
    order, so they do not overlap and fit inside the pass."""
    tracer, waits = _LoggingTracer(), []
    watchers = _watchers(cpu_peaks)
    eng = _engine(
        tiny, prefill_chunk=8, tracer=tracer, on_prefill_wait=waits.append,
        **watchers,
    )
    try:
        _serve(eng, [LONG[:20], PROMPTS[0]], new=8)  # compiles the programs
        ticks = _spy_ticks(eng)
        del tracer.log[:], waits[:]
        ticks_before = watchers["recorder"].ticks_recorded
        steps_before = eng.dispatches_total["decode"]
        rider, started = _first_token_then(eng, PROMPTS[0], 40)
        assert started.wait(timeout=120)
        doc = eng.submit(LONG, 4)
        doc.result(timeout=300)
        rider.result(timeout=300)
        steps = eng.dispatches_total["decode"] - steps_before
    finally:
        eng.shutdown()
    log = tracer.log
    passes = [(a, b) for name, a, b in log if name == ROOT]
    hidden = 0
    for a, b in passes:
        inside = [e for e in log if e[0] != ROOT and a <= e[1] and e[2] <= b]
        opened = lambda name: [e for e in inside if e[0] == name]
        chunk, step = opened("engine.prefill_dispatch"), opened(
            "engine.decode_dispatch")
        walls = sorted(
            (t0, t0 + wall) for _k, t0, wall in ticks if a <= t0 <= b
        )
        for (_s0, e0), (s1, _e1) in zip(walls, walls[1:]):
            assert e0 <= s1 + 1e-9, (walls, "walls overlap")
        assert sum(e - s for s, e in walls) <= (b - a) + 1e-9
        assert all(e <= b + 1e-9 for _s, e in walls)
        if len(chunk) != 1 or len(step) != 1:
            continue  # no chunk, its last chunk (+ the insert), or no step
        hidden += 1
        (sync,) = opened("engine.prefill_sync")
        (readback,) = opened("engine.decode_readback")
        assert step[0][2] <= sync[1], "waited before dispatching the step"
        assert sync[2] <= readback[1]
    # LONG's four non-final chunks all rode with the rider's steps.
    assert hidden == 4
    assert waits.count("step") == 4
    # ... its last chunk and the insert, and the rider's own chunk and
    # insert, are read at once: nothing was queued behind them.
    assert waits.count("none") == 4
    # The same ticks the parent journals for these requests: one a chunk,
    # one an insert, one a step; one read-back a step.
    kinds = [k for k, _t0, _w in ticks]
    assert kinds.count("prefill") == (5 + 1) + (1 + 1)
    assert kinds.count("decode") == steps
    assert set(kinds) == {"prefill", "decode"}
    assert watchers["recorder"].ticks_recorded - ticks_before == len(kinds)
    assert sum(1 for e in log if e[0] == "engine.decode_readback") == steps


@pytest.mark.parametrize("mode", list(MODES))
def test_watching_the_engine_does_not_change_its_tokens(tiny, cpu_peaks, mode):
    """A recorder and telemetry are sinks: request for request the tokens
    are those of the engine nobody watches."""
    kw = MODES[mode][0]
    served = []
    for watchers in ({}, _watchers(cpu_peaks)):
        eng = _engine(tiny, **kw, **watchers)
        try:
            served.append([list(map(int, out)) for out in _serve(eng)])
        finally:
            eng.shutdown()
    assert served[0] == served[1]
    assert all(len(out) == 12 for out in served[0])


class _Wedged:
    """Stands for a chunk's result whose program failed on the device:
    the error surfaces where the host waits for it."""

    def block_until_ready(self):
        raise RuntimeError("injected device error")


def test_a_chunk_failing_at_its_deferred_wait_fails_its_own_admission(tiny):
    eng = _engine(tiny, prefill_chunk=8)
    try:
        (reference,) = _serve(eng, [PROMPTS[1]], new=6)
        rider, started = _first_token_then(eng, PROMPTS[0], 40)
        assert started.wait(timeout=120)
        chunk_program = eng._prefill_one_chunk

        def failing_once(*args):
            eng._prefill_one_chunk = chunk_program
            _logits, *rest = chunk_program(*args)
            return (_Wedged(), *rest)

        eng._prefill_one_chunk = failing_once
        doc = eng.submit(LONG, 4)
        with pytest.raises(RuntimeError, match="injected device error"):
            doc.result(timeout=300)
        # The step was already queued behind the chunk, so the failure
        # surfaced inside ``_step``: the admission owns it all the same,
        # and the slots go the way of any lost device state.
        with pytest.raises(RuntimeError, match="generation step failed"):
            rider.result(timeout=300)
        assert eng._poison_counts == {eng._fingerprint(np.asarray(LONG)): 1}
        (again,) = _serve(eng, [PROMPTS[1]], new=6)
        assert not eng._pending and not eng._open_ticks
    finally:
        eng.shutdown()
    assert list(again) == list(reference)


def test_prefill_tokens_exclude_cached_prefix_tokens(tiny):
    from tpumlops.server.prefix_cache import PrefixCacheConfig

    counted = []
    eng = _engine(
        tiny, prefill_chunk=8,
        prefix_cache=PrefixCacheConfig(
            enabled=True, budget_bytes=2**24, chunk_tokens=8
        ),
        on_prefill_tokens=counted.append,
    )
    shared = list(range(5, 29))  # three full chunks
    prompts = [shared + [40 + i, 50 + i, 60 + i] for i in range(4)]
    try:
        for p in prompts:  # one at a time: the later ones hit the cache
            _serve(eng, [p], new=4)
        cached, prefilled = eng.prefix_cached_tokens, eng.prefill_tokens
    finally:
        eng.shutdown()
    assert cached == 3 * len(shared)
    assert prefilled == sum(len(p) for p in prompts) - cached
    assert sum(counted) == prefilled


def test_profiler_capture_holds_engine_spans_on_the_engine_line(tiny, tmp_path):
    from jax.profiler import ProfileData

    eng = _engine(tiny, prefill_chunk=8)
    try:
        _serve(eng, PROMPTS[:2], new=4)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            _serve(eng, PROMPTS[:2], new=6)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    (xplane,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(xplane)
    lo = hi = None
    lines = []  # (line name, engine.* events) on /host:CPU
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            for e in events:
                lo = e.start_ns if lo is None else min(lo, e.start_ns)
                end = e.start_ns + e.duration_ns
                hi = end if hi is None else max(hi, end)
            mine = [e for e in events if e.name.startswith("engine.")]
            if mine:
                assert plane.name == "/host:CPU"
                lines.append((line.name, mine))
    assert len(lines) == 1, [name for name, _ in lines]  # the engine thread's
    events = lines[0][1]
    names = {e.name for e in events}
    assert {ROOT, "engine.decode_dispatch", "engine.decode_readback",
            "engine.prefill_dispatch", "engine.emit"} <= names
    assert all(lo <= e.start_ns and e.start_ns + e.duration_ns <= hi
               for e in events)
    # Nested on the shared clock: every read-back lies inside a pass.
    passes = [(e.start_ns, e.start_ns + e.duration_ns)
              for e in events if e.name == ROOT]
    for e in events:
        if e.name == "engine.decode_readback":
            assert any(s <= e.start_ns and e.start_ns + e.duration_ns <= t
                       for s, t in passes)


def test_operator_imports_no_jax():
    """The operator's timers are spans of the same primitive, and its
    process must still not pay for (or need) jax."""
    code = (
        "import sys\n"
        "import tpumlops.operator.reconciler, tpumlops.operator.runtime\n"
        "import tpumlops.operator.telemetry, tpumlops.operator.__main__\n"
        "from tpumlops.utils.tracing import GLOBAL_TRACER\n"
        "with GLOBAL_TRACER.span('operator.probe'):\n"
        "    pass\n"
        "assert GLOBAL_TRACER.as_dict()['operator.probe']['count'] == 1\n"
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]\n"
        "assert not bad, bad\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
    )
    assert done.returncode == 0, done.stderr
