"""Host-time spans (utils/tracing.py) and the engine loop's ``engine.*``
phases: nesting and self time, the nine phases over every tick kind, the
profiler sink on the capture's clock, the prefill-token counter, where the
loop takes the wait for a prefill chunk (behind the step's dispatch, with
or without a recorder watching) and sends the next one (right behind that
dispatch, before the pass reads anything back), and the operator staying
off jax.

Engines here are tiny and start WITHOUT the warm-up sweep (each program
compiles on first use, a few seconds a mode), so the cases run in the
fast tranche; every wait has its own timeout.
"""

import glob
import queue
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


def _intervals(eng) -> dict:
    """Starved intervals closed so far, by what was dispatched at their end."""
    return {k: n for k, (_s, n) in eng._starved.by_label.copy().items()}


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


def _rider_then_doc(eng, doc=LONG, rider_new=40, doc_new=4):
    """A stream that is decoding when ``doc`` arrives: (rider, doc)."""
    rider, started = _first_token_then(eng, PROMPTS[0], rider_new)
    assert started.wait(timeout=120)
    return rider, eng.submit(doc, doc_new)


def test_the_next_chunk_is_dispatched_right_behind_the_steps_dispatch(
    tiny, cpu_peaks
):
    """A pass with an active slot and an admission whose first chunk is
    out: step dispatch, the NEXT chunk's dispatch, and only then the wait
    for the chunk before the step and the step's read-back; the chunk sent
    ahead is waited for behind the next pass's step.  The journaled walls
    are taken in completion order, so they do not overlap, and each ends
    in a pass and starts no earlier than the pass before that."""
    tracer, sent, starved = _LoggingTracer(), [], []
    watchers = _watchers(cpu_peaks)

    def chunk_sent(when):  # on the engine thread, behind the dispatch's stamp
        sent.append(when)
        starved.append(_intervals(eng).get("chunk", 0))

    eng = _engine(
        tiny, prefill_chunk=8, tracer=tracer,
        on_prefill_dispatch=chunk_sent, **watchers,
    )
    try:
        _serve(eng, [LONG[:20], PROMPTS[0]], new=8)  # compiles the programs
        ticks = _spy_ticks(eng)
        del tracer.log[:], sent[:], starved[:]
        chunk_intervals = _intervals(eng).get("chunk", 0)
        ticks_before = watchers["recorder"].ticks_recorded
        steps_before = eng.dispatches_total["decode"]
        chunks_before = eng.prefill_chunks_dispatched
        rider, doc = _rider_then_doc(eng)
        doc.result(timeout=300)
        rider.result(timeout=300)
        steps = eng.dispatches_total["decode"] - steps_before
        chunks = eng.prefill_chunks_dispatched - chunks_before
    finally:
        eng.shutdown()
    log = tracer.log
    passes = [(a, b) for name, a, b in log if name == ROOT]
    ahead = 0
    for a, b in passes:
        inside = [e for e in log if e[0] != ROOT and a <= e[1] and e[2] <= b]
        opened = lambda name: [e for e in inside if e[0] == name]
        step = opened("engine.decode_dispatch")
        if len(step) != 1:
            continue
        behind = [e for e in opened("engine.prefill_dispatch")
                  if e[1] >= step[0][2]]
        if not behind:
            continue
        ahead += 1
        (chunk,) = behind
        (sync,) = opened("engine.prefill_sync")
        # The step still in flight (where the pass found one) is read back
        # before the wait for the chunk sent in turn, and the pass's own
        # step last: a chunk behind it, it stays out no longer.
        readbacks = opened("engine.decode_readback")
        assert 1 <= len(readbacks) <= 2
        assert chunk[2] <= min(sync[1], readbacks[0][1]), (
            "waited before sending the next chunk")
        assert sync[2] <= readbacks[-1][1]
    # LONG's chunks after the first all went out behind the rider's steps,
    # the last one too; its first and the rider's own in the admit phase.
    assert ahead == 4 and sent.count("ahead") == 4
    assert sent.count("in_turn") == 2 and len(sent) == chunks
    # The starvation account says the same from inside: a chunk sent ahead
    # goes out behind a step the host has not seen to end, so its dispatch
    # closes no interval; only a chunk sent in turn can have met an idle chip.
    closed = [n - m for m, n in zip([chunk_intervals] + starved, starved)]
    assert [c for c, when in zip(closed, sent) if when == "ahead"] == [0] * 4
    assert all(c in (0, 1) for c in closed)
    walls = sorted((t0, t0 + wall) for _k, t0, wall in ticks)
    for (_s0, e0), (s1, _e1) in zip(walls, walls[1:]):
        assert e0 <= s1 + 1e-9, (walls, "walls overlap")
    for s, e in walls:
        (i,) = [i for i, (a, b) in enumerate(passes) if a <= e <= b]
        assert s >= passes[max(i - 1, 0)][0], "a wall over three passes"
    assert sum(e - s for s, e in walls) <= passes[-1][1] - passes[0][0]
    # The same ticks the parent journals for these requests: one a chunk,
    # one an insert, one a step; one read-back a step.
    kinds = [k for k, _t0, _w in ticks]
    assert kinds.count("prefill") == (5 + 1) + (1 + 1)
    assert kinds.count("decode") == steps
    assert set(kinds) == {"prefill", "decode"}
    assert watchers["recorder"].ticks_recorded - ticks_before == len(kinds)
    assert sum(1 for e in log if e[0] == "engine.decode_readback") == steps


def _starved(eng) -> tuple[dict, dict]:
    """The starvation account as it stands: ({before: (seconds,
    intervals)}, {span: seconds})."""
    return eng._starved.by_label.copy(), eng._starved.by_span.copy()


def _assert_account_adds_up(eng, since=None):
    """Seconds by ``before`` == seconds by span, no interval holds time
    spent waiting for traffic, and all of it lies inside the loop's busy
    time (root less ``engine.wait_work``)."""
    by_before, by_span = _starved(eng)
    st = eng.tracer.stats()
    total = sum(s for s, _n in by_before.values())
    assert total == pytest.approx(sum(by_span.values()), rel=1e-9, abs=1e-9)
    assert "engine.wait_work" not in by_span
    assert set(by_span) <= ({ROOT} | PHASES)
    assert all(s > -1e-9 for s in by_span.values()), by_span
    assert total <= st[ROOT].total_s - st["engine.wait_work"].total_s + 1e-6
    return total


def test_a_step_onto_an_idle_chip_closes_one_interval_before_decode(tiny):
    """A lone stream, no chunks: its first step goes out onto a chip the
    engine thread has seen idle (its first token was just read), so it
    closes one interval under ``before="decode"``, as long as the spans
    between that read and the dispatch's return, and no longer.  Every
    later step is dispatched behind the one still in flight, before that
    one is read back: it closes none, and goes out ``ahead``."""
    tracer, sent = _LoggingTracer(), []
    eng = _engine(tiny, tracer=tracer, on_decode_dispatch=sent.append)
    try:
        _serve(eng, [PROMPTS[0]], new=4)  # compiles the programs
        n0, steps0 = _intervals(eng), eng.dispatches_total["decode"]
        s0 = _starved(eng)[0].get("decode", (0.0, 0))[0]
        del tracer.log[:], sent[:]
        _serve(eng, [PROMPTS[0]], new=10)
        steps = eng.dispatches_total["decode"] - steps0
        n1 = _intervals(eng)
        seconds = _starved(eng)[0]["decode"][0] - s0
        log = list(tracer.log)
        _assert_account_adds_up(eng)
        by_span = _starved(eng)[1]
        assert eng._ahead is None and eng._unseen == 0
    finally:
        eng.shutdown()
    # The fused admission's prefill met a chip the last request had left
    # idle (one interval, the wait for traffic taken out of it); its first
    # token is read before the first step goes out, so that step closes
    # one too, and no step after it does.
    assert steps == 9
    assert n1["decode"] - n0["decode"] == 1
    assert n1["prefill"] - n0.get("prefill", 0) == 1
    assert set(n1) == {"prefill", "decode"}
    assert sent == ["in_turn"] + ["ahead"] * (steps - 1)
    assert sent.count("ahead") / len(sent) == (steps - 1) / steps
    readbacks = [e for e in log if e[0] == "engine.decode_readback"]
    dispatches = [e for e in log if e[0] == "engine.decode_dispatch"]
    admits = [e for e in log if e[0] == "engine.prefill_dispatch"]
    assert len(readbacks) == len(dispatches) == steps and len(admits) == 1
    # Step k+1 goes out before step k is read back; the last is read back
    # in a pass that dispatches nothing (its row has no budget past it).
    for k in range(steps - 1):
        assert dispatches[k + 1][2] <= readbacks[k][1]
    assert readbacks[-1][1] >= dispatches[-1][2]
    # The one interval runs from where the first token was seen (behind
    # the wait for it, before that tick's journal) to inside the first
    # step's dispatch.
    sync = [e for e in log if e[0] == "engine.prefill_sync"][0]
    journal = min(
        (e for e in log if e[0] == "engine.journal" and e[1] >= sync[2]),
        key=lambda e: e[1],
    )
    assert dispatches[0][1] - journal[1] <= seconds
    assert seconds <= dispatches[0][2] - sync[2]
    # What the host was doing: the first token's emission, the assembly
    # and the dispatch itself.
    assert {"engine.decode_dispatch", "engine.decode_assemble",
            "engine.emit"} <= set(by_span)


def test_time_waiting_for_traffic_is_in_no_interval(tiny):
    """Between two requests the loop sits in ``engine.wait_work``; the
    interval the second one's first dispatch closes is the host's work on
    either side of that wait, not the wait."""
    eng = _engine(tiny, prefill_chunk=8)
    try:
        _serve(eng, [PROMPTS[0]], new=4)
        before = _assert_account_adds_up(eng)
        waited0 = eng.tracer.stats()["engine.wait_work"].total_s
        time.sleep(0.4)
        n0 = _intervals(eng).get("chunk", 0)
        _serve(eng, [PROMPTS[0]], new=1)
        waited = eng.tracer.stats()["engine.wait_work"].total_s - waited0
        after = _assert_account_adds_up(eng)
        n1 = _intervals(eng)["chunk"]
    finally:
        eng.shutdown()
    assert n1 - n0 == 1  # the first chunk met an idle chip
    assert waited > 0.3
    assert after - before < waited / 2  # the wait is not in it


STARVED = {  # mode -> (engine kwargs, labels its dispatches may close under,
    # labels they must)
    "plain": ({}, {"prefill", "decode"}, {"prefill", "decode"}),
    "chunked": (MODES["chunked"][0], {"chunk", "insert", "decode"},
                {"chunk", "decode"}),
    "packed": (MODES["packed"][0], {"packed-prefill", "decode"},
               {"packed-prefill", "decode"}),
    "unified": (MODES["unified"][0], {"superstep"}, {"superstep"}),
    "fused": (MODES["fused"][0], {"prefill", "decode", "multistep"},
              {"prefill", "multistep"}),
    "verify": ("speculative", {"prefill", "decode", "verify"},
               {"prefill", "verify"}),
}


@pytest.mark.parametrize("mode", list(STARVED))
def test_every_tick_path_opens_and_closes_the_account(tiny, mode):
    """Whatever program a path hands the device, its dispatch ends the
    open interval under its own kind, and its completion, once nothing
    else is out, begins the next: after any traffic nothing is unseen, an
    interval is open, and the two tables add up to the same seconds."""
    kw, may, must = STARVED[mode]
    if kw == "speculative":
        from tpumlops.server.speculative import SpeculativeConfig

        kw = dict(speculative=SpeculativeConfig(
            enabled=True, draft_tokens=2, ngram_min=1, ngram_max=4))
    eng = _engine(tiny, **kw)
    if mode == "verify":
        eng._propose = lambda slot, budget: [5, 7][:budget]
    try:
        _serve(eng)
        assert _starved(eng)[0], "nothing counted"
        for _ in range(2):
            _serve(eng)
            assert eng._unseen == 0 and not eng._open_ticks
            assert eng._starved.mark is not None
            _assert_account_adds_up(eng)
        labels = set(_intervals(eng))
        ticks = {k for k, n in eng.dispatches_total.items() if n}
    finally:
        eng.shutdown()
    assert must <= labels <= may, labels
    # The labels are the tick kinds, ``prefill`` told apart where known.
    told_apart = {"chunk": "prefill", "insert": "prefill"}
    assert {told_apart.get(k, k) for k in labels} <= ticks


def test_the_warm_up_sweep_counts_nothing(tiny):
    """A warm-up sweep dispatches and reads back every program; none of
    it is a tick, and none of it is starvation."""
    params, cfg = tiny
    eng = GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float32, prefill_chunk=16,
    )
    try:
        eng.start(warmup=True)
        assert eng._starved.by_label == {} and eng._starved.by_span == {}
        assert eng._unseen == 0 and eng._starved.mark is None
        _serve(eng, [PROMPTS[0]], new=3)
        assert set(_intervals(eng)) == {"decode"}
    finally:
        eng.shutdown()


def test_a_lost_step_leaves_nothing_unseen(tiny):
    """A step that fails is never seen to end: the account forgets what
    was out with the device state, and counts on from the next dispatch."""
    eng = _engine(tiny, prefill_chunk=8)
    try:
        _serve(eng, [PROMPTS[0]], new=3)
        step_program, calls = eng._decode_greedy, []

        def failing_once(*args):
            calls.append(1)
            if len(calls) != 3:
                return step_program(*args)
            raise RuntimeError("injected step error")

        eng._decode_greedy = failing_once
        with pytest.raises(RuntimeError, match="generation step failed"):
            _serve(eng, [PROMPTS[0]], new=12)
        assert eng._unseen == 0 and eng._starved.mark is None
        _serve(eng, [PROMPTS[0]], new=3)
        assert eng._unseen == 0
        _assert_account_adds_up(eng)
    finally:
        eng.shutdown()


def _family(name):
    """A tiny configuration of a family behind the ``causal_lm`` handle
    beside llama (the ``tiny`` fixture): (params, cfg, engine kwargs)."""
    from tpumlops.models import gdn_moe, mla_moe

    mod, cfg = {
        "mla-moe": (mla_moe, mla_moe.MlaMoeConfig.tiny()),
        "gdn-moe": (gdn_moe, gdn_moe.GdnMoeConfig.tiny()),
    }[name]
    return mod.init(jax.random.key(0), cfg, jnp.float32), cfg, {"family": mod}


ORDERS = [(mode, "llama") for mode in MODES] + [
    ("chunked", "mla-moe"), ("chunked", "gdn-moe"),
]


@pytest.mark.parametrize("mode,family", ORDERS)
def test_sending_chunks_ahead_does_not_change_the_tokens(tiny, mode, family):
    """Request for request the tokens are those of the parent's order of
    dispatches (the same engine with nothing sent ahead), in every mode
    and for every family; the chunked path does send chunks ahead."""
    params, cfg, kw = _family(family) if family != "llama" else (*tiny, {})
    sent = []
    eng = GenerationEngine(
        params, cfg, max_slots=4, dtype=jnp.float32,
        on_prefill_dispatch=sent.append, **MODES[mode][0], **kw,
    )
    eng.start(warmup=False)

    def serve():
        rider, doc = _rider_then_doc(eng, doc_new=6)
        rest = [eng.submit(p, 12) for p in PROMPTS]
        return [list(map(int, f.result(timeout=300)))
                for f in (rider, doc, *rest)]

    try:
        send_ahead, eng._send_chunk_ahead = eng._send_chunk_ahead, lambda: None
        parent_order = serve()
        assert "ahead" not in sent
        eng._send_chunk_ahead = send_ahead
        served = serve()
        assert not eng._open_ticks and not eng._pending
    finally:
        eng.shutdown()
    assert served == parent_order
    assert [len(out) for out in served] == [40, 6] + [12] * len(PROMPTS)
    # Packed and unified admissions never take the single-admission path;
    # without ``prefill_chunk`` there is no chunk to send.
    assert ("ahead" in sent) == (mode == "chunked")


class _CountsReadEarly:
    """A chunk's on-device counts that remember being converted while the
    chunk's tick (the one that waits on ``logits``) was still open."""

    def __init__(self, eng, logits, counts, early):
        self.eng, self.logits, self.counts, self.early = (
            eng, logits, counts, early)

    def __array__(self, *args, **kwargs):
        if any(t.wait_on is self.logits for t in self.eng._open_ticks):
            self.early.append(self)
        return np.asarray(self.counts)


def test_a_steps_read_back_converts_no_count_of_an_open_chunk():
    """With ``on_moe`` set, the counts of a chunk queued behind the step
    stay pending until its tick is closed: the step's read-back never
    waits a whole chunk, and every count still arrives."""
    params, cfg, kw = _family("mla-moe")
    calls, early, sent = [], [], []
    eng = GenerationEngine(
        params, cfg, max_slots=4, dtype=jnp.float32, prefill_chunk=8,
        on_moe=lambda program, *_rest: calls.append(program),
        on_prefill_dispatch=sent.append, **kw,
    )
    eng.start(warmup=False)
    chunk_program = eng._prefill_one_chunk

    def guarded(*args):
        logits, *rest, counts = chunk_program(*args)
        return (logits, *rest, _CountsReadEarly(eng, logits, counts, early))

    try:
        eng._prefill_one_chunk = guarded
        rider, doc = _rider_then_doc(eng)
        doc.result(timeout=300)
        rider.result(timeout=300)
        steps = eng.dispatches_total["decode"]
    finally:
        eng.shutdown()
    assert sent.count("ahead") == 4 and not early
    assert calls.count("prefill") == len(sent) == 6
    assert calls.count("decode") == steps


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


def _nth_call(eng, n, then):
    """Wrap the chunk program: its ``n``-th call from now returns
    ``then(outputs)``."""
    chunk_program, seen = eng._prefill_one_chunk, []

    def program(*args):
        out = chunk_program(*args)
        seen.append(1)
        return then(out) if len(seen) == n else out

    eng._prefill_one_chunk = program


# Which of LONG's chunks fails at its deferred wait: the first is sent in
# the admit phase and waited for behind its pass's step, the second goes
# out behind that step and is waited for behind the next pass's.
@pytest.mark.parametrize("nth,when", [(1, "in_turn"), (2, "ahead")])
def test_a_chunk_failing_at_its_deferred_wait_fails_its_own_admission(
    tiny, nth, when
):
    sent = []
    eng = _engine(tiny, prefill_chunk=8, on_prefill_dispatch=sent.append)
    try:
        (reference,) = _serve(eng, [PROMPTS[1]], new=6)
        rider, started = _first_token_then(eng, PROMPTS[0], 40)
        assert started.wait(timeout=120)
        del sent[:]
        _nth_call(eng, nth, lambda out: (_Wedged(), *out[1:]))
        doc = eng.submit(LONG, 4)
        with pytest.raises(RuntimeError, match="injected device error"):
            doc.result(timeout=300)
        # A step was already queued behind the chunk, so the failure
        # surfaced inside ``_step``: the admission owns it all the same,
        # and the slots go the way of any lost device state.
        with pytest.raises(RuntimeError, match="generation step failed"):
            rider.result(timeout=300)
        assert sent[nth - 1] == when
        assert eng._poison_counts == {eng._fingerprint(np.asarray(LONG)): 1}
        (again,) = _serve(eng, [PROMPTS[1]], new=6)
        assert not eng._pending and not eng._open_ticks
    finally:
        eng.shutdown()
    assert list(again) == list(reference)


def test_a_step_failing_with_a_chunk_in_flight_spares_the_admission(tiny):
    """The chunk sent ahead lives in the scratch, not in the slots' cache:
    when the step after it fails, its tick is still journaled and the
    admission goes on to the tokens it serves alone."""
    sent = []
    eng = _engine(tiny, prefill_chunk=8, on_prefill_dispatch=sent.append)
    try:
        (reference,) = _serve(eng, [LONG], new=4)
        rider, started = _first_token_then(eng, PROMPTS[0], 40)
        assert started.wait(timeout=120)
        ticks = _spy_ticks(eng)
        step_program = eng._decode_greedy

        def failing_once(*args):
            if "ahead" not in sent:
                return step_program(*args)
            eng._decode_greedy = step_program
            raise RuntimeError("injected step error")

        eng._decode_greedy = failing_once
        del sent[:]
        doc = eng.submit(LONG, 4)
        with pytest.raises(RuntimeError, match="generation step failed"):
            rider.result(timeout=300)
        served = doc.result(timeout=300)
        assert not eng._pending and not eng._open_ticks
    finally:
        eng.shutdown()
    assert list(served) == list(reference)
    assert len(sent) == 5 and sent[:2] == ["in_turn", "ahead"]
    assert [k for k, _t0, _w in ticks].count("prefill") == 5 + 1


def test_a_request_cancelled_with_its_chunk_in_flight_leaves_nothing_open(tiny):
    sent = []
    eng = _engine(tiny, prefill_chunk=8, on_prefill_dispatch=sent.append)
    try:
        (reference,) = _serve(eng, [PROMPTS[1]], new=6)
        rider, started = _first_token_then(eng, PROMPTS[0], 40)
        assert started.wait(timeout=120)
        del sent[:]
        cancelled, docs = [], queue.Queue()
        _nth_call(
            eng, 2,
            lambda out: cancelled.append(docs.get(timeout=60).cancel()) or out,
        )
        doc = eng.submit(LONG, 4)
        docs.put(doc)
        assert len(rider.result(timeout=300)) == 40
        assert cancelled == [True] and doc.cancelled()
        (again,) = _serve(eng, [PROMPTS[1]], new=6)
        # The admission ran to its end on the scratch and gave its slot
        # back at its first token; the next one starts a fresh scratch.
        assert sent[:5] == ["in_turn"] + ["ahead"] * 4
        assert not eng._pending and not eng._open_ticks
        assert all(s is None for s in eng._slots)
    finally:
        eng.shutdown()
    assert list(again) == list(reference)


def test_a_control_op_finds_no_open_tick_under_it(tiny):
    seen, ops = [], []

    def sent(when):
        # From the engine thread, with the chunk sent ahead in flight.
        if when == "ahead" and not ops:
            ops.append(eng.run_control(
                lambda: seen.append(len(eng._open_ticks))))

    eng = _engine(tiny, prefill_chunk=8, on_prefill_dispatch=sent)
    try:
        rider, doc = _rider_then_doc(eng)
        doc.result(timeout=300)
        rider.result(timeout=300)
        ops[0].result(timeout=60)
    finally:
        eng.shutdown()
    assert seen == [0]


def test_a_write_back_and_a_preemption_find_no_open_tick_under_them(tiny):
    """With the prefix cache on, a chunk that owes a write-back is read
    at once and is never sent ahead (a padded tail owes none); an eviction
    reads the slots' cache with no tick open either."""
    from tpumlops.server.prefix_cache import PrefixCacheConfig

    sent, open_at = [], {"write-back": [], "evict": []}
    eng = _engine(
        tiny, prefill_chunk=8, preemption=True, slo_class="batch",
        prefix_cache=PrefixCacheConfig(
            enabled=True, budget_bytes=2**24, chunk_tokens=8),
        on_prefill_dispatch=sent.append,
    )
    insert, evict = eng._prefix_cache.insert_chunk, eng._evict_slot

    def insert_chunk(*args):
        open_at["write-back"].append(len(eng._open_ticks))
        return insert(*args)

    def evict_slot(idx):
        open_at["evict"].append(len(eng._open_ticks))
        return evict(idx)

    eng._prefix_cache.insert_chunk, eng._evict_slot = insert_chunk, evict_slot
    try:
        rider, doc = _rider_then_doc(eng, doc=LONG[:27])  # 3 chunks + a tail
        doc.result(timeout=300)
        rider.result(timeout=300)
        # The rider's chunk is a padded tail; LONG's three full chunks are
        # written back, its tail goes behind a step.
        assert sent == ["in_turn"] * 4 + ["ahead"]
        assert open_at["write-back"] == [0, 0, 0]
        # Four batch streams hold every slot; an interactive request
        # evicts one.
        streams = [_first_token_then(eng, [7 + i, 9, 11], 30) for i in range(4)]
        assert all(started.wait(timeout=120) for _f, started in streams)
        urgent = eng.submit(PROMPTS[3], 4, slo_class="interactive")
        assert len(urgent.result(timeout=300)) == 4
        for fut, _started in streams:
            assert len(fut.result(timeout=300)) == 30
        assert open_at["evict"] == [0]
    finally:
        eng.shutdown()


def test_shutdown_with_a_chunk_in_flight_returns(tiny):
    from tpumlops.server.generation import EngineShutdown

    in_flight, stopping = threading.Event(), threading.Event()

    def sent(when):  # the engine thread holds here until the stop is set
        if when == "ahead":
            in_flight.set()
            assert stopping.wait(timeout=120)

    eng = _engine(tiny, prefill_chunk=8, on_prefill_dispatch=sent)
    try:
        rider, doc = _rider_then_doc(eng)
        assert in_flight.wait(timeout=120)
        eng._stop.set()
    finally:
        stopping.set()
        eng.shutdown()
    assert not eng._thread.is_alive()
    assert not eng._open_ticks and not eng._pending
    with pytest.raises(EngineShutdown):
        doc.result(timeout=60)


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


@pytest.fixture(scope="module")
def capture_report():
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parent.parent / "scripts" / "capture_report.py"
    spec = importlib.util.spec_from_file_location("capture_report", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


US = 1e3  # nanoseconds


def test_gap_table_names_a_gap_by_the_program_that_follows(capture_report):
    """`scripts/capture_report.py::gap_table` on plain lists, by hand: a
    chunk, a step queued 20 us behind it (no gap), 400 us idle in front of
    the next step, an insert that overlaps a step's tail, 2000 us in front
    of the scratch's zero-fill and 100 us in front of the chunk behind it."""
    modules = [
        ("jit__prefill_one_chunk(1)", 0 * US, 1000 * US),
        ("jit__decode_greedy(2)", 1020 * US, 1500 * US),   # 20 us: under the floor
        ("jit__decode_greedy(2)", 1900 * US, 2400 * US),   # 400 us idle before it
        ("jit__insert_only(3)", 2300 * US, 2500 * US),     # overlaps: no gap
        ("jit__cache_buffers(4)", 4500 * US, 4600 * US),   # 2000 us idle
        ("jit__prefill_one_chunk(1)", 4700 * US, 5700 * US),  # 100 us idle
    ]
    spans = [
        ("engine.iteration", 0 * US, 2450 * US),
        ("engine.decode_readback", 1000 * US, 1600 * US),
        ("engine.emit", 1600 * US, 1700 * US),
        ("engine.decode_dispatch", 1850 * US, 1950 * US),
        ("engine.iteration", 2500 * US, 5800 * US),
        ("engine.admit", 2600 * US, 4800 * US),
        ("engine.prefill_dispatch", 4000 * US, 4750 * US),
    ]
    t = capture_report.gap_table(modules, spans)
    assert t["window_s"] == pytest.approx(5700e-6)
    assert t["gaps"] == 3 and t["gap_s"] == pytest.approx(2500e-6)
    assert {k: (pytest.approx(v[0]), v[1]) for k, v in t["by_program"].items()} == {
        "jit__cache_buffers": (pytest.approx(2000e-6), 1),
        "jit__decode_greedy": (pytest.approx(400e-6), 1),
        "jit__prefill_one_chunk": (pytest.approx(100e-6), 1),
    }
    assert list(t["by_program"]) == [
        "jit__cache_buffers", "jit__decode_greedy", "jit__prefill_one_chunk"]
    # 1500-1900: readback 100, emit 100, the root's own 150, dispatch 50;
    # 2500-4500: the root's own 100, admit 1400, prefill_dispatch 500;
    # 4600-4700: prefill_dispatch.
    assert t["by_span"] == {
        "engine.admit": pytest.approx(1400e-6),
        "engine.prefill_dispatch": pytest.approx(600e-6),
        "engine.iteration": pytest.approx(250e-6),
        "engine.decode_readback": pytest.approx(100e-6),
        "engine.emit": pytest.approx(100e-6),
        "engine.decode_dispatch": pytest.approx(50e-6),
    }
    assert sum(t["by_span"].values()) == pytest.approx(t["gap_s"])
    # No host line in the capture: every gap is under no span.
    bare = capture_report.gap_table(modules, [])
    assert bare["by_span"] == {"(no span)": pytest.approx(2500e-6)}
    assert bare["by_program"] == t["by_program"]
    assert capture_report.gap_table([], spans)["gaps"] == 0


def test_account_delta_reads_the_two_payloads_of_a_profile_answer(capture_report):
    def at(iteration, wait, decode, chunk, emit):
        return {
            "spans": {"engine.iteration": {"total_s": iteration},
                      "engine.wait_work": {"total_s": wait}},
            "device_starved": {
                "by_label": {"decode": {"seconds": decode[0], "intervals": decode[1]},
                             "chunk": {"seconds": chunk[0], "intervals": chunk[1]}},
                "by_span_s": {"engine.emit": emit, "engine.admit": 1.0},
            },
        }

    d = capture_report.account_delta({
        "start": at(10.0, 4.0, (0.5, 100), (0.2, 7), 0.3),
        "stop": at(12.5, 4.5, (0.75, 200), (0.2, 7), 0.55),
    })
    assert d["busy_s"] == pytest.approx(2.0)
    assert d["starved_s"] == pytest.approx(0.25)
    assert d["by_before"] == {"decode": [pytest.approx(0.25), 100]}  # no new chunk gap
    assert d["by_span"] == {"engine.emit": pytest.approx(0.25)}
    # A server from before the account answers without it.
    assert capture_report.account_delta({"start": {"spans": {}}, "stop": {"spans": {}}}) is None
    assert capture_report.account_delta({}) is None


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
