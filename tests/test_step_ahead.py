"""One decode step in flight across the end of a pass: a pass that sends
no chunk ahead dispatches step n+1 before it reads step n back, and reads
step n from the token array it held before that dispatch.

With the tiny configuration of each causal-LM family (dense llama, the
latent-attention sparse-expert ``mla_moe``, the Gated DeltaNet ``gdn_moe``):
the tokens are ``generate_greedy``'s under staggered arrivals, a slot
re-filled behind the step in flight gets no stale token, a row crossing a
window bucket with a step in flight is covered, a step's expert counts are
read at its own read-back only, and every point where host state must be
exact finds nothing un-read, and a step that fails at its read-back inside
an admission blames no prompt.  Engines start without the warm-up sweep.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumlops.models import gdn_moe, llama, mla_moe
from tpumlops.server.generation import GenerationEngine

FAMILIES = {
    "llama": (llama, lambda: llama.LlamaConfig.tiny(max_seq=64)),
    "mla-moe": (mla_moe, mla_moe.MlaMoeConfig.tiny),
    "gdn-moe": (gdn_moe, gdn_moe.GdnMoeConfig.tiny),
}


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    """(module, cfg, params, engine kwargs) of one family, float32."""
    mod, make = FAMILIES[request.param]
    cfg = make()
    params = mod.init(jax.random.key(3), cfg, jnp.float32)
    kw = {} if mod is llama else {"family": mod}
    return mod, cfg, params, kw


def _engine(family, **kw):
    _mod, cfg, params, fkw = family
    eng = GenerationEngine(
        params, cfg, dtype=jnp.float32, **{"max_slots": 3, **fkw, **kw}
    )
    eng.start(warmup=False)
    return eng


REF_NEW = 40  # one reference length: a greedy run's prefix is the shorter run


def _greedy(family, prompt, n):
    mod, cfg, params, _kw = family
    out = mod.generate_greedy(
        params, jnp.asarray([prompt], jnp.int32), REF_NEW, cfg,
        dtype=jnp.float32,
    )
    return np.asarray(out)[0, -REF_NEW:][:n].tolist()


def _prompts(sizes, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 200, n).astype(np.int32).tolist() for n in sizes]


def _first_token_then(eng, prompt, new, **kw):
    started = threading.Event()
    fut = eng.submit(prompt, new, on_token=lambda _t: started.set(), **kw)
    return fut, started


def test_served_tokens_equal_generate_greedy_under_staggered_arrivals(family):
    """Requests join while others decode, on three slots with chunked
    prefill (chunks sent ahead, chunks in turn, steps in flight between
    them): every request's tokens are ``generate_greedy``'s, and most steps
    went out behind the one in flight."""
    sent = []
    eng = _engine(family, prefill_chunk=8, on_decode_dispatch=sent.append)
    prompts = _prompts((5, 19, 8, 5, 19, 8))
    news = (20, 7, 12, 9, 5, 14)
    try:
        first, started = _first_token_then(eng, prompts[0], news[0])
        assert started.wait(timeout=300)
        futs = [first]
        for p, n in zip(prompts[1:], news[1:]):
            futs.append(eng.submit(p, n))
            time.sleep(0.01)
        outs = [list(map(int, f.result(timeout=300))) for f in futs]
        assert eng._ahead is None and eng._unseen == 0
    finally:
        eng.shutdown()
    for p, n, out in zip(prompts, news, outs):
        assert out == _greedy(family, p, n)
    assert sent.count("ahead") > sent.count("in_turn") > 0


def test_a_slot_refilled_behind_the_step_in_flight_gets_no_stale_token(family):
    """A request that finishes on EOS at step n had a row in step n+1 (its
    budget said it needed one); a queued request is admitted into its slot
    in the very next pass, behind step n+1, before that step is read
    back.  Row and slot no longer match at the read-back: the old token is
    emitted to nobody, and the new request's tokens are its own."""
    rider_p, done_p, next_p = _prompts((8, 8, 8), seed=11)
    ref = _greedy(family, done_p, 24)
    # EOS at the first token that has not occurred before, past the third.
    j = next(i for i in range(3, len(ref)) if ref[i] not in ref[:i])
    refilled = []
    eng = _engine(family, max_slots=2)
    read_step = eng._read_step

    def spy(step):
        refilled.extend(
            i for i, s in enumerate(step.slots)
            if s is not None and eng._slots[i] not in (None, s)
        )
        return read_step(step)

    eng._read_step = spy
    try:
        rider, started = _first_token_then(eng, rider_p, 40)
        assert started.wait(timeout=300)
        done = eng.submit(done_p, 24, eos_id=ref[j])
        nxt = eng.submit(next_p, 10)  # waits for the slot ``done`` frees
        got_done = list(map(int, done.result(timeout=300)))
        got_next = list(map(int, nxt.result(timeout=300)))
        got_rider = list(map(int, rider.result(timeout=300)))
    finally:
        eng.shutdown()
    assert refilled, "no slot was re-filled behind a step in flight"
    assert got_done == ref[: j + 1]
    assert got_next == _greedy(family, next_p, 10)
    assert got_rider == _greedy(family, rider_p, 40)


def test_a_row_crossing_a_window_bucket_with_a_step_in_flight(family):
    """A row whose next write crosses a window bucket (16 -> 24 -> 32) while
    the step before it is still in flight: the window picked for the step
    covers the position the device writes, counting the step in flight,
    and the tokens are ``generate_greedy``'s."""
    crossings = []
    eng = _engine(family)
    dispatch = eng._dispatch_step

    def spy(active_np, window, sampling):
        in_flight = eng._ahead is not None
        dispatch(active_np, window, sampling)
        # The step just dispatched writes at each active row's length
        # before it (read here, after the fact: this blocks the test only).
        lengths = np.asarray(eng._lengths) - 1
        written = int(lengths[active_np].max())
        assert written < window, (written, window)
        crossings.append((in_flight, window, written))

    eng._dispatch_step = spy
    prompt = _prompts((13,), seed=5)[0]
    try:
        (out,) = [eng.submit(prompt, 22).result(timeout=300)]
    finally:
        eng.shutdown()
    assert list(map(int, out)) == _greedy(family, prompt, 22)
    windows = [w for _f, w, _x in crossings]
    assert windows == sorted(windows) and len(set(windows)) >= 3
    # The first step of a bucket went out behind the last of the one below.
    edges = [c for c, p in zip(crossings[1:], crossings) if c[1] != p[1]]
    assert edges and all(in_flight for in_flight, _w, _x in edges)
    # ... at the first write past the bucket below, not a step early.
    assert all(written >= prev_w for (_f, _w, written), (_pf, prev_w, _px)
               in zip(crossings[1:], crossings) if _w != prev_w)


class _CountsGuard:
    """A step's on-device counts that remember being converted anywhere but
    inside that step's own read-back (``reading`` holds the step being read
    back, if any)."""

    def __init__(self, reading, counts, stray):
        self.reading, self.counts, self.stray = reading, counts, stray

    def __array__(self, *args, **kwargs):
        own = self.reading and any(
            entry[-1] is self for entry in self.reading[-1].counts)
        if not own:
            self.stray.append(self)
        return np.asarray(self.counts)


@pytest.mark.parametrize("name", ["mla-moe", "gdn-moe"])
def test_a_steps_expert_counts_are_read_at_its_own_read_back(name):
    """``on_moe`` gets each step's counts once that step is read back, never
    while it is in flight (a conversion there would wait for it and queue
    nothing behind it), and every step's and chunk's counts arrive."""
    mod, make = FAMILIES[name]
    cfg = make()
    fam = (mod, cfg, mod.init(jax.random.key(3), cfg, jnp.float32),
           {"family": mod})
    calls, stray, sent, reading = [], [], [], []
    eng = _engine(
        fam, prefill_chunk=8, on_decode_dispatch=sent.append,
        on_moe=lambda program, *_rest: calls.append(program),
    )
    step_program, read_step = eng._decode_greedy, eng._read_step

    def guarded(*args):
        *outs, counts = step_program(*args)
        return (*outs, _CountsGuard(reading, counts, stray))

    def spy(step):
        reading.append(step)
        try:
            return read_step(step)
        finally:
            reading.pop()

    eng._decode_greedy, eng._read_step = guarded, spy
    try:
        first, started = _first_token_then(eng, _prompts((5,))[0], 16)
        assert started.wait(timeout=300)
        later = eng.submit(_prompts((19,), seed=2)[0], 6)
        first.result(timeout=300), later.result(timeout=300)
        steps, chunks = eng.dispatches_total["decode"], eng.prefill_chunks_dispatched
    finally:
        eng.shutdown()
    assert "ahead" in sent and not stray
    assert calls.count("decode") == steps == len(sent)
    assert calls.count("prefill") == chunks


def _rider(eng, new=50, after=4):
    """A stream that has emitted ``after`` tokens: its steps go out behind
    the one in flight by then."""
    seen, steady = [], threading.Event()

    def on_token(_t):
        seen.append(1)
        if len(seen) >= after:
            steady.set()

    fut = eng.submit(_prompts((6,), seed=13)[0], new, on_token=on_token)
    assert steady.wait(timeout=300)
    return fut


def _drain_spy(eng, name, seen):
    """Wrap ``eng.<name>``: note whether a step was in flight when it was
    called and what was un-read once it ran."""
    inner = getattr(eng, name)

    def spy(*args, **kwargs):
        before = eng._ahead is not None
        out = inner(*args, **kwargs)
        seen.append((before, eng._ahead is None and eng._unseen == 0))
        return out

    setattr(eng, name, spy)


def _point_control_op(family, tiny_prefix):
    eng = _engine(family)
    seen = []
    _drain_spy(eng, "_settle_ticks", seen)
    try:
        rider = _rider(eng)
        op = eng.run_control(lambda: (eng._ahead, eng._unseen))
        assert op.result(timeout=300) == (None, 0)
        assert len(rider.result(timeout=300)) == 50
    finally:
        eng.shutdown()
    return seen


def _point_cancellation(family, tiny_prefix):
    eng = _engine(family)
    seen = []
    _drain_spy(eng, "_settle_ticks", seen)  # a wait for traffic settles first
    try:
        rider = _rider(eng, new=50)
        del seen[:]
        rider.cancel()
        deadline = time.monotonic() + 120
        while not seen and time.monotonic() < deadline:
            time.sleep(0.01)  # the loop frees the slot, then waits for traffic
        assert all(s is None for s in eng._slots)
    finally:
        eng.shutdown()
    return seen


def _point_preemption(family, tiny_prefix):
    eng = _engine(family, max_slots=2, preemption=True, slo_class="batch",
                  prefill_chunk=8, prefix_cache=tiny_prefix)
    seen = []
    _drain_spy(eng, "_settle_ticks", seen)
    evicted = []
    evict = eng._evict_slot

    def evict_slot(idx):
        evicted.append(eng._ahead is None and eng._unseen == 0)
        return evict(idx)

    eng._evict_slot = evict_slot
    try:
        streams = [_first_token_then(eng, [7 + i, 9, 11], 30) for i in range(2)]
        assert all(started.wait(timeout=300) for _f, started in streams)
        urgent = eng.submit([5, 6, 7], 4, slo_class="interactive")
        assert len(urgent.result(timeout=300)) == 4
        assert all(len(f.result(timeout=300)) == 30 for f, _s in streams)
    finally:
        eng.shutdown()
    assert evicted == [True]
    return seen


def _point_write_back(family, tiny_prefix):
    eng = _engine(family, prefill_chunk=8, prefix_cache=tiny_prefix)
    seen = []
    _drain_spy(eng, "_cache_chunk", seen)
    try:
        rider = _rider(eng)
        doc = eng.submit(_prompts((27,), seed=17)[0], 4)  # 3 chunks + a tail
        assert len(doc.result(timeout=300)) == 4
        assert len(rider.result(timeout=300)) == 50
    finally:
        eng.shutdown()
    return seen


def _point_shutdown(family, tiny_prefix):
    stopping, out = threading.Event(), []

    def sent(when):  # the engine thread holds here until the stop is set
        if when == "ahead" and not stopping.is_set():
            out.append(1)
            assert stopping.wait(timeout=120)

    eng = _engine(family, on_decode_dispatch=sent)
    try:
        rider = _rider(eng, new=50, after=1)  # its second step holds the thread
        deadline = time.monotonic() + 120
        while not out and time.monotonic() < deadline:
            time.sleep(0.01)
        eng._stop.set()
    finally:
        stopping.set()
        eng.shutdown()
    assert not eng._thread.is_alive() and rider.done()
    return [(True, eng._ahead is None and eng._unseen == 0)]


POINTS = {
    "cancellation": _point_cancellation,
    "control-op": _point_control_op,
    "preemption": _point_preemption,
    "write-back": _point_write_back,
    "shutdown": _point_shutdown,
}


# The radix prefix cache (a write-back, and preemption, which parks K/V
# through it) is the dense family's alone.
DRAINS = [(f, p) for f in FAMILIES for p in POINTS
          if f == "llama" or p not in ("preemption", "write-back")]


@pytest.mark.parametrize("family,point", DRAINS, indirect=["family"])
def test_a_drain_point_leaves_nothing_unread(family, point):
    """Where host state must be exact (a control op, an eviction, a
    prefix-cache write-back, a wait for traffic after a cancellation,
    shutdown), the step in flight has been read back first: nothing is
    un-seen, and at least one such point found a step in flight."""
    from tpumlops.server.prefix_cache import PrefixCacheConfig

    prefix = PrefixCacheConfig(enabled=True, budget_bytes=2**24, chunk_tokens=8)
    seen = POINTS[point](family, prefix)
    assert seen and all(ok for _before, ok in seen), seen
    assert any(before for before, _ok in seen), "no step was in flight"


def test_the_decode_dispatch_counter_on_metrics(tmp_path):
    """A real server on a tiny artifact: warm-up counts nothing, and a
    /generate of n tokens puts n - 1 steps on
    ``tpumlops_decode_dispatch_total``: the first ``in_turn``, the rest
    ``ahead``."""
    import dataclasses

    import httpx

    from tpumlops.clients.localplane import free_port, start_model_server
    from tpumlops.server import loader
    from tpumlops.utils.config import TpuSpec

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    loader.save_native_model(
        tmp_path / "m", "llama-generate",
        llama.init(jax.random.key(0), cfg, jnp.bfloat16),
        config=dataclasses.asdict(cfg))
    port = free_port()
    handle = start_model_server(
        str(tmp_path / "m"), "v1", port, model_name="m",
        tpu=TpuSpec.from_spec({"meshShape": {"tp": 1}, "maxSlots": 2}))

    def samples():
        text = httpx.get(f"http://127.0.0.1:{port}/metrics", timeout=30).text
        return {
            line.split('when="', 1)[1].split('"', 1)[0]: float(line.rsplit(" ", 1)[1])
            for line in text.splitlines()
            if line.startswith("tpumlops_decode_dispatch_total{")
        }

    try:
        assert samples() == {}
        r = httpx.post(
            f"http://127.0.0.1:{port}/v2/models/m/generate",
            json={"prompt_ids": list(range(1, 10)), "max_new_tokens": 8},
            timeout=120)
        assert r.status_code == 200, r.text
        got = samples()
    finally:
        handle.stop()
    assert got == {"in_turn": 1.0, "ahead": 6.0}


class _Unreadable:
    """Stands for the tokens of a step that failed on the device: the error
    surfaces at the read-back."""

    def __array__(self, *args, **kwargs):
        raise RuntimeError("injected read-back error")


def _fail_the_step_in_flight_at(eng, name):
    """At the first call of ``eng.<name>`` with a step in flight, that step
    fails at its read-back; returns the list the failed step lands in."""
    inner, failed = getattr(eng, name), []

    def spy(*args, **kwargs):
        if not failed and eng._ahead is not None:
            failed.append(eng._ahead)
            eng._ahead = dataclasses.replace(eng._ahead, tokens=_Unreadable())
        return inner(*args, **kwargs)

    setattr(eng, name, spy)
    return failed


# Where an admission blocks with a step in flight: the read of its first
# token (every family), a prefix-cache write-back (the dense family's).
ADMISSION_POINTS = [(f, "_emit_first") for f in FAMILIES] + [
    ("llama", "_cache_chunk"),
]


@pytest.mark.parametrize("family,point", ADMISSION_POINTS, indirect=["family"])
def test_a_step_failing_under_an_admission_blames_no_prompt(family, point):
    """The step in flight fails at its read-back while an admission waits
    at one of its blocking points: the decode crash is not attributed (no
    poison count), the admission and the streams are lost with the device
    state, nothing stays out, and the engine serves again."""
    from tpumlops.server.prefix_cache import PrefixCacheConfig

    kw = {}
    if point == "_cache_chunk":
        kw = {"prefill_chunk": 8, "prefix_cache": PrefixCacheConfig(
            enabled=True, budget_bytes=2**24, chunk_tokens=8)}
    eng = _engine(family, **kw)
    doc_p = _prompts((19,), seed=23)[0]
    try:
        rider = _rider(eng)
        failed = _fail_the_step_in_flight_at(eng, point)
        doc = eng.submit(doc_p, 4)
        with pytest.raises(RuntimeError, match="injected read-back error"):
            doc.result(timeout=300)
        with pytest.raises(RuntimeError, match="generation step failed"):
            rider.result(timeout=300)
        assert failed, f"no step was in flight at {point}"
        assert eng._poison_counts == {} and not eng._quarantined
        again = list(map(int, eng.submit(doc_p, 4).result(timeout=300)))
        assert eng._ahead is None and not eng._open_ticks
        assert eng._unseen == 0
    finally:
        eng.shutdown()
    assert again == _greedy(family, doc_p, 4)
