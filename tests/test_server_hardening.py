"""Regression tests for the server-hardening review findings: warmup covers
the capped bucket, client batches ride warmed buckets, shutdown fails queued
futures."""

import numpy as np
import pytest

from tpumlops.models.registry import Predictor
from tpumlops.server.batching import DynamicBatcher
from tpumlops.server.engine import InferenceEngine


def make_engine(max_batch):
    seen_batches = []

    def predict(x):
        seen_batches.append(x.shape[0])
        return x.sum(axis=-1)

    pred = Predictor(
        name="t",
        predict=predict,
        jittable=False,  # host path: shapes recorded verbatim
        example_input=lambda b: np.zeros((b, 4), np.float32),
    )
    return InferenceEngine(pred, max_batch_size=max_batch), seen_batches


def test_warmup_includes_non_pow2_cap():
    engine, seen = make_engine(max_batch=24)
    # Reuse warmup's default bucket enumeration via a fake jittable path:
    # engine._jitted is None (pyfunc), so emulate by calling the bucket logic.
    buckets = []
    b = 1
    while b <= engine.max_batch_size:
        buckets.append(b)
        b <<= 1
    if buckets[-1] != engine.max_batch_size:
        buckets.append(engine.max_batch_size)
    assert buckets == [1, 2, 4, 8, 16, 24]


def test_client_batches_ride_buckets():
    from tpumlops.server.app import TpuInferenceServer
    from tpumlops.server.metrics import ServerMetrics

    engine, seen = make_engine(max_batch=8)
    server = TpuInferenceServer(
        engine,
        ServerMetrics("d", "v1", "ns"),
        model_name="m",
        max_batch_size=8,
    )
    # Odd client batch of 5 -> padded to bucket 8, sliced back to 5.
    out = server._predict_bucketed({"x": np.ones((5, 4), np.float32)})
    assert np.asarray(out).shape == (5,)
    assert seen == [8]
    # Batch of 20 > cap 8 -> chunks of 8, 8, then 4 (bucket for remainder 4).
    seen.clear()
    out = server._predict_bucketed({"x": np.ones((20, 4), np.float32)})
    assert np.asarray(out).shape == (20,)
    assert seen == [8, 8, 4]


def test_stop_fails_queued_futures():
    import threading

    release = threading.Event()

    def slow_batch(inputs):
        release.wait(2)
        return inputs["x"]

    b = DynamicBatcher(slow_batch, max_batch_size=2, max_batch_delay_ms=1)
    b.start()
    f1 = b.submit({"x": np.ones((2,), np.float32)})
    # Different trailing shape: gets re-queued by the collector.
    f2 = b.submit({"x": np.ones((3,), np.float32)})
    release.set()
    b.stop()
    # f1 either completed or failed-at-shutdown; f2 must NOT hang forever.
    assert f2.done() or f2.exception(timeout=1) is not None
    with pytest.raises((RuntimeError, Exception)):
        if f2.exception(timeout=1):
            raise f2.exception()


def _grid_predictor(traced):
    def predict(x):
        traced.append(tuple(x.shape))  # recorded at trace time: one per shape
        return x.sum(axis=-1)

    return Predictor(
        name="t",
        predict=predict,
        jittable=True,
        example_input=lambda b: {"x": np.zeros((b, 16), np.float32)},
        seq_pad={"axis": 1, "max_len": 64, "min_bucket": 16, "pad_values": {"x": 0}},
    )


def test_warmup_default_warms_length_ladder_edges_only():
    traced = []
    engine = InferenceEngine(_grid_predictor(traced), max_batch_size=4)
    engine.warmup()
    # base length: every batch bucket; other lengths: batch 1 and max only
    assert (2, 16) in traced
    assert (1, 32) in traced and (4, 32) in traced
    assert (2, 32) not in traced and (2, 64) not in traced


def test_warmup_full_grid_covers_interior_buckets():
    """spec.tpu.warmupFullGrid: interior batch buckets at non-base lengths
    must be compiled at startup, not on first live traffic (ADVICE r2)."""
    traced = []
    engine = InferenceEngine(
        _grid_predictor(traced), max_batch_size=4, warmup_full_grid=True
    )
    engine.warmup()
    for b in (1, 2, 4):
        for s in (16, 32, 64):
            assert (b, s) in traced, (b, s)


# ---------------------------------------------------------------------------
# Admission control + lossless drain (the data-plane half of autoscaling):
# 429 shed contract, shed-never-reaches-the-engine, SSE across a drain.
# ---------------------------------------------------------------------------

import asyncio
import json
import threading
import time

import httpx

from tpumlops.server.generation import EngineOverloaded
from tpumlops.utils.config import ServerConfig, TpuSpec


class _HttpHandle:
    """Run a built server's aiohttp app on a daemon thread (the
    test_server.py harness, trimmed)."""

    def __init__(self, server, port: int):
        from aiohttp import web

        self.server = server
        self.base = f"http://127.0.0.1:{port}"
        self._loop = asyncio.new_event_loop()

        def run():
            asyncio.set_event_loop(self._loop)
            runner = web.AppRunner(server.build_app())
            self._loop.run_until_complete(runner.setup())
            self._loop.run_until_complete(
                web.TCPSite(runner, "127.0.0.1", port).start()
            )
            self._loop.run_forever()

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()
        for _ in range(200):
            try:
                httpx.get(self.base + "/v2/health/live", timeout=0.5)
                return
            except Exception:
                time.sleep(0.05)
        raise RuntimeError("server did not come up")

    def stop(self):
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=5)
        self.server.shutdown()


def _build_llm_server(tmp_path, budget: int = 0):
    import jax

    from tpumlops.models import llama
    from tpumlops.server.app import build_server
    from tpumlops.server.loader import save_native_model

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    art = tmp_path / "llm"
    save_native_model(
        art,
        "llama-generate",
        llama.init(jax.random.key(3), cfg),
        config={
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.hidden_size,
            "num_layers": cfg.num_layers,
            "num_heads": cfg.num_heads,
            "num_kv_heads": cfg.num_kv_heads,
            "intermediate_size": cfg.intermediate_size,
            "max_seq": cfg.max_seq,
        },
    )
    return build_server(
        ServerConfig(
            model_name="llm",
            model_uri=str(art),
            predictor_name="v1",
            deployment_name="llm",
            namespace="models",
            tpu=TpuSpec.from_spec(
                {
                    "meshShape": {"tp": 1},
                    "maxBatchSize": 2,
                    "maxSlots": 2,
                    "admissionQueueBudget": budget,
                    "drainGraceSeconds": 30,
                }
            ),
        ),
        # Lazy compiles are fine here (admission control and the drain
        # protocol are scheduling behavior, not numerics) and warmup is
        # the bulk of the fixture's wall time.
        warmup=False,
    )


_SHED_PORT = [19650]


@pytest.fixture(scope="module")
def shed_server(tmp_path_factory):
    server = _build_llm_server(
        tmp_path_factory.mktemp("shed"), budget=64
    )
    _SHED_PORT[0] += 1
    handle = _HttpHandle(server, _SHED_PORT[0])
    yield handle
    handle.stop()


def _metric(handle, family: str, labels: str = "") -> float:
    text = httpx.get(handle.base + "/metrics", timeout=10).text
    total = 0.0
    for line in text.splitlines():
        if line.startswith(family) and labels in line:
            total += float(line.rsplit(" ", 1)[1])
    return total


def _saturate(eng):
    """Fill both slots and leave one request queued — the busy shape the
    budget bounds (the backlog, never request size).  Slot occupants are
    admitted ONE AT A TIME (two queued at once would already exceed the
    tiny budget and shed each other).  Returns the futures so the
    caller can wait the fixture clean."""
    slot_futs = []
    for _ in range(2):
        # Each token of an occupant holds the scheduler a little (its
        # callback runs on that thread), so the occupants outlast the
        # caller's request: finishing first would admit the queued one
        # and empty the backlog the budget bounds.
        slot_futs.append(eng.submit(
            [5, 9, 2, 7], 56, on_token=lambda _t: time.sleep(0.05)))
        deadline = time.monotonic() + 60
        while eng._queue.qsize() > 0 and time.monotonic() < deadline:
            time.sleep(0.01)  # admitted into a slot
        assert eng._queue.qsize() == 0
    queued = eng.submit([5, 9, 2, 7], 56)  # est 60 of 64 budget queued
    return slot_futs + [queued]


def test_shed_429_body_and_retry_after_contract(shed_server):
    """With the admission queue already holding work near the budget, a
    request that would push it over sheds with the pinned contract:
    HTTP 429, JSON body naming the typed reason and retry_after_s, and
    a Retry-After header that matches it."""
    eng = shed_server.server.gen_engine
    futs = _saturate(eng)
    try:
        resp = httpx.post(
            shed_server.base + "/v2/models/llm/generate",
            # est 4+56=60: queued 60 + 60 > budget 64 -> shed.
            json={"prompt_ids": [5, 9, 2, 7], "max_new_tokens": 56},
            timeout=30,
        )
        assert resp.status_code == 429, resp.text
        body = resp.json()
        assert body["reason"] == "budget"
        assert body["retry_after_s"] >= 1
        assert resp.headers["Retry-After"] == str(body["retry_after_s"])
        assert "budget" in body["error"]
        # Shed requests never reach the engine: the queue still holds
        # exactly the one pre-shed request, in-flight is exactly the
        # three admitted sequences, and the counter says why.
        assert eng._queue.qsize() == 1
        assert eng.inflight() == 3
        assert _metric(
            shed_server, "tpumlops_engine_shed_total", 'reason="budget"'
        ) >= 1.0
    finally:
        for f in futs:
            f.result(timeout=120)
    # Engine idle again: the same request now serves 200.
    ok = httpx.post(
        shed_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [5, 9, 2], "max_new_tokens": 4},
        timeout=60,
    )
    assert ok.status_code == 200, ok.text


def test_oversized_single_request_admits_on_idle_engine(shed_server):
    """The budget bounds the BACKLOG, not request size: a request whose
    estimate alone exceeds the budget must ADMIT when the queue is
    empty — shedding it would 429 identically on every replica, a
    deterministic fleet-wide outage for servable work."""
    eng = shed_server.server.gen_engine
    deadline = time.monotonic() + 60
    while eng.inflight() > 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    resp = httpx.post(
        shed_server.base + "/v2/models/llm/generate",
        # Two prompts, est 120 total > budget 64 — but the queue is
        # empty, so it runs.
        json={"prompt_ids": [[5, 9, 2, 7], [1, 2, 3, 4]],
              "max_new_tokens": 56},
        timeout=120,
    )
    assert resp.status_code == 200, resp.text
    assert len(resp.json()["outputs"]) == 2


def test_shed_is_atomic_for_multi_prompt_requests(shed_server):
    """The whole-request reservation: a shed multi-prompt request must
    not leave earlier siblings admitted (generating into abandoned
    futures)."""
    eng = shed_server.server.gen_engine
    futs = _saturate(eng)
    before = eng.shed_total
    try:
        resp = httpx.post(
            shed_server.base + "/v2/models/llm/generate",
            json={
                "inputs": [
                    {
                        "name": "prompt_ids",
                        "shape": [3, 4],
                        "datatype": "INT64",
                        "data": [5, 9, 2, 7] * 3,
                    }
                ],
                "parameters": {"max_new_tokens": 40},
            },
            timeout=30,
        )
        assert resp.status_code == 429
        assert eng.shed_total == before + 1  # ONE shed, whole request
        assert eng.inflight() == 3  # no sibling joined the saturators
    finally:
        for f in futs:
            f.result(timeout=120)


def test_ready_flip_then_begin_drain_still_arms_engine(shed_server):
    """The SIGTERM path flips ``ready = False`` (endpoint-removal lag)
    BEFORE calling begin_drain(); begin_drain must still arm the engine
    — an early-return on lifecycle == "draining" would leave the drain
    admitting forever and wait_drained() spinning out its full grace."""
    server = shed_server.server
    eng = server.gen_engine
    try:
        server.ready = False  # phase 1: NotReady, still admitting
        assert server.lifecycle == "draining"
        assert not eng.draining
        server.begin_drain()  # phase 2 must NOT be a no-op
        assert eng.draining
        assert eng.drained()  # idle fixture: drain completes instantly
        # Once SIGTERM commits the exit, cancel is refused — a client
        # must not re-open admissions on a dying pod.
        server.terminating = True
        assert server.cancel_drain() is False
        assert server.lifecycle == "draining" and eng.draining
    finally:
        server.terminating = False
        assert server.cancel_drain() is True
        assert server.lifecycle == "ready" and not eng.draining


def test_engine_level_shed_when_queue_over_budget():
    """Direct engine contract: queued-but-unadmitted work past the
    budget sheds synchronously; the queue and counters prove nothing
    entered."""
    import jax

    from tpumlops.models import llama
    from tpumlops.server.generation import GenerationEngine

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(0), cfg)
    engine = GenerationEngine(
        params, cfg, max_slots=1, admission_queue_budget=100
    )
    engine.start(warmup=False)
    try:
        # Slot 1 admits (leaves the queue); the next two queue 60 est
        # tokens each: the second pushes 120 > 100 and sheds.
        f1 = engine.submit([5, 9, 2, 7], 40)
        deadline = time.monotonic() + 30
        while engine._queue.qsize() > 0 and time.monotonic() < deadline:
            time.sleep(0.01)  # wait for admission to drain the queue
        f2 = engine.submit([5, 9, 2, 7], 56)  # queued: est 60 <= 100
        with pytest.raises(EngineOverloaded) as err:
            engine.submit([5, 9, 2, 7], 56)  # 60 + 60 > 100
        assert err.value.reason == "budget"
        assert err.value.retry_after_s >= 1
        assert engine.shed_total == 1
        assert engine._queue.qsize() == 1  # only f2's request is queued
        import numpy as np

        assert np.asarray(f1.result(timeout=60)).size == 40
        assert np.asarray(f2.result(timeout=60)).size == 56
    finally:
        engine.shutdown()


def test_per_model_admission_fairness_on_shared_replica():
    """Multiplexed warm pool: with two models holding outstanding work
    on one replica, each is bounded by an equal SHARE of the admission
    budget — the flooded model sheds reason=model_budget at its share
    while the tail model's first request is admitted even though the
    GLOBAL backlog already exceeds the budget (fairness replaces the
    global check; a hot model's backlog must never shed the tail
    model's first token).  Without model= the single-model contract is
    byte-identical (pinned above)."""
    import jax

    from tpumlops.models import llama
    from tpumlops.server.generation import GenerationEngine

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(0), cfg)
    engine = GenerationEngine(
        params, cfg, max_slots=1, admission_queue_budget=80
    )
    # Never started: reservations stay queued, so the ledger is exact.
    engine.reserve_admission(60, model="hot")  # empty queue: admitted
    # Tail model's FIRST request admits despite 60 queued + 30 > 80.
    engine.reserve_admission(30, model="tail")
    # The hot model is now bounded by budget/2 = 40 < its 60 backlog.
    with pytest.raises(EngineOverloaded) as err:
        engine.reserve_admission(10, model="hot")
    assert err.value.reason == "model_budget"
    assert err.value.retry_after_s >= 1
    # The share binds the tail model too once IT has outstanding work.
    with pytest.raises(EngineOverloaded) as err:
        engine.reserve_admission(30, model="tail")
    assert err.value.reason == "model_budget"
    assert engine.shed_total == 2
    # The HTTP-request-scoped release returns the reservation: the tail
    # model drops to zero outstanding and admits again.
    engine.release_model_admission("tail", 30)
    engine.reserve_admission(5, model="tail")
    engine.release_model_admission("tail", 5)
    engine.release_model_admission("hot", 60)
    assert engine._model_est == {}  # ledger empty: single-model path back


def test_sse_stream_survives_drain_and_new_requests_shed(tmp_path):
    """The lossless-drain contract end to end: an SSE stream in flight
    when /admin/drain lands keeps streaming to completion; new requests
    shed 429 reason="draining"; /readyz flips to draining then the
    drain reports zero in-flight."""
    server = _build_llm_server(tmp_path, budget=0)
    _SHED_PORT[0] += 1
    handle = _HttpHandle(server, _SHED_PORT[0])
    try:
        drain_result = {}

        def drain_midflight():
            drain_result.update(
                httpx.post(
                    handle.base + "/admin/drain",
                    json={"grace_s": 60},
                    timeout=90,
                ).json()
            )

        tokens = []
        final = {}
        with httpx.stream(
            "POST",
            handle.base + "/v2/models/llm/generate",
            json={"prompt_ids": [5, 9, 2], "max_new_tokens": 24,
                  "stream": True},
            timeout=120,
        ) as resp:
            assert resp.status_code == 200
            drainer = None
            for line in resp.iter_lines():
                if not line.startswith("data: "):
                    continue
                payload = json.loads(line[len("data: "):])
                if payload.get("done"):
                    final = payload
                    break
                tokens.append(payload["token"])
                if len(tokens) == 2 and drainer is None:
                    # Drain lands mid-stream, grace far longer than the
                    # remaining generation.
                    drainer = threading.Thread(target=drain_midflight)
                    drainer.start()
                    # Readiness flips promptly while the stream lives.
                    deadline = time.monotonic() + 10
                    while time.monotonic() < deadline:
                        r = httpx.get(handle.base + "/readyz", timeout=5)
                        if r.status_code == 503:
                            break
                        time.sleep(0.02)
                    assert r.status_code == 503
                    assert r.json()["lifecycle"] == "draining"
                    # New work is shed, not dropped.
                    shed = httpx.post(
                        handle.base + "/v2/models/llm/generate",
                        json={"prompt_ids": [5], "max_new_tokens": 2},
                        timeout=30,
                    )
                    assert shed.status_code == 429
                    assert shed.json()["reason"] == "draining"
                    assert "Retry-After" in shed.headers
        # The in-flight stream survived the drain to full completion.
        assert "error" not in final, final
        assert len(final["output_ids"]) == 24
        assert len(tokens) == 24
        if drainer is not None:
            drainer.join(timeout=90)
        assert drain_result.get("drained") is True
        assert drain_result.get("inFlight") == 0
        assert drain_result.get("lifecycle") == "draining"
        # The drain is reversible (cancel): a stray or mistaken drain
        # must not be a one-way kill switch on an unauthenticated
        # endpoint.
        undo = httpx.post(
            handle.base + "/admin/drain", json={"cancel": True},
            timeout=10,
        )
        assert undo.status_code == 200 and undo.json()["cancelled"]
        assert httpx.get(handle.base + "/readyz", timeout=5).status_code \
            == 200
        ok = httpx.post(
            handle.base + "/v2/models/llm/generate",
            json={"prompt_ids": [5, 9, 2], "max_new_tokens": 2},
            timeout=60,
        )
        assert ok.status_code == 200, ok.text
    finally:
        handle.stop()


# ---------------------------------------------------------------------------
# Failure containment (PR 13): SSE terminal error events + poison 422
# ---------------------------------------------------------------------------


def test_sse_mid_generation_death_emits_terminal_error_event(tmp_path):
    """An SSE stream whose engine dies mid-generation must NOT just drop
    the connection: it ends with a terminal SSE ``error`` event carrying
    the request_id and a typed reason, so clients can distinguish
    truncation from completion."""
    server = _build_llm_server(tmp_path, budget=0)
    _SHED_PORT[0] += 1
    handle = _HttpHandle(server, _SHED_PORT[0])
    eng = server.gen_engine
    try:
        real_step = eng._dispatch_step
        armed = {"tokens_seen": 0}

        def dying_step(*a, **kw):
            if armed["tokens_seen"] >= 2:
                raise RuntimeError("device wedged mid-generation")
            armed["tokens_seen"] += 1
            return real_step(*a, **kw)

        eng._dispatch_step = dying_step
        tokens = []
        events = []  # (sse_event_name, payload)
        current_event = [""]
        with httpx.stream(
            "POST",
            handle.base + "/v2/models/llm/generate",
            json={"prompt_ids": [5, 9, 2], "max_new_tokens": 24,
                  "stream": True},
            headers={"X-Request-Id": "sse-death-1"},
            timeout=120,
        ) as resp:
            assert resp.status_code == 200
            for line in resp.iter_lines():
                if line.startswith("event: "):
                    current_event[0] = line[len("event: "):]
                    continue
                if not line.startswith("data: "):
                    continue
                payload = json.loads(line[len("data: "):])
                events.append((current_event[0], payload))
                current_event[0] = ""
                if payload.get("done"):
                    break
                tokens.append(payload["token"])
        assert tokens  # generation genuinely started
        name, final = events[-1]
        assert name == "error"  # a TYPED terminal event, not a bare drop
        assert final["done"] is True
        assert final["request_id"] == "sse-death-1"
        assert final["reason"] == "engine_failed"
        assert "error" in final
    finally:
        handle.stop()


def test_sse_completion_has_no_error_event(tmp_path):
    """Control: a stream that completes normally ends with the plain
    ``data:`` final event — no ``event: error`` framing anywhere."""
    server = _build_llm_server(tmp_path, budget=0)
    _SHED_PORT[0] += 1
    handle = _HttpHandle(server, _SHED_PORT[0])
    try:
        lines = []
        with httpx.stream(
            "POST",
            handle.base + "/v2/models/llm/generate",
            json={"prompt_ids": [5, 9, 2], "max_new_tokens": 4,
                  "stream": True},
            timeout=120,
        ) as resp:
            assert resp.status_code == 200
            for line in resp.iter_lines():
                lines.append(line)
                if line.startswith("data: ") and json.loads(
                    line[len("data: "):]
                ).get("done"):
                    break
        assert not any(ln.startswith("event: ") for ln in lines)
        final = json.loads(lines[-1][len("data: "):])
        assert final["done"] is True and "output_ids" in final
    finally:
        handle.stop()


def test_poison_quarantine_http_422_contract(tmp_path):
    """The HTTP shape of the quarantine: two admission crashes (500s),
    then the SAME prompt gets a typed 422 {reason: poison_quarantined}
    with the fingerprint, while other prompts keep serving 200 — and the
    poison counters move."""
    server = _build_llm_server(tmp_path, budget=0)
    _SHED_PORT[0] += 1
    handle = _HttpHandle(server, _SHED_PORT[0])
    eng = server.gen_engine
    try:
        real_admit = eng._dispatch_admit
        crashes = [0]

        def crashing_admit(*a, **kw):
            if crashes[0] < 2:
                crashes[0] += 1
                raise RuntimeError("injected admission crash")
            return real_admit(*a, **kw)

        eng._dispatch_admit = crashing_admit
        body = {"prompt_ids": [7, 7, 7, 7], "max_new_tokens": 3}
        for _ in range(2):
            r = httpx.post(
                handle.base + "/v2/models/llm/generate", json=body,
                timeout=120,
            )
            assert r.status_code == 500  # the crash itself: a plain 500
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and (
            eng.poison_quarantined_total < 1
        ):
            time.sleep(0.02)
        r = httpx.post(
            handle.base + "/v2/models/llm/generate", json=body, timeout=30
        )
        assert r.status_code == 422, r.text
        payload = r.json()
        assert payload["reason"] == "poison_quarantined"
        assert payload["crashes"] == 2
        assert len(payload["fingerprint"]) == 16
        assert "Retry-After" not in r.headers  # unprocessable EVERYWHERE
        # Innocent prompts serve normally on the recovered engine.
        ok = httpx.post(
            handle.base + "/v2/models/llm/generate",
            json={"prompt_ids": [5, 9, 2], "max_new_tokens": 2},
            timeout=120,
        )
        assert ok.status_code == 200, ok.text
        metrics = httpx.get(handle.base + "/metrics", timeout=10).text
        assert "tpumlops_engine_poison_quarantined_total" in metrics
        q = [
            ln for ln in metrics.splitlines()
            if ln.startswith("tpumlops_engine_poison_quarantined_total{")
        ]
        rj = [
            ln for ln in metrics.splitlines()
            if ln.startswith("tpumlops_engine_poison_rejected_total{")
        ]
        assert float(q[0].rsplit(" ", 1)[1]) == 1.0
        assert float(rj[0].rsplit(" ", 1)[1]) == 1.0
    finally:
        handle.stop()


def test_typed_error_bodies_carry_request_id(shed_server):
    """Every typed error BODY carries the request id (the trace-plane
    audit): a client stack that drops headers on error paths must still
    be able to correlate the shed/refusal with the router journey and
    the server's completion log line."""
    eng = shed_server.server.gen_engine
    futs = _saturate(eng)
    try:
        # 429 shed.
        resp = httpx.post(
            shed_server.base + "/v2/models/llm/generate",
            json={"prompt_ids": [5, 9, 2, 7], "max_new_tokens": 56},
            headers={"X-Request-Id": "shed-rid-1"},
            timeout=30,
        )
        assert resp.status_code == 429
        assert resp.json()["request_id"] == "shed-rid-1"
        assert resp.headers["X-Request-Id"] == "shed-rid-1"
    finally:
        for f in futs:
            f.result(timeout=120)
    # 400 (unknown generate parameter).
    resp = httpx.post(
        shed_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [5, 9, 2], "max_new_token": 4},
        headers={"X-Request-Id": "bad-param-1"},
        timeout=30,
    )
    assert resp.status_code == 400
    assert resp.json()["request_id"] == "bad-param-1"
    assert resp.headers["X-Request-Id"] == "bad-param-1"
    # The id joins the W3C context when a traceparent rides along: the
    # engine trace adopts trace id + parent span (stitching contract).
    tp = "00-" + "ef" * 16 + "-" + "12" * 8 + "-01"
    ok = httpx.post(
        shed_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [5, 9, 2], "max_new_tokens": 2, "debug": True},
        headers={"X-Request-Id": "traced-1", "traceparent": tp},
        timeout=60,
    )
    assert ok.status_code == 200
    timing = ok.json()["timing"]["rows"][0]
    assert timing["trace_id"] == "ef" * 16
    assert timing["parent_span"] == "12" * 8
    # Without a traceparent the block stays byte-for-byte (no keys).
    ok = httpx.post(
        shed_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [5, 9, 2], "max_new_tokens": 2, "debug": True},
        timeout=60,
    )
    assert "trace_id" not in ok.json()["timing"]["rows"][0]
