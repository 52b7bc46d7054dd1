"""Flight recorder: ring bounding, snapshot shape, Chrome trace validity.

The fast tests drive :class:`FlightRecorder` directly (no JAX, no
server); the slow tranche brings up the real server with
``spec.tpu.observability.traceRing`` set and asserts the
``/debug/engine`` + ``/debug/trace?format=chrome`` contract end-to-end —
the exported JSON must parse, every request async-span must begin/end
paired, and every per-token instant must fall inside its request span.
"""

import json
import time

import numpy as np
import pytest

from tpumlops.server.flight_recorder import FlightRecorder, RequestTrace


def _chrome_invariants(doc: dict) -> None:
    """The invariant set every Chrome trace export must satisfy (shared
    by the unit test and the live-server test)."""
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    for e in events:
        assert isinstance(e["ph"], str)
        assert isinstance(e["ts"], int) if "ts" in e else True
        assert e.get("pid") == 1 or e["ph"] == "M"
    # Complete events: engine ticks on tid 0, or a relayed request's
    # kv-handoff span on its row track. Non-negative durations on both.
    ticks = [e for e in events if e["ph"] == "X"]
    for t in ticks:
        assert t["dur"] >= 0
        assert t["cat"] in ("tick", "handoff")
        if t["cat"] == "tick":
            assert t["tid"] == 0
        else:
            assert t["name"] == "kv-handoff"
    # Async request spans: every begin pairs with exactly one end of the
    # same id, end never precedes begin, and both sit on the same track.
    begins = {e["id"]: e for e in events if e["ph"] == "b"}
    ends = {e["id"]: e for e in events if e["ph"] == "e"}
    assert set(begins) == set(ends)
    assert len([e for e in events if e["ph"] == "b"]) == len(begins)
    for rid, b in begins.items():
        e = ends[rid]
        assert e["ts"] >= b["ts"], rid
        assert e["tid"] == b["tid"], rid
        assert e["cat"] == b["cat"] == "request"
    # Token instants nest inside their request's span.
    for tok in (e for e in events if e.get("cat") == "token"):
        rid = tok["args"]["request_id"]
        assert begins[rid]["ts"] <= tok["ts"] <= ends[rid]["ts"]


def test_rings_are_bounded_and_totals_keep_counting():
    rec = FlightRecorder(capacity=8)
    t0 = time.perf_counter()
    for i in range(50):
        rec.tick("decode", t0, 0.001, active_slots=2, tokens=2)
        rec.event(f"r{i}", "enqueued")
    snap = rec.snapshot()
    assert len(snap["ticks"]) == 8
    assert len(snap["events"]) == 8
    assert snap["ticks_recorded"] == 50
    assert snap["events_recorded"] == 50
    # The ring keeps the TAIL (most recent) records.
    assert snap["events"][-1]["request_id"] == "r49"
    assert snap["capacity"] == 8


def test_recorder_rejects_nonpositive_capacity():
    with pytest.raises(ValueError):
        FlightRecorder(0)


def test_request_trace_timing_block_math():
    tr = RequestTrace(request_id="abc", prompt_tokens=7)
    base = time.perf_counter()
    tr.t_submit = base
    tr.t_admit = base + 0.010
    tr.t_first = base + 0.025
    tr.note_token(base + 0.025)
    tr.note_token(base + 0.030)
    tr.finish("eos", t=base + 0.030)
    tr.finish("cancelled")  # first writer wins
    block = tr.timing_block()
    assert block["queue_ms"] == pytest.approx(10.0, abs=0.01)
    assert block["ttft_ms"] == pytest.approx(25.0, abs=0.01)
    assert block["total_ms"] == pytest.approx(30.0, abs=0.01)
    assert block["tokens"] == 2
    assert block["finish_reason"] == "eos"
    # Unset endpoints report None, never a negative delta.
    assert RequestTrace("x").timing_block()["ttft_ms"] is None


def test_kv_import_tick_and_handoff_stamps():
    """Disaggregated-fleet relay reconstruction: the ``kv-import`` tick
    kind journals like any engine tick, and a relayed request's trace
    carries the router-measured handoff wall in its timing block."""
    rec = FlightRecorder(capacity=16)
    t0 = time.perf_counter()
    rec.tick("kv-import", t0, 0.002, batch_fill=2, tokens=16)
    snap = rec.snapshot()
    tick = snap["ticks"][-1]
    assert tick["kind"] == "kv-import"
    assert tick["batch_fill"] == 2 and tick["tokens"] == 16
    assert "steps" not in tick  # not a fused tick: record shape unchanged

    tr = RequestTrace(request_id="relay-1")
    tr.t_submit = t0
    tr.t_handoff = t0 - 0.005
    tr.handoff_ms = 12.5
    tr.finish("length", t=t0 + 0.1)
    assert tr.timing_block()["handoff_ms"] == 12.5
    # Non-relayed requests carry None — the key exists, the value says
    # "no handoff", and old assertions on other fields are untouched.
    assert RequestTrace("x").timing_block()["handoff_ms"] is None
    # The chrome export renders the kv-import tick on the engine track.
    rec.complete(tr)
    doc = rec.chrome_trace()
    kinds = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "kv-import" in kinds
    # ...and the receipt stamp anchors the router-measured handoff as a
    # span on the request's track, ending at t_handoff.
    spans = [
        e
        for e in doc["traceEvents"]
        if e["ph"] == "X" and e["name"] == "kv-handoff"
    ]
    assert len(spans) == 1
    assert spans[0]["dur"] == 12500
    assert spans[0]["args"]["request_id"] == "relay-1"
    _chrome_invariants(doc)


def test_chrome_trace_is_valid_and_spans_pair_up():
    rec = FlightRecorder(capacity=64)
    base = time.perf_counter()
    for i in range(5):
        rec.tick(
            "decode", base + i * 0.01, 0.005, active_slots=2, tokens=2
        )
    rec.tick("packed-prefill", base + 0.06, 0.02, batch_fill=4, tokens=1)
    for i, reason in enumerate(["length", "eos", "cancelled"]):
        tr = RequestTrace(request_id=f"req-{i}", prompt_tokens=4, slot=i)
        tr.t_submit = base + i * 0.001
        tr.t_admit = tr.t_submit + 0.002
        tr.t_first = tr.t_admit + 0.003
        tr.note_token(tr.t_first)
        tr.note_token(tr.t_first + 0.004)
        tr.finish(reason, t=tr.t_first + 0.004)
        rec.event(tr.request_id, "first_token", slot=i)
        rec.complete(tr)
    # Round-trip through real JSON: the endpoint serves exactly this.
    doc = json.loads(json.dumps(rec.chrome_trace()))
    _chrome_invariants(doc)
    # One track per cache row used, named by row.
    names = {
        e["args"]["name"]
        for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert {"engine ticks", "cache row 0", "cache row 2"} <= names
    kinds = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert kinds == {"decode", "packed-prefill"}


def test_tick_steps_field_only_on_multistep_records():
    # Fused multi-step ticks carry "steps" (K scan iterations under the
    # one dispatch); every other kind's record stays byte-for-byte the
    # pre-fused shape — no new key.
    rec = FlightRecorder(capacity=8)
    rec.tick("decode", time.perf_counter(), 0.001, tokens=1)
    rec.tick("multistep", time.perf_counter(), 0.004, tokens=7, steps=4)
    ticks = rec.snapshot()["ticks"]
    assert "steps" not in ticks[0]
    assert ticks[1]["steps"] == 4 and ticks[1]["tokens"] == 7
    doc = json.loads(json.dumps(rec.chrome_trace()))
    by_kind = {
        e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"
    }
    assert by_kind["multistep"]["args"]["steps"] == 4
    assert "steps" not in by_kind["decode"]["args"]


def test_tick_roles_field_only_on_superstep_records():
    # Unified super-step ticks carry "roles" (the per-dispatch
    # {prefill, decode, verify} row mix); every other kind's record
    # stays byte-for-byte the pre-unified shape — no new key.
    rec = FlightRecorder(capacity=8)
    rec.tick("decode", time.perf_counter(), 0.001, tokens=1)
    rec.tick("multistep", time.perf_counter(), 0.004, tokens=7, steps=4)
    rec.tick(
        "superstep", time.perf_counter(), 0.005, tokens=9, steps=4,
        roles={"prefill": 1, "decode": 2, "verify": 1},
    )
    ticks = rec.snapshot()["ticks"]
    assert "roles" not in ticks[0] and "roles" not in ticks[1]
    assert ticks[2]["roles"] == {"prefill": 1, "decode": 2, "verify": 1}
    assert ticks[2]["steps"] == 4


def test_chrome_trace_role_fill_counter_tracks():
    # Perfetto export: superstep ticks emit a "role_fill" counter event
    # (one series per role) next to the tick track; exports holding no
    # superstep ticks stay byte-for-byte free of the counter.
    rec = FlightRecorder(capacity=8)
    rec.tick("decode", time.perf_counter(), 0.001, tokens=1)
    doc = json.loads(json.dumps(rec.chrome_trace()))
    assert not [
        e for e in doc["traceEvents"] if e.get("name") == "role_fill"
    ]
    rec.tick(
        "superstep", time.perf_counter(), 0.005, tokens=9, steps=4,
        roles={"prefill": 2, "decode": 1, "verify": 0},
    )
    doc = json.loads(json.dumps(rec.chrome_trace()))
    _chrome_invariants(doc)
    counters = [
        e for e in doc["traceEvents"] if e.get("name") == "role_fill"
    ]
    assert len(counters) == 1
    c = counters[0]
    assert c["ph"] == "C" and c["cat"] == "roles"
    assert c["args"] == {"prefill": 2, "decode": 1, "verify": 0}
    # The tick's X event carries the same breakdown in its args.
    sup = [
        e for e in doc["traceEvents"]
        if e["ph"] == "X" and e["name"] == "superstep"
    ]
    assert sup and sup[0]["args"]["roles"] == {
        "prefill": 2, "decode": 1, "verify": 0,
    }


@pytest.mark.slow
def test_multistep_tick_reconstructs_per_token_timestamps():
    """Multi-token fused ticks must not corrupt ITL/tick accounting: the
    K tokens of one dispatch get timestamps spaced across the tick wall
    (never all on the harvest instant, never non-monotonic), the tick
    record carries kind="multistep" with steps=K and the real token
    count, and the Perfetto export keeps the instants distinct."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama
    from tpumlops.server.generation import GenerationEngine

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(0), cfg, dtype=jnp.float32)
    rec = FlightRecorder(capacity=256)
    itls: list = []
    K = 4
    engine = GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float32, decode_steps=K,
        recorder=rec, on_itl=itls.append,
    )
    engine.start(warmup=True)
    try:
        trace = RequestTrace(request_id="ms-1")
        out = engine.submit(
            [5, 9, 2], 17, request_id="ms-1", trace=trace
        ).result(timeout=300)
        assert len(out) == 17
    finally:
        engine.shutdown()
    snap = rec.snapshot()
    ms = [t for t in snap["ticks"] if t["kind"] == "multistep"]
    assert ms, "no fused tick recorded"
    for t in ms:
        assert t["steps"] == K
        assert 1 <= t["tokens"] <= K
        assert t["active_slots"] == 1
    # 16 decode-emitted tokens in ceil(16/4)=4 fused dispatches.
    assert len(ms) == 4
    # Per-token instants: strictly increasing, spread across tick walls
    # (reconstruction), never stacked on one harvest read.
    times = trace.token_times
    assert len(times) == 17
    deltas = np.diff(times)
    assert (deltas > 0).all(), "token timestamps must be monotone"
    # ITL observations mirror the reconstructed spacing: all positive,
    # and more than one distinct value would appear even within a
    # single fused tick only by reconstruction.
    assert len(itls) == 16 and all(d > 0 for d in itls)
    doc = json.loads(json.dumps(rec.chrome_trace()))
    toks = [
        e["ts"] for e in doc["traceEvents"]
        if e["ph"] == "i" and e["name"] == "token"
    ]
    assert len(set(toks)) == len(toks), "token instants must be distinct"


def test_snapshot_is_json_serializable_and_isolated():
    rec = FlightRecorder(capacity=4)
    rec.tick("decode", time.perf_counter(), 0.001)
    snap = json.loads(json.dumps(rec.snapshot()))
    snap["ticks"][0]["kind"] = "mutated"
    assert rec.snapshot()["ticks"][0]["kind"] == "decode"


# ---------------------------------------------------------------------------
# Live server: /debug/engine + /debug/trace through real HTTP
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_llm_server(tmp_path_factory, cpu_peaks):
    import jax

    from tpumlops.models import llama
    from tpumlops.server.app import build_server
    from tpumlops.server.loader import save_native_model
    from tpumlops.utils.config import ServerConfig, TpuSpec

    from test_server import serve

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(3), cfg)
    art = tmp_path_factory.mktemp("artifacts") / "llm-traced"
    save_native_model(
        art,
        "llama-generate",
        params,
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
    config = ServerConfig(
        model_name="llm",
        model_uri=str(art),
        predictor_name="v1",
        deployment_name="llm",
        namespace="models",
        tpu=TpuSpec.from_spec(
            {
                "meshShape": {"tp": 1},
                "maxBatchSize": 4,
                "prefillChunk": 16,
                # deviceTelemetry ON: this fixture doubles as the e2e
                # for the HBM ledger / per-tick MFU / Perfetto counter
                # track (speculative gives the verify tick kind).
                "observability": {"traceRing": 512, "deviceTelemetry": True},
                "speculative": {"enabled": True},
            }
        ),
    )
    server = build_server(config, peaks=cpu_peaks)
    handle = serve(server)
    yield handle
    handle.stop()


@pytest.mark.slow
def test_debug_engine_snapshot_over_http(traced_llm_server):
    import httpx

    resp = httpx.post(
        traced_llm_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [5, 9, 2], "max_new_tokens": 5},
        headers={"X-Request-Id": "snap-req"},
        timeout=60,
    )
    assert resp.status_code == 200, resp.text
    snap = httpx.get(
        traced_llm_server.base + "/debug/engine", timeout=10
    ).json()
    assert snap["ticks_recorded"] > 0
    kinds = {t["kind"] for t in snap["ticks"]}
    assert "decode" in kinds and "prefill" in kinds
    done = [r for r in snap["requests"] if r["request_id"] == "snap-req"]
    assert done and done[0]["tokens"] == 5
    assert done[0]["finish_reason"] == "length"
    # prefillChunk 16 over a 3-token prompt: one chunk, then the insert.
    assert done[0]["prefill_chunks"] == 1
    names = {e["event"] for e in snap["events"]}
    assert {"enqueued", "admission", "first_token", "finish"} <= names


@pytest.mark.slow
def test_debug_trace_chrome_export_over_http(traced_llm_server):
    import httpx

    for i in range(3):
        r = httpx.post(
            traced_llm_server.base + "/v2/models/llm/generate",
            json={"prompt_ids": [7, 1, 4, 8], "max_new_tokens": 4},
            headers={"X-Request-Id": f"perfetto-{i}"},
            timeout=60,
        )
        assert r.status_code == 200, r.text
    raw = httpx.get(
        traced_llm_server.base + "/debug/trace?format=chrome", timeout=10
    )
    assert raw.status_code == 200
    doc = json.loads(raw.text)  # the acceptance bar: valid JSON
    _chrome_invariants(doc)
    span_ids = {e["id"] for e in doc["traceEvents"] if e["ph"] == "b"}
    assert {"perfetto-0", "perfetto-1", "perfetto-2"} <= span_ids
    # Unknown format 400s with the valid set named.
    bad = httpx.get(
        traced_llm_server.base + "/debug/trace?format=pprof", timeout=10
    )
    assert bad.status_code == 400
    assert "chrome" in bad.json()["error"]


@pytest.mark.slow
def test_debug_device_and_utilization_over_http(traced_llm_server):
    """Device telemetry e2e: the analytic HBM ledger agrees with
    ``device.memory_stats()`` where available, per-tick MFU lands in
    (0, 1] for the decode / verify / prefill tick kinds, and the
    Perfetto export carries the utilization counter track."""
    import httpx

    # All-same-token prompt: the n-gram drafter matches on the first
    # decode tick, so a verify tick is guaranteed to be journaled.
    r = httpx.post(
        traced_llm_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [7] * 8, "max_new_tokens": 24},
        headers={"X-Request-Id": "devtel-req"},
        timeout=60,
    )
    assert r.status_code == 200, r.text

    dev = httpx.get(
        traced_llm_server.base + "/debug/device", timeout=10
    ).json()
    hbm = dev["hbm"]
    assert hbm["device_total_bytes"] > 0
    assert hbm["components"]["kv_cache"] > 0
    assert any(k.startswith("weights_") for k in hbm["components"])
    assert hbm["kv_bytes_per_row"] > 0 and hbm["max_cache_rows"] > 0
    # The cross-check arms itself where the platform reports memory
    # (TPU/GPU); the CPU dev environment reports None.
    if hbm.get("ledger_vs_measured_pct") is not None:
        assert abs(hbm["ledger_vs_measured_pct"]) <= 10.0, hbm
    assert dev["compile"]["ops"], dev["compile"]
    assert dev["peaks"]["flops_per_s"] > 0

    snap = httpx.get(
        traced_llm_server.base + "/debug/engine", timeout=10
    ).json()
    by_kind: dict = {}
    for t in snap["ticks"]:
        if "mfu" in t:
            by_kind.setdefault(t["kind"], t)
    assert {"decode", "verify", "prefill"} <= set(by_kind), sorted(by_kind)
    for kind, t in by_kind.items():
        assert 0.0 < t["mfu"] <= 1.0, (kind, t)
        assert 0.0 < t["hbm_bw_util"] <= 1.0, (kind, t)

    doc = json.loads(
        httpx.get(
            traced_llm_server.base + "/debug/trace?format=chrome", timeout=10
        ).text
    )
    _chrome_invariants(doc)
    counters = {e["name"] for e in doc["traceEvents"] if e["ph"] == "C"}
    assert {"mfu", "hbm_bw_util"} <= counters


@pytest.mark.slow
def test_debug_trace_404_when_recorder_disabled(tmp_path_factory):
    """The default (traceRing 0) serves 404 with the enabling knob named
    — and the recorder attribute is None, so the engine path carries no
    journaling branch work at all."""
    import httpx
    from sklearn.datasets import load_iris
    from sklearn.linear_model import LogisticRegression

    from tpumlops.server.app import build_server
    from tpumlops.server.loader import save_sklearn_model
    from tpumlops.utils.config import ServerConfig, TpuSpec

    from test_server import serve

    X, y = load_iris(return_X_y=True)
    sk = LogisticRegression(max_iter=200).fit(X, y)
    art = tmp_path_factory.mktemp("artifacts") / "iris-plain"
    save_sklearn_model(art, sk, "sklearn-linear")
    server = build_server(
        ServerConfig(
            model_name="iris",
            model_uri=str(art),
            tpu=TpuSpec.from_spec({"meshShape": {"tp": 1}, "maxBatchSize": 4}),
        )
    )
    handle = serve(server)
    try:
        assert server.recorder is None
        for path in ("/debug/engine", "/debug/trace?format=chrome"):
            resp = httpx.get(handle.base + path, timeout=10)
            assert resp.status_code == 404
            assert "traceRing" in resp.json()["error"]
        # Device telemetry is off by default too, with its own knob named.
        assert server.telemetry is None
        resp = httpx.get(handle.base + "/debug/device", timeout=10)
        assert resp.status_code == 404
        assert "deviceTelemetry" in resp.json()["error"]
    finally:
        handle.stop()
