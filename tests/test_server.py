"""End-to-end data-plane tests: artifact -> loader -> engine -> HTTP."""

import asyncio
import json
import threading
import time

import httpx
import numpy as np
import pytest
from aiohttp import web

from tpumlops.server.app import TpuInferenceServer, build_server
from tpumlops.server.engine import InferenceEngine
from tpumlops.server.loader import (
    ModelLoadError,
    load_predictor,
    resolve_uri,
    save_native_model,
    save_sklearn_model,
)
from tpumlops.utils.config import ServerConfig, TpuSpec


# ---------------------------------------------------------------------------
# Harness: run an aiohttp app in a background thread, talk httpx to it.
# ---------------------------------------------------------------------------


class ServerHandle:
    def __init__(self, server: TpuInferenceServer, port: int):
        self.server = server
        self.port = port
        self.base = f"http://127.0.0.1:{port}"
        self._loop = asyncio.new_event_loop()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        asyncio.set_event_loop(self._loop)
        self._runner = web.AppRunner(self.server.build_app())
        self._loop.run_until_complete(self._runner.setup())
        site = web.TCPSite(self._runner, "127.0.0.1", self.port)
        self._loop.run_until_complete(site.start())
        self._loop.run_forever()

    def start(self):
        self._thread.start()
        for _ in range(100):
            try:
                httpx.get(self.base + "/v2/health/live", timeout=0.5)
                return self
            except Exception:
                time.sleep(0.05)
        raise RuntimeError("server did not come up")

    def stop(self):
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=5)
        self.server.shutdown()


_PORT = [19300]


def serve(server: TpuInferenceServer) -> ServerHandle:
    _PORT[0] += 1
    return ServerHandle(server, _PORT[0]).start()


@pytest.fixture(scope="module")
def iris_server(tmp_path_factory):
    from sklearn.datasets import load_iris
    from sklearn.linear_model import LogisticRegression

    X, y = load_iris(return_X_y=True)
    sk = LogisticRegression(max_iter=500).fit(X, y)
    art = tmp_path_factory.mktemp("artifacts") / "iris"
    save_sklearn_model(art, sk, "sklearn-linear")

    config = ServerConfig(
        model_name="iris",
        model_uri=str(art),
        predictor_name="v1",
        deployment_name="iris",
        namespace="models",
        tpu=TpuSpec.from_spec({"meshShape": {"tp": 1}, "maxBatchSize": 8, "maxBatchDelayMs": 2}),
    )
    server = build_server(config)
    handle = serve(server)
    yield handle, sk, X, y
    handle.stop()


# ---------------------------------------------------------------------------
# V2 protocol
# ---------------------------------------------------------------------------


def test_v2_single_infer_matches_sklearn(iris_server):
    handle, sk, X, y = iris_server
    row = X[7]
    resp = httpx.post(
        handle.base + "/v2/models/iris/infer",
        json={
            "inputs": [
                {
                    "name": "x",
                    "shape": [1, 4],
                    "datatype": "FP32",
                    "data": [float(v) for v in row],
                }
            ]
        },
        timeout=30,
    )
    assert resp.status_code == 200, resp.text
    out = resp.json()["outputs"][0]
    assert out["shape"] == [1]
    assert out["data"][0] == int(sk.predict(row[None])[0])


def test_v2_client_batched_infer(iris_server):
    handle, sk, X, y = iris_server
    batch = X[:12]
    resp = httpx.post(
        handle.base + "/v2/models/iris/infer",
        json={
            "inputs": [
                {
                    "name": "x",
                    "shape": [12, 4],
                    "datatype": "FP32",
                    "data": [float(v) for v in batch.ravel()],
                }
            ]
        },
        timeout=30,
    )
    assert resp.status_code == 200
    out = resp.json()["outputs"][0]
    np.testing.assert_array_equal(out["data"], sk.predict(batch))


def test_concurrent_singles_are_batched(iris_server):
    handle, sk, X, y = iris_server

    def one(i):
        return httpx.post(
            handle.base + "/v2/models/iris/infer",
            json={
                "inputs": [
                    {
                        "name": "x",
                        "shape": [1, 4],
                        "datatype": "FP32",
                        "data": [float(v) for v in X[i]],
                    }
                ]
            },
            timeout=30,
        )

    threads_out = [None] * 16

    def worker(i):
        threads_out[i] = one(i)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    preds = [r.json()["outputs"][0]["data"][0] for r in threads_out]
    np.testing.assert_array_equal(preds, sk.predict(X[:16]))
    # The dynamic batcher should have produced at least one multi-example batch.
    metrics_text = httpx.get(handle.base + "/metrics").text
    assert "tpumlops_batch_size_bucket" in metrics_text


def test_seldon_protocol_compat(iris_server):
    handle, sk, X, y = iris_server
    resp = httpx.post(
        handle.base + "/api/v1.0/predictions",
        json={"data": {"ndarray": [[float(v) for v in X[3]]]}},
        timeout=30,
    )
    assert resp.status_code == 200
    assert resp.json()["data"]["ndarray"][0] == int(sk.predict(X[3][None])[0])


def test_feedback_endpoint_counts_under_feedback_service(iris_server):
    """The reference counts feedback posts via service="feedback"
    (mlflow_operator.py:410-415) — in its stack Seldon's executor serves
    the route; here the first-party server must (VERDICT r3 missing #2).
    Feedback must count WITHOUT polluting the latency histogram the gate's
    p95/mean queries read."""
    import re

    handle, *_ = iris_server

    def client_count() -> float:
        text = httpx.get(handle.base + "/metrics").text
        m = re.search(
            r"seldon_api_executor_client_requests_seconds_count{[^}]*} "
            r"([0-9.e+-]+)",
            text,
        )
        return float(m.group(1)) if m else 0.0

    def feedback_count() -> float:
        text = httpx.get(handle.base + "/metrics").text
        total = 0.0
        for m in re.finditer(
            r"seldon_api_executor_server_requests_seconds_count"
            r"{([^}]*)} ([0-9.e+-]+)",
            text,
        ):
            if 'service="feedback"' in m.group(1):
                total += float(m.group(2))
        return total

    lat_before, fb_before = client_count(), feedback_count()
    resp = httpx.post(
        handle.base + "/api/v1.0/feedback",
        json={"reward": 1.0, "response": {"data": {"ndarray": [[0]]}}},
        timeout=30,
    )
    assert resp.status_code == 200
    assert feedback_count() == fb_before + 1
    assert client_count() == lat_before  # latency gate series untouched
    text = httpx.get(handle.base + "/metrics").text
    assert "tpumlops_feedback_reward_total" in text

    # Malformed reward is a 400 — still under service="feedback".
    resp = httpx.post(
        handle.base + "/api/v1.0/feedback",
        json={"reward": "five stars"},
        timeout=30,
    )
    assert resp.status_code == 400
    assert feedback_count() == fb_before + 2


def test_gate_compatible_metrics_identity(iris_server):
    handle, *_ = iris_server
    text = httpx.get(handle.base + "/metrics").text
    # Exactly the series + labels the promotion gate queries
    # (mlflow_operator.py:367,:375).
    assert 'seldon_api_executor_client_requests_seconds_bucket{' in text
    assert 'deployment_name="iris"' in text
    assert 'predictor_name="v1"' in text
    assert 'namespace="models"' in text
    # The gate reads the _count series of a histogram (mlflow_operator.py:375);
    # a Counter would export _total and the error queries would read 0.
    assert 'seldon_api_executor_server_requests_seconds_count{' in text
    assert 'seldon_api_executor_server_requests_seconds_sum{' in text
    assert 'code="200"' in text


def test_bad_request_400_and_error_metric(iris_server):
    handle, *_ = iris_server
    resp = httpx.post(
        handle.base + "/v2/models/iris/infer",
        json={"inputs": [{"name": "x", "shape": [1, 4], "datatype": "NOPE", "data": [1, 2, 3, 4]}]},
        timeout=30,
    )
    assert resp.status_code == 400
    text = httpx.get(handle.base + "/metrics").text
    assert 'code="400"' in text


def test_health_and_metadata(iris_server):
    handle, *_ = iris_server
    assert httpx.get(handle.base + "/v2/health/live").status_code == 200
    assert httpx.get(handle.base + "/v2/health/ready").status_code == 200
    meta = httpx.get(handle.base + "/v2/models/iris").json()
    assert meta["flavor"] == "sklearn-linear"
    assert meta["jittable"] is True


# ---------------------------------------------------------------------------
# Native artifacts + loader
# ---------------------------------------------------------------------------


def test_native_bert_artifact_roundtrip(tmp_path):
    import jax

    from tpumlops.models import bert

    cfg = bert.BertConfig.tiny()
    params = bert.init(jax.random.key(0), cfg)
    art = tmp_path / "bert"
    save_native_model(
        art,
        "bert-classifier",
        params,
        config={
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.hidden_size,
            "num_layers": cfg.num_layers,
            "num_heads": cfg.num_heads,
            "intermediate_size": cfg.intermediate_size,
            "max_position_embeddings": cfg.max_position_embeddings,
        },
        builder_kwargs={"seq_len": 16},
    )
    pred = load_predictor(str(art))
    engine = InferenceEngine(pred, max_batch_size=4)
    engine.warmup([1, 2])
    ex = pred.example_input(2)
    out = engine.predict(ex)
    assert np.asarray(out).shape == (2, cfg.num_labels)


def test_capacity_log_line_on_causal_lm_load(tmp_path, caplog):
    """Every causal-LM load stamps ONE model-capacity line (weights
    bytes by dtype, KV bytes/row, max cache rows) — telemetry off or
    on; the deviceTelemetry layer only adds the live /debug/device
    view on top of it."""
    import logging

    import jax

    from tpumlops.models import llama

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(0), cfg)
    art = tmp_path / "llama-cap"
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
    with caplog.at_level(logging.INFO, logger="tpumlops.capacity"):
        load_predictor(str(art))
    lines = [
        r.getMessage() for r in caplog.records if r.name == "tpumlops.capacity"
    ]
    assert len(lines) == 1, lines
    line = lines[0]
    assert line.startswith("model capacity: weights ")
    assert "B/row" in line and "max cache rows" in line

    # Non-causal artifacts emit no capacity line (there is no KV cache
    # to plan against).
    from sklearn.linear_model import LogisticRegression

    sk = LogisticRegression(max_iter=50).fit([[0.0], [1.0]], [0, 1])
    sk_art = tmp_path / "sk-cap"
    save_sklearn_model(sk_art, sk, "sklearn-linear")
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="tpumlops.capacity"):
        load_predictor(str(sk_art))
    assert not [
        r for r in caplog.records if r.name == "tpumlops.capacity"
    ]


def test_native_artifact_with_tp_mesh(tmp_path):
    import jax

    from tpumlops.models import llama

    cfg = llama.LlamaConfig.tiny(num_kv_heads=4)
    params = llama.init(jax.random.key(0), cfg)
    art = tmp_path / "llama"
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
        builder_kwargs={"max_new_tokens": 4},
    )
    pred = load_predictor(str(art), mesh_shape={"dp": 2, "tp": 4})
    out = pred.predict(np.ones((2, 8), np.int32))
    assert np.asarray(out).shape == (2, 4)


def test_loader_mirror_resolution(tmp_path, monkeypatch):
    (tmp_path / "mlflow" / "1" / "m").mkdir(parents=True)
    monkeypatch.setenv("TPUMLOPS_ARTIFACT_MIRROR", str(tmp_path))
    p = resolve_uri("s3://mlflow/1/m")
    assert p == tmp_path / "mlflow" / "1" / "m"


def test_loader_s3_without_mirror_is_loud(monkeypatch):
    monkeypatch.delenv("TPUMLOPS_ARTIFACT_MIRROR", raising=False)
    with pytest.raises(ModelLoadError, match="TPUMLOPS_ARTIFACT_MIRROR"):
        resolve_uri("s3://mlflow/1/m")


def test_loader_sniffs_forest_flavor(tmp_path):
    from sklearn.datasets import make_regression
    from sklearn.ensemble import RandomForestRegressor

    X, y = make_regression(n_samples=50, n_features=4, random_state=0)
    sk = RandomForestRegressor(n_estimators=5, max_depth=4, random_state=0).fit(X, y)
    art = tmp_path / "forest"
    save_sklearn_model(art, sk, "sklearn-forest")
    pred = load_predictor(str(art))
    assert pred.name == "sklearn-forest"
    out = np.asarray(pred.predict(np.asarray(X[:8], np.float32)))
    np.testing.assert_allclose(out, sk.predict(X[:8]), rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# /generate endpoint (continuous batching, causal-LM flavors)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def llm_artifact(tmp_path_factory):
    import jax

    from tpumlops.models import llama

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(3), cfg)
    art = tmp_path_factory.mktemp("artifacts") / "llm"
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
    return art


def _llm_server(art, **tpu):
    config = ServerConfig(
        model_name="llm",
        model_uri=str(art),
        predictor_name="v1",
        deployment_name="llm",
        namespace="models",
        tpu=TpuSpec.from_spec(
            {"meshShape": {"tp": 1}, "maxBatchSize": 4, **tpu}),
    )
    return serve(build_server(config))


@pytest.fixture(scope="module")
def llm_server(llm_artifact):
    handle = _llm_server(llm_artifact)
    yield handle
    handle.stop()


@pytest.fixture(scope="module")
def chunked_llm_server(llm_artifact):
    handle = _llm_server(llm_artifact, prefillChunk=8)
    yield handle
    handle.stop()


@pytest.mark.slow
def test_generate_endpoint_simple_form(llm_server):
    resp = httpx.post(
        llm_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [5, 9, 2], "max_new_tokens": 6},
        timeout=60,
    )
    assert resp.status_code == 200, resp.text
    out = resp.json()["outputs"][0]
    assert out["datatype"] == "INT32"
    assert out["shape"] == [6]
    assert len(out["data"]) == 6


@pytest.mark.slow
def test_generate_endpoint_multi_sequence_and_v2_form(llm_server):
    # two sequences in one request, V2 tensor form (zero-padded rows)
    resp = httpx.post(
        llm_server.base + "/v2/models/llm/generate",
        json={
            "inputs": [
                {
                    "name": "prompt_ids",
                    "datatype": "INT32",
                    "shape": [2, 4],
                    "data": [5, 9, 2, 0, 7, 1, 4, 8],
                }
            ],
            "parameters": {"max_new_tokens": 4},
        },
        timeout=60,
    )
    assert resp.status_code == 200, resp.text
    outs = resp.json()["outputs"]
    assert len(outs) == 2
    assert all(len(o["data"]) == 4 for o in outs)


@pytest.mark.slow
def test_generate_unknown_parameter_400s(llm_server):
    """A typo'd generation knob must 400 with the key named, never be
    silently ignored (the request-level mirror of the spec.tpu
    unknown-key audit in utils/config.py)."""
    resp = httpx.post(
        llm_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [5, 9, 2], "max_new_token": 6},  # missing 's'
        timeout=30,
    )
    assert resp.status_code == 400
    assert "max_new_token" in resp.json()["error"]
    assert "max_new_tokens" in resp.json()["error"]  # the allowed set
    # V2 form: typo inside "parameters".
    resp = httpx.post(
        llm_server.base + "/v2/models/llm/generate",
        json={
            "inputs": [
                {
                    "name": "prompt_ids",
                    "datatype": "INT32",
                    "shape": [1, 3],
                    "data": [5, 9, 2],
                }
            ],
            "parameters": {"max_new_tokens": 4, "temprature": 0.5},
        },
        timeout=30,
    )
    assert resp.status_code == 400
    assert "temprature" in resp.json()["error"]


@pytest.mark.slow
def test_generate_endpoint_validation_and_metrics(llm_server):
    resp = httpx.post(
        llm_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": list(range(60)), "max_new_tokens": 30},
        timeout=30,
    )
    assert resp.status_code == 400
    assert "capacity" in resp.json()["error"]
    text = httpx.get(llm_server.base + "/metrics", timeout=10).text
    assert "tpumlops_generated_tokens_total" in text
    assert "# TYPE tpumlops_tick_seconds histogram" in text
    assert "# TYPE tpumlops_device_starved_seconds_total counter" in text


def test_generate_route_absent_for_non_llm(iris_server):
    handle, *_ = iris_server
    resp = httpx.post(
        handle.base + "/v2/models/iris/generate",
        json={"prompt_ids": [1], "max_new_tokens": 2},
        timeout=10,
    )
    assert resp.status_code in (404, 405)


@pytest.mark.slow
def test_generate_v2_lengths_tensor_preserves_zero_tokens(llm_server):
    # Row [5, 0, 9] with lengths=[3]: token 0 is REAL, not padding.
    resp = httpx.post(
        llm_server.base + "/v2/models/llm/generate",
        json={
            "inputs": [
                {"name": "prompt_ids", "datatype": "INT32", "shape": [1, 4],
                 "data": [5, 0, 9, 0]},
                {"name": "lengths", "datatype": "INT32", "shape": [1],
                 "data": [3]},
            ],
            "parameters": {"max_new_tokens": 3},
        },
        timeout=60,
    )
    assert resp.status_code == 200, resp.text
    assert len(resp.json()["outputs"][0]["data"]) == 3


def test_generate_batch_validation_is_atomic(llm_server):
    # Second prompt exceeds capacity -> whole request 400s, and the engine
    # still serves afterwards (first prompt was never admitted).
    resp = httpx.post(
        llm_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [[1, 2, 3], list(range(1, 61))],
              "max_new_tokens": 30},
        timeout=30,
    )
    assert resp.status_code == 400
    ok = httpx.post(
        llm_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [1, 2, 3], "max_new_tokens": 2},
        timeout=60,
    )
    assert ok.status_code == 200


def test_generate_endpoint_sampling_seeded_reproducible(llm_server):
    body = {
        "prompt_ids": [5, 9, 2],
        "max_new_tokens": 6,
        "temperature": 0.8,
        "top_k": 8,
        "top_p": 0.9,
        "seed": 42,
    }
    r1 = httpx.post(llm_server.base + "/v2/models/llm/generate", json=body, timeout=60)
    r2 = httpx.post(llm_server.base + "/v2/models/llm/generate", json=body, timeout=60)
    assert r1.status_code == r2.status_code == 200, r1.text
    assert r1.json()["outputs"][0]["data"] == r2.json()["outputs"][0]["data"]
    bad = dict(body, top_p=0)
    r3 = httpx.post(llm_server.base + "/v2/models/llm/generate", json=bad, timeout=30)
    assert r3.status_code == 400
    assert "top_p" in r3.json()["error"]


def test_generate_batch_same_prompt_seeded_rows_differ(llm_server):
    resp = httpx.post(
        llm_server.base + "/v2/models/llm/generate",
        json={
            "prompt_ids": [[5, 9, 2], [5, 9, 2], [5, 9, 2]],
            "max_new_tokens": 8,
            "temperature": 1.5,
            "seed": 7,
        },
        timeout=60,
    )
    assert resp.status_code == 200, resp.text
    outs = [tuple(o["data"]) for o in resp.json()["outputs"]]
    # Identical prompts in one seeded batch must get distinct streams.
    assert len(set(outs)) > 1


def test_generate_streaming_sse(llm_server):
    # Non-streaming reference (greedy = deterministic).
    ref = httpx.post(
        llm_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [5, 9, 2], "max_new_tokens": 6},
        timeout=60,
    ).json()["outputs"][0]["data"]

    events = []
    with httpx.stream(
        "POST",
        llm_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [5, 9, 2], "max_new_tokens": 6, "stream": True},
        timeout=60,
    ) as resp:
        assert resp.status_code == 200
        assert resp.headers["content-type"].startswith("text/event-stream")
        for line in resp.iter_lines():
            if line.startswith("data: "):
                events.append(json.loads(line[len("data: "):]))
    *toks, final = events
    assert [e["token"] for e in toks] == ref
    assert [e["index"] for e in toks] == list(range(6))
    assert final == {"done": True, "output_ids": ref}


def test_streaming_observes_one_emit_lag_per_token(llm_server):
    """``tpumlops_emit_lag_seconds``: the engine stamps each token as it
    hands it to the event loop, the SSE writer observes once per event
    written; ``tpumlops_prefill_tokens_total`` counts the prompt."""
    def scrape():
        text = httpx.get(llm_server.base + "/metrics", timeout=10).text
        return (_metric_total(text, "tpumlops_emit_lag_seconds_count"),
                _metric_total(text, "tpumlops_emit_lag_seconds_sum"),
                _metric_total(text, "tpumlops_prefill_tokens_total"))

    n0, s0, p0 = scrape()
    with httpx.stream(
        "POST",
        llm_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [5, 9, 2, 7], "max_new_tokens": 5, "stream": True},
        timeout=60,
    ) as resp:
        assert resp.status_code == 200
        events = [ln for ln in resp.iter_lines() if ln.startswith("data: ")]
    assert len(events) == 6  # five tokens and the final event
    n1, s1, p1 = scrape()
    assert n1 - n0 == 5 and 0.0 <= s1 - s0 < 5.0
    assert p1 - p0 == 4


def test_starvation_account_on_metrics_and_debug_spans(llm_server):
    """``tpumlops_device_starved_*``: when the chip had nothing to run, by
    what it was then given (``before``) and by what the engine thread was
    doing (``span``); the two ``seconds`` families hold the same total, and
    ``GET /debug/spans`` shows the same account beside the span table."""
    def family(text, name, label):
        out = {}
        for ln in text.splitlines():
            if ln.startswith(name + "{"):
                key = ln.split(label + '="', 1)[1].split('"', 1)[0]
                out[key] = float(ln.rsplit(" ", 1)[1])
        return out

    for _ in range(2):  # the second request's first dispatch meets an idle chip
        resp = httpx.post(
            llm_server.base + "/v2/models/llm/generate",
            json={"prompt_ids": [5, 9, 2, 7, 1], "max_new_tokens": 4}, timeout=60,
        )
        assert resp.status_code == 200
    text = httpx.get(llm_server.base + "/metrics", timeout=10).text
    seconds = family(text, "tpumlops_device_starved_seconds_total", "before")
    intervals = family(text, "tpumlops_device_starved_intervals_total", "before")
    by_span = family(text, "tpumlops_device_starved_by_span_seconds_total", "span")
    ticks = family(text, "tpumlops_tick_seconds_count", "kind")
    assert set(seconds) == set(intervals) and "decode" in seconds
    # ``before`` is a tick kind, ``prefill`` told apart where the site knows.
    assert {{"chunk": "prefill", "insert": "prefill"}.get(k, k)
            for k in seconds} <= set(ticks)
    assert all(n >= 1 and n == int(n) for n in intervals.values())
    assert intervals["decode"] <= ticks["decode"]
    assert "engine.wait_work" not in by_span
    assert all(k.startswith("engine.") for k in by_span)
    assert sum(seconds.values()) == pytest.approx(sum(by_span.values()), rel=1e-6)
    assert 'deployment_name="' in text.split(
        "tpumlops_device_starved_seconds_total{", 1)[1].split("}", 1)[0]
    body = httpx.get(llm_server.base + "/debug/spans", timeout=10).json()
    assert "engine.iteration" in body["spans"]
    account = body["device_starved"]
    assert set(account) == {"by_label", "by_span_s"}
    assert set(account["by_label"]) >= set(seconds)
    assert all(set(v) == {"seconds", "intervals"}
               for v in account["by_label"].values())
    assert set(account["by_span_s"]) >= set(by_span)


def test_prefill_dispatch_counts_one_a_chunk_program(chunked_llm_server):
    """``tpumlops_prefill_dispatch_total{when}``: ``ahead`` + ``in_turn``
    is the chunk programs dispatched (the prefill ticks less one insert a
    request); a request's first chunk is always sent in its turn."""
    base = chunked_llm_server.base

    def scrape():
        text = httpx.get(base + "/metrics", timeout=10).text
        assert "# TYPE tpumlops_prefill_dispatch_total counter" in text
        sent = {"ahead": 0.0, "in_turn": 0.0}
        for ln in text.splitlines():
            if ln.startswith("tpumlops_prefill_dispatch_total{"):
                labels, value = ln.rsplit(" ", 1)
                (when,) = [w for w in sent if f'when="{w}"' in labels]
                assert all(f'{name}="' in labels for name in (
                    "deployment_name", "predictor_name", "namespace"))
                sent[when] += float(value)
        ticks = sum(
            float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
            if ln.startswith("tpumlops_engine_dispatches_total{")
            and 'op="prefill"' in ln)
        return sent, ticks

    sent0, ticks0 = scrape()
    url = base + "/v2/models/llm/generate"
    with httpx.stream(
        "POST", url, timeout=120,
        json={"prompt_ids": [5, 9, 2, 7], "max_new_tokens": 40, "stream": True},
    ) as rider:
        lines = rider.iter_lines()
        assert next(ln for ln in lines if ln.startswith("data: "))
        doc = httpx.post(  # four chunks of 8 beside the rider's steps
            url, timeout=120,
            json={"prompt_ids": list(range(3, 33)), "max_new_tokens": 3})
        assert doc.status_code == 200
        assert sum(ln.startswith("data: ") for ln in lines) == 40
    sent1, ticks1 = scrape()
    ahead, in_turn = (sent1[w] - sent0[w] for w in ("ahead", "in_turn"))
    assert ahead + in_turn == (ticks1 - ticks0) - 2 == 1 + 4
    assert in_turn >= 2


def test_generate_streaming_rejects_multi_prompt(llm_server):
    resp = httpx.post(
        llm_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [[1, 2], [3, 4]], "max_new_tokens": 2,
              "stream": True},
        timeout=30,
    )
    assert resp.status_code == 400
    assert "one prompt" in resp.json()["error"]


def test_request_id_echo_and_traceparent(iris_server):
    """Request identity contract: X-Request-Id in -> echoed verbatim;
    W3C traceparent in -> its 32-hex trace id becomes the request id;
    neither in -> the server mints one.  Errors carry the echo too."""
    handle, sk, X, y = iris_server
    body = {
        "inputs": [
            {
                "name": "x",
                "shape": [1, 4],
                "datatype": "FP32",
                "data": [float(v) for v in X[0]],
            }
        ]
    }
    url = handle.base + "/v2/models/iris/infer"
    resp = httpx.post(
        url, json=body, headers={"X-Request-Id": "my-id-42"}, timeout=30
    )
    assert resp.headers["X-Request-Id"] == "my-id-42"
    trace_id = "0af7651916cd43dd8448eb211c80319c"
    resp = httpx.post(
        url,
        json=body,
        headers={"traceparent": f"00-{trace_id}-b7ad6b7169203331-01"},
        timeout=30,
    )
    assert resp.headers["X-Request-Id"] == trace_id
    resp = httpx.post(url, json=body, timeout=30)
    assert len(resp.headers["X-Request-Id"]) == 32  # server-minted uuid4
    bad = httpx.post(
        url, json={"inputs": []}, headers={"X-Request-Id": "err-7"}, timeout=30
    )
    assert bad.status_code == 400
    assert bad.headers["X-Request-Id"] == "err-7"
    # Router-level 404s are RAISED HTTPExceptions, not returned
    # responses — they carry the echo too (misrouted requests are the
    # ones a client most needs to correlate).
    lost = httpx.get(
        handle.base + "/no/such/path",
        headers={"X-Request-Id": "lost-1"},
        timeout=30,
    )
    assert lost.status_code == 404
    assert lost.headers["X-Request-Id"] == "lost-1"
    # An id that sanitizes to nothing falls through to a minted one
    # (httpx refuses to send control chars, so this level is unit-only).
    from tpumlops.server.app import request_id_from_headers

    assert len(request_id_from_headers({"X-Request-Id": "\x01\x02"})) == 32
    assert request_id_from_headers({"X-Request-Id": "ok-1"}) == "ok-1"


def test_debug_spans_endpoint(iris_server):
    """The server's tracer (the one its engine loop writes ``engine.*``
    spans into) is readable off the data plane, self time included."""
    handle, *_ = iris_server
    tracer = handle.server.metrics.tracer
    with tracer.span("test-span-probe"):
        with tracer.span("test-span-child"):
            pass
    resp = httpx.get(handle.base + "/debug/spans", timeout=10)
    assert resp.status_code == 200
    spans = resp.json()["spans"]
    assert spans["test-span-probe"]["count"] >= 1
    assert set(spans["test-span-probe"]) == {
        "count", "total_s", "self_s", "mean_ms", "max_ms"
    }
    assert spans["test-span-probe"]["self_s"] <= spans["test-span-probe"]["total_s"]
    # ... and on /metrics, rendered at scrape time with the identity labels.
    text = httpx.get(handle.base + "/metrics", timeout=10).text
    line = next(
        ln for ln in text.splitlines()
        if ln.startswith("tpumlops_spans_total{") and 'span="test-span-probe"' in ln
    )
    assert 'deployment_name="iris"' in line and float(line.rsplit(" ", 1)[1]) >= 1


def test_debug_timeseries_disabled_is_404_naming_the_flag(iris_server):
    """ISSUE 20 pin: with spec.tpu.observability.timeseriesRing unset
    (the default) the ring endpoint 404s and the body names BOTH the
    spec key and the CLI flag — the operator's ring fetch treats the
    404 as ring-off, never as an error."""
    handle, *_ = iris_server
    resp = httpx.get(handle.base + "/debug/timeseries", timeout=10)
    assert resp.status_code == 404
    body = resp.json()
    assert "timeseriesRing" in body["error"]
    assert "--timeseries-ring" in body["error"]


def _metric_total(text: str, family: str) -> float:
    """Sum every sample of ``family`` in a Prometheus exposition."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith(family) and line[len(family)] in "{ ":
            total += float(line.rsplit(" ", 1)[1])
    return total


@pytest.mark.slow
def test_generate_debug_timing_block_agrees_with_metrics(llm_server):
    """``"debug": true`` returns the per-request timing block, and its
    token / cached-token / speculative totals agree with the Prometheus
    counters that same request incremented."""
    before = httpx.get(llm_server.base + "/metrics", timeout=10).text
    resp = httpx.post(
        llm_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [5, 9, 2], "max_new_tokens": 7, "debug": True},
        headers={"X-Request-Id": "debug-req-1"},
        timeout=60,
    )
    assert resp.status_code == 200, resp.text
    assert resp.headers["X-Request-Id"] == "debug-req-1"
    after = httpx.get(llm_server.base + "/metrics", timeout=10).text
    timing = resp.json()["timing"]
    assert timing["request_id"] == "debug-req-1"

    def delta(family):
        return _metric_total(after, family) - _metric_total(before, family)

    assert timing["tokens"] == 7
    assert timing["tokens"] == delta("tpumlops_generated_tokens_total")
    assert timing["cached_tokens"] == delta(
        "tpumlops_prefix_cache_cached_tokens_total"
    )
    assert timing["spec_accepted"] == delta(
        "tpumlops_spec_accepted_tokens_total"
    )
    assert delta("tpumlops_request_tokens_count") == 1
    assert delta("tpumlops_request_tokens_sum") == 7
    # 7 tokens = 1 from prefill + 6 decode ticks -> 6 inter-token gaps.
    assert delta("tpumlops_itl_seconds_count") == 6
    assert delta("tpumlops_tick_seconds_count") >= 6  # decode + prefill
    assert 'kind="decode"' in after and 'kind="prefill"' in after
    assert timing["finish_reasons"] == ["length"]
    assert timing["queue_ms"] is not None and timing["queue_ms"] >= 0
    assert timing["ttft_ms"] is not None and timing["ttft_ms"] >= 0
    assert timing["rows"][0]["prompt_tokens"] == 3
    # Without the flag the block is absent (and typo'd knobs still 400).
    plain = httpx.post(
        llm_server.base + "/v2/models/llm/generate",
        json={"prompt_ids": [5, 9, 2], "max_new_tokens": 2},
        timeout=60,
    )
    assert "timing" not in plain.json()


@pytest.mark.slow
def test_generate_multi_row_debug_totals(llm_server):
    """Row sub-ids derive from the request id; totals sum across rows."""
    resp = httpx.post(
        llm_server.base + "/v2/models/llm/generate",
        json={
            "prompt_ids": [[5, 9, 2], [7, 1, 4, 8]],
            "max_new_tokens": 3,
            "debug": True,
        },
        headers={"X-Request-Id": "multi-1"},
        timeout=60,
    )
    assert resp.status_code == 200, resp.text
    timing = resp.json()["timing"]
    assert timing["tokens"] == 6
    assert [r["request_id"] for r in timing["rows"]] == [
        "multi-1/0", "multi-1/1"
    ]


def test_debug_profile_endpoint(iris_server, tmp_path, monkeypatch):
    import os
    import tempfile
    import threading

    import jax

    handle, *_ = iris_server
    # The server runs in this process: its captures go under the
    # temporary directory the process is given ($TMPDIR), not /tmp.
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    resp = httpx.post(
        handle.base + "/debug/profile",
        json={"duration_s": 0.2},
        timeout=30,
    )
    assert resp.status_code == 200, resp.text
    out = resp.json()
    # paths are server-chosen (unauthenticated endpoint: no client dirs)
    assert out["trace_dir"].startswith(str(tmp_path / "tpumlops-profile") + os.sep)
    assert out["stop_trace_s"] >= 0.0
    # The span table and the starvation account as the capture began and
    # ended: what scripts/capture_report.py sets beside the device's gaps.
    assert set(out["spans_at"]) == {"start", "stop"}
    assert all(set(at) == {"spans", "device_starved"}
               for at in out["spans_at"].values())
    found = []
    for _root, _dirs, files in os.walk(out["trace_dir"]):
        found += files
    assert found, "trace directory is empty"
    # non-finite durations rejected; the lock is released afterwards
    bad = httpx.post(
        handle.base + "/debug/profile", json={"duration_s": "nan"}, timeout=10
    )
    assert bad.status_code == 400

    # stop_trace runs off the event loop: while a (slowed) stop is in
    # progress the server still answers.
    stopping, real_stop = threading.Event(), jax.profiler.stop_trace

    def slow_stop():
        stopping.set()
        time.sleep(1.0)
        try:
            real_stop()
        finally:
            stopping.clear()

    monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop)
    result = {}

    def capture():
        result["again"] = httpx.post(
            handle.base + "/debug/profile", json={"duration_s": 0.1}, timeout=30
        )

    t = threading.Thread(target=capture)
    t.start()
    assert stopping.wait(timeout=20)
    live = httpx.get(handle.base + "/v2/health/live", timeout=0.5)
    assert live.status_code == 200 and stopping.is_set()
    t.join(timeout=30)
    assert not t.is_alive()
    assert result["again"].status_code == 200
    assert result["again"].json()["stop_trace_s"] >= 1.0


def test_profile_capture_gc_keeps_newest_dirs(tmp_path):
    """ISSUE 20 satellite: /debug/profile keeps only the newest
    PROFILE_KEEP_DIRS capture dirs — unbounded /tmp growth was the
    leak; the evicted names come back in the endpoint response."""
    import os

    from tpumlops.server.app import PROFILE_KEEP_DIRS, _gc_profile_dirs

    assert PROFILE_KEEP_DIRS == 8
    root = tmp_path / "prof"
    root.mkdir()
    for i in range(11):
        d = root / f"cap-{i:02d}"
        d.mkdir()
        os.utime(d, (1000 + i, 1000 + i))
    evicted = _gc_profile_dirs(str(root), keep=8)
    assert sorted(evicted) == ["cap-00", "cap-01", "cap-02"]
    assert sorted(p.name for p in root.iterdir()) == [
        f"cap-{i:02d}" for i in range(3, 11)
    ]
    # Idempotent once under the cap; a missing root is a no-op, never
    # an endpoint error.
    assert _gc_profile_dirs(str(root), keep=8) == []
    assert _gc_profile_dirs(str(tmp_path / "nope")) == []


def test_bert_server_buckets_variable_lengths(tmp_path):
    """Odd-length requests through the live HTTP path: seq bucketing
    pads them (mask synthesized), results match direct predict, and two
    different lengths land in one compiled shape."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models import bert

    cfg = bert.BertConfig.tiny(num_labels=3)
    params = bert.init(jax.random.key(0), cfg)
    art = tmp_path / "bertvar"
    save_native_model(
        art,
        "bert-classifier",
        params,
        config={
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.hidden_size,
            "num_layers": cfg.num_layers,
            "num_heads": cfg.num_heads,
            "intermediate_size": cfg.intermediate_size,
            "max_position_embeddings": cfg.max_position_embeddings,
            "num_labels": cfg.num_labels,
        },
        builder_kwargs={"seq_len": 16},
    )
    config = ServerConfig(
        model_name="bertvar",
        model_uri=str(art),
        predictor_name="v1",
        deployment_name="bertvar",
        namespace="models",
        tpu=TpuSpec.from_spec({"meshShape": {"tp": 1}, "maxBatchSize": 4}),
    )
    handle = serve(build_server(config))
    try:
        for L in (9, 13):  # both bucket to 16
            ids = np.arange(1, L + 1, dtype=np.int32).reshape(1, L)
            r = httpx.post(
                handle.base + "/v2/models/bertvar/infer",
                json={
                    "inputs": [
                        {
                            "name": "input_ids",
                            "shape": [1, L],
                            "datatype": "INT32",
                            "data": ids.ravel().tolist(),
                        }
                    ]
                },
                timeout=60,
            )
            assert r.status_code == 200, r.text
            got = np.asarray(r.json()["outputs"][0]["data"], np.float32)
            ref = np.asarray(
                bert.classify(
                    params,
                    jnp.asarray(ids),
                    jnp.ones_like(jnp.asarray(ids)),
                    cfg=cfg,
                    dtype=jnp.float32,
                )
            )[0]
            np.testing.assert_allclose(got, ref, rtol=5e-2, atol=5e-2)
    finally:
        handle.stop()


def test_shutdown_drains_queued_requests_with_engine_shutdown():
    """Graceful shutdown must FAIL queued (not-yet-admitted) requests
    with a clear EngineShutdown instead of leaving callers hanging on
    futures nobody will resolve (or a bare CancelledError they cannot
    tell apart from their own cancel)."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama
    from tpumlops.server.generation import EngineShutdown, GenerationEngine

    cfg = llama.LlamaConfig.tiny(max_seq=32)
    params = llama.init(jax.random.key(2), cfg, dtype=jnp.float32)
    # Never started: every submitted request is queued-but-unadmitted.
    engine = GenerationEngine(params, cfg, max_slots=2, dtype=jnp.float32)
    futs = [engine.submit([1, 2, 3], 4) for _ in range(3)]
    engine.shutdown()
    for fut in futs:
        assert fut.done()
        with pytest.raises(EngineShutdown, match="retry on another replica"):
            fut.result(timeout=5)
    # EngineShutdown is a RuntimeError: the HTTP layer's generic 500
    # path already renders it with the message intact.
    assert issubclass(EngineShutdown, RuntimeError)


def test_streaming_loader_consumer_crash_releases_reader(tmp_path, monkeypatch):
    """A consumer failure (e.g. device OOM mid-transfer) must not strand
    the npz reader thread on the bounded queue: the thread would hold the
    open npz handle plus buffered leaves for the life of the process, and
    a server retrying load_predictor would accumulate one wedged reader
    per attempt."""
    from tpumlops.server import loader as loader_mod

    npz = tmp_path / "params.npz"
    np.savez(npz, **{f"leaf{i}": np.ones((64, 64), np.float32) for i in range(8)})

    def boom(q, leaves, quantize_leaves, timing):
        raise MemoryError("simulated device OOM")

    monkeypatch.setattr(loader_mod, "_consume_leaves", boom)
    with pytest.raises(MemoryError, match="simulated device OOM"):
        loader_mod._stream_native_params(npz)

    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if not any(t.name == "npz-reader" for t in threading.enumerate()):
            break
        time.sleep(0.05)
    alive = [t.name for t in threading.enumerate() if t.name == "npz-reader"]
    assert not alive, f"reader threads still wedged: {alive}"
