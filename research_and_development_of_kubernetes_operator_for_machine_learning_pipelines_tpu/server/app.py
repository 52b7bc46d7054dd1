"""HTTP inference server: V2 (kfserving) + Seldon protocol + Prometheus.

Serves the protocols the reference's stack expects — the SeldonDeployment
declares ``protocol: kfserving`` (``mlflow_operator.py:235``), i.e. the V2
dataplane, and Istio routes raw HTTP between predictor versions — while
exporting the gate-compatible metrics (see ``metrics.py``).

Endpoints:
- ``GET  /v2/health/live``, ``GET /v2/health/ready``
- ``GET  /v2/models/{name}``, ``GET /v2/models/{name}/ready``
- ``POST /v2/models/{name}/infer``      (V2 JSON tensors)
- ``POST /api/v1.0/predictions``        (Seldon ndarray compat)
- ``GET  /metrics``                      (Prometheus exposition)

Single-example requests are cross-request batched by the dynamic batcher;
client-batched requests run directly.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import logging
import os
import re
import time
import uuid
from typing import Any

import numpy as np
from aiohttp import web

from ..utils.config import ServerConfig, TpuSpec
from .batching import DynamicBatcher
from .engine import InferenceEngine
from .generation import EngineOverloaded, PoisonRequest
from .loader import load_predictor
from .metrics import ServerMetrics

_log = logging.getLogger(__name__)
# One structured completion line per generation request (request-id
# correlated; --log-format json emits it as a machine-parseable object).
_req_log = logging.getLogger("tpumlops.request")

# W3C traceparent: version-traceid-spanid-flags; the 32-hex trace id is
# the request identity we adopt (so spans correlate across the mesh) and
# the 16-hex span id is the immediate parent (with the router's journey
# ring on: the router's per-leg span).
_TRACEPARENT = re.compile(
    r"^[0-9a-f]{2}-([0-9a-f]{32})-([0-9a-f]{16})-[0-9a-f]{2}$"
)


def trace_context_from_headers(headers) -> tuple[str, str]:
    """``(trace_id, parent_span)`` from a well-formed ``traceparent``
    header, or ``("", "")`` — the engine ``RequestTrace`` then carries
    the propagated context so a fleet stitcher can join this replica's
    spans to the router journey that produced them."""
    m = _TRACEPARENT.match(headers.get("traceparent", "").strip().lower())
    if m:
        return m.group(1), m.group(2)
    return "", ""


def request_id_from_headers(headers) -> str:
    """Inbound request identity: ``X-Request-Id`` verbatim, else the W3C
    ``traceparent`` trace id, else a fresh uuid4 hex.  Always echoed back
    as ``X-Request-Id`` so clients (and the router's access logs) can
    correlate a slow response with the server's completion line and the
    flight recorder's span."""
    # Bound + sanitize: the id lands in log lines and trace JSON.  An id
    # that sanitizes to nothing falls through to the next source — an
    # empty identity would make the request uncorrelatable.
    rid = "".join(
        c for c in headers.get("X-Request-Id", "").strip()[:128]
        if c.isprintable()
    )
    if rid:
        return rid
    tp = headers.get("traceparent", "").strip().lower()
    m = _TRACEPARENT.match(tp)
    if m:
        return m.group(1)
    return uuid.uuid4().hex


@web.middleware
async def request_id_middleware(request: web.Request, handler):
    rid = request["request_id"] = request_id_from_headers(request.headers)
    request["trace_id"], request["parent_span"] = trace_context_from_headers(
        request.headers
    )
    try:
        resp = await handler(request)
    except web.HTTPException as exc:
        # Router 404/405 and 413-over-max-size are raised, not returned
        # — exactly the responses a client most needs to correlate.
        exc.headers.setdefault("X-Request-Id", rid)
        raise
    # A streaming response has already sent its status line (its headers
    # carry the id from _stream_generation); everything else gets the
    # echo here, errors included.
    if not getattr(resp, "prepared", False):
        resp.headers.setdefault("X-Request-Id", rid)
    return resp

_V2_TO_NP = {
    "FP32": np.float32,
    "FP64": np.float64,
    "FP16": np.float16,
    "BF16": np.float32,  # JSON carries floats; cast happens model-side
    "INT32": np.int32,
    "INT64": np.int64,
    "UINT8": np.uint8,
    "BOOL": np.bool_,
}
_NP_TO_V2 = {
    np.dtype(np.float32): "FP32",
    np.dtype(np.float64): "FP64",
    np.dtype(np.float16): "FP16",
    np.dtype(np.int32): "INT32",
    np.dtype(np.int64): "INT64",
    np.dtype(np.uint8): "UINT8",
    np.dtype(np.bool_): "BOOL",
}


# Recognized /generate parameters.  Unknown keys 400 instead of being
# silently ignored — a typo'd knob ("max_new_token") quietly generating
# the default is the worst failure mode for a client.  The check itself
# is the CRD-side unknown-key rejection (utils/config), so the error
# contract (key named + allowed set) stays spelled once.
_GEN_PARAM_KEYS = frozenset(
    {"max_new_tokens", "eos_id", "temperature", "top_k", "top_p", "seed",
     "stream", "debug", "slo_class"}
)


def _check_gen_params(params: dict, allowed: frozenset) -> None:
    from ..utils.config import _reject_unknown_keys

    _reject_unknown_keys(params, allowed, "generate parameters")


# Capture directories kept under /tmp/tpumlops-profile: a device trace
# is tens of MB, the endpoint is unauthenticated, and nothing else ever
# cleaned the path — the newest N stay, older ones are deleted after
# each successful capture.
PROFILE_KEEP_DIRS = 8


def _gc_profile_dirs(root: str, keep: int = PROFILE_KEEP_DIRS) -> list:
    """Delete all but the ``keep`` newest capture dirs under ``root``;
    returns the deleted directory names (the ``evicted`` response
    field).  Best-effort: a dir that vanishes mid-walk is skipped, never
    an endpoint error — GC must not fail a successful capture."""
    import shutil

    try:
        entries = [
            e for e in os.scandir(root) if e.is_dir(follow_symlinks=False)
        ]
    except OSError:
        return []
    def _mtime(entry) -> float:
        try:
            return entry.stat().st_mtime
        except OSError:
            return 0.0

    entries.sort(key=_mtime, reverse=True)
    evicted = []
    for entry in entries[keep:]:
        try:
            shutil.rmtree(entry.path)
            evicted.append(entry.name)
        except OSError:
            continue
    return evicted


class TpuInferenceServer:
    def __init__(
        self,
        engine: InferenceEngine | None,
        metrics: ServerMetrics,
        model_name: str,
        max_batch_size: int = 32,
        max_batch_delay_ms: float = 5.0,
        gen_engine=None,
        max_inflight_batches: int = 2,
        recorder=None,
        drain_grace_s: float = 20.0,
        telemetry=None,
        attach_fn=None,
        cold_start_anchor_wall: float | None = None,
        fleet_role: str = "unified",
        snapshot_dir=None,
        timeseries=None,
    ):
        self.engine = engine
        self.metrics = metrics
        self.model_name = model_name
        # Single source of truth for the serving lifecycle: loading ->
        # ready -> draining -> shutdown, plus "warm-pool" — booted,
        # compile-swept, but holding NO weights until /admin/attach.
        # /readyz, /v2/health/ready (the manifest's readiness-probe path
        # — same handler), the drain protocol, and the SIGTERM path all
        # read/write THIS field; there is no second "ready" boolean
        # anywhere to fall out of sync.
        self.lifecycle = "loading"
        self.drain_grace_s = float(drain_grace_s)
        # Set by the SIGTERM path: the process is irrevocably exiting,
        # so a drain can no longer be cancelled (an unauthenticated
        # cancel re-opening admissions on a dying pod would route fresh
        # traffic straight into the teardown's EngineShutdown).
        self.terminating = False
        self.gen_engine = gen_engine  # GenerationEngine for causal-LM flavors
        self.recorder = recorder  # flight_recorder.FlightRecorder | None
        self.telemetry = telemetry  # device_telemetry.DeviceTelemetry | None
        self.timeseries = timeseries  # timeseries.TimeseriesRing | None
        # Warm-pool seam: builds (engine, gen_engine, predictor) for a
        # model URI on demand — None on a normal (model-at-boot) server.
        self.attach_fn = attach_fn
        self.predictor = None  # set by attach (release target on replace)
        # Attached-model identity contract (warm-pool only): what is on
        # the device right now, echoed by /readyz and /admin/attach so a
        # multiplexing bin-packer can prove convergence (and skip swaps
        # that would restore identical weights) without device access.
        self.snapshot_dir = snapshot_dir
        self.attached_model_uri: str | None = None
        self.attached_snapshot_hash: str | None = None
        self._attached_geometry: dict | None = None
        self._batch_geometry = (max_batch_size, max_batch_delay_ms,
                                max_inflight_batches)
        # Wall-clock anchor of the current cold start (wake signal time
        # when known, else boot time); the first token served after it
        # closes the tpumlops_cold_start_seconds ladder.
        self._cold_anchor_wall = cold_start_anchor_wall
        # Disaggregated-fleet role (unified | prefill | decode):
        # advisory identity on /readyz and log lines — the router's
        # role-tagged backend table decides who exports/imports KV.
        self.fleet_role = fleet_role
        import threading

        self._profile_lock = threading.Lock()
        self._attach_lock = asyncio.Lock()
        self.batcher = None
        if engine is not None:
            self._wire_batcher(engine)

    def _wire_batcher(self, engine) -> None:
        # Pipelined when the engine supports async dispatch (the jit
        # tier): batch N+1 stacks/dispatches while N executes on device.
        max_batch_size, max_batch_delay_ms, max_inflight = (
            self._batch_geometry
        )
        has_async = hasattr(engine, "predict_async")
        self.batcher = DynamicBatcher(
            run_batch=engine.predict_async if has_async else engine.predict,
            max_batch_size=max_batch_size,
            max_batch_delay_ms=max_batch_delay_ms,
            on_batch=self.metrics.observe_batch,
            materialize=engine.materialize if has_async else None,
            max_inflight=max_inflight,
        )

    def _not_attached(self, request: web.Request) -> web.Response | None:
        """Typed 503 while a warm-pool replica holds no model (clients
        retry after the operator attaches one).  Carries the request id
        like every typed error body — a shed must stay correlatable
        with the router journey when client stacks drop headers."""
        if self.engine is not None:
            return None
        return web.json_response(
            {
                "error": "no model attached to this warm-pool replica",
                "reason": "warm_pool_empty",
                "retry_after_s": 5,
                "request_id": request.get("request_id", ""),
            },
            status=503,
            headers={"Retry-After": "5"},
        )

    def _snapshot_probe(
        self, model_uri: str
    ) -> tuple[str | None, dict | None]:
        """Best-effort (content_hash, geometry) of ``model_uri``'s
        on-disk snapshot — (None, None) when there is no snapshot yet
        (first attach of a raw model writes one during the load)."""
        if not self.snapshot_dir:
            return None, None
        try:
            from . import snapshot as _snap

            spath = _snap.snapshot_path_for(self.snapshot_dir, model_uri)
            if not (spath / _snap.MANIFEST_NAME).exists():
                return None, None
            manifest = _snap.read_manifest(spath)
            geom = manifest.get("config")
            return (
                manifest.get("content_hash"),
                dict(geom) if isinstance(geom, dict) else None,
            )
        except Exception:
            return None, None

    def note_first_token(self) -> None:
        """First token served since the cold-start anchor: close the
        tpumlops_cold_start_seconds ladder (one-shot per boot/attach)."""
        anchor = self._cold_anchor_wall
        if anchor is None:
            return
        self._cold_anchor_wall = None
        self.metrics.observe_cold_start("first_token", time.time() - anchor)

    # -- lifecycle -----------------------------------------------------------

    @property
    def ready(self) -> bool:
        """Back-compat view of the lifecycle (probes read this)."""
        return self.lifecycle == "ready"

    @ready.setter
    def ready(self, value: bool) -> None:
        # Legacy writers (SIGTERM path, tests) flip a boolean; map it
        # onto the lifecycle without ever resurrecting a shutdown server.
        if value:
            self.lifecycle = "ready"
        elif self.lifecycle == "ready":
            self.lifecycle = "draining"

    def startup(self, warmup: bool = True) -> None:
        if self.engine is None:
            # Warm-pool boot: compile programs are pre-baked (see
            # prewarm_from_snapshot) but there are no weights to serve —
            # readiness stays down until /admin/attach.
            self.lifecycle = "warm-pool"
            self.metrics.ready.labels(**self.metrics.identity).set(0)
            return
        if warmup:
            self.engine.warmup()
        if self.gen_engine is not None:
            self.gen_engine.start(warmup=warmup)
        self.batcher.start()
        self.lifecycle = "ready"
        self.metrics.ready.labels(**self.metrics.identity).set(1)

    def begin_drain(self) -> None:
        """Enter the lossless-drain state: readiness flips (kubelet and
        balancers stop routing here), the generation engine sheds NEW
        submissions with 429 + Retry-After, and everything already
        admitted — queued, mid-prefill, decoding, streaming — runs to
        completion.

        Idempotent, and deliberately NOT guarded on lifecycle ==
        "draining": the SIGTERM path flips ``ready = False`` first (the
        endpoint-removal lag keeps ADMITTING while NotReady), which
        already reads as "draining" — an early-return there would skip
        arming the engine and the drain would never shed or complete.
        Only a shut-down server is past draining."""
        if self.lifecycle == "shutdown":
            return
        self.lifecycle = "draining"
        self.metrics.ready.labels(**self.metrics.identity).set(0)
        if self.gen_engine is not None:
            self.gen_engine.begin_drain()

    def cancel_drain(self) -> bool:
        """Reverse a drain (``POST /admin/drain {"cancel": true}``): the
        engine admits again and readiness returns.  The escape hatch
        that keeps the unauthenticated drain endpoint from being a
        one-way kill switch — a stray or mistaken drain is repairable
        without a pod restart.  Refused (False) once the process is
        terminating (SIGTERM already committed to exit) or shut down."""
        if self.terminating:
            return False
        if self.lifecycle != "draining":
            return self.lifecycle == "ready"
        if self.gen_engine is not None:
            self.gen_engine.cancel_drain()
        self.lifecycle = "ready"
        self.metrics.ready.labels(**self.metrics.identity).set(1)
        return True

    def note_watchdog_stall(self, kind: str, age_s: float, inventory) -> None:
        """Watchdog monitor-thread callback: a scheduler tick exceeded
        the deadline (hung XLA dispatch / wedged device).  Flip
        ``/readyz`` unready so balancers route elsewhere, count the
        stall, and journal the in-flight picture — the flight-recorder
        event is what lets an operator attribute the wedge to a tick
        kind and slot set after the pod restarts."""
        if self.lifecycle == "ready":
            self.lifecycle = "stalled"
            self.metrics.ready.labels(**self.metrics.identity).set(0)
        self.metrics.inc_watchdog_stall()
        if self.recorder is not None:
            self.recorder.event(
                "", "watchdog",
                kind=kind, age_s=round(float(age_s), 3),
                slots=list(inventory),
            )

    def note_watchdog_recover(self) -> None:
        """The stalled tick completed after all (transient contention, a
        pathological compile): re-ready — unless a drain/shutdown landed
        meanwhile, whose state must win."""
        if self.lifecycle == "stalled":
            self.lifecycle = "ready"
            self.metrics.ready.labels(**self.metrics.identity).set(1)

    async def wait_drained(self, grace_s: float | None = None) -> bool:
        """Await in-flight completion (bounded by ``grace_s``); True when
        the engine owes no sequence another token."""
        grace = self.drain_grace_s if grace_s is None else float(grace_s)
        deadline = time.monotonic() + max(0.0, grace)
        while True:
            if self.gen_engine is None or self.gen_engine.drained():
                return True
            if time.monotonic() >= deadline:
                return False
            await asyncio.sleep(0.05)

    def shutdown(self) -> None:
        self.lifecycle = "shutdown"
        if self.telemetry is not None:
            # Stop the process-global compile listeners attributing into
            # this (now retired) server's observatory and metrics.
            from ..utils.compile_cache import detach_observatory

            detach_observatory(self.telemetry.observatory)
        if self.batcher is not None:
            self.batcher.stop()
        if self.gen_engine is not None:
            self.gen_engine.shutdown()
        if hasattr(self.engine, "shutdown"):
            # multi-host leader: release follower processes after the
            # batcher has drained (no more broadcasts can follow)
            self.engine.shutdown()

    # -- request handling ----------------------------------------------------

    async def _run(self, inputs: dict[str, np.ndarray]) -> Any:
        """Dispatch: batch-1 via the dynamic batcher, larger directly —
        but always through the warmed power-of-two buckets, never a raw
        client batch size (each distinct shape is an XLA compile)."""
        seq_pad = getattr(self.engine.predictor, "seq_pad", None)
        if seq_pad:
            from .batching import apply_seq_pad

            inputs = apply_seq_pad(inputs, seq_pad)
        batch = next(iter(inputs.values())).shape[0]
        if batch == 1:
            single = {k: v[0] for k, v in inputs.items()}
            fut = self.batcher.submit(single)
            out = await asyncio.wrap_future(fut)
            return _add_batch_dim(out)
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, self._predict_bucketed, inputs)

    def _predict_bucketed(self, inputs: dict[str, np.ndarray]) -> Any:
        """Pad a client batch up to the nearest warmed bucket (chunking
        batches larger than max_batch_size), then slice back."""
        from .batching import next_bucket

        batch = next(iter(inputs.values())).shape[0]
        cap = self.batcher.max_batch_size
        chunks_out = []
        for start in range(0, batch, cap):
            chunk = {k: v[start : start + cap] for k, v in inputs.items()}
            n = next(iter(chunk.values())).shape[0]
            bucket = next_bucket(n, cap)
            if bucket > n:
                chunk = {
                    k: np.concatenate([v, np.repeat(v[-1:], bucket - n, axis=0)])
                    for k, v in chunk.items()
                }
            out = self.engine.predict(chunk)
            chunks_out.append(_slice_batch(out, n))
        return _concat_batches(chunks_out)

    async def handle_v2_infer(self, request: web.Request) -> web.Response:
        err = self._not_attached(request)
        if err is not None:
            return err
        t0 = time.perf_counter()
        code = 200
        try:
            body = await request.json()
            inputs: dict[str, np.ndarray] = {}
            for tensor in body.get("inputs", []):
                dt = _V2_TO_NP.get(tensor.get("datatype", "FP32"))
                if dt is None:
                    raise ValueError(f"unsupported datatype {tensor.get('datatype')}")
                arr = np.asarray(tensor["data"], dtype=dt).reshape(tensor["shape"])
                inputs[tensor["name"]] = arr
            if not inputs:
                raise ValueError("request has no inputs")
            out = await self._run(inputs)
            outputs = _to_v2_outputs(out)
            return web.json_response(
                {
                    "model_name": self.model_name,
                    "id": body.get("id", ""),
                    "outputs": outputs,
                }
            )
        except (ValueError, KeyError, TypeError, json.JSONDecodeError) as e:
            code = 400
            return web.json_response(
                {"error": str(e), "request_id": request.get("request_id", "")},
                status=400,
            )
        except Exception as e:  # model/runtime failure
            _log.exception("inference failed")
            code = 500
            return web.json_response(
                {"error": str(e), "request_id": request.get("request_id", "")},
                status=500,
            )
        finally:
            self.metrics.observe_request(time.perf_counter() - t0, code=code)

    async def handle_seldon_predict(self, request: web.Request) -> web.Response:
        """Seldon-protocol compatibility (``{"data": {"ndarray": ...}}``)."""
        err = self._not_attached(request)
        if err is not None:
            return err
        t0 = time.perf_counter()
        code = 200
        try:
            body = await request.json()
            data = body.get("data", {})
            if "ndarray" in data:
                arr = np.asarray(data["ndarray"], dtype=np.float32)
            elif "tensor" in data:
                t = data["tensor"]
                arr = np.asarray(t["values"], np.float32).reshape(t["shape"])
            else:
                raise ValueError("data.ndarray or data.tensor required")
            out = await self._run({"x": arr})
            out_arr = np.asarray(out if not isinstance(out, tuple) else out[0])
            return web.json_response(
                {"data": {"ndarray": out_arr.tolist()}, "meta": {}}
            )
        except (ValueError, KeyError, TypeError, json.JSONDecodeError) as e:
            code = 400
            return web.json_response(
                {"error": str(e), "request_id": request.get("request_id", "")},
                status=400,
            )
        except Exception as e:
            _log.exception("inference failed")
            code = 500
            return web.json_response(
                {"error": str(e), "request_id": request.get("request_id", "")},
                status=500,
            )
        finally:
            self.metrics.observe_request(time.perf_counter() - t0, code=code)

    async def handle_feedback(self, request: web.Request) -> web.Response:
        """Seldon feedback API (``/api/v1.0/feedback``).

        The reference's metric collector counts these per predictor
        (``mlflow_operator.py:410-415``, ``service="feedback"``) — in the
        reference stack Seldon's executor serves the route; here the
        first-party data plane does.  The body is the Seldon shape
        ``{"request": .., "response": .., "reward": r, "truth": ..}``;
        the count (and reward sum) is the product — feedback is reward
        signal, not inference, so nothing is recomputed.
        """
        t0 = time.perf_counter()
        code = 200
        try:
            body = await request.json()
            if not isinstance(body, dict):
                raise ValueError("feedback body must be a JSON object")
            reward = body.get("reward", 0.0)
            if not isinstance(reward, (int, float)):
                raise ValueError("reward must be a number")
            self.metrics.observe_feedback_reward(float(reward))
            return web.json_response({"meta": {}})
        except (ValueError, TypeError, json.JSONDecodeError) as e:
            code = 400
            return web.json_response(
                {"error": str(e), "request_id": request.get("request_id", "")},
                status=400,
            )
        except Exception as e:
            _log.exception("feedback handling failed")
            code = 500
            return web.json_response(
                {"error": str(e), "request_id": request.get("request_id", "")},
                status=500,
            )
        finally:
            self.metrics.observe_request(
                time.perf_counter() - t0, code=code, service="feedback"
            )

    async def handle_generate(self, request: web.Request) -> web.Response:
        """Text generation with continuous batching (causal-LM flavors only).

        Accepts either the simple form ``{"prompt_ids": [[...]], "max_new_tokens": N,
        "eos_id": E?}`` (``prompt_ids`` may be one sequence or a list of
        sequences) or a V2-style tensor ``{"inputs": [{"name": "prompt_ids",
        ...}], "parameters": {"max_new_tokens": N}}``.  Sequences in one
        request are scheduled independently — they share decode steps with
        every other in-flight request, not just each other.
        """
        err = self._not_attached(request)
        if err is not None:
            return err
        t0 = time.perf_counter()
        code = 200
        # Multiplexed warm pool: the wildcard route carries the model id
        # the router addressed; it keys the per-model admission share so
        # a flooded hot model sheds at its share instead of filling the
        # whole queue against the tail models.  The literal (boot-name)
        # route has no mux_model — the ledger stays untouched there.
        mux_model = request.match_info.get("mux_model")
        mux_reserved = 0
        try:
            if self.gen_engine is None:
                code = 400
                return web.json_response(
                    {"error": f"model {self.model_name} is not a causal LM"},
                    status=400,
                )
            body = await request.json()
            if "inputs" in body:
                tensors = {
                    t["name"]: np.asarray(t["data"], np.int32).reshape(t["shape"])
                    for t in body["inputs"]
                }
                if "prompt_ids" not in tensors:
                    raise ValueError('missing input tensor "prompt_ids"')
                rows = tensors["prompt_ids"]
                if "lengths" in tensors:
                    # Explicit per-row lengths disambiguate right-padding
                    # from legitimate trailing 0 tokens.
                    lens = tensors["lengths"].reshape(-1)
                    if lens.size != rows.shape[0]:
                        raise ValueError(
                            f'"lengths" has {lens.size} entries for '
                            f"{rows.shape[0]} prompt rows"
                        )
                    prompts = [row[: int(n)] for row, n in zip(rows, lens)]
                else:
                    # Fallback: strip trailing zeros (document: send
                    # "lengths" if 0 is a real token in your vocabulary).
                    prompts = [np.trim_zeros(row, "b") for row in rows]
                params = body.get("parameters", {})
                _check_gen_params(params, _GEN_PARAM_KEYS)
            else:
                raw = body["prompt_ids"]
                prompts = [raw] if raw and np.isscalar(raw[0]) else list(raw)
                params = body
                _check_gen_params(
                    params, _GEN_PARAM_KEYS | {"prompt_ids", "id"}
                )
            if not prompts:  # covers both forms (zero-row tensor, empty list)
                raise ValueError("prompt_ids is empty")
            max_new = int(params.get("max_new_tokens", 16))
            eos_id = params.get("eos_id")
            eos_id = int(eos_id) if eos_id is not None else None
            seed = params.get("seed")
            sampling = {
                "temperature": float(params.get("temperature", 0.0)),
                "top_k": int(params.get("top_k", 0)),
                "top_p": float(params.get("top_p", 1.0)),
                "seed": int(seed) if seed is not None else None,
            }
            # Per-request SLO class override (falls back to the engine's
            # --slo-class default when absent).  Validated here so a typo
            # 400s before any sibling is admitted.
            slo_class = params.get("slo_class")
            if slo_class is not None:
                slo_class = str(slo_class)
                from .generation import SLO_CLASSES

                if slo_class not in SLO_CLASSES:
                    raise ValueError(
                        f"slo_class {slo_class!r} not in {SLO_CLASSES}"
                    )
            # Validate every prompt BEFORE admitting any: a bad sibling must
            # not leave earlier ones generating into abandoned futures.
            prompts = [
                self.gen_engine.validate(
                    p,
                    max_new,
                    sampling["temperature"],
                    sampling["top_k"],
                    sampling["top_p"],
                    sampling["seed"],
                )
                for p in prompts
            ]

            def row_seed(i: int) -> int | None:
                # Distinct stream per row, reproducible from the request
                # seed: identical prompts sampled in one batch must differ.
                base = sampling["seed"]
                return None if base is None else (base + i) % (2**63)

            rid = request.get("request_id") or request_id_from_headers(
                request.headers
            )
            debug = bool(params.get("debug", False))
            if params.get("stream"):
                if len(prompts) != 1:
                    raise ValueError("stream=true supports exactly one prompt")
                codebox = {"code": 200}
                try:
                    return await self._stream_generation(
                        request, prompts[0], max_new, eos_id, sampling,
                        codebox, rid, slo_class=slo_class,
                    )
                finally:
                    code = codebox["code"]
            from .flight_recorder import RequestTrace

            # Admission control: reserve the WHOLE request's estimated
            # tokens up front, so it is admitted whole or shed whole —
            # a 429 must never leave earlier siblings generating into
            # abandoned futures.  Raises EngineOverloaded (-> 429 below)
            # before anything is enqueued.
            est_total = sum(int(p.size) + max_new for p in prompts)
            self.gen_engine.reserve_admission(
                est_total, slo_class=slo_class, model=mux_model,
            )
            if mux_model:
                mux_reserved = est_total
            traces = [
                RequestTrace(
                    request_id=rid if len(prompts) == 1 else f"{rid}/{i}",
                    trace_id=request.get("trace_id", ""),
                    parent_span=request.get("parent_span", ""),
                )
                for i in range(len(prompts))
            ]
            _stamp_handoff(request, traces)
            futures = [
                self.gen_engine.submit(
                    p, max_new, eos_id,
                    **{**sampling, "seed": row_seed(i)},
                    request_id=traces[i].request_id,
                    trace=traces[i],
                    est_reserved=True,
                    slo_class=slo_class,
                )
                for i, p in enumerate(prompts)
            ]
            outs = await asyncio.gather(
                *(asyncio.wrap_future(f) for f in futures)
            )
            self.note_first_token()
            summary = _timing_summary(rid, traces)
            self._log_completion(summary, code=200)
            payload = {
                "model_name": self.model_name,
                "id": body.get("id", ""),
                "outputs": [
                    {
                        "name": f"output_ids_{i}",
                        "datatype": "INT32",
                        "shape": [int(o.size)],
                        "data": o.tolist(),
                    }
                    for i, o in enumerate(outs)
                ],
            }
            if debug:
                payload["timing"] = summary
            return web.json_response(payload)
        except EngineOverloaded as e:
            # Shed contract: 429 + Retry-After, body naming the typed
            # reason ("budget" under load, "draining" during scale-down
            # / shutdown) AND the request id — a shed body must be
            # correlatable with the router journey / access-log line
            # without header access (many client stacks drop headers on
            # error paths).  Nothing reached the engine — clients retry
            # verbatim on another replica.
            code = 429
            body = {
                "error": str(e),
                "reason": e.reason,
                "retry_after_s": e.retry_after_s,
                "request_id": request.get("request_id", ""),
            }
            # Per-class sheds name the class so dashboards (and clients)
            # can tell best-effort load-shedding from real overload.
            if e.slo_class is not None:
                body["slo_class"] = e.slo_class
            return web.json_response(
                body,
                status=429,
                headers={"Retry-After": str(e.retry_after_s)},
            )
        except PoisonRequest as e:
            # Quarantine contract: 422, NOT 4xx-retryable — the prompt
            # itself crashes admission, so a retry (here or on any other
            # replica) would crash it too.  No Retry-After on purpose.
            code = 422
            return web.json_response(
                {
                    "error": str(e),
                    "reason": "poison_quarantined",
                    "fingerprint": e.fingerprint,
                    "crashes": e.crashes,
                    "request_id": request.get("request_id", ""),
                },
                status=422,
            )
        except (ValueError, KeyError, TypeError, json.JSONDecodeError) as e:
            code = 400
            return web.json_response(
                {"error": str(e), "request_id": request.get("request_id", "")},
                status=400,
            )
        except Exception as e:
            _log.exception("generation failed")
            code = 500
            return web.json_response(
                {"error": str(e), "request_id": request.get("request_id", "")},
                status=500,
            )
        finally:
            if mux_reserved and self.gen_engine is not None:
                self.gen_engine.release_model_admission(
                    mux_model, mux_reserved
                )
            self.metrics.observe_request(time.perf_counter() - t0, code=code)

    async def _stream_generation(
        self, request, prompt, max_new, eos_id, sampling, codebox,
        request_id: str = "", slo_class: str | None = None,
    ) -> web.StreamResponse:
        """SSE token stream: one ``data:`` event per token, then a final
        event with the full sequence.  Client disconnect cancels the
        request's future, which frees its engine slot at the next tick.

        The HTTP status line is committed as 200 before the outcome is
        known, so the gate-visible request metric takes ``codebox["code"]``
        instead (500 on engine failure, 499 on cancel/disconnect): a broken
        engine serving only streams must still trip the canary gate's
        error-rate query."""
        from .flight_recorder import RequestTrace

        loop = asyncio.get_running_loop()
        tokens: asyncio.Queue = asyncio.Queue()

        def on_token(t: int) -> None:  # scheduler thread -> event loop
            loop.call_soon_threadsafe(
                tokens.put_nowait, (int(t), time.perf_counter())
            )

        trace = RequestTrace(
            request_id=request_id,
            trace_id=request.get("trace_id", ""),
            parent_span=request.get("parent_span", ""),
        )
        _stamp_handoff(request, [trace])
        fut = self.gen_engine.submit(
            prompt, max_new, eos_id, **sampling, on_token=on_token,
            request_id=request_id, trace=trace, slo_class=slo_class,
        )
        fut.add_done_callback(
            lambda f: loop.call_soon_threadsafe(tokens.put_nowait, None)
        )
        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
                # The status line commits before the middleware could add
                # the echo, so the stream carries it itself.
                "X-Request-Id": request_id,
            }
        )
        await resp.prepare(request)
        emitted: list[int] = []
        try:
            while True:
                item = await tokens.get()
                if item is None:
                    break
                token, stamped = item
                emitted.append(token)
                if len(emitted) == 1:
                    self.note_first_token()
                payload = json.dumps({"index": len(emitted) - 1, "token": token})
                await resp.write(f"data: {payload}\n\n".encode())
                self.metrics.observe_emit_lag(time.perf_counter() - stamped)
            if fut.cancelled():
                codebox["code"] = 499
                await _write_sse_error(
                    resp, request_id, "cancelled", "generation cancelled"
                )
            elif fut.exception() is not None:
                codebox["code"] = 500
                await _write_sse_error(
                    resp, request_id, "engine_failed", str(fut.exception())
                )
            else:
                final = {"done": True, "output_ids": fut.result().tolist()}
                await resp.write(f"data: {json.dumps(final)}\n\n".encode())
        except (ConnectionError, OSError):
            # Client/transport went away mid-stream: free the engine slot
            # and end quietly (the outer handler must not try to write JSON
            # to a response that already started streaming).
            fut.cancel()
            codebox["code"] = 499
        except asyncio.CancelledError:
            fut.cancel()  # frees the slot at the next scheduler tick
            codebox["code"] = 499
            raise
        except Exception as e:
            # Anything else: still cancel (or the slot decodes to
            # max_new_tokens for nobody) — the status line is out, so a
            # JSON error body can't be started, but a terminal SSE
            # ``error`` event usually still can: without it the client
            # sees a dropped connection and cannot tell truncation from
            # completion.
            _log.exception("stream failed mid-generation")
            fut.cancel()
            codebox["code"] = 500
            with contextlib.suppress(Exception):
                await _write_sse_error(
                    resp, request_id, "stream_failed", str(e)
                )
        finally:
            # A cancel frees the engine slot only at the NEXT scheduler
            # tick — finish the trace here (first writer wins: the
            # engine's own later finish becomes a no-op) so the 499/500
            # completion line never reports "in-flight" for exactly the
            # requests an operator most needs to attribute.
            if codebox["code"] != 200:
                trace.finish(
                    "cancelled" if codebox["code"] == 499 else "error"
                )
            self._log_completion(
                _timing_summary(request_id, [trace]), code=codebox["code"]
            )
            with contextlib.suppress(Exception):
                await resp.write_eof()
        return resp

    def _log_completion(self, summary: dict, code: int) -> None:
        """One structured completion line per generation request (the
        request-scoped counterpart of the aggregate histograms; carries
        ``request_id`` as a record attribute for the JSON log format)."""
        _req_log.info(
            "generate done request_id=%s code=%d rows=%d tokens=%d "
            "queue_ms=%s ttft_ms=%s prefill_chunks=%d cached_tokens=%d "
            "spec_accepted=%d/%d finish=%s",
            summary["request_id"],
            code,
            len(summary["rows"]),
            summary["tokens"],
            summary["queue_ms"],
            summary["ttft_ms"],
            summary["prefill_chunks"],
            summary["cached_tokens"],
            summary["spec_accepted"],
            summary["spec_proposed"],
            ",".join(summary["finish_reasons"]),
            extra={"request_id": summary["request_id"]},
        )

    async def handle_profile(self, request: web.Request) -> web.Response:
        """Capture a JAX/XLA device trace (SURVEY §5: the reference has no
        profiling anywhere; the TPU data plane gets ``jax.profiler``).

        ``POST /debug/profile {"duration_s": 3}`` records device + host
        activity for the window and returns the trace directory (TensorBoard
        / xprof readable; always under ``<tempfile.gettempdir()>/
        tpumlops-profile``, which honours ``$TMPDIR`` — the endpoint is
        unauthenticated, so no caller-chosen paths).  One capture at a
        time.  After a successful capture only the newest
        :data:`PROFILE_KEEP_DIRS` capture directories are kept — older
        ones are deleted (the dir used to grow without bound across
        calls) and returned as ``evicted``.

        The Python tracer is off (``python_tracer_level`` 0): it slows
        the engine thread whose gaps the capture is meant to show, and
        the ``engine.*`` spans (host TraceMe events, still recorded) say
        what it was left on to show.  ``start_trace`` / ``stop_trace``
        run in an executor: ``stop_trace`` serializes the capture for
        seconds, and the event loop keeps writing SSE meanwhile.  The
        answer carries ``spans_at``: the ``/debug/spans`` payload (span
        table and starvation account) as the capture began and as it
        ended, so ``scripts/capture_report.py`` can set the account's
        deltas beside the device's own gaps over the same seconds."""
        import math
        import tempfile

        import jax

        try:
            body = await request.json() if request.can_read_body else {}
            if not isinstance(body, dict):
                raise ValueError("request body must be a JSON object")
            duration = float(body.get("duration_s", 3.0))
            if not math.isfinite(duration):
                raise ValueError(f"duration_s must be finite, got {duration}")
            duration = min(max(duration, 0.1), 60.0)
            root = os.path.join(tempfile.gettempdir(), "tpumlops-profile")
            out_dir = os.path.join(
                root, f"{self.model_name}-{int(time.time())}"
            )
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            if not self._profile_lock.acquire(blocking=False):
                return web.json_response(
                    {"error": "a profile capture is already running"}, status=409
                )
            loop = asyncio.get_running_loop()
            spans_at: dict = {}

            def start() -> None:
                jax.profiler.start_trace(out_dir, profiler_options=options)
                spans_at["start"] = self._spans_payload()

            def stop() -> float:
                spans_at["stop"] = self._spans_payload()
                t0 = time.perf_counter()
                with contextlib.suppress(Exception):
                    # raises "no session" when start_trace itself failed
                    jax.profiler.stop_trace()
                return time.perf_counter() - t0

            try:
                try:
                    await loop.run_in_executor(None, start)
                    await asyncio.sleep(duration)
                finally:
                    stop_s = await loop.run_in_executor(None, stop)
                evicted = _gc_profile_dirs(root)
            finally:
                self._profile_lock.release()
            return web.json_response(
                {
                    "trace_dir": out_dir,
                    "duration_s": duration,
                    "stop_trace_s": round(stop_s, 3),
                    "evicted": evicted,
                    "spans_at": spans_at,
                }
            )
        except (ValueError, TypeError, json.JSONDecodeError) as e:
            return web.json_response({"error": str(e)}, status=400)
        except Exception as e:
            _log.exception("profile capture failed")
            return web.json_response({"error": str(e)}, status=500)

    async def handle_metrics(self, request: web.Request) -> web.Response:
        return web.Response(
            body=self.metrics.exposition(),
            content_type="text/plain",
            charset="utf-8",
        )

    # -- flight recorder / span debug endpoints ------------------------------

    def _recorder_or_none(self) -> web.Response | None:
        if self.recorder is not None:
            return None
        return web.json_response(
            {
                "error": "flight recorder disabled; set "
                "spec.tpu.observability.traceRing (--trace-ring) > 0"
            },
            status=404,
        )

    async def _debug_json(self, build) -> web.Response:
        """Build + serialize a debug payload OFF the event loop: a full
        ring renders to megabytes of JSON, and a synchronous dumps here
        would stall /generate, health probes, and SSE mid-debugging —
        observation must not perturb serving."""
        loop = asyncio.get_running_loop()
        text = await loop.run_in_executor(None, lambda: json.dumps(build()))
        return web.Response(text=text, content_type="application/json")

    async def handle_debug_engine(self, request: web.Request) -> web.Response:
        """Live engine snapshot: tick/event/trace rings verbatim."""
        err = self._recorder_or_none()
        if err is not None:
            return err
        return await self._debug_json(self.recorder.snapshot)

    async def handle_debug_trace(self, request: web.Request) -> web.Response:
        """Chrome trace-event export (open in Perfetto: ui.perfetto.dev)."""
        err = self._recorder_or_none()
        if err is not None:
            return err
        fmt = request.query.get("format", "chrome")
        if fmt == "chrome":
            return await self._debug_json(self.recorder.chrome_trace)
        if fmt == "json":
            return await self._debug_json(self.recorder.snapshot)
        return web.json_response(
            {"error": f"unknown format {fmt!r}; use chrome or json"},
            status=400,
        )

    async def handle_debug_device(self, request: web.Request) -> web.Response:
        """Device telemetry snapshot: HBM ledger vs measured memory,
        per-tick-kind utilization, compile observatory (spec.tpu.
        observability.deviceTelemetry; 404 names the knob when off)."""
        if self.telemetry is None:
            return web.json_response(
                {
                    "error": "device telemetry disabled; set "
                    "spec.tpu.observability.deviceTelemetry "
                    "(--device-telemetry 1)"
                },
                status=404,
            )
        return await self._debug_json(self.telemetry.snapshot)

    async def handle_debug_timeseries(
        self, request: web.Request
    ) -> web.Response:
        """Per-second serving time-series ring (the anomaly detector's
        input plane; spec.tpu.observability.timeseriesRing; 404 names
        the knob when off)."""
        if self.timeseries is None:
            return web.json_response(
                {
                    "error": "timeseries ring disabled; set "
                    "spec.tpu.observability.timeseriesRing "
                    "(--timeseries-ring) > 0"
                },
                status=404,
            )
        return await self._debug_json(self.timeseries.snapshot)

    async def handle_debug_spans(self, request: web.Request) -> web.Response:
        """The server's tracer (``utils/tracing.py``): per span name the
        count, total, self time, mean and max — the engine loop's
        ``engine.*`` phases — and the engine's starvation account: when
        the chip had nothing to run, by what it was then given and by
        what the host was doing meanwhile."""
        return web.json_response(self._spans_payload())

    def _spans_payload(self) -> dict:
        tracer = self.metrics.tracer
        return {
            "spans": tracer.as_dict(),
            "device_starved": tracer.account("device_starved").as_dict(),
        }

    async def handle_live(self, request: web.Request) -> web.Response:
        # Live through loading AND draining: kubelet must not kill a pod
        # that is busy finishing its in-flight request tail.
        return web.json_response(
            {"live": self.lifecycle != "shutdown", "lifecycle": self.lifecycle},
            status=200 if self.lifecycle != "shutdown" else 503,
        )

    async def handle_ready(self, request: web.Request) -> web.Response:
        """The lifecycle endpoint (``/readyz``; ``/v2/health/ready`` is
        the same handler, which is what the builder's readiness-probe
        stanza points at): 200 only in the ``ready`` state — loading,
        draining, and shutdown all 503 so balancers route elsewhere —
        with the state named in the body either way."""
        status = 200 if self.lifecycle == "ready" else 503
        body = {"ready": self.lifecycle == "ready", "lifecycle": self.lifecycle}
        if self.fleet_role != "unified":
            body["fleetRole"] = self.fleet_role
        if self.lifecycle == "draining" and self.gen_engine is not None:
            body["inFlight"] = self.gen_engine.inflight()
        if self.attach_fn is not None:
            # Attached-model report (warm-pool replicas only): the
            # multiplexer's bin-packer and the router's known-model sets
            # read WHAT is on the device, not just whether something is.
            body["model"] = self.attached_model_uri
            if self.attached_snapshot_hash is not None:
                body["snapshotHash"] = self.attached_snapshot_hash
        return web.json_response(body, status=status)

    async def handle_admin_drain(self, request: web.Request) -> web.Response:
        """``POST /admin/drain``: the lossless scale-down protocol.

        Stops admissions (new /generate requests shed 429 + Retry-After),
        flips ``/readyz`` to draining, then waits — bounded by
        ``grace_s`` (default ``--drain-grace-seconds``) — for every
        admitted sequence, SSE streams included, to finish.  Returns the
        final state; the caller (autoscaler teardown, preStop hook, an
        operator's kubectl) deletes the pod only after ``drained`` is
        true.  SIGTERM runs the same protocol.
        """
        try:
            body = await request.json() if request.can_read_body else {}
            if not isinstance(body, dict):
                raise ValueError("drain body must be a JSON object")
            grace = float(body.get("grace_s", self.drain_grace_s))
            if not (0.0 <= grace <= 3600.0):
                raise ValueError(
                    f"grace_s must be in [0, 3600], got {grace}"
                )
            cancel = bool(body.get("cancel", False))
        except (ValueError, TypeError, json.JSONDecodeError) as e:
            return web.json_response({"error": str(e)}, status=400)
        if cancel:
            restored = self.cancel_drain()
            return web.json_response(
                {"lifecycle": self.lifecycle, "cancelled": restored},
                status=200 if restored else 409,
            )
        self.begin_drain()
        drained = await self.wait_drained(grace)
        inflight = (
            self.gen_engine.inflight() if self.gen_engine is not None else 0
        )
        return web.json_response(
            {
                "lifecycle": self.lifecycle,
                "drained": drained,
                "inFlight": inflight,
            }
        )

    async def handle_admin_attach(self, request: web.Request) -> web.Response:
        """``POST /admin/attach``: snapshot-restore a model into a
        warm-pool replica (or swap the attached one with ``replace``).

        The warm-pool replica booted with the compile sweep already run
        against the persistent cache, so the attach path is: restore the
        pre-baked device tree (zero transform work) + deserialize the
        pre-baked executables + flip ``/readyz`` — the whole
        ``tpumlops_cold_start_seconds`` ladder minus the pod boot.

        Body: ``{"model_uri": "...", "replace": false,
        "wake_start_wall": <unix-seconds>?}`` — ``wake_start_wall`` is
        stamped by whoever decided to wake the CR, so the ladder's
        ``wake`` stage measures decision → attach receipt.
        """
        if self.attach_fn is None:
            return web.json_response(
                {
                    "error": "not a warm-pool server (boot with "
                    "--warm-pool 1 to attach models at runtime)"
                },
                status=400,
            )
        try:
            body = await request.json() if request.can_read_body else {}
            if not isinstance(body, dict):
                raise ValueError("attach body must be a JSON object")
            model_uri = body.get("model_uri")
            if not model_uri or not isinstance(model_uri, str):
                raise ValueError('attach requires "model_uri"')
            replace = bool(body.get("replace", False))
            wake_start = body.get("wake_start_wall")
            wake_start = float(wake_start) if wake_start is not None else None
        except (ValueError, TypeError, json.JSONDecodeError) as e:
            return web.json_response({"error": str(e)}, status=400)
        if self.terminating or self.lifecycle == "shutdown":
            return web.json_response(
                {"error": "server is terminating"}, status=409
            )
        async with self._attach_lock:
            req_hash, req_geom = self._snapshot_probe(model_uri)
            if (
                self.engine is not None
                and self.attached_model_uri == model_uri
                and req_hash is not None
                and self.attached_snapshot_hash == req_hash
            ):
                # Idempotent no-op: same uri AND same snapshot hash as
                # what is already on the device — a replace here would
                # drain in-flight work to restore identical weights,
                # a pointless swap the bin-packer would otherwise pay
                # on every convergence pass.
                return web.json_response(
                    {
                        "lifecycle": self.lifecycle,
                        "model_uri": model_uri,
                        "snapshot_hash": req_hash,
                        "noop": True,
                    }
                )
            if self.engine is not None and not replace:
                return web.json_response(
                    {
                        "error": "a model is already attached; pass "
                        '"replace": true to swap it',
                        "lifecycle": self.lifecycle,
                    },
                    status=409,
                )
            if (
                self.engine is not None
                and req_geom is not None
                and self._attached_geometry is not None
                and req_geom != self._attached_geometry
            ):
                # Geometry-incompatible replace: the incoming snapshot's
                # model dims differ from what this replica's compile
                # sweep was baked for — an attach would stall in a full
                # recompile, exactly what the warm pool exists to avoid.
                # Typed 409 BEFORE the quiesce: the attached model keeps
                # serving, and the bin-packer routes the swap to a
                # compatible (or empty) replica instead.
                return web.json_response(
                    {
                        "error": (
                            f"snapshot geometry of {model_uri} does not "
                            "match the attached model's compiled "
                            "programs"
                        ),
                        "reason": "geometry_incompatible",
                        "attached_model_uri": self.attached_model_uri,
                        "lifecycle": self.lifecycle,
                    },
                    status=409,
                )
            t_receipt = time.time()
            if wake_start is not None:
                self.metrics.observe_cold_start(
                    "wake", t_receipt - wake_start
                )
            # Local anchor for THIS attach's arithmetic: a request served
            # during the startup await below one-shots (and nulls) the
            # instance field via note_first_token — the ladder's "total"
            # must not race it.
            anchor = wake_start if wake_start is not None else t_receipt
            self._cold_anchor_wall = anchor
            loop = asyncio.get_running_loop()
            old_predictor = self.predictor
            if self.engine is not None:
                # Replace: quiesce the old engine before its tree is
                # freed (attach_fn releases the device buffers).
                if self.batcher is not None:
                    self.batcher.stop()
                if self.gen_engine is not None:
                    self.gen_engine.shutdown()
                self.lifecycle = "loading"
                self.metrics.ready.labels(**self.metrics.identity).set(0)
                self.engine = None
                self.gen_engine = None
                self.attached_model_uri = None
                self.attached_snapshot_hash = None
                self._attached_geometry = None
            try:
                load_stats: dict = {}
                attached = await loop.run_in_executor(
                    None,
                    lambda: self.attach_fn(
                        model_uri, old_predictor, load_stats
                    ),
                )
                self.predictor = attached["predictor"]
                self.gen_engine = attached.get("gen_engine")
                engine = attached["engine"]
                self._wire_batcher(engine)
                self.metrics.observe_model_load(load_stats)
                restored = load_stats.get("restore_s") is not None
                self.metrics.observe_cold_start(
                    "restore" if restored else "load",
                    load_stats.get("restore_s")
                    or load_stats.get("wall_s")
                    or 0.0,
                )
                t_warm = time.time()
                # startup() runs the warmup sweep — against the compile
                # cache the warm-pool boot already primed, so this is
                # executable deserialization, not compilation.
                self.engine = engine
                await loop.run_in_executor(
                    None, lambda: self.startup(warmup=True)
                )
                self.metrics.observe_cold_start(
                    "compile", time.time() - t_warm
                )
                self.metrics.observe_cold_start(
                    "total", time.time() - anchor
                )
                # Re-probe AFTER the load: a first attach of a raw
                # model writes its snapshot during load_predictor, so
                # the identity contract is complete from attach one.
                self.attached_model_uri = model_uri
                (
                    self.attached_snapshot_hash,
                    self._attached_geometry,
                ) = self._snapshot_probe(model_uri)
                if self.timeseries is not None:
                    # Baseline-reset stamp for the anomaly detector:
                    # drift is measured against the post-attach window.
                    self.timeseries.mark("attach")
            except Exception as e:
                _log.exception("attach of %s failed", model_uri)
                # Quiesce whatever got wired before the failure — a
                # half-attached engine left running would leak its
                # worker thread and device tree.
                if self.batcher is not None:
                    with contextlib.suppress(Exception):
                        self.batcher.stop()
                    self.batcher = None
                if self.gen_engine is not None:
                    with contextlib.suppress(Exception):
                        self.gen_engine.shutdown()
                self.engine = None
                self.gen_engine = None
                self.attached_model_uri = None
                self.attached_snapshot_hash = None
                self._attached_geometry = None
                self.lifecycle = "warm-pool"
                return web.json_response(
                    {"error": f"attach failed: {e}"}, status=500
                )
        return web.json_response(
            {
                "lifecycle": self.lifecycle,
                "model_uri": model_uri,
                "snapshot_hash": self.attached_snapshot_hash,
                "restored": restored,
                "load_breakdown_s": load_stats,
            }
        )

    # -- KV handoff (disaggregated prefill/decode fleets) --------------------

    def _kv_engine_or_error(
        self, request: web.Request
    ) -> tuple[object | None, web.Response | None]:
        """Common gating for the KV endpoints: attached causal-LM engine
        with the radix prefix cache on (the handoff unit IS its chunk)."""
        err = self._not_attached(request)
        if err is not None:
            return None, err
        if self.gen_engine is None:
            return None, web.json_response(
                {"error": f"model {self.model_name} is not a causal LM"},
                status=400,
            )
        if getattr(self.gen_engine, "_prefix_cache", None) is None:
            return None, web.json_response(
                {
                    "error": "KV handoff requires the radix prefix cache; "
                    "enable spec.tpu.prefixCache (--prefix-cache 1)",
                    "reason": "prefix_cache_disabled",
                },
                status=409,
            )
        return self.gen_engine, None

    async def handle_admin_kv_export(self, request: web.Request) -> web.Response:
        """``POST /admin/kv/export``: serialize a prompt's committed
        prefix K/V for handoff to a decode replica.

        Body is the generate shape (``{"prompt_ids": [...]}``); the
        response is one ``application/octet-stream`` handoff blob
        (``server/kv_transfer.py`` wire format) covering the prompt's
        whole-chunk prefix.  A prefix not yet in this replica's radix
        cache is prefilled first (one max_new_tokens=1 admission whose
        write-backs populate the cache) — that forward pass is the work
        the decode pool is NOT doing, which is the point."""
        from . import kv_transfer
        from .flight_recorder import RequestTrace

        engine, err = self._kv_engine_or_error(request)
        if err is not None:
            return err
        t0 = time.perf_counter()
        code = 200
        try:
            body = await request.json()
            if not isinstance(body, dict):
                raise ValueError("export body must be a JSON object")
            raw = body.get("prompt_ids")
            if raw is None:
                raise ValueError('export requires "prompt_ids"')
            if raw and not np.isscalar(raw[0]):
                if len(raw) != 1:
                    raise ValueError(
                        "export supports exactly one prompt sequence"
                    )
                raw = raw[0]
            prompt = engine.validate(raw, 1)
            covered = engine.exportable_prefix_tokens(prompt)
            if covered <= 0:
                code = 400
                return web.json_response(
                    {
                        "error": f"prompt of {prompt.size} tokens has no "
                        "whole-chunk prefix to export",
                        "reason": "prompt_too_short",
                    },
                    status=400,
                )
            loop = asyncio.get_running_loop()
            matched, chunks = await loop.run_in_executor(
                None, engine.export_prefix_kv, prompt
            )
            if matched < covered:
                # Cold prefix: prefill it here (write-backs land the
                # chunks in the radix cache), then re-read.  Sheds and
                # validation errors surface as their usual statuses —
                # the router treats any non-200 as "fall back".
                rid = request.get("request_id") or request_id_from_headers(
                    request.headers
                )
                trace = RequestTrace(
                    request_id=rid,
                    trace_id=request.get("trace_id", ""),
                    parent_span=request.get("parent_span", ""),
                )
                fut = engine.submit(
                    prompt, 1, request_id=rid, trace=trace
                )
                await asyncio.wrap_future(fut)
                matched, chunks = await loop.run_in_executor(
                    None, engine.export_prefix_kv, prompt
                )
            if matched <= 0 or not chunks:
                code = 503
                return web.json_response(
                    {
                        "error": "prefix did not land in the radix cache "
                        "(budget too small for the prompt?)",
                        "reason": "export_unavailable",
                        "retry_after_s": 1,
                    },
                    status=503,
                    headers={"Retry-After": "1"},
                )
            blob = await loop.run_in_executor(
                None,
                lambda: kv_transfer.serialize_chunks(
                    engine._prefill_chunk_size, prompt, chunks
                ),
            )
            return web.Response(
                body=blob,
                content_type="application/octet-stream",
                headers={"X-Tpumlops-Kv-Tokens": str(matched)},
            )
        except EngineOverloaded as e:
            code = 429
            body = {
                "error": str(e),
                "reason": e.reason,
                "retry_after_s": e.retry_after_s,
            }
            if e.slo_class is not None:
                body["slo_class"] = e.slo_class
            return web.json_response(
                body,
                status=429,
                headers={"Retry-After": str(e.retry_after_s)},
            )
        except (ValueError, KeyError, TypeError, json.JSONDecodeError) as e:
            code = 400
            return web.json_response({"error": str(e)}, status=400)
        except Exception as e:
            _log.exception("kv export failed")
            code = 500
            return web.json_response({"error": str(e)}, status=500)
        finally:
            self.metrics.observe_request(
                time.perf_counter() - t0, code=code, service="kv-export"
            )

    async def handle_admin_kv_import(self, request: web.Request) -> web.Response:
        """``POST /admin/kv/import``: install a handoff blob into this
        replica's radix prefix cache.

        The blob's geometry (chunk size, K/V shape, dtype) must match
        this engine exactly — a mismatch is a typed 409, never a silent
        cast that would blur the token-for-token handoff parity.  The
        import journals a ``kv-import`` engine tick, so the relayed
        request that follows is reconstructable from ``/debug/trace``."""
        from . import kv_transfer

        engine, err = self._kv_engine_or_error(request)
        if err is not None:
            return err
        t0 = time.perf_counter()
        code = 200
        try:
            blob = await request.read()
            loop = asyncio.get_running_loop()
            try:
                header, chunks = await loop.run_in_executor(
                    None, kv_transfer.deserialize_chunks, blob
                )
            except kv_transfer.KvTransferError as e:
                code = 400
                return web.json_response(
                    {"error": str(e), "reason": "bad_blob"}, status=400
                )
            C = engine._prefill_chunk_size
            cfg = engine._cfg
            expected_shape = [
                cfg.num_layers, 1, C, cfg.num_kv_heads, cfg.head_dim,
            ]
            if int(header["chunk_tokens"]) != C or list(
                header["kv_shape"]
            ) != expected_shape:
                code = 409
                return web.json_response(
                    {
                        "error": f"handoff geometry {header['kv_shape']} "
                        f"@ {header['chunk_tokens']} tokens does not "
                        f"match this engine ({expected_shape} @ {C})",
                        "reason": "geometry_mismatch",
                    },
                    status=409,
                )
            import jax.numpy as jnp

            if kv_transfer._dtype_from_name(
                header["dtype"]
            ) != jnp.dtype(engine._dtype):
                code = 409
                return web.json_response(
                    {
                        "error": f"handoff dtype {header['dtype']} does "
                        f"not match engine dtype "
                        f"{jnp.dtype(engine._dtype).name}",
                        "reason": "dtype_mismatch",
                    },
                    status=409,
                )
            prompt = kv_transfer.chunk_token_ids(header)
            imported = await loop.run_in_executor(
                None, engine.import_prefix_kv, prompt, chunks
            )
            return web.json_response(
                {"imported_tokens": int(imported), "chunks": len(chunks)}
            )
        except (ValueError, KeyError, TypeError) as e:
            code = 400
            return web.json_response({"error": str(e)}, status=400)
        except Exception as e:
            _log.exception("kv import failed")
            code = 500
            return web.json_response({"error": str(e)}, status=500)
        finally:
            self.metrics.observe_request(
                time.perf_counter() - t0, code=code, service="kv-import"
            )

    async def handle_model_metadata(self, request: web.Request) -> web.Response:
        err = self._not_attached(request)
        if err is not None:
            return err
        p = self.engine.predictor
        return web.json_response(
            {
                "name": self.model_name,
                "platform": "tpumlops-jax",
                "flavor": p.name,
                "jittable": p.jittable,
                "metadata": p.metadata,
            }
        )

    # -- app wiring ----------------------------------------------------------

    def build_app(self) -> web.Application:
        app = web.Application(
            client_max_size=256 * 1024 * 1024,
            middlewares=[request_id_middleware],
        )
        name = self.model_name
        app.router.add_get("/v2/health/live", self.handle_live)
        app.router.add_get("/v2/health/ready", self.handle_ready)
        # Canonical lifecycle endpoint — same handler as the V2 ready
        # route above, so the manifest probe and the drain protocol read
        # one truth.
        app.router.add_get("/readyz", self.handle_ready)
        # The router's half-open recovery probes GET /healthz; same
        # handler as /readyz, so a draining/stalled replica (503) is
        # never re-admitted by a probe.
        app.router.add_get("/healthz", self.handle_ready)
        app.router.add_get("/livez", self.handle_live)
        app.router.add_post("/admin/drain", self.handle_admin_drain)
        app.router.add_post("/admin/attach", self.handle_admin_attach)
        app.router.add_get(f"/v2/models/{name}", self.handle_model_metadata)
        app.router.add_get(f"/v2/models/{name}/ready", self.handle_ready)
        app.router.add_post(f"/v2/models/{name}/infer", self.handle_v2_infer)
        if self.gen_engine is not None or self.attach_fn is not None:
            # Warm-pool servers register the generate route up front: the
            # attached model may be a causal LM, and routes cannot be
            # added after the app starts (pre-attach requests get the
            # typed warm_pool_empty 503).
            app.router.add_post(f"/v2/models/{name}/generate", self.handle_generate)
            # KV handoff endpoints (disaggregated fleets): export on
            # prefill replicas, import on decode replicas — registered
            # on every role (the router's role table decides who is
            # asked what; a unified replica can do both).
            app.router.add_post("/admin/kv/export", self.handle_admin_kv_export)
            app.router.add_post("/admin/kv/import", self.handle_admin_kv_import)
        if self.attach_fn is not None:
            # Multiplexed warm pool: the router addresses requests by the
            # CR's model id, which is NOT this replica's boot name — the
            # wildcard routes catch any model id (the router only sends
            # ids whose attachment it has confirmed; the server cannot
            # map CR id -> uri and stays permissive).  Literal routes
            # above win exact matches, so single-model wire behavior is
            # unchanged.  {mux_model} keys the per-model admission share.
            app.router.add_post(
                "/v2/models/{mux_model}/generate", self.handle_generate
            )
            app.router.add_post(
                "/v2/models/{mux_model}/infer", self.handle_v2_infer
            )
            app.router.add_get(
                "/v2/models/{mux_model}/ready", self.handle_ready
            )
        app.router.add_post("/api/v1.0/predictions", self.handle_seldon_predict)
        app.router.add_post("/api/v1.0/feedback", self.handle_feedback)
        app.router.add_get("/metrics", self.handle_metrics)
        app.router.add_post("/debug/profile", self.handle_profile)
        app.router.add_get("/debug/engine", self.handle_debug_engine)
        app.router.add_get("/debug/trace", self.handle_debug_trace)
        app.router.add_get("/debug/spans", self.handle_debug_spans)
        app.router.add_get("/debug/device", self.handle_debug_device)
        app.router.add_get("/debug/timeseries", self.handle_debug_timeseries)

        async def on_shutdown(_app):
            self.shutdown()

        app.on_shutdown.append(on_shutdown)
        return app


async def _write_sse_error(
    resp: web.StreamResponse, request_id: str, reason: str, message: str
) -> None:
    """Terminal SSE ``error`` event: a stream that dies mid-generation
    must end with a typed event (request_id + reason) — a bare dropped
    connection leaves the client unable to distinguish truncation from
    completion.  ``done: true``/``error`` keys are kept so pre-existing
    data-event consumers still terminate cleanly."""
    payload = {
        "done": True,
        "error": message,
        "request_id": request_id,
        "reason": reason,
    }
    await resp.write(
        f"event: error\ndata: {json.dumps(payload)}\n\n".encode()
    )


def _stamp_handoff(request: web.Request, traces) -> None:
    """Relayed-request stamp: the router forwards a request AFTER a
    prefill→decode KV handoff with ``X-Tpumlops-Handoff: <ms>`` (the
    handoff wall it measured).  ``t_handoff`` anchors the relay in this
    process's perf_counter domain; ``handoff_ms`` carries the router's
    cross-process measurement verbatim."""
    hdr = request.headers.get("X-Tpumlops-Handoff")
    if not hdr:
        return
    try:
        hms = float(hdr)
    except ValueError:
        return  # malformed stamp: treat as not relayed, never half-mark
    now = time.perf_counter()
    for tr in traces:
        tr.t_handoff = now
        tr.handoff_ms = hms


def _add_batch_dim(out: Any) -> Any:
    if isinstance(out, tuple):
        return tuple(_add_batch_dim(o) for o in out)
    if isinstance(out, dict):
        return {k: _add_batch_dim(v) for k, v in out.items()}
    return np.asarray(out)[None, ...]


def _slice_batch(out: Any, n: int) -> Any:
    if isinstance(out, tuple):
        return tuple(_slice_batch(o, n) for o in out)
    if isinstance(out, dict):
        return {k: _slice_batch(v, n) for k, v in out.items()}
    return np.asarray(out)[:n]


def _concat_batches(chunks: list[Any]) -> Any:
    if len(chunks) == 1:
        return chunks[0]
    first = chunks[0]
    if isinstance(first, tuple):
        return tuple(
            _concat_batches([c[i] for c in chunks]) for i in range(len(first))
        )
    if isinstance(first, dict):
        return {k: _concat_batches([c[k] for c in chunks]) for k in first}
    return np.concatenate([np.asarray(c) for c in chunks], axis=0)


def _timing_summary(request_id: str, traces) -> dict:
    """Aggregate per-sequence :class:`RequestTrace` blocks into the one
    request-level timing object (``"debug": true`` response field and the
    completion log line).  Totals agree with the Prometheus counters the
    request incremented — asserted in tests/test_server.py."""
    rows = [t.timing_block() for t in traces]
    queue = [r["queue_ms"] for r in rows if r["queue_ms"] is not None]
    ttft = [r["ttft_ms"] for r in rows if r["ttft_ms"] is not None]
    return {
        "request_id": request_id,
        "tokens": sum(r["tokens"] for r in rows),
        "prefill_chunks": sum(r["prefill_chunks"] for r in rows),
        "cached_tokens": sum(r["cached_tokens"] for r in rows),
        "spec_proposed": sum(r["spec_proposed"] for r in rows),
        "spec_accepted": sum(r["spec_accepted"] for r in rows),
        # Worst row's queue wait, best row's TTFT: the spread between
        # them is the packing/admission story for a multi-row request.
        "queue_ms": max(queue) if queue else None,
        "ttft_ms": min(ttft) if ttft else None,
        "finish_reasons": sorted({r["finish_reason"] for r in rows}),
        "rows": rows,
    }


def _to_v2_outputs(out: Any) -> list[dict]:
    if isinstance(out, dict):
        items = list(out.items())
    elif isinstance(out, tuple):
        items = [(f"output_{i}", o) for i, o in enumerate(out)]
    else:
        items = [("output_0", out)]
    v2 = []
    for name, arr in items:
        arr = np.asarray(arr)
        v2.append(
            {
                "name": name,
                "shape": list(arr.shape),
                "datatype": _NP_TO_V2.get(arr.dtype, "FP32"),
                "data": arr.ravel().tolist(),
            }
        )
    return v2


# ---------------------------------------------------------------------------
# CLI (the container entrypoint generated by the manifest builder)
# ---------------------------------------------------------------------------


def _fan(*fns):
    """Chain observer callbacks onto ONE engine hook (the timeseries
    ring rides the metrics callbacks instead of new instrumentation
    points).  None entries drop out; a single survivor is returned
    unwrapped so the common no-ring path stays the bare bound method."""
    live = [f for f in fns if f is not None]
    if not live:
        return None
    if len(live) == 1:
        return live[0]

    def fanned(*args, **kwargs):
        for f in live:
            f(*args, **kwargs)

    return fanned


def make_gen_engine(
    predictor, config: ServerConfig, channel=None, metrics=None,
    recorder=None, telemetry=None, watchdog=None, timeseries=None,
):
    """Construct the GenerationEngine for a causal-LM predictor.

    ONE construction site for leader and followers: lockstep replay needs
    bit-identical slot counts / dtype / kv_quant on every host, so the
    shared knobs must never be spelled twice.
    """
    from ..utils.config import validate_serving_for_family
    from .generation import GenerationEngine

    family = predictor.causal_lm.get("family")
    if family is not None:
        # The engine refuses its own knobs; the fleet role is the server's.
        validate_serving_for_family(
            family.FLAVOR, family.UNSUPPORTED, fleet_role=config.fleet_role
        )
        if metrics and hasattr(family, "state_row_bytes"):
            metrics.set_cache_state_bytes(
                family.state_row_bytes(predictor.causal_lm["cfg"]))
    ts = timeseries  # per-second ring: fans onto the metric callbacks

    prefix_cache = None
    if config.tpu.prefix_cache.enabled:
        from .prefix_cache import PrefixCacheConfig

        # Same spec on leader and followers (this one construction site):
        # the derived prefill-chunk size must agree or lockstep replay
        # runs mismatched chunk shapes.
        prefix_cache = PrefixCacheConfig(
            enabled=True,
            budget_bytes=config.tpu.prefix_cache.budget_mb * 2**20,
            chunk_tokens=config.tpu.prefix_cache.chunk_tokens,
            l2_budget_bytes=config.tpu.prefix_cache.l2_budget_mb * 2**20,
        )
    speculative = None
    if config.tpu.speculative.enabled:
        from .speculative import SpeculativeConfig

        # Same draft geometry on leader and followers (this one
        # construction site): a verify tick is replayed in lockstep, so
        # the compiled (draft length, window) variants must agree.
        speculative = SpeculativeConfig(
            enabled=True,
            draft_tokens=config.tpu.speculative.draft_tokens,
            ngram_min=config.tpu.speculative.ngram_min,
            ngram_max=config.tpu.speculative.ngram_max,
            adaptive=config.tpu.speculative.adaptive,
        )
    return GenerationEngine(
        predictor.causal_lm["params"],
        predictor.causal_lm["cfg"],
        # Default stays latency-first; spec.tpu.maxSlots raises it for
        # throughput (decode re-reads all weights per step — slots
        # amortize that).
        max_slots=config.tpu.max_slots or min(config.tpu.max_batch_size, 8),
        eos_id=predictor.causal_lm.get("eos_id"),
        on_step=_fan(
            metrics.observe_decode_step if metrics else None,
            ts.observe_decode_step if ts else None,
        ),
        on_tokens=metrics.inc_generated_tokens if metrics else None,
        channel=channel,
        kv_quant=config.tpu.quantize == "int8kv",
        prefill_chunk=config.tpu.prefill_chunk,
        prefix_cache=prefix_cache,
        on_prefix_hit=metrics.observe_prefix_hit if metrics else None,
        on_prefix_evict=metrics.inc_prefix_evictions if metrics else None,
        on_prefix_l2=metrics.inc_prefix_l2 if metrics else None,
        speculative=speculative,
        on_spec=metrics.observe_speculative if metrics else None,
        # Fused multi-step decode: same K on leader and followers (this
        # one construction site) — the compiled (K, window) variants
        # must agree for lockstep replay.  1 = single-step loop.
        decode_steps=config.tpu.decode_steps,
        # Unified ragged super-step: same engine kind on leader and
        # followers (this one construction site) — the one-per-tick
        # superstep program must exist on both for lockstep replay.
        unified_step=config.tpu.unified_step,
        on_dispatch=metrics.inc_dispatch if metrics else None,
        on_prefill_tokens=metrics.inc_prefill_tokens if metrics else None,
        on_prefill_dispatch=metrics.inc_prefill_dispatch if metrics else None,
        on_decode_dispatch=metrics.inc_decode_dispatch if metrics else None,
        on_key_blocks=metrics.inc_prefill_key_blocks if metrics else None,
        family=family,
        on_moe=metrics.inc_moe if metrics else None,
        tracer=metrics.tracer if metrics else None,
        # Packed multi-admission prefill: same batch geometry on leader
        # and followers (this one construction site) — the compiled B_p
        # bucket variants must agree for lockstep replay.
        prefill_batch=config.tpu.prefill_batch,
        prefill_token_budget=config.tpu.prefill_token_budget,
        on_prefill_batch=metrics.observe_prefill_batch if metrics else None,
        on_admission_wait=metrics.observe_admission_wait if metrics else None,
        on_ttft=metrics.observe_ttft if metrics else None,
        on_itl=_fan(
            metrics.observe_itl if metrics else None,
            ts.observe_itl if ts else None,
        ),
        on_request_tokens=metrics.observe_request_tokens if metrics else None,
        on_tick=_fan(
            metrics.observe_tick if metrics else None,
            ts.observe_tick if ts else None,
        ),
        # Leader-side only: the scheduler (and so the journal) runs on
        # the leader; follower processes replay device ops blind.
        recorder=recorder,
        # Admission control (leader-side: followers never take
        # submissions): shed past the queued-token budget, 429 upstream.
        admission_queue_budget=config.tpu.admission_queue_budget,
        on_shed=_fan(
            metrics.inc_shed if metrics else None,
            ts.inc_shed if ts else None,
        ),
        # Leader-side only, like the recorder: the ledger/observatory
        # describe the scheduling process; followers replay blind.
        telemetry=telemetry,
        # Leader-side only: the scheduler heartbeat the watchdog
        # monitors runs on the leader; followers block inside replayed
        # collectives by design.
        watchdog=watchdog,
        on_poison=_fan(
            metrics.inc_poison if metrics else None,
            ts.inc_poison if ts else None,
        ),
        # Tensor-parallel mesh: same shape on leader and followers (this
        # one construction site) — sharded programs must agree for
        # lockstep replay.  {"dp": 1, "tp": 1} (the default) arms
        # nothing; the loader already sharded the params over the same
        # device prefix the engine's mesh covers.
        mesh_shape=dict(config.tpu.mesh_shape),
        # sp > 1: cold prompts at/over this length prefill through the
        # ring-attention pass instead of serial chunks.
        sp_prefill_threshold=config.tpu.sp_prefill_threshold,
        # SLO classes + mid-decode preemption: the default class every
        # submit inherits (per-request slo_class overrides) and whether
        # a waiting higher class may evict a lower-class slot at a tick
        # boundary.  Leader-side scheduling, but preemption=True also on
        # followers so the restore program exists for lockstep replay.
        slo_class=config.tpu.slo_class,
        preemption=config.tpu.preemption,
        on_preempt=metrics.inc_preempt if metrics else None,
    )


def prewarm_from_snapshot(config: ServerConfig) -> float | None:
    """Warm-pool boot sweep: compile every engine program from the
    snapshot manifest's *geometry* — a zero-filled tree of the exact
    dtypes/shapes the real weights will have — so the XLA executables
    land in the (persistent) compile cache before any model is attached.
    The zero tree is released afterwards: the replica holds compiled
    programs, not weights.  Best-effort; returns the sweep wall seconds
    or None when there is no snapshot to read geometry from."""
    import numpy as np

    from ..models.registry import get_builder
    from . import snapshot as _snap
    from .loader import (
        _build_config,
        _unflatten,
        release_predictor,
    )

    if not config.tpu.snapshot.enabled:
        return None
    spath = _snap.snapshot_path_for(
        config.tpu.snapshot.dir, config.model_uri
    )
    if not (spath / _snap.MANIFEST_NAME).exists():
        _log.info(
            "warm-pool prewarm skipped: no snapshot at %s yet", spath
        )
        return None
    t0 = time.perf_counter()
    try:
        manifest = _snap.read_manifest(spath)
        if manifest["flavor"] != "llama-generate":
            return None
        flat = {
            leaf["key"]: np.zeros(
                leaf["shape"], dtype=_snap._dtype_from_name(leaf["dtype"])
            )
            for leaf in manifest["leaves"]
        }
        cfg = _build_config(manifest["flavor"], manifest.get("config", {}))
        pred = get_builder(manifest["flavor"])(
            _unflatten(flat),
            **{
                **manifest.get("builder_kwargs", {}),
                **({"cfg": cfg} if cfg is not None else {}),
            },
        )
        gen = make_gen_engine(pred, config)
        try:
            gen.start(warmup=True)
        finally:
            gen.shutdown()
        release_predictor(pred)
        wall = time.perf_counter() - t0
        _log.info(
            "warm-pool prewarm: compile sweep over snapshot geometry "
            "done in %.1fs (programs pre-baked for attach)",
            wall,
        )
        return wall
    except Exception as e:
        _log.warning("warm-pool prewarm failed (attach still works): %s", e)
        return None


def build_server(
    config: ServerConfig,
    warmup: bool = True,
    transport=None,
    wake_start_wall: float | None = None,
    peaks=None,
) -> TpuInferenceServer:
    """Build the leader-side server.

    ``transport`` (a ``multihost.GroupTransport``) makes this process the
    leader of a multi-host predictor unit: every engine call is broadcast
    so follower processes execute it in lockstep (SURVEY §7 hard part 5).
    Single-host units pass None and run the engine directly.

    ``config.warm_pool`` boots the server with NO weights: the compile
    sweep runs against the snapshot manifest's geometry (persistent
    cache primed), and ``POST /admin/attach`` snapshot-restores a model
    on demand.  ``wake_start_wall`` (unix seconds) is the instant the
    controller decided to wake this replica — it anchors the
    ``tpumlops_cold_start_seconds`` ladder's ``wake`` stage.

    ``peaks`` (a ``device_telemetry.DevicePeaks``) is what the device
    telemetry layer divides by; None asks the attached device, which
    must then be one the peaks table knows.
    """
    boot_wall = time.time()
    mesh_shape = dict(config.tpu.mesh_shape)
    snapshot_dir = (
        config.tpu.snapshot.dir if config.tpu.snapshot.enabled else None
    )
    telemetry = None
    if config.tpu.observability.device_telemetry:
        from .device_telemetry import DeviceTelemetry

        # Before load_predictor so even the loader-phase compiles (the
        # streamed quantizer) land in the observatory's journal.
        telemetry = DeviceTelemetry(peaks=peaks)
    metrics = ServerMetrics(
        deployment_name=config.deployment_name or config.model_name,
        predictor_name=config.predictor_name,
        namespace=config.namespace,
        device_telemetry=telemetry is not None,
    )
    if telemetry is not None:
        telemetry.bind_metrics(metrics)
    recorder = None
    if config.tpu.observability.trace_ring > 0:
        from .flight_recorder import FlightRecorder

        recorder = FlightRecorder(config.tpu.observability.trace_ring)
    timeseries = None
    if config.tpu.observability.timeseries_ring > 0:
        from .timeseries import TimeseriesRing

        # Leader-side only, like the recorder: the callback stream it
        # distills runs on the scheduling leader; followers replay blind.
        timeseries = TimeseriesRing(config.tpu.observability.timeseries_ring)
        if telemetry is not None:
            # MFU / HBM-bandwidth per bucket come from the telemetry
            # layer's existing last_util gauge — no new hook.
            timeseries.bind_telemetry(telemetry)
    watchdog = None
    if config.watchdog_deadline_s > 0:
        from .watchdog import EngineWatchdog

        # Leader-side only, like the recorder: followers block inside
        # replayed collectives by design, and the leader's escalation
        # (process exit -> pod restart) tears the whole unit down.
        watchdog = EngineWatchdog(
            deadline_s=config.watchdog_deadline_s,
            grace_s=config.watchdog_grace_s,
            on_age=metrics.set_watchdog_tick_age,
        )

    def _build_engines(predictor, channel=None):
        engine = InferenceEngine(
            predictor,
            max_batch_size=config.tpu.max_batch_size,
            on_compile=lambda: metrics.compilations.labels(
                **metrics.identity
            ).inc(),
            warmup_full_grid=config.tpu.warmup_full_grid,
        )
        gen_engine = None
        if predictor.causal_lm is not None:
            # On a multi-host unit the scheduler runs leader-side only;
            # every device call is broadcast on the unit's channel so
            # followers replay it in lockstep (their GenerationEngine is
            # built in main()'s follower path, driven by follower_loop).
            gen_engine = make_gen_engine(
                predictor, config, channel=channel, metrics=metrics,
                recorder=recorder, telemetry=telemetry, watchdog=watchdog,
                timeseries=timeseries,
            )
        return engine, gen_engine

    if config.warm_pool:
        if transport is not None:
            raise ValueError(
                "--warm-pool is single-host only (a multi-host unit "
                "cannot attach weights after its process group formed)"
            )

        def attach_fn(model_uri, old_predictor, load_stats):
            predictor = load_predictor(
                model_uri,
                mesh_shape=mesh_shape,
                quantize=config.tpu.quantize,
                load_stats=load_stats,
                snapshot_dir=snapshot_dir,
                release_first=old_predictor,
            )
            engine, gen_engine = _build_engines(predictor)
            return {
                "predictor": predictor,
                "engine": engine,
                "gen_engine": gen_engine,
            }

        server = TpuInferenceServer(
            None,
            metrics,
            model_name=config.model_name,
            max_batch_size=config.tpu.max_batch_size,
            max_batch_delay_ms=config.tpu.max_batch_delay_ms,
            max_inflight_batches=config.tpu.max_inflight_batches,
            recorder=recorder,
            drain_grace_s=config.tpu.drain_grace_s,
            telemetry=telemetry,
            attach_fn=attach_fn,
            fleet_role=config.fleet_role,
            snapshot_dir=snapshot_dir,
            timeseries=timeseries,
        )
        if watchdog is not None:
            watchdog.on_stall = server.note_watchdog_stall
            watchdog.on_recover = server.note_watchdog_recover
        if warmup:
            prewarm_from_snapshot(config)
        server.startup(warmup=False)  # lifecycle -> "warm-pool"
        return server

    load_stats: dict = {}
    predictor = load_predictor(
        config.model_uri,
        mesh_shape=mesh_shape,
        quantize=config.tpu.quantize,
        load_stats=load_stats,
        snapshot_dir=snapshot_dir,
    )
    engine = InferenceEngine(
        predictor,
        max_batch_size=config.tpu.max_batch_size,
        on_compile=lambda: metrics.compilations.labels(
            **metrics.identity
        ).inc(),
        warmup_full_grid=config.tpu.warmup_full_grid,
    )
    channel = None
    if transport is not None:
        from .multihost import MultihostEngine

        engine = MultihostEngine(engine, transport)
        channel = engine.channel
    gen_engine = None
    if predictor.causal_lm is not None:
        gen_engine = make_gen_engine(
            predictor, config, channel=channel, metrics=metrics,
            recorder=recorder, telemetry=telemetry, watchdog=watchdog,
            timeseries=timeseries,
        )
    metrics.observe_model_load(load_stats)
    restored = load_stats.get("restore_s") is not None
    anchor = wake_start_wall if wake_start_wall is not None else boot_wall
    if wake_start_wall is not None:
        metrics.observe_cold_start("wake", boot_wall - wake_start_wall)
    if load_stats:
        metrics.observe_cold_start(
            "restore" if restored else "load",
            load_stats.get("restore_s") or load_stats.get("wall_s") or 0.0,
        )
    server = TpuInferenceServer(
        engine,
        metrics,
        model_name=config.model_name,
        max_batch_size=config.tpu.max_batch_size,
        max_batch_delay_ms=config.tpu.max_batch_delay_ms,
        gen_engine=gen_engine,
        max_inflight_batches=config.tpu.max_inflight_batches,
        recorder=recorder,
        drain_grace_s=config.tpu.drain_grace_s,
        telemetry=telemetry,
        cold_start_anchor_wall=anchor,
        fleet_role=config.fleet_role,
        timeseries=timeseries,
    )
    server.predictor = predictor
    if watchdog is not None:
        # Wire the readiness/journal callbacks BEFORE startup arms the
        # monitor — a stall must never fire into unassigned hooks.
        watchdog.on_stall = server.note_watchdog_stall
        watchdog.on_recover = server.note_watchdog_recover
    t_warm = time.time()
    server.startup(warmup=warmup)
    metrics.observe_cold_start("compile", time.time() - t_warm)
    metrics.observe_cold_start("total", time.time() - anchor)
    if timeseries is not None:
        # Baseline anchor for the anomaly detector: samples before this
        # mark are warmup noise, not serving behavior.
        timeseries.mark("warmup")
    return server


def _serve_follower_health(host: str, port: int) -> None:
    """Minimal live/ready listener for follower pods (daemon thread).

    The StatefulSet template shares one readinessProbe across the unit;
    followers answer it here so they don't sit NotReady forever."""
    import threading

    def run() -> None:
        async def ok(_request: web.Request) -> web.Response:
            return web.json_response({"role": "follower", "ok": True})

        app = web.Application()
        app.router.add_get("/v2/health/live", ok)
        app.router.add_get("/v2/health/ready", ok)
        app.router.add_get("/healthz", ok)
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        loop.run_until_complete(web.TCPSite(runner, host, port).start())
        loop.run_forever()

    threading.Thread(target=run, daemon=True, name="follower-health").start()


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser("tpumlops-server")
    ap.add_argument("--model-uri", required=True)
    ap.add_argument("--model-name", default="model")
    ap.add_argument("--predictor-name", default="v1")
    ap.add_argument("--deployment-name", default="")
    ap.add_argument("--namespace", default="default")
    ap.add_argument("--mesh-shape", default='{"dp": 1, "tp": 1}')
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--max-batch-size", type=int, default=32)
    ap.add_argument("--max-batch-delay-ms", type=float, default=5.0)
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=9000)
    ap.add_argument(
        "--metrics-port",
        type=int,
        default=6000,
        help="dedicated /metrics listener (matches the manifest's metrics "
        "containerPort); 0 disables the second listener",
    )
    ap.add_argument(
        "--drain-s",
        type=float,
        default=3.0,
        help="seconds to keep serving (NotReady) after SIGTERM before "
        "the in-flight drain begins, so rolling steps don't 503 the "
        "request tail still being routed here",
    )
    ap.add_argument(
        "--admission-queue-budget",
        type=int,
        default=0,
        help="estimated-token bound (prompt + max_new) on queued-but-"
        "unadmitted generation work; beyond it /generate sheds with "
        "429 + Retry-After (tpumlops_engine_shed_total counts them). "
        "0 = unbounded (the pre-admission-control behavior)",
    )
    ap.add_argument(
        "--drain-grace-seconds",
        type=float,
        default=20.0,
        help="lossless-drain window: seconds SIGTERM / POST /admin/drain "
        "waits for in-flight sequences (SSE streams included) to finish "
        "after admissions stop, before teardown",
    )
    ap.add_argument(
        "--prefill-chunk",
        type=int,
        default=0,
        help="chunked prefill size (0 = whole-prompt); long prompts stop "
        "stalling in-flight decode streams",
    )
    ap.add_argument(
        "--prefill-batch",
        type=int,
        default=1,
        help="concurrent admissions whose next prompt chunks batch into "
        "ONE prefill call per tick (amortizes the weight stream under "
        "bursty load; 1 = single-admission pipeline, requires "
        "--prefill-chunk or --prefix-cache when > 1)",
    )
    ap.add_argument(
        "--prefill-token-budget",
        type=int,
        default=0,
        help="prompt tokens prefilled per engine tick, Sarathi-style "
        "(0 = uncapped); bounds decode-cadence jitter under long-prompt "
        "bursts",
    )
    ap.add_argument(
        "--sp-prefill-threshold",
        type=int,
        default=1024,
        help="prompt length at/over which a cold prompt prefills via the "
        "sequence-parallel ring-attention pass (effective only when "
        "meshShape carries sp > 1)",
    )
    ap.add_argument(
        "--prefix-cache",
        type=int,
        default=0,
        help="1 enables the radix prefix KV cache (shared prompt prefixes "
        "prefill once and are copied thereafter)",
    )
    ap.add_argument(
        "--prefix-cache-budget-mb",
        type=int,
        default=256,
        help="host-memory byte budget for cached prefix K/V (LRU eviction)",
    )
    ap.add_argument(
        "--prefix-cache-chunk",
        type=int,
        default=0,
        help="prefix reuse unit in tokens (0 = follow --prefill-chunk, or "
        "64 when that is unset too); an explicit mismatch with "
        "--prefill-chunk is rejected at startup",
    )
    ap.add_argument(
        "--prefix-cache-l2-budget-mb",
        type=int,
        default=0,
        help="second-tier host-RAM pool for evicted prefix chunks (LRU "
        "under this budget, promoted back on a radix-walk miss); 0 "
        "(default) = single-tier behavior byte-for-byte",
    )
    ap.add_argument(
        "--fleet-role",
        default="unified",
        choices=["unified", "prefill", "decode"],
        help="disaggregated-fleet role of this replica (advisory: "
        "surfaced on /readyz and logs; the router's role-tagged backend "
        "table decides who is asked to export/import KV)",
    )
    ap.add_argument(
        "--speculative",
        type=int,
        default=0,
        help="1 enables self-speculative n-gram decoding (draft from the "
        "sequence's own history, verify k+1 positions per weight stream; "
        "greedy-exact output)",
    )
    ap.add_argument(
        "--speculative-draft-tokens",
        type=int,
        default=4,
        help="max draft tokens per slot per verify tick",
    )
    ap.add_argument(
        "--speculative-ngram-min",
        type=int,
        default=1,
        help="shortest history suffix the n-gram drafter may match",
    )
    ap.add_argument(
        "--speculative-ngram-max",
        type=int,
        default=4,
        help="longest history suffix tried first",
    )
    ap.add_argument(
        "--speculative-adaptive",
        type=int,
        default=1,
        help="1: per-slot draft length halves on consecutive zero-accept "
        "verifies and regrows on success; 0: fixed draft length",
    )
    ap.add_argument(
        "--decode-steps",
        type=int,
        default=1,
        help="decode iterations fused into ONE device dispatch per tick "
        "(lax.scan with on-device sampling + EOS latch, lag-1 async "
        "token readback; engages only when no admissions or drafts are "
        "pending).  1 = the single-step tick loop; max 16",
    )
    ap.add_argument(
        "--unified-step",
        type=int,
        default=0,
        help="1: unified ragged super-step engine — ONE jit program per "
        "tick covers packed-prefill chunks, fused-K decode, and "
        "speculative verify via per-row role tensors, collapsing the "
        "warmup sweep to (window-bucket x sampling-mode) variants; "
        "0 (default) keeps the split-program engine byte-for-byte",
    )
    ap.add_argument(
        "--quantize",
        default="none",
        choices=["none", "int8", "int8kv"],
        help="int8: weight-only; int8kv: weights + KV cache "
        "(halves decode HBM traffic twice over)",
    )
    ap.add_argument(
        "--snapshot-dir",
        default="",
        help="pre-baked weight snapshot directory (server/snapshot.py): "
        "the post-shard, post-quantize device tree is baked here after "
        "the first cold load and restored on later boots/attaches with "
        "zero transform work (scale-to-zero fast path); empty disables",
    )
    ap.add_argument(
        "--warm-pool",
        type=int,
        default=0,
        help="1 boots a warm-pool replica: no weights, compile sweep run "
        "against the snapshot manifest's geometry (persistent cache "
        "primed), POST /admin/attach snapshot-restores a model on "
        "demand; requires --snapshot-dir",
    )
    ap.add_argument(
        "--compile-cache-dir",
        default=None,
        help="persistent XLA compile cache (SURVEY §7 hard part 3); "
        "JAX_COMPILATION_CACHE_DIR, when set, places it and wins; unset "
        "and no flag = the fixed in-checkout default "
        "(utils/compile_cache.py); empty string disables",
    )
    ap.add_argument(
        "--trace-ring",
        type=int,
        default=0,
        help="engine flight-recorder ring size (ticks/events/requests "
        "kept in memory, served at /debug/engine and /debug/trace); "
        "0 disables recording entirely (the default — zero overhead)",
    )
    ap.add_argument(
        "--timeseries-ring",
        type=int,
        default=0,
        help="per-second serving time-series ring size (seconds of "
        "history kept: tick-wall quantiles, ITL, queue depth, MFU/HBM "
        "bandwidth, shed/poison counts; served at /debug/timeseries — "
        "the operator anomaly detector's input plane); 0 disables the "
        "ring entirely (the default — zero overhead)",
    )
    ap.add_argument(
        "--device-telemetry",
        type=int,
        default=0,
        help="1 enables the device telemetry layer: analytic HBM ledger "
        "(GET /debug/device, tpumlops_device_hbm_bytes), per-op compile "
        "observatory (tpumlops_compile_*), and per-tick MFU/HBM-bandwidth "
        "utilization gauges + recorder fields; 0 (default) constructs "
        "none of it",
    )
    ap.add_argument(
        "--watchdog-deadline-s",
        type=float,
        default=0.0,
        help="scheduler-tick watchdog deadline: a device dispatch "
        "blocking past this flips /readyz unready and journals a "
        "watchdog event (tpumlops_engine_watchdog_stalls_total); armed "
        "only after warmup.  0 (default) disables the monitor entirely",
    )
    ap.add_argument(
        "--watchdog-grace-s",
        type=float,
        default=30.0,
        help="grace past the watchdog deadline before the process exits "
        "non-zero so Kubernetes restarts the pod (a restart is the only "
        "remedy for a wedged device)",
    )
    ap.add_argument(
        "--slo-class",
        default="",
        help="default SLO class for requests that don't carry one "
        "(interactive | batch | best-effort); arms the priority "
        "admission queues — higher classes drain first and lower "
        "classes shed at a fraction of the admission budget",
    )
    ap.add_argument(
        "--preemption",
        type=int,
        default=0,
        help="1: a waiting higher-class request may evict a lower-class "
        "slot at a tick boundary (KV parked in the prefix cache, "
        "restored on re-admission with no lost work); requires "
        "--prefix-cache 1",
    )
    ap.add_argument(
        "--log-format",
        default="text",
        choices=["text", "json"],
        help="json: one JSON object per log line carrying request_id, so "
        "per-request completion lines are machine-parseable",
    )
    args = ap.parse_args(argv)
    from ..utils.logging import configure as configure_logging

    configure_logging(json_format=args.log_format == "json")

    from ..parallel.distributed import maybe_initialize_distributed
    from ..utils.compile_cache import (
        enable_persistent_compile_cache,
        resolve_compile_cache_dir,
    )

    maybe_initialize_distributed()
    # Before any jit trace (warmup included), so even the first-ever
    # compile of each batch bucket is persisted for the next pod.
    enable_persistent_compile_cache(
        resolve_compile_cache_dir(args.compile_cache_dir)
    )

    config = ServerConfig(
        model_name=args.model_name,
        model_uri=args.model_uri,
        predictor_name=args.predictor_name,
        deployment_name=args.deployment_name or args.model_name,
        namespace=args.namespace,
        host=args.host,
        port=args.port,
        tpu=TpuSpec.from_spec(
            {
                "meshShape": json.loads(args.mesh_shape),
                "dtype": args.dtype,
                "maxBatchSize": args.max_batch_size,
                "maxBatchDelayMs": args.max_batch_delay_ms,
                "quantize": args.quantize,
                "prefillChunk": args.prefill_chunk or None,
                "prefillBatch": args.prefill_batch,
                "prefillTokenBudget": args.prefill_token_budget,
                "spPrefillThreshold": args.sp_prefill_threshold,
                "prefixCache": {
                    "enabled": bool(args.prefix_cache),
                    "budgetMB": args.prefix_cache_budget_mb,
                    "chunkTokens": args.prefix_cache_chunk or None,
                    "l2BudgetMB": args.prefix_cache_l2_budget_mb,
                },
                "speculative": {
                    "enabled": bool(args.speculative),
                    "draftTokens": args.speculative_draft_tokens,
                    "ngramMin": args.speculative_ngram_min,
                    "ngramMax": args.speculative_ngram_max,
                    "adaptive": bool(args.speculative_adaptive),
                },
                "decodeSteps": args.decode_steps,
                "unifiedStep": bool(args.unified_step),
                "observability": {
                    "traceRing": args.trace_ring,
                    "deviceTelemetry": bool(args.device_telemetry),
                    "timeseriesRing": args.timeseries_ring,
                },
                "admissionQueueBudget": args.admission_queue_budget,
                "drainGraceSeconds": args.drain_grace_seconds,
                **({"sloClass": args.slo_class} if args.slo_class else {}),
                "preemption": bool(args.preemption),
                "snapshot": {
                    "enabled": bool(args.snapshot_dir),
                    **(
                        {"dir": args.snapshot_dir}
                        if args.snapshot_dir
                        else {}
                    ),
                },
            }
        ),
        warm_pool=bool(args.warm_pool),
        fleet_role=args.fleet_role,
        watchdog_deadline_s=args.watchdog_deadline_s,
        watchdog_grace_s=args.watchdog_grace_s,
    )
    if config.warm_pool and not config.tpu.snapshot.enabled:
        ap.error("--warm-pool requires --snapshot-dir")
    if config.fleet_role != "unified" and not config.tpu.prefix_cache.enabled:
        ap.error(
            "--fleet-role prefill/decode requires --prefix-cache 1 "
            "(KV handoff moves radix prefix-cache chunks)"
        )

    import jax  # deferred: process topology is meaningful only after init

    if jax.process_count() > 1:
        from .multihost import JaxProcessTransport, follower_loop

        transport = JaxProcessTransport()
        if not transport.is_leader:
            # Follower pod of a multi-host predictor unit: no inference
            # frontend, but it must still answer the unit's shared
            # readiness probe — joining the process group (init returned)
            # IS follower-readiness.  Then execute the leader's broadcast
            # steps until it shuts the unit down.
            _serve_follower_health(config.host, config.port)
            predictor = load_predictor(
                args.model_uri,
                mesh_shape=dict(config.tpu.mesh_shape),
                quantize=config.tpu.quantize,
            )
            engine = InferenceEngine(
                predictor,
                max_batch_size=config.tpu.max_batch_size,
                warmup_full_grid=config.tpu.warmup_full_grid,
            )
            gen_engine = None
            if predictor.causal_lm is not None:
                # Not started: driven entirely by replayed leader ops.
                gen_engine = make_gen_engine(predictor, config)
            _log.info("follower process %d ready", jax.process_index())
            follower_loop(engine, transport, gen_engine=gen_engine)
            return
    else:
        transport = None

    # Stamped by whoever decided to wake this replica (the operator's
    # scale-from-zero path / LocalReplicaSet): anchors the
    # tpumlops_cold_start_seconds ladder's "wake" stage.
    wake_env = os.environ.get("TPUMLOPS_WAKE_START_WALL")
    server = build_server(
        config,
        transport=transport,
        wake_start_wall=float(wake_env) if wake_env else None,
    )

    async def _serve() -> None:
        runner = web.AppRunner(server.build_app())
        await runner.setup()
        await web.TCPSite(runner, config.host, config.port).start()
        if args.metrics_port:
            # Dedicated /metrics listener on the manifest's metrics port.
            metrics_app = web.Application()
            metrics_app.router.add_get("/metrics", server.handle_metrics)
            mrunner = web.AppRunner(metrics_app)
            await mrunner.setup()
            await web.TCPSite(mrunner, config.host, args.metrics_port).start()
        _log.info(
            "serving on %s:%d (metrics on %s)",
            config.host,
            config.port,
            args.metrics_port or f"{config.port}/metrics",
        )
        # Kubernetes terminates pods with SIGTERM, not Ctrl-C: without a
        # handler the multi-host leader would die before broadcasting
        # OP_SHUTDOWN and its followers would block out their whole grace
        # period in a dead collective.
        import signal

        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(sig, stop.set)
            except (NotImplementedError, RuntimeError):  # non-main thread
                pass
        await stop.wait()
        # Lossless drain before teardown, in two phases.
        #
        # Phase 1 (--drain-s): keep ADMITTING while NotReady.  Kubernetes
        # removes a Terminating pod from endpoints asynchronously, so for
        # a short window traffic is still routed here; without accepting
        # that tail every rolling canary step 503s it, which the gate
        # reads as an error-rate spike on whichever version was being
        # replaced.
        server.ready = False
        _log.info(
            "termination signal; endpoint lag %.1fs before drain",
            args.drain_s,
        )
        await asyncio.sleep(max(0.0, args.drain_s))
        # Phase 2 (--drain-grace-seconds): stop admissions — new
        # /generate requests shed 429 + Retry-After so clients go to
        # another replica — and wait for every admitted sequence (SSE
        # streams included) to finish.  Scale-down and rollout teardown
        # never drop a request.
        server.terminating = True  # a committed exit: cancel refused
        server.begin_drain()
        drained = await server.wait_drained(args.drain_grace_seconds)
        if not drained and server.gen_engine is not None:
            _log.warning(
                "drain grace %.1fs expired with %d sequence(s) in flight",
                args.drain_grace_seconds,
                server.gen_engine.inflight(),
            )
        await runner.cleanup()  # fires on_shutdown -> server.shutdown()

    try:
        asyncio.run(_serve())
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()  # idempotent; covers non-signal exits


if __name__ == "__main__":  # pragma: no cover
    main()
