"""Metric-identity contract (SURVEY §7 hard part 4).

The promotion gate's PromQL — and every dashboard, alert, and the
canary-judge queries built on it — reads these exact family names and
label sets.  prometheus_client would happily accept a rename and the
gate would then read 0 through its ``or on() vector(0)`` fallback, which
is the worst failure mode: green dashboards over a blind gate.  This
test snapshots the full inventory of both registries so an accidental
rename (or label drop) fails HERE, in tier-1.  The catalog test at the
end holds docs/OBSERVABILITY.md's series tables to the same inventory
(``make metrics-catalog`` is its command-line form).

Names below are prometheus_client *family* names (``describe()``):
Counters declared with a ``_total`` suffix appear stripped here and
re-gain ``_total`` in the exposition; Counters declared without one
(e.g. ``tpumlops_prefix_cache_hits``) gain ``_total`` only at export.

Intentional renames are fine — update the snapshot AND the PromQL that
reads the series (operator/judge.py, docs/OBSERVABILITY.md) in the same
commit.
"""

import importlib.util
from pathlib import Path

import pytest
from prometheus_client.metrics import MetricWrapperBase

from tpumlops.operator.telemetry import OperatorTelemetry
from tpumlops.server.metrics import ServerMetrics, _SpanCollector

_IDENT = ("deployment_name", "predictor_name", "namespace")

EXPECTED_SERVER = {
    "seldon_api_executor_client_requests_seconds": ("histogram", _IDENT),
    "seldon_api_executor_server_requests_seconds": (
        "histogram", _IDENT + ("code", "service")),
    "tpumlops_admission_wait_ms": ("histogram", _IDENT),
    "tpumlops_batch_run_seconds": ("histogram", _IDENT),
    "tpumlops_batch_size": ("histogram", _IDENT),
    "tpumlops_compilations": ("counter", _IDENT),
    "tpumlops_decode_batch_size": ("histogram", _IDENT),
    "tpumlops_engine_active_slots": ("gauge", _IDENT),
    "tpumlops_engine_admitting": ("gauge", _IDENT),
    "tpumlops_engine_queue_depth": ("gauge", _IDENT),
    # Engine device dispatches by tick kind (decode/verify/multistep/
    # prefill/packed-prefill/seed); exported as
    # tpumlops_engine_dispatches_total.  With generated_tokens this is
    # the dispatches-per-token amortization series the fused multi-step
    # path (spec.tpu.decodeSteps) collapses ~K-fold.
    "tpumlops_engine_dispatches": ("counter", _IDENT + ("op",)),
    # Admission control: sheds by typed reason ("budget" | "draining" |
    # "class_<slo class>" for per-class budget sheds); exported as
    # tpumlops_engine_shed_total.  The autoscaler's alert surface for
    # "replica refusing load".
    "tpumlops_engine_shed": ("counter", _IDENT + ("reason",)),
    # Mid-decode preemption (spec.tpu.preemption): evict/restore event
    # pairs; exported as tpumlops_engine_preempt_total.  No samples
    # unless preemption is armed.
    "tpumlops_engine_preempt": ("counter", _IDENT + ("event",)),
    # Failure containment (PR 13): scheduler-watchdog stalls + heartbeat
    # age (0 while disarmed — the families exist so dashboards are
    # uniform across fleets with and without --watchdog-deadline-s), and
    # the always-on poison-request quarantine (fingerprints quarantined
    # after repeated admission crashes; typed-422 refusals).
    "tpumlops_engine_watchdog_stalls": ("counter", _IDENT),
    "tpumlops_engine_watchdog_last_tick_age_seconds": ("gauge", _IDENT),
    "tpumlops_engine_poison_quarantined": ("counter", _IDENT),
    "tpumlops_engine_poison_rejected": ("counter", _IDENT),
    "tpumlops_feedback_reward_total": ("gauge", _IDENT),
    "tpumlops_generated_tokens": ("counter", _IDENT),
    "tpumlops_itl_seconds": ("histogram", _IDENT),
    # Model-load stage breakdown (loader load_stats made first-party):
    # disk/transfer/quantize/shard, restore on the snapshot path, total.
    "tpumlops_model_load_seconds": ("gauge", _IDENT + ("stage",)),
    # Scale-to-zero cold-start ladder: wake/load|restore/compile/
    # first_token/total of the most recent boot or /admin/attach.
    "tpumlops_cold_start_seconds": ("gauge", _IDENT + ("stage",)),
    "tpumlops_model_ready": ("gauge", _IDENT),
    "tpumlops_pipeline_wait_seconds": ("histogram", _IDENT),
    "tpumlops_prefill_batch_fill": ("histogram", _IDENT),
    # Real prompt tokens prefilled (cached-prefix tokens excluded);
    # exported as tpumlops_prefill_tokens_total.
    "tpumlops_prefill_tokens": ("counter", _IDENT),
    # Chunk programs of the single-admission path by where the engine
    # dispatched them ("ahead": right behind the pass's decode step |
    # "in_turn": in the admit phase).
    "tpumlops_prefill_dispatch": ("counter", _IDENT + ("when",)),
    # Plain decode steps by where the engine dispatched them ("ahead":
    # behind the step still in flight | "in_turn": with none in flight).
    "tpumlops_decode_dispatch": ("counter", _IDENT + ("when",)),
    # Key blocks of the capacity a prefill chunk of the latent-attention
    # family multiplied ("walked") and did not reach ("skipped").
    "tpumlops_prefill_key_blocks": ("counter", _IDENT + ("kind",)),
    # (query, key) pairs the attention cores multiplied and those inside
    # the causal and window masks, by program and layer kind (full |
    # sliding), for a family that says so.
    "tpumlops_attn_pairs_computed": ("counter", _IDENT + ("program", "kind")),
    "tpumlops_attn_pairs_attended": ("counter", _IDENT + ("program", "kind")),
    # Engine on_token stamp -> the SSE event's write returning.
    "tpumlops_emit_lag_seconds": ("histogram", _IDENT),
    # Routed-expert traffic of a sparse-expert family by program (prefill
    # | decode): (token, expert) pairs, and experts that got a real token.
    "tpumlops_moe_assignments": ("counter", _IDENT + ("program",)),
    "tpumlops_moe_expert_activations": ("counter", _IDENT + ("program",)),
    # An expert share: the pairs routed to experts held elsewhere.
    "tpumlops_moe_assignments_routed_away": ("counter", _IDENT + ("program",)),
    # Indexed sparse attention: positions scored and kept, by program.
    "tpumlops_dsa_keys_scored": ("counter", _IDENT + ("program",)),
    "tpumlops_dsa_keys_selected": ("counter", _IDENT + ("program",)),
    # A linear-attention family's recurrent state: real tokens folded in
    # and rows whose state a call read and wrote, by program; the bytes of
    # state a cache slot holds.
    "tpumlops_gdn_tokens": ("counter", _IDENT + ("program",)),
    "tpumlops_gdn_state_passes": ("counter", _IDENT + ("program",)),
    "tpumlops_cache_state_bytes": ("gauge", _IDENT),
    # (expert, row tile) visits of the grouped matmuls' schedule, and the
    # static rows a visit multiplies: assignments / (visits x rows) is how
    # full the tiles are.
    "tpumlops_moe_row_tile_visits": ("counter", _IDENT + ("program",)),
    "tpumlops_moe_row_tile_rows": ("gauge", _IDENT + ("program",)),
    # utils/tracing.py span stats, rendered at scrape time by one custom
    # collector (no per-span prometheus call); exported with _total.
    "tpumlops_span_seconds": ("counter", _IDENT + ("span",)),
    "tpumlops_span_self_seconds": ("counter", _IDENT + ("span",)),
    "tpumlops_spans": ("counter", _IDENT + ("span",)),
    # The engine's starvation account (tracer.account("device_starved")),
    # rendered by the same collector: seconds and intervals the chip had
    # nothing to run, by the tick program dispatched at their end, and the
    # same seconds by the engine.* span whose self time covered them.
    "tpumlops_device_starved_seconds": ("counter", _IDENT + ("before",)),
    "tpumlops_device_starved_intervals": ("counter", _IDENT + ("before",)),
    "tpumlops_device_starved_by_span_seconds": ("counter", _IDENT + ("span",)),
    "tpumlops_prefix_cache_cached_tokens": ("counter", _IDENT),
    "tpumlops_prefix_cache_evictions": ("counter", _IDENT),
    "tpumlops_prefix_cache_hits": ("counter", _IDENT),
    # Second-tier (host-RAM) prefix cache (prefixCache.l2BudgetMB):
    # spills caught from L1 eviction, promote-back hits, LRU age-outs.
    "tpumlops_prefix_cache_l2_evictions": ("counter", _IDENT),
    "tpumlops_prefix_cache_l2_hits": ("counter", _IDENT),
    "tpumlops_prefix_cache_l2_spills": ("counter", _IDENT),
    "tpumlops_queue_seconds": ("histogram", _IDENT),
    "tpumlops_request_tokens": ("histogram", _IDENT),
    "tpumlops_spec_acceptance_rate": ("histogram", _IDENT),
    "tpumlops_spec_accepted_len": ("histogram", _IDENT),
    "tpumlops_spec_accepted_tokens": ("counter", _IDENT),
    "tpumlops_spec_proposed_tokens": ("counter", _IDENT),
    "tpumlops_tick_seconds": ("histogram", _IDENT + ("kind",)),
    "tpumlops_ttft_seconds": ("histogram", _IDENT),
}

# Device telemetry layer (spec.tpu.observability.deviceTelemetry): these
# families exist ONLY when the registry is built with
# device_telemetry=True — even an unobserved labeled family adds
# HELP/TYPE lines to the exposition, and the disabled contract is
# byte-for-byte (pinned below).
EXPECTED_SERVER_DEVICE = {
    **EXPECTED_SERVER,
    "tpumlops_device_hbm_bytes": ("gauge", _IDENT + ("component",)),
    "tpumlops_device_mfu": ("gauge", _IDENT + ("kind",)),
    "tpumlops_device_hbm_bw_util": ("gauge", _IDENT + ("kind",)),
    # Tensor-parallel serving: analytic ICI collective walls per engine
    # dispatch (op = all_reduce | all_gather); exported as
    # tpumlops_engine_collective_seconds_total.  No samples at tp == 1.
    "tpumlops_engine_collective_seconds": ("counter", _IDENT + ("op",)),
    "tpumlops_compile_seconds": ("counter", _IDENT + ("op",)),
    "tpumlops_compile_cache_hits": ("counter", _IDENT),
    "tpumlops_compile_cache_misses": ("counter", _IDENT),
}

_OP_IDENT = ("namespace", "name")

EXPECTED_OPERATOR = {
    # Fleet anomaly observatory (spec.anomaly; operator/anomaly.py) —
    # no samples until a CR arms the detector.
    "tpumlops_operator_anomaly_active": ("gauge", _OP_IDENT + ("kind",)),
    "tpumlops_operator_anomaly_events": (
        "counter", _OP_IDENT + ("kind",)),
    # Replica autoscaler (operator/autoscaler.py): controlled + wanted
    # counts, applied scalings by direction, holds by typed reason.
    "tpumlops_operator_autoscale_desired_replicas": ("gauge", _OP_IDENT),
    "tpumlops_operator_autoscale_events": (
        "counter", _OP_IDENT + ("direction",)),
    "tpumlops_operator_autoscale_holds": (
        "counter", _OP_IDENT + ("reason",)),
    "tpumlops_operator_autoscale_replicas": ("gauge", _OP_IDENT),
    "tpumlops_operator_events": ("counter", _OP_IDENT + ("reason",)),
    "tpumlops_operator_gate_attempt": ("gauge", _OP_IDENT),
    "tpumlops_operator_gate_evaluations": (
        "counter", _OP_IDENT + ("result",)),
    "tpumlops_operator_gate_margin": ("gauge", _OP_IDENT + ("check",)),
    # Multi-model multiplexing (spec.multiplex; operator/multiplexer.py)
    # — no samples until a CR joins a shared pool.
    "tpumlops_operator_mux_moves": ("counter", _OP_IDENT + ("action",)),
    "tpumlops_operator_mux_parked_requests": ("gauge", _OP_IDENT),
    "tpumlops_operator_phase": ("gauge", _OP_IDENT + ("phase",)),
    "tpumlops_operator_promotions": ("counter", _OP_IDENT + ("outcome",)),
    "tpumlops_operator_reconcile": ("counter", _OP_IDENT + ("result",)),
    "tpumlops_operator_reconcile_seconds": ("histogram", _OP_IDENT),
    "tpumlops_operator_resources": ("gauge", ()),
    "tpumlops_operator_rollout_duration_seconds": ("histogram", _OP_IDENT),
    # SLO error-budget accounting (spec.slo; operator/slo.py) — no
    # samples until a CR configures spec.slo.
    "tpumlops_operator_slo_attainment": ("gauge", _OP_IDENT + ("slo",)),
    "tpumlops_operator_slo_burn_rate": ("gauge", _OP_IDENT + ("slo",)),
    "tpumlops_operator_slo_error_budget_remaining": (
        "gauge", _OP_IDENT + ("slo",)),
    "tpumlops_operator_step_component_seconds": (
        "histogram", _OP_IDENT + ("component",)),
    "tpumlops_operator_traffic_percent": ("gauge", _OP_IDENT),
}


def _inventory(obj) -> dict:
    out = {}
    for attr in vars(obj).values():
        if isinstance(attr, MetricWrapperBase):
            fam = attr.describe()[0]
            out[fam.name] = (fam.type, tuple(attr._labelnames))
        elif isinstance(attr, _SpanCollector):
            for fam in attr.collect():
                out[fam.name] = (fam.type, tuple(fam._labelnames))
    return out


def test_server_metric_families_are_pinned():
    metrics = ServerMetrics(
        deployment_name="d", predictor_name="p", namespace="n"
    )
    assert _inventory(metrics) == EXPECTED_SERVER


def test_server_metric_families_with_device_telemetry():
    metrics = ServerMetrics(
        deployment_name="d", predictor_name="p", namespace="n",
        device_telemetry=True,
    )
    assert _inventory(metrics) == EXPECTED_SERVER_DEVICE


def test_device_telemetry_families_absent_from_disabled_exposition():
    """The disabled registry's exposition must not even carry the
    HELP/TYPE headers of the device families — byte-for-byte means no
    new lines, not just no new samples."""
    metrics = ServerMetrics(
        deployment_name="d", predictor_name="p", namespace="n"
    )
    text = metrics.exposition().decode()
    # (``tpumlops_device_starved_*`` is the always-on starvation account,
    # not device telemetry: it shares a prefix, not a switch.)
    for family in set(EXPECTED_SERVER_DEVICE) - set(EXPECTED_SERVER):
        assert family not in text
    assert "tpumlops_compile_" not in text


def test_operator_metric_families_are_pinned():
    assert _inventory(OperatorTelemetry()) == EXPECTED_OPERATOR


def test_router_fleet_series_pinned():
    """The router's first-party series are emitted by native/router.cc,
    not prometheus_client — pin the full family inventory against a live
    binary so a rename there fails HERE too (the affinity/handoff
    dashboards in docs/OBSERVABILITY.md read these exact names)."""
    import socket
    import time

    from tpumlops.clients.router import RouterProcess, parse_prometheus_text

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    router = RouterProcess(port=port, backends={}, deployment="d",
                           namespace="n").start()
    try:
        names = set()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not names:
            parsed = parse_prometheus_text(router.admin.metrics_text())
            names = {
                name.replace("_bucket", "").replace("_sum", "")
                .replace("_count", "")
                for name, _ in parsed
            }
        # Per-BACKEND families (seldon_api_executor_*) emit only once a
        # backend exists; their identity is pinned in tests/
        # test_router.py.  This set is the backend-independent surface.
        assert names == {
            "tpumlops_router_proxied_total",
            "tpumlops_router_parked_requests",
            "tpumlops_router_parked_total",
            "tpumlops_router_park_released_total",
            "tpumlops_router_park_overflow_total",
            "tpumlops_router_park_timeouts_total",
            "tpumlops_router_park_wait_seconds",
            # Disaggregated fleets: prefix-affinity ring + KV handoff.
            "tpumlops_router_affinity_hits",
            "tpumlops_router_affinity_misses",
            "tpumlops_router_kv_handoff_bytes",
            "tpumlops_router_kv_handoff_failures",
            "tpumlops_router_kv_handoff_seconds",
            # Failure containment: failover re-dispatches + half-open
            # probe walls (deployment-scoped; backend_healthy /
            # circuit_open_total are per-backend and pinned in
            # tests/test_router.py).
            "tpumlops_router_failover_total",
            "tpumlops_router_probe_seconds",
        }
        # With the default config the fleet trace plane's family must be
        # absent even as a header — byte-for-byte exposition at
        # --journey-ring 0.
        assert "tpumlops_router_request_seconds" not in (
            router.admin.metrics_text()
        )
    finally:
        router.stop()


def test_router_journey_family_pinned_when_ring_on():
    """--journey-ring N adds exactly ONE new family —
    tpumlops_router_request_seconds{outcome} — visible before any
    traffic (docs/OBSERVABILITY.md catalogs it by this name)."""
    import socket
    import time

    from tpumlops.clients.router import RouterProcess, parse_prometheus_text

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    router = RouterProcess(port=port, backends={}, deployment="d",
                           namespace="n", journey_ring=16).start()
    try:
        names = set()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not names:
            parsed = parse_prometheus_text(router.admin.metrics_text())
            names = {
                name.replace("_bucket", "").replace("_sum", "")
                .replace("_count", "")
                for name, _ in parsed
            }
        base = {
            "tpumlops_router_proxied_total",
            "tpumlops_router_parked_requests",
            "tpumlops_router_parked_total",
            "tpumlops_router_park_released_total",
            "tpumlops_router_park_overflow_total",
            "tpumlops_router_park_timeouts_total",
            "tpumlops_router_park_wait_seconds",
            "tpumlops_router_affinity_hits",
            "tpumlops_router_affinity_misses",
            "tpumlops_router_kv_handoff_bytes",
            "tpumlops_router_kv_handoff_failures",
            "tpumlops_router_kv_handoff_seconds",
            "tpumlops_router_failover_total",
            "tpumlops_router_probe_seconds",
        }
        assert names == base | {"tpumlops_router_request_seconds"}
        # The outcome label rides every sample of the new family.
        parsed = parse_prometheus_text(router.admin.metrics_text())
        outcome_series = [
            dict(labels)
            for name, labels in parsed
            if name.startswith("tpumlops_router_request_seconds")
        ]
        assert outcome_series and all(
            "outcome" in labels for labels in outcome_series
        )
    finally:
        router.stop()


def test_gate_series_present_in_exposition():
    """The two families the gate's PromQL reads directly
    (mlflow_operator.py:367,:375) must appear in the exposition with
    their identity labels even before any traffic."""
    metrics = ServerMetrics(
        deployment_name="d", predictor_name="p", namespace="n"
    )
    metrics.observe_request(0.01, code=200)
    text = metrics.exposition().decode()
    assert (
        'seldon_api_executor_client_requests_seconds_count{'
        'deployment_name="d",namespace="n",predictor_name="p"}' in text
    )
    assert "seldon_api_executor_server_requests_seconds_count{" in text
    assert 'code="200"' in text


def test_router_mux_family_pinned_when_mux_on():
    """--mux-models 1 adds exactly ONE new family —
    tpumlops_router_model_backends{model} (usable replicas per attached
    model) — and the parked gauge's samples gain the model label; both
    are the bin-packer's observability surface (docs/SCALE.md).  The
    mux-OFF surface is pinned byte-for-byte by
    test_router_fleet_series_pinned above."""
    import socket
    import time

    from tpumlops.clients.router import RouterProcess, parse_prometheus_text

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        bport = s.getsockname()[1]  # never connected: identity only
    router = RouterProcess(port=port, backends={}, deployment="d",
                           namespace="n", mux_models=1).start()
    try:
        router.admin.set_config(
            [{"name": "v1", "host": "127.0.0.1", "port": bport,
              "weight": 100, "model": "llm-a"}]
        )
        names = set()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not names:
            parsed = parse_prometheus_text(router.admin.metrics_text())
            names = {
                name.replace("_bucket", "").replace("_sum", "")
                .replace("_count", "")
                for name, _ in parsed
                if name.startswith("tpumlops_router_")
            }
        base = {
            "tpumlops_router_proxied_total",
            "tpumlops_router_parked_requests",
            "tpumlops_router_parked_total",
            "tpumlops_router_park_released_total",
            "tpumlops_router_park_overflow_total",
            "tpumlops_router_park_timeouts_total",
            "tpumlops_router_park_wait_seconds",
            "tpumlops_router_affinity_hits",
            "tpumlops_router_affinity_misses",
            "tpumlops_router_kv_handoff_bytes",
            "tpumlops_router_kv_handoff_failures",
            "tpumlops_router_kv_handoff_seconds",
            "tpumlops_router_failover_total",
            "tpumlops_router_probe_seconds",
            # Per-backend containment families: present because this
            # test configures a backend (identity pinned in
            # tests/test_router.py), not because of mux.
            "tpumlops_router_backend_healthy",
            "tpumlops_router_circuit_open_total",
        }
        assert names == base | {"tpumlops_router_model_backends"}
        parsed = parse_prometheus_text(router.admin.metrics_text())
        model_series = [
            dict(labels)
            for name, labels in parsed
            if name == "tpumlops_router_model_backends"
        ]
        assert model_series and all(
            labels["model"] == "llm-a" for labels in model_series
        )
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# docs/OBSERVABILITY.md's series catalog against what each plane exports
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def catalog():
    """scripts/check_metrics_catalog.py's own readers: (exported, documented)."""
    path = Path(__file__).resolve().parent.parent / "scripts" / "check_metrics_catalog.py"
    spec = importlib.util.spec_from_file_location("check_metrics_catalog", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.exported_families(), module.doc_families()


@pytest.mark.parametrize(
    "direction", ["exported_not_documented", "documented_not_exported"]
)
@pytest.mark.parametrize("plane", ["server", "operator", "router"])
def test_series_catalog_matches_the_exporters(catalog, plane, direction):
    exported, documented = catalog
    if direction == "exported_not_documented":
        stray = exported[plane] - documented[plane]
    else:
        stray = documented[plane] - exported[plane]
    assert not stray, f"{plane}, {direction.replace('_', ' ')}: {sorted(stray)}"
