"""Device telemetry layer (server/device_telemetry.py).

Unit coverage for the HBM ledger arithmetic, the analytic cost model,
and the compile observatory's attribution — plus the disabled-path
byte-identity contract: with ``deviceTelemetry`` off, engine tick
records, the Chrome trace export, the metrics exposition, and the built
manifest are byte-for-byte what they were before this layer existed.
The live-HTTP e2e (ledger vs measured, per-tick MFU, Perfetto counter
track) lives in tests/test_flight_recorder.py.
"""

import json
import logging
import time

import jax
import jax.numpy as jnp
import pytest

from tpumlops.models import llama
from tpumlops.server.device_telemetry import (
    CompileObservatory,
    DeviceTelemetry,
    LlamaCostModel,
    build_hbm_ledger,
    capacity_log_line,
    cost_from_analysis,
    detect_peaks,
    kv_cache_bytes_per_row,
    weights_bytes_by_dtype,
)
from tpumlops.server.flight_recorder import FlightRecorder


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(0), cfg)
    return params, cfg


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------


def test_weights_bytes_by_dtype_totals_match_tree(tiny):
    params, _ = tiny
    by_dtype = weights_bytes_by_dtype(params)
    total = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(params)
    )
    assert sum(by_dtype.values()) == total
    assert all(v > 0 for v in by_dtype.values())


def test_kv_bytes_per_row_bf16_and_int8kv(tiny):
    _, cfg = tiny
    elems = cfg.num_layers * cfg.num_kv_heads * cfg.max_seq * cfg.head_dim
    assert kv_cache_bytes_per_row(cfg, kv_quant=False) == 2 * elems * 2
    # int8 values + one f32 scale per head_dim group, k and v each.
    assert kv_cache_bytes_per_row(cfg, kv_quant=True) == 2 * (
        elems + (elems // cfg.head_dim) * 4
    )


def test_ledger_components_and_rows(tiny, cpu_peaks):
    params, cfg = tiny
    ledger = build_hbm_ledger(
        params, cfg, max_slots=4, prefix_cache_budget_bytes=7 * 2**20
    )
    comps = ledger.components
    assert comps["kv_cache"] == 4 * ledger.kv_bytes_per_row
    assert comps["sampling_state"] > 0
    assert any(k.startswith("weights_") for k in comps)
    # Host budget rides along but never counts toward the device total.
    assert ledger.host_components == {"prefix_cache_budget": 7 * 2**20}
    assert ledger.device_total() == sum(comps.values())
    # Capacity planning: rows scale with spare HBM, never negative.
    assert ledger.max_cache_rows(2**34) > 4
    assert ledger.max_cache_rows(0) == 0
    snap = json.loads(json.dumps(ledger.snapshot(cpu_peaks)))
    assert snap["device_total_bytes"] == ledger.device_total()
    assert snap["max_cache_rows"] >= 0


def test_capacity_log_line_has_the_planning_facts(tiny, cpu_peaks):
    params, cfg = tiny
    line = capacity_log_line(params, cfg, kv_quant=False, peaks=cpu_peaks)
    assert line.startswith("model capacity: weights ")
    assert "B/row" in line and "max cache rows" in line
    assert f"max_seq {cfg.max_seq}" in line
    assert "test test-cpu" in line  # the peaks it priced rows against
    assert "int8kv" in capacity_log_line(
        params, cfg, kv_quant=True, peaks=cpu_peaks
    )
    # No peaks row for this device kind: the line keeps the
    # device-independent facts and says what it did not compute.
    bare = capacity_log_line(params, cfg, kv_quant=False)
    assert bare.startswith("model capacity: weights ")
    assert "max cache rows not computed (device kind 'cpu'" in bare


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------


def test_cost_model_decode_scales_with_window_and_s(tiny):
    params, cfg = tiny
    cost = LlamaCostModel.for_model(params, cfg)
    f1, b1 = cost.decode(4, 64)
    f2, b2 = cost.decode(4, 128)
    assert f2 > f1 and b2 > b1  # attention term grows with the window
    fv, bv = cost.decode(4, 64, s=3)
    assert fv > 2.9 * f1  # verify: ~s x the matmul work
    # Every program streams the whole weight tree at least once.
    assert b1 > cost.weight_bytes
    fp, bp = cost.prefill(2, 16, attended=40.0)
    assert fp > 0 and bp > cost.weight_bytes
    fs, bs = cost.seed(32)
    assert fs == 0.0 and bs > 0


def test_cost_from_analysis_parses_xla_shapes():
    d = {"flops": 123.0, "bytes accessed": 456.0, "utilization0{}": 1.0}
    assert cost_from_analysis(d) == (123.0, 456.0)
    assert cost_from_analysis([d]) == (123.0, 456.0)  # older jax: 1-list
    assert cost_from_analysis({}) is None
    assert cost_from_analysis(None) is None
    assert cost_from_analysis([]) is None


def test_cost_model_vs_real_cost_analysis(tiny):
    """The analytic decode FLOPs should agree with XLA's own
    cost_analysis on the dominant matmul term (same order of magnitude;
    XLA counts exact fused ops, the model counts 2*params + attention)."""
    params, cfg = tiny
    cost = LlamaCostModel.for_model(params, cfg)
    x = jnp.ones((4, cfg.hidden_size), jnp.float32)
    w = jnp.ones((cfg.hidden_size, cfg.vocab_size), jnp.float32)
    compiled = jax.jit(lambda a, b: a @ b).lower(x, w).compile()
    parsed = cost_from_analysis(compiled.cost_analysis())
    assert parsed is not None
    flops, _ = parsed
    assert flops == pytest.approx(2 * 4 * cfg.hidden_size * cfg.vocab_size,
                                  rel=0.01)


# ---------------------------------------------------------------------------
# Compile observatory
# ---------------------------------------------------------------------------


def test_observatory_attributes_compiles_to_wrapped_op():
    obs = CompileObservatory()

    def fake_jit(x):
        # Simulate the monitoring listener firing mid-dispatch.
        obs.on_event("cache_miss")
        obs.on_event("compile", 0.25)
        return x + 1

    wrapped = obs.wrap_jit("decode", fake_jit)
    assert wrapped(41) == 42
    snap = obs.snapshot()
    assert snap["ops"]["decode"]["compiles"] == 1
    assert snap["ops"]["decode"]["seconds"] == pytest.approx(0.25)
    assert snap["ops"]["decode"]["cache_misses"] == 1
    assert snap["events"][-1]["op"] == "decode"
    # Outside any wrapper, events attribute to "other".
    obs.on_event("compile", 0.1)
    assert obs.snapshot()["ops"]["other"]["compiles"] == 1


def test_observatory_warns_past_readiness_budget(caplog):
    obs = CompileObservatory(readiness_budget_s=0.0)
    obs.begin_warmup()
    obs.on_event("compile", 0.5)
    time.sleep(0.01)
    with caplog.at_level(
        logging.WARNING, logger="tpumlops.device_telemetry"
    ):
        report = obs.end_warmup()
    assert report["compiles"] == 1
    assert report["wall_s"] > 0
    assert any("readiness budget" in r.getMessage() for r in caplog.records)


def test_tick_util_clamps_to_unit_interval(cpu_peaks):
    tel = DeviceTelemetry(peaks=cpu_peaks)
    hot = tel.tick_util("decode", 1e-9, 1e30, 1e30)
    assert hot == {"mfu": 1.0, "hbm_bw_util": 1.0}
    cold = tel.tick_util("decode", 10.0, 1.0, 1.0)
    assert 0.0 < cold["mfu"] <= 1.0
    assert 0.0 < cold["hbm_bw_util"] <= 1.0
    zero = tel.tick_util("seed", 0.01, 0.0, 1e6)
    assert zero["mfu"] == 0.0  # a pure copy has no FLOPs
    snap = tel.snapshot()
    assert set(snap["utilization"]) == {"decode", "seed"}
    assert snap["peaks"]["flops_per_s"] > 0


def test_known_kind_detected_unknown_kind_raises():
    from tpumlops.server.device_telemetry import (
        UnknownDeviceKind,
        peaks_for,
    )

    # What jax reports for a v5e chip.
    peaks = peaks_for("TPU v5 lite")
    assert peaks.source == "detected" and peaks.kind == "tpu-v5e"
    assert peaks.flops_per_s == 197e12 and peaks.hbm_bytes_per_s == 819e9
    assert peaks.int8_ops_per_s == 394e12 and peaks.hbm_bytes == 16 * 2**30
    # No default row: this process's device (cpu) is not a known part.
    with pytest.raises(UnknownDeviceKind, match="'cpu'") as exc:
        detect_peaks()
    assert exc.value.kind == "cpu"
    with pytest.raises(UnknownDeviceKind, match="TPU v9"):
        peaks_for("TPU v9")
    with pytest.raises(UnknownDeviceKind):
        DeviceTelemetry()


# ---------------------------------------------------------------------------
# Engine integration (telemetry ON)
# ---------------------------------------------------------------------------


def test_engine_ticks_carry_utilization_with_telemetry(tiny, cpu_peaks):
    from tpumlops.server.generation import GenerationEngine

    params, cfg = tiny
    telemetry = DeviceTelemetry(peaks=cpu_peaks)
    recorder = FlightRecorder(256)
    engine = GenerationEngine(
        params, cfg, max_slots=2, telemetry=telemetry, recorder=recorder,
        prefill_chunk=16,
    )
    engine.start(warmup=True)
    try:
        out = engine.generate([1, 2, 3], 5)
        assert out.size == 5
    finally:
        engine.shutdown()
    # Observation changes no token: the same engine with no ledger, no
    # cost model, no wrapped jits and no recorder emits the same five.
    plain = GenerationEngine(params, cfg, max_slots=2, prefill_chunk=16)
    plain.start(warmup=False)
    try:
        assert plain.generate([1, 2, 3], 5).tolist() == out.tolist()
    finally:
        plain.shutdown()
    # Ledger + cost model attached with the engine's real geometry.
    assert telemetry.ledger is not None
    assert telemetry.ledger.max_slots == 2
    assert telemetry.cost is not None
    # Every decode/prefill tick carries MFU and bandwidth in (0, 1].
    ticks = recorder.snapshot()["ticks"]
    kinds = {t["kind"] for t in ticks if "mfu" in t}
    assert {"decode", "prefill"} <= kinds
    # (The chunked-mode final INSERT tick carries no cost by design —
    # it is a sampling-state install, not a weight stream.)
    for t in ticks:
        if "mfu" in t:
            assert 0.0 < t["mfu"] <= 1.0, t
            assert 0.0 < t["hbm_bw_util"] <= 1.0, t
    # The Chrome export grew the utilization counter track.
    counters = [
        e for e in recorder.chrome_trace()["traceEvents"] if e["ph"] == "C"
    ]
    assert counters
    assert {e["name"] for e in counters} == {"mfu", "hbm_bw_util"}
    # The warmup sweep was observed and attributed.
    comp = telemetry.observatory.snapshot()
    assert comp["warmup"].get("compiles", 0) > 0
    assert "decode" in comp["ops"]


# ---------------------------------------------------------------------------
# Disabled path: byte-for-byte
# ---------------------------------------------------------------------------


def test_tick_record_keys_unchanged_without_util():
    rec = FlightRecorder(8)
    rec.tick("decode", time.perf_counter(), 0.001, active_slots=1, tokens=1)
    (tick,) = rec.snapshot()["ticks"]
    assert set(tick) == {
        "ts_us", "dur_us", "kind", "active_slots", "queue_depth",
        "batch_fill", "tokens", "spec_accepted",
    }
    assert not [
        e for e in rec.chrome_trace()["traceEvents"] if e["ph"] == "C"
    ]


def test_metrics_exposition_unchanged_when_disabled():
    from tpumlops.server.metrics import ServerMetrics

    off = ServerMetrics("d", "p", "n")
    assert off.device_hbm_bytes is None
    text = off.exposition().decode()
    # (``tpumlops_device_starved_*`` is the always-on starvation account:
    # it shares the prefix, not the switch.)
    for family in ("tpumlops_device_hbm", "tpumlops_device_mfu"):
        assert family not in text
    assert "tpumlops_compile_" not in text

    on = ServerMetrics("d", "p", "n", device_telemetry=True)
    on.observe_hbm_component("kv_cache", 123)
    on.observe_device_util("decode", 0.5, 0.6)
    on.observe_compile("decode", 1.5)
    on.observe_compile_cache(True)
    on.observe_compile_cache(False)
    text = on.exposition().decode()
    assert 'tpumlops_device_hbm_bytes{component="kv_cache"' in text
    assert 'tpumlops_device_mfu{' in text
    assert 'tpumlops_compile_seconds_total{' in text
    assert "tpumlops_compile_cache_hits_total{" in text
    assert "tpumlops_compile_cache_misses_total{" in text


def test_builder_manifest_unchanged_when_disabled():
    from tpumlops.operator.builder import build_deployment
    from tpumlops.utils.config import OperatorConfig

    base_spec = {
        "modelName": "m", "modelAlias": "prod", "backend": "tpu",
        "tpu": {"tpuTopology": "v5e-1", "meshShape": {"tp": 1}},
    }
    explicit_off = json.loads(json.dumps(base_spec))
    explicit_off["tpu"]["observability"] = {"deviceTelemetry": False}
    kw = dict(
        name="m", namespace="ns", owner_uid="u",
        current_version="1", new_model_uri="s3://b/m",
        traffic_current=100,
    )
    plain = build_deployment(
        config=OperatorConfig.from_spec(base_spec), **kw
    )
    off = build_deployment(
        config=OperatorConfig.from_spec(explicit_off), **kw
    )
    assert plain == off
    args = plain["spec"]["predictors"][0]["componentSpecs"][0]["spec"][
        "containers"
    ][0]["args"]
    assert "--device-telemetry" not in args

    enabled_spec = json.loads(json.dumps(base_spec))
    enabled_spec["tpu"]["observability"] = {"deviceTelemetry": True}
    on = build_deployment(
        config=OperatorConfig.from_spec(enabled_spec), **kw
    )
    args_on = on["spec"]["predictors"][0]["componentSpecs"][0]["spec"][
        "containers"
    ][0]["args"]
    assert args_on[-2:] == ["--device-telemetry", "1"]


def test_observability_spec_parses_and_rejects_unknown_keys():
    from tpumlops.utils.config import ObservabilitySpec

    spec = ObservabilitySpec.from_spec(
        {"traceRing": 64, "deviceTelemetry": True}
    )
    assert spec.trace_ring == 64 and spec.device_telemetry is True
    assert ObservabilitySpec.from_spec({}).device_telemetry is False
    with pytest.raises(ValueError, match="deviceTelemtry"):
        ObservabilitySpec.from_spec({"deviceTelemtry": True})


def test_capacity_status_summary_gated_on_device_telemetry():
    from tpumlops.operator.reconciler import _capacity_summary
    from tpumlops.utils.config import OperatorConfig

    base = {
        "modelName": "m", "modelAlias": "prod", "backend": "tpu",
        "tpu": {"tpuTopology": "v5e-8", "meshShape": {"tp": 8}},
    }
    assert _capacity_summary(OperatorConfig.from_spec(base)) is None

    on = json.loads(json.dumps(base))
    on["tpu"]["observability"] = {"deviceTelemetry": True}
    cap = _capacity_summary(OperatorConfig.from_spec(on))
    assert cap == {
        "topology": "v5e-8",
        "chips": 8,
        "hosts": 1,
        "meshShape": {"tp": 8},
        "tensorParallel": 8,
        "quantize": "none",
        "deviceTelemetry": True,
        "hbmGiBPerChip": 16,
        "hbmGiBTotal": 128,
    }

    seldon = json.loads(json.dumps(on))
    seldon["backend"] = "seldon"
    assert _capacity_summary(OperatorConfig.from_spec(seldon)) is None


def test_engine_without_telemetry_has_no_cost_hooks(tiny):
    """The default engine carries None everywhere the telemetry would
    hook — no wrapped jits, no cost computation on any tick path."""
    from tpumlops.server.generation import GenerationEngine

    params, cfg = tiny
    engine = GenerationEngine(params, cfg, max_slots=2)
    try:
        assert engine._telemetry is None
        assert engine._cost_decode(64) is None
        assert engine._cost_prefill(1, 16) is None
        assert engine._cost_seed(16) is None
        assert not hasattr(engine, "_sync_ticks")
    finally:
        engine.shutdown()


def test_status_capacity_appears_and_clears_with_spec_toggle():
    """Reconciler-level round trip: enabling deviceTelemetry surfaces
    status.capacity on the next steady-state step; disabling it clears
    the key with one explicit-null patch; off-from-birth CRs never see
    the key at all (byte-for-byte status)."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from test_reconciler import cr_ref, make_world, reconcile

    tpu_spec = {
        "backend": "tpu",
        "tpu": {"tpuTopology": "v5e-1", "meshShape": {"tp": 1}},
    }
    kube, registry, metrics, clock, rec = make_world(spec_extra=tpu_spec)
    reconcile(kube, rec)
    assert "capacity" not in (kube.get(cr_ref()).get("status") or {})

    obj = kube.get(cr_ref())
    obj["spec"]["tpu"]["observability"] = {"deviceTelemetry": True}
    rec.reconcile(obj)
    cap = kube.get(cr_ref())["status"]["capacity"]
    assert cap["topology"] == "v5e-1" and cap["chips"] == 1
    assert cap["hbmGiBPerChip"] == 16 and cap["deviceTelemetry"] is True

    # Steady state with the key in place: no further churn needed, the
    # summary just persists (recomputed each step from spec).
    obj = kube.get(cr_ref())
    obj["spec"]["tpu"]["observability"] = {"deviceTelemetry": True}
    rec.reconcile(obj)
    assert kube.get(cr_ref())["status"]["capacity"] == cap

    obj = kube.get(cr_ref())
    obj["spec"]["tpu"]["observability"] = {"deviceTelemetry": False}
    rec.reconcile(obj)
    assert kube.get(cr_ref())["status"].get("capacity") is None


def test_peaks_scale_to_param_device_set(tiny, cpu_peaks):
    """The cost model and ledger count the WHOLE sharded model, so the
    peaks must cover the device set holding it — and re-attaching must
    never compound the scaling."""
    from tpumlops.server.device_telemetry import param_device_count

    params, cfg = tiny
    base = cpu_peaks
    s = base.scaled(8)
    assert s.chips == 8
    assert s.flops_per_s == base.flops_per_s * 8
    assert s.hbm_bytes == base.hbm_bytes * 8
    assert param_device_count(params) == 1  # unsharded tree

    tel = DeviceTelemetry(peaks=cpu_peaks)
    tel.attach_model(params, cfg, 2)
    assert tel.peaks.chips == 1
    tel.attach_model(params, cfg, 2)  # idempotent, never compounds
    assert tel.peaks.flops_per_s == base.flops_per_s


def test_param_device_count_sees_real_sharding():
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from tpumlops.server.device_telemetry import param_device_count

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices (conftest provides 8 on CPU)")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("x",))
    arr = jax.device_put(
        jnp.zeros((4, 8)), NamedSharding(mesh, PartitionSpec("x"))
    )
    assert param_device_count({"w": arr}) == 2


def test_config_error_step_leaves_capacity_untouched():
    """A transient spec typo in an UNRELATED field must not wipe
    status.capacity — the summary still reflects the last valid spec."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from test_reconciler import cr_ref, make_world

    tpu_spec = {
        "backend": "tpu",
        "tpu": {
            "tpuTopology": "v5e-1",
            "meshShape": {"tp": 1},
            "observability": {"deviceTelemetry": True},
        },
    }
    kube, registry, metrics, clock, rec = make_world(spec_extra=tpu_spec)
    rec.reconcile(kube.get(cr_ref()))
    cap = kube.get(cr_ref())["status"]["capacity"]
    assert cap["deviceTelemetry"] is True

    bad = kube.get(cr_ref())
    bad["spec"]["autoscaling"] = {"enabled": True, "minReplicas": 5,
                                  "maxReplicas": 1}
    out = rec.reconcile(bad)
    assert out.state.error  # the config error surfaced on status
    assert kube.get(cr_ref())["status"]["capacity"] == cap  # untouched

    good = kube.get(cr_ref())
    good["spec"].pop("autoscaling", None)  # the bad edit was in-memory
    rec.reconcile(good)
    assert kube.get(cr_ref())["status"]["capacity"] == cap
