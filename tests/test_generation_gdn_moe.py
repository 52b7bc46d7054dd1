"""The serving engine with the linear-attention family: the same
scheduler, slot cache, chunked prefill and insert as the other two
families, reached through the predictor's ``causal_lm["family"]`` handle
(greedy tokens against the plain reference are in
tests/test_models_qwen3_next.py); here what the family's programs lack is
refused typed and for the family's own reason, and the loader, the HBM
ledger's state line, the cost model and the ``tpumlops_gdn_*`` /
``tpumlops_cache_state_bytes`` metric families."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumlops.models import gdn_moe, mla_moe
from tpumlops.server.generation import GenerationEngine
from tpumlops.utils.config import (
    UnsupportedForFamily,
    validate_serving_for_family,
)

CFG = gdn_moe.GdnMoeConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return gdn_moe.init(jax.random.key(1), CFG, jnp.float32)


UNSUPPORTED = [
    ({"kv_quant": True}, "int8"),
    ({"mesh_shape": {"dp": 1, "tp": 2}}, "more than one chip"),
    ({"mesh_shape": {"sp": 2}}, "ring prefill"),
    ({"speculative": "on"}, "no function of a position.*does not undo"),
    ({"prefix_cache": "on", "prefill_chunk": 8},
     "no function of a position.*snapshot of it at a chunk boundary"),
    ({"preemption": True}, "prefix cache and preemption"),
    ({"prefill_batch": 2, "prefill_chunk": 8}, "packed"),
    ({"decode_steps": 4}, "multi-step"),
    ({"unified_step": True}, "super-step"),
]


@pytest.mark.parametrize("kwargs,names", UNSUPPORTED)
def test_engine_refuses_what_the_family_lacks(params, kwargs, names):
    from tpumlops.server.prefix_cache import PrefixCacheConfig
    from tpumlops.server.speculative import SpeculativeConfig

    kwargs = dict(kwargs)
    if kwargs.get("speculative"):
        kwargs["speculative"] = SpeculativeConfig(enabled=True)
    if kwargs.get("prefix_cache"):
        kwargs["prefix_cache"] = PrefixCacheConfig(enabled=True, chunk_tokens=8)
    with pytest.raises(UnsupportedForFamily, match=names) as err:
        GenerationEngine(params, CFG, dtype=jnp.float32, family=gdn_moe, **kwargs)
    assert err.value.family == gdn_moe.FLAVOR


def test_validate_names_kv_transfer_and_multihost():
    lacks = gdn_moe.UNSUPPORTED
    with pytest.raises(UnsupportedForFamily, match="KV transfer.*the wire carries rows"):
        validate_serving_for_family(gdn_moe.FLAVOR, lacks, fleet_role="prefill")
    with pytest.raises(UnsupportedForFamily, match="more than one chip"):
        validate_serving_for_family(gdn_moe.FLAVOR, lacks, multihost=True)
    validate_serving_for_family(
        gdn_moe.FLAVOR, lacks, quantize="none", mesh_shape={"dp": 1, "tp": 1},
        fleet_role="unified")
    # Everything the sparse family refuses this one refuses too.
    assert set(gdn_moe.UNSUPPORTED) == set(mla_moe.UNSUPPORTED)


def test_native_artifact_round_trip_in_bf16(tmp_path):
    """save_native_model / load_predictor: the flavor, its config class,
    the tree's dtypes (bf16 matrices, float32 ``A_log`` and ``dt_bias``),
    the family handle; int8 and a mesh are refused before the load."""
    from tpumlops.server import loader

    p16 = gdn_moe.init(jax.random.key(2), CFG, jnp.bfloat16)
    loader.save_native_model(
        tmp_path / "m", gdn_moe.FLAVOR, p16, config=dataclasses.asdict(CFG))
    pred = loader.load_predictor(str(tmp_path / "m"))
    lm = pred.causal_lm
    assert pred.name == gdn_moe.FLAVOR and lm["family"] is gdn_moe
    assert lm["cfg"] == CFG
    first, last = lm["params"]["layers"][0], lm["params"]["layers"][-1]
    assert first["experts"]["gate"].dtype == first["conv"].dtype == jnp.bfloat16
    assert first["A_log"].dtype == first["dt_bias"].dtype == jnp.float32
    assert "qkvz" in first and "qkvz" not in last and last["q"].shape == (64, 128)
    assert "router_bias" not in last and last["shared_expert_gate"].shape == (64, 1)
    toks = np.arange(1, 9, dtype=np.int32)[None]
    np.testing.assert_array_equal(
        np.asarray(pred.predict(jnp.asarray(toks))),
        np.asarray(gdn_moe.generate_greedy(
            p16, jnp.asarray(toks), pred.metadata["max_new_tokens"], CFG)))
    with pytest.raises(UnsupportedForFamily, match="int8"):
        loader.load_predictor(str(tmp_path / "m"), quantize="int8")
    with pytest.raises(UnsupportedForFamily, match="more than one chip"):
        loader.load_predictor(str(tmp_path / "m"), mesh_shape={"tp": 2})


def test_ledger_counts_the_state_beside_the_rows(params, cpu_peaks):
    from tpumlops.server.device_telemetry import (
        DeviceTelemetry, build_hbm_ledger, capacity_log_line,
        kv_cache_bytes_per_row,
    )

    # A slot: one full layer's K and V a position, three linear layers'
    # float32 state and three carried rows of the convolution's input.
    rows = 1 * CFG.max_seq * 2 * CFG.kv_width * 2
    state = 3 * (4 * 16 * 16 * 4 + 3 * CFG.conv_dim * 2)
    assert CFG.conv_dim == 2 * 32 + 64
    assert gdn_moe.state_row_bytes(CFG) == state
    assert kv_cache_bytes_per_row(CFG, kv_quant=False, family=gdn_moe) == rows + state
    ledger = build_hbm_ledger(params, CFG, max_slots=4, family=gdn_moe)
    comps = ledger.components
    routed = sum(leaf.nbytes for lp in params["layers"]
                 for leaf in lp["experts"].values())
    tree = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    assert comps["weights_routed_experts"] == routed
    assert comps["weights_float32"] == tree - routed
    assert (comps["kv_cache"], comps["cache_state"]) == (4 * rows, 4 * state)
    # What the engine allocates for the cache is what the ledger says.
    cache = gdn_moe.RaggedKVCache.create(CFG, 4)
    held = {name: sum(b.nbytes for b in bufs)
            for name, bufs in {**cache.k, **cache.v}.items()}
    assert held["key"] + held["value"] == comps["kv_cache"]
    assert held["conv"] + held["state"] == comps["cache_state"]
    assert cache.v["state"][0].dtype == jnp.float32
    assert ledger.device_total() == tree + 4 * (rows + state) + comps["sampling_state"]
    # A family without a state has no such line.
    other = mla_moe.MlaMoeConfig.tiny()
    assert "cache_state" not in build_hbm_ledger(
        mla_moe.init(jax.random.key(0), other, jnp.float32), other, 2,
        family=mla_moe).components

    active, total = gdn_moe.param_counts(CFG)
    cost = gdn_moe.cost_model(params, CFG)
    assert (cost.active_params, cost.total_params) == (active, total)
    assert cost.expert_bytes == 3 * CFG.hidden_size * CFG.moe_intermediate_size * 4
    # One token reaches top-k experts a layer, many reach them all; a row
    # reads and writes its state once a call whatever the call's tokens.
    f1, b1 = cost.decode(1, 16)
    _, b_all = cost.decode(4096, 16)
    one = cost.moe_layers * CFG.num_experts_per_tok * cost.expert_bytes
    assert b1 == pytest.approx(
        cost.unrouted_bytes + one + cost.kv_pos_bytes * 17 + 2 * state, rel=1e-3)
    assert b_all - 4096 * (cost.kv_pos_bytes * 17 + 2 * state) == pytest.approx(
        cost.unrouted_bytes + routed)
    assert f1 > 2 * active + cost.rule_flops
    f8, b8 = cost.prefill(1, 8, attended=12)
    f16, b16 = cost.prefill(1, 16, attended=12)
    assert f16 > f8 > 2 * active * 8
    assert b16 - b8 < 8 * cost.kv_pos_bytes + cost.moe_layers * 8 * cost.expert_bytes

    tel = DeviceTelemetry(peaks=cpu_peaks)
    tel.attach_model(params, CFG, max_slots=4, family=gdn_moe)
    snap = tel.snapshot()
    assert snap["params"] == {"active": active, "total": total}
    assert snap["hbm"]["components"]["cache_state"] == 4 * state
    assert f"params active {active} of {total}" in capacity_log_line(
        params, CFG, kv_quant=False, peaks=cpu_peaks, family=gdn_moe)


def test_gdn_counter_families_on_the_registry_and_a_family_without_them():
    from prometheus_client import generate_latest

    from tpumlops.server.metrics import ServerMetrics

    m = ServerMetrics(deployment_name="d", predictor_name="p", namespace="n")
    ours = lambda *v: dict(zip(gdn_moe.COUNTS, v))
    m.inc_moe("prefill", ours(1024, 1140, 1300, 6 * 490, 6), 5120 * 8, 128)
    m.inc_moe("prefill", ours(1020, 1100, 1290, 6 * 512, 6), 5120 * 8, 128)
    m.inc_moe("decode", ours(90, 90, 20, 6 * 7, 6 * 7), 80 * 8, 16)
    # The sparse family's counts carry no gdn_* names, this one's no dsa_*.
    m.inc_moe("decode", dict(zip(mla_moe.COUNTS, (5, 5, 16, 300, 100))), 64, 16)
    m.set_cache_state_bytes(12_877_824)
    text = generate_latest(m.registry).decode()

    def sample(family, **labels):
        lines = [l for l in text.splitlines() if l.startswith(family + "{")
                 and all(f'{k}="{v}"' in l for k, v in labels.items())]
        return [float(l.rsplit(" ", 1)[1]) for l in lines]

    assert sample("tpumlops_gdn_tokens_total", program="prefill") == [6.0 * 1002]
    assert sample("tpumlops_gdn_state_passes_total", program="prefill") == [12.0]
    assert sample("tpumlops_gdn_tokens_total", program="decode") == [42.0]
    assert sample("tpumlops_gdn_state_passes_total", program="decode") == [42.0]
    assert sample("tpumlops_dsa_keys_scored_total", program="decode") == [300.0]
    assert sample("tpumlops_dsa_keys_scored_total", program="prefill") == []
    assert sample("tpumlops_moe_assignments_total", program="decode") == [36.0]
    assert sample("tpumlops_cache_state_bytes") == [12_877_824.0]


def test_gdn_counters_and_the_state_gauge_on_metrics_after_a_generate(tmp_path):
    """A real server on a tiny bf16 artifact: the warm-up sweep leaves no
    ``tpumlops_gdn_*`` sample and the gauge says what a slot's state
    holds; one /generate puts both programs' tokens and passes on
    /metrics."""
    import httpx

    from tpumlops.clients.localplane import free_port, start_model_server
    from tpumlops.server import loader
    from tpumlops.utils.config import TpuSpec

    loader.save_native_model(
        tmp_path / "m", gdn_moe.FLAVOR, gdn_moe.init(jax.random.key(0), CFG, jnp.bfloat16),
        config=dataclasses.asdict(CFG))
    port = free_port()
    handle = start_model_server(
        str(tmp_path / "m"), "v1", port, model_name="m",
        tpu=TpuSpec.from_spec({"meshShape": {"tp": 1}, "maxSlots": 2, "prefillChunk": 8}))

    def samples(prefix):
        text = httpx.get(f"http://127.0.0.1:{port}/metrics", timeout=30).text
        out = {}
        for line in text.splitlines():
            if line.startswith(prefix) and "_created" not in line:
                name, labels = line.split("{", 1)
                program = labels.split('program="', 1)[-1].split('"', 1)[0]
                out[name, program if 'program="' in labels else ""] = float(
                    line.rsplit(" ", 1)[1])
        return out

    try:
        assert samples("tpumlops_gdn_") == {}
        assert samples("tpumlops_cache_state_bytes") == {
            ("tpumlops_cache_state_bytes", ""): gdn_moe.state_row_bytes(CFG)}
        r = httpx.post(
            f"http://127.0.0.1:{port}/v2/models/m/generate",
            json={"prompt_ids": list(range(1, 14)), "max_new_tokens": 5}, timeout=120)
        assert r.status_code == 200, r.text
        got = samples("tpumlops_gdn_")
        moe = samples("tpumlops_moe_assignments_total")
    finally:
        handle.stop()
    linear = len(CFG.linear_layers)
    assert got == {
        ("tpumlops_gdn_tokens_total", "prefill"): linear * 13,
        ("tpumlops_gdn_state_passes_total", "prefill"): linear * 2,  # chunks of 8
        ("tpumlops_gdn_tokens_total", "decode"): linear * 4,
        ("tpumlops_gdn_state_passes_total", "decode"): linear * 4,
    }
    fan = CFG.num_experts_per_tok * CFG.num_moe_layers
    assert moe["tpumlops_moe_assignments_total", "prefill"] == fan * 13
