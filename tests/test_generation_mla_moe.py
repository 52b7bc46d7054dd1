"""The serving engine with the latent-attention, sparse-expert family:
the same scheduler, slot cache and chunked prefill as llama-generate,
reached through the predictor's ``causal_lm["family"]`` handle; greedy
tokens equal the plain reference's in float32; what the family's programs
lack is refused typed; loader, HBM ledger, cost model and the
``tpumlops_moe_*`` metric families."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumlops.models import mla_moe
from tpumlops.server.generation import GenerationEngine
from tpumlops.utils.config import (
    UnsupportedForFamily,
    validate_serving_for_family,
)

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
CFG = mla_moe.MlaMoeConfig.tiny()
SEQ = 32


@pytest.fixture(scope="module")
def params():
    return mla_moe.init(jax.random.key(1), CFG, jnp.float32)


@pytest.fixture(scope="module")
def reference_greedy(params):
    """Greedy continuation by the plain reference: the whole forward over
    the padded row for every new token, no cache."""
    if str(BENCH) not in sys.path:
        sys.path.append(str(BENCH))
    import importlib

    ref_mod = importlib.import_module("references.mla_moe_decoder")
    ref = ref_mod.build(dataclasses.asdict(CFG), SEQ)

    def logits_at(row, pos):
        x = params["embed"][jnp.asarray(row[None])].astype(jnp.float32)
        for l, lp in enumerate(params["layers"]):
            x = ref.attention(x, {k: lp[k] for k in ref_mod.ATTN_MATS})
            if l < CFG.num_dense_layers:
                x = ref.dense_ffn(x, lp)
            else:
                x = ref.moe_ffn(x, {**{k: v for k, v in lp.items() if k != "experts"},
                                    **lp["experts"]})
        return np.asarray(ref.head(x, jnp.asarray([[pos]]), params["lm_head"]))[0, 0]

    def greedy(prompt, new):
        row = np.zeros((SEQ,), np.int32)
        row[:len(prompt)] = prompt
        out = []
        for i in range(new):
            tok = int(logits_at(row, len(prompt) + i - 1).argmax())
            out.append(tok)
            row[len(prompt) + i] = tok
        return out

    return greedy


def prompts():
    rng = np.random.default_rng(5)
    return [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
            for n in (5, 13, 20, 9, 17, 3)]


@pytest.mark.parametrize("prefill_chunk", [8, None])
def test_engine_tokens_equal_the_reference_as_requests_join_and_leave(
        params, reference_greedy, prefill_chunk):
    """Six requests on three slots (so they queue, join and leave), new
    token counts that differ: chunked prefill (a padded last chunk) and
    the fused bucketed path both."""
    seen = []
    engine = GenerationEngine(
        params, CFG, max_slots=3, dtype=jnp.float32, family=mla_moe,
        prefill_chunk=prefill_chunk, on_moe=lambda *a: seen.append(a),
    )
    engine.start()
    try:
        news = (6, 4, 7, 5, 3, 6)
        futs = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts(), news)]
        outs = [f.result(timeout=300) for f in futs]
    finally:
        engine.shutdown()
    for p, n, out in zip(prompts(), news, outs):
        assert out.tolist() == reference_greedy(p, n)
    # The counters: every real prompt token and every decoded token was
    # routed (top-2 x 2 expert layers), padding and idle slots were not,
    # and warm-up counted nothing.
    fan = CFG.num_experts_per_tok * CFG.num_moe_layers
    by = {"prefill": [0, 0], "decode": [0, 0]}
    for program, counts, routed, tile in seen:
        assert tuple(counts) == mla_moe.COUNTS
        assignments, activations, visits = (
            counts["local_assignments"], counts["experts_hit"], counts["row_tile_visits"])
        assert assignments == routed  # every expert is held here
        by[program][0] += assignments
        by[program][1] += activations
        assert 0 < activations <= min(assignments, CFG.num_moe_layers * CFG.n_routed_experts)
        # An expert that got a token costs a visit, a tile boundary inside
        # its rows one more; the tile is the call's static one.
        assert activations <= visits <= assignments
        assert tile in (16, 32, 64, 128)
    assert {tile for program, *_, tile in seen if program == "decode"} == {
        mla_moe.moe_row_tile(CFG, 3)}
    assert by["prefill"][0] == fan * sum(len(p) for p in prompts())
    # A request's first token comes from its prefill; the rest are steps.
    assert by["decode"][0] == fan * sum(n - 1 for n in news)


def test_llama_programs_carry_no_extra_output():
    """The seam adds nothing to the dense family's programs: the decode
    program's outputs are the four it had."""
    from tpumlops.models import llama

    cfg = llama.LlamaConfig.tiny()
    engine = GenerationEngine(
        llama.init(jax.random.key(0), cfg), cfg, max_slots=2, dtype=jnp.float32)
    out = engine._decode_greedy(
        engine._params, engine._tokens, engine._cache_k, engine._cache_v,
        engine._lengths, jnp.zeros((2,), bool), 16)
    assert len(out) == 4
    assert engine._pad_id == llama.PAD_ID == 0 and not llama.UNSUPPORTED


UNSUPPORTED = [
    ({"kv_quant": True}, "int8"),
    ({"mesh_shape": {"dp": 1, "tp": 2}}, "more than one chip"),
    ({"mesh_shape": {"sp": 2}}, "ring prefill"),
    ({"speculative": "on"}, "speculative"),
    ({"prefix_cache": "on", "prefill_chunk": 8}, "prefix cache"),
    ({"prefill_batch": 2, "prefill_chunk": 8}, "packed"),
    ({"decode_steps": 4}, "multi-step"),
    ({"unified_step": True}, "super-step"),
    ({"preemption": True}, "preemption"),
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
        GenerationEngine(params, CFG, dtype=jnp.float32, family=mla_moe, **kwargs)
    assert err.value.family == mla_moe.FLAVOR
    # The dense family's module lacks nothing: the same knobs pass.
    from tpumlops.models import llama

    validate_serving_for_family(llama.FLAVOR, llama.UNSUPPORTED, quantize="int8kv",
                                mesh_shape={"tp": 4}, speculative=True)


def test_validate_names_kv_transfer_and_multihost():
    lacks = mla_moe.UNSUPPORTED
    with pytest.raises(UnsupportedForFamily, match="KV transfer"):
        validate_serving_for_family(mla_moe.FLAVOR, lacks, fleet_role="prefill")
    with pytest.raises(UnsupportedForFamily, match="more than one chip"):
        validate_serving_for_family(mla_moe.FLAVOR, lacks, multihost=True)
    validate_serving_for_family(
        mla_moe.FLAVOR, lacks, quantize="none", mesh_shape={"dp": 1, "tp": 1},
        fleet_role="unified")
    # A family that lacks one mechanism is refused that one alone.
    validate_serving_for_family("x", {"mesh": "m"}, quantize="int8")
    with pytest.raises(UnsupportedForFamily, match="does not implement m "):
        validate_serving_for_family("x", {"mesh": "m"}, mesh_shape={"tp": 2})


def test_native_artifact_round_trip_in_bf16(tmp_path):
    """save_native_model / load_predictor: the flavor, its config class,
    the tree's dtypes (bf16 matrices, the float32 router bias), the
    family handle; int8 and a mesh are refused before the load."""
    from tpumlops.server import loader

    p16 = mla_moe.init(jax.random.key(2), CFG, jnp.bfloat16)
    loader.save_native_model(
        tmp_path / "m", mla_moe.FLAVOR, p16, config=dataclasses.asdict(CFG))
    pred = loader.load_predictor(str(tmp_path / "m"))
    lm = pred.causal_lm
    assert pred.name == mla_moe.FLAVOR and lm["family"] is mla_moe
    assert lm["cfg"] == CFG
    last = lm["params"]["layers"][-1]
    assert last["experts"]["gate"].dtype == jnp.bfloat16
    assert last["router_bias"].dtype == jnp.float32
    assert "experts" not in lm["params"]["layers"][0]
    toks = np.arange(1, 9, dtype=np.int32)[None]
    np.testing.assert_array_equal(
        np.asarray(pred.predict(jnp.asarray(toks))),
        np.asarray(mla_moe.generate_greedy(
            p16, jnp.asarray(toks), pred.metadata["max_new_tokens"], CFG)))
    with pytest.raises(UnsupportedForFamily, match="int8"):
        loader.load_predictor(str(tmp_path / "m"), quantize="int8")
    with pytest.raises(UnsupportedForFamily, match="more than one chip"):
        loader.load_predictor(str(tmp_path / "m"), mesh_shape={"tp": 2})


def test_ledger_counts_experts_rest_and_latent_cache(params, cpu_peaks):
    from tpumlops.server.device_telemetry import (
        DeviceTelemetry, build_hbm_ledger, capacity_log_line,
        kv_cache_bytes_per_row,
    )

    # The RoPE key's row is padded to the chip's 128 lanes.
    row = CFG.num_layers * CFG.max_seq * (CFG.kv_lora_rank + mla_moe.LANES) * 2
    assert kv_cache_bytes_per_row(CFG, kv_quant=False, family=mla_moe) == row
    ledger = build_hbm_ledger(params, CFG, max_slots=4, family=mla_moe)
    comps = ledger.components
    routed = sum(leaf.nbytes for lp in params["layers"] if "experts" in lp
                 for leaf in lp["experts"].values())
    tree = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    assert comps["weights_routed_experts"] == routed
    assert comps["weights_float32"] == tree - routed
    assert comps["kv_cache"] == 4 * row
    # What the engine allocates for the cache is what the ledger says.
    cache = mla_moe.RaggedKVCache.create(CFG, 4)
    assert sum(buf.nbytes for buf in jax.tree.leaves((cache.k, cache.v))) == comps["kv_cache"]
    assert ledger.device_total() == tree + 4 * row + comps["sampling_state"]

    active, total = mla_moe.param_counts(CFG)
    cost = mla_moe.cost_model(params, CFG)
    assert (cost.active_params, cost.total_params) == (active, total)
    assert cost.expert_bytes == 3 * CFG.hidden_size * CFG.moe_intermediate_size * 4
    # One token reaches top-k experts a layer, many reach them all.
    f1, b1 = cost.decode(1, 16)
    _, b_all = cost.decode(4096, 16)
    one = cost.moe_layers * CFG.num_experts_per_tok * cost.expert_bytes
    assert cost.unrouted_bytes + one <= b1 < cost.unrouted_bytes + one + 4096
    (layers, _pair_flops, row_bytes, most), = cost.attn  # one layer kind, no limit
    assert (layers, most) == (CFG.num_layers, 0) and cost.index == (0, 0, 0)
    assert b_all - layers * row_bytes * 4096 * 17 == pytest.approx(
        cost.unrouted_bytes + routed)
    assert f1 > 2 * active
    flops, nbytes = cost.prefill(1, 8, attended=12)
    assert flops > 2 * active * 8 and nbytes > cost.unrouted_bytes

    tel = DeviceTelemetry(peaks=cpu_peaks)
    tel.attach_model(params, CFG, max_slots=4, family=mla_moe)
    snap = tel.snapshot()
    assert snap["params"] == {"active": active, "total": total}
    assert "weights_routed_experts" in snap["hbm"]["components"]
    assert f"params active {active} of {total}" in capacity_log_line(
        params, CFG, kv_quant=False, peaks=cpu_peaks, family=mla_moe)


def test_moe_counter_families_on_the_registry():
    from prometheus_client import generate_latest

    from tpumlops.server.metrics import ServerMetrics

    m = ServerMetrics(deployment_name="d", predictor_name="p", namespace="n")
    def counts(hit, visits, local):
        return dict(zip(mla_moe.COUNTS, (hit, visits, local, 0, 0)))

    m.inc_moe("prefill", counts(1024, 1140, 4096 * 4), 4096 * 4, 128)
    m.inc_moe("decode", counts(228, 230, 64 * 4), 64 * 4, 16)
    m.inc_moe("decode", counts(226, 226, 64 * 4), 64 * 4, 16)
    text = generate_latest(m.registry).decode()
    for family, program, value in (
        ("tpumlops_moe_assignments_total", "prefill", 16384.0),
        ("tpumlops_moe_expert_activations_total", "decode", 454.0),
        ("tpumlops_moe_row_tile_visits_total", "prefill", 1140.0),
        ("tpumlops_moe_row_tile_visits_total", "decode", 456.0),
        ("tpumlops_moe_row_tile_rows", "prefill", 128.0),
        ("tpumlops_moe_row_tile_rows", "decode", 16.0),
    ):
        line = next(l for l in text.splitlines()
                    if l.startswith(family + "{") and f'program="{program}"' in l)
        assert float(line.rsplit(" ", 1)[1]) == value


def test_row_tile_visits_on_metrics_after_a_generate_and_not_through_warm_up(tmp_path):
    """A real server on a tiny bf16 artifact: the warm-up sweep (every
    program, run) leaves no ``tpumlops_moe_*`` sample; one /generate puts
    the visits of both programs on /metrics, between the experts that got
    a token and the token copies, with the static tile beside them."""
    import httpx

    from tpumlops.clients.localplane import free_port, start_model_server
    from tpumlops.server import loader
    from tpumlops.utils.config import TpuSpec

    loader.save_native_model(
        tmp_path / "m", mla_moe.FLAVOR, mla_moe.init(jax.random.key(0), CFG, jnp.bfloat16),
        config=dataclasses.asdict(CFG))
    port = free_port()
    handle = start_model_server(
        str(tmp_path / "m"), "v1", port, model_name="m",
        tpu=TpuSpec.from_spec({"meshShape": {"tp": 1}, "maxSlots": 2, "prefillChunk": 8}))

    def moe_samples():
        text = httpx.get(f"http://127.0.0.1:{port}/metrics", timeout=30).text
        out = {}
        for line in text.splitlines():
            if line.startswith("tpumlops_moe_") and "_created" not in line:
                name, labels = line.split("{", 1)
                program = labels.split('program="', 1)[1].split('"', 1)[0]
                out[name, program] = float(line.rsplit(" ", 1)[1])
        return out

    try:
        assert moe_samples() == {}
        r = httpx.post(
            f"http://127.0.0.1:{port}/v2/models/m/generate",
            json={"prompt_ids": list(range(1, 14)), "max_new_tokens": 5}, timeout=120)
        assert r.status_code == 200, r.text
        got = moe_samples()
    finally:
        handle.stop()
    fan = CFG.num_experts_per_tok * CFG.num_moe_layers
    for program, tokens, rows in (("prefill", 13, 8), ("decode", 4, 2)):
        assert got["tpumlops_moe_assignments_total", program] == fan * tokens
        hit = got["tpumlops_moe_expert_activations_total", program]
        visits = got["tpumlops_moe_row_tile_visits_total", program]
        assert 0 < hit <= visits <= fan * tokens
        assert got["tpumlops_moe_row_tile_rows", program] == mla_moe.moe_row_tile(CFG, rows)
