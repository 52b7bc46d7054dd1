"""The serving engine with the GQA family's window configuration
(``models/gdn_moe.py`` with sliding-window layers on a ring; the model
against its reference is tests/test_models_laguna.py): greedy tokens of
requests that join and leave through ``GenerationEngine`` against the
plain reference, what the configuration cannot serve refused typed and
for the ring's own reason, the loader, the HBM ledger's ring line, and
the ``tpumlops_attn_pairs_*`` counter families from the engine's
dispatches to a real server's /metrics."""

import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumlops.models import gdn_moe
from tpumlops.server.generation import GenerationEngine
from tpumlops.utils.config import UnsupportedForFamily, validate_serving_for_family

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
F, S = gdn_moe.FULL, gdn_moe.SLIDING
CFG = gdn_moe.GdnMoeConfig.tiny(
    num_layers=4, layer_types=(F, S, S, F), num_heads=4, swa_num_heads=6,
    num_kv_heads=2, head_dim=32, sliding_window=12, partial_rotary_factor=0.5,
    rope_theta=1e4, rope_type="yarn", rope_factor=4.0,
    rope_original_max_position=2048,
    rope_attention_factor=0.1 * math.log(4.0) + 1.0, swa_rope_theta=1e4,
    swa_partial_rotary_factor=1.0, attention_gate="headwise", norm="plain",
    qk_norm=False, shared_expert_gate=False, mlp_only_layers=(0,),
    intermediate_size=96, routed_scaling_factor=2.5, n_local_experts=4,
    local_expert_start=2, max_seq=128)
ATOL = 3e-5  # as tests/test_models_laguna.py: float32 both sides


@pytest.fixture(scope="module")
def params():
    return gdn_moe.init(jax.random.key(3), CFG, jnp.float32)


def _reference():
    if str(BENCH) not in sys.path:
        sys.path.append(str(BENCH))
    from references import laguna_decoder

    return laguna_decoder


def reference_logits(params, row):
    """The reference's full forward over one row: logits at every position."""
    ref = _reference().build(dataclasses.asdict(CFG), row.shape[1])
    x = params["embed"][jnp.asarray(row)].astype(jnp.float32)
    for l, (kind, lp) in enumerate(zip(CFG.kinds, params["layers"])):
        names = ("q", "k", "v", "attn_gate", "o", "attn_norm")
        x = ref.attention[kind](x, {k: lp[k] for k in names})
        ffn = {k: v for k, v in lp.items() if k not in names and k != "experts"}
        ffn.update(lp.get("experts", {}))
        x = ref.mlp(x, ffn) if l in CFG.mlp_only_layers else ref.moe_ffn(x, ffn)
    idx = np.arange(row.shape[1])[None]
    return np.asarray(ref.head(x, jnp.asarray(idx), params["final_norm"],
                               params["lm_head"]))[0]


@pytest.mark.parametrize("prefill_chunk", [8, None])
def test_engine_tokens_equal_the_reference_as_requests_join_and_leave(
        params, prefill_chunk, monkeypatch):
    """Four requests on two slots (they queue, join and leave, a slot sits
    idle while the other decodes), prompts of 13-50 (one to four rings of
    16) by chunks of 8 that straddle the window of 12, and by the engine's
    default, one call over the prompt's bucket; 8 to 12 new tokens each,
    so a slot's ring wraps while it decodes.  Greedy tokens equal the
    reference's own greedy continuation, which they can only do if the
    rings hold what the window says; the pairs the engine hands on are
    the arithmetic's for every chunk and step it dispatched, and a sliding
    chunk's computed pairs are the keys its core was handed (the ring's
    16 rows and the chunk's 8) a real query."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
               for n in (13, 37, 21, 50)]
    news = (10, 8, 12, 9)

    def assert_reference_greedy(prompt, out):
        """The served tokens teacher-forced through the reference: each is
        the reference's argmax given the ones before it (causal, so this
        is its own greedy continuation), by a margin no rounding closes."""
        row = np.zeros((1, 64), np.int32)
        row[0, :len(prompt) + len(out)] = np.concatenate([prompt, out])
        with jax.default_matmul_precision("highest"):
            logits = reference_logits(params, row)
        for i, tok in enumerate(out):
            at = logits[len(prompt) - 1 + i]
            top2 = np.sort(at)[-2:]
            assert top2[1] - top2[0] > 4 * ATOL, "a near-tie: pick another seed"
            assert int(np.argmax(at)) == tok, (len(prompt), i)

    cores = []  # (queries, keys, window) of every prefill core traced
    core = gdn_moe.gqa_prefill_attention

    def seen_core(q, keys, *a, window, **kw):
        cores.append((q.shape[1], keys.shape[1], window))
        return core(q, keys, *a, window=window, **kw)

    monkeypatch.setattr(gdn_moe, "gqa_prefill_attention", seen_core)
    pairs = []
    engine = GenerationEngine(
        params, CFG, max_slots=2, dtype=jnp.float32, family=gdn_moe,
        prefill_chunk=prefill_chunk, on_attn_pairs=lambda *a: pairs.append(a),
    )
    engine.start()
    try:
        futs = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
        outs = [f.result(timeout=600) for f in futs]
    finally:
        engine.shutdown()
    for p, n, out in zip(prompts, news, outs):
        assert len(out) == n
        assert_reference_greedy(p, np.asarray(out))
    steps = [p for program, p in pairs if program == "decode"]
    chunks = [p for program, p in pairs if program == "prefill"]
    # Every generated token past the first is one row of a step.
    attended = lambda kind: sum(p[kind][1] for p in steps)
    rows = sum(n - 1 for n in news)
    want = {F: 0, S: 0}
    for p, n in zip(prompts, news):
        for t in range(len(p), len(p) + n - 1):  # a step's query sits at t
            want[F] += 2 * (t + 1)
            want[S] += 2 * min(t + 1, 12)
    assert (attended(F), attended(S)) == (want[F], want[S])
    assert sum(p[S][0] for p in steps) == 2 * rows * (16 + 1)
    if prefill_chunk:
        assert len(chunks) == sum(-(-len(p) // 8) for p in prompts)
        assert sum(p[F][1] for p in chunks) == 2 * sum(
            len(p) * (len(p) + 1) // 2 for p in prompts)
        assert sum(p[S][1] for p in chunks) == 2 * sum(
            sum(min(t + 1, 12) for t in range(len(p))) for p in prompts)
        assert {k for s, k, w in cores if w and s == 8} == {16 + 8}
        assert sum(p[S][0] for p in chunks) == 2 * (16 + 8) * sum(map(len, prompts))
    else:
        assert chunks == []  # the whole-prompt call is no chunk


UNSUPPORTED = [
    ({"speculative": "on"}, "overwritten the one a window back"),
    ({"prefix_cache": "on", "prefill_chunk": 8}, "past the window needs rows it has overwritten"),
    ({"preemption": True}, "prefix cache and preemption"),
    ({"kv_quant": True}, "int8"),
]


@pytest.mark.parametrize("kwargs,names", UNSUPPORTED)
def test_engine_refuses_what_the_ring_cannot_serve(params, kwargs, names):
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


def test_kv_transfer_is_refused_for_the_rings_reason():
    with pytest.raises(UnsupportedForFamily, match="every position's row"):
        validate_serving_for_family(gdn_moe.FLAVOR, gdn_moe.UNSUPPORTED,
                                    fleet_role="decode")


def test_native_artifact_round_trip_and_a_program_that_does_not_know_the_keys(tmp_path):
    """save_native_model / load_predictor with the window configuration's
    keys: the flavor, its config (tables as tuples), the family handle, the
    tree at its kinds' widths; a key the config class does not know is
    refused typed."""
    from tpumlops.server import loader

    p16 = gdn_moe.init(jax.random.key(2), CFG, jnp.bfloat16)
    loader.save_native_model(
        tmp_path / "m", gdn_moe.FLAVOR, p16, config=dataclasses.asdict(CFG))
    pred = loader.load_predictor(str(tmp_path / "m"))
    lm = pred.causal_lm
    assert lm["family"] is gdn_moe and lm["cfg"] == CFG
    assert lm["params"]["layers"][1]["attn_gate"].shape == (64, 6)
    toks = np.arange(1, 9, dtype=np.int32)[None]
    np.testing.assert_array_equal(
        np.asarray(pred.predict(jnp.asarray(toks))),
        np.asarray(gdn_moe.generate_greedy(
            p16, jnp.asarray(toks), pred.metadata["max_new_tokens"], CFG)))
    with pytest.raises(ValueError, match="does not know.*swa_heads"):
        loader._build_config(gdn_moe.FLAVOR, {"sliding_window": 512, "swa_heads": 72})


def test_a_program_before_the_window_keys_refuses_the_artifact_at_once(tmp_path, monkeypatch):
    """What a program from before this configuration does with the cell:
    its config class knows the Qwen3-Next keys alone, so the reference's
    artifact writer refuses, naming the model type, before a byte of the
    5.7 GB tree is written."""
    import json

    from tpumlops.server import loader

    before = dataclasses.make_dataclass(
        "Before", [(f.name, f.type, dataclasses.field(default=f.default))
                   for f in dataclasses.fields(gdn_moe.GdnMoeConfig)
                   if f.name not in ("layer_types", "sliding_window", "swa_num_heads",
                                     "rope_type", "attention_gate", "norm")])
    monkeypatch.setattr(loader, "_build_config", lambda flavor, cfg: before())
    model = json.loads((BENCH / "configs" / "laguna-s-2.1-bf16.json").read_text())["model"]
    with pytest.raises(ValueError, match="cannot run model_type 'laguna'"):
        _reference().write_artifact(str(tmp_path / "m"), model, 1)
    assert not (tmp_path / "m").exists()


def test_ledger_counts_the_ring_as_rows_a_slot(params, cpu_peaks):
    from tpumlops.server.device_telemetry import (
        DeviceTelemetry, build_hbm_ledger, kv_cache_bytes_per_row,
    )

    # A slot: two full layers' K and V a position, two rings of 16 rows.
    rows = 2 * CFG.max_seq * 2 * CFG.kv_width * 2
    ring = 2 * 16 * 2 * CFG.kv_width * 2
    assert gdn_moe.ring_row_bytes(CFG) == ring and gdn_moe.state_row_bytes(CFG) == 0
    assert kv_cache_bytes_per_row(CFG, kv_quant=False, family=gdn_moe) == rows + ring
    ledger = build_hbm_ledger(params, CFG, max_slots=4, family=gdn_moe)
    comps = ledger.components
    assert (comps["kv_cache"], comps["cache_ring"], comps["cache_state"]) == (
        4 * rows, 4 * ring, 0)
    # What the engine allocates for the cache is what the ledger says.
    cache = gdn_moe.RaggedKVCache.create(CFG, 4)
    held = {name: sum(b.nbytes for b in bufs)
            for name, bufs in {**cache.k, **cache.v}.items()}
    assert held["key"] + held["value"] == comps["kv_cache"]
    assert held["ring_key"] + held["ring_value"] == comps["cache_ring"]
    # Qwen3-Next's configuration has no ring line.
    other = gdn_moe.GdnMoeConfig.tiny()
    assert "cache_ring" not in build_hbm_ledger(
        gdn_moe.init(jax.random.key(0), other, jnp.float32), other, 2,
        family=gdn_moe).components
    # The cost model's attention: a step's sliding layers see at most the
    # window, its full layers every earlier position.
    cost = gdn_moe.cost_model(params, CFG)
    near, _ = cost.decode(1, 12)
    far, _ = cost.decode(1, 100)
    assert far - near == pytest.approx(2.0 * 88 * 2 * cost.pair_flops)
    tel = DeviceTelemetry(peaks=cpu_peaks)
    tel.attach_model(params, CFG, max_slots=4, family=gdn_moe)
    assert tel.snapshot()["hbm"]["components"]["cache_ring"] == 4 * ring


def test_attn_pair_counters_on_metrics_after_a_generate(tmp_path):
    """A real server on a tiny bf16 artifact: the warm-up sweep leaves no
    ``tpumlops_attn_pairs_*`` sample; one /generate of a 21-token prompt in
    chunks of 8 and 5 new tokens puts both programs' pairs of both kinds
    on /metrics, attended no more than computed."""
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

    def samples():
        text = httpx.get(f"http://127.0.0.1:{port}/metrics", timeout=30).text
        out = {}
        for line in text.splitlines():
            if line.startswith("tpumlops_attn_pairs_") and "_created" not in line:
                name, labels = line.split("{", 1)
                get = lambda k: labels.split(f'{k}="', 1)[1].split('"', 1)[0]
                out[name, get("program"), get("kind")] = float(line.rsplit(" ", 1)[1])
        return out

    try:
        assert samples() == {}
        r = httpx.post(
            f"http://127.0.0.1:{port}/v2/models/m/generate",
            json={"prompt_ids": list(range(1, 22)), "max_new_tokens": 5}, timeout=120)
        assert r.status_code == 200, r.text
        got = samples()
    finally:
        handle.stop()
    computed = lambda p, k: got["tpumlops_attn_pairs_computed_total", p, k]
    attended = lambda p, k: got["tpumlops_attn_pairs_attended_total", p, k]
    # Prefill: positions 0-20 attend 1..21 keys in the full layers, at most
    # 12 in the sliding ones; the four steps' queries sit at 21-24.
    assert attended("prefill", "full") == 2 * 21 * 22 // 2
    assert attended("prefill", "sliding") == 2 * (sum(range(1, 13)) + 9 * 12)
    assert attended("decode", "full") == 2 * (22 + 23 + 24 + 25)
    assert attended("decode", "sliding") == 2 * 4 * 12
    assert computed("decode", "sliding") == 2 * 4 * 17  # a ring of 16 and the position in flight
    for program in ("prefill", "decode"):
        for kind in ("full", "sliding"):
            assert 0 < attended(program, kind) <= computed(program, kind)
