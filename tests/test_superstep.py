"""Unified ragged super-step: f64 parity vs every legacy path.

The acceptance bar (ISSUE 16): with ``spec.tpu.unifiedStep: true`` the
engine runs ONE jit program per tick — packed-prefill chunk commits,
fused-K decode with on-device sampling chains, and speculative verify
share a dispatch via per-row role tensors — and output is token-for-
token identical to the split-program engine across greedy, seeded
sampling, prefix-cache, speculative, packed prefill, multistep, int8kv,
and tp∈{2,4}, with leader/follower multihost replay leaving identical
device state.  Exact-parity tests run in float64 (same policy as
test_generation.py).  The fast tranche covers the config/builder/engine
gating: ``unifiedStep: false`` (the default) must keep the legacy
engine byte-for-byte.
"""

import threading

import numpy as np
import pytest

from tpumlops.server.generation import (
    decode_window_buckets,
    superstep_window,
)

# ---------------------------------------------------------------------------
# Fast: window pre-pick, config plumbing, engine gating
# ---------------------------------------------------------------------------


def test_superstep_window_covers_both_role_classes():
    # A decode row needs its start position plus K - 1 chained steps;
    # a verify/prefill row needs only its own high-water position.
    assert superstep_window(10, 0, 4, 64) >= 13
    assert superstep_window(0, 40, 4, 64) >= 40
    assert superstep_window(10, 40, 4, 64) >= 40
    # Capacity clamps: a row already at the top bucket stays dispatchable.
    assert superstep_window(64, 64, 16, 64) == 64
    # All-idle (warmup parked dispatch) still yields a legal bucket.
    assert superstep_window(0, 0, 4, 64) in decode_window_buckets(64)


def test_unified_step_spec_parses_and_rejects_nothing_new():
    from tpumlops.utils.config import TpuSpec

    assert TpuSpec.from_spec({}).unified_step is False
    assert TpuSpec.from_spec({"unifiedStep": True}).unified_step is True
    assert TpuSpec.from_spec({"unifiedStep": False}).unified_step is False


def test_builder_emits_unified_step_flag_only_when_true():
    from tpumlops.operator.builder import build_deployment
    from tpumlops.utils.config import OperatorConfig

    def args_for(tpu_spec):
        config = OperatorConfig.from_spec(
            {
                "modelName": "iris", "modelAlias": "champion",
                "minioSecret": "minio-creds", "backend": "tpu",
                "tpu": {"tpuTopology": "v5e-8",
                        "meshShape": {"dp": 1, "tp": 8}, **tpu_spec},
            }
        )
        sd = build_deployment(
            name="iris", namespace="models", owner_uid="u", config=config,
            current_version="1",
            new_model_uri="s3://mlflow/1/aaa/artifacts/model",
            traffic_current=100,
        )
        pod = sd["spec"]["predictors"][0]["componentSpecs"][0]["spec"]
        return pod["containers"][0]["args"]

    base = args_for({"decodeSteps": 4})
    on = args_for({"decodeSteps": 4, "unifiedStep": True})
    off = args_for({"decodeSteps": 4, "unifiedStep": False})
    assert "--unified-step" not in base
    # unifiedStep: false must keep the manifest byte-for-byte (the
    # same contract every post-PR-7 flag honors).
    assert off == base
    assert on[on.index("--unified-step") + 1] == "1"


def test_engine_gating_builds_one_program_space_not_both():
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama
    from tpumlops.server.generation import GenerationEngine

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(0), cfg)
    legacy = GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float32, decode_steps=4
    )
    unified = GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float32, decode_steps=4,
        unified_step=True,
    )
    # The unified engine owns the superstep program and never builds the
    # fused-multistep pair; the legacy engine is the exact inverse.
    assert hasattr(unified, "_superstep")
    assert not hasattr(unified, "_multistep")
    assert hasattr(legacy, "_multistep")
    assert not hasattr(legacy, "_superstep")
    assert not legacy._unified and unified._unified


# ---------------------------------------------------------------------------
# Engine parity on the tiny CPU llama fixture (slow tranche)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def x64():
    import jax

    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="module")
def tiny(x64):
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(0), cfg, dtype=jnp.float64)
    return params, cfg


def _ref(params, cfg, prompt, n, eos=None):
    import jax.numpy as jnp

    from tpumlops.models import llama

    out = llama.generate_greedy(
        params, jnp.asarray([prompt], jnp.int32), n, cfg, dtype=jnp.float64
    )
    toks = np.asarray(out)[0].tolist()
    if eos is not None and eos in toks:
        toks = toks[: toks.index(eos) + 1]
    return toks


def _engine(params, cfg, *, unified=True, **kw):
    import jax.numpy as jnp

    from tpumlops.server.generation import GenerationEngine

    kw.setdefault("max_slots", 2)
    kw.setdefault("decode_steps", 4)
    return GenerationEngine(
        params, cfg, dtype=jnp.float64, unified_step=unified, **kw
    )


def _run(engine, jobs):
    engine.start(warmup=True)
    try:
        futs = [engine.submit(*args, **kw) for args, kw in jobs]
        return [f.result(timeout=300).tolist() for f in futs]
    finally:
        engine.shutdown()


@pytest.mark.slow
def test_greedy_parity_and_one_dispatch_per_tick(tiny):
    """Concurrent greedy streams under K=4 match generate_greedy token-
    for-token, and every engine tick is ONE superstep dispatch — no
    decode/multistep/verify/packed programs ever run."""
    params, cfg = tiny
    engine = _engine(params, cfg)
    jobs = [((([7, 1, 4, 8, 3], 8)), {}), ((([6, 2, 8, 4, 1], 8)), {})]
    outs = _run(engine, jobs)
    assert outs == [_ref(params, cfg, p, n) for (p, n), _ in jobs]
    assert engine.dispatches_total.get("superstep", 0) > 0
    for op in ("decode", "multistep", "verify", "chunks"):
        assert engine.dispatches_total.get(op, 0) == 0, op


@pytest.mark.slow
def test_seeded_sampling_parity_vs_legacy_single_step(tiny):
    """The on-device key chain advances one split per emitted token, so
    seeded sampling under the unified K=4 program reproduces the legacy
    single-step loop exactly."""
    params, cfg = tiny
    jobs = [
        (([7, 1, 4, 8, 3], 8), dict(temperature=0.8, top_k=20, seed=123)),
        (([6, 2, 8, 4, 1], 8), dict(temperature=0.6, top_p=0.9, seed=7)),
    ]
    legacy = _run(_engine(params, cfg, unified=False, decode_steps=1), jobs)
    unified = _run(_engine(params, cfg), jobs)
    assert unified == legacy


@pytest.mark.slow
def test_speculative_parity_vs_legacy_verify_path(tiny):
    """Draft-carrying rows ride the dispatch as verify-role rows: the
    n-gram drafter + unified verify emit exactly what the legacy
    dedicated verify program emits (greedy, so acceptance is exact)."""
    from tpumlops.server.speculative import SpeculativeConfig

    params, cfg = tiny
    spec = dict(
        speculative=SpeculativeConfig(
            enabled=True, draft_tokens=2, ngram_min=1, ngram_max=4,
            adaptive=True,
        )
    )
    rep = [5, 9, 5, 9, 5, 9, 5, 9]
    legacy = _run(
        _engine(params, cfg, unified=False, decode_steps=1, **spec),
        [((rep, 12), {})],
    )
    unified = _run(_engine(params, cfg, **spec), [((rep, 12), {})])
    assert unified == legacy
    assert unified[0] == _ref(params, cfg, rep, 12)


@pytest.mark.slow
def test_packed_prefill_parity_ragged_chunk_counts(tiny):
    """A burst of admissions with ragged chunk counts (sub-chunk,
    exactly-one, multi-with-partial-tail) prefills as prefill-role rows
    inside the shared dispatches and matches generate_greedy."""
    params, cfg = tiny
    engine = _engine(
        params, cfg, max_slots=4, prefill_chunk=8, prefill_batch=4
    )
    prompts = [
        ([5, 9, 2], 6),
        ([7, 1, 4, 8, 3, 9, 2, 6], 5),
        (list(range(2, 23)), 7),
        ([11, 3], 4),
    ]
    outs = _run(engine, [((p, n), {}) for p, n in prompts])
    assert outs == [_ref(params, cfg, p, n) for p, n in prompts]


@pytest.mark.slow
def test_prefix_cache_hit_parity(tiny):
    """A cached prefix seeds (its own op, as before) and the remainder
    prefills through the unified dispatch; tokens match the cold run."""
    from tpumlops.server.prefix_cache import PrefixCacheConfig

    params, cfg = tiny
    engine = _engine(
        params, cfg, prefill_chunk=8, prefill_batch=2,
        prefix_cache=PrefixCacheConfig(
            enabled=True, budget_bytes=8 * 2**20, chunk_tokens=8
        ),
    )
    p = list(range(2, 19))
    engine.start(warmup=True)
    try:
        cold = engine.generate(p, 6, timeout=300).tolist()
        warm = engine.generate(p, 6, timeout=300).tolist()
        hits = engine.prefix_hits
    finally:
        engine.shutdown()
    assert cold == warm == _ref(params, cfg, p, 6)
    assert hits >= 1


@pytest.mark.slow
def test_int8kv_parity_vs_legacy(tiny):
    """The quantized-cache commit path (scale planes, drop-scatter per
    position) is shared with the legacy programs: int8kv tokens agree
    engine-vs-engine (the f64 reference does not apply — int8kv is
    lossy by design)."""
    params, cfg = tiny
    jobs = [((([7, 1, 4, 8, 3], 8)), {}), ((([6, 2, 8, 4, 1], 8)), {})]
    legacy = _run(
        _engine(params, cfg, unified=False, decode_steps=1, kv_quant=True),
        jobs,
    )
    unified = _run(_engine(params, cfg, kv_quant=True), jobs)
    assert unified == legacy


@pytest.mark.slow
@pytest.mark.parametrize("tp", [2, 4])
def test_tensor_parallel_parity(x64, tp):
    """tp-sharded unified serving matches the unsharded f64 reference
    token-for-token.  Own fixture geometry: num_kv_heads=4 so the KV
    heads axis divides at tp=4 (the module `tiny` has 2 and is
    rejected at config validation)."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama, partition

    cfg = llama.LlamaConfig.tiny(max_seq=64, num_kv_heads=4)
    params = llama.init(jax.random.key(0), cfg, dtype=jnp.float64)
    mesh_shape = {"dp": 1, "tp": tp}
    sharded = partition.shard_llama_params(
        params, partition.build_serving_mesh(mesh_shape)
    )
    engine = _engine(sharded, cfg, mesh_shape=mesh_shape)
    engine.start(warmup=False)
    try:
        outs = [
            engine.generate(p, n, timeout=300).tolist()
            for p, n in [([5, 9, 2], 6), ([7, 1, 4, 8, 3], 9)]
        ]
    finally:
        engine.shutdown()
    assert outs == [
        _ref(params, cfg, [5, 9, 2], 6),
        _ref(params, cfg, [7, 1, 4, 8, 3], 9),
    ]


@pytest.mark.slow
def test_warmup_variant_count_collapses_3x(tiny, cpu_peaks):
    """The acceptance bar: at decodeSteps=4 + speculative + packed
    prefill the unified warmup sweep compiles >= 3x fewer jit variants
    than the legacy sweep (one per window-bucket x sampling-mode, all
    attributed to the one 'superstep' op)."""
    from tpumlops.server.device_telemetry import DeviceTelemetry
    from tpumlops.server.speculative import SpeculativeConfig

    params, cfg = tiny

    def boot(unified):
        tel = DeviceTelemetry(peaks=cpu_peaks)
        engine = _engine(
            params, cfg, unified=unified, max_slots=4,
            prefill_chunk=8, prefill_batch=4,
            speculative=SpeculativeConfig(
                enabled=True, draft_tokens=2, ngram_min=1, ngram_max=4,
                adaptive=True,
            ),
            telemetry=tel,
        )
        engine.start(warmup=True)
        engine.shutdown()
        return tel.observatory.snapshot()["warmup"]

    legacy = boot(False)
    unified = boot(True)
    assert unified["compiles"] > 0
    assert legacy["compiles"] >= 3 * unified["compiles"], (legacy, unified)
    # The variant inventory (satellite: one structured line per sweep)
    # attributes the whole unified sweep to the single superstep op.
    assert set(unified["ops"]) == {"superstep"}
    assert unified["ops"]["superstep"] == unified["compiles"]
    assert set(legacy["ops"]) >= {"decode", "multistep", "verify"}


@pytest.mark.slow
def test_multihost_replay_leaves_identical_device_state(tiny):
    """OP_GEN_SUPERSTEP replay: the follower rebuilds each tick from the
    self-contained broadcast payload — tokens, lengths, K/V, and the
    sampling key chain end identical to the leader's."""
    from tpumlops.server.multihost import (
        OP_SHUTDOWN,
        UnitChannel,
        _LocalGroup,
        encode_message,
        follower_loop,
    )

    params, cfg = tiny
    group = _LocalGroup(2)
    transports = group.transports()
    channel = UnitChannel(transports[0])
    leader = _engine(params, cfg, channel=channel)
    follower = _engine(params, cfg)

    class _NoPredict:
        def predict(self, inputs):  # pragma: no cover - never called
            raise AssertionError("no predict ops in this test")

    result = {}

    def run():
        result["steps"] = follower_loop(
            _NoPredict(), transports[1], gen_engine=follower
        )

    th = threading.Thread(target=run, daemon=True)
    th.start()

    prompt = [5, 9, 2]
    leader.start(warmup=True)
    try:
        ref = _ref(params, cfg, prompt, 14)
        assert leader.generate(prompt, 14, timeout=300).tolist() == ref
        # Seeded sampling rides the same replay (key chains advance in
        # the compiled program, identically on every host).
        sampled = leader.generate(
            [7, 1, 4], 6, temperature=0.8, seed=7, timeout=300
        ).tolist()
        assert len(sampled) == 6
        assert leader.dispatches_total.get("superstep", 0) > 1
    finally:
        leader.shutdown()
        channel.close_with(encode_message(OP_SHUTDOWN))
    th.join(timeout=60)

    assert result.get("steps", 0) > 0
    for name in ("_tokens", "_lengths", "_cache_k", "_cache_v"):
        np.testing.assert_array_equal(
            np.asarray(getattr(leader, name)),
            np.asarray(getattr(follower, name)),
            err_msg=name,
        )
    import jax

    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(leader._keys)),
        np.asarray(jax.random.key_data(follower._keys)),
    )


@pytest.mark.slow
def test_superstep_tick_records_role_breakdown(tiny):
    """The flight recorder's superstep tick carries the per-dispatch
    role mix; no legacy tick kind ever appears on the unified engine."""
    from tpumlops.server.flight_recorder import FlightRecorder

    params, cfg = tiny
    recorder = FlightRecorder(capacity=512)
    engine = _engine(
        params, cfg, max_slots=4, prefill_chunk=8, prefill_batch=4,
        recorder=recorder,
    )
    prompts = [(list(range(2, 23)), 6), ([5, 9, 2], 6)]
    outs = _run(engine, [((p, n), {}) for p, n in prompts])
    assert outs == [_ref(params, cfg, p, n) for p, n in prompts]
    ticks = recorder.snapshot()["ticks"]
    supers = [t for t in ticks if t["kind"] == "superstep"]
    assert supers
    assert {t["kind"] for t in ticks} <= {"superstep", "seed", "kv-import"}
    for t in supers:
        assert set(t["roles"]) == {"prefill", "decode", "verify"}
        assert t["steps"] == 4
    # At least one dispatch mixed roles: a prefill chunk rode a tick
    # that also decoded (the interleave the unified program exists for).
    assert any(
        t["roles"]["prefill"] and t["roles"]["decode"] for t in supers
    )
