"""Tensor-parallel multi-chip serving (spec.tpu.meshShape tp > 1).

The acceptance bar (ISSUE 15): with ``meshShape {"dp": 1, "tp": N}`` the
engine compiles every program with explicit shardings — weights Megatron-
split, the ragged KV cache split on its heads axis, sampling state
replicated — and emitted tokens are token-for-token identical to the
tp=1 engine (f64, so no backend fast-math can blur it): greedy and
seeded sampling, prefix-cache + speculative + packed-prefill + multistep
composition, int8kv, and multihost lockstep replay.  The default
``{"dp": 1, "tp": 1}`` is pinned byte-for-byte: no mesh object, no
sharded program, single-device state.  tp in {2, 4} runs on the virtual
8-device CPU mesh (conftest) — the same SPMD programs a v5e slice
compiles.  Engine-tracing tests are ``slow`` (same policy as
test_multistep.py); constructor/validation pins run in the fast tranche.
"""

import numpy as np
import pytest

# ---------------------------------------------------------------------------
# Fast tranche: construction-time pins (no program ever traces)
# ---------------------------------------------------------------------------


def _tiny_cfg(**kw):
    from tpumlops.models import llama

    # Geometry every tp in {2, 4} divides (heads, kv heads, mlp, vocab).
    defaults = dict(num_heads=4, num_kv_heads=4, max_seq=64)
    defaults.update(kw)
    return llama.LlamaConfig.tiny(**defaults)


def test_default_mesh_builds_no_sharded_state():
    """meshShape {"dp": 1, "tp": 1} (and None) is byte-for-byte: no mesh
    object exists, no sharding handle exists, and the engine cache is
    ordinary single-device state."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama
    from tpumlops.server.generation import GenerationEngine

    cfg = _tiny_cfg()
    params = llama.init(jax.random.key(0), cfg)
    for shape in (None, {"dp": 1, "tp": 1}, {"tp": 1}):
        engine = GenerationEngine(
            params, cfg, max_slots=2, dtype=jnp.float32, mesh_shape=shape
        )
        assert engine._mesh is None
        assert engine._shard_kv is None and engine._shard_rep is None
        assert not hasattr(engine._cache_k.sharding, "spec") or (
            len(engine._cache_k.sharding.device_set) == 1
        )


def test_engine_rejects_non_dp_sp_tp_parallel_axes():
    """dp/sp/tp are real engine axes (PR 17); pp/ep stay typed-rejected
    — no pipeline or expert machinery exists to back them."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama
    from tpumlops.server.generation import GenerationEngine

    cfg = _tiny_cfg()
    params = llama.init(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="dp/sp/tp"):
        GenerationEngine(
            params, cfg, max_slots=2, dtype=jnp.float32,
            mesh_shape={"pp": 2, "tp": 2},
        )
    with pytest.raises(ValueError, match="dp/sp/tp"):
        GenerationEngine(
            params, cfg, max_slots=2, dtype=jnp.float32,
            mesh_shape={"ep": 2},
        )


def test_engine_rejects_indivisible_dp_rows_typed():
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama
    from tpumlops.server.generation import GenerationEngine

    cfg = _tiny_cfg()
    params = llama.init(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="does not divide maxSlots"):
        GenerationEngine(
            params, cfg, max_slots=3, dtype=jnp.float32,
            mesh_shape={"dp": 2},
        )


def test_engine_rejects_non_power_of_two_sp_typed():
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama
    from tpumlops.server.generation import GenerationEngine

    cfg = _tiny_cfg()
    params = llama.init(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="power of two"):
        GenerationEngine(
            params, cfg, max_slots=4, dtype=jnp.float32,
            mesh_shape={"sp": 3},
        )


def test_engine_rejects_indivisible_tp_typed():
    """The engine-side half of the reconcile-time check: a tp that does
    not divide the KV-head count fails typed at CONSTRUCTION (before any
    device state), naming the knob — not as an XLA shape error at the
    first warmup dispatch."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama
    from tpumlops.server.generation import GenerationEngine

    cfg = _tiny_cfg(num_heads=4, num_kv_heads=2)
    params = llama.init(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="meshShape tp=4.*num_kv_heads"):
        GenerationEngine(
            params, cfg, max_slots=2, dtype=jnp.float32,
            mesh_shape={"dp": 1, "tp": 4},
        )


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

    cfg = _tiny_cfg()
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


def _engine(params, cfg, tp=1, **kw):
    import jax.numpy as jnp

    from tpumlops.server.generation import GenerationEngine

    mesh_shape = {"dp": 1, "tp": tp}
    if tp > 1:
        from tpumlops.models import partition

        params = partition.shard_llama_params(
            params, partition.build_serving_mesh(mesh_shape)
        )
    return GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float64,
        mesh_shape=mesh_shape, **kw,
    )


@pytest.mark.parametrize("tp", [2, 4])
def test_greedy_parity_with_slot_churn(tiny, tp):
    """f64 token-for-token: tp-sharded greedy decode across staggered
    joins and slot reuse equals tp=1, the cache STAYS sharded across
    ticks (no per-tick gather), and per-token dispatch counts are
    unchanged."""
    from jax.sharding import PartitionSpec as P

    params, cfg = tiny
    prompts = [
        ([1, 2, 3] * 5, 10),
        ([5, 9, 2], 6),
        ([7, 1, 4, 8, 3], 9),
        ([42], 4),
    ]
    counts = {}
    outs = {}
    for degree in (1, tp):
        engine = _engine(params, cfg, tp=degree)
        engine.start(warmup=False)
        try:
            # Serial submissions: deterministic tick schedule, so the
            # dispatch ledgers of the two degrees are comparable 1:1.
            outs[degree] = [
                engine.generate(p, n, timeout=300).tolist()
                for p, n in prompts
            ]
            counts[degree] = dict(engine.dispatches_total)
            if degree > 1:
                assert engine._cache_k.sharding.spec == P(
                    None, None, None, "tp", None
                )
                assert engine._lengths.sharding.spec == P()
        finally:
            engine.shutdown()
    refs = [_ref(params, cfg, p, n) for p, n in prompts]
    assert outs[1] == refs
    assert outs[tp] == refs
    # Sharding must not add host round-trips: dispatches per kind equal.
    assert counts[tp] == counts[1]


@pytest.mark.slow
@pytest.mark.parametrize("tp", [2, 4])
def test_seeded_sampling_parity(tiny, tp):
    """Seeded sampling: the replicated on-device key chain advances
    identically on every chip — same seed, same stream, at every tp."""
    params, cfg = tiny
    req = dict(temperature=0.9, top_k=7, top_p=0.95, seed=123)
    outs = {}
    for degree in (1, tp):
        engine = _engine(params, cfg, tp=degree)
        engine.start(warmup=False)
        try:
            outs[degree] = engine.generate(
                [5, 9, 2], 9, timeout=300, **req
            ).tolist()
        finally:
            engine.shutdown()
    assert outs[tp] == outs[1]
    assert len(outs[1]) == 9


@pytest.mark.slow
def test_full_composition_parity_tp2(tiny):
    """The whole stack at once — prefix cache (chunked prefill), packed
    multi-admission prefill, fused K-step decode, self-speculative
    drafting — token-for-token across tp=2 vs tp=1, with the warm
    prefix path actually seeding."""
    from tpumlops.server.prefix_cache import PrefixCacheConfig
    from tpumlops.server.speculative import SpeculativeConfig

    params, cfg = tiny
    shared = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3]  # one chunk
    kw = dict(
        decode_steps=4,
        prefill_chunk=16,
        prefill_batch=2,
        prefix_cache=PrefixCacheConfig(
            enabled=True, budget_bytes=1 << 22, chunk_tokens=16
        ),
        speculative=SpeculativeConfig(
            enabled=True, draft_tokens=2, ngram_min=1, ngram_max=4,
            adaptive=True,
        ),
    )
    outs = {}
    hits = {}
    for degree in (1, 2):
        engine = _engine(params, cfg, tp=degree, **kw)
        engine.start(warmup=False)
        try:
            o = []
            o.append(engine.generate(shared + [11, 12], 8,
                                     timeout=300).tolist())
            o.append(engine.generate(shared + [13], 8, timeout=300).tolist())
            o.append(engine.generate([1, 2, 3] * 5, 10, timeout=300).tolist())
            outs[degree] = o
            hits[degree] = engine.prefix_hits
        finally:
            engine.shutdown()
    assert outs[2] == outs[1]
    assert outs[1][0] == _ref(params, cfg, shared + [11, 12], 8)
    assert outs[1][2] == _ref(params, cfg, [1, 2, 3] * 5, 10)
    assert hits[1] > 0 and hits[2] > 0  # the warm path seeded on both


@pytest.mark.slow
def test_int8kv_cache_parity_tp2(tiny):
    """int8kv at tp=2: the (values, scales) cache pair shards on its
    heads axis and quantized decode matches the tp=1 int8kv stream
    token-for-token (quantization error is identical per shard — the
    per-(pos, head) scales are head-local)."""
    params, cfg = tiny
    outs = {}
    for degree in (1, 2):
        engine = _engine(params, cfg, tp=degree, kv_quant=True)
        engine.start(warmup=False)
        try:
            outs[degree] = engine.generate([5, 9, 2], 8, timeout=300).tolist()
            if degree == 2:
                from jax.sharding import PartitionSpec as P

                k8, kscale = engine._cache_k
                assert k8.sharding.spec == P(None, None, None, "tp", None)
                assert kscale.sharding.spec == P(None, None, None, "tp", None)
        finally:
            engine.shutdown()
    assert outs[2] == outs[1]


@pytest.mark.slow
def test_warmup_sweep_compiles_under_mesh(tiny):
    """The full warmup sweep (decode buckets x variants, verify chain,
    fused K, packed B_p buckets, seed ops) runs under the tp mesh and
    serves a real request after — no live-path lazy compile, no shape
    error anywhere in the swept grid."""
    from tpumlops.server.prefix_cache import PrefixCacheConfig

    params, cfg = tiny
    engine = _engine(
        params, cfg, tp=2, decode_steps=2, prefill_chunk=16,
        prefill_batch=2,
        prefix_cache=PrefixCacheConfig(
            enabled=True, budget_bytes=1 << 22, chunk_tokens=16
        ),
    )
    engine.start(warmup=True)
    try:
        out = engine.generate([5, 9, 2], 6, timeout=300).tolist()
    finally:
        engine.shutdown()
    assert out == _ref(params, cfg, [5, 9, 2], 6)


@pytest.mark.slow
def test_multihost_replay_state_equality_tp2(tiny):
    """Leader/follower lockstep at tp=2: the follower replays every
    sharded op and both processes' device state — tokens, lengths,
    sharded cache shards, key chains — ends identical."""
    import threading

    import jax

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
    leader = _engine(params, cfg, tp=2, decode_steps=2, channel=channel)
    follower = _engine(params, cfg, tp=2, decode_steps=2)

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

    leader.start(warmup=False)
    try:
        ref = _ref(params, cfg, [5, 9, 2], 10)
        assert leader.generate([5, 9, 2], 10, timeout=300).tolist() == ref
        sampled = leader.generate(
            [7, 1, 4], 6, temperature=0.8, seed=7, timeout=300
        ).tolist()
        assert len(sampled) == 6
    finally:
        leader.shutdown()
        channel.close_with(encode_message(OP_SHUTDOWN))
    th.join(timeout=60)

    assert result.get("steps", 0) > 0
    np.testing.assert_array_equal(
        np.asarray(leader._tokens), np.asarray(follower._tokens)
    )
    np.testing.assert_array_equal(
        np.asarray(leader._lengths), np.asarray(follower._lengths)
    )
    np.testing.assert_array_equal(
        np.asarray(leader._cache_k), np.asarray(follower._cache_k)
    )
    np.testing.assert_array_equal(
        np.asarray(leader._cache_v), np.asarray(follower._cache_v)
    )
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(leader._keys)),
        np.asarray(jax.random.key_data(follower._keys)),
    )
    # Replay preserved the follower's SHARDED layout too.
    assert (
        leader._cache_k.sharding.spec == follower._cache_k.sharding.spec
    )


@pytest.mark.slow
def test_per_chip_ledger_and_collectives_under_tp(tiny, cpu_peaks):
    """Device telemetry learns the tp axis: per-chip HBM components
    (exact shard bytes for the weights, heads/tp for the KV rows) and
    analytic collective walls appear at tp=2 — and the tp=1 snapshot of
    the same model carries NEITHER (byte-for-byte pin)."""
    import jax

    from tpumlops.models import partition
    from tpumlops.server.device_telemetry import DeviceTelemetry

    params, cfg = tiny
    mesh = partition.build_serving_mesh({"dp": 1, "tp": 2})
    sharded = partition.shard_llama_params(params, mesh)

    tel = DeviceTelemetry(peaks=cpu_peaks)
    tel.attach_model(sharded, cfg, max_slots=2)
    ledger = tel.ledger
    assert ledger.per_chip, "per-chip view missing at tp=2"
    total = sum(
        v for k, v in ledger.components.items() if k.startswith("weights_")
    )
    chip = sum(
        v for k, v in ledger.per_chip.items() if k.startswith("weights_")
    )
    # Sharded matrices halve; replicated norms don't: strictly between.
    assert total / 2 < chip < total
    assert ledger.per_chip["kv_bytes_per_row"] * 2 == ledger.kv_bytes_per_row
    # Analytic collective walls ride decode ticks at tp>1 only.
    util = tel.tick_util("decode", 0.01, 1e6, 1e6)
    assert util.get("collective_s", 0) > 0
    coll = tel.cost.collective_bytes(2)
    assert coll["all_reduce"] > 0 and coll["all_gather"] > 0

    tel1 = DeviceTelemetry(peaks=cpu_peaks)
    tel1.attach_model(params, cfg, max_slots=2)
    assert not tel1.ledger.per_chip
    assert tel1.cost.collective_bytes(2) == {}
    assert "collective_s" not in tel1.tick_util("decode", 0.01, 1e6, 1e6)
