"""Data-parallel batch serving (spec.tpu.meshShape dp > 1) — PR 17.

The acceptance bar: with ``meshShape {"dp": N}`` the ragged KV cache
shards its ROW (slot/batch) axis over dp while the weights and sampling
state replicate — and emitted tokens are token-for-token identical to
the dp=1 engine in f64 across greedy + slot churn, seeded sampling, the
prefix-cache/speculative/packed-prefill composition, the unified
super-step, int8kv, and multihost lockstep replay.  dp composes with tp
({"dp": 2, "tp": 2}) on the virtual 8-device CPU mesh (conftest).  No
new programs and no extra dispatches: the per-kind dispatch ledger at
dp=N equals dp=1 exactly.  Engine-tracing tests are ``slow``;
constructor/geometry pins run in the fast tranche.
"""

import numpy as np
import pytest

# ---------------------------------------------------------------------------
# Fast tranche: construction-time geometry pins
# ---------------------------------------------------------------------------


def _tiny_cfg(**kw):
    from tpumlops.models import llama

    defaults = dict(num_heads=4, num_kv_heads=4, max_seq=64)
    defaults.update(kw)
    return llama.LlamaConfig.tiny(**defaults)


def test_dp_cache_rows_shard_and_sampling_state_replicates():
    """dp=2: the ragged cache's row axis carries the dp mesh axis, the
    lengths/sampling state stays replicated, and the weights replicate
    (every device holds the full tree)."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama, partition
    from tpumlops.server.generation import GenerationEngine

    cfg = _tiny_cfg()
    params = llama.init(jax.random.key(0), cfg)
    mesh = partition.build_serving_mesh({"dp": 2})
    engine = GenerationEngine(
        params, cfg, max_slots=4, dtype=jnp.float32,
        mesh_shape={"dp": 2},
    )
    assert engine._dp == 2
    assert engine._cache_k.sharding.spec[1] == "dp"
    assert engine._lengths.sharding.is_fully_replicated
    del mesh


def test_dp_free_slot_balances_across_row_shards():
    """Admission spreads across the contiguous dp row blocks: with shard
    0 fuller than shard 1, the next slot comes from shard 1 — filling
    0..k-1 first would idle every chip but the first."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama
    from tpumlops.server.generation import GenerationEngine

    cfg = _tiny_cfg()
    params = llama.init(jax.random.key(0), cfg)
    engine = GenerationEngine(
        params, cfg, max_slots=4, dtype=jnp.float32,
        mesh_shape={"dp": 2},
    )
    # rows = 4 // 2 = 2: slots {0,1} are shard 0, {2,3} are shard 1.
    engine._slots[0] = object()
    assert engine._free_slot() == 2  # least-loaded shard, lowest index
    engine._slots[2] = object()
    assert engine._free_slot() == 1  # tie -> lowest index
    engine._slots[0] = None
    engine._slots[2] = None
    assert engine._free_slot() == 0  # empty engine: plain first-free


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


def _engine(params, cfg, mesh_shape=None, max_slots=4, **kw):
    import jax.numpy as jnp

    from tpumlops.models import partition
    from tpumlops.server.generation import GenerationEngine

    if mesh_shape and partition.mesh_device_count(mesh_shape) > 1:
        params = partition.shard_llama_params(
            params, partition.build_serving_mesh(mesh_shape)
        )
    return GenerationEngine(
        params, cfg, max_slots=max_slots, dtype=jnp.float64,
        mesh_shape=mesh_shape, **kw,
    )


@pytest.mark.parametrize("dp", [2, 4])
def test_dp_greedy_parity_with_slot_churn(tiny, dp):
    """f64 token-for-token: dp-sharded greedy decode across staggered
    joins and slot reuse equals dp=1, the cache rows STAY dp-sharded
    across ticks, and the per-kind dispatch ledger is unchanged — dp
    adds zero programs and zero host round-trips."""
    params, cfg = tiny
    prompts = [
        ([1, 2, 3] * 5, 10),
        ([5, 9, 2], 6),
        ([7, 1, 4, 8, 3], 9),
        ([42], 4),
        ([9, 9, 1, 2], 7),
    ]
    counts = {}
    outs = {}
    for degree in (1, dp):
        shape = {"dp": degree} if degree > 1 else None
        engine = _engine(params, cfg, mesh_shape=shape)
        engine.start(warmup=False)
        try:
            outs[degree] = [
                engine.generate(p, n, timeout=300).tolist()
                for p, n in prompts
            ]
            counts[degree] = dict(engine.dispatches_total)
            if degree > 1:
                assert engine._cache_k.sharding.spec[1] == "dp"
        finally:
            engine.shutdown()
    refs = [_ref(params, cfg, p, n) for p, n in prompts]
    assert outs[1] == refs
    assert outs[dp] == refs
    assert counts[dp] == counts[1]


@pytest.mark.slow
def test_dp_seeded_sampling_parity(tiny):
    """Seeded sampling at dp=2: the replicated key chain advances
    identically — same seed, same stream, regardless of which row shard
    the slot landed on."""
    params, cfg = tiny
    req = dict(temperature=0.9, top_k=7, top_p=0.95, seed=123)
    outs = {}
    for shape in (None, {"dp": 2}):
        engine = _engine(params, cfg, mesh_shape=shape)
        engine.start(warmup=False)
        try:
            key = "dp" if shape else "base"
            outs[key] = engine.generate(
                [5, 9, 2], 9, timeout=300, **req
            ).tolist()
        finally:
            engine.shutdown()
    assert outs["dp"] == outs["base"]
    assert len(outs["base"]) == 9


@pytest.mark.slow
def test_dp_full_composition_parity(tiny):
    """Prefix cache (chunked prefill) + packed multi-admission prefill +
    fused K-step decode + self-speculative drafting, token-for-token at
    dp=2 vs dp=1, with the warm prefix path actually seeding on both."""
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
    for shape in (None, {"dp": 2}):
        key = "dp" if shape else "base"
        engine = _engine(params, cfg, mesh_shape=shape, **kw)
        engine.start(warmup=False)
        try:
            o = []
            o.append(engine.generate(shared + [11, 12], 8,
                                     timeout=300).tolist())
            o.append(engine.generate(shared + [13], 8, timeout=300).tolist())
            o.append(engine.generate([1, 2, 3] * 5, 10, timeout=300).tolist())
            outs[key] = o
            hits[key] = engine.prefix_hits
        finally:
            engine.shutdown()
    assert outs["dp"] == outs["base"]
    assert outs["base"][0] == _ref(params, cfg, shared + [11, 12], 8)
    assert hits["base"] > 0 and hits["dp"] > 0


@pytest.mark.slow
def test_dp_superstep_parity(tiny):
    """The unified super-step (one dispatch per tick) under dp=2: same
    tokens as the dp=1 super-step AND the legacy per-phase dp=1 engine,
    with 'superstep' actually carrying the ticks."""
    params, cfg = tiny
    prompts = [([5, 9, 2], 8), ([1, 2, 3, 4, 5], 6)]
    outs = {}
    counts = {}
    for key, shape in (("base", None), ("dp", {"dp": 2})):
        engine = _engine(
            params, cfg, mesh_shape=shape, unified_step=True,
            decode_steps=2,
        )
        engine.start(warmup=False)
        try:
            outs[key] = [
                engine.generate(p, n, timeout=300).tolist()
                for p, n in prompts
            ]
            counts[key] = dict(engine.dispatches_total)
        finally:
            engine.shutdown()
    refs = [_ref(params, cfg, p, n) for p, n in prompts]
    assert outs["base"] == refs
    assert outs["dp"] == refs
    assert counts["dp"].get("superstep", 0) > 0
    assert counts["dp"] == counts["base"]


@pytest.mark.slow
def test_dp_int8kv_cache_parity(tiny):
    """int8kv at dp=2: the (values, scales) cache pair shards on its ROW
    axis and quantized decode matches the dp=1 int8kv stream — the
    per-(pos, head) scales are row-local, so sharding rows cannot move
    the quantization error."""
    params, cfg = tiny
    outs = {}
    for shape in (None, {"dp": 2}):
        key = "dp" if shape else "base"
        engine = _engine(params, cfg, mesh_shape=shape, kv_quant=True)
        engine.start(warmup=False)
        try:
            outs[key] = engine.generate([5, 9, 2], 8, timeout=300).tolist()
            if shape:
                k8, kscale = engine._cache_k
                assert k8.sharding.spec[1] == "dp"
                assert kscale.sharding.spec[1] == "dp"
        finally:
            engine.shutdown()
    assert outs["dp"] == outs["base"]


@pytest.mark.slow
def test_dp_tp_composed_mesh_parity(tiny):
    """The full 2x2 mesh: rows shard over dp, heads over tp, on the same
    cache — tokens equal the single-device stream and the cache spec
    carries BOTH axes."""
    params, cfg = tiny
    prompts = [([5, 9, 2], 8), ([7, 1, 4, 8, 3], 6), ([42], 5)]
    engine = _engine(params, cfg, mesh_shape={"dp": 2, "tp": 2})
    engine.start(warmup=False)
    try:
        outs = [
            engine.generate(p, n, timeout=300).tolist() for p, n in prompts
        ]
        spec = engine._cache_k.sharding.spec
        assert spec[1] == "dp" and spec[3] == "tp"
    finally:
        engine.shutdown()
    assert outs == [_ref(params, cfg, p, n) for p, n in prompts]


@pytest.mark.slow
def test_multihost_replay_state_equality_dp2(tiny):
    """Leader/follower lockstep at dp=2: the follower replays the SAME
    op stream (no dp-specific ops exist) and both processes' device
    state — tokens, lengths, row-sharded cache, key chains — ends
    identical, shard layout included."""
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
    leader = _engine(
        params, cfg, mesh_shape={"dp": 2}, decode_steps=2, channel=channel
    )
    follower = _engine(params, cfg, mesh_shape={"dp": 2}, decode_steps=2)

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
    assert (
        leader._cache_k.sharding.spec == follower._cache_k.sharding.spec
    )


@pytest.mark.slow
def test_dp_snapshot_geometry_dedupes_to_tp_bytes(tiny, tmp_path):
    """Snapshot geometry under dp: weights replicate over dp, so a
    {dp:2, tp:2} bake writes the SAME per-leaf shard records (count and
    bytes) as the {dp:1, tp:2} bake — partial replication dedupes by
    slice start — and the restore under the dp identity is
    bit-identical with specs preserved."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama, partition
    from tpumlops.server import snapshot as snap

    cfg = _tiny_cfg()
    base = llama.init(jax.random.key(3), cfg, dtype=jnp.float32)
    trees = {}
    paths = {}
    for name, shape in (("tp", {"dp": 1, "tp": 2}),
                        ("dptp", {"dp": 2, "tp": 2})):
        mesh = partition.build_serving_mesh(shape)
        tree = partition.shard_llama_params(base, mesh)
        ident = snap.snapshot_identity("model://dp", "none", shape)
        d = tmp_path / name
        d.mkdir()
        paths[name] = snap.write_snapshot(
            d, tree, identity=ident, flavor="llama-generate"
        )
        trees[name] = (tree, ident)

    m_tp = snap.read_manifest(paths["tp"])
    m_dptp = snap.read_manifest(paths["dptp"])
    def geom(m):
        return [
            (
                leaf["key"],
                len(leaf["shards"]) if "shards" in leaf else None,
                sum(s["nbytes"] for s in leaf["shards"])
                if "shards" in leaf else leaf["nbytes"],
            )
            for leaf in sorted(m["leaves"], key=lambda l: l["key"])
        ]

    assert geom(m_dptp) == geom(m_tp)
    assert m_dptp["total_bytes"] == m_tp["total_bytes"]

    tree, ident = trees["dptp"]
    restored, _ = snap.load_snapshot(paths["dptp"], identity=ident)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.sharding.spec == b.sharding.spec
