"""Radix prefix KV cache: tree semantics, LRU budget, engine parity.

Pure radix/LRU tests run in the fast tranche; everything that traces
jitted programs on the tiny CPU llama fixture is marked ``slow`` (same
policy as test_generation.py — exact-parity runs in float64 so no
backend fast-math can blur the bit-identity assertions).
"""

import numpy as np
import pytest

from tpumlops.server.prefix_cache import PrefixCacheConfig, RadixPrefixCache


def _kv(nbytes_each: int = 64):
    """A (k, v) host pair of a known byte size."""
    k = np.zeros((nbytes_each // 8,), np.float64)
    return k, k.copy()


def _chunks(*tokens_lists):
    return np.concatenate([np.asarray(t, np.int32) for t in tokens_lists])


# ---------------------------------------------------------------------------
# Radix tree semantics (pure python, fast tranche)
# ---------------------------------------------------------------------------


def test_radix_longest_prefix_match():
    cache = RadixPrefixCache(budget_bytes=1 << 20, chunk_tokens=4)
    a, b, c = [1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]
    prompt = _chunks(a, b, c, [13])
    k0, v0 = _kv()
    k1, v1 = _kv()
    assert cache.insert_chunk(prompt, 0, k0, v0)
    assert cache.insert_chunk(prompt, 1, k1, v1)

    # Full two-chunk match; the third chunk was never inserted.
    n, kvs = cache.lookup(prompt)
    assert n == 8
    assert len(kvs) == 2
    assert kvs[0][0] is k0 and kvs[1][0] is k1

    # Divergence after chunk 0: only chunk 0 matches.
    other = _chunks(a, [99, 98, 97, 96], [1])
    n, kvs = cache.lookup(other)
    assert n == 4 and len(kvs) == 1

    # No shared prefix at all.
    n, kvs = cache.lookup(_chunks([42, 42, 42, 42], [1]))
    assert n == 0 and kvs == []


def test_radix_match_capped_below_prompt_length():
    """At least one token must run real prefill: a fully-cached prompt
    still gets its last chunk(s) recomputed for final-position logits."""
    cache = RadixPrefixCache(budget_bytes=1 << 20, chunk_tokens=4)
    a, b = [1, 2, 3, 4], [5, 6, 7, 8]
    prompt = _chunks(a, b)
    cache.insert_chunk(prompt, 0, *_kv())
    cache.insert_chunk(prompt, 1, *_kv())
    # len 8, C=4: max match is (8-1)//4 = 1 chunk, never both.
    n, kvs = cache.lookup(prompt)
    assert n == 4 and len(kvs) == 1
    # One token longer: both chunks may serve.
    n, _ = cache.lookup(_chunks(a, b, [9]))
    assert n == 8


def test_radix_insert_requires_parent_path():
    """Attaching chunk k without chunks 0..k-1 must be refused — the
    cumulative key would be wrong."""
    cache = RadixPrefixCache(budget_bytes=1 << 20, chunk_tokens=4)
    prompt = _chunks([1, 2, 3, 4], [5, 6, 7, 8], [9])
    assert not cache.insert_chunk(prompt, 1, *_kv())
    assert len(cache) == 0
    assert cache.insert_chunk(prompt, 0, *_kv())
    assert cache.insert_chunk(prompt, 1, *_kv())
    assert len(cache) == 2


def test_lru_eviction_at_byte_budget():
    """Budget fits 3 chunk entries; the least-recently-used LEAF goes."""
    evicted = []
    cache = RadixPrefixCache(
        budget_bytes=3 * 128, chunk_tokens=4, on_evict=evicted.append
    )
    pa = _chunks([1, 1, 1, 1], [2, 2, 2, 2], [0])
    pb = _chunks([3, 3, 3, 3], [0])
    pc = _chunks([4, 4, 4, 4], [0])
    cache.insert_chunk(pa, 0, *_kv(64))
    cache.insert_chunk(pa, 1, *_kv(64))
    cache.insert_chunk(pb, 0, *_kv(64))
    assert cache.bytes == 3 * 128 and cache.evictions == 0

    # Touch pa (both nodes) so pb becomes the LRU leaf, then overflow.
    cache.lookup(pa)
    cache.insert_chunk(pc, 0, *_kv(64))
    assert cache.evictions == 1 and evicted == [128]
    assert cache.bytes == 3 * 128
    assert cache.lookup(pb)[0] == 0  # pb evicted
    assert cache.lookup(pa)[0] == 8  # recently-used survived
    assert cache.lookup(pc)[0] == 4

    # Interior nodes are never evicted from under their children: pa's
    # chunk-0 node is interior; repeated pressure drains leaves first.
    pd = _chunks([5, 5, 5, 5], [0])
    cache.insert_chunk(pd, 0, *_kv(64))
    assert cache.lookup(pa)[0] >= 4


def test_spec_chunk_tokens_follows_prefill_chunk_and_rejects_mismatch():
    """The likely misconfiguration (prefillChunk set, chunkTokens left
    to default) must resolve at reconcile time, and an EXPLICIT mismatch
    must fail there — in CR status, not as a pod CrashLoopBackOff."""
    from tpumlops.utils.config import TpuSpec

    t = TpuSpec.from_spec(
        {"prefillChunk": 256, "prefixCache": {"enabled": True}}
    )
    assert t.prefix_cache.chunk_tokens == 256
    with pytest.raises(ValueError, match="chunkTokens"):
        TpuSpec.from_spec(
            {"prefillChunk": 256,
             "prefixCache": {"enabled": True, "chunkTokens": 64}}
        )
    # Disabled cache: never rejects (old CRs keep parsing unchanged).
    t2 = TpuSpec.from_spec(
        {"prefillChunk": 256, "prefixCache": {"chunkTokens": 64}}
    )
    assert not t2.prefix_cache.enabled
    # No prefillChunk: chunkTokens stands alone (default 64).
    assert TpuSpec.from_spec(
        {"prefixCache": {"enabled": True}}
    ).prefix_cache.chunk_tokens == 64


def test_oversized_chunk_and_bad_config_rejected():
    cache = RadixPrefixCache(budget_bytes=100, chunk_tokens=4)
    assert not cache.insert_chunk(_chunks([1, 2, 3, 4], [0]), 0, *_kv(64))
    assert cache.bytes == 0
    with pytest.raises(ValueError, match="budget"):
        RadixPrefixCache(budget_bytes=0, chunk_tokens=4)
    with pytest.raises(ValueError, match="chunk_tokens"):
        RadixPrefixCache(budget_bytes=100, chunk_tokens=0)


# ---------------------------------------------------------------------------
# Second tier (host-RAM L2): spill on L1 eviction, promote on miss
# ---------------------------------------------------------------------------


def test_l2_catches_evictions_and_promotes_on_lookup():
    events = []
    cache = RadixPrefixCache(
        budget_bytes=2 * 128, chunk_tokens=4,
        l2_budget_bytes=1 << 20, on_l2_event=events.append,
    )
    pa = _chunks([1, 1, 1, 1], [0])
    pb = _chunks([2, 2, 2, 2], [0])
    pc = _chunks([3, 3, 3, 3], [0])
    ka, va = _kv(64)
    cache.insert_chunk(pa, 0, ka, va)
    cache.insert_chunk(pb, 0, *_kv(64))
    # Overflow: pa (LRU) spills into the L2 instead of vanishing.
    cache.insert_chunk(pc, 0, *_kv(64))
    assert cache.evictions == 1 and cache.l2_spills == 1
    assert cache.l2_bytes == 128
    assert events == ["spill"]
    # The radix walk misses, the L2 serves, the chunk is BACK in the
    # tree (and out of the L2) with its exact arrays.
    n, kvs = cache.lookup(pa)
    assert n == 4
    assert kvs[0][0] is ka and kvs[0][1] is va
    # Promotion freed pa's L2 entry and spilled the then-LRU (pb) down.
    assert cache.l2_hits == 1 and cache.l2_bytes == 128
    assert events == ["spill", "hit", "spill"]
    # Promotion kept L1 within budget by spilling the then-LRU entry.
    assert cache.bytes <= cache.budget_bytes


def test_l2_lru_ages_out_under_its_own_budget():
    cache = RadixPrefixCache(
        budget_bytes=128, chunk_tokens=4, l2_budget_bytes=2 * 128
    )
    prompts = [_chunks([i, i, i, i], [0]) for i in range(1, 5)]
    for p in prompts:
        cache.insert_chunk(p, 0, *_kv(64))
    # Each insert evicts the previous leaf into the L2; the L2 itself
    # holds 2 entries, so the two oldest spills aged out.
    assert cache.l2_spills == 3
    assert cache.l2_evictions == 1
    assert cache.l2_bytes == 2 * 128
    # The aged-out chunk is gone from both tiers.
    assert cache.lookup(prompts[0])[0] == 0
    assert cache.l2_hits == 0
    # A surviving spill still promotes.
    assert cache.lookup(prompts[2])[0] == 4
    assert cache.l2_hits == 1


def test_l2_disabled_is_single_tier_byte_for_byte():
    cache = RadixPrefixCache(budget_bytes=128, chunk_tokens=4)
    pa = _chunks([1, 1, 1, 1], [0])
    pb = _chunks([2, 2, 2, 2], [0])
    cache.insert_chunk(pa, 0, *_kv(64))
    cache.insert_chunk(pb, 0, *_kv(64))
    cache.insert_chunk(_chunks([3, 3, 3, 3], [0]), 0, *_kv(64))
    assert cache.evictions >= 1
    assert cache.l2_spills == 0 and cache.l2_bytes == 0
    assert cache.lookup(pa)[0] == 0  # evicted means GONE, no second tier


def test_l2_spec_knob_parses_and_rejects_negatives():
    from tpumlops.utils.config import TpuSpec

    t = TpuSpec.from_spec(
        {"prefixCache": {"enabled": True, "l2BudgetMB": 512}}
    )
    assert t.prefix_cache.l2_budget_mb == 512
    assert TpuSpec.from_spec({}).prefix_cache.l2_budget_mb == 0
    with pytest.raises(ValueError, match="l2BudgetMB"):
        TpuSpec.from_spec(
            {"prefixCache": {"enabled": True, "l2BudgetMB": -1}}
        )


# ---------------------------------------------------------------------------
# Engine integration on the tiny CPU llama fixture (slow tranche)
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


def _ref(params, cfg, prompt, n):
    import jax.numpy as jnp

    from tpumlops.models import llama

    out = llama.generate_greedy(
        params, jnp.asarray([prompt], jnp.int32), n, cfg, dtype=jnp.float64
    )
    return np.asarray(out)[0].tolist()


def _engine(params, cfg, budget_bytes=1 << 22, **kw):
    import jax.numpy as jnp

    from tpumlops.server.generation import GenerationEngine

    return GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float64,
        prefix_cache=PrefixCacheConfig(
            enabled=True, budget_bytes=budget_bytes, chunk_tokens=8
        ),
        **kw,
    )


def test_cached_prefix_bit_identical_to_cold_prefill(tiny):
    """The acceptance bar: a warm (cached-prefix) admission must produce
    BIT-identical final-position logits and tokens to the cold one."""
    params, cfg = tiny
    prompt = list(range(2, 22))  # 20 tokens; C=8 -> cached prefix is 16
    ref = _ref(params, cfg, prompt, 5)

    engine = _engine(params, cfg)
    # Capture the exact pre-insert logits of every admission.
    captured = []
    real_insert = engine._device_insert

    def spy(*a, **kw):
        captured.append(np.asarray(engine._seq_state[0]))
        return real_insert(*a, **kw)

    engine._device_insert = spy
    engine.start(warmup=True)
    try:
        out_cold = engine.generate(prompt, 5).tolist()
        chunks_cold = engine.prefill_chunks_dispatched
        assert engine.prefix_hits == 0
        out_warm = engine.generate(prompt, 5).tolist()
        chunks_warm = engine.prefill_chunks_dispatched - chunks_cold
    finally:
        engine.shutdown()

    assert out_cold == ref and out_warm == ref
    # Cached admit skipped recomputation: 3 chunk calls cold, 1 warm.
    assert chunks_cold == 3 and chunks_warm == 1
    assert engine.prefix_hits == 1
    assert engine.prefix_cached_tokens == 16
    # Bit-identical logits at the sampled position (row 3 of the final
    # chunk: token 19 of 20 at chunk offset 16).
    assert np.array_equal(captured[0][3], captured[1][3])


@pytest.mark.slow
def test_partial_prefix_reuse_across_different_prompts(tiny):
    """A second prompt sharing only the first chunk reuses exactly that
    chunk and still matches the greedy reference."""
    params, cfg = tiny
    shared = list(range(2, 10))  # exactly one 8-token chunk
    p1 = shared + [30, 31, 32]
    p2 = shared + [40, 41, 42, 43]
    engine = _engine(params, cfg)
    engine.start(warmup=True)
    try:
        out1 = engine.generate(p1, 4).tolist()
        out2 = engine.generate(p2, 4).tolist()
        assert engine.prefix_hits == 1
        assert engine.prefix_cached_tokens == 8
    finally:
        engine.shutdown()
    assert out1 == _ref(params, cfg, p1, 4)
    assert out2 == _ref(params, cfg, p2, 4)


@pytest.mark.slow
def test_disabled_cache_behaves_exactly_as_before(tiny):
    """enabled: false must be byte-for-byte the old chunked engine: no
    lookups, no seeds, same chunk count on repeat prompts."""
    import jax.numpy as jnp

    from tpumlops.server.generation import GenerationEngine

    params, cfg = tiny
    prompt = list(range(2, 22))
    engine = GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float64, prefill_chunk=8,
        prefix_cache=PrefixCacheConfig(enabled=False),
    )
    assert engine._prefix_cache is None
    engine.start(warmup=True)
    try:
        ref = _ref(params, cfg, prompt, 4)
        assert engine.generate(prompt, 4).tolist() == ref
        assert engine.generate(prompt, 4).tolist() == ref
        assert engine.prefix_hits == 0
        assert engine.prefill_chunks_dispatched == 6  # 3 + 3, no reuse
    finally:
        engine.shutdown()


@pytest.mark.slow
def test_chunk_mismatch_rejected_and_chunking_derived(tiny):
    import jax.numpy as jnp

    from tpumlops.server.generation import GenerationEngine

    params, cfg = tiny
    with pytest.raises(ValueError, match="chunkTokens"):
        GenerationEngine(
            params, cfg, dtype=jnp.float64, prefill_chunk=16,
            prefix_cache=PrefixCacheConfig(enabled=True, chunk_tokens=8),
        )
    # prefillChunk unset: enabling the cache turns on chunked prefill.
    engine = GenerationEngine(
        params, cfg, dtype=jnp.float64,
        prefix_cache=PrefixCacheConfig(enabled=True, chunk_tokens=8),
    )
    assert engine._prefill_chunk_size == 8


@pytest.mark.slow
def test_eviction_under_tight_budget_keeps_results_exact(tiny):
    """A budget that can't hold both prompts' prefixes forces evictions;
    correctness must be unaffected (cache misses just re-prefill)."""
    params, cfg = tiny
    # One f64 chunk node: 2 * L*1*C*NKV*D * 8B = 2*2*8*2*16*8 = 8 KiB.
    p1 = list(range(2, 22))
    p2 = list(range(100, 120))
    engine = _engine(params, cfg, budget_bytes=9 * 1024)  # ~1 node
    engine.start(warmup=True)
    try:
        for p in (p1, p2, p1, p2):
            assert engine.generate(p, 3).tolist() == _ref(params, cfg, p, 3)
        assert engine.prefix_evictions > 0
    finally:
        engine.shutdown()


@pytest.mark.slow
def test_prefix_hit_and_evict_callbacks_fire(tiny):
    params, cfg = tiny
    hits = []
    evicts = []
    # Budget holds exactly one prompt's two chunk nodes (8 KiB each in
    # f64 at the tiny shape): the warm hit sees the full 16-token prefix,
    # then the second prompt's inserts force evictions.
    engine = _engine(
        params, cfg, budget_bytes=17 * 1024,
        on_prefix_hit=lambda n: hits.append(n),
        on_prefix_evict=lambda: evicts.append(1),
    )
    engine.start(warmup=True)
    try:
        prompt = list(range(2, 22))
        engine.generate(prompt, 3)
        engine.generate(prompt, 3)
        engine.generate(list(range(100, 120)), 3)  # evicts under budget
    finally:
        engine.shutdown()
    assert hits == [16]
    assert len(evicts) == engine.prefix_evictions > 0


# ---------------------------------------------------------------------------
# Multihost lockstep replay of the seed op
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_multihost_replay_of_insert_from_cache(tiny):
    """A cached-prefix admission on a 2-'host' unit must leave leader and
    follower device state identical: the follower replays OP_GEN_SEED
    (K/V shipped in the payload) without a prefix cache of its own."""
    import threading

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

    prompt = list(range(2, 22))
    leader.start(warmup=True)
    try:
        ref = _ref(params, cfg, prompt, 4)
        assert leader.generate(prompt, 4).tolist() == ref
        assert leader.generate(prompt, 4).tolist() == ref  # warm: seeds
        assert leader.prefix_hits == 1
    finally:
        leader.shutdown()
        channel.close_with(encode_message(OP_SHUTDOWN))
    th.join(timeout=30)

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
