"""Packed multi-admission prefill: parity, bucketing, multihost replay.

The acceptance bar (ISSUE 3): with ``prefillBatch`` > 1, concurrent
admissions' next prompt chunks run as ONE batched prefill call per engine
tick, and output is bit-identical to sequential single-admission chunked
prefill — across prefix-cache hits, ragged chunk counts, and B_p bucket
boundaries, with followers of a multihost unit replaying the packed op to
identical device state.  Exact-parity tests run in float64 (same policy
as test_generation.py: no backend fast-math can blur near-tie argmaxes of
an untrained model).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumlops.models import llama
from tpumlops.server.generation import GenerationEngine

# XLA compiles on the virtual CPU mesh: excluded from the fast core, but
# for the chunk-call count (several admissions share one weight stream).


@pytest.fixture(scope="module", autouse=True)
def x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="module")
def tiny(x64):
    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(0), cfg, dtype=jnp.float64)
    return params, cfg


def _ref(params, cfg, prompt, n):
    out = llama.generate_greedy(
        params, jnp.asarray([prompt], jnp.int32), n, cfg, dtype=jnp.float64
    )
    return np.asarray(out)[0].tolist()


# ---------------------------------------------------------------------------
# Model-layer: packed chunk forward vs the fused reference, exact logits
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_prefill_chunks_ragged_matches_fused_forward_logits(tiny):
    """Two sequences' chunks packed into one call must reproduce the
    fused whole-prompt forward's logits at every position.

    The f64 layer stack is exact through the final norm, but the model's
    lm_head matmul emits float32 (``preferred_element_type``), so the
    LAST reduction rounds per program — logits agree to f32 epsilon and
    every argmax matches; the bit-identical claim is proven at the TOKEN
    level by the engine parity tests below (greedy argmax over these
    logits, token-for-token against generate_greedy)."""
    params, cfg = tiny
    C = 8
    p1 = list(range(2, 18))  # 2 chunks
    p2 = [5, 9, 2, 7, 1, 4, 8, 3, 11, 13, 17, 19, 23, 29, 31, 37]

    # Fused reference logits over each whole prompt.
    refs = []
    for p in (p1, p2):
        logits, _ = llama.prefill(
            params, jnp.asarray([p], jnp.int32), cfg, dtype=jnp.float64
        )
        refs.append(np.asarray(logits)[0])  # [L, vocab]

    cache = llama.RaggedKVCache.create(cfg, 2, jnp.float64)
    got = {0: [], 1: []}
    for chunk_idx in range(2):
        ids = np.stack(
            [
                np.asarray(p1[chunk_idx * C : (chunk_idx + 1) * C], np.int32),
                np.asarray(p2[chunk_idx * C : (chunk_idx + 1) * C], np.int32),
            ]
        )
        logits, cache = llama.prefill_chunks_ragged(
            params,
            jnp.asarray(ids),
            cache,
            jnp.asarray([0, 1], jnp.int32),
            jnp.asarray([chunk_idx * C, chunk_idx * C], jnp.int32),
            cfg,
            dtype=jnp.float64,
        )
        for row in (0, 1):
            got[row].append(np.asarray(logits)[row])
    for row, ref in enumerate(refs):
        packed = np.concatenate(got[row], axis=0)[: ref.shape[0]]
        np.testing.assert_allclose(packed, ref, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(
            packed.argmax(-1), ref.argmax(-1)
        )


@pytest.mark.slow
def test_prefill_chunks_ragged_parked_rows_write_nothing(tiny):
    """A pad row (offset == capacity) must leave the cache bit-identical
    — that is what lets a packed call pad up to a power-of-two bucket."""
    params, cfg = tiny
    shape = llama.RaggedKVCache.create(cfg, 2).k.shape
    k0 = jax.random.normal(jax.random.key(1), shape, jnp.float64)
    v0 = jax.random.normal(jax.random.key(2), shape, jnp.float64)
    cache = llama.RaggedKVCache(k0, v0, jnp.zeros((2,), jnp.int32))
    ids = np.zeros((2, 8), np.int32)
    ids[0] = np.arange(2, 10)
    _, cache2 = llama.prefill_chunks_ragged(
        params,
        jnp.asarray(ids),
        cache,
        jnp.asarray([0, 1], jnp.int32),
        # Row 1 parked at capacity: every one of its writes must drop.
        jnp.asarray([0, cfg.max_seq], jnp.int32),
        cfg,
        dtype=jnp.float64,
    )
    np.testing.assert_array_equal(np.asarray(cache2.k[:, 1]), np.asarray(k0[:, 1]))
    np.testing.assert_array_equal(np.asarray(cache2.v[:, 1]), np.asarray(v0[:, 1]))
    # Row 0's chunk really landed.
    assert not np.array_equal(np.asarray(cache2.k[:, 0]), np.asarray(k0[:, 0]))


# ---------------------------------------------------------------------------
# Engine: packed vs sequential admission, token-for-token
# ---------------------------------------------------------------------------


def _packed_engine(params, cfg, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("dtype", jnp.float64)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("prefill_batch", 4)
    return GenerationEngine(params, cfg, **kw)


def test_packed_engine_matches_reference_ragged_chunk_counts(tiny):
    """Concurrent admissions with DIFFERENT chunk counts (1, exactly-one,
    3-with-partial-tail) must reproduce generate_greedy token-for-token:
    the packed call handles per-row ragged offsets and staggered
    finalization."""
    params, cfg = tiny
    engine = _packed_engine(params, cfg)
    prompts = [
        ([5, 9, 2], 6),  # < one chunk
        ([7, 1, 4, 8, 3, 9, 2, 6], 5),  # exactly one chunk
        (list(range(2, 23)), 7),  # 3 chunks, last partial
        ([11, 3], 4),  # joins the same packed calls
    ]
    # Queue the whole burst BEFORE the scheduler starts: the first admit
    # phase then pops all four together and the packed-call count is
    # deterministic (no race against the submitting thread).
    futs = [engine.submit(p, n) for p, n in prompts]
    engine.start(warmup=True)
    try:
        outs = [f.result(timeout=300).tolist() for f in futs]
        packed_calls = engine.prefill_forwards
    finally:
        engine.shutdown()
    refs = [_ref(params, cfg, p, n) for p, n in prompts]
    assert outs == refs
    # 4 admissions totalling 1+1+3+1 = 6 chunks in at most 3 packed
    # calls (the longest admission's chunk count): the weight stream was
    # genuinely shared, not serialized.
    assert packed_calls <= 3, packed_calls


@pytest.mark.slow
def test_packed_engine_bucket_boundaries(tiny):
    """1, 2, 3, and 4 concurrent admissions exercise the B_p buckets
    (1, 2, 4) including the padded 3-in-bucket-4 case; every wave must
    match the reference."""
    params, cfg = tiny
    engine = _packed_engine(params, cfg)
    engine.start(warmup=True)
    try:
        for wave in (1, 2, 3, 4):
            prompts = [
                (list(range(2 + i, 12 + i)), 4) for i in range(wave)
            ]
            futs = [engine.submit(p, n) for p, n in prompts]
            outs = [f.result(timeout=300).tolist() for f in futs]
            assert outs == [_ref(params, cfg, p, n) for p, n in prompts], wave
    finally:
        engine.shutdown()


@pytest.mark.slow
def test_packed_engine_matches_sequential_engine_first_tokens(tiny):
    """Packed vs sequential single-admission engines: same tokens from
    the same prompts (the first sampled token included — it comes from
    the packed call's fused finalize)."""
    params, cfg = tiny
    prompts = [(list(range(3, 20)), 5), ([9, 8, 7, 6, 5, 4], 5)]

    def run(prefill_batch):
        engine = _packed_engine(params, cfg, prefill_batch=prefill_batch)
        engine.start(warmup=True)
        try:
            futs = [engine.submit(p, n) for p, n in prompts]
            return [f.result(timeout=300).tolist() for f in futs]
        finally:
            engine.shutdown()

    assert run(4) == run(1)


@pytest.mark.slow
def test_packed_engine_seeded_sampling_parity(tiny):
    """A seeded sampled request admitted through the packed call must
    reproduce the sequential engine's stream exactly: the batched
    finalize installs the same per-slot key discipline."""
    params, cfg = tiny

    def run(prefill_batch):
        engine = _packed_engine(params, cfg, prefill_batch=prefill_batch)
        engine.start(warmup=True)
        try:
            return engine.generate(
                [5, 9, 2, 7, 1, 4, 8, 3, 11], 6,
                temperature=0.9, top_k=4, top_p=0.95, seed=1234,
                timeout=300,
            ).tolist()
        finally:
            engine.shutdown()

    assert run(4) == run(1)


@pytest.mark.slow
def test_packed_engine_prefix_cache_hits(tiny):
    """Prefix-cache composition: warm admissions seed the cached prefix
    straight into their reserved slot and only the suffix chunks run —
    outputs still match the reference exactly."""
    params, cfg = tiny
    from tpumlops.server.prefix_cache import PrefixCacheConfig

    engine = GenerationEngine(
        params, cfg, max_slots=4, dtype=jnp.float64,
        prefix_cache=PrefixCacheConfig(
            enabled=True, budget_bytes=32 * 2**20, chunk_tokens=8
        ),
        prefill_batch=4,
    )
    engine.start(warmup=True)
    try:
        shared = list(range(2, 18))  # 16 tokens = 2 cacheable chunks
        cold = engine.submit(shared + [40], 5)
        assert cold.result(timeout=300).tolist() == _ref(
            params, cfg, shared + [40], 5
        )
        f0 = engine.prefill_forwards
        c0 = engine.prefill_chunks_dispatched
        warm_prompts = [shared + [50 + i] for i in range(3)]
        futs = [engine.submit(p, 5) for p in warm_prompts]
        outs = [f.result(timeout=300).tolist() for f in futs]
        warm_calls = engine.prefill_forwards - f0
        warm_chunks = engine.prefill_chunks_dispatched - c0
    finally:
        engine.shutdown()
    assert outs == [_ref(params, cfg, p, 5) for p in warm_prompts]
    assert engine.prefix_hits >= 3
    # Each warm admission ran exactly ONE uncached suffix chunk (the
    # shared 16-token prefix was seeded, never re-prefilled), and the
    # suffix chunks packed into fewer calls than admissions would have
    # paid serially (3 only if the submitting thread raced the first
    # tick; typically 1).
    assert warm_chunks == 3, warm_chunks
    assert warm_calls <= 3, warm_calls


@pytest.mark.slow
def test_packed_engine_speculative_composition(tiny):
    """Packed admission + self-speculative decode in one engine: both
    amortizations compose and output stays exact."""
    params, cfg = tiny
    from tpumlops.server.speculative import SpeculativeConfig

    engine = _packed_engine(
        params, cfg,
        speculative=SpeculativeConfig(
            enabled=True, draft_tokens=4, ngram_min=1, ngram_max=4,
            adaptive=True,
        ),
    )
    engine.start(warmup=True)
    try:
        prompts = [([1, 2, 3] * 5, 10), ([4, 5, 6] * 4, 8)]
        futs = [engine.submit(p, n) for p, n in prompts]
        outs = [f.result(timeout=300).tolist() for f in futs]
        assert engine.spec_verify_ticks > 0
    finally:
        engine.shutdown()
    assert outs == [_ref(params, cfg, p, n) for p, n in prompts]


@pytest.mark.slow
def test_packed_engine_validation():
    cfg = llama.LlamaConfig.tiny(max_seq=32)
    params = llama.init(jax.random.key(1), cfg, dtype=jnp.float64)
    with pytest.raises(ValueError, match="chunked prefill"):
        GenerationEngine(params, cfg, dtype=jnp.float64, prefill_batch=2)
    with pytest.raises(ValueError, match="prefill_batch"):
        GenerationEngine(
            params, cfg, dtype=jnp.float64, prefill_chunk=8, prefill_batch=0
        )
    with pytest.raises(ValueError, match="prefill_token_budget"):
        GenerationEngine(
            params, cfg, dtype=jnp.float64, prefill_chunk=8,
            prefill_batch=2, prefill_token_budget=-1,
        )


@pytest.mark.slow
def test_packed_token_budget_caps_chunks_per_call(tiny):
    """prefillTokenBudget caps the chunks one packed call may carry:
    budget 16 at chunk 8 packs at most 2 admissions per tick, and the
    observed per-call fill must respect that while outputs stay exact."""
    params, cfg = tiny
    fills = []
    engine = _packed_engine(
        params, cfg, prefill_token_budget=16, on_prefill_batch=fills.append
    )
    engine.start(warmup=True)
    try:
        prompts = [(list(range(2 + i, 14 + i)), 4) for i in range(4)]
        futs = [engine.submit(p, n) for p, n in prompts]
        outs = [f.result(timeout=300).tolist() for f in futs]
    finally:
        engine.shutdown()
    assert outs == [_ref(params, cfg, p, n) for p, n in prompts]
    assert fills and max(fills) <= 2, fills


@pytest.mark.slow
def test_packed_admission_metrics_fire(tiny):
    """on_prefill_batch / on_admission_wait / on_ttft fire per admission
    with sane values (waits and TTFTs positive, fill counts the real
    rows packed)."""
    params, cfg = tiny
    fills, waits, ttfts = [], [], []
    engine = _packed_engine(
        params, cfg,
        on_prefill_batch=fills.append,
        on_admission_wait=waits.append,
        on_ttft=ttfts.append,
    )
    prompts = [(list(range(2 + i, 14 + i)), 3) for i in range(3)]
    # Queued before start: the first admit phase pops the whole burst,
    # so the first packed call's fill is deterministically 3.
    futs = [engine.submit(p, n) for p, n in prompts]
    engine.start(warmup=True)
    try:
        for f in futs:
            f.result(timeout=300)
    finally:
        engine.shutdown()
    assert len(ttfts) == 3 and all(t > 0 for t in ttfts)
    assert len(waits) == 3 and all(w >= 0 for w in waits)
    assert fills and max(fills) >= 2  # the burst really packed


# ---------------------------------------------------------------------------
# Multihost lockstep replay of the packed ops
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_multihost_replay_of_packed_prefill(tiny):
    """A packed-admission burst on a 2-'host' unit must leave leader and
    follower device state identical: followers replay OP_GEN_CHUNKS (and
    OP_GEN_SEED_SLOT on prefix hits) with the broadcast batch."""
    from tpumlops.server.multihost import (
        OP_SHUTDOWN,
        UnitChannel,
        _LocalGroup,
        encode_message,
        follower_loop,
    )
    from tpumlops.server.prefix_cache import PrefixCacheConfig

    params, cfg = tiny
    group = _LocalGroup(2)
    transports = group.transports()
    channel = UnitChannel(transports[0])

    def make(chan=None):
        return GenerationEngine(
            params, cfg, max_slots=4, dtype=jnp.float64,
            prefix_cache=PrefixCacheConfig(
                enabled=True, budget_bytes=32 * 2**20, chunk_tokens=8
            ),
            prefill_batch=4, channel=chan,
        )

    leader = make(channel)
    follower = make()

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

    leader.start(warmup=True)
    try:
        shared = list(range(2, 18))
        # Cold wave populates the radix cache; warm wave replays seeds.
        cold = [leader.submit(shared + [40 + i], 4) for i in range(2)]
        for f in cold:
            f.result(timeout=300)
        warm = [leader.submit(shared + [60 + i], 4) for i in range(3)]
        outs = [f.result(timeout=300).tolist() for f in warm]
        assert leader.prefix_hits >= 3
    finally:
        leader.shutdown()
        channel.close_with(encode_message(OP_SHUTDOWN))
    th.join(timeout=60)

    assert outs == [
        _ref(params, cfg, shared + [60 + i], 4) for i in range(3)
    ]
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


# ---------------------------------------------------------------------------
# Warmup coverage
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_warmup_compiles_every_pack_bucket(tiny):
    """No live burst may pay a packed-call compile: after warmup every
    B_p bucket variant is already compiled."""
    params, cfg = tiny
    engine = _packed_engine(params, cfg)
    engine.start(warmup=True)
    try:
        want = len(engine._pack_buckets())  # 1, 2, 4
        assert engine._prefill_chunks._cache_size() >= want, (
            engine._prefill_chunks._cache_size(), want
        )
    finally:
        engine.shutdown()
