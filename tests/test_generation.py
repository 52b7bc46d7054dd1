"""Continuous-batching generation: ragged decode parity, engine scheduling.

Exact-parity tests run in float64 (module-wide ``jax_enable_x64``, global
config rather than the thread-local context manager so the engine's
scheduler thread sees it too): the CPU backend's oneDNN matmuls pick
batch-size-dependent kernels in float32, which perturbs logits ~1e-3 and
flips near-tie argmaxes of an untrained random model.  In f64 there is no
fast-math path, so the continuous-batching schedule must reproduce
``generate_greedy`` token-for-token.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumlops.models import llama
from tpumlops.server.generation import GenerationEngine, prefill_bucket

# ~4 min of XLA compiles on the virtual mesh: excluded from the fast
# core (`make test-fast`, VERDICT r3 #10).
pytestmark = pytest.mark.slow


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
# Model-layer primitives
# ---------------------------------------------------------------------------


def _fresh_cache(cfg, batch):
    return llama.RaggedKVCache.create(cfg, batch, jnp.float64)


def _admit(params, cfg, cache, toks, prompt, slot):
    """Right-pad to a 16-token bucket, prefill, insert into ``slot``."""
    ids = np.zeros((1, 16), np.int32)
    ids[0, : len(prompt)] = prompt
    logits, seq = llama.prefill(params, jnp.asarray(ids), cfg, dtype=jnp.float64)
    cache = llama.insert_sequence(
        cache, seq, jnp.int32(slot), jnp.int32(len(prompt))
    )
    toks[slot, 0] = int(jnp.argmax(logits[0, len(prompt) - 1]))
    return cache


def test_ragged_decode_matches_generate_greedy_staggered(tiny):
    """Two sequences admitted at different times, decoded in one batch."""
    params, cfg = tiny
    p1, p2 = [5, 9, 2], [7, 1, 4, 8, 3]
    ref1 = _ref(params, cfg, p1, 6)
    ref2 = _ref(params, cfg, p2, 6)

    cache = _fresh_cache(cfg, 3)
    toks = np.zeros((3, 1), np.int32)

    cache = _admit(params, cfg, cache, toks, p1, 0)
    out1 = [int(toks[0, 0])]
    active = np.array([True, False, False])
    logits, cache = llama.decode_ragged(
        params, jnp.asarray(toks), cache, cfg, jnp.asarray(active),
        dtype=jnp.float64,
    )
    toks[0, 0] = int(jnp.argmax(logits[0, -1]))
    out1.append(int(toks[0, 0]))

    cache = _admit(params, cfg, cache, toks, p2, 1)  # joins mid-flight
    out2 = [int(toks[1, 0])]
    active = np.array([True, True, False])
    for _ in range(5):
        logits, cache = llama.decode_ragged(
            params, jnp.asarray(toks), cache, cfg, jnp.asarray(active),
            dtype=jnp.float64,
        )
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        if len(out1) < 6:
            toks[0, 0] = nxt[0]
            out1.append(int(nxt[0]))
        if len(out2) < 6:
            toks[1, 0] = nxt[1]
            out2.append(int(nxt[1]))

    assert out1 == ref1
    assert out2 == ref2


def test_slot_reuse_is_isolated_from_previous_occupant(tiny):
    """A sequence decoded in a reused slot matches one in a fresh cache."""
    params, cfg = tiny
    cache = _fresh_cache(cfg, 2)
    toks = np.zeros((2, 1), np.int32)

    def run_in_slot(cache, prompt, n):
        cache = _admit(params, cfg, cache, toks, prompt, 0)
        out = [int(toks[0, 0])]
        active = np.array([True, False])
        for _ in range(n - 1):
            logits, cache = llama.decode_ragged(
                params, jnp.asarray(toks), cache, cfg, jnp.asarray(active),
                dtype=jnp.float64,
            )
            toks[0, 0] = int(jnp.argmax(logits[0, -1]))
            out.append(int(toks[0, 0]))
        return cache, out

    # First occupant decodes 10 tokens into slot 0, then the slot is reused.
    cache, _ = run_in_slot(cache, [11, 13, 17, 19, 23, 29], 10)
    cache, out = run_in_slot(cache, [3, 1, 4], 8)
    assert out == _ref(params, cfg, [3, 1, 4], 8)


def test_prefill_bucket():
    assert prefill_bucket(1, 2048) == 16
    assert prefill_bucket(16, 2048) == 16
    assert prefill_bucket(17, 2048) == 32
    assert prefill_bucket(100, 2048) == 128
    assert prefill_bucket(100, 64) == 64  # capped at capacity


# ---------------------------------------------------------------------------
# GenerationEngine scheduling
# ---------------------------------------------------------------------------


def test_engine_concurrent_requests_match_reference(tiny):
    params, cfg = tiny
    engine = GenerationEngine(params, cfg, max_slots=3, dtype=jnp.float64)
    engine.start(warmup=True)
    try:
        prompts = [
            ([5, 9, 2], 6),
            ([7, 1, 4, 8, 3], 9),
            ([42], 4),
            ([10, 20, 30, 40, 50, 60, 70], 5),
            ([2, 3], 7),  # 5 requests > 3 slots: forces slot reuse
        ]
        futs = [engine.submit(p, n) for p, n in prompts]
        outs = [f.result(timeout=120).tolist() for f in futs]
        refs = [_ref(params, cfg, p, n) for p, n in prompts]
    finally:
        engine.shutdown()
    assert outs == refs
    assert engine.tokens_generated >= sum(n for _, n in prompts)


def test_engine_eos_stops_early(tiny):
    params, cfg = tiny
    ref = _ref(params, cfg, [5, 9, 2], 8)
    eos = ref[2]  # force a stop after the 3rd token
    engine = GenerationEngine(params, cfg, max_slots=2, dtype=jnp.float64)
    engine.start(warmup=False)
    try:
        out = engine.generate([5, 9, 2], 8, eos_id=eos).tolist()
    finally:
        engine.shutdown()
    assert out == ref[:3]


def test_engine_rejects_oversized_and_empty(tiny):
    cfg = llama.LlamaConfig.tiny(max_seq=32)
    params = llama.init(jax.random.key(1), cfg, dtype=jnp.float64)
    engine = GenerationEngine(params, cfg, max_slots=2, dtype=jnp.float64)
    with pytest.raises(ValueError, match="capacity"):
        engine.submit(list(range(30)), 10)
    with pytest.raises(ValueError, match="empty"):
        engine.submit([], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit([1, 2], 0)


def test_engine_shutdown_fails_queued_with_engine_shutdown(tiny):
    from tpumlops.server.generation import EngineShutdown

    cfg = llama.LlamaConfig.tiny(max_seq=32)
    params = llama.init(jax.random.key(1), cfg, dtype=jnp.float64)
    engine = GenerationEngine(params, cfg, max_slots=2, dtype=jnp.float64)
    # never started: queued (not-yet-admitted) requests must fail with a
    # CLEAR EngineShutdown — not hang, and not a bare CancelledError a
    # caller can't tell apart from its own cancel.
    fut = engine.submit([1, 2, 3], 4)
    engine.shutdown()
    with pytest.raises(EngineShutdown, match="before admission"):
        fut.result(timeout=5)


def test_engine_recovers_after_failed_step(tiny):
    """A poisoned jitted step must not brick the engine: donated buffers are
    reallocated and later requests succeed."""
    params, cfg = tiny
    engine = GenerationEngine(params, cfg, max_slots=2, dtype=jnp.float64)
    engine.start(warmup=True)
    try:
        ref = _ref(params, cfg, [5, 9, 2], 4)
        assert engine.generate([5, 9, 2], 4).tolist() == ref

        # Sabotage one decode step, then confirm in-flight fails + recovery.
        # (greedy traffic takes the _decode_greedy variant)
        real_decode = engine._decode_greedy
        calls = {"n": 0}

        def bomb(*a, **kw):
            calls["n"] += 1
            raise RuntimeError("injected XLA failure")

        engine._decode_greedy = bomb
        fut = engine.submit([7, 1, 4], 5)
        with pytest.raises(RuntimeError):
            fut.result(timeout=30)
        engine._decode_greedy = real_decode
        assert calls["n"] >= 1
        # Engine must serve fresh requests after recovery.
        assert engine.generate([5, 9, 2], 4).tolist() == ref
    finally:
        engine.shutdown()


def test_engine_eos_zero_is_respected(tiny):
    """eos_id=0 must not fall back to the engine default (falsy-zero).

    The engine DEFAULT eos is a token that WOULD stop generation after two
    tokens; the request overrides it with eos_id=0 (a token that never
    appears in the greedy output).  With the falsy-zero bug, 0 falls back
    to the default and generation stops early — so the full-length output
    proves the override took effect."""
    params, cfg = tiny
    ref = _ref(params, cfg, [5, 9, 2], 8)
    assert 0 not in ref  # precondition for the test to be meaningful
    engine = GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float64, eos_id=ref[1]
    )
    engine.start(warmup=False)
    try:
        # default used when eos_id is None -> stops after 2 tokens
        assert engine.generate([5, 9, 2], 8).tolist() == ref[:2]
        # explicit 0 must override the default -> full 8 tokens
        assert engine.generate([5, 9, 2], 8, eos_id=0).tolist() == ref
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------------
# Sampling (temperature / top-k / top-p / seed)
# ---------------------------------------------------------------------------


def test_sample_logits_greedy_and_filters(tiny):
    import jax

    from tpumlops.models.sampling import sample_logits

    logits = jnp.asarray(
        [[0.1, 3.0, 2.0, -1.0, 0.5]] * 4, jnp.float32
    )
    keys = jax.random.split(jax.random.key(7), 4)
    zeros = jnp.zeros((4,), jnp.float32)
    # temperature 0 -> argmax regardless of key
    out = sample_logits(logits, keys, zeros, jnp.zeros((4,), jnp.int32), jnp.ones((4,), jnp.float32))
    assert out.tolist() == [1, 1, 1, 1]
    # top_k=1 -> argmax even at high temperature
    out = sample_logits(
        logits, keys, zeros + 5.0, jnp.ones((4,), jnp.int32), jnp.ones((4,), jnp.float32)
    )
    assert out.tolist() == [1, 1, 1, 1]
    # tiny top_p -> only the most probable token survives
    out = sample_logits(
        logits, keys, zeros + 5.0, jnp.zeros((4,), jnp.int32), zeros + 1e-6
    )
    assert out.tolist() == [1, 1, 1, 1]


def test_sample_logits_topk_mask_never_leaks(tiny):
    import jax

    from tpumlops.models.sampling import sample_logits

    logits = jnp.asarray([[1.0, 0.9, -5.0, -5.0, -5.0]], jnp.float32)
    drawn = set()
    for i in range(64):
        keys = jax.random.split(jax.random.key(i), 1)
        tok = sample_logits(
            logits,
            keys,
            jnp.asarray([10.0], jnp.float32),  # hot: flattens distribution
            jnp.asarray([2], jnp.int32),
            jnp.asarray([1.0], jnp.float32),
        )
        drawn.add(int(tok[0]))
    assert drawn == {0, 1}  # tokens outside top-2 must never appear


def test_engine_seeded_sampling_matches_reference_loop(tiny):
    """Seeded sampled generation is slot-independent and reproducible:
    the engine (continuous batching, shared decode steps) must equal a
    hand-rolled loop using the same per-slot key discipline."""
    import jax

    from tpumlops.models.sampling import sample_logits

    params, cfg = tiny
    prompt, n, seed = [5, 9, 2], 7, 1234
    temp, tk, tp = 0.9, 4, 0.95

    # Reference loop (batch 1, unpadded).
    key = jax.random.key(seed)
    logits, cache = llama.prefill(
        params, jnp.asarray([prompt], jnp.int32), cfg, dtype=jnp.float64
    )
    key, use = jax.random.split(key)
    t_ = jnp.asarray([temp], jnp.float32)
    k_ = jnp.asarray([tk], jnp.int32)
    p_ = jnp.asarray([tp], jnp.float32)
    tok = sample_logits(logits[:, -1, :], use[None], t_, k_, p_)
    ref = [int(tok[0])]
    for _ in range(n - 1):
        logits, cache = llama.decode_step(
            params, tok[:, None], cache, cfg, dtype=jnp.float64
        )
        key, use = jax.random.split(key)
        tok = sample_logits(logits[:, -1, :], use[None], t_, k_, p_)
        ref.append(int(tok[0]))

    engine = GenerationEngine(params, cfg, max_slots=3, dtype=jnp.float64)
    engine.start(warmup=True)
    try:
        # A concurrent greedy request shares decode steps with the sampled
        # one — per-slot keys must keep the sampled stream unaffected.
        other = engine.submit([7, 1, 4], 9)
        out = engine.generate(
            prompt, n, temperature=temp, top_k=tk, top_p=tp, seed=seed
        ).tolist()
        other.result(timeout=60)
        # Reproducible: same seed, same stream.
        out2 = engine.generate(
            prompt, n, temperature=temp, top_k=tk, top_p=tp, seed=seed
        ).tolist()
    finally:
        engine.shutdown()
    assert out == ref
    assert out2 == out


def test_engine_sampling_validation():
    cfg = llama.LlamaConfig.tiny(max_seq=32)
    params = llama.init(jax.random.key(1), cfg, dtype=jnp.float64)
    engine = GenerationEngine(params, cfg, max_slots=2, dtype=jnp.float64)
    with pytest.raises(ValueError, match="temperature"):
        engine.submit([1, 2], 4, temperature=-1.0)
    with pytest.raises(ValueError, match="top_p"):
        engine.submit([1, 2], 4, top_p=0.0)
    with pytest.raises(ValueError, match="top_k"):
        engine.submit([1, 2], 4, top_k=-2)


def test_engine_validation_rejects_hostile_inputs():
    """ADVICE round 1: malformed requests must 400 at validate(), never
    reach the jitted step (where an OverflowError would fail every
    in-flight request via _fail_all_and_recover)."""
    cfg = llama.LlamaConfig.tiny(max_seq=32)
    params = llama.init(jax.random.key(1), cfg, dtype=jnp.float64)
    engine = GenerationEngine(params, cfg, max_slots=2, dtype=jnp.float64)
    # top_k beyond int32: passed validation before, then overflowed in _admit.
    with pytest.raises(ValueError, match="top_k"):
        engine.validate([1, 2], 4, top_k=2**31)
    with pytest.raises(ValueError, match="top_k"):
        engine.validate([1, 2], 4, top_k=2**40)
    assert engine.validate([1, 2], 4, top_k=2**31 - 1).tolist() == [1, 2]
    # Out-of-vocab ids silently clamp in jnp.take -> garbage 200s.
    with pytest.raises(ValueError, match="prompt ids"):
        engine.validate([cfg.vocab_size], 4)
    with pytest.raises(ValueError, match="prompt ids"):
        engine.validate([-1], 4)
    # ids past int64 raised OverflowError, which the HTTP layer mapped to 500.
    with pytest.raises(ValueError, match="prompt ids"):
        engine.validate([2**63], 4)
    with pytest.raises(ValueError, match="prompt ids"):
        engine.validate([2**31], 4)  # would overflow a direct int32 asarray
    ok = engine.validate([0, cfg.vocab_size - 1], 4)
    assert ok.dtype == np.int32


def test_engine_seed_validation_and_greedy_variant(tiny):
    params, cfg = tiny
    engine = GenerationEngine(params, cfg, max_slots=2, dtype=jnp.float64)
    with pytest.raises(ValueError, match="seed"):
        engine.submit([1, 2], 4, seed=2**63)
    engine.start(warmup=True)
    try:
        # All-greedy traffic must take the argmax variant and stay exact.
        ref = _ref(params, cfg, [5, 9, 2], 5)
        assert engine.generate([5, 9, 2], 5).tolist() == ref
    finally:
        engine.shutdown()


def test_engine_streaming_callback_and_cancel_frees_slot(tiny):
    """on_token fires per token; cancelling the future mid-generation frees
    the slot instead of decoding to max_new_tokens."""
    import threading

    params, cfg = tiny
    engine = GenerationEngine(params, cfg, max_slots=1, dtype=jnp.float64)
    engine.start(warmup=True)
    seen = []
    three = threading.Event()
    fut_box = {}

    def on_token(t):
        seen.append(t)
        if len(seen) == 3:
            fut_box["fut"].cancel()
            three.set()

    try:
        fut = engine.submit([5, 9, 2], 50, on_token=on_token)
        fut_box["fut"] = fut
        assert three.wait(timeout=60)
        # The slot must free well before 50 tokens; the next request on the
        # single-slot engine proves capacity was reclaimed.
        ref = _ref(params, cfg, [7, 1, 4], 4)
        assert engine.generate([7, 1, 4], 4, timeout=60).tolist() == ref
        assert len(seen) < 50
        assert fut.cancelled()
    finally:
        engine.shutdown()


def test_windowed_decode_matches_full_capacity(tiny):
    """window only trims the attended prefix — logits must be exact."""
    params, cfg = tiny
    cache = _fresh_cache(cfg, 2)
    toks = np.zeros((2, 1), np.int32)
    cache = _admit(params, cfg, cache, toks, [5, 9, 2], 0)
    cache = _admit(params, cfg, cache, toks, [7, 1, 4, 8], 1)
    active = np.array([True, True])
    lw_full, _ = llama.decode_ragged(
        params, jnp.asarray(toks), cache, cfg, jnp.asarray(active),
        dtype=jnp.float64,
    )
    lw_win, _ = llama.decode_ragged(
        params, jnp.asarray(toks), cache, cfg, jnp.asarray(active),
        dtype=jnp.float64, window=16,
    )
    assert jnp.array_equal(lw_full, lw_win)


def test_warmup_compiles_all_window_buckets(tiny):
    """No live request may pay a decode compile: after warmup, every
    power-of-two window bucket of both variants is already compiled."""
    params, cfg = tiny  # capacity 64 -> buckets 16, 32, 64
    engine = GenerationEngine(params, cfg, max_slots=2, dtype=jnp.float64)
    engine.start(warmup=True)
    try:
        greedy_sizes = engine._decode_greedy._cache_size()
        sampling_sizes = engine._decode._cache_size()
        assert greedy_sizes >= 3, greedy_sizes
        assert sampling_sizes >= 3, sampling_sizes
        # ADVICE round 1: the fused prefill program must also be compiled
        # at every power-of-two prompt bucket (16, 32, 64 at capacity 64),
        # or the first long prompt on a cold node stalls the scheduler.
        prefill_sizes = engine._prefill_insert._cache_size()
        assert prefill_sizes >= 3, prefill_sizes
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------------
# Chunked prefill (decode interleaving)
# ---------------------------------------------------------------------------


def test_chunked_prefill_exact_parity_with_fused(tiny):
    """Causal attention decomposes over prompt chunks exactly: a chunked
    engine must reproduce fused-prefill outputs token-for-token (f64)."""
    params, cfg = tiny
    engine = GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float64, prefill_chunk=8
    )
    engine.start(warmup=True)
    try:
        prompts = [
            ([5, 9, 2], 6),  # < one chunk
            ([7, 1, 4, 8, 3, 9, 2, 6], 5),  # exactly one chunk
            (list(range(2, 23)), 7),  # 3 chunks, last partial
        ]
        futs = [engine.submit(p, n) for p, n in prompts]
        outs = [f.result(timeout=120).tolist() for f in futs]
    finally:
        engine.shutdown()
    refs = [_ref(params, cfg, p, n) for p, n in prompts]
    assert outs == refs


def test_chunked_prefill_interleaves_with_decode(tiny):
    """A long prompt must not stall an in-flight stream: its tokens keep
    arriving between prefill chunks."""
    import threading

    params, cfg = tiny
    engine = GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float64, prefill_chunk=8
    )
    engine.start(warmup=True)
    order = []
    lock = threading.Lock()

    real_chunk = engine._dispatch_chunk
    real_step = engine._device_step

    def spy_chunk(ids, fresh):
        with lock:
            order.append("chunk")
        return real_chunk(ids, fresh)

    def spy_step(active, window, sampling):
        with lock:
            order.append("step")
        return real_step(active, window, sampling)

    engine._dispatch_chunk = spy_chunk
    engine._device_step = spy_step
    try:
        slow = engine.submit([5, 9, 2], 30)  # streaming tokens
        import time as _t

        _t.sleep(0.3)  # let it decode a bit
        long_prompt = engine.submit(list(range(2, 50)), 4)  # 6 chunks
        assert slow.result(timeout=120).shape == (30,)
        assert long_prompt.result(timeout=120).shape == (4,)
    finally:
        engine.shutdown()
    # Decode ticks must appear BETWEEN prefill chunks (interleaving), not
    # only after all of them.
    chunk_idx = [i for i, o in enumerate(order) if o == "chunk"]
    assert len(chunk_idx) >= 6
    interleaved = any(
        "step" in order[a + 1 : b] for a, b in zip(chunk_idx, chunk_idx[1:])
    )
    assert interleaved, order


def test_chunked_prefill_rejects_nothing_extra(tiny):
    params, cfg = tiny
    engine = GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float64, prefill_chunk=8
    )
    with pytest.raises(ValueError, match="capacity"):
        engine.submit(list(range(80)), 10)


def test_chunked_prefill_validation_and_shutdown_cancel(tiny):
    params, cfg = tiny  # capacity 64
    with pytest.raises(ValueError, match="divide"):
        GenerationEngine(params, cfg, dtype=jnp.float64, prefill_chunk=24)
    with pytest.raises(ValueError, match="positive"):
        GenerationEngine(params, cfg, dtype=jnp.float64, prefill_chunk=-8)

    # A mid-prefill admission must be cancelled on shutdown, not hang.
    engine = GenerationEngine(
        params, cfg, max_slots=1, dtype=jnp.float64, prefill_chunk=8
    )
    engine.start(warmup=False)
    blocker = engine.submit([5, 9, 2], 40)  # occupies the only slot
    import time as _t

    _t.sleep(0.2)
    pending = engine.submit(list(range(2, 40)), 4)
    _t.sleep(0.1)
    engine.shutdown()
    from tpumlops.server.generation import EngineShutdown

    with pytest.raises(EngineShutdown):  # queued or mid-prefill at shutdown
        pending.result(timeout=10)
    assert blocker.done()


def test_decode_window_bucket_sequence():
    """1.5x intermediate buckets: attention cost is linear in W at the
    G=1 matvec floor, so pure power-of-two windows overpay up to 2x
    just under a boundary; {2^k, 3*2^(k-1)} caps the overshoot at 33%."""
    from tpumlops.server.generation import (
        _MIN_BUCKET, decode_window_bucket, decode_window_buckets)

    cases = (
        (1, 1024, _MIN_BUCKET), (64, 1024, 64), (65, 1024, 96),
        (96, 1024, 96), (97, 1024, 128), (129, 1024, 192),
        (193, 1024, 256), (260, 1024, 384), (385, 1024, 512),
        (600, 1024, 768), (800, 1024, 1024),
        # capacity caps every bucket, including non-power capacities
        (260, 300, 300), (1, 32, _MIN_BUCKET),
    )
    for n, cap, want in cases:
        assert decode_window_bucket(n, cap) == want, (n, cap)
    # Monotone and always sufficient.
    prev = 0
    for n in range(1, 1025):
        w = decode_window_bucket(n, 1024)
        assert w >= n and w >= prev
        prev = w
    # The warmup sweep enumerates exactly the reachable windows — at
    # power AND non-power capacities (a capacity-capped bucket must not
    # produce a 3/4 step the sweep never compiled: a lazy compile would
    # stall the scheduler thread mid-traffic).
    for cap in (17, 48, 64, 100, 300, 768, 1024):
        enumerated = set(decode_window_buckets(cap))
        reachable = {decode_window_bucket(n, cap) for n in range(1, cap + 1)}
        assert reachable <= enumerated, (cap, sorted(reachable - enumerated))


def test_engine_uses_intermediate_window_bucket(tiny):
    """A request whose positions land between 2^k buckets must decode at
    the 3*2^(k-1) window, not the next power of two."""
    from tpumlops.server.generation import GenerationEngine, decode_window_bucket

    params, cfg = tiny  # capacity 64
    engine = GenerationEngine(params, cfg, max_slots=2, dtype=jnp.float64)
    # Observe the windows the engine ACTUALLY dispatches — a regression
    # to the power-of-two bucket would still generate correct tokens.
    seen: list[int] = []
    real_dispatch = engine._dispatch_step

    def spy(active_np, window, sampling):
        seen.append(int(window))
        return real_dispatch(active_np, window, sampling)

    engine._dispatch_step = spy
    engine.start(warmup=False)
    try:
        # prompt 30 + 8 new tokens -> write positions 30..37: steps at
        # 30..32 fit window 32, the rest take the intermediate 48 — the
        # power-of-two 64 must never be dispatched.
        fut = engine.submit(list(range(1, 31)), 8)
        out = fut.result(timeout=120)
        assert len(out) == 8
        assert seen, "no decode steps observed"
        assert 48 in seen and 64 not in seen, seen
    finally:
        engine.shutdown()
