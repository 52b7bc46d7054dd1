"""Fused multi-step decode (spec.tpu.decodeSteps): parity + amortization.

The acceptance bar (ISSUE 10): with ``decodeSteps`` K > 1 the engine
dispatches ONE ``lax.scan`` program per decode tick — K steps with an
on-device sampling chain and EOS latch, token block read back one tick
behind — and emitted tokens are token-for-token identical to the
single-step loop (f64, so no backend fast-math can blur it): greedy and
seeded sampling, EOS mid-scan, slot churn, prefix-cache and speculative
composition, and multihost lockstep replay.  Pure window-bucket edge
cases run in the fast tranche; everything tracing jitted programs on the
tiny CPU llama fixture is marked ``slow`` (same policy as
test_speculative.py).
"""

import numpy as np
import pytest

from tpumlops.server.generation import (
    decode_window_bucket,
    decode_window_buckets,
)

# ---------------------------------------------------------------------------
# Window-bucket edge cases (pure functions, fast tranche)
# ---------------------------------------------------------------------------


def test_window_bucket_capacity_boundary():
    # A row at (or clamped to) capacity must bucket to capacity itself —
    # the fused scheduler passes min(needed + K - 1, capacity), and an
    # over-capacity bucket would name an executable warmup never swept.
    for cap in (64, 1024, 768):  # power and non-power capacities
        assert decode_window_bucket(cap, cap) == cap
        assert decode_window_bucket(cap - 1, cap) in decode_window_buckets(cap)
        assert max(decode_window_buckets(cap)) == cap


def test_window_bucket_exact_edges():
    # Lengths sitting EXACTLY on a bucket edge stay on it; one past it
    # steps to the next bucket.  A fused tick whose row lands exactly on
    # an edge mid-scan is covered because the window was pre-picked for
    # length + K - 1 (engine-level assertion below).
    cap = 1024
    for edge in (16, 24, 32, 48, 64, 96, 128, 192, 256, 384, 512, 768, 1024):
        assert decode_window_bucket(edge, cap) == edge
    assert decode_window_bucket(97, cap) == 128
    assert decode_window_bucket(193, cap) == 256
    assert decode_window_bucket(769, cap) == 1024


def test_window_bucket_growth_across_fused_tick():
    # The scheduler's pre-pick rule: the LAST scan step attends positions
    # up to needed + K - 1, so the chosen bucket must cover it even when
    # the row crosses one (or two) bucket edges inside the K steps.
    cap = 1024
    for needed in (15, 16, 95, 96, 97, 383, 1020):
        for k in (2, 4, 8, 16):
            w = decode_window_bucket(min(needed + k - 1, cap), cap)
            assert w >= min(needed + k - 1, cap), (needed, k, w)
            assert w in decode_window_buckets(cap), (needed, k, w)


def test_window_buckets_cover_every_fused_pick():
    # Exhaustive over a small capacity: every (length, K) pre-pick lands
    # on an enumerated bucket — the warmup sweep compiles exactly that
    # set, so a miss here would be a live-path lazy compile.
    for cap in (64, 96):
        buckets = set(decode_window_buckets(cap))
        for needed in range(1, cap + 1):
            for k in (1, 2, 4, 8, 16):
                assert (
                    decode_window_bucket(min(needed + k - 1, cap), cap)
                    in buckets
                )


def test_superstep_window_covers_mixed_role_ticks_exhaustively():
    # MIXED-role dispatches (unifiedStep): a K-step decode row and a
    # verify/prefill row share ONE window pre-pick.  Exhaustive over
    # small capacities: for every (decode-high-water, other-high-water, K)
    # the picked bucket covers BOTH worst cases — the decode row's last
    # scan step attending decode_hi + K - 1 positions AND the
    # verify/prefill row's own high-water — and lands on an enumerated
    # bucket (the warmup sweep compiles exactly that set, so a miss
    # would be a live-path lazy compile).
    from tpumlops.server.generation import superstep_window

    for cap in (64, 96):
        buckets = set(decode_window_buckets(cap))
        for decode_hi in range(0, cap + 1):
            for other_hi in range(0, cap + 1):
                for k in (1, 2, 4, 16):
                    w = superstep_window(decode_hi, other_hi, k, cap)
                    assert w in buckets, (cap, decode_hi, other_hi, k, w)
                    if decode_hi:
                        assert w >= min(decode_hi + k - 1, cap), (
                            cap, decode_hi, other_hi, k, w,
                        )
                    assert w >= min(other_hi, cap), (
                        cap, decode_hi, other_hi, k, w,
                    )


def test_engine_rejects_bad_decode_steps():
    # Constructor-level validation fires before any device state is
    # built for out-of-range K (the params dict is never touched).
    from tpumlops.server.generation import GenerationEngine

    class _Cfg:
        max_seq = 64
        vocab_size = 16

    for bad in (0, -1, 17):
        with pytest.raises(ValueError, match="decode_steps"):
            GenerationEngine({}, _Cfg(), decode_steps=bad)


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


def _engine(params, cfg, *, decode_steps=4, **kw):
    import jax.numpy as jnp

    from tpumlops.server.generation import GenerationEngine

    return GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float64,
        decode_steps=decode_steps, **kw,
    )


@pytest.mark.slow
def test_decode_multistep_matches_sequential_steps(tiny):
    """Model layer: ONE decode_multistep scan must reproduce K sequential
    decode_ragged steps — tokens, valid counts, lengths, and committed
    K/V (f64; logits agree to f32-accumulator rounding, tokens exactly).
    """
    import jax.numpy as jnp

    from tpumlops.models import llama

    params, cfg = tiny
    def fresh():
        return llama.RaggedKVCache.create(cfg, 2, jnp.float64)

    prompt = [5, 9, 2]
    ids = np.zeros((1, 16), np.int32)
    ids[0, : len(prompt)] = prompt
    logits, seq = llama.prefill(params, jnp.asarray(ids), cfg, dtype=jnp.float64)
    first = int(jnp.argmax(logits[0, len(prompt) - 1]))
    ref = _ref(params, cfg, prompt, 6)
    assert ref[0] == first

    active = np.array([True, False])
    K = 4

    # Sequential: K decode_ragged steps feeding argmax back in.
    cache = llama.insert_sequence(
        fresh(), seq, jnp.int32(0), jnp.int32(len(prompt))
    )
    toks = np.zeros((2, 1), np.int32)
    toks[0, 0] = first
    seq_toks = []
    for _ in range(K):
        lg, cache = llama.decode_ragged(
            params, jnp.asarray(toks), cache, cfg, jnp.asarray(active),
            dtype=jnp.float64, window=16,
        )
        toks = np.asarray(jnp.argmax(lg[:, -1, :], axis=-1)).astype(np.int32)[
            :, None
        ]
        seq_toks.append(int(toks[0, 0]))

    # Fused: ONE scan over the same K steps.
    cache2 = llama.insert_sequence(
        fresh(), seq, jnp.int32(0), jnp.int32(len(prompt))
    )
    t0 = np.zeros((2, 1), np.int32)
    t0[0, 0] = first

    def sample(lg, carry):
        return carry, jnp.argmax(lg, axis=-1).astype(jnp.int32)

    tok_block, valid, _toks, cache2, act2, rem2, _ = llama.decode_multistep(
        params, jnp.asarray(t0), cache2, cfg, jnp.asarray(active),
        jnp.asarray(np.array([10, 0], np.int32)),
        jnp.asarray(np.array([-1, -1], np.int32)),
        K, sample, sample_carry=None, dtype=jnp.float64, window=16,
    )
    assert np.asarray(tok_block)[0].tolist() == seq_toks == ref[1 : K + 1]
    assert np.asarray(valid).tolist() == [K, 0]
    L = len(prompt)
    # Lengths advanced by exactly the valid counts; inactive row frozen.
    assert np.asarray(cache2.lengths).tolist() == [L + K, 0]
    np.testing.assert_allclose(
        np.asarray(cache.k[:, 0, : L + K]),
        np.asarray(cache2.k[:, 0, : L + K]),
        rtol=1e-5, atol=1e-6,
    )
    assert bool(np.asarray(act2)[0]) and not bool(np.asarray(act2)[1])
    assert np.asarray(rem2).tolist() == [10 - K, 0]


@pytest.mark.slow
def test_decode_multistep_eos_latch_freezes_row(tiny):
    """EOS latch inside the scan: the row emits its EOS token, then
    freezes — no further tokens, no further length advance, no K/V
    committed past it."""
    import jax.numpy as jnp

    from tpumlops.models import llama

    params, cfg = tiny
    prompt = [5, 9, 2]
    ref = _ref(params, cfg, prompt, 8)
    eos = ref[3]  # the 4th generated token: mid-scan for K=8
    cache = llama.insert_sequence(
        llama.RaggedKVCache.create(cfg, 2, jnp.float64),
        llama.prefill(
            params,
            jnp.asarray(
                np.pad(np.asarray([prompt], np.int32), ((0, 0), (0, 13)))
            ),
            cfg, dtype=jnp.float64,
        )[1],
        jnp.int32(0), jnp.int32(len(prompt)),
    )
    t0 = np.zeros((2, 1), np.int32)
    t0[0, 0] = ref[0]

    def sample(lg, carry):
        return carry, jnp.argmax(lg, axis=-1).astype(jnp.int32)

    tok_block, valid, _toks, cache, act, _rem, _ = llama.decode_multistep(
        params, jnp.asarray(t0), cache, cfg,
        jnp.asarray(np.array([True, False])),
        jnp.asarray(np.array([20, 0], np.int32)),
        jnp.asarray(np.array([eos, -1], np.int32)),
        8, sample, sample_carry=None, dtype=jnp.float64, window=24,
    )
    v = int(np.asarray(valid)[0])
    assert v == 3  # tokens ref[1], ref[2], ref[3] == eos
    assert np.asarray(tok_block)[0, :v].tolist() == ref[1:4]
    assert int(np.asarray(cache.lengths)[0]) == len(prompt) + v
    assert not bool(np.asarray(act)[0])  # latched off mid-scan


@pytest.mark.slow
def test_engine_fused_matches_reference_with_slot_churn(tiny):
    """The acceptance bar: K=4 fused decode is token-for-token equal to
    plain greedy decode across staggered joins and slot reuse, while
    actually dispatching fused ticks."""
    params, cfg = tiny
    engine = _engine(params, cfg, decode_steps=4)
    engine.start(warmup=True)
    try:
        prompts = [
            ([1, 2, 3] * 5, 10),
            ([5, 9, 2], 6),
            ([7, 1, 4, 8, 3], 9),
            ([42], 4),
            ([10, 20, 30, 40, 50, 60, 70], 5),  # 5 reqs > 2 slots: reuse
        ]
        futs = [engine.submit(p, n) for p, n in prompts]
        outs = [f.result(timeout=300).tolist() for f in futs]
        refs = [_ref(params, cfg, p, n) for p, n in prompts]
    finally:
        engine.shutdown()
    assert outs == refs
    assert engine.dispatches_total.get("multistep", 0) > 0


@pytest.mark.slow
def test_engine_fused_seeded_sampling_matches_single_step(tiny):
    """Seeded sampling: the fused scan's on-device key chain (one split
    per step, every row) must reproduce the single-step loop's stream
    exactly — same seed, same tokens, at every K."""
    params, cfg = tiny
    req = dict(temperature=0.9, top_k=7, top_p=0.95, seed=123)
    outs = {}
    for k in (1, 2, 4, 8):
        engine = _engine(params, cfg, decode_steps=k)
        engine.start(warmup=True)
        try:
            outs[k] = engine.generate([5, 9, 2], 9, timeout=300, **req).tolist()
            # Mixed tick: a greedy request decodes alongside a sampled
            # one (the sampling fused variant serves both rows).
            mixed = engine.submit([7, 1, 4], 6, temperature=0.7, seed=9)
            greedy = engine.generate([1, 2, 3], 6, timeout=300).tolist()
            assert greedy == _ref(params, cfg, [1, 2, 3], 6)
            assert len(mixed.result(timeout=300)) == 6
        finally:
            engine.shutdown()
        if k > 1:
            assert engine.dispatches_total.get("multistep", 0) > 0
    assert outs[2] == outs[1]
    assert outs[4] == outs[1]
    assert outs[8] == outs[1]


@pytest.mark.slow
def test_engine_fused_eos_mid_scan_and_short_budgets(tiny):
    """EOS landing mid-scan-block stops the stream exactly where the
    single-step loop would; a request budget shorter than K emits
    exactly its budget (the latch counts remaining on device)."""
    params, cfg = tiny
    full = _ref(params, cfg, [5, 9, 2], 24)
    eos = full[5]
    expect = _ref(params, cfg, [5, 9, 2], 24, eos=eos)
    engine = _engine(params, cfg, decode_steps=8)
    engine.start(warmup=True)
    try:
        out = engine.generate([5, 9, 2], 24, eos_id=eos, timeout=300).tolist()
        short = engine.generate([7, 1, 4], 3, timeout=300).tolist()
    finally:
        engine.shutdown()
    assert out == expect
    assert short == _ref(params, cfg, [7, 1, 4], 3)
    assert len(short) == 3  # never over-emits past the budget


def test_engine_fused_amortizes_dispatches(tiny):
    """One long request: decode dispatches collapse ~K-fold (ceil((n-1)/K)
    fused ticks for n-1 decode-emitted tokens) — the series the
    tpumlops_engine_dispatches_total counter exports."""
    params, cfg = tiny
    prompt, n, K = [5, 9, 2], 25, 4
    ref = _ref(params, cfg, prompt, n)
    seen = []
    engine = _engine(params, cfg, decode_steps=K, on_dispatch=seen.append)
    engine.start(warmup=True)
    try:
        out = engine.generate(prompt, n, timeout=300).tolist()
    finally:
        engine.shutdown()
    assert out == ref
    fused = engine.dispatches_total.get("multistep", 0)
    assert fused == -(-(n - 1) // K)  # 24 tokens -> 6 fused dispatches
    assert engine.dispatches_total.get("decode", 0) == 0
    assert engine.decode_tokens == n - 1
    # The callback mirrors the host counter (the Prometheus feed).
    assert seen.count("multistep") == fused
    assert seen.count("prefill") == engine.dispatches_total.get("prefill", 0)


@pytest.mark.slow
def test_engine_fused_window_pre_pick_covers_k_steps(tiny):
    """Every fused dispatch's static window must cover the LAST scan
    step's attended positions (length + K - 1) — a row crossing a
    bucket edge inside the K steps is the regression this pins."""
    params, cfg = tiny
    engine = _engine(params, cfg, decode_steps=4)
    windows = []
    orig = engine._dispatch_multistep

    def spy(active, remaining, eos_ids, window, sampling):
        if not engine._in_warmup:
            hi = max(
                s.prompt_len + len(s.generated)
                for s in engine._slots if s is not None
            )
            windows.append((window, hi))
        return orig(active, remaining, eos_ids, window, sampling)

    engine._dispatch_multistep = spy
    engine.start(warmup=True)
    try:
        # Prompt length 14: the stream crosses the 16 and 24 buckets
        # inside fused blocks.
        prompt = list(range(1, 15))
        out = engine.generate(prompt, 20, timeout=300).tolist()
    finally:
        engine.shutdown()
    assert out == _ref(params, cfg, prompt, 20)
    assert windows, "fused path never engaged"
    for window, hi in windows:
        need = min(hi + engine._decode_steps - 1, engine.capacity)
        assert window >= need, (window, hi)
        assert window in decode_window_buckets(engine.capacity)


@pytest.mark.slow
def test_engine_fused_with_prefix_cache(tiny):
    """Prefix-cache composition: a radix-cache hit seeds the prompt and
    the fused decode that follows still matches the reference."""
    params, cfg = tiny
    from tpumlops.server.prefix_cache import PrefixCacheConfig

    shared = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3]  # one chunk
    engine = _engine(
        params, cfg, decode_steps=4,
        prefill_chunk=16,
        prefix_cache=PrefixCacheConfig(
            enabled=True, budget_bytes=1 << 20, chunk_tokens=16
        ),
    )
    engine.start(warmup=True)
    try:
        p1 = shared + [11, 12]
        p2 = shared + [13]
        o1 = engine.generate(p1, 8, timeout=300).tolist()
        hits0 = engine.prefix_hits
        o2 = engine.generate(p2, 8, timeout=300).tolist()
    finally:
        engine.shutdown()
    assert o1 == _ref(params, cfg, p1, 8)
    assert o2 == _ref(params, cfg, p2, 8)
    assert engine.prefix_hits > hits0  # the warm path actually seeded
    assert engine.dispatches_total.get("multistep", 0) > 0


@pytest.mark.slow
def test_engine_fused_composes_with_speculative(tiny):
    """Per-slot composition (documented fallback, not an error): ticks
    holding draft proposals run verify, draft-less ticks fuse — output
    stays token-for-token greedy either way."""
    import jax.numpy as jnp

    from tpumlops.server.generation import GenerationEngine
    from tpumlops.server.speculative import SpeculativeConfig

    params, cfg = tiny
    rep, rep_n = [1, 2, 3] * 5, 10
    rep_ref = _ref(params, cfg, rep, rep_n)
    engine = GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float64, decode_steps=4,
        speculative=SpeculativeConfig(
            enabled=True, draft_tokens=2, ngram_min=1, ngram_max=4,
            adaptive=True,
        ),
    )

    # Oracle drafter for the rep stream only (deterministic: the n-gram
    # drafter's hits depend on what the random-weight model happens to
    # emit): ticks where rep is live carry drafts -> verify fallback;
    # every other stream proposes nothing -> fused ticks.
    def propose(slot, budget):
        if slot.history[: slot.prompt_len].tolist() == rep:
            g = len(slot.generated)
            return rep_ref[g : g + budget]
        return []

    engine._propose = propose
    engine.start(warmup=True)
    try:
        rnd = ([7, 1, 4, 8, 3], 9)
        futs = [engine.submit(rep, rep_n), engine.submit(*rnd)]
        outs = [f.result(timeout=300).tolist() for f in futs]
        # A draft-less solo stream fuses.
        solo = engine.generate([6, 2, 8, 4, 1], 8, timeout=300).tolist()
    finally:
        engine.shutdown()
    assert outs[0] == rep_ref
    assert outs[1] == _ref(params, cfg, rnd[0], rnd[1])
    assert solo == _ref(params, cfg, [6, 2, 8, 4, 1], 8)
    assert engine.spec_verify_ticks > 0, "verify fallback never engaged"
    assert engine.dispatches_total.get("multistep", 0) > 0, (
        "fused path never engaged"
    )


@pytest.mark.slow
def test_engine_default_single_step_is_untouched(tiny):
    """decodeSteps=1 (the default): no fused program exists, no fused
    tick is ever dispatched, and the loop is the single-step tick loop
    byte-for-byte."""
    params, cfg = tiny
    engine = _engine(params, cfg, decode_steps=1)
    assert not engine._fused
    assert not hasattr(engine, "_multistep")
    assert not hasattr(engine, "_multistep_greedy")
    engine.start(warmup=True)
    try:
        out = engine.generate([5, 9, 2], 6, timeout=300).tolist()
    finally:
        engine.shutdown()
    assert out == _ref(params, cfg, [5, 9, 2], 6)
    assert "multistep" not in engine.dispatches_total
    assert engine.dispatches_total.get("decode", 0) > 0


@pytest.mark.slow
def test_engine_fused_defers_to_admissions(tiny):
    """A queued request suppresses fusing: slots must free at single-step
    cadence while someone is waiting for one (fused ticks would hold a
    finishing slot for up to K extra tokens)."""
    params, cfg = tiny
    engine = _engine(params, cfg, decode_steps=8)
    engine.start(warmup=True)
    try:
        # 3 requests > 2 slots: while the third queues, ticks single-step.
        futs = [
            engine.submit([5, 9, 2], 8),
            engine.submit([7, 1, 4], 8),
            engine.submit([1, 2, 3], 8),
        ]
        outs = [f.result(timeout=300).tolist() for f in futs]
    finally:
        engine.shutdown()
    assert outs == [
        _ref(params, cfg, [5, 9, 2], 8),
        _ref(params, cfg, [7, 1, 4], 8),
        _ref(params, cfg, [1, 2, 3], 8),
    ]
    # Both modes ran: single-step while the queue was non-empty, fused
    # after it drained.
    assert engine.dispatches_total.get("decode", 0) > 0
    assert engine.dispatches_total.get("multistep", 0) > 0


@pytest.mark.slow
def test_warmup_compiles_multistep_variants(tiny):
    """No live request may pay a fused-program compile: after warmup
    every (K, window bucket) variant of BOTH token rules is compiled."""
    params, cfg = tiny  # capacity 64 -> buckets 16, 24, 32, 48, 64
    engine = _engine(params, cfg, decode_steps=4)
    engine.start(warmup=True)
    try:
        want = len(decode_window_buckets(engine.capacity))
        assert engine._multistep_greedy._cache_size() >= want, (
            engine._multistep_greedy._cache_size(), want
        )
        assert engine._multistep._cache_size() >= want, (
            engine._multistep._cache_size(), want
        )
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------------
# Multihost lockstep replay of the fused op
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_multihost_replay_of_multistep(tiny):
    """A fused stream on a 2-'host' unit must leave leader and follower
    device state identical: the follower replays OP_GEN_MULTISTEP —
    burst-start ticks with the broadcast mask/budgets/EOS ids, chained
    ticks from its OWN device-resident chain state."""
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
    leader = _engine(params, cfg, decode_steps=4, channel=channel)
    follower = _engine(params, cfg, decode_steps=4)

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
        assert leader.dispatches_total.get("multistep", 0) > 1  # chained
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
    import jax

    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(leader._keys)),
        np.asarray(jax.random.key_data(follower._keys)),
    )
