"""Self-speculative n-gram decoding: drafter, adaptive control, parity.

Pure host-side pieces (n-gram proposal, the adaptive draft controller,
spec parsing) run in the fast tranche; everything that traces jitted
programs on the tiny CPU llama fixture is marked ``slow`` (same policy
as test_generation.py — exact-parity runs in float64 so no backend
fast-math can blur the bit-identity assertions).

The acceptance bar (ISSUE 2): with speculation enabled, emitted tokens
are bit-identical to non-speculative greedy decode — across slot churn,
prefix-cache hits, and multihost lockstep replay — while verify ticks
emit multiple tokens per forward when drafts are accepted.
"""

import numpy as np
import pytest

from tpumlops.server.speculative import (
    DraftState,
    SpeculativeConfig,
    draft_chain,
    pad_to_chain,
    propose_ngram,
)

# ---------------------------------------------------------------------------
# N-gram drafter (pure numpy, fast tranche)
# ---------------------------------------------------------------------------


def test_propose_ngram_basic_match():
    # History contains "7 8" once before the suffix; the tokens after the
    # match are the draft.
    ctx = [1, 2, 7, 8, 5, 6, 9, 7, 8]
    assert propose_ngram(ctx, 3, 1, 4) == [5, 6, 9]
    # Cap respected.
    assert propose_ngram(ctx, 2, 1, 4) == [5, 6]


def test_propose_ngram_prefers_longest_suffix_then_most_recent():
    # Suffix "3 4" occurs at two earlier sites with different successors;
    # the MOST RECENT one wins.
    ctx = [3, 4, 10, 5, 3, 4, 20, 5, 3, 4]
    assert propose_ngram(ctx, 1, 1, 4) == [20]
    # A longer suffix match beats a shorter one: "5 3 4" matched at its
    # only earlier site even though "3 4" alone has a more recent one.
    ctx2 = [5, 3, 4, 30, 1, 3, 4, 40, 5, 3, 4]
    assert propose_ngram(ctx2, 1, 1, 4) == [30]


def test_propose_ngram_no_match_and_min_bound():
    assert propose_ngram([1, 2, 3, 4, 5], 4, 1, 4) == []  # all distinct
    # ngram_min=2: a single-token match is not enough.
    assert propose_ngram([7, 1, 7], 2, 2, 4) == []
    assert propose_ngram([7, 1, 7], 2, 1, 4) == [1, 7]
    # Degenerate contexts never crash.
    assert propose_ngram([], 4, 1, 4) == []
    assert propose_ngram([5], 4, 1, 4) == []
    assert propose_ngram([5, 5], 0, 1, 4) == []


def test_propose_ngram_periodic_context_drafts_the_cycle():
    # The payoff case: a repeating pattern drafts its own continuation,
    # TILED — the most recent match sits one period back, and the copy
    # hypothesis context[j] == context[j-d] extends the short cycle to
    # the full budget instead of truncating at the match's tail.
    ctx = [11, 12, 13] * 4
    assert propose_ngram(ctx, 4, 1, 4) == [11, 12, 13, 11]
    assert propose_ngram(ctx, 7, 1, 4) == [11, 12, 13, 11, 12, 13, 11]
    assert propose_ngram(ctx + [11], 4, 1, 4) == [12, 13, 11, 12]
    # Period 1 (the classic greedy loop): the whole draft is one token.
    assert propose_ngram([9, 9, 9], 3, 1, 4) == [9, 9, 9]


def test_draft_chain_and_padding():
    assert draft_chain(4) == (1, 2, 4)
    assert draft_chain(5) == (1, 2, 5)
    assert draft_chain(1) == (1,)
    with pytest.raises(ValueError):
        draft_chain(0)
    chain = draft_chain(8)  # (1, 2, 4, 8)
    assert pad_to_chain(1, chain) == 1
    assert pad_to_chain(3, chain) == 4
    assert pad_to_chain(8, chain) == 8


# ---------------------------------------------------------------------------
# Adaptive controller (pure python, fast tranche)
# ---------------------------------------------------------------------------


def test_draft_state_halves_on_zero_accept_and_regrows():
    st = DraftState(4, adaptive=True)
    assert st.budget() == 4
    st.observe(4, 0)
    assert st.budget() == 4  # one zero tick is not a collapse
    st.observe(4, 0)
    assert st.budget() == 2  # two consecutive zeros halve
    st.observe(2, 0)
    st.observe(2, 0)
    assert st.budget() == 1
    st.observe(1, 0)
    st.observe(1, 0)
    assert st.budget() == 0  # parked: plain single-token decode
    # Success regrows toward the max.
    st.length = 1
    st.observe(1, 1)
    assert st.budget() == 2
    st.observe(2, 2)
    assert st.budget() == 4
    st.observe(4, 4)
    assert st.budget() == 4  # capped at the configured max


def test_draft_state_zero_accept_streak_resets_on_success():
    st = DraftState(4, adaptive=True)
    st.observe(4, 0)
    st.observe(4, 1)  # streak broken
    st.observe(4, 0)
    assert st.budget() == 4  # never two CONSECUTIVE zeros


def test_draft_state_parked_slot_reprobes():
    st = DraftState(4, adaptive=True)
    st.length = 0
    probes = [st.budget() for _ in range(2 * DraftState.REPROBE_AFTER)]
    assert probes.count(1) == 2  # one probation draft per cooldown
    assert set(probes) <= {0, 1}
    # A successful probe revives the slot.
    st.observe(1, 1)
    assert st.budget() == 1


def test_draft_state_non_adaptive_is_pinned():
    st = DraftState(4, adaptive=False)
    for _ in range(10):
        st.observe(4, 0)
        assert st.budget() == 4


# ---------------------------------------------------------------------------
# Spec parsing (fast tranche; unknown-key audit is in test_config.py)
# ---------------------------------------------------------------------------


def test_speculative_spec_parsing_and_validation():
    from tpumlops.utils.config import SpeculativeSpec, TpuSpec

    t = TpuSpec.from_spec(
        {"speculative": {"enabled": True, "draftTokens": 8, "ngramMax": 6}}
    )
    assert t.speculative.enabled
    assert t.speculative.draft_tokens == 8
    assert t.speculative.ngram_min == 1
    assert t.speculative.ngram_max == 6
    assert t.speculative.adaptive is True
    # Disabled by default; absent block parses to the inert spec.
    assert TpuSpec.from_spec({}).speculative.enabled is False
    with pytest.raises(ValueError, match="draftTokens"):
        SpeculativeSpec.from_spec({"enabled": True, "draftTokens": 0})
    with pytest.raises(ValueError, match="ngram"):
        SpeculativeSpec.from_spec(
            {"enabled": True, "ngramMin": 3, "ngramMax": 2}
        )
    # Disabled spec never rejects values (old CRs keep parsing).
    assert SpeculativeSpec.from_spec({"draftTokens": 0}).draft_tokens == 0


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


def _engine(params, cfg, *, draft_tokens=2, adaptive=True, **kw):
    import jax.numpy as jnp

    from tpumlops.server.generation import GenerationEngine

    # draft_tokens=2 keeps the warmup verify sweep small (|chain|=2) on
    # the CPU fixture; individual tests raise it where the draft length
    # matters.
    return GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float64,
        speculative=SpeculativeConfig(
            enabled=True, draft_tokens=draft_tokens, ngram_min=1,
            ngram_max=4, adaptive=adaptive,
        ),
        **kw,
    )


def _oracle(engine, refs_by_prompt):
    """Drafter oracle: proposes the KNOWN greedy continuation, so every
    draft is accepted — isolates the verify/commit/rollback path from
    drafter quality."""

    def propose(slot, budget):
        ref = refs_by_prompt[tuple(slot.history[: slot.prompt_len].tolist())]
        g = len(slot.generated)
        return ref[g : g + budget]

    engine._propose = propose


@pytest.mark.slow
def test_verify_forward_matches_sequential_decode(tiny):
    """Model layer: ONE verify_ragged chunk must reproduce the logits of
    sequential single-token decode_ragged steps (f64)."""
    import jax.numpy as jnp

    from tpumlops.models import llama

    params, cfg = tiny
    def fresh():
        return llama.RaggedKVCache.create(cfg, 2, jnp.float64)

    prompt = [5, 9, 2]
    ids = np.zeros((1, 16), np.int32)
    ids[0, : len(prompt)] = prompt
    logits, seq = llama.prefill(
        params, jnp.asarray(ids), cfg, dtype=jnp.float64
    )
    first = int(jnp.argmax(logits[0, len(prompt) - 1]))
    ref = _ref(params, cfg, prompt, 5)
    assert ref[0] == first

    # Sequential: 4 decode_ragged steps teacher-forced on the reference.
    cache = llama.insert_sequence(
        fresh(), seq, jnp.int32(0), jnp.int32(len(prompt))
    )
    seq_logits = []
    toks = np.zeros((2, 1), np.int32)
    active = np.array([True, False])
    for t in ref[:4]:
        toks[0, 0] = t
        lg, cache = llama.decode_ragged(
            params, jnp.asarray(toks), cache, cfg, jnp.asarray(active),
            dtype=jnp.float64, window=16,
        )
        seq_logits.append(np.asarray(lg[0, -1]))

    # Chunked: ONE verify over the same 4 tokens.
    cache2 = llama.insert_sequence(
        fresh(), seq, jnp.int32(0), jnp.int32(len(prompt))
    )
    chunk = np.zeros((2, 4), np.int32)
    chunk[0] = ref[:4]
    vlogits, cache2 = llama.verify_ragged(
        params, jnp.asarray(chunk), cache2, cfg, dtype=jnp.float64,
        window=16,
    )
    for j in range(4):
        # Activations ride float32 matmul accumulators (_qmatmul's
        # preferred_element_type) even under f64 params, so two program
        # shapes agree to f32 rounding, not bitwise; the engine-level
        # bit-identity bar is TOKEN equality (asserted throughout this
        # module), exactly like decode_ragged vs generate_greedy.
        np.testing.assert_allclose(
            np.asarray(vlogits[0, j]), seq_logits[j], rtol=1e-5, atol=1e-6
        )
        assert int(jnp.argmax(vlogits[0, j])) == ref[j + 1]
    # Committed K/V at the written positions matches the sequential
    # path's to the same f32-accumulator tolerance (rollback-by-
    # truncation leaves these bytes as the only live state).
    L = len(prompt)
    np.testing.assert_allclose(
        np.asarray(cache.k[:, 0, : L + 4]),
        np.asarray(cache2.k[:, 0, : L + 4]),
        rtol=1e-5, atol=1e-6,
    )
    # verify_ragged leaves lengths for the CALLER to advance.
    assert np.asarray(cache2.lengths).tolist() == [L, 0]


@pytest.mark.slow
def test_engine_speculative_matches_reference_with_slot_churn(tiny):
    """The acceptance bar: enabled speculation is token-for-token equal
    to plain greedy decode across staggered joins, slot reuse, and both
    repetitive (draftable) and adversarial (random) prompts."""
    params, cfg = tiny
    engine = _engine(params, cfg, draft_tokens=4)
    engine.start(warmup=True)
    try:
        prompts = [
            ([1, 2, 3] * 5, 10),  # repetitive: the drafter fires
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
    assert engine.spec_verify_ticks > 0  # the verify path actually ran


def test_engine_oracle_drafter_amortizes_forwards(tiny):
    """With a perfect drafter every draft is accepted: the engine must
    emit multiple tokens per decode forward and still match greedy."""
    params, cfg = tiny
    prompt, n = [5, 9, 2], 12
    ref = _ref(params, cfg, prompt, n)
    engine = _engine(params, cfg, draft_tokens=4)
    _oracle(engine, {tuple(prompt): ref})
    engine.start(warmup=True)
    try:
        f0 = engine.decode_forwards
        out = engine.generate(prompt, n, timeout=300).tolist()
        forwards = engine.decode_forwards - f0
    finally:
        engine.shutdown()
    assert out == ref
    # 11 decode-emitted tokens (first comes from prefill) in ceil(11/5)=3
    # verify ticks of up to 4 accepted drafts + 1 bonus each.
    assert forwards < n - 1, (forwards, n)
    assert engine.spec_accepted_tokens == engine.spec_proposed_tokens > 0
    assert engine.decode_tokens == n - 1


@pytest.mark.slow
def test_engine_eos_inside_accepted_run_stops_exactly(tiny):
    """eos produced mid-acceptance must truncate the emission exactly
    where sequential decode would have stopped."""
    params, cfg = tiny
    prompt = [5, 9, 2]
    ref = _ref(params, cfg, prompt, 8)
    eos = ref[4]  # falls inside an accepted span under the oracle drafter
    engine = _engine(params, cfg, draft_tokens=4)
    _oracle(engine, {tuple(prompt): ref})
    engine.start(warmup=True)
    try:
        out = engine.generate(prompt, 8, eos_id=eos, timeout=300).tolist()
    finally:
        engine.shutdown()
    assert out == ref[:5]


@pytest.mark.slow
def test_engine_adaptive_collapse_parks_bad_drafter(tiny):
    """A drafter that is always wrong must decay to the plain step (per
    slot) without perturbing output."""
    params, cfg = tiny
    prompt, n = [5, 9, 2], 14
    ref = _ref(params, cfg, prompt, n)

    engine = _engine(params, cfg, draft_tokens=4)

    def wrong(slot, budget):
        g = len(slot.generated)
        if g >= len(ref):
            return []
        return [(ref[g] + 1) % cfg.vocab_size]  # guaranteed mismatch

    engine._propose = wrong
    engine.start(warmup=True)
    try:
        out = engine.generate(prompt, n, timeout=300).tolist()
        proposed = engine.spec_proposed_tokens
    finally:
        engine.shutdown()
    assert out == ref
    assert engine.spec_accepted_tokens == 0
    # Adaptive halving (4 -> 2 -> 1 -> 0 after 2 zero-accepts each) parks
    # the slot long before every tick could draft.
    assert proposed < n - 1, proposed


@pytest.mark.slow
def test_engine_sampling_slot_falls_back_and_stays_reproducible(tiny):
    """Any sampling slot forces the plain step (verification is a
    greedy-argmax rule): the sampled stream must match a non-speculative
    engine's stream for the same seed."""
    import jax.numpy as jnp

    from tpumlops.server.generation import GenerationEngine

    params, cfg = tiny
    kw = dict(temperature=0.9, top_k=4, top_p=0.95, seed=1234)

    plain = GenerationEngine(params, cfg, max_slots=2, dtype=jnp.float64)
    plain.start(warmup=True)
    try:
        want = plain.generate([5, 9, 2], 7, **kw).tolist()
    finally:
        plain.shutdown()

    engine = _engine(params, cfg)
    engine.start(warmup=True)
    try:
        got = engine.generate([5, 9, 2], 7, **kw).tolist()
        assert engine.spec_verify_ticks == 0  # never speculated
    finally:
        engine.shutdown()
    assert got == want


@pytest.mark.slow
def test_engine_speculative_with_prefix_cache(tiny):
    """Speculation composes with the radix prefix cache: a warm (seeded)
    admission decodes speculatively and still matches greedy."""
    from tpumlops.server.prefix_cache import PrefixCacheConfig

    params, cfg = tiny
    prompt = list(range(2, 22))  # 20 tokens; C=8 -> cached prefix is 16
    ref = _ref(params, cfg, prompt, 6)
    engine = _engine(
        params, cfg, draft_tokens=4,
        prefix_cache=PrefixCacheConfig(
            enabled=True, budget_bytes=1 << 22, chunk_tokens=8
        ),
    )
    _oracle(engine, {tuple(prompt): ref})
    engine.start(warmup=True)
    try:
        assert engine.generate(prompt, 6, timeout=300).tolist() == ref
        assert engine.generate(prompt, 6, timeout=300).tolist() == ref
        assert engine.prefix_hits == 1
        assert engine.spec_accepted_tokens > 0
    finally:
        engine.shutdown()


@pytest.mark.slow
def test_verify_int8kv_reads_chunk_through_quantize_roundtrip(tiny):
    """On the int8 cache, the sequential path attends an earlier chunk
    token AFTER its quantize round-trip (it was committed before being
    read); the verify chunk term must read it the same way, or logits
    diverge by the quantization error (~1e-4) instead of reduction
    rounding (~1e-7) and near-tie argmaxes break token parity."""
    import jax.numpy as jnp

    from tpumlops.models import llama

    params, cfg = tiny
    cache = llama.QuantRaggedKVCache.create(cfg, 2)
    prompt = [5, 9, 2]
    ids = np.zeros((1, 16), np.int32)
    ids[0, : len(prompt)] = prompt
    logits, seq = llama.prefill(
        params, jnp.asarray(ids), cfg, dtype=jnp.float64
    )
    cache = llama.insert_sequence(
        cache, seq, jnp.int32(0), jnp.int32(len(prompt))
    )
    t0 = int(jnp.argmax(logits[0, len(prompt) - 1]))

    cache_seq = cache
    toks = np.zeros((2, 1), np.int32)
    active = np.array([True, False])
    toks[0, 0] = t0
    lg, cache_seq = llama.decode_ragged(
        params, jnp.asarray(toks), cache_seq, cfg, jnp.asarray(active),
        dtype=jnp.float64, window=16,
    )
    g0 = int(jnp.argmax(lg[0, -1]))
    toks[0, 0] = g0
    lg2, _ = llama.decode_ragged(
        params, jnp.asarray(toks), cache_seq, cfg, jnp.asarray(active),
        dtype=jnp.float64, window=16,
    )

    chunk = np.zeros((2, 2), np.int32)
    chunk[0] = [t0, g0]
    vlogits, _ = llama.verify_ragged(
        params, jnp.asarray(chunk), cache, cfg, dtype=jnp.float64,
        window=16,
    )
    # Position 1 attends t0 from the chunk: must see the SAME quantized
    # bytes the sequential read saw (f32-rounding tolerance only).
    np.testing.assert_allclose(
        np.asarray(vlogits[0, 1]), np.asarray(lg2[0, -1]),
        rtol=1e-5, atol=1e-6,
    )


@pytest.mark.slow
def test_speculative_with_int8_kv_cache_matches_plain(tiny):
    """The verify program's quant-cache branch (int8 K/V with factored
    scales): speculative output must equal the plain int8kv engine's —
    same quantization points, same acceptance rule."""
    import jax.numpy as jnp

    from tpumlops.server.generation import GenerationEngine

    params, cfg = tiny
    prompt, n = [1, 2, 3] * 5, 10

    plain = GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float64, kv_quant=True
    )
    plain.start(warmup=False)
    try:
        want = plain.generate(prompt, n, timeout=300).tolist()
    finally:
        plain.shutdown()

    engine = _engine(params, cfg, kv_quant=True)
    engine.start(warmup=False)
    try:
        got = engine.generate(prompt, n, timeout=300).tolist()
        assert engine.spec_verify_ticks > 0
    finally:
        engine.shutdown()
    assert got == want


@pytest.mark.slow
def test_disabled_speculation_keeps_plain_dispatch(tiny):
    """speculative=None (the default) must never touch the verify path:
    every tick dispatches the original single-token step."""
    import jax.numpy as jnp

    from tpumlops.server.generation import GenerationEngine

    params, cfg = tiny
    engine = GenerationEngine(params, cfg, max_slots=2, dtype=jnp.float64)
    assert engine._spec is None
    calls = []
    real = engine._dispatch_step
    engine._dispatch_step = lambda *a: (calls.append(1), real(*a))[1]
    engine.start(warmup=False)
    try:
        ref = _ref(params, cfg, [5, 9, 2], 5)
        assert engine.generate([5, 9, 2], 5, timeout=300).tolist() == ref
    finally:
        engine.shutdown()
    assert len(calls) >= 4
    assert engine.spec_verify_ticks == 0
    assert engine.spec_proposed_tokens == 0


@pytest.mark.slow
def test_midstream_join_and_leave_during_speculation(tiny):
    """A request joining while another slot is mid-speculative-stream
    (and leaving before it finishes) must not perturb either stream."""
    import time as _t

    params, cfg = tiny
    long_p, long_n = [1, 2, 3] * 5, 16
    short_p, short_n = [7, 1, 4], 4
    engine = _engine(params, cfg, draft_tokens=4)
    refs = {
        tuple(np.asarray(long_p, np.int32).tolist()):
            _ref(params, cfg, long_p, long_n),
        tuple(np.asarray(short_p, np.int32).tolist()):
            _ref(params, cfg, short_p, short_n),
    }
    _oracle(engine, refs)
    engine.start(warmup=True)
    try:
        slow = engine.submit(long_p, long_n)
        _t.sleep(0.3)  # let it stream a few verify ticks
        fast = engine.submit(short_p, short_n)  # joins mid-flight
        assert fast.result(timeout=300).tolist() == refs[tuple(short_p)]
        # ... and leaves before the long one finishes (short_n << long_n)
        assert slow.result(timeout=300).tolist() == refs[tuple(long_p)]
    finally:
        engine.shutdown()


@pytest.mark.slow
def test_warmup_compiles_verify_variants(tiny):
    """No live request may pay a verify compile: after warmup every
    (draft chain length, window bucket) variant is already compiled."""
    from tpumlops.server.generation import decode_window_buckets

    params, cfg = tiny  # capacity 64 -> buckets 16, 24, 32, 48, 64
    engine = _engine(params, cfg, draft_tokens=4)  # chain (1, 2, 4)
    engine.start(warmup=True)
    try:
        want = len(decode_window_buckets(engine.capacity)) * len(
            engine._spec_chain
        )
        assert engine._verify._cache_size() >= want, (
            engine._verify._cache_size(), want
        )
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------------
# Multihost lockstep replay of the verify op
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_multihost_replay_of_verify(tiny):
    """A speculative stream on a 2-'host' unit must leave leader and
    follower device state identical: the follower replays OP_GEN_VERIFY
    with the broadcast drafts and the same acceptance falls out of the
    same program."""
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
    leader = _engine(params, cfg, draft_tokens=4, channel=channel)
    follower = _engine(params, cfg, draft_tokens=4)

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

    prompt = [1, 2, 3] * 5  # repetitive: real n-gram drafts fire
    leader.start(warmup=True)
    try:
        ref = _ref(params, cfg, prompt, 10)
        assert leader.generate(prompt, 10, timeout=300).tolist() == ref
        assert leader.spec_verify_ticks > 0
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
