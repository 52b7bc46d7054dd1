"""Mid-decode preemption: SLO-class eviction with NO lost work.

The engine contract under test (ISSUE 18): when an interactive request
arrives and every slot is busy, the engine evicts the youngest
best-effort slot AT A TICK BOUNDARY, spills its KV through the prefix
cache (L1, overflowing to the host L2 tier), requeues it, and later
restores it — PRNG carry, pending token, and sampling rows included —
such that the preempted stream's final output is BIT-identical to an
uninterrupted run.  Every parity test runs in float64 on the tiny CPU
llama fixture (module-wide ``jax_enable_x64``) so no backend fast-math
can blur the identity assertions; everything tracing jitted programs is
marked ``slow`` (same tranche policy as test_generation.py).
"""

import threading

import numpy as np
import pytest

from tpumlops.server.prefix_cache import PrefixCacheConfig

BE_PROMPT = list(range(2, 14))
IA_PROMPT = list(range(30, 40))


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


def _pc(budget_bytes=1 << 22, **kw):
    return PrefixCacheConfig(
        enabled=True, budget_bytes=budget_bytes, chunk_tokens=8, **kw
    )


def _engine(params, cfg, max_slots=1, **kw):
    import jax.numpy as jnp

    from tpumlops.server.generation import GenerationEngine

    kw.setdefault("prefix_cache", _pc())
    return GenerationEngine(
        params, cfg, max_slots=max_slots, dtype=jnp.float64,
        preemption=True, **kw,
    )


def _run_preempted(engine, n_be=20, trigger_at=4, **submit_kw):
    """Fill the engine with a best-effort stream, inject an interactive
    request after ``trigger_at`` tokens (forcing the evict), and return
    (best-effort output, interactive output, preemptions, restores)."""
    engine.start(warmup=True)
    try:
        got = threading.Event()
        count = [0]

        def on_tok(_t):
            count[0] += 1
            if count[0] >= trigger_at:
                got.set()

        f_be = engine.submit(
            BE_PROMPT, n_be, on_token=on_tok, slo_class="best-effort",
            **submit_kw,
        )
        assert got.wait(60), "best-effort stream never produced tokens"
        f_i = engine.submit(IA_PROMPT, 5, slo_class="interactive")
        out_i = np.asarray(f_i.result(60)).tolist()
        out_be = np.asarray(f_be.result(60)).tolist()
        return out_be, out_i, engine.preemptions, engine.preempt_restores
    finally:
        engine.shutdown()


def _run_clean(engine, n_be=20, **submit_kw):
    """The uninterrupted reference run on an identically-built engine."""
    engine.start(warmup=True)
    try:
        return np.asarray(
            engine.submit(BE_PROMPT, n_be, **submit_kw).result(60)
        ).tolist()
    finally:
        engine.shutdown()


def test_preempt_greedy_no_lost_work(tiny):
    """The headline invariant: the evicted-and-restored best-effort
    stream equals the pure-model greedy reference token for token, and
    the interactive request that displaced it is untouched too."""
    params, cfg = tiny
    out_be, out_i, n_pre, n_res = _run_preempted(_engine(params, cfg))
    assert n_pre >= 1 and n_res >= 1
    assert out_be == _ref(params, cfg, BE_PROMPT, 20)
    assert out_i == _ref(params, cfg, IA_PROMPT, 5)


@pytest.mark.slow
def test_preempt_seeded_sampling_parity(tiny):
    """Sampling: the restore must reinstall the PRNG carry WITHOUT a
    split, so the preempted seeded stream matches the clean one."""
    params, cfg = tiny
    kw = dict(temperature=1.0, seed=7)
    out_p, _, n_pre, _ = _run_preempted(_engine(params, cfg), **kw)
    out_c = _run_clean(_engine(params, cfg), **kw)
    assert n_pre >= 1
    assert out_p == out_c


@pytest.mark.slow
def test_preempt_mid_multistep_parity(tiny):
    """decodeSteps=4: eviction lands between fused super-steps, never
    inside one — output still bit-identical."""
    params, cfg = tiny
    out_p, _, n_pre, _ = _run_preempted(
        _engine(params, cfg, decode_steps=4)
    )
    out_c = _run_clean(_engine(params, cfg, decode_steps=4))
    assert n_pre >= 1
    assert out_p == out_c


@pytest.mark.slow
def test_preempt_during_speculative_parity(tiny):
    """Speculative decode: preemption between draft/verify rounds keeps
    the accepted-token stream identical to the uninterrupted run."""
    from tpumlops.server.speculative import SpeculativeConfig

    params, cfg = tiny
    spec = SpeculativeConfig(enabled=True, draft_tokens=4)
    out_p, _, n_pre, _ = _run_preempted(
        _engine(params, cfg, speculative=spec)
    )
    out_c = _run_clean(_engine(params, cfg, speculative=spec))
    assert n_pre >= 1
    assert out_p == out_c


@pytest.mark.slow
def test_preempt_packed_prefill_parity(tiny):
    """prefillBatch=2 with two concurrent best-effort streams: evicting
    one to admit the interactive request leaves both streams' outputs
    equal to their clean-engine counterparts."""
    params, cfg = tiny
    engine = _engine(params, cfg, max_slots=2, prefill_batch=2)
    other = list(range(50, 60))
    engine.start(warmup=True)
    try:
        got = threading.Event()
        count = [0]

        def on_tok(_t):
            count[0] += 1
            if count[0] >= 4:
                got.set()

        f1 = engine.submit(
            BE_PROMPT, 20, on_token=on_tok, slo_class="best-effort"
        )
        f2 = engine.submit(other, 20, slo_class="best-effort")
        assert got.wait(60)
        f_i = engine.submit(IA_PROMPT, 5, slo_class="interactive")
        f_i.result(60)
        out1 = np.asarray(f1.result(60)).tolist()
        out2 = np.asarray(f2.result(60)).tolist()
        n_pre = engine.preemptions
    finally:
        engine.shutdown()
    assert n_pre >= 1
    clean = _run_clean(_engine(params, cfg, max_slots=2, prefill_batch=2))
    assert out1 == clean
    assert out2 == _ref(params, cfg, other, 20)


@pytest.mark.slow
def test_restore_through_l2_tier(tiny):
    """A starved L1 (9 KiB) forces the evicted slot's KV chunks into the
    host L2 tier; the restore promotes them back — counted as l2 hits —
    and the stream still matches the greedy reference."""
    params, cfg = tiny
    engine = _engine(
        params, cfg,
        prefix_cache=_pc(budget_bytes=9 * 1024, l2_budget_bytes=1 << 22),
    )
    out_be, _, n_pre, _ = _run_preempted(engine, n_be=24, trigger_at=10)
    assert n_pre >= 1
    assert engine._prefix_cache.l2_hits > 0
    assert out_be == _ref(params, cfg, BE_PROMPT, 24)


@pytest.mark.slow
def test_multihost_replay_parity(tiny):
    """Lockstep replay: the leader's evict + restore ride the existing
    op stream (seed-slot dispatch + gen_restore), so a follower replays
    to BIT-identical tokens, lengths, PRNG keys, and KV cache."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama
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
    steps = [None]

    class _Dummy:
        def predict(self, x):
            return x

    th = threading.Thread(
        target=lambda: steps.__setitem__(
            0, follower_loop(_Dummy(), transports[1], gen_engine=follower)
        ),
        daemon=True,
    )
    th.start()
    leader.start(warmup=True)
    try:
        got = threading.Event()
        count = [0]

        def on_tok(_t):
            count[0] += 1
            if count[0] >= 4:
                got.set()

        f_be = leader.submit(
            BE_PROMPT, 16, on_token=on_tok, slo_class="best-effort"
        )
        assert got.wait(60)
        f_i = leader.submit(IA_PROMPT, 5, slo_class="interactive")
        f_i.result(60)
        out_be = np.asarray(f_be.result(60)).tolist()
        assert leader.preemptions >= 1 and leader.preempt_restores >= 1
    finally:
        leader.shutdown()
        channel.close_with(encode_message(OP_SHUTDOWN))
    th.join(timeout=30)
    assert steps[0], "follower replayed no steps"
    np.testing.assert_array_equal(
        np.asarray(leader._tokens), np.asarray(follower._tokens)
    )
    np.testing.assert_array_equal(
        np.asarray(leader._lengths), np.asarray(follower._lengths)
    )
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(leader._keys)),
        np.asarray(jax.random.key_data(follower._keys)),
    )
    np.testing.assert_allclose(
        np.asarray(leader._cache_k), np.asarray(follower._cache_k)
    )
    ref = np.asarray(
        llama.generate_greedy(
            params, jnp.asarray([BE_PROMPT], jnp.int32), 16, cfg,
            dtype=jnp.float64,
        )
    )[0].tolist()
    assert out_be == ref
