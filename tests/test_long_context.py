"""Long-context serving: sp ring-attention prefill (PR 17).

Two layers of proof.  Op level: ``ring_attention_sharded`` in f64 against
the dense oracle — causal boundaries that land mid-ring-step, an uneven
(padded) last shard, and the GQA ``prefill_ring`` forward against the
dense ``prefill``.  Engine level: a cold prompt at or above
``spPrefillThreshold`` routes through the sp ring-prefill program and the
emitted tokens are f64 token-for-token identical to the unsharded engine
— greedy, below/above-threshold routing, int8kv, prefix-cache seeding
from the sp pass, and the sp x tp composed mesh.  ``{"sp": 1}`` is
pinned byte-for-byte: no mesh, no sp program, identical dispatch ledger.
Engine-tracing tests are ``slow``; op-level and constructor pins run in
the fast tranche.
"""

import numpy as np
import pytest


def _tiny_cfg(**kw):
    from tpumlops.models import llama

    defaults = dict(num_heads=4, num_kv_heads=4, max_seq=64)
    defaults.update(kw)
    return llama.LlamaConfig.tiny(**defaults)


@pytest.fixture(scope="module")
def x64():
    import jax

    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------------------------------
# Op level: ring attention vs the dense oracle, f64
# ---------------------------------------------------------------------------


def _dense_causal_f64(q, k, v, scale=None):
    """Dense causal attention, fully f64: an oracle that accumulated its
    scores in f32 would put an f32 noise floor under an exactness claim."""
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    qi = jnp.arange(q.shape[2])
    ki = jnp.arange(k.shape[2])
    s = jnp.where(ki[None, None, None, :] <= qi[None, None, :, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def test_ring_f64_parity_and_causal_boundary(x64):
    """f64 ring attention over sp=4 equals the dense causal oracle to
    ulp-level tolerance — including the query rows at every ring-step
    boundary (position S/n - 1 attends its whole local shard; position
    S/n sees exactly one remote block), where a mask off-by-one would
    show first."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models.partition import build_serving_mesh
    from tpumlops.ops.ring_attention import ring_attention_sharded

    mesh = build_serving_mesh({"sp": 4})
    b, h, s, d = 1, 4, 32, 8
    ks = jax.random.split(jax.random.key(7), 3)
    q, k, v = (
        jax.random.normal(kk, (b, h, s, d), jnp.float64) for kk in ks
    )
    out = ring_attention_sharded(q, k, v, mesh, causal=True)
    ref = _dense_causal_f64(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-12, atol=1e-13
    )
    # The boundary rows explicitly: chunk = 8, so rows 7 and 8 straddle
    # the first ring step.
    chunk = s // 4
    for row in (chunk - 1, chunk, 2 * chunk - 1, 2 * chunk, s - 1):
        np.testing.assert_allclose(
            np.asarray(out)[:, :, row],
            np.asarray(ref)[:, :, row],
            rtol=1e-12, atol=1e-13,
        )


def test_ring_uneven_last_shard_via_padding(x64):
    """The serving path pads a prompt whose length does not divide sp up
    to the bucket; causal masking makes every REAL query row independent
    of the garbage tail, so out[:, :, :L] must still equal the dense
    oracle on the unpadded prefix."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models.partition import build_serving_mesh
    from tpumlops.ops.ring_attention import ring_attention_sharded

    mesh = build_serving_mesh({"sp": 4})
    b, h, s, d = 1, 4, 32, 8
    L = 27  # uneven: last shard holds 3 real rows + 5 pad rows
    ks = jax.random.split(jax.random.key(11), 4)
    q, k, v = (
        jax.random.normal(kk, (b, h, L, d), jnp.float64) for kk in ks[:3]
    )
    pad = 1e3 * jax.random.normal(ks[3], (b, h, s - L, d), jnp.float64)
    qp = jnp.concatenate([q, pad], axis=2)
    kp = jnp.concatenate([k, pad], axis=2)
    vp = jnp.concatenate([v, pad], axis=2)
    out = ring_attention_sharded(qp, kp, vp, mesh, causal=True)
    ref = _dense_causal_f64(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out)[:, :, :L], np.asarray(ref), rtol=1e-12, atol=1e-13
    )


def test_prefill_ring_gqa_matches_dense_prefill(x64):
    """The full forward: ``prefill_ring`` (ring attention, GQA repeat,
    seq-sharded activations) matches the dense ``prefill`` — same
    argmax token at the last position (the serving contract) and K/V
    prefix / logits within the model's f32 accumulation floor
    (``_qmatmul`` pins ``preferred_element_type=f32``, so exact-ulp is
    not on the table for the full forward even with f64 params).
    num_kv_heads=2 under num_heads=4 exercises the grouped-query repeat
    inside the ring block."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama
    from tpumlops.models.partition import build_serving_mesh

    cfg = _tiny_cfg(num_heads=4, num_kv_heads=2, max_seq=64)
    params = llama.init(jax.random.key(0), cfg, dtype=jnp.float64)
    mesh = build_serving_mesh({"sp": 2})
    ids = jax.random.randint(jax.random.key(5), (1, 32), 0, cfg.vocab_size)
    logits, k_all, v_all = llama.prefill_ring(
        params, ids, cfg, mesh=mesh, last_idx=31, dtype=jnp.float64
    )
    ref_logits, cache = llama.prefill(params, ids, cfg, dtype=jnp.float64)
    assert int(np.argmax(np.asarray(logits)[0])) == int(
        np.argmax(np.asarray(ref_logits)[0, -1])
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits)[:, -1], rtol=1e-4,
        atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(k_all), np.asarray(cache.k)[:, :, :32], rtol=1e-4,
        atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(v_all), np.asarray(cache.v)[:, :, :32], rtol=1e-4,
        atol=1e-6,
    )


def test_sp1_engine_builds_no_sp_program():
    """{"sp": 1} is byte-for-byte the unsharded engine: no mesh, no ring
    prefill program, threshold routing can never fire."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama
    from tpumlops.server.generation import GenerationEngine

    cfg = _tiny_cfg()
    params = llama.init(jax.random.key(0), cfg)
    engine = GenerationEngine(
        params, cfg, max_slots=2, dtype=jnp.float32,
        mesh_shape={"dp": 1, "sp": 1, "tp": 1},
        sp_prefill_threshold=16,
    )
    assert engine._mesh is None
    assert engine._sp == 1
    assert getattr(engine, "_prefill_sp", None) is None


# ---------------------------------------------------------------------------
# Engine level: sp routing + parity (slow tranche)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny(x64):
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama

    cfg = _tiny_cfg()
    params = llama.init(jax.random.key(0), cfg, dtype=jnp.float64)
    return params, cfg


def _ref(params, cfg, prompt, n):
    import jax.numpy as jnp

    from tpumlops.models import llama

    out = llama.generate_greedy(
        params, jnp.asarray([prompt], jnp.int32), n, cfg, dtype=jnp.float64
    )
    return np.asarray(out)[0].tolist()


def _engine(params, cfg, mesh_shape=None, **kw):
    import jax.numpy as jnp

    from tpumlops.models import partition
    from tpumlops.server.generation import GenerationEngine

    if mesh_shape and partition.mesh_device_count(mesh_shape) > 1:
        params = partition.shard_llama_params(
            params, partition.build_serving_mesh(mesh_shape)
        )
    return GenerationEngine(
        params, cfg, max_slots=4, dtype=jnp.float64,
        mesh_shape=mesh_shape, **kw,
    )


def _long_prompt(n, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 200, size=n).tolist()


@pytest.mark.parametrize("sp", [2, 4])
def test_sp_prefill_parity_and_routing(tiny, sp):
    """A cold prompt >= spPrefillThreshold routes through the ring
    prefill ('sp-prefill' in the dispatch ledger) and the whole decoded
    stream is f64 token-for-token vs the unsharded engine; a prompt one
    token BELOW threshold stays on the dense path."""
    params, cfg = tiny
    long_p = _long_prompt(32)
    short_p = _long_prompt(15, seed=4)
    engine = _engine(
        params, cfg, mesh_shape={"sp": sp}, sp_prefill_threshold=16
    )
    engine.start(warmup=False)
    try:
        out_long = engine.generate(long_p, 8, timeout=300).tolist()
        n_sp = engine.dispatches_total.get("sp-prefill", 0)
        assert n_sp == 1
        out_short = engine.generate(short_p, 6, timeout=300).tolist()
        assert engine.dispatches_total.get("sp-prefill", 0) == n_sp
    finally:
        engine.shutdown()
    assert out_long == _ref(params, cfg, long_p, 8)
    assert out_short == _ref(params, cfg, short_p, 6)


@pytest.mark.slow
def test_sp_int8kv_parity(tiny):
    """int8kv under sp=2: the ring-prefilled K/V quantizes on insert
    exactly as the dense-prefilled cache does — the quantized stream
    matches the sp=1 int8kv stream token-for-token."""
    params, cfg = tiny
    long_p = _long_prompt(32, seed=9)
    outs = {}
    for key, shape in (("base", None), ("sp", {"sp": 2})):
        engine = _engine(
            params, cfg, mesh_shape=shape, kv_quant=True,
            sp_prefill_threshold=16,
        )
        engine.start(warmup=False)
        try:
            outs[key] = engine.generate(long_p, 8, timeout=300).tolist()
            if shape:
                assert engine.dispatches_total.get("sp-prefill", 0) == 1
        finally:
            engine.shutdown()
    assert outs["sp"] == outs["base"]


@pytest.mark.slow
def test_sp_prefix_cache_seeded_from_ring_prefill(tiny):
    """The sp pass feeds the prefix cache: after one long cold prompt
    through ring prefill, a second request sharing the 16-token prefix
    HITS the cache, and both streams match the unsharded engine."""
    from tpumlops.server.prefix_cache import PrefixCacheConfig

    params, cfg = tiny
    shared = _long_prompt(32, seed=21)
    follow = shared[:16] + _long_prompt(4, seed=22)
    kw = dict(
        prefill_chunk=16,
        prefix_cache=PrefixCacheConfig(
            enabled=True, budget_bytes=1 << 22, chunk_tokens=16
        ),
        sp_prefill_threshold=16,
    )
    outs = {}
    hits = {}
    for key, shape in (("base", None), ("sp", {"sp": 2})):
        engine = _engine(params, cfg, mesh_shape=shape, **kw)
        engine.start(warmup=False)
        try:
            o = [engine.generate(shared, 6, timeout=300).tolist()]
            o.append(engine.generate(follow, 6, timeout=300).tolist())
            outs[key] = o
            hits[key] = engine.prefix_hits
            if shape:
                assert engine.dispatches_total.get("sp-prefill", 0) >= 1
        finally:
            engine.shutdown()
    assert outs["sp"] == outs["base"]
    assert outs["base"][0] == _ref(params, cfg, shared, 6)
    assert hits["sp"] > 0 and hits["base"] > 0


@pytest.mark.slow
def test_sp_tp_composed_mesh_parity(tiny):
    """sp ring prefill composes with tp decode on a {"sp": 2, "tp": 2}
    mesh: one engine, both axes live, tokens equal the single-device
    stream for long (ring) and short (dense) prompts alike."""
    params, cfg = tiny
    long_p = _long_prompt(32, seed=31)
    short_p = _long_prompt(10, seed=32)
    engine = _engine(
        params, cfg, mesh_shape={"sp": 2, "tp": 2}, sp_prefill_threshold=16
    )
    engine.start(warmup=False)
    try:
        out_long = engine.generate(long_p, 8, timeout=300).tolist()
        out_short = engine.generate(short_p, 6, timeout=300).tolist()
        assert engine.dispatches_total.get("sp-prefill", 0) == 1
    finally:
        engine.shutdown()
    assert out_long == _ref(params, cfg, long_p, 8)
    assert out_short == _ref(params, cfg, short_p, 6)


def test_sp1_dispatch_ledger_byte_for_byte(tiny):
    """{"sp": 1} (and the absent mesh) serve the same requests with the
    IDENTICAL per-kind dispatch ledger — no new programs, no sp-prefill
    entry, no extra host round-trips from the threshold check."""
    params, cfg = tiny
    prompts = [(_long_prompt(32, seed=41), 6), (_long_prompt(8, seed=42), 4)]
    counts = {}
    outs = {}
    for key, shape in (("none", None), ("sp1", {"dp": 1, "sp": 1, "tp": 1})):
        engine = _engine(
            params, cfg, mesh_shape=shape, sp_prefill_threshold=16
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
    assert outs["sp1"] == outs["none"]
    assert counts["sp1"] == counts["none"]
    assert "sp-prefill" not in counts["sp1"]


@pytest.mark.slow
def test_sp_warmup_sweep_covers_ring_buckets(tiny):
    """warmup=True under sp=2 pre-compiles the ring bucket ladder; the
    first live long request then dispatches with no lazy compile and
    still matches the reference stream."""
    params, cfg = tiny
    long_p = _long_prompt(32, seed=51)
    engine = _engine(
        params, cfg, mesh_shape={"sp": 2}, sp_prefill_threshold=16
    )
    engine.start(warmup=True)
    try:
        out = engine.generate(long_p, 6, timeout=300).tolist()
        assert engine.dispatches_total.get("sp-prefill", 0) >= 1
    finally:
        engine.shutdown()
    assert out == _ref(params, cfg, long_p, 6)
