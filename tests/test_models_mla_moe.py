"""The latent-attention, sparse-expert family (models/mla_moe.py) against
its plain float32 reference (benchmarks/references/mla_moe_decoder.py) on
seeded random weights at tiny widths: logits, never tokens, wherever the
two can be compared position by position."""

import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumlops.models import mla_moe

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
CFG = mla_moe.MlaMoeConfig.tiny()  # hidden 64, 4 heads, 8 experts top-2 + 1 shared, 1 + 2 layers
SEQ = 24


@pytest.fixture(scope="module")
def ref_mod():
    if str(BENCH) not in sys.path:
        sys.path.append(str(BENCH))
    import importlib

    return importlib.import_module("references.mla_moe_decoder")


def geometry(cfg):
    return dataclasses.asdict(cfg)  # the artifact's config: the reference's keys


@pytest.fixture(scope="module")
def params():
    p = mla_moe.init(jax.random.key(0), CFG, jnp.float32)
    # A bias that decides some choices and not all (asserted below).
    for l, lp in enumerate(p["layers"][CFG.num_dense_layers:]):
        lp["router_bias"] = 0.05 * jax.random.normal(
            jax.random.key(9 + l), lp["router_bias"].shape, jnp.float32)
    return p


def moe_weights(lp):
    """A layer's FFN leaves under the reference's flat names."""
    return {**{k: v for k, v in lp.items() if k != "experts"}, **lp["experts"]}


def reference_logits(ref_mod, params, toks, cfg=CFG, levels=None):
    """The reference's full forward over rows ``toks`` [R, S]: logits at
    every position, from the program's own tree (same leaf names), and
    the (layer, expert) pairs its router chose at least once."""
    ref = ref_mod.build(geometry(cfg), toks.shape[1], levels)
    x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
    hit = 0
    for l, lp in enumerate(params["layers"]):
        x = ref.attention(x, {k: lp[k] for k in ref_mod.ATTN_MATS})
        if l < cfg.num_dense_layers:
            x = ref.dense_ffn(x, lp)
        else:
            w = moe_weights(lp)
            xn = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.rms_eps)
            chosen, _ = ref.route(
                xn.reshape(-1, cfg.hidden_size),
                w["router"].astype(jnp.float32), w["router_bias"])
            hit += len(np.unique(np.asarray(chosen)))
            x = ref.moe_ffn(x, w)
    idx = np.tile(np.arange(toks.shape[1]), (toks.shape[0], 1))
    return np.asarray(ref.head(x, jnp.asarray(idx), params["lm_head"])), hit


@pytest.fixture(scope="module")
def toks():
    return np.random.default_rng(0).integers(0, CFG.vocab_size, (2, SEQ)).astype(np.int32)


@pytest.fixture(scope="module")
def want_and_hit(ref_mod, params, toks):
    return reference_logits(ref_mod, params, toks)


@pytest.fixture(scope="module")
def want(want_and_hit):
    return want_and_hit[0]


@pytest.fixture(params=["einsums", "fused_core"])
def attn_core(request, monkeypatch):
    """The prefill softmax core: the einsum body the CPU takes, or
    ``ops/prefill_attention.py``'s kernel in interpret mode over key
    blocks of 16 (the capacity of 64 is four of them, walked as far as
    written), where the chip would run it compiled over blocks of 512."""
    if request.param == "fused_core":
        from tpumlops.ops.prefill_attention import prefill_attention

        monkeypatch.setattr(mla_moe, "KEY_BLOCK", 16)
        monkeypatch.setattr(
            mla_moe, "prefill_attention",
            functools.partial(prefill_attention, interpret=True))
    return request.param


def test_full_forward_logits_equal_the_reference(params, toks, want_and_hit, attn_core):
    want, hit = want_and_hit
    logits, cache, counts = mla_moe.prefill(params, jnp.asarray(toks), CFG, jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), want, atol=2e-6)
    assert int(cache.length) == SEQ
    # The device's count of experts that got a token is the reference's.
    assert 0 < int(counts[0]) == hit <= CFG.num_moe_layers * CFG.n_routed_experts


def test_chunked_prefill_then_ragged_decode_equals_the_full_forward(
        params, toks, want, attn_core):
    """A prompt of 13 tokens in chunks of 8 (the last one padded with ids
    < 0) into the batch-1 scratch, inserted into slot 2 of a 4-slot latent
    cache, then teacher-forced decode steps: the logits at every position
    are the reference's full forward's."""
    row, prompt, chunk = toks[0], 13, 8
    seq = mla_moe.KVCache.create(CFG, 1, jnp.float32)
    got = []
    for start in range(0, prompt, chunk):
        ids = np.full((1, chunk), -1, np.int32)
        n = min(chunk, prompt - start)
        ids[0, :n] = row[start:start + n]
        logits, seq, _ = mla_moe.forward(params, jnp.asarray(ids), seq, CFG, jnp.float32)
        got.append(np.asarray(logits[0, :n]))
    cache = mla_moe.RaggedKVCache.create(CFG, 4, jnp.float32)
    cache = mla_moe.insert_sequence(cache, seq, 2, prompt)
    assert cache.lengths.tolist() == [0, 0, prompt, 0]
    active = jnp.asarray([False, False, True, False])
    for pos in range(prompt, SEQ):
        step = np.zeros((4, 1), np.int32)
        step[2, 0] = row[pos]
        logits, cache, counts = mla_moe.decode_ragged(
            params, jnp.asarray(step), cache, CFG, active=active,
            dtype=jnp.float32, window=32,
        )
        got.append(np.asarray(logits[2]))
        assert 1 <= int(counts[0]) <= CFG.num_moe_layers * CFG.num_experts_per_tok
    np.testing.assert_allclose(np.concatenate(got), want[0], atol=5e-6)
    assert cache.lengths.tolist() == [0, 0, SEQ, 0]


def test_router_choices_and_weights_equal_and_the_bias_decides_some(ref_mod, params):
    x = jax.random.normal(jax.random.key(3), (64, CFG.hidden_size), jnp.float32)
    lp = params["layers"][CFG.num_dense_layers]
    router, bias = lp["router"], lp["router_bias"]
    idx, w = mla_moe.route(x, router, bias, CFG)
    ref = ref_mod.build(geometry(CFG), SEQ)
    idx_ref, w_ref = ref.route(x, router, bias)
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(np.asarray(idx_ref), -1))
    order, order_ref = np.argsort(np.asarray(idx), -1), np.argsort(np.asarray(idx_ref), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, -1),
        np.take_along_axis(np.asarray(w_ref), order_ref, -1), rtol=1e-6)
    # Weights sum to the scaling factor; the bias picks and does not weigh.
    np.testing.assert_allclose(np.asarray(w).sum(-1), CFG.routed_scaling_factor, rtol=1e-5)
    unbiased, _ = mla_moe.route(x, router, jnp.zeros_like(bias), CFG)
    changed = (np.sort(np.asarray(unbiased), -1) != np.sort(np.asarray(idx), -1)).any(-1)
    assert 0 < changed.sum() < len(changed)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_shared_route_under_sigmoid_is_what_it_was(params, scoring):
    """``route`` serves two families since PR 35 (``models/gdn_moe.py``
    asks it for softmax scores and no selection bias).  Under ``sigmoid``
    with a bias it gives, bit for bit, what its body gave before (written
    out here); under ``softmax`` with none, a softmax over every routed
    expert, its top-k, renormalised."""
    from types import SimpleNamespace

    x = jax.random.normal(jax.random.key(4), (64, CFG.hidden_size), jnp.float32)
    lp = params["layers"][CFG.num_dense_layers]
    logits = jnp.matmul(x, lp["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    k = CFG.num_experts_per_tok
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, want_idx = jax.lax.top_k(scores + lp["router_bias"], k)
        cfg, bias, scale = CFG, lp["router_bias"], CFG.routed_scaling_factor
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        _, want_idx = jax.lax.top_k(scores, k)
        cfg = SimpleNamespace(scoring_func="softmax", num_experts_per_tok=k,
                              routed_scaling_factor=1.0)
        bias, scale = None, 1.0
    chosen = jnp.take_along_axis(scores, want_idx, axis=-1)
    want_w = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * scale
    idx, w = mla_moe.route(x, lp["router"], bias, cfg)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(want_w))
    np.testing.assert_allclose(np.asarray(w).sum(-1), scale, rtol=1e-5)


def test_absorbed_decode_equals_expanded_attention(params, toks):
    """`decode_ragged` absorbs W_kvb; `forward` with one token expands
    keys and values from the latent: the same logits."""
    row, prompt = toks[1], 11
    logits, seq, _ = mla_moe.prefill(
        params, jnp.asarray(row[None, :prompt]), CFG, jnp.float32)
    cache = mla_moe.insert_sequence(
        mla_moe.RaggedKVCache.create(CFG, 2, jnp.float32), seq, 1, prompt)
    step = jnp.asarray([[0], [int(row[prompt])]], jnp.int32)
    absorbed, _, _ = mla_moe.decode_ragged(
        params, step, cache, CFG, active=jnp.asarray([False, True]),
        dtype=jnp.float32)
    expanded, _, _ = mla_moe.forward(
        params, jnp.asarray(row[None, prompt:prompt + 1]), seq, CFG, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(absorbed[1, 0]), np.asarray(expanded[0, 0]), atol=5e-6)


def test_padding_rows_change_neither_output_nor_counters(params, toks):
    row = toks[0]
    exact, _, counts_exact = mla_moe.prefill(
        params, jnp.asarray(row[None, :5]), CFG, jnp.float32)
    padded = np.full((1, 16), -1, np.int32)
    padded[0, :5] = row[:5]
    got, _, counts_padded = mla_moe.prefill(params, jnp.asarray(padded), CFG, jnp.float32)
    np.testing.assert_allclose(np.asarray(got[0, :5]), np.asarray(exact[0]), atol=2e-6)
    assert int(counts_padded[0]) == int(counts_exact[0]) <= 5 * 2 * CFG.num_moe_layers
    # Decode: an inactive slot's row is not routed and not written.
    cache = mla_moe.RaggedKVCache.create(CFG, 4, jnp.float32)
    one = jnp.asarray([True, False, False, False])
    toks4 = jnp.asarray([[7], [9], [11], [13]], jnp.int32)
    _, cache1, counts1 = mla_moe.decode_ragged(
        params, toks4, cache, CFG, active=one, dtype=jnp.float32)
    assert int(counts1[0]) <= CFG.num_experts_per_tok * CFG.num_moe_layers
    assert cache1.lengths.tolist() == [1, 0, 0, 0]
    assert not any(np.asarray(buf[1:]).any() for buf in cache1.v["latent"])
    _, _, counts4 = mla_moe.decode_ragged(
        params, toks4, cache, CFG, active=None, dtype=jnp.float32)
    assert int(counts4[0]) > int(counts1[0])


@pytest.fixture
def kernel_interpreted(monkeypatch):
    """Steer ``moe_ffn``'s grouped matmuls to the Pallas kernel, in
    interpret mode, where the CPU would take ``lax.ragged_dot``."""
    from tpumlops.ops.grouped_matmul import grouped_matmul

    monkeypatch.setattr(
        mla_moe, "grouped_matmul", functools.partial(grouped_matmul, interpret=True))
    # ``moe_ffn`` is jitted: a trace made without the patch must not answer
    # for these shapes, nor one made with it for a later test's.
    jax.clear_caches()
    yield
    jax.clear_caches()


def numpy_visits(sizes, tm):
    """(group, row tile) pairs that share a row, from the group sizes."""
    ends = np.cumsum(sizes)
    return sum((e - 1) // tm - (e - n) // tm + 1 for e, n in zip(ends, sizes) if n)


@pytest.mark.parametrize("rows,seq,real", [(3, 16, (16, 11, 0)), (1, 8, (5,)), (4, 1, (1, 0, 1, 1))])
def test_expert_layer_through_the_kernel_equals_the_reference(
        ref_mod, params, kernel_interpreted, rows, seq, real):
    """One expert layer's FFN with its grouped matmuls in the kernel
    against the reference's, padded rows included (a prompt chunk's
    padded tail, an all-padding row, a decode step's idle slot); and the
    program's counts against numpy counts from the same group sizes."""
    from tpumlops.ops.grouped_matmul import row_tile

    lp = params["layers"][CFG.num_dense_layers]
    x = jax.random.normal(jax.random.key(rows * seq), (rows, seq, CFG.hidden_size))
    valid = np.arange(seq)[None, :] < np.asarray(real)[:, None]
    ffn = lambda x, v: mla_moe._ffn(x, lp, v, CFG)
    assert "pallas_call" in str(jax.make_jaxpr(ffn)(x, jnp.asarray(valid)))
    got, counts = jax.jit(ffn)(x, jnp.asarray(valid))
    want = ref_mod.build(geometry(CFG), seq).moe_ffn(x, moe_weights(lp))
    np.testing.assert_allclose(np.asarray(got)[valid], np.asarray(want)[valid], atol=2e-6)
    assert np.isfinite(np.asarray(got)).all()
    xn = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + CFG.rms_eps)
    idx, _ = mla_moe.route(
        xn.reshape(-1, CFG.hidden_size), lp["router"], lp["router_bias"], CFG)
    sizes = np.bincount(
        np.asarray(idx)[valid.reshape(-1)].reshape(-1), minlength=CFG.n_routed_experts)
    copies = rows * seq * CFG.num_experts_per_tok
    tm = row_tile(copies, CFG.n_routed_experts)
    assert tm == mla_moe.moe_row_tile(CFG, rows * seq)
    assert counts.tolist() == [np.count_nonzero(sizes), numpy_visits(sizes, tm), int(sizes.sum())]


def test_programs_through_the_kernel_equal_the_full_forward(
        params, toks, want, kernel_interpreted):
    """Prefill and a teacher-forced ragged decode step with every expert
    matmul in the kernel: the reference's logits at the tolerances the
    fallback meets."""
    prompt = 13
    logits, seq, counts = mla_moe.prefill(
        params, jnp.asarray(toks[:1, :prompt]), CFG, jnp.float32)
    np.testing.assert_allclose(np.asarray(logits[0]), want[0, :prompt], atol=2e-6)
    assert 0 < int(counts[0]) <= int(counts[1])  # an expert hit costs a visit or more
    cache = mla_moe.insert_sequence(
        mla_moe.RaggedKVCache.create(CFG, 2, jnp.float32), seq, 1, prompt)
    step = jnp.asarray([[0], [int(toks[0, prompt])]], jnp.int32)
    logits, _, counts = mla_moe.decode_ragged(
        params, step, cache, CFG, active=jnp.asarray([False, True]),
        dtype=jnp.float32, window=32)
    np.testing.assert_allclose(np.asarray(logits[1, 0]), want[0, prompt], atol=5e-6)
    # One live token: top-2 experts a layer, each one visit of one tile,
    # every assignment landing here; no indexer, so nothing scored or kept.
    fan = CFG.num_experts_per_tok * CFG.num_moe_layers
    assert counts.tolist() == [fan, fan, fan, 0, 0]
    assert len(mla_moe.COUNTS) == 5


def fake_int8(tree):
    """Every matrix rounded to int8 levels per output channel, as the
    benchmark's control does in the reference's place."""
    def q(w):
        if w.ndim < 2:
            return w
        w32 = w.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(w32), axis=-2, keepdims=True), 1e-12) / 127
        return (jnp.clip(jnp.round(w32 / scale), -127, 127) * scale).astype(w.dtype)

    return jax.tree.map(q, tree)  # norms and the router bias are 1-D


def test_bf16_is_within_a_tolerance_that_int8_weights_break(ref_mod, toks):
    """The serving precision (bf16 weights, activations and cache; router
    in float32) against the float32 reference ON THE SAME bf16 WEIGHTS:
    the mean absolute logit error stays under 0.001 (it reads 0.0006:
    bf16 keeps 8 bits of logits ~0.2 in size), and the same program on
    weights rounded to int8 per output channel reads over it (0.0015)."""
    cfg = CFG
    p16 = mla_moe.init(jax.random.key(0), cfg, jnp.bfloat16)
    want, _ = reference_logits(ref_mod, p16, toks)
    got, _, _ = mla_moe.prefill(p16, jnp.asarray(toks), cfg, jnp.bfloat16)
    err = float(np.abs(np.asarray(got, np.float32) - want).mean())
    low, _, _ = mla_moe.prefill(fake_int8(p16), jnp.asarray(toks), cfg, jnp.bfloat16)
    err_int8 = float(np.abs(np.asarray(low, np.float32) - want).mean())
    limit = 0.001
    assert err < limit < err_int8, (err, err_int8)


def test_generate_greedy_feeds_its_own_tokens(params, toks, want):
    out = mla_moe.generate_greedy(params, jnp.asarray(toks[:, :6]), 3, CFG, jnp.float32)
    assert out.shape == (2, 3)
    # The first new token is the reference's argmax after the prompt.
    assert np.array_equal(np.asarray(out[:, 0]), want[:, 5].argmax(-1))


def test_param_counts_at_the_published_widths():
    """ISSUE 27's table: 26,345,472 attention parameters a layer,
    4,718,592 an expert, 1,239,547,904 an expert layer."""
    cfg = mla_moe.MlaMoeConfig(num_layers=5, max_seq=2048)
    active, total = mla_moe.param_counts(cfg)
    attn, expert, head = 26_345_472, 4_718_592, 129280 * 2048
    dense = attn + 3 * 2048 * 7168
    assert dense == 70_385_664
    assert total == dense + 4 * 1_239_547_904 + 2 * head == 5_558_108_160
    assert active == dense + 4 * (attn + 2048 * 256 + 9 * expert) + head
    assert mla_moe.routed_assignments(cfg, 512) == 512 * 8 * 4


@pytest.mark.parametrize("bad", [
    {"first_k_dense_replace": 4}, {"num_experts_per_tok": 9}, {"qk_rope_head_dim": 7},
    {"n_group": 8}, {"topk_group": 4}, {"scoring_func": "softmax"},
    {"norm_topk_prob": False},
])
def test_config_rejects_what_the_layers_cannot_be(bad):
    with pytest.raises(ValueError):
        mla_moe.MlaMoeConfig.tiny(**bad)


def test_the_engine_counts_the_key_blocks_a_chunk_walks_and_skips(params, monkeypatch):
    """``tpumlops_prefill_key_blocks_total`` through the engine: a prompt
    of 27 tokens in chunks of 8 over a capacity of 64 positions in key
    blocks of 16, three full-attention layers.  Chunk n (offset 8 n)
    walks ceil((8 n + 8) / 16) blocks a layer and skips the rest of the
    four: walked + skipped = layers x blocks of the capacity, a chunk."""
    from tpumlops.server.generation import GenerationEngine
    from tpumlops.server.metrics import ServerMetrics

    monkeypatch.setattr(mla_moe, "KEY_BLOCK", 16)
    assert mla_moe.prefill_key_blocks(CFG, 0, 8) == (3 * 1, 3 * 3)
    assert mla_moe.prefill_key_blocks(CFG, 56, 8) == (3 * 4, 0)
    metrics = ServerMetrics(deployment_name="d", predictor_name="p", namespace="n")
    engine = GenerationEngine(
        params, CFG, max_slots=1, dtype=jnp.float32, family=mla_moe,
        prefill_chunk=8, on_key_blocks=metrics.inc_prefill_key_blocks)
    engine.start()
    try:
        prompt = np.arange(27, dtype=np.int32) % CFG.vocab_size
        engine.submit(prompt, max_new_tokens=2).result(timeout=600)
    finally:
        engine.shutdown()
    read = lambda kind: metrics.registry.get_sample_value(
        "tpumlops_prefill_key_blocks_total", dict(metrics.identity, kind=kind))
    walked, skipped = read("walked"), read("skipped")
    chunks = 4  # offsets 0, 8, 16, 24
    assert walked == 3 * (1 + 1 + 2 + 2)
    assert walked + skipped == CFG.num_layers * (CFG.max_seq // 16) * chunks
