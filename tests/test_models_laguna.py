"""The GQA family's window configuration (``models/gdn_moe.py`` with
``layer_types``: sliding-window GQA layers on a ring beside full GQA
layers, query heads by kind, YaRN on part of the head in the full
layers, a headwise gate, a leading dense layer, softmax-routed experts
scaled x2.5 beside an ungated shared expert) against its plain float32
reference (benchmarks/references/laguna_decoder.py: the window a mask over
the whole sequence, YaRN written from its formula) on seeded random
weights, at a tiny size where every mechanism bites: a window of 12 on a
ring of 16 rows, prompts several times the ring, chunks that straddle
the window, a slot that wraps its ring again and again, 6 query heads in
a sliding layer and 4 in a full one over 2 KV heads, a YaRN correction
range inside the rotary pairs, 8 routed experts of which this share
holds 4.  Logits, never tokens, wherever the two can be compared
position by position."""

import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumlops.models import gdn_moe, mla_moe

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
F, S = gdn_moe.FULL, gdn_moe.SLIDING
CFG = gdn_moe.GdnMoeConfig.tiny(
    num_layers=8, layer_types=(F, S, S, S, F, S, S, S), num_heads=4,
    swa_num_heads=6, num_kv_heads=2, head_dim=32, sliding_window=12,
    partial_rotary_factor=0.5, rope_theta=1e4, rope_type="yarn",
    rope_factor=4.0, rope_original_max_position=2048,
    rope_attention_factor=0.1 * math.log(4.0) + 1.0, swa_rope_theta=1e4,
    swa_partial_rotary_factor=1.0, attention_gate="headwise", norm="plain",
    qk_norm=False, shared_expert_gate=False, mlp_only_layers=(0,),
    intermediate_size=96, routed_scaling_factor=2.5, n_local_experts=4,
    local_expert_start=2, max_seq=256)
SEQ = 112
# float32 on both sides and the same equations: what is left is the form
# (key blocks and a ring against a mask over the sequence, the grouped
# matmul against one expert at a time) and the order of the sums, a few
# ulp of logits of size ~0.2 (3e-7 seen).
ATOL = 3e-5


@pytest.fixture(autouse=True)
def small_key_blocks(monkeypatch):
    """Blocks of 16 keys: the capacity of 256 is sixteen of them, so
    prefill takes the blocked path the published size takes (a sliding
    layer's ring and chunk, 16 + 16 keys and more, too)."""
    monkeypatch.setattr(mla_moe, "KEY_BLOCK", 16)
    monkeypatch.setattr(mla_moe, "ONE_PASS", 16)


def _load(name):
    if str(BENCH) not in sys.path:
        sys.path.append(str(BENCH))
    import importlib

    return importlib.import_module(f"references.{name}")


@pytest.fixture(scope="module")
def ref_mod():
    return _load("laguna_decoder")


def geometry(cfg):
    """The artifact's config: the reference's keys (it reads no more)."""
    return dataclasses.asdict(cfg)


@pytest.fixture(scope="module")
def params():
    p = gdn_moe.init(jax.random.key(3), CFG, jnp.float32)
    # Norm weights that are not the identity, so the plain norm is checked.
    for l, lp in enumerate(p["layers"]):
        for name in ("attn_norm", "ffn_norm"):
            lp[name] = 1.0 + 0.1 * jax.random.normal(jax.random.key(40 + l), (64,))
    return p


def layer_weights(lp):
    """A layer's leaves under the reference's flat names: (attention, ffn)."""
    attn = {k: lp[k] for k in ("q", "k", "v", "attn_gate", "o", "attn_norm")}
    ffn = {k: v for k, v in lp.items() if k not in attn and k != "experts"}
    return attn, {**ffn, **lp.get("experts", {})}


def reference_logits(ref_mod, params, toks, cfg=CFG, **controls):
    """The reference's full forward over rows ``toks`` [R, S]: logits at
    every position, from the program's own tree (same leaf names)."""
    rows, seq = toks.shape
    ref = ref_mod.build(geometry(cfg), seq, **controls)
    x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
    for l, (kind, lp) in enumerate(zip(cfg.kinds, params["layers"])):
        attn, ffn = layer_weights(lp)
        x = ref.attention[kind](x, attn)
        x = ref.mlp(x, ffn) if l in cfg.mlp_only_layers else ref.moe_ffn(x, ffn)
    idx = np.tile(np.arange(seq), (rows, 1))
    return np.asarray(ref.head(x, jnp.asarray(idx), params["final_norm"],
                               params["lm_head"]))


@pytest.fixture(scope="module")
def toks():
    return np.random.default_rng(1).integers(0, CFG.vocab_size, (2, SEQ)).astype(np.int32)


@pytest.fixture(scope="module")
def want(ref_mod, params, toks):
    with jax.default_matmul_precision("highest"):
        return reference_logits(ref_mod, params, toks)


def test_the_config_reads_the_table_and_refuses_what_it_does_not_implement():
    tiny = gdn_moe.GdnMoeConfig.tiny
    assert CFG.kinds == CFG.layer_types and CFG.sliding_layers == (1, 2, 3, 5, 6, 7)
    assert CFG.full_layers == (0, 4) and CFG.linear_layers == ()
    assert CFG.attention_kinds == (F, S) and CFG.ring_rows == 16
    assert CFG.num_moe_layers == 7
    view = CFG.view(S)
    assert (view.num_heads, view.rotary_dim, view.rope_type) == (6, 32, "default")
    assert CFG.view(F) is CFG and CFG.rotary_dim == 16
    with pytest.raises(ValueError, match="once a layer"):
        tiny(layer_types=(F, S))
    with pytest.raises(ValueError, match="unknown kinds"):
        tiny(num_layers=2, layer_types=(F, "chunked_attention"))
    with pytest.raises(ValueError, match="need a sliding_window"):
        tiny(num_layers=2, layer_types=(F, S))
    with pytest.raises(ValueError, match="multiple of KV heads"):
        tiny(num_layers=2, layer_types=(F, S), sliding_window=4, swa_num_heads=3)
    with pytest.raises(ValueError, match="attention_gate"):
        tiny(attention_gate="per_token")
    with pytest.raises(ValueError, match="norm="):
        tiny(norm="layer")
    with pytest.raises(ValueError, match="yarn"):
        tiny(rope_type="yarn")
    with pytest.raises(ValueError, match="mlp_only_layers"):
        tiny(mlp_only_layers=(0,))
    with pytest.raises(ValueError, match="routed_scaling_factor"):
        tiny(routed_scaling_factor=0.0)
    # An artifact's JSON lists come back as the config's tuples.
    assert tiny(num_layers=2, layer_types=[F, F], mlp_only_layers=[0],
                intermediate_size=8).mlp_only_layers == (0,)


def test_yarn_frequencies_are_the_formula_at_the_published_widths(ref_mod):
    """At the published full layer (64 of 128 dims rotated, theta 5e5,
    factor 128 over an original 8192, betas 32 and 1) the correction range
    is [9, 18] (9.04 and 17.49 before rounding), and the program's
    frequencies are the formula's, in program and reference alike; the
    sliding kind keeps plain RoPE on all 128 dims."""
    import json

    model = json.loads((BENCH / "configs" / "laguna-s-2.1-bf16.json").read_text())["model"]
    g = ref_mod.geometry(model)
    cfg = gdn_moe.GdnMoeConfig(**g)
    c = lambda beta: 64 * math.log(8192 / (2 * math.pi * beta)) / (2 * math.log(5e5))
    assert round(c(32), 2) == 9.04 and round(c(1), 2) == 17.49
    assert gdn_moe.yarn_correction_range(cfg) == ref_mod.yarn_range(g) == (9, 18)
    i = np.arange(32)
    base = 5e5 ** (-2.0 * i / 64)
    r = 1.0 - np.clip((i - 9) / (18 - 9), 0.0, 1.0)
    formula = (1.0 - r) * base / 128 + r * base
    np.testing.assert_allclose(gdn_moe.yarn_inv_freq(cfg), formula, rtol=1e-6)
    freq, amp = ref_mod.inv_freq(g, F)
    np.testing.assert_allclose(freq, formula, rtol=1e-12)
    assert amp == pytest.approx(0.1 * math.log(128) + 1, rel=1e-6)
    # The first nine pairs keep their frequency, pairs 18 on are divided
    # by 128, the blend between depends on no position.
    np.testing.assert_allclose(formula[:10], base[:10])
    np.testing.assert_allclose(formula[18:], base[18:] / 128)
    cos, _sin = gdn_moe.rope_cos_sin(jnp.asarray([0, 4096]), cfg)
    np.testing.assert_allclose(np.asarray(cos[0]), 1.4852030263919618, rtol=1e-6)
    slide = cfg.view(S)
    assert (slide.rotary_dim, slide.rope_theta, slide.num_heads) == (128, 1e4, 72)
    np.testing.assert_allclose(ref_mod.inv_freq(g, S)[0],
                               1e4 ** (-np.arange(0, 128, 2) / 128))


def test_full_forward_logits_equal_the_reference(params, toks, want):
    """One prefill of 112 positions: the sliding layers attend a ring
    that is still empty (negative positions masked) and the chunk's own
    rows under the window mask, the full layers seven key blocks of
    sixteen."""
    logits, cache, counts = gdn_moe.prefill(params, jnp.asarray(toks), CFG, jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), want, atol=ATOL)
    assert int(cache.length) == SEQ
    assert counts[3:].tolist() == [0, 0]  # no recurrent state
    landed, every = int(counts[2]), gdn_moe.routed_assignments(CFG, 2 * SEQ)
    assert every == 2 * SEQ * 2 * 7  # top-2, seven expert layers
    assert 0.25 * every < landed < 0.75 * every  # 4 of 8 held


@pytest.mark.parametrize("chunk", [8, 20, 64])
def test_chunked_prefill_then_ragged_decode_equals_the_full_forward(
        params, toks, want, chunk):
    """A prompt of 47 tokens (three rings) in chunks shorter than the
    window, longer than the ring, and longer than the prompt, the last one
    padded with ids < 0 that no ring may take in, into the batch-1
    scratch, inserted into slot 1 of a 3-slot cache, then 40 single-token
    steps, so the slot's ring wraps twice more: every logit is the full
    forward's at that position."""
    prompt, steps = 47, 40
    row = toks[0]
    seq = gdn_moe.KVCache.create(CFG, 1, jnp.float32)
    for at in range(0, prompt, chunk):
        ids = np.full((1, chunk), gdn_moe.PAD_ID, np.int32)
        n = min(chunk, prompt - at)
        ids[0, :n] = row[at:at + n]
        logits, seq, _ = gdn_moe.forward(params, jnp.asarray(ids), seq, CFG, jnp.float32)
        np.testing.assert_allclose(
            np.asarray(logits[0, :n]), want[0, at:at + n], atol=ATOL)
    cache = gdn_moe.insert_sequence(
        gdn_moe.RaggedKVCache.create(CFG, 3, jnp.float32), seq, 1, prompt)
    live = jnp.asarray([False, True, False])
    for t in range(prompt, prompt + steps):
        step = jnp.asarray([[0], [int(row[t])], [0]], jnp.int32)
        logits, cache, _ = gdn_moe.decode_ragged(
            params, step, cache, CFG, active=live, dtype=jnp.float32, window=128)
        np.testing.assert_allclose(np.asarray(logits[1, 0]), want[0, t], atol=ATOL)
    assert cache.lengths.tolist() == [0, prompt + steps, 0]
    # Idle slots were never written, in any buffer of either kind.
    for buf in jax.tree.leaves((cache.k, cache.v)):
        assert not np.asarray(buf[0]).any() and not np.asarray(buf[2]).any()


def test_chunked_prefill_through_the_fused_core_equals_the_reference(
        params, toks, want, monkeypatch):
    """The TPU's prefill core (``ops/gqa_prefill_attention.py``, interpret
    mode) in every attention layer of a prompt of 47 in chunks of 16: a
    sliding call takes the ring's 16 rows in position order and the
    chunk's 16, two whole key blocks as the published 512 and 512 make
    (the oldest row outside every window), a full one the blocks written
    so far; every logit is the float32 reference's."""
    from tpumlops.ops import gqa_prefill_attention as ga

    calls = []

    def core(q, keys, values, *a, window, **kw):
        calls.append((keys.shape[1], window))
        return ga.gqa_prefill_attention(q, keys, values, *a, window=window,
                                        interpret=True, **kw)

    monkeypatch.setattr(gdn_moe, "gqa_prefill_attention", core)
    prompt, chunk = 47, 16
    seq = gdn_moe.KVCache.create(CFG, 1, jnp.float32)
    for at in range(0, prompt, chunk):
        ids = np.full((1, chunk), gdn_moe.PAD_ID, np.int32)
        n = min(chunk, prompt - at)
        ids[0, :n] = toks[0, at:at + n]
        logits, seq, _ = gdn_moe.forward(params, jnp.asarray(ids), seq, CFG, jnp.float32)
        np.testing.assert_allclose(
            np.asarray(logits[0, :n]), want[0, at:at + n], atol=ATOL)
    assert calls == 3 * (2 * ([(256, 0)] + 3 * [(32, 12)]))  # F S S S F S S S a chunk
    assert gdn_moe.attn_pairs(CFG, "prefill", [0], [16], 16)[S][0] == 6 * 16 * 32


def test_padding_leaves_the_ring_alone(params, toks):
    """A chunk's padding rows take no ring row: the ring after a prompt
    of 13 in a padded chunk of 32 is the ring after the same 13 unpadded,
    and its rows are where their positions put them."""
    ids = jnp.asarray(toks[:1, :13])
    padded = jnp.concatenate([ids, jnp.full((1, 19), gdn_moe.PAD_ID, jnp.int32)], 1)
    _, plain, _ = gdn_moe.forward(params, ids, gdn_moe.KVCache.create(CFG, 1, jnp.float32),
                                  CFG, jnp.float32)
    _, pad, _ = gdn_moe.forward(params, padded, gdn_moe.KVCache.create(CFG, 1, jnp.float32),
                                CFG, jnp.float32)
    # (The same rows up to the order of the projection's sums: a call of
    # 32 rows tiles them otherwise than one of 13.)
    for bufs in ((plain.k["ring_key"], pad.k["ring_key"]),
                 (plain.v["ring_value"], pad.v["ring_value"])):
        for a, b in zip(*bufs):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
            # 13 rows written at positions 0-12, rows 13-15 of the ring empty.
            assert np.asarray(a[0, :13]).any(axis=-1).all()
            assert not np.asarray(a[0, 13:]).any()


def test_a_slot_that_sits_idle_through_steps_keeps_its_ring(params, toks):
    """Slot 0 holds a prompt's rows and ring and then sits out twenty
    steps that slot 2 takes (its own ring wrapping past slot 0's length):
    every buffer row of slot 0 and its length are bit for bit what they
    were, and its next step gives the logits it would have given at
    once."""
    prompt = 30
    seq = gdn_moe.KVCache.create(CFG, 1, jnp.float32)
    _, seq, _ = gdn_moe.forward(
        params, jnp.asarray(toks[:1, :prompt]), seq, CFG, jnp.float32)
    cache = gdn_moe.RaggedKVCache.create(CFG, 3, jnp.float32)
    cache = gdn_moe.insert_sequence(cache, seq, 0, prompt)
    cache = gdn_moe.insert_sequence(cache, seq, 2, prompt)
    step = lambda cache, live, tok: gdn_moe.decode_ragged(
        params, jnp.full((3, 1), tok, jnp.int32), cache, CFG,
        active=jnp.asarray(live), dtype=jnp.float32, window=64)
    at_once, _, _ = step(cache, [True, False, False], 7)
    held = [np.asarray(buf[0]) for buf in jax.tree.leaves((cache.k, cache.v))]
    for t in range(20):
        _, cache, _ = step(cache, [False, False, True], 11 + t)
    for before, buf in zip(held, jax.tree.leaves((cache.k, cache.v))):
        np.testing.assert_array_equal(before, np.asarray(buf[0]))
    assert cache.lengths.tolist() == [prompt, 0, prompt + 20]
    later, _, _ = step(cache, [True, False, False], 7)
    np.testing.assert_array_equal(np.asarray(later[0]), np.asarray(at_once[0]))


@pytest.mark.parametrize("control", ["window", "yarn"])
def test_the_window_and_yarn_move_the_logits(ref_mod, params, toks, want, control):
    """The two mechanisms this configuration adds are seen by the
    comparison: the reference with the window dropped (sliding layers
    attending every earlier position) and with plain RoPE in the full
    layers each leave the served logits by far more than the tolerance
    every other test holds them to, and by nothing before the window
    first bites (the window: position 12)."""
    with jax.default_matmul_precision("highest"):
        other = reference_logits(ref_mod, params, toks, **{control: False})
    moved = np.abs(other - want).max(-1)
    assert moved.max() > 300 * ATOL
    if control == "window":
        assert moved[:, :12].max() < ATOL


def test_the_int8_control_rounds_both_operands_of_every_weight_product(ref_mod, params):
    """The reference's precision control is computed as an int8 matrix
    unit takes its operands: every weight matrix rounded to 127 levels a
    side per output channel AND the activation it multiplies rounded per
    row; `weights_only` rounds the matrices alone.  Pinned at the head,
    then at the dense layer's SwiGLU: the two controls and the plain
    reference are three different layers."""
    fq = ref_mod._fake_quant
    x = jax.random.normal(jax.random.key(5), (3, 64)) * jnp.array([[1.0], [0.01], [50.0]])
    rows = fq(jnp, x, 127, axis=-1)
    scale = jnp.abs(x).max(-1, keepdims=True) / 127
    np.testing.assert_allclose(rows / scale, jnp.round(rows / scale), atol=1e-4)
    np.testing.assert_allclose(jnp.abs(rows).max(-1), jnp.abs(x).max(-1), rtol=1e-6)
    assert float(jnp.abs(rows - x).max(-1)[1]) < 0.01 * float(jnp.abs(rows - x).max(-1)[0])

    h = jax.random.normal(jax.random.key(6), (1, 4, 64))
    idx = jnp.asarray([[1, 3]])
    norm, head = params["final_norm"], params["lm_head"]
    with jax.default_matmul_precision("highest"):
        got = ref_mod.build(geometry(CFG), 4, levels=127).head(h, idx, norm, head)
        picked = h[:, [1, 3]]
        normed = picked * jax.lax.rsqrt(jnp.mean(picked * picked, -1, keepdims=True)
                                        + CFG.rms_eps) * norm
        by_hand = fq(jnp, normed, 127, axis=-1) @ fq(jnp, head, 127)
        np.testing.assert_allclose(np.asarray(got), np.asarray(by_hand), atol=1e-6)
        _attn, ffn = layer_weights(params["layers"][0])
        out = {kw: np.asarray(ref_mod.build(geometry(CFG), 4, **dict(kw)).mlp(h, ffn))
               for kw in ((), (("levels", 127),),
                          (("levels", 127), ("weights_only", True)))}
    plain, w8a8, w8 = out.values()
    # One layer beside its residual: the rounding moves it by ~1e-4, far
    # more than float32's 1e-7 on the same equations.
    for a, b in ((w8a8, plain), (w8, plain), (w8a8, w8)):
        assert np.abs(a - b).max() > 1e-5


def test_heads_by_kind_and_the_headwise_gate(params):
    """A full layer of 4 query heads and a sliding one of 6 over the same 2
    KV heads: the projections have their kind's widths and the gate one
    number a head; heads 0-1 / 0-2 read KV head 0 and the rest KV head 1."""
    full, slide = params["layers"][0], params["layers"][1]
    assert full["q"].shape == (64, 4 * 32) and full["o"].shape == (4 * 32, 64)
    assert slide["q"].shape == (64, 6 * 32) and slide["o"].shape == (6 * 32, 64)
    assert full["k"].shape == slide["k"].shape == (64, 2 * 32)
    assert full["attn_gate"].shape == (64, 4) and slide["attn_gate"].shape == (64, 6)
    assert "q_norm" not in full and "shared_expert_gate" not in params["layers"][1]
    assert {"gate", "up", "down"} <= set(full) and "router" not in full
    active, total = gdn_moe.param_counts(CFG)
    leaves = sum(x.size for x in jax.tree.leaves(params))
    assert total == leaves - (8 * 2 * 64 + 64)  # the norms
    attn = lambda nh: 64 * nh * 32 * 2 + 64 * nh + 2 * 64 * 64
    moe = 64 * 8 + 3 * 64 * 32  # router, ungated shared expert
    assert active == (2 * attn(4) + 6 * attn(6) + 3 * 64 * 96 + 64 * 256
                      + 7 * (moe + 3 * 64 * 32 * (2 * 4 // 8)))


@pytest.mark.parametrize("gate", ["headwise", "elementwise"])
def test_each_gate_against_its_reference(gate):
    """The attention sub-layer with each gate against the reference that
    has it: the headwise gate (one sigmoid a head, from the normed input)
    against ``laguna_decoder``'s sliding layer, the elementwise gate (one
    sigmoid a number, the query projection's other half, with q/k norms
    and zero-centred norms) against ``qwen3_next_decoder``'s full layer."""
    seq = 24
    x = jax.random.normal(jax.random.key(5), (2, seq, 64))
    if gate == "headwise":
        cfg, kind, ref = CFG, S, _load("laguna_decoder")
    else:
        cfg = gdn_moe.GdnMoeConfig.tiny(num_layers=4, n_local_experts=4)
        kind, ref = F, _load("qwen3_next_decoder")
    lp = gdn_moe.init(jax.random.key(6), cfg, jnp.float32)["layers"][cfg.kinds.index(kind)]
    c = cfg.view(kind)
    positions = jnp.arange(seq)
    cos, sin = gdn_moe.rope_cos_sin(positions[None], c)
    xn = gdn_moe._norm(x, lp["attn_norm"], cfg)
    q, g, k, v = gdn_moe._attn_qkv(xn, lp, cos, sin, c)
    if kind == S:
        # An empty ring (positions -ring .. -1) in front of the chunk.
        pad = jnp.zeros((2, cfg.ring_rows, k.shape[-1]))
        ctx = gdn_moe._gqa_blocks(q, jnp.concatenate([pad, k], 1), jnp.concatenate([pad, v], 1),
                                  0, cfg.ring_rows + seq, key_start=-cfg.ring_rows,
                                  window=cfg.sliding_window)
    else:
        cache = jnp.zeros((2, cfg.max_seq, k.shape[-1]))
        ctx = gdn_moe._gqa_blocks(q, cache.at[:, :seq].set(k), cache.at[:, :seq].set(v),
                                  0, seq)
    got = gdn_moe._attn_out(x, ctx, g, xn, lp, c)
    with jax.default_matmul_precision("highest"):
        built = ref.build(geometry(cfg), seq)
        if gate == "headwise":
            attn, _ = layer_weights(lp)
            want = built.attention[kind](x, attn)
        else:
            want = built.attention(x, {k_: v_ for k_, v_ in lp.items() if k_ in (
                "q", "k", "v", "o", "attn_norm", "q_norm", "k_norm")})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


@pytest.mark.parametrize("held", [2, 4])
def test_the_shares_add_up_to_the_uncut_expert_layer(ref_mod, params, held):
    """The share test: the shares of two experts each (and of
    four), the ungated shared expert counted once, add up to what the
    uncut layer (every expert held) gives, the routing weights scaled by
    2.5; program and reference, share by share."""
    whole = dataclasses.replace(CFG, n_local_experts=0, local_expert_start=0)
    lp = gdn_moe.init(jax.random.key(7), whole, jnp.float32)["layers"][1]
    x = jax.random.normal(jax.random.key(11), (2, 12, CFG.hidden_size))
    valid = jnp.ones((2, 12), bool)
    _, ffn = layer_weights(lp)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref_mod.build(geometry(dataclasses.replace(
            whole, n_local_experts=8)), 12).moe_ffn(x, ffn)) - np.asarray(x)
    got_whole, _ = gdn_moe._ffn(x, lp, valid, whole)
    np.testing.assert_allclose(np.asarray(got_whole - x), want, atol=ATOL)
    xn = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + CFG.rms_eps) * lp["ffn_norm"]
    shared = np.asarray((jax.nn.silu(xn @ lp["shared_gate"]) * (xn @ lp["shared_up"]))
                        @ lp["shared_down"])
    total_prog, total_ref = shared.copy(), shared.copy()
    for start in range(0, 8, held):
        share = dataclasses.replace(CFG, n_local_experts=held, local_expert_start=start)
        part = {**lp, "experts": {k: v[start:start + held] for k, v in lp["experts"].items()}}
        got, _counts = gdn_moe._ffn(x, part, valid, share)
        _, part_ffn = layer_weights(part)
        with jax.default_matmul_precision("highest"):
            ref_part = np.asarray(ref_mod.build(geometry(share), 12).moe_ffn(x, part_ffn))
        np.testing.assert_allclose(np.asarray(got), ref_part, atol=ATOL)
        total_prog += np.asarray(got) - np.asarray(x) - shared
        total_ref += ref_part - np.asarray(x) - shared
    np.testing.assert_allclose(total_ref, want, atol=ATOL)
    np.testing.assert_allclose(total_prog, want, atol=ATOL)
    # The routed part is scaled: the same layer at a factor of 1 adds 1/2.5
    # of what it routes.
    one = dataclasses.replace(whole, routed_scaling_factor=1.0)
    got_one, _ = gdn_moe._ffn(x, lp, valid, one)
    np.testing.assert_allclose(np.asarray(got_one - x) - shared,
                               (want - shared) / 2.5, atol=ATOL)


def test_the_pairs_the_cores_multiply_and_the_masks_keep():
    """``attn_pairs``: host arithmetic of what the cores multiply for real
    queries and what the causal and window masks keep, by kind, a pair a
    layer."""
    # A chunk of 8 slots, 5 real, from position 20: a full layer walks
    # ceil(28 / 16) = 2 blocks of 16 keys; a sliding one the ring's 16
    # rows and the 8 slots.  Attended: positions 20-24 see 21-25 keys / 12.
    pairs = gdn_moe.attn_pairs(CFG, "prefill", [20], [5], 8)
    # (the capacity 256 is no more than ONE_PASS = 16 blocks of 16 here:
    # blocks of 16, as the fixture makes them)
    assert pairs == {F: (2 * 5 * 32, 2 * (21 + 22 + 23 + 24 + 25)),
                     S: (6 * 5 * 24, 6 * 5 * 12)}
    # From position 0 the window has not begun to bite.
    assert gdn_moe.attn_pairs(CFG, "prefill", [0], [8], 8)[S] == (6 * 8 * 24, 6 * 36)
    # Straddling it: positions 8-15 see 9, 10, 11, 12, 12, 12, 12, 12.
    assert gdn_moe.attn_pairs(CFG, "prefill", [8], [8], 8)[S][1] == 6 * (9 + 10 + 11 + 5 * 12)
    # A step of two live rows at positions 3 and 40 in a window of 64: the
    # full layers read 64 + 1 keys a row, the sliding ones the ring of 16
    # and the position in flight.
    assert gdn_moe.attn_pairs(CFG, "decode", [3, 40], [1, 1], 64) == {
        F: (2 * 2 * 65, 2 * (4 + 41)), S: (6 * 2 * 17, 6 * (4 + 12))}
    # Qwen3-Next's configuration has no sliding kind.
    qwen = gdn_moe.GdnMoeConfig.tiny()
    assert set(gdn_moe.attn_pairs(qwen, "decode", [3], [1], 8)) == {F}
