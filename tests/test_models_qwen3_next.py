"""The linear-attention family (``models/gdn_moe.py``: Gated DeltaNet
layers whose recurrent state lives beside a GQA cache, a gated
full-attention layer every fourth, softmax-routed experts of which a
share is held, a gated shared expert) against its plain float32
reference (benchmarks/references/qwen3_next_decoder.py: the recurrence
token by token) on seeded random weights, at a tiny size where every
mechanism bites: sequences of 150 and more against sub-chunks of 64,
decays of 0.5-0.999 a token so a state from 100 tokens back still
weighs, key blocks of 16 so a prefill chunk walks a dynamic number of
them, 8 routed experts of which this share holds 2.  Logits, never
tokens, wherever the two can be compared position by position."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumlops.models import gdn_moe, mla_moe
from tpumlops.server.generation import GenerationEngine

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
CFG = gdn_moe.GdnMoeConfig.tiny(
    num_layers=8, max_seq=256, n_local_experts=2, local_expert_start=2)
SEQ = 176
# float32 on both sides and the same equations: what is left is the form
# (the chunked rule against the recurrence, key blocks, the grouped
# matmul) and the order of the sums, a few ulp of logits of size ~1.
ATOL = 3e-5


@pytest.fixture(autouse=True)
def small_key_blocks(monkeypatch):
    """Blocks of 16 keys: the capacity of 256 is sixteen of them, so
    prefill takes the blocked path the published size takes."""
    monkeypatch.setattr(mla_moe, "KEY_BLOCK", 16)
    monkeypatch.setattr(mla_moe, "ONE_PASS", 16)


def _load(name):
    if str(BENCH) not in sys.path:
        sys.path.append(str(BENCH))
    import importlib

    return importlib.import_module(f"references.{name}")


@pytest.fixture(scope="module")
def ref_mod():
    return _load("qwen3_next_decoder")


def geometry(cfg):
    """The artifact's config: the reference's keys (it reads no more)."""
    return dataclasses.asdict(cfg)


@pytest.fixture(scope="module")
def params():
    p = gdn_moe.init(jax.random.key(3), CFG, jnp.float32)
    # A gated norm's weight and a dt_bias that are not the identity, so
    # both are checked.
    for l in CFG.linear_layers:
        lp = p["layers"][l]
        lp["gdn_norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.key(40 + l), (16,))
        lp["dt_bias"] = 0.2 * jax.random.normal(jax.random.key(50 + l), (4,))
    return p


def layer_weights(lp):
    """A layer's leaves under the reference's flat names, split as its
    forward pass takes them: (mixer, ffn)."""
    ffn_keys = {"router", "shared_gate", "shared_up", "shared_down",
                "shared_expert_gate", "ffn_norm", "experts"}
    mixer = {k: v for k, v in lp.items() if k not in ffn_keys}
    ffn = {**{k: v for k, v in lp.items() if k in ffn_keys and k != "experts"},
           **lp["experts"]}
    return mixer, ffn


def reference_logits(ref_mod, params, toks, cfg=CFG, drop_at=None):
    """The reference's full forward over rows ``toks`` [R, S]: logits at
    every position, from the program's own tree (same leaf names)."""
    rows, seq = toks.shape
    ref = ref_mod.build(geometry(cfg), seq)
    drop = jnp.full((rows,), seq, jnp.int32) if drop_at is None else jnp.asarray(drop_at)
    x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
    for kind, lp in zip(cfg.kinds, params["layers"]):
        mixer, ffn = layer_weights(lp)
        x = (ref.attention(x, mixer) if kind == gdn_moe.FULL
             else ref.linear(x, mixer, drop))
        x = ref.moe_ffn(x, ffn)
    idx = np.tile(np.arange(seq), (rows, 1))
    return np.asarray(ref.head(x, jnp.asarray(idx), params["final_norm"],
                               params["lm_head"]))


@pytest.fixture(scope="module")
def toks():
    return np.random.default_rng(1).integers(0, CFG.vocab_size, (2, SEQ)).astype(np.int32)


@pytest.fixture(scope="module")
def want(ref_mod, params, toks):
    return reference_logits(ref_mod, params, toks)


def test_the_config_refuses_what_it_does_not_implement():
    tiny = gdn_moe.GdnMoeConfig.tiny
    assert CFG.kinds == (gdn_moe.LINEAR,) * 3 + (gdn_moe.FULL,) + (
        gdn_moe.LINEAR,) * 3 + (gdn_moe.FULL,)
    with pytest.raises(ValueError, match="no full-attention layer"):
        tiny(num_layers=3)
    with pytest.raises(ValueError, match="sigmoid router scores"):
        tiny(scoring_func="sigmoid")
    with pytest.raises(ValueError, match="un-normalised"):
        tiny(norm_topk_prob=False)
    with pytest.raises(ValueError, match="expert share"):
        tiny(n_local_experts=4, local_expert_start=6)
    with pytest.raises(ValueError, match="RoPE rotates pairs"):
        tiny(head_dim=6, partial_rotary_factor=0.5)
    with pytest.raises(ValueError, match="multiple of KV heads"):
        tiny(num_heads=3)
    # The sparse family's own config still refuses softmax scores: no
    # reference of that block with them exists.
    with pytest.raises(ValueError, match="softmax router scores"):
        mla_moe.MlaMoeConfig.tiny(scoring_func="softmax")


def test_an_artifact_of_another_variant_fails_to_load():
    from tpumlops.server import loader

    assert loader._build_config(gdn_moe.FLAVOR, {}) == gdn_moe.GdnMoeConfig()
    with pytest.raises(ValueError, match="does not know.*mtp_layers"):
        loader._build_config(gdn_moe.FLAVOR, {"mtp_layers": 1})


def test_full_forward_logits_equal_the_reference(params, toks, want):
    """One prefill of 176 positions: three sub-chunks of 64 (the last
    padded) against the recurrence token by token, eleven key blocks
    walked of sixteen."""
    logits, cache, counts = gdn_moe.prefill(params, jnp.asarray(toks), CFG, jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), want, atol=ATOL)
    assert int(cache.length) == SEQ
    # 2 rows x 176 tokens x 6 linear layers folded in, a pass a row a layer.
    assert counts[3:].tolist() == [2 * SEQ * 6, 2 * 6]
    # The share: of 2 x 176 x top-2 x 8 layers assignments, those to
    # experts 2 and 3 landed here (near a quarter under seeded weights).
    landed, every = int(counts[2]), gdn_moe.routed_assignments(CFG, 2 * SEQ)
    assert 0.1 * every < landed < 0.45 * every
    assert 0 < int(counts[0]) <= 2 * CFG.num_moe_layers


@pytest.mark.parametrize("chunk", [8, 64, 80, 128])
def test_chunked_prefill_then_ragged_decode_equals_the_full_forward(
        params, toks, want, chunk):
    """A prompt of 147 tokens in chunks that are and are not multiples of
    the rule's 64 (the last one padded with ids < 0: 5, 45, 13 and 109
    slots that no state and no convolution tail may take in) into the
    batch-1 scratch, inserted into slot 1 of a 3-slot cache, then 16
    single-token steps: every step's logits are the full forward's at
    that position."""
    prompt, steps = 147, 16
    row = toks[0]
    seq = gdn_moe.KVCache.create(CFG, 1, jnp.float32)
    for at in range(0, prompt, chunk):
        ids = np.full((1, chunk), gdn_moe.PAD_ID, np.int32)
        n = min(chunk, prompt - at)
        ids[0, :n] = row[at:at + n]
        logits, seq, counts = gdn_moe.forward(
            params, jnp.asarray(ids), seq, CFG, jnp.float32)
        np.testing.assert_allclose(
            np.asarray(logits[0, :n]), want[0, at:at + n], atol=ATOL)
        assert counts[3:].tolist() == [6 * n, 6]
    cache = gdn_moe.insert_sequence(
        gdn_moe.RaggedKVCache.create(CFG, 3, jnp.float32), seq, 1, prompt)
    live = jnp.asarray([False, True, False])
    for t in range(prompt, prompt + steps):
        step = jnp.asarray([[0], [int(row[t])], [0]], jnp.int32)
        logits, cache, counts = gdn_moe.decode_ragged(
            params, step, cache, CFG, active=live, dtype=jnp.float32, window=192)
        np.testing.assert_allclose(np.asarray(logits[1, 0]), want[0, t], atol=ATOL)
        assert counts[3:].tolist() == [6, 6]
    assert cache.lengths.tolist() == [0, prompt + steps, 0]
    # Idle slots were never written, in any buffer of either kind.
    for buf in jax.tree.leaves((cache.k, cache.v)):
        assert not np.asarray(buf[0]).any() and not np.asarray(buf[2]).any()


def test_the_chunked_rule_equals_the_recurrence_on_a_state_that_has_not_decayed():
    """``_delta_chunks`` against ``_delta_step`` token by token, from a
    state that is not zero, with decays so near 1 (``exp(g)`` 0.99-1.0)
    that the incoming state is still most of what the last token reads,
    and with rows of ``beta = g = 0`` in the middle (no-ops in both)."""
    b, s, h, dk, dv = 2, 150, 3, 16, 8
    keys = jax.random.split(jax.random.key(0), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (b, s, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (b, s, h, dk)))
    v = jax.random.normal(keys[2], (b, s, h, dv))
    g = -0.01 * jax.random.uniform(keys[3], (b, s, h))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, s, h)))
    idle = (jnp.arange(s) % 7 == 3)[None, :, None]
    g, beta = jnp.where(idle, 0.0, g), jnp.where(idle, 0.0, beta)
    state0 = jax.random.normal(keys[5], (b, h, dk, dv))
    got, got_state = gdn_moe._delta_chunks(q, k, v, g, beta, state0)
    state, outs = state0, []
    for t in range(s):
        o, state = gdn_moe._delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                       beta[:, t], state)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(got), np.stack(outs, 1), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(state), atol=2e-5)
    # The incoming state still weighs at the end (the delta rule has
    # rewritten most of its 16 key directions by then, the decay nothing):
    # without it the last token reads something else, by a thousand times
    # the tolerance.
    cold, _ = gdn_moe._delta_chunks(q, k, v, g, beta, jnp.zeros_like(state0))
    assert np.abs(np.asarray(cold[:, -1] - got[:, -1])).max() > 5e-3


def test_a_slot_that_sits_idle_through_steps_keeps_its_state(params, toks):
    """Slot 0 holds a prompt's state and then sits out eight steps that
    slot 2 takes: its state, its convolution tail, its rows and its
    length are bit for bit what they were, and its next step gives the
    logits it would have given at once."""
    prompt = 70
    seq = gdn_moe.KVCache.create(CFG, 1, jnp.float32)
    _, seq, _ = gdn_moe.forward(
        params, jnp.asarray(toks[:1, :prompt]), seq, CFG, jnp.float32)
    cache = gdn_moe.RaggedKVCache.create(CFG, 3, jnp.float32)
    cache = gdn_moe.insert_sequence(cache, seq, 0, prompt)
    cache = gdn_moe.insert_sequence(cache, seq, 2, prompt)
    step = lambda cache, live, tok: gdn_moe.decode_ragged(
        params, jnp.full((3, 1), tok, jnp.int32), cache, CFG,
        active=jnp.asarray(live), dtype=jnp.float32, window=128)
    at_once, _, _ = step(cache, [True, False, False], 7)
    held = [np.asarray(buf[0]) for buf in jax.tree.leaves((cache.k, cache.v))]
    for t in range(8):
        _, cache, _ = step(cache, [False, False, True], 11 + t)
    for before, buf in zip(held, jax.tree.leaves((cache.k, cache.v))):
        np.testing.assert_array_equal(before, np.asarray(buf[0]))
    assert cache.lengths.tolist() == [prompt, 0, prompt + 8]
    later, _, _ = step(cache, [True, False, False], 7)
    np.testing.assert_array_equal(np.asarray(later[0]), np.asarray(at_once[0]))


@pytest.mark.parametrize("what", ["state", "conv"])
def test_a_dropped_state_or_tail_at_a_chunk_boundary_moves_the_logits(
        params, toks, want, what):
    """The scratch between two chunks is what this family adds: with the
    recurrent state (or the convolution's carried rows) zeroed at
    position 128, the logits after it leave the reference's by far more
    than the tolerance every other test holds them to; and the
    reference's own dropped-state control says the same of both at
    once."""
    seq = gdn_moe.KVCache.create(CFG, 1, jnp.float32)
    ids = jnp.asarray(toks[:1])
    _, seq, _ = gdn_moe.forward(params, ids[:, :128], seq, CFG, jnp.float32)
    name, bufs = ("state", seq.v) if what == "state" else ("conv", seq.k)
    lost = {**bufs, name: tuple(jnp.zeros_like(b) for b in bufs[name])}
    seq = seq._replace(**{"v" if what == "state" else "k": lost})
    logits, _, _ = gdn_moe.forward(params, ids[:, 128:], seq, CFG, jnp.float32)
    moved = np.abs(np.asarray(logits[0]) - want[0, 128:]).max(-1)
    # The convolution forgets in three tokens, but the state took their
    # wrong writes in and carries them on.
    assert moved[0] > 100 * ATOL and moved[-1] > (100 if what == "state" else 3) * ATOL


def test_the_references_dropped_state_control_is_what_losing_the_scratch_computes(
        ref_mod, params, toks):
    seq = gdn_moe.KVCache.create(CFG, 1, jnp.float32)
    ids = jnp.asarray(toks[:1])
    _, seq, _ = gdn_moe.forward(params, ids[:, :128], seq, CFG, jnp.float32)
    zero = lambda bufs, name: {**bufs, name: tuple(jnp.zeros_like(b) for b in bufs[name])}
    seq = seq._replace(k=zero(seq.k, "conv"), v=zero(seq.v, "state"))
    logits, _, _ = gdn_moe.forward(params, ids[:, 128:], seq, CFG, jnp.float32)
    control = reference_logits(ref_mod, params, toks[:1], drop_at=[128])
    np.testing.assert_allclose(np.asarray(logits[0]), control[0, 128:], atol=ATOL)


@pytest.mark.parametrize("prefill_chunk", [16, None])
def test_engine_tokens_equal_the_reference_as_requests_join_and_leave(
        ref_mod, params, prefill_chunk):
    """Through ``GenerationEngine`` and not a side call: four requests on
    two slots (so they queue, join and leave, and a slot sits idle while
    the other decodes), 8 to 12 new tokens each, by chunked prefill of 16
    (prompts end in a padded chunk) and by the engine's default, one call
    over the prompt's power-of-two bucket (up to 64 slots, the prompt's
    end padded).  Greedy tokens equal the reference's own greedy
    continuation (its full forward re-run on the growing row), which they
    can only do if the state, the tail and the rows all hold what the
    equations say."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
               for n in (13, 37, 21, 50)]
    news = (10, 8, 12, 9)

    def reference_greedy(prompt, n):
        row = np.zeros((1, 64), np.int32)
        row[0, :len(prompt)] = prompt
        out = []
        for t in range(len(prompt), len(prompt) + n):
            logits = reference_logits(ref_mod, params, row)[0, t - 1]
            top2 = np.sort(logits)[-2:]
            assert top2[1] - top2[0] > 4 * ATOL, "a near-tie: pick another seed"
            out.append(int(np.argmax(logits)))
            row[0, t] = out[-1]
        return out

    seen = []
    engine = GenerationEngine(
        params, CFG, max_slots=2, dtype=jnp.float32, family=gdn_moe,
        prefill_chunk=prefill_chunk, on_moe=lambda *a: seen.append(a),
    )
    engine.start()
    try:
        futs = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
        outs = [f.result(timeout=600) for f in futs]
    finally:
        engine.shutdown()
    for p, n, out in zip(prompts, news, outs):
        assert out.tolist() == reference_greedy(p, n)
    # The counters the engine hands on: every assignment is either here or
    # routed away; every real token was folded into six states, a prompt
    # chunk a pass, a step a pass a live row.
    fan = CFG.num_experts_per_tok * CFG.num_moe_layers
    landed = sum(counts["local_assignments"] for _, counts, _, _ in seen)
    away = sum(routed for _, _, routed, _ in seen) - landed
    tokens = sum(len(p) for p in prompts) + sum(n - 1 for n in news)
    assert landed + away == fan * tokens and 0 < landed < away
    by = lambda program, name: sum(
        counts[name] for prog, counts, _, _ in seen if prog == program)
    assert by("prefill", "gdn_tokens") == 6 * sum(len(p) for p in prompts)
    chunks = sum(-(-len(p) // (prefill_chunk or 64)) for p in prompts)
    assert by("prefill", "gdn_state_passes") == 6 * chunks
    assert by("decode", "gdn_tokens") == by("decode", "gdn_state_passes") == 6 * sum(
        n - 1 for n in news)


@pytest.mark.parametrize("held", [2, 4])
def test_the_shares_add_up_to_the_uncut_expert_layer(ref_mod, params, held):
    """The guide's share test: the four shares of two experts each (and
    the two of four), the gated shared expert counted once, add up to
    what the uncut layer (every expert held) gives; program and
    reference, share by share."""
    whole = dataclasses.replace(CFG, n_local_experts=0, local_expert_start=0)
    lp = gdn_moe.init(jax.random.key(7), whole, jnp.float32)["layers"][1]
    x = jax.random.normal(jax.random.key(11), (2, 12, CFG.hidden_size))
    valid = jnp.ones((2, 12), bool)
    _, ffn = layer_weights(lp)
    want = np.asarray(ref_mod.build(geometry(dataclasses.replace(
        whole, n_local_experts=8)), 12).moe_ffn(x, ffn)) - np.asarray(x)
    got_whole, _ = gdn_moe._ffn(x, lp, valid, whole)
    np.testing.assert_allclose(np.asarray(got_whole - x), want, atol=ATOL)
    xn = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + CFG.rms_eps) * (
        1.0 + lp["ffn_norm"])
    shared = np.asarray(
        ((jax.nn.silu(xn @ lp["shared_gate"]) * (xn @ lp["shared_up"]))
         @ lp["shared_down"]) * jax.nn.sigmoid(xn @ lp["shared_expert_gate"]))
    total_prog, total_ref = shared.copy(), shared.copy()
    for start in range(0, 8, held):
        share = dataclasses.replace(CFG, n_local_experts=held, local_expert_start=start)
        part = {**lp, "experts": {k: v[start:start + held] for k, v in lp["experts"].items()}}
        got, _counts = gdn_moe._ffn(x, part, valid, share)
        _, part_ffn = layer_weights(part)
        ref_part = np.asarray(ref_mod.build(geometry(share), 12).moe_ffn(x, part_ffn))
        np.testing.assert_allclose(np.asarray(got), ref_part, atol=ATOL)
        total_prog += np.asarray(got) - np.asarray(x) - shared
        total_ref += ref_part - np.asarray(x) - shared
    np.testing.assert_allclose(total_ref, want, atol=ATOL)
    np.testing.assert_allclose(total_prog, want, atol=ATOL)


def test_a_vocabulary_slices_logits_are_the_whole_heads_rows(params, toks, want):
    """A sliced vocabulary is a smaller vocabulary: the head over rows
    64..127 of the vocabulary gives the whole head's logits 64..127."""
    cut = dataclasses.replace(CFG, vocab_size=64)
    sliced = {**params, "lm_head": params["lm_head"][:, 64:128]}
    logits, _, _ = gdn_moe.prefill(sliced, jnp.asarray(toks), cut, jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), want[..., 64:128], atol=ATOL)


def test_the_seeded_decays_span_half_to_nearly_one(ref_mod):
    """``A_log`` is seeded so that memory matters (the configuration's
    ``assumed``): at ``a = 0`` a token's decay runs from 0.5 to 0.999
    across a layer's heads, in the program's init and the reference's
    generator alike."""
    cfg = gdn_moe.GdnMoeConfig()
    a_log = np.asarray(gdn_moe.decay_log_a(cfg))
    np.testing.assert_allclose(
        a_log, ref_mod.decay_log_a(geometry(cfg)), rtol=1e-6)
    decay = np.exp(-np.exp(a_log) * np.log(2.0))
    assert decay.shape == (32,) and np.all(np.diff(decay) > 0)
    np.testing.assert_allclose(decay[[0, -1]], [0.5, 0.999], rtol=1e-5)


_LOWER = """
import hashlib, jax, jax.numpy as jnp
from tpumlops.models import gdn_moe
cfg = gdn_moe.GdnMoeConfig.tiny()
p = jax.eval_shape(lambda: gdn_moe.init(jax.random.key(0), cfg, jnp.float32))
slots = jax.eval_shape(lambda: gdn_moe.RaggedKVCache.create(cfg, 2, jnp.float32))
seq = jax.eval_shape(lambda: gdn_moe.KVCache.create(cfg, 1, jnp.float32))
ids = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
step = jax.jit(lambda p, t, c: gdn_moe.decode_ragged(p, t, c, cfg, dtype=jnp.float32))
chunk = jax.jit(lambda p, t, c: gdn_moe.forward(p, t, c, cfg, jnp.float32))
text = step.lower(p, ids(2, 1), slots).as_text() + chunk.lower(p, ids(1, 8), seq).as_text()
print(list({gdn_moe.FULL, gdn_moe.LINEAR})[0], hashlib.sha1(text.encode()).hexdigest())
"""


def test_the_lowered_programs_do_not_depend_on_the_string_hash_seed():
    """A compile-cache key is the lowered program's text (PERF.md 6, PR
    33): the step and the chunk lower to the same text under two string
    hash seeds that order a set of the two kind names differently."""
    import os
    import subprocess

    root = Path(__file__).resolve().parents[1]
    firsts, digests = set(), set()
    for seed in ("1", "2", "3", "4"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu",
                   PYTHONPATH=str(root))
        out = subprocess.run(
            [sys.executable, "-c", _LOWER], env=env, cwd=root,
            capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        first, digest = out.stdout.split()[-2:]
        firsts.add(first)
        digests.add(digest)
    assert len(firsts) == 2, "the seeds no longer order the set differently"
    assert len(digests) == 1
