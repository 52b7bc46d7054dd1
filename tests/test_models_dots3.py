"""The sparse family's second configuration shape (layers of two kinds,
an indexer that keeps the best earlier positions, a ring of rows for the
sliding layers, a headwise gate, rescaled latents, an expert share)
against its plain float32 reference
(benchmarks/references/dots3_note_decoder.py) on seeded random weights,
at a tiny size where every mechanism bites: ``index_topk`` 8 and a window
of 5 against sequences of 40 and more, a ring of 8 rows that wraps five
times, 8 routed experts of which this share holds 2, and key blocks of 16
so a prefill chunk walks a dynamic number of them.  Logits, never tokens,
wherever the two can be compared position by position."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumlops.models import mla_moe
from tpumlops.server.generation import GenerationEngine

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
F, S = mla_moe.FULL, mla_moe.SLIDING
CFG = mla_moe.MlaMoeConfig.tiny(
    num_layers=5, layer_types=(F, F, S, S, S), rms_eps=1e-5,
    sliding_window=5, swa_num_heads=2, swa_q_lora_rank=20, swa_kv_lora_rank=32,
    swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
    swa_rope_theta=500.0, index_n_heads=4, index_head_dim=16, index_topk=8,
    attention_gate="headwise", lora_rescale=True,
    n_local_experts=2, local_expert_start=2,
)
SEQ = 48
# float32 on both sides and the same equations: what is left is the order
# of the sums (blocks, the absorbed form, the grouped matmul), a few ulp
# of logits of size ~1.
ATOL = 2e-5


@pytest.fixture(autouse=True)
def small_key_blocks(monkeypatch):
    """Blocks of 16 keys: the capacity of 64 is four of them, so prefill
    takes the blocked path the published size takes."""
    monkeypatch.setattr(mla_moe, "KEY_BLOCK", 16)
    monkeypatch.setattr(mla_moe, "ONE_PASS", 16)


@pytest.fixture(params=["einsums", "fused_core"])
def attn_core(request, monkeypatch):
    """The prefill softmax core of both layer kinds: the einsum body the
    CPU takes, or ``ops/prefill_attention.py``'s kernel in interpret mode
    (the same key blocks of 16, the selection's mask and the window rule
    as its ``sees``), where the chip would run it compiled."""
    if request.param == "fused_core":
        import functools

        from tpumlops.ops.prefill_attention import prefill_attention

        monkeypatch.setattr(
            mla_moe, "prefill_attention",
            functools.partial(prefill_attention, interpret=True))
    return request.param


def _load(name):
    if str(BENCH) not in sys.path:
        sys.path.append(str(BENCH))
    import importlib

    return importlib.import_module(f"references.{name}")


@pytest.fixture(scope="module")
def ref_mod():
    return _load("dots3_note_decoder")


def geometry(cfg):
    g = dataclasses.asdict(cfg)  # the artifact's config: the reference's keys
    g["layer_types"] = list(g["layer_types"])
    return g


@pytest.fixture(scope="module")
def params():
    p = mla_moe.init(jax.random.key(3), CFG, jnp.float32)
    for l, lp in enumerate(p["layers"][CFG.num_dense_layers:]):
        lp["router_bias"] = 0.05 * jax.random.normal(
            jax.random.key(9 + l), lp["router_bias"].shape, jnp.float32)
    # A key norm and bias that are not the identity, so both are checked.
    for l in CFG.full_layers:
        lp = p["layers"][l]
        lp["idx_k_norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.key(40 + l), (16,))
        lp["idx_k_bias"] = 0.1 * jax.random.normal(jax.random.key(50 + l), (16,))
    return p


def layer_weights(lp):
    """A layer's leaves under the reference's flat names, split as its
    forward pass takes them: (attention, ffn)."""
    ffn_keys = {"gate", "up", "down", "router", "router_bias", "shared_gate",
                "shared_up", "shared_down", "ffn_norm", "experts"}
    attn = {k: v for k, v in lp.items() if k not in ffn_keys}
    ffn = {**{k: v for k, v in lp.items() if k in ffn_keys and k != "experts"},
           **lp.get("experts", {})}
    return attn, ffn


def reference_logits(ref_mod, params, toks, cfg=CFG):
    """The reference's full forward over rows ``toks`` [R, S]: logits at
    every position, from the program's own tree (same leaf names)."""
    ref = ref_mod.build(geometry(cfg), toks.shape[1])
    x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
    for l, (kind, lp) in enumerate(zip(cfg.kinds, params["layers"])):
        attn, ffn = layer_weights(lp)
        x = ref.attention[kind](x, attn)
        x = ref.dense_ffn(x, ffn) if l < cfg.num_dense_layers else ref.moe_ffn(x, ffn)
    idx = np.tile(np.arange(toks.shape[1]), (toks.shape[0], 1))
    return np.asarray(ref.head(x, jnp.asarray(idx), params["lm_head"]))


@pytest.fixture(scope="module")
def toks():
    return np.random.default_rng(1).integers(0, CFG.vocab_size, (2, SEQ)).astype(np.int32)


@pytest.fixture(scope="module")
def want(ref_mod, params, toks):
    return reference_logits(ref_mod, params, toks)


def test_the_config_refuses_what_it_does_not_implement():
    tiny = mla_moe.MlaMoeConfig.tiny
    with pytest.raises(ValueError, match="layer_types"):
        tiny(layer_types=(F, S))  # three layers
    with pytest.raises(ValueError, match="sliding-window layers alone"):
        tiny(layer_types=(S, S, S), sliding_window=4)
    with pytest.raises(ValueError, match="swa_"):
        tiny(layer_types=(F, S, S), sliding_window=4)
    with pytest.raises(ValueError, match="index_topk"):
        tiny(index_n_heads=2, index_head_dim=16)
    with pytest.raises(ValueError, match="attention_gate"):
        tiny(attention_gate="elementwise")
    with pytest.raises(ValueError, match="expert share"):
        tiny(n_local_experts=4, local_expert_start=6)
    # The ring: the window rounded up to the layout's tile of positions.
    assert CFG.ring_rows == 8
    assert dataclasses.replace(CFG, sliding_window=513).ring_rows == 640
    # A cache row: two full layers of RoPE key (a row of 128 lanes) +
    # latent 16 + index key 16 over 64 positions, three rings of 8 rows of
    # RoPE key + latent 32.
    assert mla_moe.kv_row_bytes(CFG) == 2 * (2 * 64 * (128 + 32) + 3 * 8 * (128 + 32))


@pytest.mark.parametrize("flavor", [
    "mla-moe-generate", "llama-generate", "bert-classifier", "resnet-classifier"])
def test_an_artifact_of_another_variant_fails_to_load(flavor):
    """A config key the class does not know names a variant this program
    does not implement: refused, not dropped and served as the plain
    block (what a program from before layer kinds would otherwise do with
    this configuration's artifact).  One rule for every family."""
    from tpumlops.server import loader

    assert loader._build_config(flavor, {}) is not None
    with pytest.raises(ValueError, match="does not know.*layer_kinds_v2"):
        loader._build_config(flavor, {"layer_kinds_v2": []})


def test_full_forward_logits_equal_the_reference(params, toks, want, attn_core):
    """One prefill of 48 positions: the indexer drops keys from position
    8 on, the window from position 5 on, and the capacity's four key
    blocks are walked as far as written (three)."""
    logits, cache, counts = mla_moe.prefill(params, jnp.asarray(toks), CFG, jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), want, atol=ATOL)
    assert int(cache.length) == SEQ
    # The counts: 2 rows x 48 positions x 2 indexed layers.
    seen = np.arange(1, SEQ + 1)
    assert counts[3:].tolist() == [2 * 2 * seen.sum(), 2 * 2 * np.minimum(seen, 8).sum()]
    # The share: of 2 x 48 x top-2 x 4 expert layers assignments, those to
    # experts 2 and 3 landed here (near a quarter under seeded weights).
    landed, every = int(counts[2]), mla_moe.routed_assignments(CFG, 2 * SEQ)
    assert 0.1 * every < landed < 0.45 * every
    assert 0 < int(counts[0]) <= 2 * CFG.num_moe_layers


def test_the_selected_sets_are_the_references(ref_mod, params, toks):
    """Layer 0's selection for row 0, program (scores a key block at a
    time, the k-th largest by bisection) against reference
    (``lax.top_k``): the same sets, every row holding min(t + 1, 8)."""
    lp = params["layers"][0]
    x = params["embed"][jnp.asarray(toks[:1])].astype(jnp.float32)
    xn = mla_moe.rms_norm(x, lp["attn_norm"], CFG.rms_eps)
    positions = jnp.arange(SEQ)
    cos, sin = mla_moe.rope_cos_sin(positions[None], CFG)
    _, _, cq = mla_moe._mla_q(xn, lp, cos, sin, CFG)
    qi, ki, wi = mla_moe._index_qkw(xn, cq, lp, cos, sin, CFG)
    buf = jnp.zeros((1, 64, 16)).at[:, :SEQ].set(ki)
    got, picked = mla_moe._dsa_select(
        qi, wi, buf, positions, jnp.ones((1, SEQ), bool), 3, 16, CFG)
    got = got[0, :, :SEQ]
    # The counts are the tensors' own: the finite scores, the kept mask.
    assert picked.tolist() == [SEQ * (SEQ + 1) // 2, int(np.asarray(got).sum())]
    ref = ref_mod.build(geometry(CFG), SEQ)
    attn, _ = layer_weights(lp)
    attn = {k: v.astype(jnp.float32) for k, v in attn.items()}
    want = ref.select(xn[0], cq[0], attn, CFG.rope_theta)
    assert np.asarray(got).sum(-1).tolist() == np.minimum(np.arange(1, SEQ + 1), 8).tolist()
    assert (np.asarray(got) == np.asarray(want)).all()


def test_kth_largest_is_exact():
    x = jax.random.normal(jax.random.key(0), (5, 37))
    x = x.at[0, :30].set(-jnp.inf).at[1, 3].set(x[1, 4])  # few finite; a tie
    x = x.at[2, 5:25].set(0.0).at[3].set(1.5)  # many ties, as relu gives; all equal
    for k in (1, 8, 37):
        want = np.sort(np.asarray(x), axis=-1)[:, -k]
        np.testing.assert_array_equal(np.asarray(mla_moe._kth_largest(x, k)), want)
        # The mask is ``lax.top_k``'s set: a tie goes to the lower index.
        vals, idx = jax.lax.top_k(x, k)
        picked = np.zeros(x.shape, bool)
        np.put_along_axis(picked, np.asarray(idx), np.asarray(vals) > -np.inf, -1)
        np.testing.assert_array_equal(np.asarray(mla_moe._top_mask(x, k)), picked)


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_chunked_prefill_then_ragged_decode_equals_the_full_forward(
        params, toks, want, chunk, attn_core):
    """A prompt of 27 tokens in chunks (the last one padded with ids
    < 0) into the batch-1 scratch, inserted into slot 1 of a 3-slot
    cache, then 16 single-token steps: every step's logits are the full
    forward's at that position, with the ring (8 rows) wrapping from the
    prompt on, the selection dropping keys in every step, and the padding
    rows of the last chunk written to no ring.  Chunks of 16 and 32 are
    longer than the ring and end in 5 padding slots: the ring takes the
    last 8 REAL rows of a call, wherever its slots end."""
    prompt, steps = 27, 16
    row = toks[0]
    seq = mla_moe.KVCache.create(CFG, 1, jnp.float32)
    for at in range(0, prompt, chunk):
        ids = np.full((1, chunk), mla_moe.PAD_ID, np.int32)
        n = min(chunk, prompt - at)
        ids[0, :n] = row[at:at + n]
        logits, seq, _ = mla_moe.forward(params, jnp.asarray(ids), seq, CFG, jnp.float32)
        np.testing.assert_allclose(
            np.asarray(logits[0, :n]), want[0, at:at + n], atol=ATOL)
    cache = mla_moe.insert_sequence(
        mla_moe.RaggedKVCache.create(CFG, 3, jnp.float32), seq, 1, prompt)
    live = jnp.asarray([False, True, False])
    for t in range(prompt, prompt + steps):
        step = jnp.asarray([[0], [int(row[t])], [0]], jnp.int32)
        logits, cache, counts = mla_moe.decode_ragged(
            params, step, cache, CFG, active=live, dtype=jnp.float32, window=48)
        np.testing.assert_allclose(np.asarray(logits[1, 0]), want[0, t], atol=ATOL)
        assert counts[3:].tolist() == [2 * (t + 1), 2 * 8]
    assert cache.lengths.tolist() == [0, prompt + steps, 0]
    # Idle slots' rows were never written, in any buffer of any row kind.
    for buf in jax.tree.leaves((cache.k, cache.v)):
        assert not np.asarray(buf[0]).any() and not np.asarray(buf[2]).any()


@pytest.mark.parametrize("prefill_chunk", [8, None])
def test_engine_tokens_equal_the_reference_through_all_three_row_kinds(
        ref_mod, params, prefill_chunk):
    """Through ``GenerationEngine`` and not a side call: four requests on
    two slots (so they queue, join and leave), 12 to 16 new tokens each,
    by chunked prefill of 8 and by the engine's default, one call over the
    prompt's power-of-two bucket (16 or 32 slots against a ring of 8, the
    prompt's end padded).  Greedy tokens equal the reference's own greedy
    continuation (its full forward re-run on the growing row), which they
    can only do if the ring, the index keys and the latent rows all hold
    what the equations say."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
               for n in (13, 30, 21, 26)]
    news = (14, 12, 16, 13)
    ref = ref_mod.build(geometry(CFG), SEQ)

    def reference_greedy(prompt, n):
        row = np.zeros((1, SEQ), np.int32)
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
        params, CFG, max_slots=2, dtype=jnp.float32, family=mla_moe,
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
    del ref
    # The counters the engine hands on: every assignment is either here
    # or routed away; the indexer scored every position of every real
    # query in both indexed layers and kept at most 8.
    fan = CFG.num_experts_per_tok * CFG.num_moe_layers
    landed = sum(counts["local_assignments"] for _, counts, _, _ in seen)
    away = sum(routed for _, _, routed, _ in seen) - landed
    tokens = sum(len(p) for p in prompts) + sum(n - 1 for n in news)
    assert landed + away == fan * tokens and 0 < landed < away
    scored = sum(counts["dsa_keys_scored"] for _, counts, _, _ in seen)
    kept = sum(counts["dsa_keys_selected"] for _, counts, _, _ in seen)
    want_scored = want_kept = 0
    for p, n in zip(prompts, news):
        seen_positions = np.arange(1, len(p) + n)  # queries at 0 .. len + n - 2
        want_scored += 2 * seen_positions.sum()
        want_kept += 2 * np.minimum(seen_positions, 8).sum()
    assert (scored, kept) == (want_scored, want_kept)


@pytest.mark.parametrize("held", [1, 2])
def test_the_shares_add_up_to_the_uncut_expert_layer(ref_mod, params, held):
    """The guide's share test: the eight shares of one expert each (and
    the four of two), the
    shared expert counted once, add up to what the uncut reference
    (``references/mla_moe_decoder.py``: every expert held) gives for the
    whole layer; program and reference, share by share."""
    uncut_mod = _load("mla_moe_decoder")
    whole = dataclasses.replace(CFG, n_local_experts=0, local_expert_start=0)
    lp = mla_moe.init(jax.random.key(7), whole, jnp.float32)["layers"][2]
    lp["router_bias"] = 0.05 * jax.random.normal(jax.random.key(8), (8,))
    x = jax.random.normal(jax.random.key(11), (2, 12, CFG.hidden_size))
    valid = jnp.ones((2, 12), bool)
    _, ffn = layer_weights(lp)
    uncut_g = {**geometry(whole), "num_heads": 4}
    want = np.asarray(uncut_mod.build(uncut_g, 12).moe_ffn(x, ffn)) - np.asarray(x)
    xn = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + CFG.rms_eps)
    shared = np.asarray(
        (jax.nn.silu(xn @ lp["shared_gate"]) * (xn @ lp["shared_up"])) @ lp["shared_down"])
    total_prog, total_ref = shared.copy(), shared.copy()
    for start in range(0, 8, held):
        share = dataclasses.replace(CFG, n_local_experts=held, local_expert_start=start)
        part = {**lp, "experts": {k: v[start:start + held] for k, v in lp["experts"].items()}}
        got, counts = mla_moe._ffn(x, part, valid, share)
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
    logits, _, _ = mla_moe.prefill(sliced, jnp.asarray(toks), cut, jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), want[..., 64:128], atol=ATOL)


_LOWER = """
import hashlib, jax, jax.numpy as jnp
from tpumlops.models import mla_moe
F, S = mla_moe.FULL, mla_moe.SLIDING
cfg = mla_moe.MlaMoeConfig.tiny(
    num_layers=3, layer_types=(F, S, S), sliding_window=5, swa_num_heads=2,
    swa_q_lora_rank=20, swa_kv_lora_rank=32, swa_qk_nope_head_dim=24,
    swa_qk_rope_head_dim=8, swa_v_head_dim=16, swa_rope_theta=500.0,
    index_n_heads=4, index_head_dim=16, index_topk=8)
p = jax.eval_shape(lambda: mla_moe.init(jax.random.key(0), cfg, jnp.float32))
slots = jax.eval_shape(lambda: mla_moe.RaggedKVCache.create(cfg, 2, jnp.float32))
seq = jax.eval_shape(lambda: mla_moe.KVCache.create(cfg, 1, jnp.float32))
ids = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
step = jax.jit(lambda p, t, c: mla_moe.decode_ragged(p, t, c, cfg, dtype=jnp.float32))
chunk = jax.jit(lambda p, t, c: mla_moe.forward(p, t, c, cfg, jnp.float32))
text = step.lower(p, ids(2, 1), slots).as_text() + chunk.lower(p, ids(1, 8), seq).as_text()
print(list({F, S})[0], hashlib.sha1(text.encode()).hexdigest())
"""


def test_the_lowered_programs_do_not_depend_on_the_string_hash_seed():
    """A compile-cache key is the lowered program's text.  With the two
    layer kinds walked in a ``set``'s order, the text flipped with the
    process's string hash seed, and a replica's boot missed the cache for
    every model program at random (PERF.md 6, PR 33: 566-589 s of set-up
    against 105-113).  Seeds 1 and 3 order a set of the two kind names
    differently; the step and the chunk lower to the same text in both."""
    import os
    import subprocess

    root = Path(__file__).resolve().parents[1]
    firsts, digests = set(), set()
    for seed in ("1", "3"):
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
