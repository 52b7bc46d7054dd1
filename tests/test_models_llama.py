"""Llama: prefill/decode consistency, HF parity with copied weights, and
tensor-parallel execution on the virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumlops.models import llama

TINY = llama.LlamaConfig.tiny()


def test_prefill_decode_matches_full_forward():
    params = llama.init(jax.random.key(0), TINY)
    ids = jax.random.randint(jax.random.key(1), (2, 12), 0, TINY.vocab_size)

    # Full-sequence prefill in one shot.
    full_logits, _ = llama.prefill(params, ids, TINY, dtype=jnp.float32)

    # Prefill on the first 8 tokens, then 4 single-token decode steps.
    logits, cache = llama.prefill(params, ids[:, :8], TINY, dtype=jnp.float32)
    steps = [logits[:, -1]]
    for t in range(8, 12):
        logits, cache = llama.decode_step(
            params, ids[:, t : t + 1], cache, TINY, dtype=jnp.float32
        )
        steps.append(logits[:, -1])
    np.testing.assert_allclose(
        np.asarray(steps[-1]), np.asarray(full_logits[:, -1]), atol=1e-4, rtol=1e-4
    )


@pytest.fixture(scope="module")
def torch_twin():
    import torch
    from transformers import LlamaConfig as HFConfig
    from transformers import LlamaForCausalLM

    hf_cfg = HFConfig(
        vocab_size=TINY.vocab_size,
        hidden_size=TINY.hidden_size,
        num_hidden_layers=TINY.num_layers,
        num_attention_heads=TINY.num_heads,
        num_key_value_heads=TINY.num_kv_heads,
        intermediate_size=TINY.intermediate_size,
        max_position_embeddings=TINY.max_seq,
        rope_theta=TINY.rope_theta,
        rms_norm_eps=TINY.rms_eps,
        tie_word_embeddings=False,
        attention_bias=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(hf_cfg)
    model.eval()
    return model


def test_parity_with_transformers(torch_twin):
    import torch

    params = llama.from_torch(torch_twin, TINY)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, TINY.vocab_size, size=(2, 16))
    with torch.no_grad():
        hf_logits = torch_twin(input_ids=torch.tensor(ids)).logits.numpy()
    logits, _ = llama.prefill(params, jnp.asarray(ids, jnp.int32), TINY, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), hf_logits, atol=3e-4, rtol=3e-4)


def test_greedy_generation_matches_transformers(torch_twin):
    import torch

    params = llama.from_torch(torch_twin, TINY)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, TINY.vocab_size, size=(1, 8))
    with torch.no_grad():
        hf_out = torch_twin.generate(
            torch.tensor(ids), max_new_tokens=6, do_sample=False
        ).numpy()[:, 8:]
    ours = llama.generate_greedy(
        params, jnp.asarray(ids, jnp.int32), 6, TINY, dtype=jnp.float32
    )
    np.testing.assert_array_equal(np.asarray(ours), hf_out)


def test_tp_sharded_forward_matches_unsharded():
    from tpumlops.parallel import build_mesh, shard_pytree

    mesh = build_mesh({"dp": 2, "tp": 4})
    cfg = llama.LlamaConfig.tiny(num_kv_heads=4)
    params = llama.init(jax.random.key(0), cfg)
    sharded = shard_pytree(params, llama.param_logical_axes(cfg), mesh)
    ids = jax.random.randint(jax.random.key(1), (4, 12), 0, cfg.vocab_size)

    ref_logits, _ = llama.prefill(params, ids, cfg, dtype=jnp.float32)
    logits, _ = jax.jit(
        lambda p, i: llama.prefill(p, i, cfg, dtype=jnp.float32)
    )(sharded, ids)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), atol=2e-4, rtol=2e-4
    )


def test_cache_is_static_shape():
    cache = llama.KVCache.create(TINY, batch=2)
    assert cache.k.shape == (
        TINY.num_layers,
        2,
        TINY.max_seq,
        TINY.num_kv_heads,
        TINY.head_dim,
    )
    params = llama.init(jax.random.key(0), TINY)
    ids = jnp.ones((2, 4), jnp.int32)
    _, cache2 = llama.forward(params, ids, cache, TINY)
    assert cache2.k.shape == cache.k.shape  # capacity never changes
    assert int(cache2.length) == 4
    # The ragged cache is the same layout with per-row lengths; its
    # capacity is asked of the type, never read off a shape index.
    ragged = llama.RaggedKVCache.create(TINY, batch=2)
    assert ragged.k.shape == ragged.v.shape == cache.k.shape
    quant = llama.QuantRaggedKVCache.create(TINY, batch=2)
    assert quant.k8.shape == cache.k.shape
    assert quant.k_scale.shape == cache.k.shape[:-1] + (1,)
    assert ragged.capacity == quant.capacity == TINY.max_seq


@pytest.mark.parametrize("kv", ["plain", "int8kv"])
def test_ragged_cache_equals_plain_cache_position_for_position(kv):
    """insert + N ragged decode steps leave slot ``b`` holding what the
    plain single-sequence ``KVCache`` path holds, position for position
    (teacher-forced, two slots at different lengths and a parked one).
    ``int8kv`` stores what ``_quant_kv`` makes of those rows: after
    layer 0 its K/V sit within the quantisation error of the plain
    path's, because each layer attends the quantised cache below it."""
    params = llama.init(jax.random.key(0), TINY)
    prompts = {0: [5, 9, 2, 77, 31], 2: [8, 1, 4]}
    forced = jax.random.randint(jax.random.key(3), (6, 3), 1, TINY.vocab_size)
    steps = forced.shape[0]

    # Plain path: one KVCache a sequence, prefill then decode_step.
    plain = {}
    for slot, prompt in prompts.items():
        _, seq = llama.prefill(
            params, jnp.asarray([prompt], jnp.int32), TINY, dtype=jnp.float32
        )
        for t in range(steps):
            _, seq = llama.decode_step(
                params, forced[t, slot][None, None], seq, TINY, dtype=jnp.float32
            )
        plain[slot] = seq

    # Ragged path: padded prefill, insert, batched decode with slot 1 idle.
    if kv == "int8kv":
        cache = llama.QuantRaggedKVCache.create(TINY, 3)
    else:
        cache = llama.RaggedKVCache.create(TINY, 3, jnp.float32)
    for slot, prompt in prompts.items():
        ids = np.zeros((1, 8), np.int32)
        ids[0, : len(prompt)] = prompt
        _, seq = llama.prefill(params, jnp.asarray(ids), TINY, dtype=jnp.float32)
        cache = llama.insert_sequence(
            cache, seq, jnp.int32(slot), jnp.int32(len(prompt))
        )
    active = jnp.asarray([True, False, True])
    step = jax.jit(
        lambda toks, cache: llama.decode_ragged(
            params, toks, cache, TINY, active=active, dtype=jnp.float32,
            window=16,
        )
    )
    for t in range(steps):
        _, cache = step(forced[t][:, None], cache)

    assert np.asarray(cache.lengths).tolist() == [5 + steps, 0, 3 + steps]
    if kv == "int8kv":
        got_k = cache.k8.astype(jnp.float32) * cache.k_scale
        got_v = cache.v8.astype(jnp.float32) * cache.v_scale
        tol = dict(atol=0.02, rtol=0.05)
    else:
        got_k, got_v = cache.k, cache.v
        tol = dict(atol=2e-5, rtol=2e-5)
    for slot, seq in plain.items():
        n = int(seq.length)
        assert n == len(prompts[slot]) + steps
        for got, want in ((got_k, seq.k), (got_v, seq.v)):
            np.testing.assert_allclose(
                np.asarray(got[:, slot, :n]), np.asarray(want[:, 0, :n]), **tol
            )
            if kv == "int8kv":
                # Layer 0 reads no cache: exactly the quantised plain rows.
                q8, scale = llama._quant_kv(want[0, 0, :n])
                np.testing.assert_allclose(
                    np.asarray(got[0, slot, :n]),
                    np.asarray(q8.astype(jnp.float32) * scale),
                    atol=1e-5, rtol=1e-5,
                )
    # The parked slot was never written.
    assert not np.asarray(got_k[:, 1]).any() and not np.asarray(got_v[:, 1]).any()


@pytest.mark.slow
def test_decode_self_attention_at_exact_window_boundary():
    """A row whose position EQUALS the attention window must still attend
    its own current token (via the deferred-decode self-term).  The old
    write-then-attend design sliced the cache to [0, window) AFTER
    writing the current token at index == window — dropping the query's
    self-attention exactly at power-of-two bucket boundaries (the
    engine's window policy produces window == position there).
    Oracle: a window that comfortably covers everything."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(2), cfg, dtype=jnp.float32)
    W = 16  # the boundary window

    def run(window):
        cache = llama.RaggedKVCache.create(cfg, 1, jnp.float32)
        # teacher-force W tokens so positions 0..W-1 hold real content
        logits = None
        for i in range(W):
            tok = jnp.asarray([[(7 * i) % cfg.vocab_size]], jnp.int32)
            logits, cache = llama.decode_ragged(
                params, tok, cache, cfg, dtype=jnp.float32, window=64
            )
        # the step at position == W, with the boundary window
        tok = jnp.asarray([[5]], jnp.int32)
        logits, _ = llama.decode_ragged(
            params, tok, cache, cfg, dtype=jnp.float32, window=window
        )
        return np.asarray(logits[0, -1])

    at_boundary = run(window=W)      # position W, window W
    oracle = run(window=64)          # same state, window covers all
    np.testing.assert_allclose(at_boundary, oracle, rtol=2e-5, atol=2e-5)


def test_commit_rows_drops_write_at_capacity():
    """A row whose length equals cache capacity must NOT be written: the
    scatter spelling (`.at[...].set`) drops out-of-bounds updates, and
    the dynamic_update_slice spelling must not silently clamp onto the
    row's last real K/V (a finished request parked at capacity while
    other slots decode would corrupt itself)."""
    from tpumlops.models.llama import _commit_rows

    L, B, H, T, D = 2, 3, 2, 4, 3
    buf = jnp.zeros((L, B, T, H, D), jnp.float32)
    vals = jnp.ones((L, B, H, D), jnp.float32)
    lengths = jnp.array([1, T, 3], jnp.int32)  # row 1 is AT capacity
    out = jax.jit(_commit_rows)(buf, vals, lengths)
    np.testing.assert_array_equal(np.asarray(out[:, 0, 1]), 1.0)
    np.testing.assert_array_equal(np.asarray(out[:, 2, 3]), 1.0)
    # ... and nowhere else in the rows that did write.
    assert float(out[:, 0].sum()) == float(out[:, 2].sum()) == L * H * D
    # Row 1: untouched everywhere, including the last position a clamped
    # start would have overwritten.
    np.testing.assert_array_equal(np.asarray(out[:, 1]), 0.0)
