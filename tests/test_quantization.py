"""Weight-only int8 quantization: accuracy, pytree mechanics, serving path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumlops.models import llama
from tpumlops.models.quantization import (
    dequantize_tensor,
    is_quantized,
    quantize_llama,
    quantize_tensor,
    quantized_bytes,
)


def test_quantize_tensor_roundtrip_error_bound():
    w = jax.random.normal(jax.random.key(0), (4, 64, 128), jnp.float32) * 0.02
    q = quantize_tensor(w)
    assert q["q8"].dtype == jnp.int8 and q["q8"].shape == w.shape
    assert q["scale"].shape == (4, 1, 128)
    back = dequantize_tensor(q, jnp.float32)
    # Symmetric int8: per-channel max error is scale/2.
    max_err = jnp.abs(back - w).max()
    assert max_err <= float(q["scale"].max()) / 2 + 1e-7
    # Storage really is ~half of bf16.
    assert quantized_bytes(q) < 0.6 * w.size * 2


def test_quantized_llama_logits_close_and_greedy_stable():
    cfg = llama.LlamaConfig.tiny(max_seq=32)
    params = llama.init(jax.random.key(0), cfg, dtype=jnp.float32)
    qparams = quantize_llama(params)
    assert is_quantized(qparams["layers"]["q"])
    assert is_quantized(qparams["lm_head"])
    assert not is_quantized(qparams["embed"])  # gather path stays raw

    ids = jnp.asarray([[5, 9, 2, 11, 7]], jnp.int32)
    lf, _ = llama.prefill(params, ids, cfg, dtype=jnp.float32)
    lq, _ = llama.prefill(qparams, ids, cfg, dtype=jnp.float32)
    # Per-channel int8 keeps logits close in relative terms.
    rel = float(jnp.abs(lq - lf).max() / (jnp.abs(lf).max() + 1e-9))
    assert rel < 0.15, rel
    cos = float(
        jnp.sum(lq[0, -1] * lf[0, -1])
        / (jnp.linalg.norm(lq[0, -1]) * jnp.linalg.norm(lf[0, -1]))
    )
    assert cos > 0.999, cos


@pytest.mark.slow
def test_quantized_params_flow_through_generation_engine():
    from tpumlops.server.generation import GenerationEngine

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(1), cfg, dtype=jnp.float32)
    qparams = quantize_llama(params)
    engine = GenerationEngine(qparams, cfg, max_slots=2, dtype=jnp.float32)
    engine.start(warmup=True)
    try:
        out = engine.generate([5, 9, 2], 6)
        assert out.shape == (6,)
        out2 = engine.generate([5, 9, 2], 6)
        assert out.tolist() == out2.tolist()  # greedy: deterministic
    finally:
        engine.shutdown()


def test_loader_quantize_plumbing(tmp_path):
    from tpumlops.server.loader import ModelLoadError, load_predictor, save_native_model

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(2), cfg, dtype=jnp.float32)
    art = tmp_path / "llm"
    save_native_model(
        art,
        "llama-generate",
        params,
        config={
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.hidden_size,
            "num_layers": cfg.num_layers,
            "num_heads": cfg.num_heads,
            "num_kv_heads": cfg.num_kv_heads,
            "intermediate_size": cfg.intermediate_size,
            "max_seq": cfg.max_seq,
        },
    )
    pred = load_predictor(str(art), quantize="int8")
    assert is_quantized(pred.causal_lm["params"]["lm_head"])
    # Every layer matmul too (regression: the streaming loader's leaf
    # name list must use the npz flat-key separator, or layers silently
    # stay full-precision while lm_head matches by accident).
    for name in ("q", "k", "v", "o", "gate", "up", "down"):
        assert is_quantized(pred.causal_lm["params"]["layers"][name]), name
    out = pred.predict(np.ones((1, 4), np.int32))
    assert np.asarray(out).shape[0] == 1

    # Non-causal flavors reject quantization loudly.
    from sklearn.datasets import load_iris
    from sklearn.linear_model import LogisticRegression

    from tpumlops.server.loader import save_sklearn_model

    X, y = load_iris(return_X_y=True)
    iris = tmp_path / "iris"
    save_sklearn_model(iris, LogisticRegression(max_iter=200).fit(X, y), "sklearn-linear")
    with pytest.raises(ModelLoadError, match="llama-generate"):
        load_predictor(str(iris), flavor="sklearn-linear", quantize="int8")


def test_quantize_with_tp_sharding():
    """Quantizing sharded params keeps shardings and stays serveable."""
    from tpumlops.parallel import build_mesh, shard_pytree

    cfg = llama.LlamaConfig.tiny(max_seq=32, num_kv_heads=4)
    params = llama.init(jax.random.key(3), cfg, dtype=jnp.float32)
    mesh = build_mesh({"dp": 2, "tp": 4})
    sharded = shard_pytree(params, llama.param_logical_axes(cfg), mesh)
    q = quantize_llama(sharded)
    ids = jnp.asarray([[5, 9, 2]], jnp.int32)
    lf, _ = llama.prefill(params, ids, cfg, dtype=jnp.float32)
    lq, _ = llama.prefill(q, ids, cfg, dtype=jnp.float32)
    cos = float(
        jnp.sum(lq[0, -1] * lf[0, -1])
        / (jnp.linalg.norm(lq[0, -1]) * jnp.linalg.norm(lf[0, -1]))
    )
    assert cos > 0.999, cos


def test_dequantize_bf16_single_rounding():
    """The dequant product must round once (f32 multiply -> bf16), not
    twice (bf16 scale then bf16 multiply)."""
    w = jax.random.normal(jax.random.key(5), (64, 128), jnp.float32) * 0.02
    q = quantize_tensor(w)
    good = dequantize_tensor(q, jnp.bfloat16).astype(jnp.float32)
    double_rounded = (
        q["q8"].astype(jnp.bfloat16) * q["scale"].astype(jnp.bfloat16)
    ).astype(jnp.float32)
    err_good = float(jnp.abs(good - w).max())
    err_double = float(jnp.abs(double_rounded - w).max())
    assert err_good <= err_double
    # And bf16 dequant stays within int8 quantization error + bf16 ulp.
    assert err_good <= float(q["scale"].max()) / 2 + 0.01 * float(jnp.abs(w).max())


# ---------------------------------------------------------------------------
# KV-cache int8 (quantize: int8kv)
# ---------------------------------------------------------------------------


# name: (num_heads, num_kv_heads, capacity, row lengths, window, steps).
# The int8 cache through the XLA chain of ``_block_decode_deferred`` (the
# one decode attention there is) beside the full-precision cache: the
# shapes of a batch that a kernel would have to get right as well.
_KV_DECODE_CASES = {
    "g2_whole_capacity_six_steps": (4, 2, 32, (4, 0), None, 6),
    "zero_length_row_attends_only_its_self_term": (4, 2, 64, (0, 9), 32, 2),
    "three_lengths_in_one_batch": (4, 2, 64, (7, 23, 30), 32, 2),
    "g1": (4, 4, 64, (5, 17, 30), 32, 2),
    "g8": (8, 1, 64, (5, 17, 30), 32, 2),
    "window_96": (4, 2, 128, (3, 40, 93), 96, 2),
    "window_384": (4, 2, 512, (1, 200, 381), 384, 2),
}


@pytest.mark.parametrize("case", sorted(_KV_DECODE_CASES))
def test_quant_kv_cache_decode_close_to_full_precision(case):
    from tpumlops.models.llama import QuantRaggedKVCache, RaggedKVCache

    heads, kv_heads, capacity, lengths, window, steps = _KV_DECODE_CASES[case]
    cfg = llama.LlamaConfig.tiny(
        num_heads=heads, num_kv_heads=kv_heads, max_seq=capacity
    )
    params = llama.init(jax.random.key(0), cfg, dtype=jnp.float32)
    prefill = jax.jit(
        lambda ids: llama.prefill(params, ids, cfg, dtype=jnp.float32)
    )
    step = jax.jit(
        lambda toks, cache: llama.decode_ragged(
            params, toks, cache, cfg, dtype=jnp.float32, window=window
        )
    )

    full = RaggedKVCache.create(cfg, len(lengths), jnp.float32)
    quant = QuantRaggedKVCache.create(cfg, len(lengths))
    first = []
    for row, n in enumerate(lengths):
        # One prompt length a case: causal attention keeps the first n
        # positions blind to the padding behind them.
        ids = jax.random.randint(
            jax.random.key(10 + row), (1, max(lengths)), 1, cfg.vocab_size
        )
        logits, seq = prefill(ids)
        # An empty row holds the whole prompt as junk in the int8 cache and
        # zeros in the other: neither may be attended.
        quant = llama.insert_sequence(quant, seq, jnp.int32(row), jnp.int32(n))
        if n:
            full = llama.insert_sequence(full, seq, jnp.int32(row), jnp.int32(n))
        first.append(int(jnp.argmax(logits[0, n - 1])) if n else 1)
    tok = jnp.asarray(first, jnp.int32)[:, None]

    for i in range(steps):
        lf, full = step(tok, full)
        lq, quant = step(tok, quant)
        for row, n in enumerate(lengths):
            a, b = lq[row, -1], lf[row, -1]
            if n == 0 and i == 0:
                # Only the exact self-term is attended: no int8 value
                # reaches the logits, so the two agree to rounding.
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
                )
            cos = float(jnp.sum(a * b) / (jnp.linalg.norm(a) * jnp.linalg.norm(b)))
            assert cos > 0.995, (row, i, cos)
        tok = jnp.argmax(lf[:, -1:], axis=-1).astype(jnp.int32)
    # storage really is int8
    assert quant.k8.dtype == jnp.int8
    assert quant.lengths.tolist() == full.lengths.tolist()
    assert quant.lengths.tolist() == [n + steps for n in lengths]


@pytest.mark.slow
def test_engine_kv_quant_end_to_end():
    from tpumlops.server.generation import GenerationEngine

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(1), cfg, dtype=jnp.float32)
    engine = GenerationEngine(
        quantize_llama(params), cfg, max_slots=2, dtype=jnp.float32, kv_quant=True
    )
    engine.start(warmup=True)
    try:
        out = engine.generate([5, 9, 2], 6)
        assert out.shape == (6,)
        # deterministic (greedy) and reproducible with a quantized cache
        assert engine.generate([5, 9, 2], 6).tolist() == out.tolist()
        # sampled path over the quantized cache
        s1 = engine.generate([7, 1], 5, temperature=0.9, seed=3)
        s2 = engine.generate([7, 1], 5, temperature=0.9, seed=3)
        assert s1.tolist() == s2.tolist()
    finally:
        engine.shutdown()


def test_loader_int8kv_mode(tmp_path):
    from tpumlops.server.loader import load_predictor, save_native_model

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(2), cfg, dtype=jnp.float32)
    art = tmp_path / "llm"
    save_native_model(
        art,
        "llama-generate",
        params,
        config={
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.hidden_size,
            "num_layers": cfg.num_layers,
            "num_heads": cfg.num_heads,
            "num_kv_heads": cfg.num_kv_heads,
            "intermediate_size": cfg.intermediate_size,
            "max_seq": cfg.max_seq,
        },
    )
    pred = load_predictor(str(art), quantize="int8kv")
    assert is_quantized(pred.causal_lm["params"]["lm_head"])


# ---------------------------------------------------------------------------
# Int8 BERT classify (VERDICT round 1, next #4)
# ---------------------------------------------------------------------------


def test_bert_int8_classify_matches_bf16():
    """Dynamic-activation int8 BERT must track the bf16 logits closely
    (the two int8 roundings are the only approximation)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpumlops.models import bert
    from tpumlops.models.quantization import quantize_bert

    cfg = bert.BertConfig.tiny(num_labels=4)
    params = bert.init(jax.random.key(0), cfg)
    qparams = quantize_bert(params)
    ids = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab_size)

    ref = np.asarray(
        jax.jit(lambda p, i: bert.classify(p, i, cfg=cfg, dtype=jnp.float32))(
            params, ids
        )
    )
    got = np.asarray(
        jax.jit(lambda p, i: bert.classify(p, i, cfg=cfg, dtype=jnp.float32))(
            qparams, ids
        )
    )
    # Logit-scale agreement: quant noise well under the logit spread.
    spread = np.abs(ref).max()
    assert np.abs(got - ref).max() < 0.05 * max(spread, 1.0), (
        np.abs(got - ref).max(), spread
    )


def test_quantize_bert_only_touches_layer_matmuls():
    import jax
    import jax.numpy as jnp

    from tpumlops.models import bert
    from tpumlops.models.quantization import is_quantized, quantize_bert

    cfg = bert.BertConfig.tiny(num_labels=2)
    params = bert.init(jax.random.key(0), cfg)
    q = quantize_bert(params)
    for layer in q["layers"]:
        for g, n in (("attn", "q"), ("attn", "k"), ("attn", "v"),
                     ("attn", "o"), ("mlp", "up"), ("mlp", "down")):
            assert is_quantized(layer[g][n]["w"])
            assert layer[g][n]["b"].dtype == jnp.float32
        assert layer["attn"]["ln"]["scale"].dtype == jnp.float32
    # embeddings / pooler / classifier stay full precision
    assert q["embeddings"]["word"].dtype == jnp.float32
    assert not is_quantized(q["pooler"]["w"])
    assert not is_quantized(q["classifier"]["w"])


def test_loader_bert_int8(tmp_path):
    """spec.tpu.quantize: int8 now applies to bert-classifier (the MXU
    int8 path), with int8kv still rejected (no KV cache)."""
    import pytest

    from tpumlops.models import bert
    from tpumlops.server.loader import ModelLoadError, load_predictor, save_native_model

    cfg = bert.BertConfig.tiny(num_labels=3)
    params = bert.init(jax.random.key(4), cfg)
    art = tmp_path / "bertq"
    save_native_model(
        art,
        "bert-classifier",
        params,
        config={
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.hidden_size,
            "num_layers": cfg.num_layers,
            "num_heads": cfg.num_heads,
            "intermediate_size": cfg.intermediate_size,
            "max_position_embeddings": cfg.max_position_embeddings,
            "num_labels": cfg.num_labels,
        },
    )
    pred = load_predictor(str(art), quantize="int8")
    ids = np.ones((2, 16), np.int32)
    ref = load_predictor(str(art))
    got = np.asarray(pred.predict(ids))
    want = np.asarray(ref.predict(ids))
    assert got.shape == want.shape == (2, 3)
    assert np.abs(got - want).max() < 0.05 * max(np.abs(want).max(), 1.0)
    with pytest.raises(ModelLoadError, match="int8kv"):
        load_predictor(str(art), quantize="int8kv")


def test_streamed_host_quantize_matches_device_quantize(tmp_path, monkeypatch):
    """The loader's host-side (numpy) quantize-on-arrival — the
    TPUMLOPS_HOST_QUANTIZE=1 fallback since round 4 made on-device
    quantize the streaming default — must implement the same scheme as
    quantization.quantize_tensor: identical scales and q8 within one
    rounding ulp."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("TPUMLOPS_HOST_QUANTIZE", "1")

    from tpumlops.models import llama
    from tpumlops.models.quantization import quantize_llama

    from tpumlops.server.loader import load_predictor, save_native_model

    cfg = llama.LlamaConfig.tiny()
    params = llama.init(jax.random.key(7), cfg, dtype=jnp.bfloat16)
    art = tmp_path / "llq"
    save_native_model(
        art, "llama-generate", params,
        config={
            "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
            "num_layers": cfg.num_layers, "num_heads": cfg.num_heads,
            "num_kv_heads": cfg.num_kv_heads,
            "intermediate_size": cfg.intermediate_size, "max_seq": cfg.max_seq,
        },
    )
    streamed = load_predictor(str(art), quantize="int8").causal_lm["params"]
    ref = quantize_llama(
        load_predictor(str(art)).causal_lm["params"]
    )
    for name in ("q", "k", "v", "o", "gate", "up", "down"):
        s_leaf = streamed["layers"][name]
        r_leaf = ref["layers"][name]
        np.testing.assert_allclose(
            np.asarray(s_leaf["scale"]), np.asarray(r_leaf["scale"]),
            rtol=1e-6, err_msg=name,
        )
        diff = np.abs(
            np.asarray(s_leaf["q8"], np.int32) - np.asarray(r_leaf["q8"], np.int32)
        )
        assert diff.max() <= 1, (name, diff.max())  # rounding-tie ulp only


def test_streamed_device_quantize_is_exact(tmp_path):
    """The default streaming path quantizes ON DEVICE through the one
    canonical quantize_tensor, so its output must be bit-identical to
    quantizing the loaded bf16 tree in one shot."""
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama
    from tpumlops.models.quantization import quantize_llama
    from tpumlops.server.loader import load_predictor, save_native_model

    cfg = llama.LlamaConfig.tiny()
    params = llama.init(jax.random.key(11), cfg, dtype=jnp.bfloat16)
    art = tmp_path / "llq2"
    save_native_model(
        art, "llama-generate", params,
        config={
            "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
            "num_layers": cfg.num_layers, "num_heads": cfg.num_heads,
            "num_kv_heads": cfg.num_kv_heads,
            "intermediate_size": cfg.intermediate_size, "max_seq": cfg.max_seq,
        },
    )
    streamed = load_predictor(str(art), quantize="int8").causal_lm["params"]
    ref = quantize_llama(load_predictor(str(art)).causal_lm["params"])
    for name in ("q", "k", "v", "o", "gate", "up", "down"):
        np.testing.assert_array_equal(
            np.asarray(streamed["layers"][name]["q8"]),
            np.asarray(ref["layers"][name]["q8"]), err_msg=name,
        )
        np.testing.assert_array_equal(
            np.asarray(streamed["layers"][name]["scale"]),
            np.asarray(ref["layers"][name]["scale"]), err_msg=name,
        )
    np.testing.assert_array_equal(
        np.asarray(streamed["lm_head"]["q8"]), np.asarray(ref["lm_head"]["q8"])
    )
