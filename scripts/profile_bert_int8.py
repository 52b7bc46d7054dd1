"""Ablation profile of the int8 BERT-base classify path (VERDICT r2 item 1).

Answers: where does the non-matmul half of the int8 batch go?  Each probe
is timed with the bench's pipelined-dispatch methodology (bench.py _timed;
single-call timing would measure the host round trip, not the chip).

Probes
  1. bf16 / int8 full classify                  — the numbers of record
  2. raw GEMM ladders at the exact layer shapes — achievable MXU ceiling
     (bf16, s8 pre-quantized operands, s8 with on-the-fly act quant)
  3. model ablations: no-layernorm, no-softmax, f32-vs-bf16 softmax,
     attention-einsums-in-int8
Prints one JSON dict at the end.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

from tpumlops.models import bert
from tpumlops.models.quantization import dense_q8, quantize_bert, quantize_tensor
from tpumlops.utils.compile_cache import (
    enable_persistent_compile_cache,
    resolve_compile_cache_dir,
)

enable_persistent_compile_cache(resolve_compile_cache_dir())

BATCH, SEQ = 32, 128
RUNS, INNER = 6, 64


def timed(f, *args, runs=RUNS, inner=INNER):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else f(
        *args
    ).block_until_ready()
    samples = []
    for _ in range(runs):
        t0 = time.perf_counter()
        out = None
        for _ in range(inner):
            out = f(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
        samples.append((time.perf_counter() - t0) / inner)
    return min(samples)  # min: least host-noise estimate of steady state


results: dict = {}

cfg = bert.BertConfig.base()
params = bert.init(jax.random.key(0), cfg)
qparams = quantize_bert(params)
ids = jax.random.randint(jax.random.key(1), (BATCH, SEQ), 0, cfg.vocab_size)
mask = jnp.ones((BATCH, SEQ), jnp.int32)

f = jax.jit(lambda p, i, m: bert.classify(p, i, m, cfg=cfg, dtype=jnp.bfloat16))
results["full_int8_ms"] = timed(f, qparams, ids, mask) * 1e3
results["full_bf16_ms"] = timed(f, params, ids, mask) * 1e3
print("full:", results, flush=True)

# ---------------------------------------------------------------------------
# 2. Raw GEMM ladders at the exact per-layer shapes.
# One BERT layer = 4x (T,768)@(768,768) + (T,768)@(768,3072) +
# (T,3072)@(3072,768), T = B*S = 4096.  Chain 12 layers' worth so the
# timed region is model-sized and cannot be elided (output feeds back).
# ---------------------------------------------------------------------------
T, H, I = BATCH * SEQ, cfg.hidden_size, cfg.intermediate_size
kw = jax.random.split(jax.random.key(2), 6)
w_h = [jax.random.normal(k, (H, H), jnp.bfloat16) * 0.02 for k in kw[:4]]
w_up = jax.random.normal(kw[4], (H, I), jnp.bfloat16) * 0.02
w_dn = jax.random.normal(kw[5], (I, H), jnp.bfloat16) * 0.02
x0 = jax.random.normal(jax.random.key(3), (T, H), jnp.bfloat16)

qw_h = [quantize_tensor(w) for w in w_h]
qw_up, qw_dn = quantize_tensor(w_up), quantize_tensor(w_dn)


def ladder_bf16(x):
    for _ in range(cfg.num_layers):
        for w in w_h:
            x = jnp.matmul(x, w, preferred_element_type=jnp.float32).astype(
                jnp.bfloat16
            )
        u = jnp.matmul(x, w_up, preferred_element_type=jnp.float32).astype(
            jnp.bfloat16
        )
        x = jnp.matmul(u, w_dn, preferred_element_type=jnp.float32).astype(
            jnp.bfloat16
        )
    return x


def ladder_q8_dyn(x):
    # on-the-fly activation quant, exactly what dense_q8 does in the model
    for _ in range(cfg.num_layers):
        for qw in qw_h:
            x = dense_q8(x, qw)
        u = dense_q8(x, qw_up)
        x = dense_q8(u, qw_dn)
    return x


def ladder_q8_static(x8):
    # upper bound: operands already int8, rescale folded to a single mul
    for _ in range(cfg.num_layers):
        for qw in qw_h:
            y = jax.lax.dot_general(
                x8, qw["q8"], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            x8 = jnp.clip(y // 1024, -127, 127).astype(jnp.int8)
        y = jax.lax.dot_general(
            x8, qw_up["q8"], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        u8 = jnp.clip(y // 1024, -127, 127).astype(jnp.int8)
        y = jax.lax.dot_general(
            u8, qw_dn["q8"], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        x8 = jnp.clip(y // 1024, -127, 127).astype(jnp.int8)
    return x8

gemm_flops = cfg.num_layers * 2 * T * (4 * H * H + 2 * H * I)

t = timed(jax.jit(ladder_bf16), x0)
results["gemm_bf16_ms"] = t * 1e3
results["gemm_bf16_tflops"] = gemm_flops / t / 1e12
t = timed(jax.jit(ladder_q8_dyn), x0)
results["gemm_q8_dyn_ms"] = t * 1e3
results["gemm_q8_dyn_tflops"] = gemm_flops / t / 1e12
x0_8 = quantize_tensor(x0, axis=-1)["q8"]
t = timed(jax.jit(ladder_q8_static), x0_8)
results["gemm_q8_static_ms"] = t * 1e3
results["gemm_q8_static_tflops"] = gemm_flops / t / 1e12
print("gemm ladders:", results, flush=True)

# ---------------------------------------------------------------------------
# 3. Model ablations (int8 path): knock out one non-matmul component at a
# time; the delta vs full_int8 prices that component.
# ---------------------------------------------------------------------------
import tpumlops.models.common as common_mod

_orig_ln = common_mod.layer_norm
_orig_softmax = jax.nn.softmax


def run_variant(name, patch, unpatch):
    patch()
    try:
        g = jax.jit(
            lambda p, i, m: bert.classify(p, i, m, cfg=cfg, dtype=jnp.bfloat16)
        )
        results[name] = timed(g, qparams, ids, mask) * 1e3
    finally:
        unpatch()
    print(name, results[name], flush=True)


# no layernorm (identity)
run_variant(
    "ablate_no_layernorm_ms",
    lambda: setattr(bert, "layer_norm", lambda x, s, b, eps=1e-12: x),
    lambda: setattr(bert, "layer_norm", _orig_ln),
)

# softmax in bf16 instead of f32 scores
_orig_attn = bert._self_attention


def _attn_bf16_softmax(p, x, mask_bias, cfg):
    b, s, h = x.shape
    nh, hd = cfg.num_heads, cfg.head_dim
    q = bert._dense(x, p["q"]).reshape(b, s, nh, hd)
    k = bert._dense(x, p["k"]).reshape(b, s, nh, hd)
    v = bert._dense(x, p["v"]).reshape(b, s, nh, hd)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.bfloat16(hd**0.5)
    scores = scores + mask_bias.astype(x.dtype)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v).reshape(b, s, h)
    return bert._dense(ctx, p["o"])


def _attn_no_softmax(p, x, mask_bias, cfg):
    b, s, h = x.shape
    nh, hd = cfg.num_heads, cfg.head_dim
    q = bert._dense(x, p["q"]).reshape(b, s, nh, hd)
    k = bert._dense(x, p["k"]).reshape(b, s, nh, hd)
    v = bert._dense(x, p["v"]).reshape(b, s, nh, hd)
    scores = jnp.einsum(
        "bqnd,bknd->bnqk", q, k, preferred_element_type=jnp.float32
    )
    probs = (scores * 0.001).astype(x.dtype)  # keep the tensor, drop softmax
    ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v).reshape(b, s, h)
    return bert._dense(ctx, p["o"])


run_variant(
    "ablate_softmax_bf16_ms",
    lambda: setattr(bert, "_self_attention", _attn_bf16_softmax),
    lambda: setattr(bert, "_self_attention", _orig_attn),
)
run_variant(
    "ablate_no_softmax_ms",
    lambda: setattr(bert, "_self_attention", _attn_no_softmax),
    lambda: setattr(bert, "_self_attention", _orig_attn),
)

print(json.dumps({k: round(v, 3) for k, v in results.items()}))
