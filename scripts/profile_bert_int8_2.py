"""Ablation round 2: price the attention core and GELU on the int8 path."""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

from tpumlops.models import bert
from tpumlops.models.quantization import quantize_bert
from tpumlops.utils.compile_cache import (
    enable_persistent_compile_cache,
    resolve_compile_cache_dir,
)

enable_persistent_compile_cache(resolve_compile_cache_dir())

BATCH, SEQ = 32, 128
RUNS, INNER = 6, 64


def timed(f, *args):
    f(*args).block_until_ready()
    samples = []
    for _ in range(RUNS):
        t0 = time.perf_counter()
        out = None
        for _ in range(INNER):
            out = f(*args)
        out.block_until_ready()
        samples.append((time.perf_counter() - t0) / INNER)
    return min(samples)


results: dict = {}
cfg = bert.BertConfig.base()
params = bert.init(jax.random.key(0), cfg)
qparams = quantize_bert(params)
ids = jax.random.randint(jax.random.key(1), (BATCH, SEQ), 0, cfg.vocab_size)
mask = jnp.ones((BATCH, SEQ), jnp.int32)


def run(name):
    g = jax.jit(lambda p, i, m: bert.classify(p, i, m, cfg=cfg, dtype=jnp.bfloat16))
    results[name] = timed(g, qparams, ids, mask) * 1e3
    print(name, results[name], flush=True)


run("full_int8_ms")

_orig_attn = bert._self_attention
_orig_gelu = bert.gelu


def _attn_passthrough(p, x, mask_bias, cfg):
    # QKV+O projections kept (they're in the GEMM budget); the attention
    # core (scores einsum + softmax + ctx einsum) replaced by identity-v.
    b, s, h = x.shape
    nh, hd = cfg.num_heads, cfg.head_dim
    bert._dense(x, p["q"])
    bert._dense(x, p["k"])
    v = bert._dense(x, p["v"]).reshape(b, s, nh, hd)
    return bert._dense(v.reshape(b, s, h), p["o"])


bert._self_attention = _attn_passthrough
run("ablate_attn_core_ms")
bert._self_attention = _orig_attn

bert.gelu = lambda x: x
run("ablate_gelu_ms")

bert.gelu = lambda x: jax.nn.gelu(x, approximate=True)
run("gelu_tanh_ms")
bert.gelu = _orig_gelu


# Attention core restructured: merge (b, n) into one leading batch dim so
# the two attention matmuls are plain 3-D batched GEMMs, softmax in bf16
# with explicit max-sub (numerics: scores are post-scale, small range).
def _attn_merged(p, x, mask_bias, cfg):
    b, s, h = x.shape
    nh, hd = cfg.num_heads, cfg.head_dim
    q = bert._dense(x, p["q"]).reshape(b, s, nh, hd)
    k = bert._dense(x, p["k"]).reshape(b, s, nh, hd)
    v = bert._dense(x, p["v"]).reshape(b, s, nh, hd)
    q = q.transpose(0, 2, 1, 3).reshape(b * nh, s, hd)
    k = k.transpose(0, 2, 1, 3).reshape(b * nh, s, hd)
    v = v.transpose(0, 2, 1, 3).reshape(b * nh, s, hd)
    scores = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    ) / jnp.float32(hd**0.5)
    scores = scores.reshape(b, nh, s, s) + mask_bias
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype).reshape(b * nh, s, s)
    ctx = jax.lax.dot_general(probs, v, (((2,), (1,)), ((0,), (0,))))
    ctx = ctx.reshape(b, nh, s, hd).transpose(0, 2, 1, 3).reshape(b, s, h)
    return bert._dense(ctx, p["o"])


bert._self_attention = _attn_merged
run("attn_merged_bn_ms")
bert._self_attention = _orig_attn

print(json.dumps({k: round(v, 3) for k, v in results.items()}))
