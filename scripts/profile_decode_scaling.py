"""Why does ragged-decode ms/step scale ~linearly with slots at 1.35B?

Expected: decode is weight-streaming-bound (1.35 GiB/step constant), so
doubling slots should barely move ms/step.  Measured (BENCH r3 ladder):
8.67 -> 16.1 -> 32.1 -> 65.7 ms for 8 -> 64 slots.  This probe prices one
decoder layer's components at B=8 vs B=32 to find the linear term:

  full        — write (vmapped DUS) + attention + matmuls (mirror of
                llama._block decode path, quant cache)
  write_at    — same but cache write via indexed .at[].set scatter
  no_write    — attention + matmuls only
  no_attn     — write + matmuls only
  matmuls     — matmuls only

Timing: bench.py scan-delta (data-chained lax.scan, varied carries,
params explicit) over a SINGLE layer's weights, 24 iterations standing in
for 24 layers.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
from jax import lax

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import _scan_delta_timed
from tpumlops.utils.compile_cache import (
    enable_persistent_compile_cache,
    resolve_compile_cache_dir,
)

enable_persistent_compile_cache(resolve_compile_cache_dir())

H, NKV, NH, HD, I = 2048, 16, 16, 128, 5632
CAP, WINDOW, POS = 768, 512, 256
L = 24  # scan length multiplier: one layer body iterated L times


def make_weights(key):
    ks = jax.random.split(key, 7)
    w = {
        "q": jax.random.normal(ks[0], (H, NH * HD), jnp.bfloat16) * 0.02,
        "k": jax.random.normal(ks[1], (H, NKV * HD), jnp.bfloat16) * 0.02,
        "v": jax.random.normal(ks[2], (H, NKV * HD), jnp.bfloat16) * 0.02,
        "o": jax.random.normal(ks[3], (NH * HD, H), jnp.bfloat16) * 0.02,
        "gate": jax.random.normal(ks[4], (H, I), jnp.bfloat16) * 0.02,
        "up": jax.random.normal(ks[5], (H, I), jnp.bfloat16) * 0.02,
        "down": jax.random.normal(ks[6], (I, H), jnp.bfloat16) * 0.02,
    }
    from tpumlops.models.quantization import quantize_tensor

    return {k: quantize_tensor(v) for k, v in w.items()}


def deq(qw, dtype):
    return (qw["q8"].astype(jnp.float32) * qw["scale"]).astype(dtype)


def layer(p, x, k8, ks, v8, vs, start, variant):
    b = x.shape[0]
    q = jnp.matmul(x, deq(p["q"], x.dtype), preferred_element_type=jnp.float32)
    k = jnp.matmul(x, deq(p["k"], x.dtype), preferred_element_type=jnp.float32)
    v = jnp.matmul(x, deq(p["v"], x.dtype), preferred_element_type=jnp.float32)
    q = q.astype(x.dtype).reshape(b, 1, NH, HD)
    k = k.astype(x.dtype).reshape(b, 1, NKV, HD)
    v = v.astype(x.dtype).reshape(b, 1, NKV, HD)

    from tpumlops.models.llama import _quant_kv

    kq, kqs = _quant_kv(k)
    vq, vqs = _quant_kv(v)

    if variant in ("full", "no_attn"):
        def _write(row_cache, row_kv, row_start):
            z = jnp.zeros((), row_start.dtype)
            return lax.dynamic_update_slice(row_cache, row_kv, (row_start, z, z))

        k8 = jax.vmap(_write)(k8, kq.astype(k8.dtype), start)
        ks = jax.vmap(_write)(ks, kqs.astype(ks.dtype), start)
        v8 = jax.vmap(_write)(v8, vq.astype(v8.dtype), start)
        vs = jax.vmap(_write)(vs, vqs.astype(vs.dtype), start)
    elif variant == "write_at":
        rows = jnp.arange(b)
        k8 = k8.at[rows, start].set(kq[:, 0].astype(k8.dtype))
        ks = ks.at[rows, start].set(kqs[:, 0].astype(ks.dtype))
        v8 = v8.at[rows, start].set(vq[:, 0].astype(v8.dtype))
        vs = vs.at[rows, start].set(vqs[:, 0].astype(vs.dtype))

    if variant in ("full", "no_write", "write_at", "attn_i8"):
        qg = q.reshape(b, 1, NKV, NH // NKV, HD)
        key_pos = jnp.arange(WINDOW)
        valid = key_pos[None, None, :] <= start[:, None, None]
        mask = jnp.where(valid, 0.0, -1e9).astype(jnp.float32)[:, None]
        kscale = jnp.moveaxis(ks[:, :WINDOW, :, 0], 1, 2)[:, :, None, None, :]
        vscale = jnp.moveaxis(vs[:, :WINDOW, :, 0], 1, 2)[:, :, None, None, :]
        if variant == "attn_i8":
            # int8 x int8 -> int32 on the MXU: q quantized per (row, head);
            # the int8 cache is contracted RAW — no bf16 window copy.
            from tpumlops.models.quantization import quantize_tensor

            qq = quantize_tensor(qg, axis=-1)
            q8a, qs = qq["q8"], qq["scale"]  # [b,1,NKV,G,HD], [...,1]
            scores = jax.lax.dot_general(
                q8a, k8[:, :WINDOW],
                (((4,), (3,)), ((0, 2), (0, 2))),
                preferred_element_type=jnp.int32,
            )  # [b, NKV, 1(s), G, W]
            scores = scores.astype(jnp.float32).transpose(0, 1, 3, 2, 4)
            # fold q's per-(row, head) scale: [b,1,NKV,G,1] -> [b,NKV,G,1,1]
            scores = scores * qs.transpose(0, 2, 3, 1, 4)
            scores = scores / jnp.sqrt(jnp.float32(HD))
            scores = scores * kscale + mask[:, None]
            probs = jax.nn.softmax(scores, axis=-1)
            probs = probs * vscale
            pq = quantize_tensor(probs, axis=-1)
            p8, ps = pq["q8"], pq["scale"]  # [b,NKV,G,1,W]
            ctx = jax.lax.dot_general(
                p8, v8[:, :WINDOW],
                (((4,), (1,)), ((0, 1), (0, 2))),
                preferred_element_type=jnp.int32,
            )  # [b, NKV, G, 1, HD]
            ctx = ctx.astype(jnp.float32) * ps
            ctx = ctx.astype(x.dtype).transpose(0, 3, 1, 2, 4).reshape(b, NH * HD)
        else:
            kw = k8[:, :WINDOW]
            scores = jnp.einsum(
                "bqngd,bknd->bngqk", qg, kw.astype(x.dtype),
                preferred_element_type=jnp.float32,
            ) / jnp.sqrt(jnp.float32(HD))
            scores = scores * kscale + mask[:, None]
            probs = jax.nn.softmax(scores, axis=-1)
            probs = (probs * vscale).astype(x.dtype)
            ctx = jnp.einsum(
                "bngqk,bknd->bqngd", probs, v8[:, :WINDOW].astype(x.dtype)
            ).reshape(b, NH * HD)
    else:
        ctx = q.reshape(b, NH * HD)

    attn = jnp.matmul(ctx, deq(p["o"], x.dtype), preferred_element_type=jnp.float32)
    x = x + attn.astype(x.dtype).reshape(b, H)
    g = jnp.matmul(x, deq(p["gate"], x.dtype), preferred_element_type=jnp.float32)
    u = jnp.matmul(x, deq(p["up"], x.dtype), preferred_element_type=jnp.float32)
    act = (jax.nn.silu(g) * u).astype(x.dtype)
    d = jnp.matmul(act, deq(p["down"], x.dtype), preferred_element_type=jnp.float32)
    return (x + d.astype(x.dtype)), k8, ks, v8, vs


results = {}
params = make_weights(jax.random.key(0))
for b in (8, 32):
    start = jnp.full((b,), POS, jnp.int32)
    k8 = jnp.zeros((b, CAP, NKV, HD), jnp.int8)
    ks = jnp.zeros((b, CAP, NKV, 1), jnp.float32)
    v8 = jnp.zeros((b, CAP, NKV, HD), jnp.int8)
    vs = jnp.zeros((b, CAP, NKV, 1), jnp.float32)
    x0 = jax.random.normal(jax.random.key(1), (b, H), jnp.bfloat16)

    for variant in ("full", "write_at", "attn_i8", "no_write", "no_attn", "matmuls"):
        def step(p, carry, variant=variant):
            x, k8, ks, v8, vs = carry
            x, k8, ks, v8, vs = layer(p, x, k8, ks, v8, vs, start, variant)
            return (x, k8, ks, v8, vs), x[0, 0]

        def carry_at(i, b=b, x0=x0, k8=k8, ks=ks, v8=v8, vs=vs):
            return (x0 + jnp.bfloat16(0.01) * i, k8, ks, v8, vs)

        try:
            t0 = time.time()
            p50 = _scan_delta_timed(step, carry_at, runs=6, n1=8, n2=8 + L * 8,
                                    params=params)[50]
            # per-"model-step" equivalent: x L layers
            results[f"b{b}_{variant}_ms_per_24layers"] = round(p50 * L * 1000, 3)
            print(f"b{b} {variant}: {p50 * L * 1000:.3f} ms/24-layer-step "
                  f"({time.time() - t0:.0f}s)", flush=True)
        except Exception as e:
            results[f"b{b}_{variant}"] = f"{type(e).__name__}: {e}"[:100]
            print(f"b{b} {variant}: FAILED {e}", flush=True)

print(json.dumps(results))
