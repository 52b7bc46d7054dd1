"""Weight-only int8 quantization (symmetric, per-output-channel).

Why: autoregressive decode is HBM-bandwidth-bound — every generated token
re-reads every weight matrix, so at serving batch sizes the time-per-token
floor is ``bytes(weights) / HBM_bandwidth``, not FLOPs (a BERT forward
pass is the opposite: compute-bound).
Storing the matmul weights as int8 halves the bytes read per token, which
halves the decode floor; the dequantize (int8 → bf16 multiply by a
per-channel scale) is elementwise work XLA fuses into the matmul's operand
read, so no bf16 copy of the weight ever lands in HBM.

Scheme: for a weight ``w [..., in, out]`` the scale is
``max|w| / 127`` reduced over the ``in`` axis (per output channel, per
stacked layer), kept at the same rank so sharding specs line up with the
original weight's logical axes.  Symmetric (no zero point): one fused
multiply on the read path, and LLM weight distributions are near-centered.

Quantized leaves are plain dicts ``{"q8": int8, "scale": f32}`` — ordinary
pytree nodes, so they travel through ``lax.scan``, ``jit`` donation, and
checkpointing unchanged.  ``models/llama.py`` consumes either form via its
``_mat`` helper; norms and the embedding table stay full-precision (the
embedding is a gather — only B rows are read per step — and norm vectors
are noise-sensitive and tiny).

Measured on a v5e chip (1.35B-param shape, B=8 slots, capacity 1024):
bf16 13.3 ms/step vs int8 11.5 ms/step — 1.16x.  The gap to the 2x byte
ratio is the KV cache: decode also streams the full static-capacity cache
(~1.6 GiB here) every step, which int8 weights don't shrink.  The speedup
grows with model size (7B: ~13.5 GB weights vs the same cache traffic);
enable per model via the CRD's ``spec.tpu.quantize: int8``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def quantize_tensor(w: jax.Array, axis: int = -2) -> dict[str, jax.Array]:
    """Symmetric int8 with the |max| reduced over ``axis`` (kept at rank).

    ``axis=-2`` (default) is per-output-channel for ``[..., in, out]``
    weights; the KV cache uses ``axis=-1`` (per position+head over
    head_dim).  ONE implementation of the scheme — epsilon, rounding, and
    clip live here only."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q8 = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return {"q8": q8, "scale": scale}


def dequantize_tensor(q: dict[str, jax.Array], dtype=jnp.bfloat16) -> jax.Array:
    # Multiply in f32 and round ONCE into the target dtype: casting the
    # scale to bf16 first would round twice (~2x the weight error) for the
    # same fused HBM traffic.
    return (q["q8"].astype(jnp.float32) * q["scale"]).astype(dtype)


def is_quantized(leaf: Any) -> bool:
    return isinstance(leaf, dict) and "q8" in leaf and "scale" in leaf


# Llama matmul weights worth quantizing: everything the decode step streams
# from HBM in full.  Norm vectors and the embedding gather stay as-is.
_LLAMA_LAYER_MATS = ("q", "k", "v", "o", "gate", "up", "down")


def quantize_llama(params: dict) -> dict:
    """Return a params tree with layer matmuls + lm_head as int8 leaves.

    Runs under jit so sharded inputs produce identically-sharded q8/scale
    outputs (the reduction over the ``in`` axis inserts a collective when
    that axis is sharded — correct per-channel scales on every shard).
    """

    @jax.jit
    def _q(params):
        out = dict(params)
        out["layers"] = dict(params["layers"])
        for name in _LLAMA_LAYER_MATS:
            out["layers"][name] = quantize_tensor(params["layers"][name])
        out["lm_head"] = quantize_tensor(params["lm_head"])
        return out

    return _q(params)


def dense_q8(x: jax.Array, qw: dict, b: jax.Array | None = None) -> jax.Array:
    """Dynamic-activation int8 matmul: ``x [..., in] @ q8 [in, out]``.

    Unlike the weight-only scheme above (a bandwidth lever for decode),
    this feeds the MXU actual int8 operands — on v5e the int8 systolic
    path has 2x the bf16 throughput, the lever for a COMPUTE-bound
    workload like BERT prefill.  Activations quantize per row (per
    token): symmetric, scale = max|x| / 127 over the contraction axis,
    computed on the fly — XLA fuses it into the matmul read (round-3
    ablation: the dynamic-quant GEMM ladder runs at 188 TFLOP/s, ~0 cost
    over pre-quantized operands).  The
    int32 accumulator rescales by (a_scale x w_scale) in f32, so the
    only approximation is the two roundings to int8.  End to end the
    int8 path pairs with tanh-GELU (loader default under quantize: int8
    — see common.gelu_tanh) for ~1.4x over bf16-erf at b32/s128.
    """
    qa = quantize_tensor(x, axis=-1)  # per-row (per-token) scales
    x8, a_scale = qa["q8"], qa["scale"]
    y = jax.lax.dot_general(
        x8,
        qw["q8"],
        (((x8.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    # w scale was reduced over axis=-2 with keepdims -> shape [1, out].
    y32 = y.astype(jnp.float32) * a_scale * qw["scale"].reshape(-1)
    if b is not None:
        y32 = y32 + b.astype(jnp.float32)
    return y32.astype(x.dtype)


# BERT dense layers worth int8-ing: the six big matmuls per encoder layer.
# ~97% of classify FLOPs at b32/s128 live here (12*S*H^2 vs 2*S^2*H for the
# attention einsums); pooler/classifier/embeddings are noise-sensitive and
# a rounding error away from flipping a logit, for no measurable FLOPs.
_BERT_LAYER_MATS = (("attn", "q"), ("attn", "k"), ("attn", "v"), ("attn", "o"),
                    ("mlp", "up"), ("mlp", "down"))


def quantize_bert(params: dict) -> dict:
    """Params tree with each encoder layer's dense weights as int8 leaves.

    The per-dense dicts keep their ``b`` (bias) and gain ``{"q8","scale"}``
    in place of ``w``; ``models/bert.py``'s dense dispatch routes such
    layers through :func:`dense_q8`.
    """

    @jax.jit
    def _q(params):
        out = dict(params)
        layers = []
        for layer in params["layers"]:
            new_layer = {k: dict(v) for k, v in layer.items()}
            for group, name in _BERT_LAYER_MATS:
                d = dict(new_layer[group][name])
                d["w"] = quantize_tensor(d["w"])
                new_layer[group][name] = d
            layers.append(new_layer)
        out["layers"] = layers
        return out

    return _q(params)


def quantized_bytes(params: Any) -> int:
    """Total parameter bytes as stored (int8 leaves count 1 byte/elem)."""
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.size * leaf.dtype.itemsize
    return total
