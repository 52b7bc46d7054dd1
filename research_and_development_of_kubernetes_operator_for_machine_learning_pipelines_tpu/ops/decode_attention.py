"""Fused ragged-decode attention over an int8 KV window (Pallas, TPU).

One decode step's attention for one layer is, in XLA, ~15 small ops per
layer: two dequant-scale transposes, two einsums, mask add, self-term
concat, softmax, weighted-sum split.  Each reduce breaks fusion, and at
single-token shapes the per-op latency — not bandwidth — dominates
(round-4 profile: a weights-only decode step ran ~3x the int8 stream
floor with the GEMMs themselves measured at 76-87% of peak, leaving
~90 us/layer of elementwise soup).  This kernel collapses the block to
ONE program per (slot, kv-head): both MXU dots back-to-back over the
VMEM-resident K/V window, the int8 scales folded into score/probability
rows (exact — see below), the mask added in-register, and the current
token's self-term joined into the softmax without a concat.

Exactness of the scale folding (same algebra as ``models.llama._qmatmul``):
the cache scale is per (position, kv-head) over head_dim, so

  q . (k8[w] * ks[w]) == (q . k8[w]) * ks[w]          (score row scale)
  sum_w p[w] * (v8[w] * vs[w]) == (p * vs) @ v8        (prob row scale)

— int8 values convert exactly to f32, so the kernel is bit-compatible
with dequantize-then-attend up to f32 summation order.

Layouts (B slots, W window, NKV kv heads, G = heads/kv_head, D head_dim):

  q       [B, NKV, G, D]   current token's queries, grouped by kv head
  k8, v8  [B, NKV, W, D]   int8 cache window, one (slot, head)'s window
                           contiguous (the caller transposes the
                           cache's [B, W, NKV, D] slab)
  ks, vs  [B, NKV, W, 1]   f32 scales, transposed likewise (the trailing
                           1 keeps the block tile-legal)
  k_self  [B, NKV, 1, D]   current token's K/V (exact, never quantized)
  v_self  [B, NKV, 1, D]
  mask    [B, 1, W]        f32 additive bias (0 keep / large negative
                           drop — any magnitude that underflows exp()
                           to 0 in f32; the production caller
                           ``decode_ragged`` passes -1e9),
                           STRICT: position w < lengths[b]
  out     [B, NKV, G, D]   f32

Reference behavior is pinned against the XLA path in
``tests/test_ops.py`` (interpret mode, so the parity runs on CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

def _decode_attn_kernel_mxu(q_ref, k_ref, ks_ref, v_ref, vs_ref,
                            kself_ref, vself_ref, mask_ref, o_ref,
                            *, scale, bb):
    """MXU decode-attention program over ``bb`` slots of one kv head.

    ``bb == 1`` is the classic one-program-per-(slot, head) shape; the
    slot-batched variant unrolls ``bb`` slots back-to-back in VMEM so
    the grid (and its per-program overhead) shrinks by ``bb``.  Measured
    on a v5e at 1.35B geometry the distinction barely matters — both sit
    ~2.3x above XLA's batched-dot emitter because the cost is the f32
    [G,W]x[W,D] dots at G=1, not the grid (scripts/ab_attention.py;
    PERF.md round 5) — but the two spellings stay A/B-able from ONE
    kernel body so a numerics fix cannot diverge them."""
    for t in range(bb):
        q = q_ref[t, 0].astype(jnp.float32) * scale       # [G, D]
        k = k_ref[t, 0].astype(jnp.float32)               # [W, D]
        ks = ks_ref[t, 0, :, 0].astype(jnp.float32)       # [W]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                 # [G, W]
        s = s * ks[None, :] + mask_ref[t]

        k_self = kself_ref[t, 0].astype(jnp.float32)      # [1, D]
        s_self = jnp.sum(q * k_self, axis=-1, keepdims=True)

        m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), s_self)
        p = jnp.exp(s - m)
        p_self = jnp.exp(s_self - m)
        denom = jnp.sum(p, axis=-1, keepdims=True) + p_self

        vs = vs_ref[t, 0, :, 0].astype(jnp.float32)
        v = v_ref[t, 0].astype(jnp.float32)
        ctx = jax.lax.dot_general(
            p * vs[None, :], v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        v_self = vself_ref[t, 0].astype(jnp.float32)
        o_ref[t, 0] = (ctx + p_self * v_self) / denom


def _slot_block(b: int, w: int) -> int:
    """Largest power-of-two slot block (<=8) dividing ``b`` whose window
    rows fit VMEM: the per-position scale planes [bb, 1, W, 1] pad to a
    full lane each, so the footprint grows with bb x W — the v5e compiler
    accepts 8 slots up to W=512 and refuses them at W=1024 (20 MiB
    against the 16 MiB scoped limit; tests/test_tpu_compile.py), hence
    bb x W <= 4096.  Smaller b falls back so any slot count lowers."""
    for bb in (8, 4, 2):
        if b % bb == 0 and bb * w <= 4096:
            return bb
    return 1


def _mxu_decode_call(q, k8, ks, v8, vs, k_self, v_self, mask,
                     *, bb, interpret):
    """Shared pallas_call wrapper for the MXU kernel at block size ``bb``."""
    b, nkv, g, d = q.shape
    w = k8.shape[2]
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(_decode_attn_kernel_mxu, scale=scale, bb=bb)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, d), jnp.float32),
        grid=(b // bb, nkv),
        in_specs=[
            pl.BlockSpec((bb, 1, g, d), lambda i, j: (i, j, 0, 0)),   # q
            pl.BlockSpec((bb, 1, w, d), lambda i, j: (i, j, 0, 0)),   # k8
            pl.BlockSpec((bb, 1, w, 1), lambda i, j: (i, j, 0, 0)),   # ks
            pl.BlockSpec((bb, 1, w, d), lambda i, j: (i, j, 0, 0)),   # v8
            pl.BlockSpec((bb, 1, w, 1), lambda i, j: (i, j, 0, 0)),   # vs
            pl.BlockSpec((bb, 1, 1, d), lambda i, j: (i, j, 0, 0)),   # k_self
            pl.BlockSpec((bb, 1, 1, d), lambda i, j: (i, j, 0, 0)),   # v_self
            pl.BlockSpec((bb, 1, w), lambda i, j: (i, 0, 0)),         # mask
        ],
        out_specs=pl.BlockSpec((bb, 1, g, d), lambda i, j: (i, j, 0, 0)),
        interpret=interpret,
    )(q, k8, ks, v8, vs, k_self, v_self, mask)


def decode_attention(
    q: jax.Array,
    k8: jax.Array,
    ks: jax.Array,
    v8: jax.Array,
    vs: jax.Array,
    k_self: jax.Array,
    v_self: jax.Array,
    mask: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Fused int8-KV decode attention, one program per (slot, kv head);
    see module docstring for layouts."""
    return _mxu_decode_call(
        q, k8, ks, v8, vs, k_self, v_self, mask, bb=1, interpret=interpret)


def decode_attention_batched(
    q: jax.Array,
    k8: jax.Array,
    ks: jax.Array,
    v8: jax.Array,
    vs: jax.Array,
    k_self: jax.Array,
    v_self: jax.Array,
    mask: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Fused int8-KV decode attention, ``_slot_block(b)`` slots per grid
    program (same contract and kernel body as :func:`decode_attention`)."""
    return _mxu_decode_call(
        q, k8, ks, v8, vs, k_self, v_self, mask,
        bb=_slot_block(q.shape[0], k8.shape[2]), interpret=interpret)


_LANE = 128  # VPU lane width: W is retiled as [W // _LANE, _LANE]


def _decode_attn_kernel_vpu(q_ref, k_ref, ks_ref, v_ref, vs_ref,
                            kself_ref, vself_ref, mask_ref, o_ref,
                            *, scale, bb, wg):
    """VPU formulation for G == 1 (num_heads == num_kv_heads) decode.

    Why not the MXU: with one query row per kv head the score/ctx dots
    are [1,W]x[W,D] matvecs, and the MXU's tiling floor (~512 cycles per
    pass regardless of M) makes attention cost ~0.5 us x slots x heads
    x 2 dots x layers — 24 ms/step at 1.35B/64 slots, ~10x the actual
    HBM traffic cost, capping decode bw_util at ~0.2 (measured: both
    XLA's batched dot emitter and the MXU pallas kernels sit at this
    floor, scripts/ab_attention.py).  Decode attention at G=1 is ~1
    FLOP/byte — bandwidth-bound — so the VPU's elementwise
    multiply+reduce does the EXACT work with no padding waste and can
    keep pace with the DMA stream.  No dot_general appears in this
    kernel: Mosaic lowers the multiply+reduce chains to vector ops,
    which is the point.

    Mosaic constraints shape the spelling: every intermediate stays
    >= 2-D with W retiled as [wg, 128] so softmax runs dense across
    lanes, and every reduction is a keepdims reduction over one axis at
    a time (scalar-form reductions of 1-D vectors fail to lower with
    "Not implemented: Offset change").  The scale/mask operands arrive
    pre-retiled from the wrapper."""
    for t in range(bb):
        q2 = q_ref[t, 0].astype(jnp.float32) * scale       # [1, D]
        d = q2.shape[1]
        k3 = k_ref[t, 0].astype(jnp.float32).reshape(wg, _LANE, d)
        s3 = jnp.sum(k3 * q2[None], axis=-1)               # [Wg, 128]
        s3 = s3 * ks_ref[t, 0].astype(jnp.float32) + mask_ref[t]

        kself2 = kself_ref[t, 0].astype(jnp.float32)       # [1, D]
        s_self = jnp.sum(q2 * kself2, axis=-1, keepdims=True)  # [1, 1]

        m = jnp.max(jnp.max(s3, axis=1, keepdims=True), axis=0, keepdims=True)
        m = jnp.maximum(m, s_self)                         # [1, 1]
        p3 = jnp.exp(s3 - m)                               # [Wg, 128]
        p_self = jnp.exp(s_self - m)                       # [1, 1]
        denom = jnp.sum(
            jnp.sum(p3, axis=1, keepdims=True), axis=0, keepdims=True
        ) + p_self                                         # [1, 1]

        pv3 = p3 * vs_ref[t, 0].astype(jnp.float32)        # [Wg, 128]
        v3 = v_ref[t, 0].astype(jnp.float32).reshape(wg, _LANE, d)
        acc = jnp.sum(pv3[:, :, None] * v3, axis=0)        # [128, D]
        ctx = jnp.sum(acc, axis=0, keepdims=True)          # [1, D]
        vself2 = vself_ref[t, 0].astype(jnp.float32)       # [1, D]
        o_ref[t, 0] = (ctx + p_self * vself2) / denom


def decode_attention_vpu(
    q: jax.Array,
    k8: jax.Array,
    ks: jax.Array,
    v8: jax.Array,
    vs: jax.Array,
    k_self: jax.Array,
    v_self: jax.Array,
    mask: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Fused int8-KV decode attention on the VPU; requires G == 1 and
    W % 128 == 0 (serving windows are powers of two >= 128).

    Same contract as :func:`decode_attention` (see the kernel docstring
    for the roofline argument)."""
    b, nkv, g, d = q.shape
    if g != 1:
        raise ValueError(f"decode_attention_vpu requires G == 1, got {g}")
    w = k8.shape[2]
    if w % _LANE != 0:
        raise ValueError(
            f"decode_attention_vpu requires W % {_LANE} == 0, got {w}")
    wg = w // _LANE
    scale = 1.0 / (d ** 0.5)
    bb = _slot_block(b, w)
    # Retile the per-position vectors [.., W, 1] -> [.., Wg, 128] (and
    # the mask [B, 1, W] -> [B, Wg, 128]) on the XLA side: pure reshapes
    # of tiny arrays, giving the kernel lane-dense softmax layouts.
    ks_t = ks[..., 0].reshape(b, nkv, wg, _LANE)
    vs_t = vs[..., 0].reshape(b, nkv, wg, _LANE)
    mask_t = mask.reshape(b, wg, _LANE)
    kernel = functools.partial(
        _decode_attn_kernel_vpu, scale=scale, bb=bb, wg=wg)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, d), jnp.float32),
        grid=(b // bb, nkv),
        in_specs=[
            pl.BlockSpec((bb, 1, g, d), lambda i, j: (i, j, 0, 0)),    # q
            pl.BlockSpec((bb, 1, w, d), lambda i, j: (i, j, 0, 0)),    # k8
            pl.BlockSpec((bb, 1, wg, _LANE), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((bb, 1, w, d), lambda i, j: (i, j, 0, 0)),    # v8
            pl.BlockSpec((bb, 1, wg, _LANE), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((bb, 1, 1, d), lambda i, j: (i, j, 0, 0)),    # k_self
            pl.BlockSpec((bb, 1, 1, d), lambda i, j: (i, j, 0, 0)),    # v_self
            pl.BlockSpec((bb, wg, _LANE), lambda i, j: (i, 0, 0)),     # mask
        ],
        out_specs=pl.BlockSpec((bb, 1, g, d), lambda i, j: (i, j, 0, 0)),
        interpret=interpret,
    )(q, k8, ks_t, v8, vs_t, k_self, v_self, mask_t)


def decode_attention_reference(
    q, k8, ks, v8, vs, k_self, v_self, mask
) -> jax.Array:
    """Pure-XLA oracle with the identical contract (f32 everywhere)."""
    d = q.shape[-1]
    qf = q.astype(jnp.float32) / (d ** 0.5)
    s = jnp.einsum("bngd,bnwd->bngw", qf, k8.astype(jnp.float32))
    s = s * ks[..., 0][:, :, None, :] + mask[:, :, None, :]
    s_self = jnp.einsum(
        "bngd,bnsd->bngs", qf, k_self.astype(jnp.float32)
    )
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), s_self)
    p = jnp.exp(s - m)
    p_self = jnp.exp(s_self - m)
    denom = jnp.sum(p, axis=-1, keepdims=True) + p_self
    ctx = jnp.einsum("bngw,bnwd->bngd", p * vs[..., 0][:, :, None, :],
                     v8.astype(jnp.float32))
    ctx = ctx + p_self * v_self.astype(jnp.float32)
    return ctx / denom
