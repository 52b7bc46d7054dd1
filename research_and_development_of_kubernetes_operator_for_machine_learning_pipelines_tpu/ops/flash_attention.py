"""Flash attention for TPU (Pallas) with an XLA reference fallback.

Blockwise online-softmax attention: each grid program owns one query tile
in VMEM and streams key/value tiles through it, maintaining running max and
denominator — the score matrix never materializes, so memory is O(S) and
the two matmuls per tile run back-to-back on the MXU.

Layout: [batch, heads, seq, head_dim]; grid is (batch*heads, q_tiles).
Tiles default to 128x128 (the MXU native tile).  Causal masking and a
static ``kv_len`` (for padded keys) fold into the tile mask via iota.

Size limit: each program keeps the WHOLE padded K and V of its
(batch, head) resident in VMEM (the BlockSpecs below), double-buffered
by the pipeline, so key length is bounded by fast memory, not HBM.  The
v5e compiler (compile-only, tests/test_tpu_compile.py) accepts 14 Ki
bf16 keys at head_dim 128 (14 MiB resident) and refuses 16 Ki (16 MiB):
:func:`flash_attention` raises :class:`FlashAttentionVmemError` past
:data:`KV_VMEM_BUDGET_BYTES` instead of reaching the compiler.  Longer
sequences need K/V tiled over a grid axis (ROADMAP D4 decides whether
this kernel earns that).

Not on the serving path: the model zoo keeps XLA attention (the
[B,H,S,D] interface forces layout transposes XLA's fused QKV chain
avoids); no timing of this kernel exists on today's code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -1e30

# K + V, double-buffered, resident per program (see module docstring):
# the largest footprint the v5e compiler was seen to accept.
KV_VMEM_BUDGET_BYTES = 14 * 2**20
_LANE = 128


class FlashAttentionVmemError(ValueError):
    """Key length whose resident K/V exceed :data:`KV_VMEM_BUDGET_BYTES`."""


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    kv_len: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Pure-XLA oracle: [B,H,S,D] x [B,H,T,D] -> [B,H,S,D]."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    t = k.shape[2]
    if kv_len is not None:
        key_ok = jnp.arange(t) < kv_len
        s = jnp.where(key_ok[None, None, None, :], s, NEG_INF)
    if causal:
        qi = jnp.arange(q.shape[2])
        ki = jnp.arange(t)
        s = jnp.where(ki[None, None, None, :] <= qi[None, None, :, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool, kv_len: int, scale: float
):
    q = q_ref[0].astype(jnp.float32) * scale  # [BQ, D]
    bq = q.shape[0]
    total_k = k_ref.shape[1]
    nk = total_k // block_k
    qi0 = pl.program_id(1) * bq

    def body(j, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)  # [BQ, BK]

        k_idx = j * block_k + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        mask = k_idx < kv_len
        if causal:
            q_idx = qi0 + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            mask = mask & (k_idx <= q_idx)
        s = jnp.where(mask, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jnp.dot(p, v_blk, preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, q_ref.shape[2]), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = lax.fori_loop(0, nk, body, (acc0, m0, l0))
    # Fully-masked rows (l == 0) produce 0 output instead of NaN.
    out = acc / jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = out.astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    kv_len: int | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention over [B,H,S,D]; pads S/T internally to tile multiples.

    ``kv_len`` masks trailing (padded) keys; defaults to the true key length.
    """
    b, h, s_q, d = q.shape
    t_k = k.shape[2]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    kv_len = int(kv_len) if kv_len is not None else t_k

    block_q = min(block_q, _round_up(s_q, 8))
    block_k = min(block_k, _round_up(t_k, 8))
    s_pad = _round_up(s_q, block_q)
    t_pad = _round_up(t_k, block_k)
    # 2 arrays (K, V) x 2 pipeline buffers, minor dim padded to a lane.
    resident = 4 * t_pad * _round_up(d, _LANE) * k.dtype.itemsize
    if not interpret and resident > KV_VMEM_BUDGET_BYTES:
        raise FlashAttentionVmemError(
            f"flash_attention keeps all {t_pad} padded keys of a head "
            f"resident in VMEM: {resident / 2**20:.1f} MiB of K/V "
            f"({k.dtype}, head_dim {d}) exceeds the "
            f"{KV_VMEM_BUDGET_BYTES / 2**20:.0f} MiB the TPU compiler accepts"
        )
    qp = _pad_seq(q, s_pad)
    kp = _pad_seq(k, t_pad)
    vp = _pad_seq(v, t_pad)

    qf = qp.reshape(b * h, s_pad, d)
    kf = kp.reshape(b * h, t_pad, d)
    vf = vp.reshape(b * h, t_pad, d)

    grid = (b * h, s_pad // block_q)
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, causal=causal, kv_len=kv_len, scale=scale
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, d), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, t_pad, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, t_pad, d), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s_pad, d)[:, :, :s_q, :]


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _pad_seq(x: jax.Array, target: int) -> jax.Array:
    pad = target - x.shape[2]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
