"""Prefill attention of grouped queries over rows as they lie (Pallas,
TPU): a key block's scores never leave VMEM.

``gqa_prefill_attention(q [B,S,KV,R,D], keys [B,T,KV*D], values
[B,T,KV*D], start, written, key_start) -> [B,S,KV*R*D]``: the softmax core
of a prefill call of the GQA-and-experts family (``models/gdn_moe.py``),
both layer kinds.  Query ``i`` sits at position ``start + i``, key ``k``
at ``key_start + k``; a query sees a key at or before its own position,
and with a ``window`` only one at a position ``>= 0`` and less than
``window`` before its own.  Only the first ``written`` keys are walked, a
block of ``key_block`` at a time.  Off the TPU, and at shapes the tiles do
not take, it is the caller's ``fallback`` (``gdn_moe._gqa_blocks``'s
einsum body), which is also the oracle of ``tests/test_ops.py``.

Why a kernel: XLA's blocked softmax writes a key block's float32 scores
``[KV, R, queries, keys]`` to HBM and reads them back for the masked
maximum, the exponent, the sum and the cast (50 MB a block at 48 heads x
512 x 512; a sliding layer's one pass over 1023 keys at 72 heads, 151
MB).  Here:

- a grid step is (batch row, group of query heads of one KV head, query
  tile, key block); the key blocks are the innermost axis and its extent
  is the TRACED count of blocks that hold a written position;
- the keys and values are read as the cache holds them, a KV head's
  ``D`` lanes of a ``[B, T, KV*D]`` row, once a step for the step's
  heads; the queries and the output keep the projections' layout, heads
  side by side on the lanes, so nothing is transposed in front of the
  kernel or behind it;
- each head of the group scores the block, masks it, and updates its
  running maximum, sum and float32 accumulator, which live in scratch
  across the key blocks; scores and probabilities exist for one head at a
  time, 1 MB each at 512 x 512;
- the mask comes from positions (two scalars and the static ``window``),
  not from a ``[S, T]`` tensor, computed once a step for the group's
  heads; a block every query sees whole skips it;
- the group is the largest divisor of ``R`` whose step stays under the
  scoped VMEM (:func:`heads_per_step`).

The mathematics is the fallback's to the operation: float32 scores times
``scale``, masked maximum from ``-1e30``, ``p = exp(score - max)`` zeroed
where masked, probabilities cast to the operands' type before the value
matmul, ``acc / max(sum, 1e-30)`` at the end.

``_fused`` is jitted so that a program's layers of one shape are traced
once a process and lowered once a program.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .prefill_attention import _LANE, _LOW, _NT, _VMEM_BUDGET, _query_tile


class Tiles(NamedTuple):
    """The static tiling of one call."""

    queries: int  # queries a grid step attends
    keys: int  # keys a grid step attends
    heads: int  # query heads a grid step attends, a divisor of the group
    stat: int  # lanes a running maximum / sum is held on (1: a column)


def heads_per_step(r: int, tq: int, kb: int, d: int, itemsize: int) -> int:
    """Query heads of one KV head a grid step attends: the largest divisor
    of ``r`` whose blocks (double-buffered), scratch and one head's
    temporaries stay under the VMEM budget.  More heads a step read the
    key block and build the mask less often."""
    def vmem(g):
        blocks = 2 * itemsize * (2 * tq * g * d + 2 * kb * d)  # q, out; k, v
        scratch = 4 * g * tq * (d + 2 * _LANE)  # acc, max, sum
        temps = 4 * (3 * tq * kb + tq * d) + 4 * tq * kb  # scores, exp, p; p @ v; mask
        return blocks + scratch + temps

    return max(g for g in range(1, r + 1)
               if r % g == 0 and (g == 1 or vmem(g) <= _VMEM_BUDGET))


def tiles_for(s: int, t: int, r: int, d: int, key_block: int, itemsize: int,
              aligned: bool = True) -> Tiles | None:
    """The tiling of a call of these static shapes, or ``None`` where the
    chip's layouts do not take one (``aligned``: a query tile of whole
    sublane groups, key blocks and head width whole lanes): a single-token
    step through ``forward``, a bucket under 16 tokens, a sliding call
    whose ring and chunk make no lane-wide block.  The interpreter
    (``aligned=False``) takes any shape."""
    if t % key_block:
        return None
    if not aligned:
        tq = _query_tile(s, 1)
        return Tiles(tq, key_block, heads_per_step(r, tq, key_block, d, itemsize), 1)
    tq = _query_tile(s, 32 // itemsize)
    if tq is None or key_block % _LANE or d % _LANE:
        return None
    return Tiles(tq, key_block, heads_per_step(r, tq, key_block, d, itemsize), _LANE)


def _core_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 tiles: Tiles, scale: float, window: int):
    tq, kb, g = tiles.queries, tiles.keys, tiles.heads
    d = k_ref.shape[-1]
    dt = q_ref.dtype
    i, j = pl.program_id(2), pl.program_id(3)

    def wide(x, n):  # a statistic [tq, stat] against a tile n lanes wide
        return x if tiles.stat == 1 else jnp.tile(x, (1, n // tiles.stat))

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _LOW, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q0 = pos_ref[1] + i * tq  # the tile's first query position
    k0 = pos_ref[2] + j * kb  # the block's first key position

    def attend(sees):
        k, v = k_ref[0], v_ref[0]
        for h in range(g):
            sc = lax.dot_general(q_ref[0, :, h * d:(h + 1) * d], k, _NT,
                                 preferred_element_type=jnp.float32) * scale
            top = m_ref[h]
            masked = sc if sees is None else jnp.where(sees, sc, _LOW)
            top2 = jnp.maximum(top, jnp.max(masked, axis=1, keepdims=True))
            p = jnp.exp(sc - wide(top2, kb))
            if sees is not None:
                p = jnp.where(sees, p, 0.0)
            keep = jnp.exp(top - top2)
            m_ref[h] = top2
            l_ref[h] = l_ref[h] * keep + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * wide(keep, d) + jnp.dot(
                p.astype(dt), v, preferred_element_type=jnp.float32)

    # Every query of the tile sees every key of the block: no mask.
    whole = (k0 >= 0) & (k0 + kb - 1 <= q0)
    if window:
        whole &= q0 + tq - 1 - k0 < window

    @pl.when(whole)
    def _():
        attend(None)

    @pl.when(jnp.logical_not(whole))
    def _():
        # qpos - kpos, a query a row and a key a column.
        ahead = (q0 - k0) + (lax.broadcasted_iota(jnp.int32, (tq, kb), 0)
                             - lax.broadcasted_iota(jnp.int32, (tq, kb), 1))
        sees = ahead >= 0
        if window:
            sees &= (ahead < window) & (
                lax.broadcasted_iota(jnp.int32, (tq, kb), 1) >= -k0)
        attend(sees)

    @pl.when(j == pos_ref[0] - 1)
    def _():
        for h in range(g):
            total = jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, :, h * d:(h + 1) * d] = (
                acc_ref[h] / wide(total, d)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tiles", "scale", "window", "interpret"))
def _fused(q, keys, values, pos, *, tiles: Tiles, scale: float, window: int,
           interpret: bool) -> jax.Array:
    b, s, nkv, r, d = q.shape
    dt = q.dtype
    tq, kb, g, stat = tiles
    per_kv = r // g  # grid steps a KV head's query heads take
    return pl.pallas_call(
        functools.partial(_core_kernel, tiles=tiles, scale=scale, window=window),
        out_shape=jax.ShapeDtypeStruct((b, s, nkv * r * d), dt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nkv * per_kv, s // tq, pos[0]),
            in_specs=[
                pl.BlockSpec((1, tq, g * d), lambda bi, n, i, j, p: (bi, i, n)),
                pl.BlockSpec((1, kb, d), lambda bi, n, i, j, p: (bi, j, n // per_kv)),
                pl.BlockSpec((1, kb, d), lambda bi, n, i, j, p: (bi, j, n // per_kv)),
            ],
            out_specs=pl.BlockSpec((1, tq, g * d), lambda bi, n, i, j, p: (bi, i, n)),
            scratch_shapes=[
                pltpu.VMEM((g, tq, stat), jnp.float32),
                pltpu.VMEM((g, tq, stat), jnp.float32),
                pltpu.VMEM((g, tq, d), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        name="gqa_prefill_attention",
        interpret=interpret,
    )(pos, q.reshape(b, s, nkv * r * d), keys.astype(dt), values.astype(dt))


def gqa_prefill_attention(
    q: jax.Array,
    keys: jax.Array,
    values: jax.Array,
    start,
    written,
    key_start,
    *,
    window: int,
    key_block: int,
    scale: float,
    fallback: Callable[..., jax.Array],
    interpret: bool = False,
) -> jax.Array:
    """Attention of ``S`` queries ``q`` [B,S,KV,R,D] at positions ``start
    ..`` over the first ``written`` of ``T`` keys at positions
    ``key_start ..`` (ints or traced scalars; ``written`` at least 1 and
    every query sees a key), in blocks of ``key_block``: causal, and with a
    ``window`` > 0 only keys at positions ``>= 0`` and ``< window`` before
    the query's.  ``fallback`` takes the six array arguments and computes
    the same; it runs off the TPU and wherever :func:`tiles_for` has no
    tiling.  Returns ``[B, S, KV*R*D]`` in the queries' type."""
    _b, s, _nkv, r, d = q.shape
    tiles = tiles_for(s, keys.shape[1], r, d, key_block, q.dtype.itemsize,
                      aligned=not interpret)
    args = (q, keys, values, *(jnp.asarray(x, jnp.int32)
                               for x in (start, written, key_start)))
    if tiles is None:
        return fallback(*args)

    def fused(q, keys, values, start, written, key_start):
        pos = jnp.stack([(written + key_block - 1) // key_block, start, key_start])
        return _fused(q, keys, values, pos, tiles=tiles, scale=scale,
                      window=window, interpret=interpret)

    if interpret:
        return fused(*args)
    return lax.platform_dependent(*args, tpu=fused, default=fallback)
