"""Prefill attention over a latent cache (Pallas, TPU): a key block's
scores never leave VMEM.

``prefill_attention(q_nope [B,S,NH,nope], q_rope [B,S,NH,rope], k_rope
[B,T,R], latent [B,T,rank], w_kvb [rank, NH*(nope+v)], sees [B|1,S,T],
n_blocks) -> [B,S,NH*v]``: the softmax core of a prefill call of the
latent-attention family.  Keys and values are expanded from the latent
(``[k_nope | v] = latent W_kvb``, a head at a time), a query scores
``q_nope . k_nope + q_rope . k_rope`` over the keys ``sees`` lets it see,
and only the first ``n_blocks`` blocks of ``key_block`` keys are walked.
Off the TPU, and at shapes the tiles do not fit, it is the caller's
``fallback`` (``models/mla_moe.py::_attn_blocks``'s einsum body), which
is also the oracle of ``tests/test_ops.py``.

Why a kernel: XLA's blocked softmax writes a key block's float32 scores
``[heads, queries, keys]`` to HBM and reads them back for the masked
maximum, the exponent, the sum and the cast (134 MB a block at 128 heads
x 512 x 512): 0.9 ms a block against 0.2 ms of matmul at peak, 16.7 of a
dots3-note chunk's 37.9 ms (PERF.md 5, PR 33).  Here:

- a grid step is (batch row, group of heads, query tile, key block); the
  key blocks are the innermost axis and its extent is the TRACED
  ``n_blocks``, so a block no query can see (behind the written
  positions) costs nothing, not even a grid step;
- in a step each head of the group expands its keys and values from the
  block's latent in VMEM (bf16 operands, float32 accumulation, rounded
  to the operands' type as XLA's expansion is), scores them, masks, and
  updates the running maximum, sum and float32 accumulator, which live
  in scratch across the key blocks; scores and probabilities exist for
  one head at a time, 1 MB each at 512 x 512;
- the ``sees`` tile is int8 ``[queries, keys]``, read once a step and
  shared by the group's heads, and the block's latent and RoPE keys are
  read once a step too: the group is as large as the scoped VMEM allows
  (:func:`heads_per_step`);
- queries, weights and output keep the layouts the projections give
  them, heads side by side on the lanes (``[B, S, NH*D]``, ``[rank,
  NH*D]``), each head's part at a multiple of 128 lanes, so nothing is
  transposed in front of the kernel or behind it; a ``nope`` that is no
  multiple of 128 (192) is zero-padded to one, which costs the MXU
  nothing (its passes are 128 deep).

The mathematics is the fallback's to the operation: float32 scores times
``scale``, masked maximum from ``-1e30``, ``p = exp(score - max)`` zeroed
where masked, probabilities cast to the operands' type before the value
matmul, ``acc / max(sum, 1e-30)`` at the end.  The schedule is that of
``jax.experimental.pallas.ops.tpu.flash_attention`` without its bias
tensor (a ``[b, h, q, k]`` float32: the thing removed here) and with the
latent's expansion inside.

``_fused`` is jitted so that a program's layers of one shape are traced
once a process and lowered once a program (PERF.md 6, PR 28: lowering
every kernel of every program anew added 13.5 s to a cached boot).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
# What ``heads_per_step``'s count of a grid step's VMEM may reach.  The
# compiler gives a kernel 16 MiB of scoped VMEM; it took the steps this
# counts at 13.9 and 14.2 MB (two sliding heads, four full ones) and
# refused those it counts at 20.7 and 22.5 (tests/test_tpu_compile.py
# compiles the three published geometries).  Four heads a step ran 3 %
# faster than two on the chip (PERF.md 6, PR 34).
_VMEM_BUDGET = 14 * 2**20
_QUERY_TILE = 512  # queries a grid step attends at most
_MAX_HEADS = 8  # heads a grid step attends at most: its body is unrolled over them
_LOW = -1e30  # a masked score's stand-in under the running maximum
_NT = (((1,), (1,)), ((), ()))  # a [m, d] x [n, d] -> [m, n] contraction


class Tiles(NamedTuple):
    """The static tiling of one call."""

    queries: int  # queries a grid step attends
    keys: int  # keys a grid step attends
    heads: int  # heads a grid step attends
    nope: int  # a head's nope width as laid out (padded to the lanes)
    stat: int  # lanes a running maximum / sum is held on (1: a column)


def _query_tile(s: int, sublanes: int) -> int | None:
    """All ``s`` queries up to 512, else the largest divisor of ``s`` at
    or under 512; a multiple of the operands' sublanes, or none."""
    for tq in range(min(s, _QUERY_TILE), 0, -1):
        if s % tq == 0:
            return tq if tq % sublanes == 0 else None
    return None


def heads_per_step(nh: int, tq: int, kb: int, rank: int, dq: int, dh: int,
                   dv: int, itemsize: int) -> int:
    """Heads a grid step attends: the largest power of two dividing ``nh``
    whose blocks (double-buffered), scratch and one head's temporaries
    stay under the VMEM budget.  More heads a step re-read the key
    block's latent, RoPE keys and mask less often and pay the step's
    fixed cost less often."""
    def vmem(g):
        per_head = 2 * itemsize * (tq * dq + rank * dh + tq * dv)  # q, w, out
        shared = 2 * (itemsize * kb * (rank + _LANE) + tq * kb)  # latent, rope, sees
        scratch = 4 * g * tq * (dv + 2 * _LANE)  # acc, max, sum
        temps = 4 * kb * dh + 3 * 4 * tq * kb + 4 * tq * dv  # kv, scores, p, p @ v
        return g * per_head + shared + scratch + temps

    g = 1
    while g < _MAX_HEADS and nh % (2 * g) == 0 and vmem(2 * g) <= _VMEM_BUDGET:
        g *= 2
    return g


def tiles_for(s: int, t: int, nh: int, nope: int, v: int, rank: int,
              rope_row: int, key_block: int, itemsize: int,
              aligned: bool = True) -> Tiles | None:
    """The tiling of a call of these static shapes, or ``None`` where the
    chip's layouts do not take one (``aligned``: a query tile of whole
    sublane groups, key blocks, value width, rank and the RoPE row whole
    lanes): a single-token step through ``forward``, a bucket under 16
    tokens.  The interpreter (``aligned=False``) takes any shape."""
    if t % key_block:
        return None
    if not aligned:
        return Tiles(_query_tile(s, 1), key_block, 1, nope, 1)
    tq = _query_tile(s, 32 // itemsize)
    if tq is None or any(d % _LANE for d in (key_block, v, rank, rope_row)):
        return None
    dn = -(-nope // _LANE) * _LANE
    g = heads_per_step(nh, tq, key_block, rank, dn + rope_row, dn + v, v, itemsize)
    return Tiles(tq, key_block, g, dn, _LANE)


def _core_kernel(nb_ref, q_ref, kr_ref, c_ref, w_ref, sees_ref, o_ref,
                 m_ref, l_ref, acc_ref, *, tiles: Tiles, dv: int, scale: float):
    g, dn, kb = tiles.heads, tiles.nope, tiles.keys
    dq, dh = q_ref.shape[-1] // g, w_ref.shape[-1] // g
    dt = q_ref.dtype
    j = pl.program_id(3)

    def wide(x, n):  # a statistic [tq, stat] against a tile n lanes wide
        return x if tiles.stat == 1 else jnp.tile(x, (1, n // tiles.stat))

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _LOW, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    c, kr = c_ref[0], kr_ref[0]
    sees = sees_ref[0].astype(jnp.int32) != 0  # [tq, kb], the group's heads share it
    for h in range(g):
        kv = jnp.dot(c, w_ref[:, h * dh:(h + 1) * dh],
                     preferred_element_type=jnp.float32).astype(dt)
        sc = (
            lax.dot_general(q_ref[0, :, h * dq:h * dq + dn], kv[:, :dn], _NT,
                            preferred_element_type=jnp.float32)
            + lax.dot_general(q_ref[0, :, h * dq + dn:(h + 1) * dq], kr, _NT,
                              preferred_element_type=jnp.float32)
        ) * scale
        top = m_ref[h]
        top2 = jnp.maximum(
            top, jnp.max(jnp.where(sees, sc, _LOW), axis=1, keepdims=True))
        p = jnp.where(sees, jnp.exp(sc - wide(top2, kb)), 0.0)
        keep = jnp.exp(top - top2)
        m_ref[h] = top2
        l_ref[h] = l_ref[h] * keep + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * wide(keep, dv) + jnp.dot(
            p.astype(dt), kv[:, dn:], preferred_element_type=jnp.float32)

    @pl.when(j == nb_ref[0] - 1)
    def _():
        for h in range(g):
            total = jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, :, h * dv:(h + 1) * dv] = (
                acc_ref[h] / wide(total, dv)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tiles", "scale", "interpret"))
def _fused(q_nope, q_rope, k_rope, latent, w_kvb, sees, n_blocks, *,
           tiles: Tiles, scale: float, interpret: bool) -> jax.Array:
    b, s, nh, nope = q_nope.shape
    dr, rank = k_rope.shape[-1], latent.shape[-1]
    dv = w_kvb.shape[-1] // nh - nope
    dt = q_nope.dtype
    tq, kb, g, dn, stat = tiles
    # Heads side by side on the lanes, a head's parts at whole lanes:
    # [nope (padded) | rope (as wide as a cached RoPE row)] for a query,
    # [nope (padded) | v] for the expansion.  Where ``nope`` is whole
    # lanes already the weights go in as they lie.
    pad = lambda x, n: jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n - x.shape[-1])])
    q = jnp.concatenate([pad(q_nope, dn), pad(q_rope, dr)], axis=-1)
    q = q.reshape(b, s, nh * (dn + dr))
    w = w_kvb.astype(dt)
    if dn != nope:
        w = w.reshape(rank, nh, nope + dv)
        w = jnp.concatenate([pad(w[..., :nope], dn), w[..., nope:]], axis=-1)
        w = w.reshape(rank, nh * (dn + dv))
    own = sees.shape[0] > 1  # a mask a batch row, or one for all
    return pl.pallas_call(
        functools.partial(_core_kernel, tiles=tiles, dv=dv, scale=scale),
        out_shape=jax.ShapeDtypeStruct((b, s, nh * dv), dt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nh // g, s // tq, n_blocks),
            in_specs=[
                pl.BlockSpec((1, tq, g * (dn + dr)), lambda r, n, i, j, nb: (r, i, n)),
                pl.BlockSpec((1, kb, dr), lambda r, n, i, j, nb: (r, j, 0)),
                pl.BlockSpec((1, kb, rank), lambda r, n, i, j, nb: (r, j, 0)),
                pl.BlockSpec((rank, g * (dn + dv)), lambda r, n, i, j, nb: (0, n)),
                pl.BlockSpec((1, tq, kb),
                             lambda r, n, i, j, nb: (r if own else 0, i, j)),
            ],
            out_specs=pl.BlockSpec((1, tq, g * dv), lambda r, n, i, j, nb: (r, i, n)),
            scratch_shapes=[
                pltpu.VMEM((g, tq, stat), jnp.float32),
                pltpu.VMEM((g, tq, stat), jnp.float32),
                pltpu.VMEM((g, tq, dv), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        name="prefill_attention",
        interpret=interpret,
    )(jnp.reshape(n_blocks, (1,)).astype(jnp.int32), q, k_rope.astype(dt),
      latent.astype(dt), w, sees.astype(jnp.int8))


def prefill_attention(
    q_nope: jax.Array,
    q_rope: jax.Array,
    k_rope: jax.Array,
    latent: jax.Array,
    w_kvb: jax.Array,
    sees: jax.Array,
    written,
    *,
    key_block: int,
    scale: float,
    fallback: Callable[..., jax.Array],
    interpret: bool = False,
) -> jax.Array:
    """Attention of ``S`` queries over the first ``written`` of ``T``
    cached positions (an int or a traced scalar: an upper bound on the
    positions any query sees, at least 1), in blocks of ``key_block``
    keys.  ``sees`` bool ``[B or 1, S, T]`` says which keys a query sees;
    every query must see one.  ``k_rope`` rows are as cached: the RoPE
    key, zeros behind it.  ``fallback`` takes the seven array arguments
    and computes the same; it runs off the TPU and wherever
    :func:`tiles_for` has no tiling.  Returns ``[B, S, NH*v]`` in the
    queries' type."""
    _b, s, nh, nope = q_nope.shape
    t, rope_row = k_rope.shape[1:]
    tiles = tiles_for(
        s, t, nh, nope, w_kvb.shape[-1] // nh - nope, latent.shape[-1], rope_row,
        key_block, q_nope.dtype.itemsize, aligned=not interpret)
    args = (q_nope, q_rope, k_rope, latent, w_kvb, sees,
            jnp.asarray(written, jnp.int32))
    if tiles is None:
        return fallback(*args)

    def fused(q_nope, q_rope, k_rope, latent, w_kvb, sees, written):
        return _fused(q_nope, q_rope, k_rope, latent, w_kvb, sees,
                      (written + key_block - 1) // key_block,
                      tiles=tiles, scale=scale, interpret=interpret)

    if interpret:
        return fused(*args)
    return lax.platform_dependent(*args, tpu=fused, default=fallback)
