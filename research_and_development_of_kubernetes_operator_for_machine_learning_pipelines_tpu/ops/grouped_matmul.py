"""Grouped matmul (Pallas, TPU) whose row tile fits the rows a group gets.

``grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G]) -> [M, N]``
multiplies rows ``offsets[g]:offsets[g+1]`` of ``lhs`` by ``rhs[g]``: the
expert matmuls of a sparse-expert FFN over token copies sorted by expert.
Off the TPU it is ``lax.ragged_dot``, which is also the oracle of
``tests/test_ops.py``.

Why a kernel: XLA lowers ``ragged_dot`` to a grouped matmul tiled
(512, 512, 256) at 4096 rows and visits every group.  With 256 groups of
~16 rows each visit multiplies a 512-row tile for 16 rows (PERF.md §5),
and a decode step's 64 rows walk a static grid of 256 groups to reach
~57.  Here:

- the row tile ``tm`` follows from the static ``(M, G)`` alone
  (:func:`row_tile`): near the mean group, between the smallest tile the
  bf16 layout allows and the MXU's edge;
- a VISIT is one (group, row tile) pair that shares a row.  The visits,
  in row order, are computed from ``group_sizes`` in the program
  (:func:`row_tile_schedule`) and go in as scalar prefetch: the grid is
  as long as the visits, so an empty group costs nothing, and the row
  tiles of one group are consecutive, so its matrix stays in VMEM;
- ``K`` is whole in one block, so every group's matrix is read once a
  call, nothing accumulates across grid steps and no partial sum leaves
  VMEM; ``N`` is split only where the blocks would not fit;
- a visit writes only its group's rows (the tile's other rows belong to
  the visits before and after, which find the output block still in
  VMEM); rows behind the last group are left unwritten, as XLA's are.

The schedule is that of ``jax.experimental.pallas.ops.tpu.megablox``
without its ``K`` loop, accumulator and sharding offsets.

``grouped_matmul`` and ``row_tile_schedule`` are jitted so that a
program's calls of one shape are traced once a process and lowered once
a program: the persistent compile cache skips XLA, not the lowering of
a kernel to Mosaic, and lowering all twelve kernels of all 36 serving
programs added 13.5 s to a cached boot of 72 s (PERF.md §6, PR 28).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128  # the MXU's edge and the lane count
_MIN_ROWS = 16  # a bf16 tile's sublanes
# Blocks of one grid step, double-buffered, must stay under the 16 MiB of
# scoped VMEM the compiler gives a kernel (PR 21's decode kernel did not).
_VMEM_BLOCK_BUDGET = 12 * 2**20


def row_tile(m: int, g: int) -> int:
    """Rows of ``lhs`` a visit multiplies, from the static shapes alone:
    the power of two at or above eight mean groups' rows (then about one
    group in eight straddles a tile boundary and costs a second visit),
    kept between the bf16 layout's 16 sublanes and the MXU's 128.  A
    visit loads the group's whole matrix into the MXU whatever ``tm`` is,
    so below 128 rows its cost is the matrix's and fewer visits win; the
    sweep on the chip is in PERF.md §6 (PR 28): 128 at a 512-token
    chunk's 4096 copies over 256 experts, 16 at a decode step's 64."""
    mean = -(-m // max(1, g))
    tm = _MIN_ROWS
    while tm < min(_LANE, 8 * mean):
        tm *= 2
    return tm


class RowTileSchedule(NamedTuple):
    """The visits of one grouped matmul, in row order."""

    offsets: jax.Array  # int32 [G + 1]: group g is rows offsets[g]:offsets[g+1]
    group_ids: jax.Array  # int32 [V]: the group of visit v
    tile_ids: jax.Array  # int32 [V]: its row tile
    visits: jax.Array  # int32 []: how many of the V are real


@functools.partial(jax.jit, static_argnums=(1, 2))
def row_tile_schedule(group_sizes: jax.Array, m: int, tm: int) -> RowTileSchedule:
    """One visit for every row tile a non-empty group has a row in.  ``V``
    is the static bound ``min(G, M) + tiles - 1``: a group adds a visit,
    and so does every tile boundary inside one."""
    g = group_sizes.shape[0]
    tiles = pl.cdiv(m, tm)
    bound = min(g, m) + tiles - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    touched = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(touched)  # visits up to and with each group's
    # Visit v is group g's where upto[g] - touched[g] <= v < upto[g]: a
    # [V, G] comparison, a few plain ops to lower where a repeat and two
    # gathers were many (every program pays the lowering at every boot).
    v = jnp.arange(bound, dtype=jnp.int32)[:, None]
    mine = (v >= upto - touched) & (v < upto)
    pick = lambda per_group: jnp.sum(jnp.where(mine, per_group, 0), axis=1)
    return RowTileSchedule(
        offsets=jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]),
        group_ids=pick(jnp.arange(g, dtype=jnp.int32)),
        tile_ids=pick(first + v - (upto - touched)),
        visits=upto[-1],
    )


def grouped_matmul_reference(lhs, rhs, group_sizes) -> jax.Array:
    return lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=jnp.float32)


def _col_tile(k: int, n: int, tm: int, itemsize: int) -> int:
    """The widest split of ``N`` (all of it where it fits) whose lhs, rhs
    and float32 output blocks fit the budget twice over."""
    def blocks(tn):
        return 2 * (tm * k * itemsize + k * tn * itemsize + tm * tn * 4)

    tn = n
    while blocks(tn) > _VMEM_BLOCK_BUDGET and tn % (2 * _LANE) == 0:
        tn //= 2
    return tn


def _visit_kernel(offsets_ref, group_ids_ref, tile_ids_ref, lhs_ref, rhs_ref,
                  out_ref, *, tm: int):
    v = pl.program_id(1)
    g = group_ids_ref[v]
    rows = tile_ids_ref[v] * tm + lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    mine = (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])
    acc = jnp.dot(lhs_ref[...], rhs_ref[...], preferred_element_type=jnp.float32)
    out_ref[...] = jnp.where(mine, acc, out_ref[...])


def _grouped_matmul_kernel(lhs, rhs, schedule: RowTileSchedule, *, tm: int,
                           interpret: bool) -> jax.Array:
    m, k = lhs.shape
    _g, _k, n = rhs.shape
    tn = _col_tile(k, n, tm, lhs.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_visit_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, schedule.visits),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, off, gid, tid: (tid[v], 0)),
                pl.BlockSpec((None, k, tn), lambda j, v, off, gid, tid: (gid[v], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, off, gid, tid: (tid[v], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="grouped_matmul",
        interpret=interpret,
    )(schedule.offsets, schedule.group_ids, schedule.tile_ids, lhs, rhs)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    group_sizes: jax.Array,
    schedule: RowTileSchedule | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``lhs [M, K] x rhs [G, K, N] -> [M, N]`` float32, group ``g`` of
    the sorted rows by ``rhs[g]``.  Rows behind ``sum(group_sizes)`` are
    left unwritten.  ``schedule`` is ``row_tile_schedule(group_sizes, M,
    row_tile(M, G))`` where the caller already holds it (matmuls over the
    same rows share one)."""
    m, g = lhs.shape[0], rhs.shape[0]
    tm = row_tile(m, g)
    if schedule is None:
        schedule = row_tile_schedule(group_sizes, m, tm)

    def kernel(lhs, rhs, sizes, *schedule):
        return _grouped_matmul_kernel(
            lhs, rhs, RowTileSchedule(*schedule), tm=tm, interpret=interpret)

    args = (lhs, rhs, group_sizes, *schedule)
    if interpret:
        return kernel(*args)
    return lax.platform_dependent(
        *args,
        tpu=kernel,
        default=lambda lhs, rhs, sizes, *_: grouped_matmul_reference(lhs, rhs, sizes),
    )
