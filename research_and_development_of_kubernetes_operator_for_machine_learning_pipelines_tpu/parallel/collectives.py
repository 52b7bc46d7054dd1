"""Collective helpers used inside ``shard_map``-ped kernels.

XLA emits the actual ICI/DCN traffic; this module carries the
communication *patterns* the models compose:

- named primitives (``ring_shift``, ``psum``, ``reduce_scatter``) so
  kernel code reads as intent;
- :func:`ring_shift_bidirectional` — full-duplex torus links, both ring
  directions at once (the bandwidth-optimal ring-attention step);
- :func:`hierarchical_psum` — ICI-then-DCN all-reduce that crosses the
  slow links exactly once per byte (multi-host slices);
- :func:`all_to_all_swap` — the sequence-parallel head/sequence
  re-shard pivot (Ulysses-style).

Semantics are pinned by ``tests/test_parallel.py`` on the virtual
8-device mesh — the same SPMD program a v5e slice compiles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def ring_shift(x: jax.Array, axis_name: str, shift: int = 1) -> jax.Array:
    """Shift ``x`` around the mesh-axis ring by ``shift`` hops.

    Device i receives the block from device ``(i - shift) % n``.  On a TPU
    torus this is nearest-neighbor ICI traffic — the primitive under ring
    attention and pipelined all-gathers.
    """
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def all_gather_concat(x: jax.Array, axis_name: str, axis: int = 0) -> jax.Array:
    """All-gather shards and concatenate along ``axis`` (tiled=True)."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=True)


def psum(x, axis_name: str):
    return lax.psum(x, axis_name)


def pmean(x, axis_name: str):
    return lax.pmean(x, axis_name)


def reduce_scatter(x: jax.Array, axis_name: str, axis: int = 0) -> jax.Array:
    """Sum across the axis and leave each device with its shard of the
    result (the memory-lean half of an all-reduce)."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def axis_index(axis_name: str) -> jax.Array:
    return lax.axis_index(axis_name)


def ring_shift_bidirectional(
    x: jax.Array, axis_name: str, axis: int = 0
) -> jax.Array:
    """One bandwidth-optimal ring step: both halves move at once.

    A torus link is full-duplex; a unidirectional ring step uses half the
    wire.  Splitting ``x`` along ``axis`` and shifting the halves in
    opposite directions doubles per-step ICI bandwidth — the standard
    trick under bidirectional ring attention.  After ``n // 2`` steps
    every device has seen every block (vs ``n - 1`` unidirectional).
    Returns the two halves re-concatenated: front half came from the left
    neighbor, back half from the right.
    """
    n = x.shape[axis]
    if n % 2:
        raise ValueError(f"axis {axis} of size {n} cannot split into halves")
    fwd, bwd = jnp.split(x, 2, axis=axis)
    return jnp.concatenate(
        [ring_shift(fwd, axis_name, 1), ring_shift(bwd, axis_name, -1)],
        axis=axis,
    )


def hierarchical_psum(
    x: jax.Array, fast_axis: str, slow_axis: str, scatter_axis: int = 0
) -> jax.Array:
    """All-reduce across two mesh axes, cheap-link-aware.

    For a multi-host mesh (``fast_axis`` = ICI within a slice,
    ``slow_axis`` = DCN across hosts) a flat ``psum`` over both axes makes
    every byte cross DCN ``fast-1`` redundant times.  The hierarchical
    form sends each byte over the slow links exactly once:

    1. reduce-scatter over ``fast_axis``  (each device owns 1/fast of the
       partial sum — pure ICI),
    2. psum the small shard over ``slow_axis``  (the only DCN traffic:
       ``|x| / fast`` bytes per device),
    3. all-gather over ``fast_axis``  (pure ICI again).

    Numerically identical to ``psum(psum(x, fast), slow)`` up to float
    reduction order; ``scatter_axis``'s size must divide by the fast-axis
    size.
    """
    shard = reduce_scatter(x, fast_axis, axis=scatter_axis)
    shard = psum(shard, slow_axis)
    return all_gather_concat(shard, fast_axis, axis=scatter_axis)


def all_to_all_swap(
    x: jax.Array, axis_name: str, split_axis: int, concat_axis: int
) -> jax.Array:
    """Transpose which dimension is sharded across ``axis_name``.

    The sequence-parallel pivot (DeepSpeed-Ulysses style): attention
    wants heads local and sequence sharded for QKV projections, but the
    softmax needs the full sequence per head.  ``all_to_all`` re-shards
    from split over ``split_axis`` to split over ``concat_axis`` with
    each device exchanging only ``1/n``-sized blocks — O(|x|) total
    traffic vs an all-gather's O(n * |x|).
    """
    return lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis,
        tiled=True,
    )
