"""Ring attention: exact attention over a sequence sharded on the ``sp``
mesh axis (long-context serving / context parallelism).

Each device keeps its sequence shard of Q resident and streams K/V shards
around the ICI ring (``ppermute`` to the nearest neighbor — one hop per
step on the v5e torus).  Blockwise online softmax merges each incoming
block into running (acc, max, denom), so the full S x S score matrix never
exists anywhere and per-device memory stays O(S/n * S/n) per step.

This is the TPU-native equivalent of the sequence/context parallelism the
rebuild is mandated to provide first-class (the reference has none —
SURVEY §2.3, §5 long-context row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec

NEG_INF = -1e30


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
    causal: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Call INSIDE shard_map: q/k/v are local shards [B, H, S/n, D]."""
    n = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    b, h, chunk, d = q.shape
    scale = scale if scale is not None else 1.0 / (d**0.5)
    # Accumulate in at least f32; f64 inputs (the parity-proof harness)
    # keep f64 accumulation so the online softmax matches the dense
    # reference to the last ulp instead of quantizing through f32.
    acc_dtype = jnp.promote_types(q.dtype, jnp.float32)
    qf = q.astype(acc_dtype) * scale

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(t, carry):
        acc, m, l, kk, vv = carry
        # After t shifts, this device holds the block that originated on
        # device (r - t) mod n.
        k_origin = (r - t) % n
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", qf, kk.astype(acc_dtype),
            preferred_element_type=acc_dtype,
        )
        if causal:
            q_global = r * chunk + lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
            k_global = k_origin * chunk + lax.broadcasted_iota(
                jnp.int32, (chunk, chunk), 1
            )
            s = jnp.where((k_global <= q_global)[None, None], s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vv.astype(acc_dtype),
            preferred_element_type=acc_dtype,
        )
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        return acc_new, m_new, l_new, kk, vv

    acc0 = jnp.zeros((b, h, chunk, d), acc_dtype)
    m0 = jnp.full((b, h, chunk, 1), NEG_INF, acc_dtype)
    l0 = jnp.zeros((b, h, chunk, 1), acc_dtype)
    acc, m, l, _, _ = lax.fori_loop(0, n, step, (acc0, m0, l0, k, v))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.astype(q.dtype)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    causal: bool = False,
    axis_name: str = "sp",
) -> jax.Array:
    """Convenience wrapper: global [B,H,S,D] arrays, seq sharded over ``sp``."""
    spec = PartitionSpec(None, None, axis_name, None)
    f = jax.shard_map(
        functools.partial(ring_attention, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return f(q, k, v)
