"""Ring attention on the virtual sp mesh and the grouped-matmul kernel
(interpret mode on CPU), each against its plain oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumlops.ops.ring_attention import ring_attention_sharded
from tpumlops.parallel import build_mesh


def qkv(b=2, h=3, s=64, d=16, t=None, key=0):
    t = t or s
    k1, k2, k3 = jax.random.split(jax.random.key(key), 3)
    q = jax.random.normal(k1, (b, h, s, d), jnp.float32)
    k = jax.random.normal(k2, (b, h, t, d), jnp.float32)
    v = jax.random.normal(k3, (b, h, t, d), jnp.float32)
    return q, k, v


def attention_reference(q, k, v, causal=False):
    """Dense [B,H,S,D] x [B,H,T,D] -> [B,H,S,D] attention, the ring's oracle."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[-1] ** 0.5
    if causal:
        qi, ki = jnp.arange(q.shape[2]), jnp.arange(k.shape[2])
        s = jnp.where(ki[None, :] <= qi[:, None], s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


# ---------------------------------------------------------------------------
# Ring attention over the sp mesh axis
# ---------------------------------------------------------------------------


def test_ring_attention_matches_reference():
    mesh = build_mesh({"sp": 8})
    q, k, v = qkv(b=1, h=2, s=64, d=16, key=3)
    out = ring_attention_sharded(q, k, v, mesh)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_causal_matches_reference():
    mesh = build_mesh({"sp": 8})
    q, k, v = qkv(b=1, h=2, s=64, d=16, key=4)
    out = ring_attention_sharded(q, k, v, mesh, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_jit_with_sp_mesh():
    mesh = build_mesh({"sp": 4}, jax.devices()[:4])
    q, k, v = qkv(b=1, h=1, s=32, d=8, key=5)
    f = jax.jit(lambda q, k, v: ring_attention_sharded(q, k, v, mesh, causal=True))
    out = f(q, k, v)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


# --- grouped matmul: the sparse-expert FFN's kernel against lax.ragged_dot ---

from tpumlops.ops.grouped_matmul import (  # noqa: E402
    grouped_matmul, grouped_matmul_reference, row_tile, row_tile_schedule)


def _sizes(m, g, rng, empty=()):
    """``g`` group sizes over all ``m`` rows, the groups in ``empty`` none."""
    live = [i for i in range(g) if i not in set(empty)]
    sizes = np.zeros((g,), np.int32)
    sizes[live] = rng.multinomial(m, [1 / len(live)] * len(live))
    return sizes


GROUPED = {
    # name: (m, k, n, g, sizes(rng) -> [g], dtype)
    "k_wider_than_n": (256, 256, 128, 8, lambda r: _sizes(256, 8, r), jnp.float32),
    "n_wider_than_k": (256, 128, 256, 8, lambda r: _sizes(256, 8, r), jnp.float32),
    "groups_start_mid_tile": (
        64, 128, 128, 32, lambda r: _sizes(64, 32, r), jnp.float32),
    "empty_groups_leading_trailing_and_in_runs": (
        256, 128, 128, 16,
        lambda r: _sizes(256, 16, r, empty=(0, 1, 5, 6, 7, 11, 14, 15)), jnp.float32),
    "every_row_in_one_group": (
        256, 128, 128, 8, lambda r: np.eye(8, dtype=np.int32)[5] * 256, jnp.float32),
    "m_not_a_multiple_of_the_tile": (
        200, 128, 128, 8, lambda r: _sizes(200, 8, r), jnp.float32),
    "rows_behind_the_last_group_poisoned": (
        256, 128, 128, 8, lambda r: _sizes(150, 8, r, empty=(7,)), jnp.float32),
    "no_group_has_a_row": (
        64, 128, 128, 8, lambda r: np.zeros((8,), np.int32), jnp.float32),
    "bf16_in_float32_out": (256, 256, 128, 8, lambda r: _sizes(256, 8, r), jnp.bfloat16),
    "n_split_where_the_blocks_do_not_fit": (
        32, 2048, 4096, 2, lambda r: np.asarray([13, 19], np.int32), jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(GROUPED))
def test_grouped_matmul_kernel_equals_ragged_dot(case):
    """The kernel (interpret mode) against XLA's ``ragged_dot`` over the
    rows that belong to a group; the rows behind the last group carry NaN
    in ``lhs`` and may come back as anything."""
    m, k, n, g, make, dtype = GROUPED[case]
    rng = np.random.default_rng(sorted(GROUPED).index(case))
    sizes = make(rng)
    valid = int(sizes.sum())
    lhs = rng.standard_normal((m, k)).astype(np.float32)
    lhs[valid:] = np.nan
    lhs = jnp.asarray(lhs, dtype)
    rhs = jnp.asarray(rng.standard_normal((g, k, n)).astype(np.float32), dtype)
    got = grouped_matmul(lhs, rhs, jnp.asarray(sizes), interpret=True)
    assert got.dtype == jnp.float32 and got.shape == (m, n)
    got = np.asarray(got)[:valid]
    assert np.isfinite(got).all()
    want = np.asarray(grouped_matmul_reference(
        jnp.nan_to_num(lhs), rhs, jnp.asarray(sizes)))[:valid]
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)
    # The schedule: a visit for every (group, row tile) pair that shares
    # a row, in row order, and no other.
    tm = row_tile(m, g)
    plan = row_tile_schedule(jnp.asarray(sizes), m, tm)
    ends = np.cumsum(sizes)
    pairs = [(i, t) for i in range(g) if sizes[i]
             for t in range((ends[i] - sizes[i]) // tm, (ends[i] - 1) // tm + 1)]
    v = int(plan.visits)
    assert v == len(pairs) <= plan.group_ids.shape[0]
    assert list(zip(np.asarray(plan.group_ids)[:v].tolist(),
                    np.asarray(plan.tile_ids)[:v].tolist())) == pairs


def test_grouped_matmul_off_the_tpu_is_ragged_dot():
    rng = np.random.default_rng(0)
    sizes = jnp.asarray(_sizes(64, 8, rng))
    lhs = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((8, 32, 16)), jnp.float32)
    text = jax.jit(grouped_matmul).lower(lhs, rhs, sizes).as_text()
    assert "tpu_custom_call" not in text  # and no schedule either: cumsum is ragged_dot's own
    np.testing.assert_array_equal(
        np.asarray(jax.jit(grouped_matmul)(lhs, rhs, sizes)),
        np.asarray(grouped_matmul_reference(lhs, rhs, sizes)))


def test_row_tile_is_a_function_of_the_static_rows_and_groups():
    """Near the rows a group gets, between the bf16 layout's 16 sublanes
    and the MXU's 128: the prefill chunk's and the decode step's classes
    at the published 256 experts top-8, and monotone in the mean."""
    assert row_tile(512 * 8, 256) == 128  # a 512-token chunk: mean 16 rows
    assert row_tile(8 * 8, 256) == 16  # a decode step of 8 slots
    assert row_tile(1 * 8, 256) == 16  # one token
    tiles = [row_tile(tokens * 8, 256) for tokens in (1, 8, 32, 64, 128, 512, 2048)]
    assert tiles == sorted(tiles) and set(tiles) <= {16, 32, 64, 128}
    assert row_tile(4096, 8) == 128 and row_tile(4096, 4096) == 16
