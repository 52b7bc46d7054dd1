"""Pallas kernels vs XLA oracles (interpret mode on CPU) and ring attention
on the virtual sp mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpumlops.ops import attention_reference, flash_attention, rmsnorm, rmsnorm_reference
from tpumlops.ops.ring_attention import ring_attention_sharded
from tpumlops.parallel import build_mesh


def qkv(b=2, h=3, s=64, d=16, t=None, key=0):
    t = t or s
    k1, k2, k3 = jax.random.split(jax.random.key(key), 3)
    q = jax.random.normal(k1, (b, h, s, d), jnp.float32)
    k = jax.random.normal(k2, (b, h, t, d), jnp.float32)
    v = jax.random.normal(k3, (b, h, t, d), jnp.float32)
    return q, k, v


def test_flash_matches_reference_full():
    q, k, v = qkv()
    out = flash_attention(q, k, v, interpret=True, block_q=32, block_k=32)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_matches_reference_causal():
    q, k, v = qkv(s=48)
    out = flash_attention(q, k, v, causal=True, interpret=True, block_q=16, block_k=16)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_non_divisible_seq_padding():
    q, k, v = qkv(s=50, t=50)
    out = flash_attention(q, k, v, interpret=True, block_q=16, block_k=16)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_kv_len_masks_padded_keys():
    q, k, v = qkv(s=32, t=64)
    out = flash_attention(q, k, v, kv_len=40, interpret=True, block_q=16, block_k=16)
    ref = attention_reference(q, k, v, kv_len=40)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_bf16_io():
    q, k, v = [x.astype(jnp.bfloat16) for x in qkv(s=32)]
    out = flash_attention(q, k, v, interpret=True, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2
    )


def test_rmsnorm_matches_reference():
    x = jax.random.normal(jax.random.key(0), (4, 96, 256), jnp.float32)
    scale = jax.random.normal(jax.random.key(1), (256,)) + 1.0
    out = rmsnorm(x, scale, interpret=True)
    ref = rmsnorm_reference(x, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_rmsnorm_non_divisible_rows():
    x = jax.random.normal(jax.random.key(0), (7, 33), jnp.float32)
    scale = jnp.ones((33,))
    out = rmsnorm(x, scale, block_rows=4, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(rmsnorm_reference(x, scale)), atol=1e-5
    )


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


class TestDecodeAttention:
    """Fused int8-KV decode attention (ops/decode_attention.py)."""

    def _rand_inputs(self, B=3, W=64, NKV=2, G=2, D=32):
        import jax

        ks = [jax.random.key(i) for i in range(8)]
        q = jax.random.normal(ks[0], (B, NKV, G, D), jnp.float32)
        k8 = jax.random.randint(ks[1], (B, NKV, W, D), -127, 128, jnp.int8)
        v8 = jax.random.randint(ks[2], (B, NKV, W, D), -127, 128, jnp.int8)
        kscale = jnp.abs(jax.random.normal(ks[3], (B, NKV, W, 1))) * 0.01 + 1e-3
        vscale = jnp.abs(jax.random.normal(ks[4], (B, NKV, W, 1))) * 0.01 + 1e-3
        k_self = jax.random.normal(ks[5], (B, NKV, 1, D), jnp.float32)
        v_self = jax.random.normal(ks[6], (B, NKV, 1, D), jnp.float32)
        lengths = jnp.array([0, W // 2, W])[:B]
        mask = jnp.where(
            jnp.arange(W)[None, :] < lengths[:, None], 0.0, -1e30
        ).astype(jnp.float32)[:, None, :]
        return q, k8, kscale, v8, vscale, k_self, v_self, mask

    def test_kernel_matches_reference(self):
        from tpumlops.ops.decode_attention import (
            decode_attention, decode_attention_reference)

        args = self._rand_inputs()
        ref = decode_attention_reference(*args)
        out = decode_attention(*args, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
        )

    def test_vpu_kernel_matches_reference(self):
        """The VPU (multiply+reduce, no dot_general) kernel must match
        the oracle bit-for-bit up to f32 summation order — the G == 1
        fast path for ungrouped-head models."""
        from tpumlops.ops.decode_attention import (
            decode_attention_reference, decode_attention_vpu)

        args = self._rand_inputs(G=1, W=256)  # W % 128 == 0 required
        ref = decode_attention_reference(*args)
        out = decode_attention_vpu(*args, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
        )

    def test_batched_kernel_matches_reference(self):
        """The slot-batched kernel (bb slots per program) must be
        numerically identical to the per-slot kernel's oracle, including
        when b is not divisible by 8 (falls back to a smaller block)."""
        from tpumlops.ops.decode_attention import (
            decode_attention_batched, decode_attention_reference)

        args = self._rand_inputs()
        ref = decode_attention_reference(*args)
        out = decode_attention_batched(*args, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
        )

    def test_batched_kernel_multi_slot_block(self):
        """B=8 drives bb=8 — one program per kv head unrolling all eight
        slots — so the t > 0 unroll and the bb-sized BlockSpec index
        maps are actually exercised (B=3 degenerates to bb=1)."""
        import jax

        from tpumlops.ops.decode_attention import (
            _slot_block, decode_attention_batched, decode_attention_reference)

        assert _slot_block(8, 64) == 8
        # The block shrinks with the window so the scale planes fit VMEM
        # (what the v5e compiler accepts: tests/test_tpu_compile.py).
        assert [_slot_block(16, w) for w in (512, 1024, 2048, 8192)] == [
            8, 4, 2, 1]
        B, W, NKV, G, D = 8, 64, 2, 2, 32
        ks = [jax.random.key(100 + i) for i in range(8)]
        q = jax.random.normal(ks[0], (B, NKV, G, D), jnp.float32)
        k8 = jax.random.randint(ks[1], (B, NKV, W, D), -127, 128, jnp.int8)
        v8 = jax.random.randint(ks[2], (B, NKV, W, D), -127, 128, jnp.int8)
        kscale = jnp.abs(jax.random.normal(ks[3], (B, NKV, W, 1))) * 0.01 + 1e-3
        vscale = jnp.abs(jax.random.normal(ks[4], (B, NKV, W, 1))) * 0.01 + 1e-3
        k_self = jax.random.normal(ks[5], (B, NKV, 1, D), jnp.float32)
        v_self = jax.random.normal(ks[6], (B, NKV, 1, D), jnp.float32)
        # Distinct lengths per slot so a block-index bug (e.g. block i
        # offset i instead of i*bb) changes some row's mask/output.
        lengths = jnp.arange(B) * (W // B)
        mask = jnp.where(
            jnp.arange(W)[None, :] < lengths[:, None], 0.0, -1e30
        ).astype(jnp.float32)[:, None, :]
        args = (q, k8, kscale, v8, vscale, k_self, v_self, mask)
        ref = decode_attention_reference(*args)
        out = decode_attention_batched(*args, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
        )

    def test_zero_length_row_attends_only_self(self):
        from tpumlops.ops.decode_attention import decode_attention

        q, k8, ks, v8, vs, k_self, v_self, mask = self._rand_inputs()
        out = decode_attention(q, k8, ks, v8, vs, k_self, v_self, mask,
                               interpret=True)
        # Row 0 has length 0: every cache key masked, so the context is
        # exactly the (exact, unquantized) self V.
        np.testing.assert_allclose(
            np.asarray(out[0]), np.asarray(jnp.broadcast_to(
                v_self[0].astype(jnp.float32), out[0].shape)),
            rtol=1e-5, atol=1e-5,
        )

    def test_integrated_decode_matches_xla_path(self, monkeypatch):
        """Full decode_ragged through the pallas attention must match the
        einsum path — grouped heads (G=2), ragged lengths, int8 cache."""
        import functools

        import jax

        from tpumlops.ops import decode_attention as da

        # The kernels never choose interpret mode themselves; the layer
        # calls them without it (it runs on the chip).  This CPU test
        # asks for it here.
        monkeypatch.setattr(
            da, "decode_attention_batched",
            functools.partial(da.decode_attention_batched, interpret=True),
        )

        from tpumlops.models import llama
        from tpumlops.models.quantization import quantize_llama

        cfg = llama.LlamaConfig.tiny()
        params = quantize_llama(
            llama.init(jax.random.key(0), cfg, dtype=jnp.bfloat16)
        )
        cache = llama.QuantRaggedKVCache.create(cfg, 3)
        # Distinct per-row positions, one row empty.
        cache = cache._replace(lengths=jnp.array([0, 7, 23], jnp.int32))
        # Fill the cache with plausible values so attended positions matter.
        key = jax.random.key(1)
        cache = cache._replace(
            k8=jax.random.randint(key, cache.k8.shape, -127, 128, jnp.int8),
            v8=jax.random.randint(key, cache.v8.shape, -127, 128, jnp.int8),
            k_scale=jnp.abs(jax.random.normal(key, cache.k_scale.shape)) * 0.01,
            v_scale=jnp.abs(jax.random.normal(key, cache.v_scale.shape)) * 0.01,
        )
        toks = jnp.array([[3], [5], [7]], jnp.int32)

        prev = llama._DECODE_ATTN
        try:
            llama._DECODE_ATTN = "xla"
            ref_logits, ref_cache = llama.decode_ragged(
                params, toks, cache, cfg, window=32
            )
            llama._DECODE_ATTN = "pallas"
            out_logits, out_cache = llama.decode_ragged(
                params, toks, cache, cfg, window=32
            )
        finally:
            llama._DECODE_ATTN = prev
        np.testing.assert_allclose(
            np.asarray(out_logits), np.asarray(ref_logits),
            rtol=2e-2, atol=2e-2,
        )
        # The commit path is shared, but upstream activations differ by
        # bf16 ulps between the two attention implementations.  Two
        # independent mechanisms each move a committed int8 value by at
        # most one quantization step: (1) the value itself rounds the
        # other way when it sits near a step boundary (bf16 ulp ~2^-8
        # relative vs a step of absmax/127 ~ 0.8% of absmax — comparable
        # magnitudes); (2) the per-row scale is the row absmax, which can
        # itself differ by a bf16 ulp and rescales EVERY element of the
        # row, shifting boundary-adjacent ones again.  Hence the bound is
        # 2 steps on the raw codes, while the dequantized values must
        # agree to a small multiple of the step size.
        dq = np.abs(
            np.asarray(out_cache.k8, np.int32) - np.asarray(ref_cache.k8, np.int32)
        )
        assert dq.max() <= 2, dq.max()
        # >1-step disagreements are the rare double-boundary cases only.
        assert (dq > 1).mean() < 0.01, (dq > 1).mean()
        def _steps(scale, ndim):
            s = np.asarray(scale, np.float32)
            return s.reshape(s.shape + (1,) * (ndim - s.ndim))

        k8 = np.asarray(out_cache.k8, np.float32)
        out_deq = k8 * _steps(out_cache.k_scale, k8.ndim)
        ref_deq = np.asarray(ref_cache.k8, np.float32) * _steps(
            ref_cache.k_scale, k8.ndim)
        step = np.maximum(_steps(ref_cache.k_scale, k8.ndim), 1e-30)
        worst = float(np.max(np.abs(out_deq - ref_deq) / step))
        assert worst < 3.0, worst
        np.testing.assert_array_equal(
            np.asarray(out_cache.lengths), np.asarray(ref_cache.lengths)
        )


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
