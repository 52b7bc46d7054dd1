"""Ring attention on the virtual sp mesh and the grouped-matmul kernel
(interpret mode on CPU), each against its plain oracle."""

import functools

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


# --- prefill attention: the fused core against the einsum body it replaces ---

from tpumlops.models import mla_moe  # noqa: E402
from tpumlops.ops import prefill_attention as pa  # noqa: E402


def _core_cfg(heads, nope, rope, v, rank):
    return mla_moe.MlaMoeConfig.tiny(
        num_heads=heads, qk_nope_head_dim=nope, qk_rope_head_dim=rope,
        v_head_dim=v, kv_lora_rank=rank)


# The three layer geometries the cells serve (dots3-note full 128 x
# (128 | 64, v 128) rank 512; sliding 64 x (192 | 64) rank 1024; JoyAI 32
# x (128 | 64) rank 512) at their published head widths and ranks, scaled
# down in ROWS only: heads, queries, keys.
FULL_W = dict(nope=128, rope=64, v=128, rank=512)
SLIDING_W = dict(nope=192, rope=64, v=128, rank=1024)
CORE = {
    # name: (widths, heads, queries, capacity, key block, start, mask)
    "causal_only_every_block_written": (FULL_W, 4, 32, 128, 32, 96, "causal"),
    "causal_only_written_short_of_the_capacity": (FULL_W, 4, 32, 256, 32, 64, "causal"),
    "kept_mask_with_ties_at_the_kth_value": (FULL_W, 4, 32, 256, 32, 160, "kept"),
    "kept_mask_first_blocks_fully_masked_for_every_query": (
        FULL_W, 2, 32, 256, 32, 160, "kept_late"),
    "sliding_window_with_unwritten_negative_positions": (
        SLIDING_W, 2, 32, 64, 32, 0, "window"),
    "sliding_window_mid_prompt": (SLIDING_W, 2, 32, 64, 32, 200, "window"),
    "padding_rows_behind_the_prompts_end": (FULL_W, 4, 32, 128, 32, 64, "padded"),
    "one_block_is_the_whole_capacity": (FULL_W, 2, 16, 64, 64, 48, "causal"),
    "two_query_tiles_and_two_batch_rows": (FULL_W, 2, 1024, 2048, 512, 1024, "rows"),
}


def _core_case(case):
    """The operands of one case, float32 holding bf16-exact values so the
    kernel's and the oracle's matmuls multiply the same numbers."""
    widths, nh, s, t, kb, start, mask = CORE[case]
    cfg = _core_cfg(nh, widths["nope"], widths["rope"], widths["v"], widths["rank"])
    rng = np.random.default_rng(sorted(CORE).index(case))
    b = 2 if mask == "rows" else 1
    bf = lambda x: jnp.asarray(x, jnp.bfloat16)
    q_nope = bf(rng.standard_normal((b, s, nh, widths["nope"])))
    q_rope = bf(rng.standard_normal((b, s, nh, widths["rope"])))
    kr = np.zeros((b, t, mla_moe.LANES), np.float32)
    kr[..., :widths["rope"]] = rng.standard_normal((b, t, widths["rope"]))
    c = rng.standard_normal((b, t, widths["rank"])).astype(np.float32)
    w = bf(rng.standard_normal(
        (widths["rank"], nh * (widths["nope"] + widths["v"]))) / widths["rank"] ** 0.5)
    positions = start + np.arange(s)
    written = start + s
    if mask == "window":
        # A sliding layer's keys: the window - 1 positions before the
        # chunk (negative where the prompt has not got that far: rows
        # nobody wrote), then the chunk's own.
        window = t - s + 1
        key_pos = np.concatenate([start - (window - 1) + np.arange(window - 1), positions])
        sees = ((key_pos[None, :] >= 0) & (key_pos[None, :] <= positions[:, None])
                & (positions[:, None] - key_pos[None, :] < window))[None]
        written = t
    else:
        sees = (np.arange(t)[None, :] <= positions[:, None])[None]
    if mask in ("kept", "kept_late"):
        # The selection's mask: index scores with many ties (as relu
        # gives), the top 24 of them by ``_top_mask``, a tie at the 24th
        # value going to the lower position.
        scores = np.round(rng.standard_normal((1, s, t)) * 2) / 2
        if mask == "kept_late":
            scores[..., :3 * kb] = -9.0  # nothing kept in the first three blocks
        scores = np.where(sees, scores, -np.inf)
        kept = np.asarray(mla_moe._top_mask(jnp.asarray(scores, jnp.float32), 24))
        assert (kept.sum(-1) == 24).all() and not (kept & ~sees).any()
        at = np.sort(np.where(kept, scores, np.inf), -1)[..., :1]
        assert ((scores == at) & sees & ~kept).any(), "no tie was cut at the k-th value"
        if mask == "kept_late":
            assert not kept[..., :3 * kb].any()
        sees = kept
    # ("padded": a prompt's last chunk; the rows behind its end are
    # queries like any other to the core, at the positions they pad.)
    if mask == "rows":
        sees = np.broadcast_to(sees, (b, s, t)).copy()
        sees[1, :, :7] = False  # a row of the batch with a mask of its own
        sees[1, :, 7] = True
    return cfg, (q_nope, q_rope, jnp.asarray(kr), jnp.asarray(c), w,
                 jnp.asarray(sees)), written, kb


@pytest.mark.parametrize("case", sorted(CORE))
def test_prefill_attention_kernel_equals_the_einsum_body(case, monkeypatch):
    """The fused core (interpret mode) against ``_attn_blocks``'s einsum
    body on the same operands.  Keys no query sees carry poison: NaN in
    the blocks behind ``written`` (never walked: a NaN would come out of
    ``0 * NaN``), 1e4 in the unseen rows of the walked blocks (a masked
    key's probability is an exact 0, so nothing of it reaches the sum;
    any leak would be huge).  The oracle gets the clean copy: equal
    outputs mean the same positions were attended, row for row."""
    cfg, (q_nope, q_rope, kr, c, w, sees), written, kb = _core_case(case)
    monkeypatch.setattr(mla_moe, "KEY_BLOCK", kb)
    monkeypatch.setattr(mla_moe, "ONE_PASS", kb)
    seen = np.asarray(sees).any(axis=(0, 1)) if sees.shape[0] == 1 else None
    c_clean, kr_clean = np.array(c), np.array(kr)
    c_bad, kr_bad = c_clean.copy(), kr_clean.copy()
    if seen is not None:
        c_clean[:, ~seen], kr_clean[:, ~seen] = 0.0, 0.0
        c_bad[:, ~seen] = 1e4
        kr_bad[:, ~seen, :cfg.qk_rope_head_dim] = 1e4
    walked = -(-written // kb) * kb
    c_bad[:, walked:], kr_bad[:, walked:] = np.nan, np.nan
    lp = {"kv_b": w}
    bf = lambda x: jnp.asarray(x, jnp.bfloat16)

    def run(c, kr, **kw):
        monkeypatch.setattr(
            mla_moe, "prefill_attention", functools.partial(pa.prefill_attention, **kw))
        return np.asarray(mla_moe._attn_blocks(
            q_nope, q_rope, bf(kr), bf(c), sees, jnp.int32(written), lp, cfg
        ).astype(jnp.float32))

    want = run(c_clean, kr_clean)  # off the TPU: the einsum body
    got = run(c_bad, kr_bad, interpret=True)
    assert got.shape == want.shape == (*q_nope.shape[:2], cfg.num_heads * cfg.v_head_dim)
    assert np.isfinite(got).all()
    # bf16 outputs of size ~1 from float32 statistics: the two differ by
    # the order of the float32 sums and at most an ulp of bf16 (2**-8
    # relative) where a sum lands on a rounding boundary.
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
    assert np.abs(got - want).mean() < 1e-3
    # The walk: blocks of the kernel's grid = the written ones.
    tiles = pa.tiles_for(
        q_nope.shape[1], c.shape[1], cfg.num_heads, cfg.qk_nope_head_dim,
        cfg.v_head_dim, cfg.kv_lora_rank, mla_moe.LANES, kb, 2, aligned=False)
    assert tiles.keys == kb and walked // kb <= c.shape[1] // kb


def test_prefill_attention_off_the_tpu_is_the_einsum_body():
    """No kernel in a CPU lowering, and ``tiles_for`` has no tiling for
    what the chip's layouts do not take (a single-token step, a capacity
    that is no multiple of a lane-wide key block)."""
    cfg, (q_nope, q_rope, kr, c, w, sees), written, kb = _core_case(
        "causal_only_every_block_written")
    f = lambda *a: mla_moe._attn_blocks(*a, jnp.int32(written), {"kv_b": w}, cfg)
    bf = lambda x: jnp.asarray(x, jnp.bfloat16)
    text = jax.jit(f).lower(q_nope, q_rope, bf(kr), bf(c), sees).as_text()
    assert "tpu_custom_call" not in text
    geo = dict(nh=128, nope=128, v=128, rank=512, rope_row=128, itemsize=2)
    assert pa.tiles_for(512, 8704, key_block=512, **geo) == pa.Tiles(512, 512, 4, 128, 128)
    assert pa.tiles_for(1, 8704, key_block=512, **geo) is None
    assert pa.tiles_for(8, 64, key_block=64, **geo) is None
    assert pa.tiles_for(512, 8704, key_block=500, **geo) is None
    # A nope width that is no multiple of the lanes is laid out padded.
    assert pa.tiles_for(512, 1024, 64, 192, 128, 1024, 128, 512, 2).nope == 256
    # 2048 queries: four tiles of 512.
    assert pa.tiles_for(2048, 2048, key_block=512, **geo).queries == 512


# --- GQA prefill attention: the fused core against the einsum body it replaces ---

from tpumlops.models import gdn_moe  # noqa: E402
from tpumlops.ops import gqa_prefill_attention as ga  # noqa: E402

GQA_CORE = {
    # name: (kind, KV heads, query heads a KV head, head width, queries,
    #        capacity (full) or ring rows (sliding), key block, start,
    #        window, batch rows)
    "full_one_block_written": ("full", 2, 6, 128, 32, 128, 32, 0, 0, 1),
    "full_two_blocks_written": ("full", 2, 6, 128, 32, 128, 32, 32, 0, 1),
    "full_last_block_partly_written_group_of_8_width_256": (
        "full", 2, 8, 256, 32, 160, 32, 80, 0, 1),
    "full_padded_query_rows_two_batch_rows": ("full", 2, 6, 128, 32, 128, 32, 40, 0, 2),
    "sliding_ring_empty": ("sliding", 2, 9, 128, 32, 32, 32, 0, 32, 1),
    "sliding_ring_partly_filled": ("sliding", 2, 9, 128, 32, 32, 32, 16, 32, 1),
    "sliding_ring_wrapped_window_under_the_ring": ("sliding", 2, 9, 128, 32, 32, 32, 200, 28, 1),
}


def _gqa_case(case):
    """The operands of one case, bf16 (the kernel's and the oracle's
    matmuls multiply the same numbers), with the keys no query sees
    poisoned for the kernel: NaN in the blocks behind ``written`` (never
    walked: a NaN would come out of ``0 * NaN``), 1e4 in the unseen keys
    of the walked blocks (a masked key's probability is an exact 0, so any
    leak would be huge); the oracle's copy has zeros there."""
    kind, nkv, r, d, s, rows, kb, start, window, b = GQA_CORE[case]
    rng = np.random.default_rng(sorted(GQA_CORE).index(case))
    t = rows + s if kind == "sliding" else rows
    q = rng.standard_normal((b, s, nkv, r, d))
    keys = rng.standard_normal((b, t, nkv * d))
    values = rng.standard_normal((b, t, nkv * d))
    qpos = start + np.arange(s)
    if kind == "sliding":
        # The ring's rows in position order, then the chunk's own.
        key_start, written = start - rows, t
        kpos = key_start + np.arange(t)
        seen = ((kpos[None] >= 0) & (kpos[None] <= qpos[:, None])
                & (qpos[:, None] - kpos[None] < window)).any(0)
    else:
        key_start, written = 0, start + s
        kpos = np.arange(t)
        seen = kpos <= qpos[-1]
    walked = -(-written // kb) * kb
    clean = [keys.copy(), values.copy()]
    bad = [keys.copy(), values.copy()]
    for c, x in zip(clean, bad):
        c[:, ~seen] = 0.0
        x[:, ~seen] = 1e4
        x[:, walked:] = np.nan
    bf = lambda x: jnp.asarray(x, jnp.bfloat16)
    return (bf(q), [bf(x) for x in clean], [bf(x) for x in bad],
            dict(start=start, written=written, key_start=key_start, window=window, kb=kb))


def _gqa_run(monkeypatch, q, keys, values, at, **kw):
    """``gdn_moe._gqa_blocks`` on one case's operands, its core the public
    op with ``kw`` (``interpret=True``: the kernel; else, off the TPU, the
    einsum body), ``written`` traced."""
    monkeypatch.setattr(gdn_moe, "gqa_prefill_attention",
                        functools.partial(ga.gqa_prefill_attention, **kw))
    return np.asarray(gdn_moe._gqa_blocks(
        q, keys, values, jnp.int32(at["start"]), jnp.int32(at["written"]),
        key_start=jnp.int32(at["key_start"]), window=at["window"],
    ).astype(jnp.float32))


@pytest.mark.parametrize("case", sorted(GQA_CORE))
def test_gqa_prefill_attention_kernel_equals_the_einsum_body(case, monkeypatch):
    """The GQA core (interpret mode) against ``gdn_moe._gqa_blocks``'s
    einsum body in its blocked form on the same operands, both layer
    kinds: a full layer over one, two and a last partly written key block
    of a traced ``written``; a sliding layer over a ring that is empty,
    partly filled and wrapped, then the chunk, under the window mask;
    groups of 6, 8 and 9 query heads, widths 128 and 256; query rows at
    positions behind a prompt's end, two batch rows."""
    q, clean, bad, at = _gqa_case(case)
    kb = at["kb"]
    monkeypatch.setattr(mla_moe, "KEY_BLOCK", kb)
    monkeypatch.setattr(mla_moe, "ONE_PASS", kb)
    tiles = []
    fused = ga._fused

    def seen_fused(*a, **kw):
        tiles.append(kw["tiles"])
        return fused(*a, **kw)

    monkeypatch.setattr(ga, "_fused", seen_fused)
    want = _gqa_run(monkeypatch, q, *clean, at)  # off the TPU: the einsum body
    assert not tiles
    got = _gqa_run(monkeypatch, q, *bad, at, interpret=True)
    b, s, nkv, r, d = q.shape
    assert tiles == [ga.Tiles(s, kb, r, 1)]
    assert got.shape == want.shape == (b, s, nkv * r * d)
    assert np.isfinite(got).all()
    # The same float32 operations in the same order on the CPU: bf16
    # outputs equal but where a sum's order tips a rounding.
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
    assert np.abs(got - want).mean() < 1e-3


@pytest.mark.parametrize("group", [1, 3])
def test_gqa_prefill_attention_in_head_groups_of_a_kv_head(group, monkeypatch):
    """A KV head's query heads over several grid steps (a group of 1 or 3
    of its 6, as the scoped VMEM makes it at wider tiles): each step reads
    its own heads' lanes of the queries and output and the KV head's
    lanes of the rows."""
    q, clean, bad, at = _gqa_case("full_two_blocks_written")
    monkeypatch.setattr(mla_moe, "KEY_BLOCK", at["kb"])
    monkeypatch.setattr(mla_moe, "ONE_PASS", at["kb"])
    monkeypatch.setattr(ga, "heads_per_step", lambda *a: group)
    got = _gqa_run(monkeypatch, q, *bad, at, interpret=True)
    want = _gqa_run(monkeypatch, q, *clean, at)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
    assert np.abs(got - want).mean() < 1e-3


def test_gqa_prefill_attention_off_the_tpu_is_the_einsum_body():
    """No kernel in a CPU lowering of either kind, and ``tiles_for`` has
    no tiling for what the chip's layouts do not take (a single-token
    call, a sliding call of 511 ring rows and a chunk, a head width under
    the lanes); at the published geometries it has one."""
    q, clean, _bad, at = _gqa_case("sliding_ring_partly_filled")
    for key_start, window in ((0, 0), (at["key_start"], at["window"])):
        f = lambda q, k, v: gdn_moe._gqa_blocks(
            q, k, v, at["start"], at["written"], key_start=key_start, window=window)
        assert "tpu_custom_call" not in jax.jit(f).lower(q, *clean).as_text()
    assert ga.tiles_for(512, 8704, 6, 128, 512, 2) == ga.Tiles(512, 512, 6, 128)
    assert ga.tiles_for(512, 1024, 9, 128, 512, 2) == ga.Tiles(512, 512, 3, 128)
    assert ga.tiles_for(512, 8704, 8, 256, 512, 2) == ga.Tiles(512, 512, 4, 128)
    assert ga.tiles_for(1, 8704, 6, 128, 512, 2) is None
    assert ga.tiles_for(512, 1023, 9, 128, mla_moe._key_tile(1023), 2) is None
    assert ga.tiles_for(512, 8704, 6, 64, 512, 2) is None
    assert ga.tiles_for(512, 8704, 6, 128, 500, 2) is None
