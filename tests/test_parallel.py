"""Mesh/sharding/collectives on the virtual 8-device CPU mesh."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from tpumlops.parallel import (
    AXIS_DATA,
    AXIS_TENSOR,
    TRANSFORMER_RULES,
    build_mesh,
    local_mesh,
    logical_sharding,
    logical_spec,
    ring_shift,
    shard_pytree,
)

shard_map = functools.partial(jax.shard_map, check_vma=False)


def test_eight_virtual_devices_present():
    assert len(jax.devices()) == 8


def test_build_mesh_axis_order_canonical():
    mesh = build_mesh({"tp": 4, "dp": 2})  # dict order must not matter
    assert mesh.axis_names == ("dp", "tp")
    assert mesh.devices.shape == (2, 4)


def test_build_mesh_wrong_device_count():
    with pytest.raises(ValueError, match="devices"):
        build_mesh({"dp": 3, "tp": 2})


def test_build_mesh_unknown_axis():
    with pytest.raises(ValueError, match="unknown mesh axes"):
        build_mesh({"x": 8})


def test_logical_spec_maps_transformer_axes():
    spec = logical_spec(("batch", "seq", "heads", "head_dim"))
    assert spec == PartitionSpec("dp", "sp", "tp", None)


def test_logical_spec_deduplicates_mesh_axis():
    # Two logical axes mapping to tp: only the first is sharded.
    spec = logical_spec(("heads", "mlp"))
    assert spec == PartitionSpec("tp", None)


def test_shard_pytree_places_params():
    mesh = build_mesh({"dp": 2, "tp": 4})
    params = {
        "wq": jnp.zeros((16, 8, 4)),  # (embed, heads, head_dim)
        "bias": jnp.zeros((8,)),
    }
    axes = {"wq": ("embed", "heads", "head_dim"), "bias": None}
    sharded = shard_pytree(params, axes, mesh)
    wq_sh = sharded["wq"].sharding
    assert wq_sh.spec == PartitionSpec(None, "tp", None)
    # Each device holds heads/4.
    assert sharded["wq"].addressable_shards[0].data.shape == (16, 2, 4)
    assert sharded["bias"].sharding.spec == PartitionSpec()


def test_jit_matmul_with_tp_sharding_inserts_collectives():
    # Megatron-style two-layer split: y = relu(x @ W1) @ W2 with W1
    # column-sharded and W2 row-sharded over tp -> one psum at the end.
    mesh = local_mesh({"tp": 8})
    x = jnp.ones((4, 16))
    w1 = jnp.ones((16, 32))
    w2 = jnp.ones((32, 16))
    xs = jax.device_put(x, NamedSharding(mesh, PartitionSpec(None, None)))
    w1s = jax.device_put(w1, NamedSharding(mesh, PartitionSpec(None, "tp")))
    w2s = jax.device_put(w2, NamedSharding(mesh, PartitionSpec("tp", None)))

    @jax.jit
    def f(x, w1, w2):
        return jax.nn.relu(x @ w1) @ w2

    out = f(xs, w1s, w2s)
    np.testing.assert_allclose(out, jax.nn.relu(x @ w1) @ w2, rtol=1e-5)


def test_ring_shift_rotates_blocks():
    mesh = local_mesh({"tp": 8})
    x = jnp.arange(8.0).reshape(8, 1)  # one scalar block per device

    def body(blk):
        return ring_shift(blk, "tp", shift=1)

    f = shard_map(
        body,
        mesh=mesh,
        in_specs=PartitionSpec("tp", None),
        out_specs=PartitionSpec("tp", None),
    )
    out = f(x)
    # Device i receives block from device i-1 (ring).
    np.testing.assert_array_equal(
        np.asarray(out).ravel(), np.roll(np.arange(8.0), 1)
    )


def test_ring_shift_bidirectional_moves_halves_opposite_ways():
    from tpumlops.parallel.collectives import ring_shift_bidirectional

    mesh = local_mesh({"tp": 8})
    # Two scalar blocks per device: rows 2i, 2i+1 live on device i.
    x = jnp.arange(16.0).reshape(16, 1)

    f = shard_map(
        lambda blk: ring_shift_bidirectional(blk, "tp", axis=0),
        mesh=mesh,
        in_specs=PartitionSpec("tp", None),
        out_specs=PartitionSpec("tp", None),
    )
    out = np.asarray(f(x)).reshape(8, 2)
    ref = np.arange(16.0).reshape(8, 2)
    # Front halves (col 0) shifted +1 (from the left neighbor), back
    # halves (col 1) shifted -1 (from the right neighbor).
    np.testing.assert_array_equal(out[:, 0], np.roll(ref[:, 0], 1))
    np.testing.assert_array_equal(out[:, 1], np.roll(ref[:, 1], -1))


def test_hierarchical_psum_matches_flat_psum():
    from tpumlops.parallel.collectives import hierarchical_psum

    mesh = local_mesh({"dp": 2, "tp": 4})
    x = jnp.arange(64.0).reshape(8, 8) + 0.5

    flat = shard_map(
        lambda b: jax.lax.psum(jax.lax.psum(b, "tp"), "dp"),
        mesh=mesh,
        in_specs=PartitionSpec(("dp", "tp"), None),
        out_specs=PartitionSpec(("dp", "tp"), None),
    )(x)
    hier = shard_map(
        # scatter over axis 1 (the locally-full axis): each device block
        # is [1, 8] under this spec and 8 % tp == 0.
        lambda b: hierarchical_psum(b, fast_axis="tp", slow_axis="dp",
                                    scatter_axis=1),
        mesh=mesh,
        in_specs=PartitionSpec(("dp", "tp"), None),
        out_specs=PartitionSpec(("dp", "tp"), None),
    )(x)
    np.testing.assert_allclose(np.asarray(hier), np.asarray(flat), rtol=1e-6)


def test_all_to_all_swap_reshards_heads_to_sequence():
    from tpumlops.parallel.collectives import all_to_all_swap

    mesh = local_mesh({"sp": 8})
    # Global [seq=8, heads=8]: start sequence-sharded, pivot to
    # head-sharded (each device then holds ALL positions of one head).
    x = jnp.arange(64.0).reshape(8, 8)

    f = shard_map(
        lambda blk: all_to_all_swap(blk, "sp", split_axis=1, concat_axis=0),
        mesh=mesh,
        in_specs=PartitionSpec("sp", None),
        out_specs=PartitionSpec(None, "sp"),
    )
    out = np.asarray(f(x))
    np.testing.assert_array_equal(out, np.arange(64.0).reshape(8, 8))


# ---------------------------------------------------------------------------
# Regex partition-rule matching (tensor-parallel rule tables)
# ---------------------------------------------------------------------------


def test_match_partition_rules_unmatched_leaf_falls_back_replicated():
    from tpumlops.parallel import match_partition_rules

    rules = [(r"wq$", PartitionSpec(None, "tp"))]
    tree = {
        "wq": jnp.zeros((4, 8)),
        "mystery_aux": jnp.zeros((3, 3)),  # no rule: must replicate
    }
    specs = match_partition_rules(rules, tree)
    assert specs["wq"] == PartitionSpec(None, "tp")
    assert specs["mystery_aux"] == PartitionSpec()


def test_match_partition_rules_order_precedence():
    from tpumlops.parallel import match_partition_rules

    # Both rules match "layers/q/scale"; the FIRST must win.
    rules = [
        (r"q/scale$", PartitionSpec()),
        (r"layers/q", PartitionSpec(None, "tp")),
    ]
    tree = {"layers": {"q": {"scale": jnp.zeros((1, 8)),
                             "q8": jnp.zeros((4, 8))}}}
    specs = match_partition_rules(rules, tree)
    assert specs["layers"]["q"]["scale"] == PartitionSpec()
    assert specs["layers"]["q"]["q8"] == PartitionSpec(None, "tp")


def test_match_partition_rules_rank_mismatch_is_typed():
    from tpumlops.parallel import PartitionRuleError, match_partition_rules

    rules = [(r"wq$", PartitionSpec(None, None, "tp"))]  # rank 3 vs rank 2
    with pytest.raises(PartitionRuleError, match="rank-3.*rank-2|wq"):
        match_partition_rules(rules, {"wq": jnp.zeros((4, 8))})
    # Under-rank is typed too: P("tp") on a rank-2 leaf would silently
    # shard the LEADING axis — the wrong-axis drift the guard exists
    # to catch.  An explicit P() (fully replicated) stays valid.
    with pytest.raises(PartitionRuleError, match="rank-1"):
        match_partition_rules(
            [(r"wq$", PartitionSpec("tp"))], {"wq": jnp.zeros((4, 8))}
        )
    specs = match_partition_rules(
        [(r"wq$", PartitionSpec())], {"wq": jnp.zeros((4, 8))}
    )
    assert specs["wq"] == PartitionSpec()


def test_match_partition_rules_scalars_always_replicate():
    from tpumlops.parallel import match_partition_rules

    rules = [(r".", PartitionSpec("tp"))]  # matches everything
    specs = match_partition_rules(rules, {"step": jnp.zeros(())})
    assert specs["step"] == PartitionSpec()


def test_llama_rule_table_covers_bf16_and_int8_trees():
    """Every leaf of both llama layouts must land on a spec whose rank
    matches, with the Megatron split where expected — the table the
    loader, engine, and per-shard snapshots all key off."""
    import jax

    from tpumlops.models import llama
    from tpumlops.models.partition import llama_param_specs
    from tpumlops.models.quantization import quantize_llama

    cfg = llama.LlamaConfig.tiny(num_heads=4, num_kv_heads=4)
    params = llama.init(jax.random.key(0), cfg)
    specs = llama_param_specs(params)
    assert specs["layers"]["q"] == PartitionSpec(None, None, "tp")
    assert specs["layers"]["down"] == PartitionSpec(None, "tp", None)
    assert specs["layers"]["attn_norm"] == PartitionSpec()
    assert specs["embed"] == PartitionSpec("tp", None)
    assert specs["lm_head"] == PartitionSpec(None, "tp")

    q = quantize_llama(params)
    qspecs = llama_param_specs(q)
    assert qspecs["layers"]["q"]["q8"] == PartitionSpec(None, None, "tp")
    assert qspecs["layers"]["q"]["scale"] == PartitionSpec(None, None, "tp")
    # Row-split matrices: the scale's reduced axis is size 1 — it must
    # replicate or device_put fails on an indivisible axis.
    assert qspecs["layers"]["down"]["q8"] == PartitionSpec(None, "tp", None)
    assert qspecs["layers"]["down"]["scale"] == PartitionSpec()
    assert qspecs["layers"]["o"]["scale"] == PartitionSpec()

    # The whole int8 tree device-puts cleanly at tp=4 (rank + divisibility).
    from tpumlops.models.partition import build_serving_mesh, shard_llama_params

    mesh = build_serving_mesh({"dp": 1, "tp": 4})
    sharded = shard_llama_params(q, mesh)
    q8 = sharded["layers"]["down"]["q8"]
    assert q8.sharding.spec == PartitionSpec(None, "tp", None)
    assert q8.addressable_shards[0].data.shape[1] == q8.shape[1] // 4


def test_config_mesh_axes_mirror_parallel_mesh():
    """utils.config.MESH_AXES must stay in lockstep with the jax-side
    axis table (config cannot import jax; this test can)."""
    from tpumlops.parallel import MESH_AXIS_ORDER
    from tpumlops.utils.config import MESH_AXES

    assert tuple(MESH_AXES) == tuple(MESH_AXIS_ORDER)


def test_dp_mean_loss_matches_single_device():
    mesh = build_mesh({"dp": 8})
    x = jnp.arange(32.0).reshape(8, 4)
    xs = jax.device_put(x, NamedSharding(mesh, PartitionSpec("dp", None)))

    @jax.jit
    def loss(x):
        return jnp.mean(x**2)

    np.testing.assert_allclose(loss(xs), loss(x), rtol=1e-6)
