"""Tensor-parallel partition rules for the llama serving stack.

One rule table (regex path -> :class:`~jax.sharding.PartitionSpec`, the
SNIPPETS [2]/[3] shape) maps everything the generation engine holds on
device onto a ``{"dp": 1, "tp": N}`` mesh:

- the llama param tree — Megatron column/row splits: q/k/v/gate/up shard
  their OUTPUT axis, o/down their INPUT axis, embed/lm_head the vocab
  axis; norms replicate.  The int8 layout's ``q8`` planes shard exactly
  like the bf16 matrices they quantize; ``scale`` planes shard on their
  OUTPUT axis only (the reduced axis is size 1 — q/k/v/gate/up scales
  follow their weights, o/down scales replicate);
- the :class:`~.llama.RaggedKVCache` (and its int8kv variant) — the
  ``kv_heads`` axis, so each chip holds its heads' K/V window and the
  decode attention einsums never cross chips;
- the per-sequence prefill scratch :class:`~.llama.KVCache` — same
  heads split, position-major layout;
- sampling state (tokens, PRNG keys, temps/topk/topp, lengths, masks) —
  replicated, so host reads and the on-device sampling chain see the
  same values on every chip.

XLA inserts the collectives: one all-reduce after the o and down
projections per layer (the Megatron pair), one all-gather where a
replicated output (sampled tokens, logits read-backs) consumes the
vocab-sharded lm_head product.  Nothing here gathers the cache — K/V
commits scatter into the sharded buffers and stay resident.

``build_serving_mesh`` builds the mesh over a PREFIX of the visible
devices (``jax.devices()[:n]``), not all of them: the 8-device CPU test
environment runs tp in {1, 2, 4} ladders side by side, and a production
slice where the mesh consumes every chip is the n == len(devices)
special case.
"""

from __future__ import annotations

import math
from typing import Any, Mapping

from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..parallel import (
    AXIS_DATA,
    AXIS_SEQ,
    AXIS_TENSOR,
    build_mesh,
    match_partition_rules,
)

P = PartitionSpec
TP = AXIS_TENSOR
DP = AXIS_DATA
SP = AXIS_SEQ

# Regex path -> PartitionSpec, first match wins (rule ORDER is load-
# bearing: the quantized scale/q8 rules sit above the bare-matrix rules
# they would otherwise shadow).  Matched by re.search against "/"-joined
# tree paths, e.g. "layers/q/q8".
LLAMA_PARTITION_RULES: tuple[tuple[str, PartitionSpec], ...] = (
    # int8 weight layout: q8 shards like its source matrix; scale is
    # [..., 1, out] so only output-axis-sharded matrices shard it.
    (r"layers/(q|k|v|gate|up)/q8$", P(None, None, TP)),
    (r"layers/(q|k|v|gate|up)/scale$", P(None, None, TP)),
    (r"layers/(o|down)/q8$", P(None, TP, None)),
    (r"layers/(o|down)/scale$", P()),
    (r"lm_head/q8$", P(None, TP)),
    (r"lm_head/scale$", P(None, TP)),
    # bf16/f32 weight matrices (Megatron column/row split).
    (r"layers/(q|k|v|gate|up)$", P(None, None, TP)),
    (r"layers/(o|down)$", P(None, TP, None)),
    (r"embed$", P(TP, None)),
    (r"lm_head$", P(None, TP)),
    # Norms replicate (tiny, consumed by every chip's residual stream).
    (r"(attn_norm|mlp_norm|final_norm)$", P()),
)

# Engine device state outside the param tree.  The ragged cache and the
# prefill scratch are both [L, B, T, NKV, D] (kv heads on axis 3); the
# int8kv scale planes share their buffer's rank.
# Under dp > 1 the ragged cache ALSO shards its row (batch) axis — see
# ``ragged_kv_spec`` — so each dp shard holds B/dp cache rows and the
# decode forward partitions on batch with replicated weights.
SEQ_KV_SPEC = P(None, None, None, TP, None)
RAGGED_KV_SPEC = SEQ_KV_SPEC
RAGGED_KV_SPEC_DP = P(None, DP, None, TP, None)
REPLICATED = P()


def ragged_kv_spec(dp: int) -> PartitionSpec:
    """The ragged cache's PartitionSpec: heads on tp always; the row
    (batch) axis joins dp only when that axis is real — ``dp <= 1``
    keeps the PR 15 spec object byte-for-byte (the ``{dp: 1}`` pin)."""
    return RAGGED_KV_SPEC_DP if int(dp) > 1 else RAGGED_KV_SPEC


def tp_degree(mesh_shape: Mapping[str, int] | None) -> int:
    """The ``tp`` axis size of a meshShape (1 when absent/empty)."""
    if not mesh_shape:
        return 1
    return int(mesh_shape.get(AXIS_TENSOR, 1))


def dp_degree(mesh_shape: Mapping[str, int] | None) -> int:
    """The ``dp`` axis size of a meshShape (1 when absent/empty)."""
    if not mesh_shape:
        return 1
    return int(mesh_shape.get(AXIS_DATA, 1))


def sp_degree(mesh_shape: Mapping[str, int] | None) -> int:
    """The ``sp`` axis size of a meshShape (1 when absent/empty)."""
    if not mesh_shape:
        return 1
    return int(mesh_shape.get(AXIS_SEQ, 1))


def mesh_device_count(mesh_shape: Mapping[str, int] | None) -> int:
    n = 1
    for v in (mesh_shape or {}).values():
        n *= int(v)
    return n


def build_serving_mesh(mesh_shape: Mapping[str, int]) -> Mesh:
    """Mesh over the first ``prod(mesh_shape)`` visible devices.

    A prefix, not the full set: parity tests run tp in {1, 2, 4} on one
    8-device CPU process, and on a real slice the CRD's reconcile-time
    ``meshShape x tpuTopology`` check already pins prod == chip count.
    """
    import jax

    n = mesh_device_count(mesh_shape)
    devices = jax.devices()
    if n > len(devices):
        raise ValueError(
            f"meshShape {dict(mesh_shape)} needs {n} devices, "
            f"have {len(devices)}"
        )
    return build_mesh(mesh_shape, devices[:n])


def llama_param_specs(params: Any) -> Any:
    """PartitionSpec pytree for a llama param tree (bf16 or int8)."""
    return match_partition_rules(LLAMA_PARTITION_RULES, params)


def _spec_on_mesh(spec: PartitionSpec, mesh: Mesh) -> PartitionSpec:
    """Drop axis names the mesh doesn't carry (NamedSharding rejects
    them): an ``{sp: N}``-only mesh has no ``tp`` axis, so the rule
    table's tp entries degrade to replication there, exactly as a
    size-1 tp axis would."""
    names = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            return kept if kept else None
        return entry if entry in names else None

    return PartitionSpec(*(keep(e) for e in spec))


def llama_param_shardings(params: Any, mesh: Mesh) -> Any:
    import jax

    return jax.tree.map(
        lambda spec: NamedSharding(mesh, _spec_on_mesh(spec, mesh)),
        llama_param_specs(params),
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def shard_llama_params(params: Any, mesh: Mesh) -> Any:
    """Device-put a llama param tree sharded per the rule table."""
    import jax

    return jax.tree.map(
        jax.device_put, params, llama_param_shardings(params, mesh)
    )


def validate_llama_mesh(cfg, mesh_shape: Mapping[str, int] | None) -> None:
    """Reject a meshShape the llama geometry cannot shard — typed, with
    the knob named, instead of the opaque XLA shape error the first
    warmup dispatch would otherwise raise (see
    ``utils.config.validate_mesh_for_model``, which this wraps with the
    model's numbers filled in)."""
    from ..utils.config import validate_mesh_for_model

    validate_mesh_for_model(
        mesh_shape,
        num_kv_heads=cfg.num_kv_heads,
        num_heads=cfg.num_heads,
        intermediate_size=cfg.intermediate_size,
        vocab_size=cfg.vocab_size,
    )


def engine_state_shardings(mesh: Mesh, kv_quant: bool):
    """The generation engine's device-state shardings on ``mesh``:
    ``(replicated, ragged_kv, seq_kv)`` where the kv entries mirror the
    engine's cache repr — a bare NamedSharding for the bf16 cache, a
    ``(values, scales)`` pair under int8kv.  When the mesh carries a
    real ``dp`` axis the ragged cache's row axis shards over it (each
    dp shard holds B/dp rows; sampling state and token read-backs stay
    replicated so host slot truth is mesh-shape-independent)."""
    dp = int(dict(mesh.shape).get(DP, 1))
    rep = NamedSharding(mesh, REPLICATED)
    ragged = NamedSharding(mesh, _spec_on_mesh(ragged_kv_spec(dp), mesh))
    seq = NamedSharding(mesh, _spec_on_mesh(SEQ_KV_SPEC, mesh))
    if kv_quant:
        return rep, (ragged, ragged), seq
    return rep, ragged, seq


def shard_bytes(leaf) -> int:
    """Bytes ONE device holds of ``leaf`` (the per-chip HBM ledger's
    exact term — replicated leaves count whole, sharded leaves their
    shard)."""
    shape = leaf.sharding.shard_shape(leaf.shape)
    return math.prod(shape) * leaf.dtype.itemsize
