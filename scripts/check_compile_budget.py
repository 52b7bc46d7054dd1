"""Compile-budget regression gate (``make verify`` -> ``compile-budget``).

The unified super-step engine's whole point is a SMALL program space:
one jit variant per (window-bucket x sampling-mode) instead of the
legacy decode/verify/multistep/packed cross-product.  This gate runs
both warmup sweeps on the tiny CPU model with the compile observatory
attached and fails if:

- the unified sweep's jit-variant count exceeds the committed budget,
- the legacy/unified collapse ratio drops below the committed floor
  (the ISSUE 16 acceptance bar: >= 3x at decodeSteps=4 + speculative +
  packed prefill), or
- the unified sweep's ``tpumlops_compile_seconds`` total exceeds the
  committed ceiling (generous — CPU XLA walls vary; the count is the
  tight contract, the seconds bound catches pathological blowups).

Budgets live in COMPILE_BUDGET.json at the repo root, next to the bench
records.  A legitimate program-space change (a new window bucket, a new
sampling mode) updates that file in the same PR, with the new inventory
visible in the diff.

Usage: ``env JAX_PLATFORMS=cpu python scripts/check_compile_budget.py``
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

# 8 virtual CPU devices BEFORE jax initializes: the dp/sp sweeps below
# build real multi-device meshes (same trick as tests/conftest.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _xla_flags:
    os.environ["XLA_FLAGS"] = (
        _xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT))

BUDGET_PATH = _ROOT / "COMPILE_BUDGET.json"


def _sweep(unified: bool, mesh_shape: dict | None = None,
           sp_prefill_threshold: int = 1024) -> dict:
    import jax
    import jax.numpy as jnp

    from tpumlops.models import llama, partition
    from tpumlops.server.device_telemetry import DeviceTelemetry, peaks_for
    from tpumlops.server.generation import GenerationEngine
    from tpumlops.server.speculative import SpeculativeConfig

    cfg = llama.LlamaConfig.tiny(max_seq=64)
    params = llama.init(jax.random.key(0), cfg, dtype=jnp.float32)
    if mesh_shape:
        mesh = partition.build_serving_mesh(mesh_shape)
        params = partition.shard_llama_params(params, mesh)
    # This gate runs on the CPU and reads only the observatory's compile
    # COUNTS; the utilization ratios are never looked at, so it names the
    # row of the part the budget is for instead of asking the device.
    telemetry = DeviceTelemetry(peaks=peaks_for("TPU v5 lite"))
    engine = GenerationEngine(
        params, cfg, max_slots=4, dtype=jnp.float32, decode_steps=4,
        speculative=SpeculativeConfig(
            enabled=True, draft_tokens=2, ngram_min=1, ngram_max=4,
            adaptive=True,
        ),
        prefill_chunk=8, prefill_batch=4,
        unified_step=unified, telemetry=telemetry,
        mesh_shape=mesh_shape,
        sp_prefill_threshold=sp_prefill_threshold,
    )
    engine.start(warmup=True)
    engine.shutdown()
    return telemetry.observatory.snapshot()["warmup"]


def main() -> int:
    budget = json.loads(BUDGET_PATH.read_text())
    legacy = _sweep(unified=False)
    unified = _sweep(unified=True)
    # dp shards the EXISTING programs' row axis — zero new variants
    # allowed.  sp adds the ring-prefill bucket ladder (+ the shared
    # [1, V] insert variant), a bounded count pinned here so the sp
    # axis cannot silently regrow the PR 16 collapse.
    dp = _sweep(unified=True, mesh_shape={"dp": 2, "tp": 1})
    sp = _sweep(
        unified=True, mesh_shape={"sp": 2}, sp_prefill_threshold=32
    )
    ratio = legacy["compiles"] / max(1, unified["compiles"])
    print(
        f"compile-budget: legacy={legacy['compiles']} "
        f"({legacy['seconds']:.1f}s) {legacy['ops']}"
    )
    print(
        f"compile-budget: unified={unified['compiles']} "
        f"({unified['seconds']:.1f}s) {unified['ops']} "
        f"ratio={ratio:.2f}"
    )
    dp_extra = dp["compiles"] - unified["compiles"]
    sp_extra = sp["compiles"] - unified["compiles"]
    print(
        f"compile-budget: dp2={dp['compiles']} (extra {dp_extra}) "
        f"{dp['ops']}"
    )
    print(
        f"compile-budget: sp2={sp['compiles']} (extra {sp_extra}) "
        f"{sp['ops']}"
    )
    failures = []
    if dp_extra > budget["max_dp_extra_compiles"]:
        failures.append(
            f"dp=2 adds {dp_extra} jit variants over the unified sweep "
            f"(budget {budget['max_dp_extra_compiles']}: dp must reshard "
            "existing programs, not mint new ones)"
        )
    if sp_extra > budget["max_sp_extra_compiles"]:
        failures.append(
            f"sp=2 adds {sp_extra} jit variants over the unified sweep, "
            f"budget {budget['max_sp_extra_compiles']}"
        )
    if unified["compiles"] > budget["max_unified_compiles"]:
        failures.append(
            f"unified jit-variant count {unified['compiles']} exceeds "
            f"budget {budget['max_unified_compiles']}"
        )
    if ratio < budget["min_collapse_ratio"]:
        failures.append(
            f"legacy/unified collapse ratio {ratio:.2f} below floor "
            f"{budget['min_collapse_ratio']}"
        )
    if unified["seconds"] > budget["max_unified_compile_seconds"]:
        failures.append(
            f"unified compile seconds {unified['seconds']:.1f} exceed "
            f"ceiling {budget['max_unified_compile_seconds']}"
        )
    if failures:
        for f in failures:
            print(f"compile-budget: FAIL: {f}", file=sys.stderr)
        print(
            "compile-budget: a legitimate program-space change must "
            "update COMPILE_BUDGET.json in the same PR",
            file=sys.stderr,
        )
        return 1
    print("compile-budget: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
