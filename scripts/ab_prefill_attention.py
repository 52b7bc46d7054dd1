"""In-process A/B of the latent family's prefill softmax core on the chip:
``models/mla_moe.py::_attn_blocks``'s einsum body (XLA) against
``ops/prefill_attention.py`` at each head group, at the published widths
of the three layer geometries the benchmark's cells serve (dots3-note
full and sliding layers, JoyAI-LLM-Flash) over a 512-token chunk at
several offsets into a prompt, and at smaller query buckets for the
crossover.

The public op takes no head group: this script reaches under it
(``tiles_for(...)._replace(heads=)``) so that the sweep that settled
``heads_per_step`` can be made again.  Every variant runs ``--reps``
calls over distinct queries inside one jitted scan (so a sub-millisecond
call is not timed by its dispatch) and reports the MIN over ``--rounds``
interleaved rounds, with the largest and the mean difference from the
einsum body.

Usage: ``python scripts/ab_prefill_attention.py [--cases full,sliding,joyai,buckets] [--heads 1,2,4]``
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# name: (heads, nope, rope, v, rank, capacity, window or 0)
GEOMETRIES = {
    "full": (128, 128, 64, 128, 512, 8704, 0),
    "sliding": (64, 192, 64, 128, 1024, 1024, 513),
    "joyai": (32, 128, 64, 128, 512, 2048, 0),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", default="full,sliding,joyai,buckets")
    ap.add_argument("--heads", default="1,2,4")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/ab_prefill_attention.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from tpumlops.models import mla_moe
    from tpumlops.ops import prefill_attention as pa

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    cases = []  # (label, geometry, queries, chunk offset)
    want = args.cases.split(",")
    if "full" in want:
        cases += [("full", "full", 512, at) for at in (0, 1536, 4096, 8192)]
    if "sliding" in want:
        cases += [("sliding", "sliding", 512, 4096)]
    if "joyai" in want:
        cases += [("joyai", "joyai", 512, at) for at in (0, 512, 1536)]
    if "buckets" in want:
        cases += [("bucket", "joyai", s, 0) for s in (16, 64, 128, 256)]
        cases += [("bucket", "full", s, 0) for s in (64, 256)]
    results = []
    for label, geo, s, at in cases:
        nh, nope, rope, v, rank, t, window = GEOMETRIES[geo]
        cfg = mla_moe.MlaMoeConfig.tiny(
            num_heads=nh, qk_nope_head_dim=nope, qk_rope_head_dim=rope,
            v_head_dim=v, kv_lora_rank=rank)
        keys = jax.random.split(jax.random.key(at + s), 5)
        bf = lambda k, shape, scale=1.0: (
            scale * jax.random.normal(k, shape, jnp.float32)).astype(jnp.bfloat16)
        q_nope = bf(keys[0], (args.reps, 1, s, nh, nope))
        q_rope = bf(keys[1], (args.reps, 1, s, nh, rope))
        kr = jnp.pad(bf(keys[2], (1, t, rope)), ((0, 0), (0, 0), (0, mla_moe.LANES - rope)))
        c = bf(keys[3], (1, t, rank))
        w = bf(keys[4], (rank, nh * (nope + v)), rank ** -0.5)
        positions = at + np.arange(s)
        if window:
            key_pos = np.concatenate([at - (window - 1) + np.arange(window - 1), positions])
            key_pos = key_pos[-t:] if len(key_pos) >= t else np.pad(
                key_pos, (t - len(key_pos), 0), constant_values=-1)
            sees = ((key_pos[None] >= 0) & (key_pos[None] <= positions[:, None])
                    & (positions[:, None] - key_pos[None] < window))[None]
            written = t
        else:
            # Causal, and of the keys a query sees a seeded 2048 at most
            # (the selection's shape, not its values).
            sees = np.arange(t)[None, :] <= positions[:, None]
            rank_of = np.random.default_rng(at).random((s, t))
            rank_of[~sees] = 2.0
            kth = np.sort(rank_of, -1)[:, min(2048, t) - 1][:, None]
            sees = (sees & (rank_of <= kth))[None]
            written = at + s
        sees = jnp.asarray(sees)
        kb = mla_moe._key_tile(t)
        lp = {"kv_b": w}

        def einsums(*a, fallback, **_kw):
            return fallback(*a[:6], jnp.asarray(a[6], jnp.int32))

        def fused_at(heads):
            def op(*a, key_block, scale, fallback, **_kw):
                tiles = pa.tiles_for(
                    s, t, nh, nope, v, rank, mla_moe.LANES, key_block, 2)
                if tiles is None:
                    raise ValueError(f"no tiling for {s} queries over {t} keys")
                return pa._fused(
                    *a[:6], (jnp.asarray(a[6], jnp.int32) + key_block - 1) // key_block,
                    tiles=tiles._replace(heads=heads), scale=scale, interpret=False)
            return op

        def scanned(op):
            def run(q_nope, q_rope, kr, c, sees, written):
                def body(acc, q):
                    # The op is looked up when traced: one variant a jit.
                    mla_moe.prefill_attention = op
                    y = mla_moe._attn_blocks(q[0], q[1], kr, c, sees, written, lp, cfg)
                    return acc + y[0, 0, 0].astype(jnp.float32), y
                _, ys = lax.scan(body, jnp.zeros((), jnp.float32), (q_nope, q_rope))
                return ys[-1]
            return jax.jit(run)

        variants = {"einsums": scanned(einsums)}
        variants.update({f"heads{h}": scanned(fused_at(h))
                         for h in (int(x) for x in args.heads.split(",")) if nh % h == 0})
        operands = (q_nope, q_rope, kr, c, sees, jnp.int32(written))
        ref, best, diff = None, {}, {}
        for name, fn in variants.items():
            try:
                got = np.asarray(fn(*operands).astype(jnp.float32))
            except Exception as e:  # a group the compiler refuses, a shape with no tiling
                print(f"{label} {geo} s={s} at={at} {name}: {str(e)[:300]}", flush=True)
                continue
            if ref is None:
                ref = got
            diff[name] = [float(np.abs(got - ref).max()), float(np.abs(got - ref).mean())]
            best[name] = float("inf")
        for _ in range(args.rounds):
            for name in best:
                t0 = time.perf_counter()
                variants[name](*operands).block_until_ready()
                best[name] = min(best[name], (time.perf_counter() - t0) / args.reps)
        blocks = -(-written // kb)
        flops = 2 * blocks * kb * nh * (rank * (nope + v) + s * (nope + rope + v))
        tiles = pa.tiles_for(s, t, nh, nope, v, rank, mla_moe.LANES, kb, 2)
        row = {
            "case": label, "geometry": geo, "queries": s, "offset": at,
            "blocks_walked": blocks, "blocks": t // kb,
            "matmul_ms_at_peak": round(flops / 197e12 * 1e3, 4),
            "heads_per_step": tiles.heads if tiles else None,
            "ms": {k: round(x * 1e3, 4) for k, x in best.items()},
            "max_mean_abs_diff": diff, "device": dev.device_kind,
        }
        print(json.dumps(row), flush=True)
        results.append(row)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
