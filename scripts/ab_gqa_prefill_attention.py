"""In-process A/B of the GQA-and-experts family's prefill softmax core on
the chip: ``models/gdn_moe.py::_gqa_blocks``'s einsum body (XLA) against
``ops/gqa_prefill_attention.py`` at each head group, at the published
widths of the three layer geometries the benchmark's cells serve (Laguna
full and sliding layers, Qwen3-Next's full layer) over a 512-token chunk
at several offsets into a prompt.

The public op takes no head group: this script reaches under it
(``tiles_for(...)._replace(heads=)``) so that the sweep that settles
``heads_per_step`` can be made again.  Every variant runs ``--reps``
calls over distinct queries inside one jitted scan (so a sub-millisecond
call is not timed by its dispatch) and reports the MIN over ``--rounds``
interleaved rounds, with the largest and the mean difference from the
einsum body.

Usage: ``python scripts/ab_gqa_prefill_attention.py [--cases laguna_full,laguna_sliding,qwen3_next_full]``
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# name: (KV heads, query heads a KV head, head width, keys, window, chunk offsets)
GEOMETRIES = {
    "laguna_full": (8, 6, 128, 8704, 0, (0, 3072, 7680)),
    "laguna_sliding": (8, 9, 128, 1024, 512, (0, 4096)),
    "qwen3_next_full": (2, 8, 256, 8704, 0, (0, 3072, 7680)),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", default=",".join(GEOMETRIES))
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--out", default="benchmarks/.work/ab_gqa_prefill_attention.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from tpumlops.models import gdn_moe
    from tpumlops.ops import gqa_prefill_attention as ga

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    s = 512
    results = []
    for geo in args.cases.split(","):
        nkv, r, d, t, window, offsets = GEOMETRIES[geo]
        tiles = ga.tiles_for(s, t, r, d, 512, 2)
        for at in offsets:
            keys = jax.random.split(jax.random.key(at + r), 3)
            bf = lambda k, shape: jax.random.normal(k, shape, jnp.float32).astype(jnp.bfloat16)
            q = bf(keys[0], (args.reps, 1, s, nkv, r, d))
            k = bf(keys[1], (1, t, nkv * d))
            v = bf(keys[2], (1, t, nkv * d))
            # A sliding layer: the ring's rows before the chunk, then the
            # chunk's; a full layer: the capacity, written up to the chunk.
            written = t if window else at + s

            def einsums(q, keys, values, *scalars, fallback, **_kw):
                return fallback(q, keys, values, *scalars)

            def fused_at(heads):
                def op(q, keys, values, start, written, key_start, *, window,
                       key_block, scale, **_kw):
                    pos = jnp.stack([(jnp.asarray(written, jnp.int32) + key_block - 1)
                                     // key_block, jnp.asarray(start, jnp.int32),
                                     jnp.asarray(key_start, jnp.int32)])
                    return ga._fused(q, keys, values, pos, tiles=tiles._replace(heads=heads),
                                     scale=scale, window=window, interpret=False)
                return op

            def scanned(op):
                def run(q, k, v, start):
                    def body(acc, qi):
                        # The op is looked up when traced: one variant a jit.
                        gdn_moe.gqa_prefill_attention = op
                        # Traced where the chunk program has them traced.
                        y = gdn_moe._gqa_blocks(
                            qi, k, v, start, t if window else start + s,
                            key_start=start - (t - s) if window else 0, window=window)
                        return acc + y[0, 0, 0].astype(jnp.float32), y
                    _, ys = lax.scan(body, jnp.zeros((), jnp.float32), q)
                    return ys[-1]
                return jax.jit(run)

            variants = {"einsums": scanned(einsums)}
            variants.update({f"heads{g}": scanned(fused_at(g))
                             for g in range(1, r + 1) if r % g == 0})
            operands = (q, k, v, jnp.int32(at))
            ref, best, diff = None, {}, {}
            for name, fn in variants.items():
                try:
                    got = np.asarray(fn(*operands).astype(jnp.float32))
                except Exception as e:  # a group the compiler refuses
                    print(f"{geo} at={at} {name}: {str(e)[:300]}", flush=True)
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
            blocks = -(-written // 512)
            flops = 4 * blocks * 512 * s * nkv * r * d
            row = {
                "geometry": geo, "queries": s, "offset": at, "blocks_walked": blocks,
                "matmul_ms_at_peak": round(flops / 197e12 * 1e3, 4),
                "heads_per_step": tiles.heads,
                "ms": {k_: round(x * 1e3, 4) for k_, x in best.items()},
                "max_mean_abs_diff": diff, "device": dev.device_kind,
            }
            print(json.dumps(row), flush=True)
            results.append(row)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
