"""In-process A/B of the sparse-expert grouped matmul on the chip:
``lax.ragged_dot`` (XLA's lowering) against ``ops/grouped_matmul.py`` at
each legal row tile, at the published widths of JoyAI-LLM-Flash
(256 experts, 2048 x 768 and 768 x 2048) for a prefill chunk's 4096
token copies and a decode step's 64.

The public op takes no tile: this script reaches under it
(``_grouped_matmul_kernel(..., tm=)``) so that the sweep that settled
``row_tile`` can be made again.  Every variant runs ``--reps`` calls
over distinct ``lhs`` inside one jitted scan (so a 0.2 ms call is not
timed by its dispatch) and reports the MIN over ``--rounds`` interleaved
rounds, with the largest difference from ``ragged_dot`` over the rows
that belong to a group.

Usage: ``python scripts/ab_grouped_matmul.py [--rows 4096,64] [--tiles 16,32,64,128,256]``
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def group_sizes(rng, m: int, g: int, k: int = 8):
    """Sizes as the router makes them: ``m / k`` tokens each choosing
    ``k`` distinct experts uniformly."""
    import numpy as np

    sizes = np.zeros((g,), np.int32)
    for _ in range(m // k):
        sizes[rng.choice(g, size=k, replace=False)] += 1
    return sizes


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="4096,64")
    ap.add_argument("--tiles", default="16,32,64,128,256")
    ap.add_argument("--groups", type=int, default=256)
    ap.add_argument("--reps", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/ab_grouped_matmul.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from tpumlops.ops import grouped_matmul as gm

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    g = args.groups
    rng = np.random.default_rng(args.seed)
    results = []
    for m in (int(x) for x in args.rows.split(",")):
        sizes_np = group_sizes(rng, m, g)
        sizes = jnp.asarray(sizes_np)
        for k, n in ((2048, 768), (768, 2048)):
            lhs = (jax.random.normal(jax.random.key(1), (args.reps, m, k), jnp.float32)
                   ).astype(jnp.bfloat16)
            rhs = (0.02 * jax.random.normal(jax.random.key(2), (g, k, n), jnp.float32)
                   ).astype(jnp.bfloat16)

            def scanned(op):
                def run(lhs, rhs, sizes):
                    def body(acc, x):
                        y = op(x, rhs, sizes)
                        return acc + y[0, 0], y
                    _, ys = lax.scan(body, jnp.zeros((), jnp.float32), lhs)
                    return ys[-1]
                return jax.jit(run)

            def kernel_at(tm):
                def op(x, rhs, sizes):
                    return gm._grouped_matmul_kernel(
                        x, rhs, gm.row_tile_schedule(sizes, m, tm), tm=tm,
                        interpret=False)
                return op

            tiles = [t for t in (int(x) for x in args.tiles.split(",")) if t <= max(16, m)]
            variants = {"ragged_dot": scanned(gm.grouped_matmul_reference)}
            variants.update({f"tm{t}": scanned(kernel_at(t)) for t in tiles})
            valid = int(sizes_np.sum())
            want = None
            best, err, visits = {}, {}, {}
            for name, fn in variants.items():
                try:
                    got = np.asarray(fn(lhs, rhs, sizes))[:valid]
                except Exception as e:  # a tile the compiler refuses
                    print(f"{m}x{k}x{n} {name}: {str(e)[:300]}", flush=True)
                    continue
                if want is None:
                    want = got
                err[name] = float(np.abs(got - want).max())
                best[name] = float("inf")
                if name != "ragged_dot":
                    visits[name] = int(gm.row_tile_schedule(sizes, m, int(name[2:])).visits)
            for _ in range(args.rounds):
                for name in best:
                    t0 = time.perf_counter()
                    variants[name](lhs, rhs, sizes).block_until_ready()
                    best[name] = min(best[name], (time.perf_counter() - t0) / args.reps)
            row = {
                "m": m, "k": k, "n": n, "groups": g,
                "nonempty": int((sizes_np > 0).sum()),
                "stream_ms": round(int((sizes_np > 0).sum()) * k * n * 2 / 819e9 * 1e3, 4),
                "row_tile": gm.row_tile(m, g),
                "ms": {v: round(best[v] * 1e3, 4) for v in best},
                "max_abs_diff": err, "visits": visits,
                "device": dev.device_kind,
            }
            print(json.dumps(row), flush=True)
            results.append(row)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
