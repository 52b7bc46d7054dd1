"""In-process A/B of decode layer-walk variants (fori vs scan).

Timings differ between processes (host load, compile session), so
variant comparisons are only valid INTERLEAVED in one process: A, B, A, B per slot count, reporting
each variant's MIN over rounds (the min strips additive stalls).

Usage: ``python scripts/ab_decode.py [--slots 8,16,32,64] [--rounds 2]``
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", default="8,16,32,64")
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--window", type=int, default=512)
    args = ap.parse_args()

    import bench

    jax = bench._setup_jax()
    import jax.numpy as jnp

    from tpumlops.models import llama
    from tpumlops.models.quantization import quantize_llama

    cfg = llama.LlamaConfig(
        vocab_size=32000, hidden_size=2048, num_layers=24,
        num_heads=16, num_kv_heads=16, intermediate_size=5632, max_seq=768,
    )
    params = quantize_llama(llama.init(jax.random.key(0), cfg, dtype=jnp.bfloat16))

    out: dict = {}
    for slots in (int(s) for s in args.slots.split(",")):
        best = {"fori": float("inf"), "scan": float("inf")}
        for _ in range(args.rounds):
            for variant in ("fori", "scan"):
                llama._DECODE_LAYER_LOOP = variant
                dt = bench._decode_device_loop(
                    jax, params, cfg, slots, kv_quant=True,
                    window=args.window, position=256, n1=6, n2=30,
                )
                best[variant] = min(best[variant], dt)
        entry = {
            f"{v}_ms": round(best[v] * 1e3, 2) for v in best
        } | {
            f"{v}_tok_s": round(slots / best[v], 1) for v in best
        }
        out[str(slots)] = entry
        print(f"AB {slots}: {json.dumps(entry)}", flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
