"""In-process A/B of decode attention variants (xla einsum chain vs the
fused Pallas kernel, ops/decode_attention.py) at serving geometry.

Interleaved in one process: timings drift between processes, so only
A/B/A/B comparisons in one session are valid.  Reports each variant's
MIN over rounds.

Usage: ``python scripts/ab_attention.py [--slots 8,16,32] [--rounds 2]``
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", default="8,16,32")
    ap.add_argument("--variants", default="xla,pallas",
                    help="comma list of xla,pallas,pallas_single,pallas_vpu")
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--window", type=int, default=512)
    ap.add_argument("--position", type=int, default=256)
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

    variants = tuple(args.variants.split(","))
    out: dict = {}
    for slots in (int(s) for s in args.slots.split(",")):
        best = {v: float("inf") for v in variants}
        for _ in range(args.rounds):
            for variant in variants:
                llama._DECODE_ATTN = variant
                dt = bench._decode_device_loop(
                    jax, params, cfg, slots, kv_quant=True,
                    window=args.window, position=args.position, n1=6, n2=30,
                )
                best[variant] = min(best[variant], dt)
        entry = {f"{v}_ms": round(best[v] * 1e3, 2) for v in best} | {
            f"{v}_tok_s": round(slots / best[v], 1) for v in best
        }
        out[str(slots)] = entry
        print(f"AB {slots}: {json.dumps(entry)}", flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
