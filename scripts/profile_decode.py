"""Decode-step component profile on the real chip (VERDICT r4 item #2).

Where does the missing HBM bandwidth go as slots grow?  The step's
traffic decomposes as weights + KV-window reads + scatter commit; this
script measures each by ablation at several slot counts:

- ``full``      — the production ``decode_ragged`` step (window=512);
- ``no_commit`` — same but the post-scan scatter is skipped (cache
  returned unmodified): isolates the commit's cost;
- ``win64``     — window=64: nearly removes KV READ traffic while
  keeping weights + commit (isolates read scaling);
- ``weights``   — window=1 and no commit: the pure weight-stream floor.

Marginal interpretation: (full - no_commit) = commit cost;
(full - win64) ~ cost of the extra 448 window positions; (win64 -
weights) ~ small-window attention overhead.  Run:
``python scripts/profile_decode.py [--slots 8,16,32,64] [--seven-b]``.
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
    ap.add_argument("--seven-b", action="store_true",
                    help="7B geometry from BENCH_7B_CKPT (default: 1.35B random)")
    ap.add_argument("--window", type=int, default=512)
    ap.add_argument("--position", type=int, default=256)
    args = ap.parse_args()

    import bench
    from bench import _scan_delta_timed, _decode_hbm_bytes, V5E_HBM_GBPS

    jax = bench._setup_jax()
    import jax.numpy as jnp

    from tpumlops.models import llama
    from tpumlops.models.quantization import quantize_llama, quantized_bytes

    if args.seven_b:
        import os

        from tpumlops.server.loader import load_predictor

        ckpt = os.environ.get("BENCH_7B_CKPT") or str(
            Path(__file__).resolve().parent.parent / ".ckpt7b"
        )
        pred = load_predictor(ckpt, quantize="int8")
        params, cfg = pred.causal_lm["params"], pred.causal_lm["cfg"]
        import dataclasses

        cfg = dataclasses.replace(cfg, max_seq=768)
    else:
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=2048, num_layers=24,
            num_heads=16, num_kv_heads=16, intermediate_size=5632,
            max_seq=768,
        )
        params = quantize_llama(llama.init(jax.random.key(0), cfg, dtype=jnp.bfloat16))

    wbytes = quantized_bytes(params)

    def step_time(slots: int, *, window: int, commit: bool,
                  n1: int = 6, n2: int = 30) -> float:
        def step(p, carry):
            toks, c = carry
            logits, c2 = llama.decode_ragged(p, toks, c, cfg, window=window)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            out_cache = c2 if commit else c
            return (nxt, out_cache), nxt[0, 0]

        def carry_at(i):
            # Fresh cache per call: the carry is donated (matching the
            # production loop in bench._decode_device_loop) so the cache
            # lives once and in-loop writes can alias in place.
            cache = llama.QuantRaggedKVCache.create(cfg, slots)
            cache = cache._replace(
                lengths=jnp.full((slots,), args.position, jnp.int32)
            )
            toks = jnp.full((slots, 1), (7 + i) % 1000 + 1, jnp.int32)
            return (toks, cache)

        p = _scan_delta_timed(
            step, carry_at, n1=n1, n2=n2, params=params, donate_carry=True
        )
        return p[50]

    out: dict = {"geometry": "7B" if args.seven_b else "1.35B",
                 "weight_gib": round(wbytes / 2**30, 2), "window": args.window}
    for slots in (int(s) for s in args.slots.split(",")):
        full = step_time(slots, window=args.window, commit=True)
        nocm = step_time(slots, window=args.window, commit=False)
        w64 = step_time(slots, window=64, commit=True)
        wonly = step_time(slots, window=1, commit=False)
        kv_bytes = _decode_hbm_bytes(params, cfg, slots, args.window, True) - wbytes
        entry = {
            "full_ms": round(full * 1e3, 2),
            "tok_per_s": round(slots / full, 1),
            "bw_util": round(
                (wbytes + kv_bytes) / full / 1e9 / V5E_HBM_GBPS, 3
            ),
            "no_commit_ms": round(nocm * 1e3, 2),
            "commit_cost_ms": round((full - nocm) * 1e3, 2),
            "win64_ms": round(w64 * 1e3, 2),
            "kv_read_cost_ms": round((full - w64) * 1e3, 2),
            "weights_only_ms": round(wonly * 1e3, 2),
            "kv_read_gib": round(kv_bytes / 2**30, 2),
            "kv_marginal_gbps": round(
                kv_bytes / max(full - w64, 1e-9) / 1e9, 1
            ),
        }
        out[str(slots)] = entry
        print(f"PROFILE {slots}: {json.dumps(entry)}", flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
