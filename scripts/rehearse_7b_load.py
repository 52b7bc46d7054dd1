"""7B-scale load rehearsal on the real chip (VERDICT round 1, next #10):
stream the 13.5 GB synthetic Llama-2-7B checkpoint through
server/loader.py with quantize: int8, record wall time + HBM footprint,
then prove the loaded model decodes."""
import json, os, time
import jax
import jax.numpy as jnp
import numpy as np

dev = jax.devices()[0]
print("device:", dev)
t0 = time.time()
from tpumlops.server.loader import load_predictor
# Where scripts/gen_7b_checkpoint.py writes it (inside the checkout).
CKPT = os.environ.get("BENCH_7B_CKPT") or os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".ckpt7b"
)
pred = load_predictor(CKPT, quantize="int8")
load_s = time.time() - t0
stats = dev.memory_stats() or {}
in_use = stats.get("bytes_in_use", 0)
peak = stats.get("peak_bytes_in_use", 0)
limit = stats.get("bytes_limit", 0)
print(f"load time: {load_s:.1f}s")
print(f"HBM in use: {in_use/2**30:.2f} GiB  peak: {peak/2**30:.2f} GiB  limit: {limit/2**30:.2f} GiB")

from tpumlops.models.quantization import is_quantized, quantized_bytes
params = pred.causal_lm["params"]
for name in ("q", "k", "v", "o", "gate", "up", "down"):
    assert is_quantized(params["layers"][name]), name
assert is_quantized(params["lm_head"])
print(f"stored param bytes: {quantized_bytes(params)/2**30:.2f} GiB (int8 leaves)")

# Decode sanity: one prefill + a few decode steps through the model API.
from tpumlops.models import llama
cfg = pred.causal_lm["cfg"]
t0 = time.time()
cache = llama.RaggedKVCache.create(cfg, 1, jnp.bfloat16)
ids = jnp.ones((1, 16), jnp.int32)
logits, seq = llama.prefill(params, ids, cfg, dtype=jnp.bfloat16)
cache = llama.insert_sequence(cache, seq, jnp.int32(0), jnp.int32(16))
tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
for _ in range(8):
    logits, cache = llama.decode_ragged(params, tok, cache, cfg, window=512)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
tok.block_until_ready()
assert bool(jnp.isfinite(logits).all())
print(f"prefill+8 decode steps (incl. compile): {time.time()-t0:.1f}s; logits finite")
stats = dev.memory_stats() or {}
print(f"HBM after decode: {stats.get('bytes_in_use',0)/2**30:.2f} GiB  peak: {stats.get('peak_bytes_in_use',0)/2**30:.2f} GiB")
print(json.dumps({"load_s": round(load_s,1), "hbm_weights_gib": round(in_use/2**30,2),
                  "hbm_peak_gib": round(stats.get('peak_bytes_in_use',0)/2**30,2),
                  "hbm_limit_gib": round(limit/2**30,2)}))
print("REHEARSAL OK")
