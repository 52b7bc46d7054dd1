"""Generate a synthetic Llama-2-7B checkpoint (real shapes, random bf16)
through save_native_model — the multi-GiB artifact for the load rehearsal."""
import os, time
os.environ["JAX_PLATFORMS"] = "cpu"  # generation shouldn't touch the chip
import numpy as np
import ml_dtypes

H, L, NH, NKV, INTER, VOCAB, SEQ = 4096, 32, 32, 32, 11008, 32000, 4096
rng = np.random.default_rng(0)

def rnd(*shape):
    # generate in manageable float32 chunks, store bf16
    out = np.empty(shape, ml_dtypes.bfloat16)
    flat = out.reshape(-1)
    CH = 1 << 24
    for i in range(0, flat.size, CH):
        n = min(CH, flat.size - i)
        flat[i:i+n] = (rng.standard_normal(n, dtype=np.float32) * 0.02).astype(ml_dtypes.bfloat16)
    return out

t0 = time.time()
params = {
    "embed": rnd(VOCAB, H),
    "layers": {
        "q": rnd(L, H, H), "k": rnd(L, H, H), "v": rnd(L, H, H), "o": rnd(L, H, H),
        "gate": rnd(L, H, INTER), "up": rnd(L, H, INTER), "down": rnd(L, INTER, H),
        "attn_norm": rnd(L, H).astype(ml_dtypes.bfloat16),
        "mlp_norm": rnd(L, H),
    },
    "final_norm": rnd(H),
    "lm_head": rnd(H, VOCAB),
}
print(f"generated in {time.time()-t0:.0f}s")
from tpumlops.server.loader import save_native_model
t0 = time.time()
CKPT = os.environ.get("BENCH_7B_CKPT") or os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".ckpt7b"
)
save_native_model(CKPT, "llama-generate", params, config={
    "vocab_size": VOCAB, "hidden_size": H, "num_layers": L, "num_heads": NH,
    "num_kv_heads": NKV, "intermediate_size": INTER, "max_seq": 1024})
print(f"saved in {time.time()-t0:.0f}s")
import subprocess
print(subprocess.run(["du","-sh",CKPT], capture_output=True, text=True).stdout)
