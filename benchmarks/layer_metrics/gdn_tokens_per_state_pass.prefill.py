"""Real prompt tokens a linear-attention layer folds into a recurrent
state for each pass over that state, in prompt chunks: the server's
tpumlops_gdn_tokens_total (real tokens x linear layers, counted on the
device from the call's validity mask) over
tpumlops_gdn_state_passes_total (rows whose state a call read and wrote
x linear layers), label program="prefill".  A chunk reads and writes a
row's float32 state (2 MiB a layer) once whatever it brings it: 512 at a
full chunk, ~490 over prompts of 4096-8192 (the last chunk is partial),
1 if a prefill ever falls back to the recurrence a token.  A program
without the counters gives nothing."""
from harness import prom


def compute(ctx):
    tokens = prom.delta(ctx.before, ctx.after,
                        "tpumlops_gdn_tokens_total", program="prefill")
    passes = prom.delta(ctx.before, ctx.after,
                        "tpumlops_gdn_state_passes_total", program="prefill")
    return None if tokens <= 0 or passes <= 0 else tokens / passes
