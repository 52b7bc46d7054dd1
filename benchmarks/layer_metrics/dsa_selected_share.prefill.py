"""How sparse this traffic made the indexed layers' attention, in prompt
chunks: the server's tpumlops_dsa_keys_selected_total (positions the
indexers kept for the softmax, min(position + 1, index_topk) a real query
row an indexed layer) over tpumlops_dsa_keys_scored_total (positions they
scored: every one up to the query's own), label program="prefill", in %.
A prompt of P tokens reads (2048 P - 2048^2 / 2) / (P^2 / 2): 75 % at
4096, 44 % at 8192; 100 % means no context passed index_topk and the cell
no longer works the mechanism.  A program without the counters gives
nothing."""
from harness import prom


def compute(ctx):
    scored = prom.delta(ctx.before, ctx.after,
                        "tpumlops_dsa_keys_scored_total", program="prefill")
    kept = prom.delta(ctx.before, ctx.after,
                      "tpumlops_dsa_keys_selected_total", program="prefill")
    return None if scored <= 0 or kept <= 0 else 100.0 * kept / scored
