"""How sparse this traffic made the indexed layers' attention, in decode
steps: tpumlops_dsa_keys_selected_total over
tpumlops_dsa_keys_scored_total, label program="decode", in % (see
dsa_selected_share.prefill).  A step at context L keeps 2048 of L + 1:
50 % at 4096, 25 % at 8192.  A program without the counters gives
nothing."""
from harness import prom


def compute(ctx):
    scored = prom.delta(ctx.before, ctx.after,
                        "tpumlops_dsa_keys_scored_total", program="decode")
    kept = prom.delta(ctx.before, ctx.after,
                      "tpumlops_dsa_keys_selected_total", program="decode")
    return None if scored <= 0 or kept <= 0 else 100.0 * kept / scored
