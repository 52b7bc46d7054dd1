"""Tokens an expert that was read got to work on, in decode steps: the
server's tpumlops_moe_assignments_total over
tpumlops_moe_expert_activations_total, label program="decode" (see
moe_tokens_per_expert.prefill).  8 rows x top-8 over 256 experts hit ~57
of them: a little over 1.  A program without the counters gives nothing."""
from harness import prom


def compute(ctx):
    routed = prom.delta(ctx.before, ctx.after,
                        "tpumlops_moe_assignments_total", program="decode")
    hit = prom.delta(ctx.before, ctx.after,
                     "tpumlops_moe_expert_activations_total", program="decode")
    return None if routed <= 0 or hit <= 0 else routed / hit
