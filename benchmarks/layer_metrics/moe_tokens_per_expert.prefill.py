"""Tokens an expert that was read got to work on, in prompt chunks: the
server's tpumlops_moe_assignments_total (real tokens x experts a token x
expert layers) over tpumlops_moe_expert_activations_total (experts that
got at least one real token, summed over calls and layers, counted on the
device), label program="prefill".  A chunk streams every expert it hits
whatever it brings them: 16 at a full 512-token chunk over 256 experts.
A program without the counters gives nothing."""
from harness import prom


def compute(ctx):
    routed = prom.delta(ctx.before, ctx.after,
                        "tpumlops_moe_assignments_total", program="prefill")
    hit = prom.delta(ctx.before, ctx.after,
                     "tpumlops_moe_expert_activations_total", program="prefill")
    return None if routed <= 0 or hit <= 0 else routed / hit
