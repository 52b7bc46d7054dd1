"""Prompt tokens whose K/V the engine prefilled inside the window, a
second: the server's tpumlops_prefill_tokens_total, credited a chunk at a
time and not a prompt at its first token."""
from harness import prom


def compute(ctx):
    n = prom.delta(ctx.before, ctx.after, "tpumlops_prefill_tokens_total")
    return None if n <= 0 or ctx.seconds <= 0 else n / ctx.seconds
