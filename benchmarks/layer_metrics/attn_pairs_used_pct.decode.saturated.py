"""Of the (query, key) pairs the attention cores multiplied for the live
rows of decode steps in a saturated cell, the share inside the causal
and window masks, in percent: the server's
tpumlops_attn_pairs_attended_total over tpumlops_attn_pairs_computed_total,
label program="decode", both layer kinds summed (host arithmetic at each
step's dispatch).  A step's full layers read the whole window bucket of
the furthest row for every row, a sliding layer its whole ring: a step
that reads only what each row attends raises it.  The split by kind goes
to the run's log.  A program without the counters gives nothing."""
from harness import prom

PROGRAM, NAME = "decode", "decode.saturated"


def compute(ctx):
    split = {}
    for kind in ("full", "sliding"):
        got = [prom.delta(ctx.before, ctx.after, f"tpumlops_attn_pairs_{what}_total",
                          program=PROGRAM, kind=kind)
               for what in ("attended", "computed")]
        if got[1] > 0:
            split[kind] = got
    computed = sum(c for _a, c in split.values())
    if computed <= 0:
        return None
    if hasattr(ctx, "note"):
        ctx.note(f"attn_pairs_used_pct.{NAME}: " + ", ".join(
            f"{kind} {100.0 * a / c:.2f} % of {c:.4g}" for kind, (a, c) in split.items()))
    return 100.0 * sum(a for a, _c in split.values()) / computed
