"""Of the (query, key) pairs the attention cores multiplied for the real
rows of prompt chunks, the share inside the causal and window masks, in
percent: the server's tpumlops_attn_pairs_attended_total over
tpumlops_attn_pairs_computed_total, label program="prefill", both layer
kinds summed (host arithmetic at each chunk's dispatch).  A full layer
walks the key blocks of its capacity that hold a written position (the
last one partly past the chunk's queries), a sliding layer the ring's
window - 1 rows before the chunk and the chunk's own for a window of
512: a core that stops multiplying masked keys raises it.  The split by
kind goes to the run's log.  A program without the counters gives
nothing."""
from harness import prom

PROGRAM = "prefill"


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
        ctx.note(f"attn_pairs_used_pct.{PROGRAM}: " + ", ".join(
            f"{kind} {100.0 * a / c:.2f} % of {c:.4g}" for kind, (a, c) in split.items()))
    return 100.0 * sum(a for a, _c in split.values()) / computed
