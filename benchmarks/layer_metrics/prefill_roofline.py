"""The least time the chip could take for the mean prompt chunk of the
window, over the prefill-chunk program's device time."""
from harness import counts


def compute(ctx):
    ms = ctx.program_ms("prefill")
    chunks = ctx.prefill_offsets()
    if ms is None or ctx.peaks is None or not chunks:
        return None
    costs = [ctx.shapes.prefill_chunk(n, o) for o, n in chunks]
    flops = sum(c[0] for c in costs) / len(costs)
    nbytes = sum(c[1] for c in costs) / len(costs)
    least, bound = counts.roofline_ms(flops, nbytes, ctx.peaks)
    ctx.note(f"prefill_roofline: {len(chunks)} chunks, least {least:.3f} ms, "
             f"bound by {bound}, device {ms:.3f} ms")
    return 100.0 * least / ms
