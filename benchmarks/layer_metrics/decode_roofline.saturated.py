"""The least time the chip could take for the mean decode step of the
window (the server's own mean batch, the attended lengths the client
saw), over the decode program's device time, in a saturated cell: there
a decode step rides on every prefill tick, so it moves `tokens_per_s`."""
from harness import counts


def compute(ctx):
    ms = ctx.program_ms("decode")
    batch = ctx.hist_mean("tpumlops_decode_batch_size")
    ctxs = ctx.decode_tokens()
    if ms is None or ctx.peaks is None or not batch or not ctxs:
        return None
    mean_ctx = sum(ctxs) / len(ctxs)
    flops, nbytes = ctx.shapes.decode_step(batch, batch * mean_ctx)
    least, bound = counts.roofline_ms(flops, nbytes, ctx.peaks)
    ctx.note(f"decode_roofline.saturated: batch {batch:.2f}, attended "
             f"{mean_ctx:.0f}, least {least:.3f} ms, bound by {bound}, "
             f"device {ms:.3f} ms")
    return 100.0 * least / ms
