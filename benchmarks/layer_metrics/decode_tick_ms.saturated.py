"""Host time of one decode tick of the scheduler, in a saturated cell
(it moves `tokens_per_s` there)."""


def compute(ctx):
    v = ctx.hist_mean("tpumlops_tick_seconds", kind="decode")
    return None if v is None else 1e3 * v
