"""Mean active slots of a decode step over the slots the server has."""


def compute(ctx):
    v = ctx.hist_mean("tpumlops_decode_batch_size")
    slots = ctx.serving.get("maxSlots")
    return None if v is None or not slots else 100.0 * v / slots
