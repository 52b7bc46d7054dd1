"""Milliseconds a prefill chunk the chip had nothing to run before the
chunk was dispatched (`tpumlops_device_starved_seconds_total{before=
"chunk"}` over `tpumlops_prefill_dispatch_total`, both `when`): a chunk
sent ahead behind a step counts as 0, an admission's first chunk behind
the scratch's zero-fill is in it.  The guard of PR 36's mechanism."""
from harness import starved


def compute(ctx):
    d = starved.read(ctx)
    return None if d is None else starved.before_chunk_ms(d)
