"""Milliseconds a decode step the chip had nothing to run before the step
was dispatched (`tpumlops_device_starved_seconds_total{before="decode"}`
over the steps of the window).  In a saturated cell a step mostly queues
behind a chunk and counts as 0; what is left are the passes with no chunk
and the step behind an admission's insert."""
from harness import starved


def compute(ctx):
    d = starved.read(ctx)
    return None if d is None else starved.before_step_ms(d)
