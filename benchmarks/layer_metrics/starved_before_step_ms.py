"""Milliseconds a decode step the chip had nothing to run before the step
was dispatched (`tpumlops_device_starved_seconds_total{before="decode"}`
over the steps of the window): what the host puts between a read-back and
the next dispatch, the part of `tpot_p90_ms` that is not device time."""
from harness import starved


def compute(ctx):
    d = starved.read(ctx)
    return None if d is None else starved.before_step_ms(d)
