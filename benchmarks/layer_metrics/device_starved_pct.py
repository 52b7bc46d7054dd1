"""Share of the engine loop's busy time (the root span less the wait for
traffic) in which the chip had nothing to run as far as the engine thread
could see: `tpumlops_device_starved_seconds_total` over the window.  In a
steady cell it separates the chip waiting on the host inside a busy period
from the batch running empty, which `device_idle_pct.decode` lumps."""
from harness import starved


def compute(ctx):
    d = starved.read(ctx)
    return None if d is None else starved.starved_pct(d)
