"""Share of the engine loop's busy time in which the chip had nothing to
run as far as the engine thread could see
(`tpumlops_device_starved_seconds_total` over the window): the program's
own word for what `device_idle_pct.prefill` times from outside, over the
whole window and by true names."""
from harness import starved


def compute(ctx):
    d = starved.read(ctx)
    return None if d is None else starved.starved_pct(d)
