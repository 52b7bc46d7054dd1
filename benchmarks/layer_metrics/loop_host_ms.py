"""The engine loop's period per decode step less the time it is blocked
on the device (engine.decode_readback, engine.prefill_sync): what the
host itself costs a step, from the server's span counters."""
from harness import spans


def compute(ctx):
    d = spans.read(ctx)
    return None if d is None else spans.loop_host_ms(d)
