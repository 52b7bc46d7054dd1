"""Host time of one pass of the engine loop per decode step, waiting for
traffic left out: T(engine.iteration) - T(engine.wait_work) over the
count of engine.decode_readback, from the server's span counters."""
from harness import spans


def compute(ctx):
    d = spans.read(ctx)
    return None if d is None else spans.loop_period_ms(d)
