"""Host time of one pass of the engine loop per decode step, waiting for
traffic left out: T(engine.iteration) - T(engine.wait_work) over the
count of engine.decode_readback, from the server's span counters.  In a
saturated cell a pass is a prefill chunk and a decode step, and the
period moves `tokens_per_s`."""
from harness import spans


def compute(ctx):
    d = spans.read(ctx)
    return None if d is None else spans.loop_period_ms(d)
