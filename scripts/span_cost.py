#!/usr/bin/env python3
"""What one `Tracer.span` costs: enter + exit, in microseconds, best of
five rounds, nested under a root as the engine's are.  The budget is 2 us
with no capture running (ISSUE 25); `--capture` also times it inside a
`jax.profiler` capture, where each span writes a TraceMe event.

    python scripts/span_cost.py [--capture]
"""

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from tpumlops.utils.tracing import Tracer  # noqa: E402

N = 200_000


def cost_us(tracer: Tracer) -> float:
    span = tracer.span
    best = float("inf")
    for _ in range(5):
        with span("root"):
            t0 = time.perf_counter()
            for _ in range(N):
                with span("child"):
                    pass
            best = min(best, (time.perf_counter() - t0) / N * 1e6)
    return best


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--capture", action="store_true")
    args = ap.parse_args()
    out = {"plain_us": cost_us(Tracer()),
           "profiler_sink_idle_us": cost_us(Tracer(profiler=True))}
    if args.capture:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d, profiler_options=opts)
            try:
                out["profiler_sink_capturing_us"] = cost_us(Tracer(profiler=True))
            finally:
                jax.profiler.stop_trace()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
