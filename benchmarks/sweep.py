#!/usr/bin/env python3
"""The builder's tool for finding a steady cell's knee: one server, a
ladder of offered rates, a short window at each.

    python3 benchmarks/sweep.py --workload <cell> --rates 2,3,4,5,6 --seconds 20

Every rung is drained before the next begins: the client waits for each
request's answer (`drained_s` past the close), so no backlog carries over,
and with one `--seed` every rung's schedule comes from the same draw.

The knee is the highest rate at which completions keep up with arrivals
over the window: the backlog at the close (requests due but unfinished)
is no larger than the slots can hold, and TTFT does not climb through the
window.  A steady cell's `rate_rps` is then 0.8 of it, written into
`cells/<cell>.json` by hand with the ladder in PERF.md.  Run it again
when an optimisation has overtaken the rate (nearly every request meets
any limit): only a `benchmark` PR may then change the cell's file.

Not part of a benchmark run; prints one JSON line per rate.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT))

import run as bench_run  # noqa: E402
from harness import loadgen, manifest, metrics  # noqa: E402
from harness import server as srv  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    cell = manifest.load_cell(args.workload, ROOT)
    if cell.mix["loop"] != "open":
        print("error: a sweep is for open-loop mixes", file=sys.stderr)
        return 2
    platform = "cpu" if args.rehearse_cpu else "tpu"
    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu" and not args.rehearse_cpu:
        print("error: JAX_PLATFORMS=cpu: no accelerator to sweep on", file=sys.stderr)
        return 3
    work = Path(tempfile.mkdtemp(prefix="tpumlops-sweep-"))
    child = None
    try:
        uri = bench_run.make_artifact(work, cell, args.seed)
        child = srv.ServerChild(uri, cell.config["serving"], bench_run.cache_dir(),
                                work / "server.log", platform, work / "profile", ROOT)
        child.start()
        print(f"server ready in {child.boot_s:.1f}s", flush=True)
        shapes = manifest.load_reference(cell, ROOT).shapes(cell.model)
        slots = int(cell.config["serving"]["tpu"].get("maxSlots") or 8)
        for rate in (float(r) for r in args.rates.split(",")):
            plan = loadgen.build_plan(
                cell.mix, {"rate_rps": rate}, shapes.vocab,
                int(cell.model["max_position_embeddings"]), args.seed,
                args.seconds)
            driver = loadgen.Driver(child.generate_url, plan)
            driver.run()
            win = metrics.measured(driver.records, args.seconds)
            done_in = [r for r in win if r.complete and r.token_times[-1] < args.seconds]
            backlog = len(win) - len(done_in)
            half = args.seconds / 2
            first = [metrics.ttft_s(r) for r in win if r.start < half]
            second = [metrics.ttft_s(r) for r in win if r.start >= half]
            e2e = metrics.end_to_end(driver.records, args.seconds)
            print(json.dumps({
                "rate_rps": rate, "due": len(win),
                "failed": sum(not r.complete for r in win),
                "finished_in_window": len(done_in),
                "backlog_at_close": backlog, "slots": slots,
                "ttft_p50_first_half_ms": 1e3 * metrics.percentile(first, 50),
                "ttft_p50_second_half_ms": 1e3 * metrics.percentile(second, 50),
                "ttft_p90_ms": e2e["ttft_p90_ms"], "tpot_p50_ms": e2e["tpot_p50_ms"],
                "tpot_p90_ms": e2e["tpot_p90_ms"],
                "tokens_per_s": e2e["tokens_per_s"],
                "late_ms": loadgen.lateness_ms(driver),
                "drained_s": max((r.gave_up for r in driver.records), default=0.0)
                - args.seconds,
            }), flush=True)
        child.terminate()
        child = None
        return 0
    finally:
        if child is not None:
            sys.stderr.write(child.log_tail(40) + "\n")
            child.kill()
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
