"""Offline SLO planner CLI (the scriptable face of ``spec.planner``).

Replays a journey-ring trace (``GET /router/debug/requests`` export, or
the committed fixture) through the analytic cost model and prints the
cheapest knob configuration meeting the objective as JSON — exactly the
dict the reconciler writes to ``status.plan``.

``make verify`` runs this as the ``plan-contract`` step: ``--dry-run
--expect tests/fixtures/journey_plan.json`` re-plans the committed
fixture trace and fails on ANY byte drift from the committed plan, so a
cost-model change must re-commit the fixture plan (and say why) instead
of silently re-shaping fleets.

Usage:
    python scripts/plan.py --trace export.json --objective-ttft-p99-ms 250
    python scripts/plan.py --dry-run --expect tests/fixtures/journey_plan.json
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

DEFAULT_TRACE = "tests/fixtures/journey_trace.json"
DEFAULT_OBJECTIVE_MS = 250.0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--trace", default=DEFAULT_TRACE,
        help="journey trace: a /router/debug/requests export on disk",
    )
    ap.add_argument(
        "--objective-ttft-p99-ms", type=float,
        default=DEFAULT_OBJECTIVE_MS,
        help="the interactive-class TTFT p99 objective the plan must meet",
    )
    ap.add_argument(
        "--chips", type=int, default=8,
        help="chips the topology provides (bounds tp * replicas)",
    )
    ap.add_argument(
        "--seed", type=int, default=0,
        help="recorded in the plan for provenance (the search is "
        "exhaustive and deterministic; the seed changes nothing)",
    )
    ap.add_argument(
        "--dry-run", action="store_true",
        help="plan and print only — never touches a cluster (currently "
        "the only mode)",
    )
    ap.add_argument(
        "--expect",
        help="path to a committed plan JSON; exit 1 if the computed "
        "plan differs byte-for-byte (the plan-contract CI gate)",
    )
    args = ap.parse_args()

    from tpumlops.operator import planner
    from tpumlops.utils.journey_trace import (
        TraceFormatError,
        load_journey_trace,
    )

    try:
        trace = load_journey_trace(args.trace)
        result = planner.plan(
            trace,
            {"ttftP99Ms": args.objective_ttft_p99_ms},
            chips_available=args.chips,
            seed=args.seed,
        )
    except (TraceFormatError, ValueError) as e:
        print(f"plan: {e}", file=sys.stderr)
        return 2
    text = json.dumps(result, indent=1, sort_keys=True) + "\n"
    sys.stdout.write(text)
    if args.expect:
        expected = Path(args.expect).read_text()
        if text != expected:
            print(
                f"plan-contract FAILED: computed plan differs from "
                f"{args.expect} — the cost model or grid drifted; if "
                "intentional, re-commit the fixture plan",
                file=sys.stderr,
            )
            return 1
        print(f"plan-contract OK ({args.expect})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
