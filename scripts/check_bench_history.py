"""Bench regression sentinel (``make verify`` -> ``bench-history``).

Every committed bench record (``BENCH_*.json`` at the repo root) carries
a handful of headline numbers — tok/s, TTFT/ITL tails, dispatch ratios,
compile collapse, token agreement.  Those numbers regress silently: a
PR re-runs one scenario, pastes the new JSON, and nobody compares it to
the record it replaced.  This gate keeps an append-only history
(``BENCH_HISTORY.jsonl``, one compact line per committed record
revision) and fails when a watched key moves the wrong way past its
tolerance versus the LAST committed revision of the same scenario:

- ``higher``: value must not drop below ``last * (1 - tol)``
  (throughput, collapse ratios, speedups),
- ``lower``:  value must not rise above ``last * (1 + tol)``
  (tail latencies, compile counts, loss/hang/shed tallies),
- ``max_delta``: ``abs(new - last)`` must stay within an absolute bound
  (keys that hover near zero or legitimately go negative, like
  observability ``overhead_pct``),
- ``exact``: byte-equal (token_agreement — correctness is not a dial).

Tolerances are deliberately loose for wall-clock keys (CPU bench walls
vary run to run) and zero for deterministic counters.  A legitimate
trade-off (e.g. a feature that costs throughput) updates this registry
or the history in the same PR, visible in the diff.

When a record changed AND passes, its compact line is appended to the
history so the next revision compares against it.  An unchanged record
appends nothing — re-running ``make verify`` is idempotent.

Usage: ``python scripts/check_bench_history.py [--dry-run]``
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
HISTORY = _ROOT / "BENCH_HISTORY.jsonl"

# file -> (scenario, {key: (kind, tolerance)}).  kind semantics in the
# module docstring; tolerance is relative for higher/lower, absolute
# points for max_delta, ignored for exact.
WATCHED: dict[str, tuple[str, dict[str, tuple[str, float]]]] = {
    "BENCH_FLEET_TRACE.json": (
        "fleet_trace",
        {
            "tok_per_s_on": ("higher", 0.30),
            "overhead_pct": ("max_delta", 10.0),
            "stitched_components": ("lower", 0.0),
            "token_agreement": ("exact", 0.0),
        },
    ),
    "BENCH_MULTIMODEL.json": (
        "multimodel_mux",
        {
            "lost": ("lower", 0.0),
            "chips_saved": ("higher", 0.0),
            "p99_ratio": ("max_delta", 0.75),
            "token_agreement": ("exact", 0.0),
        },
    ),
    "BENCH_CHAOS.json": (
        "chaos_resilience",
        {
            "availability_pct": ("higher", 0.0),
            "bare_502": ("lower", 0.0),
            "hangs": ("lower", 0.0),
        },
    ),
    "BENCH_COLD_START.json": (
        "cold_start",
        {
            "restore_speedup_vs_native": ("higher", 0.50),
            "bytes_reduction": ("higher", 0.20),
            "token_agreement": ("exact", 0.0),
        },
    ),
    "BENCH_LONGCTX.json": (
        "longctx_sp",
        {
            "est_ttft_gain_32k": ("higher", 0.10),
            "sp_dispatches": ("lower", 0.0),
            "token_agreement": ("exact", 0.0),
        },
    ),
    "BENCH_TP.json": (
        "tp_dp_ladder",
        {
            "dp_tokens_per_dispatch_ratio": ("higher", 0.10),
            "token_agreement": ("exact", 0.0),
            "dp_token_agreement": ("exact", 0.0),
        },
    ),
    "BENCH_ANOMALY.json": (
        "anomaly_observability_serving",
        {
            "tok_per_s_on": ("higher", 0.30),
            "overhead_pct": ("max_delta", 10.0),
            "straggler_flagged": ("exact", 0.0),
            "false_positives": ("lower", 0.0),
            "token_agreement": ("exact", 0.0),
        },
    ),
}


def lookup(record: dict, key: str):
    """Find ``key`` in ``record``, descending into dict values.

    Committed record shapes vary: most are flat, some nest the numbers
    under ``"result"`` (BENCH_FLEET_TRACE.json).  First match wins on a
    deterministic (insertion-order) walk.
    """
    if key in record:
        return record[key]
    for v in record.values():
        if isinstance(v, dict):
            found = lookup(v, key)
            if found is not None:
                return found
    return None


def extract(record: dict, rules: dict) -> dict:
    out = {}
    for key in rules:
        val = lookup(record, key)
        if isinstance(val, bool):
            val = int(val)
        if val is not None:
            out[key] = val
    return out


def check(scenario: str, keys: dict, last: dict, rules: dict) -> list[str]:
    problems = []
    for key, (kind, tol) in rules.items():
        if key not in keys:
            problems.append(f"{scenario}: watched key {key!r} missing from record")
            continue
        if key not in last:
            continue  # key is new — nothing to regress against
        new, old = keys[key], last[key]
        if kind == "exact":
            if new != old:
                problems.append(
                    f"{scenario}: {key} changed {old!r} -> {new!r} (exact pin)"
                )
        elif kind == "higher":
            floor = old * (1.0 - tol) if old >= 0 else old * (1.0 + tol)
            if new < floor:
                problems.append(
                    f"{scenario}: {key} regressed {old} -> {new} "
                    f"(floor {floor:.4g}, tol {tol:.0%})"
                )
        elif kind == "lower":
            ceil = old * (1.0 + tol) if old >= 0 else old * (1.0 - tol)
            if new > ceil:
                problems.append(
                    f"{scenario}: {key} regressed {old} -> {new} "
                    f"(ceiling {ceil:.4g}, tol {tol:.0%})"
                )
        elif kind == "max_delta":
            if abs(new - old) > tol:
                problems.append(
                    f"{scenario}: {key} moved {old} -> {new} "
                    f"(|delta| {abs(new - old):.4g} > {tol:g})"
                )
    return problems


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser("check_bench_history")
    ap.add_argument(
        "--dry-run",
        action="store_true",
        help="check only; never append to BENCH_HISTORY.jsonl",
    )
    args = ap.parse_args(argv)

    history: dict[str, dict] = {}  # scenario -> last line (latest wins)
    if HISTORY.exists():
        for line in HISTORY.read_text().splitlines():
            line = line.strip()
            if line:
                rec = json.loads(line)
                history[rec["scenario"]] = rec

    problems: list[str] = []
    appends: list[dict] = []
    for fname, (scenario, rules) in sorted(WATCHED.items()):
        path = _ROOT / fname
        if not path.exists():
            continue  # scenario not committed yet — nothing to watch
        record = json.loads(path.read_text())
        keys = extract(record, rules)
        last = history.get(scenario)
        if last is None:
            appends.append({"scenario": scenario, "file": fname, "keys": keys})
            print(f"bench-history: {scenario}: first record, seeding history")
            continue
        if keys == last["keys"]:
            continue  # unchanged — idempotent re-run
        found = check(scenario, keys, last["keys"], rules)
        if found:
            problems.extend(found)
        else:
            appends.append({"scenario": scenario, "file": fname, "keys": keys})
            print(f"bench-history: {scenario}: record changed, within tolerance")

    if problems:
        print("bench-history: REGRESSION (history not updated):", file=sys.stderr)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)
        print(
            "bench-history: a deliberate trade-off updates the registry in "
            "scripts/check_bench_history.py (or amends BENCH_HISTORY.jsonl) "
            "in the same PR.",
            file=sys.stderr,
        )
        return 1

    if appends and not args.dry_run:
        with HISTORY.open("a") as f:
            for rec in appends:
                f.write(json.dumps(rec, sort_keys=True) + "\n")
    n = len(history) + len(appends)
    print(f"bench-history: OK ({n} scenario(s) tracked, {len(appends)} appended)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
