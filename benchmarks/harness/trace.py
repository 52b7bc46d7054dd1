"""From a profiler trace (`.xplane.pb`) to device busy time, per-program
device time and the breakdown of the last line.

Two steps, so the arithmetic can be checked on a small recorded trace
without the profiler: `read_xplane` flattens the file to plain lists,
`reduce` does the rest."""

from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(root: str | Path) -> list[Path]:
    """Every capture under `root`, which is one run's own directory."""
    return sorted(Path(root).rglob("*.xplane.pb"))


def read_xplane(path: str | Path, lines: tuple[str, ...] = (OPS_LINE, MODULES_LINE)) -> dict:
    """{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns], ...]}]}]} for the device planes, plus the span of every plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes, lo, hi = [], None, None
    for plane in data.planes:
        keep = plane.name.startswith(DEVICE_PLANE_PREFIX)
        out_lines = []
        for line in plane.lines:
            want = keep and line.name in lines
            events = []
            for ev in line.events:
                s, d = float(ev.start_ns), float(ev.duration_ns)
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
                if want:
                    events.append([ev.name, s, d])
            if want:
                out_lines.append({"name": line.name, "events": events})
        if keep:
            planes.append({"name": plane.name, "lines": out_lines})
    return {"planes": planes, "span_ns": [lo or 0.0, hi or 0.0]}


@dataclass
class TraceSummary:
    window_s: float = 0.0
    busy_s: float = 0.0  # averaged over the device planes
    devices: int = 0
    # program key -> {"seconds": device time, "executions": n}
    programs: dict[str, dict] = field(default_factory=dict)
    modules: list[list] = field(default_factory=list)  # [name, seconds, n]
    device_ops: list[list] = field(default_factory=list)  # [name, seconds]
    idle_gaps: list[list] = field(default_factory=list)  # [what, seconds]


def _union(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """Total covered length and the merged intervals."""
    merged: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


def _short(op: str) -> str:
    """`%copy.55 = bf16[32,8,...]{...} copy(...)` -> `%copy.55 bf16[32,8,...]`:
    the trace names an op by its whole HLO line."""
    head, _, rest = op.partition(" = ")
    shape = "" if rest.startswith("(") else rest.split("{", 1)[0].split(" ", 1)[0]
    return (head + (" " + shape if shape else ""))[:96]


def _base(name: str) -> str:
    """`jit__decode_greedy(123)` -> `jit__decode_greedy`."""
    return name.split("(", 1)[0]


def reduce(flat: dict, programs: dict[str, list[str]]) -> TraceSummary:
    """`programs` maps a key ("decode", "prefill") to substrings of the
    program names the trace's modules line gives."""
    out = TraceSummary()
    lo, hi = flat["span_ns"]
    out.window_s = max(0.0, hi - lo) / 1e9
    busy_total = 0.0
    ops: dict[str, float] = {}
    mods: dict[str, list[float]] = {}
    gaps: dict[str, float] = {}
    for plane in flat["planes"]:
        by_line = {l["name"]: l["events"] for l in plane["lines"]}
        op_events = by_line.get(OPS_LINE) or []
        mod_events = by_line.get(MODULES_LINE) or []
        basis = op_events or mod_events
        if not basis:
            continue
        out.devices += 1
        busy, merged = _union([(s, s + d) for _n, s, d in basis])
        busy_total += busy
        for n, _s, d in op_events:
            n = _short(n)
            ops[n] = ops.get(n, 0.0) + d
        for n, _s, d in mod_events:
            rec = mods.setdefault(_base(n), [0.0, 0])
            rec[0] += d
            rec[1] += 1
        # Idle gaps, named by the program that ran next: what the device
        # was waiting to be given.
        starts = sorted((s, _base(n)) for n, s, _d in mod_events)
        j = 0
        for (_a, end), (nxt, _b) in zip(merged, merged[1:]):
            while j < len(starts) and starts[j][0] < nxt:
                j += 1
            what = "before " + starts[j][1] if j < len(starts) else "after the last program"
            gaps[what] = gaps.get(what, 0.0) + (nxt - end)
    if out.devices:
        out.busy_s = busy_total / out.devices / 1e9
    out.modules = sorted(([n, v[0] / 1e9, v[1]] for n, v in mods.items()),
                         key=lambda r: -r[1])
    for key, needles in programs.items():
        sec = n_exec = 0
        for name, s, n in out.modules:
            if any(x in name for x in needles):
                sec += s
                n_exec += n
        if n_exec:
            out.programs[key] = {"seconds": sec, "executions": n_exec}
    out.device_ops = sorted(([n, v / 1e9] for n, v in ops.items()),
                            key=lambda r: -r[1])[:10]
    out.idle_gaps = sorted(([n, v / 1e9] for n, v in gaps.items()),
                           key=lambda r: -r[1])[:10]
    return out
