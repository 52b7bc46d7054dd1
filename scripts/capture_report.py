#!/usr/bin/env python3
"""What one `POST /debug/profile` capture says about the engine loop.

    python scripts/capture_report.py <capture dir or .xplane.pb> [--json out.json]

Opens the `.xplane.pb` with `jax.profiler.ProfileData` and prints:

- the `/host:CPU` line(s) that hold `engine.*` spans (the engine thread's),
  and per span name the count, total and self ms inside the capture, with
  the loop's period and host time a step as the benchmark's readers compute
  them from the counters;
- for every `engine.decode_dispatch` .. end of the next
  `engine.decode_readback`, which device programs ran in between (the
  shared clock at work);
- the largest device ops with what the profiler knows of each (`tf_op` is
  the jax name stack, `jax.named_scope`s included, and `source` the line
  that asked for the op), and device seconds grouped by innermost scope.
  Those two live in the xplane's event metadata, which `ProfileData` does
  not expose: they are read through tensorflow's `xplane_pb2` where that
  is installed, and left out where it is not.

An executable loaded from jax's persistent compile cache keeps the
metadata of whoever compiled it first (the cache key leaves metadata out):
scopes added since then show only in a capture of a process that compiled
cold (`JAX_COMPILATION_CACHE_DIR` pointed at an empty directory).
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

ROOT = "engine.iteration"
STEP = "engine.decode_readback"
NOT_HOST = ("engine.wait_work", "engine.decode_readback", "engine.prefill_sync")
SCOPES = ("embed", "layer.attn_qkv", "layer.attn_core", "layer.attn_out",
          "layer.mlp", "kv_commit", "head", "sample",
          # models/mla_moe.py (PR 27)
          "layer.mla_q", "layer.mla_kv", "layer.moe_router",
          "layer.moe_experts", "layer.moe_shared")
_SCOPE = re.compile("|".join(re.escape(s) for s in SCOPES))


def find_xplane(path: Path) -> Path:
    if path.is_file():
        return path
    found = sorted(path.rglob("*.xplane.pb"))
    if len(found) != 1:
        raise SystemExit(f"{path} holds {len(found)} captures: {found}")
    return found[0]


def host_spans(data) -> dict[str, list[tuple[str, float, float]]]:
    """{line name: [(span, start_ns, end_ns), ...]} for host lines that
    hold engine.* events."""
    out = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            mine = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                    for e in line.events if e.name.startswith("engine.")]
            if mine:
                out[f"{plane.name}/{line.name}"] = sorted(mine, key=lambda r: r[1])
    return out


def self_times(events: list[tuple[str, float, float]]) -> dict[str, dict]:
    """Count, total and self seconds per name, nesting by containment."""
    stats: dict[str, dict] = {}
    stack: list[list] = []  # [name, start, end, child_ns]

    def close(rec):
        name, s, e, child = rec
        st = stats.setdefault(name, {"n": 0, "total_s": 0.0, "self_s": 0.0, "max_s": 0.0})
        st["n"] += 1
        st["total_s"] += (e - s) / 1e9
        st["self_s"] += (e - s - child) / 1e9
        st["max_s"] = max(st["max_s"], (e - s) / 1e9)
        if stack:
            stack[-1][3] += e - s

    for name, s, e in sorted(events, key=lambda r: (r[1], -r[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return stats


def device_events(data):
    """(line, name, start_ns, end_ns) of every op and module event of the
    device planes."""
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for e in line.events:
                yield (line.name, e.name, float(e.start_ns),
                       float(e.start_ns + e.duration_ns))


def op_metadata(xplane: Path) -> dict[str, dict]:
    """{op's event name: {"tf_op", "source"}} from the device planes' event
    metadata; empty where tensorflow's xplane proto is not installed."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        return {}
    space = xplane_pb2.XSpace()
    space.ParseFromString(xplane.read_bytes())
    out: dict[str, dict] = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for md in plane.event_metadata.values():
            rec = {}
            for st in md.stats:
                key = names.get(st.metadata_id)
                if key in ("tf_op", "source"):
                    rec[key] = st.str_value or names.get(st.ref_value, "")
            out.setdefault(md.name, rec)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("capture")
    ap.add_argument("--json")
    ap.add_argument("--top", type=int, default=16)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    xplane = find_xplane(Path(args.capture))
    data = ProfileData.from_file(str(xplane))
    report: dict = {"xplane": str(xplane), "bytes": xplane.stat().st_size,
                    "planes": [p.name for p in data.planes]}
    print(f"{xplane} ({report['bytes'] / 2**20:.1f} MiB); planes {report['planes']}")

    lines = host_spans(data)
    report["engine_lines"] = {}
    for name, events in lines.items():
        st = self_times(events)
        steps = st.get(STEP, {}).get("n", 0)
        row = {"spans": st, "steps": steps}
        if steps and ROOT in st:
            wait = st.get("engine.wait_work", {}).get("total_s", 0.0)
            blocked = sum(st.get(n, {}).get("total_s", 0.0) for n in NOT_HOST)
            row["loop_period_ms"] = 1e3 * (st[ROOT]["total_s"] - wait) / steps
            row["loop_host_ms"] = 1e3 * (st[ROOT]["total_s"] - blocked) / steps
        report["engine_lines"][name] = row
        print(f"host line {name}: {len(events)} engine.* events, {steps} steps, "
              f"period {row.get('loop_period_ms')}, host {row.get('loop_host_ms')}")
        for span, s in sorted(st.items()):
            print(f"  {span:26s} n={s['n']:5d} total {1e3 * s['total_s']:9.3f} ms "
                  f"self {1e3 * s['self_s']:9.3f} ms max {1e3 * s['max_s']:8.3f} ms"
                  + (f"  self/step {1e3 * s['self_s'] / steps:7.3f}" if steps else ""))

    dev = list(device_events(data))
    modules = [d for d in dev if d[0] == "XLA Modules"]
    ops = [d for d in dev if d[0] == "XLA Ops"]
    # Device programs between a decode dispatch and the end of its read-back.
    pairs = []
    for events in lines.values():
        dispatches = [(s, e) for n, s, e in events if n == "engine.decode_dispatch"]
        readbacks = [(s, e) for n, s, e in events if n == STEP]
        for ds, _de in dispatches:
            nxt = next((re_ for rs, re_ in readbacks if rs >= ds), None)
            if nxt is None:
                continue
            # By midpoint: the device's clock is joined to the host's to
            # within a millisecond or two, the edges may cross.
            inside = sorted({m[1].split("(", 1)[0] for m in modules
                             if ds <= (m[2] + m[3]) / 2 <= nxt})
            pairs.append({"dispatch_ns": ds, "readback_end_ns": nxt,
                          "ms": (nxt - ds) / 1e6, "programs": inside})
    with_decode = sum(any("decode" in p for p in r["programs"]) for r in pairs)
    report["dispatch_to_readback"] = {
        "pairs": len(pairs), "with_a_decode_program_inside": with_decode,
        "first": pairs[:3]}
    print(f"decode_dispatch -> end of decode_readback: {len(pairs)} pairs, "
          f"{with_decode} with a decode program's device execution inside; first: "
          f"{pairs[:2]}")

    by_op: dict[str, list] = {}
    for _line, name, s, e in ops:
        rec = by_op.setdefault(name, [0.0, 0])
        rec[0] += (e - s) / 1e9
        rec[1] += 1
    top = sorted(by_op.items(), key=lambda kv: -kv[1][0])[: args.top]
    meta = op_metadata(xplane)
    report["top_ops"] = []
    by_scope: dict[str, float] = {}
    for name, (sec, _n) in by_op.items():
        found = _SCOPE.findall(meta.get(name, {}).get("tf_op", ""))
        key = found[-1] if found else "(no scope)"
        by_scope[key] = by_scope.get(key, 0.0) + sec
    for name, (sec, n) in top:
        stats = meta.get(name, {})
        report["top_ops"].append({"op": name[:300], "seconds": sec, "n": n, **stats})
        print(f"op {sec:8.4f}s x{n:5d} {name[:140]}")
        for k, v in stats.items():
            print(f"      {k}: {v[:300]}")
    report["device_seconds_by_scope"] = dict(sorted(by_scope.items(), key=lambda kv: -kv[1]))
    print("device seconds by innermost named scope:", report["device_seconds_by_scope"])
    if args.json:
        Path(args.json).parent.mkdir(parents=True, exist_ok=True)
        Path(args.json).write_text(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
