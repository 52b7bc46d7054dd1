#!/usr/bin/env python3
"""What one `POST /debug/profile` capture says about the engine loop.

    python scripts/capture_report.py <capture dir or .xplane.pb> [--json out.json]
        [--profile-response resp.json]
    python scripts/capture_report.py --cell <benchmark cell> --seed N --keep <dir>

The second form runs `benchmarks/run.py --workload <cell> --trace 1` (on the
chip: through `chiprun`), keeps the run's capture (the harness deletes its
work directory: the capture is hard-linked into `<dir>` as it appears) and
its log, and reports on it with the `/debug/profile` answer the log holds.

Opens the `.xplane.pb` with `jax.profiler.ProfileData` and prints:

- the `/host:CPU` line(s) that hold `engine.*` spans (the engine thread's),
  and per span name the count, total and self ms inside the capture, with
  the loop's period and host time a step as the benchmark's readers compute
  them from the counters;
- for every `engine.decode_dispatch` .. end of the next
  `engine.decode_readback`, which device programs ran in between (the
  shared clock at work);
- **the device's idle gaps by their true names**: every gap of at least
  50 us on a device's `XLA Modules` line, seconds and count by the program
  that truly follows it (the harness's `idle_gaps` are named one program
  late, PERF.md 7.10) and by the `engine.*` span whose self time covers it
  on the shared clock; beside them, where the `/debug/profile` answer is
  given (`spans_at`), the engine's own starvation account over the same
  seconds (`tpumlops_device_starved_*`): the host's view against the
  device's;
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


def self_segments(spans: list[tuple[str, float, float]]) -> list[tuple[float, float, str]]:
    """[(start, end, name)]: whose SELF time each instant of one thread's
    spans is (the innermost span open there), nesting by containment;
    sorted, not overlapping."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[str, float]] = []  # (name, end)
    at = 0.0

    def upto(t: float) -> None:
        nonlocal at
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > at:
                out.append((at, end, name))
                at = end
        if stack and t > at:
            out.append((at, t, stack[-1][0]))
        at = max(at, t)

    for name, s, e in sorted(spans, key=lambda r: (r[1], -r[2])):
        upto(s)
        stack.append((name, e))
    upto(float("inf"))
    return out


def gap_table(
    modules: list[tuple[str, float, float]],
    spans: list[tuple[str, float, float]],
    min_gap_ns: float = 50e3,
) -> dict:
    """The idle gaps of ONE device's `XLA Modules` line, by true names.

    `modules` are (name, start_ns, end_ns) of its program executions,
    `spans` (name, start_ns, end_ns) of the engine thread's `engine.*`
    events on the same clock.  A gap is the time between the end of
    everything that ran so far and the next program's start, kept where
    it is at least `min_gap_ns`.  Returns the window (first start to last
    end), the gaps' seconds and count by the program that follows
    (`jit__decode_greedy(123)` -> `jit__decode_greedy`) and their seconds
    by the span whose self time covers them (`(no span)` where none
    does)."""
    import bisect

    mods = sorted(modules, key=lambda m: m[1])
    by_program: dict[str, list] = {}
    by_span: dict[str, float] = {}
    segs = self_segments(spans)
    starts = [s for s, _e, _n in segs]
    total, n, end = 0.0, 0, None
    for name, s, e in mods:
        if end is not None and s - end >= min_gap_ns:
            rec = by_program.setdefault(name.split("(", 1)[0], [0.0, 0])
            rec[0] += (s - end) / 1e9
            rec[1] += 1
            total += (s - end) / 1e9
            n += 1
            left = s - end
            i = max(0, bisect.bisect_right(starts, end) - 1)
            while i < len(segs) and segs[i][0] < s:
                a, b, span = segs[i]
                part = min(b, s) - max(a, end)
                if part > 0:
                    by_span[span] = by_span.get(span, 0.0) + part / 1e9
                    left -= part
                i += 1
            if left > 0:
                by_span["(no span)"] = by_span.get("(no span)", 0.0) + left / 1e9
        end = e if end is None else max(end, e)
    window = (end - mods[0][1]) / 1e9 if mods else 0.0
    order = lambda d, key: dict(sorted(d.items(), key=key))  # noqa: E731
    return {
        "window_s": window, "gap_s": total, "gaps": n,
        "by_program": order(by_program, lambda kv: -kv[1][0]),
        "by_span": order(by_span, lambda kv: -kv[1]),
    }


def account_delta(spans_at: dict) -> dict | None:
    """The starvation account and the loop's busy seconds between the two
    `/debug/spans` payloads of a `/debug/profile` answer (`spans_at`)."""
    a, b = spans_at.get("start"), spans_at.get("stop")
    if not a or not b or "device_starved" not in b:
        return None
    d0, d1 = a["device_starved"], b["device_starved"]
    by_before = {}
    for label, rec in d1["by_label"].items():
        was = d0["by_label"].get(label, {"seconds": 0.0, "intervals": 0})
        n = rec["intervals"] - was["intervals"]
        if n:
            by_before[label] = [rec["seconds"] - was["seconds"], n]
    by_span = {k: v - d0["by_span_s"].get(k, 0.0) for k, v in d1["by_span_s"].items()}
    total = lambda at, name: at["spans"].get(name, {}).get("total_s", 0.0)  # noqa: E731
    busy = (total(b, ROOT) - total(a, ROOT)
            - total(b, "engine.wait_work") + total(a, "engine.wait_work"))
    return {
        "busy_s": busy, "starved_s": sum(s for s, _n in by_before.values()),
        "by_before": dict(sorted(by_before.items(), key=lambda kv: -kv[1][0])),
        "by_span": {k: v for k, v in sorted(by_span.items(), key=lambda kv: -kv[1]) if v > 0},
    }


def print_gaps(report: dict, data, lines: dict, spans_at: dict | None) -> None:
    spans = [ev for events in lines.values() for ev in events]
    report["idle_gaps"] = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        mods = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                for line in plane.lines if line.name == "XLA Modules"
                for e in line.events]
        if not mods:
            continue
        t = report["idle_gaps"][plane.name] = gap_table(mods, spans)
        w = t["window_s"]
        print(f"{plane.name}: idle gaps >= 50 us on the XLA Modules line: "
              f"{t['gap_s']:.4f} s in {t['gaps']} gaps of a {w:.3f} s window "
              f"({100 * t['gap_s'] / w if w else 0:.2f} %)")
        for name, (sec, n) in t["by_program"].items():
            print(f"  before {name:34s} {sec:8.4f} s = {1e3 * sec / n:7.3f} ms x {n:4d}"
                  f"  {100 * sec / w:5.2f} %")
        for name, sec in t["by_span"].items():
            print(f"  under  {name:34s} {sec:8.4f} s  {100 * sec / w:5.2f} %")
    acc = account_delta(spans_at) if spans_at else None
    report["device_starved"] = acc
    if acc is None:
        print("no /debug/profile answer with `spans_at` given: the engine's own "
              "account over the capture is not shown")
        return
    busy = acc["busy_s"]
    print(f"the engine's starvation account over the capture: {acc['starved_s']:.4f} s "
          f"of {busy:.3f} s busy ({100 * acc['starved_s'] / busy if busy else 0:.2f} %)")
    for label, (sec, n) in acc["by_before"].items():
        print(f"  before {label:34s} {sec:8.4f} s = {1e3 * sec / n:7.3f} ms x {n:4d}"
              f"  {100 * sec / busy if busy else 0:5.2f} %")
    for name, sec in acc["by_span"].items():
        print(f"  under  {name:34s} {sec:8.4f} s  {100 * sec / busy if busy else 0:5.2f} %")


def run_cell(cell: str, seed: int, keep: Path, extra: list[str]) -> tuple[Path, dict | None]:
    """One traced run of a benchmark cell with its capture kept: the
    harness deletes its work directory when it is done, so every
    `.xplane.pb` that appears under the run's `$TMPDIR` is hard-linked into
    `keep` at once.  Returns the capture and the `/debug/profile` answer's
    `spans_at` as the run's log has it."""
    import ast
    import os
    import subprocess
    import threading

    keep.mkdir(parents=True, exist_ok=True)
    tmp = keep / "tmp"
    tmp.mkdir(exist_ok=True)
    log = keep / f"{cell}.t1.{seed}.out"  # the run's log, its result line last
    err = keep / f"{cell}.t1.{seed}.err"
    done = threading.Event()
    kept: list[Path] = []

    def watch() -> None:
        while not done.wait(0.2):
            for f in tmp.rglob("*.xplane.pb"):
                to = keep / f"{cell}.{seed}.xplane.pb"
                if not to.exists():
                    os.link(f, to)
                    kept.append(to)

    threading.Thread(target=watch, daemon=True).start()
    # From the root of the checkout, as `BENCHMARK.json`'s command runs it.
    cmd = [sys.executable, "benchmarks/run.py", "--workload", cell,
           "--seed", str(seed), "--trace", "1", *extra]
    try:
        with open(log, "w") as std, open(err, "w") as errs:
            rc = subprocess.run(cmd, env=dict(os.environ, TMPDIR=str(tmp.resolve())),
                                stdout=std, stderr=errs).returncode
    finally:
        done.set()
    lines = log.read_text(errors="replace").splitlines()
    print(f"{' '.join(cmd)}: exit {rc}; log {log}, {err}")
    print("\n".join(lines[-1:]))
    if rc != 0 or not kept:
        raise SystemExit(f"no capture kept (exit {rc}): see {log} and {err}")
    spans_at = None
    for line in lines:
        _, found, rest = line.partition("profile: ")
        if found and rest.startswith("{"):
            spans_at = (ast.literal_eval(rest).get("body") or {}).get("spans_at")
    return kept[0], spans_at


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
    ap.add_argument("capture", nargs="?")
    ap.add_argument("--json")
    ap.add_argument("--top", type=int, default=16)
    ap.add_argument("--profile-response",
                    help="the JSON answer of the POST /debug/profile that made the capture")
    ap.add_argument("--cell", help="run this benchmark cell traced and report on its capture")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="passed on to benchmarks/run.py (a tiny cell on the CPU)")
    ap.add_argument("--keep", default="benchmarks/.work/captures",
                    help="where --cell keeps the capture and the run's log")
    args = ap.parse_args()
    spans_at = None
    if args.cell:
        extra = ["--seconds", str(args.seconds)] if args.seconds is not None else []
        extra += ["--rehearse-cpu"] if args.rehearse_cpu else []
        capture, spans_at = run_cell(args.cell, args.seed, Path(args.keep), extra)
    elif args.capture:
        capture = Path(args.capture)
    else:
        ap.error("a capture or --cell is needed")
    if args.profile_response:
        spans_at = json.loads(Path(args.profile_response).read_text()).get("spans_at")
    from jax.profiler import ProfileData

    xplane = find_xplane(capture)
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

    print_gaps(report, data, lines, spans_at)

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
