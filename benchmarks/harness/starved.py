"""The engine's starvation account, read from the server's counters
between the window's two scrapes: when the chip had nothing to run as far
as the engine thread could see, what it was then given and what the host
was doing meanwhile.

`tpumlops_device_starved_seconds_total{before}` and
`tpumlops_device_starved_intervals_total{before}`: an interval opens where
the engine thread sees the last tick program it dispatched end and closes
where the call that hands the device the next one returns; `before` is that
program's tick kind (`decode`, `chunk`, `insert`, `seed`, ...).
`tpumlops_device_starved_by_span_seconds_total{span}`: the same seconds by
the `engine.*` phase whose self time covered them.  Time the loop waits for
traffic (`engine.wait_work`) is in no interval, so the share is taken of
the loop's busy time: the root `engine.iteration` less `engine.wait_work`
(`harness/spans.py`).  It is the host's view and can only under-count.

A program that has no such counters (a commit before them) gives `None`
everywhere, and the metric is left out of the line."""

from __future__ import annotations

from . import prom, spans

SECONDS = "tpumlops_device_starved_seconds_total"
INTERVALS = "tpumlops_device_starved_intervals_total"
BY_SPAN = "tpumlops_device_starved_by_span_seconds_total"
CHUNKS = "tpumlops_prefill_dispatch_total"  # one a chunk program, both `when`


def _labels(samples: dict, name: str, label: str) -> list[str]:
    """Every value `label` takes on the series of `name`."""
    return sorted({v for (n, labels) in samples if n == name
                   for k, v in labels if k == label})


def deltas(before: dict, after: dict) -> dict | None:
    """{"busy_s", "steps", "chunks", "starved_s", "before": {kind: [seconds,
    intervals]}, "span": {name: seconds}} over the window; None when the
    program keeps no account or no step closed in the window."""
    kinds = _labels(after, SECONDS, "before")
    d = spans.deltas(before, after)
    if not kinds or d is None:
        return None

    def over(name: str, label: str, value: str) -> float:
        # (`prom.delta` takes its scrapes as `before` and `after`: a label
        # of that name has to go round it.)
        want = {label: value}
        return prom.total(after, name, **want) - prom.total(before, name, **want)

    by_before = {k: [over(SECONDS, "before", k), over(INTERVALS, "before", k)]
                 for k in kinds}
    by_span = {s: over(BY_SPAN, "span", s) for s in _labels(after, BY_SPAN, "span")}
    return {
        "busy_s": d[spans.ROOT]["total_s"] - d["engine.wait_work"]["total_s"],
        "steps": d[spans.STEP]["n"],
        "chunks": prom.delta(before, after, CHUNKS),
        "starved_s": sum(s for s, _n in by_before.values()),
        "before": by_before,
        "span": by_span,
    }


def starved_pct(d: dict) -> float | None:
    """Share of the loop's busy time the chip had nothing to run."""
    return 100.0 * d["starved_s"] / d["busy_s"] if d["busy_s"] > 0 else None


def before_step_ms(d: dict) -> float:
    """Starved time in front of a decode step, a step (a step that met a
    busy chip counts as 0)."""
    return 1e3 * d["before"].get("decode", [0.0, 0])[0] / d["steps"]


def before_chunk_ms(d: dict) -> float | None:
    """Starved time in front of a prefill chunk, a chunk program of the
    single-admission path (a chunk that met a busy chip counts as 0; an
    admission's first chunk, sent in turn behind the scratch, is in it)."""
    if d["chunks"] <= 0:
        return None
    return 1e3 * d["before"].get("chunk", [0.0, 0])[0] / d["chunks"]


def table(d: dict) -> str:
    busy = d["busy_s"]
    pct = lambda s: 100.0 * s / busy if busy > 0 else 0.0  # noqa: E731
    rows = ", ".join(
        f"{kind} {s:.4f} s = {1e3 * s / n if n else 0.0:.3f} ms x {n:.0f} ({pct(s):.2f} %)"
        for kind, (s, n) in sorted(d["before"].items(), key=lambda kv: -kv[1][0])
        if n
    )
    under = ", ".join(
        f"{name.split('.', 1)[-1]} {s:.4f} ({pct(s):.2f} %)"
        for name, s in sorted(d["span"].items(), key=lambda kv: -kv[1]) if s > 0
    )
    return (f"device starved {d['starved_s']:.4f} s of {busy:.3f} s busy "
            f"({pct(d['starved_s']):.2f} %) over {d['steps']:.0f} steps and "
            f"{d['chunks']:.0f} chunks; before: {rows}; under span, s: {under}")


def read(ctx) -> dict | None:
    """The window's deltas, with the whole table put into the run's log
    once, whichever reader comes first."""
    d = deltas(ctx.before, ctx.after)
    if d is not None:
        line = table(d)
        if line not in ctx.notes:
            ctx.note(line)
    return d
