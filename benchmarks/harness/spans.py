"""The engine loop's phase spans, read from the server's span counters
(`tpumlops_span_seconds_total`, `tpumlops_span_self_seconds_total`,
`tpumlops_spans_total`, label `span`) between the window's two scrapes.

A pass of the loop is the root span `engine.iteration`; nine phases under
it cover it.  A phase's *total* includes spans nested in it (the blocking
read-back of a verify or super-step sits inside its dispatch), its *self*
time does not, so the self times of the phases and of the root add up to
the root's total.  A step is one `engine.decode_readback`: one blocking
read of a decode dispatch's tokens.

A program that has no such counters (a commit before them) gives `None`
everywhere, and the metric is left out of the line."""

from __future__ import annotations

from . import prom

ROOT = "engine.iteration"
PHASES = (
    "engine.wait_work", "engine.admit", "engine.prefill_dispatch",
    "engine.prefill_sync", "engine.decode_assemble", "engine.decode_dispatch",
    "engine.decode_readback", "engine.emit", "engine.journal",
)
STEP = "engine.decode_readback"
# Blocked on the device or waiting for traffic: not the host's own cost.
NOT_HOST = ("engine.wait_work", "engine.decode_readback", "engine.prefill_sync")


def deltas(before: dict, after: dict) -> dict[str, dict[str, float]] | None:
    """{span: {"n", "total_s", "self_s"}} over the window, or None when no
    step closed in it."""
    out = {}
    for name in (ROOT,) + PHASES:
        out[name] = {
            "n": prom.delta(before, after, "tpumlops_spans_total", span=name),
            "total_s": prom.delta(before, after, "tpumlops_span_seconds_total", span=name),
            "self_s": prom.delta(before, after, "tpumlops_span_self_seconds_total", span=name),
        }
    return out if out[STEP]["n"] > 0 else None


def loop_period_ms(d: dict) -> float:
    """Host time of the loop a step, waiting for traffic left out: what
    TPOT is made of."""
    return 1e3 * (d[ROOT]["total_s"] - d["engine.wait_work"]["total_s"]) / d[STEP]["n"]


def loop_host_ms(d: dict) -> float:
    """The loop's period less the time it is blocked on the device."""
    busy = d[ROOT]["total_s"] - sum(d[name]["total_s"] for name in NOT_HOST)
    return 1e3 * busy / d[STEP]["n"]


def covered_pct(d: dict) -> float:
    """Share of the root's time that the phases cover."""
    root = d[ROOT]
    return 100.0 * (1.0 - root["self_s"] / root["total_s"]) if root["total_s"] > 0 else 0.0


def table(d: dict) -> str:
    steps = d[STEP]["n"]
    cells = ", ".join(
        f"{name.split('.', 1)[1]} {1e3 * d[name]['self_s'] / steps:.3f}"
        for name in PHASES
    )
    return (f"engine loop, self ms a step over {steps:.0f} steps and "
            f"{d[ROOT]['n']:.0f} passes: {cells}; uninstrumented "
            f"{1e3 * d[ROOT]['self_s'] / steps:.3f} (phases cover "
            f"{covered_pct(d):.2f} % of engine.iteration); period "
            f"{loop_period_ms(d):.3f}, host {loop_host_ms(d):.3f}")


def read(ctx) -> dict | None:
    """The window's deltas, with the phase table put into the run's log
    once, whichever reader comes first."""
    d = deltas(ctx.before, ctx.after)
    if d is not None:
        line = table(d)
        if line not in ctx.notes:
            ctx.note(line)
    return d
