"""Metric arithmetic on client-side token timelines.

Times are seconds on the load generator's clock with the window's start
at 0.  A request that failed, or never finished, misses every latency:
its time is the wait until the harness gave up on it."""

from __future__ import annotations

import math
from dataclasses import dataclass, field


@dataclass
class Record:
    """One request as the client saw it."""

    idx: int
    prompt_len: int
    max_new: int
    start: float  # due time (open loop) or send time (closed loop)
    sent: float = math.nan
    token_times: list[float] = field(default_factory=list)
    tokens: list[int] = field(default_factory=list)
    final_ids: list[int] | None = None
    error: str | None = None
    gave_up: float = math.nan  # when the harness stopped waiting
    prompt_ids: list[int] | None = None

    @property
    def complete(self) -> bool:
        return (self.error is None and self.final_ids is not None
                and len(self.tokens) == self.max_new)


def percentile(values: list[float], pct: float) -> float:
    """Nearest rank (copied from bench.py `_percentiles`)."""
    if not values:
        return math.nan
    ordered = sorted(values)
    rank = max(1, math.ceil(pct / 100.0 * len(ordered)))
    return ordered[min(rank, len(ordered)) - 1]


def ttft_s(r: Record) -> float:
    if r.token_times and r.error is None:
        return r.token_times[0] - r.start
    return r.gave_up - r.start


def tpot_s(r: Record) -> float | None:
    """(last token - first token) / (tokens - 1); a request that did not
    finish is charged its whole wait over the tokens it owed."""
    if r.complete:
        if len(r.token_times) < 2:
            return None
        return (r.token_times[-1] - r.token_times[0]) / (len(r.token_times) - 1)
    first = r.token_times[0] if r.token_times else r.start
    return (r.gave_up - first) / max(1, r.max_new - 1)


def measured(records: list[Record], seconds: float) -> list[Record]:
    """Requests of the window: due (or sent) in [0, seconds)."""
    return [r for r in records if 0.0 <= r.start < seconds]


def tokens_in_window(records: list[Record], seconds: float) -> tuple[int, int]:
    """(prompt tokens credited at a first token inside the window,
    generated tokens arriving inside it), over every request alive in the
    window, whenever it was sent: all work over all time."""
    prompt = generated = 0
    for r in records:
        for i, t in enumerate(r.token_times):
            if 0.0 <= t < seconds:
                generated += 1
                if i == 0:
                    prompt += r.prompt_len
    return prompt, generated


def end_to_end(records: list[Record], seconds: float) -> dict[str, float]:
    """Every end-to-end quantity the harness can take from timelines; the
    manifest decides which of them a cell reports."""
    win = measured(records, seconds)
    ttfts = [ttft_s(r) for r in win]
    tpots = [v for v in (tpot_s(r) for r in win) if v is not None]
    prompt, generated = tokens_in_window(records, seconds)
    return {
        "ttft_p50_ms": 1e3 * percentile(ttfts, 50),
        "ttft_p90_ms": 1e3 * percentile(ttfts, 90),
        "tpot_p50_ms": 1e3 * percentile(tpots, 50),
        "tpot_p90_ms": 1e3 * percentile(tpots, 90),
        "tokens_per_s": (prompt + generated) / seconds,
        "generated_tokens_per_s": generated / seconds,
    }
