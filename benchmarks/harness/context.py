"""What a per-layer metric's reader is handed: the window's timelines,
the server's counters before and after the window, the reduced trace,
the configuration's shapes and the chip's peaks.  A reader that finds
nothing to read returns None and the metric is left out of the line."""

from __future__ import annotations

from dataclasses import dataclass, field

from . import counts, metrics, prom
from .trace import TraceSummary


@dataclass
class Context:
    cell: object  # manifest.Cell
    seconds: float
    records: list[metrics.Record]
    before: dict  # /metrics at the window's start
    after: dict  # /metrics at its close
    trace: TraceSummary | None
    shapes: object  # the reference module's `shapes(model)`: counts.Shapes for dense decoders
    peaks: counts.Peaks | None  # None: no chip, so no share of a peak
    notes: list[str] = field(default_factory=list)

    def note(self, text: str) -> None:
        """A line for the run's log (which bound binds, what was assumed)."""
        self.notes.append(text)

    def hist_mean(self, base: str, **labels: str) -> float | None:
        return prom.mean_delta(self.before, self.after, base, **labels)

    @property
    def serving(self) -> dict:
        return self.cell.config["serving"]["tpu"]

    def program_ms(self, key: str) -> float | None:
        """Device time per execution of a traced program, in ms."""
        if self.trace is None:
            return None
        rec = self.trace.programs.get(key)
        if not rec or not rec["executions"] or rec["seconds"] <= 0:
            return None
        return 1e3 * rec["seconds"] / rec["executions"]

    def window_flops(self) -> float:
        """Model flops of every token processed inside the window."""
        s, total = self.shapes, 0.0
        for r in self.records:
            for i, t in enumerate(r.token_times):
                if 0.0 <= t < self.seconds:
                    total += (s.prompt_flops(r.prompt_len) if i == 0
                              else s.token_flops(r.prompt_len + i))
        return total

    def decode_tokens(self) -> list[int]:
        """Attended length of every decode step's token in the window."""
        return [
            r.prompt_len + i
            for r in self.records
            for i, t in enumerate(r.token_times)
            if i > 0 and 0.0 <= t < self.seconds
        ]

    def prefill_offsets(self) -> list[tuple[int, int]]:
        """(offset, tokens) of every prompt chunk of the requests whose
        first token arrived in the window."""
        chunk = int(self.serving.get("prefillChunk") or 0)
        out = []
        for r in self.records:
            if r.token_times and 0.0 <= r.token_times[0] < self.seconds:
                step = chunk or r.prompt_len
                out.extend((o, min(step, r.prompt_len - o))
                           for o in range(0, r.prompt_len, step))
        return out

    def idle_pct(self) -> float | None:
        t = self.trace
        if t is None or not t.devices or t.window_s <= 0 or t.busy_s <= 0:
            return None
        return 100.0 * (1.0 - t.busy_s / t.window_s)

    def mfu_pct(self) -> float | None:
        if self.peaks is None:
            return None
        flops = self.window_flops()
        if flops <= 0:
            return None
        return 100.0 * flops / (self.seconds * self.peaks.bf16_flops)
