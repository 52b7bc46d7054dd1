#!/usr/bin/env python3
"""What one `Tracer.span` costs: enter + exit, in microseconds, best of
five rounds, nested under a root as the engine's are.  The budget is 2 us
with no capture running (ISSUE 25); `--capture` also times it inside a
`jax.profiler` capture, where each span writes a TraceMe event.

Also what the engine's starvation account costs a pass of its loop
(`account_us_per_pass`; the budget is 5 us, ISSUE 37): a pass of the root
and its phases as `GenerationEngine._step` opens them, once plain and once
with what the account adds: a stamp behind each of the two dispatches
(`_dispatched`), an interval opened at the read-back (`_tick_done`) and
closed, split by span, at the next dispatch.  A saturated pass, whose step
queues behind a chunk, opens no interval
(`account_us_per_pass_no_interval`: the stamps and their checks).

And what the attention-pairs counters cost a decode step of 8 active rows
(`attn_pairs_us_per_step.<configuration>`): the engine's query positions,
`gdn_moe.attn_pairs` and `ServerMetrics.inc_attn_pairs`, at the two GQA
configurations of the benchmark as the program runs them.

    python scripts/span_cost.py [--capture]
"""

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from tpumlops.utils.tracing import Tracer  # noqa: E402

N = 200_000


def cost_us(tracer: Tracer) -> float:
    span = tracer.span
    best = float("inf")
    for _ in range(5):
        with span("root"):
            t0 = time.perf_counter()
            for _ in range(N):
                with span("child"):
                    pass
            best = min(best, (time.perf_counter() - t0) / N * 1e6)
    return best


class _Loop:
    """The account's side of a `GenerationEngine`, as `_step` drives it."""

    def __init__(self, tracer: Tracer, every_pass: bool):
        from tpumlops.server.generation import GenerationEngine

        self.tracer, self._span = tracer, tracer.span
        self._starved = tracer.account("device_starved")
        self._unseen, self._in_warmup, self._done_at = 0, False, 0.0
        self._every_pass = every_pass
        for name in ("_dispatched", "_tick_done"):
            setattr(self, name, getattr(GenerationEngine, name).__get__(self))

    def _tick_done_before(self, t0: float) -> tuple[float, float]:
        """`_tick_done` as it was before the account: the stamp alone."""
        now = time.perf_counter()
        start = max(t0, self._done_at)
        self._done_at = now
        return start, now - start

    def one_pass(self, account: bool) -> None:
        span = self._span
        tick_done = self._tick_done if account else self._tick_done_before
        with span("engine.iteration"):
            with span("engine.admit"):
                with span("engine.prefill_dispatch"):
                    if account:
                        self._dispatched("chunk")
            with span("engine.decode_assemble"):
                pass
            with span("engine.decode_dispatch"):
                if account:
                    self._dispatched("decode")
            with span("engine.prefill_sync"):
                pass
            tick_done(0.0)  # the chunk: the step is still out
            with span("engine.decode_readback"):
                tick_done(0.0)
            if account and not self._every_pass:
                self._unseen = 1  # a chunk sent ahead: nothing opens
                self._starved.drop()
            with span("engine.journal"):
                pass
            with span("engine.emit"):
                pass


def pass_cost_us(every_pass: bool, n: int = 20_000) -> float:
    """Best of five of (a pass with the account) - (a pass without)."""
    loop = _Loop(Tracer(), every_pass)
    best = {}
    for account in (False, True):
        rounds = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                loop.one_pass(account)
            rounds.append((time.perf_counter() - t0) / n * 1e6)
        best[account] = min(rounds)
    if every_pass:
        _s, intervals = loop._starved.by_label["chunk"]
        assert intervals >= 5 * n - 1, intervals
    return best[True] - best[False]


def attn_pairs_us_per_step(cfg, n: int = 20_000) -> float:
    """Best of five of what `GenerationEngine._step_out` adds a decode
    step for a family with `attn_pairs`: 8 active rows, one step in
    flight, the widest window bucket."""
    from types import SimpleNamespace

    from tpumlops.models import gdn_moe
    from tpumlops.server.metrics import ServerMetrics

    metrics = ServerMetrics("d", "p", "ns")
    slots = [SimpleNamespace(prompt_len=4096 + 512 * i, generated=list(range(20 + i)))
             for i in range(8)]
    active_np, out, window = [True] * 8, tuple(slots), cfg.max_seq
    rounds = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            first = [
                s.prompt_len + len(s.generated) - 1 + int(out[i] is s)
                for i, s in enumerate(slots) if active_np[i]
            ]
            metrics.inc_attn_pairs("decode", gdn_moe.attn_pairs(
                cfg, "decode", first, [1] * len(first), window))
        rounds.append((time.perf_counter() - t0) / n * 1e6)
    return min(rounds)


def gqa_configs() -> dict:
    """The benchmark's two GQA configurations as the program runs them."""
    import json

    from tpumlops.models import gdn_moe

    bench = Path(__file__).resolve().parents[1] / "benchmarks"
    sys.path.append(str(bench))
    from references import laguna_decoder

    model = json.loads((bench / "configs" / "laguna-s-2.1-bf16.json").read_text())["model"]
    return {
        "qwen3-next-80b-a3b-bf16": gdn_moe.GdnMoeConfig(
            vocab_size=37984, num_layers=8, n_local_experts=128, max_seq=8704),
        "laguna-s-2.1-bf16": gdn_moe.GdnMoeConfig(**laguna_decoder.geometry(model)),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--capture", action="store_true")
    args = ap.parse_args()
    out = {"plain_us": cost_us(Tracer()),
           "profiler_sink_idle_us": cost_us(Tracer(profiler=True))}
    if args.capture:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d, profiler_options=opts)
            try:
                out["profiler_sink_capturing_us"] = cost_us(Tracer(profiler=True))
            finally:
                jax.profiler.stop_trace()
    out["account_us_per_pass"] = pass_cost_us(every_pass=True)
    out["account_us_per_pass_no_interval"] = pass_cost_us(every_pass=False)
    for name, cfg in gqa_configs().items():
        out[f"attn_pairs_us_per_step.{name}"] = attn_pairs_us_per_step(cfg)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
