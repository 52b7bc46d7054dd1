"""The one general load generator: a traffic mix (a data file) and a
cell's load become a plan that is a function of the seed, and an asyncio
client streams it at the server and stamps every token's arrival.

Every seed gets the same multiset of lengths and arrival gaps (drawn once
from the mix's own `draw_seed`), in another order or, where the mix says
`"order": "fixed"`, in the same one: two runs differ in order and token
ids, never in the multiset of work."""

from __future__ import annotations

import asyncio
import json
import math
import time
from dataclasses import dataclass

import numpy as np

from .metrics import Record

DRAIN_S = 60.0  # wait this long past the window's close for answers


@dataclass(frozen=True)
class Planned:
    idx: int
    due: float | None  # seconds from the window's start; None: closed loop
    prompt_len: int
    max_new: int


@dataclass(frozen=True)
class Plan:
    loop: str  # "open" | "closed"
    warm_s: float
    seconds: float
    clients: int
    requests: tuple[Planned, ...]
    vocab: int
    seed: int

    def prompt_ids(self, idx: int) -> list[int]:
        p = self.requests[idx]
        rng = np.random.default_rng([self.seed, 7, idx])
        return rng.integers(1, self.vocab, p.prompt_len).tolist()


def _draw(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(int)


def build_plan(mix: dict, load: dict, vocab: int, max_seq: int, seed: int,
               seconds: float) -> Plan:
    fixed = np.random.default_rng(int(mix["draw_seed"]))
    # "order": "seeded" (default) permutes sizes and gaps by the seed;
    # "fixed" keeps the drawn order for every seed (the seed then changes
    # token ids and weights only): a tail over some tens of requests
    # follows the order of arrivals, not the system (PERF.md 2: a p90
    # TPOT spreads 4.9 % over seeded orders, 1.0-1.3 % over the fixed one).
    if mix.get("order", "seeded") == "fixed":
        order = np.random.default_rng(int(mix["draw_seed"]) + 1)
    else:
        order = np.random.default_rng([int(seed), 3])
    warm = float(mix["warm_s"])
    if mix["loop"] == "open":
        rate = float(load["rate_rps"])
        total = warm + seconds
        # Exactly rate x duration arrivals: exponential gaps scaled to fill
        # the duration, so the offered load is the cell's, not the draw's.
        n = max(1, round(rate * total))
        gaps = fixed.exponential(1.0, n + 1)
        gaps = order.permutation(gaps * (total / gaps.sum()))[:n]
        dues = np.cumsum(gaps) - warm
        clients = 0
    elif mix["loop"] == "closed":
        clients = int(load["clients"])
        # More than any run can finish; clients take them in order.
        n = max(256, int(load.get("plan_requests", 2048)))
        dues = [None] * n
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    prompts = _draw(mix["prompt_tokens"], n, fixed)
    answers = _draw(mix["answer_tokens"], n, fixed)
    perm = order.permutation(n)
    prompts, answers = prompts[perm], answers[perm]
    if int((prompts + answers).max()) > max_seq:
        raise ValueError(
            f"mix asks {int((prompts + answers).max())} positions, the "
            f"configuration holds {max_seq}"
        )
    reqs = tuple(
        Planned(i, None if dues[i] is None else float(dues[i]),
                int(prompts[i]), int(answers[i]))
        for i in range(n)
    )
    return Plan(mix["loop"], warm, float(seconds), clients, reqs, vocab, int(seed))


class Driver:
    """Runs one plan against one server; all times relative to the
    window's start on `time.perf_counter`."""

    def __init__(self, url: str, plan: Plan, hooks: dict | None = None):
        self.url, self.plan = url, plan
        self.records: list[Record] = []
        self.late: list[float] = []  # send - due, open loop, window only
        # hooks: {"at": [(t_rel, async fn)], ...} run beside the load.
        self.hooks = hooks or {}
        self.t_zero = math.nan  # perf_counter at the window's start

    def now(self) -> float:
        return time.perf_counter() - self.t_zero

    async def _one(self, session, rec: Record) -> None:
        body = json.dumps({
            "prompt_ids": rec.prompt_ids, "max_new_tokens": rec.max_new,
            "stream": True,
        }).encode()
        rec.sent = self.now()
        try:
            async with session.post(
                self.url, data=body,
                headers={"Content-Type": "application/json"},
            ) as resp:
                if resp.status != 200:
                    rec.error = f"http {resp.status}: {(await resp.read())[:200]!r}"
                    return
                async for raw in resp.content:
                    if not raw.startswith(b"data:"):
                        if raw.startswith(b"event: error"):
                            rec.error = "sse error event"
                        continue
                    t = self.now()
                    ev = json.loads(raw[5:])
                    if "token" in ev:
                        rec.token_times.append(t)
                        rec.tokens.append(int(ev["token"]))
                    elif ev.get("done"):
                        if ev.get("error"):
                            rec.error = f"sse: {ev.get('reason') or ev['error']}"
                        else:
                            rec.final_ids = [int(x) for x in ev["output_ids"]]
        except asyncio.CancelledError:
            rec.error = rec.error or "no answer by the drain limit"
            raise
        except Exception as e:  # a transport failure is this request's failure
            rec.error = f"{type(e).__name__}: {e}"
        finally:
            rec.gave_up = self.now()

    def _record(self, p: Planned, start: float) -> Record:
        rec = Record(p.idx, p.prompt_len, p.max_new, start,
                     prompt_ids=self.plan.prompt_ids(p.idx))
        self.records.append(rec)
        return rec

    async def _open_loop(self, session, tasks: list) -> None:
        for p in self.plan.requests:
            delay = p.due - self.now()
            if delay > 0:
                await asyncio.sleep(delay)
            rec = self._record(p, p.due)
            if 0.0 <= p.due < self.plan.seconds:
                self.late.append(self.now() - p.due)
            tasks.append(asyncio.create_task(self._one(session, rec)))

    async def _closed_loop(self, session, tasks: list) -> None:
        it = iter(self.plan.requests)

        async def client() -> None:
            while self.now() < self.plan.seconds:
                p = next(it, None)
                if p is None:
                    return
                rec = self._record(p, self.now())
                await self._one(session, rec)

        tasks.extend(asyncio.create_task(client()) for _ in range(self.plan.clients))

    async def _hook_at(self, t_rel: float, fn) -> None:
        delay = t_rel - self.now()
        if delay > 0:
            await asyncio.sleep(delay)
        await fn(self)

    def run(self) -> None:
        asyncio.run(self.run_async())

    async def run_async(self) -> None:
        import aiohttp

        self.t_zero = time.perf_counter() + self.plan.warm_s
        conn = aiohttp.TCPConnector(limit=0)
        timeout = aiohttp.ClientTimeout(total=None)
        async with aiohttp.ClientSession(connector=conn, timeout=timeout) as session:
            self.session = session
            hook_tasks = [
                asyncio.create_task(self._hook_at(t, fn))
                for t, fn in self.hooks.get("at", [])
            ]
            tasks: list = []
            if self.plan.loop == "open":
                await self._open_loop(session, tasks)
            else:
                await self._closed_loop(session, tasks)
            delay = self.plan.seconds - self.now()
            if delay > 0:
                await asyncio.sleep(delay)
            await asyncio.gather(*hook_tasks)
            # An answer that comes late is late, not wrong: wait for it.
            _done, pending = await asyncio.wait(tasks, timeout=DRAIN_S)
            for t in pending:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)


def lateness_ms(driver: Driver) -> dict[str, float]:
    from .metrics import percentile

    if not driver.late:
        return {}
    return {
        "p50": 1e3 * percentile(driver.late, 50),
        "p99": 1e3 * percentile(driver.late, 99),
        "max": 1e3 * max(driver.late),
    }
