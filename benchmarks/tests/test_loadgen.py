"""The load generator is a function of the seed, gives every seed the
same work, and reports how late it sent."""

import asyncio
import json

from aiohttp import web

from harness import loadgen
from harness.manifest import BENCH
from harness.metrics import measured


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def test_plan_is_a_function_of_the_seed():
    seeded = dict(mix("chat"), order="seeded")
    a = loadgen.build_plan(seeded, {"rate_rps": 3.0}, 32768, 2048, 2**31 + 5, 20)
    b = loadgen.build_plan(seeded, {"rate_rps": 3.0}, 32768, 2048, 2**31 + 5, 20)
    c = loadgen.build_plan(seeded, {"rate_rps": 3.0}, 32768, 2048, 6, 20)
    assert a.requests == b.requests and a.prompt_ids(3) == b.prompt_ids(3)
    assert a.requests != c.requests and a.prompt_ids(3) != c.prompt_ids(3)
    # Same multiset of sizes and of arrival gaps, in another order.
    assert sorted((r.prompt_len, r.max_new) for r in a.requests) == sorted(
        (r.prompt_len, r.max_new) for r in c.requests)
    assert len(a.requests) == len(c.requests) == round(3.0 * (20 + 6))
    assert all(1 <= t < 32768 for t in a.prompt_ids(0))
    assert max(r.prompt_len + r.max_new for r in a.requests) <= 896


def test_fixed_order_keeps_the_schedule_and_changes_the_ids():
    m = dict(mix("chat"), order="fixed")
    a = loadgen.build_plan(m, {"rate_rps": 1.5}, 32768, 1024, 1, 45)
    b = loadgen.build_plan(m, {"rate_rps": 1.5}, 32768, 1024, 2, 45)
    assert a.requests == b.requests and a.prompt_ids(0) != b.prompt_ids(0)
    # The offered load is the cell's: rate x (warm + window) arrivals.
    assert len(a.requests) == round(1.5 * (45 + m["warm_s"]))
    assert a.requests[0].due < 0 < a.requests[-1].due < 45


def test_closed_plan_and_capacity_check():
    p = loadgen.build_plan(mix("docs"), {"clients": 32}, 32768, 2048, 1, 20)
    assert p.loop == "closed" and p.clients == 32 and p.requests[0].due is None
    assert all(1024 <= r.prompt_len <= 1792 and 16 <= r.max_new <= 64 for r in p.requests)
    try:
        loadgen.build_plan(mix("docs"), {"clients": 32}, 32768, 1024, 1, 20)
    except ValueError:
        pass
    else:
        raise AssertionError("a mix longer than the configuration must be refused")


async def _fake_generate(request):
    body = await request.json()
    resp = web.StreamResponse(headers={"Content-Type": "text/event-stream"})
    await resp.prepare(request)
    out = []
    for i in range(body["max_new_tokens"]):
        await asyncio.sleep(0.002)
        out.append(i)
        await resp.write(f"data: {json.dumps({'index': i, 'token': i})}\n\n".encode())
    await resp.write(f"data: {json.dumps({'done': True, 'output_ids': out})}\n\n".encode())
    return resp


def test_driver_streams_and_reports_lateness():
    m = dict(mix("chat"), warm_s=0.5)
    m["prompt_tokens"] = {"dist": "uniform", "min": 4, "max": 8}
    m["answer_tokens"] = {"dist": "uniform", "min": 3, "max": 6}
    plan = loadgen.build_plan(m, {"rate_rps": 20.0}, 100, 64, 3, 2.0)

    async def go():
        app = web.Application()
        app.router.add_post("/v2/models/m/generate", _fake_generate)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        d = loadgen.Driver(f"http://127.0.0.1:{port}/v2/models/m/generate", plan)
        try:
            await d.run_async()
        finally:
            await runner.cleanup()
        return d

    d = asyncio.run(go())
    win = measured(d.records, 2.0)
    assert len(win) > 20 and all(r.complete and r.final_ids == r.tokens for r in win)
    assert len(d.late) == len(win) and all(x >= 0 for x in d.late)
    late = loadgen.lateness_ms(d)
    assert 0 <= late["p50"] <= late["p99"] <= late["max"] < 500
    # Timed from when it was due, not from when it was sent.
    r = win[0]
    assert r.start == plan.requests[r.idx].due and r.sent >= r.start
