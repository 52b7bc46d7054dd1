"""The starvation account's readers on two recorded scrapes of `/metrics`
(`data/starved_before.prom`, `data/starved_after.prom`: the exposition of a
chunked server, the values set so the arithmetic can be done by hand)."""

import json
from pathlib import Path

import pytest
from harness import manifest, prom, starved
from harness.context import Context

DATA = Path(__file__).resolve().parent / "data"
BEFORE = prom.parse((DATA / "starved_before.prom").read_text())
AFTER = prom.parse((DATA / "starved_after.prom").read_text())
READERS = {
    "device_starved_pct": "tpot_p90_ms",
    "device_starved_pct.saturated": "tokens_per_s",
    "starved_before_step_ms": "tpot_p90_ms",
    "starved_before_step_ms.saturated": "tokens_per_s",
    "starved_before_chunk_ms.saturated": "tokens_per_s",
}

# Over the window: the root 45.0 - 5.0 = 40.0 s, of it 13.0 - 3.0 = 10.0 s
# waiting for traffic: 30.0 s busy; 1050 - 50 = 1000 steps; 730 + 220 - 50 =
# 900 chunk programs.  Starved: before a step 1.5 - 0.3 = 1.2 s in 600
# intervals, before a chunk 0.5 - 0.05 = 0.45 s in 150, before an insert
# 0.15 s in 100 (a label the first scrape does not hold yet): 1.8 s.


def test_deltas_by_hand():
    d = starved.deltas(BEFORE, AFTER)
    assert d["busy_s"] == pytest.approx(30.0)
    assert d["steps"] == 1000 and d["chunks"] == 900
    assert d["starved_s"] == pytest.approx(1.8)
    assert d["before"] == {"chunk": [pytest.approx(0.45), 150],
                           "decode": [pytest.approx(1.2), 600],
                           "insert": [pytest.approx(0.15), 100]}
    # The same seconds by what the host was doing: 0.5 + 0.2 + 0.3 + 0.6 + 0.1 + 0.1.
    assert d["span"] == {
        "engine.admit": pytest.approx(0.5), "engine.decode_assemble": pytest.approx(0.2),
        "engine.decode_dispatch": pytest.approx(0.3), "engine.emit": pytest.approx(0.6),
        "engine.iteration": pytest.approx(0.1), "engine.journal": pytest.approx(0.1)}
    assert sum(d["span"].values()) == pytest.approx(d["starved_s"])
    assert starved.starved_pct(d) == pytest.approx(6.0)  # 1.8 of 30.0
    assert starved.before_step_ms(d) == pytest.approx(1.2)  # 1.2 s over 1000 steps
    assert starved.before_chunk_ms(d) == pytest.approx(0.5)  # 0.45 s over 900 chunks
    line = starved.table(d)
    assert line.startswith("device starved 1.8000 s of 30.000 s busy (6.00 %) "
                           "over 1000 steps and 900 chunks")
    assert "decode 1.2000 s = 2.000 ms x 600 (4.00 %)" in line
    assert "chunk 0.4500 s = 3.000 ms x 150 (1.50 %)" in line
    assert "insert 0.1500 s = 1.500 ms x 100 (0.50 %)" in line
    assert "under span, s: emit 0.6000 (2.00 %), admit 0.5000 (1.67 %)" in line


@pytest.mark.parametrize("name,value", [
    ("device_starved_pct", 6.0), ("device_starved_pct.saturated", 6.0),
    ("starved_before_step_ms", 1.2), ("starved_before_step_ms.saturated", 1.2),
    ("starved_before_chunk_ms.saturated", 0.5),
])
def test_each_reader_reads_its_value(name, value):
    ctx = Context(None, 42.0, [], BEFORE, AFTER, None, None, None)
    assert manifest.load_layer_metric(name).compute(ctx) == pytest.approx(value)
    assert len(ctx.notes) == 1 and ctx.notes[0].startswith("device starved")


def test_the_table_is_noted_once_a_run():
    ctx = Context(None, 42.0, [], BEFORE, AFTER, None, None, None)
    for name in READERS:
        assert manifest.load_layer_metric(name).compute(ctx) is not None
    assert [n.split(" ", 2)[:2] for n in ctx.notes] == [["device", "starved"]]


def _without(samples: dict, *families: str) -> dict:
    return {k: v for k, v in samples.items() if not k[0].startswith(families)}


@pytest.mark.parametrize("name", list(READERS))
def test_a_program_without_the_account_reads_nothing(name):
    """The parent commit has the spans and no account: every reader gives
    None, notes nothing, and the line leaves the metric out."""
    old = (_without(BEFORE, "tpumlops_device_starved"),
           _without(AFTER, "tpumlops_device_starved"))
    ctx = Context(None, 42.0, [], *old, None, None, None)
    assert manifest.load_layer_metric(name).compute(ctx) is None
    assert ctx.notes == []
    bare = Context(None, 42.0, [], {}, {}, None, None, None)
    assert manifest.load_layer_metric(name).compute(bare) is None
    # No step closed in the window: nothing to divide by.
    still = Context(None, 42.0, [], AFTER, AFTER, None, None, None)
    assert manifest.load_layer_metric(name).compute(still) is None


def test_no_chunk_program_in_the_window_leaves_the_chunk_metric_out():
    after = _without(AFTER, "tpumlops_prefill_dispatch_total")
    before = _without(BEFORE, "tpumlops_prefill_dispatch_total")
    ctx = Context(None, 42.0, [], before, after, None, None, None)
    assert manifest.load_layer_metric("starved_before_chunk_ms.saturated").compute(ctx) is None
    assert manifest.load_layer_metric("starved_before_step_ms.saturated").compute(ctx) == (
        pytest.approx(1.2))


def test_the_manifests_new_entries_name_cells_that_report_what_they_move():
    m = json.loads((manifest.ROOT / "BENCHMARK.json").read_text())
    by_name = {e["name"]: e for e in m["per_layer"]}
    reports = {
        w["name"]: {e["name"] for e in m["end_to_end"]
                    if "workloads" not in e or w["name"] in e["workloads"]}
        for w in m["workloads"]
    }
    assert [e["name"] for e in m["per_layer"][-5:]] == list(READERS)  # appended, in order
    for name, moves in READERS.items():
        e = by_name[name]
        assert set(e) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (e["moves"], e["better"], e["source"]) == (moves, "lower", "program_counter")
        assert e["layer"] == "scheduler + KV cache"
        assert e["unit"] == ("%" if "pct" in name else "ms")
        assert e["workloads"], name
        for cell in e["workloads"]:
            assert moves in reports[cell], (name, cell)
            assert cell.endswith("-saturated") == name.endswith(".saturated")
            # The cell's own list of readers holds it: a traced run reports it.
            assert name in {x["name"] for x in manifest.load_cell(cell).per_layer}
    steady = [w["name"] for w in m["workloads"] if w["name"].endswith("-steady")]
    saturated = [w["name"] for w in m["workloads"] if w["name"].endswith("-saturated")]
    assert by_name["device_starved_pct"]["workloads"] == steady
    assert by_name["starved_before_chunk_ms.saturated"]["workloads"] == saturated
