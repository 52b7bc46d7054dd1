"""`BENCHMARK.json` against the contract's limits the driver checks
before any run, and every name resolved to its file."""

import json
import re

from harness import manifest
from harness.manifest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(text, n=200):
    return 1 <= len(text) <= n and "\n" not in text and "\t" not in text


def test_manifest_meets_the_contract():
    raw = (ROOT / "BENCHMARK.json").read_text()
    assert len(raw.encode()) <= 64 * 1024
    m = json.loads(raw)
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert m["command"] == ["python3", "benchmarks/run.py"] and m["paths"] == ["benchmarks"]
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["configs"]) <= 24 and 1 <= len(m["workloads"]) <= 24
    cfgs = {c["name"] for c in m["configs"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmarks/") and (ROOT / c["file"]).is_file()
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["reduced"] == c["reduced"] and body["source"] == c["source"]
        for k in c["reduced"]:  # never a width
            assert not re.search(r"(hidden|intermediate|_dim$|_rank$|head_dim)", k)
    assert len({c["file"] for c in m["configs"]}) == len(m["configs"])
    cells = {}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and line(w["why"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        cells[w["name"]] = w
    assert len(cells) == len(m["workloads"])
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == len(cells)
    assert {w["config"] for w in m["workloads"]} == cfgs  # each config used
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, len(cells) // 4)

    e2e = {}
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher") and 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
        e2e[e["name"]] = set(e.get("workloads") or cells)
        assert e2e[e["name"]] <= set(cells)
    assert "setup_s" in e2e and e2e["setup_s"] == set(cells)
    assert 1 <= len(m["per_layer"]) <= 128
    names = set(e2e)
    per_cell = {c: 0 for c in cells}
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(p["name"]) and UNIT.match(p["unit"]) and line(p["layer"])
        assert p["better"] in ("lower", "higher") and p["source"] in SOURCES
        assert p["name"] not in names
        names.add(p["name"])
        assert p["moves"] in e2e
        for c in p.get("workloads") or e2e[p["moves"]]:
            assert c in e2e[p["moves"]], (p["name"], c)
            per_cell[c] += 1
        if p["name"].endswith("_roofline") or "mfu" in re.split(r"[._\-]", p["name"]):
            assert p["unit"] == "%"
        manifest.load_layer_metric(p["name"])  # its reader is a file of its own
    for c in cells:
        assert sum(c in s for n, s in e2e.items() if n != "setup_s") >= 1
        assert per_cell[c] >= 1
        cell = manifest.load_cell(c)  # config, mix and load files resolve
        assert "limits" in cell.load and cell.mix["loop"] in ("open", "closed")
        manifest.load_reference(cell)


def test_every_file_under_paths_has_a_plain_name():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    skip = (".cache", ".work", ".export", "__pycache__", ".pytest_cache")
    for p in (ROOT / "benchmarks").rglob("*"):
        rel = p.relative_to(ROOT).as_posix()
        if any(part in skip for part in p.parts):
            continue
        assert ok.match(rel) and len(rel) <= 200, rel
