"""Finds every piece of a cell by the names `BENCHMARK.json` gives.

A cell is data: its manifest entry names a configuration and a traffic
mix, `cells/<cell>.json` holds its load (rate or clients) and the limits
of its output check, and each per-layer metric is a reader of its own in
`layer_metrics/<name>.py`.  Adding any of them is adding files.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
PKG = "research_and_development_of_kubernetes_operator_for_machine_learning_pipelines_tpu"


class ManifestError(Exception):
    pass


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise ManifestError(f"missing file {path}") from None
    except json.JSONDecodeError as e:
        raise ManifestError(f"{path} is not JSON: {e}") from None


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict  # configs/<config>.json
    mix_name: str
    mix: dict  # traffic/<mix>.json
    load: dict  # cells/<cell>.json
    end_to_end: list[dict]  # manifest entries this cell reports
    per_layer: list[dict]

    @property
    def model(self) -> dict:
        return self.config["model"]


def load_manifest(root: Path = ROOT) -> dict:
    return _read_json(root / "BENCHMARK.json")


def _applies(entry: dict, cell_name: str) -> bool:
    names = entry.get("workloads")
    return names is None or cell_name in names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    manifest = load_manifest(root)
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json (known: {known})")
    cfg_entry = next(
        (c for c in manifest["configs"] if c["name"] == entry["config"]), None
    )
    if cfg_entry is None:
        raise ManifestError(f"workload {name!r} names unknown config {entry['config']!r}")
    bench = root / "benchmarks"
    e2e = [m for m in manifest["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [
        m for m in manifest["per_layer"]
        if _applies(m, name) and m["moves"] in reported
    ]
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        config=_read_json(root / cfg_entry["file"]),
        mix_name=entry["traffic"],
        mix=_read_json(bench / "traffic" / f"{entry['traffic']}.json"),
        load=_read_json(bench / "cells" / f"{name}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def _load(kind: str, directory: str, name: str, needs: tuple[str, ...], root: Path):
    path = root / "benchmarks" / directory / f"{name}.py"
    if not path.is_file():
        raise ManifestError(f"{kind} {name!r} has no file at {path}")
    spec = importlib.util.spec_from_file_location(
        f"{directory}_" + name.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for fn in needs:
        if not callable(getattr(mod, fn, None)):
            raise ManifestError(f"{path} defines no {fn}()")
    return mod


def load_layer_metric(name: str, root: Path = ROOT):
    """The reader of one per-layer metric: a module with `compute(ctx)`."""
    return _load("per-layer metric", "layer_metrics", name, ("compute",), root)


def load_reference(cell: Cell, root: Path = ROOT):
    """The configuration's plain reference, with its artifact writer and
    count functions (see references/dense_decoder.py)."""
    return _load("reference", "references", cell.config["reference"],
                 ("write_artifact", "shapes", "compare"), root)
