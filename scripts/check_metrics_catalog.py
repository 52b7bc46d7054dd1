"""Metrics-catalog lint (``make metrics-catalog``; tier-1 runs the same
check through ``tests/test_metrics_contract.py``).

docs/OBSERVABILITY.md carries a "Prometheus series catalog" — three
tables (Server / Operator / Router) that are supposed to enumerate
every exported family.  Catalogs rot: a PR adds a Counter and forgets
the row, or renames one and strands the old row.  This gate collects
the real family inventory from each plane and diffs it against the
parsed tables, failing on EITHER direction (exported-but-undocumented
or documented-but-gone):

- Server: instantiate ``ServerMetrics(device_telemetry=True)`` and walk
  its registry (prometheus_client strips ``_total`` from counter family
  names on collect(); the catalog uses exposition names, so counters
  get the suffix re-appended here).
- Operator: same, via ``OperatorTelemetry()``.
- Router: the native router has no Python registry — parse the
  ``# TYPE <family> <type>`` exposition lines straight out of
  ``native/router.cc``.

Table cells may name several families (comma- or slash-separated) and
use brace expansion (``tpumlops_prefix_cache_{hits,evictions}_total``);
a trailing ``{label}`` annotation (no comma inside) is stripped.

Usage: ``python scripts/check_metrics_catalog.py``
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT))

DOC = _ROOT / "docs" / "OBSERVABILITY.md"
PKG = (
    "research_and_development_of_kubernetes_operator_for_"
    "machine_learning_pipelines_tpu"
)
ROUTER_CC = _ROOT / PKG / "native" / "router.cc"

_BRACES = re.compile(r"\{([^{}]*)\}")


def expand_cell(cell: str) -> set[str]:
    """``cell`` is one backtick-quoted family token from a table row."""
    # Trailing {label} annotation (no comma) is documentation, not a
    # name component; {a,b,c} anywhere is brace expansion.
    names = {cell}
    while True:
        expanded = set()
        again = False
        for name in names:
            m = _BRACES.search(name)
            if m is None:
                expanded.add(name)
            elif "," in m.group(1):
                again = True
                for alt in m.group(1).split(","):
                    expanded.add(name[: m.start()] + alt.strip() + name[m.end() :])
            else:
                again = True
                expanded.add(name[: m.start()] + name[m.end() :])
        names = expanded
        if not again:
            return names


def doc_families() -> dict[str, set[str]]:
    """Parse the three catalog tables -> {"server"|"operator"|"router": names}."""
    text = DOC.read_text()
    try:
        catalog = text.split("## Prometheus series catalog", 1)[1]
    except IndexError:
        raise SystemExit("metrics-catalog: catalog heading missing from doc")
    out: dict[str, set[str]] = {}
    for plane in ("Server", "Operator", "Router"):
        m = re.search(rf"### {plane}\b.*?\n(.*?)(?=\n### |\n## |\Z)", catalog, re.S)
        if m is None:
            raise SystemExit(f"metrics-catalog: '### {plane}' table missing")
        names: set[str] = set()
        for line in m.group(1).splitlines():
            if not line.startswith("|") or line.startswith("|---"):
                continue
            first = line.split("|")[1]
            if first.strip() == "family":
                continue
            for token in re.findall(r"`([^`]+)`", first):
                names |= expand_cell(token.strip())
        out[plane.lower()] = names
    return out


def registry_families(registry) -> set[str]:
    names = set()
    for mf in registry.collect():
        name = mf.name
        if mf.type == "counter":
            name += "_total"
        names.add(name)
    return names


def router_cc_families() -> set[str]:
    names = set()
    for m in re.finditer(r"# TYPE (tpumlops_router_\w+) \w+", ROUTER_CC.read_text()):
        names.add(m.group(1))
    return names


def exported_families() -> dict[str, set[str]]:
    """What each plane exports -> {"server"|"operator"|"router": names}."""
    from research_and_development_of_kubernetes_operator_for_machine_learning_pipelines_tpu.operator.telemetry import (  # noqa: E501
        OperatorTelemetry,
    )
    from research_and_development_of_kubernetes_operator_for_machine_learning_pipelines_tpu.server.metrics import (  # noqa: E501
        ServerMetrics,
    )

    return {
        "server": registry_families(
            ServerMetrics("d", "p", "ns", device_telemetry=True).registry
        ),
        "operator": registry_families(OperatorTelemetry().registry),
        "router": router_cc_families(),
    }


def main() -> int:
    exported = exported_families()
    documented = doc_families()

    problems: list[str] = []
    for plane in ("server", "operator", "router"):
        for name in sorted(exported[plane] - documented[plane]):
            problems.append(f"{plane}: `{name}` exported but not in the catalog")
        for name in sorted(documented[plane] - exported[plane]):
            problems.append(f"{plane}: `{name}` in the catalog but not exported")

    if problems:
        print("metrics-catalog: OUT OF SYNC with docs/OBSERVABILITY.md:", file=sys.stderr)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)
        return 1
    total = sum(len(v) for v in exported.values())
    print(f"metrics-catalog: OK ({total} families across 3 planes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
