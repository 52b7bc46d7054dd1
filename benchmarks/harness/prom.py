"""Prometheus text as the server exposes it -> samples, and deltas of
histogram sums and counts between two scrapes."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})?\s+(\S+)")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> dict[tuple[str, tuple], float]:
    out: dict[tuple[str, tuple], float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _LINE.match(line)
        if not m:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(2) or "")))
        try:
            out[(m.group(1), labels)] = float(m.group(3))
        except ValueError:
            continue
    return out


def total(samples: dict, name: str, **want: str) -> float:
    """Sum of every series of `name` whose labels include `want`."""
    s = 0.0
    for (n, labels), v in samples.items():
        if n == name and all(dict(labels).get(k) == w for k, w in want.items()):
            s += v
    return s


def delta(before: dict, after: dict, name: str, **want: str) -> float:
    return total(after, name, **want) - total(before, name, **want)


def mean_delta(before: dict, after: dict, base: str, **want: str) -> float | None:
    """Δsum/Δcount of a histogram between two scrapes; None when nothing
    was observed in between."""
    n = delta(before, after, base + "_count", **want)
    if n <= 0:
        return None
    return delta(before, after, base + "_sum", **want) / n


def window_means(before: dict, after: dict) -> dict[str, list[float]]:
    """{series: [mean, observations]} of every histogram that was observed
    between two scrapes: the run's log carries them all, named as the
    server names them."""
    out = {}
    for (name, labels), v in sorted(after.items()):
        if not name.endswith("_count") or any(k == "le" for k, _ in labels):
            continue
        n = v - before.get((name, labels), 0.0)
        key = (name[:-6] + "_sum", labels)
        if n > 0 and key in after:
            tag = name[:-6] + ("{" + ",".join(f"{k}={w}" for k, w in labels) + "}"
                               if labels else "")
            out[tag] = [(after[key] - before.get(key, 0.0)) / n, n]
    return out
