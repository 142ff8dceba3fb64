"""Read the product's public `/metrics` (Prometheus text) and take deltas
between two scrapes.  The benchmark's own parser: nothing of the program."""

from __future__ import annotations

import re

_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{([^}]*)\})? (\S+)(?: -?\d+)?$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> dict:
    """→ {(name, ((label, value), ...)): float}"""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _SAMPLE.match(line)
        if not m:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(2) or "")))
        try:
            out[(m.group(1), labels)] = float(m.group(3))
        except ValueError:
            continue
    return out


def value(snap: dict, name: str, **labels):
    """The sample of that name whose labels include `labels` (summed where
    several do); None when the product does not export it."""
    want = set(labels.items())
    hits = [v for (n, ls), v in snap.items()
            if n == name and want <= set(ls)]
    return sum(hits) if hits else None


def delta(before: dict, after: dict, name: str, **labels):
    a, b = value(after, name, **labels), value(before, name, **labels)
    if a is None:
        return None
    return a - (b or 0.0)


def ratio(before: dict, after: dict, num: tuple, den: tuple, scale=1.0):
    """Δnum ÷ Δden × scale, each given as (name, labels); None when either
    is missing or Δden is 0."""
    n = delta(before, after, num[0], **num[1])
    d = delta(before, after, den[0], **den[1])
    if n is None or not d:
        return None
    return n / d * scale


def stage_ms_per_kline(ctx: dict, stage: str):
    """A pipeline stage's milliseconds (the product's per-batch stage
    histogram, host clock) per thousand lines drained in the window."""
    return ratio(ctx["prom0"], ctx["prom1"],
                 ("banjax_stage_duration_seconds_sum", {"stage": stage}),
                 ("banjax_pipeline_processed_lines_total", {}), 1e6)
