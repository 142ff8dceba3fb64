"""Reduce a JAX profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read: device busy seconds, the traced window, seconds per named
kernel, the device operations that took most time, and the longest idle
gaps, each named by what the host was doing.

Read with `jax.profiler.ProfileData` and nothing else.  Which trace event
is which kernel is data: `trace_names.json` maps a metric-side name to
regular expressions over event names, written after looking at one trace
by hand.  Busy time is the union of the intervals of the device's op line
(events nest: a fusion inside a while loop is not counted twice), and an
op's own time is its duration less its children's.
"""

from __future__ import annotations

import glob
import json
import os
import re

from benchmark.harness import found


def newest(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def names() -> dict:
    with open(os.path.join(found.ROOT, "trace_names.json"),
              encoding="utf-8") as f:
        return json.load(f)


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def load(path: str) -> dict:
    """→ {"device": {plane: [(name, start_ns, dur_ns)] of its op line},
          "host": [(name, start_ns, dur_ns)] of the program's named spans}"""
    from jax.profiler import ProfileData

    cfg = names()
    dev_re = re.compile(cfg["device_plane"])
    span_names = set(cfg["host_spans"])
    device, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        if dev_re.search(plane.name):
            for line in plane.lines:
                if line.name == cfg["op_line"]:
                    device[plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in span_names:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
    return {"device": device, "host": host}


def reduce(path: str, top: int = 10) -> dict:
    cfg = names()
    tr = load(path)
    per_plane = sorted((p, ev) for p, ev in tr["device"].items() if ev)
    if not per_plane:
        raise ValueError(
            f"no device plane with events on a {cfg['op_line']!r} line")
    # the traced window: first start to last end over host spans and ops
    starts = [s for _, ev in per_plane for _, s, _ in ev]
    ends = [s + d for _, ev in per_plane for _, s, d in ev]
    starts += [s for _, s, _ in tr["host"]]
    ends += [s + d for _, s, d in tr["host"]]
    w0, w1 = min(starts), max(ends)
    busy_ns, by_name, count, gaps = [], {}, {}, []
    for _, events in per_plane:
        merged = _union([(s, s + d) for _, s, d in events if d > 0])
        busy_ns.append(sum(e - s for s, e in merged))
        for name, self_ns in _self_times(events):
            by_name[name] = by_name.get(name, 0) + self_ns
            count[name] = count.get(name, 0) + 1
        edge = w0
        for s, e in merged:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
        if w1 > edge:
            gaps.append((edge, w1))
    n = len(per_plane)
    kernels, kernel_ops = {}, {}
    for metric, patterns in cfg["kernels"].items():
        rx = [re.compile(p) for p in patterns]
        hit = {name: ns for name, ns in by_name.items()
               if any(r.search(name) for r in rx)}
        kernels[metric] = sum(hit.values()) / n / 1e9
        kernel_ops[metric] = [[name, ns / n / 1e9, count[name] / n]
                              for name, ns in hit.items()]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = {}
    for s, e in gaps[:500]:
        what = _host_doing(tr["host"], s, e)
        idle[what] = idle.get(what, 0.0) + (e - s) / n / 1e9
    return {
        "busy_s": sum(busy_ns) / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "planes": n,
        "kernel_s": kernels,
        "kernel_ops": kernel_ops,
        "device_ops": [[k, v / n / 1e9] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:top]],
        "host_span_s": _span_totals(tr["host"]),
    }


def _self_times(events: list) -> list:
    """(name, own ns) per event of one line, where events nest."""
    out, stack = [], []  # stack of (end, index into out)
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack and s + d <= stack[-1][0]:
            out[stack[-1][1]][1] -= d
        out.append([name, d])
        stack.append((s + d, len(out) - 1))
    return [(name, max(0, ns)) for name, ns in out]


def _host_doing(host: list, s: int, e: int) -> str:
    """The program's span that covers most of [s, e): `none` when none of
    them was open, which is the host waiting for lines."""
    best, best_ns = "none", 0
    for name, hs, hd in host:
        ov = min(e, hs + hd) - max(s, hs)
        if ov > best_ns:
            best, best_ns = name, ov
    return best


def _span_totals(host: list) -> dict:
    out = {}
    for name, _, d in host:
        out[name] = out.get(name, 0.0) + d / 1e9
    return out


def describe(path: str, top: int = 40) -> str:
    """What a trace holds, for a first look by hand: planes, lines, and the
    event names that take most time on each line."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            tot, n = {}, 0
            for ev in line.events:
                n += 1
                t = tot.setdefault(ev.name, [0, 0])
                t[0] += ev.duration_ns
                t[1] += 1
            out.append(f"  line {line.name!r}: {n} events, {len(tot)} names")
            for name, (ns, k) in sorted(
                    tot.items(), key=lambda kv: -kv[1][0])[:top]:
                out.append(f"    {ns / 1e6:12.3f} ms {k:7d}x  {name[:140]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    sys.path.insert(0, found.REPO)
    print(describe(sys.argv[1]))
