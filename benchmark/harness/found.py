"""Find the benchmark's data files and small modules by name.

Whatever belongs to one configuration, one traffic mix, one feed kind, one
ruleset generator or one per-layer metric is a file of its own under the
benchmark's directory, named after the entry in BENCHMARK.json (or after
the `kind`/`generator` a data file gives).  Adding one is adding a file."""

from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(ROOT)
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def check_name(name: str) -> str:
    if not _NAME.match(name):
        raise SystemExit(f"not a name: {name!r}")
    return name


def data(kind_dir: str, name: str) -> dict:
    path = os.path.join(ROOT, kind_dir, check_name(name) + ".json")
    if not os.path.isfile(path):
        raise SystemExit(f"no {kind_dir}/{name}.json")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def module(kind_dir: str, name: str):
    path = os.path.join(ROOT, kind_dir, check_name(name) + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"no {kind_dir}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind_dir}_{name.replace('.', '_').replace('-', '_')}",
        path,
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_json() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def cell(name: str) -> dict:
    """One entry of `workloads`, with its configuration and traffic files
    and the metrics it reports."""
    bj = benchmark_json()
    cells = {w["name"]: w for w in bj["workloads"]}
    if name not in cells:
        raise SystemExit(
            f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in bj["configs"] if c["name"] == w["config"])
    with open(os.path.join(REPO, cfg_entry["file"]), encoding="utf-8") as f:
        config = json.load(f)

    def reported(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bj["end_to_end"] if reported(m)]
    e2e_names = {m["name"] for m in e2e}
    layers = [m for m in bj["per_layer"]
              if reported(m) and m["moves"] in e2e_names]
    return {"name": name, "chips": w["chips"], "config": config,
            "traffic": data("traffic", w["traffic"]),
            "end_to_end": e2e, "per_layer": layers}


def ruleset(spec: dict) -> list:
    """A configuration's `ruleset` block → rule records (with recipes)."""
    return module("rulesets", spec["generator"]).build(**spec["args"])


def product_rules(rules: list) -> list:
    """The records without private keys, as the product reads them."""
    return [{k: v for k, v in r.items() if not k.startswith("_")}
            for r in rules]
