"""Compiled rules kept beside the compile cache (matcher/rulecache.py),
and the shard choice that made compiling 10,000 rules minutes
(rulec.choose_shards)."""

import dataclasses
import io
import os
import random

import numpy as np
import pytest
import yaml

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.effectors.banner import Banner
from banjax_tpu.matcher import rulec, rulecache
from banjax_tpu.matcher.prefilter import build_plan
from banjax_tpu.matcher.runner import TpuMatcher
from benchmark.rulesets import stress_distinct


def _rules(n=40, **change):
    out = [{k: v for k, v in r.items() if not k.startswith("_")}
           for r in stress_distinct.build(n, seed=7)]
    out[3].update(change)
    return out


def _matcher(rules, monkeypatch, cache_dir):
    monkeypatch.setattr(rulecache, "default_directory", lambda: (
        os.path.join(str(cache_dir), "banjax_rules") if cache_dir else ""))
    cfg = config_from_yaml_text(yaml.safe_dump({"regexes_with_rates": rules}))
    cfg.matcher_device_windows = True
    cfg.matcher_window_capacity = 64
    banner = Banner(DynamicDecisionLists(start_sweeper=False), io.StringIO(),
                    io.StringIO(), ipset_instance=None)
    return TpuMatcher(cfg, banner, StaticDecisionLists(cfg),
                      RegexRateLimitStates())


def _same(a, b):
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray):
            assert x.dtype == y.dtype and np.array_equal(x, y), f.name
        elif dataclasses.is_dataclass(x):
            _same(x, y)
        else:
            assert x == y and type(x) is type(y), f.name


def test_round_trip_of_both_compiled_forms(tmp_path):
    pats = [r["regex"] for r in _rules()] + ["a(?=b)"]   # one unsupported
    comp = rulec.compile_rules(pats, n_shards="auto")
    assert comp.unsupported
    plan = build_plan(pats, byte_classes=(comp.byte_to_class, comp.n_classes))
    for name, obj in (("c", comp), ("p", plan)):
        path = tmp_path / f"{name}.npz"
        rulecache.save(str(path), obj)
        _same(obj, rulecache.load(str(path)))
    # a plan that is stage 1 alone
    alone = build_plan(["^GET", "^POST"])
    assert alone.stage2 is None
    rulecache.save(str(tmp_path / "a.npz"), alone)
    _same(alone, rulecache.load(str(tmp_path / "a.npz")))


def test_same_ruleset_loads_and_matches_the_same(tmp_path, monkeypatch):
    first = _matcher(_rules(), monkeypatch, tmp_path)
    assert (first.rules_cache.source, first.rules_cache.loaded) == (
        "compiled", 0)
    kept = sorted(os.listdir(tmp_path / "banjax_rules"))
    assert len(kept) == first.rules_cache.compiled >= 2
    again = _matcher(_rules(), monkeypatch, tmp_path)
    assert (again.rules_cache.source, again.rules_cache.compiled) == (
        "loaded", 0)
    assert again.rules_cache.loaded == len(kept)
    assert sorted(os.listdir(tmp_path / "banjax_rules")) == kept
    _same(first.compiled, again.compiled)
    _same(first._prefilter.plan, again._prefilter.plan)
    assert again.describe()["downgrades"] == []


@pytest.mark.parametrize("change", [
    {"hits_per_interval": 3}, {"interval": 299},
    {"regex": "GET /another-word/[a-z]+"}, {"rule": "renamed"},
    {"decision": "iptables_block"},
], ids=lambda c: next(iter(c)))
def test_one_changed_field_of_one_rule_is_another_key(
    tmp_path, monkeypatch, change
):
    base = _matcher(_rules(), monkeypatch, tmp_path)
    other = _matcher(_rules(**change), monkeypatch, tmp_path)
    assert other.rules_cache.key != base.rules_cache.key
    assert (other.rules_cache.source, other.rules_cache.loaded) == (
        "compiled", 0)


def test_no_cache_directory_and_a_bad_file_compile_as_before(
    tmp_path, monkeypatch
):
    none = _matcher(_rules(), monkeypatch, None)
    entries, off = none._entries, none.rules_cache
    assert (off.directory, off.key, off.source) == ("", "", "compiled")
    assert off.get("x", lambda: 5) == 5 and not os.listdir(tmp_path)
    on = rulecache.RuleCache(entries, directory=str(tmp_path / "r"))
    comp = on.get("single", lambda: rulec.compile_rules(["abc"]))
    path = tmp_path / "r" / f"{on.key}-single.npz"
    path.write_bytes(b"not an npz")
    again = rulecache.RuleCache(entries, directory=str(tmp_path / "r"))
    _same(comp, again.get("single", lambda: rulec.compile_rules(["abc"])))
    assert again.source == "compiled" and again.seconds > 0


def _choose_shards_by_simulation(lengths, align):
    """rulec.choose_shards as it stood: every shard count simulated."""
    order = sorted(lengths, reverse=True)
    total = sum(order)
    best, best_cost = 1, None
    for ns in range(1, max(1, -(-total // 2048)) + 1):
        bits = [0] * ns
        for ln in order:
            s = min(range(ns), key=bits.__getitem__)
            bits[s] += ln
        wps = -(-max(bits) // 32)
        wps_p = max(align, -(-wps // align) * align)
        if wps_p > 512:
            continue
        if best_cost is None or ns * wps_p < best_cost:
            best, best_cost = ns, ns * wps_p
    return best


@pytest.mark.parametrize("seed", range(6))
def test_choose_shards_is_the_full_simulation_s_choice(seed):
    rng = random.Random(seed)
    n = rng.choice([1, 7, 60, 400, 1500, 3000])
    lengths = [rng.randint(3, 90) for _ in range(n)]
    for align in (32, 128):
        assert rulec.choose_shards(lengths, align) == \
            _choose_shards_by_simulation(lengths, align)
