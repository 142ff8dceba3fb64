"""`capped1k-edge` (PR 41): the operator's rate caps in front of signature
rules, and a plan that runs a rule behind a weak gate whole in stage 1.

  * the configuration's rehearsal stream through the product (pipeline,
    fused path, device windows, banner) against the benchmark's own plain
    reference (`benchmark/harness/reference.py`): ban log exact, no fused
    fallback;
  * the plan's routes for the fixture's five rules;
  * which rules the plan promotes at plan time (a gate of four bytes or
    fewer in front of an automaton of one word) and which it leaves
    filtered;
  * a WIDE rule with a hot factor (`GET .*/(articles|news|static)/...`) is
    NOT promoted: its chunks overflow `candidates` and replay, every
    result and ban-log line is `cpu_ref`'s, and the log line of the
    overflow names the bucket and the rule (matcher/selectivity.py
    `hottest_bucket`; a re-plan from observed hits is not in the tree);
  * a restart loads the promoted plan from the rule cache;
  * the plans of the four older rulesets, bit for bit what the parent's
    `build_plan` made of them (digests taken on the parent commit).
"""

import dataclasses
import hashlib
import io
import json
import logging
import time

import numpy as np
import pytest
import yaml

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.effectors.banner import Banner
from banjax_tpu.matcher import rulecache, selectivity
from banjax_tpu.matcher.cpu_ref import CpuMatcher
from banjax_tpu.matcher.prefilter import build_plan
from banjax_tpu.matcher.runner import TpuMatcher
from benchmark.harness import found, genproc, reference, stream
from benchmark.harness.cellrun import overlay
from tests.differential.test_dense_default_differential import (
    BATCH,
    _run_pipelined,
)
from tests.differential.test_tpu_matcher import result_key

CAP = "All sites/GET: 45 req/60 sec"
WIDE = {"rule": "wide", "interval": 60, "hits_per_interval": 5,
        "decision": "nginx_block",
        "regex": r"GET .*/(articles|news|static)/[a-z0-9/_-]+\.(html|js|css)"}


def _build(cls, rules, **over):
    cfg = config_from_yaml_text(yaml.safe_dump({"regexes_with_rates": rules}))
    for k, v in {
        "matcher_device_windows": True, "matcher_window_capacity": 256,
        "matcher_batch_lines": BATCH, "matcher_max_line_len": 256,
        "matcher_prefilter": True,
        "warm_tier_enabled": True, "warm_tier_capacity": 4096, **over,
    }.items():
        setattr(cfg, k, v)
    ban_log = io.StringIO()
    banner = Banner(DynamicDecisionLists(start_sweeper=False), ban_log,
                    io.StringIO(), ipset_instance=None)
    m = cls(cfg, banner, StaticDecisionLists(cfg), RegexRateLimitStates())
    return m, ban_log


# ---- the configuration, against the benchmark's plain reference ----


def test_rehearsal_stream_through_the_product_equals_the_plain_reference():
    cell = found.cell("capped1k.flood")
    config = overlay(cell["config"], cell["config"]["rehearse"])
    traffic = overlay(cell["traffic"], cell["traffic"]["rehearse"])
    rules = found.ruleset(config["ruleset"])
    assert len(rules) == 17
    rests, n_benign, _ = genproc.build_pools(rules, traffic, 41)
    strm = stream.Stream(traffic, n_benign, len(rests) - n_benign, 41)
    ips, ridx = strm.block(0)
    n = 16 * BATCH
    now = time.time()
    lines = [f"{now - 2.0 + i * 4e-4:.6f} {ip} {rests[r]}"
             for i, (ip, r) in enumerate(zip(ips[:n], ridx[:n]))]

    # 512 slots: the stream's batches hold up to ~200 distinct addresses
    # and the pipeline keeps four batches' slots pinned; the table still
    # turns over
    m, ban_log = _build(TpuMatcher, found.product_rules(rules),
                        matcher_window_capacity=512)
    d = m.describe()
    assert d["fused_protocol"] == "single-kernel" and d["downgrades"] == []
    assert d["plan_promoted"] == [CAP] == config["expect"]["plan_promoted"]
    _run_pipelined(m, lines, now)
    assert m.pipelined_fused_chunks == n // BATCH
    assert m.pipelined_fused_fallbacks == 0 and m.fallback_batches == 0
    assert sum(m._fw_pipeline.overflow_causes.values()) == 0
    assert m.device_windows.eviction_count > 50
    # a chunk's records reach the ban log as one batch: at most one write
    # of each of the two files an applied chunk
    writes = sum(m.banner.ban_log_writes.values())
    assert 0 < writes <= 2 * m.pipelined_fused_chunks
    assert m.banner.regex_ban_batches <= m.pipelined_fused_chunks
    assert m.banner.regex_ban_records > 10 * writes
    # the hottest factor bucket of the last batch is far under what the
    # compaction holds: the cap's `GET ` is no factor of stage 1
    hot = selectivity.hottest_bucket(m._prefilter.plan,
                                     m._prefilter.last_bucket_hits)
    assert hot is not None and hot[1] < m._prefilter.cand_frac / 2

    got = [reference.product_record(x)
           for x in ban_log.getvalue().splitlines()]
    ref = reference.run(rules, lines, lambda ip: True, procs=1)
    cmp_ = reference.compare(got, ref["bans"])
    assert [cmp_[k] for k in ("ban_records_missing", "ban_records_extra",
                              "ips_out_of_order", "ban_keys_differing")
            ] == [0, 0, 0, 0], cmp_
    triggers = {json.loads(x)["trigger"] for x in ref["bans"]}
    # the cap, the challenge-all switch on its one host, and signatures
    assert CAP in triggers
    assert "Challenge all but skip localhost:8081" in triggers
    assert any(t.startswith("crs-") for t in triggers)
    assert all(json.loads(x)["client_request_host"]
               == "press.rights-watch.net" for x in ref["bans"]
               if json.loads(x)["trigger"].startswith("Challenge all"))
    m.close()


def test_plan_routes_of_the_fixtures_five_rules():
    with open("tests/fixtures/banjax-config-test-regex-banner.yaml",
              encoding="utf-8") as f:
        five = yaml.safe_load(f)["regexes_with_rates"]
    pats = [r["regex"] for r in five]
    assert pats == [".*allowme.*", ".*blockme.*", ".*challengeme.*", ".*",
                    "GET .* /"]
    plan = build_plan(pats)
    assert plan.routes() == {"always": 1, "decided": 0, "promoted": 1,
                             "filtered": 3, "host": 0}
    assert plan.a_idx.tolist() == [3, 4] and plan.p_idx.tolist() == [4]
    assert plan.f_idx.tolist() == [0, 1, 2]
    # each filtered rule gates on one bucket, and the map says which
    assert sorted(plan.fb_rule.tolist()) == [0, 1, 2]
    assert all(len(plan.rules_of_bucket(b)) >= 1
               for b in range(plan.n_factors))
    # a wide rule with a strong factor stays filtered
    wide = build_plan(pats + [WIDE["regex"]])
    assert wide.routes()["promoted"] == 1 and 5 in wide.f_idx.tolist()


@pytest.mark.parametrize("regex,route", [
    ("GET .* /", "promoted"),            # gates `GET ` and ` /`: 4 and 2
    (r"GET .*\.php", "promoted"),        # PR 28's overflowing default rule
    (r".*\.php", "promoted"),            # four bytes, one narrow branch
    ("POST .* /admin", "filtered"),      # `POST ` is five bytes
    (WIDE["regex"], "filtered"),         # a hot gate, but 100+ positions
    (r"GET /attack1word/[a-z]+\.php", "filtered"),
    ("^GET", "decided"),
    (".*", "always"),
])
def test_route_of_a_rule_at_plan_time(regex, route):
    # beside two rules that keep a plan worth building
    plan = build_plan([r".*blockme.*", r".*challengeme.*", regex])
    routes = plan.routes()
    assert routes[route] == (3 if route == "filtered" else 1), routes
    assert (2 in plan.p_idx.tolist()) == (route == "promoted")


# ---- the wide hot rule ----


def _wide_rules():
    rules = [dict(WIDE)]
    for i in range(12):
        rules.append({"rule": f"r{i}", "interval": 5, "hits_per_interval": 2,
                      "regex": rf"GET /attack{i}word/[a-z]+\.php",
                      "decision": "challenge"})
    return rules


def _wide_lines(now, k):
    """Batch k: two lines in three carry `/static/` or `/articles/`, most
    of them match the wide rule; a few attack lines; a few hundred
    addresses, some far over the wide rule's limit."""
    out = []
    for i in range(BATCH):
        j = k * BATCH + i
        if i % 3 == 0:
            path = f"/page{j}"
        elif i % 3 == 1:
            path = f"/static/js/app{j % 7}.js"
        else:
            path = f"/articles/2026/story-{j % 11}.html"
        if i % 41 == 0:
            path = f"/attack{j % 12}word/probe.php"
        meth = "GET" if i % 5 else "POST"
        ip = f"1.2.{j % 3}.{j % 90}"
        out.append(f"{now - 1.0 + j * 1e-5:.6f} {ip} {meth} h.com {meth} "
                   f"{path} HTTP/1.1 Mozilla/5.0 -")
    return out


@pytest.mark.parametrize("entry", ["sync", "pipeline"])
def test_wide_hot_rule_overflows_replays_exactly_and_is_named(entry, caplog):
    rules = _wide_rules()
    now = time.time()
    batches = [_wide_lines(now, k) for k in range(4)]
    cpu, cpu_log = _build(CpuMatcher, rules)
    want = [[cpu.consume_line(ln, now_unix=now) for ln in b] for b in batches]

    m, log = _build(TpuMatcher, rules)
    assert m.describe()["plan_promoted"] == []
    assert m.describe()["plan_routes"]["filtered"] == 13
    with caplog.at_level(logging.INFO, logger="banjax_tpu.matcher.runner"):
        for k, b in enumerate(batches):
            if entry == "sync":
                got = m.consume_lines(b, now)
            else:
                got = _run_pipelined(m, b, now)
            for i, (a, g) in enumerate(zip(want[k], got)):
                assert result_key(a) == result_key(g), f"batch {k} line {i}"
    assert log.getvalue() == cpu_log.getvalue()
    assert cpu_log.getvalue().count('"trigger":"wide"') > 3

    # every chunk overflowed its candidates and replayed, exactly
    fw = m._fw_pipeline
    assert fw.overflow_causes["candidates"] == fw.fallback_batches == 4
    assert fw.fused_batches == 0
    assert m.describe()["plan_promoted"] == []     # and nothing re-plans
    # one log line (at most one in 10 s) names the bucket and its rule
    named = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("candidates overflow:")]
    assert len(named) == 1 and "'wide'" in named[0], named
    bucket, share, ids = selectivity.hottest_bucket(
        m._prefilter.plan, m._prefilter.last_bucket_hits)
    # (a bucket superimposes up to sixteen factors: the line names every
    # rule behind it, the wide one first)
    assert ids.tolist()[0] == 0 and share > 0.25
    assert f"factor bucket {bucket} hit" in named[0]
    m.close()


def test_hottest_bucket_of_a_batch():
    plan = build_plan([r["regex"] for r in _wide_rules()])
    n = plan.n_factors
    assert selectivity.hottest_bucket(plan, None) is None
    assert selectivity.hottest_bucket(
        plan, (0, np.zeros(n, dtype=np.int32))) is None
    assert selectivity.hottest_bucket(
        plan, (64, np.zeros(0, dtype=np.int32))) is None
    hits = np.zeros(n, dtype=np.int32)
    hits[n - 1] = 16
    hits[0] = 3
    bucket, share, ids = selectivity.hottest_bucket(plan, (64, hits))
    assert (bucket, share) == (n - 1, 0.25)
    assert ids.tolist() == plan.rules_of_bucket(n - 1).tolist() != []
    # every filtered rule gates on some bucket, every bucket has a rule
    assert sorted(set(plan.fb_rule.tolist())) == plan.f_idx.tolist()
    assert sorted(set(plan.fb_bucket.tolist())) == list(range(n))


# ---- a restart ----


def test_a_restart_loads_the_promoted_plan_from_the_rule_cache(
        tmp_path, monkeypatch):
    monkeypatch.setattr(rulecache, "default_directory",
                        lambda: str(tmp_path / "banjax_rules"))
    rules = _wide_rules() + [{
        "rule": CAP, "regex": "GET .* /", "interval": 60,
        "hits_per_interval": 45, "decision": "nginx_block"}]
    first, _ = _build(TpuMatcher, rules)
    assert first.rules_cache.source == "compiled"
    assert first.describe()["plan_promoted"] == [CAP]
    first.close()

    again, log = _build(TpuMatcher, rules)
    assert again.rules_cache.source == "loaded"     # the plan too
    assert again.describe()["plan_promoted"] == [CAP]
    assert again.describe()["plan_routes"] == first.describe()["plan_routes"]
    p0, p1 = first._prefilter.plan, again._prefilter.plan
    for name in ("p_idx", "fb_rule", "fb_bucket", "a_idx", "f_idx"):
        assert getattr(p0, name).tolist() == getattr(p1, name).tolist()
    now = time.time()
    cpu, cpu_log = _build(CpuMatcher, rules)
    lines = [ln for ln in _wide_lines(now, 0) if "/page" in ln]
    want = [cpu.consume_line(ln, now_unix=now) for ln in lines]
    got = again.consume_lines(lines, now)
    assert [result_key(r) for r in got] == [result_key(r) for r in want]
    assert log.getvalue() == cpu_log.getvalue()
    assert again._fw_pipeline.fused_batches >= 1
    assert again._fw_pipeline.fallback_batches == 0
    again.close()


# ---- the four older rulesets ----

# sha256 over every field of the plan the parent commit (5bc685d) has,
# from `build_plan` on the configuration's rules in the product's column
# order: taken on the parent, so a change here is a change of the cells
_PLANS_AT_PARENT = {
    "crs1k-edge":
        "09fbc4abbc9c788f8e7332cde69c5f08103ff242f4c9c8d40a74560b4a41a94c",
    "default-edge":
        "53a966c50e0f951cd546aea705bbc1a81e87e14afaf68e7b10df0de61679af6c",
    "upstream-stress10k":
        "14fe36cbc3d661728d8848083e947ce7c63658fe79f518730639c52e7f21ade5",
    "multisite-edge":
        "59c2162e7020b011878f392e0a740c5ad2d70f1340d368a53396238139e5bbbe",
}
_NEW_FIELDS = ("p_idx", "fb_rule", "fb_bucket")


def _plan_digest(plan) -> str:
    h = hashlib.sha256()

    def feed(obj):
        for f in dataclasses.fields(obj):
            if f.name in _NEW_FIELDS:
                continue
            v = getattr(obj, f.name)
            h.update(f.name.encode())
            if isinstance(v, np.ndarray):
                h.update(str(v.dtype).encode())
                h.update(str(v.shape).encode())
                h.update(np.ascontiguousarray(v).tobytes())
            elif dataclasses.is_dataclass(v):
                feed(v)
            else:
                h.update(repr(v).encode())

    feed(plan)
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(_PLANS_AT_PARENT))
def test_plan_of_an_older_ruleset_is_what_the_parent_made(name):
    entry = next(c for c in found.benchmark_json()["configs"]
                 if c["name"] == name)
    with open(entry["file"], encoding="utf-8") as f:
        rules = found.ruleset(json.load(f)["ruleset"])
    pats = ([r["regex"] for r in rules if r.get("_site")]
            + [r["regex"] for r in rules if not r.get("_site")])
    plan = build_plan(pats)
    assert len(plan.p_idx) == 0          # nothing routed by weak_gate
    assert _plan_digest(plan) == _PLANS_AT_PARENT[name]
