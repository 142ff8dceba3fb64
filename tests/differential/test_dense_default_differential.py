"""The shipped default rules (deploy/banjax-config.yaml: `^GET` 800/30 s,
`^POST` 45/60 s, `.*challengeme.*` 0/1 s) on the fused single-kernel path,
against the serial reference (matcher/cpu_ref.py).

With these rules nearly every log line is a window event, every client
address holds live counters when its slot is evicted, and a heavy address
writes a ban record each time it crosses a limit.  The fused path has to
commit every chunk on the device (no overflow replay, no CPU fallback):
the plan routes the anchored literals as always-columns by itself and the
event ceiling follows rows x always-columns.  Small sizes: a 256-slot
table, 256-line batches, a few hundred addresses, so slots turn over with
live counters and addresses come back inside the rules' intervals.
"""

import io
import random
import threading
import time

import pytest
import yaml

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.effectors.banner import Banner
from banjax_tpu.matcher.cpu_ref import CpuMatcher
from banjax_tpu.matcher.prefilter import build_plan
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.pipeline import PipelineScheduler
from banjax_tpu.pipeline.sizer import AdaptiveBatchSizer
from benchmark.rulesets import crs_shaped
from tests.differential.test_tpu_matcher import result_key

DEFAULT_RULES = [
    {"rule": "All GET requests", "regex": "^GET", "interval": 30,
     "hits_per_interval": 800, "decision": "nginx_block"},
    {"rule": "POST flood", "regex": "^POST", "interval": 60,
     "hits_per_interval": 45, "decision": "iptables_block"},
    {"rule": "instant challenge (demo)", "regex": ".*challengeme.*",
     "interval": 1, "hits_per_interval": 0, "decision": "challenge"},
]
EVERY_LINE = {"rule": "every line", "regex": ".*", "interval": 5,
              "hits_per_interval": 60, "decision": "challenge"}
N_LINES = 3072
BATCH = 256


def _crs_rules(n=12):
    return [{k: v for k, v in r.items() if not k.startswith("_")}
            for r in crs_shaped.build(n, seed=7)]


RULESETS = {
    "default": DEFAULT_RULES,
    "every_line_rule": DEFAULT_RULES + [EVERY_LINE],
    "with_12_crs_shaped": _crs_rules() + DEFAULT_RULES,
}


def _lines(now, seed, rules):
    """A dense stream: >= 90 % of the lines are window events (GET/POST;
    HEAD lines are not), one address far over the GET limit, several over
    the POST limit, and a tail of 330 addresses drawn from a window of 20
    that slides on by one every 8 lines: a 256-line batch holds under 60
    distinct addresses (the pipeline keeps four batches' slots pinned at
    once), the 256-slot table turns over with live counters in it, and an
    address is back after 2,640 lines, well inside the rules' intervals."""
    rng = random.Random(seed)
    crs = [r for r in crs_shaped.build(12, seed=7)
           if any(r["rule"] == x["rule"] for x in rules)]
    out = []
    for i in range(N_LINES):
        t = now - 2.0 + i * 5e-4
        u = rng.random()
        if u < 0.36:
            ip = "7.7.7.7"                       # > 800 GETs in the stream
        elif u < 0.46:
            ip = f"7.7.8.{rng.randrange(4)}"     # > 45 POSTs each
        else:
            ip = f"9.9.{(i // 8 + rng.randrange(20)) % 330}.1"
        m = rng.random()
        method = "POST" if (ip.startswith("7.7.8") or m > 0.93) else (
            "HEAD" if m < 0.05 else "GET")
        path = f"/p/{rng.randrange(50)}"
        if rng.random() < 0.01:
            path = f"/x/challengeme/{i}"
        ua = "Mozilla/5.0 (X11; Linux x86_64)"
        if crs and rng.random() < 0.05:
            a = rng.choice(crs)["_attack"]
            if "path" in a:
                path = a["path"].replace("%s", "abc").replace("%d", "7")
            if "ua" in a:
                ua = a["ua"][0]
            if "method" in a:
                method = a["method"].split("|")[0]
        out.append(f"{t:.6f} {ip} {method} example.com {method} {path} "
                   f"HTTP/1.1 {ua} -")
    return out


def _build(cls, rules, **over):
    cfg = config_from_yaml_text(yaml.safe_dump({"regexes_with_rates": rules}))
    # the keys benchmark/configs/crs1k-edge.json sets, at test size, and
    # the deploy file's tiers (warm tier and slot admission on)
    for k, v in {
        "matcher_device_windows": True, "matcher_window_capacity": 256,
        "matcher_batch_lines": BATCH, "matcher_max_line_len": 256,
        "matcher_prefilter": True,
        "warm_tier_enabled": True, "warm_tier_capacity": 4096,
        "slot_admission_enabled": True, **over,
    }.items():
        setattr(cfg, k, v)
    states = RegexRateLimitStates()
    ban_log = io.StringIO()
    banner = Banner(DynamicDecisionLists(start_sweeper=False), ban_log,
                    io.StringIO(), ipset_instance=None)
    return cls(cfg, banner, StaticDecisionLists(cfg), states), states, ban_log


class _FixedSizer(AdaptiveBatchSizer):
    def __init__(self):
        super().__init__(budget_ms=1000.0)

    def target(self) -> int:
        return BATCH


def _run_pipelined(matcher, lines, now):
    collected, lock = [], threading.Lock()

    def sink(batch_lines, results):
        with lock:
            collected.append((batch_lines, results))

    sched = PipelineScheduler(lambda: matcher, on_results=sink,
                              now_fn=lambda: now)
    sched._sizer = _FixedSizer()
    sched.start()
    for i in range(0, len(lines), BATCH):
        sched.submit(lines[i : i + BATCH])
    assert sched.flush(240)
    sched.stop()
    assert [ln for ls, _ in collected for ln in ls] == lines
    return [r for _, rs in collected for r in rs]


def _counters(get, ips):
    out = {}
    for ip in ips:
        states, ok = get(ip)
        if ok:
            out[ip] = {name: (s.num_hits, s.interval_start_time_ns)
                       for name, s in states.items()}
    return out


def _reference(rules, lines, now):
    cpu, states, log = _build(CpuMatcher, rules)
    results = [cpu.consume_line(ln, now_unix=now) for ln in lines]
    return results, states, log


@pytest.mark.parametrize("entry", ["sync", "pipeline"])
@pytest.mark.parametrize("ruleset", sorted(RULESETS))
def test_dense_ruleset_commits_every_chunk_on_the_device(ruleset, entry):
    rules = RULESETS[ruleset]
    now = time.time()
    lines = _lines(now, seed=28, rules=rules)
    ref_results, ref_states, ref_log = _reference(rules, lines, now)
    n_events = sum(
        1 for r in ref_results
        if any(rr.rate_limit_result is not None for rr in r.rule_results))
    assert n_events >= 0.9 * len(lines)
    # the window restarts at 0 on an exceed, so a heavy address writes one
    # record per limit + 1 hits: all three default rules fire in the stream
    assert ref_log.getvalue().count("\n") > 30
    for r in DEFAULT_RULES:
        assert f'"trigger":"{r["rule"]}"' in ref_log.getvalue()

    tpu, _, log = _build(TpuMatcher, rules)
    fw = tpu._fw_pipeline
    assert fw is not None
    assert tpu.describe()["downgrades"] == []
    if entry == "sync":
        results = []
        for i in range(0, len(lines), BATCH):
            results.extend(tpu.consume_lines(lines[i : i + BATCH], now))
    else:
        results = _run_pipelined(tpu, lines, now)

    # every chunk committed on the device: no overflow of any cause
    assert tpu.pipelined_fused_fallbacks == 0
    assert fw.fallback_batches == 0 and sum(fw.overflow_causes.values()) == 0
    assert tpu.fallback_batches == 0
    assert fw.fused_batches == len(lines) // BATCH
    dw = tpu.device_windows
    assert dw.device_events >= 0.9 * len(lines)
    # slots turned over with live counters, and addresses came back
    assert dw.eviction_count > 64
    assert dw.warm_spills > 64 and dw.warm_refills > 16

    assert log.getvalue() == ref_log.getvalue()
    for i, (a, b) in enumerate(zip(ref_results, results)):
        assert result_key(a) == result_key(b), f"line {i}"
    ips = {ln.split(" ", 2)[1] for ln in lines}
    assert _counters(dw.get, ips) == _counters(ref_states.get, ips)


@pytest.mark.parametrize("entry", ["sync", "pipeline"])
def test_overflow_replay_at_this_density_equals_the_reference(
    entry, monkeypatch
):
    """The overflow replay stays reachable (candidates, pairs, a ruleset
    past the event cap, the chain): with the cap lowered so that EVERY
    chunk overflows its events and commits nothing in the kernel, the
    classic replay of the same dense stream — evictions with live
    counters, refills, bans — still equals the reference."""
    from banjax_tpu.matcher import prefilter

    monkeypatch.setattr(prefilter, "_MAX_EVENT_CAPACITY", 128)
    rules = DEFAULT_RULES
    now = time.time()
    lines = _lines(now, seed=29, rules=rules)
    ref_results, ref_states, ref_log = _reference(rules, lines, now)

    tpu, _, log = _build(TpuMatcher, rules)
    fw = tpu._fw_pipeline
    assert fw is not None
    if entry == "sync":
        results = []
        for i in range(0, len(lines), BATCH):
            results.extend(tpu.consume_lines(lines[i : i + BATCH], now))
    else:
        results = _run_pipelined(tpu, lines, now)

    n_chunks = len(lines) // BATCH
    assert fw.fused_batches == 0 and fw.fallback_batches == n_chunks
    assert fw.overflow_causes["events"] >= 1
    assert (fw.overflow_causes["events"] + fw.overflow_causes["chain"]
            == n_chunks)
    assert tpu.fallback_batches == 0        # classic replay, not the CPU
    dw = tpu.device_windows
    assert dw.eviction_count > 64
    assert dw.warm_spills > 64 and dw.warm_refills > 16

    assert log.getvalue() == ref_log.getvalue()
    for i, (a, b) in enumerate(zip(ref_results, results)):
        assert result_key(a) == result_key(b), f"line {i}"
    ips = {ln.split(" ", 2)[1] for ln in lines}
    assert _counters(dw.get, ips) == _counters(ref_states.get, ips)


def test_plan_routes_dense_rules_as_always_columns_by_itself():
    """`^GET`/`^POST` are anchored literals (stage 1 decides them);
    `.*` has no factor; `.*challengeme.*` keeps its gate.  The
    unanchored `GET .*\\.php` gates on four bytes and fits one word: since
    PR 41 it runs whole in stage 1 (selectivity.weak_gate), where PR 28
    left it to overflow the candidates.  The event ceiling follows rows x
    always-columns, and a plan may have no stage 2 at all."""
    plan = build_plan([r["regex"] for r in DEFAULT_RULES + [EVERY_LINE]]
                      + [r"GET .*\.php"])
    assert sorted(plan.a_idx.tolist()) == [0, 1, 3, 4]
    assert plan.f_idx.tolist() == [2] and plan.n_decided == 2
    assert plan.p_idx.tolist() == [4]
    only = build_plan(["^GET", "^POST"])
    assert only.stage2 is None and only.n_always == 2
    # 1,000 sparse rules: nothing is routed, the plan is what it was
    crs = build_plan([r["regex"] for r in crs_shaped.build(1000, seed=7)])
    assert crs.n_always == 0 and crs.n_decided == 0

    tpu3, _, _ = _build(TpuMatcher, DEFAULT_RULES)
    tpu4, _, _ = _build(TpuMatcher, DEFAULT_RULES + [EVERY_LINE])
    pf3, pf4 = tpu3._prefilter, tpu4._prefilter
    p3 = pf3.pair_capacity(BATCH, pf3.capacities(BATCH)[1])
    p4 = pf4.pair_capacity(BATCH, pf4.capacities(BATCH)[1])
    assert pf3.event_capacity(BATCH, p3) == 2 * BATCH + p3
    assert pf4.event_capacity(BATCH, p4) == 3 * BATCH + p4
    assert tpu3.describe()["stage1_decided_rules"] == 2


def test_stage1_only_plan_runs_fused():
    """No rule left to filter: the fused program is stage 1 + windows."""
    rules = DEFAULT_RULES[:2]
    now = time.time()
    lines = _lines(now, seed=3, rules=rules)[:1024]
    ref_results, _, ref_log = _reference(rules, lines, now)
    tpu, _, log = _build(TpuMatcher, rules)
    assert tpu._prefilter.plan.stage2 is None and tpu._fw_pipeline is not None
    results = []
    for i in range(0, len(lines), BATCH):
        results.extend(tpu.consume_lines(lines[i : i + BATCH], now))
    assert tpu.pipelined_fused_fallbacks == 0
    assert tpu._fw_pipeline.fallback_batches == 0
    assert log.getvalue() == ref_log.getvalue()
    assert [result_key(r) for r in results] == [
        result_key(r) for r in ref_results]
