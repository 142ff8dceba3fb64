"""The benchmark's hold on the program, as a tier-1 test.

`benchmark/` reads the product through names: `/metrics` families, host
span names, keys of `TpuMatcher.describe()`, keys of the product config.
A per-layer reader returns nothing where the program lacks the counter
(PERF.md §3) and says so nowhere, so a refactor that renames one of these
would blind a metric in silence.  This file reads `benchmark/` and
`BENCHMARK.json`, edits nothing there, and fails here instead."""

import glob
import json
import os
import re
import time

import pytest
import yaml

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.obs import registry, trace
from banjax_tpu.pipeline import PipelineScheduler
from tests.mock_banner import MockBanner

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_BENCH = os.path.join(_REPO, "benchmark")

# `banjax_*` words in the benchmark's sources that are no metric: the
# package's name and nginx's log format
_NOT_METRICS = {"banjax_tpu", "banjax_format"}

# product-config keys a configuration file may still carry though the
# schema no longer knows them (PR 30 removed the three protocol options;
# the loader ignores unknown keys, and the files may not be edited)
_REMOVED_KEYS = {"pallas_single_kernel", "pipeline_fused", "drain_resolve_depth"}


def _families_read():
    names = set()
    for sub in ("layers", "harness"):
        for path in sorted(glob.glob(os.path.join(_BENCH, sub, "*.py"))):
            with open(path, encoding="utf-8") as f:
                names.update(re.findall(r"banjax_[a-z0-9_]+", f.read()))
    return sorted(names - _NOT_METRICS)


def _host_spans():
    with open(os.path.join(_BENCH, "trace_names.json"), encoding="utf-8") as f:
        return json.load(f)["host_spans"]


def _configurations():
    with open(os.path.join(_REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return [(c["name"], c["file"]) for c in json.load(f)["configs"]]


def _configuration(rel):
    with open(os.path.join(_REPO, rel), encoding="utf-8") as f:
        return json.load(f)


def test_the_benchmark_reads_some_families():
    assert len(_families_read()) >= 11


@pytest.mark.parametrize("name", _families_read())
def test_family_the_benchmark_reads_is_declared(name):
    """Counters and gauges by their own name, a histogram by the name of
    one of its samples."""
    declared = {f.prom: f.kind for f in registry.FAMILIES if f.prom}
    if name in declared:
        return
    base = re.sub(r"_(sum|count|bucket)$", "", name)
    assert declared.get(base) == registry.HISTOGRAM, (
        f"benchmark/ reads {name}, which obs/registry.py does not declare: "
        "the reader would return nothing"
    )


_RULES = yaml.safe_dump({"regexes_with_rates": [{
    "rule": "r", "regex": "GET /attack.*", "interval": 5,
    "hits_per_interval": 2, "decision": "nginx_block",
}]})


def _matcher():
    cfg = config_from_yaml_text(_RULES)
    cfg.matcher_device_windows = True
    return TpuMatcher(cfg, MockBanner(), StaticDecisionLists(cfg),
                      RegexRateLimitStates())


@pytest.fixture(scope="module")
def span_names():
    """Span names of one small traced stream through the scheduler and
    the fused matcher."""
    tracer = trace.configure(enabled=True, ring_size=4096)
    try:
        now = time.time()
        m = _matcher()
        sched = PipelineScheduler(lambda: m, now_fn=lambda: now)
        sched.start()
        # mostly benign, so the chunk commits fused and does not
        # overflow the candidate capacity
        sched.submit([
            f"{now:.6f} 1.2.3.{i % 5} GET h.com GET "
            f"/{'attack' if i % 13 == 0 else 'page'}{i} HTTP/1.1 ua -"
            for i in range(40)
        ])
        assert sched.flush(120)
        sched.stop()
        assert m.pipelined_fused_chunks > 0
        return {s["name"] for s in tracer.snapshot()}
    finally:
        trace.configure(enabled=False)


@pytest.mark.parametrize(
    "span",
    _host_spans() + ["program-ab-fused", "effector-replay", "submit-resolve",
                     "rules-compile", "submit-pass", "submit-sketch",
                     "submit-operands", "submit-maintenance",
                     "submit-dispatch"],
)
def test_host_span_the_benchmark_names_is_opened(span_names, span):
    """`benchmark/trace_names.json` `host_spans`, and the documented spans
    of the fused path beside them: the program's dispatch, the drain's
    replay, the submit stage's one pass over a batch's addresses, whose
    seconds `resolve_ms_per_kline` reads, the start's `rules-compile`
    (ISSUE 33), whose seconds are `banjax_rules_compile_seconds`, and the
    submit stage's five named phases (ISSUE 39), whose seconds are
    `banjax_submit_phase_seconds_total`."""
    assert span in span_names, sorted(span_names)


_RESOLVE_FAMILIES = {
    "banjax_submit_resolve_addresses_total":
        [{"outcome": o} for o in
         ("hit", "shadow", "warm", "unseen", "refused")],
    "banjax_submit_resolve_probes_total":
        [{"table": "slots"}, {"table": "warm"}],
    "banjax_submit_gate_derived_batches_total": [{}],
    "banjax_submit_resolve_seconds_total": [{}],
}


@pytest.mark.parametrize("family", sorted(_RESOLVE_FAMILIES))
def test_resolve_family_is_on_metrics_with_its_labels(family):
    """The families of the submit stage's address resolution (ISSUE 32),
    as the benchmark's own parser reads them off `/metrics`: declared,
    exported with the labels the readers select by, and moving when a
    stream went through the fused path."""
    from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
    from banjax_tpu.decisions.rate_limit import (
        FailedChallengeRateLimitStates,
    )
    from banjax_tpu.obs.exposition import render_prometheus
    from benchmark.harness import prom

    assert family in {f.prom for f in registry.FAMILIES}
    now = time.time()
    cfg = config_from_yaml_text(_RULES)
    cfg.matcher_device_windows = True
    cfg.slot_admission_enabled = True
    cfg.warm_tier_enabled = True
    cfg.warm_tier_capacity = 1024
    m = TpuMatcher(cfg, MockBanner(), StaticDecisionLists(cfg),
                   RegexRateLimitStates())
    sched = PipelineScheduler(lambda: m, now_fn=lambda: now)
    sched.start()
    for k in range(3):
        sched.submit([
            f"{now:.6f} 1.2.{k}.{i % 7} GET h.com GET /page{i} HTTP/1.1 ua -"
            for i in range(30)
        ])
        assert sched.flush(120)
    sched.stop()
    snap = prom.parse(render_prometheus(
        DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
        FailedChallengeRateLimitStates(), matcher=m,
    ))
    for labels in _RESOLVE_FAMILIES[family]:
        assert prom.value(snap, family, **labels) is not None, labels
    assert prom.value(snap, "banjax_submit_resolve_seconds_total") > 0
    # threshold 3 here (hits_per_interval 2): the sketch is asked about
    # the unseen addresses of a batch, so not every verdict is derived
    distinct = prom.value(snap, "banjax_submit_resolve_addresses_total")
    assert distinct == prom.value(
        snap, "banjax_submit_resolve_probes_total", table="slots")
    m.close()


_STRESS_FAMILIES = {
    # family: (labels the reader or PERF.md selects by, its reader)
    "banjax_prefilter_candidates_total":
        ({}, ["stage2_candidates_per_kline", "match_stage2_roofline"]),
    "banjax_warm_tier_bytes_written_total": ({}, ["warm_record_bytes_mean"]),
    "banjax_device_windows_table_bytes": ({}, []),
    "banjax_rules_compile_seconds": ({"source": "compiled"}, []),
}


@pytest.mark.parametrize("family", sorted(_STRESS_FAMILIES))
def test_stress10k_family_is_on_metrics_and_its_reader_reads_it(family):
    """The counters `upstream-stress10k` brought (ISSUE 33), off
    `/metrics` through the benchmark's own parser, and through the
    per-layer readers that take them: candidates stage 2 scanned, bytes
    the warm tier wrote, the window table's bytes, the start's seconds
    on its rules."""
    from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
    from banjax_tpu.decisions.rate_limit import (
        FailedChallengeRateLimitStates,
    )
    from banjax_tpu.obs.exposition import render_prometheus
    from benchmark.harness import found, prom

    labels, readers = _STRESS_FAMILIES[family]
    assert family in {f.prom for f in registry.FAMILIES}
    now = time.time()
    cfg = config_from_yaml_text(_RULES)
    cfg.matcher_device_windows = True
    cfg.matcher_window_capacity = 256
    cfg.warm_tier_enabled = True
    cfg.warm_tier_capacity = 1024
    m = TpuMatcher(cfg, MockBanner(), StaticDecisionLists(cfg),
                   RegexRateLimitStates())

    sched = PipelineScheduler(lambda: m, now_fn=lambda: now)

    def scrape():
        return prom.parse(render_prometheus(
            DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
            FailedChallengeRateLimitStates(), matcher=m, pipeline=sched,
        ))

    sched.start()
    before = None
    for k in range(8):   # 8 x 100 addresses through 256 slots: spills
        sched.submit([
            f"{now:.6f} 1.2.{k}.{i} GET h.com GET "
            f"/{'attack' if i % 10 == 0 else 'page'}{i} HTTP/1.1 ua -"
            for i in range(100)
        ])
        assert sched.flush(120)
        before = before or scrape()
    sched.stop()
    snap = scrape()
    assert prom.value(snap, family, **labels) is not None
    # ten lines in a hundred carry the rule's factor, under the 16 the
    # 128-row program has room for: none overflowed
    assert prom.value(snap, "banjax_prefilter_candidates_total") == 8 * 10
    spills = prom.value(snap, "banjax_warm_tier_spills_total")
    assert spills >= 25
    # every spilled address holds one counter: 128 + 24 bytes a record
    assert prom.value(
        snap, "banjax_warm_tier_bytes_written_total") == 152 * spills
    assert prom.value(
        snap, "banjax_device_windows_table_bytes") == 256 * (16 * 1 + 5)
    assert 0 < prom.value(snap, "banjax_rules_compile_seconds") < 60
    ctx = {"prom0": before, "prom1": snap, "trace": None, "trace_lines": 0,
           "mean_len": 0.0}
    want = {"stage2_candidates_per_kline": 100.0,
            "warm_record_bytes_mean": 152.0, "match_stage2_roofline": None}
    for name in readers:
        assert found.module("layers", name).read(ctx) == want[name]
    m.close()


_MULTISITE_FAMILIES = {
    # family: (label sets the readers select by, its readers)
    "banjax_fused_pairs_total": ([{}], ["site_pairs_per_kline"]),
    "banjax_window_events_total":
        ([{"scope": "site"}, {"scope": "global"}], ["site_events_share"]),
    "banjax_fused_overflows_total":
        ([{"cause": "pairs"}], ["pairs_overflow_share"]),
}


@pytest.mark.parametrize("family", sorted(_MULTISITE_FAMILIES))
def test_multisite_family_is_on_metrics_and_its_reader_reads_it(family):
    """The counters `multisite-edge` brought (ISSUE 37), off `/metrics`
    through the benchmark's own parser and through the three readers of
    `multisite.botnet`: pairs the fused programs counted after the site
    mask, window events by the scope of their rule, overflows by cause."""
    from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
    from banjax_tpu.decisions.rate_limit import (
        FailedChallengeRateLimitStates,
    )
    from banjax_tpu.obs.exposition import render_prometheus
    from benchmark.harness import found, prom

    label_sets, readers = _MULTISITE_FAMILIES[family]
    assert family in {f.prom for f in registry.FAMILIES}
    one = {"regex": "GET /attack.*", "interval": 5, "hits_per_interval": 2,
           "decision": "nginx_block"}
    cfg = config_from_yaml_text(yaml.safe_dump({
        "regexes_with_rates": [{**one, "rule": "everywhere",
                                "regex": "GET /probe.*"}],
        "per_site_regexes_with_rates": {
            f"s{k}.com": [{**one, "rule": f"s{k}"}] for k in range(4)},
    }))
    cfg.matcher_device_windows = True
    m = TpuMatcher(cfg, MockBanner(), StaticDecisionLists(cfg),
                   RegexRateLimitStates())
    now = time.time()
    sched = PipelineScheduler(lambda: m, now_fn=lambda: now)

    def scrape():
        return prom.parse(render_prometheus(
            DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
            FailedChallengeRateLimitStates(), matcher=m, pipeline=sched,
        ))

    sched.start()
    before = scrape()
    # of 100 lines, 8 fire their own site's rule, 1 carries the pattern on
    # a host with no rule for it (no pair), 3 fire the global rule
    sched.submit([
        f"{now:.6f} 1.2.3.{i} GET "
        f"{'other.com' if i % 50 == 0 else f's{i % 4}.com'} GET "
        f"/{'attack' if i % 12 == 0 else 'probe' if i % 45 == 1 else 'page'}"
        f"{i} HTTP/1.1 ua -"
        for i in range(100)
    ])
    assert sched.flush(120)
    sched.stop()
    snap = scrape()
    for labels in label_sets:
        assert prom.value(snap, family, **labels) is not None, labels
    assert prom.value(snap, "banjax_fused_pairs_total") == 11
    assert prom.value(snap, "banjax_window_events_total", scope="site") == 8
    assert prom.value(snap, "banjax_window_events_total", scope="global") == 3
    assert prom.value(snap, "banjax_window_events_total") == prom.value(
        snap, "banjax_device_windows_events_total")
    ctx = {"prom0": before, "prom1": snap, "trace": None, "trace_lines": 0,
           "mean_len": 0.0}
    want = {"site_pairs_per_kline": 110.0, "pairs_overflow_share": 0.0,
            "site_events_share": pytest.approx(100 * 8 / 11)}
    for name in readers:
        assert found.module("layers", name).read(ctx) == want[name]
        # a program without the counter: the reader is silent
        assert found.module("layers", name).read(
            {**ctx, "prom0": {}, "prom1": {}}) is None
    m.close()


_CAPPED_FAMILIES = {
    # family: (label sets the readers select by, its readers)
    "banjax_plan_rules":
        ([{"route": r} for r in ("always", "decided", "promoted",
                                 "filtered", "host")],
         ["plan_promoted_rules"]),
    "banjax_fused_overflows_total":
        ([{"cause": "candidates"}], ["candidates_overflow_share"]),
    "banjax_fused_event_feed_total":
        ([{"source": "always"}, {"source": "pairs"}],
         ["always_events_share"]),
    "banjax_plan_hottest_bucket_share": ([{}], []),
}


@pytest.fixture(scope="module")
def capped_scrapes():
    """`/metrics` before and after 300 lines through the scheduler and a
    matcher whose plan has one rule of each kind `capped1k-edge` has: the
    rate cap (promoted), a challenge-all rule (always) and a signature
    (filtered).  Tracing is off."""
    from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
    from banjax_tpu.decisions.rate_limit import (
        FailedChallengeRateLimitStates,
    )
    from banjax_tpu.obs.exposition import render_prometheus
    from benchmark.harness import prom

    assert not trace.enabled()
    one = {"interval": 60, "hits_per_interval": 45, "decision": "nginx_block"}
    cfg = config_from_yaml_text(yaml.safe_dump({"regexes_with_rates": [
        {**one, "rule": "cap", "regex": "GET .* /"},
        {**one, "rule": "all", "regex": ".*",
         "hosts_to_skip": {"h.com": True}},
        {**one, "rule": "sig", "regex": "GET /attack[0-9]+"},
    ]}))
    cfg.matcher_device_windows = True
    m = TpuMatcher(cfg, MockBanner(), StaticDecisionLists(cfg),
                   RegexRateLimitStates())
    now = time.time()
    sched = PipelineScheduler(lambda: m, now_fn=lambda: now)

    def scrape():
        return prom.parse(render_prometheus(
            DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
            FailedChallengeRateLimitStates(), matcher=m, pipeline=sched,
        ))

    sched.start()
    before = scrape()
    for k in range(3):
        # of 100 lines, 80 are GETs (the cap fires), 10 of those carry the
        # signature; 10 lines are of the one host `all` is not skipped on
        sched.submit([
            f"{now:.6f} 1.2.{k}.{i} {'GET' if i % 5 else 'POST'} "
            f"{'other.org' if i % 10 == 3 else 'h.com'} "
            f"{'GET' if i % 5 else 'POST'} "
            f"/{'attack' if i % 10 == 1 else 'page'}{i} HTTP/1.1 ua -"
            for i in range(100)
        ])
        assert sched.flush(120)
    sched.stop()
    after = scrape()
    m.close()
    return before, after


@pytest.mark.parametrize("family", sorted(_CAPPED_FAMILIES))
def test_capped1k_family_is_on_metrics_and_its_reader_reads_it(
        capped_scrapes, family):
    """The families `capped1k-edge` brought or gave their first reader
    (ISSUE 41), off `/metrics` with tracing off through the benchmark's
    own parser and through the three readers of `capped1k.flood`: the
    plan's rules by route, overflows by cause, window events by the list
    the program took them from."""
    from benchmark.harness import found, prom

    before, snap = capped_scrapes
    label_sets, readers = _CAPPED_FAMILIES[family]
    assert family in {f.prom for f in registry.FAMILIES}
    for labels in label_sets:
        assert prom.value(snap, family, **labels) is not None, labels
    assert prom.value(snap, "banjax_plan_rules", route="promoted") == 1
    assert prom.value(snap, "banjax_plan_rules", route="always") == 1
    assert prom.value(snap, "banjax_plan_rules", route="filtered") == 1
    assert prom.value(
        snap, "banjax_fused_event_feed_total", source="always") == 3 * 90
    assert prom.value(
        snap, "banjax_fused_event_feed_total", source="pairs") == 3 * 10
    # 10 lines in 100 carry the signature's factor
    assert prom.value(
        snap, "banjax_plan_hottest_bucket_share") == pytest.approx(0.1)
    ctx = {"prom0": before, "prom1": snap, "trace": None, "trace_lines": 0,
           "mean_len": 0.0}
    want = {"plan_promoted_rules": 1.0, "candidates_overflow_share": 0.0,
            "always_events_share": 90.0}
    for name in readers:
        assert found.module("layers", name).read(ctx) == want[name]
        # a program without the family (the parent): the reader is silent
        assert found.module("layers", name).read(
            {**ctx, "prom0": {}, "prom1": {}}) is None


@pytest.fixture(scope="module")
def banner_scrapes():
    """`/metrics` before and after three batches through the scheduler,
    the fused matcher and the real banner: every line of each batch
    crosses an instant rule, one host in four is under `disable_logging`.
    Tracing is off."""
    import io

    from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
    from banjax_tpu.decisions.rate_limit import (
        FailedChallengeRateLimitStates,
    )
    from banjax_tpu.effectors.banner import Banner
    from banjax_tpu.obs.exposition import render_prometheus
    from benchmark.harness import prom

    assert not trace.enabled()
    cfg = config_from_yaml_text(yaml.safe_dump({
        "regexes_with_rates": [{
            "rule": "instant", "regex": ".*blockme.*", "interval": 1,
            "hits_per_interval": 0, "decision": "nginx_block"}],
        "disable_logging": {"quiet.org": True}}))
    cfg.matcher_device_windows = True
    lists = DynamicDecisionLists(start_sweeper=False)
    banner = Banner(lists, io.StringIO(), io.StringIO(), ipset_instance=None)
    m = TpuMatcher(cfg, banner, StaticDecisionLists(cfg),
                   RegexRateLimitStates())
    now = time.time()
    sched = PipelineScheduler(lambda: m, now_fn=lambda: now)

    def scrape():
        return prom.parse(render_prometheus(
            lists, RegexRateLimitStates(), FailedChallengeRateLimitStates(),
            matcher=m, pipeline=sched,
        ))

    sched.start()
    before = scrape()
    for k in range(3):
        sched.submit([
            f"{now:.6f} 1.2.{k}.{i} GET {'quiet.org' if i % 4 == 1 else 'h.com'}"
            f" GET /blockme{i} HTTP/1.1 ua -" for i in range(40)
        ])
        assert sched.flush(120)
    sched.stop()
    after = scrape()
    m.close()
    return before, after


@pytest.mark.parametrize("family,labels", [
    ("banjax_ban_log_writes_total", {"target": "main"}),
    ("banjax_ban_log_writes_total", {"target": "temp"}),
    ("banjax_banner_batches_total", {}),
    ("banjax_regex_ban_records_total", {}),
])
def test_banner_family_is_on_metrics_with_tracing_off(
        banner_scrapes, family, labels):
    """The families of the banner's batch entry (ISSUE 42): a write of a
    ban-log file and a batch are counted where they happen and exported
    with tracing off; with the records they give records a write."""
    from benchmark.harness import prom

    before, snap = banner_scrapes
    assert family in {f.prom for f in registry.FAMILIES}
    assert prom.value(before, family, **labels) == 0
    want = {"banjax_regex_ban_records_total": 120,
            "banjax_banner_batches_total": 3,
            "banjax_ban_log_writes_total": 3}[family]
    # one batch an applied chunk, and one write a file a batch
    assert prom.value(snap, family, **labels) == want
    assert prom.value(snap, "banjax_pipeline_batches_total") == 3


@pytest.mark.parametrize("scrapes,want", [
    ("pair", pytest.approx(1e3 * 6 / 120)),
    ("empty", None),        # a program without the family: the parent
    ("no_lines", None),     # nothing drained between the scrapes
])
def test_ban_log_writes_reader(banner_scrapes, scrapes, want):
    from benchmark.harness import found

    before, snap = banner_scrapes
    prom0, prom1 = {"pair": (before, snap), "empty": ({}, {}),
                    "no_lines": (snap, snap)}[scrapes]
    ctx = {"prom0": prom0, "prom1": prom1, "trace": None, "trace_lines": 0,
           "mean_len": 0.0}
    assert found.module("layers", "ban_log_writes_per_kline").read(ctx) == want
    if scrapes == "pair":
        # every line is a record here: the per-record path would read 1,000
        assert found.module("layers", "ban_records_per_kline").read(ctx) \
            == pytest.approx(1e3)


@pytest.fixture(scope="module")
def shadow_scrape():
    """`/metrics` after a stream whose addresses all fire the rule and
    turn a 64-slot table over twice, so that events are absorbed, records
    spill, come back and are restored — and the matcher's own tallies."""
    from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
    from banjax_tpu.decisions.rate_limit import (
        FailedChallengeRateLimitStates,
    )
    from banjax_tpu.obs.exposition import render_prometheus
    from benchmark.harness import prom

    cfg = config_from_yaml_text(_RULES)
    cfg.matcher_device_windows = True
    cfg.matcher_window_capacity = 64
    cfg.warm_tier_enabled = True
    cfg.warm_tier_capacity = 1024
    m = TpuMatcher(cfg, MockBanner(), StaticDecisionLists(cfg),
                   RegexRateLimitStates())
    now = time.time()
    sched = PipelineScheduler(lambda: m, now_fn=lambda: now)
    sched.start()
    for k in range(12):           # 4 x 48 addresses, three times round
        sched.submit([
            f"{now:.6f} 1.2.{k % 4}.{i} GET h.com GET /attack{i} HTTP/1.1 ua -"
            for i in range(48)
        ])
        assert sched.flush(120)
    sched.stop()
    snap = prom.parse(render_prometheus(
        DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
        FailedChallengeRateLimitStates(), matcher=m,
    ))
    dw = m.device_windows
    tallies = {"absorb": dw.device_events, "spill": dw.warm_spills,
               "refill": dw.warm_refills, "native": dw.slotmgr_native}
    m.close()
    return snap, tallies


@pytest.mark.parametrize("op", ["absorb", "spill", "refill", "restore"])
def test_shadow_family_is_on_metrics_by_op_and_path(shadow_scrape, op):
    """`banjax_shadow_records_total{op, path}` (ISSUE 38): what moved
    through the host shadow, by the form that handled it.  Read by no
    cell; a chip run's `/metrics` shows by it that no operation fell
    back: with the native libraries loaded `path="dict"` reads 0, and the
    native counts are the window events, the spills and the refills."""
    from benchmark.harness import prom

    snap, tallies = shadow_scrape
    assert "banjax_shadow_records_total" in {f.prom for f in registry.FAMILIES}
    mine, other = (("native", "dict") if tallies["native"]
                   else ("dict", "native"))
    got = prom.value(snap, "banjax_shadow_records_total", op=op, path=mine)
    assert prom.value(
        snap, "banjax_shadow_records_total", op=op, path=other) == 0
    if op == "restore":
        assert 0 < got <= tallies["refill"]
    else:
        assert got == tallies[op] > 0
    assert tallies["absorb"] == prom.value(
        snap, "banjax_device_windows_events_total")


@pytest.fixture(scope="module")
def submit_scrapes():
    """Two scrapes of `/metrics` around a stream through the scheduler
    and the fused matcher, the second with the pipeline's threads still
    running: the submit stage from inside (ISSUE 39)."""
    from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
    from banjax_tpu.decisions.rate_limit import (
        FailedChallengeRateLimitStates,
    )
    from banjax_tpu.obs.exposition import render_prometheus
    from benchmark.harness import prom

    cfg = config_from_yaml_text(_RULES)
    cfg.matcher_device_windows = True
    cfg.matcher_window_capacity = 256
    cfg.warm_tier_enabled = True
    cfg.warm_tier_capacity = 1024
    m = TpuMatcher(cfg, MockBanner(), StaticDecisionLists(cfg),
                   RegexRateLimitStates())
    now = time.time()
    sched = PipelineScheduler(lambda: m, now_fn=lambda: now)

    def scrape():
        return prom.parse(render_prometheus(
            DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
            FailedChallengeRateLimitStates(), matcher=m, pipeline=sched,
        ))

    sched.start()
    before = scrape()
    t0 = time.time()
    for k in range(6):
        sched.submit([
            f"{now:.6f} 1.2.{k}.{i} GET h.com GET "
            f"/{'attack' if i % 10 == 0 else 'page'}{i} HTTP/1.1 ua -"
            for i in range(100)
        ])
        assert sched.flush(120)
    after = scrape()
    seconds = time.time() - t0
    sched.stop()
    m.close()
    return before, after, seconds


_PIPELINE_THREADS = ("pipeline-encode", "pipeline-device", "pipeline-drain")
_SUBMIT_FAMILIES = {
    "banjax_submit_phase_seconds_total":
        [{"phase": p} for p in trace.SUBMIT_PHASES],
    "banjax_submit_cpu_seconds_total": [{}],
    "banjax_windows_lock_wait_seconds_total":
        [{"stage": s} for s in ("submit", "drain")],
    "banjax_windows_lock_contended_total":
        [{"stage": s} for s in ("submit", "drain")],
    "banjax_thread_cpu_seconds_total":
        [{"thread": t} for t in _PIPELINE_THREADS],
    "banjax_pipeline_batch_target_changes_total":
        [{"direction": "up"}, {"direction": "down"}],
    # one call into the runtime a chunk, the window table's maintenance
    # riding it (ISSUE 49)
    "banjax_submit_runtime_calls_total": [{}],
    "banjax_device_windows_maintenance_steps_by_carrier_total":
        [{"carrier": c} for c in ("fused", "own")],
    # the encode stage's gate: one native call a shard, a string an
    # address only where one is asked for (ISSUE 50)
    "banjax_encode_gate_shards_total":
        [{"path": p} for p in ("native", "python")],
    "banjax_gate_address_strings_total": [{}],
    # the cyclic collector under the pipeline (ISSUE 40)
    "banjax_gc_collections_total": [{"generation": g} for g in "012"],
    "banjax_gc_pause_seconds_total": [{"generation": g} for g in "012"],
    "banjax_gc_collected_objects_total": [{"generation": g} for g in "012"],
    "banjax_gc_frozen_objects": [{}],
}


@pytest.mark.parametrize("family", sorted(_SUBMIT_FAMILIES))
def test_submit_stage_family_is_on_metrics_with_its_labels(
        submit_scrapes, family):
    """The families ISSUE 39 and ISSUE 40 brought, off `/metrics` through
    the benchmark's own parser, tracing off: declared, and exported with
    every label the readers select by while the pipeline's threads run."""
    from benchmark.harness import prom

    _, snap, _ = submit_scrapes
    assert family in {f.prom for f in registry.FAMILIES}
    for labels in _SUBMIT_FAMILIES[family]:
        assert prom.value(snap, family, **labels) is not None, labels
    if family == "banjax_submit_phase_seconds_total":
        for phase in trace.SUBMIT_PHASES:
            if phase != "other":
                assert prom.value(snap, family, phase=phase) > 0, phase
    if family == "banjax_submit_cpu_seconds_total":
        # the thread never ran longer than the wall its stage took
        wall = prom.value(snap, "banjax_submit_phase_seconds_total")
        assert 0 < prom.value(snap, family) <= wall + 1e-3
    if family == "banjax_thread_cpu_seconds_total":
        assert prom.value(snap, family, thread="pipeline-device") > 0
    if family == "banjax_submit_runtime_calls_total":
        # six batches, each one fused chunk: one call a batch, and one a
        # separate step run on padding beside each program built
        before = submit_scrapes[0]
        batches = prom.delta(before, snap, "banjax_pipeline_batches_total")
        assert batches <= prom.delta(before, snap, family) <= batches + 8
    if family.endswith("_steps_by_carrier_total"):
        assert prom.value(snap, family) == prom.value(
            snap, "banjax_device_windows_maintenance_steps_total")
    if family == "banjax_encode_gate_shards_total":
        # six batches under the shard floor: each gated whole, natively
        before = submit_scrapes[0]
        assert prom.delta(before, snap, family, path="native") == \
            prom.delta(before, snap, "banjax_pipeline_batches_total") == 6
        assert prom.value(snap, family, path="python") == 0
    if family == "banjax_gate_address_strings_total":
        # six hundred distinct addresses gated, placed and counted in
        # their windows, none past its limit: nothing asked for a string
        assert prom.delta(submit_scrapes[0], snap, family) == 0
    if family == "banjax_gc_collections_total":
        # the freeze at the first matcher collects the heap whole first
        assert prom.value(snap, family, generation="2") >= 1
        assert prom.value(
            snap, "banjax_gc_pause_seconds_total", generation="2") > 0
    if family == "banjax_gc_frozen_objects":
        assert not trace.enabled()
        assert prom.value(snap, family) > 10_000


def test_submit_phases_sum_to_the_device_stage(submit_scrapes):
    """The lap clock's six phases partition the submit stage: their wall
    is the scheduler's own `stage="device"` sum less the collects, which
    on this stream are a few per cent of it at most."""
    from benchmark.harness import prom

    _, snap, _ = submit_scrapes
    phases = prom.value(snap, "banjax_submit_phase_seconds_total")
    stage = prom.value(snap, "banjax_stage_duration_seconds_sum",
                       stage="device")
    assert 0.95 * stage <= phases <= stage
    assert prom.value(snap, "banjax_submit_phase_seconds_total",
                      phase="other") < 0.10 * phases
    # `resolve` keeps its extent: the pass and the sketch's note in it
    resolve = prom.value(snap, "banjax_submit_resolve_seconds_total")
    by = {p: prom.value(snap, "banjax_submit_phase_seconds_total",
                        phase=p) for p in ("pass", "sketch")}
    assert by["pass"] <= resolve + 1e-3 <= by["pass"] + by["sketch"] + 2e-3


def _scrape_pair(family, label_sets, before, after):
    """Two synthetic scrapes: every label set of `family` goes from
    `before` to `after`, beside 10,000 lines drained."""
    lines = ("banjax_pipeline_processed_lines_total", ())
    p0, p1 = {lines: 5_000.0}, {lines: 15_000.0}
    for labels in label_sets:
        key = (family, tuple(sorted(labels.items())))
        p0[key], p1[key] = before, after
    return p0, p1


_PHASE = "banjax_submit_phase_seconds_total"
_NEW_READERS = {
    # reader: (family, label sets the scrapes carry, before, after, reading)
    **{f"submit_{p}_ms_per_kline":
       (_PHASE, [{"phase": p}], 1.0, 1.5, 50.0)
       for p in trace.SUBMIT_PHASES},
    # six phases: 3 s more on the wall, 2.4 s of them on the thread's clock
    "submit_wait_share":
        (_PHASE, [{"phase": p} for p in trace.SUBMIT_PHASES],
         1.0, 1.5, None),
    "windows_lock_wait_ms_per_kline":
        ("banjax_windows_lock_wait_seconds_total", [{"stage": "submit"}],
         0.25, 0.5, 25.0),
    "pipeline_cores_busy":
        ("banjax_thread_cpu_seconds_total",
         [{"thread": t} for t in _PIPELINE_THREADS], 5.0, 25.0, 1.5),
    "batch_bucket_changes":
        ("banjax_pipeline_batch_target_changes_total",
         [{"direction": "up"}, {"direction": "down"}], 3.0, 4.0, 2.0),
    # ISSUE 40: all three generations' pauses, 1.5 s over 10,000 lines
    "gc_pause_ms_per_kline":
        ("banjax_gc_pause_seconds_total",
         [{"generation": g} for g in "012"], 1.0, 1.5, 150.0),
}


@pytest.mark.parametrize("name", sorted(_NEW_READERS))
def test_submit_stage_reader_reads_its_family(submit_scrapes, name):
    """Each of the ten per-layer readers PR 39 brought (ISSUE 39's
    eleventh, `device_thread_runqueue_share`, waits for a host that keeps
    a `schedstat`: PERF.md §7) and PR 40's `gc_pause_ms_per_kline`:
    listed in BENCHMARK.json for every cell,
    reads its family from two synthetic scrapes 40 s apart, reads
    something off a real pair, and is silent on a program without the
    family."""
    from benchmark.harness import found

    with open(os.path.join(_REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    entry = listed[name]
    assert entry["moves"] == "lines_per_s" and "workloads" not in entry
    family, label_sets, before, after, want = _NEW_READERS[name]
    p0, p1 = _scrape_pair(family, label_sets, before, after)
    if name == "submit_wait_share":
        key = ("banjax_submit_cpu_seconds_total", ())
        p0[key], p1[key] = 3.0, 5.4
        want = pytest.approx(100 * (3.0 - 2.4) / 3.0)
    reader = found.module("layers", name)
    ctx = {"prom0": p0, "prom1": p1, "seconds": 40.0}
    assert reader.read(ctx) == want
    assert reader.read({**ctx, "prom0": {}, "prom1": {}}) is None
    real0, real1, seconds = submit_scrapes
    got = reader.read({"prom0": real0, "prom1": real1, "seconds": seconds})
    assert got is not None and got >= 0


_CALLS = "banjax_submit_runtime_calls_total"
_BY_CARRIER = "banjax_device_windows_maintenance_steps_by_carrier_total"
_CALL_READERS = {
    # reader: (its entry in BENCHMARK.json less the name, the reading off
    #          the synthetic pair below)
    "submit_runtime_calls_per_batch": (
        {"unit": "count/batch", "better": "lower", "layer": "matcher"}, 1.2),
    "maintenance_fused_share": (
        {"unit": "%", "better": "higher",
         "layer": "device windows and tiers"}, 90.0),
}


@pytest.mark.parametrize("name", sorted(_CALL_READERS))
def test_runtime_call_reader_reads_its_family(submit_scrapes, name):
    """ISSUE 49's two readers: listed for every cell, read their families
    off two synthetic scrapes (60 calls over 50 batches; 90 maintenance
    runs carried fused of 100) and off a real pair, and are silent on a
    program without the counter and over an idle window."""
    from benchmark.harness import found

    entry, want = _CALL_READERS[name]
    assert [m for m in found.benchmark_json()["per_layer"]
            if m["name"] == name] == [{
                "name": name, **entry, "source": "program_counter",
                "moves": "lines_per_s"}]
    fused = (_BY_CARRIER, (("carrier", "fused"),))
    own = (_BY_CARRIER, (("carrier", "own"),))
    batches = ("banjax_pipeline_batches_total", ())
    p0 = {(_CALLS, ()): 100.0, batches: 10.0, fused: 8.0, own: 2.0}
    p1 = {(_CALLS, ()): 160.0, batches: 60.0, fused: 98.0, own: 12.0}
    reader = found.module("layers", name)
    assert reader.read({"prom0": p0, "prom1": p1}) == pytest.approx(want)
    assert reader.read({"prom0": {}, "prom1": {}}) is None
    assert reader.read({"prom0": p1, "prom1": p1}) is None
    real0, real1, _ = submit_scrapes
    got = reader.read({"prom0": real0, "prom1": real1})
    if name == "submit_runtime_calls_per_batch":
        assert 1.0 <= got <= 2.5
    else:
        # 600 addresses through 256 slots: every run rode its chunk
        assert got == 100.0


_GATE_SHARDS = "banjax_encode_gate_shards_total"
_GATE_STRINGS = "banjax_gate_address_strings_total"
_GATE_READERS = {
    # reader: (its entry in BENCHMARK.json less the name, the reading off
    #          the synthetic pair below)
    "encode_cpu_ms_per_kline": (
        {"unit": "ms/kline", "better": "lower"}, 350.0),
    "gate_native_share": ({"unit": "%", "better": "higher"}, 95.0),
    "gate_address_strings_per_kline": (
        {"unit": "count/kline", "better": "lower"}, 4.0),
}


@pytest.mark.parametrize("name", sorted(_GATE_READERS))
def test_gate_reader_reads_its_family(submit_scrapes, name):
    """ISSUE 50's three readers: listed for every cell under the layer
    "host encode", read their families off two synthetic scrapes (3.5 s
    of encode-side CPU, 95 of 100 shards gated natively and 40 address
    strings made, over 10,000 lines) and off a real pair, and are silent
    on a program without the family and over an idle window."""
    from benchmark.harness import found

    entry, want = _GATE_READERS[name]
    assert [m for m in found.benchmark_json()["per_layer"]
            if m["name"] == name] == [{
                "name": name, **entry, "source": "program_counter",
                "layer": "host encode", "moves": "lines_per_s"}]
    cpu = "banjax_thread_cpu_seconds_total"
    p0, p1 = _scrape_pair(cpu, [{"thread": "pipeline-encode"}], 1.0, 2.5)
    worker = (cpu, (("thread", "pipeline-encode-worker"),))
    device = (cpu, (("thread", "pipeline-device"),))
    native = (_GATE_SHARDS, (("path", "native"),))
    python = (_GATE_SHARDS, (("path", "python"),))
    p0.update({worker: 4.0, device: 9.0, native: 10.0, python: 0.0,
               (_GATE_STRINGS, ()): 5.0})
    p1.update({worker: 6.0, device: 99.0, native: 105.0, python: 5.0,
               (_GATE_STRINGS, ()): 45.0})
    reader = found.module("layers", name)
    assert reader.read({"prom0": p0, "prom1": p1}) == pytest.approx(want)
    assert reader.read({"prom0": {}, "prom1": {}}) is None
    assert reader.read({"prom0": p1, "prom1": p1}) is None
    if name == "encode_cpu_ms_per_kline":
        # a pipeline without the pool (no shard ever fanned out) has the
        # encode thread's clock alone
        del p0[worker], p1[worker]
        assert reader.read({"prom0": p0, "prom1": p1}) == pytest.approx(150.0)
    real0, real1, _ = submit_scrapes
    got = reader.read({"prom0": real0, "prom1": real1})
    if name == "gate_native_share":
        assert got == 100.0
    elif name == "gate_address_strings_per_kline":
        # 600 addresses, none past its limit: no row's line was asked for
        assert got == 0.0
    else:
        assert got is not None and got >= 0


def test_stage2_readers_split_a_trace_by_nfa_words():
    """`match_stage2_us_per_kline` and `match_stage2_roofline` take stage
    2 to be the match-kernel launches with more NFA words than stage 1's
    (trace_names.json `match_kernel_shapes`), and the work to be the
    counted candidates at the span's mean length: a share of a roofline
    never counts the padded columns."""
    from benchmark.harness import found, roofline

    def op(words, lines, seconds, launches):
        return [f"%single.3 = u32[{words},{lines}]{{1,0}} custom-call("
                f"s32[1]{{0}} %a, s32[256,{lines}]{{1,0}} %b, "
                f"s32[1,{lines}]{{1,0}} %c, s8[{4 * words},128]{{1,0}} %d), "
                'custom_call_target="tpu_custom_call"', seconds, launches]

    trace_ = {"kernel_ops": {"match_kernel": [
        op(480, 4096, 0.010, 20.0), op(26752, 512, 0.400, 20.0)]}}
    ctx = {"trace": trace_, "trace_lines": 80_000, "mean_len": 150.0,
           "prom0": {("banjax_prefilter_candidates_total", ()): 0.0,
                     ("banjax_pipeline_processed_lines_total", ()): 0.0},
           "prom1": {("banjax_prefilter_candidates_total", ()): 30_000.0,
                     ("banjax_pipeline_processed_lines_total", ()): 1e6},
           "device": {"kind": "TPU v5 lite"}}
    us = found.module("layers", "match_stage2_us_per_kline")
    assert us.read(ctx) == pytest.approx(0.400 * 1e9 / 80_000)
    rf = found.module("layers", "match_stage2_roofline")
    work = rf.stage2_work(0.03 * 80_000, 150.0, 20.0, 26752, 128)
    assert work == roofline.match_kernel_work(
        0.03 * 80_000 * 150.0, 0.03 * 80_000, 20.0, 26752, 128)
    share = rf.read(ctx)
    assert share == pytest.approx(
        100 * work["int8_ops"] / 393e12 / 0.400) and 0 < share < 100
    # one width only (a plan that is stage 1 alone), or no counter: silent
    trace_["kernel_ops"]["match_kernel"].pop()
    assert us.read(ctx) is None and rf.read(ctx) is None
    assert rf.read({**ctx, "prom0": {}, "prom1": {}}) is None


_LONGLINE_FAMILIES = {
    # family: (label sets the readers select by, its readers)
    "banjax_matcher_long_lines_total":
        ([{}], ["long_lines_share", "long_match_roofline"]),
    "banjax_matcher_long_line_bytes_total": ([{}], ["long_match_roofline"]),
    "banjax_matcher_long_candidates_total": ([{}], ["long_match_roofline"]),
    "banjax_matcher_long_candidate_bytes_total":
        ([{}], ["long_match_roofline"]),
    "banjax_matcher_unfused_batches_total":
        ([{"cause": "line_length"}, {"cause": "non_ascii"}],
         ["unfused_batches_share"]),
    "banjax_fused_overflows_total": ([{"cause": "long_rows"}], []),
}


@pytest.fixture(scope="module")
def longline_scrapes():
    """`/metrics` before and after three batches of 100 lines through the
    scheduler: in each, five lines over the short width (one of them a
    payload whose match begins past byte 256), and in the last a line
    with a byte over 0x7F.  Tracing is off."""
    from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
    from banjax_tpu.decisions.rate_limit import (
        FailedChallengeRateLimitStates,
    )
    from banjax_tpu.obs.exposition import render_prometheus
    from benchmark.harness import prom

    assert not trace.enabled()
    m = _matcher()
    now = time.time()
    sched = PipelineScheduler(lambda: m, now_fn=lambda: now)

    def scrape():
        return prom.parse(render_prometheus(
            DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
            FailedChallengeRateLimitStates(), matcher=m, pipeline=sched,
        ))

    def path(k, i):
        if i % 20 == 7:
            return "/" + "q=" * (150 + i) + ("/x GET /attack" if i == 7
                                             else "")
        return f"/caf\u00e9{i}" if (k, i) == (2, 50) else f"/page{i}"

    sched.start()
    before = scrape()
    for k in range(3):
        sched.submit([
            f"{now:.6f} 1.2.{k}.{i} GET h.com GET {path(k, i)} HTTP/1.1 ua -"
            for i in range(100)
        ])
        assert sched.flush(120)
    sched.stop()
    after = scrape()
    m.close()
    return before, after


@pytest.mark.parametrize("family", sorted(_LONGLINE_FAMILIES))
def test_longline_family_is_on_metrics_and_its_reader_reads_it(
        longline_scrapes, family):
    """The counters `longline1k-edge` brought (ISSUE 43), off `/metrics`
    through the benchmark's own parser and through the readers of
    `longline1k.flood`: lines over the short width and their bytes, the
    ones stage 2 scanned, batches that went classic for one line's sake by
    cause, and batches cut for want of room in a chunk's long operand."""
    from benchmark.harness import found, prom

    label_sets, readers = _LONGLINE_FAMILIES[family]
    assert family in {f.prom for f in registry.FAMILIES}
    before, after = longline_scrapes
    for labels in label_sets:
        assert prom.value(after, family, **labels) is not None, labels
    assert prom.value(after, "banjax_matcher_long_lines_total") == 15
    assert prom.value(after, "banjax_matcher_long_line_bytes_total") > 15 * 300
    # the payload line carries the rule's factor past byte 256, in each
    # of the two batches that went fused
    assert prom.value(after, "banjax_matcher_long_candidates_total") == 2
    assert prom.value(
        after, "banjax_matcher_unfused_batches_total", cause="non_ascii") == 1
    assert prom.value(
        after, "banjax_matcher_unfused_batches_total",
        cause="line_length") == 0
    assert prom.value(
        after, "banjax_fused_overflows_total", cause="long_rows") == 0
    assert prom.value(after, "banjax_pipelined_fused_chunks_total") == 2
    ctx = {"prom0": before, "prom1": after, "trace": None, "trace_lines": 0,
           "mean_len": 0.0, "config": {"product_config":
                                       {"matcher_max_line_len": 256}}}
    want = {"long_lines_share": 5.0, "long_match_roofline": None,
            "unfused_batches_share": pytest.approx(100 / 3)}
    for name in readers:
        assert found.module("layers", name).read(ctx) == want[name]
        # a program without the counter: the reader is silent
        assert found.module("layers", name).read(
            {**ctx, "prom0": {}, "prom1": {}}) is None


def test_sketch_updates_family_is_on_metrics_and_its_reader_reads_it(
        longline_scrapes):
    """`banjax_sketch_updates_total{path}` (ISSUE 44), off `/metrics` with
    tracing off and through `sketch_fused_share`: of the fixture's three
    batches two commit fused and carry their fold, and the one a byte over
    0x7F sends the classic way folds as a program of its own."""
    from benchmark.harness import found, prom

    family = "banjax_sketch_updates_total"
    assert family in {f.prom for f in registry.FAMILIES}
    before, after = longline_scrapes
    assert prom.value(before, family, path="fused") == 0
    assert prom.value(after, family, path="fused") == 2
    assert prom.value(after, family, path="standalone") == 1
    assert prom.value(after, "banjax_traffic_sketch_lines_total") == 300
    reader = found.module("layers", "sketch_fused_share")
    ctx = {"prom0": before, "prom1": after}
    assert reader.read(ctx) == pytest.approx(200 / 3)
    # the phase the fold left still has its family and its reader
    assert prom.value(
        after, "banjax_submit_phase_seconds_total", phase="sketch") > 0
    assert found.module(
        "layers", "submit_sketch_ms_per_kline").read(ctx) > 0
    # a program without the counter (PR 44's parent), an idle window
    assert reader.read({"prom0": {}, "prom1": {}}) is None
    assert reader.read({"prom0": after, "prom1": after}) is None
    entry = [m for m in found.benchmark_json()["per_layer"]
             if m["name"] == "sketch_fused_share"]
    assert entry == [{
        "name": "sketch_fused_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "device windows and tiers",
        "moves": "lines_per_s"}]


def test_address_pass_families_are_on_metrics_and_their_readers_read_them(
        longline_scrapes):
    """`banjax_submit_resolve_passes_total{form}` and
    `banjax_slot_eviction_scanned_slots_total` (ISSUE 45), off `/metrics`
    with tracing off and through `resolve_spans_share` and
    `eviction_scan_slots_per_kline`: each of the fixture's three batches
    is parsed natively and resolved in one pass over byte spans — the two
    that commit fused at submit, the classic one at its drain — and none
    evicts, so no placement had a victim to look for."""
    from benchmark.harness import found, prom

    passes = "banjax_submit_resolve_passes_total"
    scanned = "banjax_slot_eviction_scanned_slots_total"
    assert {passes, scanned} <= {f.prom for f in registry.FAMILIES}
    before, after = longline_scrapes
    assert prom.value(before, passes, form="spans") == 0
    assert prom.value(after, passes, form="spans") == 3
    assert prom.value(after, passes, form="strings") == 0
    assert prom.value(after, scanned) == 0
    ctx = {"prom0": before, "prom1": after}
    share = found.module("layers", "resolve_spans_share")
    scan = found.module("layers", "eviction_scan_slots_per_kline")
    assert share.read(ctx) == 100.0
    assert scan.read(ctx) == 0.0
    # two passes that took strings beside the three: the share says so
    mixed = dict(after)
    mixed[(passes, (("form", "strings"),))] = 2.0
    mixed[(scanned, ())] = 450.0
    assert share.read({"prom0": before, "prom1": mixed}) == 60.0
    assert scan.read({"prom0": before, "prom1": mixed}) == 1500.0
    for reader in (share, scan):
        # a program without the counter (PR 45's parent), an idle window
        assert reader.read({"prom0": {}, "prom1": {}}) is None
        assert reader.read({"prom0": after, "prom1": after}) is None
    entries = [m for m in found.benchmark_json()["per_layer"]
               if m["name"] in ("resolve_spans_share",
                                "eviction_scan_slots_per_kline")]
    assert entries == [
        {"name": "resolve_spans_share", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "device windows and tiers",
         "moves": "lines_per_s"},
        {"name": "eviction_scan_slots_per_kline", "unit": "count/kline",
         "better": "lower", "source": "program_counter",
         "layer": "device windows and tiers", "moves": "lines_per_s"}]


def test_long_match_readers_take_the_launches_over_the_short_width():
    """`long_match_us_per_kline` and `long_match_roofline` take the long
    launches to be the match-kernel launches whose padded line length
    (trace_names.json `match_kernel_shapes`) is over the configuration's
    `matcher_max_line_len`, both stages', and the work to be the long
    lines' own bytes as the program counted them: a share of a roofline
    never counts the padded rows x columns of a launch."""
    from benchmark.harness import found, roofline

    def op(words, lines, line_len, seconds, launches):
        return [f"%long-rows.3 = u32[{words},{lines}]{{1,0}} custom-call("
                f"s32[2]{{0}} %a, s32[{line_len},{lines}]{{1,0}} %b, "
                f"s32[1,{lines}]{{1,0}} %c, s8[{4 * words},128]{{1,0}} %d), "
                'custom_call_target="tpu_custom_call"', seconds, launches]

    trace_ = {"kernel_ops": {"match_kernel": [
        op(64, 4096, 256, 0.010, 20.0), op(2304, 512, 256, 0.030, 20.0),
        op(64, 256, 1024, 0.005, 20.0), op(2304, 256, 1024, 0.050, 20.0),
        op(64, 128, 8192, 0.015, 20.0), op(2304, 128, 8192, 0.150, 20.0)]}}
    fam = "banjax_matcher_long_"
    p1 = {(fam + "lines_total", ()): 31_000.0,
          (fam + "line_bytes_total", ()): 13e6,
          (fam + "candidates_total", ()): 2_000.0,
          (fam + "candidate_bytes_total", ()): 4e6,
          ("banjax_pipeline_processed_lines_total", ()): 1e6}
    ctx = {"trace": trace_, "trace_lines": 80_000, "mean_len": 160.0,
           "prom0": dict.fromkeys(p1, 0.0), "prom1": p1,
           "device": {"kind": "TPU v5 lite"},
           "config": {"product_config": {"matcher_max_line_len": 256}}}
    us = found.module("layers", "long_match_us_per_kline")
    assert us.read(ctx) == pytest.approx(0.220 * 1e9 / 80_000)
    rf = found.module("layers", "long_match_roofline")
    s1 = rf.long_work(13.0 * 80_000, 0.031 * 80_000, 40.0, 64, 128)
    s2 = rf.long_work(4.0 * 80_000, 0.002 * 80_000, 40.0, 2304, 128)
    assert s1 == roofline.match_kernel_work(
        13.0 * 80_000, 0.031 * 80_000, 40.0, 64, 128)
    share = rf.read(ctx)
    assert share == pytest.approx(
        100 * (s1["int8_ops"] + s2["int8_ops"]) / 393e12 / 0.220)
    assert 0 < share < 5
    # no launch over the short width (the other five configurations, or a
    # program without the long operand), or no counter: silent
    del trace_["kernel_ops"]["match_kernel"][2:]
    assert us.read(ctx) is None and rf.read(ctx) is None
    trace_["kernel_ops"]["match_kernel"].append(
        op(64, 256, 8192, 0.020, 20.0))
    assert rf.read({**ctx, "prom0": {}, "prom1": {}}) is None


@pytest.mark.parametrize("name,rel", _configurations())
def test_expect_keys_are_keys_of_describe(name, rel):
    """`correct` compares the configuration's `expect` with
    `/healthz`'s matcher info (`TpuMatcher.describe()`), and reads
    `downgrades` beside it."""
    d = _matcher().describe()
    want = set(_configuration(rel)["expect"]) | {
        "fused_protocol", "prefilter", "downgrades",
    }
    assert want <= set(d), sorted(want - set(d))
    assert d["fused_protocol"] in ("single-kernel", "classic")


@pytest.mark.parametrize("name,rel", _configurations())
def test_product_config_keys_are_known_to_the_schema(name, rel):
    """The loader ignores a key it does not know.  A configuration that
    sets one would run the default in silence: only the three protocol
    keys removed in PR 30 may be left over, and the value a file gives
    them is the one behaviour that is left."""
    pc = _configuration(rel)["product_config"]
    cfg = config_from_yaml_text(yaml.safe_dump(pc))
    unknown = {k for k in pc if not hasattr(cfg, k)}
    assert unknown <= _REMOVED_KEYS, sorted(unknown - _REMOVED_KEYS)
    assert pc.get("pallas_single_kernel", "auto") == "auto"
    assert pc.get("pipeline_fused", True) is True
    for k in pc:
        if k not in unknown and not isinstance(pc[k], (dict, list)):
            assert getattr(cfg, k) == pc[k], k
