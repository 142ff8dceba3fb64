"""Exposition-schema stability: every key the 29 s line emits and every
/metrics family is declared in the one registry (obs/registry.py), the
reference's five keys stay byte-identical, /metrics parses under the
strict text-format parser, and the README metrics table stays in
lock-step with the registry (scripts/check_metrics_docs.py)."""

import io
import json
import os
import subprocess
import sys
import time
import types

import pytest

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.rate_limit import (
    FailedChallengeRateLimitStates,
    RegexRateLimitStates,
)
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.obs import registry
from banjax_tpu.obs.exposition import (
    ExpositionError,
    parse_text_format,
    render_prometheus,
)
from banjax_tpu.obs.metrics import write_metrics_line
from banjax_tpu.pipeline import PipelineScheduler
from banjax_tpu.resilience.health import HealthRegistry
from tests.mock_banner import MockBanner

RULES_YAML = """
regexes_with_rates:
  - decision: nginx_block
    rule: r
    regex: 'GET .*'
    interval: 5
    hits_per_interval: 100
"""

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))


@pytest.fixture(scope="module")
def loaded_system():
    """A matcher + drained pipeline with device windows on — the fullest
    legitimately reachable snapshot surface."""
    cfg = config_from_yaml_text(RULES_YAML)
    cfg.matcher_device_windows = True
    m = TpuMatcher(cfg, MockBanner(), StaticDecisionLists(cfg),
                   RegexRateLimitStates())
    now = time.time()
    m.consume_lines(
        [f"{now:.6f} 9.9.9.{i} GET h.com GET /x HTTP/1.1" for i in range(8)],
        now,
    )
    sched = PipelineScheduler(lambda: m, now_fn=lambda: now)
    sched.start()
    sched.submit(
        [f"{now:.6f} 8.8.8.{i % 40} GET h.com GET /y HTTP/1.1"
         for i in range(256)]
    )
    assert sched.flush(60)
    # sharded-encode stats so the per-worker gauges have data
    sched.stats.note_encode_shards([4.0, 5.0], 5.5)
    sched.stats.note_encode_shards([3.0, 6.0], 6.5)
    health = HealthRegistry()
    health.register("tailer").ok()
    health.register("pipeline").degraded("test")
    sup = types.SimpleNamespace(n_workers=2, respawn_count=1)
    yield m, sched, health, sup
    sched.stop()


def _full_line(m, sched, health, sup) -> dict:
    out = io.StringIO()
    write_metrics_line(
        out, DynamicDecisionLists(start_sweeper=False),
        RegexRateLimitStates(), FailedChallengeRateLimitStates(),
        m, sup, health, sched,
    )
    return json.loads(out.getvalue())


def test_every_line_key_is_declared(loaded_system):
    line = _full_line(*loaded_system)
    undeclared = [k for k in line if not registry.is_declared_line_key(k)]
    assert not undeclared, (
        f"29s-line keys missing from obs/registry.py: {undeclared} — "
        "declare them (name, type, help) or the dashboards chase ghosts"
    )


def test_reference_five_keys_byte_identical(loaded_system):
    line = _full_line(*loaded_system)
    for key in registry.REFERENCE_LINE_KEYS:
        assert key in line, f"reference key {key} missing"
    # the declared tuple itself is the reference's exact bytes
    assert registry.REFERENCE_LINE_KEYS == (
        "Time", "LenExpiringChallenges", "LenExpiringBlocks",
        "LenIpToRegexStates", "LenFailedChallengeStates",
    )


def test_metrics_families_all_declared_and_parse(loaded_system):
    m, sched, health, sup = loaded_system
    text = render_prometheus(
        DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
        FailedChallengeRateLimitStates(), matcher=m, pipeline=sched,
        health=health, supervisor=sup,
    )
    fams = parse_text_format(text)  # strict: raises on any malformation
    undeclared = [f for f in fams if f not in registry.PROM_FAMILIES]
    assert not undeclared, f"/metrics families not in registry: {undeclared}"
    # declared type matches emitted type
    for name, ent in fams.items():
        assert ent["type"] == registry.PROM_FAMILIES[name].kind, name
    # core families present with plausible values
    samples = {
        s[0]: s[2] for ent in fams.values() for s in ent["samples"]
        if not s[1]
    }
    assert samples["banjax_matcher_lines_total"] >= 8
    assert samples["banjax_pipeline_processed_lines_total"] == 256
    assert samples["banjax_health_status"] == 1  # degraded component


@pytest.mark.parametrize("line_key, family, attr", [
    ("DeviceWindowsEvictions", "banjax_device_windows_evictions_total",
     "eviction_count"),
    ("DeviceWindowsMaintenanceSteps",
     "banjax_device_windows_maintenance_steps_total", "maintenance_steps"),
    ("DeviceWindowsMaintenanceElems",
     "banjax_device_windows_maintenance_elems_total", "maintenance_elems"),
])
def test_device_windows_counters_on_line_and_metrics(
    loaded_system, line_key, family, attr
):
    """The eviction counter and the two maintenance counters beside it
    (elems / evictions says whether the step stayed O(evicted slots)):
    on the 29 s line, declared, and exposed as counters with the same
    value the windows object holds."""
    m, sched, health, sup = loaded_system
    want = getattr(m.device_windows, attr)
    line = _full_line(m, sched, health, sup)
    assert line[line_key] == want
    assert registry.is_declared_line_key(line_key)
    assert registry.PROM_FAMILIES[family].kind == registry.COUNTER
    fams = parse_text_format(render_prometheus(
        DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
        FailedChallengeRateLimitStates(), matcher=m,
    ))
    assert fams[family]["type"] == registry.COUNTER
    assert [s[2] for s in fams[family]["samples"]] == [want]


def test_breaker_state_is_one_hot(loaded_system):
    m, sched, health, sup = loaded_system
    text = render_prometheus(
        DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
        FailedChallengeRateLimitStates(), matcher=m,
    )
    fams = parse_text_format(text)
    states = {
        s[1]["state"]: s[2]
        for s in fams["banjax_matcher_breaker_state"]["samples"]
    }
    assert set(states) == {"closed", "open", "half-open"}
    assert sum(states.values()) == 1
    assert states["closed"] == 1


def test_per_worker_busy_fraction_and_skew(loaded_system):
    m, sched, health, sup = loaded_system
    text = render_prometheus(
        DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
        FailedChallengeRateLimitStates(), pipeline=sched,
    )
    fams = parse_text_format(text)
    workers = {
        s[1]["worker"]: s[2]
        for s in fams["banjax_encode_worker_busy_fraction"]["samples"]
    }
    assert set(workers) == {"0", "1"}
    assert 0.0 < workers["0"] <= 1.0 and 0.0 < workers["1"] <= 1.0
    # shard 1 is the consistently slower one in the fixture data
    assert workers["1"] > workers["0"]
    (skew,) = [
        s[2] for s in fams["banjax_encode_shard_skew_max"]["samples"]
    ]
    assert skew > 1.0


def test_scrape_does_not_steal_line_windows(loaded_system):
    """peek()-based exposition must leave the 29 s line's interval
    windows untouched: scrape between two lines, the line still sees the
    full interval delta."""
    m, sched, health, sup = loaded_system
    now = time.time()
    m.consume_lines(
        [f"{now:.6f} 7.7.7.{i} GET h.com GET /z HTTP/1.1" for i in range(5)],
        now,
    )
    for _ in range(3):  # scrapes between line snapshots
        render_prometheus(
            DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
            FailedChallengeRateLimitStates(), matcher=m, pipeline=sched,
        )
    line = _full_line(m, sched, health, sup)
    # the interval window still holds the 5 lines: scrapes didn't reset it
    assert line["MatcherLinesPerSec"] > 0


def test_parser_rejects_malformed_exposition():
    bad_cases = [
        "banjax_x 1\n",                      # sample without TYPE
        "# TYPE banjax_x counter\nbanjax_x 1",  # missing trailing newline
        "# TYPE banjax_x counter\nbanjax_x notanumber\n",
        "# TYPE banjax_x counter\n# TYPE banjax_x counter\nbanjax_x 1\n",
        '# TYPE banjax_x counter\nbanjax_x{bad-label="v"} 1\n',
        "# TYPE banjax_x counter\nbanjax_x -3\n",  # negative counter
    ]
    for text in bad_cases:
        with pytest.raises(ExpositionError):
            parse_text_format(text)


def test_parser_rejects_bad_histograms():
    head = "# TYPE banjax_h histogram\n"
    no_inf = head + (
        'banjax_h_bucket{le="1.0"} 1\nbanjax_h_sum 1\nbanjax_h_count 1\n'
    )
    non_monotone = head + (
        'banjax_h_bucket{le="1.0"} 5\nbanjax_h_bucket{le="+Inf"} 3\n'
        "banjax_h_sum 1\nbanjax_h_count 3\n"
    )
    inf_ne_count = head + (
        'banjax_h_bucket{le="1.0"} 1\nbanjax_h_bucket{le="+Inf"} 2\n'
        "banjax_h_sum 1\nbanjax_h_count 3\n"
    )
    for text in (no_inf, non_monotone, inf_ne_count):
        with pytest.raises(ExpositionError):
            parse_text_format(text)


def test_histogram_observations_land_in_buckets(loaded_system):
    m, sched, health, sup = loaded_system
    text = render_prometheus(
        DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
        FailedChallengeRateLimitStates(), matcher=m, pipeline=sched,
    )
    fams = parse_text_format(text)
    batch = fams["banjax_batch_latency_seconds"]["samples"]
    count = [v for n, l, v in batch if n.endswith("_count")][0]
    assert count >= 1  # consume_lines recorded batches
    stages = {
        s[1].get("stage") for s in
        fams["banjax_stage_duration_seconds"]["samples"]
        if s[0].endswith("_bucket")
    }
    assert {"encode", "device", "drain"} <= stages


def test_check_metrics_docs_passes_and_catches_drift(tmp_path):
    script = os.path.join(_REPO, "scripts", "check_metrics_docs.py")
    r = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        cwd=_REPO, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    # drift detection: drop one documented row -> nonzero exit
    with open(os.path.join(_REPO, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    drifted = readme.replace("| `banjax_matcher_lines_total` |", "| `x` |", 1)
    p = tmp_path / "README.md"
    p.write_text(drifted, encoding="utf-8")
    r = subprocess.run(
        [sys.executable, script, str(p)], capture_output=True, text=True,
        cwd=_REPO, timeout=120,
    )
    assert r.returncode == 1
    assert "banjax_matcher_lines_total" in r.stderr


def test_provenance_slo_flightrec_families_render_and_declare(
    loaded_system, tmp_path
):
    """The ISSUE 6 families: banjax_decision_inserts_total{source,
    decision}, banjax_slo_burn_rate{slo,window}, the one-hot
    banjax_slo_breached, banjax_matcher_budget_trips_total and
    banjax_flightrec_incidents_total all render from real objects,
    parse strictly, and are registry-declared."""
    from banjax_tpu.obs import provenance
    from banjax_tpu.obs.flightrec import FlightRecorder
    from banjax_tpu.obs.slo import SloEngine

    m, sched, health, sup = loaded_system
    provenance.configure(enabled=True, ring_size=64)
    try:
        provenance.record(provenance.SOURCE_KAFKA, "1.2.3.4", "NginxBlock",
                          rule="block_ip")
        provenance.record(provenance.SOURCE_RATE_LIMIT, "1.2.3.4",
                          "Challenge", rule="r")
        m.budget_trips += 2
        engine = SloEngine(
            matcher_getter=lambda: m, pipeline_getter=lambda: sched,
            batch_budget_s_fn=lambda: 0.25,
        )
        engine.sample()
        engine.sample()
        rec = FlightRecorder(str(tmp_path / "inc"), min_interval_s=0.0)
        rec.notify("test")
        text = render_prometheus(
            DynamicDecisionLists(start_sweeper=False),
            RegexRateLimitStates(), FailedChallengeRateLimitStates(),
            matcher=m, pipeline=sched, health=health, supervisor=sup,
            slo=engine, flightrec=rec,
        )
        fams = parse_text_format(text)
        undeclared = [f for f in fams if f not in registry.PROM_FAMILIES]
        assert not undeclared, undeclared

        inserts = {
            (s[1]["source"], s[1]["decision"]): s[2]
            for s in fams["banjax_decision_inserts_total"]["samples"]
        }
        assert inserts[("kafka", "NginxBlock")] == 1
        assert inserts[("rate_limit", "Challenge")] == 1

        burn = {
            (s[1]["slo"], s[1]["window"])
            for s in fams["banjax_slo_burn_rate"]["samples"]
        }
        assert ("batch_latency", "5m") in burn
        assert ("shed_ratio", "5m") in burn
        breached = {
            s[1]["slo"]: s[2]
            for s in fams["banjax_slo_breached"]["samples"]
        }
        assert set(breached) == {
            "batch_latency", "shed_ratio", "stale_ratio", "breaker_open",
            "budget_trips",
        }
        scalars = {
            s[0]: s[2] for ent in fams.values() for s in ent["samples"]
            if not s[1]
        }
        assert scalars["banjax_matcher_budget_trips_total"] == 2
        assert scalars["banjax_flightrec_incidents_total"] == 1
    finally:
        provenance.configure(enabled=True)


def test_budget_trips_on_the_29s_line(loaded_system):
    line = _full_line(*loaded_system)
    assert "MatcherBudgetTrips" in line
    assert registry.is_declared_line_key("MatcherBudgetTrips")


def test_traffic_families_render_and_declare(loaded_system):
    """The ISSUE 8 families: scalar banjax_traffic_* gauges/counters
    ride the line-key map, the labeled banjax_traffic_rule_pressure
    comes from the sketch's pulled summary, and everything parses
    strictly and is registry-declared."""
    m, sched, health, sup = loaded_system
    assert m.traffic_sketch is not None
    m.traffic_sketch.pull(force=True)  # a fresh summary for the render
    text = render_prometheus(
        DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
        FailedChallengeRateLimitStates(), matcher=m, pipeline=sched,
        health=health, supervisor=sup,
    )
    fams = parse_text_format(text)
    undeclared = [f for f in fams if f not in registry.PROM_FAMILIES]
    assert not undeclared, undeclared
    scalars = {
        s[0]: s[2] for ent in fams.values() for s in ent["samples"]
        if not s[1]
    }
    assert scalars["banjax_traffic_sketch_lines_total"] >= 8
    assert scalars["banjax_traffic_distinct_ips_estimate"] > 0
    assert scalars["banjax_traffic_sketch_pull_bytes_total"] > 0
    assert "banjax_traffic_sketch_pull_age_seconds" in fams
    # the fixture's rule ("GET .*") fires on every line: pressure renders
    pressure = {
        s[1]["rule"]: s[2]
        for s in fams["banjax_traffic_rule_pressure"]["samples"]
    }
    assert pressure.get("r", 0) > 0
    # ... and the line keys are declared too
    line = _full_line(m, sched, health, sup)
    for key in ("TrafficSketchLines", "TrafficDistinctIpsEst",
                "TrafficHeavyHitterShare", "TrafficSketchPullBytes",
                "TrafficSketchPullAgeSeconds"):
        assert key in line, key
        assert registry.is_declared_line_key(key), key


def test_challenge_families_render_and_declare():
    """The ISSUE 17 families: drive the real challenge plane — stateless
    issuance, an accepted device-path verification, a rejected one, and
    a bounded failure state under eviction pressure — then require every
    banjax_challenge_* family and Challenge* line key on both surfaces,
    registry-declared, with the values the drive produced."""
    from banjax_tpu.challenge import issuer, verifier
    from banjax_tpu.challenge.failures import BoundedFailedChallengeStates
    from banjax_tpu.challenge.stats import get_stats as challenge_stats
    from banjax_tpu.crypto.challenge import (
        CookieError,
        solve_challenge_for_testing,
    )

    challenge_stats().reset()
    secret, binding = "expo-secret", "5.6.7.8"
    cookie = issuer.issue(secret, 300, binding)
    solved = solve_challenge_for_testing(cookie, zero_bits=6)
    dv = verifier.DeviceVerifier(batch_max=16, interpret=True)
    now = time.time()
    verifier.verify_sha_inv(secret, solved, now, binding, 6, device=dv)
    with pytest.raises(CookieError):
        verifier.verify_sha_inv(secret, solved, now, binding, 250, device=dv)

    fc = BoundedFailedChallengeStates(4)
    cfg = config_from_yaml_text(RULES_YAML)
    for i in range(12):
        fc.apply(f"6.6.6.{i}", cfg)
    assert len(fc) == 4
    assert fc.evictions_total == 8

    text = render_prometheus(
        DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
        fc,
    )
    fams = parse_text_format(text)
    undeclared = [f for f in fams if f not in registry.PROM_FAMILIES]
    assert not undeclared, undeclared
    scalars = {
        s[0]: s[2] for ent in fams.values() for s in ent["samples"]
        if not s[1]
    }
    assert scalars["banjax_challenge_issued_total"] == 1
    assert scalars["banjax_challenge_failure_state_entries"] == 4
    assert scalars["banjax_challenge_failure_evictions_total"] == 8
    verif = {
        (s[1]["result"], s[1]["path"]): s[2]
        for s in fams["banjax_challenge_verifications_total"]["samples"]
    }
    assert verif[("accept", "device")] == 1
    assert verif[("reject", "device")] == 1
    hist = fams["banjax_challenge_verify_batch_size"]["samples"]
    count = [v for n, l, v in hist if n.endswith("_count")][0]
    assert count == 2  # one dispatch per verification above

    out = io.StringIO()
    write_metrics_line(
        out, DynamicDecisionLists(start_sweeper=False),
        RegexRateLimitStates(), fc,
    )
    line = json.loads(out.getvalue())
    for key in ("ChallengeIssued", "ChallengeVerifications",
                "ChallengeFailureStateEntries", "ChallengeFailureEvictions"):
        assert key in line, key
        assert registry.is_declared_line_key(key), key
    assert line["ChallengeIssued"] == 1
    assert line["ChallengeVerifications"] == 2
    assert line["ChallengeFailureStateEntries"] == 4
    # the reference length key reports the bounded exact tier
    assert line["LenFailedChallengeStates"] == 4


def test_challenge_quiet_process_stays_schema_clean(loaded_system):
    """A process that never touched the challenge plane must emit no
    Challenge* line keys and no banjax_challenge_* families — the
    reference's exact key set is preserved."""
    from banjax_tpu.challenge.stats import get_stats as challenge_stats

    challenge_stats().reset()
    line = _full_line(*loaded_system)
    assert not [k for k in line if k.startswith("Challenge")]
    text = render_prometheus(
        DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
        FailedChallengeRateLimitStates(),
    )
    assert "banjax_challenge_" not in text


def test_mega_state_families_render_and_declare():
    """The ISSUE 14 tiering families: a gated matcher whose unseen IPs
    all land BELOW the derived admission threshold (the fixture rule
    needs 101 hits) refuses every slot claim, homes the refused-row
    window state in the warm tier, and must surface all of it on both
    exposition surfaces with every name registry-declared."""
    cfg = config_from_yaml_text(RULES_YAML)
    cfg.matcher_device_windows = True
    cfg.matcher_window_capacity = 64
    cfg.traffic_sketch_enabled = True
    cfg.slot_admission_enabled = True   # min_estimate 0 -> derived 101
    cfg.warm_tier_enabled = True
    cfg.warm_tier_capacity = 1024
    m = TpuMatcher(cfg, MockBanner(), StaticDecisionLists(cfg),
                   RegexRateLimitStates())
    try:
        now = time.time()
        m.consume_lines(
            [f"{now:.6f} 7.7.{i >> 8}.{i & 255} GET h.com GET /x HTTP/1.1"
             for i in range(48)],
            now,
        )
        dw = m.device_windows
        assert dw.slot_refusals >= 48      # every unseen IP refused
        assert dw.warm_spills > 0          # refused state homes warm
        text = render_prometheus(
            DynamicDecisionLists(start_sweeper=False),
            RegexRateLimitStates(), FailedChallengeRateLimitStates(),
            matcher=m,
        )
        fams = parse_text_format(text)
        undeclared = [f for f in fams if f not in registry.PROM_FAMILIES]
        assert not undeclared, undeclared
        scalars = {
            s[0]: s[2] for ent in fams.values() for s in ent["samples"]
            if not s[1]
        }
        assert scalars["banjax_slot_refusals_total"] >= 48
        assert scalars["banjax_sketch_admissions_total"] == 0
        assert scalars["banjax_sketch_admission_fp_rate"] == 0
        assert scalars["banjax_warm_tier_spills_total"] > 0
        assert scalars["banjax_warm_tier_refills_total"] == 0
        assert scalars["banjax_warm_tier_dropped_total"] == 0
        assert scalars["banjax_warm_tier_occupancy"] > 0
        assert scalars["banjax_warm_tier_capacity"] == 1024
        # the C table's own counts (the Python fallback reports 0 for both)
        native = hasattr(dw._warm, "record_reads")
        assert (scalars["banjax_warm_tier_probes_total"] > 0) == native
        assert (scalars["banjax_warm_tier_record_reads_total"]
                <= scalars["banjax_warm_tier_probes_total"])
        out = io.StringIO()
        write_metrics_line(
            out, DynamicDecisionLists(start_sweeper=False),
            RegexRateLimitStates(), FailedChallengeRateLimitStates(), m,
        )
        line = json.loads(out.getvalue())
        for key in ("SlotRefusals", "SketchAdmissions",
                    "SketchAdmissionFpRate", "WarmTierSpills",
                    "WarmTierRefills", "WarmTierDropped",
                    "WarmTierOccupancy", "WarmTierCapacity",
                    "WarmTierProbes", "WarmTierRecordReads"):
            assert key in line, key
            assert registry.is_declared_line_key(key), key
        assert line["SlotRefusals"] >= 48
        assert line["WarmTierCapacity"] == 1024
    finally:
        m.close()
