"""The acceptance scenario for the resilience tentpole, end to end:

with a failpoint forcing matcher device errors, the breaker trips, batches
keep flowing through the CPU reference matcher (no line errors, bans still
fire), /healthz reports the matcher DEGRADED and the metrics line carries
the breaker keys; after disarming, the half-open probe succeeds, the
breaker closes, and /healthz reports healthy again.
"""

import io
import json
import time

import pytest
import requests

from banjax_tpu.resilience import failpoints
from banjax_tpu.resilience.breaker import CLOSED, OPEN

BASE = "http://localhost:8081"


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.disarm()
    yield
    failpoints.disarm()


def _lines(n, path="/blockme"):
    now = time.time()
    return [
        f"{now:.6f} 7.7.7.{i} GET example.com GET {path} HTTP/1.1 ua"
        for i in range(n)
    ]


def _healthz():
    r = requests.get(f"{BASE}/healthz", timeout=5)
    return r.status_code, r.json()


def test_breaker_trip_fallback_healthz_and_recovery(app_factory):
    app = app_factory("banjax-config-test-tpu-breaker.yaml")

    # 1. healthy device path: the TPU matcher (xla backend) serves a batch
    results = app._consume_lines(_lines(4))
    matcher = app._matcher
    assert matcher.breaker.state == CLOSED
    assert all(not r.error for r in results)
    assert all(r.rule_results and r.rule_results[0].regex_match
               for r in results)
    code, snap = _healthz()
    assert code == 200
    assert snap["status"] == "healthy"
    assert snap["components"]["matcher"]["status"] == "healthy"
    assert snap["components"]["tailer"]["status"] == "healthy"

    # 2. force device errors; threshold is 2 → two batches trip it OPEN.
    #    every batch still produces full results via the CPU reference
    #    matcher: no line errors, the block rule still matches and bans
    failpoints.arm("matcher.device")
    for _ in range(2):
        results = app._consume_lines(_lines(3))
        assert all(not r.error for r in results)
        assert all(
            r.rule_results
            and r.rule_results[0].regex_match
            and r.rule_results[0].rate_limit_result.exceeded
            for r in results
        )
    assert matcher.breaker.state == OPEN
    assert matcher.fallback_batches >= 2

    # 3. observable degradation: /healthz (200 — still serving!) and the
    #    additive metrics keys
    code, snap = _healthz()
    assert code == 200
    assert snap["status"] == "degraded"
    assert snap["components"]["matcher"]["status"] == "degraded"
    assert "breaker" in snap["components"]["matcher"]["detail"]
    line = matcher.stats.snapshot(None, matcher)
    assert line["MatcherBreakerState"] == "open"
    assert line["MatcherBreakerTrips"] >= 1
    assert line["MatcherCpuFallbackBatches"] >= 2

    # 4. while OPEN the device path is not even attempted
    fired_before = failpoints.fired_count("matcher.device")
    results = app._consume_lines(_lines(2))
    assert all(not r.error for r in results)
    assert failpoints.fired_count("matcher.device") == fired_before

    # 5. disarm + recovery window (0.05 s in the fixture): the half-open
    #    probe batch runs the device path again and closes the breaker
    #    The tailer feeds the /healthz requests' own log lines through the
    #    same breaker from its thread: its probe can be the one in flight
    #    when ours asks, and ours then rides the fallback — so send batches
    #    until a probe of either has closed it.
    failpoints.disarm("matcher.device")
    deadline = time.monotonic() + 5.0
    while matcher.breaker.state != CLOSED and time.monotonic() < deadline:
        time.sleep(0.08)
        results = app._consume_lines(_lines(3))
        assert all(not r.error for r in results)
    assert matcher.breaker.state == CLOSED
    code, snap = _healthz()
    assert code == 200
    assert snap["status"] == "healthy"
    assert snap["components"]["matcher"]["status"] == "healthy"


def test_metrics_line_carries_health_keys(app_factory):
    from banjax_tpu.obs.metrics import write_metrics_line
    from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
    from banjax_tpu.decisions.rate_limit import (
        FailedChallengeRateLimitStates,
        RegexRateLimitStates,
    )

    app = app_factory("banjax-config-test-tpu-breaker.yaml")
    app._consume_lines(_lines(1))
    out = io.StringIO()
    write_metrics_line(
        out, DynamicDecisionLists(start_sweeper=False),
        RegexRateLimitStates(), FailedChallengeRateLimitStates(),
        health=app.health,
    )
    line = json.loads(out.getvalue())
    assert line["HealthStatus"] == "healthy"
    assert line["Health_matcher"] == "healthy"
    assert line["Health_tailer"] == "healthy"
