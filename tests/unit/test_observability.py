"""Metrics line (reference schema + additive TPU keys) and the
profile-endpoint wiring (`profile: true`, VERDICT r1 weak #9)."""

import io
import json
import time

import pytest

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.rate_limit import (
    FailedChallengeRateLimitStates,
    RegexRateLimitStates,
)
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.obs.metrics import write_metrics_line
from tests.mock_banner import MockBanner

RULES_YAML = """
regexes_with_rates:
  - decision: nginx_block
    rule: r
    regex: 'GET .*'
    interval: 5
    hits_per_interval: 100
"""

REFERENCE_KEYS = {
    "Time", "LenExpiringChallenges", "LenExpiringBlocks",
    "LenIpToRegexStates", "LenFailedChallengeStates",
}


def _line(matcher=None):
    out = io.StringIO()
    write_metrics_line(
        out,
        DynamicDecisionLists(start_sweeper=False),
        RegexRateLimitStates(),
        FailedChallengeRateLimitStates(),
        matcher,
    )
    return json.loads(out.getvalue())


def test_reference_schema_unchanged_without_matcher():
    assert set(_line()) == REFERENCE_KEYS


def test_matcher_keys_are_additive():
    cfg = config_from_yaml_text(RULES_YAML)
    cfg.matcher_device_windows = True
    m = TpuMatcher(cfg, MockBanner(), StaticDecisionLists(cfg), RegexRateLimitStates())
    now = time.time()
    m.consume_lines(
        [f"{now:.6f} 9.9.9.{i} GET h.com GET /x HTTP/1.1" for i in range(10)], now
    )
    line = _line(m)
    assert REFERENCE_KEYS < set(line)  # reference keys all still present
    assert line["MatcherLinesTotal"] == 10
    assert line["MatcherBatchesTotal"] == 1
    assert line["MatcherLinesPerSec"] > 0
    assert line["MatcherBatchLatencyP50Ms"] > 0
    assert line["MatcherBatchLatencyP99Ms"] >= line["MatcherBatchLatencyP50Ms"]
    assert line["DeviceWindowsOccupancy"] == 10
    # capacity 0 in config = auto-size; the line reports the ACTUAL table
    assert line["DeviceWindowsCapacity"] == m.device_windows.capacity > 0
    assert line["DeviceWindowsEvictions"] == 0
    assert line["DeviceWindowsEvictionsPerInterval"] == 0
    # nothing evicted or restored yet: no maintenance dispatch, no operand
    assert line["DeviceWindowsMaintenanceSteps"] == 0
    assert line["DeviceWindowsMaintenanceElems"] == 0
    assert line["DeviceWindowsGrows"] == 0
    # the lines/sec window resets per snapshot
    line2 = _line(m)
    assert line2["MatcherLinesPerSec"] == 0


@pytest.mark.parametrize("profile_on", [False, True])
def test_profile_routes_registered_only_when_enabled(profile_on, monkeypatch):
    from banjax_tpu.httpapi import server as server_mod

    cfg = config_from_yaml_text(RULES_YAML)
    cfg.profile = profile_on
    cfg.standalone_testing = True

    class Holder:
        def get(self):
            return cfg

    from banjax_tpu.decisions.protected_paths import PasswordProtectedPaths

    deps = server_mod.ServerDeps(
        config_holder=Holder(),
        static_lists=StaticDecisionLists(cfg),
        dynamic_lists=DynamicDecisionLists(start_sweeper=False),
        protected_paths=PasswordProtectedPaths(cfg),
        regex_states=RegexRateLimitStates(),
        failed_challenge_states=FailedChallengeRateLimitStates(),
        banner=MockBanner(),
    )
    app = server_mod.build_app(deps)
    routes = {r.resource.canonical for r in app.router.routes()}
    assert ("/debug/pprof/profile" in routes) == profile_on
    assert ("/debug/pprof/threads" in routes) == profile_on
    assert ("/debug/jax/trace" in routes) == profile_on


def test_pprof_endpoints_respond():
    """Drive the profile endpoints through a real aiohttp test client."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from banjax_tpu.decisions.protected_paths import PasswordProtectedPaths
    from banjax_tpu.httpapi import server as server_mod

    cfg = config_from_yaml_text(RULES_YAML)
    cfg.profile = True
    cfg.standalone_testing = True

    class Holder:
        def get(self):
            return cfg

    deps = server_mod.ServerDeps(
        config_holder=Holder(),
        static_lists=StaticDecisionLists(cfg),
        dynamic_lists=DynamicDecisionLists(start_sweeper=False),
        protected_paths=PasswordProtectedPaths(cfg),
        regex_states=RegexRateLimitStates(),
        failed_challenge_states=FailedChallengeRateLimitStates(),
        banner=MockBanner(),
    )

    async def drive():
        app = server_mod.build_app(deps)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get("/debug/pprof/profile", params={"seconds": "0.1"})
            assert r.status == 200
            assert "cumulative" in await r.text()
            r = await client.get("/debug/pprof/threads")
            assert r.status == 200
            assert "thread" in await r.text()
        finally:
            await client.close()

    asyncio.run(drive())


def test_snapshot_window_reset_is_atomic_under_concurrency():
    """Regression for the snapshot race window: window counters used to
    be read and the eviction delta updated outside the stats lock, so a
    concurrent snapshot could double-count or lose an interval delta.
    Hammer record/snapshot from many threads and assert the deltas
    telescope exactly (conservation) and totals never regress."""
    import threading

    from banjax_tpu.obs.stats import MatcherStats

    class FakeWindows:
        """Minimal device_windows surface with a racing eviction count."""

        capacity = 64
        occupancy = 10
        grow_count = 0
        eviction_count = 0

        def __len__(self):
            return 10

    stats = MatcherStats()
    windows = FakeWindows()
    stop = threading.Event()
    snapshots = []
    snap_lock = threading.Lock()

    def recorder():
        while not stop.is_set():
            stats.record_batch(10, 0.001)
            stats.note_xfer(100, 50)
            windows.eviction_count += 1  # single mutator thread

    def snapshotter():
        while not stop.is_set():
            s = stats.snapshot(windows)
            with snap_lock:
                snapshots.append(s)

    threads = [threading.Thread(target=recorder)] + [
        threading.Thread(target=snapshotter) for _ in range(3)
    ]
    for t in threads:
        t.start()
    time.sleep(0.5)
    stop.set()
    for t in threads:
        t.join(10)

    final = stats.snapshot(windows)
    snapshots.append(final)
    # conservation: interval eviction deltas telescope to the final
    # absolute count with nothing lost or double-counted
    assert sum(
        s["DeviceWindowsEvictionsPerInterval"] for s in snapshots
    ) == final["DeviceWindowsEvictions"]
    assert final["MatcherLinesTotal"] == 10 * final["MatcherBatchesTotal"]
    assert final["MatcherH2dBytesTotal"] == 100 * final["MatcherBatchesTotal"]


def test_supervisor_keys_are_additive():
    """Multi-worker serving health keys appear only when a supervisor is
    passed (the reference schema stays untouched otherwise)."""
    import types

    sup = types.SimpleNamespace(n_workers=2, respawn_count=3)
    out = io.StringIO()
    write_metrics_line(
        out,
        DynamicDecisionLists(start_sweeper=False),
        RegexRateLimitStates(),
        FailedChallengeRateLimitStates(),
        None,
        sup,
    )
    line = json.loads(out.getvalue())
    assert set(line) == REFERENCE_KEYS | {
        "HttpWorkers", "HttpWorkerRespawns", "HttpFcDropped",
    }
    assert line["HttpWorkers"] == 2
    assert line["HttpWorkerRespawns"] == 3
    assert line["HttpFcDropped"] == 0  # python limiter has no drop counter
