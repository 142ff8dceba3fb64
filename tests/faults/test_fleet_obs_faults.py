"""Fleet observability under injected faults (ISSUE 20): an armed
``obs.fleet.pull`` degrades the federated scrape to partial-but-200
through the real /metrics route, an armed ``obs.fleet.capture`` turns a
peer's bundle tree into an error.txt while the local capture still
lands, and a dead owner mid-explain falls back to the local answer —
flagged, never a 500."""

import asyncio
import json

import pytest

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.protected_paths import PasswordProtectedPaths
from banjax_tpu.decisions.rate_limit import (
    FailedChallengeRateLimitStates,
    RegexRateLimitStates,
)
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.fabric.peer import PeerUnavailable
from banjax_tpu.httpapi import server as server_mod
from banjax_tpu.obs import provenance
from banjax_tpu.obs.exposition import parse_text_format
from banjax_tpu.obs.fleet import FleetScraper, capture_fleet
from banjax_tpu.obs.flightrec import FlightRecorder
from banjax_tpu.resilience import failpoints
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

LOCAL_TEXT = (
    "# HELP banjax_x_total t\n# TYPE banjax_x_total counter\n"
    "banjax_x_total 3\n"
)
PEER_TEXT = LOCAL_TEXT.replace(" 3", " 4")


@pytest.fixture(autouse=True)
def _disarm():
    yield
    failpoints.disarm()


class FakeFabricService:
    """owner_of/explain_remote/node_id — what the explain proxy uses."""

    def __init__(self, node_id, owner, remote_payload=None, fail=False):
        self.node_id = node_id
        self._remote_payload = remote_payload
        self._fail = fail
        svc = self

        class _Router:
            @staticmethod
            def owner_of(ip):
                return owner

        self.router = _Router()

    def explain_remote(self, owner, ip):
        if self._fail:
            raise PeerUnavailable(f"{owner} is down")
        return dict(self._remote_payload)


def _deps(cfg, fleet=None, fabric_service=None):
    class Holder:
        def get(self):
            return cfg

    health = HealthRegistry()
    health.register("tailer").ok()
    return server_mod.ServerDeps(
        config_holder=Holder(),
        static_lists=StaticDecisionLists(cfg),
        dynamic_lists=DynamicDecisionLists(start_sweeper=False),
        protected_paths=PasswordProtectedPaths(cfg),
        regex_states=RegexRateLimitStates(),
        failed_challenge_states=FailedChallengeRateLimitStates(),
        banner=MockBanner(),
        health=health,
        fleet_getter=(lambda: fleet),
        fabric_service_getter=(lambda: fabric_service),
    )


def _get(deps, path):
    from aiohttp.test_utils import TestClient, TestServer

    async def go():
        app = server_mod.build_app(deps, listen_host="127.0.0.1")
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get(path)
            return r.status, await r.text()
        finally:
            await client.close()

    return asyncio.run(go())


def test_metrics_fleet_armed_pull_stays_200_and_parseable():
    cfg = config_from_yaml_text(RULES_YAML)
    scraper = FleetScraper(
        "w0", lambda: LOCAL_TEXT,
        peers_fn=lambda: {"w1": lambda: PEER_TEXT},
    )
    failpoints.arm("obs.fleet.pull")  # every pull faults
    status, text = _get(_deps(cfg, fleet=scraper), "/metrics?fleet=1")
    assert status == 200
    parsed = parse_text_format(text)  # strictly parseable while degraded
    unreach = {
        labels["instance"]: v
        for _n, labels, v in
        parsed["banjax_fleet_peer_unreachable"]["samples"]
    }
    assert unreach == {"w0": 0, "w1": 1}
    assert failpoints.fired_count("obs.fleet.pull") >= 1


def test_metrics_fleet_404_when_scraper_absent():
    cfg = config_from_yaml_text(RULES_YAML)
    status, _ = _get(_deps(cfg, fleet=None), "/metrics?fleet=1")
    assert status == 404
    # the plain scrape keeps working regardless
    status, text = _get(_deps(cfg, fleet=None), "/metrics")
    assert status == 200
    parse_text_format(text)


def test_explain_proxy_dead_owner_falls_back_local_flagged():
    # the ledger is process-wide: start from an empty one, or a record an
    # earlier file of this worker left for 9.9.9.9 is the "local answer"
    provenance.configure(enabled=True)
    cfg = config_from_yaml_text(RULES_YAML)
    svc = FakeFabricService("w0", owner="w1", fail=True)
    status, text = _get(
        _deps(cfg, fabric_service=svc), "/decisions/explain?ip=9.9.9.9"
    )
    assert status == 200
    doc = json.loads(text)
    assert doc["node_id"] == "w0"
    assert doc["owner_unreachable"] == "w1"
    assert doc["records"] == []


def test_explain_proxy_live_owner_tagged_with_owning_node():
    cfg = config_from_yaml_text(RULES_YAML)
    remote = {
        "ip": "9.9.9.9", "ledger_enabled": True,
        "records": [["9.9.9.9", "NginxBlock"]], "active_decision": None,
        "node_id": "w1",
    }
    svc = FakeFabricService("w0", owner="w1", remote_payload=remote)
    status, text = _get(
        _deps(cfg, fabric_service=svc), "/decisions/explain?ip=9.9.9.9"
    )
    assert status == 200
    doc = json.loads(text)
    assert doc["owning_node"] == "w1"
    assert doc["proxied"] is True
    assert doc["records"] == [["9.9.9.9", "NginxBlock"]]


def test_explain_owned_locally_skips_the_proxy():
    cfg = config_from_yaml_text(RULES_YAML)
    svc = FakeFabricService("w0", owner="w0", fail=True)  # proxy would blow
    status, text = _get(
        _deps(cfg, fabric_service=svc), "/decisions/explain?ip=9.9.9.9"
    )
    assert status == 200
    doc = json.loads(text)
    assert doc["node_id"] == "w0"
    assert "owning_node" not in doc
    assert "owner_unreachable" not in doc


def test_capture_failpoint_yields_error_txt_local_bundle_lands(tmp_path):
    failpoints.arm("obs.fleet.capture")
    rec = FlightRecorder(
        str(tmp_path / "incidents"), min_interval_s=0.0,
        metrics_text_fn=lambda: LOCAL_TEXT,
        fleet_capture_fn=lambda incident: capture_fleet(
            incident,
            lambda: {"w1": lambda i: {"metrics.prom": PEER_TEXT}},
        ),
    )
    name = rec.notify("fabric-takeover", "drill")
    assert name is not None
    bundle = tmp_path / "incidents" / name
    # local capture landed whole; the faulted peer is an error.txt
    assert (bundle / "metrics.prom").read_text() == LOCAL_TEXT
    err = (bundle / "peers" / "w1" / "error.txt").read_text()
    assert "obs.fleet.capture" in err or "capture failed" in err
    assert failpoints.fired_count("obs.fleet.capture") == 1
