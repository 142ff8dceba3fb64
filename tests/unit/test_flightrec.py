"""Incident flight recorder (obs/flightrec.py): bundle layout and
atomicity, capture debounce, retention pruning, path-traversal guards
on the read surface, and the module-level trigger hook."""

import json
import os

import pytest

from banjax_tpu.obs import flightrec, provenance, trace
from banjax_tpu.obs.flightrec import FlightRecorder


@pytest.fixture(autouse=True)
def _clean_modules():
    yield
    flightrec.install(None)
    provenance.configure(enabled=True)
    trace.configure(enabled=False)


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _recorder(tmp_path, **kw):
    kw.setdefault("min_interval_s", 0.0)
    return FlightRecorder(str(tmp_path / "incidents"), **kw)


def test_bundle_layout_and_contents(tmp_path):
    provenance.configure(enabled=True, ring_size=64)
    provenance.record(provenance.SOURCE_KAFKA, "4.4.4.4", "NginxBlock",
                      rule="block_ip")
    tracer = trace.configure(enabled=True, ring_size=64)
    tid = tracer.new_trace()
    with tracer.span("drain", tid, parent=0):
        pass
    rec = _recorder(
        tmp_path,
        metrics_text_fn=lambda: "# HELP x y\n# TYPE x counter\nx 1\n",
        config_hash_fn=lambda: "abc123",
    )
    name = rec.notify("breaker-trip", "matcher-device")
    assert name is not None and name.startswith("incident-")
    bundle = tmp_path / "incidents" / name
    assert sorted(os.listdir(bundle)) == [
        "meta.json", "metrics.prom", "provenance.json", "trace.json",
        "traffic.json",
    ]
    # no traffic_fn installed: the section says so instead of vanishing
    traffic_doc = json.loads((bundle / "traffic.json").read_text())
    assert traffic_doc == {"enabled": False}
    trace_doc = json.loads((bundle / "trace.json").read_text())
    assert any(e.get("ph") == "X" for e in trace_doc["traceEvents"])
    prov_doc = json.loads((bundle / "provenance.json").read_text())
    assert prov_doc["records"][-1]["ip"] == "4.4.4.4"
    assert prov_doc["counters"]["kafka/NginxBlock"] == 1
    meta = json.loads((bundle / "meta.json").read_text())
    assert meta["reason"] == "breaker-trip"
    assert meta["detail"] == "matcher-device"
    assert meta["config_hash"] == "abc123"
    assert (bundle / "metrics.prom").read_text().endswith("x 1\n")
    # no stranded tmp dirs: publish is rename-atomic
    assert not [e for e in os.listdir(tmp_path / "incidents")
                if e.endswith(".tmp")]
    assert rec.incident_count == 1


def test_debounce_bounds_capture_rate(tmp_path):
    clock = Clock()
    rec = _recorder(tmp_path, min_interval_s=60.0, clock=clock)
    assert rec.notify("shed-burst") is not None
    clock.t += 30.0
    assert rec.notify("shed-burst") is None       # inside the interval
    clock.t += 31.0
    assert rec.notify("breaker-trip") is not None  # past it
    assert rec.incident_count == 2


def test_prune_keeps_newest(tmp_path):
    clock = Clock()
    rec = _recorder(tmp_path, keep=3, clock=clock)
    names = []
    for i in range(6):
        clock.t += 1
        names.append(rec.notify(f"r{i}"))
    listed = [e["name"] for e in rec.list_incidents()]
    assert len(listed) == 3
    assert set(listed) <= set(names[-3:]) | set(names)  # newest retained
    for stale in names[:3]:
        assert stale not in listed


def test_list_and_read_surface(tmp_path):
    rec = _recorder(tmp_path, metrics_text_fn=lambda: "m 1\n")
    name = rec.notify("slo-shed_ratio", "burn 50")
    entries = rec.list_incidents()
    assert entries[0]["name"] == name
    assert entries[0]["reason"] == "slo-shed_ratio"
    assert "meta.json" in entries[0]["files"]
    assert rec.read_file(name, "metrics.prom") == b"m 1\n"
    assert rec.read_file(name, "nope.json") is None
    # traversal attempts are refused, not resolved
    assert rec.read_file("../" + name, "meta.json") is None
    assert rec.read_file(name, "../../etc/passwd") is None
    assert rec.read_file("incident-evil/..", "meta.json") is None


def test_capture_failure_never_propagates(tmp_path):
    def boom():
        raise RuntimeError("render failed")

    rec = _recorder(tmp_path, metrics_text_fn=boom)
    name = rec.notify("breaker-trip")
    # the bundle still lands, with the failure noted in metrics.prom
    assert name is not None
    data = rec.read_file(name, "metrics.prom")
    assert b"capture failed" in data


def test_module_hook_noop_without_recorder(tmp_path):
    flightrec.install(None)
    assert flightrec.notify("breaker-trip") is None
    rec = _recorder(tmp_path)
    flightrec.install(rec)
    assert flightrec.notify("breaker-trip") is not None
    assert flightrec.installed() is rec


def test_bundle_traffic_section_from_sketch(tmp_path):
    """A recorder wired with a traffic_fn (cli passes the matcher's
    sketch snapshot) lands the flood view in traffic.json — heavy
    hitters, cardinality and rule pressure as of the incident."""
    import numpy as np

    from banjax_tpu.obs.sketch import TrafficSketch, hash_ip

    sk = TrafficSketch(["r0"], width=1024, pull_seconds=3600.0)
    sk.note_assignments(["6.6.6.6"])
    sk.update(np.full(32, hash_ip("6.6.6.6"), dtype=np.uint32), 32)
    sk.note_rule_events([0, 0, 0])
    rec = _recorder(tmp_path, traffic_fn=sk.incident_snapshot)
    name = rec.notify("shed-burst", "flood")
    assert name is not None
    doc = json.loads(
        (tmp_path / "incidents" / name / "traffic.json").read_text()
    )
    assert doc["enabled"] is True
    assert doc["top"][0]["ip"] == "6.6.6.6"
    assert doc["top"][0]["est_count"] >= 32
    assert doc["rule_pressure"] == [
        {"rule": "r0", "index": 0, "events": 3}
    ]
    # the incident pull is FORCED: fresh even under a long interval
    assert doc["lines_total"] == 32


def test_bundle_traffic_section_survives_a_failing_fn(tmp_path):
    rec = _recorder(tmp_path, traffic_fn=lambda: 1 / 0)
    name = rec.notify("breaker-trip")
    assert name is not None
    doc = json.loads(
        (tmp_path / "incidents" / name / "traffic.json").read_text()
    )
    assert doc["enabled"] is False and "error" in doc


def test_bundle_peers_tree_from_fleet_capture(tmp_path):
    """ISSUE 20: a cluster incident bundle grows a peers/<node_id>/
    tree with each ALIVE member's contribution, listed in meta.json
    and readable through the nested read surface."""
    rec = _recorder(
        tmp_path,
        metrics_text_fn=lambda: "m 1\n",
        fleet_capture_fn=lambda incident: {
            "w1": {"metrics.prom": "m 2\n",
                   "fabric.json": '{"enabled": true}'},
            "w2": {"error.txt": "capture failed: dead\n"},
        },
    )
    name = rec.notify("fabric-takeover", "w2 died")
    bundle = tmp_path / "incidents" / name
    assert (bundle / "peers" / "w1" / "metrics.prom").read_text() == "m 2\n"
    assert (bundle / "peers" / "w2" / "error.txt").read_text().startswith(
        "capture failed"
    )
    meta = json.loads((bundle / "meta.json").read_text())
    assert "peers/w1/metrics.prom" in meta["files"]
    assert "peers/w2/error.txt" in meta["files"]
    # nested read surface
    assert rec.read_file(name, "peers/w1/metrics.prom") == b"m 2\n"
    assert rec.read_file(name, "peers/nope/metrics.prom") is None
    # traversal through the nested form is refused, not resolved
    assert rec.read_file(name, "peers/../meta.json") is None
    assert rec.read_file(name, "peers/w1/../../meta.json") is None
    assert rec.read_file(name, "peers/w1/.hidden") is None


def test_bundle_fleet_capture_failure_never_propagates(tmp_path):
    def boom(incident):
        raise RuntimeError("fan-out exploded")

    rec = _recorder(tmp_path, fleet_capture_fn=boom)
    name = rec.notify("breaker-trip")
    assert name is not None  # the local bundle still lands
    bundle = tmp_path / "incidents" / name
    assert not (bundle / "peers").exists()


def test_bundle_fleet_capture_sanitizes_hostile_names(tmp_path):
    """Hostile node ids / file names from a compromised peer are
    basenamed into the bundle — nothing ever lands outside it, and
    dot-prefixed names are dropped."""
    rec = _recorder(
        tmp_path,
        fleet_capture_fn=lambda incident: {
            "../evil": {"x": "contained"},
            "w1": {"../../escape": "contained", "ok.txt": "yes",
                   ".hidden": "dropped"},
        },
    )
    name = rec.notify("chaos")
    bundle = tmp_path / "incidents" / name
    assert (bundle / "peers" / "w1" / "ok.txt").read_text() == "yes"
    # traversal components are stripped: the payloads land INSIDE the
    # bundle under their basenames, never beside/above it
    assert not (tmp_path / "incidents" / "evil").exists()
    assert not (tmp_path / "escape").exists()
    assert (bundle / "peers" / "evil" / "x").read_text() == "contained"
    assert (bundle / "peers" / "w1" / "escape").read_text() == "contained"
    assert not (bundle / "peers" / "w1" / ".hidden").exists()
    meta = json.loads((bundle / "meta.json").read_text())
    assert "peers/w1/ok.txt" in meta["files"]
    assert all(".." not in f for f in meta["files"])
