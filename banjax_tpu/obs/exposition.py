"""Prometheus text-format exposition for the /metrics route.

Pull-based exposition (Prometheus exposition format 0.0.4) over the
same accumulators the 29-second line snapshots — WITHOUT renaming the
legacy line or stealing its interval windows: every value here comes
from the non-destructive `peek()` accessors (obs/stats.py), monotone
totals and point-in-time gauges, so any number of scrapers can pull at
any cadence alongside the line's single periodic consumer.

Every family is declared in obs/registry.py (name, type, help); the
renderer walks the registry, so an undeclared family cannot be emitted
and a renamed one fails the schema test, not a dashboard.

`parse_text_format()` is the strict parser the tests (and operators
debugging a scrape) use: it validates name/label syntax, HELP/TYPE
placement, histogram bucket monotonicity and the `le="+Inf"` == count
invariant — stricter than Prometheus' own forgiving ingest, on purpose.
"""

from __future__ import annotations

import math
import re
import time
from typing import Dict, List, Optional, Tuple

from banjax_tpu.obs import registry
from banjax_tpu.obs.registry import (
    COUNTER,
    FAMILIES,
    GAUGE,
    HISTOGRAM,
    Histogram,
)
from banjax_tpu.resilience.breaker import CLOSED, HALF_OPEN, OPEN

_HEALTH_LEVELS = {"healthy": 0, "degraded": 1, "failed": 2, "unknown": 1}
_BREAKER_STATES = (CLOSED, OPEN, HALF_OPEN)


def _fmt(v) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, int):
        return str(v)
    f = float(v)
    if math.isinf(f):
        return "+Inf" if f > 0 else "-Inf"
    return repr(f)


def _esc(label_value: str) -> str:
    return (str(label_value).replace("\\", "\\\\")
            .replace("\n", "\\n").replace('"', '\\"'))


def _labels(pairs: Dict[str, object]) -> str:
    if not pairs:
        return ""
    inner = ",".join(f'{k}="{_esc(v)}"' for k, v in pairs.items())
    return "{" + inner + "}"


class _Writer:
    def __init__(self):
        self.lines: List[str] = []
        self._declared = set()

    def head(self, fam) -> None:
        if fam.prom in self._declared:
            return
        self._declared.add(fam.prom)
        self.lines.append(f"# HELP {fam.prom} {fam.help}")
        self.lines.append(f"# TYPE {fam.prom} {fam.kind}")

    def sample(self, fam, value, labels: Optional[dict] = None) -> None:
        self.head(fam)
        self.lines.append(f"{fam.prom}{_labels(labels or {})} {_fmt(value)}")

    def histogram(self, fam, hist: Histogram,
                  labels: Optional[dict] = None) -> None:
        self.head(fam)
        bounds, cum, total_sum, count = hist.snapshot()
        base = dict(labels or {})
        for b, c in zip(bounds, cum):
            self.lines.append(
                f"{fam.prom}_bucket{_labels({**base, 'le': _fmt(float(b))})} {c}"
            )
        self.lines.append(
            f"{fam.prom}_bucket{_labels({**base, 'le': '+Inf'})} {count}"
        )
        self.lines.append(f"{fam.prom}_sum{_labels(base)} {_fmt(total_sum)}")
        self.lines.append(f"{fam.prom}_count{_labels(base)} {count}")

    def text(self) -> str:
        return "\n".join(self.lines) + "\n"


def _thread_cpu_samples(w: "_Writer", thread_ids: Dict[str, list]) -> None:
    """banjax_thread_cpu_seconds_total{thread}: each thread's own CPU
    clock by its native id (the id Linux gives a thread's CPU clock: the
    tid's complement shifted by 3, `| 6` = per thread, scheduler's
    count), read here, at scrape time, never on the pipeline's path."""
    fam = registry.PROM_FAMILIES["banjax_thread_cpu_seconds_total"]
    for label, tids in thread_ids.items():
        seconds = None
        for tid in tids:
            try:
                cpu = time.clock_gettime((~tid << 3) | 6)
            except (OSError, OverflowError):
                continue
            seconds = (seconds or 0.0) + cpu
        if seconds is not None:
            w.sample(fam, round(seconds, 6), {"thread": label})


def render_prometheus(
    dynamic_lists,
    regex_states,
    failed_challenge_states,
    matcher=None,
    pipeline=None,
    health=None,
    supervisor=None,
    slo=None,
    flightrec=None,
    fabric=None,
) -> str:
    """Render the full /metrics payload.  Args mirror
    obs.metrics.write_metrics_line — same sources, non-destructive
    reads."""
    # line-key-shaped value map from the non-destructive accessors; the
    # registry maps line_key -> prom family for everything scalar
    values: Dict[str, object] = {}
    challenges, blocks = dynamic_lists.metrics()
    values["LenExpiringChallenges"] = challenges
    values["LenExpiringBlocks"] = blocks
    values["LenIpToRegexStates"] = len(regex_states)
    values["LenFailedChallengeStates"] = len(failed_challenge_states)
    if matcher is not None:
        values.update(matcher.stats.peek(
            getattr(matcher, "device_windows", None), matcher
        ))
    if pipeline is not None:
        values.update(pipeline.prom_snapshot())
    try:
        from banjax_tpu.ingest import kafka_wire

        values["KafkaSkippedBatches"] = kafka_wire.skipped_batch_count()
    except Exception:  # noqa: BLE001 — exposition must not require kafka
        values["KafkaSkippedBatches"] = 0
    if fabric is not None:
        values.update(fabric.peek())
    if supervisor is not None:
        values["HttpWorkers"] = supervisor.n_workers
        values["HttpWorkerRespawns"] = supervisor.respawn_count
        values["HttpFcDropped"] = getattr(failed_challenge_states, "dropped", 0)

    w = _Writer()
    breaker_state = values.pop("MatcherBreakerState", None)
    for fam in FAMILIES:
        if not fam.prom or fam.kind == HISTOGRAM or fam.labels:
            continue
        if fam.line_key and fam.line_key in values:
            v = values[fam.line_key]
            if v is not None:
                w.sample(fam, v)

    # breaker state: one-hot by state label so dashboards can alert on
    # `banjax_matcher_breaker_state{state="open"} == 1`
    if breaker_state is not None:
        fam = registry.PROM_FAMILIES["banjax_matcher_breaker_state"]
        for s in _BREAKER_STATES:
            w.sample(fam, 1 if breaker_state == s else 0, {"state": s})

    # fused overflows by cause (prom-only labeled counter; the unlabeled
    # banjax_pipelined_fused_fallbacks_total keeps its shape beside it)
    fw = getattr(matcher, "_fw_pipeline", None) if matcher else None
    if fw is not None:
        fam = registry.PROM_FAMILIES["banjax_fused_overflows_total"]
        for cause, v in fw.overflow_causes.items():
            w.sample(fam, v, {"cause": cause})
        fam = registry.PROM_FAMILIES["banjax_fused_event_feed_total"]
        for source, v in fw.event_feed.items():
            w.sample(fam, v, {"source": source})
        w.sample(registry.PROM_FAMILIES["banjax_fused_pairs_total"],
                 fw.pairs_total)
        w.sample(
            registry.PROM_FAMILIES["banjax_matcher_long_candidates_total"],
            fw.long_candidates)
        w.sample(registry.PROM_FAMILIES[
            "banjax_matcher_long_candidate_bytes_total"],
            fw.long_candidate_bytes)

    unfused = getattr(matcher, "unfused_batches", None) if matcher else None
    if unfused is not None:
        fam = registry.PROM_FAMILIES["banjax_matcher_unfused_batches_total"]
        for cause, v in unfused.items():
            w.sample(fam, v, {"cause": cause})

    # the encode stage's gate: shards by what gated them, and the
    # address strings made from spans since
    gated = getattr(matcher, "gate_shards", None) if matcher else None
    if gated is not None:
        fam = registry.PROM_FAMILIES["banjax_encode_gate_shards_total"]
        for path, v in gated.items():
            w.sample(fam, v, {"path": path})
        w.sample(registry.PROM_FAMILIES["banjax_gate_address_strings_total"],
                 matcher.gate_address_strings)

    # ban-log writes by file: with banjax_regex_ban_records_total,
    # records a write
    writes = getattr(
        getattr(matcher, "banner", None), "ban_log_writes", None
    ) if matcher else None
    if writes is not None:
        fam = registry.PROM_FAMILIES["banjax_ban_log_writes_total"]
        for target, v in writes.items():
            w.sample(fam, v, {"target": target})

    # the prefilter plan: its routes, and the factor bucket that hit
    # most rows of the last batch read back
    pf = getattr(matcher, "_prefilter", None) if matcher else None
    if pf is not None:
        from banjax_tpu.matcher.selectivity import hottest_bucket

        fam = registry.PROM_FAMILIES["banjax_plan_rules"]
        for route, v in pf.plan.routes().items():
            w.sample(fam, v, {"route": route})
        hot = hottest_bucket(pf.plan, pf.last_bucket_hits)
        w.sample(registry.PROM_FAMILIES["banjax_plan_hottest_bucket_share"],
                 round(hot[1], 6) if hot else 0)

    # what this start spent on its rules, labeled by how it got them
    rc = getattr(matcher, "rules_cache", None) if matcher else None
    if rc is not None:
        w.sample(registry.PROM_FAMILIES["banjax_rules_compile_seconds"],
                 round(rc.seconds, 6), {"source": rc.source})

    # the submit stage's address resolution: what the pass found, and
    # the keys it handed to each table (prom-only labeled counters)
    dw = getattr(matcher, "device_windows", None) if matcher else None
    if dw is not None and hasattr(dw, "site_events"):
        fam = registry.PROM_FAMILIES["banjax_window_events_total"]
        w.sample(fam, dw.site_events, {"scope": "site"})
        w.sample(fam, dw.device_events - dw.site_events, {"scope": "global"})
    if dw is not None and hasattr(dw, "maintenance_carried"):
        fam = registry.PROM_FAMILIES[
            "banjax_device_windows_maintenance_steps_by_carrier_total"]
        for carrier, v in dw.maintenance_carried.items():
            w.sample(fam, v, {"carrier": carrier})
    if dw is not None and hasattr(dw, "shadow_records"):
        fam = registry.PROM_FAMILIES["banjax_shadow_records_total"]
        for op, by_path in dw.shadow_records.items():
            for path, v in by_path.items():
                w.sample(fam, v, {"op": op, "path": path})
    if dw is not None and hasattr(dw, "resolve_outcomes"):
        fam = registry.PROM_FAMILIES["banjax_submit_resolve_addresses_total"]
        for outcome, v in dw.resolve_outcomes.items():
            w.sample(fam, v, {"outcome": outcome})
        fam = registry.PROM_FAMILIES["banjax_submit_resolve_probes_total"]
        for table, v in dw.resolve_probes.items():
            w.sample(fam, v, {"table": table})
    if dw is not None and hasattr(dw, "resolve_passes"):
        fam = registry.PROM_FAMILIES["banjax_submit_resolve_passes_total"]
        for form, v in dw.resolve_passes.items():
            w.sample(fam, v, {"form": form})
        w.sample(
            registry.PROM_FAMILIES["banjax_slot_eviction_scanned_slots_total"],
            dw.eviction_scanned_slots)

    if dw is not None and hasattr(dw, "lock_waits"):
        wait_fam = registry.PROM_FAMILIES[
            "banjax_windows_lock_wait_seconds_total"]
        n_fam = registry.PROM_FAMILIES["banjax_windows_lock_contended_total"]
        for stage, (seconds, n) in dw.lock_waits().items():
            w.sample(wait_fam, round(seconds, 6), {"stage": stage})
            w.sample(n_fam, n, {"stage": stage})

    # the submit stage from inside, the sizer's moves, what each
    # pipeline thread got of a core, and what the cyclic collector took
    # from all of them
    if pipeline is not None:
        wall_by_phase, cpu_s = pipeline.submit_phase_seconds()
        fam = registry.PROM_FAMILIES["banjax_submit_phase_seconds_total"]
        for phase, seconds in wall_by_phase.items():
            w.sample(fam, round(seconds, 6), {"phase": phase})
        w.sample(registry.PROM_FAMILIES["banjax_submit_cpu_seconds_total"],
                 round(cpu_s, 6))
        w.sample(registry.PROM_FAMILIES["banjax_submit_runtime_calls_total"],
                 pipeline.submit_runtime_calls())
        fam = registry.PROM_FAMILIES[
            "banjax_pipeline_batch_target_changes_total"]
        for direction, n in pipeline.batch_target_changes().items():
            w.sample(fam, n, {"direction": direction})
        _thread_cpu_samples(w, pipeline.thread_ids())
        heap = pipeline.collector_stats()
        for name, key in (
            ("banjax_gc_collections_total", "collections"),
            ("banjax_gc_pause_seconds_total", "pause_s"),
            ("banjax_gc_collected_objects_total", "collected"),
        ):
            fam = registry.PROM_FAMILIES[name]
            for generation, v in enumerate(heap[key]):
                w.sample(fam, round(v, 6), {"generation": str(generation)})
        w.sample(registry.PROM_FAMILIES["banjax_gc_frozen_objects"],
                 heap["frozen"])
        # per-worker encode busy fractions (prom-only labeled gauge)
        fracs = pipeline.stats.worker_busy_fractions()
        if fracs:
            fam = registry.PROM_FAMILIES["banjax_encode_worker_busy_fraction"]
            for k, frac in enumerate(fracs):
                w.sample(fam, frac, {"worker": str(k)})

    # traffic introspection: per-rule match-pressure counters from the
    # device sketch's last compact pull (obs/sketch.py) — only rules
    # with any recorded pressure emit, so a 1k-rule config doesn't pay
    # 1k lines per scrape while idle
    sketch = getattr(matcher, "traffic_sketch", None) if matcher else None
    if sketch is not None:
        fam = registry.PROM_FAMILIES["banjax_sketch_updates_total"]
        for path, v in sketch.updates_by_path.items():
            w.sample(fam, v, {"path": path})
        try:
            pressure = sketch.pull().get("rule_pressure", ())
        except Exception:  # noqa: BLE001 — telemetry must not break a scrape
            pressure = ()
        if pressure:
            fam = registry.PROM_FAMILIES["banjax_traffic_rule_pressure"]
            for row in sorted(pressure, key=lambda r: r["rule"]):
                w.sample(fam, row["events"], {"rule": row["rule"]})

    # decision provenance: per-(source, decision) insert totals from the
    # process ledger (obs/provenance.py) — the attribution counter family
    from banjax_tpu.obs import provenance as provenance_mod

    prov_counters = provenance_mod.get_ledger().counters()
    if prov_counters:
        fam = registry.PROM_FAMILIES["banjax_decision_inserts_total"]
        for (source, decision), v in sorted(prov_counters.items()):
            w.sample(fam, v, {"source": source, "decision": decision})

    # SLO burn rates + the one-hot breach gauge (obs/slo.py)
    if slo is not None:
        burn_fam = registry.PROM_FAMILIES["banjax_slo_burn_rate"]
        for slo_name, windows in sorted(slo.burn_rates().items()):
            for window, rate in sorted(windows.items()):
                w.sample(burn_fam, rate, {"slo": slo_name, "window": window})
        breach_fam = registry.PROM_FAMILIES["banjax_slo_breached"]
        for slo_name, hit in sorted(slo.breached().items()):
            w.sample(breach_fam, 1 if hit else 0, {"slo": slo_name})

    # incident flight recorder (obs/flightrec.py)
    if flightrec is not None:
        w.sample(
            registry.PROM_FAMILIES["banjax_flightrec_incidents_total"],
            flightrec.incident_count,
        )

    # adversarial scenario harness (banjax_tpu/scenarios/stats.py — a
    # leaf module): last-run rows per attack shape, rendered only when
    # this process actually ran scenarios
    try:
        from banjax_tpu.scenarios.stats import get_stats as _scen_stats

        scen = _scen_stats().prom_snapshot()
    except Exception:  # noqa: BLE001 — the harness must not break a scrape
        scen = None
    if scen is not None and scen["runs_total"]:
        w.sample(registry.PROM_FAMILIES["banjax_scenario_runs_total"],
                 scen["runs_total"])
        w.sample(
            registry.PROM_FAMILIES[
                "banjax_scenario_injected_episodes_total"
            ],
            scen["episodes_total"],
        )
        w.sample(
            registry.PROM_FAMILIES[
                "banjax_scenario_invariant_failures_total"
            ],
            scen["invariant_failures_total"],
        )
        per_gauge = {
            "lines_per_sec": "banjax_scenario_lines_per_sec",
            "shed_ratio": "banjax_scenario_shed_ratio",
            "precision": "banjax_scenario_ban_precision",
            "recall": "banjax_scenario_ban_recall",
            "slo_burn_peak": "banjax_scenario_slo_burn_peak",
        }
        for name, row in sorted(scen["scenarios"].items()):
            for field, fam_name in per_gauge.items():
                if field in row:
                    w.sample(registry.PROM_FAMILIES[fam_name],
                             row[field], {"scenario": name})

    # challenge plane (banjax_tpu/challenge/stats.py — a leaf module):
    # issuance / verification / bounded-failure-state families, rendered
    # only when this process touched the challenge plane
    try:
        from banjax_tpu.challenge.stats import get_stats as _challenge_stats

        chal = _challenge_stats()
        chal_snap = chal.prom_snapshot() if chal.active() else None
        chal_hist = chal.verify_batch_size
    except Exception:  # noqa: BLE001 — a leaf must not break a scrape
        chal_snap = None
        chal_hist = None
    if chal_snap is not None:
        w.sample(
            registry.PROM_FAMILIES["banjax_challenge_issued_total"],
            chal_snap["issued_total"],
        )
        fam = registry.PROM_FAMILIES["banjax_challenge_verifications_total"]
        for (result, path), v in sorted(chal_snap["verifications"].items()):
            w.sample(fam, v, {"result": result, "path": path})
        w.sample(
            registry.PROM_FAMILIES["banjax_challenge_failure_state_entries"],
            chal_snap["failure_state_entries"],
        )
        w.sample(
            registry.PROM_FAMILIES[
                "banjax_challenge_failure_evictions_total"
            ],
            chal_snap["failure_evictions_total"],
        )
        w.histogram(
            registry.PROM_FAMILIES["banjax_challenge_verify_batch_size"],
            chal_hist,
        )

    # compiled serving fast path (httpapi/serve_stats.py — a leaf
    # module): per-tier hits, per-reason misses, table gauges; rendered
    # only when this process consulted the fast path / attached a table
    try:
        from banjax_tpu.httpapi.serve_stats import get_stats as _serve_stats

        serve = _serve_stats()
        serve_snap = serve.prom_snapshot() if serve.active() else None
    except Exception:  # noqa: BLE001 — a leaf must not break a scrape
        serve_snap = None
    if serve_snap is not None:
        fam = registry.PROM_FAMILIES["banjax_serve_fastpath_hits_total"]
        for tier, v in sorted(serve_snap["hits"].items()):
            w.sample(fam, v, {"tier": tier})
        fam = registry.PROM_FAMILIES["banjax_serve_fastpath_misses_total"]
        for reason, v in sorted(serve_snap["misses"].items()):
            w.sample(fam, v, {"reason": reason})
        w.sample(
            registry.PROM_FAMILIES["banjax_serve_fastpath_faults_total"],
            serve_snap["faults_total"],
        )
        w.sample(
            registry.PROM_FAMILIES["banjax_serve_fastpath_table_entries"],
            serve_snap["table_entries"],
        )
        w.sample(
            registry.PROM_FAMILIES[
                "banjax_serve_fastpath_table_dropped_total"
            ],
            serve_snap["table_dropped_total"],
        )
        w.sample(
            registry.PROM_FAMILIES[
                "banjax_serve_fastpath_table_session_entries"
            ],
            serve_snap["table_session_entries"],
        )
        w.sample(
            registry.PROM_FAMILIES[
                "banjax_serve_fastpath_mirror_errors_total"
            ],
            serve_snap["mirror_errors_total"],
        )

    # kernel-edge ban batching (effectors/ipset_stats.py — a leaf
    # module): batch sends, routed failures, queue pressure
    try:
        from banjax_tpu.effectors.ipset_stats import get_stats as _ipset_stats

        ipset = _ipset_stats()
        ipset_snap = ipset.prom_snapshot() if ipset.active() else None
    except Exception:  # noqa: BLE001 — a leaf must not break a scrape
        ipset_snap = None
    if ipset_snap is not None:
        w.sample(
            registry.PROM_FAMILIES["banjax_ipset_batch_sends_total"],
            ipset_snap["batch_sends_total"],
        )
        w.sample(
            registry.PROM_FAMILIES["banjax_ipset_batch_entries_total"],
            ipset_snap["batch_entries_total"],
        )
        fam = registry.PROM_FAMILIES["banjax_ipset_errors_total"]
        for path, v in sorted(ipset_snap["errors"].items()):
            w.sample(fam, v, {"path": path})
        w.sample(
            registry.PROM_FAMILIES["banjax_ipset_fallback_total"],
            ipset_snap["fallback_total"],
        )
        w.sample(
            registry.PROM_FAMILIES["banjax_ipset_queue_shed_total"],
            ipset_snap["queue_shed_total"],
        )
        w.sample(
            registry.PROM_FAMILIES["banjax_ipset_queue_depth"],
            ipset_snap["queue_depth"],
        )

    # multi-host fabric: per-peer liveness gauge + takeover duration
    # histogram (banjax_tpu/fabric/stats.py; scalar totals merged above)
    if fabric is not None:
        peers = fabric.peers_snapshot()
        if peers:
            fam = registry.PROM_FAMILIES["banjax_fabric_peer_up"]
            for pid, up in sorted(peers.items()):
                w.sample(fam, 1 if up else 0, {"peer": pid})
        w.histogram(
            registry.PROM_FAMILIES["banjax_fabric_takeover_duration_seconds"],
            fabric.takeover_duration,
        )
        states = fabric.member_states_snapshot()
        if states:
            fam = registry.PROM_FAMILIES["banjax_fabric_membership_state"]
            enc = {"alive": 0, "suspect": 1, "dead": 2, "left": 3}
            for pid, state in sorted(states.items()):
                w.sample(fam, enc.get(state, 2), {"peer": pid})
        w.histogram(
            registry.PROM_FAMILIES[
                "banjax_fabric_membership_detection_seconds"
            ],
            fabric.detection_time,
        )
        frames = fabric.frames_snapshot()
        if frames:
            fam = registry.PROM_FAMILIES["banjax_fabric_frames_total"]
            for (version, transport), n in sorted(frames.items()):
                w.sample(fam, n,
                         {"version": version, "transport": transport})
        w.histogram(
            registry.PROM_FAMILIES["banjax_fabric_frame_bytes"],
            fabric.frame_bytes,
        )
        w.histogram(
            registry.PROM_FAMILIES["banjax_fabric_ack_rtt_seconds"],
            fabric.ack_rtt,
        )
        # gossip-piggybacked fleet health bits (obs/fleet.py encoding)
        peer_health = fabric.peer_health_snapshot()
        if peer_health:
            fam = registry.PROM_FAMILIES["banjax_fabric_peer_health"]
            for nid, bits in sorted(peer_health.items()):
                w.sample(fam, bits, {"node": nid})

    # component health: aggregate + one labeled gauge per component
    if health is not None:
        snap = health.snapshot()
        fam = registry.PROM_FAMILIES["banjax_health_status"]
        w.sample(fam, _HEALTH_LEVELS.get(snap["status"], 1))
        comp_fam = registry.PROM_FAMILIES["banjax_health_component_status"]
        for name, comp in sorted(snap["components"].items()):
            w.sample(comp_fam, _HEALTH_LEVELS.get(comp["status"], 1),
                     {"component": name})

    # histograms
    if matcher is not None:
        w.histogram(
            registry.PROM_FAMILIES["banjax_batch_latency_seconds"],
            matcher.stats.batch_latency_hist,
        )
    if pipeline is not None:
        w.histogram(
            registry.PROM_FAMILIES["banjax_device_stage_latency_seconds"],
            pipeline.stats.device_latency_hist,
        )
        stage_fam = registry.PROM_FAMILIES["banjax_stage_duration_seconds"]
        for stage, hist in pipeline.stats.stage_hists.items():
            w.histogram(stage_fam, hist, {"stage": stage})
        # tailer read -> effector commit, by hop (local vs fabric)
        e2e_fam = registry.PROM_FAMILIES["banjax_e2e_latency_seconds"]
        for hop, hist in pipeline.stats.e2e_hists.items():
            w.histogram(e2e_fam, hist, {"hop": hop})
    return w.text()


# ---------------------------------------------------------------------------
# strict text-format parser (tests + scrape debugging)
# ---------------------------------------------------------------------------

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?"
    r" (?P<value>\S+)(?: (?P<ts>-?\d+))?$"
)
_LABEL_RE = re.compile(
    r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"(?:,|$)'
)


class ExpositionError(ValueError):
    pass


def _family_of(sample_name: str, types: Dict[str, str]) -> str:
    """Map a sample name to its family (histogram samples use the
    _bucket/_sum/_count suffixes)."""
    for suffix in ("_bucket", "_sum", "_count"):
        if sample_name.endswith(suffix):
            base = sample_name[: -len(suffix)]
            if types.get(base) == HISTOGRAM:
                return base
    return sample_name


def parse_text_format(text: str) -> Dict[str, dict]:
    """Parse + validate Prometheus text format strictly.

    Returns {family: {"type", "help", "samples": [(name, labels, value)]}}.
    Raises ExpositionError on: missing trailing newline, samples without
    a preceding TYPE, bad metric/label syntax, unparsable values,
    histogram buckets that are non-monotone / missing +Inf / +Inf !=
    count, or a family declared twice.
    """
    if not text.endswith("\n"):
        raise ExpositionError("exposition must end with a newline")
    helps: Dict[str, str] = {}
    types: Dict[str, str] = {}
    fams: Dict[str, dict] = {}
    for ln, raw in enumerate(text.split("\n")[:-1], 1):
        if not raw:
            continue
        if raw.startswith("# HELP "):
            rest = raw[len("# HELP "):]
            name, _, help_text = rest.partition(" ")
            if not _NAME_RE.match(name):
                raise ExpositionError(f"line {ln}: bad HELP name {name!r}")
            if name in helps:
                raise ExpositionError(f"line {ln}: duplicate HELP {name}")
            helps[name] = help_text
            continue
        if raw.startswith("# TYPE "):
            rest = raw[len("# TYPE "):]
            name, _, kind = rest.partition(" ")
            if not _NAME_RE.match(name):
                raise ExpositionError(f"line {ln}: bad TYPE name {name!r}")
            if kind not in (COUNTER, GAUGE, HISTOGRAM, "summary", "untyped"):
                raise ExpositionError(f"line {ln}: bad TYPE kind {kind!r}")
            if name in types:
                raise ExpositionError(f"line {ln}: duplicate TYPE {name}")
            types[name] = kind
            fams[name] = {"type": kind, "help": helps.get(name, ""),
                          "samples": []}
            continue
        if raw.startswith("#"):
            continue  # plain comment
        m = _SAMPLE_RE.match(raw)
        if not m:
            raise ExpositionError(f"line {ln}: unparsable sample {raw!r}")
        name = m.group("name")
        labels: Dict[str, str] = {}
        label_text = m.group("labels")
        if label_text:
            pos = 0
            while pos < len(label_text):
                lm = _LABEL_RE.match(label_text, pos)
                if lm is None:
                    raise ExpositionError(
                        f"line {ln}: bad label syntax {label_text!r}"
                    )
                labels[lm.group(1)] = (
                    lm.group(2).replace('\\"', '"')
                    .replace("\\n", "\n").replace("\\\\", "\\")
                )
                pos = lm.end()
        vtext = m.group("value")
        try:
            value = float(vtext) if vtext not in ("+Inf", "-Inf", "NaN") else (
                math.inf if vtext == "+Inf"
                else (-math.inf if vtext == "-Inf" else math.nan)
            )
        except ValueError:
            raise ExpositionError(
                f"line {ln}: unparsable value {vtext!r}"
            ) from None
        family = _family_of(name, types)
        if family not in fams:
            raise ExpositionError(
                f"line {ln}: sample {name!r} precedes its TYPE declaration"
            )
        fams[family]["samples"].append((name, labels, value))

    # histogram invariants, per label set
    for family, ent in fams.items():
        if ent["type"] != HISTOGRAM:
            if ent["type"] == COUNTER:
                for name, labels, value in ent["samples"]:
                    if value < 0:
                        raise ExpositionError(
                            f"counter {name} negative: {value}"
                        )
            continue
        by_labelset: Dict[tuple, dict] = {}
        for name, labels, value in ent["samples"]:
            key = tuple(sorted(
                (k, v) for k, v in labels.items() if k != "le"
            ))
            slot = by_labelset.setdefault(
                key, {"buckets": [], "sum": None, "count": None}
            )
            if name.endswith("_bucket"):
                if "le" not in labels:
                    raise ExpositionError(f"{name}: bucket without le label")
                le = labels["le"]
                bound = math.inf if le == "+Inf" else float(le)
                slot["buckets"].append((bound, value))
            elif name.endswith("_sum"):
                slot["sum"] = value
            elif name.endswith("_count"):
                slot["count"] = value
        for key, slot in by_labelset.items():
            buckets = slot["buckets"]
            if not buckets or buckets[-1][0] != math.inf:
                raise ExpositionError(
                    f"{family}{dict(key)}: missing le=+Inf bucket"
                )
            bounds = [b for b, _ in buckets]
            if bounds != sorted(bounds):
                raise ExpositionError(
                    f"{family}{dict(key)}: bucket bounds out of order"
                )
            counts = [c for _, c in buckets]
            if any(b > a for a, b in zip(counts[1:], counts)):
                raise ExpositionError(
                    f"{family}{dict(key)}: bucket counts not monotone"
                )
            if slot["count"] is None or slot["sum"] is None:
                raise ExpositionError(
                    f"{family}{dict(key)}: missing _sum/_count"
                )
            if counts[-1] != slot["count"]:
                raise ExpositionError(
                    f"{family}{dict(key)}: +Inf bucket {counts[-1]} != "
                    f"count {slot['count']}"
                )
    return fams
