"""The single exposition-schema registry.

Every key the 29-second metrics line can emit and every Prometheus
family `/metrics` can expose is declared HERE — name, type, help — so a
renamed counter fails CI (tests/unit/test_exposition.py asserts real
snapshots against this table, and scripts/check_metrics_docs.py
cross-checks the README's documented metrics table) instead of silently
breaking dashboards.

Two namespaces share one declaration:

  * `line_key` — the additive CamelCase key on the legacy 29 s JSON
    line (obs/metrics.py).  The reference's five keys keep their exact
    bytes (REFERENCE_LINE_KEYS); everything else is additive.
  * `prom` — the `banjax_*` family `/metrics` exposes
    (obs/exposition.py).  Interval-window keys (lines/sec, per-interval
    deltas) are line-only: Prometheus computes rates server-side from
    the monotone totals, and exposing the resetting window would make
    scrapes steal the 29 s line's deltas.

Histograms (fixed buckets, cumulative) live here too so the recorder
(obs/stats.py, pipeline/scheduler.py) and the renderer agree on bucket
bounds by construction.
"""

from __future__ import annotations

import bisect
import dataclasses
import threading
from typing import Dict, List, Optional, Tuple

# counter: monotone total; gauge: point-in-time value; histogram:
# fixed-bucket cumulative distribution (prom-only)
COUNTER = "counter"
GAUGE = "gauge"
HISTOGRAM = "histogram"

# the reference's exact five keys (config.go:158-181) — byte-identical,
# asserted by tests/unit/test_exposition.py
REFERENCE_LINE_KEYS = (
    "Time",
    "LenExpiringChallenges",
    "LenExpiringBlocks",
    "LenIpToRegexStates",
    "LenFailedChallengeStates",
)

# fixed latency buckets (seconds) shared by every duration histogram:
# sub-ms host stages through multi-second wedged-device tails
LATENCY_BUCKETS_S = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)

# fixed size buckets (bytes) for wire-frame histograms: a single small
# control frame through a maximally coalesced fabric_frame_max_bytes blob
FRAME_BYTES_BUCKETS = (
    256.0, 1024.0, 4096.0, 16384.0, 65536.0,
    262144.0, 1048576.0, 4194304.0,
)


@dataclasses.dataclass(frozen=True)
class Family:
    """One declared metric family.  `line_key` and/or `prom` may be
    empty — a family can live on one surface only."""

    kind: str
    help: str
    line_key: str = ""
    prom: str = ""
    labels: Tuple[str, ...] = ()


FAMILIES: List[Family] = [
    # ---- reference line keys (gauges; Time is the line's timestamp) ----
    Family(GAUGE, "metrics line timestamp (reference format)",
           line_key="Time"),
    Family(GAUGE, "expiring challenge decisions held",
           line_key="LenExpiringChallenges",
           prom="banjax_expiring_challenges"),
    Family(GAUGE, "expiring block decisions held",
           line_key="LenExpiringBlocks", prom="banjax_expiring_blocks"),
    Family(GAUGE, "per-IP regex rate-limit states held",
           line_key="LenIpToRegexStates",
           prom="banjax_ip_to_regex_states"),
    Family(GAUGE, "failed-challenge rate-limit states held",
           line_key="LenFailedChallengeStates",
           prom="banjax_failed_challenge_states"),
    # ---- matcher core ----
    Family(COUNTER, "log lines consumed by the matcher",
           line_key="MatcherLinesTotal", prom="banjax_matcher_lines_total"),
    Family(COUNTER, "matcher batches consumed",
           line_key="MatcherBatchesTotal",
           prom="banjax_matcher_batches_total"),
    Family(GAUGE, "lines/sec over the last reporting interval (line-only; "
           "Prometheus rates banjax_matcher_lines_total instead)",
           line_key="MatcherLinesPerSec"),
    Family(GAUGE, "p50 batch latency (ms) over the recent-latency ring",
           line_key="MatcherBatchLatencyP50Ms"),
    Family(GAUGE, "p99 batch latency (ms) over the recent-latency ring",
           line_key="MatcherBatchLatencyP99Ms"),
    Family(COUNTER, "host->device bytes moved by the matcher",
           line_key="MatcherH2dBytesTotal",
           prom="banjax_matcher_h2d_bytes_total"),
    Family(COUNTER, "device->host bytes moved by the matcher",
           line_key="MatcherD2hBytesTotal",
           prom="banjax_matcher_d2h_bytes_total"),
    Family(GAUGE, "h2d bytes per batch over this interval (the fused-path "
           "dense-reupload witness)", line_key="MatcherH2dBytesPerBatch"),
    Family(GAUGE, "d2h bytes per batch over this interval",
           line_key="MatcherD2hBytesPerBatch"),
    # ---- device windows ----
    Family(GAUGE, "device window slots occupied",
           line_key="DeviceWindowsOccupancy",
           prom="banjax_device_windows_occupancy"),
    Family(GAUGE, "device window slot capacity",
           line_key="DeviceWindowsCapacity",
           prom="banjax_device_windows_capacity"),
    Family(COUNTER, "device window LRU evictions (spill to host shadow)",
           line_key="DeviceWindowsEvictions",
           prom="banjax_device_windows_evictions_total"),
    Family(GAUGE, "evictions in this reporting interval (line-only delta)",
           line_key="DeviceWindowsEvictionsPerInterval"),
    Family(COUNTER, "device window maintenance runs (queued evictions "
           "and restores drained into the device state), whatever carried "
           "them",
           line_key="DeviceWindowsMaintenanceSteps",
           prom="banjax_device_windows_maintenance_steps_total"),
    Family(COUNTER, "device window maintenance runs by what carried "
           "their evict and restore steps to the device: fused (two "
           "operands of a fused chunk's program — no dispatch and no "
           "transfer of their own) or own (dispatches of their own: the "
           "classic apply, a run past one chunk's operands); the two sum "
           "to banjax_device_windows_maintenance_steps_total",
           prom="banjax_device_windows_maintenance_steps_by_carrier_total",
           labels=("carrier",)),
    Family(COUNTER, "int32 elements handed to the device by maintenance "
           "dispatches, padding included (divide by evictions: single "
           "digits while the step is O(evicted slots))",
           line_key="DeviceWindowsMaintenanceElems",
           prom="banjax_device_windows_maintenance_elems_total"),
    Family(COUNTER, "window events ((line, rule) transitions) committed "
           "by device applies, fused or classic",
           line_key="DeviceWindowsEvents",
           prom="banjax_device_windows_events_total"),
    Family(COUNTER, "device window capacity grows",
           line_key="DeviceWindowsGrows",
           prom="banjax_device_windows_grows_total"),
    Family(GAUGE, "device bytes of the window table: 16 a (slot, rule) "
           "and 5 a slot (10.5 GB at 65,536 slots x 10,000 rules)",
           line_key="DeviceWindowsTableBytes",
           prom="banjax_device_windows_table_bytes"),
    Family(GAUGE, "1 when the native C slot manager is live, 0 on the "
           "Python dict path", line_key="SlotMgrNative",
           prom="banjax_slotmgr_native"),
    Family(GAUGE, "IPs with live window counters (evicted/spilled included)",
           line_key="DeviceWindowsShadowedIps",
           prom="banjax_device_windows_shadowed_ips"),
    # ---- mega-state tiering (README "Mega-state tiering") ----
    Family(COUNTER, "rows refused a device window slot by the sketch "
           "admission gate (matched and rate-limited statelessly on the "
           "host path — counted, never dropped)",
           line_key="SlotRefusals", prom="banjax_slot_refusals_total"),
    Family(COUNTER, "unseen IPs admitted to a slot because the count-min "
           "estimate reached the admission threshold",
           line_key="SketchAdmissions",
           prom="banjax_sketch_admissions_total"),
    Family(GAUGE, "fraction of sketch-admitted slots whose hot tenure "
           "ended with no window state (wasted admissions = collision "
           "noise; sizes traffic_sketch_width)",
           line_key="SketchAdmissionFpRate",
           prom="banjax_sketch_admission_fp_rate"),
    Family(COUNTER, "evicted slot window vectors spilled into the warm "
           "tier (native/shmstate.c)",
           line_key="WarmTierSpills", prom="banjax_warm_tier_spills_total"),
    Family(COUNTER, "warm-tier entries refilled into a device slot on "
           "re-admission",
           line_key="WarmTierRefills",
           prom="banjax_warm_tier_refills_total"),
    Family(COUNTER, "spills the warm tier refused (full of unexpired "
           "entries; state falls back losslessly to the host shadow — "
           "the raise-warm_tier_capacity signal)",
           line_key="WarmTierDropped",
           prom="banjax_warm_tier_dropped_total"),
    Family(GAUGE, "warm-tier entries occupied",
           line_key="WarmTierOccupancy", prom="banjax_warm_tier_occupancy"),
    Family(GAUGE, "warm-tier entry capacity",
           line_key="WarmTierCapacity", prom="banjax_warm_tier_capacity"),
    Family(COUNTER, "keys looked up in the warm tier (put, take, peek and "
           "each key of a batched membership check)",
           line_key="WarmTierProbes", prom="banjax_warm_tier_probes_total"),
    Family(COUNTER, "warm-tier records whose memory a lookup read; the "
           "rest stopped in the tag index (divide by probes: near refills "
           "over misses while absent keys touch no record)",
           line_key="WarmTierRecordReads",
           prom="banjax_warm_tier_record_reads_total"),
    Family(COUNTER, "bytes the warm tier's puts wrote: 128 a record + 24 "
           "a counter + 8 a further 256-byte block (divide by spills: the "
           "mean record, whatever the ruleset's size)",
           line_key="WarmTierBytesWritten",
           prom="banjax_warm_tier_bytes_written_total"),
    # ---- the submit stage's address resolution (matcher/windows.py) ----
    Family(COUNTER, "distinct client addresses of submitted batches by "
           "what the one resolving pass found: hit (slot assigned), "
           "shadow or warm (state elsewhere, re-entering), unseen "
           "(admitted with no state), refused (by the slot-admission gate)",
           prom="banjax_submit_resolve_addresses_total",
           labels=("outcome",)),
    Family(COUNTER, "keys handed to the slot table and to the warm tier's "
           "membership probe by the submit stage (per batch: distinct "
           "addresses, and misses not in the shadow, once each)",
           prom="banjax_submit_resolve_probes_total", labels=("table",)),
    Family(COUNTER, "passes over a batch's distinct addresses (the submit "
           "stage's resolve, a chunk's or the classic replay's slot "
           "call), by the form the "
           "addresses came in and were worked on: spans (the parse blob's "
           "bytes, merged by bytes; no string made of an address) or "
           "strings (encoded for the pass; the dict path's pass)",
           prom="banjax_submit_resolve_passes_total", labels=("form",)),
    Family(COUNTER, "slots the native slot manager's placements read to "
           "find their eviction victims, the members of every run of the "
           "kept (last_used, slot) order they sorted included: about the "
           "victims and one batch's leftover run a batch — a scan of the "
           "table would read its capacity a batch",
           prom="banjax_slot_eviction_scanned_slots_total"),
    Family(COUNTER, "batches whose slot-admission verdict needed no sketch "
           "estimate (threshold 1: a rule bans on the first hit; or no "
           "unseen address)",
           line_key="SubmitGateDerivedBatches",
           prom="banjax_submit_gate_derived_batches_total"),
    Family(COUNTER, "host wall seconds inside the submit stage's "
           "submit-resolve spans (one pass over a batch's distinct "
           "addresses: encode, probes, gate verdict, placement, spills "
           "and refills)",
           line_key="SubmitResolveSeconds",
           prom="banjax_submit_resolve_seconds_total"),
    # ---- the submit stage from inside (obs/trace.py LapClock) ----
    Family(COUNTER, "wall seconds of the pipeline's submit stage (from "
           "the scheduler's start of a batch's device stage to the end of "
           "pipeline_submit) by phase — pass (one pass over the batch's "
           "distinct addresses), sketch (the rows' address hashes by one "
           "gather and the candidate log's append: what the traffic "
           "sketch takes outside the dispatch), operands (the fused program's inputs), "
           "maintenance (evictions and restores in front of the "
           "dispatch), dispatch (the fused program's call), other.  The "
           "six sum to the submit part of "
           "banjax_stage_duration_seconds_sum{stage=\"device\"}",
           prom="banjax_submit_phase_seconds_total", labels=("phase",)),
    Family(COUNTER, "CPU seconds the submitting thread ran inside the "
           "submit stage (its own CPU clock, read where a batch's stage "
           "starts and ends); the phases' wall less this is the time the "
           "thread waited — for the interpreter, a lock, the device, a "
           "core",
           prom="banjax_submit_cpu_seconds_total"),
    Family(COUNTER, "calls into the device runtime made by the submitting "
           "thread inside the submit stage: every dispatch of a program "
           "and every explicit host-to-device transfer, counted where it "
           "is made (one a fused chunk; each gives the interpreter up and "
           "queues for it again)",
           prom="banjax_submit_runtime_calls_total"),
    Family(COUNTER, "seconds the pipeline's stages waited for the device "
           "windows' lock when it was held (an acquire that finds it free "
           "is not timed), by the stage the waiting thread runs: submit "
           "(pipeline-device), drain (pipeline-drain)",
           prom="banjax_windows_lock_wait_seconds_total",
           labels=("stage",)),
    Family(COUNTER, "acquires of the device windows' lock that found it "
           "held, by the stage the waiting thread runs; the wait seconds "
           "over this count is the mean wait of one contention (many "
           "short waits, or a few long ones behind a maintenance step)",
           prom="banjax_windows_lock_contended_total", labels=("stage",)),
    Family(COUNTER, "CPU seconds of the pipeline's threads, read from the "
           "threads' own clocks at scrape time: pipeline-encode, "
           "pipeline-encode-worker (the pool as one), pipeline-device, "
           "pipeline-drain",
           prom="banjax_thread_cpu_seconds_total", labels=("thread",)),
    # ---- the cyclic collector under the pipeline (pipeline/heap.py) ----
    Family(COUNTER, "collections the cyclic garbage collector ran while "
           "the pipeline was up, by generation (2 = a full pass over "
           "everything not frozen)",
           prom="banjax_gc_collections_total", labels=("generation",)),
    Family(COUNTER, "seconds those collections took, by generation: every "
           "thread of the process is stopped for them, so their sum over "
           "a stretch of time is the share of it nothing ran",
           prom="banjax_gc_pause_seconds_total", labels=("generation",)),
    Family(COUNTER, "objects those collections freed (garbage that only "
           "the collector could free: reference cycles), by generation",
           prom="banjax_gc_collected_objects_total",
           labels=("generation",)),
    Family(GAUGE, "objects in the collector's permanent generation: the "
           "start-up heap (JAX, the rules, the device programs), frozen "
           "out of the full passes' reach once the matcher is built and "
           "again once programs have stopped being built; 0 = not frozen",
           prom="banjax_gc_frozen_objects"),
    # ---- mesh ----
    Family(COUNTER, "sharded-mesh batches served by the fused two-stage path",
           line_key="MeshFusedBatches", prom="banjax_mesh_fused_batches_total"),
    Family(COUNTER, "sharded-mesh batches that fell back single-stage",
           line_key="MeshFallbackBatches",
           prom="banjax_mesh_fallback_batches_total"),
    Family(GAUGE, "EWMA mesh submit wall time (ms)",
           line_key="MeshSubmitMsEwma", prom="banjax_mesh_submit_ms_ewma"),
    Family(GAUGE, "EWMA mesh d2h merge wall time (ms)",
           line_key="MeshMergeMsEwma", prom="banjax_mesh_merge_ms_ewma"),
    Family(GAUGE, "slowest shard's d2h pull in the last merge (ms)",
           line_key="MeshShardMergeMsMax",
           prom="banjax_mesh_shard_merge_ms_max"),
    Family(GAUGE, "1 when the two-stage literal prefilter is active",
           line_key="PrefilterActive", prom="banjax_prefilter_active"),
    Family(COUNTER, "lines stage 1's factor gate passed on to stage 2 "
           "(the rows the full automaton scanned; divide by lines: the "
           "candidate rate)",
           line_key="PrefilterCandidates",
           prom="banjax_prefilter_candidates_total"),
    Family(GAUGE, "seconds this start spent on its rules' compiled forms, "
           "by whether they were compiled or loaded from beside the "
           "compile cache (matcher/rulecache.py)",
           prom="banjax_rules_compile_seconds", labels=("source",)),
    # ---- fused matcher+windows ----
    Family(COUNTER, "sync-path fused matcher+windows batches",
           line_key="PipelineFusedBatches",
           prom="banjax_fused_batches_total"),
    Family(COUNTER, "fallback batches (fused overflow / pipeline generic "
           "drain)", line_key="PipelineFallbackBatches",
           prom="banjax_fused_fallback_batches_total"),
    Family(COUNTER, "fused chunks drained through the streaming pipeline",
           line_key="PipelinedFusedChunks",
           prom="banjax_pipelined_fused_chunks_total"),
    Family(COUNTER, "pipelined fused chunks replayed classically "
           "(overflow)",
           line_key="PipelinedFusedFallbacks",
           prom="banjax_pipelined_fused_fallbacks_total"),
    Family(COUNTER, "fused dispatches that committed nothing and were "
           "replayed classically, by what overflowed: the chunk's own "
           "candidates, (row, rule) pairs or window events, or chain (gated "
           "by an overflowing predecessor); and long_rows: batches cut into "
           "smaller chunks (nothing replayed) because a chunk held more "
           "lines over the short width than its long operand has room for",
           prom="banjax_fused_overflows_total", labels=("cause",)),
    Family(COUNTER, "lines over the short width (matcher_max_line_len) "
           "that the fused program's long operand can decide: ASCII, at "
           "most 8,192 bytes of request string; counted where a batch is "
           "encoded (divide by lines: 3 % of a real access log)",
           line_key="MatcherLongLines",
           prom="banjax_matcher_long_lines_total"),
    Family(COUNTER, "request-string bytes of the lines "
           "banjax_matcher_long_lines_total counts",
           line_key="MatcherLongLineBytes",
           prom="banjax_matcher_long_line_bytes_total"),
    Family(COUNTER, "long lines stage 1's gate passed on to stage 2 (the "
           "rows the second long launch scanned)",
           prom="banjax_matcher_long_candidates_total"),
    Family(COUNTER, "request-string bytes of the lines "
           "banjax_matcher_long_candidates_total counts",
           prom="banjax_matcher_long_candidate_bytes_total"),
    Family(COUNTER, "batches that went the classic way, whole, for one "
           "line's sake, by cause: non_ascii (a byte over 0x7F), "
           "line_length (a line past 8,192 bytes); no fused dispatch, so "
           "banjax_pipelined_fused_fallbacks_total does not see them",
           prom="banjax_matcher_unfused_batches_total", labels=("cause",)),
    Family(COUNTER, "encode shards (an unsharded batch is one) by what "
           "gated their lines: native (one call into C over the parse's "
           "columns: candidate rows, first-appearance tables of addresses "
           "and hosts, per-row columns) or python (the per-line loop: no "
           "native library, a line with a newline in it); counted where "
           "the encode thread merges the shards",
           prom="banjax_encode_gate_shards_total", labels=("path",)),
    Family(COUNTER, "address strings made from a gated batch's byte "
           "spans, whoever asked (the exceeded rows' lines at the drain, "
           "a deferred row's patch, the allowlist, the dict path); the "
           "hot path reads the spans and makes none",
           prom="banjax_gate_address_strings_total"),
    Family(COUNTER, "window events committed by fused programs, by where "
           "the program took the event from: a (row, rule) pair of the "
           "filtered rules, or a set bit of an always-column (their sum is "
           "the fused part of banjax_device_windows_events_total)",
           prom="banjax_fused_event_feed_total", labels=("source",)),
    Family(COUNTER, "(row, rule) pairs the fused programs counted after "
           "the site mask (flag n_pairs of every dispatch read, overflowed "
           "ones included; divide by lines: against the pair capacity of "
           "250 a thousand rows)",
           prom="banjax_fused_pairs_total"),
    Family(GAUGE, "rules the prefilter plan runs, by route: always (no "
           "factor to filter on), decided (stage 1 decides an anchored "
           "literal alone), promoted (has a factor and runs whole in stage "
           "1 all the same: a gate of four bytes or fewer in front of an "
           "automaton of one word, `GET .* /`), filtered (behind a factor, "
           "in stage 2), host (not lowerable; host regex)",
           prom="banjax_plan_rules", labels=("route",)),
    Family(GAUGE, "share of rows (0..1) that the hottest factor bucket hit "
           "in the last batch read back; candidates overflow once the "
           "buckets together pass matcher_prefilter_cand_frac, and the log "
           "line of an overflow names this bucket's rules",
           prom="banjax_plan_hottest_bucket_share"),
    Family(COUNTER, "window events committed by device applies, by whether "
           "the rule belongs to one site or is global (their sum is "
           "banjax_device_windows_events_total)",
           prom="banjax_window_events_total", labels=("scope",)),
    Family(COUNTER, "what moved through the host shadow of the device "
           "window counters (matcher/windows.py), by operation — absorb "
           "(window events folded in), spill (records a placement's "
           "victims sent to the warm tier), refill (records returning "
           "addresses took back), restore (records whose counters "
           "re-entered the device) — and by the form that handled it: "
           "native (the slot-indexed C mirror) or dict (the Python form; 0 "
           "wherever the native libraries loaded)",
           prom="banjax_shadow_records_total", labels=("op", "path")),
    Family(COUNTER, "host wall seconds inside the drain's effector-replay "
           "spans (event decode, shadow absorb, Banner replay of committed "
           "fused chunks)",
           line_key="EffectorReplaySeconds",
           prom="banjax_effector_replay_seconds_total"),
    Family(COUNTER, "ban-log records the regex rate limiter wrote",
           line_key="RegexBanRecords",
           prom="banjax_regex_ban_records_total"),
    Family(COUNTER, "batches of regex bans the banner applied: the exceeded "
           "window events of one applied chunk, or one record from the host "
           "window pass (effectors/banner.py apply_regex_bans)",
           line_key="BannerBatches", prom="banjax_banner_batches_total"),
    Family(COUNTER, "writes of a ban-log file, each one write and one flush "
           "of every line a batch or a single-record call had for that file "
           "(main, or temp for hosts under disable_logging); "
           "banjax_regex_ban_records_total over this is records a write",
           prom="banjax_ban_log_writes_total", labels=("target",)),
    # ---- single-kernel fused path (kernels/fused_match_window.py) ----
    Family(GAUGE, "d2h bytes per committed single-kernel chunk (the "
           "one-pull witness: flags + pairs + events in ONE buffer)",
           line_key="SingleKernelD2hBytesPerBatch",
           prom="banjax_single_kernel_d2h_bytes_per_batch"),
    # ---- breaker / degraded mode ----
    Family(GAUGE, "circuit breaker state (one-hot by state label)",
           line_key="MatcherBreakerState",
           prom="banjax_matcher_breaker_state", labels=("state",)),
    Family(COUNTER, "circuit breaker trips",
           line_key="MatcherBreakerTrips",
           prom="banjax_matcher_breaker_trips_total"),
    Family(COUNTER, "batches served by the CPU reference matcher (degraded)",
           line_key="MatcherCpuFallbackBatches",
           prom="banjax_matcher_cpu_fallback_batches_total"),
    Family(COUNTER, "matcher latency-budget breaches counted as breaker "
           "failures (validates the derived budget)",
           line_key="MatcherBudgetTrips",
           prom="banjax_matcher_budget_trips_total"),
    # ---- decision provenance / SLO / flight recorder ----
    Family(COUNTER, "decision insertions recorded by the provenance "
           "ledger (obs/provenance.py; /decisions/explain)",
           prom="banjax_decision_inserts_total",
           labels=("source", "decision")),
    Family(GAUGE, "SLO error-budget burn rate over the labeled window "
           "(1.0 = consuming the budget exactly at the sustainable rate)",
           prom="banjax_slo_burn_rate", labels=("slo", "window")),
    Family(GAUGE, "1 when the SLO burns >= 1.0 on every evaluated window "
           "(one-hot by slo label)",
           prom="banjax_slo_breached", labels=("slo",)),
    Family(COUNTER, "incident bundles captured by the flight recorder "
           "(obs/flightrec.py; /debug/incidents)",
           prom="banjax_flightrec_incidents_total"),
    # ---- adversarial scenario harness (banjax_tpu/scenarios/) ----
    Family(COUNTER, "scenario-harness runs completed in this process "
           "(the chaos soak)",
           prom="banjax_scenario_runs_total"),
    Family(COUNTER, "chaos failpoint episodes injected across scenario "
           "runs", prom="banjax_scenario_injected_episodes_total"),
    Family(COUNTER, "scenario invariant failures (accounting, leaked "
           "turns/pins, benign-SLO, bundle-per-episode)",
           prom="banjax_scenario_invariant_failures_total"),
    Family(GAUGE, "last run's end-to-end lines/sec for the labeled "
           "attack shape", prom="banjax_scenario_lines_per_sec",
           labels=("scenario",)),
    Family(GAUGE, "last run's (shed + drain-error) per admitted line "
           "for the labeled shape", prom="banjax_scenario_shed_ratio",
           labels=("scenario",)),
    Family(GAUGE, "last run's ban precision vs the generator oracle",
           prom="banjax_scenario_ban_precision", labels=("scenario",)),
    Family(GAUGE, "last run's ban recall vs the generator oracle",
           prom="banjax_scenario_ban_recall", labels=("scenario",)),
    Family(GAUGE, "last run's peak SLO burn rate across all SLOs and "
           "windows", prom="banjax_scenario_slo_burn_peak",
           labels=("scenario",)),
    # ---- traffic introspection plane (obs/sketch.py; /traffic/top) ----
    Family(COUNTER, "log lines folded into the device traffic sketch "
           "(count-min + HLL + rule pressure)",
           line_key="TrafficSketchLines",
           prom="banjax_traffic_sketch_lines_total"),
    Family(GAUGE, "estimated distinct client IPs (HyperLogLog registers, "
           "as of the last sketch pull)",
           line_key="TrafficDistinctIpsEst",
           prom="banjax_traffic_distinct_ips_estimate"),
    Family(GAUGE, "top heavy hitter's estimated share of sketched lines "
           "(count-min point estimate / lines folded)",
           line_key="TrafficHeavyHitterShare",
           prom="banjax_traffic_heavy_hitter_share"),
    Family(COUNTER, "bytes pulled device->host by periodic sketch "
           "refreshes (compact pulls, never per batch)",
           line_key="TrafficSketchPullBytes",
           prom="banjax_traffic_sketch_pull_bytes_total"),
    Family(GAUGE, "age of the newest sketch pull (s)",
           line_key="TrafficSketchPullAgeSeconds",
           prom="banjax_traffic_sketch_pull_age_seconds"),
    Family(COUNTER, "fired (line, rule) window events folded into the "
           "sketch, per rule — which rules absorb the flood",
           prom="banjax_traffic_rule_pressure", labels=("rule",)),
    Family(COUNTER, "chunks folded into the device traffic sketch, by "
           "the dispatch that carried the fold: fused (the chunk's own "
           "match+window program) or standalone (a program of its own: "
           "what is not dispatched fused)",
           prom="banjax_sketch_updates_total", labels=("path",)),
    # ---- multi-host decision fabric (banjax_tpu/fabric/) ----
    Family(GAUGE, "1 when the labeled fabric peer is alive in this "
           "node's membership view, 0 after it is declared dead",
           prom="banjax_fabric_peer_up", labels=("peer",)),
    Family(COUNTER, "lines forwarded to an owning peer and acked",
           line_key="FabricForwardedLines",
           prom="banjax_fabric_forwarded_lines_total"),
    Family(COUNTER, "lines received over the wire from a fabric peer",
           line_key="FabricReceivedLines",
           prom="banjax_fabric_received_lines_total"),
    Family(COUNTER, "lines owned locally and submitted in-process",
           line_key="FabricLocalLines",
           prom="banjax_fabric_local_lines_total"),
    Family(COUNTER, "lines with no alive owner — counted shed, never "
           "silently lost (the fabric half of admitted == processed + "
           "shed)", line_key="FabricShedLines",
           prom="banjax_fabric_shed_lines_total"),
    Family(COUNTER, "journal lines replayed to takeover successors "
           "after a peer death",
           line_key="FabricReplayedLines",
           prom="banjax_fabric_replayed_lines_total"),
    Family(COUNTER, "decisions produced to the Kafka command topic for "
           "fabric-wide replication",
           line_key="FabricReplicatedDecisions",
           prom="banjax_fabric_replicated_decisions_total"),
    Family(COUNTER, "replication produce attempts that failed (retried "
           "once, then counted and dropped — the local decision holds)",
           line_key="FabricReplicationErrors",
           prom="banjax_fabric_replication_errors_total"),
    Family(COUNTER, "replicated commands suppressed by the (origin, seq) "
           "deduper — own-origin echoes and duplicate inserts",
           line_key="FabricDuplicatesSuppressed",
           prom="banjax_fabric_duplicate_suppressed_total"),
    Family(COUNTER, "replicated peer decisions applied to the local "
           "dynamic lists",
           line_key="FabricReplicatedApplied",
           prom="banjax_fabric_replicated_applied_total"),
    Family(COUNTER, "range takeovers completed after a peer death",
           line_key="FabricTakeovers",
           prom="banjax_fabric_takeovers_total"),
    Family(HISTOGRAM, "takeover duration: peer declared dead -> journal "
           "fully replayed (s)",
           prom="banjax_fabric_takeover_duration_seconds"),
    Family(GAUGE, "gossip membership state of the labeled peer in this "
           "node's view (0=alive 1=suspect 2=dead 3=left)",
           prom="banjax_fabric_membership_state", labels=("peer",)),
    Family(COUNTER, "alive -> suspect transitions observed (direct + "
           "indirect probes all failed, or a suspicion digest arrived)",
           line_key="FabricMembershipSuspects",
           prom="banjax_fabric_membership_suspects_total"),
    Family(COUNTER, "suspicions that expired into confirmed-dead "
           "(drives mark_dead -> journal-replay takeover)",
           line_key="FabricMembershipConfirmedDead",
           prom="banjax_fabric_membership_confirmed_dead_total"),
    Family(COUNTER, "suspicions refuted by liveness evidence or an "
           "incarnation-bumped ALIVE from the suspect itself",
           line_key="FabricMembershipRefuted",
           prom="banjax_fabric_membership_refuted_total"),
    Family(COUNTER, "members joined or revived in this node's view "
           "(gossip join announce, rejoin, refute-after-dead)",
           line_key="FabricMembershipJoined",
           prom="banjax_fabric_membership_joined_total"),
    Family(COUNTER, "graceful LEFT departures observed (journal cleared "
           "without replay — the leaver drained first)",
           line_key="FabricMembershipLeft",
           prom="banjax_fabric_membership_left_total"),
    Family(COUNTER, "bytes of dedicated gossip probe traffic sent "
           "(digest piggybacks on data-path acks ride free)",
           line_key="FabricGossipBytes",
           prom="banjax_fabric_gossip_bytes_total"),
    Family(HISTOGRAM, "failure-detection latency: last liveness evidence "
           "for a member -> its death confirmed in this node's view (s)",
           prom="banjax_fabric_membership_detection_seconds"),
    # ---- fabric wire v2 transport (fabric/peer.py LinePipe) ----
    Family(COUNTER, "data-path frames sent to peers, by negotiated wire "
           "version (v2 binary / json fallback) and transport (tcp / shm)",
           prom="banjax_fabric_frames_total",
           labels=("version", "transport")),
    Family(COUNTER, "total data-path frames sent (all versions/transports "
           "— the 29s-line scalar of banjax_fabric_frames_total)",
           line_key="FabricFramesSent"),
    Family(COUNTER, "total data-path frame bytes sent to peers",
           line_key="FabricFrameBytes"),
    Family(HISTOGRAM, "size of each data-path frame sent (bytes) — how "
           "well send-side coalescing packs routed groups",
           prom="banjax_fabric_frame_bytes"),
    Family(COUNTER, "data-path acks received from peers (frames retired "
           "from the sliding window)",
           line_key="FabricAcksReceived",
           prom="banjax_fabric_acks_total"),
    Family(GAUGE, "frames currently in flight across all peer windows "
           "(bounded by fabric_inflight_frames per peer)",
           line_key="FabricInflightFrames",
           prom="banjax_fabric_inflight_frames"),
    Family(HISTOGRAM, "frame send -> ack round trip (s) through the "
           "pipelined window",
           prom="banjax_fabric_ack_rtt_seconds"),
    Family(GAUGE, "worst unread-byte fraction across this node's shm "
           "peer rings (0 when no ring transport is attached)",
           line_key="FabricRingOccupancy",
           prom="banjax_fabric_ring_occupancy"),
    Family(COUNTER, "takeover-replay lines skipped because their "
           "pre-death owner is still alive (already processed once — "
           "replaying would double-count rate-limit hits)",
           line_key="FabricReplaySkippedLines",
           prom="banjax_fabric_replay_skipped_lines_total"),
    # ---- pipeline scheduler ----
    Family(COUNTER, "lines+commands admitted into the pipeline",
           line_key="PipelineAdmittedLines",
           prom="banjax_pipeline_admitted_lines_total"),
    Family(COUNTER, "lines+commands fully drained",
           line_key="PipelineProcessedLines",
           prom="banjax_pipeline_processed_lines_total"),
    Family(COUNTER, "lines shed oldest-first under overload",
           line_key="PipelineShedLines",
           prom="banjax_pipeline_shed_lines_total"),
    Family(COUNTER, "lines lost to drain-stage failures (counted, never "
           "silent)", line_key="PipelineDrainErrorLines",
           prom="banjax_pipeline_drain_error_lines_total"),
    Family(COUNTER, "lines dropped stale at effector drain (10 s cutoff)",
           line_key="PipelineStaleDroppedLines",
           prom="banjax_pipeline_stale_dropped_lines_total"),
    Family(COUNTER, "pipeline batches drained",
           line_key="PipelineBatches", prom="banjax_pipeline_batches_total"),
    Family(COUNTER, "kafka command messages drained in admission order",
           line_key="PipelineCommandItems",
           prom="banjax_pipeline_command_items_total"),
    Family(COUNTER, "kafka command batches drained",
           line_key="PipelineCommandBatches",
           prom="banjax_pipeline_command_batches_total"),
    Family(COUNTER, "synthetic idle-probe failures",
           line_key="PipelineProbeFailures",
           prom="banjax_pipeline_probe_failures_total"),
    Family(GAUGE, "EWMA p99 of the device stage (ms) — feeds the derived "
           "breaker budget", line_key="PipelineDeviceP99Ms"),
    Family(GAUGE, "adaptive batch-size target (power-of-two bucket)",
           line_key="PipelineBatchTarget",
           prom="banjax_pipeline_batch_target"),
    Family(COUNTER, "moves of the adaptive batch-size target, by "
           "direction: up (doubled) or down (halved: over the budget, or "
           "sent back by the efficiency guard)",
           prom="banjax_pipeline_batch_target_changes_total",
           labels=("direction",)),
    Family(GAUGE, "command-batch take bound",
           line_key="PipelineCommandBatchTarget",
           prom="banjax_pipeline_command_batch_target"),
    Family(GAUGE, "EWMA encode-stage wall per batch (ms)",
           line_key="PipelineStageEncodeEwmaMs"),
    Family(GAUGE, "EWMA device-stage wall per batch (ms)",
           line_key="PipelineStageDeviceEwmaMs"),
    Family(GAUGE, "EWMA drain-stage wall per batch (ms)",
           line_key="PipelineStageDrainEwmaMs"),
    Family(GAUGE, "lines waiting in the admission buffer",
           line_key="PipelineBufferedLines",
           prom="banjax_pipeline_buffered_lines"),
    Family(GAUGE, "batches in flight across the stage ring",
           line_key="PipelineInflightBatches",
           prom="banjax_pipeline_inflight_batches"),
    Family(GAUGE, "configured in-flight ring size",
           line_key="PipelineRingSize", prom="banjax_pipeline_ring_size"),
    # ---- encode worker pool ----
    Family(GAUGE, "configured encode worker count (0 = single-thread)",
           line_key="EncodeWorkers", prom="banjax_encode_workers"),
    Family(COUNTER, "admission batches encoded via the sharded worker pool",
           line_key="EncodeShardedBatches",
           prom="banjax_encode_sharded_batches_total"),
    Family(GAUGE, "slowest encode shard's wall (ms) this interval",
           line_key="EncodeShardMsMax"),
    Family(GAUGE, "EWMA encode-pool utilization (1.0 = perfectly balanced)",
           line_key="EncodeWorkerUtilization",
           prom="banjax_encode_worker_utilization"),
    Family(GAUGE, "worst shard skew (max/mean shard wall) this interval",
           line_key="EncodeShardSkewMax",
           prom="banjax_encode_shard_skew_max"),
    Family(GAUGE, "EWMA per-worker busy fraction of fan-out wall (prom-"
           "only; per-shard-index label)",
           prom="banjax_encode_worker_busy_fraction", labels=("worker",)),
    # ---- kafka / http workers / health ----
    Family(COUNTER, "kafka record batches skipped (undecodable codec)",
           line_key="KafkaSkippedBatches",
           prom="banjax_kafka_skipped_batches_total"),
    Family(GAUGE, "live SO_REUSEPORT http worker processes",
           line_key="HttpWorkers", prom="banjax_http_workers"),
    Family(COUNTER, "http workers respawned after a crash",
           line_key="HttpWorkerRespawns",
           prom="banjax_http_worker_respawns_total"),
    Family(COUNTER, "failed-challenge states dropped by the shm limiter",
           line_key="HttpFcDropped", prom="banjax_http_fc_dropped_total"),
    Family(GAUGE, "aggregate health (0 healthy / 1 degraded / 2 failed)",
           line_key="HealthStatus", prom="banjax_health_status"),
    Family(GAUGE, "per-component health (0 healthy / 1 degraded / 2 "
           "failed); Health_<name> on the line",
           prom="banjax_health_component_status", labels=("component",)),
    # ---- challenge plane (banjax_tpu/challenge/) ----
    Family(COUNTER, "challenge cookies issued (stateless signed issuance, "
           "sha-inv + password)",
           line_key="ChallengeIssued", prom="banjax_challenge_issued_total"),
    Family(COUNTER, "sha-inv PoW cookie verifications by outcome and "
           "verifying path (cpu = reference oracle, device = batched "
           "sha256 kernel)",
           prom="banjax_challenge_verifications_total",
           labels=("result", "path")),
    Family(COUNTER, "sha-inv PoW cookie verifications, all outcomes and "
           "paths (line-only scalar of the labeled prom family)",
           line_key="ChallengeVerifications"),
    Family(GAUGE, "exact per-IP failed-challenge entries held by the "
           "bounded state (LRU + sketch spill/refill tiers excluded)",
           line_key="ChallengeFailureStateEntries",
           prom="banjax_challenge_failure_state_entries"),
    Family(COUNTER, "failed-challenge entries evicted from the bounded "
           "state under challenger pressure — bounded memory, never "
           "silent", line_key="ChallengeFailureEvictions",
           prom="banjax_challenge_failure_evictions_total"),
    # ---- compiled serving fast path (httpapi/fastpath.py) ----
    Family(COUNTER, "/auth_request responses served from the decision-"
           "table byte templates, by decision tier",
           prom="banjax_serve_fastpath_hits_total", labels=("tier",)),
    Family(COUNTER, "fast-path consultations that fell through to the "
           "decision chain, by reason",
           prom="banjax_serve_fastpath_misses_total", labels=("reason",)),
    Family(COUNTER, "fast-path hits, all tiers (line-only scalar of the "
           "labeled prom family)", line_key="ServeFastpathHits"),
    Family(COUNTER, "fast-path misses, all reasons (line-only scalar of "
           "the labeled prom family)", line_key="ServeFastpathMisses"),
    Family(COUNTER, "fast-path lookup faults (armed failpoint, torn "
           "seqlock read budget, unexpected error) — every one fell "
           "open to the chain", line_key="ServeFastpathFaults",
           prom="banjax_serve_fastpath_faults_total"),
    Family(GAUGE, "live entries in the shared decision table",
           line_key="ServeTableEntries",
           prom="banjax_serve_fastpath_table_entries"),
    Family(COUNTER, "inserts refused by a full decision table (the IP "
           "stays chain-served; live decisions are never evicted)",
           line_key="ServeTableDropped",
           prom="banjax_serve_fastpath_table_dropped_total"),
    Family(GAUGE, "session-id entries mirrored as a count (cookie-"
           "bearing requests defer to the chain while nonzero)",
           prom="banjax_serve_fastpath_table_session_entries"),
    Family(COUNTER, "dynamic-list -> decision-table mirror write "
           "failures (the table degrades to misses, never authority)",
           line_key="ServeMirrorErrors",
           prom="banjax_serve_fastpath_mirror_errors_total"),
    # ---- kernel-edge ban batching (effectors/ipset_netlink.py) ----
    Family(COUNTER, "coalesced netlink sendmsg batches acked clean by "
           "the kernel", line_key="IpsetBatchSends",
           prom="banjax_ipset_batch_sends_total"),
    Family(COUNTER, "ipset entries carried by those batches",
           line_key="IpsetBatchEntries",
           prom="banjax_ipset_batch_entries_total"),
    Family(COUNTER, "kernel-edge ban failures by path (netlink send/"
           "nack vs subprocess shim) — counted and routed, never "
           "raised into the ban path",
           prom="banjax_ipset_errors_total", labels=("path",)),
    Family(COUNTER, "kernel-edge ban failures, all paths (line-only "
           "scalar of the labeled prom family)", line_key="IpsetErrors"),
    Family(COUNTER, "entries re-routed from netlink to the per-entry "
           "subprocess fallback (lossless)", line_key="IpsetFallbacks",
           prom="banjax_ipset_fallback_total"),
    Family(COUNTER, "oldest queued bans shed by a full netlink queue "
           "(bounded memory, never blocks the ban path)",
           line_key="IpsetQueueShed", prom="banjax_ipset_queue_shed_total"),
    Family(GAUGE, "bans waiting in the netlink batch queue",
           prom="banjax_ipset_queue_depth"),
    # ---- fleet observability plane (obs/fleet.py) ----
    Family(GAUGE, "gossip-piggybacked health bits of the labeled fleet "
           "node (bit 1 slo_breached, bit 2 breaker open, bit 4 breaker "
           "half-open; 0 = healthy)",
           prom="banjax_fabric_peer_health", labels=("node",)),
    Family(GAUGE, "1 when the labeled peer could not be reached by the "
           "last /metrics?fleet=1 fan-out (its samples come from the "
           "stale cache or are absent — partial-but-honest view)",
           prom="banjax_fleet_peer_unreachable", labels=("instance",)),
    Family(GAUGE, "age (s) of the labeled peer's snapshot in the merged "
           "fleet exposition (near zero for a live pull)",
           prom="banjax_fleet_peer_staleness_seconds",
           labels=("instance",)),
    Family(HISTOGRAM, "tailer read -> effector commit end-to-end latency "
           "(s), by hop (local = owned by the tailing node, fabric = "
           "forwarded to its owner over the wire)",
           prom="banjax_e2e_latency_seconds", labels=("hop",)),
    # ---- histograms (prom-only) ----
    Family(HISTOGRAM, "device verification batch size (candidate "
           "solutions per sha256 kernel dispatch)",
           prom="banjax_challenge_verify_batch_size"),
    Family(HISTOGRAM, "end-to-end matcher batch latency (s)",
           prom="banjax_batch_latency_seconds"),
    Family(HISTOGRAM, "device stage (submit->collect) latency (s)",
           prom="banjax_device_stage_latency_seconds"),
    Family(HISTOGRAM, "per-stage pipeline span duration (s)",
           prom="banjax_stage_duration_seconds", labels=("stage",)),
]

# dynamic line-key prefixes (one key per registered component)
DYNAMIC_LINE_PREFIXES = ("Health_",)

LINE_KEYS: Dict[str, Family] = {
    f.line_key: f for f in FAMILIES if f.line_key
}
PROM_FAMILIES: Dict[str, Family] = {f.prom: f for f in FAMILIES if f.prom}


def is_declared_line_key(key: str) -> bool:
    if key in LINE_KEYS:
        return True
    return any(key.startswith(p) for p in DYNAMIC_LINE_PREFIXES)


class Histogram:
    """Thread-safe fixed-bucket histogram (Prometheus cumulative
    semantics at render time; counts stored per-bucket here)."""

    __slots__ = ("bounds", "_counts", "_sum", "_count", "_lock")

    def __init__(self, bounds: Tuple[float, ...] = LATENCY_BUCKETS_S):
        self.bounds = tuple(bounds)
        self._counts = [0] * (len(self.bounds) + 1)  # +1 for +Inf
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        idx = bisect.bisect_left(self.bounds, value)
        with self._lock:
            self._counts[idx] += 1
            self._sum += value
            self._count += 1

    def snapshot(self) -> Tuple[Tuple[float, ...], List[int], float, int]:
        """(bounds, cumulative_counts incl. +Inf, sum, count)."""
        with self._lock:
            counts = list(self._counts)
            total, s = self._count, self._sum
        cum = []
        running = 0
        for c in counts:
            running += c
            cum.append(running)
        return self.bounds, cum, s, total


class StageHistograms:
    """A labeled histogram set keyed by stage name, created lazily so
    only stages that actually run appear in the exposition."""

    __slots__ = ("_hists", "_lock")

    def __init__(self):
        self._hists: Dict[str, Histogram] = {}
        self._lock = threading.Lock()

    def observe(self, stage: str, value_s: float) -> None:
        h = self._hists.get(stage)
        if h is None:
            with self._lock:
                h = self._hists.setdefault(stage, Histogram())
        h.observe(value_s)

    def items(self):
        with self._lock:
            return sorted(self._hists.items())
