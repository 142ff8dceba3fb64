"""Matcher runtime counters for production observability (VERDICT r1 weak
#8: a deployed instance must see the TPU subsystem's health, not just a
benchmark's).

MatcherStats is a thread-safe accumulator every Matcher carries; the
29-second metrics line (obs/metrics.py) snapshots it with ADDITIVE keys —
the reference's five keys keep their exact schema
(/root/reference/config.go:158-181).

Two consumers read these accumulators with different contracts:

  * `snapshot()` — the 29 s line's view: includes INTERVAL keys
    (lines/sec window, per-batch byte averages, eviction deltas) and
    resets them.  Read+reset is ONE atomic lock section (a scrape
    landing between a read and its reset used to lose or double-count
    the delta — tests/unit/test_observability.py hammers it now); the
    single-periodic-consumer assumption still applies to the VALUES
    (two competing periodic consumers would each see partial windows).
  * `peek()` — the Prometheus exposition's view (obs/exposition.py):
    monotone totals and point-in-time gauges only, never touching the
    window state, so scrapes at any cadence cannot steal the line's
    deltas.  Rate math belongs to the scraper.

Every key either view emits is declared in obs/registry.py — the
exposition-schema registry CI locks (test_exposition.py).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from banjax_tpu.obs.registry import Histogram, StageHistograms

_LATENCY_RING = 512  # recent batch latencies kept for the percentiles
_DEVICE_RING = 256   # recent device-stage latencies for the pipeline p99
# Floor of the breaker's derived latency budget.  The stage's wall is mostly
# the submitting thread's wait for the interpreter, so a batch of a few lines
# takes 0.1-0.5 s whenever another thread of the process computes (found on
# the chip: PERF.md, PR 41 and PR 50), and 3x a p99 of 20-50 ms is under that:
# the faster the stage, the sooner three such batches in a row sent every
# line to the CPU matcher.  A second is a tenth of the age at which a line is
# dropped as stale, and far under what a wedged device costs
_BUDGET_FLOOR_S = 1.0


def _r3(v):
    return None if v is None else round(v, 3)


class MatcherStats:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.lines_total = 0
        self.batches_total = 0
        self._latencies = [0.0] * _LATENCY_RING
        self._lat_n = 0
        self._window_lines = 0
        self._window_start = time.monotonic()
        self._last_evictions = 0
        # host<->device transfer accounting (the fusion-win witness: the
        # pipelined fused path must show the dense-bitmap re-upload gone)
        self.h2d_bytes_total = 0
        self.d2h_bytes_total = 0
        self._window_h2d = 0
        self._window_d2h = 0
        self._window_batches = 0
        # fixed-bucket batch-latency distribution for /metrics (registry
        # buckets; same observations as the p50/p99 ring)
        self.batch_latency_hist = Histogram()

    def record_batch(self, n_lines: int, elapsed_s: float) -> None:
        self.batch_latency_hist.observe(elapsed_s)
        with self._lock:
            self.lines_total += n_lines
            self.batches_total += 1
            self._latencies[self._lat_n % _LATENCY_RING] = elapsed_s
            self._lat_n += 1
            self._window_lines += n_lines
            self._window_batches += 1

    def note_xfer(self, h2d_bytes: int = 0, d2h_bytes: int = 0) -> None:
        """Bytes a device path moved across the host boundary (encoded
        input, dense bitmaps, sparse pulls).  Counted at the runner's choke
        points, not at every jnp.asarray — the point is comparability
        between the classic and fused paths, not a byte-perfect ledger."""
        with self._lock:
            self.h2d_bytes_total += int(h2d_bytes)
            self.d2h_bytes_total += int(d2h_bytes)
            self._window_h2d += int(h2d_bytes)
            self._window_d2h += int(d2h_bytes)

    def h2d_bytes_per_batch(self) -> float:
        """Lifetime average h2d bytes per recorded batch (bench/tests)."""
        with self._lock:
            return self.h2d_bytes_total / max(1, self.batches_total)

    def _percentiles_locked(self) -> Dict[str, object]:
        n = min(self._lat_n, _LATENCY_RING)
        lats = sorted(self._latencies[:n])
        return {
            "MatcherBatchLatencyP50Ms": (
                round(lats[n // 2] * 1e3, 3) if n else None
            ),
            "MatcherBatchLatencyP99Ms": (
                round(lats[min(n - 1, (n * 99) // 100)] * 1e3, 3) if n else None
            ),
        }

    @staticmethod
    def _derived(device_windows=None, matcher=None) -> Dict[str, object]:
        """Non-stats-owned keys (device windows, mesh, fused pipeline,
        breaker).  Reads foreign objects only — no stats lock, no resets
        — so both snapshot() and peek() share it."""
        out: Dict[str, object] = {}
        if device_windows is not None:
            out["DeviceWindowsOccupancy"] = device_windows.occupancy
            out["DeviceWindowsCapacity"] = device_windows.capacity
            # single read: an eviction landing between two reads must not
            # be dropped from the next interval's delta
            out["DeviceWindowsEvictions"] = device_windows.eviction_count
            # elems / evictions: single digits while the maintenance step
            # is O(evicted slots) (matcher/windows.py)
            out["DeviceWindowsMaintenanceSteps"] = getattr(
                device_windows, "maintenance_steps", 0
            )
            out["DeviceWindowsMaintenanceElems"] = getattr(
                device_windows, "maintenance_elems", 0
            )
            out["DeviceWindowsEvents"] = getattr(
                device_windows, "device_events", 0
            )
            out["DeviceWindowsGrows"] = getattr(device_windows, "grow_count", 0)
            out["DeviceWindowsTableBytes"] = getattr(
                device_windows, "table_bytes", 0
            )
            # which slot-assignment path is live: the native C manager
            # (native/slotmgr.c) or the Python dict+LRU fallback/oracle
            out["SlotMgrNative"] = bool(
                getattr(device_windows, "slotmgr_native", False)
            )
            # shadowed IPs = all IPs with live counters (evicted included —
            # spill keeps them; see matcher/windows.py)
            out["DeviceWindowsShadowedIps"] = len(device_windows)
            # mega-state tiering: admission-gate and warm-tier telemetry.
            # Gate keys emit whenever the windows object carries them (a
            # zero refusal count under flood IS the signal the gate is
            # off); warm keys only when a tier is attached, so untiered
            # deployments keep their exact line schema.
            if hasattr(device_windows, "slot_refusals"):
                out["SlotRefusals"] = device_windows.slot_refusals
                out["SketchAdmissions"] = device_windows.sketch_admissions
                out["SketchAdmissionFpRate"] = round(
                    device_windows.sketch_admission_fp_rate, 4
                )
            if hasattr(device_windows, "gate_derived_batches"):
                out["SubmitGateDerivedBatches"] = (
                    device_windows.gate_derived_batches
                )
                out["SubmitResolveSeconds"] = round(
                    getattr(matcher, "submit_resolve_s", 0.0), 6
                )
            if getattr(device_windows, "_warm", None) is not None:
                out["WarmTierSpills"] = device_windows.warm_spills
                out["WarmTierRefills"] = device_windows.warm_refills
                out["WarmTierDropped"] = device_windows.warm_dropped
                out["WarmTierOccupancy"] = device_windows.warm_occupancy
                out["WarmTierCapacity"] = device_windows.warm_capacity
                out["WarmTierProbes"] = device_windows.warm_probes
                out["WarmTierRecordReads"] = device_windows.warm_record_reads
                out["WarmTierBytesWritten"] = getattr(
                    device_windows, "warm_bytes_written", 0
                )
        if matcher is not None:
            mm = getattr(matcher, "_mesh_matcher", None)
            if mm is not None:
                out["MeshFusedBatches"] = mm.fused_batches
                out["MeshFallbackBatches"] = mm.fallback_batches
                # sharded submit/drain latency (parallel/mesh.py): dispatch
                # wall time vs the per-shard d2h pull + line-order merge
                out["MeshSubmitMsEwma"] = _r3(
                    getattr(mm, "submit_ms_ewma", None)
                )
                out["MeshMergeMsEwma"] = _r3(
                    getattr(mm, "merge_ms_ewma", None)
                )
                shard_ms = getattr(mm, "last_shard_merge_ms", None) or []
                out["MeshShardMergeMsMax"] = _r3(
                    max(shard_ms) if shard_ms else None
                )
            if getattr(matcher, "_prefilter", None) is not None:
                out["PrefilterActive"] = True
                out["PrefilterCandidates"] = getattr(
                    matcher._prefilter, "candidates_total", 0
                )
            banner = getattr(matcher, "banner", None)
            for key, attr in (
                ("RegexBanRecords", "regex_ban_records"),
                ("BannerBatches", "regex_ban_batches"),
            ):
                n = getattr(banner, attr, None)
                if n is not None:
                    out[key] = n
            for key, attr in (
                ("MatcherLongLines", "long_lines"),
                ("MatcherLongLineBytes", "long_line_bytes"),
            ):
                n = getattr(matcher, attr, None)
                if n is not None:
                    out[key] = n
            fw = getattr(matcher, "_fw_pipeline", None)
            if fw is not None:
                out["PipelineFusedBatches"] = fw.fused_batches
                out["PipelineFallbackBatches"] = fw.fallback_batches
                # fused chunks driven by the streaming pipeline, and its
                # overflow fallbacks — distinct from the sync-path
                # counters above
                out["PipelinedFusedChunks"] = getattr(
                    matcher, "pipelined_fused_chunks", 0
                )
                out["PipelinedFusedFallbacks"] = getattr(
                    matcher, "pipelined_fused_fallbacks", 0
                )
                out["EffectorReplaySeconds"] = round(
                    getattr(matcher, "effector_replay_s", 0.0), 6
                )
                # one program, one pull per chunk
                out["SingleKernelD2hBytesPerBatch"] = round(
                    fw.sk_d2h_bytes_total / max(1, fw.fused_batches), 1
                )
            # traffic introspection plane (obs/sketch.py): the sampled
            # summary — pull() self-throttles to its sampling interval,
            # so line snapshots and scrapes share one compact d2h
            ts = getattr(matcher, "traffic_sketch", None)
            if ts is not None:
                try:
                    s = ts.pull()
                    out["TrafficSketchLines"] = ts.lines_total
                    out["TrafficDistinctIpsEst"] = s[
                        "distinct_ips_estimate"
                    ]
                    out["TrafficHeavyHitterShare"] = s[
                        "heavy_hitter_share"
                    ]
                    out["TrafficSketchPullBytes"] = ts.pull_bytes_total
                    age = ts.pull_age_seconds()
                    out["TrafficSketchPullAgeSeconds"] = (
                        None if age is None else round(age, 3)
                    )
                except Exception:  # noqa: BLE001 — telemetry must not break metrics
                    pass
            # circuit breaker (resilience/breaker.py): the one place all
            # the ad-hoc fallback counters roll up for operators —
            # nonzero MatcherCpuFallbackBatches = batches served in
            # degraded (CPU reference) mode
            br = getattr(matcher, "breaker", None)
            if br is not None:
                out["MatcherBreakerState"] = br.state
                out["MatcherBreakerTrips"] = br.trip_count
                out["MatcherCpuFallbackBatches"] = getattr(
                    matcher, "fallback_batches", 0
                )
                # latency-budget breaches — distinct from device errors
                # in the trip accounting, so the ROADMAP's "derived
                # budget never validated" note has an observable counter
                out["MatcherBudgetTrips"] = getattr(
                    matcher, "budget_trips", 0
                )
        return out

    def snapshot(self, device_windows=None, matcher=None) -> Dict[str, object]:
        """Additive metrics-line keys; resets the interval windows.

        The foreign reads (_derived) happen OUTSIDE the stats lock; every
        read-then-reset of stats-owned window state — including the
        eviction-delta bookkeeping, which used to update `_last_evictions`
        unlocked — is one atomic section, so concurrent snapshot callers
        telescope cleanly instead of double-counting a delta."""
        derived = self._derived(device_windows, matcher)
        evictions = derived.get("DeviceWindowsEvictions")
        with self._lock:
            now = time.monotonic()
            dt = max(now - self._window_start, 1e-9)
            lps = self._window_lines / dt
            self._window_lines = 0
            self._window_start = now
            out: Dict[str, object] = {
                "MatcherLinesTotal": self.lines_total,
                "MatcherBatchesTotal": self.batches_total,
                "MatcherLinesPerSec": round(lps, 1),
                **self._percentiles_locked(),
                "MatcherH2dBytesTotal": self.h2d_bytes_total,
                "MatcherD2hBytesTotal": self.d2h_bytes_total,
                # per-batch averages over THIS reporting interval: the
                # operator-visible witness that fused+pipelined killed the
                # ~16 MB/batch dense re-upload
                "MatcherH2dBytesPerBatch": round(
                    self._window_h2d / max(1, self._window_batches), 1
                ),
                "MatcherD2hBytesPerBatch": round(
                    self._window_d2h / max(1, self._window_batches), 1
                ),
            }
            self._window_h2d = 0
            self._window_d2h = 0
            self._window_batches = 0
            if evictions is not None:
                # churn rate: evictions in THIS reporting interval —
                # degraded (spill/restore) mode visible per 29 s line, not
                # only as a lifetime total.  Interval deltas assume a
                # single periodic consumer (the metrics loop); /metrics
                # scrapes use peek() and never touch this.
                out["DeviceWindowsEvictionsPerInterval"] = (
                    evictions - self._last_evictions
                )
                self._last_evictions = evictions
        out.update(derived)
        return out

    def peek(self, device_windows=None, matcher=None) -> Dict[str, object]:
        """Non-destructive view for the Prometheus exposition: totals,
        percentiles and derived gauges only — no interval keys, no
        resets.  Safe at any scrape cadence alongside the 29 s line."""
        derived = self._derived(device_windows, matcher)
        with self._lock:
            out: Dict[str, object] = {
                "MatcherLinesTotal": self.lines_total,
                "MatcherBatchesTotal": self.batches_total,
                **self._percentiles_locked(),
                "MatcherH2dBytesTotal": self.h2d_bytes_total,
                "MatcherD2hBytesTotal": self.d2h_bytes_total,
            }
        out.update(derived)
        return out


class PipelineStats:
    """Thread-safe counters for the streaming pipeline scheduler
    (banjax_tpu/pipeline/scheduler.py).

    The accounting invariant the fault suite asserts: after a flush,
    admitted_lines == processed_lines + shed_lines + drain_error_lines —
    every admitted item is either processed (a result was produced for
    it, old_line included) or counted as shed; nothing is silent.  Kafka
    command messages routed through the admission buffer count in the
    SAME admitted/processed/shed totals (the invariant spans both
    producers); command_items/command_batches break the command share
    out for operators.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.admitted_lines = 0
        self.processed_lines = 0
        self.shed_lines = 0         # oldest-first overload shed
        self.drain_error_lines = 0  # drain-stage failures, counted as shed
        self.stale_dropped_lines = 0  # aged past cutoff inside the pipeline
        self.batches = 0
        self.fallback_batches = 0   # drained generically via consume_lines
        self.command_items = 0      # kafka commands drained in admission order
        self.command_batches = 0
        self.probe_ok = 0
        self.probe_failed = 0
        self._device_ring = [0.0] * _DEVICE_RING
        self._device_n = 0
        self._device_p99_ewma: Optional[float] = None
        # sharded encode-worker pool (scheduler._begin_state): interval
        # max of the slowest shard's wall time (the merge barrier waits
        # on it), EWMA utilization = sum(shard wall) / (workers * fan-out
        # wall) — 1.0 means perfectly balanced shards, low values mean
        # the fan-out is overhead-bound and encode_workers is too high
        self.encode_sharded_batches = 0
        self._encode_shard_ms_max = 0.0  # reset each snapshot
        self._encode_util_ewma: Optional[float] = None
        # per-shard-index busy fraction (EWMA of shard wall / fan-out
        # wall) and max/mean skew — the real multi-core imbalance signal
        # the scalar utilization EWMA hides (ROADMAP PR 4 follow-up);
        # skew: interval max for the 29 s line, EWMA for /metrics
        self._worker_busy_ewma: List[float] = []
        self._shard_skew_max = 0.0       # reset each snapshot
        self._shard_skew_ewma: Optional[float] = None
        # fixed-bucket distributions for /metrics (obs/registry.py)
        self.device_latency_hist = Histogram()
        self.stage_hists = StageHistograms()
        # tailer read -> effector commit, keyed by hop (local lines vs
        # fabric-forwarded ones) — banjax_e2e_latency_seconds{hop}
        self.e2e_hists = StageHistograms()

    def note_admitted(self, n: int) -> None:
        with self._lock:
            self.admitted_lines += n

    def note_processed(self, n: int) -> None:
        with self._lock:
            self.processed_lines += n

    def note_shed(self, n: int) -> None:
        with self._lock:
            self.shed_lines += n

    def note_drain_error(self, n: int) -> None:
        with self._lock:
            self.drain_error_lines += n

    def note_stale(self, n: int) -> None:
        with self._lock:
            self.stale_dropped_lines += n

    def note_batch(self, fallback: bool) -> None:
        with self._lock:
            self.batches += 1
            if fallback:
                self.fallback_batches += 1

    def note_commands(self, n: int) -> None:
        with self._lock:
            self.command_items += n
            self.command_batches += 1

    def note_encode_shards(self, shard_ms: List[float],
                           wall_ms: float) -> None:
        """One sharded encode fan-out's timing (scheduler._begin_state):
        per-shard wall times plus the fan-out's total wall."""
        n_shards = len(shard_ms)
        if not n_shards:
            return
        wall = max(wall_ms, 1e-9)
        mean = sum(shard_ms) / n_shards
        skew = (max(shard_ms) / mean) if mean > 0 else 1.0
        util = min(1.0, max(0.0, sum(shard_ms) / (wall * n_shards)))
        with self._lock:
            self.encode_sharded_batches += 1
            if max(shard_ms) > self._encode_shard_ms_max:
                self._encode_shard_ms_max = max(shard_ms)
            self._encode_util_ewma = (
                util if self._encode_util_ewma is None
                else self._encode_util_ewma
                + 0.3 * (util - self._encode_util_ewma)
            )
            if skew > self._shard_skew_max:
                self._shard_skew_max = skew
            self._shard_skew_ewma = (
                skew if self._shard_skew_ewma is None
                else self._shard_skew_ewma
                + 0.3 * (skew - self._shard_skew_ewma)
            )
            while len(self._worker_busy_ewma) < n_shards:
                self._worker_busy_ewma.append(0.0)
            for k, ms in enumerate(shard_ms):
                frac = min(1.0, ms / wall)
                prev = self._worker_busy_ewma[k]
                self._worker_busy_ewma[k] = (
                    frac if self.encode_sharded_batches == 1
                    else prev + 0.3 * (frac - prev)
                )

    def worker_busy_fractions(self) -> List[float]:
        """Per-shard-index EWMA busy fraction of the fan-out wall —
        /metrics gauge banjax_encode_worker_busy_fraction{worker=k}."""
        with self._lock:
            return [round(v, 3) for v in self._worker_busy_ewma]

    def note_probe(self, ok: bool) -> None:
        with self._lock:
            if ok:
                self.probe_ok += 1
            else:
                self.probe_failed += 1

    def observe_device(self, elapsed_s: float) -> None:
        """One device-stage (submit→collect) wall time; feeds the p99 the
        breaker-budget satellite derives `matcher_latency_budget_ms` from."""
        self.device_latency_hist.observe(elapsed_s)
        with self._lock:
            self._device_ring[self._device_n % _DEVICE_RING] = elapsed_s
            self._device_n += 1
            n = min(self._device_n, _DEVICE_RING)
            lats = sorted(self._device_ring[:n])
            p99 = lats[min(n - 1, (n * 99) // 100)]
            self._device_p99_ewma = (
                p99 if self._device_p99_ewma is None
                else self._device_p99_ewma + 0.2 * (p99 - self._device_p99_ewma)
            )

    def observe_stages(self, stage_ms: Dict[str, float]) -> None:
        """Per-stage wall times for one drained batch → the labeled
        banjax_stage_duration_seconds histogram (scheduler drain loop)."""
        for stage, ms in stage_ms.items():
            self.stage_hists.observe(stage, ms / 1e3)

    def observe_e2e(self, hop: str, seconds: float) -> None:
        """One batch's oldest tailer-read stamp -> effector commit
        (banjax_e2e_latency_seconds{hop}); recorded at drain completion
        by the scheduler when the batch carried any read stamp."""
        self.e2e_hists.observe(hop, max(0.0, seconds))

    def suggested_latency_budget_s(self) -> float:
        """Derived breaker budget: 3x the EWMA device p99, floored at
        `_BUDGET_FLOOR_S`.  0.0 until a p99 exists — the breaker treats 0
        as 'no budget', same as the unset config."""
        with self._lock:
            if self._device_p99_ewma is None:
                return 0.0
            return max(_BUDGET_FLOOR_S, 3.0 * self._device_p99_ewma)

    def _totals_locked(self) -> Dict[str, object]:
        return {
            "EncodeShardedBatches": self.encode_sharded_batches,
            "EncodeWorkerUtilization": (
                None if self._encode_util_ewma is None
                else round(self._encode_util_ewma, 3)
            ),
            "PipelineAdmittedLines": self.admitted_lines,
            "PipelineProcessedLines": self.processed_lines,
            "PipelineShedLines": self.shed_lines,
            "PipelineDrainErrorLines": self.drain_error_lines,
            "PipelineStaleDroppedLines": self.stale_dropped_lines,
            "PipelineBatches": self.batches,
            "PipelineFallbackBatches": self.fallback_batches,
            "PipelineCommandItems": self.command_items,
            "PipelineCommandBatches": self.command_batches,
            "PipelineProbeFailures": self.probe_failed,
            "PipelineDeviceP99Ms": (
                None if self._device_p99_ewma is None
                else round(self._device_p99_ewma * 1e3, 3)
            ),
        }

    def snapshot(self) -> Dict[str, object]:
        """29 s line view: totals plus the interval maxima, which reset
        here (read+reset is one atomic section)."""
        with self._lock:
            shard_max = self._encode_shard_ms_max
            self._encode_shard_ms_max = 0.0  # interval max, like a gauge
            skew_max = self._shard_skew_max
            self._shard_skew_max = 0.0
            out = self._totals_locked()
            out["EncodeShardMsMax"] = round(shard_max, 3)
            out["EncodeShardSkewMax"] = round(skew_max, 3)
            return out

    def peek(self) -> Dict[str, object]:
        """Prometheus view: totals and EWMAs only, no interval resets.
        Shard skew is the EWMA here (an interval max is meaningless
        across uncoordinated scrapers)."""
        with self._lock:
            out = self._totals_locked()
            out["EncodeShardSkewMax"] = (
                None if self._shard_skew_ewma is None
                else round(self._shard_skew_ewma, 3)
            )
            return out
