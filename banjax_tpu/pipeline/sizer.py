"""Adaptive batch sizing for the streaming pipeline.

The fixed `matcher_batch_lines` knob is the wrong control for a latency
budget: the right batch size depends on the attached backend, the
ruleset width, and the traffic mix, all of which the scheduler can only
observe at runtime.  AdaptiveBatchSizer picks the batch target from
observed per-stage timings instead:

  * batches are sized in power-of-two buckets (the same bucketing the
    matcher uses to bound jit recompiles — every bucket the sizer visits
    is a program the device has compiled before or will compile once);
  * per-stage (encode / device / drain) per-batch timings feed EWMAs;
    the per-batch TOTAL — the latency a line sees from admission to
    effector drain once queueing is subtracted — is compared against
    `pipeline_latency_budget_ms`.  The device stage's part is its
    service time (scheduler._collect: from the batch's submit, or its
    predecessor's collect if later, to its own collect), so that a
    device-bound pipeline with two batches in flight reads d a batch
    and not d or 2d by turns;
  * AIMD within the buckets: comfortably under budget (below half) the
    bucket doubles, over budget it halves.  Extrapolating a target
    directly from per-line cost looks cleverer but deadlocks in the
    small-bucket regime, where fixed dispatch overhead dominates the
    per-line estimate and the model concludes big batches are expensive
    — exactly backwards.  AIMD probes upward and observes the truth.
  * an efficiency guard on top of AIMD: per-bucket EWMA of ms/line is
    remembered, growth into a bucket previously measured per-line WORSE
    is blocked, and a bucket that turns out less efficient than the one
    below shrinks back even when its latency fits the budget.  Latency
    headroom alone is not a reason to grow — on cache-bound backends the
    next power of two can be strictly slower per line (measured: the
    1-core CI box degrades past 2048).  Blocked growth is retried after
    `_RETRY_BLOCKED` decisions so a stale measurement (e.g. one polluted
    by a first-visit compile) cannot pin the size forever.  The verdict
    "this bucket does not pay" is taken only on probation — the first
    `_PROBATION` samples after GROWING into the bucket, from fresh
    samples against the bucket just left.  Past it the size stands until
    the budget says otherwise: held against the lower bucket's frozen
    record for good, one slow batch (a collector pass, a neighbour on the
    host) halves a size that had proven itself, and the way back is
    blocked by the record that batch left (measured on the v5e: 5 s
    slices at two thirds of the rate for 20 s of a 40 s window).
  * a bucket change resets the EWMA and requires `settle` fresh samples
    before the next move, and a sample enters the EWMA as twice its
    current value at most, so one noisy batch cannot oscillate the size
    (measured on the v5e at 10,000 rules: a 1,024-line batch runs at
    170 ms of a 250 ms budget, one batch of 480 ms as a `/metrics`
    scrape pulled the traffic sketch from a busy device halved the size,
    and 512-line batches drain a quarter fewer lines a second).

Thread-safety: observe()/target() take a lock; both are called from
different pipeline stage threads.
"""

from __future__ import annotations

import logging
import threading
from typing import Dict, Optional

log = logging.getLogger(__name__)

_STAGES = ("encode", "device", "drain")
# a bucket must be at least this much per-line worse than its lower
# neighbor before the efficiency guard acts (EWMA noise tolerance)
_EFFICIENCY_SLACK = 1.05
# decisions after which a blocked grow forgets the upper bucket's stale
# per-line record and probes again
_RETRY_BLOCKED = 50
# samples after growing into a bucket during which the efficiency guard
# may send it back
_PROBATION = 8


def _pow2_at_most(n: int) -> int:
    b = 1
    while b * 2 <= n:
        b <<= 1
    return b


class AdaptiveBatchSizer:
    def __init__(
        self,
        budget_ms: float,
        min_batch: int = 64,
        max_batch: int = 16384,
        start_batch: int = 1024,
        alpha: float = 0.3,
        settle: int = 2,
        command_max: int = 1024,
    ):
        if budget_ms <= 0:
            raise ValueError(f"budget_ms must be positive, got {budget_ms}")
        if not (0 < min_batch <= max_batch):
            raise ValueError(
                f"bad batch bounds [{min_batch}, {max_batch}]"
            )
        if command_max < 1:
            raise ValueError(
                f"command_max must be >= 1, got {command_max}"
            )
        self.budget_ms = budget_ms
        self.command_max = command_max
        self.min_batch = _pow2_at_most(min_batch)
        self.max_batch = _pow2_at_most(max_batch)
        self._alpha = alpha
        self._settle = settle
        self._lock = threading.Lock()
        self._bucket = min(
            max(_pow2_at_most(start_batch), self.min_batch), self.max_batch
        )
        self._total_ewma_ms: Optional[float] = None
        self._samples_at_bucket = 0
        # efficiency guard state: last EWMA ms/line seen at each bucket,
        # and how many grow decisions the upper bucket's record has blocked
        self._per_line_at: Dict[int, float] = {}
        self._blocked_grows = 0
        self._on_probation = False  # grew into this bucket, verdict open
        # moves of the target — banjax_pipeline_batch_target_changes_total
        self._changes = {"up": 0, "down": 0}
        # the first full batch after a bucket change pays that bucket's
        # one-time jit compile; learning from it would poison both the
        # latency EWMA and the per-line efficiency record
        self._skip_first = True
        # per-stage EWMA ms at the current bucket — metrics surface only;
        # the grow/shrink decision uses the total
        self.stage_ewma_ms: Dict[str, Optional[float]] = {
            s: None for s in _STAGES
        }

    def target(self) -> int:
        """Current batch-size cap for the encode stage."""
        with self._lock:
            return self._bucket

    def command_target(self) -> int:
        """Take-size bound for COMMAND batches (ROADMAP PR 3 follow-up).
        Commands bypass the device, so they produce no stage timings for
        AIMD to learn from; instead of riding the adaptive line bucket
        (which a command flood would stretch to max_batch) they get a
        fixed cap, chopping a Kafka command flood into bounded batches
        that interleave with line batches at the admission-order kind
        boundary rather than starving line batching."""
        return self.command_max

    def observe(self, n_lines: int, stage_ms: Dict[str, float]) -> None:
        """One drained batch's per-stage wall times (ms).  Batches of at
        most half the current bucket (a trickle, or one cut for the
        bucket below) and batches above it (cut for a bucket above)
        update the stage EWMAs for metrics but don't drive sizing —
        their latency says nothing about the bucket's."""
        total = float(sum(stage_ms.values()))
        with self._lock:
            for s, ms in stage_ms.items():
                prev = self.stage_ewma_ms.get(s)
                self.stage_ewma_ms[s] = (
                    ms if prev is None
                    else prev + self._alpha * (ms - prev)
                )
            if n_lines > self._bucket or (
                n_lines * 2 <= self._bucket and total <= self.budget_ms
            ):
                # not a sample of this bucket: a trickle — or a batch the
                # ring still held when the bucket changed, cut for the
                # bucket just left.  Counted, the two that follow a
                # doubling say "half a batch takes half the time" and
                # double again; the two that follow a halving say the
                # opposite (seen at 10,000 rules, where a batch is over
                # the budget from 2,048 lines up: 512 to 4,096 and back)
                return
            if self._skip_first:
                self._skip_first = False
                return
            if self._total_ewma_ms is not None:
                # one slow batch (a scrape that pulls from a busy device,
                # a collector pass) counts as twice the running mean at
                # most: alone it cannot put a bucket over the budget that
                # runs at two thirds of it, two in a row can
                total = min(total, 2.0 * self._total_ewma_ms)
            self._total_ewma_ms = (
                total if self._total_ewma_ms is None
                else self._total_ewma_ms
                + self._alpha * (total - self._total_ewma_ms)
            )
            per_line = total / max(1, n_lines)
            prev_pl = self._per_line_at.get(self._bucket)
            cur_pl = self._per_line_at[self._bucket] = (
                per_line if prev_pl is None
                else prev_pl + self._alpha * (per_line - prev_pl)
            )
            self._samples_at_bucket += 1
            if self._samples_at_bucket < self._settle:
                return
            ewma = self._total_ewma_ms
            lower_pl = self._per_line_at.get(self._bucket >> 1)
            upper_pl = self._per_line_at.get(self._bucket << 1)
            if ewma > self.budget_ms and self._bucket > self.min_batch:
                self._bucket >>= 1
                self._reset_locked("down")
            elif (
                self._on_probation
                and self._samples_at_bucket <= _PROBATION
                and lower_pl is not None
                and cur_pl > lower_pl * _EFFICIENCY_SLACK
            ):
                # latency fits, but this bucket is per-line WORSE than the
                # one below: larger batches are not paying here — go back
                self._bucket >>= 1
                self._reset_locked("down")
            elif ewma < self.budget_ms * 0.5 and self._bucket < self.max_batch:
                if (
                    upper_pl is not None
                    and upper_pl > cur_pl * _EFFICIENCY_SLACK
                ):
                    # the bucket above was measured per-line worse; retry
                    # eventually in case that record is stale
                    self._blocked_grows += 1
                    if self._blocked_grows >= _RETRY_BLOCKED:
                        self._per_line_at.pop(self._bucket << 1, None)
                        self._blocked_grows = 0
                    return
                self._bucket <<= 1
                self._reset_locked("up")
                # judged on what it shows now, not on an old visit
                self._per_line_at.pop(self._bucket, None)
                self._on_probation = True

    def _reset_locked(self, direction: str) -> None:
        # the bucket has just changed (a handful of times in a process's
        # life once it has settled): count it, and say why
        self._changes[direction] += 1
        log.info(
            "batch target now %d lines (EWMA %.0f ms a batch over %d "
            "samples, budget %.0f ms)", self._bucket,
            self._total_ewma_ms or 0.0, self._samples_at_bucket,
            self.budget_ms,
        )
        self._total_ewma_ms = None
        self._samples_at_bucket = 0
        self._skip_first = True
        self._on_probation = False

    def target_changes(self) -> Dict[str, int]:
        """{"up": n, "down": n}: how often the target has moved."""
        with self._lock:
            return dict(self._changes)

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            out: Dict[str, object] = {
                "PipelineBatchTarget": self._bucket,
                "PipelineCommandBatchTarget": self.command_max,
            }
            for s in _STAGES:
                v = self.stage_ewma_ms.get(s)
                out[f"PipelineStage{s.capitalize()}EwmaMs"] = (
                    None if v is None else round(v, 3)
                )
            return out
