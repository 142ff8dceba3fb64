"""Streaming pipeline scheduler: overlapped tailer→device→effector batching.

A batch goes through the matcher in four stages (begin, submit, collect,
finish: matcher/runner.py).  The synchronous consume_lines calls them in
turn on one thread, a batch at a time, and the fixed device→host latency
is only hidden when overlapped with compute.  This module is the other
caller of the same four: the continuous-batching scheduler that closes
that gap — the inference-serving pattern (SURVEY §7.2 M5) applied to log
classification.

Stages, one thread each::

    tailer → submit() → [admission buffer]
        → encode  (batch formation at the adaptive target, host
                   parse/gate/encode — matcher.pipeline_begin)
        → device  (h2d + device dispatch — matcher.pipeline_submit — with
                   up to two batches in flight, so batch N's device→host
                   pull (pipeline_collect) hides behind batch N+1's
                   compute)
        → drain   (strictly FIFO: window updates, Banner effects,
                   staleness accounting — matcher.pipeline_finish)

so batch N+1 encodes and uploads while batch N computes and batch N−1
drains.

Ordering contract: the drain stage is a single thread consuming batches
in admission order, so per-(ip, rule) window updates and ban-log lines
stay in log order across batch boundaries — byte-identical to the
synchronous path (tests/differential/test_pipeline_differential.py).

Fused path: with device windows on, the split protocol drives the fused
matcher+windows pipeline (matcher/fused_windows.py) — match AND window
commit are ONE device program per chunk, dispatched at the submit stage
any number of batches ahead; the drain stage pulls each chunk's compact
event buffer (async since submit) in admission order and replays it.
The dense bitmap never crosses the host boundary (tests/differential/
test_fused_pipeline_differential.py proves byte-identity and the h2d
win).  Because the commit happens at submit, the 10 s staleness cutoff
is evaluated there (the program's live-mask input), which is why the
submit call below receives the scheduler clock; a matcher advertises
this with `pipeline_submit_takes_now`.  A chunk that overflows commits
nothing and replays through the classic bitmap protocol at its drain
turn; batches the fused path cannot take (host-evaluated rules, a
refused slot allocation, a failed scan selftest) ride the classic
protocol end to end, with the staleness cut at drain.  Generic drains
use consume_lines_serial — the same four stages on the drain thread with
the fused path off: a fused chunk dispatched there would wait on order
turns that later batches, already submitted, hold until this very drain
is done.

Kafka commands: submit_commands() admits command messages into the SAME
buffer as tailer lines — shared bounded-block/oldest-first-shed
accounting (admitted == processed + shed spans both producers) — and
the drain thread dispatches each handler in admission order.

Batch sizing: pipeline/sizer.py grows/shrinks the encode target within
power-of-two buckets to hit `pipeline_latency_budget_ms` from observed
per-stage EWMA timings, replacing the fixed `matcher_batch_lines` guess.

Backpressure: a bounded ring of in-flight batches (`pipeline_ring_size`)
gates the encode stage; when the ring is full the admission buffer
absorbs up to `pipeline_buffer_lines`, beyond which submit() blocks the
tailer for at most `pipeline_max_block_ms` and then sheds OLDEST lines
first, counting every shed line (PipelineShedLines) — bounded memory,
never silent loss.

Staleness: the reference drops lines older than 10 s at consume time
(regex_rate_limiter.go:164-167).  Here age is measured at *effector
drain* time — a line that ages out while queued is dropped exactly as
the reference would have dropped it, marked old_line in its result, and
counted (PipelineStaleDroppedLines).

Resilience: matchers without the split protocol (CpuMatcher), batches
whose device stage failed, and batches admitted while the breaker is
OPEN all drain generically through matcher.consume_lines — which routes
to the CPU reference matcher under an open breaker — so the ring drains
through the CPU fallback and no admitted line is lost.  Failpoints
pipeline.encode / pipeline.submit / pipeline.collect / pipeline.drain
cover each stage boundary; the scheduler registers as a health
component; and an optional timer probe (`matcher_probe_seconds`) pushes
a synthetic batch through the idle device path so a wedged device trips
the breaker before the next traffic burst.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
from collections import deque
from typing import Callable, List, Optional, Sequence

from banjax_tpu.obs import flightrec, trace
from banjax_tpu.obs.stats import PipelineStats
from banjax_tpu.pipeline.heap import HeapKeeper
from banjax_tpu.pipeline.sizer import AdaptiveBatchSizer
from banjax_tpu.resilience import failpoints
from banjax_tpu.resilience.breaker import OPEN

log = logging.getLogger(__name__)

# a shard below this many rows costs more in fan-out/merge overhead than
# the parallel parse saves; batches smaller than 2x this stay single-thread
_MIN_SHARD_LINES = 2048


def resolve_encode_workers(v: int) -> int:
    """-1 = auto: min(4, cores), but 0 (single-thread, no pool) on a
    single-core host where a worker adds handoff latency for nothing."""
    if v >= 0:
        return v
    cores = os.cpu_count() or 1
    return min(4, cores) if cores > 1 else 0


class _Batch:
    __slots__ = ("lines", "matcher", "state", "t_encode_ms", "t_device_ms",
                 "t_service_ms", "t0_device", "kind", "trace_id", "root_span",
                 "e2e", "builds0")

    def __init__(self, lines: List[str], kind: str = "lines"):
        self.lines = lines      # log lines, or _Command items (kind="cmd")
        self.matcher = None
        self.state = None       # split-protocol state; None = generic drain
        self.t_encode_ms = 0.0
        self.t_device_ms = 0.0
        # the device stage's service time: from this batch's submit, or
        # from its predecessor's collect if that came later, to its own
        # collect (the sizer's sample; 0 = the batch was not collected)
        self.t_service_ms = 0.0
        self.t0_device = 0.0
        # the matcher's count of device programs built, taken when the
        # batch starts: a batch during which it moved paid for a compile
        # (or a compile-cache load) and is no latency sample for the
        # breaker's budget or the batch sizer
        self.builds0 = 0
        self.kind = kind
        # span propagation (obs/trace.py): trace id allocated at the
        # encode stage's take; the root "admission" span opens there and
        # closes when the drain stage finishes this batch (0/NOOP when
        # tracing is off — every span call below no-ops on them)
        self.trace_id = 0
        self.root_span = trace.NOOP_SPAN
        # {hop: oldest tailer-read monotonic stamp} for the lines this
        # batch took — observed into banjax_e2e_latency_seconds at drain
        self.e2e: dict = {}


class _Command:
    """One Kafka command message riding the admission buffer: the raw
    payload plus the reader's dispatch callable.  Commands share the
    buffer bound, the bounded-block/oldest-first shed, and the
    admitted == processed + shed accounting with tailer lines; the drain
    stage executes them in admission order."""

    __slots__ = ("raw", "handler")

    def __init__(self, raw: bytes, handler: Callable[[bytes], None]):
        self.raw = raw
        self.handler = handler


class PipelineScheduler:
    def __init__(
        self,
        matcher_getter: Callable[[], object],
        ring_size: int = 4,
        latency_budget_ms: float = 250.0,
        buffer_lines: int = 131072,
        max_block_ms: float = 250.0,
        min_batch: int = 64,
        max_batch: int = 16384,
        probe_seconds: float = 0.0,
        encode_workers: int = 0,
        command_take_max: int = 1024,
        health=None,
        on_results: Optional[Callable[[List[str], Optional[list]], None]] = None,
        now_fn: Callable[[], float] = time.time,
    ):
        if ring_size < 1:
            raise ValueError(f"ring_size must be >= 1, got {ring_size}")
        if buffer_lines < 1:
            raise ValueError(f"buffer_lines must be >= 1, got {buffer_lines}")
        self._matcher_getter = matcher_getter
        self.ring_size = ring_size
        self.buffer_lines = buffer_lines
        self.max_block_s = max(0.0, max_block_ms) / 1e3
        self.probe_seconds = probe_seconds
        # sharded encode-worker pool (0 = the single-thread encode path):
        # the encode stage splits each admission batch into row shards
        # fanned across this many threads — the native parse and the
        # columnar gate are GIL-free, so the host path scales with cores
        # instead of capping at one Python thread
        self.encode_workers = max(0, int(encode_workers))
        self._encode_pool = None  # created at start(), joined at stop()
        self._health = health
        self._on_results = on_results
        self._now_fn = now_fn
        self._sizer = AdaptiveBatchSizer(
            latency_budget_ms, min_batch=min_batch, max_batch=max_batch,
            command_max=command_take_max,
        )
        self.stats = PipelineStats()
        self._buf: deque = deque()
        # read-stamp runs parallel to the LINE items in _buf: [count,
        # t_read, hop] per admitted chunk, trimmed in lockstep by sheds
        # and encode takes (commands carry no stamp and no mark)
        self._marks: deque = deque()
        self._cond = threading.Condition()
        self._inflight = 0
        self._last_collect_done = 0.0  # device thread only
        self._last_activity = time.monotonic()
        self._ring = threading.Semaphore(ring_size)
        self._q_dev: "queue.Queue" = queue.Queue()
        self._q_drain: "queue.Queue" = queue.Queue()
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        # the submit stage's phases, summed over this scheduler's life
        # (the device thread marks, submit_phase_seconds reads)
        self._lap = trace.LapClock()
        # the cyclic collector's pauses, counted, and the start-up heap
        # kept out of its full passes (pipeline/heap.py)
        self._heap = HeapKeeper()
        # native ids of the encode pool's threads, each noted by the
        # thread itself as it starts (thread_ids)
        self._encode_worker_ids: List[int] = []

    @classmethod
    def from_config(cls, matcher_getter, config, health=None, on_results=None):
        return cls(
            matcher_getter,
            ring_size=getattr(config, "pipeline_ring_size", 4),
            latency_budget_ms=getattr(
                config, "pipeline_latency_budget_ms", 250.0
            ),
            buffer_lines=getattr(config, "pipeline_buffer_lines", 131072),
            max_block_ms=getattr(config, "pipeline_max_block_ms", 250.0),
            max_batch=max(64, getattr(config, "matcher_batch_lines", 16384)),
            probe_seconds=getattr(config, "matcher_probe_seconds", 0.0),
            encode_workers=resolve_encode_workers(
                getattr(config, "encode_workers", -1)
            ),
            command_take_max=getattr(
                config, "pipeline_command_take_max", 1024
            ),
            health=health,
            on_results=on_results,
        )

    # ---- lifecycle ----

    def start(self) -> None:
        self._heap.start()
        if self.encode_workers > 0 and self._encode_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._encode_pool = ThreadPoolExecutor(
                max_workers=self.encode_workers,
                thread_name_prefix="pipeline-encode-worker",
                initializer=lambda: self._encode_worker_ids.append(
                    threading.get_native_id()
                ),
            )
        loops = [
            ("pipeline-encode", self._encode_loop),
            ("pipeline-device", self._device_loop),
            ("pipeline-drain", self._drain_loop),
        ]
        if self.probe_seconds > 0:
            loops.append(("pipeline-probe", self._probe_loop))
        for name, fn in loops:
            t = threading.Thread(target=fn, name=name, daemon=True)
            t.start()
            self._threads.append(t)
        if self._health is not None:
            self._health.ok()

    def stop(self, timeout: float = 10.0) -> None:
        """Drain everything already admitted, then stop the stage threads
        (bounded by ring_size + buffer_lines, both finite by contract)."""
        with self._cond:
            self._stop.set()
            self._cond.notify_all()
        deadline = time.monotonic() + timeout
        for t in self._threads:
            t.join(max(0.1, deadline - time.monotonic()))
        self._threads = []
        if self._encode_pool is not None:
            # after the stage threads joined no new shard work can arrive
            self._encode_pool.shutdown(wait=True)
            self._encode_pool = None
            self._encode_worker_ids = []
        self._heap.stop()

    def flush(self, timeout: float = 60.0) -> bool:
        """Block until every admitted line has drained (tests/bench)."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while self._buf or self._inflight:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(remaining)
        return True

    # ---- admission (tailer thread) ----

    def submit(self, lines: Sequence[str], t_read: Optional[float] = None,
               hop: str = "local") -> None:
        """Admit a chunk of log lines.  Blocks for at most
        `pipeline_max_block_ms` when the buffer is full, then sheds
        oldest-first — the tailer is never blocked unboundedly and memory
        is never unbounded.  `t_read` is the tailer-read monotonic stamp
        and `hop` whether the chunk was tailed here ("local") or arrived
        over the fabric wire ("fabric") — together they feed the
        banjax_e2e_latency_seconds{hop} histogram at drain time."""
        self._admit(list(lines), t_read=t_read, hop=hop)

    def submit_commands(
        self, raws: Sequence[bytes], handler: Callable[[bytes], None]
    ) -> None:
        """Admit Kafka command messages into the same buffer as tailer
        lines: identical bounded-block/oldest-first-shed accounting
        (admitted == processed + shed holds across both producers), and
        the drain stage dispatches `handler(raw)` per message in admission
        order relative to everything else in the stream."""
        self._admit([_Command(r, handler) for r in raws], hop=None)

    def _mark_drop_locked(self) -> None:
        """One LINE item left the buffer head: trim the oldest mark."""
        if not self._marks:
            return
        m = self._marks[0]
        m[0] -= 1
        if m[0] <= 0:
            self._marks.popleft()

    def _take_marks_locked(self, n: int) -> dict:
        """Consume marks for `n` line items taken off the buffer head;
        returns {hop: oldest t_read} over the stamped ones."""
        out: dict = {}
        while n > 0 and self._marks:
            m = self._marks[0]
            took = min(n, m[0])
            if m[1] is not None:
                hop = m[2]
                if hop not in out or m[1] < out[hop]:
                    out[hop] = m[1]
            m[0] -= took
            n -= took
            if m[0] <= 0:
                self._marks.popleft()
        return out

    def _admit(self, lines: list, t_read: Optional[float] = None,
               hop: Optional[str] = "local") -> None:
        if not lines:
            return
        self.stats.note_admitted(len(lines))
        deadline: Optional[float] = None
        shed_burst = 0
        with self._cond:
            self._last_activity = time.monotonic()
            while (
                len(self._buf) + len(lines) > self.buffer_lines
                and not self._stop.is_set()
            ):
                if deadline is None:
                    deadline = time.monotonic() + self.max_block_s
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cond.wait(remaining)
            overflow = len(self._buf) + len(lines) - self.buffer_lines
            if overflow > 0:
                # sustained overload: oldest-first shed, every line counted
                dropped = 0
                while overflow > 0 and self._buf:
                    item = self._buf.popleft()
                    if isinstance(item, str):
                        self._mark_drop_locked()
                    overflow -= 1
                    dropped += 1
                if overflow > 0:  # chunk alone exceeds the buffer bound
                    lines = lines[overflow:]
                    dropped += overflow
                self.stats.note_shed(dropped)
                # stream-level annotation: a shed belongs to no single
                # batch, so it rides the ring as an instant event
                trace.instant("shed", {"lines": dropped,
                                       "buffered": len(self._buf)})
                if self._health is not None:
                    self._health.degraded(f"overload: shed {dropped} lines")
                shed_burst = dropped
            was_empty = not self._buf
            self._buf.extend(lines)
            if hop is not None and lines:
                self._marks.append([len(lines), t_read, hop])
            if was_empty:
                # the encode thread only sleeps on an empty buffer; waking
                # it per chunk would burn the tailer thread on notify calls
                # at high submit rates (flush/backpressure waiters are woken
                # by the encode/drain stages, not here)
                self._cond.notify_all()
        if shed_burst:
            # incident capture OUTSIDE the condition lock: the recorder
            # writes files, and the stage threads must not wait on disk
            flightrec.notify("shed-burst", f"shed {shed_burst} lines")

    # ---- encode stage ----

    def _encode_loop(self) -> None:
        try:
            while True:
                with self._cond:
                    while not self._buf and not self._stop.is_set():
                        self._cond.wait(0.2)
                    if not self._buf and self._stop.is_set():
                        return
                # reserve a ring slot OUTSIDE the lock: while the ring is
                # full the admission buffer keeps absorbing (and shedding)
                # instead of the tailer blocking on a held condition
                if not self._ring.acquire(timeout=0.2):
                    continue
                with self._cond:
                    # take whatever is buffered up to the target, never
                    # wait for a fuller batch: holding the ring slot while
                    # the buffer fills starves the device stage (measured
                    # −40% on the 1-core box); partial batches are fine —
                    # the sizer's trickle rule ignores them.  A batch is
                    # homogeneous: a run of log lines OR a run of command
                    # messages, split at the kind boundary so admission
                    # order is preserved exactly.  Command batches have
                    # their OWN take bound (sizer.command_target): they
                    # carry no device timing for AIMD, and an unbounded
                    # take would let a Kafka command flood monopolize the
                    # drain thread in one giant dispatch loop, starving
                    # line batching.
                    is_cmd = bool(self._buf) and isinstance(
                        self._buf[0], _Command
                    )
                    take = min(
                        len(self._buf),
                        self._sizer.command_target() if is_cmd
                        else self._sizer.target(),
                    )
                    lines = []
                    while (
                        len(lines) < take and self._buf
                        and isinstance(self._buf[0], _Command) == is_cmd
                    ):
                        lines.append(self._buf.popleft())
                    e2e = (
                        self._take_marks_locked(len(lines))
                        if lines and not is_cmd else {}
                    )
                    if lines:
                        self._inflight += 1
                    self._cond.notify_all()
                if not lines:  # a shed emptied the buffer under us
                    self._ring.release()
                    continue
                # take-time is where a batch exists as a unit: allocate its
                # trace id here so admission-buffer wait is excluded but
                # every stage (incl. queueing between stages) is covered
                batch = _Batch(lines, kind="cmd" if is_cmd else "lines")
                batch.e2e = e2e
                if trace.enabled():
                    batch.trace_id = trace.new_trace()
                    batch.root_span = trace.begin(
                        "admission", batch.trace_id,
                        args={"items": len(lines), "kind": batch.kind},
                    )
                if not is_cmd:
                    self._encode_batch(batch)
                self._q_dev.put(batch)
        finally:
            self._q_dev.put(None)

    def _encode_batch(self, batch: _Batch) -> None:
        lines = batch.lines
        # the getter builds the matcher on first use / hot reload (rule
        # compile, kernel self-tests): start-up, not this batch's encode
        matcher = self._matcher_getter()
        batch.matcher = matcher
        batch.builds0 = self._builds(batch)
        # start-up too: a new matcher, or programs that have just stopped
        # being built, is where the heap is collected whole and frozen
        self._heap.observe(matcher, batch.builds0)
        t0 = time.perf_counter()
        breaker = getattr(matcher, "breaker", None)
        with trace.span("encode", batch.trace_id,
                        parent=batch.root_span.span_id) as sp:
            # breaker OPEN: skip the split encode entirely — the generic
            # drain re-parses inside consume_lines, which routes to the
            # CPU fallback
            if hasattr(matcher, "pipeline_begin") and not (
                breaker is not None and breaker.state == OPEN
            ):
                if hasattr(matcher, "set_latency_budget_source"):
                    # breaker-budget satellite: when
                    # matcher_latency_budget_ms is unset the breaker
                    # derives it from this pipeline's observed device p99
                    # (3x EWMA p99, floor 1 s)
                    matcher.set_latency_budget_source(
                        self.stats.suggested_latency_budget_s
                    )
                try:
                    failpoints.check("pipeline.encode")
                    batch.state = self._begin_state(matcher, lines, sp)
                except Exception:  # noqa: BLE001 — encode failure → generic drain, no loss
                    log.exception(
                        "pipeline encode stage failed; batch drains "
                        "generically"
                    )
                    sp.note("failed", True)
                    batch.state = None
            elif breaker is not None and breaker.state == OPEN:
                sp.note("breaker", "open-skip")
        batch.t_encode_ms = (time.perf_counter() - t0) * 1e3

    def _begin_state(self, matcher, lines: List[str], encode_span):
        """pipeline_begin, sharded across the encode-worker pool when the
        batch is big enough to pay for the fan-out.  Shard boundaries are
        contiguous row ranges; the matcher's merge reassembles columnar
        arrays and unique-IP tables in strict line order, so downstream
        output is byte-identical to the single-thread path.  A failing
        shard (worker death, the pipeline.encode_shard failpoint) fails
        only THIS batch — the exception propagates to _encode_batch's
        generic-drain fallback and the pool itself survives.

        Each shard records an `encode-shard` child span of the encode
        span (explicit ids — the pool threads have no ambient parent);
        the single-thread path records one shard span covering the whole
        parse so the trace shape is uniform either way."""
        now = self._now_fn()
        pool = self._encode_pool
        n = len(lines)
        tid, parent = encode_span.trace_id, encode_span.span_id
        n_shards = 0
        if (
            pool is not None
            and hasattr(matcher, "encode_shard")
            and hasattr(matcher, "pipeline_begin_from_shards")
        ):
            n_shards = min(self.encode_workers, n // _MIN_SHARD_LINES)
        if n_shards < 2:
            with trace.span("encode-shard", tid, parent,
                            args={"shard": 0, "shards": 1, "rows": n}):
                return matcher.pipeline_begin(lines, now)
        bounds = [n * k // n_shards for k in range(n_shards + 1)]
        shard_ms = [0.0] * n_shards

        def run(k: int):
            t = time.perf_counter()
            with trace.span(
                "encode-shard", tid, parent,
                args={"shard": k, "shards": n_shards,
                      "rows": bounds[k + 1] - bounds[k]},
            ):
                failpoints.check("pipeline.encode_shard")
                out = matcher.encode_shard(
                    lines[bounds[k] : bounds[k + 1]], now
                )
            shard_ms[k] = (time.perf_counter() - t) * 1e3
            return out

        t_fan = time.perf_counter()
        futs = [pool.submit(run, k) for k in range(n_shards)]
        shards = []
        err = None
        for k, f in enumerate(futs):
            try:
                shards.append((bounds[k], f.result()))
            except Exception as e:  # noqa: BLE001 — await EVERY future before raising
                err = err or e
        if err is not None:
            raise err
        wall_ms = (time.perf_counter() - t_fan) * 1e3
        self.stats.note_encode_shards(shard_ms, wall_ms)
        return matcher.pipeline_begin_from_shards(lines, now, shards)

    # ---- device stage ----

    def _device_loop(self) -> None:
        pending: deque = deque()  # submitted, awaiting collect (≤ 2)
        # the stage from inside: the matcher's marks split the wall from
        # a batch's t0_device to the end of its submit into phases
        # (obs/trace.py LapClock)
        lap = self._lap
        trace.stage_thread("submit", lap)
        try:
            while True:
                if pending:
                    # something is in flight: only take new work that is
                    # already queued; otherwise collect now — the overlap
                    # only pays when a successor batch exists to compute
                    # behind the pull
                    try:
                        batch = self._q_dev.get_nowait()
                    except queue.Empty:
                        self._collect(pending.popleft())
                        continue
                else:
                    batch = self._q_dev.get()
                if batch is None:
                    while pending:
                        self._collect(pending.popleft())
                    return
                if batch.kind == "cmd":
                    # no device work; FIFO still holds: everything
                    # submitted before the commands reaches drain first
                    while pending:
                        self._collect(pending.popleft())
                    self._q_drain.put(batch)
                    continue
                if batch.state is not None:
                    breaker = getattr(batch.matcher, "breaker", None)
                    if breaker is not None and not breaker.allow():
                        trace.instant(
                            "breaker-reroute", {"state": breaker.state},
                            trace_id=batch.trace_id,
                        )
                        batch.state = None  # generic drain → CPU fallback
                    else:
                        batch.t0_device = lap.start(len(batch.lines))
                        try:
                            failpoints.check("pipeline.submit")
                            with trace.span(
                                "submit", batch.trace_id,
                                parent=batch.root_span.span_id,
                            ) as sp, trace.step_annotation(
                                batch.trace_id
                            ), lap.under(sp):
                                # matchers that commit state at submit
                                # (the single-kernel fused path) take the
                                # scheduler clock so the staleness cut
                                # stays deterministic under an injected
                                # now_fn
                                if getattr(
                                    batch.matcher,
                                    "pipeline_submit_takes_now", False,
                                ):
                                    batch.matcher.pipeline_submit(
                                        batch.state, now=self._now_fn()
                                    )
                                else:
                                    batch.matcher.pipeline_submit(
                                        batch.state
                                    )
                            # submit half of the device time; collect adds
                            # its half (NOT wall-from-submit: with depth-2
                            # overlap that would double-count the gap where
                            # the successor batch submits)
                            batch.t_device_ms = (
                                time.perf_counter() - batch.t0_device
                            ) * 1e3
                        except Exception:  # noqa: BLE001 — device failure → fallback drain
                            log.exception(
                                "pipeline submit stage failed; batch drains "
                                "on the CPU reference path"
                            )
                            self._device_failure(batch, "submit")
                        else:
                            pending.append(batch)
                            # keep ≤ 2 in flight: collect the older batch
                            # while this one computes
                            while len(pending) >= 2:
                                self._collect(pending.popleft())
                            continue
                # generic/failed batches keep FIFO order: everything
                # submitted before them must reach the drain queue first
                while pending:
                    self._collect(pending.popleft())
                self._q_drain.put(batch)
        finally:
            self._q_drain.put(None)

    def _collect(self, batch: _Batch) -> None:
        t0 = time.perf_counter()
        try:
            failpoints.check("pipeline.collect")
            with trace.span("collect", batch.trace_id,
                            parent=batch.root_span.span_id):
                batch.matcher.pipeline_collect(batch.state)
        except Exception:  # noqa: BLE001 — device failure → fallback drain
            log.exception(
                "pipeline collect stage failed; batch drains on the CPU "
                "reference path"
            )
            self._device_failure(batch, "collect")
        else:
            done = time.perf_counter()
            batch.t_device_ms += (done - t0) * 1e3
            # with two batches in flight, submit + collect counts the
            # predecessor's device time once (the collect waits for it)
            # or twice (the submit waits too), whichever way host and
            # device happen to be in step: a stage time of d or of 2d for
            # hundreds of batches on end.  What the stage took for THIS
            # batch once queueing is subtracted has one reading
            batch.t_service_ms = (
                done - max(self._last_collect_done, batch.t0_device)
            ) * 1e3
            self._last_collect_done = done
            self.stats.observe_device(batch.t_device_ms / 1e3)
            note = getattr(batch.matcher, "note_device_outcome", None)
            if note is not None:
                if self._builds(batch) != batch.builds0:
                    note(batch.t_device_ms / 1e3, ok=True, compiled=True)
                else:
                    note(batch.t_device_ms / 1e3, ok=True)
        self._q_drain.put(batch)

    def _device_failure(self, batch: _Batch, stage: str = "device") -> None:
        trace.instant("device-failure", {"stage": stage},
                      trace_id=batch.trace_id)
        # settle any fused chunks the failed batch already dispatched
        # (order turns + slot pins) before the generic rerun — idempotent
        abort = getattr(batch.matcher, "pipeline_abort", None)
        if abort is not None and batch.state is not None:
            try:
                abort(batch.state)
            except Exception:  # noqa: BLE001
                log.exception("pipeline abort after device failure failed")
        batch.state = None
        batch.t_device_ms = max(
            batch.t_device_ms, (time.perf_counter() - batch.t0_device) * 1e3
        )
        note = getattr(batch.matcher, "note_device_outcome", None)
        if note is not None:
            note(batch.t_device_ms / 1e3, ok=False)

    @staticmethod
    def _builds(batch: _Batch) -> int:
        fn = getattr(batch.matcher, "compile_events", None)
        return fn() if fn is not None else 0

    # ---- drain stage (admission order — the ordering contract) ----

    def _drain_loop(self) -> None:
        trace.stage_thread("drain")
        while True:
            batch = self._q_drain.get()
            if batch is None:
                return
            t0 = time.perf_counter()
            n = len(batch.lines)
            results = None
            ok = True
            sp = trace.span("drain", batch.trace_id,
                            parent=batch.root_span.span_id)
            with sp:
                try:
                    failpoints.check("pipeline.drain")
                    now = self._now_fn()
                    if batch.kind == "cmd":
                        # command batch: dispatch each message in admission
                        # order; a bad command loses itself, not the batch
                        # (the handler owns parse errors, like the
                        # reference's reader loop)
                        for item in batch.lines:
                            try:
                                item.handler(item.raw)
                            except Exception:  # noqa: BLE001
                                log.exception(
                                    "pipeline command dispatch failed"
                                )
                        self.stats.note_commands(n)
                    elif batch.state is None:
                        # generic path: full consume_lines semantics,
                        # including the breaker's CPU-reference fallback —
                        # never a loss.  consume_lines_serial (when the
                        # matcher has it) keeps fused dispatches out of
                        # the drain thread: their order turns belong to
                        # the fused pipeline and one taken here would
                        # deadlock behind in-flight later batches.
                        sp.note("fallback", "generic-drain")
                        consume = getattr(
                            batch.matcher, "consume_lines_serial", None
                        ) or batch.matcher.consume_lines
                        results = consume(batch.lines, now)
                        self.stats.note_batch(fallback=True)
                    else:
                        results, n_stale = batch.matcher.pipeline_finish(
                            batch.state, now
                        )
                        if n_stale:
                            sp.note("stale_dropped", n_stale)
                            self.stats.note_stale(n_stale)
                        self.stats.note_batch(fallback=False)
                except Exception:  # noqa: BLE001 — drain failure is counted, never silent
                    ok = False
                    log.exception(
                        "pipeline drain stage failed; %d lines counted as "
                        "shed", n
                    )
                    self.stats.note_drain_error(n)
                    if batch.state is not None:
                        # free any fused order turns/pins the unfinished
                        # batch still holds — a leaked turn would deadlock
                        # every later fused drain
                        abort = getattr(batch.matcher, "pipeline_abort", None)
                        if abort is not None:
                            try:
                                abort(batch.state)
                            except Exception:  # noqa: BLE001
                                log.exception("pipeline abort failed")
                    if self._health is not None:
                        self._health.degraded("drain failure; lines shed")
            if ok:
                self.stats.note_processed(n)
                if batch.e2e:
                    # effector commit time for every line in the batch:
                    # drain completion, measured against the oldest
                    # tailer-read stamp per hop
                    now_mono = time.monotonic()
                    for hop, t_read in batch.e2e.items():
                        self.stats.observe_e2e(hop, now_mono - t_read)
                if self._health is not None:
                    self._health.ok()
            else:
                # lines lost to a drain failure are an incident like a
                # shed burst: capture evidence (debounced; outside every
                # scheduler lock — only this stage thread waits on disk)
                flightrec.notify("drain-error",
                                 f"{n} lines counted as shed")
            t_drain_ms = (time.perf_counter() - t0) * 1e3
            batch.root_span.note("ok", ok)
            trace.end(batch.root_span)
            if batch.kind != "cmd":
                stage_ms = {
                    "encode": batch.t_encode_ms,
                    "device": batch.t_device_ms,
                    "drain": t_drain_ms,
                }
                if self._builds(batch) == batch.builds0:
                    self._sizer.observe(n, {
                        **stage_ms,
                        "device": batch.t_service_ms or batch.t_device_ms,
                    })
                # labeled per-stage duration histograms for /metrics —
                # recorded per batch regardless of tracing (the trace ring
                # is the sampled view, the histogram the complete one)
                self.stats.observe_stages(stage_ms)
            if self._on_results is not None and batch.kind != "cmd":
                try:
                    self._on_results(batch.lines, results)
                except Exception:  # noqa: BLE001 — an observer must not stall the drain
                    log.exception("pipeline on_results callback failed")
            self._ring.release()
            with self._cond:
                self._inflight -= 1
                self._last_activity = time.monotonic()
                self._cond.notify_all()

    # ---- idle probe (matcher staleness satellite) ----

    def _probe_loop(self) -> None:
        while not self._stop.wait(self.probe_seconds):
            with self._cond:
                idle = (
                    not self._buf
                    and self._inflight == 0
                    and time.monotonic() - self._last_activity
                    >= self.probe_seconds
                )
            if not idle:
                continue
            probe = getattr(self._matcher_getter(), "probe", None)
            if probe is None:
                continue
            try:
                probe_ok = bool(probe())
            except Exception:  # noqa: BLE001 — a probe bug must not kill the timer
                log.exception("pipeline device probe raised")
                probe_ok = False
            self.stats.note_probe(probe_ok)
            if self._health is not None:
                if probe_ok:
                    self._health.ok()
                else:
                    self._health.degraded("device probe failed")

    # ---- observability ----

    def snapshot(self) -> dict:
        """Additive 29 s metrics-line keys (obs/metrics.py).  Resets the
        interval windows — the line's single periodic consumer only."""
        out = self.stats.snapshot()
        out.update(self._sizer.snapshot())
        return self._live_gauges(out)

    def thread_ids(self) -> dict:
        """{label: native ids} of the pipeline's running threads — the
        three stage threads by name and the encode pool as one label —
        for whoever reads their clocks (obs/exposition.py, at scrape
        time)."""
        out = {t.name: [t.native_id] for t in self._threads
               if t.name != "pipeline-probe" and t.native_id is not None}
        if self._encode_worker_ids:
            out["pipeline-encode-worker"] = list(self._encode_worker_ids)
        return out

    def submit_phase_seconds(self) -> tuple:
        """({phase: wall seconds}, CPU seconds) of the submit stage over
        every batch so far: the wall by phase, and what the device thread
        ran of all of it."""
        return dict(self._lap.wall), self._lap.cpu_s

    def submit_runtime_calls(self) -> int:
        """Calls into the device runtime the device thread made inside
        the submit stage, over every batch so far (obs/trace.py)."""
        return self._lap.runtime_calls

    def collector_stats(self) -> dict:
        """The cyclic collector since start(): collections, seconds
        paused and objects freed, each a list by generation, and the
        objects frozen out of its reach now."""
        return self._heap.snapshot()

    def batch_target_changes(self) -> dict:
        """{"up": n, "down": n}: moves of the sizer's batch target."""
        return self._sizer.target_changes()

    def prom_snapshot(self) -> dict:
        """Non-destructive view for /metrics (obs/exposition.py): totals,
        EWMAs and live gauges; never steals the line's interval deltas."""
        out = self.stats.peek()
        out.update(self._sizer.snapshot())
        return self._live_gauges(out)

    def _live_gauges(self, out: dict) -> dict:
        with self._cond:
            out["PipelineBufferedLines"] = len(self._buf)
            out["PipelineInflightBatches"] = self._inflight
        out["PipelineRingSize"] = self.ring_size
        out["EncodeWorkers"] = self.encode_workers
        return out
