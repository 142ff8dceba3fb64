"""Pipeline tracing: a lock-cheap, ring-buffered span recorder.

The reference banjax exposes a 29-second status line and nothing else;
this reproduction has four overlapped pipeline stages, a fused
device path and sharded encode workers — none of it visible per-batch.
This module is the Dapper-style propagation layer: every admission
batch gets a trace id at the scheduler's take-time and carries it
through encode (per-shard child spans), submit (the fused program's
dispatch, mesh shard submits), collect, and drain (effector replay),
with breaker/fallback/shed events as instant annotations.

Design constraints, in order:

  * **Off ≈ free.**  `trace_enabled` defaults false; every record path
    starts with one attribute check and returns a shared no-op object —
    no allocation, no lock, no clock read.
  * **On = lock-cheap.**  A completed span is one lock acquisition and
    a handful of stores into a preallocated ring (`trace_ring_size`
    slots, oldest overwritten).  Nothing is formatted or allocated per
    span beyond the record tuple; export pays the formatting cost.
  * **Cross-thread spans are explicit.**  A batch's root span begins on
    the encode thread and ends on the drain thread, so the root rides
    the batch object (`begin`/`end`), while single-thread stage spans
    use the context-manager form, which also maintains a thread-local
    ambient parent — nested spans recorded inside the matcher (the
    fused program, effector replay, mesh shard pulls) auto-parent without the
    matcher knowing about the scheduler's ids.

Export: `export_chrome()` renders the ring as Chrome `trace_event`
JSON — load the `/debug/trace` dump straight into Perfetto
(https://ui.perfetto.dev) or chrome://tracing.  Span args become event
`args`; thread names are emitted as metadata events so each pipeline
stage gets its own named track.

JAX bridge: with `trace_jax_annotations` on, context-manager spans also
enter `jax.profiler.TraceAnnotation(name)` so host spans line up with
the XLA/TPU device timeline whenever a profiler session (the
/debug/jax/trace route, or an external `jax.profiler.start_trace`) is
active; the annotations are no-ops otherwise.  The root batch span
additionally wraps its submit stage in `StepTraceAnnotation` with the
trace id as the step number, which Perfetto/xprof group per step.

The submit stage from inside: `LapClock` splits the wall between the
scheduler's `batch.t0_device` and the end of `pipeline_submit` into
SUBMIT_PHASES, with the seconds its thread ran beside the seconds that
passed.  The sums are exported whether tracing is on or off
(`banjax_submit_phase_seconds_total{phase}`,
`banjax_submit_cpu_seconds_total`), and so is the count of the calls the
stage's thread made into the device runtime
(`banjax_submit_runtime_calls_total`); with tracing on
each named phase is also a `submit-<phase>` child span of the batch's
`submit` span, in the ring and through the JAX bridge.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from typing import Dict, List, Optional

DEFAULT_RING_SIZE = 4096

# the five pipeline stage span names the acceptance test asserts on
STAGES = ("admission", "encode", "encode-shard", "submit", "collect", "drain")

# the submit stage's phases (LapClock); every second of the stage belongs
# to exactly one, and `other` is what no mark names
SUBMIT_PHASES = ("pass", "sketch", "operands", "maintenance", "dispatch",
                 "other")


class _NoopSpan:
    """Shared do-nothing span: returned whenever recording is off (or the
    caller has no trace), so call sites never branch on enablement."""

    __slots__ = ()
    trace_id = 0
    span_id = 0

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def note(self, key: str, value) -> None:
        return None


NOOP_SPAN = _NoopSpan()


class Span:
    """One live span.  Mutable while open; recorded into the ring on
    `end()`/`__exit__`.  `note()` attaches args visible in the export
    (breaker state, fallback reasons, row counts)."""

    __slots__ = ("tracer", "trace_id", "span_id", "parent_id", "name",
                 "t0", "args", "_thread_name", "_jax_ctx")

    def __init__(self, tracer: "Tracer", name: str, trace_id: int,
                 parent_id: int, args: Optional[dict]):
        self.tracer = tracer
        self.trace_id = trace_id
        self.span_id = next(tracer._ids)
        self.parent_id = parent_id
        self.name = name
        self.args = dict(args) if args else None
        self.t0 = time.perf_counter()
        self._thread_name = threading.current_thread().name
        self._jax_ctx = None

    def note(self, key: str, value) -> None:
        if self.args is None:
            self.args = {}
        self.args[key] = value

    # -- context-manager form (single-thread spans; maintains the ambient
    # parent stack and the optional jax annotation) --

    def __enter__(self) -> "Span":
        stack = self.tracer._ambient.__dict__.setdefault("stack", [])
        stack.append(self)
        if self.tracer.jax_annotations:
            self._jax_ctx = self.tracer._enter_jax(self.name)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._jax_ctx is not None:
            try:
                self._jax_ctx.__exit__(exc_type, exc, tb)
            except Exception:  # noqa: BLE001 — tracing must never raise
                pass
            self._jax_ctx = None
        stack = self.tracer._ambient.__dict__.get("stack")
        if stack and stack[-1] is self:
            stack.pop()
        if exc_type is not None:
            self.note("error", repr(exc))
        self.tracer.end(self)


class Tracer:
    """Process-wide span recorder.  All public methods are safe to call
    from any thread; when `enabled` is False every one of them is a
    single attribute check."""

    def __init__(self, enabled: bool = False,
                 ring_size: int = DEFAULT_RING_SIZE,
                 jax_annotations: bool = False):
        self.enabled = bool(enabled)
        self.jax_annotations = bool(jax_annotations)
        self.ring_size = max(16, int(ring_size))
        self._lock = threading.Lock()
        self._ring: List[Optional[tuple]] = [None] * self.ring_size
        self._n = 0  # monotone record count; ring index = _n % ring_size
        self._dropped = 0
        self._ids = itertools.count(1)
        self._traces = itertools.count(1)
        self._ambient = threading.local()
        self._epoch = time.perf_counter()
        self._epoch_wall = time.time()

    # ---- recording ----

    def new_trace(self) -> int:
        """Allocate a trace id for one admission batch; 0 when disabled
        (0 propagates as 'don't record' through every span call)."""
        if not self.enabled:
            return 0
        return next(self._traces)

    def begin(self, name: str, trace_id: int, parent: int = 0,
              args: Optional[dict] = None):
        """Open a span explicitly (cross-thread form: `end()` may run on
        a different thread).  Does NOT touch the ambient stack."""
        if not self.enabled or not trace_id:
            return NOOP_SPAN
        return Span(self, name, trace_id, parent, args)

    def end(self, span) -> None:
        """Close a span opened with `begin()` (or via __exit__)."""
        if span is NOOP_SPAN or not isinstance(span, Span):
            return
        dur_us = (time.perf_counter() - span.t0) * 1e6
        t0_us = (span.t0 - self._epoch) * 1e6
        rec = (span.trace_id, span.span_id, span.parent_id, span.name,
               t0_us, dur_us, span._thread_name, span.args)
        self._put(rec)

    def record(self, name: str, parent: Span, t0: float, t1: float,
               args: Optional[dict] = None) -> None:
        """A finished child span of `parent` on this thread, from two
        perf_counter stamps its caller already took (LapClock)."""
        rec = (parent.trace_id, next(self._ids), parent.span_id, name,
               (t0 - self._epoch) * 1e6, (t1 - t0) * 1e6,
               parent._thread_name, args)
        self._put(rec)

    def _put(self, rec: tuple) -> None:
        with self._lock:
            self._ring[self._n % self.ring_size] = rec
            self._n += 1

    def span(self, name: str, trace_id: Optional[int] = None,
             parent: Optional[int] = None, args: Optional[dict] = None):
        """Context-manager span.  With no explicit ids it parents under
        the thread's current ambient span — and records nothing when
        there is none, so instrumented library code (matcher, mesh) is
        inert outside a traced pipeline batch."""
        if not self.enabled:
            return NOOP_SPAN
        if trace_id is None or parent is None:
            stack = self._ambient.__dict__.get("stack")
            top = stack[-1] if stack else None
            if trace_id is None:
                if top is None:
                    return NOOP_SPAN
                trace_id = top.trace_id
            if parent is None:
                parent = top.span_id if top is not None else 0
        if not trace_id:
            return NOOP_SPAN
        return Span(self, name, trace_id, parent, args)

    def instant(self, name: str, args: Optional[dict] = None,
                trace_id: int = 0) -> None:
        """Point event (shed, breaker trip, fallback): zero duration,
        recorded even without a trace id so stream-level events (an
        admission-buffer shed belongs to no single batch) still land in
        the ring."""
        if not self.enabled:
            return
        t0_us = (time.perf_counter() - self._epoch) * 1e6
        rec = (trace_id, next(self._ids), 0, name, t0_us, None,
               threading.current_thread().name, dict(args) if args else None)
        self._put(rec)

    def current_trace_id(self) -> int:
        """Trace id of the thread's ambient span (0 when none / off) —
        lets passive observers (the provenance ledger) attribute an
        effect to the admitting batch without any id plumbing."""
        if not self.enabled:
            return 0
        stack = self._ambient.__dict__.get("stack")
        return stack[-1].trace_id if stack else 0

    # ---- export ----

    def snapshot(self, clear: bool = False) -> List[dict]:
        """Ring contents oldest-first as plain dicts (tests, debugging).

        ``clear=True`` snapshots AND empties the ring in one lock
        section: a span recorded between a separate dump and clear would
        be silently dropped, and two concurrent clearing dumps could
        each report the same span — /debug/trace?clear=1 uses this
        atomic form (tests/unit/test_trace.py hammers it)."""
        with self._lock:
            n = self._n
            if n <= self.ring_size:
                recs = [r for r in self._ring[:n]]
            else:
                cut = n % self.ring_size
                recs = self._ring[cut:] + self._ring[:cut]
            if clear:
                self._ring = [None] * self.ring_size
                self._n = 0
        out = []
        for r in recs:
            if r is None:
                continue
            tid, sid, pid, name, t0_us, dur_us, thread, args = r
            out.append({
                "trace_id": tid, "span_id": sid, "parent_id": pid,
                "name": name, "t0_us": t0_us, "dur_us": dur_us,
                "thread": thread, "args": args or {},
            })
        return out

    def export_chrome(self, clear: bool = False) -> dict:
        """Chrome trace_event JSON (Perfetto / chrome://tracing).

        Complete ('X') events for spans, instant ('i') events for
        annotations; one virtual pid, one tid per recorded thread name
        with 'M' metadata naming the track.  Span/trace ids ride in
        args so Perfetto's query surface can join parent/child.
        ``clear=True`` drains the ring atomically with the read (the
        /debug/trace?clear=1 contract — no span dropped or duplicated
        against a concurrent scrape)."""
        spans = self.snapshot(clear=clear)
        tids: Dict[str, int] = {}
        events = []
        pid = os.getpid()
        for s in spans:
            tid = tids.setdefault(s["thread"], len(tids) + 1)
            args = dict(s["args"])
            args["trace_id"] = s["trace_id"]
            args["span_id"] = s["span_id"]
            if s["parent_id"]:
                args["parent_span_id"] = s["parent_id"]
            ev = {
                "name": s["name"],
                "cat": "banjax",
                "ph": "X" if s["dur_us"] is not None else "i",
                "ts": round(s["t0_us"], 3),
                "pid": pid,
                "tid": tid,
                "args": args,
            }
            if s["dur_us"] is not None:
                ev["dur"] = round(s["dur_us"], 3)
            else:
                ev["s"] = "g"  # global-scope instant
            events.append(ev)
        meta = [
            {"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
             "args": {"name": thread}}
            for thread, tid in tids.items()
        ]
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {
                "tool": "banjax-tpu trace ring",
                "ring_size": self.ring_size,
                "recorded": self._n,
                "epoch_unix": self._epoch_wall,
            },
        }

    def clear(self) -> None:
        with self._lock:
            self._ring = [None] * self.ring_size
            self._n = 0

    # ---- jax profiler bridge ----

    def _enter_jax(self, name: str):
        try:
            import jax

            ctx = jax.profiler.TraceAnnotation(name)
            ctx.__enter__()
            return ctx
        except Exception:  # noqa: BLE001 — the bridge is best-effort
            return None

    def step_annotation(self, trace_id: int):
        """StepTraceAnnotation for one batch's device submit (xprof
        groups device work per step).  Returns a context manager; a
        no-op one when the bridge is off or jax is unavailable."""
        if not (self.enabled and self.jax_annotations and trace_id):
            return NOOP_SPAN
        try:
            import jax

            return jax.profiler.StepTraceAnnotation(
                "banjax-batch", step_num=trace_id
            )
        except Exception:  # noqa: BLE001
            return NOOP_SPAN


# ---- the submit stage's lap clock -----------------------------------------


class LapClock:
    """The submit stage of one thread, split into SUBMIT_PHASES by marks.

    One clock a thread, for the thread's life: `start(rows)` opens a
    batch's stage, `mark(phase)` says what the thread does from now on,
    and the wall (`time.perf_counter`) since the last mark goes to the
    phase that was running.  No nesting: the phases partition the time
    from a start to the last mark after it by construction, and `other`
    is what runs before the first mark and after a `mark("other")`.
    Beside the wall the stage has the seconds its thread ran, `cpu_s`:
    the thread's own CPU clock (`time.thread_time`) read at a start and
    at the end of the block that follows it, and nowhere between — it is
    a system call, 17 us under load on a sandboxed kernel, and the
    stage's thread sets the pipeline's rate.  Wall less cpu is the time
    the thread did not run (waiting for the interpreter, a lock, the
    device, a core).  And the calls the thread made into the device
    runtime, `runtime_calls`: every dispatch of a program and every
    explicit transfer, counted where it is made (`runtime_calls()`
    below) — each gives the interpreter up and queues for it again.
    `wall`, `cpu_s` and `runtime_calls` are sums over every batch so far,
    written by the clock's thread alone and read by whoever exports them.

    A mark costs one clock read and one attribute check, and allocates
    nothing; it makes a span only between `under(parent)` and the end of
    that block, where each named phase is a `submit-<phase>` child of
    `parent` (never with tracing off).  A named phase must end — by the
    next mark — before a context-manager span opened before it exits, so
    that the JAX bridge's annotations nest."""

    __slots__ = ("wall", "cpu_s", "runtime_calls", "phase", "t", "_c",
                 "_rows", "_row0", "_parent", "_jax_ctx")

    def __init__(self):
        self.wall = dict.fromkeys(SUBMIT_PHASES, 0.0)
        self.cpu_s = 0.0
        self.runtime_calls = 0
        self._parent = NOOP_SPAN
        self._jax_ctx = None
        self.start()

    def start(self, rows: int = 0) -> float:
        """A batch of `rows` lines enters the stage, in `other`; the wall
        stamp that opens it."""
        self.phase = "other"
        self._rows = rows
        self._row0 = None
        self._c = time.thread_time()
        self.t = time.perf_counter()  # the last mark's wall stamp
        return self.t

    def mark(self, phase: str, row0: Optional[int] = None) -> None:
        t = time.perf_counter()
        was = self.phase
        self.wall[was] += t - self.t
        if self._parent is not NOOP_SPAN:
            self._span_edge(was, phase, t, row0)
        self.phase = phase
        self.t = t

    def under(self, parent) -> "LapClock":
        """`with clock.under(span):` — the named phases marked in the
        block are child spans of `span` (the batch's `submit` span; the
        shared no-op span when tracing is off), and the phase running at
        its end ends with it."""
        self._parent = parent
        return self

    def __enter__(self) -> "LapClock":
        return self

    def __exit__(self, *exc) -> None:
        self.mark("other")
        self._parent = NOOP_SPAN
        self.cpu_s += time.thread_time() - self._c

    def _span_edge(self, was: str, phase: str, t: float, row0) -> None:
        tracer = self._parent.tracer
        if was != "other":
            if self._jax_ctx is not None:
                try:
                    self._jax_ctx.__exit__(None, None, None)
                except Exception:  # noqa: BLE001 — tracing must never raise
                    pass
                self._jax_ctx = None
            args = {"rows": self._rows}
            if self._row0 is not None:
                args["row0"] = self._row0
            tracer.record("submit-" + was, self._parent, self.t, t, args)
        if row0 is not None:
            self._row0 = row0
        if phase != "other" and tracer.jax_annotations:
            self._jax_ctx = tracer._enter_jax("submit-" + phase)


# what a pipeline stage's thread says of itself as it starts: `.stage`,
# the label its waits are counted under, and `.clock`, its lap clock
_this_thread = threading.local()


def stage_thread(stage: str, clock: Optional[LapClock] = None) -> None:
    """Called by a pipeline stage's thread as it starts: `stage` is what
    `thread_stage()` answers on it from now on, and `clock`, where the
    stage has one, what `lap()` does."""
    _this_thread.stage = stage
    if clock is not None:
        _this_thread.clock = clock


def thread_stage() -> Optional[str]:
    """The pipeline stage this thread said it runs; None on any other."""
    return getattr(_this_thread, "stage", None)


def lap() -> LapClock:
    """This thread's lap clock: the one its stage handed over, or — the
    sync entry, a direct call of the split protocol — one of the thread's
    own, made at the first call, which nobody reads."""
    try:
        return _this_thread.clock
    except AttributeError:
        clock = _this_thread.clock = LapClock()
        return clock


def runtime_calls(n: int = 1) -> None:
    """This thread made `n` calls into the device runtime (a program's
    dispatch, an explicit host-to-device transfer): counted on its lap
    clock, so the submit stage's are `banjax_submit_runtime_calls_total`
    and any other thread's are its own affair."""
    lap().runtime_calls += n


# ---- process-wide tracer -------------------------------------------------

_tracer = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _tracer


def configure(enabled: bool, ring_size: int = DEFAULT_RING_SIZE,
              jax_annotations: bool = False) -> Tracer:
    """(Re)configure the process tracer — called by cli.BanjaxApp from
    config (`trace_enabled`, `trace_ring_size`, `trace_jax_annotations`)
    and by tests.  Swaps the module singleton so a disabled tracer keeps
    its zero-cost fast path (no indirection through a config object)."""
    global _tracer
    _tracer = Tracer(enabled=enabled, ring_size=ring_size,
                     jax_annotations=jax_annotations)
    return _tracer


# module-level delegates: call sites read the CURRENT singleton each time
# so a configure() mid-run (tests, SIGHUP) takes effect everywhere

def enabled() -> bool:
    return _tracer.enabled


def new_trace() -> int:
    return _tracer.new_trace()


def begin(name: str, trace_id: int, parent: int = 0,
          args: Optional[dict] = None):
    return _tracer.begin(name, trace_id, parent, args)


def end(span) -> None:
    _tracer.end(span)


def span(name: str, trace_id: Optional[int] = None,
         parent: Optional[int] = None, args: Optional[dict] = None):
    return _tracer.span(name, trace_id, parent, args)


def instant(name: str, args: Optional[dict] = None, trace_id: int = 0) -> None:
    _tracer.instant(name, args, trace_id)


def current_trace_id() -> int:
    return _tracer.current_trace_id()


def step_annotation(trace_id: int):
    return _tracer.step_annotation(trace_id)
