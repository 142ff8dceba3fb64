"""obs/trace.py: the ring-buffered span recorder.

Covers the no-op fast path (disabled tracing must allocate nothing and
record nothing), ambient parenting, cross-thread begin/end, ring wrap,
instant events, and the Chrome trace_event export contract Perfetto
needs (X/i phases, thread_name metadata, parent ids in args)."""

import json
import threading

import pytest

from banjax_tpu.obs import trace


@pytest.fixture()
def tracer():
    t = trace.configure(enabled=True, ring_size=64)
    yield t
    trace.configure(enabled=False)


def test_disabled_tracer_is_noop_everywhere():
    trace.configure(enabled=False)
    assert trace.new_trace() == 0
    assert trace.begin("admission", 0) is trace.NOOP_SPAN
    assert trace.span("encode") is trace.NOOP_SPAN
    assert trace.span("encode", 7, 3) is trace.NOOP_SPAN
    # the noop span is inert as a context manager and as a note sink
    with trace.span("x") as sp:
        sp.note("k", "v")
    trace.instant("shed", {"lines": 3})
    trace.end(trace.NOOP_SPAN)
    assert trace.get_tracer().snapshot() == []


def test_span_parenting_explicit_and_ambient(tracer):
    tid = tracer.new_trace()
    root = tracer.begin("admission", tid)
    with tracer.span("encode", tid, parent=root.span_id) as enc:
        with tracer.span("encode-shard") as shard:  # ambient parent
            shard.note("rows", 10)
    tracer.end(root)
    spans = {s["name"]: s for s in tracer.snapshot()}
    assert set(spans) == {"admission", "encode", "encode-shard"}
    assert spans["encode"]["parent_id"] == spans["admission"]["span_id"]
    assert spans["encode-shard"]["parent_id"] == spans["encode"]["span_id"]
    assert all(s["trace_id"] == tid for s in spans.values())
    assert spans["encode-shard"]["args"]["rows"] == 10
    # record order: children complete before parents
    names = [s["name"] for s in tracer.snapshot()]
    assert names.index("encode-shard") < names.index("encode")


def test_ambient_span_without_parent_records_nothing(tracer):
    # library instrumentation (matcher/mesh) outside a traced batch
    with tracer.span("program-ab-fused") as sp:
        assert sp is trace.NOOP_SPAN
    assert tracer.snapshot() == []


def test_cross_thread_begin_end(tracer):
    tid = tracer.new_trace()
    root = tracer.begin("admission", tid, args={"items": 5})
    done = threading.Event()

    def drain_thread():
        root.note("ok", True)
        tracer.end(root)
        done.set()

    t = threading.Thread(target=drain_thread)
    t.start()
    t.join(5)
    assert done.is_set()
    (span,) = tracer.snapshot()
    assert span["name"] == "admission"
    assert span["args"] == {"items": 5, "ok": True}
    assert span["dur_us"] >= 0


def test_ring_wraps_keeping_newest():
    tracer = trace.configure(enabled=True, ring_size=16)
    try:
        tid = tracer.new_trace()
        for i in range(50):
            with tracer.span(f"s{i}", tid, parent=0):
                pass
        spans = tracer.snapshot()
        assert len(spans) == 16
        assert [s["name"] for s in spans] == [f"s{i}" for i in range(34, 50)]
    finally:
        trace.configure(enabled=False)


def test_instant_events_and_clear(tracer):
    tracer.instant("breaker-trip", {"breaker": "matcher-device"})
    tracer.instant("shed", {"lines": 100}, trace_id=3)
    events = tracer.snapshot()
    assert [e["name"] for e in events] == ["breaker-trip", "shed"]
    assert all(e["dur_us"] is None for e in events)
    assert events[1]["trace_id"] == 3
    tracer.clear()
    assert tracer.snapshot() == []


def test_chrome_export_contract(tracer):
    tid = tracer.new_trace()
    root = tracer.begin("admission", tid)
    with tracer.span("drain", tid, parent=root.span_id):
        pass
    tracer.end(root)
    tracer.instant("shed", {"lines": 2})
    out = tracer.export_chrome()
    json.dumps(out)  # must be JSON-serializable as-is
    events = out["traceEvents"]
    metas = [e for e in events if e["ph"] == "M"]
    xs = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    assert metas and all(e["name"] == "thread_name" for e in metas)
    assert {e["name"] for e in xs} == {"admission", "drain"}
    assert all("dur" in e and "ts" in e for e in xs)
    drain = next(e for e in xs if e["name"] == "drain")
    adm = next(e for e in xs if e["name"] == "admission")
    assert drain["args"]["parent_span_id"] == adm["args"]["span_id"]
    assert instants[0]["name"] == "shed"
    assert instants[0]["s"] == "g"
    assert out["otherData"]["ring_size"] == 64


def test_concurrent_recording_is_consistent(tracer):
    """Many threads recording concurrently: no crash, every surviving
    record well-formed (the lock-cheap claim's sanity check)."""
    def worker(k):
        for i in range(200):
            tid = tracer.new_trace()
            root = tracer.begin("admission", tid)
            with tracer.span("encode", tid, parent=root.span_id):
                pass
            tracer.end(root)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    spans = tracer.snapshot()
    assert len(spans) == 64  # full ring
    for s in spans:
        assert s["name"] in ("admission", "encode")
        assert s["span_id"] > 0
        assert s["dur_us"] is not None


def test_step_annotation_noop_paths(tracer):
    # bridge off: shared noop
    assert tracer.step_annotation(5) is trace.NOOP_SPAN
    t2 = trace.configure(enabled=True, ring_size=32, jax_annotations=True)
    try:
        ctx = t2.step_annotation(5)
        with ctx:  # jax present in this env: real annotation; else noop
            pass
        assert t2.step_annotation(0) is trace.NOOP_SPAN
    finally:
        trace.configure(enabled=False)


def test_atomic_snapshot_clear_drains_exactly_once():
    tracer = trace.configure(enabled=True, ring_size=128)
    try:
        tid = tracer.new_trace()
        with tracer.span("drain", tid, parent=0):
            pass
        first = tracer.snapshot(clear=True)
        assert [s["name"] for s in first] == ["drain"]
        assert tracer.snapshot() == []  # the clear emptied the ring
    finally:
        trace.configure(enabled=False)


def test_clear_during_concurrent_dump_no_drop_or_dup():
    """Regression (ISSUE 6 satellite): /debug/trace?clear=1 racing a
    concurrent scrape must neither drop nor duplicate spans.  Writers
    record spans with unique ids while two dumper threads hammer the
    atomic snapshot(clear=True); every span id must surface in exactly
    one dump."""
    tracer = trace.configure(enabled=True, ring_size=16384)
    try:
        n_writers, per_writer = 2, 1500  # total 3000 << ring: no wrap loss
        seen = []
        seen_lock = threading.Lock()
        stop = threading.Event()

        def writer():
            tid = tracer.new_trace()
            for _ in range(per_writer):
                with tracer.span("drain", tid, parent=0):
                    pass

        def dumper():
            while not stop.is_set():
                spans = tracer.snapshot(clear=True)
                if spans:
                    with seen_lock:
                        seen.extend(s["span_id"] for s in spans)

        dumpers = [threading.Thread(target=dumper) for _ in range(2)]
        writers = [threading.Thread(target=writer) for _ in range(n_writers)]
        for t in dumpers + writers:
            t.start()
        for t in writers:
            t.join(30)
        stop.set()
        for t in dumpers:
            t.join(10)
        # final drain for anything recorded after the dumpers stopped
        seen.extend(s["span_id"] for s in tracer.snapshot(clear=True))

        total = n_writers * per_writer
        assert len(seen) == total, "a clear dropped or duplicated spans"
        assert len(set(seen)) == total  # exactly-once, no duplicates
    finally:
        trace.configure(enabled=False)


# ---- the submit stage's lap clock ------------------------------------------


def _spin(seconds):
    import time

    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


_MARK_ORDERS = [
    ["pass", "sketch", "operands", "maintenance", "dispatch", "other"],
    ["dispatch", "pass", "pass", "other", "sketch", "pass"],   # repeats
    ["other", "other"],
    [],                                                        # no mark
]


@pytest.mark.parametrize("order", _MARK_ORDERS,
                         ids=["in-order", "any-order", "other-only", "none"])
def test_lap_clock_partitions_its_total(order):
    """Marks in any order: the phases' seconds sum to the wall between
    the clock's start and the end of its block, over as many batches as
    the clock has seen, and the thread never ran longer than that wall
    (plus the clocks' tick).  What passes between one batch's end and the
    next one's start is in no phase and in no CPU second."""
    import time

    trace.configure(enabled=False)
    clock = trace.LapClock()
    wall = 0.0
    for rows in (7, 9):
        c0, t0 = time.thread_time(), time.perf_counter()
        cpu_before = clock.cpu_s
        start = clock.start(rows)
        with clock.under(trace.NOOP_SPAN):
            for phase in order:
                clock.mark(phase)
                _spin(0.002)
        t1, c1 = time.perf_counter(), time.thread_time()
        assert clock.phase == "other"
        # by construction: from the start to the last mark after it
        wall += clock.t - start
        assert sum(clock.wall.values()) == pytest.approx(wall, abs=1e-9)
        # and that is the time this test saw pass around it
        assert 0.002 * len(order) <= clock.t - start <= t1 - t0
        # the thread spun all the while: its CPU seconds are the wall's
        ran = clock.cpu_s - cpu_before
        assert ran <= c1 - c0 and ran <= clock.t - start + 1e-4
        assert ran >= 0.5 * 0.002 * len(order)
        _spin(0.002)    # between two batches: nobody's
    assert set(clock.wall) == set(trace.SUBMIT_PHASES)
    for phase in trace.SUBMIT_PHASES:
        if phase not in order and phase != "other":
            assert clock.wall[phase] == 0.0


def test_lap_clock_sees_a_thread_that_waits():
    """Wall less cpu is the time the thread did not run: a sleep is wall
    and no cpu."""
    import time

    clock = trace.LapClock()
    clock.start(1)
    with clock.under(trace.NOOP_SPAN):
        clock.mark("dispatch")
        time.sleep(0.05)
    assert clock.wall["dispatch"] >= 0.045
    assert clock.cpu_s < 0.02 <= sum(clock.wall.values()) - clock.cpu_s


def test_a_thread_has_one_lap_clock_for_its_life():
    """`lap()` answers with the clock the thread's stage handed over
    (`stage_thread`), which is also how the thread says what stage it
    runs; a thread outside a scheduler gets one clock of its own at the
    first call and the same one ever after — no clock a call."""
    import threading

    got = {}

    def stage():
        mine = trace.LapClock()
        got["before"] = trace.thread_stage()
        trace.stage_thread("submit", mine)
        got["staged"] = (trace.lap() is mine, trace.thread_stage())

    def outsider():
        first = trace.lap()
        got["outsider"] = (trace.lap() is first, trace.thread_stage())
        got["outsider_clock"] = first

    def drain():
        trace.stage_thread("drain")     # a stage with no clock to hand over
        got["drain"] = (trace.thread_stage(), trace.lap() is trace.lap())

    for fn in (stage, outsider, drain):
        t = threading.Thread(target=fn)
        t.start()
        t.join(10)
    assert got["before"] is None
    assert got["staged"] == (True, "submit")
    assert got["outsider"] == (True, None)
    assert got["drain"] == ("drain", True)
    assert trace.lap() is not got["outsider_clock"]


def test_lap_clock_with_tracing_off_records_nothing():
    """Off: no span object, no ring record, no span id spent, nothing
    allocated — a mark is one clock read and one attribute check."""
    import tracemalloc

    tracer = trace.configure(enabled=False)
    ids_before = next(tracer._ids)
    clock = trace.LapClock()
    with trace.span("submit", 0, parent=0) as sp:
        assert sp is trace.NOOP_SPAN
        clock.start(3)
        with clock.under(sp):
            for phase in trace.SUBMIT_PHASES:
                clock.mark(phase, row0=0)
    assert tracer.snapshot() == [] and tracer._n == 0
    assert next(tracer._ids) == ids_before + 1
    assert clock._jax_ctx is None and clock._parent is trace.NOOP_SPAN
    assert sum(clock.wall.values()) > 0
    # a batch of marks leaves no object behind (floats replace floats)
    tracemalloc.start()
    try:
        clock.start(3)
        clock.mark("pass")      # tracemalloc's own first-call bookkeeping
        before = tracemalloc.take_snapshot()
        for _ in range(200):
            clock.start(3)
            with clock.under(trace.NOOP_SPAN):
                for phase in trace.SUBMIT_PHASES:
                    clock.mark(phase, row0=0)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = sum(st.size_diff for st in after.compare_to(before, "filename")
                if st.traceback[0].filename == trace.__file__)
    assert grown < 200 * 16, grown


def test_lap_clock_phases_are_child_spans_of_the_submit_span(tracer):
    """On: each named phase is a `submit-<phase>` span under the batch's
    `submit` span — its trace id, its thread, the batch's rows and the
    chunk's row0 — back to back inside it; `other` makes none."""
    tid = tracer.new_trace()
    clock = trace.LapClock()
    clock.start(40)
    with tracer.span("submit", tid, parent=0) as sp:
        with clock.under(sp):
            clock.mark("pass")
            clock.mark("other")
            clock.mark("operands", row0=16)
            clock.mark("dispatch")
    spans = tracer.snapshot()
    assert [s["name"] for s in spans] == [
        "submit-pass", "submit-operands", "submit-dispatch", "submit"]
    submit = spans[-1]
    for s in spans[:-1]:
        assert s["parent_id"] == submit["span_id"]
        assert s["trace_id"] == tid and s["thread"] == submit["thread"]
        assert s["args"]["rows"] == 40
        assert s["t0_us"] >= submit["t0_us"]
        assert (s["t0_us"] + s["dur_us"]
                <= submit["t0_us"] + submit["dur_us"] + 1e-3)
    assert "row0" not in spans[0]["args"]
    assert spans[1]["args"]["row0"] == spans[2]["args"]["row0"] == 16
    assert spans[1]["t0_us"] + spans[1]["dur_us"] == pytest.approx(
        spans[2]["t0_us"], abs=1e-3)
    assert sum(s["dur_us"] for s in spans[:-1]) == pytest.approx(
        1e6 * (sum(clock.wall.values()) - clock.wall["other"]), abs=1.0)
