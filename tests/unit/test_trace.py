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
