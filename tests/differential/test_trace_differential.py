"""Differential proof of zero behavior change from tracing: a pipelined
run with `trace_enabled: true` produces byte-identical ban-log/effector
output to `trace_enabled: false`, and the recorded trace contains spans
for all five pipeline stages with consistent parent/child/trace ids."""

import random
import threading
import time

import pytest

from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.obs import trace
from banjax_tpu.pipeline import PipelineScheduler
from tests.differential.test_pipeline_differential import (
    ChurnSizer,
    _build,
    _gen_lines,
)
from tests.differential.test_tpu_matcher import result_key


@pytest.fixture(autouse=True)
def _tracer_off_after():
    yield
    trace.configure(enabled=False)


def _run_pipelined(lines, now, device_windows, seed):
    matcher, states, dyn, ban_log = _build(TpuMatcher, device_windows)
    collected = []
    lock = threading.Lock()

    def sink(batch_lines, results):
        with lock:
            collected.append((batch_lines, results))

    sched = PipelineScheduler(
        lambda: matcher, on_results=sink, now_fn=lambda: now
    )
    sched._sizer = ChurnSizer(seed=seed)
    sched.start()
    rng = random.Random(31)
    i = 0
    while i < len(lines):
        step = rng.randrange(1, 90)
        sched.submit(lines[i : i + step])
        i += step
    assert sched.flush(120)
    sched.stop()
    results = {}
    for batch_lines, batch_results in collected:
        if batch_results is None:
            continue
        for line, res in zip(batch_lines, batch_results):
            results.setdefault(line, []).append(result_key(res))
    return results, ban_log.getvalue(), states.format_states()


@pytest.mark.parametrize("device_windows", [False, True])
def test_trace_on_off_byte_identical(device_windows):
    now = time.time()
    lines = _gen_lines(1200, now)

    trace.configure(enabled=False)
    off_results, off_log, off_states = _run_pipelined(
        lines, now, device_windows, seed=7
    )
    trace.configure(enabled=True, ring_size=8192)
    on_results, on_log, on_states = _run_pipelined(
        lines, now, device_windows, seed=7
    )
    assert on_log == off_log          # ban-log bytes identical
    assert on_results == off_results  # per-line result stream identical
    assert on_states == off_states    # rate-limit window state identical
    # and the traced run actually recorded spans
    assert trace.get_tracer().snapshot()


def test_synthetic_run_records_all_five_stages_consistently():
    """Acceptance: spans for admission, encode-shard, submit, collect,
    drain present with parent/child ids consistent per trace."""
    tracer = trace.configure(enabled=True, ring_size=16384)
    now = time.time()
    lines = _gen_lines(600, now)
    matcher, states, dyn, ban_log = _build(TpuMatcher, device_windows=True)
    sched = PipelineScheduler(lambda: matcher, now_fn=lambda: now)
    sched.start()
    for i in range(0, len(lines), 100):
        sched.submit(lines[i : i + 100])
    assert sched.flush(120)
    sched.stop()

    spans = tracer.snapshot()
    by_id = {s["span_id"]: s for s in spans}
    names = {s["name"] for s in spans}
    for stage in ("admission", "encode", "encode-shard", "submit",
                  "collect", "drain"):
        assert stage in names, f"missing {stage} spans; have {sorted(names)}"

    roots = [s for s in spans if s["name"] == "admission"]
    assert roots, "no admission root spans"
    for s in spans:
        if s["dur_us"] is None:
            continue  # instant events carry no parent
        if s["parent_id"]:
            parent = by_id.get(s["parent_id"])
            # parent may have rotated out of the ring only if the ring
            # wrapped; sized here so it never does
            assert parent is not None, f"dangling parent for {s}"
            assert parent["trace_id"] == s["trace_id"], (
                f"span {s['name']} crosses traces: {s} vs {parent}"
            )
        if s["name"] in ("encode", "submit", "collect", "drain"):
            assert by_id[s["parent_id"]]["name"] == "admission", s
        if s["name"] == "encode-shard":
            assert by_id[s["parent_id"]]["name"] == "encode", s
        if s["name"] == "program-ab-fused":
            # the fused program's dispatch belongs to the submit stage
            assert by_id[s["parent_id"]]["name"] == "submit", s
        if s["name"] == "effector-replay":
            assert by_id[s["parent_id"]]["name"] == "drain", s

    # every traced batch has exactly one root whose stages share its id
    for root in roots:
        tid = root["trace_id"]
        stages = [s["name"] for s in spans if s["trace_id"] == tid
                  and s["parent_id"] == root["span_id"]]
        assert "encode" in stages and "drain" in stages, (tid, stages)

    # chrome export of a real run is well-formed and Perfetto-shaped
    import json

    out = tracer.export_chrome()
    json.dumps(out)
    phases = {e["ph"] for e in out["traceEvents"]}
    assert "X" in phases and "M" in phases
