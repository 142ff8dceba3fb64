"""The two counters that say a fused chunk is one call into the runtime
(PR 49): `banjax_submit_runtime_calls_total` — every dispatch and every
explicit transfer `pipeline_submit` makes, counted where it is made, on the
submitting thread's lap clock — and the maintenance runs of the window
table by what carried them, `fused` (operands of the chunk's program) or
`own` (the separate steps)."""

import time

import pytest

from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.rate_limit import (
    FailedChallengeRateLimitStates, RegexRateLimitStates,
)
from banjax_tpu.matcher import windows as W
from banjax_tpu.obs import registry, trace
from banjax_tpu.obs.exposition import render_prometheus
from benchmark.harness import prom
from tests.differential.test_one_drive_differential import (
    BATCH, _build, _stream,
)

STEPS = "banjax_device_windows_maintenance_steps_total"
BY_CARRIER = "banjax_device_windows_maintenance_steps_by_carrier_total"

CASES = {
    # name: (build overrides, stream kwargs, the fused path allowed,
    #        _RESTORE_CHUNK and a chunk's room for the case)
    "one-fused-chunk": ({"matcher_window_capacity": 4096}, {}, True, None),
    "chunk-carrying-evictions-and-restores": (
        {"matcher_window_capacity": 64}, {"one_shot": 0.5}, True, None),
    "restore-burst-past-one-chunk": (
        {"matcher_window_capacity": 64}, {"one_shot": 0.5}, True, 1),
    "cut-into-chunks-by-long-rows": (
        {"matcher_window_capacity": 4096}, {"long_share": 0.5}, True, None),
    "classic-prefilter": ({"matcher_window_capacity": 4096}, {}, False, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_pipeline_submit_counts_its_calls_into_the_runtime(case, monkeypatch):
    """One a fused chunk, whatever maintenance it carries; two more for
    each separate step of a run it could not carry (the transfer, the
    step); two a chunk on the classic path (the transfer of the encoded
    rows, the match program's dispatch), as its code has made them all
    along."""
    over, stream_kw, fused_ok, restore_chunk = CASES[case]
    if restore_chunk is not None:
        monkeypatch.setattr(W, "_RESTORE_CHUNK", restore_chunk)
        monkeypatch.setattr(W, "_restore_room", lambda rows: restore_chunk)
    steps, built, building = [], [], []
    for name in ("_evict_step", "_restore_step"):
        real = getattr(W, name)
        monkeypatch.setattr(
            W, name,
            lambda st, op, real=real, name=name: (
                (built if building else steps).append(name),
                real(st, op))[1])
    now = time.time()
    lines = _stream(now, 6 * BATCH, seed=len(case), **stream_kw)
    m, _ = _build(**over)
    dw, lap = m.device_windows, trace.lap()
    real_build = dw.build_maintenance_steps

    def build(max_rows):
        # beside a new fused program: each separate step once, on padding
        building.append(max_rows)
        try:
            real_build(max_rows)
        finally:
            building.pop()

    dw.build_maintenance_steps = build
    deltas = []
    for row0 in range(0, len(lines), BATCH):
        state = m.pipeline_begin(lines[row0:row0 + BATCH], now)
        if not fused_ok:
            state.pop("fused_eligible", None)
        carried = dict(dw.maintenance_carried)
        del steps[:], built[:]
        calls = lap.runtime_calls
        m.pipeline_submit(state, now)
        made = lap.runtime_calls - calls - len(built)
        chunks = len(state.get("fused") or ())
        if fused_ok:
            assert chunks >= 1
            assert made == chunks + 2 * len(steps)
            own = dw.maintenance_carried["own"] - carried["own"]
            assert bool(steps) == bool(own)
        else:
            assert chunks == 0 and state["pend"]["kind"] == "prefilter"
            assert made == 2 * len(state["pend"]["chunks"]) == 2
        deltas.append((made, chunks, list(steps)))
        m.pipeline_collect(state)
        m.pipeline_finish(state, now)
    if case == "one-fused-chunk":
        assert [d[:2] for d in deltas] == [(1, 1)] * 6
        assert dw.maintenance_steps == 0
    if case == "chunk-carrying-evictions-and-restores":
        assert [d[:2] for d in deltas] == [(1, 1)] * 6
        assert dw.maintenance_carried == {
            "fused": dw.maintenance_steps, "own": 0}
        assert dw.eviction_count > 0 and dw.warm_refills > 0
    if case == "restore-burst-past-one-chunk":
        burst = [d for d in deltas if d[2]]
        assert burst and all(
            d[2][0] == "_evict_step" and d[2].count("_restore_step") >= 2
            for d in burst)
        assert dw.maintenance_carried["own"] == len(burst)
    if case == "cut-into-chunks-by-long-rows":
        assert max(d[1] for d in deltas) > 1
        assert m._fw_pipeline.overflow_causes["long_rows"] > 0
    if case == "classic-prefilter":
        # the window applies at the drain: every run the separate steps
        assert m.pipelined_fused_chunks == 0
        assert dw.maintenance_carried["fused"] == 0


@pytest.mark.parametrize("path", ["fused", "classic"])
def test_maintenance_steps_by_carrier_sum_to_the_unlabelled_total(path):
    """`maintenance_steps` — the 29 s line's key and the unlabelled family
    every earlier reader has — stays the count of all runs; the family by
    carrier splits it, and both are on `/metrics` with tracing off."""
    assert {STEPS, BY_CARRIER, "banjax_submit_runtime_calls_total"} <= {
        f.prom for f in registry.FAMILIES}
    assert registry.PROM_FAMILIES[BY_CARRIER].labels == ("carrier",)
    assert not trace.enabled()
    now = time.time()
    lines = _stream(now, 6 * BATCH, seed=7, one_shot=0.5)
    m, _ = _build(matcher_window_capacity=64)
    for row0 in range(0, len(lines), BATCH):
        part = lines[row0:row0 + BATCH]
        if path == "fused":
            m.consume_lines(part, now_unix=now)
        else:
            m.consume_lines_serial(part, now_unix=now)
    dw = m.device_windows
    carrier, other = ("fused", "own") if path == "fused" else ("own", "fused")
    assert dw.maintenance_carried[carrier] > 0
    assert dw.maintenance_carried[other] == 0
    snap = prom.parse(render_prometheus(
        DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
        FailedChallengeRateLimitStates(), matcher=m,
    ))
    by = {c: prom.value(snap, BY_CARRIER, carrier=c)
          for c in ("fused", "own")}
    assert by == {k: float(v) for k, v in dw.maintenance_carried.items()}
    assert sum(by.values()) == prom.value(snap, STEPS) == dw.maintenance_steps
    assert dw.maintenance_steps > 0
