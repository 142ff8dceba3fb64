"""The window table's maintenance, two carriers (PR 49).

A fused chunk's program carries the table's queued evictions and restores
as two operands and runs `windows._evict` and `windows._restore` at its
head; every other caller — and a run that does not fit the operands —
dispatches `_evict_step` and `_restore_step` by themselves, as every run
did before.  Here the same seeded stream goes through two matchers: the one
under test, and a twin whose fused dispatches are refused the carrier, so
that every maintenance run of it is the separate steps in front of a chunk
that carries padding.  After every call the two must agree bit for bit —
the `DeviceWindowState` arrays, the host shadow, the `ConsumeLineResult`s
and the ban log's bytes — over:

  * evictions and refills in every batch (a table much smaller than the
    stream's addresses, the warm tier behind it), among them a slot
    evicted, reassigned and restored inside one maintenance run;
  * a restore burst past a chunk's room (the test shrinks the room and
    `_RESTORE_CHUNK` to one key, so that two returning addresses are a
    burst): the run goes whole as the separate steps; and a room of three
    such chunks, of which the program runs the last two only where a key
    lies in them;
  * a chunk that overflows right after an eviction: it commits no window
    event, and the evictions and restores it carried apply all the same;
  * a batch cut into several chunks by its long rows: every chunk's slots
    are placed before the first dispatch, so the first chunk meets more
    evicted slots than it has rows.
"""

import time

import numpy as np
import pytest

from banjax_tpu.matcher import windows as W
from banjax_tpu.obs import trace
from tests import shadow_access
from tests.differential.test_one_drive_differential import (
    BATCH, _build, _settled, _stream,
)
from tests.differential.test_tpu_matcher import result_key

STATE_FIELDS = ("hits", "start_s", "start_ns", "key_gen", "slot_gen",
                "ip_seen")


def _separate_steps(m):
    """Refuse `m`'s fused dispatches the carrier: a maintenance run in
    front of a chunk is the separate steps (what `_run_maintenance_locked`
    does for a caller without a program), and the chunk carries padding."""
    dw = m.device_windows
    real = dw._run_maintenance_locked

    def run(carry_rows=None):
        real()
        return None if carry_rows is None else real(carry_rows)

    dw._run_maintenance_locked = run


def _watch(m, seen):
    """Note, for every maintenance run a fused dispatch asks for, what the
    carrier took: (evicted slots, restored slots) as the operands hold
    them, beside the table's own count of the run's carrier."""
    dw = m.device_windows
    real = dw._run_maintenance_locked

    def run(carry_rows=None):
        before = dict(dw.maintenance_carried)
        got = real(carry_rows)
        if carry_rows is not None:
            ev, rows = got
            own = dw.maintenance_carried["own"] - before["own"]
            seen.append((ev[ev < dw.capacity].tolist(),
                         rows[0][rows[0] < dw.capacity].tolist(), own))
        return got

    dw._run_maintenance_locked = run


def _device_state(m):
    state = m.device_windows._state
    return {f: np.asarray(getattr(state, f)) for f in STATE_FIELDS}


CASES = {
    # name: (build overrides, stream kwargs, calls of how many lines,
    #        (_RESTORE_CHUNK, a chunk's room) for the case)
    "evictions-and-refills-every-batch": (
        {"matcher_window_capacity": 64}, {"one_shot": 0.5}, [64] * 8, None),
    "restore-burst-past-one-chunk": (
        {"matcher_window_capacity": 64}, {"one_shot": 0.5}, [64] * 8, (1, 1)),
    "restores-past-the-first-chunk-of-the-room": (
        {"matcher_window_capacity": 64}, {"one_shot": 0.5}, [64] * 8, (1, 3)),
    "overflow-right-after-an-eviction": (
        {"matcher_window_capacity": 64, "matcher_prefilter_cand_frac": 0.125},
        {"one_shot": 0.5}, [64] * 6, None),
    "cut-by-long-rows": (
        {"matcher_window_capacity": 96},
        {"one_shot": 0.5, "long_share": 0.5}, [64] * 8, None),
    "no-warm-tier-shadow-restores": (
        {"matcher_window_capacity": 64, "warm_tier_enabled": False},
        {"one_shot": 0.5}, [64] * 8, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_carrier_leaves_what_the_separate_steps_leave(
        case, monkeypatch):
    over, stream_kw, calls, restore = CASES[case]
    if restore is not None:
        monkeypatch.setattr(W, "_RESTORE_CHUNK", restore[0])
        monkeypatch.setattr(W, "_restore_room", lambda rows: restore[1])
    now = time.time()
    lines = _stream(now, sum(calls), seed=len(case), **stream_kw)
    fused, fused_log = _build(**over)
    apart, apart_log = _build(**over)
    _separate_steps(apart)
    seen = []
    _watch(fused, seen)
    at = 0
    for n in calls:
        part = lines[at:at + n]
        at += n
        got = fused.consume_lines(part, now_unix=now)
        want = apart.consume_lines(part, now_unix=now)
        for i, (a, b) in enumerate(zip(got, want)):
            assert result_key(a) == result_key(b), (case, at, i, part[i][:90])
        assert fused_log.getvalue() == apart_log.getvalue()
        a, b = _device_state(fused), _device_state(apart)
        for f in STATE_FIELDS:
            assert np.array_equal(a[f], b[f]), (case, at, f)
        dw, dwa = fused.device_windows, apart.device_windows
        assert dw.format_states() == dwa.format_states()
        assert shadow_access.shadow(dw) == shadow_access.shadow(dwa)
        _settled(fused), _settled(apart)
    assert fused_log.getvalue(), "the stream bans"
    assert fused.fallback_batches == 0 and fused.breaker._failures == 0

    # the case went the way its name says
    dw, dwa = fused.device_windows, apart.device_windows
    fw = fused._fw_pipeline
    assert fused.pipelined_fused_chunks == apart.pipelined_fused_chunks
    assert fused.pipelined_fused_fallbacks == apart.pipelined_fused_fallbacks
    assert dw.eviction_count == dwa.eviction_count > 0
    assert dw.maintenance_steps == dwa.maintenance_steps
    assert dwa.maintenance_carried["fused"] == 0
    assert dw.maintenance_carried["fused"] > 0
    carried = [(ev, rs) for ev, rs, own in seen if not own and (ev or rs)]
    assert any(ev and rs for ev, rs in carried), "evictions beside restores"
    if case == "evictions-and-refills-every-batch":
        # a slot evicted, handed to a returning address and restored, all
        # inside one run: the restore is stamped with the bumped generation
        assert any(set(ev) & set(rs) for ev, rs in carried)
        assert dw.warm_refills > 0
        assert dw.maintenance_carried["own"] == 0
    if case == "restore-burst-past-one-chunk":
        # a run with more live keys than the operand holds went whole as
        # the separate steps, and its chunk carried padding
        assert any(own and not ev and not rs for ev, rs, own in seen)
        assert dw.maintenance_carried["own"] > 0
    if case == "restores-past-the-first-chunk-of-the-room":
        # the program restores past its first chunk only where a key lies
        # there: runs with one key, and runs with two or three, all carried
        assert {len(rs) for _, rs in carried} >= {1, 2}
        assert max(len(rs) for _, rs in carried) <= 3
    if case == "overflow-right-after-an-eviction":
        # every chunk of the case: none committed an event, each carried
        # its evictions and restores, and the classic replay found them
        assert fw.overflow_causes["candidates"] == len(calls)
        assert fused.pipelined_fused_fallbacks == len(calls)
    else:
        assert fused.pipelined_fused_chunks >= len(calls)
    if case == "cut-by-long-rows":
        assert fw.overflow_causes["long_rows"] > 0
    if case == "no-warm-tier-shadow-restores":
        assert dw.warm_refills == 0


def test_a_failed_dispatch_does_not_lose_the_maintenance_it_carried():
    """The operands leave the table's queues when they are handed over: a
    fused program that raises has them run as the separate steps before
    the failure goes up, so the table still is what its slot manager and
    its shadow say — no slot shows a previous owner's counters."""
    now = time.time()
    lines = _stream(now, 5 * BATCH, seed=3, one_shot=0.5)
    m, log = _build(matcher_window_capacity=64)
    for s in range(0, 3 * BATCH, BATCH):
        m.consume_lines(lines[s:s + BATCH], now_unix=now)
    fw, dw = m._fw_pipeline, m.device_windows
    progs = dict(fw._progs)
    assert progs

    def boom(*a):
        raise RuntimeError("injected: the fused dispatch fails")

    for key, (fn, *caps) in progs.items():
        fw._progs[key] = (boom, *caps)
    evictions, calls = dw.eviction_count, trace.lap().runtime_calls
    # the batch is re-run on the CPU reference: the device took none of
    # its events, only the maintenance its placement had queued
    m.consume_lines(lines[3 * BATCH:4 * BATCH], now_unix=now)
    assert m.breaker._failures == 1
    assert dw.eviction_count > evictions
    assert not dw._pending_evict and not dw._pending_restore
    # an evict step and a restore step, each a transfer and a dispatch
    assert trace.lap().runtime_calls - calls == 4
    fw._progs.update(progs)
    _settled(m)
    m.consume_lines(lines[4 * BATCH:], now_unix=now)

    owner = dw.slot_addresses()
    shadow = shadow_access.shadow(dw)
    state = _device_state(m)
    n_rules = len(state["hits"]) // dw.capacity
    valid = (state["key_gen"].reshape(dw.capacity, n_rules)
             == state["slot_gen"][:, None])
    hits = state["hits"].reshape(dw.capacity, n_rules)
    assert valid.any()
    for slot in np.flatnonzero(valid.any(axis=1)).tolist():
        record = shadow.get(owner.get(slot), {})
        for rule in np.flatnonzero(valid[slot]).tolist():
            assert rule in record, (slot, owner.get(slot), rule)
            assert record[rule][0] == hits[slot, rule]
