"""Fault composition for the single-kernel fused path + the state-aware
turn-release fix (fused_windows._free_turn / _release_chunk_pins).

The bug being regression-locked: abandon() used to sweep dead turns
unconditionally, so a chunk settled by two paths (a submit-failure
abandon racing a teardown abort, or an abandon after fallback_done)
could mark the same turn dead twice and double-release slot pins — the
double pin release can free a pin held by a DIFFERENT in-flight chunk on
the same slot.  Settlement is tracked per chunk (pins_released /
turn_freed) so every path is idempotent."""

import threading
import time

import pytest

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.pipeline import PipelineScheduler
from banjax_tpu.resilience import failpoints
from tests.mock_banner import MockBanner

RULES_YAML = r"""
regexes_with_rates:
  - decision: nginx_block
    rule: r1
    regex: 'GET /attack.*'
    interval: 5
    hits_per_interval: 2
"""


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.disarm()
    yield
    failpoints.disarm()


def make_matcher(**cfg_overrides):
    cfg = config_from_yaml_text(RULES_YAML)
    cfg.matcher_device_windows = True
    for k, v in cfg_overrides.items():
        setattr(cfg, k, v)
    banner = MockBanner()
    m = TpuMatcher(cfg, banner, StaticDecisionLists(cfg),
                   RegexRateLimitStates())
    assert m._fw_pipeline is not None
    return m, banner


def lines_at(now, n, path="/attack"):
    return [
        f"{now:.6f} 1.2.3.{i % 9} GET h.com GET {path}{i % 3} HTTP/1.1 ua -"
        for i in range(n)
    ]


def mixed_lines(now, n):
    """Mostly-benign mix: the stage-1 gate holds, so fused chunks commit
    instead of overflowing the candidate capacity."""
    return [
        f"{now:.6f} 1.2.3.{i % 9} GET h.com GET "
        f"/{'attack' if i % 13 == 0 else 'page'}{i % 3} HTTP/1.1 ua -"
        for i in range(n)
    ]


def _quiescent(fw):
    """Every turn settled, no dead-turn residue, no leaked pins."""
    with fw._cv:
        assert fw._next_seq == fw._turn, (fw._next_seq, fw._turn)
        assert not fw._dead, fw._dead
    assert (fw.windows._pin_counts == 0).all()


def test_submit_failpoint_settles_turns_once():
    """pipeline.submit fires mid-stream: the failed batch drains
    generically (classic path, no fused turns), LATER fused batches keep
    committing, and the turn counters/dead sets/pins settle exactly —
    the double-sweep would leave dead-set residue or negative-clamped
    pins behind."""
    now = time.time()
    m, _ = make_matcher(matcher_prefilter_cand_frac=1.0)
    collected = []
    lock = threading.Lock()

    def sink(ls, rs):
        with lock:
            collected.append((ls, rs))

    sched = PipelineScheduler(lambda: m, on_results=sink,
                              now_fn=lambda: now)
    sched.start()
    for i in range(0, 200, 40):
        sched.submit(mixed_lines(now, 40))
    assert sched.flush(120)
    failpoints.arm("pipeline.submit", count=1)
    for i in range(0, 200, 40):
        sched.submit(mixed_lines(now, 40))
    assert sched.flush(120)
    sched.stop()

    assert failpoints.fired_count("pipeline.submit") == 1
    snap = sched.stats.peek()
    assert snap["PipelineAdmittedLines"] == \
        snap["PipelineProcessedLines"] + snap["PipelineShedLines"] + \
        snap["PipelineDrainErrorLines"]
    assert m.pipelined_fused_chunks > 0
    _quiescent(m._fw_pipeline)


def test_double_abort_is_idempotent():
    """pipeline_abort called twice on the same un-finished batch (a
    device-failure abort racing a drain-failure abort does exactly this)
    must settle each chunk's turns and pins once; a later batch then
    drains normally."""
    now = time.time()
    m, _ = make_matcher()
    s1 = m.pipeline_begin(lines_at(now, 30), now)
    m.pipeline_submit(s1, now=now)
    entries = list(s1.get("fused") or [])
    assert entries
    # teardown path one: explicit abandon of the first chunk (the
    # submit-failure cleanup), then the full abort sweeps ALL entries —
    # including the already-settled one
    m._fw_pipeline.abandon(entries[0]["pend"])
    s1["fused"] = entries
    m.pipeline_abort(s1)
    s1["fused"] = entries
    m.pipeline_abort(s1)  # and once more, for the race

    s2 = m.pipeline_begin(lines_at(now, 30), now)
    m.pipeline_submit(s2, now=now)
    m.pipeline_collect(s2)
    results, _ = m.pipeline_finish(s2, now)
    assert any(r.rule_results for r in results)
    _quiescent(m._fw_pipeline)


def test_abandon_after_fallback_cannot_double_release_pins():
    """An overflowing chunk's fallback releases its pins via apply_bitmap
    (fallback_done marks them settled); a teardown abandon arriving after
    that must NOT decrement them again — with another batch in flight on
    the same slots, the double release would let the LRU evict pinned
    state."""
    now = time.time()
    # cand_frac 1/64 + all-matching lines: every chunk overflows
    m, _ = make_matcher(
        matcher_batch_lines=64, matcher_prefilter_cand_frac=1.0 / 64,
    )
    lines = [
        f"{now:.6f} 5.5.5.{i % 7} GET h.com GET /attack{i} HTTP/1.1 ua -"
        for i in range(64)
    ]
    s = m.pipeline_begin(lines, now)
    m.pipeline_submit(s, now=now)
    entries = list(s["fused"])
    m.pipeline_collect(s)
    results, _ = m.pipeline_finish(s, now)  # overflow → classic fallback
    assert m._fw_pipeline.fallback_batches > 0
    # teardown replays the settled entries through abandon: a no-op
    for e in entries:
        m._fw_pipeline.abandon(e["pend"])
    _quiescent(m._fw_pipeline)


def test_resolve_failpoint_under_single_kernel_loses_only_its_chunk():
    """matcher.resolve firing at the drain of a single-kernel chunk marks
    only that chunk's lines as errors; later batches drain fine (turns
    freed by the state-aware settlement)."""
    now = time.time()
    m, _ = make_matcher()
    failpoints.arm("matcher.resolve", count=1)
    s1 = m.pipeline_begin(lines_at(now, 20), now)
    m.pipeline_submit(s1, now=now)
    m.pipeline_collect(s1)
    results, _ = m.pipeline_finish(s1, now)
    assert all(r.error for r in results)
    failpoints.disarm()
    s2 = m.pipeline_begin(lines_at(now, 20), now)
    m.pipeline_submit(s2, now=now)
    m.pipeline_collect(s2)
    results2, _ = m.pipeline_finish(s2, now)
    assert any(r.rule_results for r in results2)
    assert not any(r.error for r in results2)
    _quiescent(m._fw_pipeline)
