"""Parallel-host-path faults: shard failures and resolve-ahead aborts.

The sharded encode pool and the depth-2 resolve-ahead drain add two new
failure boundaries; both must degrade per-batch/per-chunk, never wedge
the pool, the order turns, or the accounting invariant
(admitted == processed + shed + drain errors).
"""

import threading
import time

import pytest

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.pipeline import PipelineScheduler
from banjax_tpu.pipeline import scheduler as sched_mod
from banjax_tpu.resilience import failpoints
from tests.mock_banner import MockBanner

RULES_YAML = r"""
regexes_with_rates:
  - decision: nginx_block
    rule: r1
    regex: 'GET /attack.*'
    interval: 5
    hits_per_interval: 0
"""


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.disarm()
    yield
    failpoints.disarm()


@pytest.fixture(autouse=True)
def _small_shards(monkeypatch):
    monkeypatch.setattr(sched_mod, "_MIN_SHARD_LINES", 8)


class _Sink:
    def __init__(self):
        self._lock = threading.Lock()
        self.lines = []
        self.results = []

    def __call__(self, lines, results):
        with self._lock:
            self.lines.extend(lines)
            if results is not None:
                self.results.extend(results)


def build(device_windows=False, **cfg_overrides):
    cfg = config_from_yaml_text(RULES_YAML)
    cfg.matcher_device_windows = device_windows
    for k, v in cfg_overrides.items():
        setattr(cfg, k, v)
    states = RegexRateLimitStates()
    banner = MockBanner()
    m = TpuMatcher(cfg, banner, StaticDecisionLists(cfg), states)
    return m, banner


def run_stream(m, n_chunks=12, chunk=25, **sched_kw):
    now = time.time()
    sink = _Sink()
    sched = PipelineScheduler(
        lambda: m, on_results=sink, now_fn=lambda: now, **sched_kw
    )
    sched.start()
    lines = []
    for c in range(n_chunks):
        batch = [
            f"{now:.6f} 9.9.{c}.{i} GET h.com GET /attack HTTP/1.1 ua -"
            for i in range(chunk)
        ]
        lines.extend(batch)
        sched.submit(batch)
    assert sched.flush(120)
    sched.stop()
    return lines, sink, sched


def assert_accounted(sched, sink, lines):
    s = sched.stats
    assert s.admitted_lines == len(lines)
    assert s.admitted_lines == (
        s.processed_lines + s.shed_lines + s.drain_error_lines
    )
    assert len(sink.results) == s.processed_lines


def test_encode_shard_failpoint_fails_batch_not_pool(caplog):
    """A failing shard worker (pipeline.encode_shard) fails only its
    batch — which then drains GENERICALLY, losing nothing — and the pool
    keeps sharding later batches."""
    m, banner = build()
    failpoints.arm("pipeline.encode_shard", count=2)
    lines, sink, sched = run_stream(m, encode_workers=3)
    assert_accounted(sched, sink, lines)
    assert sched.stats.processed_lines == len(lines)  # zero lost
    assert len(banner.regex_ban_logs) == len(lines)
    # the pool survived: with the failpoint exhausted, a second stream
    # through a fresh scheduler (same matcher) shards normally
    lines2, sink2, sched2 = run_stream(m, encode_workers=3)
    assert_accounted(sched2, sink2, lines2)
    assert sched2.stats.encode_sharded_batches > 0, (
        "pool never recovered after the shard fault"
    )


def test_encode_shard_failpoint_every_batch_still_no_loss():
    """Worst case: EVERY sharded batch loses a shard — everything drains
    generically, nothing is lost, the scheduler never wedges."""
    m, banner = build()
    failpoints.arm("pipeline.encode_shard")  # unbounded
    lines, sink, sched = run_stream(m, encode_workers=3)
    assert_accounted(sched, sink, lines)
    assert sched.stats.processed_lines == len(lines)
    assert len(banner.regex_ban_logs) == len(lines)


def test_sharded_encode_with_device_windows_accounts():
    """Sharded encode feeding the fused path under churny
    small batches: accounting holds and effects all fire."""
    m, banner = build(device_windows=True)
    failpoints.arm("pipeline.encode_shard", count=1)
    lines, sink, sched = run_stream(m, encode_workers=2)
    assert_accounted(sched, sink, lines)
    assert sched.stats.processed_lines == len(lines)
    assert len(banner.regex_ban_logs) == len(lines)


def test_resolve_abort_frees_turns():
    """matcher.resolve armed mid-stream: the aborted chunk's lines are
    marked error, but its order turn is swept (fused_windows dead-turn
    sweep) so every later chunk and batch keeps draining — a leaked turn
    would hang the flush."""
    m, banner = build(
        device_windows=True,
        matcher_batch_lines=64,
        matcher_prefilter_cand_frac=1.0,
    )
    failpoints.arm("matcher.resolve", count=3)
    lines, sink, sched = run_stream(m, n_chunks=10, chunk=80,
                                    encode_workers=0)
    assert_accounted(sched, sink, lines)
    # aborted chunks' lines are error-marked results, not silent losses
    assert sched.stats.processed_lines == len(lines)
    n_err = sum(1 for r in sink.results if r.error)
    assert n_err > 0, "the armed resolve fault never fired"
    # every non-errored attack line still banned
    assert len(banner.regex_ban_logs) == len(lines) - n_err
    # the fused pipeline is idle: no order turn leaked
    assert m._fw_pipeline.idle()


def test_resolve_abort_then_recovery():
    """After mid-pipeline resolve aborts, the SAME matcher keeps
    committing fused chunks (the turn counter advanced past the dead
    seqs)."""
    m, _ = build(
        device_windows=True,
        matcher_batch_lines=64,
        matcher_prefilter_cand_frac=1.0,
    )
    failpoints.arm("matcher.resolve", count=2)
    run_stream(m, n_chunks=6, chunk=80, encode_workers=0)
    before = m.pipelined_fused_chunks
    lines, sink, sched = run_stream(m, n_chunks=6, chunk=80,
                                    encode_workers=0)
    assert_accounted(sched, sink, lines)
    assert all(not r.error for r in sink.results)
    assert m.pipelined_fused_chunks > before, (
        "fused path did not recover after the aborts"
    )
    assert m._fw_pipeline.idle()


def test_command_flood_bounded_by_command_take_max():
    """A Kafka-style command flood takes batches of at most
    pipeline_command_take_max messages, so line batches interleave
    instead of starving behind one giant command dispatch."""
    m, _ = build()
    now = time.time()
    sink = _Sink()
    sched = PipelineScheduler(
        lambda: m, on_results=sink, now_fn=lambda: now,
        command_take_max=16,
    )
    seen_sizes = []
    handled = []
    lock = threading.Lock()

    def handler(raw):
        with lock:
            handled.append(raw)

    orig_put = sched._q_dev.put

    def spy_put(batch):
        if batch is not None and getattr(batch, "kind", None) == "cmd":
            seen_sizes.append(len(batch.lines))
        orig_put(batch)

    sched._q_dev.put = spy_put
    sched.start()
    sched.submit_commands([b"cmd%d" % i for i in range(400)], handler)
    lines = [
        f"{now:.6f} 1.1.1.{i} GET h.com GET /x HTTP/1.1 ua -"
        for i in range(50)
    ]
    sched.submit(lines)
    assert sched.flush(60)
    sched.stop()
    assert len(handled) == 400
    assert seen_sizes and max(seen_sizes) <= 16, seen_sizes
    s = sched.stats
    assert s.admitted_lines == 450
    assert s.processed_lines == 450
    assert s.command_items == 400
