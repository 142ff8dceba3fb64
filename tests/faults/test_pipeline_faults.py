"""Pipeline stage-boundary faults: the tentpole's no-silent-loss
contract under injected failures.

Every admitted line must be either processed (a result exists for it)
or counted as shed — across encode failures, device submit/collect
failures (which also drive the breaker → CPU-reference drain), drain
failures, and sustained overload.
"""

import threading
import time

import pytest

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.pipeline import PipelineScheduler
from banjax_tpu.resilience import failpoints
from banjax_tpu.resilience.breaker import OPEN
from banjax_tpu.resilience.health import HealthRegistry
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


def build(threshold=3, health=None, device_windows=False):
    cfg = config_from_yaml_text(RULES_YAML)
    cfg.breaker_failure_threshold = threshold
    cfg.matcher_device_windows = device_windows
    states = RegexRateLimitStates()
    banner = MockBanner()
    m = TpuMatcher(
        cfg, banner, StaticDecisionLists(cfg), states, health=health
    )
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
    """The invariant: admitted == processed + shed(+drain errors), and a
    result object exists for every processed line."""
    s = sched.stats
    assert s.admitted_lines == len(lines)
    assert s.admitted_lines == (
        s.processed_lines + s.shed_lines + s.drain_error_lines
    )
    assert len(sink.results) == s.processed_lines


def test_collect_failpoint_loses_nothing(caplog):
    """The acceptance fault: a failpoint in the collect stage — every
    admitted line is still processed (the failed batch re-runs through
    consume_lines on the drain thread; its device dispatch succeeds there,
    so the device is NOT wedged and the breaker rightly stays closed —
    the wedged-device trip is the matcher.device test below)."""
    m, banner = build()
    failpoints.arm("pipeline.collect")
    lines, sink, sched = run_stream(m)
    assert_accounted(sched, sink, lines)
    assert sched.stats.processed_lines == len(lines)  # zero lost
    assert sched.stats.fallback_batches >= 1
    # with hits_per_interval 0 every attack line bans: effects all fired
    assert len(banner.regex_ban_logs) == len(lines)


def test_submit_failpoint_falls_back_without_loss():
    m, banner = build(threshold=2)
    failpoints.arm("pipeline.submit")
    lines, sink, sched = run_stream(m)
    assert_accounted(sched, sink, lines)
    assert sched.stats.processed_lines == len(lines)
    assert sched.stats.fallback_batches >= 1
    assert len(banner.regex_ban_logs) == len(lines)


def test_encode_failpoint_drains_generically():
    m, banner = build()
    failpoints.arm("pipeline.encode", count=3)
    lines, sink, sched = run_stream(m)
    assert_accounted(sched, sink, lines)
    assert sched.stats.processed_lines == len(lines)
    assert sched.stats.fallback_batches >= 1
    # encode failures are host-side: they must NOT charge the breaker
    assert m.breaker.trip_count == 0
    assert len(banner.regex_ban_logs) == len(lines)


def test_drain_failpoint_counts_lines_as_shed():
    m, _ = build()
    failpoints.arm("pipeline.drain", count=1)
    lines, sink, sched = run_stream(m)
    assert_accounted(sched, sink, lines)
    assert sched.stats.drain_error_lines > 0
    assert sched.stats.processed_lines == (
        len(lines) - sched.stats.drain_error_lines
    )


def test_matcher_device_failpoint_open_breaker_drains_ring_via_cpu():
    """A wedged device (matcher.device armed unlimited): the breaker
    opens mid-stream and the remaining ring drains through the CPU
    reference matcher — results keep coming, nothing is lost."""
    health = HealthRegistry()
    m, banner = build(threshold=2, health=health)
    failpoints.arm("matcher.device")
    lines, sink, sched = run_stream(m, n_chunks=16)
    assert_accounted(sched, sink, lines)
    assert sched.stats.processed_lines == len(lines)
    assert m.breaker.state == OPEN
    assert m.fallback_batches >= 1  # consume_lines routed to the CPU ref
    assert len(banner.regex_ban_logs) == len(lines)
    assert health.snapshot()["components"]["matcher"]["status"] != "healthy"


def test_overload_shed_plus_collect_fault_still_accounts():
    """Compound failure: sustained overload (tiny buffer, no block) while
    the collect stage is failing — shed and processed still sum to
    admitted."""
    m, _ = build()
    failpoints.arm("pipeline.collect")
    lines, sink, sched = run_stream(
        m, n_chunks=30, chunk=20,
        ring_size=1, buffer_lines=40, max_block_ms=0.0,
        min_batch=64, max_batch=64,
    )
    assert_accounted(sched, sink, lines)
    assert sched.stats.shed_lines > 0


class TestFusedFaults:
    """The same no-silent-loss contract with the fused matcher+windows
    path active (device windows on → match and window commit at submit,
    pull and replay at drain).  The extra hazard class here is LEAKED
    ORDER TURNS: a chunk whose events are never collected must free its
    turn and slot pins, or every later fused drain deadlocks — which
    these streams would surface as a flush() timeout."""

    def test_fused_stream_accounts_and_engages(self):
        m, banner = build(device_windows=True)
        lines, sink, sched = run_stream(m)
        assert_accounted(sched, sink, lines)
        assert sched.stats.processed_lines == len(lines)
        # the fused path ran (commit or counted overflow fallback)
        assert m.pipelined_fused_chunks + m.pipelined_fused_fallbacks > 0
        assert len(banner.regex_ban_logs) == len(lines)

    def test_device_failpoint_under_fused_path_loses_nothing(self):
        """matcher.device armed: fused submits fail → entries abandoned →
        batches drain generically via the CPU reference.  No deadlock, no
        loss, breaker opens."""
        m, banner = build(threshold=2, device_windows=True)
        failpoints.arm("matcher.device")
        lines, sink, sched = run_stream(m, n_chunks=16)
        assert_accounted(sched, sink, lines)
        assert sched.stats.processed_lines == len(lines)
        assert m.breaker.state == OPEN
        assert len(banner.regex_ban_logs) == len(lines)

    def test_failed_then_recovered_device_does_not_wedge_fused_drains(self):
        """Phase A streams with the device failing (fused submits abandon
        their entries, batches drain generically); phase B disarms and
        streams again THROUGH THE SAME matcher — the fused path must
        engage and drain (a leaked order turn from phase A would hang
        phase B's flush)."""
        m, banner = build(threshold=100, device_windows=True)
        now = time.time()
        sink = _Sink()
        sched = PipelineScheduler(
            lambda: m, on_results=sink, now_fn=lambda: now
        )
        sched.start()
        lines = []
        failpoints.arm("matcher.device", count=8)
        for c in range(8):
            batch = [
                f"{now:.6f} 9.9.{c}.{i} GET h.com GET /attack HTTP/1.1 ua -"
                for i in range(25)
            ]
            lines.extend(batch)
            sched.submit(batch)
            assert sched.flush(60)  # one batch per chunk, failpoint per batch
        failpoints.disarm()
        for c in range(8, 14):
            batch = [
                f"{now:.6f} 9.9.{c}.{i} GET h.com GET /attack HTTP/1.1 ua -"
                for i in range(25)
            ]
            lines.extend(batch)
            sched.submit(batch)
        assert sched.flush(60), "phase B hung — leaked fused order turn"
        sched.stop()
        assert_accounted(sched, sink, lines)
        assert sched.stats.processed_lines == len(lines)
        assert m.pipelined_fused_chunks + m.pipelined_fused_fallbacks > 0
        assert len(banner.regex_ban_logs) == len(lines)

    def test_drain_failpoint_under_fused_path_frees_turns(self):
        """pipeline.drain fires before pipeline_finish: the batch's
        fused chunks are settled by pipeline_abort — the stream after
        the failed batch still drains (no leaked turn deadlock)."""
        m, _ = build(device_windows=True)
        failpoints.arm("pipeline.drain", count=2)
        lines, sink, sched = run_stream(m, n_chunks=14)
        assert_accounted(sched, sink, lines)
        assert sched.stats.drain_error_lines > 0
        assert sched.stats.processed_lines == (
            len(lines) - sched.stats.drain_error_lines
        )

    def test_collect_failpoint_under_fused_path(self):
        m, banner = build(device_windows=True)
        failpoints.arm("pipeline.collect", count=3)
        lines, sink, sched = run_stream(m)
        assert_accounted(sched, sink, lines)
        assert sched.stats.processed_lines == len(lines)
        assert len(banner.regex_ban_logs) == len(lines)


class TestCommandRouting:
    """Kafka command messages through the admission buffer: the
    admitted == processed + shed invariant spans both producers."""

    def test_commands_share_accounting_with_lines(self):
        m, _ = build()
        now = time.time()
        sink = _Sink()
        handled = []
        sched = PipelineScheduler(
            lambda: m, on_results=sink, now_fn=lambda: now
        )
        sched.start()
        total = 0
        for c in range(8):
            batch = [
                f"{now:.6f} 9.9.{c}.{i} GET h.com GET /attack HTTP/1.1 ua -"
                for i in range(10)
            ]
            sched.submit(batch)
            sched.submit_commands(
                [f"cmd-{c}-{k}".encode() for k in range(3)], handled.append
            )
            total += 13
        assert sched.flush(60)
        sched.stop()
        s = sched.stats
        assert s.admitted_lines == total
        assert s.admitted_lines == (
            s.processed_lines + s.shed_lines + s.drain_error_lines
        )
        assert s.command_items == 24
        assert handled == [
            f"cmd-{c}-{k}".encode() for c in range(8) for k in range(3)
        ], "commands executed out of admission order"
        # on_results only sees log lines, never command items
        assert len(sink.lines) == total - 24

    def test_command_overload_sheds_and_counts(self):
        m, _ = build()
        handled = []
        sched = PipelineScheduler(
            lambda: m, ring_size=1, buffer_lines=16, max_block_ms=0.0,
            min_batch=64, max_batch=64,
        )
        sched.start()
        for c in range(40):
            sched.submit_commands(
                [f"c{c}-{k}".encode() for k in range(4)], handled.append
            )
        assert sched.flush(60)
        sched.stop()
        s = sched.stats
        assert s.admitted_lines == 160
        assert s.shed_lines > 0
        assert s.admitted_lines == (
            s.processed_lines + s.shed_lines + s.drain_error_lines
        )
        assert len(handled) == s.processed_lines

    def test_bad_command_loses_itself_not_the_batch(self):
        m, _ = build()
        good = []

        def handler(raw):
            if raw == b"boom":
                raise ValueError("bad command")
            good.append(raw)

        sched = PipelineScheduler(lambda: m)
        sched.start()
        sched.submit_commands([b"a", b"boom", b"b"], handler)
        assert sched.flush(30)
        sched.stop()
        assert good == [b"a", b"b"]
        assert sched.stats.processed_lines == 3  # boom counted, logged


def test_pipeline_registers_health_and_degrades_on_shed():
    health = HealthRegistry()
    m, _ = build()
    comp = health.register("pipeline")
    now = time.time()
    sched = PipelineScheduler(
        lambda: m, buffer_lines=16, max_block_ms=0.0, health=comp,
        now_fn=lambda: now,
    )
    sched.start()
    sched.submit(
        [f"{now:.6f} 1.1.1.{i} GET h.com GET /x HTTP/1.1 ua -"
         for i in range(64)]
    )
    snap = health.snapshot()
    assert snap["components"]["pipeline"]["status"] == "degraded"
    assert sched.flush(30)
    sched.stop()
    # a healthy drain restores the component
    assert health.snapshot()["components"]["pipeline"]["status"] == "healthy"
