"""Streaming pipeline scheduler units (banjax_tpu/pipeline/): the
adaptive sizer policy, the TpuMatcher split protocol (begin/submit/
collect/finish), drain-time staleness, backpressure + shed accounting,
the idle device probe, and the pipeline-derived breaker latency budget.

Everything here runs on the CPU backend (tests/conftest.py pins
JAX_PLATFORMS=cpu) — tier-1 marker hygiene for the pipeline suite.
"""

import gc
import statistics
import threading
import time
import weakref

import pytest

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.matcher.api import ConsumeLineResult
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.obs import trace
from banjax_tpu.obs.stats import PipelineStats
from banjax_tpu.pipeline import AdaptiveBatchSizer, PipelineScheduler
from banjax_tpu.pipeline import heap as heap_mod
from banjax_tpu.resilience import failpoints
from banjax_tpu.resilience.breaker import CLOSED, OPEN
from tests.classic_downgrade import scan_selftest_failing
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


def make_matcher(device_windows=False, **cfg_overrides):
    cfg = config_from_yaml_text(RULES_YAML)
    cfg.matcher_device_windows = device_windows
    for k, v in cfg_overrides.items():
        setattr(cfg, k, v)
    states = RegexRateLimitStates()
    banner = MockBanner()
    m = TpuMatcher(cfg, banner, StaticDecisionLists(cfg), states)
    return m, states, banner


def lines_at(now, n, path="/attack"):
    return [
        f"{now:.6f} 1.2.3.{i % 9} GET h.com GET {path}{i % 3} HTTP/1.1 ua -"
        for i in range(n)
    ]


# ---------------------------------------------------------------------------
# adaptive sizer
# ---------------------------------------------------------------------------


class TestAdaptiveBatchSizer:
    def test_grows_when_under_half_budget(self):
        s = AdaptiveBatchSizer(100.0, min_batch=64, max_batch=4096,
                               start_batch=256)
        for _ in range(4):
            s.observe(256, {"encode": 2.0, "device": 10.0, "drain": 1.0})
        assert s.target() == 512

    def test_shrinks_when_over_budget(self):
        s = AdaptiveBatchSizer(100.0, min_batch=64, max_batch=4096,
                               start_batch=1024)
        for _ in range(4):
            s.observe(1024, {"encode": 20.0, "device": 200.0, "drain": 10.0})
        assert s.target() == 512

    def test_clamps_at_bounds(self):
        s = AdaptiveBatchSizer(100.0, min_batch=64, max_batch=256,
                               start_batch=256)
        for _ in range(8):
            s.observe(256, {"device": 1.0})
        assert s.target() == 256  # fast but already at max
        s2 = AdaptiveBatchSizer(100.0, min_batch=64, max_batch=256,
                                start_batch=64)
        for _ in range(8):
            s2.observe(64, {"device": 500.0})
        assert s2.target() == 64  # slow but already at min

    def test_trickle_batches_do_not_drive_sizing(self):
        s = AdaptiveBatchSizer(100.0, min_batch=64, max_batch=4096,
                               start_batch=1024)
        # tiny batches, fast: say nothing about the 1024 bucket's latency
        for _ in range(10):
            s.observe(8, {"device": 0.5})
        assert s.target() == 1024

    def test_trickle_over_budget_still_shrinks(self):
        # a SLOW tiny batch is evidence regardless of its size
        s = AdaptiveBatchSizer(100.0, min_batch=64, max_batch=4096,
                               start_batch=1024)
        for _ in range(4):
            s.observe(8, {"device": 300.0})
        assert s.target() == 512

    def test_batches_cut_for_the_bucket_just_left_are_no_samples(self):
        """A ruleset wide enough that a batch is over the budget from
        2,048 lines up (10,000 rules on one chip: ~118 ms a thousand
        lines).  After a doubling the ring still holds batches cut for
        the old bucket; taken as samples of the new one they say it takes
        half the time, and the sizer doubles again."""
        budget = 250.0

        def ms(n):
            return {"device": 0.118 * n}

        s = AdaptiveBatchSizer(budget, max_batch=4096, start_batch=1024)
        for _ in range(3):
            s.observe(1024, ms(1024))           # 121 ms < half the budget
        assert s.target() == 2048
        for _ in range(3):
            s.observe(1024, ms(1024))           # the ring's leftovers
        assert s.target() == 2048               # was 4096
        for _ in range(3):
            s.observe(2048, ms(2048))           # 242 ms: fits, stays
        assert s.target() == 2048
        for _ in range(3):
            s.observe(2048, ms(2048) | {"drain": 30.0})   # over: halve
        assert s.target() == 1024
        for _ in range(3):
            s.observe(2048, ms(2048) | {"drain": 30.0})   # leftovers again
        assert s.target() == 1024               # was 512
        # an over-budget trickle is still evidence
        for _ in range(3):
            s.observe(8, {"device": 300.0})
        assert s.target() == 512

    def test_one_slow_batch_does_not_halve_a_size_two_in_a_row_do(self):
        s = AdaptiveBatchSizer(250.0, max_batch=4096, start_batch=1024)
        for _ in range(20):
            s.observe(1024, {"device": 170.0})
        s.observe(1024, {"device": 900.0})      # a scrape, a collector pass
        assert s.target() == 1024               # 170 + 0.3 * 170 = 221
        for _ in range(20):
            s.observe(1024, {"device": 170.0})
        for _ in range(2):
            s.observe(1024, {"device": 900.0})  # 221, then 287: over
        assert s.target() == 512

    def test_settle_prevents_single_sample_moves(self):
        s = AdaptiveBatchSizer(100.0, start_batch=1024, settle=3)
        s.observe(1024, {"device": 1.0})  # first full batch: compile, skipped
        s.observe(1024, {"device": 1.0})
        s.observe(1024, {"device": 1.0})
        assert s.target() == 1024  # two counted samples < settle=3
        s.observe(1024, {"device": 1.0})
        assert s.target() == 2048

    def test_power_of_two_normalization_and_validation(self):
        s = AdaptiveBatchSizer(100.0, min_batch=100, max_batch=5000,
                               start_batch=3000)
        assert s.target() == 2048
        assert s.min_batch == 64 and s.max_batch == 4096
        with pytest.raises(ValueError):
            AdaptiveBatchSizer(0.0)

    def test_snapshot_keys(self):
        s = AdaptiveBatchSizer(100.0)
        s.observe(1024, {"encode": 1.0, "device": 2.0, "drain": 3.0})
        snap = s.snapshot()
        assert snap["PipelineBatchTarget"] == s.target()
        assert snap["PipelineStageDeviceEwmaMs"] == 2.0

    def test_efficiency_guard_shrinks_back_from_worse_bucket(self):
        """Latency headroom alone must not hold a bucket that is per-line
        WORSE than the one below (the cache-bound backend shape)."""
        s = AdaptiveBatchSizer(250.0, min_batch=64, max_batch=8192,
                               start_batch=1024)
        # 1024: 50 ms total (~0.049 ms/line) → under half budget → grow
        for _ in range(4):
            s.observe(1024, {"device": 50.0})
        assert s.target() == 2048
        # 2048 turns out per-line worse (0.122 vs 0.049) though 250 ms
        # still fits the budget
        for _ in range(4):
            s.observe(2048, {"device": 250.0 * 0.9})
        assert s.target() == 1024
        # and growth back into the measured-worse bucket stays blocked
        for _ in range(6):
            s.observe(1024, {"device": 50.0})
        assert s.target() == 1024

    def test_efficiency_guard_acts_only_on_probation(self):
        """A bucket that proved itself against the one below is not sent
        back by one slow batch later on: the lower bucket's frozen record
        is no tripwire, only the budget shrinks a size past probation."""
        from banjax_tpu.pipeline import sizer as sizer_mod

        s = AdaptiveBatchSizer(250.0, min_batch=64, max_batch=2048,
                               start_batch=1024)
        for _ in range(4):
            s.observe(1024, {"device": 35.0})
        assert s.target() == 2048
        # 2048 pays (0.030 ms/line against 0.034) and passes probation
        for _ in range(sizer_mod._PROBATION + 2):
            s.observe(2048, {"device": 62.0})
        assert s.target() == 2048
        # a collector pass: twice the time, far under the 250 ms budget
        s.observe(2048, {"device": 124.0})
        for _ in range(4):
            s.observe(2048, {"device": 62.0})
        assert s.target() == 2048
        # the budget still rules
        for _ in range(6):
            if s.target() == 2048:
                s.observe(2048, {"device": 400.0})
        assert s.target() == 1024

    def test_efficiency_guard_allows_growth_when_upper_is_better(self):
        s = AdaptiveBatchSizer(500.0, min_batch=64, max_batch=8192,
                               start_batch=1024)
        for _ in range(4):
            s.observe(1024, {"device": 100.0})
        assert s.target() == 2048
        # amortization pays: per-line improves at 2048 → keeps growing
        for _ in range(4):
            s.observe(2048, {"device": 150.0})
        assert s.target() == 4096

    def test_blocked_grow_retries_after_decay(self):
        from banjax_tpu.pipeline import sizer as sizer_mod

        s = AdaptiveBatchSizer(250.0, min_batch=64, max_batch=8192,
                               start_batch=2048)
        # poison the upper bucket's record (e.g. a first-visit compile)
        s._per_line_at[4096] = 10.0
        for _ in range(sizer_mod._RETRY_BLOCKED + 6):
            s.observe(2048, {"device": 50.0})
            if s.target() != 2048:
                break
        # the stale record was eventually forgotten and growth retried
        assert s.target() == 4096


# ---------------------------------------------------------------------------
# split protocol (matcher-level, no threads)
# ---------------------------------------------------------------------------


class TestSplitProtocol:
    @pytest.mark.parametrize("device_windows", [False, True])
    def test_split_equals_sync(self, device_windows):
        now = time.time()
        lines = lines_at(now, 40) + [
            f"{now:.6f} 5.5.5.5 GET h.com GET /benign HTTP/1.1 ua -",
            "garbage",
        ]
        sync_m, sync_states, sync_banner = make_matcher(device_windows)
        want = sync_m.consume_lines(lines, now)

        m, states, banner = make_matcher(device_windows)
        state = m.pipeline_begin(lines, now)
        m.pipeline_submit(state)
        m.pipeline_collect(state)
        got, n_stale = m.pipeline_finish(state, now)
        assert n_stale == 0
        for a, b in zip(want, got):
            assert (a.error, a.old_line, a.exempted) == (
                b.error, b.old_line, b.exempted
            )
            assert [
                (r.rule_name, r.regex_match, r.seen_ip) for r in a.rule_results
            ] == [
                (r.rule_name, r.regex_match, r.seen_ip) for r in b.rule_results
            ]
        assert sync_banner.regex_ban_logs == banner.regex_ban_logs
        sync_view = (
            sync_m.device_windows if device_windows else sync_states
        )
        view = m.device_windows if device_windows else states
        assert sync_view.format_states() == view.format_states()

    @pytest.mark.parametrize("device_windows", [False, True])
    def test_stale_at_drain_time_is_dropped_and_counted(self, device_windows):
        now = time.time()
        lines = lines_at(now, 20)
        # drop-at-DRAIN is the classic protocol's contract (the fused
        # path commits at submit and takes the cut there —
        # tests/unit/test_fused_single_kernel.py); with device windows the
        # classic protocol is reached by the scan-selftest downgrade
        with scan_selftest_failing():
            m, states, banner = make_matcher(device_windows)
        assert m.describe()["fused_protocol"] == "classic"
        state = m.pipeline_begin(lines, now)
        m.pipeline_submit(state)
        m.pipeline_collect(state)
        # the batch sat in the pipeline past the 10 s cutoff: age is
        # measured at effector drain time, so every line drops old_line
        results, n_stale = m.pipeline_finish(state, now + 30)
        assert n_stale == 20
        assert all(r.old_line and not r.rule_results for r in results)
        assert banner.bans == [] and banner.regex_ban_logs == []
        # no window state was touched for stale lines
        if device_windows:
            assert len(m.device_windows) == 0
        else:
            assert len(states) == 0

    def test_partial_staleness_keeps_fresh_lines(self):
        now = time.time()
        fresh = lines_at(now, 10)
        old = lines_at(now - 8, 5)  # fresh at parse, stale at drain+3
        m, states, banner = make_matcher()
        state = m.pipeline_begin(old + fresh, now)
        m.pipeline_submit(state)
        m.pipeline_collect(state)
        results, n_stale = m.pipeline_finish(state, now + 3)
        assert n_stale == 5
        assert all(r.old_line for r in results[:5])
        assert all(not r.old_line for r in results[5:])
        assert sum(len(r.rule_results) for r in results[5:]) > 0

    def test_parse_time_old_lines_are_not_double_counted(self):
        now = time.time()
        m, _, _ = make_matcher()
        state = m.pipeline_begin(lines_at(now - 100, 6), now)
        m.pipeline_submit(state)
        m.pipeline_collect(state)
        results, n_stale = m.pipeline_finish(state, now)
        # already old at parse: normal old_line results, not pipeline-stale
        assert n_stale == 0
        assert all(r.old_line for r in results)


class TestFusedSplit:
    """The fused matcher+windows protocol under the split calls (device
    windows on → submit dispatches and commits, finish pulls and
    replays)."""

    def test_multi_chunk_batch_commits_in_order(self):
        """A batch wider than matcher_batch_lines splits into several
        fused chunks; they commit strictly in chunk order at submit and
        replay in that order at finish — identical to the sync fused
        path."""
        now = time.time()
        # mixed traffic: mostly benign so the candidate gate holds
        lines = [
            f"{now:.6f} 1.2.{i % 5}.{i % 9} GET h.com GET "
            f"/{'attack' if i % 11 == 0 else 'page'}{i % 3} HTTP/1.1 ua -"
            for i in range(300)
        ]
        sync_m, _, sync_banner = make_matcher(
            device_windows=True, matcher_batch_lines=64
        )
        want = sync_m.consume_lines(lines, now)

        m, _, banner = make_matcher(
            device_windows=True, matcher_batch_lines=64
        )
        state = m.pipeline_begin(lines, now)
        assert state.get("fused_eligible")
        m.pipeline_submit(state)
        assert len(state["fused"]) > 1, "expected several fused chunks"
        m.pipeline_collect(state)
        got, n_stale = m.pipeline_finish(state, now)
        assert n_stale == 0
        assert m.pipelined_fused_chunks == len(
            [1 for _ in range(0, 300, 64)]
        ) - m.pipelined_fused_fallbacks
        for a, b in zip(want, got):
            assert [
                (r.rule_name, r.regex_match, r.seen_ip,
                 r.rate_limit_result and r.rate_limit_result.exceeded)
                for r in a.rule_results
            ] == [
                (r.rule_name, r.regex_match, r.seen_ip,
                 r.rate_limit_result and r.rate_limit_result.exceeded)
                for r in b.rule_results
            ]
        assert sync_banner.regex_ban_logs == banner.regex_ban_logs
        assert sync_m.device_windows.format_states() == \
            m.device_windows.format_states()

    def test_abort_frees_turns_for_later_batches(self):
        """pipeline_abort on an un-finished batch must free its order
        turns: a later batch's finish would otherwise deadlock."""
        now = time.time()
        m, _, _ = make_matcher(device_windows=True)
        s1 = m.pipeline_begin(lines_at(now, 10), now)
        m.pipeline_submit(s1)
        assert s1.get("fused")
        s2 = m.pipeline_begin(lines_at(now, 10), now)
        m.pipeline_submit(s2)
        m.pipeline_abort(s1)  # batch 1 dies before its drain
        m.pipeline_collect(s2)
        results, _ = m.pipeline_finish(s2, now)  # must not hang
        assert any(r.rule_results for r in results)
        # pins fully released: every slot usable again
        assert (m.device_windows._pin_counts == 0).all()


# ---------------------------------------------------------------------------
# scheduler (threads)
# ---------------------------------------------------------------------------


class _CollectingSink:
    def __init__(self):
        self.lock = threading.Lock()
        self.lines = []
        self.results = []

    def __call__(self, lines, results):
        with self.lock:
            self.lines.extend(lines)
            if results is not None:
                self.results.extend(results)


class TestScheduler:
    def test_end_to_end_parity_and_order(self):
        now = time.time()
        lines = lines_at(now, 700)
        sync_m, sync_states, sync_banner = make_matcher()
        want = sync_m.consume_lines(lines, now)

        m, states, banner = make_matcher()
        sink = _CollectingSink()
        sched = PipelineScheduler(
            lambda: m, on_results=sink, now_fn=lambda: now
        )
        sched.start()
        for i in range(0, len(lines), 53):
            sched.submit(lines[i : i + 53])
        assert sched.flush(60)
        sched.stop()
        assert sink.lines == lines  # admission order preserved
        assert len(sink.results) == len(want)
        assert sync_banner.regex_ban_logs == banner.regex_ban_logs
        assert sync_states.format_states() == states.format_states()
        snap = sched.snapshot()
        assert snap["PipelineAdmittedLines"] == len(lines)
        assert snap["PipelineProcessedLines"] == len(lines)
        assert snap["PipelineShedLines"] == 0

    def test_generic_matcher_without_split_protocol(self):
        """A matcher with no pipeline_begin (the CpuMatcher shape) drains
        generically through consume_lines — same results, fallback
        counted."""

        class PlainMatcher:
            def consume_lines(self, lines, now_unix=None):
                return [ConsumeLineResult() for _ in lines]

        sink = _CollectingSink()
        sched = PipelineScheduler(
            PlainMatcher, on_results=sink,  # getter: a fresh instance is fine
        )
        sched.start()
        sched.submit(["a b c d e f g"] * 10)
        assert sched.flush(10)
        sched.stop()
        assert len(sink.results) == 10
        assert sched.snapshot()["PipelineFallbackBatches"] >= 1

    def test_backpressure_sheds_oldest_and_accounts_every_line(self):
        """Sustained overload: tiny buffer, no blocking, a slow matcher —
        lines are shed oldest-first, counted, and the accounting invariant
        holds exactly after a flush."""

        class SlowMatcher:
            def consume_lines(self, lines, now_unix=None):
                time.sleep(0.05)
                return [ConsumeLineResult() for _ in lines]

        m = SlowMatcher()
        sink = _CollectingSink()
        sched = PipelineScheduler(
            lambda: m, ring_size=1, buffer_lines=64, max_block_ms=0.0,
            min_batch=64, max_batch=64, on_results=sink,
        )
        sched.start()
        for _ in range(40):
            sched.submit(["w x y z a b c"] * 16)
        assert sched.flush(60)
        sched.stop()
        s = sched.stats
        assert s.admitted_lines == 40 * 16
        assert s.shed_lines > 0
        assert len(sink.results) == s.processed_lines
        # the invariant the tentpole promises: admitted lines are either
        # processed or counted — never silently lost
        assert s.admitted_lines == (
            s.processed_lines + s.shed_lines + s.drain_error_lines
        )

    def test_oversized_single_chunk_sheds_its_own_head(self):
        class PlainMatcher:
            def consume_lines(self, lines, now_unix=None):
                return [ConsumeLineResult() for _ in lines]

        sched = PipelineScheduler(
            lambda: PlainMatcher(), buffer_lines=32, max_block_ms=0.0,
        )
        sched.start()
        sched.submit([f"l{i} a b c d e f" for i in range(100)])
        assert sched.flush(10)
        sched.stop()
        s = sched.stats
        assert s.shed_lines == 68
        assert s.admitted_lines == s.processed_lines + s.shed_lines

    def test_snapshot_metric_keys(self):
        m, _, _ = make_matcher()
        sched = PipelineScheduler(lambda: m)
        sched.start()
        now = time.time()
        sched.submit(lines_at(now, 10))
        assert sched.flush(30)
        sched.stop()
        snap = sched.snapshot()
        for key in (
            "PipelineAdmittedLines", "PipelineProcessedLines",
            "PipelineShedLines", "PipelineStaleDroppedLines",
            "PipelineBatches", "PipelineFallbackBatches",
            "PipelineBatchTarget", "PipelineStageDeviceEwmaMs",
            "PipelineBufferedLines", "PipelineInflightBatches",
            "PipelineRingSize", "PipelineDeviceP99Ms",
        ):
            assert key in snap, key


# ---------------------------------------------------------------------------
# idle probe + pipeline-derived breaker budget
# ---------------------------------------------------------------------------


class TestProbeAndBudget:
    def test_probe_succeeds_on_healthy_device(self):
        m, _, banner = make_matcher()
        assert m.probe() is True
        assert m.breaker.state == CLOSED
        assert banner.bans == []  # a probe has no side effects

    def test_probe_failure_trips_breaker_while_idle(self):
        m, _, _ = make_matcher(breaker_failure_threshold=1)
        failpoints.arm("matcher.device")
        assert m.probe() is False
        assert m.breaker.state == OPEN

    def test_scheduler_probe_thread_surfaces_wedged_device(self):
        m, _, _ = make_matcher(breaker_failure_threshold=1)
        m.probe()  # warm the device path before arming the failpoint
        failpoints.arm("matcher.device")
        sched = PipelineScheduler(lambda: m, probe_seconds=0.05)
        sched.start()
        deadline = time.monotonic() + 5
        while m.breaker.state != OPEN and time.monotonic() < deadline:
            time.sleep(0.02)
        sched.stop()
        assert m.breaker.state == OPEN
        assert sched.stats.probe_failed >= 1

    def test_effective_budget_prefers_config_over_source(self):
        m, _, _ = make_matcher(matcher_latency_budget_ms=123.0)
        m.set_latency_budget_source(lambda: 9.9)
        assert m.effective_latency_budget_s() == pytest.approx(0.123)

    def test_effective_budget_derives_from_pipeline_p99(self):
        m, _, _ = make_matcher()  # budget unset
        assert m.effective_latency_budget_s() == 0.0
        stats = PipelineStats()
        m.set_latency_budget_source(stats.suggested_latency_budget_s)
        assert m.effective_latency_budget_s() == 0.0  # no samples yet
        stats.observe_device(0.004)  # 4 ms p99 → 3x = 12 ms → the 1 s floor
        assert m.effective_latency_budget_s() == pytest.approx(1.0)
        for _ in range(300):
            stats.observe_device(0.1)  # 100 ms p99 → 300 ms: the floor still
        assert m.effective_latency_budget_s() == pytest.approx(1.0)
        for _ in range(300):
            stats.observe_device(0.6)  # 600 ms p99 → 1.8 s budget
        assert m.effective_latency_budget_s() == pytest.approx(1.8, rel=0.1)

    def test_a_fast_stage_does_not_open_the_breaker_on_three_slow_batches(self):
        """The derived budget follows the stage's p99 down only as far as
        its floor: behind a stage of 20 ms a batch, three batches in a row
        that waited 0.1-0.5 s for the interpreter (what the benchmark's
        tail sends while its reference computes; the driver's first check
        of PR 50) are no failures, and three of 1.5 s are."""
        m, _, _ = make_matcher()  # budget unset, threshold 3
        stats = PipelineStats()
        m.set_latency_budget_source(stats.suggested_latency_budget_s)
        for _ in range(300):
            stats.observe_device(0.020)
        for slow in (0.12, 0.46, 0.30):
            m.note_device_outcome(slow, ok=True)
        assert m.budget_trips == 0 and m.breaker.state == CLOSED
        for _ in range(3):
            m.note_device_outcome(1.5, ok=True)
        assert m.budget_trips == 3 and m.breaker.state == OPEN


class TestStartUpSamples:
    """What the sizer and the breaker's budget learn from (PR 24: found on
    the first chip run — a burst collapsed the batch target to 64, and a
    cold start opened the breaker)."""

    def test_drain_sample_is_not_the_tailer_read_age(self):
        """The drain-stage sample handed to the sizer is the drain's own
        wall time, not the age of the batch since the tailer read it."""

        class PlainMatcher:
            def consume_lines(self, lines, now_unix=None):
                return [ConsumeLineResult() for _ in lines]

        sched = PipelineScheduler(PlainMatcher)
        sched.start()
        sched.submit(["a b c d e f g"] * 10,
                     t_read=time.monotonic() - 1000.0)
        assert sched.flush(10)
        sched.stop()
        assert sched._sizer.stage_ewma_ms["drain"] < 60_000.0

    def test_batch_that_built_a_program_is_no_latency_sample(self):
        """A batch during which the matcher's build counter moved is left
        out of the sizer's samples and of the breaker's latency budget;
        an equally slow batch that built nothing still counts."""
        calls = []

        class BuildingMatcher:
            builds = 0

            def compile_events(self):
                return self.builds

            def pipeline_begin(self, lines, now):
                return {"results": [ConsumeLineResult() for _ in lines]}

            def pipeline_submit(self, state):
                if state["results"] and calls == []:
                    self.builds += 1  # first batch pays for a compile

            def pipeline_collect(self, state):
                pass

            def pipeline_finish(self, state, now):
                return state["results"], 0

            def note_device_outcome(self, elapsed_s, ok, compiled=False):
                calls.append(compiled)

        m = BuildingMatcher()
        sched = PipelineScheduler(lambda: m, encode_workers=0)
        sched.start()
        for _ in range(2):
            sched.submit(["a b c d e f g"] * 10)
            assert sched.flush(10)
        sched.stop()
        assert calls == [True, False]
        # only the second batch reached the sizer's stage EWMAs
        assert sched._sizer.stage_ewma_ms["device"] is not None

        real, _, _ = make_matcher(matcher_latency_budget_ms=1.0,
                                  breaker_failure_threshold=1)
        real.note_device_outcome(5.0, ok=True, compiled=True)
        assert real.budget_trips == 0 and real.breaker.state == CLOSED
        real.note_device_outcome(5.0, ok=True)
        assert real.budget_trips == 1 and real.breaker.state == OPEN

    def test_device_sample_is_the_stage_s_service_time(self):
        """A device that is the bound, two batches in flight: whether the
        wait for the predecessor falls into this batch's submit, into its
        collect or into both is an accident of timing (submit + collect
        read d or 2d), so the sizer is given what the stage took for the
        batch after its predecessor left it."""
        seen = []

        class DeviceBound:
            """Every batch takes 100 ms of a device that runs one at a
            time; submit waits for the predecessor (as a full dispatch
            queue does), collect for the batch itself."""
            free_at = 0.0

            def pipeline_begin(self, lines, now):
                return {"results": [ConsumeLineResult() for _ in lines]}

            def pipeline_submit(self, state):
                time.sleep(max(0.0, self.free_at - time.perf_counter()))
                self.free_at = time.perf_counter() + 0.100
                state["done_at"] = self.free_at

            def pipeline_collect(self, state):
                time.sleep(max(0.0, state["done_at"] - time.perf_counter()))

            def pipeline_finish(self, state, now):
                return state["results"], 0

        m = DeviceBound()
        sched = PipelineScheduler(lambda: m, encode_workers=0,
                                  min_batch=64, max_batch=64)
        observe = sched._sizer.observe
        sched._sizer.observe = lambda n, ms: (seen.append(ms), observe(n, ms))
        sched.start()
        sched.submit(["a b c d e f g"] * (12 * 64))
        assert sched.flush(20)
        sched.stop()
        service = [ms["device"] for ms in seen[2:]]
        assert len(service) >= 8
        # d, not 2d: the median, because on a loaded machine one late
        # wake-up makes one sample long and the next one short by as much
        assert 85.0 <= statistics.median(service) <= 130.0, service
        assert all(25.0 <= d <= 175.0 for d in service), service


# ---------------------------------------------------------------------------
# the cyclic collector under the pipeline (pipeline/heap.py, ISSUE 40)
# ---------------------------------------------------------------------------


class _BuildingMatcher:
    """A generic matcher with a build counter the test moves, and a
    reference cycle: only the collector can free one."""

    def __init__(self):
        self.builds = 0
        self.me = self

    def compile_events(self):
        return self.builds

    def consume_lines(self, lines, now_unix=None):
        return [ConsumeLineResult() for _ in lines]


class TestCollector:
    @pytest.fixture(autouse=True)
    def _thawed(self):
        yield
        gc.unfreeze()  # whatever a failing test left frozen

    @staticmethod
    def _one_batch(sched):
        sched.submit(["a b c d e f g"] * 10)
        assert sched.flush(10)

    def test_an_observer_that_reads_every_row_leaves_no_garbage(self):
        """What the benchmark's observer and chip_smoke's do: read a flag
        of every row of every drained batch.  Fifty batches through the
        fused CPU pipeline, and what only a collection could free stays
        under 50 objects a batch (at PR 39: two a line, 512 a batch)."""
        m, _, _ = make_matcher(device_windows=True,
                               matcher_window_capacity=256)
        now = time.time()
        seen = []

        def observer(lines, results):
            seen.append(sum(r.old_line for r in results))

        sched = PipelineScheduler(lambda: m, on_results=observer,
                                  now_fn=lambda: now, max_batch=256)
        sched.start()
        for _ in range(2):  # programs built, the heap frozen
            sched.submit(lines_at(now, 256))
            assert sched.flush(120)
        gc.collect()
        freed0 = sum(sched.collector_stats()["collected"])
        batches0 = sched.stats.batches
        for k in range(50):
            sched.submit(lines_at(now, 256, path=f"/p{k}"))
            assert sched.flush(60)
        gc.collect()
        stats = sched.collector_stats()
        batches = sched.stats.batches - batches0
        sched.stop()
        m.close()
        assert batches >= 50 and len(seen) >= 52 and not any(seen)
        assert sum(stats["collected"]) - freed0 < 50 * batches
        assert stats["collections"][2] >= 2 and stats["pause_s"][2] > 0
        assert stats["frozen"] > 0

    def test_freezes_at_the_first_matcher_and_again_once_builds_stand_still(
            self, monkeypatch):
        monkeypatch.setattr(heap_mod, "_SETTLE_S", 0.05)
        m = _BuildingMatcher()
        sched = PipelineScheduler(lambda: m)
        gc.unfreeze()
        sched.start()
        assert sched.collector_stats()["frozen"] == 0
        self._one_batch(sched)
        frozen = sched.collector_stats()["frozen"]
        assert frozen > 1000
        ballast = [[i] for i in range(5000)]  # allocated after the freeze
        m.builds = 3                          # a program was built
        self._one_batch(sched)
        self._one_batch(sched)                # still for 0 s: not yet
        assert sched.collector_stats()["frozen"] <= frozen + 100
        time.sleep(0.06)
        self._one_batch(sched)                # stood still: frozen again
        assert sched.collector_stats()["frozen"] >= frozen + 5000
        time.sleep(0.06)
        calls = []
        monkeypatch.setattr(heap_mod.gc, "freeze", lambda: calls.append(1))
        self._one_batch(sched)                # nothing moved: not again
        assert calls == []
        monkeypatch.undo()
        sched.stop()
        assert gc.get_freeze_count() == 0     # stopping gives the heap back
        assert sched._heap._on_gc not in gc.callbacks
        del ballast

    def test_a_hot_reload_lets_the_old_matcher_be_collected(self):
        holder = [_BuildingMatcher()]
        sched = PipelineScheduler(lambda: holder[0])
        sched.start()
        self._one_batch(sched)
        assert sched.collector_stats()["frozen"] > 0
        old = weakref.ref(holder[0])
        holder[0] = _BuildingMatcher()        # the app's _current_matcher
        self._one_batch(sched)
        gc.collect()
        assert old() is None
        sched.stop()

    def test_a_slow_full_pass_is_an_event_of_the_thread_it_ran_on(
            self, monkeypatch):
        monkeypatch.setattr(heap_mod, "_SLOW_PASS_S", 0.0)
        trace.configure(enabled=True, ring_size=64)
        try:
            keeper = heap_mod.HeapKeeper()
            keeper.start()
            t = threading.Thread(target=gc.collect, name="collects-here")
            t.start()
            t.join(10)
            gc.collect(0)                     # a young one: counted only
            keeper.stop()
            events = [e for e in trace.get_tracer().snapshot()
                      if e["name"] == "gc-pass"]
        finally:
            trace.configure(enabled=False)
        assert [e["thread"] for e in events] == ["collects-here"]
        assert events[0]["args"]["ms"] >= 0
        assert keeper.collections[2] == 1 and keeper.collections[0] == 1
        assert keeper.pause_s[2] > 0


# ---------------------------------------------------------------------------
# soak (excluded from tier-1: -m 'not slow')
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sustained_stream_soak():
    """Minutes-scale shape in miniature: a sustained mixed stream through
    the full scheduler with probe thread on — accounting exact at the
    end, no drift, breaker closed."""
    m, states, banner = make_matcher()
    sched = PipelineScheduler(lambda: m, probe_seconds=0.2)
    sched.start()
    now = time.time()
    total = 0
    t_end = time.monotonic() + 8
    i = 0
    while time.monotonic() < t_end:
        n = 17 + (i % 91)
        sched.submit(lines_at(now, n))
        total += n
        i += 1
        if i % 40 == 0:
            time.sleep(0.05)  # let the idle probe get a look in
    assert sched.flush(120)
    sched.stop()
    s = sched.stats
    assert s.admitted_lines == total
    assert s.processed_lines + s.shed_lines + s.drain_error_lines == total
    assert s.drain_error_lines == 0
    assert m.breaker.state == CLOSED
