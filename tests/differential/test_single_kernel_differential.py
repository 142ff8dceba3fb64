"""Single-kernel fused path vs the classic bitmap protocol: byte-identical.

The fused path (kernels/fused_match_window.py) is one dispatch per chunk
whose overflow handling is gated in-kernel and whose window commit
happens at submit; the classic protocol pulls a dense bitmap and applies
the windows at the drain.  The classic side is reached the way the
product reaches it: a failing scan selftest (tests/classic_downgrade.py).
These tests prove the two differ in NOTHING observable: for the same
stimulus, single-kernel == classic == CPU reference on

  * the per-line result stream (victim/refusal sequences),
  * ban-log bytes,
  * dynamic-decision metrics,
  * the full window counter state (format_states — spills included),

across slot-eviction churn, overflow bursts (the chain-gate composition),
mid-pipeline staleness, breaker trips, and mid-pipeline aborts."""

import io
import threading
import time

import pytest

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.effectors.banner import Banner
from banjax_tpu.matcher.cpu_ref import CpuMatcher
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.pipeline import PipelineScheduler
from banjax_tpu.resilience import failpoints
from tests.classic_downgrade import scan_selftest_failing
from tests.differential.test_pipeline_differential import ChurnSizer, _gen_lines
from tests.differential.test_tpu_matcher import CONFIG_YAML, result_key


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.disarm()
    yield
    failpoints.disarm()


def _build(matcher_cls, **cfg_overrides):
    config = config_from_yaml_text(CONFIG_YAML)
    config.matcher_device_windows = True
    for k, v in cfg_overrides.items():
        setattr(config, k, v)
    states = RegexRateLimitStates()
    ban_log = io.StringIO()
    dyn = DynamicDecisionLists(start_sweeper=False)
    banner = Banner(dyn, ban_log, io.StringIO(), ipset_instance=None)
    matcher = matcher_cls(config, banner, StaticDecisionLists(config), states)
    return matcher, states, dyn, ban_log


def _pair(**cfg):
    """(single-kernel matcher, classic-protocol matcher), identical cfg."""
    sk = _build(TpuMatcher, **cfg)
    with scan_selftest_failing():
        tp = _build(TpuMatcher, **cfg)
    assert sk[0].describe()["fused_protocol"] == "single-kernel"
    assert tp[0].describe()["fused_protocol"] == "classic"
    return sk, tp


def _run_pipelined(matcher, phases, now_box, sizer_seed=7):
    """Drive `phases` (lists of lines) through the scheduler, flushing
    between phases so a mutated now_box['now'] applies to whole phases
    deterministically (encode/submit/drain all see the same clock)."""
    collected = []
    lock = threading.Lock()

    def sink(batch_lines, results):
        with lock:
            collected.append((batch_lines, results))

    sched = PipelineScheduler(lambda: matcher, on_results=sink,
                              now_fn=lambda: now_box["now"])
    sched._sizer = ChurnSizer(seed=sizer_seed)
    sched.start()
    for phase in phases:
        for i in range(0, len(phase), 97):
            sched.submit(phase[i : i + 97])
        assert sched.flush(180)
    sched.stop()
    return [r for _, rs in collected for r in rs], sched


def _assert_identical(tag, a_results, b_results, a, b):
    (am, _, adyn, alog) = a
    (bm, _, bdyn, blog) = b
    assert [result_key(r) for r in a_results] == \
        [result_key(r) for r in b_results], f"{tag}: result stream diverged"
    assert alog.getvalue() == blog.getvalue(), f"{tag}: ban-log bytes diverged"
    assert adyn.metrics() == bdyn.metrics(), f"{tag}: decision metrics diverged"
    assert am.device_windows.format_states() == \
        bm.device_windows.format_states(), f"{tag}: window state diverged"


def test_churn_stream_byte_identical_and_cpu_exact():
    """Adversarial batch churn with shared IPs crossing chunk boundaries
    plus a CPU-reference anchor: single-kernel == classic == CPU."""
    now = time.time()
    lines = _gen_lines(1500, now)

    cpu, _, _, cpu_log = _build(CpuMatcher)
    cpu_results = [cpu.consume_line(l, now_unix=now) for l in lines]

    sk, tp = _pair()
    sk_results, _ = _run_pipelined(sk[0], [lines], {"now": now}, sizer_seed=7)
    tp_results, _ = _run_pipelined(tp[0], [lines], {"now": now}, sizer_seed=7)

    for i, (c, s) in enumerate(zip(cpu_results, sk_results)):
        assert result_key(c) == result_key(s), f"single-kernel diverged at {i}"
    _assert_identical("churn", sk_results, tp_results, sk, tp)
    assert sk[3].getvalue() == cpu_log.getvalue()
    assert sk[0]._fw_pipeline.fused_batches > 0, "single kernel never engaged"


def test_eviction_churn_byte_identical():
    """Slot capacity far below the distinct-IP load: spill/restore churn
    under both protocols stays lossless and identical."""
    now = time.time()
    lines = _gen_lines(900, now, seed=19)
    sk, tp = _pair(matcher_window_capacity=16, matcher_batch_lines=64,
                   matcher_prefilter_cand_frac=1.0)
    sk_results, _ = _run_pipelined(sk[0], [lines], {"now": now}, sizer_seed=3)
    tp_results, _ = _run_pipelined(tp[0], [lines], {"now": now}, sizer_seed=3)
    _assert_identical("evict", sk_results, tp_results, sk, tp)
    assert sk[0].device_windows.eviction_count > 0


def test_overflow_bursts_with_phase_gaps():
    """All-matching bursts (candidate overflow) alternating with benign
    phases, flushed between phases: the chain gate replays the poisoned
    tail classically and reseeds at each quiescent gap — identical
    output, and the single kernel demonstrably commits again after every
    burst (both counters move)."""
    now = time.time()
    phases = []
    for burst in range(6):
        if burst % 2:
            phases.append([
                f"{now:f} 7.7.{burst}.{i} POST example.com POST /x{i} "
                "HTTP/1.1 ua -"
                for i in range(80)
            ])
        else:
            phases.append(_gen_lines(120, now, seed=300 + burst))

    sk, tp = _pair(matcher_batch_lines=64, matcher_prefilter_cand_frac=0.125)
    sk_results, _ = _run_pipelined(sk[0], phases, {"now": now}, sizer_seed=5)
    tp_results, _ = _run_pipelined(tp[0], phases, {"now": now}, sizer_seed=5)
    _assert_identical("overflow", sk_results, tp_results, sk, tp)
    fw = sk[0]._fw_pipeline
    assert fw.fallback_batches > 0, "overflow never hit the in-kernel gate"
    assert fw.fused_batches > 0, "chain never reseeded across phase gaps"


def test_mixed_path_batches_keep_window_order():
    """The cross-batch ordering hazard of commit-at-submit: a batch with
    host-eval rows (garbage line) takes the classic pend path and applies
    its window updates at its DRAIN turn; a later single-kernel batch
    would commit at SUBMIT — before that drain — unless the order gate
    (runner._single_kernel_ordered) routes it classic too.  Shared IPs
    hammer the same rules near their thresholds so one reordered window
    update shifts which exact hit fires — the oracle comparison catches
    a single slip."""
    now = time.time()
    lines = []
    for k in range(600):
        if k % 90 == 44:
            lines.append("short garbage")  # host-eval → classic batch
        lines.append(
            f"{now + k * 1e-4:f} 3.3.3.{k % 4} GET per-site.com GET "
            "/blockme HTTP/1.1 ua -"
        )

    sk, tp = _pair(matcher_batch_lines=64, matcher_prefilter_cand_frac=1.0)
    sk_results, _ = _run_pipelined(sk[0], [lines], {"now": now}, sizer_seed=21)
    tp_results, _ = _run_pipelined(tp[0], [lines], {"now": now}, sizer_seed=21)
    _assert_identical("mixed-path", sk_results, tp_results, sk, tp)
    assert sk[0]._fw_pipeline.fused_batches > 0
    # the drain-apply gate fully released (no leaked slots)
    assert sk[0]._drain_window_batches == 0
    assert tp[0]._drain_window_batches == 0


def test_mid_pipeline_staleness_identical():
    """Lines fresh at encode but past the 10 s cutoff at commit: the
    single-kernel path cuts at submit (live-mask input), the classic
    protocol at its drain — same observable drop, same surviving
    commits, driven through the split protocol directly so both clocks
    are pinned to the same instant."""
    now = time.time()
    old = [
        f"{now - 8:f} 9.9.9.{i} GET per-site.com GET /blockme HTTP/1.1 ua -"
        for i in range(6)
    ]
    fresh = [
        f"{now:f} 8.8.8.{i} GET per-site.com GET /blockme HTTP/1.1 ua -"
        for i in range(6)
    ]
    lines = old + fresh
    sk, tp = _pair()

    s = sk[0].pipeline_begin(lines, now)
    assert s.get("fused_eligible")
    sk[0].pipeline_submit(s, now=now + 3)  # old rows now 11 s stale
    sk[0].pipeline_collect(s)
    sk_results, sk_stale = sk[0].pipeline_finish(s, now + 3)

    t = tp[0].pipeline_begin(lines, now)
    tp[0].pipeline_submit(t, now=now + 3)
    tp[0].pipeline_collect(t)
    tp_results, tp_stale = tp[0].pipeline_finish(t, now + 3)

    assert sk_stale == tp_stale == 6
    _assert_identical("stale", sk_results, tp_results, sk, tp)
    assert all(r.old_line for r in sk_results[:6])
    assert all(r.rule_results for r in sk_results[6:])


def test_breaker_trip_mid_stream_identical():
    """Phase 2 runs with the breaker OPEN (CPU reference drain), then the
    breaker recovers: both protocols route the same batches to the same
    paths, so the streams stay identical end to end."""
    now = time.time()
    phase1 = _gen_lines(300, now, seed=41)
    phase2 = _gen_lines(200, now, seed=43)
    phase3 = _gen_lines(300, now, seed=47)

    def run(m):
        box = {"now": now}
        collected = []
        lock = threading.Lock()

        def sink(ls, rs):
            with lock:
                collected.append((ls, rs))

        sched = PipelineScheduler(lambda: m, on_results=sink,
                                  now_fn=lambda: box["now"])
        sched.start()
        for i in range(0, len(phase1), 37):
            sched.submit(phase1[i : i + 37])
        assert sched.flush(120)
        for _ in range(m.breaker.failure_threshold):
            m.breaker.record_failure()
        assert not m.breaker.allow()
        for i in range(0, len(phase2), 37):
            sched.submit(phase2[i : i + 37])
        assert sched.flush(120)
        m.breaker.record_success()
        for i in range(0, len(phase3), 37):
            sched.submit(phase3[i : i + 37])
        assert sched.flush(120)
        sched.stop()
        return [r for _, rs in collected for r in rs]

    sk, tp = _pair(matcher_prefilter_cand_frac=1.0)
    sk_results = run(sk[0])
    tp_results = run(tp[0])
    _assert_identical("breaker", sk_results, tp_results, sk, tp)
    assert sk[0].fallback_batches > 0  # phase 2 really took the CPU path
    assert sk[0]._fw_pipeline.fused_batches > 0


def test_mid_pipeline_abort_identical():
    """pipeline.submit failpoint mid-stream: the aborted batch dies
    BEFORE any device dispatch on both paths (no commit anywhere), drains
    generically through the classic protocol, and everything after it
    stays byte-identical."""
    now = time.time()
    phases = [
        _gen_lines(300, now, seed=61),
        _gen_lines(300, now, seed=67),
    ]

    def run(m, seed):
        box = {"now": now}
        collected = []
        lock = threading.Lock()

        def sink(ls, rs):
            with lock:
                collected.append((ls, rs))

        sched = PipelineScheduler(lambda: m, on_results=sink,
                                  now_fn=lambda: box["now"])
        sched._sizer = ChurnSizer(seed=seed)
        sched.start()
        for i in range(0, len(phases[0]), 97):
            sched.submit(phases[0][i : i + 97])
        assert sched.flush(120)
        # the NEXT batch's submit fails before dispatch → generic drain
        failpoints.arm("pipeline.submit", count=1)
        for i in range(0, len(phases[1]), 97):
            sched.submit(phases[1][i : i + 97])
        assert sched.flush(120)
        failpoints.disarm()
        sched.stop()
        snap = sched.stats.peek()
        assert snap["PipelineAdmittedLines"] == \
            snap["PipelineProcessedLines"] + snap["PipelineShedLines"] + \
            snap["PipelineDrainErrorLines"]
        return [r for _, rs in collected for r in rs]

    sk, tp = _pair(matcher_prefilter_cand_frac=1.0)
    sk_results = run(sk[0], seed=9)
    tp_results = run(tp[0], seed=9)
    _assert_identical("abort", sk_results, tp_results, sk, tp)
    assert sk[0]._fw_pipeline.fused_batches > 0
