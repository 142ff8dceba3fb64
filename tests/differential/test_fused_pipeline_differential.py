"""Pipelined FUSED path vs the synchronous fused path: byte identical
under adversarial conditions (the pipeline's ordering contract).

The streaming pipeline drives the fused matcher+windows program — match
and window commit in one dispatch at the submit stage, any number of
batches ahead; the drain stage pulls and replays in admission order.
These tests prove the overlap changes NOTHING observable:

  * adversarial batch churn with shared IPs crossing every batch/chunk
    boundary (window counters must accumulate in exact log order);
  * overflow chunks interleaved with ok chunks (the classic mid-pipeline
    replay, order turns held);
  * breaker-OPEN mid-stream draining through the CPU reference matcher;
  * the h2d witness: the pipelined fused path must move FAR fewer bytes
    host→device than the classic bitmap path (no dense re-upload).
"""

import io
import random
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
from tests.classic_downgrade import scan_selftest_failing
from tests.differential.test_pipeline_differential import ChurnSizer, _gen_lines
from tests.differential.test_tpu_matcher import CONFIG_YAML, result_key


def _build(matcher_cls, fused=True, **cfg_overrides):
    """fused=False: the classic bitmap protocol, by the scan-selftest
    downgrade (tests/classic_downgrade.py)."""
    config = config_from_yaml_text(CONFIG_YAML)
    config.matcher_device_windows = True
    for k, v in cfg_overrides.items():
        setattr(config, k, v)
    states = RegexRateLimitStates()
    ban_log = io.StringIO()
    dyn = DynamicDecisionLists(start_sweeper=False)
    banner = Banner(dyn, ban_log, io.StringIO(), ipset_instance=None)
    with scan_selftest_failing(not fused):
        matcher = matcher_cls(
            config, banner, StaticDecisionLists(config), states
        )
    if matcher_cls is TpuMatcher:
        assert (matcher._fw_pipeline is not None) == fused
    return matcher, states, dyn, ban_log


def _run_pipelined(matcher, lines, now, sizer_seed=7, submit_seed=11,
                   tail=()):
    """`tail`: lines submitted after the stream has drained whole."""
    collected = []
    lock = threading.Lock()

    def sink(batch_lines, results):
        with lock:
            collected.append((batch_lines, results))

    sched = PipelineScheduler(lambda: matcher, on_results=sink,
                              now_fn=lambda: now)
    sched._sizer = ChurnSizer(seed=sizer_seed)
    sched.start()
    rng = random.Random(submit_seed)
    i = 0
    while i < len(lines):
        step = rng.randrange(1, 120)
        sched.submit(lines[i : i + step])
        i += step
    assert sched.flush(180)
    if tail:
        sched.submit(list(tail))
        assert sched.flush(180)
    sched.stop()
    pipe_lines = [l for ls, _ in collected for l in ls]
    pipe_results = [r for _, rs in collected for r in rs]
    assert pipe_lines == lines + list(tail), "admission order broken"
    return pipe_results, sched


def test_pipelined_fused_is_byte_identical_and_kills_dense_upload():
    """The tentpole acceptance: fused+pipelined output == sync fused ==
    CPU reference (results, ban-log bytes, window state), the fused
    path actually engaged, and the h2d byte counter shows the dense
    bitmap re-upload gone relative to the classic pipelined path."""
    now = time.time()
    lines = _gen_lines(1500, now)

    cpu, _, cpu_dyn, cpu_log = _build(CpuMatcher)
    cpu_results = [cpu.consume_line(l, now_unix=now) for l in lines]

    sync, _, _, sync_log = _build(TpuMatcher)
    sync_results = sync.consume_lines(lines, now_unix=now)

    fused, _, fused_dyn, fused_log = _build(TpuMatcher)
    fused_results, _ = _run_pipelined(fused, lines, now)

    classic, _, _, classic_log = _build(TpuMatcher, fused=False)
    classic_results, _ = _run_pipelined(classic, lines, now)

    for i, (c, s, f, k) in enumerate(zip(
        cpu_results, sync_results, fused_results, classic_results
    )):
        assert result_key(c) == result_key(s), f"sync diverged at {i}"
        assert result_key(c) == result_key(f), f"fused-pipelined diverged at {i}"
        assert result_key(c) == result_key(k), f"classic-pipelined diverged at {i}"
    assert fused_log.getvalue() == cpu_log.getvalue() == sync_log.getvalue()
    assert classic_log.getvalue() == cpu_log.getvalue()
    assert fused_dyn.metrics() == cpu_dyn.metrics()
    assert fused.device_windows.format_states() == \
        sync.device_windows.format_states()
    assert fused.device_windows.format_states() == \
        classic.device_windows.format_states()

    # the fused path really ran (this stream has host-eval-free
    # batches; some batches legitimately take the classic path when a
    # garbage line defers)
    assert fused.pipelined_fused_chunks > 0, "fused path never engaged"
    assert classic.pipelined_fused_chunks == 0


def test_h2d_witness_dense_reupload_gone_at_rule_scale():
    """The fusion-win witness at a realistic rule count: the classic
    pipelined path re-uploads a dense [B, n_rules] bitmap for the drain
    commit (n_rules bytes per line — the ~16 MB/batch at 1k rules / 65k
    lines); the fused path uploads only the encoded classes + a
    per-row live mask.  At 200 rules the classic h2d must exceed fused by
    roughly the bitmap's size."""
    import yaml as _yaml

    from banjax_tpu.scenarios.synth import generate_lines, generate_rules

    patterns = generate_rules(200)
    rules_yaml = _yaml.safe_dump({
        "regexes_with_rates": [
            {"rule": f"crs{i}", "regex": p, "interval": 60,
             "hits_per_interval": 50, "decision": "nginx_block"}
            for i, p in enumerate(patterns)
        ]
    })
    now = time.time()
    rests = generate_lines(1024, patterns, seed=51)
    lines = [
        f"{now:.6f} 10.6.{(i % 512) >> 8}.{i % 256} {r}"
        for i, r in enumerate(rests)
    ]

    def run(fused_flag):
        config = config_from_yaml_text(rules_yaml)
        config.matcher_device_windows = True
        states = RegexRateLimitStates()
        dyn = DynamicDecisionLists(start_sweeper=False)
        banner = Banner(dyn, io.StringIO(), io.StringIO(), ipset_instance=None)
        with scan_selftest_failing(not fused_flag):
            m = TpuMatcher(config, banner, StaticDecisionLists(config), states)
        assert (m._fw_pipeline is not None) == fused_flag
        sched = PipelineScheduler(
            lambda: m, now_fn=lambda: now, min_batch=256, max_batch=256,
        )
        sched.start()
        for i in range(0, len(lines), 256):
            sched.submit(lines[i : i + 256])
        assert sched.flush(300)
        sched.stop()
        return m

    fused = run(True)
    classic = run(False)
    assert fused.pipelined_fused_chunks > 0
    fused_h2d = fused.stats.h2d_bytes_per_batch()
    classic_h2d = classic.stats.h2d_bytes_per_batch()
    # the dense bitmap is 200 B/line; everything else is shared — demand
    # at least half that delta to stay robust to bucketing noise
    assert classic_h2d - fused_h2d > 0.5 * 200 * 256, (
        fused_h2d, classic_h2d
    )


def test_overflow_chunks_interleaved_with_ok_chunks():
    """Bursts of all-matching traffic (candidate overflow → classic
    mid-pipeline replay) interleaved with benign chunks: byte-identical,
    fallbacks counted, pins/turns never leak (the flush would hang).
    An overflow's chain scalar gates every chunk already dispatched
    behind it, so how many benign chunks of the stream commit fused is a
    matter of timing; the tail, submitted once the stream has drained,
    starts a fresh chain and must commit fused (the phase-gap shape of
    this lives in tests/differential/test_single_kernel_differential.py)."""
    now = time.time()
    rng = random.Random(3)
    lines = []
    for burst in range(30):
        if burst % 3 == 0:
            # every line matches 'POST .*' → stage-1 gate passes them all
            # → candidate capacity exceeded → PipelineOverflow mid-stream
            lines += [
                f"{now:f} 7.7.{burst}.{i} POST example.com POST /x{i} HTTP/1.1 ua -"
                for i in range(40)
            ]
        else:
            lines += _gen_lines(40, now, seed=100 + burst)

    tail = [
        f"{now:f} 6.6.6.{i} HEAD quiet.org HEAD /q{i} HTTP/1.1 ua -"
        for i in range(16)
    ]

    sync, _, _, sync_log = _build(TpuMatcher)
    sync_results = sync.consume_lines(lines + tail, now_unix=now)

    pipe, _, _, pipe_log = _build(TpuMatcher)
    pipe_results, _ = _run_pipelined(pipe, lines, now, sizer_seed=5,
                                     tail=tail)

    assert [result_key(r) for r in pipe_results] == \
        [result_key(r) for r in sync_results]
    assert pipe_log.getvalue() == sync_log.getvalue()
    assert pipe.device_windows.format_states() == \
        sync.device_windows.format_states()
    assert pipe.pipelined_fused_fallbacks > 0, (
        "overflow fallback never exercised — the burst should overflow"
    )
    assert pipe.pipelined_fused_chunks > 0


def test_breaker_open_mid_stream_drains_via_cpu_reference():
    """Phase 2 runs with the breaker OPEN: those batches drain through
    the CPU reference matcher (host window counters), then the breaker
    recovers and the fused path resumes — identical to a sync run that
    trips at the same stream offsets."""
    now = time.time()
    phase1 = _gen_lines(300, now, seed=41)
    phase2 = _gen_lines(200, now, seed=43)
    phase3 = _gen_lines(300, now, seed=47)

    def trip(m):
        # default recovery (30 s) keeps OPEN for the whole phase
        for _ in range(m.breaker.failure_threshold):
            m.breaker.record_failure()
        assert not m.breaker.allow()

    def recover(m):
        # record_success force-closes from any state (deterministic, no
        # wall-clock dependence)
        m.breaker.record_success()
        assert m.breaker.allow()

    # cand_frac 1.0: this mix matches often; give stage 2 full capacity
    # so the phases commit in the fused program, not the overflow fallback
    sync, _, _, sync_log = _build(
        TpuMatcher, matcher_prefilter_cand_frac=1.0
    )
    sync.consume_lines(phase1, now_unix=now)
    trip(sync)
    sync.consume_lines(phase2, now_unix=now)  # breaker-guarded → CPU ref
    recover(sync)
    sync.consume_lines(phase3, now_unix=now)

    pipe, _, _, pipe_log = _build(
        TpuMatcher, matcher_prefilter_cand_frac=1.0
    )
    collected = []
    lock = threading.Lock()

    def sink(batch_lines, results):
        with lock:
            collected.append((batch_lines, results))

    sched = PipelineScheduler(lambda: pipe, on_results=sink,
                              now_fn=lambda: now)
    sched.start()
    for i in range(0, len(phase1), 37):
        sched.submit(phase1[i : i + 37])
    assert sched.flush(120)
    trip(pipe)
    for i in range(0, len(phase2), 37):
        sched.submit(phase2[i : i + 37])
    assert sched.flush(120)
    recover(pipe)
    for i in range(0, len(phase3), 37):
        sched.submit(phase3[i : i + 37])
    assert sched.flush(120)
    sched.stop()

    assert pipe_log.getvalue() == sync_log.getvalue()
    assert pipe.device_windows.format_states() == \
        sync.device_windows.format_states()
    assert pipe.fallback_batches > 0  # phase 2 really took the CPU path
    # phases 1/3 went through the fused path (commit or its counted
    # overflow fallback — this mix can still overflow the pair budget)
    assert pipe.pipelined_fused_chunks + pipe.pipelined_fused_fallbacks > 0
    snap = sched.snapshot()
    assert snap["PipelineProcessedLines"] == len(phase1) + len(phase2) + len(phase3)
    assert snap["PipelineShedLines"] == 0


@pytest.mark.slow
def test_repeated_fused_streams_accumulate_identically():
    now = time.time()
    lines = _gen_lines(500, now, seed=29)
    sync, _, _, sync_log = _build(TpuMatcher)
    sync.consume_lines(lines, now_unix=now)
    sync.consume_lines(lines, now_unix=now)

    pipe, _, _, pipe_log = _build(TpuMatcher)
    sched = PipelineScheduler(lambda: pipe, now_fn=lambda: now)
    sched._sizer = ChurnSizer(seed=13)
    sched.start()
    for _ in range(2):
        for i in range(0, len(lines), 41):
            sched.submit(lines[i : i + 41])
    assert sched.flush(180)
    sched.stop()
    assert pipe_log.getvalue() == sync_log.getvalue()
    assert pipe.device_windows.format_states() == \
        sync.device_windows.format_states()


def _with_long_lines(lines, now, seed=3):
    """Every 37th, 53rd and 71st line a long one: the per-site rule's
    `blockme` behind 300-8,100 bytes of path (a match that begins past
    the short width), a 400-byte POST (`POST .*`: an always-column), a
    500-byte DELETE on the host that skips its rule."""
    rng = random.Random(seed)

    def pad(n):
        return "".join(
            rng.choice("abcdefghij0123456789/_=&+-") for _ in range(n))

    lines = list(lines)
    for i in range(0, len(lines), 37):
        ip = f"1.2.{i % 4}.{i % 6}"
        size = (300, 700, 2000, 8100)[i % 4]
        lines[i] = (f"{now:f} {ip} GET per-site.com GET "
                    f"/{pad(size)}/blockme HTTP/1.1 ua -")
    for i in range(5, len(lines), 53):
        ip = f"1.2.{i % 4}.{i % 6}"
        lines[i] = (f"{now:f} {ip} POST example.com POST /{pad(400)} "
                    "HTTP/1.1 ua -")
    for i in range(9, len(lines), 71):
        ip = f"1.2.{i % 4}.{i % 6}"
        lines[i] = (f"{now:f} {ip} DELETE skipme.com DELETE /{pad(500)} "
                    "HTTP/1.1 ua -")
    return lines


def test_long_lines_under_churn_are_byte_identical():
    """Lines over the short width (matcher_max_line_len) ride the fused
    program's long operand: under batch churn, with chunks that overflow
    into the classic replay beside chunks that commit, the pipelined
    fused output, the sync fused output and the classic protocol's all
    equal the CPU reference — results, ban-log bytes, window state — and
    no batch left the fused path for a line's length."""
    now = time.time()
    lines = _with_long_lines(_gen_lines(900, now), now)
    n_long = sum(len(l.split(" ", 2)[-1]) > 256 for l in lines)
    assert n_long >= 40

    cpu, _, cpu_dyn, cpu_log = _build(CpuMatcher)
    cpu_results = [cpu.consume_line(l, now_unix=now) for l in lines]
    sync, _, _, sync_log = _build(TpuMatcher)
    sync_results = sync.consume_lines(lines, now_unix=now)
    fused, _, fused_dyn, fused_log = _build(TpuMatcher)
    fused_results, _ = _run_pipelined(fused, lines, now)
    classic, _, _, classic_log = _build(TpuMatcher, fused=False)
    classic_results, _ = _run_pipelined(classic, lines, now)

    for i, (c, s, f, k) in enumerate(zip(
        cpu_results, sync_results, fused_results, classic_results
    )):
        assert result_key(c) == result_key(s), f"sync diverged at {i}"
        assert result_key(c) == result_key(f), f"fused diverged at {i}"
        assert result_key(c) == result_key(k), f"classic diverged at {i}"
    assert fused_log.getvalue() == cpu_log.getvalue() == sync_log.getvalue()
    assert classic_log.getvalue() == cpu_log.getvalue()
    assert "per-site.com" in cpu_log.getvalue()
    assert fused_dyn.metrics() == cpu_dyn.metrics()
    assert fused.device_windows.format_states() == \
        sync.device_windows.format_states() == \
        classic.device_windows.format_states()
    # the long rows were the device's in both fused entries
    assert fused.long_lines >= n_long - 10 and sync.long_lines >= n_long - 10
    assert fused.unfused_batches == sync.unfused_batches == {
        "line_length": 0, "non_ascii": 0}
    assert fused.pipelined_fused_chunks + fused.pipelined_fused_fallbacks > 0
    assert fused._fw_pipeline.long_rows_seen
