"""Differential proof that the traffic sketch is read-only telemetry:
a pipelined run with the sketch enabled produces byte-identical
ban-log / result-stream / window-state output to a run with it
disabled, under adversarial batch churn, on the fused single kernel and
on the classic bitmap protocol — and the enabled run actually populated the sketch (the
non-vacuity witness, ISSUE 8)."""

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
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.pipeline import PipelineScheduler
from tests.classic_downgrade import scan_selftest_failing
from tests.differential.test_pipeline_differential import (
    ChurnSizer,
    _gen_lines,
)
from tests.differential.test_tpu_matcher import CONFIG_YAML, result_key


def _build(sketch_on: bool, single_kernel: bool):
    config = config_from_yaml_text(CONFIG_YAML)
    config.matcher_device_windows = True
    config.traffic_sketch_enabled = sketch_on
    states = RegexRateLimitStates()
    ban_log = io.StringIO()
    dyn = DynamicDecisionLists(start_sweeper=False)
    banner = Banner(dyn, ban_log, io.StringIO(), ipset_instance=None)
    with scan_selftest_failing(not single_kernel):
        matcher = TpuMatcher(
            config, banner, StaticDecisionLists(config), states
        )
    assert matcher.describe()["fused_protocol"] == (
        "single-kernel" if single_kernel else "classic"
    )
    return matcher, states, ban_log


def _run_pipelined(lines, now, seed, sketch_on, single_kernel):
    matcher, states, ban_log = _build(sketch_on, single_kernel)
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
    rng = random.Random(23)
    i = 0
    while i < len(lines):
        step = rng.randrange(1, 90)
        sched.submit(lines[i : i + step])
        i += step
    assert sched.flush(120)
    sched.stop()
    sketch = matcher.traffic_sketch
    # the authoritative window state with device windows on is the
    # device-backed shadow, not the bypassed host RegexRateLimitStates
    dw_states = matcher.device_windows.format_states()
    matcher.close()
    results = {}
    for batch_lines, batch_results in collected:
        if batch_results is None:
            continue
        for line, res in zip(batch_lines, batch_results):
            results.setdefault(line, []).append(result_key(res))
    return results, ban_log.getvalue(), dw_states, sketch


@pytest.mark.parametrize("single_kernel", [True, False])
def test_sketch_on_off_byte_identical(single_kernel):
    """Both device protocols: single-kernel (commit at submit — where
    the sketch update rides) and the classic bitmap protocol (the sketch
    folds at the drain's window apply), reached by the scan-selftest
    downgrade."""
    now = time.time()
    lines = _gen_lines(1200, now)

    off_results, off_log, off_states, off_sketch = _run_pipelined(
        lines, now, seed=13, sketch_on=False, single_kernel=single_kernel
    )
    assert off_sketch is None

    on_results, on_log, on_states, on_sketch = _run_pipelined(
        lines, now, seed=13, sketch_on=True, single_kernel=single_kernel
    )

    assert on_log == off_log          # ban-log bytes identical
    assert on_results == off_results  # per-line result stream identical
    assert on_states == off_states    # rate-limit window state identical

    # non-vacuity: the enabled run folded real traffic and can name a
    # heavy hitter with a conservative estimate
    assert on_sketch is not None
    assert on_sketch.lines_total > 0
    summary = on_sketch.pull(force=True)
    assert summary["top"], "sketch saw traffic but has no heavy hitters"
    assert summary["distinct_ips_estimate"] > 0
