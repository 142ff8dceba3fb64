"""Parallel host path vs the serial path: byte-identical outputs.

Three surfaces of the host-parallel PR are proven here against the same
oracles the pipeline suites use (CPU reference / sync TPU batch path):

  * sharded encode workers — the scheduler splits each admission batch
    into row shards parsed/gated concurrently and merged in strict line
    order; adversarial shard boundaries (same IP straddling shards,
    all-distinct IPs, garbage/stale/deferred/non-ASCII rows landing on
    every boundary) must not perturb results, ban-log bytes, window
    state, or the unique-IP first-appearance order that slot LRU
    assignment depends on;
  * the native slot manager — runs underneath both paths here (it is on
    by default); its dedicated parity fuzz lives in
    tests/unit/test_slotmgr.py;
  * multi-chunk fused batches — a 256-line take is several 64-line
    chunks, each committed at submit and drained in order: overflow in
    the middle of a batch and per-chunk staleness masks compose.
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
from banjax_tpu.pipeline import scheduler as sched_mod
from tests.differential.test_pipeline_differential import (
    ChurnSizer,
    _gen_lines,
)
from tests.differential.test_tpu_matcher import CONFIG_YAML, result_key


def _build(matcher_cls, device_windows=True, **cfg_overrides):
    config = config_from_yaml_text(CONFIG_YAML)
    config.matcher_device_windows = device_windows
    for k, v in cfg_overrides.items():
        setattr(config, k, v)
    states = RegexRateLimitStates()
    ban_log = io.StringIO()
    dyn = DynamicDecisionLists(start_sweeper=False)
    banner = Banner(dyn, ban_log, io.StringIO(), ipset_instance=None)
    matcher = matcher_cls(config, banner, StaticDecisionLists(config), states)
    return matcher, states, dyn, ban_log


def _run_pipelined(matcher, lines, now, workers=0, sizer=None,
                   submit_step=120, seed=11):
    collected = []
    lock = threading.Lock()

    def sink(batch_lines, results):
        with lock:
            collected.append((batch_lines, results))

    sched = PipelineScheduler(
        lambda: matcher, on_results=sink, now_fn=lambda: now,
        encode_workers=workers,
    )
    if sizer is not None:
        sched._sizer = sizer
    sched.start()
    rng = random.Random(seed)
    i = 0
    while i < len(lines):
        step = rng.randrange(1, submit_step)
        sched.submit(lines[i : i + step])
        i += step
    assert sched.flush(180)
    snap = sched.snapshot()
    sched.stop()
    pipe_lines = [l for ls, _ in collected for l in ls]
    pipe_results = [r for _, rs in collected for r in rs]
    assert pipe_lines == lines, "admission order broken"
    return pipe_results, snap


@pytest.fixture
def small_shards(monkeypatch):
    """Shrink the shard floor so the worker path engages on test-sized
    batches (production floor: 2048 rows/shard)."""
    monkeypatch.setattr(sched_mod, "_MIN_SHARD_LINES", 8)


class BigSizer(ChurnSizer):
    """Random but LARGE takes, so batches span several shards (and, with
    a small matcher_batch_lines, several fused chunks)."""

    def target(self) -> int:
        return self._rng.choice([64, 100, 160, 256, 384])


@pytest.mark.parametrize("device_windows", [False, True])
def test_sharded_encode_byte_identical(small_shards, device_windows):
    """workers=3 sharded encode vs the sync oracle and the CPU
    reference: results, ban-log bytes, window state — identical, and the
    sharded path actually engaged."""
    now = time.time()
    lines = _gen_lines(1500, now)

    cpu, _, cpu_dyn, cpu_log = _build(CpuMatcher, device_windows=False)
    cpu_results = [cpu.consume_line(l, now_unix=now) for l in lines]

    sync, sync_states, _, sync_log = _build(TpuMatcher, device_windows)
    sync_results = sync.consume_lines(lines, now_unix=now)

    par, par_states, par_dyn, par_log = _build(TpuMatcher, device_windows)
    par_results, snap = _run_pipelined(
        par, lines, now, workers=3, sizer=BigSizer(seed=99)
    )

    for i, (c, s, p) in enumerate(
        zip(cpu_results, sync_results, par_results)
    ):
        assert result_key(c) == result_key(s), f"sync diverged at {i}"
        assert result_key(c) == result_key(p), f"sharded diverged at {i}"
    assert par_log.getvalue() == cpu_log.getvalue() == sync_log.getvalue()
    assert par_dyn.metrics() == cpu_dyn.metrics()
    sync_view = (
        sync.device_windows if device_windows else sync_states
    ).format_states()
    par_view = (
        par.device_windows if device_windows else par_states
    ).format_states()
    assert sync_view == par_view
    assert snap["EncodeShardedBatches"] > 0, "worker path never engaged"
    assert snap["PipelineProcessedLines"] == len(lines)


def test_sharded_encode_all_distinct_ips_with_eviction_churn(small_shards):
    """The adversarial host shape from PERF r4: every line a distinct IP,
    so every unique-table merge crosses shard boundaries and (with a tiny
    fixed slot capacity) the slot manager churns through evictions and
    restores.  Byte-identity must hold, and the merged unique-IP
    first-appearance order must produce the same slot LRU sequence."""
    now = time.time()
    lines = []
    for i in range(900):
        ip = f"9.{i >> 16 & 255}.{i >> 8 & 255}.{i & 255}"
        if i % 3 == 0:
            lines.append(
                f"{now:f} {ip} GET example.com GET /page HTTP/1.1 ua -"
            )
        elif i % 7 == 0:
            # repeat ips straddling shard boundaries
            lines.append(
                f"{now:f} 8.8.8.8 GET example.com GET /page HTTP/1.1 ua -"
            )
        else:
            lines.append(
                f"{now:f} {ip} GET news.net GET /benign HTTP/1.1 ua -"
            )

    sync, _, _, sync_log = _build(
        TpuMatcher, True, matcher_window_capacity=64
    )
    sync_results = sync.consume_lines(lines, now_unix=now)

    par, _, _, par_log = _build(
        TpuMatcher, True, matcher_window_capacity=64
    )
    par_results, snap = _run_pipelined(
        par, lines, now, workers=4, sizer=BigSizer(seed=5)
    )

    assert [result_key(r) for r in par_results] == \
        [result_key(r) for r in sync_results]
    assert par_log.getvalue() == sync_log.getvalue()
    assert par.device_windows.format_states() == \
        sync.device_windows.format_states()
    assert par.device_windows.eviction_count > 0, (
        "capacity 64 under distinct-IP flood should churn evictions"
    )
    assert snap["EncodeShardedBatches"] > 0


def test_shard_boundary_rows_with_flags(small_shards):
    """Garbage, stale, deferred-timestamp, and non-ASCII (host_eval)
    rows planted so shard boundaries land on and around them: the merge
    must rebase flagged results to global rows and fall back correctly
    when a shard's pre-encoded arrays are missing."""
    now = time.time()
    lines = []
    for i in range(600):
        k = i % 10
        ip = f"1.2.{i % 4}.{i % 6}"
        if k == 0:
            lines.append("short garbage")
        elif k == 1:
            lines.append(
                f"{now - 100:f} {ip} GET example.com GET /old HTTP/1.1 ua -"
            )
        elif k == 2:
            # underscone-separator float: C parse defers to Python
            lines.append(
                f"1_0.5 {ip} GET example.com GET /defer HTTP/1.1 ua -"
            )
        elif k == 3:
            # non-ASCII rest → host_eval row (fused ineligible batch)
            lines.append(
                f"{now:f} {ip} GET example.com GET /café HTTP/1.1 ua -"
            )
        else:
            lines.append(
                f"{now:f} {ip} GET example.com GET /page{i % 7} HTTP/1.1 ua -"
            )

    sync, _, _, sync_log = _build(TpuMatcher, True)
    sync_results = sync.consume_lines(lines, now_unix=now)

    par, _, _, par_log = _build(TpuMatcher, True)
    par_results, snap = _run_pipelined(
        par, lines, now, workers=3, sizer=BigSizer(seed=42)
    )

    assert [result_key(r) for r in par_results] == \
        [result_key(r) for r in sync_results]
    assert par_log.getvalue() == sync_log.getvalue()
    assert par.device_windows.format_states() == \
        sync.device_windows.format_states()
    assert snap["EncodeShardedBatches"] > 0


def test_multichunk_fused_batches_byte_identical(small_shards):
    """Multi-chunk fused batches (matcher_batch_lines=64 under 256-line
    takes): byte-identical results, ban-log bytes, and window state
    against the sync entry; the fused path engaged."""
    now = time.time()
    lines = _gen_lines(1200, now, seed=31)

    sync, _, _, sync_log = _build(TpuMatcher, True)
    sync_results = sync.consume_lines(lines, now_unix=now)

    # cand_frac=1.0: small (64-line) chunks must not overflow the
    # prefilter's candidate capacity — this test wants the fused commit,
    # not the fallback (that composition is tested below)
    par, _, _, par_log = _build(
        TpuMatcher, True,
        matcher_batch_lines=64, matcher_prefilter_cand_frac=1.0,
    )
    par_results, _ = _run_pipelined(
        par, lines, now, workers=0, sizer=BigSizer(seed=7)
    )

    assert [result_key(r) for r in par_results] == \
        [result_key(r) for r in sync_results]
    assert par_log.getvalue() == sync_log.getvalue()
    assert par.device_windows.format_states() == \
        sync.device_windows.format_states()
    assert par.pipelined_fused_chunks > 0, "fused path never engaged"


def test_multichunk_overflow_mid_batch_byte_identical(small_shards):
    """Overflow in the middle of a multi-chunk batch: all-matching bursts
    (candidate overflow → classic mid-pipeline replay, and the chain
    scalar gating the chunks of the SAME batch dispatched behind it)
    between benign runs, vs the same stream through the sync entry."""
    now = time.time()
    lines = []
    for burst in range(20):
        if burst % 3 == 0:
            lines += [
                f"{now:f} 7.7.{burst}.{i} POST example.com POST /x{i} HTTP/1.1 ua -"
                for i in range(40)
            ]
        else:
            lines += _gen_lines(40, now, seed=200 + burst)

    sync, _, _, sync_log = _build(
        TpuMatcher, True, matcher_batch_lines=64,
        matcher_prefilter_cand_frac=0.5,
    )
    sync_results = sync.consume_lines(lines, now_unix=now)

    par, _, _, par_log = _build(
        TpuMatcher, True, matcher_batch_lines=64,
        matcher_prefilter_cand_frac=0.5,
    )
    par_results, _ = _run_pipelined(
        par, lines, now, workers=0, sizer=BigSizer(seed=3)
    )

    assert [result_key(r) for r in par_results] == \
        [result_key(r) for r in sync_results]
    assert par_log.getvalue() == sync_log.getvalue()
    assert par.device_windows.format_states() == \
        sync.device_windows.format_states()
    assert par.pipelined_fused_fallbacks > 0, (
        "overflow fallback never exercised"
    )
    assert par._fw_pipeline.overflow_causes["chain"] > 0, (
        "no chunk was gated by a predecessor's overflow"
    )


def test_submit_stale_masks_per_chunk():
    """Staleness at the commit of a multi-chunk batch: chunks that are
    fully stale, mixed, and fully fresh — driven through the split
    protocol directly so the submit happens 3 s after encode.  Each
    chunk carries its own live mask into its program."""
    now = time.time()
    m, _, _, ban_log = _build(
        TpuMatcher, True,
        matcher_batch_lines=64, matcher_prefilter_cand_frac=1.0,
    )
    # chunk 0: all stale at submit; chunk 1: half and half; chunk 2: fresh
    old = [
        f"{now - 8:f} 9.9.{i >> 8}.{i & 255} GET per-site.com GET /blockme HTTP/1.1 ua -"
        for i in range(96)
    ]
    fresh = [
        f"{now:f} 8.8.{i >> 8}.{i & 255} GET per-site.com GET /blockme HTTP/1.1 ua -"
        for i in range(96)
    ]
    lines = old + fresh
    state = m.pipeline_begin(lines, now)
    assert state.get("fused_eligible")
    m.pipeline_submit(state, now=now + 3)
    assert state.get("fused") and len(state["fused"]) == 3
    m.pipeline_collect(state)
    results, n_stale = m.pipeline_finish(state, now + 3)
    assert n_stale == 96
    assert all(r.old_line and not r.rule_results for r in results[:96])
    assert all(not r.old_line and r.rule_results for r in results[96:])
    view = m.device_windows.format_states()
    assert "9.9.0.0" not in view and "8.8.0.0" in view
    assert ban_log.getvalue().count("instant block") == 96
    # later batches still drain (no leaked order turns from the
    # fully-stale chunk)
    state2 = m.pipeline_begin(fresh, now)
    m.pipeline_submit(state2, now=now)
    m.pipeline_collect(state2)
    results2, _ = m.pipeline_finish(state2, now)
    assert all(r.rule_results for r in results2)
