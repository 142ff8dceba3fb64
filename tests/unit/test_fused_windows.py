"""Fused matcher+windows pipeline (matcher/fused_windows.py): one device
dispatch per batch, byte-identical to the serial CPU reference — including
every overflow fallback, which must leave the device window state untouched
(the write gate) and still produce identical output via the classic path."""

import time

import numpy as np
import pytest
import yaml

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.matcher.cpu_ref import CpuMatcher
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.scenarios import synth
from tests.mock_banner import MockBanner


def _rules_yaml(patterns, hits=3, interval=20):
    return yaml.safe_dump({
        "regexes_with_rates": [
            {"rule": f"r{i}", "regex": p, "interval": interval,
             "hits_per_interval": hits, "decision": "nginx_block"}
            for i, p in enumerate(patterns)
        ]
    })


def _mk(cls, yaml_text, **ov):
    cfg = config_from_yaml_text(yaml_text)
    for k, v in ov.items():
        setattr(cfg, k, v)
    banner = MockBanner()
    return cls(cfg, banner, StaticDecisionLists(cfg), RegexRateLimitStates()), banner


def _key(res):
    return [
        (x.rule_name, x.regex_match, x.skip_host, x.seen_ip,
         None if x.rate_limit_result is None else
         (int(x.rate_limit_result.match_type), x.rate_limit_result.exceeded))
        for x in res.rule_results
    ]


def _drive_pair(patterns, lines, now, **tpu_overrides):
    y = _rules_yaml(patterns)
    cpu, cb = _mk(CpuMatcher, y)
    tpu, tb = _mk(TpuMatcher, y, matcher_device_windows=True, **tpu_overrides)
    want = [cpu.consume_line(l, now) for l in lines]
    batch = tpu_overrides.get("matcher_batch_lines", 128)
    got = []
    for s in range(0, len(lines), batch):
        got.extend(tpu.consume_lines(lines[s : s + batch], now))
    assert [_key(a) for a in want] == [_key(b) for b in got]
    assert cb.bans == tb.bans
    assert cb.regex_ban_logs == tb.regex_ban_logs
    return tpu


def _lines(patterns, n, now, attack_rate, n_ips=24, seed=3):
    rests = synth.generate_lines(n, patterns, seed=seed,
                                 attack_rate=attack_rate)
    return [
        f"{now + i * 0.0005:.6f} 10.9.{i % n_ips}.1 {r}"
        for i, r in enumerate(rests)
    ]


def test_pipeline_engages_and_matches_oracle():
    patterns = synth.generate_rules(60, seed=31) + [r".*", r"^$"]
    now = time.time()
    lines = _lines(patterns[:-2], 300, now, attack_rate=0.05) + [
        f"{now:.6f} 10.9.0.1 "  # empty rest: ^$ matches
    ]
    tpu = _drive_pair(
        patterns, lines, now + 1,
        matcher_batch_lines=128, matcher_prefilter_cand_frac=0.5,
    )
    assert tpu._fw_pipeline is not None
    assert tpu._fw_pipeline.fused_batches > 0
    assert tpu._fw_pipeline.fallback_batches == 0


def test_candidate_overflow_falls_back_identically():
    """All-matching traffic exceeds the candidate capacity: the pipeline's
    dense bitmap is incomplete, so the batch recomputes single-stage and
    replays classic — output still identical, state never corrupted."""
    patterns = synth.generate_rules(40, seed=32)
    now = time.time()
    lines = _lines(patterns, 200, now, attack_rate=1.0)
    tpu = _drive_pair(
        patterns, lines, now + 1,
        matcher_batch_lines=64, matcher_prefilter_cand_frac=1.0 / 64,
    )
    assert tpu._fw_pipeline is not None
    assert tpu._fw_pipeline.fallback_batches > 0


def test_event_overflow_falls_back_identically(monkeypatch):
    """More window events than the program's event capacity (the cap on
    rows x always-columns is lowered so `.*` overflows it): the gate drops
    every state write, and the classic apply (which splits) replays the
    batch."""
    from banjax_tpu.matcher import prefilter

    monkeypatch.setattr(prefilter, "_MAX_EVENT_CAPACITY", 64)
    patterns = synth.generate_rules(30, seed=33) + [r".*"]
    now = time.time()
    lines = _lines(patterns[:-1], 256, now, attack_rate=0.1)
    y = _rules_yaml(patterns)
    cpu, cb = _mk(CpuMatcher, y)
    tpu, tb = _mk(
        TpuMatcher, y, matcher_device_windows=True,
        matcher_batch_lines=256, matcher_prefilter_cand_frac=1.0,
    )
    # shrink max_events below the per-batch event count (every line fires .*)
    tpu.device_windows.max_events = max(tpu.compiled.n_rules, 64)
    want = [cpu.consume_line(l, now + 1) for l in lines]
    got = tpu.consume_lines(lines, now + 1)
    assert [_key(a) for a in want] == [_key(b) for b in got]
    assert cb.bans == tb.bans
    assert tpu._fw_pipeline.fallback_batches > 0


def test_multi_chunk_burst_pipelines_identically():
    """One consume_lines call larger than matcher_batch_lines goes through
    the cross-chunk pipelined submit path (chunk N+1 in flight while N
    collects) — output identical to the serial reference."""
    patterns = synth.generate_rules(30, seed=35)
    now = time.time()
    lines = _lines(patterns, 400, now, attack_rate=0.1, n_ips=40, seed=9)
    y = _rules_yaml(patterns)
    cpu, cb = _mk(CpuMatcher, y)
    tpu, tb = _mk(
        TpuMatcher, y, matcher_device_windows=True,
        matcher_batch_lines=64, matcher_prefilter_cand_frac=1.0,
    )
    want = [cpu.consume_line(l, now + 1) for l in lines]
    got = tpu.consume_lines(lines, now + 1)  # ONE call: 7 chunks pipeline
    assert [_key(a) for a in want] == [_key(b) for b in got]
    assert cb.bans == tb.bans
    assert tpu._fw_pipeline.fused_batches >= 6


def test_multi_chunk_with_tight_slot_capacity():
    """Pipelined chunks + a slot capacity too small for two chunks' pins:
    the drain-and-retry path must keep output identical."""
    patterns = synth.generate_rules(20, seed=36)
    now = time.time()
    lines = _lines(patterns, 300, now, attack_rate=0.2, n_ips=90, seed=10)
    y = _rules_yaml(patterns)
    cpu, cb = _mk(CpuMatcher, y)
    tpu, tb = _mk(
        TpuMatcher, y, matcher_device_windows=True,
        matcher_batch_lines=64, matcher_prefilter_cand_frac=1.0,
        matcher_window_capacity=48,
    )
    want = [cpu.consume_line(l, now + 1) for l in lines]
    got = tpu.consume_lines(lines, now + 1)
    assert [_key(a) for a in want] == [_key(b) for b in got]
    assert cb.bans == tb.bans


def test_mixed_overflow_chunks_keep_apply_order():
    """The ordering hazard the two-program split exists for: a burst where
    SOME chunks overflow (classic fallback) and others ride the fused
    apply, with the same IPs hitting the same rules across chunks. Any
    out-of-order window application shifts which exact hit trips the
    limit — the oracle comparison catches one event of reordering."""
    patterns = synth.generate_rules(25, seed=37)
    now = time.time()
    # alternate benign-ish and attack-heavy 64-line stretches so chunk
    # overflow status flips mid-burst, all on a small shared IP pool
    lines = []
    for stretch in range(6):
        rate = 1.0 if stretch % 2 else 0.05
        rests = synth.generate_lines(64, patterns, seed=40 + stretch,
                                     attack_rate=rate)
        for i, r in enumerate(rests):
            k = len(lines)
            lines.append(
                f"{now + k * 0.0004:.6f} 10.11.{k % 6}.1 {r}"
            )
    y = _rules_yaml(patterns, hits=4, interval=30)
    cpu, cb = _mk(CpuMatcher, y)
    tpu, tb = _mk(
        TpuMatcher, y, matcher_device_windows=True,
        matcher_batch_lines=64, matcher_prefilter_cand_frac=0.25,
    )
    want = [cpu.consume_line(l, now + 1) for l in lines]
    got = tpu.consume_lines(lines, now + 1)  # ONE call: 6 chunks overlap
    assert [_key(a) for a in want] == [_key(b) for b in got]
    assert cb.bans == tb.bans
    fw = tpu._fw_pipeline
    assert fw.fused_batches > 0 and fw.fallback_batches > 0, (
        fw.fused_batches, fw.fallback_batches,
    )


def test_pipeline_with_eviction_churn():
    """Slot eviction/spill/restore under the pipeline stays lossless."""
    patterns = synth.generate_rules(25, seed=34)
    now = time.time()
    lines = _lines(patterns, 400, now, attack_rate=0.3, n_ips=60, seed=8)
    tpu = _drive_pair(
        patterns, lines, now + 1,
        matcher_batch_lines=64, matcher_prefilter_cand_frac=1.0,
        matcher_window_capacity=16,
    )
    assert tpu.device_windows.eviction_count > 0
    assert tpu._fw_pipeline.fused_batches > 0

@pytest.mark.parametrize("seed", [11, 22, 33, 44])
def test_generative_overflow_interleaving_stress(seed):
    """Randomized knob combinations chosen to force every fallback edge at
    once — candidate overflow, pair overflow, event overflow, slot-refusal
    splits, eviction churn, multi-chunk overlap — across multiple bursts
    on a shared IP pool, byte-identical to the serial CPU reference."""
    import random

    rng = random.Random(seed)
    patterns = [r"GET /attack[0-9]+", r"(?i)scanbot", r"POST /x[a-z]{1,3}",
                r"/probe\.php"]
    now = time.time()
    knobs = dict(
        matcher_batch_lines=rng.choice([32, 64, 96]),
        matcher_prefilter_cand_frac=rng.choice([1.0 / 64, 0.1, 1.0]),
        matcher_window_capacity=rng.choice([0, 8, 16]),
    )
    tpu = None
    y = _rules_yaml(patterns, hits=rng.choice([0, 2, 5]),
                    interval=rng.choice([5, 60]))
    cpu, cb = _mk(CpuMatcher, y)
    tpu, tb = _mk(TpuMatcher, y, matcher_device_windows=True, **knobs)
    if rng.random() < 0.5:
        tpu.device_windows.max_events = max(tpu.compiled.n_rules, 16)
    want, got = [], []
    for burst in range(3):
        n = rng.choice([64, 160, 256])
        lines = _lines(
            patterns, n, now + burst, attack_rate=rng.choice([0.1, 0.6, 1.0]),
            n_ips=rng.choice([4, 24, 200]), seed=seed * 10 + burst,
        )
        want.extend(cpu.consume_line(l, now + burst) for l in lines)
        got.extend(tpu.consume_lines(lines, now + burst))
    assert [_key(a) for a in want] == [_key(b) for b in got]
    assert cb.bans == tb.bans
    assert cb.regex_ban_logs == tb.regex_ban_logs
    # full counter-state parity too (spills restored, no torn fallbacks)
    from banjax_tpu.decisions.rate_limit import RegexRateLimitStates as _R
    assert cpu.rate_limit_states.format_states() == \
        tpu.device_windows.format_states()


def test_jit_program_variants_stay_bounded():
    """Production sends ever-varying batch sizes and line lengths; the
    power-of-two bucketing must keep the number of compiled device
    programs SMALL and convergent — an unbounded jit cache is a slow
    memory leak and a per-batch recompile stall in the hot path."""
    import random

    rng = random.Random(3)
    patterns = [r"GET /at[a-z]+", r"/probe\.php"]
    y = _rules_yaml(patterns, hits=3)
    tpu, _ = _mk(TpuMatcher, y, matcher_device_windows=True,
                 matcher_batch_lines=128)
    now = time.time()
    for i in range(30):
        n = rng.randint(1, 300)
        # vary line lengths too (pads L_p buckets)
        tail = "x" * rng.randint(0, 60)
        lines = [
            f"{now + i:.6f} 10.3.{k % 7}.1 GET h.com GET /at{k}{tail} "
            f"HTTP/1.1 UA -"
            for k in range(n)
        ]
        tpu.consume_lines(lines, now + i)
    fw = tpu._fw_pipeline
    assert fw is not None
    counts = {"pipeline_programs": len(fw._progs)}
    if tpu._prefilter is not None:
        counts["prefilter_programs"] = len(tpu._prefilter._fns)
    assert counts["pipeline_programs"] > 0  # the soak really compiled
    assert all(v <= 8 for v in counts.values()), counts
