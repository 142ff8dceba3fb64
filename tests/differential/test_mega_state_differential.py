"""Differential proofs for the mega-state tiering (README "Mega-state
tiering"): the warm tier is a lossless state home and the slot-admission
gate never changes WHAT gets banned, on the fused single kernel and on
the classic bitmap protocol (the path a failing scan selftest or a
host-evaluated rule takes: tests/classic_downgrade.py).

  * admission OFF + warm tier ON is byte-identical to the ungated
    engine (same ban-log bytes, same per-line result stream, same final
    per-IP window states) under eviction churn that actually spills;
  * admission ON preserves the ban multiset AND every per-IP ban
    sequence exactly.  Stronger than the ISSUE's bounded-delay floor:
    a refused row that matches a rule still steps the same window math
    host-side (apply_host_events), so per-IP ban TIMING is identical
    too — only cross-IP interleaving may differ (refused rows of a
    batch replay before admitted rows);
  * the gated run is non-vacuous: rows were refused, refused-IP state
    went warm, and a warm IP that came back was admitted by refill.

CONFIG_YAML's cheapest rule has hits_per_interval 0, so the DERIVED
admission threshold would be 1 (admit everything): these tests pin
slot_admission_min_estimate explicitly to exercise real refusals.
"""

import io
import random
import threading
import time
from collections import Counter

import pytest

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.effectors.banner import Banner
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.pipeline import PipelineScheduler
from tests.classic_downgrade import scan_selftest_failing
from tests.differential.test_pipeline_differential import ChurnSizer
from tests.differential.test_tpu_matcher import CONFIG_YAML, result_key

MIN_EST = 4      # explicit gate threshold (see module docstring)
CAPACITY = 64    # small hot tier => real eviction churn at this scale


def _gen_tier_lines(n, now, seed):
    """The full gate surface: a long tail of DISTINCT one-shot IPs whose
    single row MATCHES rule1 (refused when gated, and their window state
    must therefore live in the warm tier), warm repeaters that cross the
    threshold mid-stream, hot offenders, instant per-site blocks on
    first-ever rows, the allowlisted IP, garbage, and stale lines."""
    rng = random.Random(seed)
    lines = []
    for i in range(n):
        kind = rng.random()
        if kind < 0.40:   # distinct cold IPs, one matching row each
            ip = f"21.{(i >> 16) & 0xFF}.{(i >> 8) & 0xFF}.{i & 0xFF}"
            lines.append(
                f"{now:f} {ip} GET example.com GET /cold{i} HTTP/1.1 ua -"
            )
        elif kind < 0.55:  # warm repeaters: a few rows each, some ban
            ip = f"22.0.0.{rng.randrange(40)}"
            lines.append(
                f"{now:f} {ip} GET example.com GET /warm HTTP/1.1 ua -"
            )
        elif kind < 0.65:  # hot offenders: ban over and over
            ip = f"23.0.0.{rng.randrange(4)}"
            lines.append(
                f"{now:f} {ip} GET example.com GET /hot HTTP/1.1 ua -"
            )
        elif kind < 0.71:  # rule2 (hits 1): second POST in window bans
            ip = f"24.0.0.{rng.randrange(6)}"
            lines.append(
                f"{now:f} {ip} POST example.com POST /s HTTP/1.1 ua -"
            )
        elif kind < 0.76:  # instant per-site block on a FIRST-EVER row:
            #                the refused path must fire it on that row
            ip = f"25.{(i >> 8) & 0xFF}.0.{i & 0xFF}"
            lines.append(
                f"{now:f} {ip} GET per-site.com GET /blockme HTTP/1.1 ua -"
            )
        elif kind < 0.80:
            lines.append(
                f"{now:f} 12.12.12.12 GET example.com GET /a HTTP/1.1 ua -"
            )
        elif kind < 0.84:
            lines.append("short garbage")
        elif kind < 0.87:
            ip = f"26.0.0.{rng.randrange(9)}"
            lines.append(
                f"{now - 100:f} {ip} GET example.com GET /old HTTP/1.1 ua -"
            )
        else:             # distinct, matches nothing
            ip = f"27.{(i >> 8) & 0xFF}.0.{i & 0xFF}"
            lines.append(
                f"{now:f} {ip} GET news.net GET /benign HTTP/1.1 ua -"
            )
    return lines


def _build(admission, warm, single_kernel):
    config = config_from_yaml_text(CONFIG_YAML)
    config.matcher_device_windows = True
    config.matcher_window_capacity = CAPACITY
    config.traffic_sketch_enabled = True
    config.slot_admission_enabled = admission
    config.slot_admission_min_estimate = MIN_EST
    config.warm_tier_enabled = warm
    config.warm_tier_capacity = 4096
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
    return matcher, ban_log


def _run_pipelined(lines, now, seed, admission, warm, single_kernel):
    matcher, ban_log = _build(admission, warm, single_kernel)
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
    rng = random.Random(29)
    i = 0
    while i < len(lines):
        step = rng.randrange(1, 90)
        sched.submit(lines[i: i + step])
        i += step
    assert sched.flush(120)
    sched.stop()
    dw = matcher.device_windows
    stats = {
        "refusals": dw.slot_refusals,
        "admissions": dw.sketch_admissions,
        "spills": dw.warm_spills,
        "refills": dw.warm_refills,
        "dropped": dw.warm_dropped,
        "states": dw.format_states(),
    }
    matcher.close()
    results = {}
    for batch_lines, batch_results in collected:
        if batch_results is None:
            continue
        for line, res in zip(batch_lines, batch_results):
            results.setdefault(line, []).append(result_key(res))
    return results, ban_log.getvalue(), stats


def _parse_states(text):
    """format_states -> {ip: {rule: state-line}}, order-insensitive: the
    same IP's state may be shadow-resident in one run and warm-resident
    in the other, which permutes the rendering order but must never
    change a single (ip, rule) vector."""
    out = {}
    ip = rule = None
    for line in text.splitlines():
        if not line:
            continue
        if not line.startswith("\t"):
            ip = line.rstrip(":")
            out[ip] = {}
        elif not line.startswith("\t\t"):
            rule = line.strip().rstrip(":")
        else:
            out[ip][rule] = line.strip()
    return out


def _per_ip_bans(log_text):
    out = {}
    for ln in log_text.splitlines():
        parts = ln.split()
        # banjax-format: "<ts>, <ip>, matched ..." — key on the ip token
        ip = parts[1].rstrip(",") if len(parts) > 1 else ln
        out.setdefault(ip, []).append(ln)
    return out


@pytest.mark.parametrize("single_kernel", [True, False])
def test_warm_tier_byte_identical_under_eviction_churn(single_kernel):
    """Admission OFF both sides; warm tier OFF vs ON.  Eviction churn
    (CAPACITY 64 << distinct IPs) spills real state into the warm tier,
    and nothing observable may move: ban-log bytes, per-line results,
    final per-IP window states."""
    now = time.time()
    lines = _gen_tier_lines(1500, now, seed=3)

    off_results, off_log, off_stats = _run_pipelined(
        lines, now, 13, admission=False, warm=False,
        single_kernel=single_kernel,
    )
    on_results, on_log, on_stats = _run_pipelined(
        lines, now, 13, admission=False, warm=True,
        single_kernel=single_kernel,
    )

    assert on_log == off_log            # identical processing order =>
    assert on_results == off_results    # byte-identical everything
    assert _parse_states(on_stats["states"]) == _parse_states(
        off_stats["states"]
    )
    # non-vacuity: the warm run actually spilled and refilled
    assert on_stats["spills"] > 0
    assert on_stats["refills"] > 0
    assert on_stats["dropped"] == 0


@pytest.mark.parametrize("single_kernel", [True, False])
def test_admission_on_preserves_ban_multiset_and_per_ip_order(
    single_kernel,
):
    """Admission ON vs OFF (warm tier on for both): the ban multiset,
    every per-IP ban sequence, the per-line result stream, and the final
    per-IP window states are all identical — the gate only reorders
    cross-IP processing inside a batch, it never changes an outcome or
    delays a ban for a row that reached the engine."""
    now = time.time()
    lines = _gen_tier_lines(1500, now, seed=5)

    off_results, off_log, off_stats = _run_pipelined(
        lines, now, 17, admission=False, warm=True,
        single_kernel=single_kernel,
    )
    on_results, on_log, on_stats = _run_pipelined(
        lines, now, 17, admission=True, warm=True,
        single_kernel=single_kernel,
    )

    assert Counter(on_log.splitlines()) == Counter(off_log.splitlines())
    assert _per_ip_bans(on_log) == _per_ip_bans(off_log)
    assert on_results == off_results
    assert _parse_states(on_stats["states"]) == _parse_states(
        off_stats["states"]
    )
    # non-vacuity: the gate refused rows, refused state went warm, and
    # returning warm IPs were admitted by refill
    assert on_stats["refusals"] > 0
    assert on_stats["spills"] > 0
    assert on_stats["refills"] > 0
    assert off_stats["refusals"] == 0
