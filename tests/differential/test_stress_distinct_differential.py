"""A ruleset wide enough that stage 2 of the fused match runs over several
word slabs (`upstream-stress10k` is 76 of them; here a few hundred rules of
the same generator give three), on the fused single-kernel path, against
the serial reference (matcher/cpu_ref.py): the same ban log, the same
per-line results and the same counters, also for an address whose
counting rule fires on its third visit with an eviction and a warm-tier
refill before each return.  Small sizes: a 256-slot table, 256-line
batches."""

import random
import time

import pytest

from banjax_tpu.matcher.cpu_ref import CpuMatcher
from banjax_tpu.matcher.runner import TpuMatcher
from benchmark.harness import lines as bench_lines
from benchmark.rulesets import stress_distinct
from tests.differential.test_dense_default_differential import (
    BATCH, _build, _counters, _run_pipelined,
)
from tests.differential.test_tpu_matcher import result_key

N_RULES = 420
N_LINES = 2560
SLOW = 6            # addresses that send one fixed attack line a visit
SLOW_EVERY = 120    # one of them every so many lines: back after 720


@pytest.fixture(scope="module")
def ruleset():
    return stress_distinct.build(N_RULES, seed=7)


def _product_rules(rules):
    return [{k: v for k, v in r.items() if not k.startswith("_")}
            for r in rules]


def _lines(now, seed, rules):
    """2 % attack lines from four fast attackers, one line in 120 from a
    slow attacker in turn (its rule counts to three inside 300 s), the
    rest benign from a window of addresses that slides on, so the
    256-slot table turns over between a slow attacker's visits."""
    rng = random.Random(seed)
    benign = bench_lines.benign_pool(
        256, {"GET": 0.8, "POST": 0.15, "HEAD": 0.05}, 255, seed)
    counting = [r for r in rules if r["hits_per_interval"] > 0]
    slow_rest = [bench_lines.attack_line(rng.choice(counting), rng, 255)
                 for _ in range(SLOW)]
    out = []
    for i in range(N_LINES):
        t = now - 2.0 + i * 5e-4
        if i % SLOW_EVERY == 7:
            j = (i // SLOW_EVERY) % SLOW
            ip, rest = f"11.254.0.{j}", slow_rest[j]
        elif rng.random() < 0.02:
            ip = f"11.255.250.{rng.randrange(4)}"
            rest = bench_lines.attack_line(rng.choice(rules), rng, 255)
        else:
            ip = f"9.9.{(i // 2 + rng.randrange(24)) % 900}.1"
            rest = rng.choice(benign)
        out.append(f"{t:.6f} {ip} {rest}")
    return out


@pytest.mark.parametrize("entry,backend", [
    ("sync", "auto"), ("pipeline", "auto"), ("sync", "pallas-interpret"),
])
def test_three_slabs_of_stage2_equal_the_reference(ruleset, entry, backend):
    rules = _product_rules(ruleset)
    now = time.time()
    n_lines = N_LINES if backend == "auto" else 3 * BATCH
    lines = _lines(now, 33, ruleset)[:n_lines]
    cpu, ref_states, ref_log = _build(CpuMatcher, rules)
    ref_results = [cpu.consume_line(ln, now_unix=now) for ln in lines]

    # the scheduler keeps four batches' slots pinned at once, and a batch
    # of this stream holds ~150 distinct addresses
    capacity = 1024 if entry == "pipeline" else 256
    tpu, _, log = _build(TpuMatcher, rules, matcher_backend=backend,
                         matcher_window_capacity=capacity)
    fw = tpu._fw_pipeline
    assert fw is not None and tpu.describe()["downgrades"] == []
    plan = tpu._prefilter.plan
    assert plan.stage2.n_shards >= 3 and not plan.unsupported
    assert plan.stage2.n_rules == N_RULES and plan.n_always == 0
    if entry == "sync":
        results = []
        for i in range(0, len(lines), BATCH):
            results.extend(tpu.consume_lines(lines[i : i + BATCH], now))
    else:
        results = _run_pipelined(tpu, lines, now)

    assert tpu.pipelined_fused_fallbacks == 0 and tpu.fallback_batches == 0
    assert fw.fallback_batches == 0 and sum(fw.overflow_causes.values()) == 0
    assert fw.fused_batches == len(lines) // BATCH
    # stage 2 scanned the attack lines and little else
    n_attack = sum(not ln.split(" ", 2)[1].startswith("9.9.") for ln in lines)
    assert n_attack <= tpu._prefilter.candidates_total <= 2 * n_attack
    dw = tpu.device_windows
    if (entry, backend) == ("sync", "auto"):
        assert dw.eviction_count > 256
        # slow attackers were evicted with a counter and came back for it
        assert dw.warm_spills >= SLOW and dw.warm_refills >= SLOW
        slow_bans = [x for x in ref_log.getvalue().splitlines()
                     if '"11.254.0.' in x]
        assert len(slow_bans) >= SLOW - 1

    assert ref_log.getvalue().count("\n") >= (4 if backend == "auto" else 1)
    assert log.getvalue() == ref_log.getvalue()
    for i, (a, b) in enumerate(zip(ref_results, results)):
        assert result_key(a) == result_key(b), f"line {i}"
    ips = {ln.split(" ", 2)[1] for ln in lines}
    assert _counters(dw.get, ips) == _counters(ref_states.get, ips)
