"""The seeded rule and line generators (banjax_tpu/scenarios/synth.py)
that `chip_smoke.py`, the perf ladder and the matcher tests draw on."""

import hashlib
import json
import re

import pytest

from banjax_tpu.scenarios import synth


def _digest(obj) -> str:
    return hashlib.sha256(json.dumps(obj).encode()).hexdigest()[:16]


def test_same_seed_same_rules_and_lines():
    """Deterministic in the seed, and byte for byte what `bench.py`
    returned for it before the generators moved here (PR 30)."""
    rules = synth.generate_rules(64, 7)
    assert rules == synth.generate_rules(64, 7)
    assert rules != synth.generate_rules(64, 8)
    lines = synth.generate_lines(512, rules, seed=11)
    assert lines == synth.generate_lines(512, rules, seed=11)
    assert lines != synth.generate_lines(512, rules, seed=12)
    assert _digest(rules) == "ec6f5c2d4273ad1a"
    assert _digest(lines) == "4946f5ca9161ed9f"


def test_every_rule_compiles_and_counts_are_honoured():
    for n in (1, 17, 200):
        rules = synth.generate_rules(n, seed=n)
        assert len(rules) == n
        for r in rules:
            re.compile(r)
        assert len(synth.generate_lines(3 * n, rules, seed=n)) == 3 * n
    assert synth.generate_lines(0, [], seed=1) == []


def test_attack_lines_match_the_rule_they_were_synthesised_from():
    """attack_rate 1: every line comes from `synthesize_match` of one
    rule of the set, and Python's `re` finds that rule in it."""
    rules = synth.generate_rules(80, seed=5)
    compiled = [re.compile(r) for r in rules]
    lines = synth.generate_lines(400, rules, seed=6, attack_rate=1.0)
    for line in lines:
        assert any(c.search(line) for c in compiled), line


@pytest.mark.parametrize("rate,share", [(0.0, 0.0), (1.0, 1.0)])
def test_attack_rate_zero_and_one_give_none_and_all(rate, share):
    rules = synth.generate_rules(40, seed=9)
    compiled = [re.compile(r) for r in rules]
    lines = synth.generate_lines(300, rules, seed=10, attack_rate=rate)
    hit = sum(1 for ln in lines if any(c.search(ln) for c in compiled))
    assert hit == share * len(lines)
    # with no rules there is nothing to synthesise from, whatever the rate
    benign = synth.generate_lines(50, [], seed=10, attack_rate=1.0)
    assert not any(c.search(ln) for ln in benign for c in compiled)
