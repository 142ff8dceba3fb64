"""The ruleset generator of `upstream-stress10k`
(benchmark/rulesets/stress_distinct.py): 10,000 pairwise distinct
regexes, and the upstream stress test's own property — a line written for
one rule matches that rule and no other — on a 4,096-line attack pool as
the benchmark's generator builds it (benchmark/harness/lines.py)."""

import random
import re

import pytest

from benchmark.harness import lines
from benchmark.rulesets import crs_shaped, stress_distinct

N = 10_000
POOL = 4096


@pytest.fixture(scope="module")
def rules():
    return stress_distinct.build(N, seed=7)


def test_ten_thousand_pairwise_distinct_regexes(rules):
    assert len(rules) == N
    assert len({r["regex"] for r in rules}) == N
    assert len({r["rule"] for r in rules}) == N
    assert stress_distinct.build(N, seed=7) == rules       # fixed by the seed
    assert stress_distinct.build(64, seed=8) != rules[:64]
    # crs_shaped at this size repeats itself, which is why this exists
    assert len({r["regex"] for r in crs_shaped.build(N, seed=7)}) < 0.8 * N


def test_shapes_shares_and_limits_are_crs_shaped(rules):
    shape = [
        r"GET /\w+-\w+/\[", r"\(GET\|POST\) /", r"POST /\w+\[a-z\]\*/",
        r"/\w+\\\.\w+\\\?", r"\(\?i\)", r"\^\(GET\|POST\|HEAD\)",
    ]
    share = [sum(bool(re.match(s, r["regex"])) for r in rules) / N
             for s in shape]
    for got, want in zip(share, [0.3, 0.2, 0.15, 0.15, 0.1, 0.1]):
        assert abs(got - want) < 0.02, share
    assert abs(sum(share) - 1) < 1e-9
    instant = [r for r in rules if r["hits_per_interval"] == 0]
    assert len(instant) == N // 100 and {r["interval"] for r in instant} == {1}
    assert {(r["interval"], r["hits_per_interval"]) for r in rules
            if r not in instant} == {(300, 2)}
    for r in rules[:200]:
        re.compile(r["regex"])


def _owners(rules):
    """word → the one rule whose regex holds it (the generator gives
    rule i the words 2i and 2i + 1 of its vocabulary)."""
    words = stress_distinct.vocabulary(2 * N, random.Random(7))
    assert len(set(words)) == 2 * N
    owner = {}
    for i, r in enumerate(rules):
        mine = [w for w in words[2 * i:2 * i + 2] if w in r["regex"]]
        assert mine, r["regex"]
        for w in mine:
            owner[w] = i
    return owner


@pytest.mark.parametrize("seed", [1, 3300000102])
def test_every_pool_line_matches_its_own_rule_and_no_other(rules, seed):
    pool = lines.attack_pool(POOL, rules, 255, seed)   # verifies "its own"
    assert len(pool) == POOL and len({i for i, _ in pool}) > 3000
    # a regex matches a line only if the line holds one of the rule's own
    # words (in either case): those rules are tried with `re`, all of them
    owner = _owners(rules)
    compiled = {}
    k = stress_distinct.WORD_LEN
    for own, line in pool:
        low = line.lower()
        cand = {owner[low[j:j + k]] for j in range(len(low) - k + 1)
                if low[j:j + k] in owner}
        assert own in cand
        for i in cand:
            rx = compiled.setdefault(i, re.compile(rules[i]["regex"]))
            assert (rx.search(line) is not None) == (i == own), (
                rules[i]["regex"], line)
    # and without the argument about words: 64 lines against every rule
    rng = random.Random(seed)
    every = [re.compile(r["regex"]) for r in rules]
    for own, line in rng.sample(pool, 64):
        assert [i for i, rx in enumerate(every) if rx.search(line)] == [own]


@pytest.mark.parametrize("needs, refused", [
    ((), False),
    (("banjax_tpu/matcher/rulecache.py",), False),
    (("banjax_tpu/matcher/rulecache.py", "banjax_tpu/no_such_file.py"), True),
])
def test_a_checkout_without_what_the_configuration_needs_is_refused(
        needs, refused):
    """`program_needs` of the configuration's ruleset block: a program that
    lacks a listed file ends the benchmark's run with exit code 1 before
    anything is started (the parent of PR 33 would be killed at the run's
    time limit instead); one that has them gets the same rules."""
    if refused:
        with pytest.raises(SystemExit) as e:
            stress_distinct.build(8, seed=7, program_needs=needs)
        assert "no_such_file.py" in str(e.value.code)
        assert "rulecache.py" not in str(e.value.code)
    else:
        assert (stress_distinct.build(8, seed=7, program_needs=needs)
                == stress_distinct.build(8, seed=7))


def test_the_configuration_names_what_its_ruleset_needs():
    import json
    import os

    with open(os.path.join(stress_distinct.REPO, "benchmark", "configs",
                           "upstream-stress10k.json"), encoding="utf-8") as f:
        config = json.load(f)
    needs = config["ruleset"]["args"]["program_needs"]
    assert needs == ["banjax_tpu/matcher/rulecache.py"]
    assert "program_needs" not in config["rehearse"]["ruleset"]["args"]
