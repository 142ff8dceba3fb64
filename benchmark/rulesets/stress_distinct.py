"""Ruleset generator `stress_distinct`, found by the name a configuration
gives (`ruleset.generator`): a module in this directory with
`build(**args)`.

`crs_shaped` draws its rules from 22 words, so at 10,000 rules it repeats
itself: the same regex many times over, and one attack line tripping all
of its copies.  Upstream's stress test (`regex_rate_limiter_test.go`
`TestPerSiteRegexStress`) is the other way round: 10,000 generated rules,
10,000 lines, each line tripping its own rule and no other.  This
generator keeps `crs_shaped`'s six pattern shapes, their shares and its
limits, and draws every rule's two words from a seeded vocabulary without
replacement, so that

  * the regex strings are pairwise distinct, and
  * a line written from a rule's `_attack` recipe (lines.py) matches that
    rule and no other: every regex needs one of its own two words
    literally (in either case for the scanner shape), and no other rule's
    recipe writes them.

Words have one length, so none is a prefix of another: `/{w1}[a-z]*/` and
`{w2}{k}` (two digits, then `?` or a space) cannot reach over into a
neighbour's line.  What a recipe's placeholders are filled with comes from
lines.py (3-9 lowercase letters, 1-4 digits); a fill that happens to spell
another rule's seven-letter word inside that rule's surroundings is a
chance of under one in a million for a whole 4,096-line pool, and the
reference would count it on both sides alike.
"""

from __future__ import annotations

import os
import random

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EXTS = ["php", "asp", "aspx", "jsp", "cgi", "sh", "bak", "sql", "old"]
_CONS = "bcdfghjklmnprstvwz"
_VOWELS = "aeiou"
WORD_LEN = 7


def vocabulary(n: int, rng: random.Random) -> list:
    """`n` distinct pronounceable words of WORD_LEN letters."""
    seen = {}
    while len(seen) < n:
        seen["".join(rng.choice(_VOWELS if i % 2 else _CONS)
                     for i in range(WORD_LEN))] = None
    return list(seen)


def refuse_without(program_needs) -> None:
    """A configuration may list files of the program it cannot be run
    without (`ruleset.args.program_needs`).  A checkout that lacks one
    says so and ends with exit code 1 here, in seconds, before a
    generator or the product is started.  `upstream-stress10k` names
    matcher/rulecache.py: the program that has it also chooses its shards
    in seconds; the one before it tries every shard count, spends 263 s
    of its first send there at 10,000 rules and 435 s in set-up (PR 33's
    chip run of its parent), and is killed at a run's time limit."""
    missing = [p for p in program_needs
               if not os.path.isfile(os.path.join(REPO, p))]
    if missing:
        raise SystemExit(
            "benchmark: this configuration's ruleset needs a program with "
            f"{', '.join(missing)} (benchmark/rulesets/stress_distinct.py "
            "refuse_without); this checkout's cannot start it inside a run")


def build(n_rules: int, seed: int, name_prefix: str = "stress",
          program_needs: tuple = ()) -> list:
    """`crs_shaped.build` with two words of its own for every rule."""
    refuse_without(program_needs)
    rng = random.Random(seed)
    words = vocabulary(2 * n_rules, rng)
    rules = []
    for i in range(n_rules):
        kind = rng.random()
        w1, w2 = words[2 * i], words[2 * i + 1]
        ext = rng.choice(EXTS)
        if kind < 0.3:
            regex = rf"GET /{w1}-{w2}/[a-z0-9_-]+\.{ext}"
            attack = {"method": "GET", "path": f"/{w1}-{w2}/%s.{ext}"}
        elif kind < 0.5:
            regex = rf"(GET|POST) /{w1}/{w2}\.{ext}"
            attack = {"method": "GET|POST", "path": f"/{w1}/{w2}.{ext}"}
        elif kind < 0.65:
            k = rng.randint(10, 99)
            regex = rf"POST /{w1}[a-z]*/{w2}{k}"
            attack = {"method": "POST", "path": f"/{w1}%s/{w2}{k}"}
        elif kind < 0.8:
            d = rng.randint(0, 9)
            regex = rf"/{w1}\.{ext}\?[a-z]+={d}[0-9]{{1,4}}"
            attack = {"path": f"/{w1}.{ext}?%s={d}%d"}
        elif kind < 0.9:
            d = rng.randint(1, 9)
            regex = rf"(?i){w1}scan|{w2}bot/{d}\.[0-9]+"
            attack = {"ua": [f"{w1}scan", f"{w2}bot/{d}.%d"]}
        else:
            regex = rf"^(GET|POST|HEAD) [a-z.-]+\.(com|org|net) .*/{w1}{w2}"
            attack = {"method": "GET|POST|HEAD", "path": f"/%s/{w1}{w2}"}
        instant = i % 100 == 7
        rules.append({
            "rule": f"{name_prefix}-{i:05d}",
            "regex": regex,
            "interval": 1 if instant else 300,
            "hits_per_interval": 0 if instant else 2,
            "decision": "challenge" if i % 2 else "nginx_block",
            "_attack": attack,
        })
    return rules
