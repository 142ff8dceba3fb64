"""Ruleset generator `crs_long`: `crs_shaped`'s rules — names, regexes,
limits and decisions letter for letter — with the attack RECIPES of one
rule in twenty (`i % 20 == 3`) lengthened into payload requests: a filler
of `[a-z0-9/_=&+-]` made from the seed, one length a rule, log-uniform over
300 to 7,600 bytes, so that with the longest User-Agent the request string
stays under nginx's 8 KB request-line buffer.

Where the rule's regex is not anchored to the start of the path — the
`?`-query kind, the User-Agent kind (its recipe gains a `path`) and the
`.*/{w1}{w2}` kind — the filler comes BEFORE the part that matches: the
match then begins hundreds to thousands of bytes into the string, and a
matcher that scans a prefix of a long line finds nothing.  For the kinds
that match at the path's first byte the filler follows the match.  Every
attack line is verified with `re` when the pool is built (lines.py), as
for any generator.
"""

from __future__ import annotations

import math
import random

from benchmark.rulesets import crs_shaped
from benchmark.rulesets.stress_distinct import refuse_without

FILLER = "abcdefghijklmnopqrstuvwxyz0123456789/_=&+-"
EVERY, AT = 20, 3
LO, HI = 300, 7600


def long_rule(i: int) -> bool:
    return i % EVERY == AT


def build(n_rules: int, seed: int, name_prefix: str = "crs",
          program_needs: tuple = ()) -> list:
    """`program_needs`: files of the program the configuration cannot be
    run without (stress_distinct.refuse_without)."""
    refuse_without(program_needs)
    rules = crs_shaped.build(n_rules, seed, name_prefix)
    rng = random.Random(seed * 1_000_003 + 43)
    for i, rule in enumerate(rules):
        if not long_rule(i):
            continue
        n = int(math.exp(rng.uniform(math.log(LO), math.log(HI))))
        filler = "".join(rng.choice(FILLER) for _ in range(n))
        recipe = dict(rule["_attack"])
        path = recipe.get("path")
        if path is None:                      # the User-Agent kind
            recipe["path"] = "/" + filler
        elif rule["regex"].startswith(("/", "^")):
            recipe["path"] = "/" + filler + path   # `?`-query, `.*/w1w2`
        else:                                 # matches at the path's start
            recipe["path"] = path + "?" + filler
        recipe["filler_bytes"] = n
        rule["_attack"] = recipe
    return rules
