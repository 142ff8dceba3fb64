"""Ruleset generator `fronted`, found by the name a configuration gives
(`ruleset.generator`): a module in this directory with `build(**args)`.

An operator's own rules in front of a signature set, in ONE global
`regexes_with_rates` list, the way upstream's shipped file holds its rate
caps and a signature side by side: the records the configuration lists
under `front`, in its order and under its names (each may carry
`hosts_to_skip` and a private `_attack` recipe, as `listed.py`'s do), then
`crs_shaped.build(n_rules, seed)` as it stands — `crs1k-edge`'s ruleset at
1,000 and seed 7, letter for letter.  Names have to be pairwise distinct
(the plain reference keys its window state by name and refuses two of
one); `crs_shaped`'s are `crs-0000` ..., so a front rule may not take one.
"""

from __future__ import annotations

from benchmark.rulesets import crs_shaped
from benchmark.rulesets.stress_distinct import refuse_without


def build(front: list, n_rules: int, seed: int,
          program_needs: tuple = ()) -> list:
    """`program_needs`: files of the program the configuration cannot be
    run without, as `stress_distinct.build` takes them."""
    refuse_without(program_needs)
    rules = [dict(r) for r in front] + crs_shaped.build(n_rules, seed)
    names = [r["rule"] for r in rules]
    if len(set(names)) != len(names):
        raise SystemExit("fronted: a front rule takes a generated rule's name")
    return rules
