"""Ruleset generator `listed`, found by the name a configuration gives
(`ruleset.generator`): the configuration lists its rules itself.

For a deployment whose rules are a handful of published records (the
project's shipped `regexes_with_rates`), not a generated set.  A rule may
carry a private `_attack` recipe (how to write a line its regex matches,
see `crs_shaped.py`); those without one get no attack lines (`lines.py`
draws among the rules that have one)."""

from __future__ import annotations


def build(rules: list) -> list:
    return [dict(r) for r in rules]
