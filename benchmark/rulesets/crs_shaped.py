"""Ruleset generator `crs_shaped`, found by the name a configuration gives
(`ruleset.generator`): a module in this directory with `build(**args)`.

A generator returns rule records as the product's `regexes_with_rates`
takes them, each with a private `_attack` recipe: how to write a
`banjax_format` line that the rule's regex matches.  The recipes belong
to the templates here; every attack line made from one is verified with
Python's `re` when the pool is built (lines.py), never through the
program's rule compiler.

`crs_shaped` is a copy of `bench.generate_rules` (same words, same six
pattern shapes, same shares) with limits as `chip_smoke.make_rules` sets
them.  The originals stay where they are (PERF.md, Open questions).
"""

from __future__ import annotations

import random

WORDS = [
    "admin", "login", "wp", "xmlrpc", "shell", "config", "backup", "env",
    "passwd", "phpmyadmin", "setup", "install", "api", "token", "debug",
    "console", "cgi", "bin", "upload", "include", "vendor", "composer",
]
EXTS = ["php", "asp", "aspx", "jsp", "cgi", "sh", "bak", "sql", "old"]


def build(n_rules: int, seed: int, name_prefix: str = "crs") -> list:
    """OWASP-CRS-shaped synthetic rules: literal attack paths, method+path
    prefixes, scanner UA tokens, classes and bounded quantifiers.  One
    rule in a hundred bans on the first hit; the rest on the third hit
    inside five minutes; decisions alternate between the two whose effect
    /auth_request shows without root."""
    rng = random.Random(seed)
    rules = []
    while len(rules) < n_rules:
        kind = rng.random()
        w1, w2 = rng.choice(WORDS), rng.choice(WORDS)
        ext = rng.choice(EXTS)
        if kind < 0.3:
            regex = rf"GET /{w1}-{w2}/[a-z0-9_-]+\.{ext}"
            attack = {"method": "GET", "path": f"/{w1}-{w2}/%s.{ext}"}
        elif kind < 0.5:
            regex = rf"(GET|POST) /{w1}/{w2}\.{ext}"
            attack = {"method": "GET|POST", "path": f"/{w1}/{w2}.{ext}"}
        elif kind < 0.65:
            k = rng.randint(0, 99)
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
        i = len(rules)
        instant = i % 100 == 7
        rules.append({
            "rule": f"{name_prefix}-{i:04d}",
            "regex": regex,
            "interval": 1 if instant else 300,
            "hits_per_interval": 0 if instant else 2,
            "decision": "challenge" if i % 2 else "nginx_block",
            "_attack": attack,
        })
    return rules
