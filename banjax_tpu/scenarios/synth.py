"""Seeded synthetic rules and request lines, shared by `chip_smoke.py` and
the tests: an OWASP-CRS-shaped ruleset (BASELINE.json configs[2]) and a
mostly benign line stream in which a share of lines is synthesised to
match a rule.  Same seed, same output.  The benchmark has generators of
its own (`benchmark/rulesets/`, `benchmark/harness/lines.py`) and stays
independent of these."""

from __future__ import annotations

import random


def generate_rules(n: int, seed: int = 7) -> list:
    """OWASP-CRS-shaped synthetic ruleset (BASELINE.json configs[2]):
    literal attack paths, method+path prefixes, scanner UA tokens, char
    classes and bounded quantifiers — the pattern shapes of
    banjax-config.yaml's production rules."""
    rng = random.Random(seed)
    words = [
        "admin", "login", "wp", "xmlrpc", "shell", "config", "backup", "env",
        "passwd", "phpmyadmin", "setup", "install", "api", "token", "debug",
        "console", "cgi", "bin", "upload", "include", "vendor", "composer",
    ]
    exts = ["php", "asp", "aspx", "jsp", "cgi", "sh", "bak", "sql", "old"]
    patterns = []
    while len(patterns) < n:
        kind = rng.random()
        w1, w2 = rng.choice(words), rng.choice(words)
        ext = rng.choice(exts)
        if kind < 0.3:
            p = rf"GET /{w1}-{w2}/[a-z0-9_-]+\.{ext}"
        elif kind < 0.5:
            p = rf"(GET|POST) /{w1}/{w2}\.{ext}"
        elif kind < 0.65:
            p = rf"POST /{w1}[a-z]*/{w2}{rng.randint(0, 99)}"
        elif kind < 0.8:
            p = rf"/{w1}\.{ext}\?[a-z]+={rng.randint(0, 9)}[0-9]{{1,4}}"
        elif kind < 0.9:
            p = rf"(?i){w1}scan|{w2}bot/{rng.randint(1, 9)}\.[0-9]+"
        else:
            p = rf"^(GET|POST|HEAD) [a-z.-]+\.(com|org|net) .*/{w1}{w2}"
        patterns.append(p)
    return patterns


def synthesize_match(pattern: str, rng: random.Random) -> str:
    """Build a string the compiled rule actually matches (attack traffic)."""
    from banjax_tpu.matcher.rulec import compile_rule

    prog = compile_rule(pattern)
    if not prog.branches:
        return "GET example.com GET / HTTP/1.1 x -"
    br = rng.choice(prog.branches)
    chars = []
    for pos in br.positions:
        # prefer printable ASCII members of the byte class
        for lo, hi in ((0x61, 0x7A), (0x30, 0x39), (0x20, 0x7E)):
            cands = [b for b in range(lo, hi + 1) if (pos.cs >> b) & 1]
            if cands:
                break
        chars.append(chr(rng.choice(cands or [0x61])))
    body = "".join(chars)
    prefix = "" if br.anchored_start else "GET example.com "
    suffix = "" if br.anchored_end else " HTTP/1.1 ua -"
    return prefix + body + suffix


def generate_lines(n: int, patterns: list, seed: int = 11, attack_rate: float = 0.02) -> list:
    """Mostly benign traffic with ~attack_rate lines synthesized to match a
    random rule — the realistic shape of the tailer's input stream."""
    rng = random.Random(seed)
    hosts = ["example.com", "site.org", "news.net", "shop.com"]
    paths = [
        "/", "/index.html", "/assets/app.js", "/img/logo.png", "/about",
        "/api/v1/items", "/search?q=red4321", "/contact", "/news/2026/07",
    ]
    uas = ["Mozilla/5.0 (X11; Linux x86_64)", "curl/8.1", "Safari/604.1"]
    out = []
    for _ in range(n):
        if patterns and rng.random() < attack_rate:
            out.append(synthesize_match(rng.choice(patterns), rng))
            continue
        method = rng.choice(["GET", "GET", "GET", "POST", "HEAD"])
        out.append(
            f"{method} {rng.choice(hosts)} {method} {rng.choice(paths)} "
            f"HTTP/1.1 {rng.choice(uas)} -"
        )
    return out
