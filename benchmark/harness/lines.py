"""Pools of `banjax_format` request strings (the part of a log line after
the client IP): `<method> <host> <method> <path> HTTP/1.1 <ua> -`.

Lengths are those of real access logs, not of a toy: full browser, crawler
and tool User-Agents (40-130 bytes) and paths with a heavy-tailed length
give a median near 150 bytes and about one line in twenty of 225 or more,
capped so that nothing exceeds the product's `matcher_max_line_len`.
Attack lines come from the ruleset's own recipes and each is verified
against its rule with Python's `re`.  No import of the program, no JAX.

Hosts.  Without a `hosts` block in the traffic file every line's host is
one of the 16 `HOSTS`, equally likely.  With one (`{"draw": "zipf" |
"uniform", "s": constant, "unprotected": n}`) the hosts are the ruleset's
sites (the distinct `_site` values in the ruleset's order, rank 1 the most
popular) followed by `n` generated names that have no rules of their own;
a benign line draws its host by `draw`; an attack line written from a
per-site rule's recipe goes to that rule's site (on any other host the
rule does not apply), one written from a global rule draws like a benign
line.
"""

from __future__ import annotations

import itertools
import random
import re
import string

USER_AGENTS = [
    "Mozilla/5.0 (Windows NT 10.0; Win64; x64) AppleWebKit/537.36 (KHTML, like Gecko) Chrome/126.0.0.0 Safari/537.36",
    "Mozilla/5.0 (Windows NT 10.0; Win64; x64) AppleWebKit/537.36 (KHTML, like Gecko) Chrome/125.0.0.0 Safari/537.36 Edg/125.0.0.0",
    "Mozilla/5.0 (Macintosh; Intel Mac OS X 10_15_7) AppleWebKit/605.1.15 (KHTML, like Gecko) Version/17.4.1 Safari/605.1.15",
    "Mozilla/5.0 (Macintosh; Intel Mac OS X 10_15_7) AppleWebKit/537.36 (KHTML, like Gecko) Chrome/126.0.0.0 Safari/537.36",
    "Mozilla/5.0 (X11; Linux x86_64) AppleWebKit/537.36 (KHTML, like Gecko) Chrome/124.0.0.0 Safari/537.36",
    "Mozilla/5.0 (X11; Ubuntu; Linux x86_64; rv:127.0) Gecko/20100101 Firefox/127.0",
    "Mozilla/5.0 (Windows NT 10.0; Win64; x64; rv:126.0) Gecko/20100101 Firefox/126.0",
    "Mozilla/5.0 (iPhone; CPU iPhone OS 17_5 like Mac OS X) AppleWebKit/605.1.15 (KHTML, like Gecko) Version/17.5 Mobile/15E148 Safari/604.1",
    "Mozilla/5.0 (iPad; CPU OS 17_4 like Mac OS X) AppleWebKit/605.1.15 (KHTML, like Gecko) Version/17.4 Mobile/15E148 Safari/604.1",
    "Mozilla/5.0 (Linux; Android 14; Pixel 8) AppleWebKit/537.36 (KHTML, like Gecko) Chrome/126.0.0.0 Mobile Safari/537.36",
    "Mozilla/5.0 (Linux; Android 13; SM-S918B) AppleWebKit/537.36 (KHTML, like Gecko) Chrome/125.0.0.0 Mobile Safari/537.36",
    "Mozilla/5.0 (Linux; Android 10; K) AppleWebKit/537.36 (KHTML, like Gecko) Chrome/124.0.0.0 Mobile Safari/537.36",
    "Mozilla/5.0 (compatible; Googlebot/2.1; +http://www.google.com/bot.html)",
    "Mozilla/5.0 (compatible; bingbot/2.0; +http://www.bing.com/bingbot.htm)",
    "Mozilla/5.0 (compatible; YandexBot/3.0; +http://yandex.com/bots)",
    "Mozilla/5.0 AppleWebKit/537.36 (KHTML, like Gecko; compatible; GPTBot/1.0; +https://openai.com/gptbot)",
    "facebookexternalhit/1.1 (+http://www.facebook.com/externalhit_uatext.php)",
    "python-requests/2.31.0 (CPython 3.11; Linux x86_64)",
    "curl/8.5.0 (x86_64-pc-linux-gnu) libcurl/8.5.0 OpenSSL/3.0.13",
    "Go-http-client/2.0 (feed fetcher; +https://example.org/fetcher)",
    "WordPress/6.5.3; https://blog.example.net (pingback verifier)",
]
HOSTS = [
    "example.com", "www.example.com", "site.org", "news.net", "shop.com",
    "blog.example.net", "media.daily-news.org", "cdn.shop.com",
    "forum.site.org", "api.example.com", "static.news.net",
    "www.independent-voices.org", "press.rights-watch.net",
    "archive.daily-news.org", "m.shop.com", "docs.site.org",
]
SEGMENTS = [
    "articles", "news", "2026", "09", "27", "category", "world", "politics",
    "assets", "static", "js", "css", "img", "media", "uploads", "thumbs",
    "api", "v1", "v2", "items", "users", "profile", "search", "tags",
    "products", "cart", "checkout", "feed", "rss", "comments", "page",
    "gallery", "video", "live", "events", "about", "contact", "help",
]
LEAVES = [
    "", "index.html", "app.min.js", "main.css", "logo.png", "photo-1280.jpg",
    "favicon.ico", "feed.xml", "view", "list", "latest", "thumb-320x240.webp",
    "a-long-headline-about-the-things-that-happened-today",
]
QUERY_KEYS = ["q", "page", "sort", "ref", "utm_source", "utm_campaign",
              "id", "lang", "session", "cb", "filter", "from"]
_ALNUM = string.ascii_lowercase + string.digits
HOST_NAME = re.compile(r"[a-z.-]+\.(com|org|net)")  # what the recipes assume
HOST_PREFIXES = ["", "", "", "www.", "cdn.", "m.", "news.", "blog."]


def _word(rng: random.Random, lo: int, hi: int, chars: str = _ALNUM) -> str:
    return "".join(rng.choice(chars) for _ in range(rng.randint(lo, hi)))


def _path(rng: random.Random, target: int) -> str:
    """A path of about `target` bytes: segments, then a query string."""
    parts = []
    n = 0
    depth = 0
    while n < min(target, 60) and depth < 6:
        seg = rng.choice(SEGMENTS)
        parts.append(seg)
        n += len(seg) + 1
        depth += 1
    path = "/" + "/".join(parts)
    leaf = rng.choice(LEAVES)
    if leaf:
        path += "/" + leaf
    if len(path) < target:
        q = []
        while len(path) + sum(len(x) + 1 for x in q) < target:
            q.append(f"{rng.choice(QUERY_KEYS)}={_word(rng, 3, 16)}")
        path += "?" + "&".join(q)
    return path[:max(1, target)]


def _path_len(rng: random.Random) -> int:
    """Heavy-tailed: most paths are short, a few carry long queries."""
    return int(min(400, 4 + 8 * rng.paretovariate(1.3)))


def _assemble(method: str, host: str, path: str, ua: str, cap: int) -> str:
    rest = f"{method} {host} {method} {path} HTTP/1.1 {ua} -"
    over = len(rest) - cap
    if over > 0:  # cut the path's tail, never the fields the rules read
        rest = f"{method} {host} {method} {path[:-over]} HTTP/1.1 {ua} -"
    return rest


def _draw(rng: random.Random, mix: dict) -> str:
    x = rng.random() * sum(mix.values())
    for k, v in mix.items():
        x -= v
        if x < 0:
            return k
    return next(iter(mix))


class SiteHosts:
    """The hosts of a traffic file's `hosts` block: the ruleset's sites,
    then `unprotected` names made from the seed, 8 to 24 bytes as `HOSTS`
    are, so line lengths stay where they are."""

    def __init__(self, rules: list, spec: dict, seed: int):
        self.names = list(dict.fromkeys(
            r["_site"] for r in rules if r.get("_site")))
        rng = random.Random(seed * 1_000_003 + 41)
        n = len(self.names) + int(spec.get("unprotected", 0))
        taken = set(self.names)
        while len(self.names) < n:
            name = (rng.choice(HOST_PREFIXES)
                    + _word(rng, 4, 13, string.ascii_lowercase)
                    + rng.choice([".com", ".org", ".net"]))
            if name not in taken:
                taken.add(name)
                self.names.append(name)
        bad = [h for h in self.names if not HOST_NAME.fullmatch(h)]
        if bad or not self.names:
            raise SystemExit(f"hosts: no host, or not a host name: {bad[:3]}")
        if spec["draw"] == "zipf":
            w = [r ** -float(spec["s"]) for r in range(1, n + 1)]
        elif spec["draw"] == "uniform":
            w = [1.0] * n
        else:
            raise SystemExit(f"unknown hosts.draw {spec['draw']!r}")
        self.cdf = list(itertools.accumulate(w))

    def draw(self, rng: random.Random) -> str:
        return rng.choices(self.names, cum_weights=self.cdf)[0]


def benign_pool(n: int, method_mix: dict, cap: int, seed: int,
                hosts: SiteHosts | None = None) -> list:
    rng = random.Random(seed * 1_000_003 + 17)
    out = []
    for _ in range(n):
        out.append(_assemble(
            _draw(rng, method_mix),
            hosts.draw(rng) if hosts else rng.choice(HOSTS),
            _path(rng, _path_len(rng)), rng.choice(USER_AGENTS), cap,
        ))
    return out


def _fill(template: str, rng: random.Random) -> str:
    """`%s` → a few lowercase letters or digits, `%d` → one to four
    digits (the recipes' own placeholders)."""
    out = []
    i = 0
    while i < len(template):
        if template.startswith("%s", i):
            out.append(_word(rng, 3, 9, string.ascii_lowercase))
            i += 2
        elif template.startswith("%d", i):
            out.append(_word(rng, 1, 4, string.digits))
            i += 2
        else:
            out.append(template[i])
            i += 1
    return "".join(out)


def attack_line(rule: dict, rng: random.Random, cap: int,
                hosts: SiteHosts | None = None) -> str:
    """One line that `rule["regex"]` matches, written from the rule's
    recipe at a realistic length and checked with `re`."""
    recipe = rule["_attack"]
    method = rng.choice(recipe.get("method", "GET").split("|"))
    ua = rng.choice(USER_AGENTS)
    if len(ua) > 110 and rng.random() < 0.5:
        ua = rng.choice(USER_AGENTS)  # attack tools lean to short UAs
    if "ua" in recipe:
        ua = f"{ua} {_fill(rng.choice(recipe['ua']), rng)}"
    if "path" in recipe:
        path = _fill(recipe["path"], rng)
        if rng.random() < 0.5:
            sep = "&" if "?" in path else "?"
            path += sep + f"{rng.choice(QUERY_KEYS)}={_word(rng, 3, 24)}"
    else:
        path = _path(rng, min(_path_len(rng), 80))
    if hosts:  # a per-site rule applies on its own site only
        host = short_host = rule.get("_site") or hosts.draw(rng)
    else:
        host, short_host = rng.choice(HOSTS), HOSTS[0]
    rest = f"{method} {host} {method} {path} HTTP/1.1 {ua} -"
    if len(rest) > cap:  # rare: the same recipe again with the shortest UA
        ua = min(USER_AGENTS, key=len) + ua[ua.rfind(" "):] * ("ua" in recipe)
        rest = f"{method} {short_host} {method} {path} HTTP/1.1 {ua} -"
    if re.search(rule["regex"], rest) is None:
        raise ValueError(
            f"recipe of {rule['rule']} wrote a line its regex "
            f"{rule['regex']!r} does not match: {rest!r}")
    return rest


def attack_pool(n: int, rules: list, cap: int, seed: int,
                hosts: SiteHosts | None = None) -> list:
    """[(rule index, line)], rules drawn uniformly among those that have a
    recipe."""
    rng = random.Random(seed * 1_000_003 + 29)
    with_recipe = [i for i, r in enumerate(rules) if r.get("_attack")]
    if n and not with_recipe:
        raise SystemExit("traffic asks for attack lines; no rule has a recipe")
    out = []
    for _ in range(n):
        i = rng.choice(with_recipe)
        out.append((i, attack_line(rules[i], rng, cap, hosts)))
    return out


def line_bucket(rest: str) -> int:
    """The matcher's line-length bucket (multiples of 32, floor 64)."""
    return max(64, -(-len(rest) // 32) * 32)
