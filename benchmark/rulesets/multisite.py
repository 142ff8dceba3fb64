"""Ruleset generator `multisite`, found by the name a configuration gives
(`ruleset.generator`): a module in this directory with `build(**args)`.

Upstream's `per_site_regexes_with_rates` the way an edge that fronts
hundreds of small sites fills it: most sites run the same few CMSes, so
their operators write the same few patterns (login, xmlrpc, a scanner's
User-Agent ...), each under limits of that operator's own choosing, and
the edge's global `regexes_with_rates` apply on every site beside them.

  * global rules: `crs_shaped.build(n_global, seed)` as it stands —
    `crs1k-edge`'s ruleset at 1,000, names `crs-0000` ... — and every 50th
    of them carries `hosts_to_skip` for the most popular site;
  * per-site rules: a catalog of `catalog` patterns in `crs_shaped`'s six
    shapes over words of this file's own (no word of `crs_shaped`, none a
    prefix of another, so a line written for one catalog pattern matches
    no other pattern of the catalog); each of `n_sites` sites takes
    `per_site` of them by a seeded draw and gives each limits by a seeded
    draw: 1 in 20 fires on the first hit (interval 1 s), the rest on the
    2nd, 3rd or 4th hit inside 60 or 300 s; decisions alternate;
  * site names as `lines.HOST_NAME` has them, 8 to 24 bytes, pairwise
    distinct, in rank order (the traffic file's `hosts` draw takes the
    first site as the most popular);
  * records come with global and per-site interleaved: the harness's rule
    index is this order, the product lays per-site rules first, and
    nothing may take one for the other.

A rule's name is `<site>-<pattern>`, so names are pairwise distinct (the
plain reference keys its window state by name and refuses two of one).
"""

from __future__ import annotations

import random
import string

from benchmark.harness.lines import HOST_PREFIXES
from benchmark.rulesets import crs_shaped
from benchmark.rulesets.stress_distinct import refuse_without

EXTS = crs_shaped.EXTS
# two words a pattern: none begins or ends with a word of crs_shaped (its
# slash-anchored shapes and its scanner shape would fire inside), none is a
# path segment of the benign pool, none a prefix of another
WORDS = [
    "wordpress", "pingback", "joomla", "drupal", "magento", "typothree",
    "cpanel", "webdav", "autodiscover", "owaauth", "jenkins", "solrcore",
    "struts", "actuator", "telescope", "dbviewer", "filemanager", "elfinder",
    "fckeditor", "timthumb", "revslider", "gravityforms", "sitecfg",
    "dotfile", "phpunit", "laravel", "symfony", "prestashop", "opencart",
    "moodle", "roundcube", "squirrelmail", "webmin", "plesk", "ispmanager",
    "whmcs", "vbulletin", "phpbb", "mybbforum", "confluence", "jiraservice",
    "gitlabci", "nexusrepo", "kibana", "grafana", "zabbix", "nagiosxi",
    "cactiweb",
]
_TLDS = [".com", ".org", ".net"]


def site_names(n: int, rng: random.Random) -> list:
    names = {}
    while len(names) < n:
        word = "".join(rng.choice(string.ascii_lowercase)
                       for _ in range(rng.randint(4, 13)))
        names[rng.choice(HOST_PREFIXES) + word + rng.choice(_TLDS)] = None
    return list(names)


def patterns(n: int, rng: random.Random) -> list:
    """[(name, regex, recipe)]: `crs_shaped`'s six shapes in turn, pattern
    k over WORDS[2k] and WORDS[2k + 1]."""
    if not 0 < 2 * n <= len(WORDS):
        raise SystemExit(f"multisite: a catalog of 1..{len(WORDS) // 2}")
    out = []
    for k in range(n):
        w1, w2 = WORDS[2 * k], WORDS[2 * k + 1]
        ext = rng.choice(EXTS)
        shape = k % 6
        if shape == 0:
            regex = rf"GET /{w1}-{w2}/[a-z0-9_-]+\.{ext}"
            attack = {"method": "GET", "path": f"/{w1}-{w2}/%s.{ext}"}
        elif shape == 1:
            regex = rf"(GET|POST) /{w1}/{w2}\.{ext}"
            attack = {"method": "GET|POST", "path": f"/{w1}/{w2}.{ext}"}
        elif shape == 2:
            d = rng.randint(10, 99)
            regex = rf"POST /{w1}[a-z]*/{w2}{d}"
            attack = {"method": "POST", "path": f"/{w1}%s/{w2}{d}"}
        elif shape == 3:
            d = rng.randint(0, 9)
            regex = rf"/{w1}\.{ext}\?[a-z]+={d}[0-9]{{1,4}}"
            attack = {"path": f"/{w1}.{ext}?%s={d}%d"}
        elif shape == 4:
            d = rng.randint(1, 9)
            regex = rf"(?i){w1}scan|{w2}bot/{d}\.[0-9]+"
            attack = {"ua": [f"{w1}scan", f"{w2}bot/{d}.%d"]}
        else:
            regex = rf"^(GET|POST|HEAD) [a-z.-]+\.(com|org|net) .*/{w1}{w2}"
            attack = {"method": "GET|POST|HEAD", "path": f"/%s/{w1}{w2}"}
        out.append((w1, regex, attack))
    return out


def build(n_sites: int, catalog: int, per_site: int, n_global: int,
          seed: int, program_needs: tuple = ()) -> list:
    """`program_needs`: files of the program the configuration cannot be
    run without, as `stress_distinct.build` takes them."""
    refuse_without(program_needs)
    rng = random.Random(seed * 1_000_003 + 53)
    sites = site_names(n_sites, rng)
    cat = patterns(catalog, rng)
    globals_ = crs_shaped.build(n_global, seed)
    for g in globals_[::50]:
        g["hosts_to_skip"] = {sites[0]: True}
    rules = []
    for i, site in enumerate(sites):
        rules += globals_[i * n_global // n_sites:
                          (i + 1) * n_global // n_sites]
        for k in sorted(rng.sample(range(catalog), per_site)):
            name, regex, attack = cat[k]
            instant = rng.random() < 0.05
            rules.append({
                "rule": f"{site}-{name}",
                "regex": regex,
                "interval": 1 if instant else rng.choice([60, 300]),
                "hits_per_interval": 0 if instant else rng.randint(1, 3),
                "decision": "challenge" if len(rules) % 2 else "nginx_block",
                "_site": site,
                "_attack": attack,
            })
    return rules
