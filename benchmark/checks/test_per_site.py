"""Per-site rules, hosts from the traffic file, and the reference's
per-site-then-global order (CPU, rehearsal size, by hand; port 8081 for
cases (b) and (c)).  The fixture (`per_site/config.json`,
`per_site/traffic.json`) is in no list of BENCHMARK.json; the cell is
assembled here as `found.cell` assembles one and handed to `cellrun.run`
as `run.py` hands one.

(a) the reference alone against the cases of upstream's
    `regex_rate_limiter_test.go:77-296`, written out as tables: a
    per-site rule fires on its site and on no other, per-site before
    global where one line fires both, `hosts_to_skip` leaves a global
    rule out on the named host only;
(b) the product rehearsed on a small per-site ruleset: all four
    comparisons 0, a fused chunk committed, a ban from a per-site rule and
    one from a global rule in the reference;
(c) the same with the product's site mask broken (the active table all
    true): `ban_records_extra` and `ban_keys_differing` fail;
(d) the reference's order broken (global first): (a)'s order table fails.
"""

import argparse
import json
import os
import re
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.harness import cellrun, found, genproc, lines  # noqa: E402
from benchmark.harness import reference, stream  # noqa: E402

COMPARED = {"ban_records_missing", "ban_records_extra", "ips_out_of_order",
            "ban_keys_differing"}
UA = "Mozilla/5.0 (X11; Linux x86_64) Firefox/127.0"


def rule(name, regex, hits, interval=300, decision="nginx_block", **more):
    return {"rule": name, "regex": regex, "interval": interval,
            "hits_per_interval": hits, "decision": decision, **more}


def log(t, ip, host, path="/", method="GET"):
    return f"{t:.6f} {ip} {method} {host} {method} {path} HTTP/1.1 {UA} -"


def triggers(rules, log_lines):
    out = reference.run(rules, log_lines, lambda ip: True, procs=1)
    return [(d["client_ip"], d["client_request_host"], d["trigger"])
            for d in map(json.loads, out["bans"])]


# ---- (a) the reference alone: upstream's cases as tables ------------------

SITE_RULE = rule("site-a-block", r".*blockme.*", 0, 1, _site="a.com")
GLOBAL_RULE = rule("global-block", r"GET .* GET /blockme", 0, 1, "challenge")
SKIPPING = rule("challenge-all", r".*", 0, 1, "challenge",
                hosts_to_skip={"skipme.com": True})
COUNTING = rule("site-a-count", r"POST .*login", 2, 300, _site="a.com")

TABLES = {
    "a per-site rule fires on its site and on no other": (
        [SITE_RULE],
        [log(1, "1.1.1.1", "a.com", "/blockme"),
         log(2, "1.1.1.2", "b.com", "/blockme"),
         log(3, "1.1.1.3", "www.a.com", "/blockme")],
        [("1.1.1.1", "a.com", "site-a-block")]),
    "per-site before global where one line fires both": (
        [GLOBAL_RULE, SITE_RULE],  # the ruleset's order is not the order met
        [log(1, "1.1.1.1", "a.com", "/blockme"),
         log(2, "1.1.1.2", "b.com", "/blockme")],
        [("1.1.1.1", "a.com", "site-a-block"),
         ("1.1.1.1", "a.com", "global-block"),
         ("1.1.1.2", "b.com", "global-block")]),
    "hosts_to_skip leaves a global rule out on the named host only": (
        [SKIPPING],
        [log(1, "1.1.1.1", "skipme.com"), log(2, "1.1.1.1", "other.com"),
         log(3, "1.1.1.2", "www.skipme.com")],
        [("1.1.1.1", "other.com", "challenge-all"),
         ("1.1.1.2", "www.skipme.com", "challenge-all")]),
    "hosts_to_skip on a per-site rule's own site silences it": (
        [rule("site-a-off", r".*", 0, 1, _site="a.com",
              hosts_to_skip={"a.com": True})],
        [log(1, "1.1.1.1", "a.com")],
        []),
    "a window is per (ip, rule): a site's count is not another's": (
        [COUNTING, rule("site-b-count", r"POST .*login", 2, 300, _site="b.com")],
        [log(t, "1.1.1.1", host, "/login", "POST")
         for t, host in enumerate(["a.com", "b.com", "a.com", "b.com",
                                   "a.com", "c.com", "b.com"])],
        [("1.1.1.1", "a.com", "site-a-count"),
         ("1.1.1.1", "b.com", "site-b-count")]),
    "window start, in window, restart, exceeded (:77-260) on a site": (
        [rule("site-a-two-in-five", r"GET a\.com GET .*", 2, 5, _site="a.com")],
        [log(t, "1.1.1.1", "a.com") for t in (0, 4, 5.5, 6, 7, 8)],
        # hits 1, 2, restart (5.5 - 0 > 5) 1, 2, 3 > 2 fires, then 1
        [("1.1.1.1", "a.com", "site-a-two-in-five")]),
    "a site's rules in the ruleset's order": (
        [rule("second-listed-global", r".*both.*", 0, 1),
         rule("a-first", r".*both.*", 0, 1, _site="a.com"),
         rule("a-second", r"GET .*both", 0, 1, _site="a.com")],
        [log(1, "1.1.1.1", "a.com", "/both")],
        [("1.1.1.1", "a.com", "a-first"), ("1.1.1.1", "a.com", "a-second"),
         ("1.1.1.1", "a.com", "second-listed-global")]),
}
ORDER_TABLE = "per-site before global where one line fires both"


@pytest.mark.parametrize("case", sorted(TABLES))
def test_reference_per_site_tables(case):
    rules, log_lines, want = TABLES[case]
    assert triggers(rules, log_lines) == want


def test_reference_global_rulesets_read_as_before():
    """No `_site`, no `hosts_to_skip`: every rule searched in every string,
    in the ruleset's order (what the four cells' rulesets are)."""
    rules = [rule("r0", r"GET .*", 0, 1), rule("r1", r".*x.*", 0, 1),
             rule("r2", r"^POST", 0, 1)]
    rests = ["GET a.com GET /x HTTP/1.1 UA -", "POST a.com POST / HTTP/1.1 UA -",
             "short x"]
    assert reference.match_table(rules, rests, 1) == {
        rests[0]: (0, 1), rests[1]: (2,), rests[2]: (1,)}


def test_reference_refuses_two_records_of_one_name():
    with pytest.raises(SystemExit):
        reference.run([SITE_RULE, dict(SITE_RULE, _site="b.com")], [],
                      lambda ip: True, procs=1)


# ---- (d) the reference's order broken: the order table has to fail --------

def test_global_first_fails_the_order_table(monkeypatch):
    monkeypatch.setattr(reference, "met", lambda own, everywhere, host:
                        everywhere + own.get(host, []))
    rules, log_lines, want = TABLES[ORDER_TABLE]
    got = triggers(rules, log_lines)
    assert got != want and sorted(got) == sorted(want)
    # and the comparison `correct` rests on sees it as an order fault
    sound = [json.dumps({"client_ip": ip, "trigger": t, "action": "x"})
             for ip, _, t in want]
    broken = [json.dumps({"client_ip": ip, "trigger": t, "action": "x"})
              for ip, _, t in got]
    cmp_ = reference.compare(broken, sound)
    assert cmp_["ips_out_of_order"] == 1 and cmp_["ban_records_missing"] == 0


# ---- the fixture: a small per-site deployment ------------------------------

PATTERNS = [  # shared by the sites, each under limits of its own
    ("login", r"POST /wp-login\.php", {"method": "POST", "path": "/wp-login.php"}),
    ("xmlrpc", r"(GET|POST) /xmlrpc\.php\?[a-z]+=", {
        "method": "GET|POST", "path": "/xmlrpc.php?%s=%d"}),
    ("scan", r"(?i)sitescan|probebot/7\.[0-9]+", {
        "ua": ["sitescan", "probebot/7.%d"]}),
]
TLDS = ["com", "org", "net"]


def site_rules(n_sites: int = 24) -> list:
    """Global and per-site records interleaved (the harness's index is this
    order; the product lays per-site rules first): four global rules, one
    of which skips the most popular site, and two of the three shared
    patterns a site."""
    sites = [f"{'www.' * (i % 3 == 0)}site-{chr(97 + i % 26)}{chr(97 + i // 26)}"
             f".{TLDS[i % 3]}" for i in range(n_sites)]
    out = [
        rule("global-env", r"GET /\.env\.[a-z0-9]+", 0, 1, "nginx_block",
             _attack={"method": "GET", "path": "/.env.%s"}),
        rule("global-backup", r"/backup-[a-z]+\.sql\?[a-z]+=7[0-9]{1,4}", 2, 300,
             "challenge", _attack={"path": "/backup-%s.sql?%s=7%d"},
             hosts_to_skip={sites[0]: True}),
    ]
    for i, site in enumerate(sites):
        for k in (i % 3, (i + 1) % 3):
            name, regex, attack = PATTERNS[k]
            instant = (i + k) % 5 == 0
            out.append(rule(
                f"{site}-{name}", regex, 0 if instant else 1 + (i + k) % 2,
                1 if instant else 300, "challenge" if (i + k) % 2 else
                "nginx_block", _site=site, _attack=attack))
        if i == n_sites // 2:
            out.append(rule(
                "global-shell", r"^(GET|POST|HEAD) [a-z.-]+\.(com|org|net) .*/cgishell",
                2, 300, "nginx_block",
                _attack={"method": "GET|POST|HEAD", "path": "/%s/cgishell"}))
    out.append(rule("global-admin", r"(GET|POST) /admin/setup\.php", 2, 300,
                    "challenge", _attack={"method": "GET|POST",
                                          "path": "/admin/setup.php"}))
    return out


def fixture_cell() -> dict:
    """What `found.cell` returns for a workload of BENCHMARK.json."""
    with open(os.path.join(HERE, "per_site", "config.json"), encoding="utf-8") as f:
        config = json.load(f)
    with open(os.path.join(HERE, "per_site", "traffic.json"), encoding="utf-8") as f:
        traffic = json.load(f)
    config["ruleset"]["args"]["rules"] = site_rules()
    bj = found.benchmark_json()
    return {"name": "per-site.fixture", "chips": 1, "config": config,
            "traffic": traffic, "end_to_end": bj["end_to_end"],
            "per_layer": [m for m in bj["per_layer"] if "workloads" not in m]}


def test_fixture_lines_go_where_their_rules_apply():
    cell = fixture_cell()
    rules = found.ruleset(cell["config"]["ruleset"])
    rests, n_benign, attack_rule = genproc.build_pools(
        rules, cell["traffic"], 35353535)
    hosts = lines.SiteHosts(rules, cell["traffic"]["hosts"], 35353535)
    sites = list(dict.fromkeys(r["_site"] for r in rules if r.get("_site")))
    assert hosts.names[:len(sites)] == sites and len(hosts.names) == 32
    assert all(lines.HOST_NAME.fullmatch(h) for h in hosts.names)
    for rest, i in zip(rests[n_benign:], attack_rule):
        host = rest.split(" ", 2)[1]
        if rules[i].get("_site"):
            assert host == rules[i]["_site"]
        assert len(rest) <= 255
    benign_hosts = [r.split(" ", 2)[1] for r in rests[:n_benign]]
    assert set(benign_hosts) <= set(hosts.names)
    # rank 1 is the most popular; unprotected names are drawn too
    assert benign_hosts.count(sites[0]) > benign_hosts.count(sites[-1])
    assert set(benign_hosts) & set(hosts.names[len(sites):])
    # the product's records: per-site under their site, no private key
    from benchmark.harness import product
    import tempfile
    import yaml
    with tempfile.TemporaryDirectory() as d:
        with open(product.write_config(d, cell["config"], rules, {}),
                  encoding="utf-8") as f:
            cfg = yaml.safe_load(f)
    assert list(cfg["per_site_regexes_with_rates"]) == sites
    assert [r["rule"] for r in cfg["regexes_with_rates"]] == [
        r["rule"] for r in rules if not r.get("_site")]
    assert [r["rule"] for rs in cfg["per_site_regexes_with_rates"].values()
            for r in rs] == [r["rule"] for r in rules if r.get("_site")]
    assert cfg["regexes_with_rates"][1]["hosts_to_skip"] == {sites[0]: True}
    assert not any(k.startswith("_") for rs in
                   [cfg["regexes_with_rates"],
                    *cfg["per_site_regexes_with_rates"].values()]
                   for r in rs for k in r)


def test_per_site_rules_need_a_hosts_block():
    cell = fixture_cell()
    traffic = {k: v for k, v in cell["traffic"].items() if k != "hosts"}
    with pytest.raises(SystemExit):
        genproc.build_pools(site_rules(), traffic, 1)


def test_control_and_compare_take_per_site_records():
    """`control_rules` picks a crossed rule by the harness's index and the
    comparison of the two references fails, unchanged code on per-site
    records."""
    cell = fixture_cell()
    rules, traffic, seed = site_rules(), cell["traffic"], 35353536
    changed, name = cellrun.control_rules(rules, traffic, seed, "limit")
    idx = [r["rule"] for r in rules].index(name)
    assert changed[idx]["hits_per_interval"] == rules[idx]["hits_per_interval"] + 1
    assert changed[idx].get("_site") == rules[idx].get("_site")
    rests, n_benign, _ = genproc.build_pools(rules, traffic, seed)
    strm = stream.Stream(traffic, n_benign, len(rests) - n_benign, seed)
    ips, ridx = strm.block(0)
    log_lines = [f"{1000 + i * 1e-3:.6f} {ip} {rests[r]}"
                 for i, (ip, r) in enumerate(zip(ips[:60000], ridx[:60000]))]
    sound = reference.run(rules, log_lines, lambda ip: True, procs=1)
    control = reference.run(changed, log_lines, lambda ip: True,
                            table=sound["table"])
    per_site = {r["rule"] for r in rules if r.get("_site")}
    fired = {json.loads(x)["trigger"] for x in sound["bans"]}
    assert fired & per_site and fired - per_site
    cmp_ = reference.compare(sound["bans"], control["bans"])
    assert any(cmp_[k] for k in COMPARED)
    assert not any(reference.compare(sound["bans"], sound["bans"])[k]
                   for k in COMPARED)


# ---- (b), (c): the product rehearsed on the fixture -------------------------

def _rehearse(capsys, seed: int) -> tuple:
    """`run.py --rehearse` past its look for a chip, on the fixture's cell.
    → (result line, the run's printed lines)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    args = argparse.Namespace(rehearse=True, seed=seed, trace=0, control="",
                              keep_trace="", keep_log="")
    cwd = os.getcwd()
    try:
        rc = cellrun.run(fixture_cell(), args, 3.0, device, jax, time.time(),
                         lambda msg: print(f"[bench] {msg}", flush=True))
    finally:
        os.chdir(cwd)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def _reference_bans(out: list) -> tuple:
    """→ (ban records in the reference, those of per-site rules)."""
    line = next(x for x in out if x.startswith("[bench] reference: "))
    m = re.search(r"; (\d+) ban records \((\d+) of per-site rules\)", line)
    return int(m.group(1)), int(m.group(2))


def all_true_site_mask(monkeypatch):
    """The product's site mask broken: every rule applies on every host."""
    import numpy as np
    from banjax_tpu.matcher import fused_windows, runner

    n = {"tables": 0}
    real_fw = fused_windows.FusedWindowsPipeline.__init__
    real_tm = runner.TpuMatcher.__init__

    def fw_init(self, prefilter, windows, active_table, *a, **kw):
        n["tables"] += 1
        real_fw(self, prefilter, windows,
                np.ones(np.asarray(active_table).shape, bool), *a, **kw)

    def tm_init(self, *a, **kw):
        real_tm(self, *a, **kw)
        if self._active_table is not None:
            n["tables"] += 1
            self._active_table = self._active_table | True

    monkeypatch.setattr(fused_windows.FusedWindowsPipeline, "__init__", fw_init)
    monkeypatch.setattr(runner.TpuMatcher, "__init__", tm_init)
    return n


def test_sound_rehearsal_of_a_per_site_deployment(monkeypatch, capsys):
    result, out = _rehearse(capsys, 3535353501)
    assert result["checks_failed"] == [], result["checks_failed"]
    assert result["failed"] == 0 and result["attempted"] > 0
    n_bans, n_per_site = _reference_bans(out)
    assert n_per_site >= 1, "no ban from a per-site rule in the reference"
    assert n_bans > n_per_site, "no ban from a global rule in the reference"


def test_an_all_true_site_mask_is_seen(monkeypatch, capsys):
    n = all_true_site_mask(monkeypatch)
    result, _ = _rehearse(capsys, 3535353502)
    assert n["tables"] >= 1
    assert {"ban_records_extra", "ban_keys_differing"} <= set(
        result["checks_failed"])
    assert result["correct"] is False
