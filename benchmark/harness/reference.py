"""The plain reference: what the reference implementation's serial loop
(`regex_rate_limiter.go` consumeLine / applyRegexToLog, `rate_limit.go`
Apply, `iptables.go` LogRegexBan) writes into the ban log for a stream of
`banjax_format` lines.  Python `re`, one dict of fixed-window counters; no
import of the program, no JAX, nothing the program has made.

Semantics held to (the configuration's guarantee):
  * `<epoch.frac> <ip> <rest>`, `rest` = `<method> <host> ...`; a line with
    fewer fields is an error and matches nothing;
  * a line of host `h` (field 2 of `rest`) meets the rules whose `_site` is
    `h`, in the ruleset's order, then the rules without a `_site` (the
    global ones) in theirs: `per_site_regexes_with_rates[h]` before
    `regexes_with_rates`, `regex_rate_limiter.go:175-211`; each rule's
    regex is searched (unanchored) in `rest`, and a rule whose
    `hosts_to_skip[h]` is set is left out on that host
    (applyRegexToLog, `:216-269`; tests `regex_rate_limiter_test.go:77-296`);
  * fixed window per (ip, rule): restart (hits := 1) when
    `t - start > interval` in integer nanoseconds, else hits += 1; when
    `hits > hits_per_interval` the rule fires and hits := 0;
  * a firing writes one ban-log record taken from the line.
`now` is held at each line's own stamp, so nothing is stale here; lines
the product dropped as stale or shed are left out by the caller and
counted as failed.  Window state is per (ip, rule record); upstream keys it
by the rule's name, so a ruleset in which two records share a name is
refused.  The configurations have no allow lists, and
`product.write_config` refuses a deployment that has.

Cost.  The host is part of the request string, so which rules apply to a
string and match it, and in what order, is a pure function of the string:
it is worked out once per distinct string (spawned children, off the
chip's process; only the string's own site's regexes and the global ones
are searched), then the per-IP window logic runs over every followed line
in order.  A cut, where one is ever needed, is by client IP and
never by line: window state and bans are per IP.
"""

from __future__ import annotations

import collections
import json
import multiprocessing as mp
import os
import re

DECISION_STRING = {
    "allow": "Allow", "challenge": "Challenge", "nginx_block": "NginxBlock",
    "iptables_block": "IptablesBlock",
}


def rule_order(rules: list) -> tuple:
    """→ ({site: its rules' indices}, the global rules' indices), each in
    the ruleset's order."""
    by_site, global_ids = {}, []
    for i, r in enumerate(rules):
        if r.get("_site"):
            by_site.setdefault(r["_site"], []).append(i)
        else:
            global_ids.append(i)
    return by_site, global_ids


def met(own: dict, everywhere: list, host) -> list:
    """The rules a line of `host` meets, in order: its host's own first,
    then the global ones (`regex_rate_limiter.go:175-193`, `:195-211`)."""
    return own.get(host, []) + everywhere


def _match_chunk(args):
    """Child: for each request string, the indices of the rules that apply
    to its host and match it, in the order they apply."""
    rules, rests = args
    by_site, global_ids = rule_order(rules)

    def compiled(ids: list) -> list:
        return [(i, re.compile(rules[i]["regex"]).search,
                 rules[i].get("hosts_to_skip") or {}) for i in ids]

    everywhere = compiled(global_ids)
    own = {}  # a site's rules are compiled when the site is first seen
    out = []
    for s in rests:
        words = s.split(" ", 2)
        host = words[1] if len(words) == 3 else None
        if host in by_site and host not in own:
            own[host] = compiled(by_site[host])
        out.append(tuple(i for i, search, skip in met(own, everywhere, host)
                         if search(s) and not skip.get(host)))
    return out


def match_table(rules: list, rests: list, procs: int) -> dict:
    rules = [{k: r.get(k) for k in ("regex", "_site", "hosts_to_skip")}
             for r in rules]
    procs = max(1, min(procs, (os.cpu_count() or 2) - 1, len(rests) // 64 or 1))
    if procs == 1:
        return dict(zip(rests, _match_chunk((rules, rests))))
    step = -(-len(rests) // (procs * 4))
    chunks = [rests[i:i + step] for i in range(0, len(rests), step)]
    with mp.get_context("spawn").Pool(procs) as pool:
        parts = pool.map(_match_chunk, [(rules, c) for c in chunks])
    return {s: m for c, p in zip(chunks, parts) for s, m in zip(c, p)}


def ban_record(ip: str, rule: dict, rest: str) -> str:
    """The ban-log line of `LogRegexBan`, without its timestring, keys
    sorted (the form both sides are compared in)."""
    words = rest.split(" ", 5)
    return json.dumps({
        "path": words[3],
        "trigger": rule["rule"],
        "client_ua": words[5].split("|", 1)[0].strip(),
        "client_ip": ip,
        "rule_type": "regex",
        "client_request_method": words[0],
        "http_request_scheme": "https",
        "client_request_host": words[1],
        "action": DECISION_STRING[rule["decision"]],
        "number_of_fails": 1,
        "disable_logging": 0,
    }, sort_keys=True)


def run(rules: list, lines, checked, procs: int = 8, table=None) -> dict:
    """`lines`: the log's lines in order; `checked(ip)` says whether an
    IP's lines are followed.  → {"bans": [record], "lines": n followed,
    "errors": n unparsable, "distinct": n distinct request strings,
    "table": which rules each request string meets and matches, in the
    order it meets them}.  `table`: that of an earlier call over the same
    lines and the same regexes, sites and `hosts_to_skip` (the control
    differs from the sound run in a limit only)."""
    if len({r["rule"] for r in rules}) != len(rules):
        raise SystemExit("reference: two rule records share a name")
    followed = []
    for line in lines:
        parts = line.split(" ", 2)
        if len(parts) < 3 or not checked(parts[1]):
            continue
        followed.append(parts)
    distinct = list(dict.fromkeys(p[2] for p in followed))
    if table is None:
        table = match_table(rules, distinct, procs)
    interval_ns = [int(r["interval"] * 1_000_000_000) for r in rules]
    limit = [int(r["hits_per_interval"]) for r in rules]
    state = {}  # (ip, rule index) -> [hits, window start ns]
    bans, errors = [], 0
    for ts, ip, rest in followed:
        hits = table[rest]
        if not hits:
            continue
        try:
            t_ns = int(float(ts) * 1e9)
        except (ValueError, OverflowError):
            errors += 1
            continue
        if len(rest.split(" ", 2)) < 3:
            errors += 1
            continue
        for i in hits:
            st = state.get((ip, i))
            if st is None:
                st = state[(ip, i)] = [1, t_ns]
            elif t_ns - st[1] > interval_ns[i]:
                st[0], st[1] = 1, t_ns
            else:
                st[0] += 1
            if st[0] > limit[i]:
                st[0] = 0
                if len(rest.split(" ", 5)) >= 6:
                    bans.append(ban_record(ip, rules[i], rest))
    return {"bans": bans, "lines": len(followed), "errors": errors,
            "distinct": len(distinct), "table": table}


def product_record(line: str) -> str:
    d = json.loads(line)
    d.pop("timestring", None)
    return json.dumps(d, sort_keys=True)


def compare(got: list, want: list) -> dict:
    """Ban logs in the compared form → the numbers `correct` rests on:
    records missing and extra (multiset), client IPs whose own records
    come in another order, and (ip, rule, decision) triples on one side
    only.  All four have the limit 0."""
    g, w = collections.Counter(got), collections.Counter(want)

    def per_ip(records):
        out = collections.defaultdict(list)
        for x in records:
            out[json.loads(x)["client_ip"]].append(x)
        return out

    def keys(records):
        return {(d["client_ip"], d["trigger"], d["action"])
                for d in map(json.loads, records)}

    gi, wi = per_ip(got), per_ip(want)
    return {
        "ban_records_missing": sum((w - g).values()),
        "ban_records_extra": sum((g - w).values()),
        "ips_out_of_order": sum(1 for ip in set(gi) | set(wi)
                                if gi.get(ip) != wi.get(ip)),
        "ban_keys_differing": len(keys(got) ^ keys(want)),
        "example_missing": list((w - g).elements())[:2],
        "example_extra": list((g - w).elements())[:2],
    }


def final_decisions(records: list) -> dict:
    """ip → the severest decision its records carry (the dynamic lists
    are monotonic in severity)."""
    rank = {"Challenge": 2, "NginxBlock": 3, "IptablesBlock": 4}
    out = {}
    for d in map(json.loads, records):
        if rank[d["action"]] > rank.get(out.get(d["client_ip"]), 0):
            out[d["client_ip"]] = d["action"]
    return out
