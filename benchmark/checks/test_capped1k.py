"""`capped1k.flood` (PR 41), by hand like `test_multisite.py`; the cases
that start no product run in tier-1 through
`tests/unit/test_benchmark_checks.py`.

Port-free: the generator's 1,005 names are pairwise distinct, the five
front rules are upstream's regex-banner fixture letter for letter (but for
the keys of `hosts_to_skip`), the 1,000 behind them are `crs1k-edge`'s; a
benign GET line on a skipped host meets the rate cap and no other front
rule; the plain reference's own count of the load's invariant
(`always_events_share`, PERF.md §3) lies in its band; the control — the cap
raised to 46 for the reference alone — fails the comparison.

With the product (port 8081, CPU): the sound rehearsal ends with all four
comparisons at 0, no chunk replayed and one promoted rule; and with the
plan's routing of the weak gate taken away (`prefilter.weak_gate` forced
false: the parent's plan) the chunks overflow `candidates` and replay,
the ban log stays exact, and the log names the bucket and the cap."""

import json
import os
import sys

import yaml

from benchmark.harness import found, genproc, reference, stream
from benchmark.rulesets import crs_shaped

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURE = os.path.join(REPO, "tests", "fixtures",
                       "banjax-config-test-regex-banner.yaml")
CAP = "All sites/GET: 45 req/60 sec"
CHALLENGE_ALL = "Challenge all but skip localhost:8081"
# the share of window events that come from the two always-columns: the
# plain reference's count over the stream's first block reads 96.3 at
# full size (this file's own case); the band is that +- 2 points
ALWAYS_SHARE_BAND = (94.3, 98.3)


def _cell(rehearse=False):
    from benchmark.harness.cellrun import overlay

    cell = found.cell("capped1k.flood")
    config, traffic = cell["config"], cell["traffic"]
    if rehearse:
        config = overlay(config, config["rehearse"])
        traffic = overlay(traffic, traffic["rehearse"])
    return config, traffic


def test_front_rules_are_the_fixtures_and_names_are_distinct():
    config, _ = _cell()
    rules = found.ruleset(config["ruleset"])
    assert len(rules) == 1005
    assert len({r["rule"] for r in rules}) == 1005
    with open(FIXTURE, encoding="utf-8") as f:
        fixture = yaml.safe_load(f)["regexes_with_rates"]
    assert len(fixture) == 5
    for mine, theirs in zip(found.product_rules(rules[:5]), fixture):
        assert set(mine) == set(theirs), mine["rule"]
        for key, value in theirs.items():
            if key == "hosts_to_skip":
                # the test server's own name, which no generated line
                # carries, is replaced by 15 of the harness's 16 hosts
                assert len(mine[key]) == 15 and all(mine[key].values())
                assert "press.rights-watch.net" not in mine[key]
            else:
                assert mine[key] == value, (mine["rule"], key)
    assert rules[5:] == crs_shaped.build(1000, 7)
    # recipes: the two instant rules that can be fired, not the allow rule
    assert [bool(r.get("_attack")) for r in rules[:5]] == [
        False, True, True, False, False]


def test_a_benign_get_meets_the_cap_and_nothing_else_of_the_front():
    config, _ = _cell()
    rules = found.ruleset(config["ruleset"])
    ua = "Mozilla/5.0 (X11; Linux x86_64) Gecko/20100101 Firefox/127.0"
    rests = [
        f"GET example.com GET /news/2026/index.html HTTP/1.1 {ua} -",
        f"POST example.com POST /api/v1/items HTTP/1.1 {ua} -",
        f"GET press.rights-watch.net GET /about HTTP/1.1 {ua} -",
        f"HEAD press.rights-watch.net HEAD /about HTTP/1.1 {ua} -",
    ]
    names = [[rules[i]["rule"] for i in hit]
             for hit in reference._match_chunk((rules, rests))]
    assert names == [[CAP], [], [CHALLENGE_ALL, CAP], [CHALLENGE_ALL]]


def _stream_lines(rules, traffic, seed, n, dt):
    rests, n_benign, _ = genproc.build_pools(rules, traffic, seed)
    strm = stream.Stream(traffic, n_benign, len(rests) - n_benign, seed)
    ips, ridx = strm.block(0)
    return [f"{1_700_000_000 + i * dt:.6f} {ip} {rests[r]}"
            for i, (ip, r) in enumerate(zip(ips[:n], ridx[:n]))], rests


def test_the_references_own_always_share_lies_in_the_band():
    """Full-size pools and the stream's first 65,536 lines: of the rule
    matches that apply (each is a window event), the share of the
    challenge-all rule and the cap."""
    config, traffic = _cell()
    rules = found.ruleset(config["ruleset"])
    lines, rests = _stream_lines(rules, traffic, 4141, 1 << 16, 1e-5)
    table = reference.match_table(
        rules, list(dict.fromkeys(rests)), procs=4)
    always = other = 0
    for line in lines:
        for i in table[line.split(" ", 2)[2]]:
            if rules[i]["rule"] in (CAP, CHALLENGE_ALL):
                always += 1
            else:
                other += 1
    share = 100.0 * always / (always + other)
    assert ALWAYS_SHARE_BAND[0] + 1 < share < ALWAYS_SHARE_BAND[1] - 1, share
    # and what a thousand lines carry, for PERF.md's reckoning
    per_kline = 1000.0 * (always + other) / len(lines)
    assert 850 < per_kline < 930, per_kline


def test_the_control_with_the_cap_at_46_fails():
    """The rehearsal's stream at 10,000 lines a second: the Zipf head
    crosses 45 GETs in 60 s many times over; a reference whose cap is 46
    writes other records, and the comparison says so."""
    config, traffic = _cell(rehearse=True)
    rules = found.ruleset(config["ruleset"])
    lines, _ = _stream_lines(rules, traffic, 4142, 1 << 15, 1e-4)
    checked = lambda ip: ip.startswith(f"{stream.IP_BASE}.")  # noqa: E731
    sound = reference.run(rules, lines, checked, procs=1)
    assert sum(json.loads(x)["trigger"] == CAP for x in sound["bans"]) > 20
    raised = [dict(r) for r in rules]
    cap = next(r for r in raised if r["rule"] == CAP)
    cap["hits_per_interval"] += 1
    control = reference.run(raised, lines, checked, table=sound["table"])
    cmp_ = reference.compare(sound["bans"], control["bans"])
    assert cmp_["ban_records_missing"] + cmp_["ban_records_extra"] > 0
    assert cmp_["ips_out_of_order"] > 0
    same = reference.compare(sound["bans"], reference.run(
        rules, lines, checked, table=sound["table"])["bans"])
    assert [same[k] for k in ("ban_records_missing", "ban_records_extra",
                              "ips_out_of_order", "ban_keys_differing")
            ] == [0, 0, 0, 0]


# ---- with the product (port 8081; by hand) ----


def _assert_sound(result, promoted=1):
    assert result["checks_failed"] == [], result["checks_failed"]
    assert result["attempted"] > 0
    m = result["metrics"]
    assert m["plan_promoted_rules"]["value"] == promoted
    assert m["evictions_per_kline"]["value"] > 100
    return m


def test_sound_rehearsal_replays_no_chunk(cell_runner):
    result = cell_runner("--workload", "capped1k.flood", "--seed",
                         "4141414141", "--trace", "1")
    m = _assert_sound(result)
    assert m["candidates_overflow_share"]["value"] == 0
    assert m["fused_fallback_share"]["value"] == 0
    # the rehearsal's 5 % attack lines on 17 rules: more pairs than the
    # cell's 2 % on 1,005
    assert 88 <= m["always_events_share"]["value"] <= 98


def test_without_the_weak_gate_route_the_chunks_replay_exactly(
        monkeypatch, capsys, caplog, tmp_path):
    """`weak_gate` forced false, as on the parent: the cap is filtered
    behind `GET `, which 80 % of the lines carry.  Every chunk of more
    rows than the compaction's floor overflows `candidates` and replays
    classically; the ban log is exact all the way, and the log says which
    bucket and which rule."""
    sys.path.insert(0, REPO)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    # a compile cache of its own: the checkout's holds the plan an
    # earlier run compiled with the route in place
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    from banjax_tpu.matcher import prefilter
    from benchmark import run

    monkeypatch.setattr(prefilter, "weak_gate", lambda prog, factors: False)
    cwd = os.getcwd()
    try:
        with caplog.at_level("INFO", logger="banjax_tpu.matcher.runner"):
            rc = run.main(["--rehearse", "--workload", "capped1k.flood",
                           "--seed", "4141414143", "--seconds", "3",
                           "--trace", "1"])
    finally:
        os.chdir(cwd)
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    m = _assert_sound(result, promoted=0)
    assert m["candidates_overflow_share"]["value"] > 50
    assert m["fused_fallback_share"]["value"] > 50
    # no window event came from a fused program, so this reader is silent
    assert "always_events_share" not in m
    named = [r.getMessage() for r in caplog.records
             if "candidates overflow: factor bucket" in r.getMessage()]
    assert named and all(repr(CAP) in ln for ln in named)
