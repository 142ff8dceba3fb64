"""`multisite-edge` (ISSUE 37): the ruleset generator
(benchmark/rulesets/multisite.py), and the program on the deployment's
rehearsal ruleset — 24 sites, each with two of three shared patterns
under limits of its own, eight global rules, one `hosts_to_skip` —
through the pipelined fused path against the benchmark's plain reference.

The fused program masks stage 2's packed rows with the candidate's host
BEFORE it counts and lists the (row, rule) pairs
(kernels/fused_match_window.py).  Host-blind, one matching line yields a
pair for each of the 16 sites that carry its pattern, the pairs pass the
program's capacity and nearly every chunk replays classically (PR 35's
fixture: 119 of 139); that program is kept here as a control."""

import json
import os
import random
import re
import time

import numpy as np
import pytest
import yaml

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.matcher import fused_windows, sitemask
from banjax_tpu.matcher import prefilter as PF
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.pipeline import PipelineScheduler
from benchmark.harness import cellrun, found, genproc, lines, reference, stream
from benchmark.rulesets import crs_shaped, multisite
from tests.mock_banner import MockBanner

COMPARED = ("ban_records_missing", "ban_records_extra", "ips_out_of_order",
            "ban_keys_differing")
CONFIG = found.data("configs", "multisite-edge")
ARGS = CONFIG["ruleset"]["args"]


# ---- (a) the generator -----------------------------------------------------

@pytest.fixture(scope="module")
def rules():
    assert CONFIG["ruleset"]["generator"] == "multisite"
    return found.ruleset(CONFIG["ruleset"])


def _sites(rules):
    return list(dict.fromkeys(r["_site"] for r in rules if r.get("_site")))


def test_ten_thousand_records_fixed_by_the_seed(rules):
    assert len(rules) == 10_000
    assert len({r["rule"] for r in rules}) == 10_000
    assert multisite.build(**ARGS) == rules
    assert multisite.build(**{**ARGS, "seed": 8}) != rules
    assert CONFIG["reduced"] == [] and ARGS["seed"] == 7


def test_the_global_rules_are_crs1k_edges(rules):
    glob = [r for r in rules if not r.get("_site")]
    assert [{k: v for k, v in r.items() if k != "hosts_to_skip"}
            for r in glob] == crs_shaped.build(1000, seed=7)
    skipping = [r for r in glob if "hosts_to_skip" in r]
    assert [r["rule"] for r in skipping] == [
        f"crs-{i:04d}" for i in range(0, 1000, 50)]
    assert all(r["hosts_to_skip"] == {_sites(rules)[0]: True}
               for r in skipping)


def test_750_sites_take_12_of_24_patterns_each(rules):
    own = [r for r in rules if r.get("_site")]
    sites = _sites(rules)
    assert len(own) == 9000 and len(sites) == 750
    by_site = {}
    for r in own:
        by_site.setdefault(r["_site"], []).append(r)
    assert {len(v) for v in by_site.values()} == {12}
    assert all(len({r["regex"] for r in v}) == 12 for v in by_site.values())
    carried = {}
    for r in own:
        carried[r["regex"]] = carried.get(r["regex"], 0) + 1
    assert len(carried) == 24
    assert 300 <= min(carried.values()) and max(carried.values()) <= 450
    assert all(r["rule"].startswith(r["_site"] + "-") for r in own)
    # crs_shaped's six shapes, four patterns each
    shape = [r"GET /\w+-\w+/\[", r"\(GET\|POST\) /", r"POST /\w+\[a-z\]\*/",
             r"/\w+\\\.\w+\\\?", r"\(\?i\)", r"\^\(GET\|POST\|HEAD\)"]
    assert [sum(bool(re.match(s, x)) for x in carried) for s in shape] == [4] * 6


def test_site_names_are_host_names_in_rank_order(rules):
    sites = _sites(rules)
    assert all(lines.HOST_NAME.fullmatch(s) for s in sites)
    assert all(8 <= len(s) <= 24 for s in sites)
    # the records come site by site, so the ruleset's order is the rank
    first = {s: i for i, r in reversed(list(enumerate(rules)))
             if (s := r.get("_site"))}
    assert sorted(sites, key=first.get) == sites


def test_limits_are_each_sites_own(rules):
    own = [r for r in rules if r.get("_site")]
    instant = [r for r in own if r["hits_per_interval"] == 0]
    assert 0.03 < len(instant) / len(own) < 0.07
    assert {r["interval"] for r in instant} == {1}
    assert {(r["interval"], r["hits_per_interval"]) for r in own
            if r["hits_per_interval"]} == {
        (i, h) for i in (60, 300) for h in (1, 2, 3)}
    assert {r["decision"] for r in own} == {"challenge", "nginx_block"}
    # one pattern, many limits: what "limits of its own" means
    one = [r for r in own if r["regex"] == own[0]["regex"]]
    assert len({(r["interval"], r["hits_per_interval"]) for r in one}) == 7


def test_global_and_per_site_records_are_interleaved(rules):
    kinds = [bool(r.get("_site")) for r in rules]
    for part in (kinds[:40], kinds[-40:]):
        assert True in part and False in part


def test_attack_lines_match_their_rule_and_go_to_its_site(rules):
    hosts = lines.SiteHosts(rules, {"draw": "zipf", "s": 0.99,
                                    "unprotected": 250}, 37)
    assert len(hosts.names) == 1000 and hosts.names[:750] == _sites(rules)
    pool = lines.attack_pool(2048, rules, 255, 37, hosts)   # verifies with re
    catalog = sorted({r["regex"] for r in rules if r.get("_site")})
    n_site = 0
    for i, rest in pool:
        assert len(rest) <= 255
        host = rest.split(" ", 2)[1]
        if rules[i].get("_site"):
            n_site += 1
            assert host == rules[i]["_site"]
            # a line written for one pattern matches no other of the catalog
            assert [x for x in catalog if re.search(x, rest)] == [
                rules[i]["regex"]]
        else:
            assert host in hosts.names
    assert 0.85 < n_site / len(pool) < 0.95


def test_program_needs_refuses_a_missing_file():
    with pytest.raises(SystemExit) as e:
        multisite.build(**{**ARGS, "program_needs": [
            "banjax_tpu/matcher/no_such_file.py"]})
    assert "no_such_file.py" in str(e.value)
    assert len(multisite.build(**{**ARGS, "program_needs": [
        "banjax_tpu/matcher/prefilter.py"]})) == 10_000


def test_the_configuration_names_the_file_the_parent_lacks():
    """The parent's program did not finish the cell's set-up inside a run
    (PR 37's chip run): `program_needs` ends it with exit code 1 at once."""
    (needed,) = ARGS["program_needs"]
    assert needed == "banjax_tpu/matcher/sitemask.py"
    assert os.path.isfile(os.path.join(found.REPO, needed))


def test_the_cell_is_the_issues():
    cell = found.cell("multisite.botnet")
    t, base = cell["traffic"], found.data("traffic", "botnet10k")
    assert {k for k in t if t[k] != base.get(k)} == {"about", "hosts", "rehearse"}
    assert t["hosts"] == {"draw": "zipf", "s": 0.99, "unprotected": 250}
    assert cell["chips"] == 1
    assert sorted(m["name"] for m in cell["per_layer"]) == sorted([
        "batch_lines_mean", "encode_ms_per_kline", "devstage_ms_per_kline",
        "fused_fallback_share", "builds_in_window", "evictions_per_kline",
        "drain_ms_per_kline", "device_idle_share", "site_pairs_per_kline",
        "pairs_overflow_share", "site_events_share",
        # the submit stage from inside (ISSUE 39): every cell
        "submit_pass_ms_per_kline", "submit_sketch_ms_per_kline",
        "submit_operands_ms_per_kline", "submit_maintenance_ms_per_kline",
        "submit_dispatch_ms_per_kline", "submit_other_ms_per_kline",
        "submit_wait_share", "windows_lock_wait_ms_per_kline",
        "pipeline_cores_busy", "batch_bucket_changes",
        # the cyclic collector's pauses (ISSUE 40): every cell
        "gc_pause_ms_per_kline",
        # which dispatch carried the traffic sketch's fold (ISSUE 44):
        # every cell
        "sketch_fused_share",
        # the address pass's form and the victims' walk (ISSUE 45): every
        # cell
        "resolve_spans_share", "eviction_scan_slots_per_kline",
        # one call into the runtime a chunk, the table's maintenance
        # riding it (ISSUE 49): every cell
        "submit_runtime_calls_per_batch", "maintenance_fused_share",
        # the encode side's CPU and the gate's two counters (ISSUE 50):
        # every cell
        "encode_cpu_ms_per_kline", "gate_native_share",
        "gate_address_strings_per_kline"])
    pc = CONFIG["product_config"]
    assert {k: v for k, v in pc.items() if k != "config_version"} == {
        k: v for k, v in found.data("configs", "upstream-stress10k")[
            "product_config"].items() if k != "config_version"}


# ---- the program on the rehearsal ruleset -----------------------------------

def test_packed_rows_are_laid_as_stage_two_packs_its_bits():
    rng = np.random.default_rng(3)
    active = rng.random((5, 21)) < 0.5
    skip = (rng.random((5, 21)) < 0.1) & ~active
    f_idx = np.asarray([20, 3, 4, 17, 0, 9, 11, 12, 13, 1, 2])   # 11 columns
    got = sitemask.packed_rows(active, skip, f_idx)
    assert got.shape == (5, 2) and got.dtype == np.uint8
    bits = np.unpackbits(got, axis=1)            # MSB-first, as m2p's
    assert (bits[:, :11] == (active | skip)[:, f_idx]).all()
    assert not bits[:, 11:].any()                # pad columns stay clear
    assert (np.unpackbits(sitemask.packed_rows(active, None, f_idx), axis=1)
            [:, :11] == active[:, f_idx]).all()
    # one shared row, or no filtered column: no mask, the older program
    assert sitemask.packed_rows(active[:1], skip[:1], f_idx) is None
    assert sitemask.packed_rows(active, skip, f_idx[:0]) is None


@pytest.fixture(scope="module")
def rehearsal():
    """→ (rules, log lines, stamp): the deployment's `rehearse` ruleset and
    the first lines of its cell's rehearsal stream."""
    rules = found.ruleset(CONFIG["rehearse"]["ruleset"])
    traffic = cellrun.overlay(found.data("traffic", "botnet-sites"),
                              found.data("traffic", "botnet-sites")["rehearse"])
    seed = 3737
    rests, n_benign, _ = genproc.build_pools(rules, traffic, seed)
    strm = stream.Stream(traffic, n_benign, len(rests) - n_benign, seed)
    ips, ridx = strm.block(0)
    now = time.time()
    log = [f"{now - 4 + i * 5e-4:.6f} {ip} {rests[r]}"
           for i, (ip, r) in enumerate(zip(ips[:6144], ridx[:6144]))]
    return rules, log, now


class _Banner(MockBanner):
    def log_regex_ban(self, config, log_time_unix, ip, rule_name,
                      log_line_rest, decision):
        self.regex_ban_logs.append(json.dumps({
            "client_ip": ip, "trigger": rule_name,
            "action": decision.name}))


def _matcher(rules):
    per_site, everywhere = {}, []
    for rule, record in zip(rules, found.product_rules(rules)):
        (per_site.setdefault(rule["_site"], []) if rule.get("_site")
         else everywhere).append(record)
    cfg = config_from_yaml_text(yaml.safe_dump({
        "regexes_with_rates": everywhere,
        "per_site_regexes_with_rates": per_site}))
    cfg.matcher_device_windows = True
    cfg.matcher_window_capacity = 2048
    cfg.matcher_batch_lines = 256
    cfg.matcher_max_line_len = 256
    cfg.warm_tier_enabled = True
    cfg.warm_tier_capacity = 4096
    return TpuMatcher(cfg, _Banner(), StaticDecisionLists(cfg),
                      RegexRateLimitStates())


def _through_the_pipeline(rehearsal):
    """The stream through scheduler, fused program and drain → (matcher,
    the comparison of its ban log with the plain reference's)."""
    rules, log, now = rehearsal
    m = _matcher(rules)
    assert m._fw_pipeline is not None and len(m._host_row) == 24
    sched = PipelineScheduler(lambda: m, max_batch=256, now_fn=lambda: now)
    sched.start()
    for i in range(0, len(log), 256):
        sched.submit(log[i:i + 256])
    assert sched.flush(300)
    sched.stop()
    want = [json.dumps({"client_ip": d["client_ip"], "trigger": d["trigger"],
                        "action": d["action"]})
            for d in map(json.loads, reference.run(
                rules, log, lambda ip: True, procs=1)["bans"])]
    got = [json.dumps({**d, "action": reference.DECISION_STRING[
        d["action"].lower()]}) for d in map(json.loads, m.banner.regex_ban_logs)]
    per_site = {r["rule"] for r in rules if r.get("_site")}
    fired = {json.loads(x)["trigger"] for x in want}
    assert fired & per_site and fired - per_site and len(want) > 50
    return m, reference.compare(got, want)


def test_rehearsal_ruleset_commits_fused_and_equals_the_reference(rehearsal):
    m, cmp_ = _through_the_pipeline(rehearsal)
    assert {k: cmp_[k] for k in COMPARED} == dict.fromkeys(COMPARED, 0)
    fw = m._fw_pipeline
    assert fw.overflow_causes == {
        "candidates": 0, "pairs": 0, "events": 0, "chain": 0,
        "long_rows": 0}
    assert m.pipelined_fused_chunks >= 20 and m.pipelined_fused_fallbacks == 0
    # about one pair a matching line: 5 % attack lines and the slow
    # attackers' — not 16 times that
    assert 0 < fw.pairs_total < 0.1 * 6144
    dw = m.device_windows
    assert dw.n_site_rules == 48
    assert 0.7 < dw.site_events / dw.device_events < 0.95
    m.close()


def test_host_blind_pairs_overflow_and_the_replay_is_exact(
        rehearsal, monkeypatch):
    """The program as it was: pairs counted before the host is known.
    Most chunks overflow their pair capacity by cause `pairs`; each is
    replayed classically, in order, and the ban log is still the
    reference's: the replay stays reachable and exact."""
    real = PF.FusedPrefilter.pairs_from_core
    monkeypatch.setattr(
        PF.FusedPrefilter, "pairs_from_core",
        lambda self, c, K, P, keep=None: real(self, c, K, P))
    m, cmp_ = _through_the_pipeline(rehearsal)
    assert {k: cmp_[k] for k in COMPARED} == dict.fromkeys(COMPARED, 0)
    fw = m._fw_pipeline
    assert fw.overflow_causes["pairs"] >= 10
    assert m.pipelined_fused_fallbacks >= fw.overflow_causes["pairs"]
    assert fw.pairs_total > 2000   # sixteen a matching line
    m.close()


def test_an_all_true_site_mask_fails_the_comparison(rehearsal, monkeypatch):
    """The control: every rule active on every host — in the fused
    program, in front of the pairs and at the events alike (both are read
    from the one table), and in the classic replay, which the chunks take
    whose pairs now pass the capacity."""
    real = fused_windows.FusedWindowsPipeline.__init__
    real_tm = TpuMatcher.__init__

    def init(self, prefilter, windows, active_table, *a, **kw):
        real(self, prefilter, windows,
             np.ones(np.asarray(active_table).shape, bool), *a, **kw)

    def tm_init(self, *a, **kw):
        real_tm(self, *a, **kw)
        self._active_table = self._active_table | True

    monkeypatch.setattr(fused_windows.FusedWindowsPipeline, "__init__", init)
    monkeypatch.setattr(TpuMatcher, "__init__", tm_init)
    m, cmp_ = _through_the_pipeline(rehearsal)
    assert cmp_["ban_records_extra"] > 0 and cmp_["ban_keys_differing"] > 0
    assert cmp_["ban_records_missing"] == 0
    m.close()


def _keeps_seen(monkeypatch):
    seen = []
    real = PF.FusedPrefilter.pairs_from_core

    def spy(self, c, K, P, keep=None):
        seen.append(keep)
        return real(self, c, K, P, keep)

    monkeypatch.setattr(PF.FusedPrefilter, "pairs_from_core", spy)
    return seen


def _one_batch(m, log, now):
    sched = PipelineScheduler(lambda: m, max_batch=256, now_fn=lambda: now)
    sched.start()
    sched.submit(log)
    assert sched.flush(120)
    sched.stop()


def test_a_ruleset_of_global_rules_builds_no_mask(rehearsal, monkeypatch):
    """No per-site rule and no `hosts_to_skip` — the four older cells —
    and the fused program is built as before: one row in the active table,
    no gather in front of the pairs.  One `hosts_to_skip` is enough for a
    second row and the mask."""
    rules, log, now = rehearsal
    seen = _keeps_seen(monkeypatch)
    everywhere = [{k: v for k, v in r.items() if k != "hosts_to_skip"}
                  for r in rules if not r.get("_site")]
    m = _matcher(everywhere)
    assert m._host_row == {} and m._active_table.shape == (1, 8)
    _one_batch(m, log[:256], now)
    assert m.pipelined_fused_chunks == 1 and seen == [None]
    m.close()
    del seen[:]
    m = _matcher([r for r in rules if not r.get("_site")])
    assert len(m._host_row) == 1
    _one_batch(m, log[:256], now)
    assert m.pipelined_fused_chunks == 1
    assert [k.shape for k in seen] == [(m._fw_pipeline.pf.capacities(256)[1],
                                        m._fw_pipeline.pf._nf8)]
    m.close()


@pytest.mark.parametrize("where,pairs,events", [
    ("own site", 1, 1),
    ("a site without the pattern", 0, 0),
    ("an unprotected host", 0, 0),
])
def test_a_line_for_a_pattern_many_sites_share_is_one_pair(
        rehearsal, where, pairs, events):
    rules, _, now = rehearsal
    own = [r for r in rules if r.get("_site")]
    rule = own[0]
    carriers = {r["_site"] for r in own if r["regex"] == rule["regex"]}
    assert 12 <= len(carriers) <= 20   # 24 sites x 2 of 3
    host = {
        "own site": rule["_site"],
        "a site without the pattern": next(
            r["_site"] for r in own if r["_site"] not in carriers),
        "an unprotected host": "nobody.example.org",
    }[where]
    rng = random.Random(5)
    rest = lines.attack_line({**rule, "_site": host}, rng, 255,
                             hosts=object())
    assert rest.split(" ", 2)[1] == host and re.search(rule["regex"], rest)
    m = _matcher(rules)
    _one_batch(m, [f"{now:.6f} 9.9.9.9 {rest}"], now)
    assert m.pipelined_fused_chunks == 1
    assert m._fw_pipeline.pairs_total == pairs
    assert m.device_windows.device_events == events
    assert m.device_windows.site_events == events
    m.close()


def test_a_skipped_global_rule_keeps_its_pair_and_fires_no_event(rehearsal):
    """`hosts_to_skip`: the drain owes the line a `skip_host` result, so
    the pair stays; the window sees nothing."""
    rules, _, now = rehearsal
    skipping = next(r for r in rules if r.get("hosts_to_skip"))
    (site,) = skipping["hosts_to_skip"]
    rng = random.Random(6)
    m = _matcher(rules)
    for host, events in ((site, 0), ("nobody.example.org", 1)):
        rest = lines.attack_line({**skipping, "_site": host}, rng, 255,
                                 hosts=object())
        before = m._fw_pipeline.pairs_total, m.device_windows.device_events
        _one_batch(m, [f"{now:.6f} 9.9.9.8 {rest}"], now)
        assert m._fw_pipeline.pairs_total - before[0] >= 1
        assert m.device_windows.device_events - before[1] == events
    assert m.device_windows.site_events == 0
    m.close()
