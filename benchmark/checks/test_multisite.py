"""`multisite.botnet` rehearsed on the CPU (by hand, not tier-1; port
8081): the `rehearse` blocks of `configs/multisite-edge.json` (24 sites,
each with two of three shared patterns under limits of its own, eight
global rules, one `hosts_to_skip`; 256 slots, 256-line batches) and of
`traffic/botnet-sites.json` (hosts Zipf(0.99) over the 24 sites and 8
unprotected names).

The sound rehearsal has to end with all four ban-log comparisons at 0, no
chunk replayed for its pairs (the program masks by site before it counts
them: PR 35's fixture of this shape read 119 of 139 chunks overflowed on
the program before) and the cell's three counters read; the same rehearsal
with the product's site mask forced all-true has to fail the comparison
(`test_per_site.py`'s fault)."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _rehearse(capsys, seed):
    sys.path.insert(0, REPO)
    from benchmark import run

    cwd = os.getcwd()
    try:
        rc = run.main(["--rehearse", "--workload", "multisite.botnet",
                       "--seed", seed, "--seconds", "3", "--trace", "1"])
    finally:
        os.chdir(cwd)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_rehearsal_replays_no_chunk_for_its_pairs(cell_runner):
    result = cell_runner("--workload", "multisite.botnet", "--seed",
                         "3737373737", "--trace", "1")
    assert result["checks_failed"] == [], result["checks_failed"]
    # `failed` is left out: on a loaded CPU some hundred lines drain later
    # than 10 s after the close, and the rehearsal's timing is no subject
    assert result["attempted"] > 0
    m = result["metrics"]
    assert m["fused_fallback_share"]["value"] == 0
    assert m["pairs_overflow_share"]["value"] == 0
    # one pair a matching line (5 % attack lines and the slow attackers'),
    # not one for each of the 16 sites that carry the line's pattern
    assert 0 < m["site_pairs_per_kline"]["value"] < 100
    # 48 of the rehearsal's 56 rules belong to a site
    assert 75 <= m["site_events_share"]["value"] <= 95
    assert m["evictions_per_kline"]["value"] > 300


def test_an_all_true_site_mask_is_seen(monkeypatch, capsys):
    from test_per_site import all_true_site_mask

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    n = all_true_site_mask(monkeypatch)
    result = _rehearse(capsys, "3737373738")
    assert n["tables"] >= 1
    assert {"ban_records_extra", "ban_keys_differing"} <= set(
        result["checks_failed"])
    assert result["correct"] is False
