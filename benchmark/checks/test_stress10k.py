"""`stress10k.botnet` rehearsed on the CPU (by hand, not tier-1): the
`rehearse` blocks of `configs/upstream-stress10k.json` (48 rules of the
same generator, 256 slots, 256-line batches) and `traffic/botnet10k.json`.

The sound rehearsal has to end with all four ban-log comparisons at 0,
`fused_fallback_share` 0 and the cell's own counters read; and the same
rehearsal with a returning address's warm-tier state thrown away has to
fail the comparison, so what a ban after two refills rests on is held to
the reference here as in the `crs1k` cells.

The fault is `test_broken_path.py`'s `lost_state_on_refill`: the returning
addresses' records thrown away at the warm tier's `take_batch`."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

COMPARED = {"ban_records_missing", "ban_records_extra", "ips_out_of_order",
            "ban_keys_differing"}


def _rehearse(capsys, seed):
    sys.path.insert(0, REPO)
    from benchmark import run

    cwd = os.getcwd()
    try:
        rc = run.main(["--rehearse", "--workload", "stress10k.botnet",
                       "--seed", seed, "--seconds", "3", "--trace", "1"])
    finally:
        os.chdir(cwd)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_rehearsal_commits_every_chunk_fused(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    result = _rehearse(capsys, "3333333333")
    assert result["checks_failed"] == [], result["checks_failed"]
    assert result["failed"] == 0 and result["attempted"] > 0
    m = result["metrics"]
    assert m["fused_fallback_share"]["value"] == 0
    assert m["evictions_per_kline"]["value"] > 300
    # attack lines, slow attackers' lines and little else reach stage 2
    assert 50 <= m["stage2_candidates_per_kline"]["value"] <= 120
    # 128 + 24 a counter: an evicted address holds a few, not 48 rules
    assert 152 <= m["warm_record_bytes_mean"]["value"] < 128 + 24 * 48


def test_thrown_away_warm_tier_state_is_seen(monkeypatch, capsys):
    from test_broken_path import lost_state_on_refill

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    n = lost_state_on_refill(monkeypatch)
    result = _rehearse(capsys, "3333333334")
    assert n["calls"] >= 3
    assert "ban_records_missing" in result["checks_failed"]
    assert result["correct"] is False
