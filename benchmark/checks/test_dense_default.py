"""`default.flood` rehearsed on the CPU (by hand, not tier-1): the shipped
default rules, where nearly every line is a window event.

An overflow replay is correct and slow, so `correct` cannot see it; the
instruments have to.  The sound rehearsal therefore has to end with all
four ban-log comparisons at 0 AND `fused_fallback_share` 0; and the same
rehearsal with `test_broken_path.py`'s `dropped_bans` fault has to fail the
comparison, so a dense ban log is compared as closely as a sparse one.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

COMPARED = {"ban_records_missing", "ban_records_extra", "ips_out_of_order",
            "ban_keys_differing"}


def _rehearse(capsys):
    sys.path.insert(0, REPO)
    from benchmark import run

    cwd = os.getcwd()
    try:
        rc = run.main(["--rehearse", "--workload", "default.flood", "--seed",
                       "2828282828", "--seconds", "3", "--trace", "1"])
    finally:
        os.chdir(cwd)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_rehearsal_commits_every_chunk_fused(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    result = _rehearse(capsys)
    assert not COMPARED & set(result["checks_failed"]), result["checks_failed"]
    m = result["metrics"]
    assert m["fused_fallback_share"]["value"] == 0
    assert m["window_events_per_kline"]["value"] > 850
    assert m["ban_records_per_kline"]["value"] > 0


def test_dropped_bans_are_seen(monkeypatch, capsys):
    from test_broken_path import dropped_bans

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    n = dropped_bans(monkeypatch)
    result = _rehearse(capsys)
    assert n["calls"] >= 3
    assert "ban_records_missing" in result["checks_failed"]
    assert result["correct"] is False
