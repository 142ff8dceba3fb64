"""A run driven past the look for a chip with the timed path broken
underneath.  The comparison with the plain reference has to see it, and
for the right reason.

* `dropped_bans`: an answer altered where it is produced: the banner
  drops every third ban-log record.
* `lost_state_on_refill`: window state lost across an eviction: an
  address that comes back finds its warm-tier entry taken and thrown
  away, so its counters start again from nothing.  Only the slow
  attackers of the traffic (back after the slot table has turned over)
  can show this; the fast ones are never evicted.  The fault sits where a
  refill happens since PR 32: `DeviceWindows.resolve_addresses` takes the
  returning addresses' records in one `take_batch` of the warm tier
  (`test_stress10k.py` plants the same fault for its cell).
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def dropped_bans(monkeypatch):
    from banjax_tpu.effectors import banner

    real = banner.Banner.log_regex_ban
    n = {"calls": 0}

    def lossy(self, *a, **kw):
        n["calls"] += 1
        if n["calls"] % 3:
            real(self, *a, **kw)

    monkeypatch.setattr(banner.Banner, "log_regex_ban", lossy)
    return n


def lost_state_on_refill(monkeypatch):
    from banjax_tpu.native import shm

    n = {"calls": 0}
    for cls in (shm.ShmWarmTier, shm.PyWarmTier):
        def forgetful(self, ips, spans=None, _real=cls.take_batch):
            got = _real(self, ips, spans)
            n["calls"] += sum(v is not None for v in got)
            return [None] * len(got)

        monkeypatch.setattr(cls, "take_batch", forgetful)
    return n


@pytest.mark.parametrize("fault", [dropped_bans, lost_state_on_refill])
def test_broken_path_is_seen(fault, monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)
    from benchmark import run

    n = fault(monkeypatch)
    cwd = os.getcwd()
    try:
        rc = run.main(["--rehearse", "--workload", "crs1k.flood", "--seed",
                       "4242424242", "--seconds", "3", "--trace", "0"])
    finally:
        os.chdir(cwd)
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert n["calls"] >= 3
    assert "ban_records_missing" in result["checks_failed"]
    assert result["correct"] is False
