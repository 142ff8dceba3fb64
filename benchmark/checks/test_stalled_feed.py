"""A run whose device stage stalls for longer than the reference rule's
10 s: what a cold program build does in the first run of a checkout.  The
product drops the lines that aged out (when it parses them late, or when
it submits them) and says so in their results.  The harness has to leave
exactly those out of the reference's feed and count them as failed: the
comparison stays clean, and `failed` is above 0."""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_lines_that_aged_out_are_failed_not_compared(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)
    from banjax_tpu.matcher import runner
    from benchmark import run

    real = runner.TpuMatcher.pipeline_submit
    n = {"calls": 0}

    def stalling(self, state, now=None):
        n["calls"] += 1
        if n["calls"] == 600:  # some seconds into the window
            time.sleep(12.0)
        return real(self, state, now)

    monkeypatch.setattr(runner.TpuMatcher, "pipeline_submit", stalling)
    cwd = os.getcwd()
    try:
        rc = run.main(["--rehearse", "--workload", "crs1k.flood", "--seed",
                       "4242424243", "--seconds", "16", "--trace", "0"])
    finally:
        os.chdir(cwd)
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert n["calls"] > 600
    assert result["failed"] > 0
    assert result["checks_failed"] == []
