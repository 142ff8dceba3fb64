"""The control has to come out not correct, and the sound comparison of
the same run correct (a rehearsal's `correct` is false by construction, so
the sound side is read from `checks_failed`, the list the verdict is made
of)."""

import pytest

COMPARISON = {"control.ban_records_missing", "control.ban_records_extra",
              "control.ips_out_of_order", "control.ban_keys_differing"}


@pytest.mark.parametrize("workload", ["crs1k.flood", "crs1k.botnet"])
def test_control_fails_and_sound_run_passes(cell_runner, workload):
    run = cell_runner("--workload", workload, "--seed", "2147483659",
                      "--control", "limit")
    assert run["checks_failed"] == []
    assert run["failed"] == 0 and run["attempted"] > 0
    assert COMPARISON & set(run["control"]["checks_failed"]), run
    assert run["control"]["correct"] is False
