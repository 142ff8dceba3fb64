"""The two guards tests/conftest.py puts around every test: the lock that
lets one xdist worker at a time own what the workers share (port 8081),
and the ceiling on one test."""

import multiprocessing
import signal
import threading
import time

import pytest

from tests import conftest


def _hold_lock(path, holding, let_go):
    conftest._WORKERS_LOCK_PATH = path
    with conftest._workers_lock():
        holding.set()
        let_go.wait(30)


def test_second_taker_of_the_workers_lock_waits_for_the_first(
    tmp_path, monkeypatch
):
    # a lock file of its own: the real one may be held by a neighbour
    path = str(tmp_path / "workers.lock")
    monkeypatch.setattr(conftest, "_WORKERS_LOCK_PATH", path)
    ctx = multiprocessing.get_context("spawn")
    holding, let_go = ctx.Event(), ctx.Event()
    child = ctx.Process(target=_hold_lock, args=(path, holding, let_go))
    child.start()
    took = threading.Event()

    def take():
        with conftest._workers_lock():
            took.set()

    taker = threading.Thread(target=take)
    try:
        assert holding.wait(30), "the child never took the lock"
        taker.start()
        assert not took.wait(1.0), "took the lock while the child held it"
    finally:
        let_go.set()
        child.join(30)
    assert not child.is_alive() and child.exitcode == 0
    taker.join(30)
    assert took.is_set(), "the lock was not handed over"


def test_time_limit_fails_a_hang_with_its_own_traceback_and_disarms():
    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception, match="ceiling") as caught:
        with conftest._time_limit(1):
            time.sleep(5)
    assert 0.9 <= time.monotonic() - t0 < 3.0
    assert "time.sleep(5)" in str(caught.getrepr())
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    time.sleep(1.2)  # nothing fires after the block has ended
