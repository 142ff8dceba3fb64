"""Test harness config: force an 8-device virtual CPU mesh.

The driver tests multi-chip sharding without hardware by running JAX on the
host platform with 8 virtual devices; the real chip is driven outside
pytest (chip_smoke.py).
"""

import os
import sys

# hard override: tests run on the 8-virtual-device CPU backend whatever
# the session exports — chip_smoke.py is what runs on the real chip
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import shutil  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

import pytest  # noqa: E402

_FIXTURES = Path(__file__).resolve().parent / "fixtures"


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 run (-m 'not slow')"
    )
    config.addinivalue_line(
        "markers", "allow_leaks: opt out of the leaked thread/process guard"
    )


def _live_child_pids():
    """PIDs of live (non-zombie) direct children, excluding the
    multiprocessing resource tracker (session-lived by design)."""
    if not os.path.isdir("/proc"):
        return set()
    me = os.getpid()
    out = set()
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat", "rb") as f:
                stat = f.read().decode("latin-1")
            # fields after the parenthesized comm: state is 1st, ppid 2nd
            rest = stat.rsplit(")", 1)[1].split()
            state, ppid = rest[0], int(rest[1])
            if ppid != me or state == "Z":
                continue
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                cmdline = f.read()
            if b"resource_tracker" in cmdline:
                continue
            out.add(int(entry))
        except (OSError, IndexError, ValueError):
            continue  # raced a process exit
    return out


@pytest.fixture(autouse=True)
def _leak_guard(request):
    """Fail any test that leaves a non-daemon thread or a live child
    process behind — a leaked worker keeps ports/shm segments alive and
    poisons every later test in the session.  Teardown of the test's own
    fixtures (e.g. app_factory stopping the app) runs BEFORE this check.
    Mark a test `@pytest.mark.allow_leaks` to opt out."""
    if request.node.get_closest_marker("allow_leaks"):
        yield
        return
    threads_before = set(threading.enumerate())
    children_before = _live_child_pids()
    yield

    def leaked():
        lt = [
            t for t in threading.enumerate()
            if t not in threads_before and t.is_alive() and not t.daemon
        ]
        lc = _live_child_pids() - children_before
        return lt, lc

    # grace window: joins/waitpids triggered by fixture teardown may still
    # be settling when we first look
    deadline = time.monotonic() + 3.0
    lt, lc = leaked()
    while (lt or lc) and time.monotonic() < deadline:
        time.sleep(0.05)
        lt, lc = leaked()
    if lt or lc:
        pytest.fail(
            f"test leaked non-daemon threads {[t.name for t in lt]} "
            f"and/or live child processes {sorted(lc)}"
        )


@pytest.fixture(autouse=True)
def _challenge_stats_isolation():
    """The challenge-plane counters are a process singleton
    (banjax_tpu/challenge/stats.py); once active they add Challenge*
    keys to the metrics line and banjax_challenge_* families to
    /metrics.  Reset after every test so the reference-schema tests see
    a challenge-quiet process regardless of ordering.  The serving
    fast path's counters (httpapi/serve_stats.py) are the same kind of
    singleton: an app started by an earlier test file on the same xdist
    worker would otherwise add Serve* keys to every later metrics line."""
    yield
    try:
        from banjax_tpu.challenge.stats import get_stats
        from banjax_tpu.httpapi import serve_stats

        get_stats().reset()
        serve_stats.get_stats().reset()
    except Exception:  # noqa: BLE001 — isolation must never fail a test
        pass


@pytest.fixture()
def app_factory(tmp_path, monkeypatch):
    """Shared standalone-server bootstrap (banjax_base_test.go:32-81
    setUp): copy a fixture config into a temp cwd, run the real app there,
    tear it down after. Used by the integration tier AND the perf tier's
    HTTP benchmark mirrors — one copy, no drift."""
    from banjax_tpu.cli import BanjaxApp

    apps = []
    monkeypatch.chdir(tmp_path)

    def start(fixture_name: str) -> "BanjaxApp":
        config_path = tmp_path / "banjax-config.yaml"
        shutil.copy(_FIXTURES / fixture_name, config_path)
        app = BanjaxApp(str(config_path), standalone_testing=True, debug=False)
        app.start_background()
        apps.append(app)
        return app

    yield start
    for app in apps:
        app.stop_background()
