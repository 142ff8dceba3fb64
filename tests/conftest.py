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

import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import fcntl  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import tempfile  # noqa: E402
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


# Ceiling on one test, fixture set-up and tear-down included.  The slowest
# test of a whole tier-1 run (6 workers) took 37-52 s on 8 cores and 91 s
# on 3 (CHANGES.md, PR 26); the driver's machine is about 2.5x slower
# than the 8-core box.
_TEST_CEILING_S = 300


@contextlib.contextmanager
def _time_limit(seconds):
    """Fail the code inside with its own traceback once `seconds` have
    passed, and write every thread's stack to stderr at that moment.
    SIGALRM reaches the main thread at its next bytecode, so a hang
    inside one native call (an XLA compile) shows in the dumped stacks
    first and fails when the call returns."""

    def on_alarm(signum, frame):
        pytest.fail(f"exceeded the {seconds} s ceiling on one test")

    previous = signal.signal(signal.SIGALRM, on_alarm)
    faulthandler.dump_traceback_later(seconds, file=sys.__stderr__)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, previous)


_WORKERS_LOCK_PATH = os.path.join(
    tempfile.gettempdir(), "banjax-tpu-tests.lock"
)


@contextlib.contextmanager
def _workers_lock():
    """One holder at a time across the xdist workers of a run (and across
    runs that share a temp directory)."""
    with open(_WORKERS_LOCK_PATH, "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        try:
            yield
        finally:
            # explicit: a forked child that inherited the descriptor must
            # not keep the lock held after the test has let go
            fcntl.flock(f, fcntl.LOCK_UN)


def _starts_the_app(request) -> bool:
    """The app listens on the constant 127.0.0.1:8081
    (httpapi/server.py LISTEN_PORT, kept equal to the reference): a test
    that starts it — through app_factory, or with a BanjaxApp /
    run_http_server its module imported — must be the only one doing so
    on this machine."""
    return "app_factory" in request.fixturenames or any(
        hasattr(request.module, name)
        for name in ("BanjaxApp", "run_http_server")
    )


@pytest.fixture(scope="session", autouse=True)
def _native_libs_built_by_one_worker():
    """The native libraries build themselves on first use into one cache
    directory under the system temp directory, straight to their final
    path: a second worker that finds the half-written file fails to load
    it and silently takes the Python path for the rest of its life.
    Build them under the workers' lock before any test runs."""
    from banjax_tpu import native
    from banjax_tpu.native import decisiontable, shm, shmring, slotmgr

    with _workers_lock():
        for module in (native, decisiontable, shm, shmring):
            module.available()
        slotmgr._load()
    yield


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


@contextlib.contextmanager
def _no_leaks():
    """Fail any test that leaves a non-daemon thread or a live child
    process behind — a leaked worker keeps ports/shm segments alive and
    poisons every later test in the session."""
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
def _around_each_test(request):
    """Outermost first: the workers' lock if the test starts the app, held
    until the leak check has seen the app's worker processes gone; the
    leak check (`@pytest.mark.allow_leaks` opts out), which runs AFTER the
    teardown of the test's own fixtures (e.g. app_factory stopping the
    app); the ceiling, which therefore does not count the wait for the
    lock."""
    with contextlib.ExitStack() as stack:
        if _starts_the_app(request):
            stack.enter_context(_workers_lock())
        if not request.node.get_closest_marker("allow_leaks"):
            stack.enter_context(_no_leaks())
        stack.enter_context(_time_limit(_TEST_CEILING_S))
        yield


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
