"""The reference's OWN headline benchmark harnesses, mirrored.

/root/reference/banjax_performance_test.go:18-31 (BenchmarkAuthRequest) and
:33-67 (BenchmarkProtectedPaths) drive the real HTTP server: b.N GETs of
/auth_request with a random client IP, and a 12-path-variant protected-path
classification loop. The reference records no numbers (BASELINE.md) — CI
runs the harness as a smoke; here each prints a requests/sec JSON line and
asserts a conservative floor so a server-path perf regression fails CI.
"""

import asyncio
import json
import os
import random
import time

import pytest

BASE = "http://localhost:8081"

# serial requests/sec floors on a 1-core CI box driving via http.client
# keepalive (~3-4.5k measured; the reference's Go harness records nothing
# to compare against, so the floors only guard OUR regressions — set at
# ~1/4 of measured for full-suite/CI-box pressure)
AUTH_FLOOR_RPS = 800
PROTECTED_FLOOR_RPS = 700
# server-capacity floor: concurrent raw-socket keepalive client (~30
# us/req of client cost) — the number comparable to driving the
# reference's Go server with its Go client. fastserve measures 5.6-7.6k
# on the 1-core build box (client sharing the core); 2k still fails on
# any fast-path regression while leaving ~3x for CI noise
CAPACITY_FLOOR_RPS = 2_000


def _best_of_three(measure) -> float:
    """The floors guard the serve path, not the neighbours: the other
    xdist workers compile XLA programs on the same cores, so one
    measurement can read low for reasons outside the server (the port is
    this test's alone while it runs: conftest's workers' lock).  A real
    4x regression fails all three."""
    return max(measure() for _ in range(3))


async def _capacity_worker(n: int, results: list, rand_ip) -> None:
    reader, writer = await asyncio.open_connection("127.0.0.1", 8081)
    for _ in range(n):
        writer.write(
            (
                f"GET /auth_request HTTP/1.1\r\nHost: localhost\r\n"
                f"X-Client-IP: {rand_ip()}\r\nConnection: keep-alive\r\n\r\n"
            ).encode()
        )
        await writer.drain()
        hdr = await reader.readuntil(b"\r\n\r\n")
        clen = 0
        for line in hdr.split(b"\r\n"):
            if line.lower().startswith(b"content-length:"):
                clen = int(line.split(b":")[1])
        if clen:
            await reader.readexactly(clen)
        results[0] += 1
    writer.close()


def measure_capacity(n_per_conn: int = 400, conc: int = 16,
                     seed: int = 11) -> float:
    """Sustained /auth_request throughput with a cheap concurrent client
    (the serial http.client mirrors above measure latency, not
    capacity)."""
    rng = random.Random(seed)

    def rand_ip():
        return (
            f"{rng.randint(1, 251)}.{rng.randint(0, 255)}"
            f".{rng.randint(0, 255)}.{rng.randint(1, 254)}"
        )

    async def run() -> float:
        results = [0]
        t0 = time.perf_counter()
        await asyncio.gather(
            *[_capacity_worker(n_per_conn, results, rand_ip) for _ in range(conc)]
        )
        return results[0] / (time.perf_counter() - t0)

    return asyncio.run(run())


@pytest.fixture()
def app(app_factory):
    return app_factory("banjax-config-test.yaml")


def _rand_ip(rng):
    return f"{rng.randint(1, 251)}.{rng.randint(0, 255)}.{rng.randint(0, 255)}.{rng.randint(1, 254)}"


def _serial_get(conn, path, ip):
    """One request over a kept-alive http.client connection — the closest
    Python analogue of the reference harness's Go http.Client (~50 us of
    client cost, vs python-requests' ~1 ms which hid the server behind
    the client on a shared core)."""
    conn.request("GET", path, headers={"X-Client-IP": ip})
    r = conn.getresponse()
    r.read()
    return r.status


def test_benchmark_auth_request(app):
    """BenchmarkAuthRequest (banjax_performance_test.go:18-31): sustained
    serial GET /auth_request with a random X-Client-IP per request."""
    import http.client

    rng = random.Random(9)
    conn = http.client.HTTPConnection("localhost", 8081, timeout=5)
    for _ in range(20):  # warm
        _serial_get(conn, "/auth_request", _rand_ip(rng))
    n = 600

    def measure():
        t0 = time.perf_counter()
        for _ in range(n):
            status = _serial_get(conn, "/auth_request", _rand_ip(rng))
            assert status in (200, 429, 403)
        return n / (time.perf_counter() - t0)

    rps = _best_of_three(measure)
    conn.close()
    print(json.dumps({"benchmark": "auth_request", "rps": round(rps, 1)}))
    assert rps >= AUTH_FLOOR_RPS


def test_benchmark_protected_paths(app):
    """BenchmarkProtectedPaths (banjax_performance_test.go:33-67): the 12
    protected/exception path variants, classified per iteration."""
    rng = random.Random(10)
    paths = [
        "wp-admin", "/wp-admin", "/wp-admin//", "wp-admin/admin.php",
        "wp-admin/admin.php#test", "wp-admin/admin.php?a=1&b=2",
        "wp-admin/admin-ajax.php", "/wp-admin/admin-ajax.php",
        "/wp-admin/admin-ajax.php?a=1", "/wp-admin/admin-ajax.php?a=1&b=2",
        "/wp-admin/admin-ajax.php#test", "wp-admin/admin-ajax.php/",
    ]
    import http.client
    from urllib.parse import quote

    conn = http.client.HTTPConnection("localhost", 8081, timeout=5)
    targets = [f"/auth_request?path={quote(p, safe='')}" for p in paths]
    for t in targets:  # warm
        _serial_get(conn, t, _rand_ip(rng))
    iters = 40

    def measure():
        t0 = time.perf_counter()
        for _ in range(iters):
            for t in targets:
                status = _serial_get(conn, t, _rand_ip(rng))
                assert status in (200, 401, 429)
        return iters * len(paths) / (time.perf_counter() - t0)

    rps = _best_of_three(measure)
    conn.close()
    print(json.dumps({"benchmark": "protected_paths", "rps": round(rps, 1)}))
    assert rps >= PROTECTED_FLOOR_RPS


def test_benchmark_auth_request_capacity(app):
    """Server capacity (single process): the concurrent keepalive client
    measures the handler path itself, not the python-requests client."""
    measure_capacity(n_per_conn=40, conc=8)  # warm
    rps = _best_of_three(measure_capacity)
    print(json.dumps({
        "benchmark": "auth_request_capacity", "rps": round(rps, 1),
        "http_workers": 0, "cpu_count": os.cpu_count(),
    }))
    assert rps >= CAPACITY_FLOOR_RPS


@pytest.mark.skipif((os.cpu_count() or 1) < 2,
                    reason="SO_REUSEPORT workers need >1 core to scale")
def test_benchmark_auth_request_capacity_workers(app_factory, tmp_path):
    """Server capacity in multi-worker mode (httpapi/workers.py):
    http_workers = cpu count, one SO_REUSEPORT process per core."""
    from pathlib import Path

    n_workers = os.cpu_count()
    fixtures = Path(__file__).resolve().parent.parent / "fixtures"
    custom = tmp_path / "banjax-config-workers.yaml"
    custom.write_text(
        (fixtures / "banjax-config-test.yaml").read_text()
        + f"\nhttp_workers: {n_workers}\n"
    )
    # app_factory joins against the fixtures dir; an absolute path wins
    app_factory(str(custom))
    time.sleep(2.0)  # let workers bind
    measure_capacity(n_per_conn=40, conc=8)  # warm
    rps = _best_of_three(lambda: measure_capacity(conc=32))
    print(json.dumps({
        "benchmark": "auth_request_capacity_workers", "rps": round(rps, 1),
        "http_workers": n_workers, "cpu_count": os.cpu_count(),
    }))
    assert rps >= CAPACITY_FLOOR_RPS
