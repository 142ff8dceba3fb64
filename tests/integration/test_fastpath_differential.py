"""Fast-path differential: decision-table hits vs the unchanged chain.

Referenced by httpapi/fastpath.py as its byte-identity proof.  The
strongest comparison runs on ONE app: the `serve.fastpath.lookup`
failpoint forces a request through the full decision chain, disarming
it lets the compiled fast path serve the identical request — status
line, header order, X-Accel-Redirect, cookies and body must match to
the byte (fresh session/challenge randomness normalized on both sides).
A second suite pins expiry-boundary agreement on BOTH HTTP layouts
(`http_fast_path` true/false), and the table-full case proves a refused
IP serves identically through the chain.
"""

import re
import socket
import time
from pathlib import Path

import pytest

from banjax_tpu.crypto.session import new_session_cookie
from banjax_tpu.decisions.model import Decision
from banjax_tpu.httpapi.serve_stats import get_stats
from banjax_tpu.resilience import failpoints
from banjax_tpu.utils import go_query_escape

_FIXTURES = Path(__file__).resolve().parent.parent / "fixtures"
HOST = "eligible.example.net"  # in no per-site/password list: fast-path eligible
SECRET = "session_secret"  # fixture session_cookie_hmac_secret


@pytest.fixture(autouse=True)
def _clean():
    failpoints.disarm()
    get_stats().reset()
    yield
    failpoints.disarm()
    get_stats().reset()


def _fastserve_app(app_factory, tmp_path, extra=""):
    cfg = tmp_path / "cfg-fpdiff.yaml"
    cfg.write_text(
        (_FIXTURES / "banjax-config-test.yaml").read_text()
        + "\nhttp_fast_path: true\nserve_fastpath_enabled: true\n"
        + extra
    )
    app = app_factory(str(cfg))
    time.sleep(0.5)
    return app


def _raw_request(ip, path="/", host=HOST, cookie=None, method="GET"):
    head = (
        f"{method} /auth_request?path={path} HTTP/1.1\r\n"
        f"Host: {host}\r\n"
        f"X-Client-IP: {ip}\r\n"
        "X-Client-User-Agent: mozilla\r\n"
    )
    if cookie:
        head += f"Cookie: {cookie}\r\n"
    head += "Connection: close\r\n\r\n"

    s = socket.create_connection(("127.0.0.1", 8081), timeout=5)
    try:
        s.sendall(head.encode())
        out = b""
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            out += chunk
    finally:
        s.close()
    return out


# fresh randomness on both sides of the diff: minted session values
# (echoed into a header and a Set-Cookie) and challenge payloads
_MASKS = (
    # to the end of the line: a '+' of the cookie's base64 is echoed as a
    # space (the reference's second QueryUnescape, decision_chain.py)
    (re.compile(rb"(X-Deflect-Session: )([^\r\n]+)"), rb"\1MASKED"),
    (re.compile(rb"(deflect_session=)([^;\r\n]+)"), rb"\1MASKED"),
    (re.compile(rb"(deflect_challenge3=)([^;\r\n]+)"), rb"\1MASKED"),
    (re.compile(rb"[A-Za-z0-9+/=]{40,}"), rb"MASKEDB64"),
)


def _norm(raw: bytes) -> bytes:
    for pat, repl in _MASKS:
        raw = pat.sub(repl, raw)
    return raw


def _diff_one(desc, **kw):
    """The same request through the chain (failpoint armed) and the fast
    path (disarmed) — normalized bytes must be identical."""
    stats = get_stats()
    failpoints.arm("serve.fastpath.lookup")
    try:
        faults_before = stats.prom_snapshot()["faults_total"]
        chain = _raw_request(**kw)
        assert stats.prom_snapshot()["faults_total"] == faults_before + 1, desc
    finally:
        failpoints.disarm("serve.fastpath.lookup")
    hits_before = stats.prom_snapshot()["hits_total"]
    fast = _raw_request(**kw)
    assert stats.prom_snapshot()["hits_total"] == hits_before + 1, desc
    assert _norm(fast) == _norm(chain), (
        desc, _norm(fast)[:400], _norm(chain)[:400]
    )
    return fast


def _session(ip, ttl=3600):
    return "deflect_session=" + go_query_escape(
        new_session_cookie(SECRET, ttl, ip)
    )


def test_fastpath_hits_are_byte_identical_to_chain(app_factory, tmp_path):
    app = _fastserve_app(app_factory, tmp_path)
    now = time.time()
    lists = app.dynamic_lists
    lists.update("43.0.0.1", now + 600, Decision.ALLOW, False, "d")
    lists.update("43.0.0.2", now + 600, Decision.NGINX_BLOCK, False, "d")
    lists.update("43.0.0.3", now + 600, Decision.IPTABLES_BLOCK, False, "d")
    lists.update("43.0.0.4", now + 600, Decision.CHALLENGE, False, "d")

    raw = _diff_one("allow, cookie echo", ip="43.0.0.1",
                    cookie=_session("43.0.0.1"))
    assert raw.startswith(b"HTTP/1.1 200")
    assert b"X-Deflect-Session-New: false\r\n" in raw

    raw = _diff_one("allow, mint", ip="43.0.0.1")
    assert b"X-Deflect-Session-New: true\r\n" in raw
    assert b"Set-Cookie: deflect_session=" in raw

    raw = _diff_one("allow, foreign-ip cookie re-mints", ip="43.0.0.1",
                    cookie=_session("99.99.99.99"))
    assert b"X-Deflect-Session-New: true\r\n" in raw

    raw = _diff_one("nginx block", ip="43.0.0.2",
                    cookie=_session("43.0.0.2"))
    assert raw.startswith(b"HTTP/1.1 403")
    assert b"X-Accel-Redirect: @access_denied\r\n" in raw

    raw = _diff_one("iptables block", ip="43.0.0.3")
    assert raw.startswith(b"HTTP/1.1 403")

    raw = _diff_one("challenge", ip="43.0.0.4")
    assert b"deflect_challenge3=" in raw

    raw = _diff_one("HEAD allow", ip="43.0.0.1", method="HEAD",
                    cookie=_session("43.0.0.1"))
    head, _, tail = raw.partition(b"\r\n\r\n")
    assert tail == b"", "HEAD leaked body bytes"

    app.stop_background()


def test_misses_defer_to_chain_identically(app_factory, tmp_path):
    """Ineligible/miss requests return None from the fast path on both
    arms — the diff still holds (trivially through the chain) and the
    miss reasons land in the counters."""
    app = _fastserve_app(app_factory, tmp_path)
    now = time.time()
    app.dynamic_lists.update("43.1.0.1", now + 600, Decision.ALLOW, False, "d")

    stats = get_stats()
    # password-protected host: chain territory (fixture lists localhost)
    a = _raw_request(ip="43.1.0.1", host="localhost")
    b = _raw_request(ip="43.1.0.1", host="localhost")
    assert _norm(a) == _norm(b)
    # unknown IP: table miss
    _raw_request(ip="43.1.0.99")
    misses = stats.prom_snapshot()["misses"]
    assert misses.get("ineligible", 0) >= 2
    assert misses.get("table", 0) >= 1
    app.stop_background()


def test_table_full_refusal_serves_through_chain(app_factory, tmp_path):
    app = _fastserve_app(app_factory, tmp_path,
                         extra="serve_decision_table_capacity: 2\n")
    table = app.decision_table
    assert table is not None and table.capacity == 2
    now = time.time()
    ips = [f"43.2.0.{i}" for i in range(1, 6)]
    for ip in ips:
        app.dynamic_lists.update(ip, now + 600, Decision.ALLOW, False, "d")
    assert len(table) == 2
    assert table.dropped >= 3  # refusals counted, never evictions

    # every IP — mirrored or refused — serves the same allow contract,
    # and a refused IP is still byte-identical chain vs fast path (both
    # arms ride the chain; the diff must hold trivially)
    for ip in ips:
        raw = _raw_request(ip=ip, cookie=_session(ip))
        assert raw.startswith(b"HTTP/1.1 200"), ip
        assert b"X-Banjax-Decision: ExpiringAccessGranted\r\n" in raw, ip
    refused = next(ip for ip in ips if table.get(ip) is None)
    _diff_one_refused = _raw_request(ip=refused, cookie=_session(refused))
    armed = None
    failpoints.arm("serve.fastpath.lookup")
    try:
        armed = _raw_request(ip=refused, cookie=_session(refused))
    finally:
        failpoints.disarm("serve.fastpath.lookup")
    assert _norm(_diff_one_refused) == _norm(armed)
    app.stop_background()


@pytest.mark.parametrize("fast_path", [True, False],
                         ids=["fastserve", "aiohttp"])
def test_expiry_boundary_agreement_both_layouts(app_factory, tmp_path,
                                                fast_path):
    """An entry crossing its expiry must flip exactly once, from the
    granted contract to the same response an unknown IP gets — on the
    fastserve layout (fast path + chain lazy-delete) AND the aiohttp
    layout (chain only)."""
    cfg = tmp_path / f"cfg-exp-{fast_path}.yaml"
    cfg.write_text(
        (_FIXTURES / "banjax-config-test.yaml").read_text()
        + f"\nhttp_fast_path: {str(fast_path).lower()}\n"
    )
    app = app_factory(str(cfg))
    time.sleep(0.5)

    import requests as rq

    def shape(ip):
        r = rq.get(
            "http://localhost:8081/auth_request", params={"path": "/"},
            headers={"X-Client-IP": ip, "Host": HOST}, timeout=5,
        )
        return (r.status_code, r.headers.get("X-Banjax-Decision"),
                r.headers.get("X-Accel-Redirect"))

    unknown = shape("43.3.0.99")  # what "no decision" looks like here

    expiry = time.time() + 1.2
    app.dynamic_lists.update("43.3.0.1", expiry, Decision.ALLOW, False, "d")
    seen = []
    while time.time() < expiry + 0.6:
        seen.append(shape("43.3.0.1"))
        time.sleep(0.1)

    granted = (200, "ExpiringAccessGranted", "@access_granted")
    assert seen[0] == granted
    assert seen[-1] == unknown
    flips = sum(1 for a, b in zip(seen, seen[1:]) if a != b)
    assert flips == 1, seen
    if fast_path:
        # the expired entry was seen by the fast path at least once
        # before the chain lazily deleted it
        snap = get_stats().prom_snapshot()
        assert snap["hits"].get("allow", 0) >= 1
    app.stop_background()
