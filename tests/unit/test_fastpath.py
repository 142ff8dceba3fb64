"""AuthFastPath unit coverage: gates, memos, fail-open plumbing.

Byte-identity against the chain is proven end to end by the integration
differential (tests/integration/test_fastpath_differential.py); this
file pins the pieces it drives through: the eligibility gates and miss reasons, the per-
generation memo caches (session validation, QueryUnescape, global-list
probes) and their bounds/invalidation, and the fail-open exits.
"""

import time
import types

import pytest

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.crypto.session import new_session_cookie
from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.model import Decision
from banjax_tpu.decisions.protected_paths import PasswordProtectedPaths
from banjax_tpu.decisions.rate_limit import FailedChallengeRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.httpapi import fastpath as fp_mod
from banjax_tpu.httpapi.fastpath import AuthFastPath, _Gen
from banjax_tpu.httpapi.serve_stats import get_stats
from banjax_tpu.native.decisiontable import PyDecisionTable
from banjax_tpu.scenarios.runtime import RecordingBanner
from banjax_tpu.utils import go_query_escape

SECRET = "unit-secret"

BASE_YAML = f"""
config_version: t
session_cookie_hmac_secret: {SECRET}
session_cookie_ttl_seconds: 3600
disable_kafka: true
"""


class Holder:
    def __init__(self, cfg):
        self.cfg = cfg

    def get(self):
        return self.cfg


class Req:
    method = "GET"
    keep_alive = True

    def __init__(self, ip, host="eligible.example.net", cookie=None, ua="mozilla"):
        self.headers = {
            "x-client-ip": ip,
            "x-requested-host": host,
            "x-requested-path": "/",
            "x-client-user-agent": ua,
        }
        if cookie:
            self.headers["cookie"] = cookie

    def header(self, name):
        return self.headers.get(name, "")


def build(yaml_extra=""):
    cfg = config_from_yaml_text(BASE_YAML + yaml_extra)
    lists = DynamicDecisionLists(start_sweeper=False)
    table = PyDecisionTable(capacity=64)
    lists.set_mirror(table)
    deps = types.SimpleNamespace(
        config_holder=Holder(cfg),
        static_lists=StaticDecisionLists(cfg),
        dynamic_lists=lists,
        protected_paths=PasswordProtectedPaths(cfg),
        failed_challenge_states=FailedChallengeRateLimitStates(),
        banner=RecordingBanner(),
        challenge_verifier=None,
        decision_table=table,
    )
    return AuthFastPath(deps), lists, table


@pytest.fixture(autouse=True)
def _clean_stats():
    get_stats().reset()
    yield
    get_stats().reset()


def _cookie(ip, ttl=3600):
    return go_query_escape(new_session_cookie(SECRET, ttl, ip))


def test_no_table_and_disabled_return_none():
    fp, _, _ = build()
    fp.deps.decision_table = None
    assert fp.try_serve(Req("1.2.3.4")) is None

    fp, lists, _ = build("serve_fastpath_enabled: false\n")
    lists.update("1.2.3.4", time.time() + 60, Decision.ALLOW, False, "d")
    assert fp.try_serve(Req("1.2.3.4")) is None


def test_allow_hit_mints_and_echoes():
    fp, lists, _ = build()
    lists.update("1.2.3.4", time.time() + 60, Decision.ALLOW, False, "d")

    raw, status = fp.try_serve(Req("1.2.3.4"))
    assert status == 200
    assert raw.startswith(b"HTTP/1.1 200 OK\r\n")
    assert b"X-Banjax-Decision: ExpiringAccessGranted\r\n" in raw
    assert b"X-Accel-Redirect: @access_granted\r\n" in raw
    assert b"X-Deflect-Session-New: true\r\n" in raw
    assert b"Set-Cookie: deflect_session=" in raw
    assert raw.endswith(b"access granted\n")

    cookie = _cookie("1.2.3.4")
    raw, status = fp.try_serve(
        Req("1.2.3.4", cookie=f"deflect_session={cookie}")
    )
    assert status == 200
    assert b"X-Deflect-Session-New: false\r\n" in raw
    assert b"Set-Cookie" not in raw
    assert get_stats().prom_snapshot()["hits"]["allow"] == 2


def test_block_hit_and_expired_miss():
    fp, lists, _ = build()
    lists.update("5.6.7.8", time.time() + 60, Decision.NGINX_BLOCK, False, "d")
    raw, status = fp.try_serve(Req("5.6.7.8"))
    assert status == 403
    assert b"X-Banjax-Decision: ExpiringBlock\r\n" in raw
    assert b"X-Accel-Redirect: @access_denied\r\n" in raw
    assert raw.endswith(b"access denied\n")

    # past-expiry entry: a MISS (the chain performs the lazy delete)
    lists.update("9.9.9.9", time.time() + 60, Decision.ALLOW, False, "d")
    fp.deps.decision_table.put("9.9.9.9", int(Decision.ALLOW),
                               time.time() - 1)
    assert fp.try_serve(Req("9.9.9.9")) is None
    assert get_stats().prom_snapshot()["misses"]["expired"] == 1


def test_eligibility_miss_reasons():
    fp, lists, _ = build(
        "password_protected_paths:\n  pw.example.net: [admin]\n"
        "password_protected_path_exceptions:\n  pw.example.net: []\n"
        "per_site_decision_lists:\n  site.example.net:\n    allow: [44.44.44.44]\n"
    )
    now = time.time()
    for ip in ("1.0.0.1", "1.0.0.2", "1.0.0.3", "1.0.0.4"):
        lists.update(ip, now + 60, Decision.ALLOW, False, "d")

    assert fp.try_serve(Req("1.0.0.1", host="pw.example.net")) is None
    assert fp.try_serve(Req("1.0.0.2", host="site.example.net")) is None
    assert fp.try_serve(
        Req("1.0.0.3", cookie="deflect_password3=whatever")
    ) is None
    fp.deps.decision_table.session_add(1)
    assert fp.try_serve(
        Req("1.0.0.4", cookie=f"deflect_session={_cookie('1.0.0.4')}")
    ) is None
    misses = get_stats().prom_snapshot()["misses"]
    assert misses["ineligible"] == 2
    assert misses["password"] == 1
    assert misses["session_guard"] == 1


def test_session_validation_memo_hits_until_expiry(monkeypatch):
    fp, lists, _ = build()
    lists.update("1.2.3.4", time.time() + 600, Decision.ALLOW, False, "d")
    cookie = _cookie("1.2.3.4")
    req = Req("1.2.3.4", cookie=f"deflect_session={cookie}")

    calls = []
    real = fp_mod.validate_session_cookie

    def counting(*a, **k):
        calls.append(a)
        return real(*a, **k)

    monkeypatch.setattr(fp_mod, "validate_session_cookie", counting)
    first, _ = fp.try_serve(req)
    second, _ = fp.try_serve(req)
    assert first == second
    assert len(calls) == 1  # second request rode the memo

    # the memo honors the expiry embedded in the cookie bytes: push the
    # cached expiry into the past and the HMAC runs again
    gen = fp._gen
    (key,) = list(gen.session_cache)
    gen.session_cache[key] = time.time() - 1
    third, _ = fp.try_serve(req)
    assert third == first
    assert len(calls) == 2


def test_unescape_memo_covers_reject_and_bound(monkeypatch):
    fp, lists, _ = build()
    lists.update("1.2.3.4", time.time() + 600, Decision.ALLOW, False, "d")

    # a malformed escape is memoized as a reject (cookie skipped) and
    # the request still serves — twice, the second off the cache
    bad = Req("1.2.3.4", cookie="deflect_session=bad%zz")
    raw1, _ = fp.try_serve(bad)
    assert b"X-Deflect-Session-New: true\r\n" in raw1
    gen = fp._gen
    assert gen.unescape_cache.get("bad%zz", "sentinel") is None
    raw2, _ = fp.try_serve(bad)
    assert b"X-Deflect-Session-New: true\r\n" in raw2

    # the bound clears rather than growing without limit
    monkeypatch.setattr(_Gen, "CACHE_MAX", 2)
    for i in range(6):
        fp.try_serve(Req("1.2.3.4", cookie=f"deflect_session=v%2B{i}"))
    assert len(gen.unescape_cache) <= 2


def test_global_list_memo_and_miss(monkeypatch):
    fp, lists, _ = build(
        "global_decision_lists:\n  nginx_block: [70.70.70.70]\n"
    )
    now = time.time()
    lists.update("70.70.70.70", now + 60, Decision.ALLOW, False, "d")
    lists.update("1.2.3.4", now + 60, Decision.ALLOW, False, "d")

    # globally-listed IP: the chain owns it, memoized either way
    assert fp.try_serve(Req("70.70.70.70")) is None
    assert fp.try_serve(Req("70.70.70.70")) is None
    assert get_stats().prom_snapshot()["misses"]["global_list"] == 2
    gen = fp._gen
    assert gen.global_ip_cache["70.70.70.70"] is True
    assert gen.global_ip_cache.get("1.2.3.4") is None

    calls = []
    real = fp.deps.static_lists.check_global

    def counting(ip):
        calls.append(ip)
        return real(ip)

    monkeypatch.setattr(fp.deps.static_lists, "check_global", counting)
    raw, status = fp.try_serve(Req("1.2.3.4"))
    assert status == 200
    assert calls == ["1.2.3.4"]
    fp.try_serve(Req("1.2.3.4"))
    assert calls == ["1.2.3.4"]  # second probe rode the memo


def test_generation_swap_rebuilds_memos():
    fp, lists, _ = build()
    lists.update("1.2.3.4", time.time() + 600, Decision.ALLOW, False, "d")
    fp.try_serve(Req("1.2.3.4", cookie=f"deflect_session={_cookie('1.2.3.4')}"))
    old_gen = fp._gen
    assert old_gen.session_cache

    # hot reload swaps the config object: fresh generation, empty memos
    fp.deps.config_holder.cfg = config_from_yaml_text(BASE_YAML)
    raw, status = fp.try_serve(Req("1.2.3.4"))
    assert status == 200
    assert fp._gen is not old_gen
    assert fp._gen.session_cache == {}


def test_unknown_decision_byte_falls_open():
    fp, lists, table = build()
    table.put("1.2.3.4", 99, time.time() + 60)
    assert fp.try_serve(Req("1.2.3.4")) is None
    assert get_stats().prom_snapshot()["misses"]["table"] == 1


def test_lookup_exception_is_a_counted_fault(monkeypatch):
    fp, lists, _ = build()
    lists.update("1.2.3.4", time.time() + 60, Decision.ALLOW, False, "d")
    monkeypatch.setattr(
        AuthFastPath, "_lookup",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")),
    )
    assert fp.try_serve(Req("1.2.3.4")) is None
    assert get_stats().prom_snapshot()["faults_total"] == 1
