"""A chunk's ban records through the banner as one batch (PR 42).

`BannerInterface.apply_regex_bans` takes the exceeded events of one
applied chunk; its own body is the per-record path (the three
single-record calls, record by record), and `Banner` overrides it with one
pass a step.  Called unbound on a `Banner`, the base body is the oracle:
what the per-record path writes, inserts and records for the same records.

  (a) the files a batch writes are byte for byte the per-record path's;
  (b) written and flushed on return, one write a file a batch;
  (c) one record whose effect raises loses that line, the others are
      applied once — through the replay of the device-window path and
      through the host-window path's batch of one;
  (d) an address twice in one batch: lists and mirror as one by one;
  (e) the provenance ring, its counters and the ambient trace id;
  (f) a banner without the override (MockBanner) through the base body;
  and the routes the replay takes to a chunk's few rows (`lines_at`;
  `NativeWork`'s in `test_workset.py`).
"""

import io
import json
import time

import numpy as np
import pytest

from banjax_tpu.config.schema import Config, config_from_yaml_text
from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.model import Decision
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.effectors.banner import Banner, BannerInterface, RegexBan
from banjax_tpu.fabric.replication import ReplicatingBanner
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.matcher.workset import CompositeWork, ListWork
from banjax_tpu.native import decisiontable
from banjax_tpu.obs import provenance, trace
from tests.mock_banner import MockBanner

T0 = 1_790_000_000.25  # a log time; the records' own, not the clock's
QUIET = "quiet.example.org"  # a host under disable_logging


def _rest(host="example.com", path="/x", ua="Mozilla/5.0 (X11)"):
    return f"GET {host} GET {path} HTTP/1.1 {ua}"


def _ban(k, *, ip=None, host="example.com", decision=Decision.NGINX_BLOCK,
         rule="r", t=T0, rest=None):
    return RegexBan(
        ip or f"10.0.{k // 250}.{k % 250}", host, decision, f"{rule}{k % 3}",
        k % 7, 3 + k % 5, t, _rest(host) if rest is None else rest,
    )


RECORD_SETS = {
    "one_record": [_ban(0)],
    "both_targets": [
        _ban(0), _ban(1, host=QUIET), _ban(2), _ban(3, host=QUIET),
        _ban(4, host=QUIET), _ban(5),
    ],
    "temp_only": [_ban(k, host=QUIET) for k in range(3)],
    "fewer_than_six_words": [
        _ban(0), _ban(1, rest="GET example.com GET /x HTTP/1.1"), _ban(2),
        _ban(3, rest=""),
    ],
    "a_bar_after_the_ua": [
        _ban(0, rest=_rest(ua="curl/8.1 | 403")),
        _ban(1, rest=_rest(ua="a|b|c")),
        _ban(2, rest=_rest(ua="| 200")),
    ],
    "non_ascii_in_path_and_ua": [
        _ban(0, rest=_rest(path="/café/中文?q=ü",
                           ua="Мозилла/5 ☃")),
        _ban(1, rest=_rest(path='/q"uote\\back', ua="tab\there")),
        _ban(2, rest=_rest(path="/\ud800lone")),
    ],
    "records_that_share_a_second": [
        _ban(k, t=T0 + 0.1 * k) for k in range(6)
    ],
    "records_of_other_seconds": [
        _ban(k, t=T0 + 1.5 * k) for k in range(6)
    ] + [_ban(9, t=T0 - 86_400.75), _ban(10, t=0.0)],
    "every_decision": [
        _ban(k, decision=d) for k, d in enumerate(
            (Decision.CHALLENGE, Decision.NGINX_BLOCK,
             Decision.IPTABLES_BLOCK, Decision.ALLOW))
    ],
    "a_chunk_of_the_flood": [
        _ban(k, host=QUIET if k % 16 == 3 else f"site{k % 16}.org",
             t=T0 + k * 4e-4,
             decision=Decision.CHALLENGE if k % 4 else Decision.NGINX_BLOCK)
        for k in range(332)
    ],
}


def _config():
    cfg = Config()
    cfg.expiring_decision_ttl_seconds = 300
    cfg.iptables_ban_seconds = 10
    cfg.standalone_testing = True
    cfg.disable_logging = {QUIET: True}
    return cfg


class _CountingFile(io.StringIO):
    def __init__(self):
        super().__init__()
        self.writes = self.flushes = 0

    def write(self, s):
        self.writes += 1
        return super().write(s)

    def flush(self):
        self.flushes += 1
        return super().flush()


def _banner(mirror=None):
    lists = DynamicDecisionLists(start_sweeper=False)
    if mirror is not None:
        lists.set_mirror(mirror)
    return Banner(lists, _CountingFile(), _CountingFile(), ipset_instance=None)


def _one_by_one(banner, config, records):
    """The per-record path on a real Banner: the base body."""
    return BannerInterface.apply_regex_bans(banner, config, records)


@pytest.fixture
def ledger():
    fresh = provenance.configure(enabled=True, ring_size=1024)
    yield fresh
    provenance.configure(enabled=True)
    trace.configure(enabled=False)


@pytest.fixture
def frozen_clock(monkeypatch):
    """Both paths stamp `expires` from the clock: hold it, so that lists
    and mirror of the two compare equal to the bit."""
    monkeypatch.setattr(time, "time", lambda: 1_790_000_100.5)


# ---- (a) byte for byte ----


@pytest.mark.parametrize("name", sorted(RECORD_SETS))
def test_batch_writes_the_per_record_paths_files(name, ledger, frozen_clock):
    records, cfg = RECORD_SETS[name], _config()
    ref, got = _banner(), _banner()
    assert _one_by_one(ref, cfg, records) == []
    ref_ring = ledger.tail(1024)
    ref_counters = ledger.counters()
    ledger = provenance.configure(enabled=True, ring_size=1024)
    assert got.apply_regex_bans(cfg, records) == []

    for which in ("_ban_log", "_ban_log_temp"):
        assert (getattr(got, which).getvalue().encode("utf-8", "surrogatepass")
                == getattr(ref, which).getvalue().encode(
                    "utf-8", "surrogatepass")), which
    written = (ref._ban_log.getvalue() + ref._ban_log_temp.getvalue())
    assert got.regex_ban_records == ref.regex_ban_records \
        == written.count("\n")
    if name != "fewer_than_six_words":
        assert got.regex_ban_records == len(records)
    assert got.decision_lists._by_ip == ref.decision_lists._by_ip
    strip = lambda recs: [  # noqa: E731 — the stamps are each path's own
        {k: v for k, v in r.items() if k not in ("t_monotonic", "time_unix")}
        for r in recs]
    assert strip(ledger.tail(1024)) == strip(ref_ring)
    assert ledger.counters() == ref_counters


def test_the_log_line_is_the_reference_structs(ledger):
    """Not only equal to the per-record path: the line itself, field
    order, separators and the cut at the bar (iptables.go:164-228)."""
    got = _banner()
    got.apply_regex_bans(_config(), [RegexBan(
        "1.2.3.4", "example.com", Decision.CHALLENGE, "rule x", 4, 46,
        T0, "GET example.com GET /a/b?c=d HTTP/1.1 curl/8.1 | 403")])
    stamp = time.strftime("%Y-%m-%dT%H:%M:%S", time.localtime(T0))
    assert got._ban_log.getvalue() == (
        '{"path":"/a/b?c=d","timestring":"' + stamp + '","trigger":"rule x",'
        '"client_ua":"curl/8.1","client_ip":"1.2.3.4","rule_type":"regex",'
        '"client_request_method":"GET","http_request_scheme":"https",'
        '"client_request_host":"example.com","action":"Challenge",'
        '"number_of_fails":1,"disable_logging":0}\n')


# ---- (b) written and flushed on return, one write a file ----


@pytest.mark.parametrize("name,main,temp", [
    ("both_targets", 1, 1), ("temp_only", 0, 1), ("one_record", 1, 0),
    ("a_chunk_of_the_flood", 1, 1),
])
def test_written_and_flushed_on_return_one_write_a_file(
        name, main, temp, tmp_path, ledger):
    records, cfg = RECORD_SETS[name], _config()
    paths = [tmp_path / "ban.log", tmp_path / "ban-temp.log"]
    # a buffer larger than the batch: only a flush gets it to the file
    files = [open(p, "w", encoding="utf-8", buffering=1 << 20) for p in paths]
    try:
        banner = Banner(DynamicDecisionLists(start_sweeper=False), *files,
                        ipset_instance=None)
        banner.apply_regex_bans(cfg, records)
        # a second handle, opened after the call, reads every line
        seen = [p.read_text(encoding="utf-8") for p in paths]
    finally:
        for f in files:
            f.close()
    ref = _banner()
    _one_by_one(ref, cfg, records)
    assert seen == [ref._ban_log.getvalue(), ref._ban_log_temp.getvalue()]
    assert sum(s.count("\n") for s in seen) == len(records)
    assert banner.ban_log_writes == {"main": main, "temp": temp}
    assert banner.regex_ban_batches == 1
    # and the per-record path writes once a record
    assert sum(ref.ban_log_writes.values()) == len(records)

    counted = _banner()
    counted.apply_regex_bans(cfg, records)
    assert (counted._ban_log.writes, counted._ban_log.flushes) == (main, main)
    assert (counted._ban_log_temp.writes,
            counted._ban_log_temp.flushes) == (temp, temp)


def test_nothing_is_carried_from_one_batch_to_the_next(ledger):
    banner, cfg = _banner(), _config()
    banner.apply_regex_bans(cfg, RECORD_SETS["both_targets"][:2])
    first = banner._ban_log.getvalue(), banner._ban_log_temp.getvalue()
    assert [s.count("\n") for s in first] == [1, 1]
    banner.apply_regex_bans(cfg, [])
    assert (banner._ban_log.getvalue(),
            banner._ban_log_temp.getvalue()) == first
    assert banner.ban_log_writes == {"main": 1, "temp": 1}
    banner.apply_regex_bans(cfg, RECORD_SETS["both_targets"][2:3])
    assert banner._ban_log.getvalue().startswith(first[0])
    assert banner.ban_log_writes == {"main": 2, "temp": 1}
    assert banner.regex_ban_batches == 3


def test_single_record_calls_count_their_writes(ledger):
    banner, cfg = _banner(), _config()
    banner.log_regex_ban(cfg, T0, "1.1.1.1", "r", _rest(), Decision.CHALLENGE)
    banner.log_failed_challenge_ban(
        cfg, "1.1.1.2", "sha_inv", QUIET, "/", 6, "ua", Decision.NGINX_BLOCK,
        "GET")
    assert banner.ban_log_writes == {"main": 1, "temp": 1}
    assert banner.regex_ban_records == 1 and banner.regex_ban_batches == 0


# ---- (c) a failing effect loses one line, not the batch ----


def test_a_record_that_cannot_be_logged_is_reported_by_index(ledger):
    records = list(RECORD_SETS["both_targets"])
    records[2] = records[2]._replace(rest=None)   # no line can be built
    banner, cfg = _banner(), _config()
    failed = banner.apply_regex_bans(cfg, records)
    assert [k for k, _ in failed] == [2]
    assert isinstance(failed[0][1], AttributeError)
    recorded = [r["ip"] for r in ledger.tail(64)]
    ref = _banner()
    _one_by_one(ref, cfg, records[:2] + records[3:])
    assert banner._ban_log.getvalue() == ref._ban_log.getvalue()
    assert banner._ban_log_temp.getvalue() == ref._ban_log_temp.getvalue()
    assert banner.regex_ban_records == 5
    # its decision is inserted, as the per-record path inserts it before
    # the line fails; its provenance record is not made
    assert set(banner.decision_lists._by_ip) == {r.ip for r in records}
    assert recorded == [r.ip for k, r in enumerate(records) if k != 2]


def test_a_failed_write_fails_the_lines_of_that_file_only(ledger):
    class _Full(io.StringIO):
        def write(self, s):
            raise OSError(28, "No space left on device")

    banner = Banner(DynamicDecisionLists(start_sweeper=False),
                    io.StringIO(), _Full(), ipset_instance=None)
    records = RECORD_SETS["both_targets"]
    failed = banner.apply_regex_bans(_config(), records)
    assert [k for k, _ in failed] == [1, 3, 4]
    assert all(isinstance(e, OSError) for _, e in failed)
    assert banner._ban_log.getvalue().count("\n") == 3
    assert banner.regex_ban_records == 3
    assert banner.ban_log_writes == {"main": 1, "temp": 0}


RULES_YAML = r"""
regexes_with_rates:
  - decision: nginx_block
    rule: 'instant block'
    regex: '.*blockme.*'
    interval: 1
    hits_per_interval: 0
  - decision: challenge
    rule: 'second get'
    regex: 'GET example\.com GET .*'
    interval: 30
    hits_per_interval: 1
"""
BAD_IP = "6.6.6.6"


class _FailingBanner(Banner):
    """The real banner, with one address whose ban-log line cannot be
    built."""

    def _regex_ban_line(self, config, log_time_unix, ip, *rest):
        if ip == BAD_IP:
            raise RuntimeError("no line for this address")
        return super()._regex_ban_line(config, log_time_unix, ip, *rest)


class _FailingMock(MockBanner):
    """A banner without the override: the base body's own handling."""

    def log_regex_ban(self, config, log_time_unix, ip, rule_name,
                      log_line_rest, decision):
        if ip == BAD_IP:
            raise RuntimeError("no line for this address")
        super().log_regex_ban(config, log_time_unix, ip, rule_name,
                              log_line_rest, decision)


@pytest.mark.parametrize("device_windows", [True, False],
                         ids=["replay_of_a_chunk", "host_pass_batch_of_one"])
@pytest.mark.parametrize("kind", ["banner", "mock"])
def test_a_failing_effector_loses_one_line_not_the_batch(
        kind, device_windows, ledger):
    cfg = config_from_yaml_text(RULES_YAML)
    cfg.matcher_device_windows = device_windows
    cfg.expiring_decision_ttl_seconds = 300
    lists = DynamicDecisionLists(start_sweeper=False)
    banner = (_FailingBanner(lists, io.StringIO(), io.StringIO(),
                             ipset_instance=None)
              if kind == "banner" else _FailingMock(lists))
    m = TpuMatcher(cfg, banner, StaticDecisionLists(cfg),
                   RegexRateLimitStates())
    now = time.time()
    ips = ["5.5.5.1", BAD_IP, "5.5.5.2", "5.5.5.3", BAD_IP, "5.5.5.4"]
    lines = [f"{now - 1 + i * 1e-3:.6f} {ip} GET example.com GET "
             f"/{'blockme' if i != 3 else 'plain'} HTTP/1.1 ua -"
             for i, ip in enumerate(ips)]
    results = m.consume_lines(lines, now_unix=now)
    m.close()
    # line 1 fails on its instant block; line 4 on it and, in the batch,
    # is still one line; line 3 matched the counting rule only
    assert [r.error for r in results] \
        == [False, True, False, False, True, False]
    want = [(ip, "instant block") for ip in ips[:3] + ips[5:]
            if ip != BAD_IP]
    if kind == "banner":
        got = [(d["client_ip"], d["trigger"]) for d in map(
            json.loads, banner._ban_log.getvalue().splitlines())]
    else:
        got = banner.regex_ban_logs
    # BAD_IP's second `GET` crossed the counting rule too: that record's
    # line fails as well, and nobody else's does
    assert got == want                      # every other record, once
    assert lists.peek(BAD_IP) is not None  # inserted all the same
    assert set(lists._by_ip) == set(ips) - {"5.5.5.3"}


# ---- (d) an address twice in one batch; the mirror ----


def _mirrors():
    kinds = [decisiontable.PyDecisionTable]
    if decisiontable.available():
        kinds.append(decisiontable.ShmDecisionTable)
    return kinds


@pytest.mark.parametrize("table", _mirrors(), ids=lambda c: c.__name__)
@pytest.mark.parametrize("order", [
    (Decision.CHALLENGE, Decision.NGINX_BLOCK),
    (Decision.NGINX_BLOCK, Decision.CHALLENGE),
    (Decision.CHALLENGE, Decision.CHALLENGE),
    (Decision.IPTABLES_BLOCK, Decision.NGINX_BLOCK),
], ids=lambda o: f"{o[0].name}_then_{o[1].name}")
def test_same_address_twice_ends_as_one_by_one(
        order, table, ledger, frozen_clock):
    cfg = _config()
    records = [
        _ban(0, ip="9.9.9.9", decision=order[0], host="a.example"),
        _ban(1, ip="8.8.8.8", host=""),
        _ban(2, ip="9.9.9.9", decision=order[1], host="b.example"),
        _ban(3, ip="", host="c.example"),          # the empty address
        _ban(4, ip="7.7.7.7", decision=Decision.CHALLENGE, host="d.example"),
    ]
    ends = []
    for apply in (_one_by_one, Banner.apply_regex_bans):
        mirror = table(capacity=64)
        banner = _banner(mirror)
        # a decision held from before the chunk, less severe than one
        # record's and as severe as another's
        banner.decision_lists.update(
            "7.7.7.7", 1_790_000_050.0, Decision.CHALLENGE, True, "old")
        assert apply(banner, cfg, records) == []
        state = (bytes(mirror._shm.buf) if hasattr(mirror, "_shm")
                 else dict(mirror._entries))
        ends.append((
            banner.decision_lists._by_ip,
            {ip: mirror.get(ip) for ip in ("9.9.9.9", "8.8.8.8", "", "7.7.7.7")},
            len(mirror), state, banner._ban_log.getvalue(),
        ))
        mirror.close()
        mirror.unlink()
    assert ends[0] == ends[1]
    by_ip, in_mirror = ends[1][0], ends[1][1]
    severest = max(order)
    assert by_ip["9.9.9.9"].decision == severest
    assert by_ip["9.9.9.9"].domain == (
        "a.example" if order[0] >= order[1] else "b.example")
    assert in_mirror["9.9.9.9"][0] == int(severest)
    # held as severe: not replaced, the mirror keeps the older entry
    assert by_ip["7.7.7.7"].domain == "old"
    assert in_mirror["7.7.7.7"] == (int(Decision.CHALLENGE),
                                    1_790_000_050.0, True)
    assert ends[1][2] == 4


@pytest.mark.parametrize("table", _mirrors(), ids=lambda c: c.__name__)
def test_put_many_is_put_in_order(table, frozen_clock):
    entries = [(f"1.1.{k % 5}.{k}", 1 + k % 3, f"site{k % 4}.org" if k % 6
                else "") for k in range(100)] + [("", 2, "x"),
                                                  ("y" * 80, 3, "ü.org")]
    one, many = table(capacity=256), table(capacity=256)
    try:
        for ip, decision, domain in entries:
            assert one.put(ip, decision, 1_790_000_400.0, False, domain)
        assert many.put_many(entries, 1_790_000_400.0) == len(entries)
        assert many.put_many([], 1.0) == 0
        assert len(one) == len(many)
        for ip, _, _ in entries:
            assert one.get(ip) == many.get(ip) is not None
        if hasattr(one, "_shm"):
            assert bytes(one._shm.buf) == bytes(many._shm.buf)
        # a table too small keeps what fits and says how many
        small = table(capacity=8)
        try:
            stored = small.put_many(entries, 1_790_000_400.0)
            assert stored == len(small) <= 8 < len(entries)
        finally:
            small.close()
            small.unlink()
    finally:
        for t in (one, many):
            t.close()
            t.unlink()


def test_a_failing_mirror_costs_the_batch_nothing(ledger):
    class _Broken:
        def put_many(self, *a, **kw):
            raise RuntimeError("segment gone")

    banner = _banner(_Broken())
    assert banner.apply_regex_bans(_config(), RECORD_SETS["both_targets"]) == []
    assert len(banner.decision_lists._by_ip) == 6
    assert banner.regex_ban_records == 6


# ---- (e) provenance ----


def test_provenance_of_a_batch_is_the_per_record_paths(ledger):
    tracer = trace.configure(enabled=True, ring_size=64)
    tid = tracer.new_trace()
    records = RECORD_SETS["every_decision"] + RECORD_SETS["both_targets"]
    banner = _banner()
    with tracer.span("drain", tid, parent=0):
        banner.apply_regex_bans(_config(), records)
    ring = ledger.tail(64)
    assert [(r["ip"], r["decision"], r["rule"], r["rule_index"], r["hits"])
            for r in ring] == [
        (r.ip, str(r.decision), r.rule_name, r.rule_index, r.hits)
        for r in records]
    assert {r["source"] for r in ring} == {provenance.SOURCE_RATE_LIMIT}
    assert {r["trace_id"] for r in ring} == {tid}
    assert "origin_node" not in ring[0]
    want = {}
    for r in records:
        key = (provenance.SOURCE_RATE_LIMIT, str(r.decision))
        want[key] = want.get(key, 0) + 1
    assert ledger.counters() == want
    assert ledger.total_records() == len(records)
    # outside a span: no ambient trace
    banner.apply_regex_bans(_config(), records[:1])
    assert ledger.tail(1)[0]["trace_id"] == 0
    assert ledger.explain(records[0].ip)[-1]["rule"] == records[0].rule_name


def test_record_many_wraps_the_ring_and_resolves_origins():
    ledger = provenance.configure(enabled=True, ring_size=16)
    provenance.set_origin_resolver(
        lambda ip: ("node-b", 77) if ip.endswith("3") else None)
    try:
        provenance.record_many(provenance.SOURCE_RATE_LIMIT, [
            (f"10.0.0.{k}", "Challenge", "r", k, 46) for k in range(40)])
        provenance.record_many(provenance.SOURCE_RATE_LIMIT, [])
        ring = ledger.tail(64)
    finally:
        provenance.set_origin_resolver(None)
        provenance.configure(enabled=True)
    assert [r["ip"] for r in ring] == [f"10.0.0.{k}" for k in range(24, 40)]
    assert [(r["ip"], r.get("origin_node"), r.get("origin_trace_id"))
            for r in ring if "origin_node" in r] \
        == [("10.0.0.33", "node-b", 77)]
    assert ledger.counters() == {("rate_limit", "Challenge"): 40}
    assert ledger.total_records() == 40
    off = provenance.configure(enabled=False)
    provenance.record_many(provenance.SOURCE_RATE_LIMIT,
                           [("1.1.1.1", "Challenge", "r", 0, 1)])
    assert off.total_records() == 0
    provenance.configure(enabled=True)


# ---- (f) a banner without the override ----


def test_mock_banner_through_the_base_body_records_what_it_recorded(ledger):
    cfg, records = _config(), RECORD_SETS["both_targets"]
    lists = DynamicDecisionLists(start_sweeper=False)
    mock = MockBanner(lists)
    assert mock.apply_regex_bans(cfg, records) == []
    by_hand = MockBanner(DynamicDecisionLists(start_sweeper=False))
    for r in records:
        by_hand.ban_or_challenge_ip(cfg, r.ip, r.decision, r.host)
        by_hand.log_regex_ban(cfg, r.log_time_unix, r.ip, r.rule_name,
                              r.rest, r.decision)
    assert mock.bans == by_hand.bans
    assert mock.regex_ban_logs == by_hand.regex_ban_logs \
        == [(r.ip, r.rule_name) for r in records]
    assert set(lists._by_ip) == {r.ip for r in records}
    assert [(r["ip"], r["rule_index"], r["hits"]) for r in ledger.tail(64)] \
        == [(r.ip, r.rule_index, r.hits) for r in records]


def test_replicating_banner_publishes_each_decision_once_in_order(ledger):
    class _Replicator:
        def __init__(self):
            self.sent = []

        def publish(self, ip, decision, domain):
            self.sent.append((ip, decision, domain))

    records = RECORD_SETS["both_targets"]
    for inner in (_banner(), MockBanner()):
        rep = _Replicator()
        wrapped = ReplicatingBanner(inner, rep)
        assert wrapped.apply_regex_bans(_config(), records) == []
        assert rep.sent == [(r.ip, r.decision, r.host) for r in records]
    assert inner.regex_ban_logs == [(r.ip, r.rule_name) for r in records]


# ---- the replay's route to a chunk's few rows ----


def _list_work(lo, hi):
    from banjax_tpu.matcher.encode import ParsedLine

    return ListWork(
        (i, ParsedLine(timestamp_ns=i, ip=f"1.1.1.{i}", host="h",
                       rest=f"GET h GET /{i} HTTP/1.1 ua"))
        for i in range(lo, hi))


@pytest.mark.parametrize("rows", [
    [], [0], [11], [3, 3, 4], [0, 4, 5, 9, 10, 11], [7, 2, 11, 0],
])
def test_lines_at_is_indexing_row_by_row(rows):
    parts = [_list_work(0, 5), _list_work(0, 5), _list_work(0, 2)]
    work = CompositeWork(parts, [0, 5, 10])
    want = [work[k] for k in rows]
    got = work.lines_at(np.asarray(rows, dtype=np.int32))
    assert [(i, p.ip, p.rest) for i, p in got] \
        == [(i, p.ip, p.rest) for i, p in want]
    assert [i for i, _ in got] == rows
    flat = _list_work(0, 12)
    assert flat.lines_at(np.asarray(rows, dtype=np.int64)) \
        == [flat[k] for k in rows]
