"""`longline1k-edge` (PR 43): a line over the short width (256 bytes of
request string) is the fused program's to decide, in one of two further
operands by its own length (few rows x 1,024 columns, one kernel block
x 8,192), and
never decides the path of the lines around it.

  * seeded streams through the product (sync entry and PipelineScheduler)
    against `CpuMatcher` and the benchmark's plain reference: lengths on
    both sides of the short width and of each of LONG_WIDTHS, a match that begins
    past byte 256 and one that ends on a line's last byte, a long and a
    short line of one address and rule on both sides of a limit,
    `hosts_to_skip` and a per-site rule on a long row;
  * every batch with a long row commits fused; a line past LONG_WIDTH or
    with a byte over 0x7F still takes its batch classic, exact and counted
    by cause; more long rows of a width than a chunk's operand holds cut
    the batch into smaller chunks, exact and counted;
  * native and Python parse agree on which rows are long;
  * a stream with no long row builds and dispatches the programs it did.
"""

import io
import json
import time

import numpy as np
import pytest
import yaml

from banjax_tpu import native
from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.rate_limit import (
    FailedChallengeRateLimitStates,
    RegexRateLimitStates,
)
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.effectors.banner import Banner
from banjax_tpu.matcher.cpu_ref import CpuMatcher
from banjax_tpu.matcher import longrows
from banjax_tpu.matcher.encode import encode_lines
from banjax_tpu.matcher.longrows import LONG_WIDTH, LONG_WIDTHS
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.matcher.workset import CompositeWork, ListWork
from banjax_tpu.obs import exposition
from banjax_tpu.pipeline import PipelineScheduler
from benchmark.harness import found, genproc, reference, stream
from benchmark.harness.cellrun import overlay
from benchmark.rulesets import crs_long
from tests.differential.test_tpu_matcher import result_key

BATCH = 64
WIDE_BATCH = 512
SHORT = 256
UA = "Mozilla/5.0 (X11; Linux x86_64) Gecko/20100101 Firefox/127.0"
FILL = "abcdefghijklmnopqrstuvwxyz0123456789/_=&+-"

RULES = [
    # the three that matter where: a literal behind anything (its match
    # begins wherever the filler ends), a User-Agent token (the line's
    # last bytes), a path at the start
    {"rule": "deep", "regex": r"/wp\.php\?[a-z]+=7[0-9]{1,4}",
     "interval": 300, "hits_per_interval": 2, "decision": "nginx_block"},
    {"rule": "ua", "regex": r"(?i)shellscan|envbot/3\.[0-9]+",
     "interval": 300, "hits_per_interval": 2, "decision": "challenge"},
    {"rule": "front", "regex": r"GET /admin-login/[a-z0-9_-]+\.php",
     "interval": 300, "hits_per_interval": 2, "decision": "nginx_block"},
    {"rule": "tail", "regex": r"tokenbot/9\.1 -$",
     "interval": 1, "hits_per_interval": 0, "decision": "nginx_block"},
    {"rule": "skipper", "regex": r"POST /backup/env\.sql",
     "interval": 300, "hits_per_interval": 0, "decision": "challenge",
     "hosts_to_skip": {"skipme.org": True}},
]
SITE_RULES = {"own.example.net": [
    {"rule": "own-deep", "regex": r"/cgi/bin\.sh\?[a-z]+=1[0-9]{1,4}",
     "interval": 300, "hits_per_interval": 1, "decision": "nginx_block"},
]}


def _filler(n, salt=0):
    return "".join(FILL[(i * 7 + salt) % len(FILL)] for i in range(n))


def _rest(path, host="example.com", method="GET", ua=UA):
    return f"{method} {host} {method} {path} HTTP/1.1 {ua} -"


def _of_len(n, host="example.com", salt=0):
    """A benign request string of exactly n bytes."""
    base = len(_rest("/", host))
    return _rest("/" + _filler(n - base, salt), host)


def _build(cls, rules=RULES, site_rules=None, **over):
    doc = {"regexes_with_rates": rules}
    if site_rules:
        doc["per_site_regexes_with_rates"] = site_rules
    cfg = config_from_yaml_text(yaml.safe_dump(doc))
    for k, v in {
        "matcher_device_windows": True, "matcher_window_capacity": 256,
        "matcher_batch_lines": BATCH, "matcher_max_line_len": SHORT,
        "matcher_prefilter": True, **over,
    }.items():
        setattr(cfg, k, v)
    ban_log = io.StringIO()
    banner = Banner(DynamicDecisionLists(start_sweeper=False), ban_log,
                    io.StringIO(), ipset_instance=None)
    m = cls(cfg, banner, StaticDecisionLists(cfg), RegexRateLimitStates())
    return m, ban_log


class _FixedSizer:
    def __init__(self, n):
        self.n = n

    def target(self):
        return self.n

    def command_target(self):
        return 1024

    def observe(self, *a, **k):
        pass

    def snapshot(self):
        return {}

    def target_changes(self):
        return {"up": 0, "down": 0}


def _run_pipelined(matcher, lines, now, batch=BATCH):
    collected = []
    sched = PipelineScheduler(
        lambda: matcher, on_results=lambda ls, rs: collected.append((ls, rs)),
        now_fn=lambda: now)
    sched._sizer = _FixedSizer(batch)
    sched.start()
    for i in range(0, len(lines), batch):
        sched.submit(lines[i:i + batch])
    assert sched.flush(240)
    sched.stop()
    assert [ln for ls, _ in collected for ln in ls] == lines
    return [r for _, rs in collected for r in rs], sched


def _stamp(now, ip_rests):
    return [f"{now - 1.0 + i * 1e-4:.6f} {ip} {rest}"
            for i, (ip, rest) in enumerate(ip_rests)]


def _assert_equals_cpu(lines, now, entry, rules=RULES, site_rules=None,
                       batch=BATCH):
    cpu, cpu_log = _build(CpuMatcher, rules, site_rules)
    want = [cpu.consume_line(ln, now_unix=now) for ln in lines]
    m, log = _build(TpuMatcher, rules, site_rules, matcher_batch_lines=batch)
    assert m.describe()["fused_protocol"] == "single-kernel"
    if entry == "sync":
        got = m.consume_lines(lines, now_unix=now)
    else:
        got, _ = _run_pipelined(m, lines, now, batch)
    for i, (a, b) in enumerate(zip(want, got)):
        assert result_key(a) == result_key(b), (i, lines[i][:120])
    assert log.getvalue() == cpu_log.getvalue()
    assert log.getvalue()  # the stream bans
    return m, log


def _mixed_stream(now):
    """Benign lines at every length that matters, and attack lines whose
    match begins past byte 256 or ends on the last byte."""
    ip_rests = []
    for k, n in enumerate((255, 256, 257, 288, 511, LONG_WIDTHS[0],
                           LONG_WIDTHS[0] + 1, 4096, 8190, LONG_WIDTH)):
        ip_rests.append((f"9.9.0.{k}", _of_len(n, salt=k)))
    for k in range(240):  # 257-512 rows: a chunk with room for 32 long
        ip_rests.append((f"9.9.1.{k % 7}", _of_len(120 + k % 130, salt=k)))
    for k, n in enumerate((10, 300, 2000, 8000, 10, 700, 900)):
        # `deep`: begins behind n bytes of filler; three hits ban
        ip_rests.append(("7.7.7.1", _rest(
            f"/{_filler(n, k)}/wp.php?id=7{k}1")))
    for k, n in enumerate((400, 5000, 20)):
        # `ua`: the match lies in the line's last bytes
        ip_rests.append(("7.7.7.2", _rest(
            "/" + _filler(n, k), ua=UA + " ShellScan")))
    for k, n in enumerate((300, 8100)):
        # `tail`: `$` — ends on the last byte of a long line; instant
        ip_rests.append((f"7.7.8.{k}", _rest(
            "/" + _filler(n, k), ua="tokenbot/9.1")))
    for k, n in enumerate((0, 900, 700)):
        # `front`: the match is at the path's start, the filler behind
        ip_rests.append(("7.7.7.3", _rest(
            f"/admin-login/x{k}.php?" + _filler(n, k))))
    return _stamp(now, ip_rests)


@pytest.mark.parametrize("entry", ["sync", "pipeline"])
def test_lengths_around_both_widths_equal_the_cpu_matcher(entry):
    now = time.time()
    lines = _mixed_stream(now)
    # one chunk of 512 rows: room for 32 long rows of up to 1,024 bytes
    # and for 8 (XLA backend: one block) of up to 8,192 — the stream's eight
    m, log = _assert_equals_cpu(lines, now, entry, batch=WIDE_BATCH)
    triggers = [json.loads(x)["trigger"] for x in log.getvalue().splitlines()]
    assert sorted(set(triggers)) == ["deep", "front", "tail", "ua"]
    assert triggers.count("tail") == 2
    # every line is the device's: nothing went classic, nothing overflowed
    assert m.unfused_batches == {"line_length": 0, "non_ascii": 0}
    assert sum(m._fw_pipeline.overflow_causes.values()) == 0
    assert m._fw_pipeline.fallback_batches == 0
    # 256 is a short row, 257 the first long one; LONG_WIDTH the last
    n_long = sum(len(ln.split(" ", 2)[2]) > SHORT for ln in lines)
    assert m.long_lines == n_long >= 15
    assert m.long_line_bytes == sum(
        len(ln.split(" ", 2)[2]) for ln in lines
        if len(ln.split(" ", 2)[2]) > SHORT)
    if entry == "pipeline":
        assert m.pipelined_fused_chunks == 1
        assert m.pipelined_fused_fallbacks == 0
    # stage 2 scanned the long attack lines (a payload's own bytes)
    assert m._fw_pipeline.long_candidates >= 8
    assert m._fw_pipeline.long_candidate_bytes > 8000


def test_a_line_past_the_long_width_takes_its_batch_classic_and_is_counted():
    now = time.time()
    first = _mixed_stream(now)
    first.insert(5, _stamp(now, [("7.7.7.1", _rest(
        f"/{_filler(LONG_WIDTH, 3)}/wp.php?id=75"))])[0])
    assert len(first[5].split(" ", 2)[2]) > LONG_WIDTH
    second = _mixed_stream(now)
    cpu, cpu_log = _build(CpuMatcher)
    want = [cpu.consume_line(ln, now_unix=now) for ln in first + second]
    m, log = _build(TpuMatcher, matcher_batch_lines=WIDE_BATCH)
    got, _ = _run_pipelined(m, first, now, WIDE_BATCH)
    # the batch went classic, whole, for that one line's sake
    assert m.unfused_batches == {"line_length": 1, "non_ascii": 0}
    assert m.pipelined_fused_chunks == 0
    # and the next batch, the same lines without it, fused
    got += _run_pipelined(m, second, now, WIDE_BATCH)[0]
    assert m.unfused_batches == {"line_length": 1, "non_ascii": 0}
    assert m.pipelined_fused_chunks == 1
    for i, (a, b) in enumerate(zip(want, got)):
        assert result_key(a) == result_key(b), i
    assert log.getvalue() == cpu_log.getvalue()
    text = exposition.render_prometheus(
        DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
        FailedChallengeRateLimitStates(), matcher=m)
    assert ('banjax_matcher_unfused_batches_total{cause="line_length"} 1'
            in text)
    assert f"banjax_matcher_long_lines_total {m.long_lines}" in text
    assert ("banjax_matcher_long_line_bytes_total "
            f"{m.long_line_bytes}") in text
    assert 'banjax_fused_overflows_total{cause="long_rows"} 0' in text


def test_a_byte_over_0x7f_still_takes_its_batch_classic():
    now = time.time()
    lines = _mixed_stream(now)
    lines[3] = _stamp(now, [("7.7.7.1", _rest(
        "/café/" + _filler(400) + "/wp.php?id=712"))])[0]
    m, _ = _assert_equals_cpu(lines, now, "pipeline", batch=WIDE_BATCH)
    assert m.unfused_batches == {"line_length": 0, "non_ascii": 1}
    assert m.pipelined_fused_chunks == 0


@pytest.mark.parametrize("entry", ["sync", "pipeline"])
def test_long_and_short_lines_of_one_address_cross_a_limit_in_order(entry):
    """Same address, same rule, one chunk: which hit crosses the limit is
    in the ban log (the record carries that line's path), so the long
    rows' events have to reach the windows at their own row's place."""
    now = time.time()
    deep = [_rest(f"/{_filler(n, k)}/wp.php?id=7{k}")
            for k, n in enumerate((5, 600, 7, 3000, 9, 400, 11, 8000, 13))]
    ip_rests = [("9.9.2.0", _of_len(150))] * 3
    for k, rest in enumerate(deep):
        ip_rests.append(("7.7.7.9", rest))
        ip_rests.append((f"9.9.2.{k}", _of_len(140 + k)))
    lines = _stamp(now, ip_rests)
    m, log = _assert_equals_cpu(lines, now, entry)
    bans = [json.loads(x) for x in log.getvalue().splitlines()]
    # limit 2: the 3rd, 6th and 9th hit fire — a short, a long, a short
    assert [len(b["path"]) > SHORT for b in bans] == [False, True, False]
    assert sum(m._fw_pipeline.overflow_causes.values()) == 0


def test_hosts_to_skip_and_a_per_site_rule_on_long_rows():
    now = time.time()
    pay = _filler(700)
    ip_rests = [
        # skipped on its host, instant on any other
        ("7.7.6.1", _rest(f"/backup/env.sql?{pay}", "skipme.org", "POST")),
        ("7.7.6.1", _rest(f"/backup/env.sql?{pay}", "example.com", "POST")),
        # the site's own rule, behind a long filler: on its site only
        ("7.7.6.2", _rest(f"/{pay}/cgi/bin.sh?q=12", "own.example.net")),
        ("7.7.6.2", _rest(f"/{pay}/cgi/bin.sh?q=13", "own.example.net")),
        ("7.7.6.3", _rest(f"/{pay}/cgi/bin.sh?q=12", "example.com")),
        ("7.7.6.3", _rest(f"/{pay}/cgi/bin.sh?q=13", "example.com")),
    ] + [(f"9.9.3.{k}", _of_len(130 + k)) for k in range(20)]
    lines = _stamp(now, ip_rests)
    m, log = _assert_equals_cpu(lines, now, "pipeline",
                                site_rules=SITE_RULES)
    bans = [(b["client_ip"], b["trigger"], b["client_request_host"])
            for b in map(json.loads, log.getvalue().splitlines())]
    assert bans == [("7.7.6.1", "skipper", "example.com"),
                    ("7.7.6.2", "own-deep", "own.example.net")]
    assert m.unfused_batches == {"line_length": 0, "non_ascii": 0}
    assert m.pipelined_fused_chunks == 1


@pytest.mark.parametrize("entry,wide", [
    ("sync", False), ("pipeline", False), ("pipeline", True)])
def test_more_long_rows_than_the_operand_holds_cut_the_batch(entry, wide):
    """A chunk of 64 or of 128 rows has room for 8 long rows of up to 1,024
    bytes and 8 of up to 8,192 (XLA backend: one block each); a batch with
    30 of the first kind, or 40 of the second, is dispatched as smaller
    chunks: fused, exact, counted, and never sent to the host's `re`."""
    now = time.time()
    n_long, batch = (40, 2 * BATCH) if wide else (30, BATCH)
    ip_rests = []
    for k in range(n_long):
        ip_rests.append(("7.7.5.1", _rest(
            f"/{_filler((1100 if wide else 300) + 9 * k, k)}/wp.php?id=7{k}")))
        ip_rests.append((f"9.9.4.{k}", _of_len(100 + k)))
    lines = _stamp(now, ip_rests)
    m, log = _assert_equals_cpu(lines, now, entry, batch=batch)
    pf = m._prefilter
    assert longrows.operands(pf, pf._row_bucket(batch)) == (
        (1024, 8), (8192, 8))
    assert m._fw_pipeline.overflow_causes["long_rows"] == 1
    assert m.unfused_batches == {"line_length": 0, "non_ascii": 0}
    assert m._fw_pipeline.fallback_batches == 0
    chunks = m._fused_chunks(m._encode_work(
        ListWork((i, _parsed(ln, now)) for i, ln in enumerate(lines))))
    assert len(chunks) > 1 and chunks[0][0] == 0
    assert all(b[0] == a[1] for a, b in zip(chunks, chunks[1:]))
    assert chunks[-1][1] == len(lines)
    if entry == "pipeline":
        assert m.pipelined_fused_chunks == len(chunks)
    # n_long hits, limit 2
    assert len(log.getvalue().splitlines()) == n_long // 3


def _parsed(line, now):
    from banjax_tpu.matcher.encode import parse_line

    return parse_line(line, now)


def test_native_and_python_parse_agree_on_long_rows():
    now = time.time()
    rests = [_of_len(n) for n in (1, 200, 255, 256, 257, 300, 8190,
                                  LONG_WIDTH, LONG_WIDTH + 1, 20000)]
    rests.append(_rest("/café"))                      # short, non-ASCII
    rests.append(_rest("/café/" + _filler(500)))      # long, non-ASCII
    rests.append(_rest("/" + _filler(LONG_WIDTH) + "é"))
    _, _, host_eval = encode_lines(rests, SHORT)
    want = longrows.long_lens(rests, host_eval)
    sizes = [len(r.encode()) for r in rests]
    assert want.tolist() == [
        0, 0, 0, 0, 257, 300, 8190, LONG_WIDTH, -1, -1, 0, 0, -1]
    assert host_eval.tolist() == [n > SHORT or not r.isascii()
                                  for n, r in zip(sizes, rests)]
    if not native.available():
        pytest.skip("no native parse here")
    m, _ = _build(TpuMatcher)
    lines = [f"{now:.6f} 1.2.3.{i} {r}" for i, r in enumerate(rests)]
    nb = native.parse_encode_batch(
        lines, m.compiled.byte_to_class, SHORT, now, 10.0)
    flags = np.asarray(nb.flags)
    assert ((flags & native.FLAG_HOST_EVAL) != 0).tolist() == \
        host_eval.tolist()
    assert ((flags & native.FLAG_LONG) != 0).tolist() == (want > 0).tolist()
    assert nb.rest_len[want > 0].tolist() == want[want > 0].tolist()
    # the matcher's two encodes of the batch agree to the element
    work, pre, _ = m._gate(lines, now, [None] * len(lines))
    py = m._encode_work(ListWork(
        (i, _parsed(ln, now)) for i, ln in enumerate(lines)))
    for a, b in zip(pre, py):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and the gather hands the long rows' bytes over as they are
    ks = np.flatnonzero(pre[3] > 0)
    flat, lens = work.rest_bytes(ks)
    assert lens.tolist() == pre[3][ks].tolist()
    assert flat.tobytes() == "".join(rests[k] for k in ks).encode()
    halves = CompositeWork([work[:6], work[6:]], [0, 6])
    flat2, lens2 = halves.rest_bytes(ks)
    assert flat2.tobytes() == flat.tobytes() and lens2.tolist() == lens.tolist()


def test_a_stream_with_no_long_row_builds_the_programs_it_did():
    """The other five configurations: no long operand, no long program,
    and the short operand narrowed to the batch's longest line as ever."""
    now = time.time()
    ip_rests = [(f"9.9.5.{k % 9}", _of_len(100 + k)) for k in range(100)]
    ip_rests += [("7.7.4.1", _rest(f"/admin-login/a{k}.php"))
                 for k in range(3)]
    lines = _stamp(now, ip_rests)
    m, _ = _assert_equals_cpu(lines, now, "pipeline")
    fw = m._fw_pipeline
    assert not fw.long_rows_seen and fw._progs_long == {}
    assert sorted(fw._progs) == [(64, 192), (64, 224)]
    assert m.long_lines == 0 and fw.long_candidates == 0
    # one long line, and from then on every chunk takes the long program,
    # at the full short width: one program a row bucket
    more = _stamp(now, [("9.9.5.1", _of_len(300))] + ip_rests[:70])
    _run_pipelined(m, more, now)
    assert fw.long_rows_seen and sorted(fw._progs_long) == [(64, 256)]
    assert sorted(fw._progs) == [(64, 192), (64, 224)]
    assert m.long_lines == 1


def test_rehearsal_stream_through_the_product_equals_the_plain_reference():
    cell = found.cell("longline1k.flood")
    config = overlay(cell["config"], cell["config"]["rehearse"])
    traffic = overlay(cell["traffic"], cell["traffic"]["rehearse"])
    rules = found.ruleset(config["ruleset"])
    assert [crs_long.long_rule(i) for i in range(len(rules))].count(True) == 4
    rests, n_benign, attack_rule = genproc.build_pools(rules, traffic, 43)
    assert max(map(len, rests)) > 1000
    strm = stream.Stream(traffic, n_benign, len(rests) - n_benign, 43)
    ips, ridx = strm.block(0)
    batch, n = 256, 24 * 256
    now = time.time()
    lines = [f"{now - 2.0 + i * 1e-4:.6f} {ip} {rests[r]}"
             for i, (ip, r) in enumerate(zip(ips[:n], ridx[:n]))]
    m, ban_log = _build(TpuMatcher, found.product_rules(rules),
                        matcher_window_capacity=1024,
                        matcher_batch_lines=batch)
    _run_pipelined(m, lines, now, batch)
    assert m.unfused_batches == {"line_length": 0, "non_ascii": 0}
    assert m.pipelined_fused_fallbacks == 0 and m.fallback_batches == 0
    cut = m._fw_pipeline.overflow_causes.pop("long_rows")
    assert sum(m._fw_pipeline.overflow_causes.values()) == 0
    assert n // batch <= m.pipelined_fused_chunks <= n // batch + 2 * cut
    n_long = sum(len(rests[r]) > SHORT for r in ridx[:n])
    assert m.long_lines == n_long > n // 50

    got = [reference.product_record(x)
           for x in ban_log.getvalue().splitlines()]
    ref = reference.run(rules, lines, lambda ip: True, procs=1)
    cmp_ = reference.compare(got, ref["bans"])
    assert [cmp_[k] for k in ("ban_records_missing", "ban_records_extra",
                              "ips_out_of_order", "ban_keys_differing")
            ] == [0, 0, 0, 0], cmp_
    # bans that rest on a long line, and on a match past byte 256
    long_bans = [d for d in map(json.loads, ref["bans"])
                 if len(d["path"]) > SHORT]
    assert len(long_bans) >= 2
    m.close()
