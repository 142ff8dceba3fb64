"""The encode stage's gate as one native call (ISSUE 50) against the
composition of numpy calls it replaced, output for output.

Two matchers over one configuration: `new` gates as the product does
(`TpuMatcher._native_gate`: native.gate, one call into fastparse.c
fp_gate), `ref` has the parent's composition put in its place
(tests/gate_reference.py).  Every batch goes through both by the
product's three entries — `pipeline_begin` on fresh buffers, the same on
the matcher's own scratch (the synchronous drive), and `encode_shard` +
`pipeline_begin_from_shards` with the cuts laid on the rows that matter —
and what comes out is compared to the element: the results vector, the
candidate rows, the per-row inverses, the first-appearance tables of
addresses and hosts, the address spans' bytes, `ts`, the Python-parsed
rows, and the encoded arrays."""

import time
import types

import numpy as np
import pytest

from banjax_tpu import native
from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.matcher.longrows import LONG_WIDTH
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.matcher.workset import (
    CompositeWork,
    ListWork,
    NativeWork,
    SpanStrings,
)
from tests.differential.test_tpu_matcher import CONFIG_YAML
from tests.gate_reference import reference_native_gate
from tests.mock_banner import MockBanner

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no native parse here (no C compiler)"
)

NOW = time.time()
NO_ALLOW_YAML = CONFIG_YAML.replace(
    "global_decision_lists:\n  allow:\n    - 12.12.12.12\n", "")
assert "allow" not in NO_ALLOW_YAML


class BareLists:
    """A decision-lists object with the one method the gate needs and
    neither `has_any_allow_entries` nor `generation`: the gate's fail-safe
    path (it asks about every distinct pair, and caches nothing)."""

    def __init__(self, lists):
        self.check_is_allowed = lists.check_is_allowed


def _matcher(lists: str) -> TpuMatcher:
    yaml_text = NO_ALLOW_YAML if lists == "none" else CONFIG_YAML
    config = config_from_yaml_text(yaml_text)
    static = StaticDecisionLists(config)
    assert static.has_any_allow_entries() == (lists != "none")
    if lists == "bare":
        static = BareLists(static)
    return TpuMatcher(config, MockBanner(), static, RegexRateLimitStates())


@pytest.fixture(scope="module", params=["none", "allow", "bare"])
def pair(request):
    """(new, ref, which lists): one-call gate, reference gate."""
    new, ref = _matcher(request.param), _matcher(request.param)
    ref._native_gate = types.MethodType(reference_native_gate, ref)
    yield new, ref, request.param
    new.close()
    ref.close()


def _line(ip, rest="GET example.com GET /page HTTP/1.1 ua -", ts=None):
    return f"{NOW if ts is None else ts:f} {ip} {rest}"


ERROR = "short garbage"
OLD = _line("9.9.9.9", ts=NOW - 100)
# the C parse defers these to Python's float(): an underscore stamp that
# is a fresh line, one that is old, one that is no number, and one whose
# nanoseconds pass int64 (a candidate: its ts is clamped)
DEFER_OK = f"{int(NOW):_}.5 7.7.7.7 GET example.com GET /d HTTP/1.1 ua -"
DEFER_OLD = "1_0.5 7.7.7.8 GET example.com GET /d HTTP/1.1 ua -"
DEFER_ERR = "1__0 7.7.7.9 GET example.com GET /d HTTP/1.1 ua -"
DEFER_BIG = "9e30 7.7.7.7 GET example.com GET /big HTTP/1.1 ua -"
DEFER_ODD_IP = (
    f"{int(NOW):_}.25 10.0.0.é GET café.example GET /d HTTP/1.1 -")
ALLOWED = _line("12.12.12.12", "GET example.com GET /allowed HTTP/1.1 ua -")


def _mixed(n=96):
    """Every kind of row, and the same address on both sides of any cut."""
    out = []
    for i in range(n):
        k = i % 12
        ip = f"10.0.{i % 3}.{i % 7}"
        if k == 3:
            out.append(ERROR)
        elif k == 5:
            out.append(OLD)
        elif k == 7:
            out.append([DEFER_OK, DEFER_OLD, DEFER_ERR, DEFER_BIG,
                        DEFER_ODD_IP][(i // 12) % 5])
        elif k == 9:
            out.append(ALLOWED)
        elif k == 10:
            out.append(_line(ip, f"GET site{i % 4}.org GET /{'q' * 300} -"))
        else:
            out.append(_line(ip, f"GET site{i % 4}.org GET /p{i} HTTP/1.1 -"))
    return out


def _long_rows():
    """Rows of every width class: short, over the short width (LONG),
    past LONG_WIDTH, a byte over 0x7F short and long — and an
    allowlisted address on the only row over the short width of its
    shard."""
    return [
        _line("1.1.1.1", "GET h.com GET /short -"),
        _line("1.1.1.2", "GET h.com GET /" + "a" * 300 + " -"),
        _line("1.1.1.3", "GET h.com GET /" + "b" * (LONG_WIDTH + 5) + " -"),
        _line("1.1.1.4", "GET h.com GET /café -"),
        _line("1.1.1.5", "GET h.com GET /" + "c" * 400 + "é -"),
        _line("1.1.1.1", "GET h.com GET /" + "d" * (LONG_WIDTH - 20) + " -"),
        _line("1.1.1.6", "GET h.com GET /short2 -"),
        _line("1.1.1.7", "GET h.com GET /short3 -"),
        _line("12.12.12.12", "GET example.com GET /" + "e" * 300 + " -"),
        _line("1.1.1.8", "GET example.com GET /short4 -"),
    ]


# name: (lines, shard cuts)
BATCHES = {
    "clean": ([_line(f"10.1.{i % 5}.{i % 11}") for i in range(64)],
              [16, 32, 48]),
    "one_address_on_every_row": ([_line("10.9.9.9")] * 48, [1, 24, 47]),
    "every_row_distinct": (
        [_line(f"10.{i // 256}.{i % 256}.1", f"GET h{i}.org GET / -")
         for i in range(300)], [100, 200]),
    "every_row_an_error": ([ERROR] * 20, [7, 14]),
    "every_row_old": ([OLD] * 20, [7, 14]),
    "every_row_deferred": (
        [DEFER_OK, DEFER_OLD, DEFER_ERR, DEFER_BIG, DEFER_ODD_IP] * 4,
        [5, 10, 13]),
    "every_row_deferred_and_dropped": ([DEFER_OLD, DEFER_ERR] * 8, [3, 8]),
    "mixed": (_mixed(), [12, 36, 60]),
    # each cut falls right before and right after a row of each kind
    "error_old_deferred_on_the_cuts": (
        _mixed(), [3, 4, 5, 6, 7, 8, 9, 10, 43, 44, 79, 80]),
    "non_ascii_addresses": (
        [_line("10.0.0.é"), _line("καφές"),
         DEFER_ODD_IP, _line("10.0.0.é"), _line("10.0.0.1 "),
         DEFER_ODD_IP, DEFER_OK, _line("10.0.0.1")], [2, 3, 6]),
    "a_deferred_row_first_and_last": (
        [DEFER_OK] + [_line(f"7.7.7.{i}") for i in range(6, 9)]
        + [DEFER_ODD_IP], [1, 4]),
    "allowlisted_pairs": (
        [ALLOWED, _line("12.12.12.12", "GET other.org GET / -"),
         _line("1.2.3.4"), ALLOWED, _line("12.12.12.12")], [1, 3]),
    "every_row_allowlisted": ([ALLOWED] * 12, [4, 8]),
    "long_rows": (_long_rows(), [2, 5, 8]),
    "empty_lines": (["", "", _line("1.2.3.4"), "", _line("1.2.3.5")],
                    [1, 3]),
    # a last line that is empty reads as a newline at the blob's end: the
    # parse hands the batch to the Python loop, on both sides
    "an_empty_last_line": (["", _line("1.2.3.4"), ""], [1]),
    "one_row": ([_line("1.2.3.4")], []),
    "no_rows": ([], []),
}


def _line_fields(work):
    return [
        (i, p.ip, p.host, p.timestamp_ns, p.rest)
        for i, p in work.lines_at(np.arange(len(work)))
    ]


def _same_array(a, b, what):
    if a is None or b is None:
        assert a is None and b is None, what
        return
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, what
    np.testing.assert_array_equal(a, b, err_msg=what)


def _assert_same_part(new, ref, ctx):
    """One shard's (or one unsharded batch's) work set."""
    assert type(new) is type(ref), ctx
    if isinstance(new, ListWork):
        # no candidate, or a batch the Python loop gated on both sides
        assert list(new) == list(ref), ctx
        return
    assert isinstance(new, NativeWork), ctx
    for name in ("rows", "ip_inv", "host_inv", "ts_ns"):
        _same_array(getattr(new, name), getattr(ref, name), f"{ctx}: {name}")
    assert isinstance(ref.ips_u, list), ctx
    assert list(new.ips_u) == ref.ips_u, ctx
    assert new.hosts_u == ref.hosts_u and isinstance(new.hosts_u, list), ctx
    assert new.defer_map == ref.defer_map, ctx
    assert list(new.defer_map) == list(ref.defer_map), ctx
    for a, b, what in zip(new.ip_spans, ref.ip_spans, ("buf", "off", "len")):
        _same_array(a, b, f"{ctx}: ip_spans {what}")
    buf, offs, lens = new.ip_spans
    assert [
        bytes(buf[o : o + n]).decode("utf-8", "surrogatepass")
        for o, n in zip(offs.tolist(), lens.tolist())
    ] == ref.ips_u, ctx


def _assert_same_state(new, ref, ctx):
    assert [(r.error, r.old_line, r.exempted) for r in new["results"]] == \
        [(r.error, r.old_line, r.exempted) for r in ref["results"]], ctx
    nw, rw = new["work"], ref["work"]
    assert type(nw) is type(rw) and len(nw) == len(rw), ctx
    if isinstance(nw, CompositeWork):
        assert nw.offsets == rw.offsets, ctx
        for j, (a, b) in enumerate(zip(nw.parts, rw.parts)):
            _assert_same_part(a, b, f"{ctx}: part {j}")
    else:
        _assert_same_part(nw, rw, ctx)
    if len(nw):
        _same_array(nw.orig_rows(), rw.orig_rows(), f"{ctx}: orig_rows")
        _same_array(nw.ts_array(), rw.ts_array(), f"{ctx}: ts_array")
        ips_n, inv_n = nw.unique_ips()
        ips_r, inv_r = rw.unique_ips()
        assert list(ips_n) == list(ips_r), ctx
        _same_array(inv_n, inv_r, f"{ctx}: unique_ips inverse")
        spans_n, spans_r = nw.unique_ip_spans(), rw.unique_ip_spans()
        assert (spans_n is None) == (spans_r is None), ctx
        if spans_n is not None:  # None: strings only (a Python parse)
            assert spans_n[0].strings() == spans_r[0].strings() \
                == list(ips_r), ctx
            _same_array(spans_n[1], spans_r[1], f"{ctx}: span inverse")
        assert _line_fields(nw) == _line_fields(rw), ctx
    assert (new["pre"] is None) == (ref["pre"] is None), ctx
    if new["pre"] is not None:
        for k, (a, b) in enumerate(zip(new["pre"], ref["pre"])):
            _same_array(a, b, f"{ctx}: pre[{k}]")
    assert new.get("fused_eligible") == ref.get("fused_eligible"), ctx


# by name: a test id made of a line would hold the clock, and differ
# from one xdist worker's collection to the next
ROWS = {
    "error": (ERROR, native.FLAG_ERROR, False),
    "old": (OLD, native.FLAG_OLD, False),
    "defer_ok": (DEFER_OK, native.FLAG_DEFER, True),
    "defer_old": (DEFER_OLD, native.FLAG_DEFER, False),
    "defer_err": (DEFER_ERR, native.FLAG_DEFER, False),
    "defer_big": (DEFER_BIG, native.FLAG_DEFER, True),
    "defer_odd_ip": (DEFER_ODD_IP, native.FLAG_DEFER, True),
    "allowed": (ALLOWED, 0, True),
}


@pytest.mark.parametrize("row", sorted(ROWS))
def test_the_adversarial_rows_are_what_they_are_named(row):
    """The batches above lean on these: what the C parse flags each row,
    and whether the row reaches the work set of a matcher without allow
    entries (a deferred row by Python's reading of its stamp)."""
    line, flag, candidate = ROWS[row]
    m = _matcher("none")
    try:
        nb = native.parse_encode_batch(
            [line], m.compiled.byte_to_class, 256, NOW, 10.0)
        assert nb.flags.tolist() == [flag]
        work = m.pipeline_begin([line], NOW)["work"]
        assert len(work) == candidate
        if line is DEFER_BIG:  # past int64: clamped in the column alone
            assert work.ts_array().tolist() == [2**63 - 1]
            assert work[0][1].timestamp_ns == int(9e30 * 1e9) > 2**63
    finally:
        m.close()


def _sharded(m, lines, cuts):
    edges = [0, *cuts, len(lines)]
    shards = [(a, m.encode_shard(lines[a:b], NOW))
              for a, b in zip(edges, edges[1:])]
    return m.pipeline_begin_from_shards(lines, NOW, shards)


@pytest.mark.parametrize("entry", ["fresh", "scratch", "sharded"])
@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_one_call_gate_equals_the_composition(pair, batch, entry):
    new, ref, lists = pair
    lines, cuts = BATCHES[batch]
    ctx = f"{batch} / {entry} / lists={lists}"
    if entry == "sharded":
        got, want = _sharded(new, lines, cuts), _sharded(ref, lines, cuts)
    else:
        scratch = entry == "scratch"
        got = new.pipeline_begin(lines, NOW, use_scratch=scratch)
        want = ref.pipeline_begin(lines, NOW, use_scratch=scratch)
    _assert_same_state(got, want, ctx)
    if lists == "allow" and "allowlisted" in batch:
        assert any(r.exempted for r in got["results"]), ctx
    if lists == "none":
        assert not any(r.exempted for r in got["results"]), ctx
        # without allow entries the gate leaves the addresses as spans
        parts = getattr(got["work"], "parts", [got["work"]])
        for w in parts:
            if isinstance(w, NativeWork) and not w.defer_map:
                assert isinstance(w.ips_u, SpanStrings), ctx


@pytest.mark.parametrize("lists", ["none", "allow"])
def test_a_batchs_strings_outlive_the_scratch(lists):
    """The synchronous drive gates every batch on the matcher's own parse
    and dedup scratch.  Batch N's addresses, asked for AFTER batch N+1
    went through the same buffers, are still batch N's — by index, by
    iteration, through lines_at, through a take and through the spans."""
    m = _matcher(lists)
    try:
        first = [_line(f"10.1.1.{i % 9}", f"GET a{i % 3}.org GET /x{i} -")
                 for i in range(40)]
        # other addresses, other lengths, more rows: every offset moves
        second = [_line(f"192.168.100.{200 + i % 50}",
                        f"GET longer-host-{i}.example GET /yy{i} -")
                  for i in range(64)]
        state = m.pipeline_begin(first, NOW, use_scratch=True)
        work = state["work"]
        rows_then = work.rows.copy()
        inv_then = work.ip_inv.copy()
        m.pipeline_begin(second, NOW, use_scratch=True)
        m.pipeline_begin(second[::-1], NOW, use_scratch=True)

        want = [f"10.1.1.{i}" for i in range(9)]
        assert list(work.ips_u) == want
        assert [work.ips_u[j] for j in range(9)] == want
        assert work.hosts_u == ["a0.org", "a1.org", "a2.org"]
        np.testing.assert_array_equal(work.rows, rows_then)
        np.testing.assert_array_equal(work.ip_inv, inv_then)
        assert [(i, p.ip, p.host) for i, p in work.lines_at(range(40))] == [
            (i, f"10.1.1.{i % 9}", f"a{i % 3}.org") for i in range(40)]
        sub = work.take(np.asarray([5, 6, 30]))
        # a subset's table is compacted in the whole table's order
        assert list(sub.unique_ips()[0]) == ["10.1.1.3", "10.1.1.5",
                                             "10.1.1.6"]
        spans, inv = work.unique_ip_spans()
        assert spans.strings() == want
        np.testing.assert_array_equal(inv, inv_then)
    finally:
        m.close()


def test_the_synchronous_entry_twice_equals_the_reference():
    """consume_lines over two batches' worth of mixed lines on one matcher
    (its scratch reused between them), results read at the end: the
    one-call gate and the composition give the same vector."""
    new, ref = _matcher("allow"), _matcher("allow")
    ref._native_gate = types.MethodType(reference_native_gate, ref)
    try:
        lines = _mixed(96) + BATCHES["long_rows"][0] + _mixed(60)
        for m in (new, ref):
            m._max_batch = 64  # three batches, the scratch reused
        got = new.consume_lines(lines, now_unix=NOW)
        want = ref.consume_lines(lines, now_unix=NOW)
        assert [(r.error, r.old_line, r.exempted, r.rule_results)
                for r in got] == \
            [(r.error, r.old_line, r.exempted, r.rule_results) for r in want]
        assert new.gate_shards == {"native": 3, "python": 0}
    finally:
        new.close()
        ref.close()
